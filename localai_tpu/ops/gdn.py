"""The Gated DeltaNet's decode step as ONE Pallas call a layer: every slot's
state S read once, both products and the update formed in VMEM, S written
once, where it lies in the carried array.

models.qwen3_next's XLA step (``gdn_step``, which stays the definition, the
CPU path and a prefill chunk's scan body) passes over S three times for a
need of two: ``d`` depends on all of ``S^T k`` before a word of the new S can
be formed, and XLA keeps nothing of S on the chip between the pass that forms
the products and the pass that forms ``exp(g) S + k (x) d``; the layer's rows
are sliced out of the scan's carry before and laid back after. Here the
carried array goes in WHOLE ([P, G, slots, Hv, dk, dv] float32, where it lies
in HBM) and comes back aliased to itself; the index map picks the block
(p, g, slot, heads) with the period from scalar prefetch, so the pipeline
copies the next block in and the last one out while this one's heads are
worked, and no other row of the array is touched.

A head is ``gdn_step``'s algebra to the letter, in float32 on the VPU: the
two products are a multiply by k (by q) down the lanes' columns and a sum
over dk, so nothing is rounded to bfloat16 on the way to the MXU. A head's S
is 64 KB; a block is a slot's heads (2 MB at the published widths), sized by
what a grid step costs and not by a head (PERF.md section 6, PR 46).

The Mamba-2 mixer's decode step (models.falcon_h1) is the same call with
``delta=False``: a head's S [N, P] decays by a scalar and gains k (x) v with
no correction by what S already holds of k (k = B, q = C, v = dt x); its
carried array has one leading axis, the layer (``g_idx`` None).

Runs under ``interpret=True`` on the CPU (tests/test_gdn_kernel.py) and is
compiled for v5e at the served widths in tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# heads of one slot a grid step holds: a slot's 32 heads are 2 MB in and 2 MB
# out, 8 MB double-buffered, and 32 grid steps a layer. On a v5e a decode
# step's nine layers took 1.923 ms at 32 heads, 1.930 at 16, 2.035 at 8, and
# the copies alone as long: the block is past where its size matters
HEAD_BLOCK = 32
VMEM_LIMIT = 32 * 2**20


def head_step(S, kc, qc, v, decay, beta, kq):
    """models.qwen3_next.gdn_step for one head, in the layouts the kernel
    holds: S [dk, dv]; the key and the query as COLUMNS [dk, 1]; v [1, dv];
    ``decay`` = exp(g), ``beta`` and ``kq`` = k . q as rows [1, dv] of one
    value (Mosaic broadcasts along lanes or along sublanes, not both at
    once). Returns (new S, o [1, dv]). Both products come from the one S."""
    Sk = jnp.sum(S * kc, axis=0, keepdims=True)
    Sq = jnp.sum(S * qc, axis=0, keepdims=True)
    d = beta * (v - decay * Sk)
    return decay * S + kc * d, decay * Sq + kq * d


def plain_step(S, kc, qc, v, decay, beta, kq):
    """``head_step`` without the delta correction
    (models.falcon_h1.ssm_step): what is written is beta v as it stands, and
    S^T k is never formed."""
    d = beta * v
    return (decay * S + kc * d,
            decay * jnp.sum(S * qc, axis=0, keepdims=True) + kq * d)


def _kernel(delta, p_ref, S_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, S_out,
            o_ref):
    del p_ref                               # the index maps read it
    q, k, v = q_ref[...], k_ref[...], v_ref[...]
    # a head's three scalars, each along the lanes of its row [hb, dv]. The
    # decay, which multiplies S, is exp OF the broadcast: a slice of a
    # broadcast folds to the [1, 1] -> [dk, dv] broadcast Mosaic has not
    g, beta, kq = (
        jnp.broadcast_to(x, v.shape) for x in (
            g_ref[...], beta_ref[...],
            jnp.sum(k * q, axis=-1, keepdims=True)))
    decay = jnp.exp(g)
    kT, qT = k.T, q.T               # [dk, hb]: a head's vector is a column
    step = head_step if delta else plain_step
    for h in range(S_ref.shape[0]):
        one = slice(h, h + 1)
        S_out[h], o_ref[one, :] = step(
            S_ref[h], kT[:, one], qT[:, one], v[one], decay[one], beta[one],
            kq[one])


def gdn_state_step(S_all, p, g_idx, q, k, v, g, beta, *, delta: bool = True,
                   head_block: int = HEAD_BLOCK, interpret: bool = False):
    """One token of the gated delta rule on layer (p, g_idx) of the carried
    state, batch row b = slot b: (``S_all`` with that layer's rows replaced,
    in place; o [slots, Hv, dv] float32).

    S_all [P, G, slots, Hv, dk, dv] float32; ``p`` a scalar (traced or not),
    ``g_idx`` a Python int, or None for a state with ONE leading axis, [L,
    slots, Hv, dk, dv], whose layer is ``p``; q, k [slots, Hv, dk], v
    [slots, Hv, dv], g, beta [slots, Hv], float32 (a row with g = 0 and beta
    = 0 is the identity on its S). ``delta`` False: the recurrence without
    the delta correction (``plain_step``), a kernel of another name
    (``ssm_state_step``)."""
    slots, Hv, dk, dv = S_all.shape[-4:]
    if S_all.dtype != jnp.float32:
        raise ValueError(f"the recurrent state is float32, not {S_all.dtype}")
    if not interpret and (dk % 128 or dv % 128):
        raise ValueError(
            f"the recurrent step kernel needs 128-aligned head dims (key "
            f"{dk}, value {dv}); set engine.attn_impl: xla to serve the "
            f"step as XLA instead")
    hb = min(head_block, Hv)
    lead = (None,) * (S_all.ndim - 3)       # the layer's axes and the slot's

    # index maps take (slot, head block, p)
    def state(s, j, p):
        layer = (p[0],) if g_idx is None else (p[0], g_idx)
        return *layer, s, j, 0, 0

    def heads(s, j, p):
        return s, j, 0

    def vec(d):
        return pl.BlockSpec((None, hb, d), heads)

    f32 = jnp.float32
    S_all, o = pl.pallas_call(
        functools.partial(_kernel, delta),
        name="gdn_state_step" if delta else "ssm_state_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(slots, pl.cdiv(Hv, hb)),
            in_specs=[pl.BlockSpec((*lead, hb, dk, dv), state),
                      vec(dk), vec(dk), vec(dv), vec(1), vec(1)],
            out_specs=[pl.BlockSpec((*lead, hb, dk, dv), state), vec(dv)],
        ),
        out_shape=[jax.ShapeDtypeStruct(S_all.shape, f32),
                   jax.ShapeDtypeStruct((slots, Hv, dv), f32)],
        input_output_aliases={1: 0},    # the state: written where it lies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(jnp.reshape(p, (1,)).astype(jnp.int32), S_all, q.astype(f32),
      k.astype(f32), v.astype(f32), g.astype(f32)[..., None],
      beta.astype(f32)[..., None])
    return S_all, o

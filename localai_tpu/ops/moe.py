"""The routed experts of one expert block as ONE Pallas call: a grouped
matmul whose grid walks the experts that HAVE a token, in ``order``.

models.experts' XLA path (``experts_loop``) is a ``lax.fori_loop`` over the
touched experts, three small dots an iteration behind a scalar-indexed slice:
nothing fetches expert i + 1 while expert i is computed. Here the three expert
leaves go in WHOLE ([P, M, E, D, F] / [P, M, E, F, D], where they lie in HBM)
and the index maps pick block (p, m, order[i]) from scalar-prefetched
indices, so the pipeline copies expert i + 1's ``w_gate``, ``w_up`` and
``w_down`` into VMEM while expert i's three dots run. The grid's bound is
``n_touched`` itself, known on the device only: no step reads an expert no
token chose. (A block no real row reached walks ONE step, which computes
nothing: its copy of ``order[0]`` is the only read of an expert nobody chose.)

The form is the loop's: every row against every touched expert, the routing
weight 0 where a token did not choose it (bytes-bound at a decode step's and a
chunk's row counts), the experts summed in the same order into a float32
[N, D] block that stays in VMEM for the whole walk. The operands are the
leaves' dtype and every product accumulates in float32; ``act(gate) * up``
(``act`` the family's: SiLU, or ReLU for models.smallthinker) is formed in
float32 and rounded once, for the third dot (the loop rounds each dot's
result).

An expert too large to lie in VMEM whole, twice (``BLOCK_BYTES``: 3072 x 3072
is 18 MiB a matrix), goes through in TILES of its intermediate width F, an
innermost grid axis: ``(act(h Wg[:, f]) * (h Wu[:, f])) Wd[f, :]`` summed
over the tiles f is the expert (the down projection is linear in F), each
tile's product rounded once as the whole expert's is. An expert that fits
(2048 x 512) has no such axis: its program is what it was.

Runs under ``interpret=True`` on the CPU (tests/test_moe_kernel.py) and is
compiled for v5e at the served widths in tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# rows a grid step holds: at 256 rows an expert's three dots take about as
# long as its 6.3 MB take to arrive (v5e, D 2048, F 512), so more rows a
# tile would buy nothing, and more rows than this are an outer grid axis
ROW_TILE = 256
# an expert's three matrices, double-buffered, are 12.6 MB at the published
# widths: with the row blocks over the 16 MB Mosaic allows a kernel unasked
# (a v5e core has 128). F in tiles would fit without asking and measured the
# same (PERF.md section 6, PR 42): whole experts are one grid axis fewer and
# contiguous copies. 32 MiB serve the kernel as well; what is asked for is
# also what XLA keeps free of its own arrays round the call, and at 64 a
# 512-row chunk's temps read lowest
VMEM_LIMIT = 64 * 2**20
# the most an expert's three blocks may take of it, double-buffered: a whole
# expert where that fits, else the widest 128-aligned tile of F that divides
# it and does (3072 x 512 at D 3072: 18 MiB, F in six tiles)
BLOCK_BYTES = 24 * 2**20


def f_tile(D: int, F: int, itemsize: int) -> int:
    """Columns of F a grid step holds: F, or the widest tile under
    ``BLOCK_BYTES`` that divides it in 128-lane multiples."""
    fits = BLOCK_BYTES // (2 * 3 * D * itemsize)
    if F <= fits:
        return F
    tiles = [t for t in range(128, F, 128) if F % t == 0 and t <= fits]
    if not tiles:
        raise ValueError(
            f"the grouped expert kernel finds no 128-aligned tile of the "
            f"expert width {F} that fits VMEM at hidden {D}; set "
            f"engine.attn_impl: xla to serve the experts as the XLA loop")
    return tiles[-1]


def _kernel(order_ref, meta_ref, h_ref, wts_ref, wg_ref, wu_ref, wd_ref,
            o_ref, *, tiled: bool, act):
    i = pl.program_id(1)
    first = i == 0
    if tiled:       # the walk's first step is the first expert's first tile
        first &= pl.program_id(2) == 0

    @pl.when(first)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < meta_ref[0])
    def _():
        h = h_ref[...]
        gate = jnp.dot(h, wg_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(h, wu_ref[...], preferred_element_type=jnp.float32)
        y = (act(gate) * up).astype(h.dtype)
        y = jnp.dot(y, wd_ref[...], preferred_element_type=jnp.float32)
        # the expert's column of the routing weights: one lane of [rows, E]
        wts = wts_ref[...]
        lane = lax.broadcasted_iota(jnp.int32, wts.shape, 1)
        col = jnp.sum(jnp.where(lane == order_ref[i], wts, 0.0), axis=1,
                      keepdims=True)
        o_ref[...] += col * y


def moe_experts(h, weights, order, n_touched, experts, p, m_idx, *,
                interpret: bool = False, act=jax.nn.silu):
    """sum over the first ``n_touched`` experts e of ``order`` of
    ``weights[:, e] * (act(h @ w_gate[p, m_idx, e]) * (h @ w_up[p, m_idx,
    e])) @ w_down[p, m_idx, e]``: [N, D] float32.

    h [N, D]; weights [N, E] float32; order [E] i32; ``experts`` the three
    leaves whole, ``w_gate`` and ``w_up`` [P, M, E, D, F], ``w_down`` [P, M,
    E, F, D]; ``p`` and ``m_idx`` scalars (traced or not)."""
    w_gate, w_up, w_down = experts
    N, D = h.shape
    E, F = w_gate.shape[2], w_gate.shape[4]
    tf = f_tile(D, F, w_gate.dtype.itemsize)
    tiled = tf < F
    tile = min(N, ROW_TILE)
    pad = -N % tile
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        weights = jnp.pad(weights, ((0, pad), (0, 0)))
    meta = jnp.stack([jnp.asarray(v, jnp.int32)
                      for v in (n_touched, p, m_idx)])

    # index maps take (row tile, expert step[, F tile], order, meta)
    def up(r, i, *rest):
        *f, order, meta = rest
        return meta[1], meta[2], order[i], 0, f[0] if f else 0

    def down(r, i, *rest):
        *f, order, meta = rest
        return meta[1], meta[2], order[i], f[0] if f else 0, 0

    def rows(r, i, *rest):
        return r, 0

    out = pl.pallas_call(
        functools.partial(_kernel, tiled=tiled, act=act),
        name="moe_experts",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=((N + pad) // tile, jnp.maximum(n_touched, 1),
                  *((F // tf,) if tiled else ())),
            in_specs=[
                pl.BlockSpec((tile, D), rows),
                pl.BlockSpec((tile, E), rows),
                pl.BlockSpec((None, None, None, D, tf), up),
                pl.BlockSpec((None, None, None, D, tf), up),
                pl.BlockSpec((None, None, None, tf, D), down),
            ],
            out_specs=pl.BlockSpec((tile, D), rows),
        ),
        out_shape=jax.ShapeDtypeStruct((N + pad, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary",
                                 *(("arbitrary",) if tiled else ())),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(order, meta, h, weights, w_gate, w_up, w_down)
    return out[:N] if pad else out

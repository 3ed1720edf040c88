"""Pipeline (layer-sharded) parallelism over the 'pipe' mesh axis.

Parity: llama.cpp's layer split mode — its default multi-GPU layout
(``--split-mode layer`` / tensor_split, /root/reference/backend/cpp/llama/
grpc-server.cpp:2240-2262 plumbs the split knobs): each device holds a
contiguous block of layers and activations flow device→device. The point
is HBM CAPACITY scaling — a model whose weights+KV exceed one chip serves
from P chips at params/P per chip — not throughput: decode is
weight-bandwidth-bound and the stage chain reads the same total bytes.

TPU formulation: the stacked layer weights and the KV cache shard their
leading L axis over 'pipe' via shard_map. One forward runs P ticks: every
device applies ITS layer block to whatever activation it holds, then the
activations rotate one hop along the 'pipe' ICI ring (ppermute). Real
data enters at stage 0 and exits stage P-1 after P ticks; KV writes gate
on ``tick == axis_index`` so off-turn (garbage) passes never touch the
cache. v1 runs the 'pipe' axis alone ('data'/'model'/'seq'/'expert' stay
1 — the runner gates; the KV-write closures capture global slot indices,
so slot-sharding composition needs a closure-free rework first).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from localai_tpu.models import llama as mdl
from localai_tpu.models import quant as qnt
from localai_tpu.models.llama import LlamaConfig


def _pipe_spec(ndim: int) -> P:
    """Leading-axis-on-'pipe' spec — the one formula for layer-stacked
    weights and the KV stack (pp_forward in_specs, pp_param_specs,
    shard_params_pp all share it so they can never drift)."""
    return P(*(("pipe",) + (None,) * (ndim - 1)))


def pp_forward(
    cfg: LlamaConfig,
    params: Any,
    tokens: jax.Array,      # [B, T] i32
    positions: jax.Array,   # [B, T] i32
    kv_write: Any,          # fn(kv_stack, layer, k, v) -> (new_stack, keys, vals)
    kv_stack: Any,          # stacked KV pytree, L axis 'pipe'-sharded
    mask: jax.Array,        # [B, T, Lk] bool
    rope: tuple[jax.Array, jax.Array],
    mesh: Mesh,
    embeds: Optional[jax.Array] = None,
) -> tuple[jax.Array, Any]:
    """models.llama.forward over a 'pipe'-sharded mesh (v1: pipe alone).

    Same contract as forward(): returns (hidden [B, T, D] replicated
    across 'pipe', updated kv_stack still 'pipe'-sharded).
    """
    n_pipe = mesh.shape["pipe"]
    dtype = jnp.dtype(cfg.dtype)
    cos_t, sin_t = rope

    def local_fn(layers_local, kv_local, embed, final_norm, tokens,
                 positions, mask, emb_in):
        p = lax.axis_index("pipe")
        cos = cos_t[positions][:, :, None, :]
        sin = sin_t[positions][:, :, None, :]
        if emb_in is None:
            x = qnt.embed_rows(embed, tokens, dtype)
        else:
            x = emb_in.astype(dtype)

        n_local = jax.tree.leaves(layers_local)[0].shape[0]

        def block(x, kv_block, write_real):
            """My layer block over x; KV updates applied only when
            ``write_real`` (this tick carries my real activations). Same
            contract as models.llama.forward: the stage's stack is the
            scan's carry, ``kv_write`` takes it whole with the stage-local
            layer index."""

            def body(carry, layer_in):
                x, kv = carry
                lp, layer = layer_in

                def attend(q, k_new, v_new):
                    new_kv, keys, values = kv_write(kv, layer, k_new, v_new)
                    out = mdl._grouped_attn(cfg, q, keys, values, mask)
                    return out, new_kv

                return mdl._layer(cfg, x, lp, cos, sin, attend), None

            (y, new_kv), _ = lax.scan(
                body, (x, kv_block),
                (layers_local, jnp.arange(n_local, dtype=jnp.int32)))
            # an off-turn tick ran on garbage activations: keep the stack
            # it started from (one select of the stage's stack a tick)
            new_kv = jax.tree.map(
                lambda new, old: jnp.where(write_real, new, old),
                new_kv, kv_block,
            )
            return y, new_kv

        def tick(carry, s):
            x, kv = carry
            y, new_kv = block(x, kv, write_real=(s == p))
            # keep OFF-TURN (garbage) activations finite so they can't
            # poison the chain with inf/nan before the real data arrives;
            # the on-turn output propagates untouched — genuine overflow
            # must stay visible, exactly as on a single device
            y = jnp.where(
                s == p, y,
                jnp.nan_to_num(y, nan=0.0, posinf=0.0, neginf=0.0))
            y = lax.ppermute(
                y, "pipe", [(i, (i + 1) % n_pipe) for i in range(n_pipe)])
            return (y, new_kv), None

        (x, kv_local), _ = lax.scan(
            tick, (x, kv_local), jnp.arange(n_pipe))
        # after P ticks + rotations the real output sits on stage 0 —
        # broadcast it so every device returns the same hidden state
        x = lax.psum(jnp.where(p == 0, x, jnp.zeros_like(x)), "pipe")
        x = mdl.rms_norm(x, final_norm, cfg.rms_norm_eps)
        return x, kv_local

    lp_specs = jax.tree.map(lambda a: _pipe_spec(a.ndim), params["layers"])
    kv_specs = jax.tree.map(lambda a: _pipe_spec(a.ndim), kv_stack)
    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(lp_specs, kv_specs, P(), P(),
                  P(), P(), P(),
                  (P() if embeds is not None else None)),
        out_specs=(P(), kv_specs),
        check_vma=False,
    )
    hidden, new_kv = fn(
        params["layers"], kv_stack, params["embed"], params["final_norm"],
        tokens, positions, mask, embeds,
    )
    return hidden, new_kv


def pp_param_specs(cfg: LlamaConfig, mesh: Mesh) -> dict:
    """PartitionSpecs for pipeline-sharded placement: stacked layer
    weights shard L over 'pipe'; embed/norm/lm_head replicate."""
    from localai_tpu.models.llama import param_shapes

    shapes = param_shapes(cfg)
    specs: dict = {
        "embed": P(),
        "final_norm": P(),
        # no _sanitize: a non-dividing layer count must FAIL placement
        # loudly (the runner validates first) — pp_forward's in_specs use
        # the same unsanitized formula, so placement and execution can
        # never disagree about what is sharded
        "layers": {
            k: _pipe_spec(len(s)) for k, s in shapes["layers"].items()
        },
    }
    if "lm_head" in shapes:
        specs["lm_head"] = P()
    return specs


def shard_params_pp(params: Any, cfg: LlamaConfig, mesh: Mesh) -> Any:
    """shard_params on a 'pipe' mesh: ParamPlacement picks pp_param_specs."""
    from localai_tpu.parallel.sharding import shard_params

    return shard_params(params, cfg, mesh)

"""Per-shape paged-attention autotuner (ops.tuning's writer).

Sweeps the paged decode attention dispatch over its real tuning axes —
kernel impl (Pallas flash vs gather+XLA ref), pool ``block_tokens``, the
kernel's ``num_buffers`` (steps of copies in flight; the kernel cuts a
step's table entries to its VMEM budget itself, so a deeper ring means
smaller steps) — on REAL timings at the shapes a model family serves,
and persists the winner per ``(head_dim, kv_heads, kv_dtype, tp)`` key to
the tuning table (``LOCALAI_TUNE_CACHE`` / ``--out``). The engine then
picks the tuned configuration automatically: ``select_paged_attn_impl``
honors the tuned impl and ``ModelRunner`` the tuned block size / buffer
depth, each lookup leaving a ``localai_autotune_*`` metric receipt.

Tensor-parallel keys (``--tp``) are measured at the per-device LOCAL
shapes (heads/tp) — under ``shard_map`` the kernel body IS the
single-device kernel, so the local measurement is the honest one and no
multi-device dispatch is needed to tune for a mesh.

It tunes the backend JAX gives it and never picks one itself. That backend
must be a TPU: a table of winners timed on anything else would steer a TPU
runner by the CPU's (or the Pallas interpreter's) preferences. ``--interpret``
is the explicit exception — the CI smoke that exercises the sweep machinery
off-TPU with the Pallas points in interpret mode; it must name its own
``--out`` (never the table a runner reads by default) and every line it
prints says ``"interpret": true``. On the chip, run it through the chip
tool, alone: a chip belongs to one process.

Only points ``ops.select_paged_attn_impl`` would serve are measured (a shape
it refuses is recorded as refused, with its reason); a point it allows that
then fails to compile or run is a bug and ends the sweep.

Usage:
    python tools/autotune.py                      # 8b shapes, on a TPU
    python tools/autotune.py --preset tiny --kv-dtypes float32,int4 \
        --tp 1,2 --interpret --out tuning.json    # off-TPU machinery smoke
    python tools/autotune.py --smoke --out tuning.json   # the CI sweep above

Output: one JSON line per point (each names the device) plus a final
summary line; the table file is the artifact CI uploads.
"""

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

# the shapes worth tuning out of the box: the serving presets (debug:1b is
# head_dim 64 — no compiled kernel serves it, so only its gather points
# would be measured)
PRESET_SHAPES = {
    "tiny": (16, 2),          # debug:tiny (tests, CI smoke)
    "small": (32, 4),
    "1b": (64, 8),            # debug:1b
    "llama3-8b": (128, 8),    # the north-star dims
}


def _timeit(fn, *args, n=10, warmup=2):
    import jax

    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def measure_point(head_dim: int, kv_heads: int, kv_dtype: str, *,
                  impl: str, block_tokens: int, num_buffers: int,
                  group: int = 4, slots: int = 4, ctx: int = 512,
                  interpret: bool = False, reps: int = 3) -> float:
    """Best-of-``reps`` microseconds for one paged decode attention
    dispatch at the given configuration (local, single-device shapes)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from localai_tpu import ops
    from localai_tpu.models.quant import quantize_lastdim, quantize_lastdim4

    rng = np.random.default_rng(0)
    bt = block_tokens
    mb = -(-ctx // bt)
    n_blocks = slots * mb + 1
    num_heads = kv_heads * group
    q = jnp.asarray(
        rng.normal(size=(slots, num_heads, head_dim)), jnp.float32)
    kf = jnp.asarray(
        rng.normal(size=(n_blocks, kv_heads, bt, head_dim)), jnp.float32)
    vf = jnp.asarray(
        rng.normal(size=(n_blocks, kv_heads, bt, head_dim)), jnp.float32)
    tables = jnp.asarray(
        np.arange(1, n_blocks).reshape(slots, mb), jnp.int32)
    positions = jnp.full((slots,), ctx - 2, jnp.int32)

    k_scale = v_scale = None
    if kv_dtype == "int8":
        kf, k_scale = quantize_lastdim(kf)
        vf, v_scale = quantize_lastdim(vf)
    elif kv_dtype == "int4":
        kf, k_scale = quantize_lastdim4(kf)
        vf, v_scale = quantize_lastdim4(vf)
    elif kv_dtype == "bfloat16":
        kf, vf = kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16)

    if impl == "pallas":
        def fn(q, k, v, t, p, ks, vs):
            # a one-layer stack: the kernel reads the pool by layer index
            ks, vs = (None, None) if ks is None else (ks[None], vs[None])
            return ops.paged_decode_attention(
                q, k[None], v[None], jnp.int32(0), t, p, ks, vs,
                interpret=interpret, num_buffers=num_buffers)
    else:
        def fn(q, k, v, t, p, ks, vs):
            return ops.paged_decode_attention_ref(q, k, v, t, p, ks, vs)

    jitted = jax.jit(fn)
    dt = min(
        _timeit(jitted, q, kf, vf, tables, positions, k_scale, v_scale)
        for _ in range(reps))
    return dt * 1e6


def sweep(shapes, kv_dtypes, tps, *, block_candidates, buffer_candidates,
          impls, ctx: int, interpret: bool, table, device: dict,
          group: int = 4) -> list[dict]:
    """Measure every point the selector would serve, install the per-key
    winners into ``table``, and return the point records."""
    from localai_tpu import ops
    from localai_tpu.ops import tuning

    records = []

    def emit(rec: dict) -> None:
        rec.update(device=device, interpret=interpret)
        records.append(rec)
        print(json.dumps(rec))

    for hd, kv in shapes:
        for kv_dtype in kv_dtypes:
            if kv_dtype == "int4" and hd % 2:
                continue
            for tp in tps:
                if kv % tp or tp < 1:
                    continue
                key = tuning.shape_key(hd, kv, kv_dtype, tp)
                t_key = time.monotonic()
                best = None
                for impl in impls:
                    bufs = buffer_candidates if impl == "pallas" else [2]
                    for bt in block_candidates:
                        if bt > ctx:
                            continue
                        if impl == "pallas":
                            try:
                                ops.select_paged_attn_impl(
                                    "pallas_interpret" if interpret
                                    else "pallas",
                                    num_heads=kv * group, num_kv_heads=kv,
                                    head_dim=hd, block_tokens=bt, tp=tp,
                                    kv_dtype=kv_dtype)
                            except ValueError as e:
                                emit({"key": key, "impl": impl,
                                      "block_tokens": bt,
                                      "refused": str(e)})
                                continue
                        for nb in bufs:
                            us = measure_point(
                                hd, kv // tp, kv_dtype, impl=impl,
                                block_tokens=bt, num_buffers=nb,
                                group=group, ctx=ctx, interpret=interpret)
                            emit({"key": key, "impl": impl,
                                  "block_tokens": bt, "num_buffers": nb,
                                  "us": round(us, 1)})
                            if best is None or us < best[0]:
                                best = (us, impl, bt, nb)
                if best is None:
                    continue
                us, impl, bt, nb = best
                table.put(key, tuning.TuneEntry(
                    impl=impl, block_tokens=bt, num_buffers=nb,
                    us=round(us, 1)))
                _note_sweep(key, time.monotonic() - t_key)
    return records


def _note_sweep(key: str, seconds: float) -> None:
    try:
        from localai_tpu.obs.metrics import REGISTRY

        REGISTRY.autotune_sweep_seconds.set(seconds, key=key)
    except Exception:  # noqa: BLE001
        pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--preset", action="append", default=[],
                    choices=sorted(PRESET_SHAPES),
                    help="model shape preset(s) to tune (default: "
                         "llama3-8b; repeatable)")
    ap.add_argument("--kv-dtypes", default="bfloat16,int8,int4",
                    help="comma list of KV dtypes to tune")
    ap.add_argument("--tp", default="1",
                    help="comma list of tensor-parallel widths to key")
    ap.add_argument("--blocks", default="16,32,64,128",
                    help="block_tokens candidates")
    ap.add_argument("--buffers", default="2,3",
                    help="num_buffers candidates: steps in flight "
                         "(pallas only)")
    ap.add_argument("--ctx", type=int, default=512,
                    help="context rows per measured slot")
    ap.add_argument("--interpret", action="store_true",
                    help="off-TPU machinery smoke: run the Pallas points "
                         "in the interpreter (timings mean nothing; needs "
                         "--out). Without it a non-TPU backend is an error")
    ap.add_argument("--out", default="",
                    help="table path (default LOCALAI_TUNE_CACHE; required "
                         "with --interpret)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI sweep: tiny shape, float32+int4, "
                         "tp 1+2, blocks 8/16, interpret")
    args = ap.parse_args(argv)

    import jax

    from localai_tpu.ops import tuning

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    on_tpu = device["platform"] == "tpu"
    if args.smoke:
        args.interpret = True
    if not on_tpu and not args.interpret:
        ap.error(f"autotune tunes a TPU and found {device}; winners timed "
                 f"here would steer a TPU runner by this backend's "
                 f"preferences (--interpret runs the off-TPU machinery "
                 f"smoke)")
    if on_tpu and args.interpret:
        ap.error("--interpret on a TPU would time the interpreter, not "
                 "the kernels")
    if args.interpret and not args.out:
        ap.error("--interpret needs --out: an interpreter-timed table must "
                 "never land where a runner reads it by default")

    if args.smoke:
        shapes = [PRESET_SHAPES["tiny"]]
        kv_dtypes = ["float32", "int4"]
        tps = [1, 2]
        blocks = [8, 16]
        buffers = [2, 3]
        ctx = 64
    else:
        presets = args.preset or ["llama3-8b"]
        shapes = [PRESET_SHAPES[p] for p in presets]
        kv_dtypes = [d for d in args.kv_dtypes.split(",") if d]
        tps = [int(t) for t in args.tp.split(",") if t]
        blocks = [int(b) for b in args.blocks.split(",") if b]
        buffers = [int(b) for b in args.buffers.split(",") if b]
        ctx = args.ctx

    impls = ["xla", "pallas"]

    path = args.out or tuning.cache_path()
    table = tuning.TuningTable.load(path)
    t0 = time.monotonic()
    records = sweep(shapes, kv_dtypes, tps, block_candidates=blocks,
                    buffer_candidates=buffers, impls=impls, ctx=ctx,
                    interpret=args.interpret, table=table, device=device)
    if not path:
        print(json.dumps({"error": "no table path (LOCALAI_TUNE_CACHE=0 "
                                   "and no --out)"}))
        return 1
    saved = table.save(path)
    tuning.reset()  # a fresh lookup sees the new entries
    print(json.dumps({
        "table": saved,
        "entries": len(table.entries),
        "points_measured": sum(1 for r in records if "us" in r),
        "points_refused": sum(1 for r in records if "refused" in r),
        "device": device,
        "interpret": args.interpret,
        "sweep_s": round(time.monotonic() - t0, 1),
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

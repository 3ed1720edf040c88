"""Micro-benchmarks for the decode roofline investigation.

Isolates where the gap between measured decode tok/s and the
weight-bandwidth bound goes:

  * quant-matmul variants at decode shapes — bf16, w8 (dequant-in-matmul),
    w8a8 (native int8 MXU dot), w4 — measuring effective HBM bandwidth.
    If w8 materializes a bf16 weight copy (the docstring'd suspect in
    models/quant.py), its GB/s will read ~1/3 of bf16's instead of ~2x.
  * forward-only vs forward+sampling decode step (sampling overhead).
  * KV-cache attention read cost vs context length.

`python bench_micro.py` prints JSON lines; on the chip it runs through the
chip tool, alone (a chip belongs to one process). tools/perf_smoke.py calls
its CPU-sized entry points as a CI regression gate — those numbers are CPU
numbers and say nothing about the device.
"""

import json
import time

import numpy as np


def _timeit(fn, *args, n=20, warmup=3):
    import jax

    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def bench_quant_matmuls(M=8, K=4096, N=14336, steps=64):
    """One decode-shaped matmul per variant, looped inside jit so dispatch
    amortizes; reports effective weight-read bandwidth."""
    import jax
    import jax.numpy as jnp

    from localai_tpu.models import quant as qnt

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.bfloat16)
    w_f = rng.normal(size=(K, N)).astype(np.float32) * 0.02
    variants = {
        "bf16": (jnp.asarray(w_f, jnp.bfloat16), 2),
        "w8": (qnt.quantize_tensor(w_f, axis=0), 1),
        "w8a8": (qnt.QuantizedTensor(
            q=qnt.quantize_tensor(w_f, axis=0).q,
            scale=qnt.quantize_tensor(w_f, axis=0).scale,
            axis=0, mode="w8a8"), 1),
        # w4 traffic includes the group-wise f32 scales: 0.5 B/weight for
        # the nibbles + 4 B per `group` weights of scale rows
        "w4": (qnt.quantize_tensor4(w_f, axis=0), 0.5 + 4.0 / 128),
    }
    if jax.default_backend() == "tpu":
        from localai_tpu.ops import qmatmul

        w8 = variants["w8"][0]
        w4 = variants["w4"][0]

        def kernel_mm(h):
            return qmatmul.w8_matmul(h, w8.q, w8.scale)

        def kernel_mm4(h):
            return qmatmul.w4_matmul(h, w4.q, w4.scale)

        variants["w8_pallas"] = (kernel_mm, 1)
        variants["w4_pallas"] = (kernel_mm4, 0.5 + 4.0 / 128)
    out = {}
    for name, (w, bytes_per) in variants.items():
        if callable(w) and not hasattr(w, "shape"):
            def make_k(f):
                def body(x):
                    def step(h, _):
                        y = f(h)
                        return h + y[:, :K].astype(h.dtype) * 1e-6, None
                    h, _ = jax.lax.scan(step, x, None, length=steps)
                    return h
                return jax.jit(body)

            dt = _timeit(make_k(w), x) / steps
            gb = K * N * bytes_per / 1e9
            out[name] = {"ms_per_matmul": round(dt * 1e3, 4),
                         "weight_gb": round(gb, 3),
                         "eff_gbps": round(gb / dt, 1)}
            continue

        def make(w):
            def body(x):
                def step(h, _):
                    y = qnt.matmul(h, w)
                    # feed a slice back so the loop isn't dead-code-elim'd
                    return h + y[:, :K].astype(h.dtype) * 1e-6, None
                h, _ = jax.lax.scan(step, x, None, length=steps)
                return h
            return jax.jit(body)

        f = make(w)
        dt = _timeit(f, x) / steps
        gb = K * N * bytes_per / 1e9
        out[name] = {"ms_per_matmul": round(dt * 1e3, 4),
                     "weight_gb": round(gb, 3),
                     "eff_gbps": round(gb / dt, 1)}
    return out


def bench_step_breakdown(preset="1b", quant="int8", multi=32, paged=False):
    """Full decode step vs forward-only (sampling cost) on the engine."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from localai_tpu.engine import kvcache as kvc
    from localai_tpu.engine.runner import ModelRunner
    from localai_tpu.models import llama as mdl
    from localai_tpu.models.registry import (
        DEBUG_PRESETS,
        synthetic_params,
    )

    cfg = dataclasses.replace(DEBUG_PRESETS[preset], dtype="bfloat16")
    params = synthetic_params(cfg, quant)
    runner = ModelRunner(cfg, params, num_slots=8, max_ctx=1024,
                         prefill_buckets=[128], kv_dtype="int8",
                         paged=paged)
    prompt = list(range(1, 101))
    for _ in range(8):
        runner.admit(runner.acquire_slot(), prompt, temperature=0.0)

    full = _timeit(lambda: runner.step_n(multi), n=5) / multi

    # forward-only: same shapes, no sampling/top_k/counts
    @jax.jit
    def fwd_only(params, kv, state):
        pos = state.positions
        mask = kvc.decode_mask(cfg, pos, runner.max_ctx)
        write = kvc.decode_write(pos, raw=False)
        hidden, _ = mdl.forward(
            cfg, params, state.tokens[:, None], pos[:, None],
            write, kv.stacked(), mask, runner.rope)
        return mdl.logits_from_hidden(cfg, params, hidden[:, 0])

    f_dt = _timeit(lambda: fwd_only(runner.params, runner.kv, runner.state),
                   n=10)
    return {
        "full_step_ms": round(full * 1e3, 3),
        "forward_logits_ms": round(f_dt * 1e3, 3),
        "sampling_overhead_ms": round((full - f_dt) * 1e3, 3),
        "tok_s_at_bs8": round(8 / full, 1),
    }


def machine_index(n=512, steps=24, repeats=3):
    """Effective GFLOP/s of a fixed jitted matmul loop — the machine-speed
    normalizer for tools/perf_smoke.py, so a decode-throughput baseline
    committed from one box transfers to a differently-sized CI runner.
    Best-of-``repeats``: a capability measure must not be dragged down by
    a noisy neighbor stealing one measurement window."""
    import jax
    import jax.numpy as jnp

    x = jnp.ones((n, n), jnp.float32)

    @jax.jit
    def body(x):
        def step(h, _):
            return jnp.tanh(h @ x) * 0.5, None
        h, _ = jax.lax.scan(step, x, None, length=steps)
        return h

    dt = min(_timeit(body, x, n=5) for _ in range(repeats))
    return 2 * n * n * n * steps / dt / 1e9


def decode_smoke(paged: bool, preset: str = "tiny", num_slots: int = 4,
                 max_ctx: int = 512, multi: int = 16, repeats: int = 5,
                 mesh_devices: int = 0, kv_dtype: str = "float32",
                 kv_block_tokens: int = 0):
    """Steady-state batched decode tok/s of a debug preset — the CI perf
    smoke measurement. Best-of-``repeats`` (fastest sample): shared
    runners have multi-x contention spikes, and one clean window measures
    the code's capability; a median would gate on the neighbors.

    ``mesh_devices`` > 1 runs the meshed layout: a pure tensor-parallel
    mesh over that many devices (model axis), params sharded with the
    production partition rules — the CI pin that the pjit/shard_map serving
    path stays alive on a multi-device host (tools/perf_smoke.py gates the
    meshed-paged ratio; callers must check the device count first).

    ``kv_dtype`` selects the pool dtype (``int4`` exercises the nibble-
    packed paged pool + fused dequant); ``kv_block_tokens`` overrides the
    pool block size (0 = runner default / tuned table)."""
    from localai_tpu.engine.runner import ModelRunner
    from localai_tpu.models.registry import resolve_model

    model = resolve_model(f"debug:{preset}", dtype="float32")
    mesh = None
    params = model.params
    if mesh_devices > 1:
        import jax

        from localai_tpu.parallel import sharding as shd
        from localai_tpu.parallel.mesh import MeshPlan, build_mesh

        mesh = build_mesh(MeshPlan(model=mesh_devices),
                          devices=jax.devices()[:mesh_devices])
        params = shd.shard_params(params, model.cfg, mesh)
    runner = ModelRunner(model.cfg, params, num_slots=num_slots,
                         max_ctx=max_ctx, prefill_buckets=[128],
                         kv_dtype=kv_dtype, paged=paged, mesh=mesh,
                         kv_block_tokens=kv_block_tokens or None)
    prompt = list(range(1, 65))
    for _ in range(num_slots):
        runner.admit(runner.acquire_slot(), prompt, temperature=0.0)
    best = 0.0
    for _ in range(repeats):
        dt = _timeit(lambda: runner.step_n(multi), n=3, warmup=1)
        best = max(best, multi * num_slots / dt)
    return best


def anatomy_smoke(preset: str = "tiny", num_slots: int = 4,
                  max_ctx: int = 512, multi: int = 16,
                  dispatches: int = 24, depth: int = 2,
                  kv_dtype: str = "float32"):
    """Dispatch-anatomy summary of the pipelined paged decode smoke.

    The same loop shape as bench.py's pipelined decode (async dispatch +
    copy_to_host_async + deferred drain), with measured launch/sync and
    gap-by-exclusion phase attribution into a private FlightRecorder
    (obs.anatomy interval tiling; the smoke loop has no admit work, so
    sched=0). Returns ``FlightRecorder.phases()`` — tools/perf_smoke.py
    records and gates ``host_overhead_fraction`` from it, the ratchet the
    fused-dispatch work must drive down. Warmup compiles outside the
    measured window, so no compile row ever lands in the ring."""
    from collections import deque

    import jax

    from localai_tpu.engine.runner import ModelRunner
    from localai_tpu.models.registry import resolve_model
    from localai_tpu.obs.flight import FlightRecorder

    model = resolve_model(f"debug:{preset}", dtype="float32")
    runner = ModelRunner(model.cfg, model.params, num_slots=num_slots,
                         max_ctx=max_ctx, prefill_buckets=[128],
                         kv_dtype=kv_dtype, paged=True)
    prompt = list(range(1, 65))
    for _ in range(num_slots):
        runner.admit(runner.acquire_slot(), prompt, temperature=0.0)
    runner.step_n(multi)  # compile outside the measurement
    jax.block_until_ready(runner.state.tokens)
    flight = FlightRecorder(capacity=max(dispatches + 2, 8))
    q: deque = deque()
    launch_acc = 0.0
    last_t = time.monotonic()

    def drain() -> None:
        nonlocal last_t, launch_acc
        ts = time.perf_counter()
        np.asarray(q.popleft())
        sync_ms = (time.perf_counter() - ts) * 1e3
        now = time.monotonic()
        wall_ms = (now - last_t) * 1e3
        sync_ms = min(sync_ms, wall_ms)
        launch_ms = min(launch_acc, wall_ms - sync_ms)
        flight.record(
            program="decode_n", steps=multi, dispatch_ms=wall_ms,
            occupancy=1.0, queue_depth=0, kv_utilization=0.0,
            tokens=multi * num_slots,
            gap_ms=max(0.0, wall_ms - launch_ms - sync_ms),
            launch_ms=launch_ms, sync_ms=sync_ms,
        )
        launch_acc = 0.0
        last_t = now

    for _ in range(dispatches):
        tl = time.perf_counter()
        toks = runner.step_n_async(multi)
        toks.copy_to_host_async()
        launch_acc += (time.perf_counter() - tl) * 1e3
        q.append(toks)
        if len(q) >= depth:
            drain()
    while q:
        drain()
    return flight.phases()


def main():
    import jax

    print(json.dumps({"backend": jax.default_backend(),
                      "devices": len(jax.devices())}))
    print(json.dumps({"quant_matmul_8b_ffn":
                      bench_quant_matmuls(M=8, K=4096, N=14336)}))
    print(json.dumps({"quant_matmul_lm_head":
                      bench_quant_matmuls(M=8, K=2048, N=128256, steps=16)}))
    print(json.dumps({"step_breakdown_1b_int8": bench_step_breakdown()}))
    print(json.dumps({"step_breakdown_1b_int8_paged":
                      bench_step_breakdown(paged=True)}))


if __name__ == "__main__":
    main()

"""Decode measurements taken directly on ModelRunner, one process, in order.

NOT this repo's benchmark: ``BENCHMARK.json`` arrives with the first
``benchmark`` PR and measures the system through its server. This file
calls the engine below the scheduler and the HTTP layer, so its numbers
are a layer metric (what the decode programs can do when nothing else is
in the way), useful next to the end-to-end ones, never instead of them.
``chip_smoke.py`` is the proof that the system starts on the chip.

Two measurement bodies:

  * ``run_decode_bench`` — prefill N slots with 100-token prompts, then timed
    pipelined multi-step decode (the scheduler's dispatch pattern), paged KV
    by default, optionally over a tensor-parallel mesh of every visible
    chip;
  * ``run_spec_bench`` — the paged + n-gram-speculation lane on repetitive
    prompts, one verify window per dispatch.

``main()`` runs the chosen phases one after another in this process and
prints one JSON line per phase. Every line names the device it ran on
(platform, device_kind, count). There is no fallback: no TPU, a phase that
raises, a kernel that does not compile — the first failure ends the run
with a traceback and a non-zero exit code, and no line is printed for it.
A chip belongs to one process: run nothing else on it meanwhile.

    python bench.py --model llama3-8b --quant int8 --phases decode,spec
"""

import argparse
import json
import sys
import time


def _load(preset: str, quant: str, meshed: bool = False):
    """(cfg, params, kv_dtype, mesh) for a debug preset, the way the server
    loads it: quantized presets are generated directly in their served form
    (an 8B bf16 init does not fit a chip) and pair with the int8 KV cache;
    with ``meshed`` every visible chip joins the 'model' axis (the widest
    split the q-head count allows) and each leaf is created on its shards."""
    import jax

    from localai_tpu.models.registry import resolve_config, resolve_model
    from localai_tpu.parallel.sharding import ParamPlacement

    ref = f"debug:{preset}"
    cfg = resolve_config(ref, dtype="bfloat16")
    mesh = None
    if meshed:
        from localai_tpu.parallel.mesh import (MeshPlan, build_mesh,
                                               default_tensor_parallel)

        devs = jax.devices()
        tp = default_tensor_parallel(len(devs), cfg.num_heads)
        if tp < 2:
            raise RuntimeError(
                f"meshed phase needs >=2 devices with a head-divisible "
                f"split; have {len(devs)} device(s), {cfg.num_heads} heads")
        mesh = build_mesh(MeshPlan(model=tp), devices=devs[:tp])
    quantized = quant in ("int8", "int4", "int8_w8a8")
    model = resolve_model(ref, dtype="bfloat16",
                          quantization=quant if quantized else "",
                          placement=ParamPlacement(cfg, mesh))
    jax.block_until_ready(jax.tree.leaves(model.params)[0])
    return (model.cfg, model.params, "int8" if quantized else "bfloat16",
            mesh)


def run_decode_bench(preset: str, quant: str, steps: int, multi: int,
                     depth: int, num_slots: int = 8, max_ctx: int = 1024,
                     flight=None, meshed: bool = False, paged: bool = True):
    """Prefill ``num_slots`` slots, then timed pipelined multi-step decode.

    Returns (aggregate decode tok/s, provenance dict). The pipelined loop
    is the scheduler's production pattern: each dispatch decodes ``multi``
    tokens per slot inside one compiled lax.scan program; ``depth``
    dispatches stay in flight with async D2H copies, so neither the device
    nor the host round-trip sits on the critical path.

    ``flight``: an obs.flight.FlightRecorder fed one record per drained
    dispatch in the timed loop (step-time percentiles, dispatch anatomy).
    """
    from collections import deque

    import jax
    import numpy as np

    from localai_tpu.engine.runner import ModelRunner

    cfg, params, kv_dtype, mesh = _load(preset, quant, meshed)
    runner = ModelRunner(
        cfg, params, num_slots=num_slots, max_ctx=max_ctx,
        prefill_buckets=[128], kv_dtype=kv_dtype, paged=paged, mesh=mesh,
    )

    prompt = list(range(1, 101))  # 100-token synthetic prompt
    for _ in range(num_slots):
        slot = runner.acquire_slot()
        runner.admit(slot, prompt, temperature=0.0)

    # warmup (compile + first dispatches)
    runner.step_n(multi)
    runner.step_n(multi)
    jax.block_until_ready(runner.state.tokens)

    def note_drain(last_t: float, launch_ms: float,
                   sync_ms: float) -> float:
        """One drained dispatch's flight record. Phase
        attribution mirrors the scheduler's interval tiling (obs.anatomy):
        measured launch (async enqueue span) + sync (the asarray block),
        gap by exclusion; the bench loop has no admit work, so sched=0."""
        now = time.monotonic()
        if flight is not None:
            wall_ms = (now - last_t) * 1e3
            sync_ms = min(max(0.0, sync_ms), wall_ms)
            launch_ms = min(max(0.0, launch_ms), wall_ms - sync_ms)
            flight.record(
                program="decode_n", steps=multi,
                dispatch_ms=wall_ms,
                occupancy=1.0, queue_depth=0,
                kv_utilization=min(1.0, (100 + steps) / max_ctx),
                tokens=multi * num_slots,
                gap_ms=max(0.0, wall_ms - launch_ms - sync_ms),
                launch_ms=launch_ms, sync_ms=sync_ms,
            )
        return now

    dispatches = max(1, steps // multi)
    t0 = time.perf_counter()
    last_t = time.monotonic()
    q: deque = deque()
    launch_acc = 0.0  # enqueue ms since the last drain (obs.anatomy)
    for _ in range(dispatches):
        tl = time.perf_counter()
        toks = runner.step_n_async(multi)
        toks.copy_to_host_async()
        launch_acc += (time.perf_counter() - tl) * 1e3
        q.append(toks)
        if len(q) >= depth:
            ts = time.perf_counter()
            np.asarray(q.popleft())
            sync_ms = (time.perf_counter() - ts) * 1e3
            last_t = note_drain(last_t, launch_acc, sync_ms)
            launch_acc = 0.0
    while q:
        ts = time.perf_counter()
        np.asarray(q.popleft())
        sync_ms = (time.perf_counter() - ts) * 1e3
        last_t = note_drain(last_t, launch_acc, sync_ms)
        launch_acc = 0.0
    dt = time.perf_counter() - t0
    # provenance for the output line: which attention kernel actually
    # served the measurement, the KV layout and dtype, and the dispatch
    # amortization — a tok/s figure means nothing without them
    impl = (runner.paged_attn_impl if paged
            else runner.decode_attn_impl)
    info = {
        "kernel_impl": "pallas" if impl == "pallas" else "lax",
        "kv": ("paged+mesh" if meshed and paged
               else "paged" if paged else "contig"),
        "kv_dtype": str(runner.kv_dtype),
        "tokens_per_dispatch": multi * num_slots,
    }
    return dispatches * multi * num_slots / dt, info


def run_spec_bench(preset: str, quant: str, steps: int,
                   num_slots: int = 8, max_ctx: int = 1024,
                   gamma: int = 4, flight=None):
    """Paged + speculative decode (localai_tpu.spec): the n-gram
    self-drafter over repetitive prompts, one verify-k window per
    dispatch. Returns (tok/s, accept_rate, tokens_per_dispatch).

    Windows serialize (the host drafter proposes from drained history),
    so the measured number is the honest end-to-end speculative TPOT —
    host proposal time included. A lookup miss falls back to one plain
    decode dispatch, exactly like the scheduler's lane."""
    import jax
    import numpy as np

    from localai_tpu.engine.runner import ModelRunner
    from localai_tpu.spec import NGramDrafter, SpecEngine

    cfg, params, kv_dtype, _ = _load(preset, quant)
    runner = ModelRunner(
        cfg, params, num_slots=num_slots, max_ctx=max_ctx,
        prefill_buckets=[128], kv_dtype=kv_dtype, paged=True,
    )
    eng = SpecEngine(runner, NGramDrafter(num_slots, gamma))
    prompt = list(range(1, 4)) * 33 + [1]  # 100-token repetitive prompt
    slots = []
    for _ in range(num_slots):
        slot = eng.acquire_slot()
        eng.admit(slot, prompt, temperature=0.0)
        slots.append(slot)
    # warmup: compile the verify window + the plain fallback. The plain
    # step's tokens MUST feed the drafter history like the fallback
    # branch below — a silently-dropped token desyncs every slot's
    # n-gram record and the measured accept rate becomes fiction.
    try:
        eng.step_spec()
    except RuntimeError:
        pass
    toks = np.asarray(runner.step())
    for s in slots:
        eng.drafter.observe(s, [int(toks[s])])
    jax.block_until_ready(runner.state.tokens)
    eng0_emitted, eng0_windows = eng.total_emitted, eng.total_windows
    target_tokens = steps * num_slots
    emitted = 0
    dispatches = 0
    t0 = time.perf_counter()
    last_t = time.monotonic()
    while emitted < target_tokens and dispatches < steps * 2:
        dispatches += 1
        tl = time.perf_counter()
        rows = eng.step_spec_async()
        launch_ms = (time.perf_counter() - tl) * 1e3
        if rows is None:  # lookup miss everywhere — plain fallback
            toks = np.asarray(runner.step())
            # the runner split its own wall (obs.anatomy scratch); the
            # declined proposal's host span above stays in gap
            launch_ms = runner.last_launch_ms
            sync_ms = runner.last_sync_ms
            for s in slots:
                eng.drafter.observe(s, [int(toks[s])])
            emitted += num_slots
            w = None
        else:
            ts = time.perf_counter()
            rows = np.asarray(rows)
            sync_ms = (time.perf_counter() - ts) * 1e3
            w = eng.observe_window(rows)
            emitted += w["emitted"]
        now = time.monotonic()
        if flight is not None:
            wall_ms = (now - last_t) * 1e3
            sync_ms = min(max(0.0, sync_ms), wall_ms)
            launch_ms = min(max(0.0, launch_ms), wall_ms - sync_ms)
            flight.record(
                program="spec" if w else "decode", steps=1,
                dispatch_ms=wall_ms, occupancy=1.0,
                queue_depth=0, kv_utilization=0.0,
                tokens=w["emitted"] if w else num_slots,
                spec_proposed=w["proposed"] if w else 0,
                spec_accepted=w["accepted"] if w else 0,
                gap_ms=max(0.0, wall_ms - launch_ms - sync_ms),
                launch_ms=launch_ms, sync_ms=sync_ms,
            )
        last_t = now
    dt = time.perf_counter() - t0
    d_emit = eng.total_emitted - eng0_emitted
    d_win = eng.total_windows - eng0_windows
    info = {
        "kernel_impl": ("pallas" if runner.paged_attn_impl == "pallas"
                        else "lax"),
        "kv": "paged+spec",
        "kv_dtype": str(runner.kv_dtype),
        # batch-level emitted tokens per verify dispatch (the per-slot
        # figure rides spec_tokens_per_dispatch)
        "tokens_per_dispatch": round(d_emit / d_win, 4) if d_win else 0.0,
    }
    return (emitted / dt, eng.accept_rate,
            (d_emit / (d_win * num_slots)) if d_win else 0.0, info)


def _phase_line(metric: str, value: float, device: dict, flight,
                t0: float, **fields) -> dict:
    line = {"metric": metric, "value": round(value, 2), "unit": "tok/s",
            "device": device, "phase_s": round(time.monotonic() - t0, 1),
            **fields}
    pct = flight.percentiles()
    if pct["step_ms_p50"] is not None:
        line["step_ms_p50"] = pct["step_ms_p50"]
        line["step_ms_p99"] = pct["step_ms_p99"]
    # dispatch anatomy (obs.anatomy): host/sync p50 + the bubble estimate,
    # so the line names its bottleneck next to the rate
    ph = flight.phases()
    if ph.get("samples") and ph.get("host_ms_p50") is not None:
        line.update(host_ms_p50=ph["host_ms_p50"],
                    sync_ms_p50=ph["sync_ms_p50"],
                    bubble=ph["device_bubble_fraction"],
                    host_overhead_fraction=ph["host_overhead_fraction"])
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="llama3-8b",
                    help="debug preset (models.registry.DEBUG_PRESETS)")
    ap.add_argument("--quant", default="int8",
                    choices=["int8", "int4", "int8_w8a8", "none"])
    ap.add_argument("--phases", default="decode,meshed,spec",
                    help="comma list of decode, contig, meshed, spec; "
                         "meshed is skipped on a one-chip host")
    ap.add_argument("--steps", type=int, default=192)
    ap.add_argument("--multi", type=int, default=32,
                    help="decode steps per dispatch")
    ap.add_argument("--depth", type=int, default=4,
                    help="dispatches kept in flight")
    args = ap.parse_args(argv)
    preset = args.model.removeprefix("debug:")

    import jax

    from localai_tpu.obs.flight import FlightRecorder

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU and found {device}; a CPU timing is "
            f"not a device metric (the measurement bodies are importable "
            f"for tests)")
    short = {"llama3-8b": "llama8b", "1b": "llama1b"}.get(preset, preset)
    for phase in [p.strip() for p in args.phases.split(",") if p.strip()]:
        t0 = time.monotonic()
        flight = FlightRecorder(512)
        if phase in ("decode", "contig", "meshed"):
            if phase == "meshed" and len(devices) < 2:
                continue
            tok_s, info = run_decode_bench(
                preset, args.quant, args.steps, args.multi, args.depth,
                flight=flight, meshed=phase == "meshed",
                paged=phase != "contig")
            tag = "_meshed" if phase == "meshed" else ""
            line = _phase_line(
                f"decode_throughput_{short}_bs8_{args.quant}{tag}", tok_s,
                device, flight, t0, **info)
        elif phase == "spec":
            tok_s, accept, per_dispatch, info = run_spec_bench(
                preset, args.quant, args.steps, flight=flight)
            line = _phase_line(
                f"decode_throughput_{short}_bs8_{args.quant}_spec", tok_s,
                device, flight, t0,
                spec_accept_rate=round(accept, 4),
                spec_tokens_per_dispatch=round(per_dispatch, 4), **info)
        else:
            raise SystemExit(f"unknown phase {phase!r}")
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

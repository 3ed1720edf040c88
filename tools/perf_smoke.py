"""CI perf smoke gate — a CPU gate by design, and it says so in what it
prints: every number here is a CPU number, a regression tripwire for the
host-side decode path, never a device metric (those come from the chip,
through the chip tool; see PERF.md).

It runs the bench_micro decode measurement on the CI runner's CPU —
contiguous AND paged KV layouts — and fails when either regresses more than
``PERF_SMOKE_TOL`` (default 10%) against the committed floor in
``BASELINE.json``'s ``perf_smoke`` entry.

Raw tok/s numbers do not transfer between machines, so the committed
floor is *normalized*: tok/s divided by a machine-speed index (a fixed
jitted matmul loop's effective GFLOP/s, ``bench_micro.machine_index``)
measured in the same process. The paged/contiguous *ratio* is additionally
gated — it is machine-independent and catches a paged-path regression
even if the normalization drifts. A speculative-lane smoke rides along:
the n-gram self-drafter on a repetitive prompt must keep accept-rate > 0
and tokens-per-dispatch > 1 (absolute gates — acceptance arithmetic is
hardware-independent).

Usage:
    python tools/perf_smoke.py              # gate (CI)
    PERF_SMOKE_UPDATE=1 python tools/perf_smoke.py   # rewrite the floor

Output: one JSON line with the measurements, the device they were taken
on (always the CPU) and the verdicts; exit 1 on any gate failure.
"""

import json
import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

# THE paged/contiguous decode-throughput floor — the ratchet ROADMAP
# item 2 tracks (0.70 → 0.85 with the int4/overlap/autotune round). One
# named constant: the recorded-baseline writer and the absent-key gate
# fallback read the same value, so the floor can never drift between the
# two paths again (ISSUE 14 satellite).
PAGED_OVER_CONTIG_MIN = 0.85
# int4 pays pack/unpack VPU work for its bandwidth saving; on CPU (no
# HBM to save) the honest expectation is "not off a cliff", not "faster"
INT4_OVER_PAGED_MIN = 0.30
# host-overhead ceiling for the pipelined paged decode smoke
# (bench_micro.anatomy_smoke → obs.anatomy host_overhead_fraction): the
# ratchet the fused k-step dispatch work will drive DOWN. The absolute
# cap is deliberately a hair under 1.0: CPU JAX hides device time from
# the sync probe so the estimator saturates ~0.997 there (run-to-run
# spread ~3e-4) — the cap still catches full saturation while the
# recorded observed+headroom value becomes the real gate on hardware
# where the fraction is meaningfully below 1.
HOST_OVERHEAD_CEILING = 0.9995
# additive noise headroom over the observed fraction when recording the
# baseline ceiling (fractions move additively with scheduling jitter,
# unlike throughput's multiplicative noise)
HOST_OVERHEAD_HEADROOM = 0.08

def _spec_smoke() -> dict:
    """Speculative-lane smoke (ISSUE 11 gate): the n-gram self-drafter
    over a paged tiny engine on a repetitive prompt must achieve a
    positive draft accept-rate and >1 emitted token per verify dispatch
    — the whole point of the verify-k window is amortizing the per-step
    host round-trip, and a regression to ≤1 means the lane is dead
    weight. Deterministic: greedy debug-model decode enters a cycle the
    prompt-lookup drafter picks up."""
    import numpy as np

    from localai_tpu.engine.runner import ModelRunner
    from localai_tpu.models.registry import resolve_model
    from localai_tpu.spec import NGramDrafter, SpecEngine

    tiny = resolve_model("debug:tiny", dtype="float32")
    runner = ModelRunner(
        tiny.cfg, tiny.params, num_slots=2, max_ctx=256,
        prefill_buckets=[64], kv_dtype="float32",
        paged=True, kv_block_tokens=16,
    )
    eng = SpecEngine(runner, NGramDrafter(2, gamma=4))
    slot = eng.acquire_slot()
    eng.admit(slot, list(b"abc abc abc abc abc abc"), temperature=0.0)
    iters = 0
    while eng.total_windows < 8 and iters < 80:
        iters += 1
        rows = eng.step_spec_async()
        if rows is None:  # lookup miss — plain decode grows the history
            tok = int(runner.step()[slot])
            eng.drafter.observe(slot, [tok])
            continue
        eng.observe_window(np.asarray(rows))
    return {
        "spec_windows": eng.total_windows,
        "spec_accept_rate": round(eng.accept_rate, 4),
        "spec_tokens_per_dispatch": round(eng.tokens_per_dispatch, 4),
        "spec_invariants": runner.allocator.check_invariants(),
    }


def _measure(tol: float) -> dict:
    import jax

    import bench_micro

    idx = bench_micro.machine_index()
    contig = bench_micro.decode_smoke(paged=False)
    paged = bench_micro.decode_smoke(paged=True)
    # int4 decode smoke: the nibble-packed paged pool + fused dequant on
    # the same shape — ratio-gated against the f32 paged number (machine-
    # independent) so a pack/unpack regression or a broken int4 scatter
    # fails the PR even though CPU sees no bandwidth win
    int4 = bench_micro.decode_smoke(paged=True, kv_dtype="int4")
    # dispatch-anatomy smoke (obs.anatomy): host-overhead fraction of the
    # pipelined paged decode — the per-token Python cost ratchet
    anat = bench_micro.anatomy_smoke()
    out = {
        "machine_gflops": round(idx, 2),
        "decode_tok_s_contig": round(contig, 1),
        "decode_tok_s_paged": round(paged, 1),
        "decode_tok_s_int4": round(int4, 1),
        "normalized_contig": round(contig / idx, 4),
        "normalized_paged": round(paged / idx, 4),
        "paged_over_contig": round(paged / contig, 4),
        "int4_over_paged": round(int4 / paged, 4),
        "host_overhead_fraction": anat["host_overhead_fraction"],
        "host_ms_p50": anat["host_ms_p50"],
        "sync_ms_p50": anat["sync_ms_p50"],
        "device_bubble_fraction": anat["device_bubble_fraction"],
        "anatomy_samples": anat["samples"],
        "tolerance": tol,
    }
    # meshed-paged smoke: the same paged decode under a 2-device
    # tensor-parallel mesh (shard_map/pjit serving path). Ratio-gated
    # against the single-device paged number — machine-independent, like
    # paged_over_contig. Skips clean when the runner has <2 devices.
    if len(jax.devices()) >= 2:
        meshed = bench_micro.decode_smoke(paged=True, mesh_devices=2)
        out["decode_tok_s_meshed"] = round(meshed, 1)
        out["meshed_over_paged"] = round(meshed / paged, 4)
    else:
        out["meshed"] = "skipped (<2 devices)"
    out.update(_spec_smoke())
    return out


def main() -> int:
    import jax

    # the CPU, whatever the machine has: this gate's floor is a CPU floor.
    # Two virtual devices for the meshed-paged smoke (single-device
    # measurements still run on device 0 only and are unaffected)
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)

    tol = float(os.environ.get("PERF_SMOKE_TOL", "0.10"))
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "note": "CPU regression gate: no number here is a device "
                      "metric"}

    def _measure_here(tol: float) -> dict:
        return {"device": device, **_measure(tol)}

    result = _measure_here(tol)

    baseline_path = REPO / "BASELINE.json"
    data = json.loads(baseline_path.read_text())
    floor = data.get("perf_smoke")

    if os.environ.get("PERF_SMOKE_UPDATE") == "1" or floor is None:
        # record the floor 8% under the observed value: run-to-run noise on
        # shared CI runners is ~5%, so gating the raw observation at 10%
        # tolerance would flake — the discount keeps the effective gate at
        # ~18% while the machine-independent paged/contig ratio still
        # catches paged-path regressions tightly
        headroom = 0.92
        data["perf_smoke"] = {
            "normalized_contig": round(result["normalized_contig"]
                                       * headroom, 4),
            "normalized_paged": round(result["normalized_paged"]
                                      * headroom, 4),
            "paged_over_contig_min": PAGED_OVER_CONTIG_MIN,
            "int4_over_paged_min": INT4_OVER_PAGED_MIN,
            # ceiling, not floor: observed + additive headroom, capped at
            # the loose absolute — drives DOWN as dispatch overhead shrinks
            "host_overhead_max": round(
                min(HOST_OVERHEAD_CEILING,
                    (result["host_overhead_fraction"] or 1.0)
                    + HOST_OVERHEAD_HEADROOM), 4),
            "note": ("decode tok/s per machine-index GFLOP/s "
                     "(tools/perf_smoke.py), recorded with 8% noise "
                     "headroom; refresh with PERF_SMOKE_UPDATE=1"),
        }
        if os.environ.get("PERF_SMOKE_UPDATE") == "1":
            baseline_path.write_text(json.dumps(data, indent=2) + "\n")
            result["updated_baseline"] = True
        else:
            result["no_baseline"] = True  # first run: record nothing, pass
        print(json.dumps(result))
        return 0

    def gate(res: dict) -> list[str]:
        failures = []
        for key in ("normalized_contig", "normalized_paged"):
            base = floor.get(key)
            if base and res[key] < base * (1 - tol):
                failures.append(
                    f"{key} {res[key]:.4f} < floor {base:.4f} "
                    f"(-{(1 - res[key] / base) * 100:.1f}%)")
        # absent-key fallback is the SAME constant the baseline writer
        # records — the 0.70-written/0.75-assumed drift class is closed
        ratio_min = floor.get("paged_over_contig_min",
                              PAGED_OVER_CONTIG_MIN)
        if res["paged_over_contig"] < ratio_min:
            failures.append(
                f"paged_over_contig {res['paged_over_contig']:.3f} "
                f"< {ratio_min} (paged decode path regressed)")
        # host-overhead ceiling (dispatch anatomy): a new Python cost on
        # the per-dispatch hot path shows up here even when throughput
        # noise hides it. None / zero-sample means the anatomy smoke
        # itself broke — fail loudly rather than skip the gate.
        host_max = floor.get("host_overhead_max", HOST_OVERHEAD_CEILING)
        hof = res.get("host_overhead_fraction")
        if not res.get("anatomy_samples"):
            failures.append(
                "anatomy smoke recorded 0 dispatches "
                "(host-overhead gate has nothing to measure)")
        elif hof is None:
            failures.append(
                "host_overhead_fraction is None (anatomy smoke produced "
                "no attributable dispatch wall time)")
        elif hof > host_max:
            failures.append(
                f"host_overhead_fraction {hof:.4f} > ceiling {host_max} "
                f"(per-dispatch host work regressed)")
        int4_min = floor.get("int4_over_paged_min", INT4_OVER_PAGED_MIN)
        if res.get("int4_over_paged", 0.0) < int4_min:
            failures.append(
                f"int4_over_paged {res.get('int4_over_paged')} "
                f"< {int4_min} (int4 paged decode path regressed)")
        # meshed-paged gate: CPU-mesh decode pays real collective overhead
        # (psum per layer over virtual devices), so the floor is loose —
        # it catches the path BREAKING or falling off a cliff, not noise.
        # Absent when <2 devices (skip-clean).
        meshed_min = floor.get("meshed_over_paged_min", 0.15)
        if ("meshed_over_paged" in res
                and res["meshed_over_paged"] < meshed_min):
            failures.append(
                f"meshed_over_paged {res['meshed_over_paged']:.3f} "
                f"< {meshed_min} (meshed-paged decode path regressed)")
        # speculative-lane gate: absolute (no machine normalization
        # needed — acceptance arithmetic is hardware-independent)
        if res.get("spec_accept_rate", 0.0) <= 0.0:
            failures.append(
                "spec_accept_rate is 0 (the n-gram self-drafter never "
                "got a draft accepted)")
        if res.get("spec_tokens_per_dispatch", 0.0) <= 1.0:
            failures.append(
                f"spec_tokens_per_dispatch "
                f"{res.get('spec_tokens_per_dispatch')} <= 1 (the "
                "verify-k window no longer amortizes dispatches)")
        if res.get("spec_invariants"):
            failures.append(
                f"spec smoke violated block invariants: "
                f"{res['spec_invariants']}")
        return failures

    failures = gate(result)
    if failures:
        # one full re-measurement before failing the PR: a contention
        # spike that survived best-of-N rarely survives a second window
        retry = _measure_here(tol)
        retry_failures = gate(retry)
        result = {**retry, "first_attempt": result,
                  "retried_after_failure": failures}
        failures = retry_failures
    result["failures"] = failures
    print(json.dumps(result))
    if failures:
        print("PERF SMOKE GATE FAILED:", "; ".join(failures),
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

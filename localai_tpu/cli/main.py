"""CLI entry: ``python -m localai_tpu.cli.main <command>``.

Parity: the reference's kong command tree (/root/reference/core/cli/
cli.go:8-20 — run, models, tts, transcript, worker, util, federated,
explorer) with env-aliased flags (run.go:19-73). argparse instead of kong;
every flag also reads LOCALAI_<NAME> from the environment.
"""

from __future__ import annotations

import argparse
import logging
import os
import sys
from typing import Optional, Sequence


def _env_default(name: str, fallback):
    for key in (f"LOCALAI_{name.upper()}", name.upper()):
        if key in os.environ:
            return os.environ[key]
    return fallback


def _env_bool(name: str, fallback: bool = False) -> bool:
    """Boolean env flags parse like AppConfig.from_env — 'false'/'0' must
    mean False, not truthy-nonempty-string."""
    v = _env_default(name, None)
    if v is None:
        return fallback
    return str(v).lower() in ("1", "true", "yes", "on")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="localai-tpu",
        description="TPU-native LocalAI: OpenAI-compatible serving on JAX/XLA",
    )
    p.add_argument("--log-level", default=_env_default("log_level", "info"),
                   choices=["error", "warn", "info", "debug", "trace"])
    p.add_argument("--log-format",
                   default=_env_default("log_format", "text"),
                   choices=["text", "json"],
                   help="json = one structured object per line, with the "
                        "request trace id bound by the API middleware")
    sub = p.add_subparsers(dest="command")

    run = sub.add_parser("run", help="start the API server (default)")
    run.add_argument("models", nargs="*", help="model refs to preload")
    run.add_argument("--address", default=_env_default("address", "0.0.0.0"))
    run.add_argument("--port", type=int,
                     default=int(_env_default("port", 8080)))
    run.add_argument("--models-path",
                     default=_env_default("models_path", "models"))
    run.add_argument("--context-size", type=int,
                     default=int(_env_default("context_size", 4096)))
    run.add_argument("--api-keys", default=_env_default("api_keys", ""),
                     help="comma-separated bearer keys")
    run.add_argument("--cors", action="store_true", default=True)
    run.add_argument("--no-cors", dest="cors", action="store_false")
    run.add_argument("--opaque-errors", action="store_true",
                     default=bool(_env_default("opaque_errors", "")))
    run.add_argument("--single-active-backend", action="store_true")
    run.add_argument("--preload-models", default="",
                     help="comma-separated model names to load eagerly")
    run.add_argument("--enable-watchdog-idle", action="store_true")
    run.add_argument("--enable-watchdog-busy", action="store_true")
    run.add_argument("--watchdog-idle-timeout", type=float, default=15 * 60)
    run.add_argument("--watchdog-busy-timeout", type=float, default=5 * 60)
    run.add_argument("--mesh", default=_env_default("mesh", ""),
                     help="mesh shape, e.g. data=2,model=4 (default: auto)")
    run.add_argument("--platform", default=_env_default("platform", None),
                     help="JAX platform of THIS process (cpu keeps a "
                          "worker-fleet front door off the chips); "
                          "workers are not affected")
    # SLO observatory targets (obs.slo): p95 latency bounds in ms; when
    # the error-budget burn rate exceeds --slo-burn-threshold on both the
    # 1m and 5m windows, new generation work is shed with 429+Retry-After
    run.add_argument("--slo-ttft-p95-ms", type=float, default=None,
                     help="p95 time-to-first-token target in ms "
                          "(0/unset = no target)")
    run.add_argument("--slo-tpot-p95-ms", type=float, default=None,
                     help="p95 per-output-token latency target in ms")
    run.add_argument("--slo-e2e-p95-ms", type=float, default=None,
                     help="p95 end-to-end request latency target in ms")
    run.add_argument("--slo-queue-p95-ms", type=float, default=None,
                     help="p95 queue-wait target in ms")
    run.add_argument("--slo-burn-threshold", type=float, default=None,
                     help="error-budget burn rate that triggers load "
                          "shedding (default 2.0)")
    run.add_argument("--request-deadline-s", type=float, default=None,
                     help="per-request generation deadline in seconds; "
                          "expiry cancels the generation and frees its "
                          "decode slot (default 600)")
    # offline batch subsystem (localai_tpu.batch): background-lane knobs
    run.add_argument("--batch-concurrency", type=int, default=None,
                     help="max in-flight batch lines on the scheduler's "
                          "background lane (default 2)")
    run.add_argument("--batch-expiry-h", type=float, default=None,
                     help="hours before a non-terminal batch job expires "
                          "(default 24)")
    # fleet router (localai_tpu.fleet): multi-replica data-parallel serving
    run.add_argument("--fleet-replicas", type=int, default=None,
                     help="serve each LLM from N engine replicas behind "
                          "one cache-aware router (0/1 = single engine)")
    run.add_argument("--fleet-prefill-replicas", type=int, default=None,
                     help="dedicated prefill replicas for disaggregated "
                          "serving: long prompts prefill here and hand "
                          "their KV prefix to a decode replica (default 0)")
    run.add_argument("--fleet-backend", default=None,
                     choices=["worker", "inprocess"],
                     help="replica shape: spawned gRPC worker processes "
                          "(default) or in-process engines")
    run.add_argument("--fleet-disagg-threshold", type=int, default=None,
                     help="prompt tokens at which a request takes the "
                          "disaggregated prefill path (default 512)")
    run.add_argument("--fleet-device-pinning", action="store_true",
                     default=_env_bool("fleet_device_pinning"),
                     help="auto-derive per-replica worker env (TPU "
                          "visible-device slices) so --fleet-replicas N "
                          "partitions the host's accelerators evenly")
    run.add_argument("--fleet-hosts", default=None,
                     help="comma-separated host:port remote workers to "
                          "adopt into every fleet pool (cross-host "
                          "serving; failed remotes are evicted and "
                          "redialed on backoff, never respawned)")
    run.add_argument("--fleet-rpc-timeout-s", type=float, default=None,
                     help="per-reply inactivity deadline on cross-"
                          "replica streams and control RPCs (default "
                          "120; 0 disables; size above worst-case "
                          "queue wait + TTFT)")
    # elastic capacity (localai_tpu.fleet.autoscale)
    run.add_argument("--autoscale", action="store_true",
                     default=_env_bool("autoscale"),
                     help="telemetry-driven fleet autoscaling: scale "
                          "decode replicas between --autoscale-min/max "
                          "off queue depth, SLO burn, and KV pressure; "
                          "drain-based scale-in loses zero requests")
    run.add_argument("--autoscale-min", type=int, default=None,
                     help="decode replica floor the autoscaler holds "
                          "(default 1)")
    run.add_argument("--autoscale-max", type=int, default=None,
                     help="decode replica ceiling for scale-out "
                          "(default 4)")
    run.add_argument("--autoscale-interval-s", type=float, default=None,
                     help="seconds between autoscale control-loop ticks "
                          "(default 5)")
    run.add_argument("--autoscale-in-idle-s", type=float, default=None,
                     help="a replica idle this long (fleet above the "
                          "floor) is drained and retired (default 120)")
    run.add_argument("--autoscale-zero-idle-s", type=float, default=None,
                     help="ALL replicas idle this long → scale the model "
                          "to zero; the next request cold-respawns one "
                          "and waits for it (0 = off, the default)")
    run.add_argument("--autoscale-standby-hosts", default=None,
                     help="comma-separated host:port standby workers "
                          "adopted (instant capacity) before spawning "
                          "when scaling out")

    models = sub.add_parser("models", help="model management")
    models_sub = models.add_subparsers(dest="models_command")
    mlist = models_sub.add_parser("list", help="list configured models")
    mlist.add_argument("--models-path", default="models")
    minstall = models_sub.add_parser(
        "install", help="install from gallery/embedded library/URL")
    minstall.add_argument("ref", help="name, gallery@name, or URL")
    minstall.add_argument("--models-path", default="models")
    minstall.add_argument("--name", default="", help="install under this name")
    minstall.add_argument("--galleries", default="",
                          help="JSON list of {name,url} galleries")
    mavail = models_sub.add_parser(
        "available", help="list models available to install")
    mavail.add_argument("--models-path", default="models")
    mavail.add_argument("--galleries", default="")

    tok = sub.add_parser("tokenize", help="tokenize text with a model")
    tok.add_argument("text")
    tok.add_argument("--model", required=True)
    tok.add_argument("--models-path", default="models")

    worker = sub.add_parser("worker", help="start a gRPC model worker")
    worker.add_argument("--addr", default="127.0.0.1:50051")

    fol = sub.add_parser(
        "follower",
        help="multi-host follower: replicate a leader's engine calls")
    fol.add_argument("--leader", required=True,
                     help="leader's mirror channel host:port")
    fol.add_argument("--model", required=True)
    fol.add_argument("--models-path",
                     default=_env_default("models_path", "models"))
    fol.add_argument("--coordinator",
                     default=_env_default("coordinator_address", ""),
                     help="jax.distributed coordinator host:port")
    fol.add_argument("--num-processes", type=int,
                     default=int(_env_default("num_processes", 1)))
    fol.add_argument("--process-id", type=int,
                     default=int(_env_default("process_id", 1)))
    fol.add_argument("--peer-token",
                     default=_env_default("peer_token", ""),
                     help="shared secret for the mirror channel")

    tts = sub.add_parser("tts", help="synthesize speech to a wav file")
    tts.add_argument("text", nargs="+")
    tts.add_argument("--model", "-m", default="")
    tts.add_argument("--voice", "-v", default="alloy")
    tts.add_argument("--language", "-l", default="")
    tts.add_argument("--output-file", "-o", default="tts.wav")
    tts.add_argument("--models-path", default=_env_default(
        "models_path", "models"))

    tr = sub.add_parser("transcript", help="transcribe a wav file")
    tr.add_argument("filename")
    tr.add_argument("--model", "-m", default="")
    tr.add_argument("--language", "-l", default="")
    tr.add_argument("--translate", action="store_true")
    tr.add_argument("--models-path", default=_env_default(
        "models_path", "models"))

    sg = sub.add_parser("sound-generation",
                        help="generate audio from a text description")
    sg.add_argument("text", nargs="+")
    sg.add_argument("--model", "-m", default="")
    sg.add_argument("--duration", "-d", type=float, default=3.0)
    sg.add_argument("--output-file", "-o", default="sound.wav")

    util = sub.add_parser("util", help="model utilities")
    util_sub = util.add_subparsers(dest="util_command")
    ci = util_sub.add_parser(
        "checkpoint-info",
        help="tensor names/shapes/dtypes of a safetensors checkpoint "
             "(the safetensors-era gguf-info)")
    ci.add_argument("path")
    ci.add_argument("--header", action="store_true",
                    help="also print config.json")
    scan = util_sub.add_parser(
        "scan", help="scan installed models for unsafe weight formats")
    scan.add_argument("--models-path", default=_env_default(
        "models_path", "models"))
    uh = util_sub.add_parser(
        "usecase-heuristic",
        help="print the usecases a model config will serve")
    uh.add_argument("name")
    uh.add_argument("--models-path", default=_env_default(
        "models_path", "models"))
    cv = util_sub.add_parser(
        "convert",
        help="convert a GGUF checkpoint (f32/f16/q8_0/q4_0/q4_1/q4_k/q6_k) "
             "to the native safetensors layout; serve the result with "
             "quantization: int4/int8 for q4/q8-class bandwidth")
    cv.add_argument("gguf", help="path to the .gguf file")
    cv.add_argument("out", nargs="?", default=None,
                    help="output dir (default: <gguf stem> next to it)")
    cv.add_argument("--dtype", default="bfloat16",
                    choices=["bfloat16", "float32", "float16"])

    exp = sub.add_parser(
        "explorer", help="multi-network discovery dashboard over "
                         "federation routers (dial-test + eviction)")
    exp.add_argument("--address", default="0.0.0.0")
    exp.add_argument("--port", type=int, default=8085)
    exp.add_argument("--router", required=True,
                     help="federation router base URL (more can be "
                          "registered at runtime via POST /api/networks)")
    exp.add_argument("--db", default="",
                     help="JSON file persisting the tracked-network list")
    exp.add_argument("--interval", type=float, default=50.0,
                     help="seconds between dial-test sweeps")
    exp.add_argument("--failure-threshold", type=int, default=3,
                     help="consecutive failures before a network is "
                          "evicted from the database")

    fed = sub.add_parser(
        "federated", help="run a federation router over instances")
    fed.add_argument("--address", default=_env_default("address", "0.0.0.0"))
    fed.add_argument("--port", type=int,
                     default=int(_env_default("port", 8080)))
    fed.add_argument("--peers", default=_env_default("peers", ""),
                     help="comma-separated instance addresses (host:port)")
    fed.add_argument("--random-worker", action="store_true",
                     default=_env_bool("random_worker"),
                     help="random selection instead of least-used")
    fed.add_argument("--target-worker",
                     default=_env_default("target_worker", ""),
                     help="pin all traffic to one instance")
    fed.add_argument("--peer-token",
                     default=_env_default("peer_token", ""),
                     help="shared secret for /federated/register")

    sub.add_parser("version", help="print version")
    return p


def _parse_mesh(spec: str) -> Optional[dict]:
    # the ONE mesh parser (parallel.mesh.parse_mesh_spec) — shared with
    # AppConfig.from_env's LOCALAI_MESH handling so flag and env agree
    from localai_tpu.parallel.mesh import parse_mesh_spec

    return parse_mesh_spec(spec)


def _run_util(args, parser) -> int:
    """`util` subcommands (parity: core/cli/util.go — gguf-info/hf-scan/
    usecase-heuristic, re-targeted at the safetensors ecosystem)."""
    if args.util_command == "checkpoint-info":
        from pathlib import Path

        p = Path(args.path)
        files = [p] if p.is_file() else sorted(p.glob("*.safetensors"))
        if not files:
            parser.error(f"no safetensors under {p}")
        cfg_dir = p.parent if p.is_file() else p
        if args.header and (cfg_dir / "config.json").exists():
            print((cfg_dir / "config.json").read_text())
        from safetensors import safe_open

        total = 0
        for fp in files:
            with safe_open(str(fp), framework="numpy") as h:
                for name in h.keys():
                    sl = h.get_slice(name)
                    shape, dtype = sl.get_shape(), sl.get_dtype()
                    n = 1
                    for d in shape:
                        n *= d
                    total += n
                    print(f"{name}\t{dtype}\t{list(shape)}")
        print(f"# total parameters: {total:,}")
        return 0

    if args.util_command == "scan":
        # safetensors-era hf-scan: weights must be safetensors; pickle
        # formats (.bin/.pt/.ckpt) execute arbitrary code at load
        from pathlib import Path

        bad = []
        for f in Path(args.models_path).rglob("*"):
            if f.suffix in (".bin", ".pt", ".pth", ".ckpt", ".pickle",
                            ".pkl"):
                bad.append(f)
        for f in bad:
            print(f"UNSAFE (pickle-format weights): {f}")
        print(f"{len(bad)} finding(s)")
        return 1 if bad else 0

    if args.util_command == "convert":
        from pathlib import Path

        from localai_tpu.utils.gguf import convert_gguf

        src = Path(args.gguf)
        if not src.is_file():
            parser.error(f"{src}: not a file")
        out = Path(args.out) if args.out else src.with_suffix("")
        convert_gguf(src, out, dtype=args.dtype)
        print(f"converted {src} -> {out}")
        return 0

    if args.util_command == "usecase-heuristic":
        from localai_tpu.config.loader import ConfigLoader
        from localai_tpu.config.model_config import Usecase

        loader = ConfigLoader(args.models_path)
        loader.load_from_path()
        mcfg = loader.get(args.name)
        if mcfg is None:
            parser.error(f"model {args.name!r} not found")
        for uc in Usecase:
            if mcfg.has_usecase(uc):
                print(uc.value)
        return 0

    parser.error("unknown util subcommand")
    return 2


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(argv if argv is not None else sys.argv[1:])
    parser = build_parser()
    args = parser.parse_args(argv)
    level = {"error": logging.ERROR, "warn": logging.WARNING,
             "info": logging.INFO, "debug": logging.DEBUG,
             "trace": logging.DEBUG}[args.log_level]
    # obs.logging imports no jax — safe before the backend initializes
    from localai_tpu.obs import logging as obs_logging

    obs_logging.setup(args.log_format, level)

    cmd = args.command or "run"
    if cmd == "version":
        from localai_tpu.version import __version__

        print(__version__)
        return 0

    if cmd == "run":
        if args.platform:
            # this process only, and never through os.environ: spawned
            # workers copy the environment, and a server kept off the
            # chips with --platform cpu must not drag its pinned TPU
            # workers onto the CPU with it
            import jax

            jax.config.update("jax_platforms", args.platform)
        from localai_tpu.api.server import serve
        from localai_tpu.config.app_config import AppConfig

        # env first (LOCALAI_* for every AppConfig field — parity with the
        # kong env tags), explicit CLI values override
        cfg = AppConfig.from_env(
            model_path=args.models_path,
            address=args.address,
            port=args.port,
            context_size=args.context_size,
            cors=args.cors,
            api_keys=[k for k in args.api_keys.split(",") if k],
            opaque_errors=args.opaque_errors,
            single_active_backend=args.single_active_backend,
            preload_models=[m for m in args.preload_models.split(",") if m]
            + list(args.models),
            watchdog_idle=args.enable_watchdog_idle,
            watchdog_busy=args.enable_watchdog_busy,
            watchdog_idle_timeout=args.watchdog_idle_timeout,
            watchdog_busy_timeout=args.watchdog_busy_timeout,
            mesh_shape=_parse_mesh(args.mesh),
            platform=args.platform,
            # None = flag not given → LOCALAI_SLO_* env (from_env) stands
            slo_ttft_p95_ms=args.slo_ttft_p95_ms,
            slo_tpot_p95_ms=args.slo_tpot_p95_ms,
            slo_e2e_p95_ms=args.slo_e2e_p95_ms,
            slo_queue_p95_ms=args.slo_queue_p95_ms,
            slo_burn_threshold=args.slo_burn_threshold,
            request_deadline_s=args.request_deadline_s,
            batch_concurrency=args.batch_concurrency,
            batch_expiry_h=args.batch_expiry_h,
            fleet_replicas=args.fleet_replicas,
            fleet_prefill_replicas=args.fleet_prefill_replicas,
            fleet_backend=args.fleet_backend,
            fleet_disagg_threshold=args.fleet_disagg_threshold,
            fleet_device_pinning=args.fleet_device_pinning or None,
            fleet_hosts=([h for h in args.fleet_hosts.split(",") if h]
                         if args.fleet_hosts is not None else None),
            fleet_rpc_timeout_s=args.fleet_rpc_timeout_s,
            autoscale=args.autoscale or None,
            autoscale_min=args.autoscale_min,
            autoscale_max=args.autoscale_max,
            autoscale_interval_s=args.autoscale_interval_s,
            autoscale_in_idle_s=args.autoscale_in_idle_s,
            autoscale_zero_idle_s=args.autoscale_zero_idle_s,
            autoscale_standby_hosts=(
                [h for h in args.autoscale_standby_hosts.split(",") if h]
                if args.autoscale_standby_hosts is not None else None),
        )
        serve(cfg)
        return 0

    if cmd == "models":
        if args.models_command == "list":
            from localai_tpu.config.loader import ConfigLoader

            loader = ConfigLoader(args.models_path)
            loader.load_from_path()
            for name in loader.names():
                print(name)
            return 0
        if args.models_command in ("install", "available"):
            import json as jsonlib

            from localai_tpu.gallery import (
                EMBEDDED_MODELS,
                Gallery,
                available_models,
                install_model,
                resolve_ref,
            )

            galleries = [
                Gallery(name=g["name"], url=g["url"])
                for g in (jsonlib.loads(args.galleries)
                          if args.galleries else [])
            ]
            if args.models_command == "available":
                for m in available_models(galleries, args.models_path):
                    mark = "*" if m.installed else " "
                    print(f"{mark} {m.id}\t{m.description}")
                for name, m in sorted(EMBEDDED_MODELS.items()):
                    print(f"  embedded@{name}\t{m.description}")
                return 0
            ref = args.ref
            model = resolve_ref(galleries, ref, name=args.name)
            if model is None:
                parser.error(f"model {ref!r} not found in embedded library "
                             "or configured galleries")

            def progress(fn, done, total):
                pct = f"{100.0 * done / total:5.1f}%" if total else "?"
                print(f"\r{fn}: {pct}", end="", flush=True)

            path = install_model(
                model, args.models_path,
                install_name=args.name or model.name, progress=progress,
            )
            print(f"\ninstalled → {path}")
            return 0
        parser.error("unknown models subcommand")

    if cmd == "tokenize":
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from localai_tpu.config.loader import ConfigLoader
        from localai_tpu.models.registry import resolve_tokenizer

        loader = ConfigLoader(args.models_path)
        loader.load_from_path()
        mcfg = loader.get(args.model)
        if mcfg is None:
            parser.error(f"model {args.model!r} not found")
        # tokenizer-only: never pull weights/KV into RAM just to encode
        tok = resolve_tokenizer(mcfg.model, args.models_path)
        print(tok.encode(args.text))
        return 0

    if cmd == "worker":
        from localai_tpu.worker.server import serve_worker

        serve_worker(args.addr)
        return 0

    if cmd == "follower":
        from localai_tpu.config.app_config import AppConfig
        from localai_tpu.config.loader import ConfigLoader

        if args.coordinator:
            from localai_tpu.parallel.multihost import initialize

            initialize(args.coordinator, args.num_processes,
                       args.process_id)
        app_cfg = AppConfig.from_env(model_path=args.models_path)
        loader = ConfigLoader(args.models_path)
        loader.load_from_path(context_size=app_cfg.context_size)
        mcfg = loader.get(args.model)
        if mcfg is None:
            parser.error(f"model {args.model!r} not found")
        from localai_tpu.models.manager import build_runner
        from localai_tpu.parallel.multihost import CommandFollower

        _model, runner = build_runner(mcfg, app_cfg)
        print(f"follower replica of {args.model} ready; replaying from "
              f"{args.leader}", flush=True)
        CommandFollower(args.leader, {args.model: runner},
                        token=args.peer_token).run_forever()
        return 0

    if cmd == "tts":
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from localai_tpu.audio import write_wav
        from localai_tpu.audio.tts import synthesize
        from localai_tpu.config.loader import ConfigLoader

        voice = args.voice
        if args.model:
            loader = ConfigLoader(args.models_path)
            loader.load_from_path()
            mcfg = loader.get(args.model)
            tcfg = getattr(mcfg, "tts", None) if mcfg else None
            if tcfg is not None and getattr(tcfg, "voice", ""):
                voice = tcfg.voice
        samples = synthesize(" ".join(args.text), voice=voice)
        with open(args.output_file, "wb") as f:
            f.write(write_wav(samples))
        print(args.output_file)
        return 0

    if cmd == "transcript":
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from pathlib import Path

        from localai_tpu.audio import read_wav
        from localai_tpu.config.loader import ConfigLoader
        from localai_tpu.models import whisper as wh

        loader = ConfigLoader(args.models_path)
        loader.load_from_path()
        name = args.model
        if not name:
            from localai_tpu.config.model_config import Usecase

            for cfg in loader.all():
                if cfg.has_usecase(Usecase.TRANSCRIPT):
                    name = cfg.name
                    break
        mcfg = loader.get(name) if name else None
        ref = (mcfg.model if mcfg else name) or name
        if not ref:
            parser.error("no transcription model configured; pass --model")
        if ref.startswith("debug:"):
            model = wh.debug_model()
        else:
            for cand in (Path(ref), Path(args.models_path) / ref):
                if (cand / "config.json").exists():
                    model = wh.load_hf_whisper(cand)
                    break
            else:
                parser.error(f"whisper model {ref!r} not found")
        audio = read_wav(Path(args.filename).read_bytes())
        result = model.transcribe(
            audio, language=args.language or None,
            translate=args.translate,
        )
        for seg in result.get("segments", []):
            print(f"[{seg['start']:7.2f}s → {seg['end']:7.2f}s] "
                  f"{seg['text']}")
        print(result.get("text", ""))
        return 0

    if cmd == "sound-generation":
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from localai_tpu.audio import write_wav
        from localai_tpu.audio.tts import generate_sound

        samples = generate_sound(" ".join(args.text),
                                 duration=args.duration)
        with open(args.output_file, "wb") as f:
            f.write(write_wav(samples))
        print(args.output_file)
        return 0

    if cmd == "util":
        return _run_util(args, parser)

    if cmd == "explorer":
        from localai_tpu.federation.explorer import serve_explorer

        serve_explorer(args.router, args.address, args.port,
                       db_path=args.db or None, interval=args.interval,
                       failure_threshold=args.failure_threshold)
        return 0

    if cmd == "federated":
        from localai_tpu.federation import FederatedServer

        fs = FederatedServer(
            [a.strip() for a in args.peers.split(",") if a.strip()],
            load_balanced=not args.random_worker,
            worker_target=args.target_worker,
            peer_token=args.peer_token,
        )
        fs.serve(args.address, args.port)
        return 0

    parser.error(f"unknown command {cmd!r}")
    return 2


if __name__ == "__main__":
    raise SystemExit(main())

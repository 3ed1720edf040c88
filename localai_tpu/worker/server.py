"""The model-worker gRPC server: one process, one loaded model.

This is the process-isolation tier of the framework — the TPU-era
counterpart of the reference's backend workers (llama.cpp gRPC server,
/root/reference/backend/cpp/llama/grpc-server.cpp:2304-2458, and the Go
harness /root/reference/pkg/grpc/server.go:23-60+): the API server spawns
one of these per model (worker.process), so an engine crash never takes
down the API, and external/third-party workers can implement the same
contract (rpc.METHODS) in any language.

Inside the process the engine is the same ModelRunner + continuous-batching
Scheduler the in-process manager uses (models.manager.build_serving_model);
the worker adds only the wire surface.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import signal
import threading
from concurrent import futures
from typing import Any, Iterator, Optional

import grpc

from localai_tpu.faults import registry as _faults
from localai_tpu.worker import backend_pb2 as pb
from localai_tpu.worker import rpc

log = logging.getLogger(__name__)


def gen_request_from_options(req: pb.PredictOptions, sm,
                             trace_id: str = "", tenant: str = ""):
    """PredictOptions → GenRequest against a ServingModel (the wire→engine
    converter; inverse of worker.serving.predict_options). Shared by the
    gRPC servicer and in-process fleet replicas, so both replica kinds
    decode one request schema identically."""
    from localai_tpu.engine.scheduler import GenRequest

    if req.tokens:
        prompt = list(req.tokens)
    else:
        prompt = sm.tokenizer.encode(req.prompt, add_bos=req.add_bos)
    constraint = None
    if req.constraint_schema:
        from localai_tpu.functions.constraint import constraint_for_schema

        constraint = constraint_for_schema(
            json.loads(req.constraint_schema), sm.tokenizer
        )
    elif req.constraint_regex:
        from localai_tpu.functions.constraint import constraint_for_regex

        constraint = constraint_for_regex(req.constraint_regex, sm.tokenizer)

    def opt(name):
        return getattr(req, name) if req.HasField(name) else None

    return GenRequest(
        prompt=prompt,
        max_new_tokens=req.max_tokens or 2048,
        temperature=opt("temperature"),
        top_k=opt("top_k"),
        top_p=opt("top_p"),
        min_p=opt("min_p"),
        repeat_penalty=opt("repeat_penalty"),
        presence_penalty=opt("presence_penalty"),
        frequency_penalty=opt("frequency_penalty"),
        seed=opt("seed"),
        logit_bias=dict(req.logit_bias) or None,
        stop=tuple(req.stop),
        ignore_eos=req.ignore_eos,
        constraint=constraint,
        correlation_id=req.correlation_id,
        # propagated from the API tier over gRPC metadata: the worker's
        # engine spans record under the same trace id (obs subsystem)
        trace_id=trace_id or req.correlation_id,
        # hashed tenant bucket for the usage ledger (obs.ledger); callers
        # that deliberately leave it empty (InProcessReplica's inner
        # resubmit) keep their engine feed unattributed
        tenant=tenant,
        stream=req.stream,
    )


class BackendServicer:
    """LLM worker: Predict/PredictStream/Embedding + lifecycle RPCs.

    Modality RPCs (TTS, transcription, image gen, rerank, stores) are
    intentionally absent here — rpc.add_servicer answers UNIMPLEMENTED for
    them, and dedicated workers (audio/image/store servicers) implement
    them instead, exactly like the reference's per-modality backends.
    """

    def __init__(self) -> None:
        self._sm: Optional[Any] = None  # ServingModel
        self._load_error = ""
        self._lock = threading.Lock()

    # -- lifecycle -------------------------------------------------------

    def Health(self, request: pb.HealthMessage, context) -> pb.Reply:
        return pb.Reply(message=b"OK")

    def LoadModel(self, request: pb.ModelOptions, context) -> pb.Result:
        from localai_tpu.config.app_config import AppConfig
        from localai_tpu.config.model_config import ModelConfig
        from localai_tpu.models.manager import build_serving_model

        with self._lock:
            if self._sm is not None:
                return pb.Result(success=True, message="already loaded")
            try:
                if request.config_yaml:
                    import yaml

                    doc = yaml.safe_load(request.config_yaml) or {}
                else:
                    doc = {"name": request.model or "model",
                           "model": request.model}
                if request.model:
                    doc.setdefault("model", request.model)
                if request.context_size:
                    doc["context_size"] = request.context_size
                if request.seed:
                    doc["seed"] = request.seed
                mcfg = ModelConfig.model_validate(doc)
                app = AppConfig(model_path=request.model_path or "models")
                self._sm = build_serving_model(mcfg, app)
                # the parent checks where this worker actually came up
                # (worker.process.check_worker_device)
                from localai_tpu.obs.device import device_report

                return pb.Result(success=True, message=json.dumps(
                    {"device": device_report()}))
            except Exception as e:  # noqa: BLE001 — report, don't crash
                self._load_error = f"{type(e).__name__}: {e}"
                log.exception("LoadModel failed")
                return pb.Result(success=False, message=self._load_error)

    # _sm/_load_error are single-assignment references set by LoadModel
    # under the lock; serving paths read them lock-free — a reader sees
    # None (not loaded) or a fully constructed model, never a torn value
    def Status(self, request: pb.HealthMessage, context) -> pb.StatusResponse:  # jaxlint: disable=lock-guarded-attr
        if self._sm is None:
            state = (pb.StatusResponse.ERROR if self._load_error
                     else pb.StatusResponse.UNINITIALIZED)
            return pb.StatusResponse(state=state)
        busy = self._sm.scheduler.busy
        state = pb.StatusResponse.BUSY if busy else pb.StatusResponse.READY
        mem = {}
        try:
            import resource

            mem["maxrss_kb"] = resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss
        except Exception:  # noqa: BLE001
            pass
        return pb.StatusResponse(state=state, memory=mem)

    def GetMetrics(self, request: pb.MetricsRequest,
                   context) -> pb.MetricsResponse:  # jaxlint: disable=lock-guarded-attr
        if self._sm is None:
            return pb.MetricsResponse(json="{}")
        payload = self._sm.scheduler.metrics()
        # the worker process has no HTTP surface, so its engine span trees
        # (recorded under trace ids propagated over the RPC metadata) ride
        # the metrics JSON — the API tier surfaces them at /backend/metrics
        from localai_tpu.obs.trace import STORE

        payload["recent_traces"] = [
            t.to_dict() for t in STORE.recent(limit=20, kind="request")
        ]
        return pb.MetricsResponse(json=json.dumps(payload))

    def GetTelemetry(self, request: pb.TelemetryRequest,
                     context) -> pb.TelemetryResponse:  # jaxlint: disable=lock-guarded-attr
        """Fleet telemetry harvest (obs/fleetview): this replica's spans
        for one trace id (or a recent window), its flight-ring snapshot,
        and its scheduler metrics dict — everything host-side, so the
        pull can never queue work behind a wedged device dispatch. The
        payload shape is owned by obs.fleetview.telemetry_payload (shared
        with InProcessReplica, so the replica kinds cannot drift)."""
        from localai_tpu.obs.fleetview import telemetry_payload

        sched = self._sm.scheduler if self._sm is not None else None
        # 0/unset → defaults; -1 is the client's explicit "none" (proto3
        # cannot carry a distinguishable 0), clamped back to 0 here
        payload = telemetry_payload(
            sched, trace_id=request.trace_id, since=request.since,
            limit=max(0, request.limit or 256),
            recent=max(0, request.recent or 20))
        return pb.TelemetryResponse(json=json.dumps(payload))

    # -- inference -------------------------------------------------------

    def _require_model(self, context):  # jaxlint: disable=lock-guarded-attr
        if self._sm is None:
            context.abort(
                grpc.StatusCode.FAILED_PRECONDITION,
                self._load_error or "no model loaded (call LoadModel first)",
            )
        return self._sm

    def _gen_request(self, req: pb.PredictOptions, sm, trace_id: str = "",
                     tenant: str = ""):
        return gen_request_from_options(req, sm, trace_id=trace_id,
                                        tenant=tenant)

    def Predict(self, request: pb.PredictOptions, context) -> pb.Reply:
        sm = self._require_model(context)
        handle = sm.scheduler.submit(self._gen_request(
            request, sm, trace_id=rpc.trace_id_from_context(context),
            tenant=rpc.tenant_from_context(context)))
        try:
            handle.result(timeout=600.0)
        finally:
            if handle.finish_reason is None:
                # timeout or abandoned RPC — free the decode slot
                handle.cancel()
        return pb.Reply(
            message=handle.text.encode("utf-8"),
            tokens=handle.completion_tokens,
            prompt_tokens=handle.prompt_tokens,
            finish_reason=handle.finish_reason or "stop",
        )

    def PredictStream(self, request: pb.PredictOptions,
                      context) -> Iterator[pb.Reply]:
        sm = self._require_model(context)
        handle = sm.scheduler.submit(self._gen_request(
            request, sm, trace_id=rpc.trace_id_from_context(context),
            tenant=rpc.tenant_from_context(context)))
        try:
            for item in handle:
                if _faults.ACTIVE:
                    # chaos: a worker stream that errors (raise) or
                    # crawls (sleep) mid-flight — the caller's failover/
                    # watchdog paths must absorb it
                    _faults.apply("worker.stream", key=sm.name)
                if item.finish_reason is not None:
                    yield pb.Reply(
                        message=b"",
                        tokens=handle.completion_tokens,
                        prompt_tokens=handle.prompt_tokens,
                        finish_reason=item.finish_reason,
                    )
                    break
                if item.delta:
                    yield pb.Reply(message=item.delta.encode("utf-8"))
        finally:
            if not context.is_active():
                handle.cancel()

    # -- fleet disaggregation (localai_tpu.fleet) ------------------------

    def _fleet_cache(self, sm):
        """The replica's in-memory prefix cache, attached lazily on first
        PrefillPrefix/TransferPrefix use. A configured disk prompt cache
        has the lookup/store surface but not the ``wait_for`` signalling
        the export blocks on, so the RAM tier FRONTS it (stores forward,
        missed lookups fall through — scheduler.attach_prompt_cache
        layer=True) instead of replacing it."""
        sched = sm.scheduler
        if not hasattr(sched.prompt_cache, "wait_for"):
            from localai_tpu.fleet.prefix import PrefixCache

            with self._lock:
                if not hasattr(sched.prompt_cache, "wait_for"):
                    sched.attach_prompt_cache(PrefixCache(
                        min_prefix=getattr(sm.runner, "prefix_reuse_min",
                                           16)), layer=True)
        return sched.prompt_cache

    def PrefillPrefix(self, request: pb.PredictOptions,
                      context) -> Iterator[pb.PrefixChunk]:
        """Prefill-replica half of the disaggregated handoff: run the
        prompt's prefill (one sampled token, then the slot frees), wait
        for the scheduler's off-thread prefix export, and stream the
        packed KV rows out in bounded chunks."""
        from localai_tpu.fleet.prefix import (PrefixUnavailable,
                                              export_prefix, pack_chunks)

        sm = self._require_model(context)
        cache = self._fleet_cache(sm)
        gr = self._gen_request(request, sm,
                               trace_id=rpc.trace_id_from_context(context))
        try:
            prompt, arrays = export_prefix(sm, gr, cache)
        except ValueError as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        except PrefixUnavailable as e:
            context.abort(grpc.StatusCode.FAILED_PRECONDITION, str(e))
        except RuntimeError as e:
            context.abort(grpc.StatusCode.INTERNAL, str(e))
        for chunk in pack_chunks(prompt, arrays):
            yield pb.PrefixChunk(**chunk)

    def TransferPrefix(self, request_iterator, context) -> pb.Result:
        """Decode-replica half: assemble the streamed chunks and seed the
        prefix cache — the next PredictStream for this prompt
        load_prefix-resumes past the transferred rows at admission."""
        from localai_tpu.fleet.prefix import import_prefix

        sm = self._require_model(context)
        cache = self._fleet_cache(sm)
        try:
            n = import_prefix(cache, request_iterator)
        except ValueError as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        return pb.Result(success=True, message=f"{n} rows")

    def Embedding(self, request: pb.EmbeddingRequest,
                  context) -> pb.EmbeddingResult:
        sm = self._require_model(context)
        if request.tokens:
            toks = list(request.tokens)
        else:
            toks = sm.tokenizer.encode(request.text, add_bos=True)
        vec = sm.runner.embed(toks)
        return pb.EmbeddingResult(embeddings=[float(x) for x in vec])

    def TokenizeString(self, request: pb.TokenizationRequest,
                       context) -> pb.TokenizationResponse:
        sm = self._require_model(context)
        ids = sm.tokenizer.encode(request.text, add_bos=request.add_bos)
        return pb.TokenizationResponse(length=len(ids), tokens=ids)

    def shutdown(self) -> None:
        with self._lock:
            if self._sm is not None:
                self._sm.scheduler.shutdown()
                self._sm = None


class StoreServicer:
    """Standalone vector-store worker (parity: the local-store Go backend
    process, /root/reference/backend/go/stores/store.go, speaking the
    Stores RPCs of the shared contract)."""

    def __init__(self) -> None:
        from localai_tpu.stores import VectorStore

        self._store = VectorStore()

    def Health(self, request: pb.HealthMessage, context) -> pb.Reply:
        return pb.Reply(message=b"OK")

    def LoadModel(self, request: pb.ModelOptions, context) -> pb.Result:
        return pb.Result(success=True, message="store ready")

    def Status(self, request: pb.HealthMessage, context) -> pb.StatusResponse:
        return pb.StatusResponse(state=pb.StatusResponse.READY)

    def StoresSet(self, request: pb.StoresSetOptions, context) -> pb.Result:
        try:
            self._store.set(
                [list(k.floats) for k in request.keys],
                [v.bytes for v in request.values],
            )
        except ValueError as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        return pb.Result(success=True)

    def StoresDelete(self, request: pb.StoresDeleteOptions,
                     context) -> pb.Result:
        try:
            self._store.delete([list(k.floats) for k in request.keys])
        except ValueError as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        return pb.Result(success=True)

    def StoresGet(self, request: pb.StoresGetOptions,
                  context) -> pb.StoresGetResult:
        try:
            keys, values = self._store.get(
                [list(k.floats) for k in request.keys]
            )
        except ValueError as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        out = pb.StoresGetResult()
        for k, v in zip(keys, values):
            if v is None:
                continue
            out.keys.append(pb.StoresKey(floats=k))
            out.values.append(pb.StoresValue(bytes=v))
        return out

    def StoresFind(self, request: pb.StoresFindOptions,
                   context) -> pb.StoresFindResult:
        if request.top_k < 0:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                          "top_k must be >= 1")
        try:
            keys, values, sims = self._store.find(
                list(request.key.floats), request.top_k or 10
            )
        except ValueError as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        out = pb.StoresFindResult(similarities=sims)
        for k, v in zip(keys, values):
            out.keys.append(pb.StoresKey(floats=k))
            out.values.append(pb.StoresValue(bytes=v))
        return out

    def shutdown(self) -> None:
        pass


class AudioServicer:
    """Audio worker: AudioTranscription + TTS + SoundGeneration RPCs
    (parity: the whisper.cpp, piper and musicgen worker processes,
    /root/reference/backend/go/transcribe/whisper/whisper.go:21-105,
    backend/go/tts/piper.go:20-49, backend/python/transformers-musicgen)."""

    def __init__(self) -> None:
        self._whisper = None
        self._lock = threading.Lock()

    def Health(self, request: pb.HealthMessage, context) -> pb.Reply:
        return pb.Reply(message=b"OK")

    def Status(self, request: pb.HealthMessage, context) -> pb.StatusResponse:
        return pb.StatusResponse(state=pb.StatusResponse.READY)

    def LoadModel(self, request: pb.ModelOptions, context) -> pb.Result:
        from pathlib import Path

        from localai_tpu.models import whisper as wh

        with self._lock:
            try:
                ref = request.model or "debug:whisper"
                if ref.startswith("debug:"):
                    self._whisper = wh.debug_model(seed=request.seed)
                else:
                    base = Path(request.model_path or "models")
                    cand = Path(ref) if Path(ref).is_dir() else base / ref
                    self._whisper = wh.load_hf_whisper(cand)
                return pb.Result(success=True, message="ok")
            except Exception as e:  # noqa: BLE001
                log.exception("audio LoadModel failed")
                return pb.Result(success=False,
                                 message=f"{type(e).__name__}: {e}")

    # same single-assignment-reference pattern as BackendServicer._sm
    def AudioTranscription(self, request: pb.TranscriptRequest,
                           context) -> pb.TranscriptResult:  # jaxlint: disable=lock-guarded-attr
        from localai_tpu.audio import read_wav

        if self._whisper is None:
            context.abort(grpc.StatusCode.FAILED_PRECONDITION,
                          "no model loaded (call LoadModel first)")
        data = request.audio
        if not data and request.path:
            try:
                data = open(request.path, "rb").read()
            except OSError as e:
                context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        try:
            audio = read_wav(data)
        except ValueError as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        res = self._whisper.transcribe(
            audio, language=request.language or None,
            translate=request.translate,
        )
        out = pb.TranscriptResult(text=res["text"])
        for seg in res["segments"]:
            out.segments.append(pb.TranscriptSegment(
                id=seg["id"],
                start=int(seg["start"] * 1e9),
                end=int(seg["end"] * 1e9),
                text=seg["text"],
                tokens=seg["tokens"],
            ))
        return out

    def TTS(self, request: pb.TTSRequest, context) -> pb.AudioResult:
        from localai_tpu.audio import write_wav
        from localai_tpu.audio import tts as ttsmod

        if not request.text:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, "empty text")
        wav = write_wav(ttsmod.synthesize(
            request.text, voice=request.voice or "alloy"))
        if request.dst:
            with open(request.dst, "wb") as f:
                f.write(wav)
            return pb.AudioResult(success=True, message=request.dst)
        return pb.AudioResult(success=True, audio=wav)

    def SoundGeneration(self, request: pb.SoundGenerationRequest,
                        context) -> pb.AudioResult:
        from localai_tpu.audio import write_wav
        from localai_tpu.audio import tts as ttsmod

        if not request.text:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, "empty text")
        dur = request.duration if request.HasField("duration") else 3.0
        temp = (request.temperature
                if request.HasField("temperature") else 1.0)
        wav = write_wav(ttsmod.generate_sound(request.text, dur, temp))
        if request.dst:
            with open(request.dst, "wb") as f:
                f.write(wav)
            return pb.AudioResult(success=True, message=request.dst)
        return pb.AudioResult(success=True, audio=wav)

    def shutdown(self) -> None:
        pass


class ImageServicer:
    """Image-generation worker behind the GenerateImage RPC (parity: the
    diffusers Python worker process, /root/reference/backend/python/
    diffusers/backend.py:263-474, and the NCNN stablediffusion backend,
    backend/go/image/stablediffusion/stablediffusion.go)."""

    def __init__(self) -> None:
        self._pipe = None
        self._lock = threading.Lock()

    def Health(self, request: pb.HealthMessage, context) -> pb.Reply:
        return pb.Reply(message=b"OK")

    def Status(self, request: pb.HealthMessage, context) -> pb.StatusResponse:
        return pb.StatusResponse(state=pb.StatusResponse.READY)

    def LoadModel(self, request: pb.ModelOptions, context) -> pb.Result:
        from localai_tpu.image import resolve_image_model

        with self._lock:
            try:
                self._pipe = resolve_image_model(
                    request.model or "debug:sd-tiny",
                    model_path=request.model_path or "models",
                )
                return pb.Result(success=True, message="ok")
            except Exception as e:  # noqa: BLE001
                log.exception("image LoadModel failed")
                return pb.Result(success=False,
                                 message=f"{type(e).__name__}: {e}")

    # same single-assignment-reference pattern as BackendServicer._sm
    def GenerateImage(self, request: pb.GenerateImageRequest,
                      context) -> pb.ImageResult:  # jaxlint: disable=lock-guarded-attr
        import io

        from PIL import Image

        if self._pipe is None:
            context.abort(grpc.StatusCode.FAILED_PRECONDITION,
                          "no model loaded (call LoadModel first)")
        try:
            result = self._pipe.generate(
                request.positive_prompt,
                negative_prompt=request.negative_prompt,
                width=request.width or 512,
                height=request.height or 512,
                steps=request.step or None,
                seed=request.seed if request.seed else None,
                cfg_scale=(request.cfg_scale
                           if request.HasField("cfg_scale") else None),
            )
        except Exception as e:  # noqa: BLE001
            log.exception("GenerateImage failed")
            return pb.ImageResult(success=False,
                                  message=f"{type(e).__name__}: {e}")
        buf = io.BytesIO()
        Image.fromarray(result.image).save(buf, format="PNG")
        png = buf.getvalue()
        if request.dst:
            with open(request.dst, "wb") as f:
                f.write(png)
            return pb.ImageResult(success=True, message=request.dst)
        return pb.ImageResult(success=True, image=png)

    def shutdown(self) -> None:
        pass


SERVICERS = {
    "llm": BackendServicer,
    "store": StoreServicer,
    "audio": AudioServicer,
    "image": ImageServicer,
}


def serve_worker(addr: str = "127.0.0.1:0",
                 servicer: Optional[Any] = None,
                 block: bool = True) -> tuple[grpc.Server, int]:
    """Start the worker gRPC server. Returns (server, bound_port)."""
    servicer = servicer or BackendServicer()
    server = grpc.server(
        futures.ThreadPoolExecutor(max_workers=32),
        options=[("grpc.max_receive_message_length", 256 * 1024 * 1024),
                 ("grpc.max_send_message_length", 256 * 1024 * 1024)],
    )
    rpc.add_servicer(server, servicer)
    port = server.add_insecure_port(addr)
    if port == 0:
        raise RuntimeError(f"could not bind worker to {addr}")
    server.start()
    log.info("worker listening on port %d", port)
    if block:
        stop = threading.Event()

        def _sig(*_a):
            stop.set()

        signal.signal(signal.SIGTERM, _sig)
        signal.signal(signal.SIGINT, _sig)
        stop.wait()
        if hasattr(servicer, "shutdown"):
            servicer.shutdown()
        server.stop(grace=5.0)
    return server, port


def main(argv: Optional[list[str]] = None) -> int:
    parser = argparse.ArgumentParser(description="localai-tpu model worker")
    parser.add_argument("--addr", default="127.0.0.1:0",
                        help="host:port to bind (port 0 = ephemeral)")
    parser.add_argument("--servicer", default="llm",
                        help=f"which servicer to run ({'/'.join(SERVICERS)})")
    args = parser.parse_args(argv)
    logging.basicConfig(
        level=os.environ.get("LOCALAI_LOG_LEVEL", "INFO").upper(),
        format="%(asctime)s %(levelname).1s %(name)s: %(message)s",
    )
    # deterministic fault injection (chaos harness): LOCALAI_FAULT_* in a
    # spawned worker's env arms its registry at boot, never per request
    _faults.install_from_env()
    try:
        servicer = SERVICERS[args.servicer]()
    except KeyError:
        parser.error(f"unknown servicer {args.servicer!r}; "
                     f"have {sorted(SERVICERS)}")
    _server, port = serve_worker(args.addr, servicer=servicer, block=False)
    # the parent process-manager greps this line for the bound port
    print(f"WORKER_READY port={port}", flush=True)
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    stop.wait()
    # stop the engine thread before tearing down grpc so no handler is
    # mid-flight when the C core unwinds
    servicer.shutdown()
    _server.stop(grace=2.0).wait(5.0)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

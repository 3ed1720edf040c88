"""ModelManager: name → live serving engine, loaded on demand.

TPU-era redesign of the reference's model-lifecycle layer
(/root/reference/pkg/model/loader.go:22-206, initializers.go:271-540,
watchdog.go:19-156): where the reference spawns one gRPC worker *process*
per model and health-checks/respawns it, the in-process manager owns one
ModelRunner+Scheduler per model inside the server process. Process-level
isolation (crash containment) is provided by the separate gRPC worker tier
(localai_tpu.worker) — this manager is the in-process fast path, and both
expose the same surface.

Watchdog parity: busy-too-long requests are cancelled, idle-too-long
models are evicted to free HBM (defaults 5m/15m — core/cli/run.go:66-69).
"""

from __future__ import annotations

import dataclasses
import logging
import os
import threading
import time
from typing import Any, Optional

from localai_tpu.config.app_config import AppConfig
from localai_tpu.config.loader import ConfigLoader
from localai_tpu.config.model_config import ModelConfig
from localai_tpu.engine.runner import ModelRunner
from localai_tpu.engine.scheduler import Scheduler
from localai_tpu.models import llama as mdl
from localai_tpu.templates.cache import TemplateCache

log = logging.getLogger(__name__)


@dataclasses.dataclass
class ServingModel:
    """One loaded model: engine + tokenizer + its declarative config."""

    name: str
    config: ModelConfig
    runner: ModelRunner
    scheduler: Scheduler
    tokenizer: Any
    templates: TemplateCache
    vision: Optional[Any] = None      # VisionTower when the model is
                                      # multimodal (mmproj / llava checkpoint)
    image_token_id: int = 0
    loaded_at: float = dataclasses.field(default_factory=time.monotonic)
    last_used: float = dataclasses.field(default_factory=time.monotonic)

    def touch(self) -> None:
        self.last_used = time.monotonic()

    @property
    def busy(self) -> bool:
        return self.scheduler.busy

    def alive(self) -> bool:
        """The engine thread is the health signal (a dead thread → reload,
        parity: CheckIsLoaded health path, loader.go:170-206)."""
        return self.scheduler._thread.is_alive()

    def close(self) -> None:
        self.scheduler.shutdown()

    def engine_metrics(self) -> dict:
        return self.scheduler.metrics()


@dataclasses.dataclass
class ImageServingModel:
    """A loaded diffusion pipeline under the same lifecycle management as
    LLMs: idle/busy watchdog, eviction, /backend/monitor visibility,
    single_active_backend accounting (VERDICT r2: the image cache used to
    bypass ModelManager entirely)."""

    name: str
    config: ModelConfig
    pipeline: Any
    loaded_at: float = dataclasses.field(default_factory=time.monotonic)
    last_used: float = dataclasses.field(default_factory=time.monotonic)
    _inflight: int = 0
    _lock: threading.Lock = dataclasses.field(default_factory=threading.Lock)
    generated: int = 0

    def touch(self) -> None:
        self.last_used = time.monotonic()

    @property
    def busy(self) -> bool:
        with self._lock:
            return self._inflight > 0

    def in_use(self):
        """Context manager holding the busy flag across a multi-image
        request so eviction sweeps can't null the pipeline between items."""
        import contextlib

        @contextlib.contextmanager
        def cm():
            with self._lock:
                self._inflight += 1
            try:
                yield self
            finally:
                with self._lock:
                    self._inflight -= 1

        return cm()

    def alive(self) -> bool:
        return self.pipeline is not None

    def close(self) -> None:
        self.pipeline = None  # frees params (HBM) once consumers drop refs

    def engine_metrics(self) -> dict:
        return {"type": "image", "images_generated": self.generated}

    def generate(self, *args, **kwargs):
        """Run the pipeline with busy accounting (watchdog-visible).

        Snapshots the pipeline ref first: a concurrent eviction nulls
        self.pipeline, but an in-flight request keeps generating against
        its snapshot (params stay alive until the last ref drops)."""
        pipe = self.pipeline
        if pipe is None:
            raise RuntimeError(f"image model {self.name} was evicted")
        with self._lock:
            self._inflight += 1
        try:
            out = pipe.generate(*args, **kwargs)
        finally:
            with self._lock:
                self._inflight -= 1
        self.generated += 1
        self.touch()
        return out


@dataclasses.dataclass
class RerankServingModel:
    """A loaded cross-encoder under the same lifecycle management as LLMs
    (watchdog, eviction, /backend/monitor) — parity: the rerankers backend
    process, /root/reference/backend/python/rerankers/backend.py."""

    name: str
    config: ModelConfig
    encoder: Any                      # models.reranker.CrossEncoder
    loaded_at: float = dataclasses.field(default_factory=time.monotonic)
    last_used: float = dataclasses.field(default_factory=time.monotonic)
    _inflight: int = 0
    _lock: threading.Lock = dataclasses.field(default_factory=threading.Lock)
    scored: int = 0

    def touch(self) -> None:
        self.last_used = time.monotonic()

    @property
    def busy(self) -> bool:
        with self._lock:
            return self._inflight > 0

    def alive(self) -> bool:
        return self.encoder is not None

    def close(self) -> None:
        self.encoder = None  # frees params once in-flight scores finish

    def engine_metrics(self) -> dict:
        return {"type": "rerank", "pairs_scored": self.scored}

    def score(self, query: str, documents: list[str]):
        """(scores, total_tokens). Token counts come from the same encoder
        snapshot as the scores — the shared self.encoder may be nulled by
        an eviction the moment the in-flight count drops."""
        enc = self.encoder  # snapshot: eviction mid-request keeps params
        if enc is None:
            raise RuntimeError(f"reranker {self.name} was evicted")
        with self._lock:
            self._inflight += 1
        try:
            out, total_tokens = enc.score_with_usage(query, documents)
        finally:
            with self._lock:
                self._inflight -= 1
        self.scored += len(documents)
        self.touch()
        return out, total_tokens


@dataclasses.dataclass
class EmbeddingServingModel:
    """A loaded sentence encoder under lifecycle management (parity: the
    sentencetransformers backend process,
    /root/reference/backend/python/sentencetransformers/backend.py)."""

    name: str
    config: ModelConfig
    encoder: Any                      # models.reranker.SentenceEncoder
    loaded_at: float = dataclasses.field(default_factory=time.monotonic)
    last_used: float = dataclasses.field(default_factory=time.monotonic)
    _inflight: int = 0
    _lock: threading.Lock = dataclasses.field(default_factory=threading.Lock)
    embedded: int = 0

    def touch(self) -> None:
        self.last_used = time.monotonic()

    @property
    def busy(self) -> bool:
        with self._lock:
            return self._inflight > 0

    def alive(self) -> bool:
        return self.encoder is not None

    def close(self) -> None:
        self.encoder = None

    def engine_metrics(self) -> dict:
        return {"type": "embeddings", "texts_embedded": self.embedded}

    def embed(self, texts: list[str]):
        """(vectors, total_tokens) — token counts come from the same
        encoder snapshot as the vectors (eviction can null self.encoder
        the moment the in-flight count drops)."""
        enc = self.encoder  # snapshot vs concurrent eviction
        if enc is None:
            raise RuntimeError(f"embedder {self.name} was evicted")
        with self._lock:
            self._inflight += 1
        try:
            out, total = enc.embed_with_usage(texts)
        finally:
            with self._lock:
                self._inflight -= 1
        self.embedded += len(texts)
        self.touch()
        return out, total


@dataclasses.dataclass
class AudioServingModel:
    """A loaded whisper or VITS model under lifecycle management —
    idle/busy watchdog, eviction, /backend/monitor visibility (the same
    contract the image pipelines got in round 2; the audio caches used to
    live in private AppState dicts outside the manager)."""

    name: str
    config: ModelConfig
    model: Any                        # WhisperModel | VitsTTS
    kind: str = "whisper"             # "whisper" | "vits"
    loaded_at: float = dataclasses.field(default_factory=time.monotonic)
    last_used: float = dataclasses.field(default_factory=time.monotonic)
    _inflight: int = 0
    _lock: threading.Lock = dataclasses.field(default_factory=threading.Lock)
    served: int = 0

    def touch(self) -> None:
        self.last_used = time.monotonic()

    @property
    def busy(self) -> bool:
        with self._lock:
            return self._inflight > 0

    def alive(self) -> bool:
        return self.model is not None

    def close(self) -> None:
        self.model = None

    def engine_metrics(self) -> dict:
        return {"type": self.kind, "requests_served": self.served}

    def run(self, fn_name: str, *args, **kwargs):
        """Invoke a model method with busy accounting (watchdog-visible);
        snapshots the model ref so a concurrent eviction can't null it
        mid-request."""
        model = self.model
        if model is None:
            raise RuntimeError(f"{self.kind} model {self.name} was evicted")
        with self._lock:
            self._inflight += 1
        try:
            out = getattr(model, fn_name)(*args, **kwargs)
        finally:
            with self._lock:
                self._inflight -= 1
        self.served += 1
        self.touch()
        return out


def _auto_mesh(cfg, num_slots: int):
    """The no-flag meshed-serving default (ROADMAP item 3): on a single
    host with >1 visible accelerator, build a dp×tp mesh with tp as wide
    as the q-head count allows (``model=all`` when it divides). CPU stays
    single-device — tier-1 semantics are byte-identical without a mesh —
    unless ``LOCALAI_MESH_AUTO=1`` forces the auto path (CPU-mesh smoke
    tests); ``LOCALAI_MESH_AUTO=0`` disables it on accelerators. Explicit
    topology (``--mesh`` / ``LOCALAI_MESH`` / sharding config) never
    reaches this function. Returns None when a mesh buys nothing."""
    import jax

    from localai_tpu.parallel.mesh import (MeshPlan, build_mesh,
                                           default_tensor_parallel)

    auto = os.environ.get("LOCALAI_MESH_AUTO", "")
    if auto == "0":
        return None
    devs = jax.devices()
    if len(devs) < 2 or (devs[0].platform == "cpu" and auto != "1"):
        return None
    tp = default_tensor_parallel(len(devs), cfg.num_heads)
    if tp < 2:
        log.warning(
            "auto mesh: %d devices visible but num_heads=%d admits no "
            "tensor-parallel split; serving single-device",
            len(devs), cfg.num_heads)
        return None
    dp = len(devs) // tp
    if dp > 1 and num_slots % dp:
        # the decode state shards slots over 'data'; an indivisible slot
        # count keeps TP only (on tp devices) rather than failing the load
        log.warning(
            "auto mesh: max_slots=%d not divisible by data=%d; using "
            "model=%d on %d of %d devices", num_slots, dp, tp, tp,
            len(devs))
        return build_mesh(MeshPlan(model=tp), devices=devs[:tp])
    return build_mesh(MeshPlan(data=dp, model=tp))


def build_runner(mcfg: ModelConfig, app: AppConfig) -> tuple[Any, ModelRunner]:
    """Config → (resolved model, live ModelRunner): weights, mesh,
    shardings. Shared by the serving path and multi-host followers — a
    follower MUST construct a bit-identical runner (same config, same
    seed) so replayed commands keep every host in the same program."""
    from localai_tpu.models.registry import resolve_config, resolve_model
    from localai_tpu.parallel.sharding import ParamPlacement

    eng = mcfg.engine
    shard = mcfg.sharding
    mesh = None
    explicit_mesh = False
    want_tp = max(1, shard.tensor_parallel_size)
    want_sp = max(1, shard.sequence_parallel_size)
    want_ep = max(1, shard.expert_parallel_size)
    want_pp = max(1, shard.pipeline_parallel_size)
    want_dp = shard.data_parallel_size  # 0 = auto
    if (want_tp > 1 or want_sp > 1 or want_ep > 1 or want_pp > 1
            or want_dp not in (0, 1) or app.mesh_shape):
        from localai_tpu.parallel.mesh import MeshPlan, build_mesh

        explicit_mesh = True
        if app.mesh_shape:
            mesh = build_mesh(MeshPlan(**app.mesh_shape))
        elif want_pp > 1:
            import jax

            if want_tp > 1 or want_sp > 1 or want_ep > 1 \
                    or want_dp not in (0, 1):
                # fail loudly: silently dropping the other knobs would
                # serve an unsharded layout the user didn't configure
                raise ValueError(
                    "pipeline_parallel_size composes with no other "
                    "sharding axis yet; unset tensor/sequence/expert/"
                    "data_parallel_size")
            # pipeline capacity mode runs the 'pipe' axis alone — claim
            # exactly pp devices
            mesh = build_mesh(MeshPlan(pipe=want_pp),
                              devices=jax.devices()[:want_pp])
        else:
            import jax

            nd = len(jax.devices())
            dp = want_dp or max(1, nd // (want_tp * want_sp * want_ep))
            mesh = build_mesh(
                MeshPlan(data=dp, seq=want_sp, expert=want_ep,
                         model=want_tp)
            )

    # the shape first, the weights last: the mesh and every leaf's placement
    # are fixed before anything is loaded, so each leaf is created in its
    # served form on the devices that will hold it
    ref = mcfg.model or mcfg.name
    cfg = resolve_config(ref, app.model_path, eng.dtype)
    if mesh is None and not explicit_mesh:
        # meshed serving is the default hot path whenever >1 accelerator
        # is visible (pjit tensor-parallel, paged pool sharded over
        # 'model'); modes whose runners assume single-device layouts keep
        # it off: multi-host command mirroring builds its own topology
        # and self-extend forces the unroped single-row cache.
        # Speculative decoding composes now — the draft runner shares
        # the target's mesh (localai_tpu.spec.ModelDrafter)
        # (nor a model whose family serves no mesh: models.llama ``UNSERVED``)
        if not (app.mirror_port or eng.grp_attn_n > 1
                or mdl.unserved(cfg, "a device mesh")):
            mesh = _auto_mesh(cfg, eng.max_slots)
            if mesh is not None:
                log.info("auto mesh for %s: %s", mcfg.name,
                         dict(mesh.shape))
    model = resolve_model(
        ref,
        model_path=app.model_path,
        dtype=eng.dtype,
        quantization=eng.quantization,
        placement=ParamPlacement(cfg, mesh),
    )
    params = model.params
    ctx = mcfg.context_size or app.context_size
    # self-extend lifts the trained-context ceiling by the group factor
    # (llama.cpp: n_ctx >= n_ctx_train * ga_n, grpc-server.cpp:535)
    ctx = min(ctx, model.cfg.max_position_embeddings * max(eng.grp_attn_n, 1))
    # paged KV (block pool + chunked prefill): the serving default for
    # single-device AND meshed engines alike (the pool shards its kv-head
    # axis over 'model'; the table mirror rides 'data'). Speculative
    # decoding runs block-native on this layout (localai_tpu.spec), so
    # draft-model engines are paged too; only multi-host mirroring still
    # drives the contiguous layout, and the runner itself gates off
    # pipeline-parallel/self-extend. Explicit per-model config
    # (engine.kv_paged) wins; otherwise the compatibility decision applies.
    paged = eng.kv_paged
    if paged is None:
        paged = ((mesh is None or mesh.shape.get("pipe", 1) == 1)
                 and eng.grp_attn_n <= 1
                 and not app.mirror_port)
    runner = ModelRunner(
        model.cfg,
        params,
        num_slots=eng.max_slots,
        max_ctx=ctx,
        prefill_buckets=eng.prefill_buckets,
        kv_dtype=eng.kv_dtype,
        rope_freq_base=mcfg.rope_freq_base,
        rope_freq_scale=mcfg.rope_freq_scale,
        seed=mcfg.seed or 0,
        mesh=mesh,
        sp_threshold=eng.sp_prefill_threshold,
        attn_impl=eng.attn_impl,
        ga_n=eng.grp_attn_n,
        ga_w=eng.grp_attn_w,
        paged=paged,
        kv_block_tokens=eng.kv_block_tokens,
        kv_num_blocks=eng.kv_num_blocks,
        prefill_chunk=eng.prefill_chunk,
    )
    return model, runner


def build_serving_model(mcfg: ModelConfig, app: AppConfig) -> ServingModel:
    """Config → live engine: resolve weights, build mesh/shardings, runner,
    scheduler, tokenizer, templates. Shared by the in-process manager and
    the gRPC worker tier (localai_tpu.worker.server), so both load paths
    behave identically."""
    t0 = time.monotonic()
    eng = mcfg.engine
    model, runner = build_runner(mcfg, app)
    mesh = runner.mesh
    ctx = runner.max_ctx
    if app.mirror_port:
        # multi-host leader: every engine call re-broadcasts to the
        # follower group before running locally (parallel/multihost.py)
        from localai_tpu.parallel.multihost import (
            MirroredRunner,
            get_leader,
        )

        leader = get_leader(app.mirror_port, app.mirror_followers,
                            token=app.peer_token)
        if app.mirror_followers:
            leader.wait_for(app.mirror_followers)
        runner = MirroredRunner(runner, leader, mcfg.name)
    # block-native speculative decoding (localai_tpu.spec): the default
    # for paged engines — the self-drafting n-gram lane needs no second
    # model, so single-model deployments get speculation out of the box;
    # a configured draft_model upgrades the drafter to a co-located
    # draft runner sharing the mesh. Contiguous engines opt in via
    # draft_model (the legacy shape). Knobs: engine.spec/spec_drafter/
    # spec_gamma, LOCALAI_SPEC=0 kill switch, LOCALAI_SPEC_DRAFTER /
    # LOCALAI_SPEC_GAMMA / LOCALAI_SPEC_NGRAM_MAX env overrides.
    spec = None
    spec_want = eng.spec
    if spec_want is None:
        spec_want = ((getattr(runner, "paged", False)
                      or bool(eng.draft_model))
                     and os.environ.get("LOCALAI_SPEC", "") != "0")
    if spec_want and app.mirror_port:
        log.warning(
            "%s: speculative decoding is not supported with multi-host "
            "command mirroring yet; serving without it", mcfg.name
        )
    elif spec_want and eng.grp_attn_n > 1:
        log.warning(
            "%s: speculative decoding is not supported with self-extend "
            "(grp_attn_n>1); serving without it", mcfg.name,
        )
    elif spec_want and mdl.unserved(model.cfg, "speculative decoding"):
        if eng.spec:    # asked for by name; the default just stays off
            log.warning("%s: %s; serving without it", mcfg.name,
                        mdl.refusal(model.cfg, "speculative decoding"))
    elif spec_want and getattr(runner, "pp_enabled", False):
        log.warning(
            "%s: speculative decoding is not supported with pipeline "
            "parallelism; serving without it", mcfg.name,
        )
    elif spec_want:
        from localai_tpu.spec import build_spec_engine

        drafter = (os.environ.get("LOCALAI_SPEC_DRAFTER", "")
                   or eng.spec_drafter or "auto")
        if drafter == "model" and not eng.draft_model:
            log.warning(
                "%s: spec_drafter=model but no draft_model configured; "
                "using the n-gram self-drafter", mcfg.name)
            drafter = "ngram"
        gamma = eng.spec_gamma
        if gamma is None and eng.draft_model:
            gamma = max(1, eng.n_draft)
        spec = build_spec_engine(
            runner,
            drafter=drafter,
            draft_ref=eng.draft_model,
            model_path=app.model_path,
            gamma=gamma,
            dtype=eng.dtype,
        )
        log.info(
            "%s: speculative decoding on (%s drafter, gamma=%d, %s KV)",
            mcfg.name, spec.drafter.name, spec.gamma,
            "paged" if spec.paged else "contiguous",
        )
    prompt_cache = None
    if mcfg.prompt_cache_path and app.mirror_port:
        log.warning(
            "%s: prompt_cache_path is not supported with multi-host command "
            "mirroring (KV loads would desync followers); ignoring", mcfg.name
        )
    elif mcfg.prompt_cache_path and mdl.unserved(
            model.cfg, "the prompt cache's import"):
        log.warning("%s: %s; ignoring prompt_cache_path", mcfg.name,
                    mdl.refusal(model.cfg, "the prompt cache's import"))
    elif mcfg.prompt_cache_path:
        from pathlib import Path

        from localai_tpu.engine.promptcache import PromptKVCache

        pc_path = Path(mcfg.prompt_cache_path)
        if not pc_path.is_absolute():
            pc_path = Path(app.model_path) / pc_path
        prompt_cache = PromptKVCache(
            pc_path, read_only=mcfg.prompt_cache_ro,
            min_prefix=runner.prefix_reuse_min,
        )
        log.info(
            "%s: prompt KV cache at %s (%s%s)", mcfg.name, pc_path,
            "ro, " if mcfg.prompt_cache_ro else "",
            "prompt+generation" if mcfg.prompt_cache_all else "prompt only",
        )
    from localai_tpu.obs import EngineTelemetry

    scheduler = Scheduler(
        runner,
        model.tokenizer,
        default_max_tokens=mcfg.parameters.max_tokens or 2048,
        multi_step=eng.decode_steps_per_dispatch,
        pipeline_depth=eng.pipeline_depth,
        stream_latency_target=eng.stream_latency_ms / 1000.0,
        spec=spec,
        prompt_cache=prompt_cache,
        prompt_cache_all=mcfg.prompt_cache_all,
        telemetry=EngineTelemetry(model=mcfg.name),
    )
    # self-healing supervisor (localai_tpu.faults): a watchdog stall on
    # this engine's channel escalates trace → drain-with-5xx → runner
    # re-init → probe dispatch, bounded+backed-off, then marks the model
    # failed (the dead-engine reload path here owns further recovery).
    # SpecEngine engines rebuild too (drafter.reinit rides the runner
    # re-init); only legacy spec objects without supports_rebuild are
    # excluded. LOCALAI_SELF_HEAL=0 disables. (multi-host mirrored
    # runners are also excluded: a leader-local rebuild would desync the
    # follower group's replayed command stream)
    if ((spec is None or getattr(spec, "supports_rebuild", False))
            and not app.mirror_port
            and os.environ.get("LOCALAI_SELF_HEAL", "1") != "0"):
        from localai_tpu.faults import EngineSupervisor

        EngineSupervisor(scheduler)
    # vision tower: explicit mmproj ref, or auto from a llava checkpoint dir
    vision = None
    vt_ref = mcfg.mmproj or (
        str(model.model_dir) if model.hf_type == "llava" else None
    )
    if vt_ref:
        from localai_tpu.models.vision import resolve_vision_tower

        vision = resolve_vision_tower(
            vt_ref,
            projection_dim=model.cfg.hidden_size,
            model_path=app.model_path,
            seed=mcfg.seed or 0,
        )
        log.info("loaded vision tower %s: %d patches -> D=%d",
                 vt_ref, vision.n_patches, model.cfg.hidden_size)
    log.info(
        "loaded model %s (%s) in %.1fs: slots=%d ctx=%d mesh=%s",
        mcfg.name, mcfg.model, time.monotonic() - t0,
        eng.max_slots, ctx, mesh.shape if mesh else None,
    )
    return ServingModel(
        name=mcfg.name,
        config=mcfg,
        runner=runner,
        scheduler=scheduler,
        tokenizer=model.tokenizer,
        templates=TemplateCache(app.model_path),
        vision=vision,
        image_token_id=(
            mcfg.image_token_id if mcfg.image_token_id is not None
            else (model.image_token_id or 0)
        ),
    )


class ModelManager:
    """Thread-safe registry of loaded models (parity: ModelLoader map +
    mutex, loader.go:22-40)."""

    def __init__(
        self,
        app_config: Optional[AppConfig] = None,
        loader: Optional[ConfigLoader] = None,
    ):
        self.app = app_config or AppConfig()
        self.loader = loader or ConfigLoader(self.app.model_path)
        self._models: dict[str, Any] = {}   # ServingModel | WorkerServingModel
                                            # | ImageServingModel
        self._load_locks: dict[str, threading.Lock] = {}
        self._reranker_detect: dict[tuple, bool] = {}
        self._lock = threading.RLock()
        self._pool = None                   # WorkerPool, created on demand
        self._watchdog: Optional[_Watchdog] = None
        if self.app.watchdog_idle or self.app.watchdog_busy:
            self._watchdog = _Watchdog(self)
            self._watchdog.start()

    def pool(self):
        """Lazy worker-process pool (spawn tier)."""
        with self._lock:
            if self._pool is None:
                from localai_tpu.worker.process import WorkerPool

                self._pool = WorkerPool()
            return self._pool

    # -- lookup / load ----------------------------------------------------

    def loaded_names(self) -> list[str]:
        with self._lock:
            return sorted(self._models)

    def loaded_snapshot(self) -> dict[str, Any]:
        """Point-in-time view of the loaded models (never triggers a load)
        — the /debug/devices HBM census walks in-process runners through
        this."""
        with self._lock:
            return dict(self._models)

    def is_loaded(self, name: str) -> bool:
        with self._lock:
            return name in self._models

    def get(self, name: str) -> ServingModel:
        """Idempotent load-or-get (parity: ModelLoader.LoadModel +
        CheckIsLoaded health path, loader.go:96-206). A dead engine
        (in-process thread or worker process) → reload/respawn."""
        return self._get_typed(name, self._load, kind="llm")

    def get_image(self, name: str) -> ImageServingModel:
        """Load-or-get a diffusion pipeline under lifecycle management
        (watchdog, eviction, monitor — same contract as LLMs)."""
        return self._get_typed(name, self._load_image, kind="image")

    def get_reranker(self, name: str) -> RerankServingModel:
        """Load-or-get a cross-encoder reranker (same lifecycle contract)."""
        return self._get_typed(name, self._load_reranker, kind="rerank")

    def get_embedder(self, name: str) -> EmbeddingServingModel:
        """Load-or-get a bert-class sentence encoder (same contract)."""
        return self._get_typed(name, self._load_embedder, kind="embed")

    def get_whisper(self, name: str) -> AudioServingModel:
        """Load-or-get a whisper STT model (same lifecycle contract)."""
        return self._get_typed(name, self._load_whisper, kind="whisper")

    def get_vits(self, name: str) -> AudioServingModel:
        """Load-or-get a VITS voice (same lifecycle contract)."""
        return self._get_typed(name, self._load_vits, kind="vits")

    def is_embedder(self, mcfg: ModelConfig) -> bool:
        """Route /v1/embeddings to the sentence encoder for bert-class
        checkpoints (backend: bert-embeddings, set explicitly or by
        autodetection at config load)."""
        return mcfg.backend in ("bert-embeddings", "sentencetransformers")

    def is_reranker(self, mcfg: ModelConfig) -> bool:
        """Route a model to the cross-encoder path: explicit
        ``backend: reranker`` or a bert-class checkpoint (auto-detect,
        guesser parity). The filesystem sniff is cached — this runs on
        every /v1/rerank request, on the event loop."""
        if mcfg.backend == "reranker":
            return True
        if mcfg.backend:
            return False
        key = (mcfg.name, mcfg.model)
        now = time.monotonic()
        with self._lock:
            hit = self._reranker_detect.get(key)
        # positive hits are stable (a bert checkpoint stays bert);
        # negatives expire so installing the checkpoint later is picked
        # up without a restart
        if hit is not None:
            found, at = hit
            if found or now - at < 30.0:
                return found
        from localai_tpu.models.reranker import is_reranker_checkpoint

        found = is_reranker_checkpoint(
            mcfg.model or mcfg.name, self.app.model_path
        )
        with self._lock:
            self._reranker_detect[key] = (found, now)
        return found

    def _get_typed(self, name: str, load, *, kind: str) -> Any:
        # fast path + cache maintenance under the global lock; the load
        # itself (worker spawn / weight read, tens of seconds) runs under a
        # per-name lock so one cold model never stalls warm lookups
        cached = self._check_cached(name, kind)
        if cached is not None:
            return cached
        with self._lock:
            lk = self._load_locks.setdefault(name, threading.Lock())
        with lk:
            cached = self._check_cached(name, kind)  # raced loader won?
            if cached is not None:
                return cached
            mcfg = self.loader.get(name)
            if mcfg is None:
                raise KeyError(f"no configuration for model {name!r}")
            if self.app.single_active_backend:
                with self._lock:
                    for other in list(self._models):
                        if not self._models[other].busy:
                            self._evict_locked(other)
            sm = load(mcfg)
            with self._lock:
                self._models[name] = sm
            return sm

    def _check_cached(self, name: str, kind: str) -> Optional[Any]:
        """Return the cached model if it is the right kind and alive;
        evict (and return None) otherwise."""
        with self._lock:
            sm = self._models.get(name)
            if sm is None:
                return None
            cached_kind = (
                "image" if isinstance(sm, ImageServingModel)
                else "rerank" if isinstance(sm, RerankServingModel)
                else "embed" if isinstance(sm, EmbeddingServingModel)
                else sm.kind if isinstance(sm, AudioServingModel)
                else "llm"
            )
            if cached_kind != kind:
                # one name, two modalities: latest request wins (same
                # semantics as single_active_backend), unless in use
                if sm.busy:
                    raise RuntimeError(
                        f"model {name!r} is busy serving as {cached_kind}"
                    )
                log.info("model %s switching modality; reloading", name)
                self._evict_locked(name)
                return None
            if not sm.alive():
                log.warning("model %s engine died; reloading", name)
                self._evict_locked(name)
                return None
            sm.touch()
            return sm

    def _load(self, mcfg: ModelConfig) -> Any:
        # fleet tier: with --fleet-replicas N (N>1) an LLM is served from
        # N data-parallel engine replicas behind one facade (cache-aware
        # routing, failover, optional prefill/decode disaggregation —
        # localai_tpu.fleet). Modality backends, externally managed
        # workers, and embeddings/rerank-capable models keep their
        # single-engine paths: the fleet facade only speaks the streaming
        # generation protocol, and /v1/embeddings//v1/rerank need the
        # in-process runner.embed surface.
        ext = self.app.external_backends.get(mcfg.name)
        # remote hosts alone are enough to go fleet-tier: a box with one
        # (or zero) local engines can still front a pod of adopted peers
        if ((self.app.fleet_replicas > 1 or self.app.fleet_hosts)
                and not ext and mcfg.backend in ("", "worker")):
            from localai_tpu.config.model_config import Usecase

            if (mcfg.has_usecase(Usecase.EMBEDDINGS)
                    or mcfg.has_usecase(Usecase.RERANK)):
                log.warning(
                    "model %s: embeddings/rerank-capable models are not "
                    "fleet-served; keeping the single-engine path",
                    mcfg.name)
            else:
                return self._load_fleet(mcfg)
        # worker-tier routing: `backend: worker` spawns a gRPC worker
        # process (crash isolation, initializers.go:271-407);
        # external_backends route to an externally managed worker address
        if ext or mcfg.backend == "worker":
            from localai_tpu.worker.serving import WorkerServingModel

            if not ext:
                self._check_one_process_per_chip(mcfg, spawning_worker=True)
            return WorkerServingModel(
                mcfg, self.app, self.pool(), external_address=ext or None
            )
        if mcfg.backend in ("huggingface", "langchain-huggingface"):
            from localai_tpu.models.hf_api import HFApiServingModel

            return HFApiServingModel(mcfg, self.app)
        if mcfg.backend in ("mamba", "rwkv"):
            from localai_tpu.models.mamba_serving import MambaServingModel

            return MambaServingModel(mcfg, self.app)
        self._check_one_process_per_chip(mcfg, spawning_worker=False)
        try:
            return build_serving_model(mcfg, self.app)
        except Exception:
            # greedy-chain tail: name the engine the checkpoint actually
            # belongs to instead of a cryptic tensor-mapping error
            # (parity: initializers.go falling through its backend list)
            from localai_tpu.models.detect import detect_backend

            family = detect_backend(
                mcfg.model or mcfg.name, self.app.model_path
            )
            if family:
                raise RuntimeError(
                    f"model {mcfg.name!r} is a {family} checkpoint, not "
                    f"an LLM — set `backend: {family}` (or use the "
                    f"matching endpoint)"
                ) from None
            raise

    def _check_one_process_per_chip(self, mcfg: ModelConfig, *,
                                    spawning_worker: bool) -> None:
        """A TPU chip belongs to one process at a time: a process that
        has initialized JAX on the TPU holds every chip it can see, and a
        second process that needs one then fails or hangs inside libtpu
        with nothing in the log. So an in-process engine and a spawned
        worker cannot share a host's chips — refuse the second load here,
        where the reason is known. (A server started with --platform cpu
        holds no chip; a worker whose ``worker_env`` puts it on the CPU or
        pins its own chips is the operator's explicit layout.)"""
        own = (self.app.platform
               or os.environ.get("JAX_PLATFORMS", "")).split(",")[0]
        if own == "cpu":
            return
        with self._lock:
            loaded = list(self._models.values())
        if spawning_worker:
            wenv = self.app.worker_env or {}
            if (wenv.get("JAX_PLATFORMS", "").split(",")[0] == "cpu"
                    or "TPU_VISIBLE_CHIPS" in wenv):
                return
            holders = [sm.name for sm in loaded
                       if isinstance(sm, ServingModel)]
            if not holders:
                return
            import jax

            if jax.default_backend() != "tpu":  # initialized: holders exist
                return
            raise RuntimeError(
                f"model {mcfg.name!r} (backend: worker) needs a TPU chip, "
                f"but this server process holds the host's chips for its "
                f"in-process model(s) {holders}; a chip belongs to one "
                f"process. Serve every model in-process, or start the "
                f"server with --platform cpu and serve them all from "
                f"workers (--fleet-device-pinning partitions the chips)")
        def spawned_on_tpu(sm) -> bool:
            # a spawned worker model, or a fleet with spawned replicas
            # (adopted remotes live on other hosts)
            if getattr(sm, "external_address", None) is not None:
                return False
            pool = getattr(sm, "pool", None)
            members = ([r for r in pool.members() if r.respawnable]
                       if hasattr(pool, "members") else [sm])
            return any((getattr(m, "device", None) or {}).get("platform")
                       == "tpu" for m in members)

        holders = [sm.name for sm in loaded if spawned_on_tpu(sm)]
        if holders:
            raise RuntimeError(
                f"model {mcfg.name!r} would load in this server process, "
                f"but spawned worker(s) for {holders} hold this host's TPU "
                f"chips; a chip belongs to one process. Give this model "
                f"`backend: worker` too and start the server with "
                f"--platform cpu")

    def _load_fleet(self, mcfg: ModelConfig) -> Any:
        """Build a FleetServingModel: N engine replicas behind one facade
        (localai_tpu.fleet). fleet_backend picks the replica shape —
        ``worker`` (default) spawns one gRPC worker process per replica
        (crash isolation; pin devices per replica via worker_env),
        ``inprocess`` builds N engines in this process (CPU tests, CI
        smoke, single-host experiments). On top of the local replicas,
        every ``host:port`` in app.fleet_hosts is adopted as a
        RemoteReplica (cross-host serving; the facade reads the list off
        the app config), and more peers can join at runtime through
        POST /federated/register."""
        from localai_tpu.fleet import FleetServingModel
        from localai_tpu.fleet.replica import InProcessReplica, WorkerReplica

        app = self.app
        # hot-swap indirection: the factory reads its model config from
        # this holder at SPAWN time, so rebinding it (fleet.autoscale
        # density.hot_swap) makes every later runtime spawn boot the new
        # checkpoint while the running generation keeps its own
        cfg_ref = {"mcfg": mcfg}
        if app.fleet_backend == "inprocess":
            def factory(rid: str, role: str):
                # each replica engine gets its own identity: under the
                # shared name its telemetry/SLO events would double-count
                # every request the fleet tier already records (worker
                # replicas are naturally separate — their own process,
                # their own registry)
                live = cfg_ref["mcfg"]
                rcfg = live.model_copy(update={
                    "name": rid, "model": live.model or live.name})
                return InProcessReplica(
                    rid, role, lambda: build_serving_model(rcfg, app))
        else:
            total = app.fleet_replicas + app.fleet_prefill_replicas

            def factory(rid: str, role: str):
                env = dict(app.worker_env or {})
                if app.fleet_device_pinning:
                    # rid suffixes are rN (decode) / pN (prefill) in pool
                    # construction order; prefill replicas take the slices
                    # after the decode block so all of them partition one
                    # host without overlap (fleet.pinning)
                    from localai_tpu.fleet.pinning import pinned_worker_env

                    kind, num = rid.rsplit("/", 1)[-1][0], rid.rsplit("/", 1)[-1][1:]
                    idx = int(num) + (app.fleet_replicas
                                      if kind == "p" else 0)
                    # runtime spawns (autoscale/hot swap) mint ever-higher
                    # indexes; fold them back into the boot partition —
                    # the replica they replace has retired its slice
                    env = pinned_worker_env(app.worker_env, idx % total,
                                            total)
                return WorkerReplica(rid, role, cfg_ref["mcfg"], app,
                                     env=env or None)
        fm = FleetServingModel(
            mcfg, app, factory,
            replicas=app.fleet_replicas,
            prefill_replicas=app.fleet_prefill_replicas,
        )
        fm.cfg_ref = cfg_ref
        if app.autoscale:
            from localai_tpu.fleet.autoscale import AutoscaleController

            fm.autoscaler = AutoscaleController(fm, manager=self)
            fm.autoscaler.start()
        return fm

    def _load_image(self, mcfg: ModelConfig) -> ImageServingModel:
        from localai_tpu.image import resolve_image_model

        kwargs = {}
        d = mcfg.diffusers
        if d.scheduler_type:
            kwargs["default_scheduler"] = d.scheduler_type
        if d.steps:
            kwargs["default_steps"] = d.steps
        if d.cfg_scale is not None:
            kwargs["default_cfg_scale"] = d.cfg_scale
        if d.clip_skip:
            kwargs["clip_skip"] = d.clip_skip
        if mcfg.lora_adapter:
            from pathlib import Path

            lp = Path(mcfg.lora_adapter)
            if not lp.is_absolute():
                # relative adapters resolve against the models dir
                # (parity: backend.py:300-305)
                lp = Path(self.app.model_path) / lp
            kwargs["lora_adapter"] = str(lp)
            kwargs["lora_scale"] = mcfg.lora_scale
        t0 = time.monotonic()
        pipe = resolve_image_model(
            mcfg.model or mcfg.name, model_path=self.app.model_path, **kwargs
        )
        if d.control_net:
            pipe.attach_controlnet(d.control_net, self.app.model_path)
        log.info("loaded image model %s in %.1fs", mcfg.name,
                 time.monotonic() - t0)
        return ImageServingModel(name=mcfg.name, config=mcfg, pipeline=pipe)

    def _load_embedder(self, mcfg: ModelConfig) -> EmbeddingServingModel:
        from localai_tpu.models.reranker import resolve_sentence_encoder

        t0 = time.monotonic()
        enc = resolve_sentence_encoder(
            mcfg.model or mcfg.name, model_path=self.app.model_path,
            seed=mcfg.seed or 0,
        )
        log.info("loaded sentence encoder %s in %.1fs", mcfg.name,
                 time.monotonic() - t0)
        return EmbeddingServingModel(name=mcfg.name, config=mcfg,
                                     encoder=enc)

    def _load_whisper(self, mcfg: ModelConfig) -> AudioServingModel:
        from pathlib import Path

        from localai_tpu.models import whisper as wh

        ref = mcfg.model or mcfg.name
        t0 = time.monotonic()
        if ref.startswith("debug:"):
            model = wh.debug_model()
        else:
            for cand in (Path(ref), Path(self.app.model_path) / ref):
                if (cand / "config.json").exists():
                    model = wh.load_hf_whisper(cand)
                    break
            else:
                raise FileNotFoundError(f"whisper model {ref!r} not found")
        log.info("loaded whisper %s in %.1fs", mcfg.name,
                 time.monotonic() - t0)
        return AudioServingModel(name=mcfg.name, config=mcfg, model=model,
                                 kind="whisper")

    def _load_vits(self, mcfg: ModelConfig) -> AudioServingModel:
        from pathlib import Path

        from localai_tpu.audio.vits import load_hf_vits

        ref = mcfg.model or mcfg.name
        t0 = time.monotonic()
        for cand in (Path(ref), Path(self.app.model_path) / ref):
            if (cand / "config.json").exists():
                model = load_hf_vits(cand)
                break
        else:
            raise FileNotFoundError(f"vits model {ref!r} not found")
        log.info("loaded vits voice %s in %.1fs", mcfg.name,
                 time.monotonic() - t0)
        return AudioServingModel(name=mcfg.name, config=mcfg, model=model,
                                 kind="vits")

    def _load_reranker(self, mcfg: ModelConfig) -> RerankServingModel:
        from localai_tpu.models.reranker import resolve_reranker

        t0 = time.monotonic()
        enc = resolve_reranker(
            mcfg.model or mcfg.name, model_path=self.app.model_path,
            seed=mcfg.seed or 0,
        )
        log.info("loaded reranker %s in %.1fs", mcfg.name,
                 time.monotonic() - t0)
        return RerankServingModel(name=mcfg.name, config=mcfg, encoder=enc)

    # -- shutdown ---------------------------------------------------------

    def _evict_locked(self, name: str) -> None:  # jaxlint: guarded-by(_lock)
        sm = self._models.pop(name, None)
        if sm is not None:
            sm.close()

    def shutdown_model(self, name: str, *, force: bool = False,
                       wait: float = 30.0) -> bool:
        """Graceful single-model shutdown: wait for in-flight work unless
        forced (parity: ShutdownModel wait loop, loader.go:143-168)."""
        deadline = time.monotonic() + wait
        while not force:
            with self._lock:
                sm = self._models.get(name)
                if sm is None:
                    return False
                if not sm.busy:
                    break
            if time.monotonic() > deadline:
                log.warning("%s still busy after %.0fs; forcing", name, wait)
                break
            time.sleep(0.1)
        with self._lock:
            if name not in self._models:
                return False
            self._evict_locked(name)
            return True

    def shutdown_all(self) -> None:
        if self._watchdog is not None:
            self._watchdog.stop()
        with self._lock:
            for name in list(self._models):
                self._evict_locked(name)
            if self._pool is not None:
                self._pool.shutdown_all()

    # -- observability -----------------------------------------------------

    def metrics(self) -> dict:
        # engine_metrics() runs OUTSIDE the manager lock: on fleet/worker
        # models it pulls stats RPCs (bounded, but seconds when a replica
        # is wedged) and holding _lock across those would stall every
        # request's model resolution for the duration of a scrape
        with self._lock:
            models = list(self._models.items())
        return {name: sm.engine_metrics() for name, sm in models}

    def monitor(self, name: str) -> dict:
        """Per-model status (parity: /backend/monitor via gopsutil,
        core/services/backend_monitor.go — process stats become engine
        stats in-process)."""
        with self._lock:
            sm = self._models.get(name)
        if sm is None:
            return {"loaded": False, "name": name}
        return {
            "loaded": True,
            "name": name,
            "busy": sm.busy,
            "age_seconds": time.monotonic() - sm.loaded_at,
            "idle_seconds": time.monotonic() - sm.last_used,
            **sm.engine_metrics(),
        }


class _Watchdog(threading.Thread):
    """Busy/idle sweeper (parity: WatchDog.Run/checkBusy/checkIdle,
    /root/reference/pkg/model/watchdog.go:82-156)."""

    INTERVAL = 5.0

    def __init__(self, manager: ModelManager):
        super().__init__(name="watchdog", daemon=True)
        self.manager = manager
        self._stop = threading.Event()

    def stop(self) -> None:
        self._stop.set()

    def run(self) -> None:
        app = self.manager.app
        while not self._stop.wait(self.INTERVAL):
            now = time.monotonic()
            with self.manager._lock:
                items = list(self.manager._models.items())
            for name, sm in items:
                if (app.watchdog_idle and not sm.busy
                        and now - sm.last_used > app.watchdog_idle_timeout):
                    log.info("watchdog: evicting idle model %s", name)
                    self.manager.shutdown_model(name, force=True)
                elif app.watchdog_busy and sm.busy:
                    self._cancel_stuck(sm, now)

    def _cancel_stuck(self, sm: Any, now: float) -> None:
        if not isinstance(sm, ServingModel):
            # worker tier has its own busy watchdog (worker.process.Watchdog);
            # image generations are bounded by their step count
            return
        timeout = self.manager.app.watchdog_busy_timeout
        with sm.scheduler._lock:
            stuck = [
                ctx.handle
                for ctx in sm.scheduler._slots.values()
                if now - ctx.handle.t_submit > timeout
            ]
        for handle in stuck:
            log.warning("watchdog: cancelling stuck request %d (>%ds)",
                        handle.id, int(timeout))
            handle.cancel()

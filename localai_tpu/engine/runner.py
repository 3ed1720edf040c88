"""ModelRunner: the jitted prefill/decode executor for one loaded LLM.

This is the TPU-era replacement for llama.cpp's slot engine hot loop
(update_slots + llama_decode + per-slot sampling,
/root/reference/backend/cpp/llama/grpc-server.cpp:1546-1990), redesigned for
XLA's compile-once/static-shape model:

  * ONE compiled decode step serves all slots every iteration (continuous
    batching = slot masking, not ragged batch rebuilds).
  * Prefill lengths are bucketed (powers of a small set) so at most
    len(buckets) prefill programs are ever compiled — no recompilation
    storms from arbitrary prompt lengths.
  * KV cache and decode state are donated on every dispatch, and the
    cache is written in place: the layer scan in models.llama.forward
    carries the stacked cache, the write policies (engine.kvcache) scatter
    the new rows into it, the Pallas kernels read it by layer index, so
    the donated buffer is aliased from argument through loop state to
    output. Compiled for v5e no program holds a cache-sized temp or copies
    a layer of the cache (tests/test_tpu_compile.py
    test_cell_programs_write_the_pool_in_place).
  * Sampling runs on device in the same program as the forward pass; the
    only per-step host traffic is the [S] sampled-token vector.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from localai_tpu.engine import kvcache as kvc
from localai_tpu.engine import paged as pgd
from localai_tpu.engine import sampling as smp
from localai_tpu.engine.kvcache import KVCache
from localai_tpu.models import llama as mdl
from localai_tpu.models.llama import LlamaConfig
from localai_tpu.obs import compile as obs_compile
from localai_tpu.obs.profiler import scoped
from localai_tpu.obs import watchdog as obs_watchdog

log = logging.getLogger(__name__)

# sampled-row sentinel for the per-row NaN/inf logits guard: a slot whose
# (biased) logits row went non-finite reports this instead of a token id,
# riding the [S] token transfer the host already pays for — zero extra
# device syncs. Distinct from the speculative SKIP sentinel (-1); the
# scheduler fails the affected request and quarantines the slot.
NAN_TOKEN = -2

# speculative-window sentinel in emitted [T, S] rows: no token for this
# (step, slot) — the slot's window ended at an earlier rejection (or the
# slot is inactive). Consumers (scheduler._process_rows) skip it.
SKIP = -1

# tokens a chunked-prefill dispatch covers unless engine.prefill_chunk says
# otherwise (never under one KV block)
PREFILL_CHUNK = 512
# rows from which a prompt's last chunk runs in quarters behind the attend
# (``ModelRunner.chunk_rows``), on one chip as on a mesh; the 128 bucket is
# bound by the weights' bytes and stays whole
CHUNK_QUARTERED = 512
# rows up to which a prompt's LAST chunk rides the decode step launched behind
# it (``ModelRunner._decode_prefill_paged_fn``): the ladder's first rung, where
# a chunk is bound by the weights' bytes and not by its rows' products, so one
# read of them serves the chunk's rows and the step's
RIDE_ROWS = 128


def _prompt_counts_row(vocab_size: int, prompt) -> np.ndarray:
    """[V] i32 bincount of the FULL prompt for resume-style prefills (the
    in-program count would only see the tail chunk)."""
    crow = np.zeros(vocab_size, np.int32)
    ids = np.asarray(prompt, np.int64)
    np.add.at(crow, ids[(ids >= 0) & (ids < vocab_size)], 1)
    return crow


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DecodeState:
    """All per-slot mutable serving state, device-resident."""

    tokens: jax.Array      # [S] i32 — next token to feed per slot
    positions: jax.Array   # [S] i32 — next KV write position per slot
    active: jax.Array      # [S] bool
    keys: jax.Array        # [S] PRNG keys
    counts: jax.Array      # [S, V] i32 — token occurrence counts (penalties)
    bias: jax.Array        # [S, V] f32 — additive logit bias (logit_bias API
                           #              + grammar/FSM masks as -1e30)
    params: smp.SamplingParams
    # per-slot state that is not keys (the family module's ``init_rec``) and,
    # for a model with routed experts, the routed work of chunks that have
    # not sampled yet; None, and so no leaf of any program, for every other
    # model
    rec: Any = None

    @staticmethod
    def init(num_slots: int, vocab_size: int, seed: int = 0,
             rec: Any = None) -> "DecodeState":
        return DecodeState(
            tokens=jnp.zeros(num_slots, jnp.int32),
            positions=jnp.zeros(num_slots, jnp.int32),
            active=jnp.zeros(num_slots, jnp.bool_),
            keys=jax.random.split(jax.random.key(seed), num_slots),
            counts=jnp.zeros((num_slots, vocab_size), jnp.int32),
            bias=jnp.zeros((num_slots, vocab_size), jnp.float32),
            params=smp.SamplingParams.init(num_slots),
            rec=rec,
        )


class ModelRunner:
    """Owns params + KV cache + decode state for one model; exposes
    admit/step/release to the scheduler."""

    def __init__(
        self,
        cfg: LlamaConfig,
        params: Any,
        *,
        num_slots: int = 8,
        max_ctx: Optional[int] = None,
        prefill_buckets: Optional[list[int]] = None,
        kv_dtype: str = "bfloat16",
        rope_freq_base: Optional[float] = None,
        rope_freq_scale: Optional[float] = None,
        seed: int = 0,
        mesh: Optional[jax.sharding.Mesh] = None,
        attn_impl: str = "auto",
        sp_threshold: int = 1024,
        ga_n: int = 1,
        ga_w: int = 512,
        paged: bool = False,
        kv_block_tokens: Optional[int] = None,
        kv_num_blocks: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
    ):
        from localai_tpu import ops

        self.cfg = cfg
        self.params = params
        # stall watchdog guarding this runner's blocking device round-trips
        # (the scheduler rebinds it to its own instance when injected); the
        # process-wide default is unstarted until a Scheduler starts it
        self.watchdog = obs_watchdog.WATCHDOG
        # dispatch-anatomy scratch (obs.anatomy): the sync-by-contract
        # entry points (step / step_n / step_frozen_n) split their wall
        # time into call-return (async enqueue) vs result-fetch (device
        # block) and leave it here for the caller to harvest. Engine-
        # thread-only, overwritten every call — an attribution side
        # channel, not state.
        self.last_launch_ms = 0.0
        self.last_sync_ms = 0.0
        # self-extend / group attention (parity: llama.cpp ga_n/ga_w slot
        # options — see engine.selfextend). ga_n>1 serves past the trained
        # context by merging neighbor + grouped attention scores; the KV
        # cache stays UNroped in this mode, so it forces the XLA attend
        # (the Pallas kernels assume pre-roped K).
        if ga_n > 1 and ga_w % ga_n:
            raise ValueError(f"ga_w ({ga_w}) must be a multiple of "
                             f"ga_n ({ga_n})")
        self.ga_n, self.ga_w = ga_n, ga_w
        if ga_n > 1:
            attn_impl = "xla"
            log.info("self-extend active (ga_n=%d ga_w=%d): XLA attention, "
                     "unroped KV cache", ga_n, ga_w)
        # pipeline (layer-sharded) parallelism: HBM capacity scaling over
        # the 'pipe' axis (parallel.pipeline — llama.cpp layer-split-mode
        # parity). v1 runs pipe alone and keeps the XLA attend.
        self.pp_enabled = (mesh is not None
                           and mesh.shape.get("pipe", 1) > 1)
        if self.pp_enabled:
            n_pipe = mesh.shape["pipe"]
            busy = [ax for ax in ("data", "model", "seq", "expert")
                    if mesh.shape.get(ax, 1) > 1]
            if busy:
                raise ValueError(
                    f"pipeline parallelism composes with no other axis "
                    f"yet; mesh also shards {busy}")
            if cfg.num_layers % n_pipe:
                raise ValueError(
                    f"num_layers {cfg.num_layers} not divisible by "
                    f"pipe={n_pipe}")
            if ga_n > 1:
                raise ValueError(
                    "self-extend is not supported with pipeline "
                    "parallelism")
            if cfg.num_passes > 1:
                raise ValueError(
                    "a looped decoder (num_passes > 1) is not supported "
                    "with pipeline parallelism: the stage chain runs the "
                    "stack once")
            attn_impl = "xla"
            log.info("pipeline parallelism: %d stages x %d layers",
                     n_pipe, cfg.num_layers // n_pipe)
        # the full decision (auto-resolve + every shape gate) lives in
        # ops.select_attn_impl so tests can assert which path a given
        # (model, mesh) lands on at hardware shapes; a shape the compiled
        # kernels cannot take raises here, at load
        if cfg.latent:
            # a latent pool has no K/V a head for the contiguous-cache
            # kernels to gate on (that layout is refused below)
            self.attn_impl, self._attn_interpret = ops.resolve_attn_impl(
                attn_impl)
        else:
            self.attn_impl, self._attn_interpret = ops.select_attn_impl(
                attn_impl,
                num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads,
                head_dim=cfg.hd,
                max_ctx=max_ctx or cfg.max_position_embeddings,
                tp=mesh.shape["model"] if mesh is not None else 1,
            )
        # int8 KV rides the same flash decode kernel: per-position scales
        # fuse into the online-softmax loop (ops.attention), so the default
        # quantized config is both length-aware (block-skip past each slot's
        # frontier) and half-bandwidth — no XLA fallback, no bf16 cache copy.
        self.decode_attn_impl = self.attn_impl
        self.num_slots = num_slots
        self.max_ctx = max_ctx or cfg.max_position_embeddings
        self.mesh = mesh
        buckets = sorted(prefill_buckets or [128, 512, 2048, 8192])
        self.buckets = [b for b in buckets if b < self.max_ctx]
        self.buckets.append(self.max_ctx)  # any admissible prompt has a bucket
        self.rope = mdl.rope_table(
            cfg, self.max_ctx, freq_base=rope_freq_base, freq_scale=rope_freq_scale
        )
        # what the model's family does not serve (its ``UNSERVED``), in its
        # own sentence: ONE pass, before the unroped table is made (a table
        # a kind is none of self-extend's) and the layouts are chosen
        mdl.refuse(cfg, (
            ("self-extend", ga_n > 1),
            ("pipeline parallelism", self.pp_enabled),
            ("the ring prefill", mesh is not None
             and mesh.shape.get("seq", 1) > 1),
            ("a device mesh", mesh is not None),
            ("the contiguous K/V layout", not paged),
            (f"a {kv_dtype} K/V pool", kv_dtype in ("int8", "int4"))))
        if self.ga_n > 1:
            from localai_tpu.engine import selfextend as se

            # forward() sees an identity table (q/k written unroped); the
            # self-extend attend applies the real rotations per score set
            self._se_rope = self.rope
            self.rope = se.identity_rope(self.rope)
        # paged KV cache (vLLM-style block pool + tables, engine.paged).
        # A plain dp×tp(×seq) mesh composes: the pool shards its kv-head
        # axis over 'model' (parallel.sharding.paged_kv_spec), the [S, MB]
        # table mirror shards slots over 'data', and the block allocator
        # stays host-side and replicated — admission, refcounts, and
        # prefix sharing are topology-blind. Incompatible modes keep the
        # slot-contiguous layout: pipeline parallelism (pp_forward's stage
        # chain assumes layer-sharded slot rows) and self-extend (unroped
        # cache + grouped rescoring assume row slices).
        incompat = []
        if self.pp_enabled:
            incompat.append("pipeline parallelism")
        if ga_n > 1:
            incompat.append("self-extend")
        # bare runners (tests, tools) are contiguous unless asked; the
        # serving manager asks for paged whenever the engine is compatible
        self.paged = bool(paged)
        # a stack with more than one kind of attention layer (models.afmoe):
        # a mask and an attend a kind, through the paged pool on one chip
        self.kinds = cfg.attn_kinds
        # latent attention (models.deepseek): one latent row a token in a
        # block pool of its own page (engine.kvcache ``LatentLayout``), on
        # one chip
        self.latent = bool(cfg.latent)
        if self.paged and incompat:
            raise ValueError(
                f"paged KV cache is incompatible with {incompat}")
        # a model whose layers carry per-slot state that is not keys
        # (``cfg.recurrent``: its family module builds the state, and its
        # forward carries it): that state is one dense row a slot beside
        # the block pool (what takes a sequence for its keys was refused
        # above)
        self.recurrent = bool(cfg.recurrent)
        # a model with routed experts (models.experts): its forward counts
        # each launch's routed work, and its own kernels (the experts as
        # ops.moe's; a recurrent family's decode step as ops.gdn's) run
        # where attention's are kernels (the value: in the Pallas
        # interpreter), None for their XLA forms where ``attn_impl`` says xla
        self.routed = bool(cfg.routed)
        # either has a forward of its family's own (``_forward_rec``)
        self.own_forward = self.routed or self.recurrent
        if self.routed:
            impl, interpret = ops.select_moe_impl(
                attn_impl, hidden=cfg.hidden_size,
                intermediate=cfg.moe_intermediate_size)
            self.family_kernels = interpret if impl == "pallas" else None
        elif self.recurrent:
            impl, interpret = ops.resolve_attn_impl(attn_impl)
            self.family_kernels = interpret if impl == "pallas" else None
        if kv_dtype == "int4" and not self.paged:
            raise ValueError(
                "kv_dtype=int4 requires the paged KV layout (the nibble-"
                "packed pool scatter only exists for block pools); use "
                "int8 for contiguous caches")
        if self.paged:
            # (a model that selects blocks of the pool says how large one
            # is: ``LlamaConfig.select_blocks``)
            self.block_tokens = max(8, int(
                kv_block_tokens or (cfg.select_blocks or (0,))[0]
                or pgd.block_tokens_default()))
            self.max_blocks = -(-self.max_ctx // self.block_tokens)
            self.ctx_pad = self.max_blocks * self.block_tokens
            # default pool = the contiguous layout's HBM footprint (every
            # slot can still reach max_ctx) scaled by the overcommit ratio
            # (paged.overcommit_default: <1 shrinks for true overcommit, >1
            # grows past the contiguous footprint), plus the trash block;
            # kv_num_blocks sets an absolute count and wins over the ratio
            self.kv_overcommit = pgd.overcommit_default()
            default_blocks = max(
                self.max_blocks,
                int(num_slots * self.max_blocks * self.kv_overcommit)) + 1
            # a model with recurrent state shares a prefix where the state
            # at its end was kept (engine.paged: a snapshot a registered
            # prompt): rows for an eighth of the slots' own state, at
            # least two, sized here from what one costs and by no option
            self.allocator = pgd.BlockAllocator(
                int(kv_num_blocks or default_blocks), self.block_tokens,
                self.max_blocks,
                snapshots=max(2, num_slots // 8) if self.recurrent else 0)
            self.prefill_chunk = max(
                self.block_tokens, int(prefill_chunk or PREFILL_CHUNK))
            if self.latent:
                (self.paged_attn_impl,
                 self._paged_attn_interpret) = ops.select_latent_attn_impl(
                    attn_impl, block_tokens=self.block_tokens,
                    kv_dtype=kv_dtype)
            else:
                (self.paged_attn_impl,
                 self._paged_attn_interpret) = ops.select_paged_attn_impl(
                    attn_impl,
                    num_heads=cfg.num_heads,
                    num_kv_heads=cfg.num_kv_heads,
                    head_dim=cfg.hd,
                    block_tokens=self.block_tokens,
                    tp=mesh.shape["model"] if mesh is not None else 1,
                    kv_dtype=kv_dtype,
                )
            # meshed decode runs the trunk as a manual-TP shard_map
            # (parallel.overlap): each chip's kernel writes its own heads,
            # two all-reduces a layer; resolve_mode gates unsupported
            # meshes back to GSPMD.
            self.overlap_mode = ""
            if mesh is not None:
                from localai_tpu.parallel import overlap as ovl

                self.overlap_mode, ovl_why = ovl.resolve_mode(cfg, mesh)
                if self.overlap_mode:
                    log.info("meshed decode: manual-TP trunk")
                elif ovl_why:
                    log.info("meshed decode: manual-TP trunk unavailable: "
                             "%s (GSPMD path)", ovl_why)
            # one device-resident zeros row reused by every non-final
            # chunk dispatch (whose sample=False program ignores counts —
            # no per-chunk [V] host alloc + H2D copy)
            self._zero_counts = jnp.zeros(cfg.vocab_size, jnp.int32)
        else:
            self.allocator = None
            self.overlap_mode = ""
        self._seed = seed
        self.kv_dtype = kv_dtype
        if mesh is not None:
            from localai_tpu.models import quant as qnt
            from localai_tpu.parallel import sharding as shd

            # the Pallas w8 matmul has no partitioning rule — GSPMD would
            # all-gather sharded weights into it every step. The block is
            # carried by THIS runner's tensors (kernel_ok metadata), so a
            # single-device runner built later keeps the kernel opt-in.
            self.params = params = qnt.block_w8_kernel_params(
                params, "runner built over a device mesh")
            shd.slots_per_data_shard(num_slots, mesh)  # divisibility check
        # how the K/V is laid out, written, attended and masked: the one
        # family of programs below asks the layout (engine.kvcache); it
        # keeps its shardings, so reinit() (self-healing engine rebuild)
        # rebuilds the device state into the exact same layout
        if self.latent:
            self.layout = kvc.LatentLayout(
                cfg, kv_dtype, num_slots, self.max_ctx,
                self.paged_attn_impl, self._paged_attn_interpret,
                self.block_tokens, self.max_blocks,
                self.allocator.num_blocks)
        elif self.paged:
            self.layout = kvc.PagedLayout(
                cfg, mesh, kv_dtype, num_slots, self.max_ctx,
                self.paged_attn_impl, self._paged_attn_interpret,
                self.block_tokens, self.max_blocks,
                self.allocator.num_blocks, self.overlap_mode)
        else:
            self.layout = kvc.ContiguousLayout(
                cfg, mesh, kv_dtype, num_slots, self.max_ctx,
                self.decode_attn_impl, self._attn_interpret, self._se_attn)
        self._init_device_state()
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            self.rope = jax.device_put(
                self.rope, NamedSharding(mesh, P())
            )
        # every jit entry point is wrapped by obs.compile.watch: the first
        # dispatch of each program shape compiles synchronously, so its
        # wall time lands in the localai_xla_compile_* series (the
        # jax.monitoring listener supplements this where available)
        obs_compile.install()
        # ONE family over both layouts (the names are the block pool's: the
        # cells' XLA modules are called by them), with the labels an
        # operator's per-program series have had on each
        self._decode_paged = obs_compile.watch(
            jax.jit(self._decode_paged_fn, donate_argnums=(1, 2)), "decode")
        self._decode_paged_n = obs_compile.watch(jax.jit(
            self._decode_paged_n_fn, static_argnames=("n",),
            donate_argnums=(1, 2),
        ), "decode_n")
        self._decode_paged_frozen_n = obs_compile.watch(jax.jit(
            self._decode_paged_frozen_n_fn, static_argnames=("n",),
            donate_argnums=(1, 2),
        ), "decode_frozen_n")
        # speculative verify (localai_tpu.spec): one batched T-wide target
        # forward scores a whole draft window per dispatch. One program per
        # gamma (the window width is baked into the proposals shape).
        self._verify_paged = obs_compile.watch(
            jax.jit(self._verify_paged_fn, donate_argnums=(1, 2)), "verify")
        self._prefill_paged = obs_compile.watch(jax.jit(
            self._prefill_paged_fn, static_argnames=("bucket", "sample"),
            donate_argnums=(1, 2),
        ), "prefill_chunk" if self.paged else "prefill_resume")
        self._decode_prefill_paged = obs_compile.watch(jax.jit(
            self._decode_prefill_paged_fn, static_argnames=("bucket",),
            donate_argnums=(1, 2),
        ), "decode_chunk")
        # the fresh whole-prompt prefills stay a layout's own (ROADMAP C1b):
        # a multimodal one and, over a 'seq' mesh axis, a ring-attention one
        # (one long prompt uses every chip without stalling decode — chosen
        # by admit / begin_admit), under the same labels on both
        def fresh(fn, label):
            return obs_compile.watch(jax.jit(
                fn, static_argnames=("bucket",), donate_argnums=(1, 2)), label)

        if self.paged:
            self._prefill_paged_mm = fresh(self._prefill_paged_mm_fn,
                                           "prefill_mm")
            self._prefill_paged_sp = fresh(self._prefill_paged_sp_fn,
                                           "prefill_sp")
        else:
            self._prefill = fresh(self._prefill_fn, "prefill")
            self._prefill_mm = fresh(self._prefill_mm_fn, "prefill_mm")
            self._prefill_sp = fresh(self._prefill_sp_fn, "prefill_sp")
        # sequence-parallel prefill: long prompts chunk over the 'seq' mesh
        # axis and run ring attention (parallel.ring) straight into the
        # slot cache. Composes with TP: weights stay 'model'-sharded
        # (Megatron layout + per-layer psums) while activations shard over
        # 'seq' — requires the head groups to split evenly so each device's
        # ring carries a consistent Hkv/tp head shard.
        sp_tp = mesh.shape.get("model", 1) if mesh is not None else 1
        self.sp_enabled = (
            mesh is not None
            and mesh.shape.get("seq", 1) > 1
            and (sp_tp == 1
                 or (cfg.num_heads % sp_tp == 0
                     and cfg.num_kv_heads % sp_tp == 0
                     and cfg.intermediate_size % sp_tp == 0))
            # expert-parallel MoE prefill stays on the GSPMD path — the
            # manual ring shard_map doesn't slice router weights per shard
            and (cfg.num_experts == 0 or mesh.shape.get("expert", 1) == 1)
            # self-extend keeps the cache unroped; the ring prefill writes
            # roped K, so the two modes are mutually exclusive
            and ga_n == 1
            # the ring prefill walks the stack once and returns a K/V row a
            # layer: a looped decoder keeps the chunked path
            and cfg.num_passes == 1
        )
        self.sp_threshold = sp_threshold
        self.last_prefill_path = ""
        self._embed = obs_compile.watch(
            jax.jit(self._embed_fn, static_argnames=("bucket",)), "embed"
        )
        # the slot's lifecycle on the device, one small program each way
        # (an eager ``.at[slot].set`` a field was a launch a field, on every
        # chip, with the device waiting)
        self._arm_slot = obs_compile.watch(
            jax.jit(self._arm_slot_fn, donate_argnums=(0, 1)), "arm_slot")
        self._release_slot = obs_compile.watch(
            jax.jit(self._release_slot_fn, donate_argnums=(0, 1)),
            "release_slot")
        # a recurrent family's state kept at a prompt's boundary and laid
        # back in front of a later prompt's tail
        self._take_snapshot = obs_compile.watch(
            jax.jit(self._move_state_fn, donate_argnums=(0,)),
            "take_snapshot")
        self._restore_snapshot = obs_compile.watch(jax.jit(
            lambda state, snaps, slot, row: dataclasses.replace(
                state, rec=self._move_state_fn(state.rec, snaps, slot, row)),
            donate_argnums=(0,)), "restore_snapshot")
        # programs the admission path has launched (arming updates and
        # prefill dispatches): Scheduler.metrics() sets it against the
        # admissions made
        self.admit_programs = 0
        # KV prefix reuse (parity: common_part, grpc-server.cpp:67-74):
        # suffix prefill only pays off past a minimum shared prefix
        self.prefix_reuse_min = 16
        self.last_prefix_reused = 0       # tokens reused by the last admit
        self.total_prefix_reused = 0

    # -- device-state lifecycle (construction + self-healing rebuild) ----

    def _init_device_state(self) -> None:
        """(Re)build everything device-resident and per-slot: KV pool,
        decode state, block tables, allocator bookkeeping, free-slot
        list. Called once at construction and again by :meth:`reinit`
        after a suspected device wedge — params, compiled programs, and
        shardings are untouched, so no retrace/recompile happens."""
        cfg = self.cfg
        # the cache and, over the pool, the table mirror (None over the
        # contiguous rows: the slot programs take it as it is)
        self.kv, self.block_tables = self.layout.init()
        if self.allocator is not None:
            self.allocator = pgd.BlockAllocator(
                self.allocator.num_blocks, self.block_tokens,
                self.max_blocks, snapshots=self.allocator.snapshots)
            # disk prompt-cache rows loaded into a slot's fresh blocks
            # (the only slot-resident reuse that survives release)
            self._loaded_rows: dict[int, int] = {}
            # HBM→host prefix-pool tiering (LOCALAI_KV_TIER_MB, off by
            # default): LRU pool evictions spill their raw block rows to
            # host RAM and re-onboard on a later chain hit. Rebuilt with
            # the allocator on every reinit — a rebuilt pool starts cold,
            # and stale spills from the pre-wedge cache must not shadow
            # it (lazy import: fleet.kveconomy is runtime-only here).
            from localai_tpu.fleet.kveconomy.tiering import tier_from_env

            tier = tier_from_env()
            if tier is not None:
                self.allocator.attach_tier(
                    tier, pack=self.pack_block, load=self.load_block)
        state = DecodeState.init(self.num_slots, cfg.vocab_size, self._seed,
                                 rec=self._init_rec(self.num_slots))
        if self.mesh is not None:
            state = self._place_state(state)
        self.state = state
        # the snapshot rows beside it (``_snap_axes``: the per-slot arrays
        # of ``rec``, the allocator's rows where their slots are)
        self.snaps = None
        if self.allocator is not None and self.allocator.snapshots:
            rows = self._init_rec(self.allocator.snapshots)
            self.snaps = {name: rows[name] for name in self._snap_axes()}
        self._free_slots = list(range(self.num_slots))
        # host mirror of which slots are serving: admit()/release() are the
        # only transitions, so liveness queries never touch the device
        self._active_slots: set[int] = set()

    def _init_rec(self, num_slots: int):
        """``DecodeState.rec`` for ``num_slots`` slots, the family module's
        ``init_rec``: the state its forward carries and, where it routes,
        the routed work of chunks whose token no copy brings to the host yet
        (``_prefill_paged_fn``). None for a model of no family."""
        fam = mdl.family_module(self.cfg)
        return None if fam is None else fam.init_rec(self.cfg, num_slots)

    def _snap_axes(self) -> dict:
        """{name: slot axis} of the arrays of ``rec`` that hold a row a
        slot: what a snapshot copies. Read off the family's ``init_rec``
        (the axis that grows with the slots), so every recurrent family is
        served by the one door; an entry that is not per slot (a routed
        count) is none."""
        fam = mdl.family_module(self.cfg)
        one, two = (jax.eval_shape(
            lambda n=n: fam.init_rec(self.cfg, n)) for n in (1, 2))
        axes = {name: next((i for i, (a, b) in enumerate(
            zip(one[name].shape, two[name].shape)) if a != b), None)
            for name in one}
        return {name: ax for name, ax in axes.items() if ax is not None}

    def _move_state_fn(self, into, out_of, to, at):
        """Row ``at`` of ``out_of``'s per-slot arrays laid over row ``to``
        of ``into``'s: a snapshot taken (slot -> row) or restored (row ->
        slot), a device copy of one slot's state."""
        return {**into, **{
            name: jax.lax.dynamic_update_slice_in_dim(
                into[name], jax.lax.dynamic_slice_in_dim(
                    out_of[name], at, 1, ax), to, ax)
            for name, ax in self._snap_axes().items()}}

    def take_snapshot(self, slot: int, row: int) -> None:
        """``slot``'s state as it stands (behind every chunk dispatched so
        far) into snapshot row ``row``."""
        self.snaps = self._take_snapshot(
            self.snaps, self.state.rec, np.int32(row), np.int32(slot))

    def restore_snapshot(self, slot: int, row: int) -> None:
        """Snapshot row ``row`` into ``slot``'s state, in front of the
        chunk that goes on from it."""
        self.state = self._restore_snapshot(
            self.state, self.snaps, np.int32(slot), np.int32(row))

    def _place_state(self, state: DecodeState) -> DecodeState:
        """Shard a fresh DecodeState over the mesh (the construction-time
        layout, reapplied verbatim on rebuild)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from localai_tpu.parallel import sharding as shd

        mesh = self.mesh
        specs = shd.state_specs(mesh)

        def place(name: str, leaf):
            spec = shd._sanitize(specs[name], leaf.shape, mesh)
            return jax.device_put(leaf, NamedSharding(mesh, spec))

        return DecodeState(
            tokens=place("tokens", state.tokens),
            positions=place("positions", state.positions),
            active=place("active", state.active),
            keys=place("keys", state.keys),
            counts=place("counts", state.counts),
            bias=place("bias", state.bias),
            params=jax.tree.map(
                lambda a: jax.device_put(
                    a, NamedSharding(mesh, P("data"))
                ),
                state.params,
            ),
            rec=state.rec,
        )

    def reinit(self) -> None:
        """Self-healing engine rebuild (faults.supervisor): drop the
        possibly-corrupt device state and allocate a fresh KV pool /
        decode state / block tables in the original layout. The old
        arrays may still be referenced by an abandoned dispatch thread
        parked in a dead round-trip; they are released here and freed
        whenever that thread exits. Callers own slot bookkeeping — every
        previously admitted request must already be failed."""
        self._init_device_state()
        self.last_prefill_path = ""
        self.last_prefix_reused = 0

    # -- jitted programs -------------------------------------------------

    def _decode_tail(self, params, state: DecodeState, hidden, logits=None):
        """Sampling + per-slot state advance of the decode step
        (KV-layout-independent), from ``hidden [S, 1, D]`` or from the
        ``logits [S, V]`` a caller has already taken of it."""
        pos = state.positions
        if logits is None:
            logits = mdl.logits_from_hidden(self.cfg, params, hidden[:, 0])
        tokens, keys = smp.sample(
            logits, state.params, state.counts, state.keys, state.bias,
            mesh=self.mesh,
        )
        # inactive/frozen slots keep their key: a seeded request's stream must
        # not depend on batch composition (key advances == tokens sampled)
        keys = jnp.where(state.active, keys, state.keys)
        tokens = jnp.where(state.active, tokens, state.tokens)
        # per-row NaN/inf guard on the effective (biased) logits: one bad
        # row must fail only its own slot, never silently poison the
        # co-batched streams. The verdict rides the sampled-token row as
        # the NAN_TOKEN sentinel — no extra transfer, no host branch.
        row_ok = jnp.all(
            jnp.isfinite(logits.astype(jnp.float32) + state.bias), axis=-1)
        tokens = jnp.where(state.active & ~row_ok, NAN_TOKEN, tokens)
        # clamp the sentinel out of the scatter index (the slot is dead
        # either way; a wrapped negative index would dirty a real count)
        counts = smp.update_counts(
            state.counts, jnp.maximum(tokens, 0), state.active)
        positions = jnp.where(
            state.active, jnp.minimum(pos + 1, self.max_ctx - 1), pos
        )
        new_state = dataclasses.replace(
            state, tokens=tokens, positions=positions, keys=keys, counts=counts
        )
        return new_state, tokens

    # -- speculative verify programs (localai_tpu.spec drives these) -----

    def _accept_scan(self, state: DecodeState, logits, proposals):
        """Accept/sample scan over a speculative window: the full sampler
        chain per position with sequentially-updated counts, so emitted
        tokens follow the exact non-speculative sampling distribution
        (naive-match acceptance: a draft token is accepted iff it equals
        the token the target itself sampled; on mismatch the target's
        sample is the correction and the window ends). PRNG keys advance
        once per EMITTED token, preserving the seeded-stream contract.

        logits [S, T, V], proposals [S, T-1]. Returns (new_state,
        emitted [T, S]) where SKIP marks positions past a slot's
        accepted window; positions roll forward by exactly the emitted
        count — the rejected tail is rolled back for every slot
        independently."""
        S = self.num_slots
        G = proposals.shape[1]
        T = G + 1

        def acc_body(carry, xs):
            counts, keys, still, n_emit, final_tok = carry
            logits_t, draft_t, t = xs  # [S, V], [S], scalar
            tok, new_keys = smp.sample(
                logits_t, state.params, counts, keys, state.bias,
                mesh=self.mesh,
            )
            # per-row NaN/inf guard, same contract as _decode_tail: a
            # non-finite effective logits row reports the NAN_TOKEN
            # sentinel instead of a sample (and ends the slot's window —
            # the sentinel can never equal a draft token), so the
            # scheduler fails ONLY that request. Speculation is the
            # default lane; skipping the guard here would reopen the
            # silent-poison class the plain path closed.
            row_ok = jnp.all(
                jnp.isfinite(logits_t.astype(jnp.float32) + state.bias),
                axis=-1)
            tok = jnp.where(row_ok, tok, NAN_TOKEN)
            emit_now = still & state.active
            keys = jnp.where(emit_now, new_keys, keys)
            # clamp the sentinel out of the scatter index (the slot is
            # dead either way; a wrapped negative index would dirty a
            # real count) — mirrors _decode_tail
            counts = counts.at[jnp.arange(S), jnp.maximum(tok, 0)].add(
                emit_now.astype(counts.dtype)
            )
            final_tok = jnp.where(emit_now, tok, final_tok)
            n_emit = n_emit + emit_now.astype(jnp.int32)
            is_match = emit_now & (t < G) & (tok == draft_t)
            emitted_t = jnp.where(emit_now, tok, SKIP)
            return (counts, keys, is_match, n_emit, final_tok), emitted_t

        init = (
            state.counts,
            state.keys,
            jnp.ones(S, jnp.bool_),
            jnp.zeros(S, jnp.int32),
            state.tokens,
        )
        draft_padded = jnp.concatenate(
            [proposals, jnp.full((S, 1), SKIP, jnp.int32)], axis=1
        )
        (counts, keys, _, n_emit, final_tok), emitted = jax.lax.scan(
            acc_body, init,
            (logits.transpose(1, 0, 2), draft_padded.T, jnp.arange(T)),
        )  # emitted [T, S]
        new_pos = jnp.minimum(state.positions + n_emit, self.max_ctx - 1)
        new_state = dataclasses.replace(
            state, tokens=final_tok, positions=new_pos, keys=keys,
            counts=counts,
        )
        return new_state, emitted

    @scoped("verify")
    def _verify_paged_fn(self, params, kv, state: DecodeState, tables,
                         proposals):
        """One speculative verify dispatch: a T=gamma+1-wide batched forward
        scores every draft position at each slot's frontier (positions
        offset per slot — decode generalized to T tokens), then the
        accept/sample scan emits the accepted prefix + correction. The
        window's rows go where the layout's write puts them (over the pool:
        through the block tables into each slot's reserved speculation
        blocks) and attend over the prefix + the window so far; the accept
        scan rolls every slot's frontier back independently — the rejected
        tail is a per-slot position rollback, never a table mutation
        (co-batched slots are untouched by construction). proposals
        [S, gamma] i32; returns emitted [T, S]."""
        cfg = self.cfg
        T = proposals.shape[1] + 1
        p0 = state.positions
        positions = p0[:, None] + jnp.arange(T)[None, :]     # [S, T]
        tokens = jnp.concatenate(
            [state.tokens[:, None], proposals], axis=1)      # [S, T]
        write, mask = self.layout.verify(tables, p0, T)
        hidden, new_stack = self._forward(
            params, tokens, positions, write, kv.stacked(), mask,
        )
        logits = mdl.logits_from_hidden(cfg, params, hidden)  # [S, T, V]
        new_state, emitted = self._accept_scan(state, logits, proposals)
        return self.layout.from_stacked(new_stack), new_state, emitted

    def _first_token(self, params, state: DecodeState, hidden, length, slot,
                     offset=None, counts_row=None, prompt=None, logits=None):
        """What ends every prefill: take the row at ``length - 1`` of
        ``hidden [1, T, D]`` (or the ``logits [1, V]`` a caller has already
        taken of it), sample it with the slot's parameters and arm
        the slot at its frontier, ``length`` behind the ``offset`` cached
        tokens of a chunk (None: a fresh prompt). The slot's penalty counts are
        ``counts_row`` ([V] i32, the host's bincount of the FULL prompt: a
        program that sees a tail of it cannot count) or, where none comes,
        ``prompt``'s ([T], or [1, T] as the forward took it) first ``length``
        tokens counted here. Returns the armed state and the token, [1]."""
        if logits is None:
            last_h = jax.lax.dynamic_index_in_dim(hidden[0], length - 1,
                                                  keepdims=True)
            logits = mdl.logits_from_hidden(self.cfg, params, last_h)  # [1, V]
        if counts_row is None:
            counts = smp.count_prompt_tokens(
                state.counts, slot, prompt[0] if prompt.ndim == 2 else prompt,
                length)
        else:
            counts = state.counts.at[slot].set(counts_row)
        slot_params = jax.tree.map(lambda a: a[slot][None], state.params)
        tok, new_key = smp.sample(
            logits, slot_params, counts[slot][None], state.keys[slot][None],
            state.bias[slot][None], mesh=self.mesh,
        )
        return dataclasses.replace(
            state,
            tokens=state.tokens.at[slot].set(tok[0]),
            positions=state.positions.at[slot].set(
                length if offset is None else offset + length),
            active=state.active.at[slot].set(True),
            keys=state.keys.at[slot].set(new_key[0]),
            counts=counts,
        ), tok

    @scoped("prefill")
    def _prefill_fn(self, params, kv: KVCache, state: DecodeState,
                    tokens, length, slot, *, bucket: int, embeds=None):
        cfg = self.cfg
        positions = jnp.arange(bucket, dtype=jnp.int32)[None, :]
        attn = self._prefill_attn(length) or self._se_attn(
            positions, positions[0])
        mask = kvc.prefill_mask(cfg, bucket, length)
        write = kvc.prefill_write(slot, jnp.zeros((), jnp.int32))
        hidden, new_stack = self._forward(
            params, tokens, positions, write, kv.stacked(), mask,
            attn=attn, embeds=embeds,
        )
        new_state, tok = self._first_token(
            params, state, hidden, length, slot, prompt=tokens)
        return KVCache.from_stacked(new_stack), new_state, tok[0]

    def _prefill_mm_fn(self, params, kv: KVCache, state: DecodeState,
                       tokens, length, slot, mm_embeds, mm_positions,
                       *, bucket: int):
        """Multimodal prefill: token embeddings with image-embedding blocks
        scattered over the placeholder positions (parity: llama.cpp's
        image-embedding batch injection, grpc-server.cpp:1397-1424 — but as
        one fused program instead of interleaved decode batches).

        mm_embeds [n_mm, D] float32, mm_positions [n_mm] i32 (positions are
        < length by construction in the scheduler)."""
        from localai_tpu.models import quant as qnt

        dtype = jnp.dtype(self.cfg.dtype)
        x = qnt.embed_rows(params["embed"], tokens, dtype)  # [1, bucket, D]
        x = x.at[0, mm_positions].set(mm_embeds.astype(dtype))
        return self._prefill_fn(
            params, kv, state, tokens, length, slot, bucket=bucket, embeds=x
        )

    @scoped("prefill")
    def _prefill_sp_fn(self, params, kv: KVCache, state: DecodeState,
                       tokens, length, slot, *, bucket: int):
        """Sequence-parallel prefill: the prompt chunks over the 'seq' mesh
        axis, each device runs blockwise ring attention (KV chunks rotating
        over ICI via ppermute — parallel.ring), and the resulting per-layer
        K/V lands in the slot cache. tokens: [bucket] i32 (1-D)."""
        from localai_tpu.parallel import ring

        cfg = self.cfg
        hidden, (ks, vs) = ring.sp_prefill_forward(
            cfg, params, tokens, length, self.mesh, self.rope
        )
        # [L, T, Hkv, hd] → cache layout [L, 1, Hkv, T, hd]
        k_hm = ks.transpose(0, 2, 1, 3)[:, None]
        v_hm = vs.transpose(0, 2, 1, 3)[:, None]
        zero = jnp.zeros((), jnp.int32)
        idx = (zero, slot, zero, zero, zero)
        if kv.quantized:
            kq, kscale = kvc._quant_chunk(k_hm)
            vq, vscale = kvc._quant_chunk(v_hm)
            new_kv = KVCache(
                k=jax.lax.dynamic_update_slice(kv.k, kq, idx),
                v=jax.lax.dynamic_update_slice(kv.v, vq, idx),
                k_scale=jax.lax.dynamic_update_slice(
                    kv.k_scale, kscale, idx[:4]),
                v_scale=jax.lax.dynamic_update_slice(
                    kv.v_scale, vscale, idx[:4]),
            )
        else:
            kdt = kv.k.dtype
            new_kv = KVCache(
                k=jax.lax.dynamic_update_slice(kv.k, k_hm.astype(kdt), idx),
                v=jax.lax.dynamic_update_slice(kv.v, v_hm.astype(kdt), idx),
            )
        new_state, tok = self._first_token(
            params, state, hidden, length, slot, prompt=tokens)
        return new_kv, new_state, tok[0]

    # -- slot lifecycle programs (both layouts) ---------------------------

    def _arm_slot_fn(self, state: DecodeState, tables, ints, floats,
                     bias_row, table_row):
        """Everything an admission writes for its slot outside the prefill
        itself, in one update: the sampling parameters (``ints`` / ``floats``
        as ``SamplingParams.pack`` orders them, behind the slot, a seed flag
        and the seed), the PRNG key where the request brought a seed, the
        logit-bias row, and on a paged runner the slot's block-table row
        (``tables`` and ``table_row`` None on a contiguous one)."""
        slot, seeded, seed = ints[0], ints[1], ints[2]
        key = jnp.where(seeded > 0, jax.random.key(seed), state.keys[slot])
        state = dataclasses.replace(
            state,
            params=state.params.with_packed(slot, ints[3:], floats),
            keys=state.keys.at[slot].set(key),
            bias=state.bias.at[slot].set(bias_row),
        )
        if tables is not None:
            tables = tables.at[slot].set(table_row)
        return state, tables

    def _release_slot_fn(self, state: DecodeState, tables, slot):
        """A slot leaves the batch. On a paged runner its device table row
        also goes back to the trash block, so that the decode programs'
        static-shape garbage writes cannot touch reallocated blocks, and
        its frontier to 0; a contiguous slot keeps its frontier, which is
        what says how many of its rows a later prompt may reuse."""
        state = dataclasses.replace(
            state, active=state.active.at[slot].set(False))
        if tables is not None:
            state = dataclasses.replace(
                state, positions=state.positions.at[slot].set(0))
            tables = tables.at[slot].set(0)
        return state, tables

    # -- the serving programs: one family over the runner's layout --------
    # (named for the block pool's: the cells' XLA modules, the benchmark's
    # readers and its tests call them so; ROADMAP C1b)

    @scoped("decode")
    def _decode_paged_fn(self, params, kv, state: DecodeState, tables):
        """Batched single-token decode. ``tables`` [S, MB] i32 is the device
        mirror of the allocator's block tables (not donated — it changes
        only at admit/release), None over the contiguous rows."""
        pos = state.positions
        if self.overlap_mode:
            # the pool's manual-TP trunk; sampling/logits keep the GSPMD tail
            hidden, new_stack = self.layout.tp_trunk(
                params, self.rope, state.tokens, pos, kv, tables)
            new_state, tokens = self._decode_tail(params, state, hidden)
            return self.layout.from_stacked(new_stack), new_state, tokens
        write, attn, mask = self.layout.decode(kv, tables, pos)
        if self.own_forward:
            # a slot with no stream is the identity on its state; the step's
            # routed work rides behind the S sampled tokens, in their copy
            hidden, new_stack, rec, routed = self._forward_rec(
                params, state.tokens[:, None], pos[:, None], write,
                kv.stacked(), mask, state.rec, state.active[:, None],
                attn=attn)
            new_state, tokens = self._decode_tail(
                params, dataclasses.replace(state, rec=rec), hidden)
            if routed is not None:
                tokens = jnp.concatenate([tokens, routed])
            return self.layout.from_stacked(new_stack), new_state, tokens
        hidden, new_stack = self._forward(
            params, state.tokens[:, None], pos[:, None],
            write, kv.stacked(), mask, attn=attn,
        )
        new_state, tokens = self._decode_tail(params, state, hidden)
        return self.layout.from_stacked(new_stack), new_state, tokens

    def _decode_paged_n_fn(self, params, kv, state, tables, *, n: int):
        """n decode steps in ONE dispatch via lax.scan — one host→device
        dispatch and one result fetch per n tokens. Returns tokens [n, S].
        The block tables are loop-invariant: every admitted slot's table
        already covers its full reservation."""

        def body(carry, _):
            kv, state = carry
            kv, state, tokens = self._decode_paged_fn(
                params, kv, state, tables)
            return (kv, state), tokens

        (kv, state), tokens = jax.lax.scan(body, (kv, state), None, length=n)
        return kv, state, tokens

    def _decode_paged_frozen_n_fn(self, params, kv, state, tables, freeze,
                                  *, n: int):
        """n decode steps in one dispatch where slots in ``freeze`` advance
        only on the FIRST step — the per-slot constraint gating path: a
        grammar-constrained slot needs its logit mask refreshed by the host
        between tokens (so it gets one token per dispatch), while the
        unconstrained slots ride the same dispatch for n tokens. Replaces the
        whole-batch synchronous fallback (one constrained request no longer
        de-pipelines the batch). Returns tokens [n, S]; rows 1..n-1 are only
        meaningful for non-frozen slots."""
        full_active = state.active

        def body(carry, i):
            kv, st = carry
            eff = jnp.where(i == 0, full_active, full_active & ~freeze)
            kv, st, tokens = self._decode_paged_fn(
                params, kv, dataclasses.replace(st, active=eff), tables
            )
            st = dataclasses.replace(st, active=full_active)
            return (kv, st), tokens

        (kv, state), tokens = jax.lax.scan(
            body, (kv, state), jnp.arange(n), length=n
        )
        return kv, state, tokens

    @scoped("prefill")
    def _prefill_paged_fn(self, params, kv, state, tokens, length, offset,
                          table_row, slot, counts_row, *, bucket: int,
                          sample: bool, embeds=None):
        """One chunk behind ``offset`` cached tokens: write its ``length``
        real tokens at absolute positions [offset, offset+length) (over the
        pool through ``table_row``, the slot's block table; over the
        contiguous rows, where ``table_row`` is None, into ``slot``'s row),
        attending resume-style over the prefix + chunk. Non-final chunks
        (``sample=False``) leave the decode state untouched; the final chunk
        samples the first token and arms the slot exactly like the fresh
        prefill paths. ``counts_row`` [V] i32 is the host-side bincount of
        the FULL prompt; it rides this dispatch so the final chunk stays a
        single program launch."""
        positions = offset + jnp.arange(bucket, dtype=jnp.int32)[None, :]
        write, attn, mask = self.layout.chunk(table_row, slot, positions,
                                              offset, length)
        routed = None
        if self.own_forward:
            # the chunk goes on from the slot's state at ``offset``: zero at
            # 0 whatever the slot held (the arming program runs before the
            # LAST chunk, too late to zero it), rows past ``length`` leave
            # it be
            hidden, new_stack, rec, routed = self._forward_rec(
                params, tokens, positions, write, kv.stacked(), mask,
                state.rec, (jnp.arange(bucket) < length)[None, :],
                attn=attn, embeds=embeds, slot=slot, fresh=offset == 0)
            if routed is not None:
                # only the final chunk's token is copied to the host: the
                # routed work of the chunks before it waits in ``routed``
                # for that copy
                routed = rec["routed"] + routed
                rec = {**rec, "routed": jnp.zeros_like(routed) if sample
                       else routed}
            state = dataclasses.replace(state, rec=rec)
        else:
            rows = self.chunk_rows(bucket, sample)
            hidden, new_stack = self._forward(
                params, tokens, positions, write, kv.stacked(), mask,
                attn=attn, embeds=embeds,
                live=None if len(rows) == 1 else (
                    rows, kvc.attend_rung(length, rows)),
            )
        new_kv = self.layout.from_stacked(new_stack)
        if not sample:
            return new_kv, state, jnp.zeros((), jnp.int32)
        new_state, tok = self._first_token(
            params, state, hidden, length, slot, offset, counts_row)
        if routed is not None:
            return new_kv, new_state, jnp.concatenate([tok, routed])
        return new_kv, new_state, tok[0]

    @property
    def rides(self) -> bool:
        """Whether a prompt's small last chunk can ride a decode step
        (``_decode_prefill_paged_fn``): over the block pool, on one chip
        (a mesh keeps the chunk and the step two programs: the manual-TP
        trunk, GSPMD's slots over 'data'), for a model that runs
        ``models.llama.forward`` or a family whose module says that its own
        forward takes such a batch (``RIDES``: the contract,
        ``models.llama.family_module``)."""
        fam = mdl.family_module(self.cfg)
        return (self.paged and self.mesh is None
                and (fam is None or getattr(fam, "RIDES", False)))

    @scoped("ride")
    def _decode_prefill_paged_fn(self, params, kv, state: DecodeState, tables,
                                 tokens, length, offset, table_row, slot,
                                 counts_row, *, bucket: int):
        """A prompt's LAST chunk of at most ``RIDE_ROWS`` rows and the decode
        step the loop would launch behind it, as ONE program: one forward
        over the chunk's ``bucket`` rows and the S slots' rows side by side
        (``PagedLayout.ride``: the rows meet nowhere but in the attend, and
        there each goes where its own program sends it; a family's forward
        is told which rows are whose, ``ride``), so the embedding, the
        projections, the MLP or the experts and the head read their weights
        once for both. The step samples for the streams the state held as it
        stood: the new slot is not among them (its device table row,
        installed by the arming update in front, is put back on the trash
        block for the step's rows), and then the chunk's last real row
        samples the first token and arms the slot, as ``_prefill_paged_fn``
        does. What the step then the chunk leave, this leaves: pool, state,
        tokens. Returns the S tokens and the first token behind them,
        [S + 1], and behind those a routed model's count of the launch (the
        chunks' before it too, as a final chunk's)."""
        S = self.num_slots
        pos = state.positions
        positions = jnp.concatenate(
            [offset + jnp.arange(bucket, dtype=jnp.int32), pos])[None, :]
        write, attn, mask = self.layout.ride(
            kv, tables.at[slot].set(0), pos, table_row, slot,
            positions[:, :bucket], offset, length)
        tokens = jnp.concatenate([tokens[0], state.tokens])[None, :]
        routed = None
        if self.own_forward:
            hidden, new_stack, rec, routed = self._forward_rec(
                params, tokens, positions, write, kv.stacked(), mask,
                state.rec, jnp.concatenate(
                    [jnp.arange(bucket) < length, state.active])[None, :],
                attn=attn, slot=slot, fresh=offset == 0, ride=bucket)
            if routed is not None:
                routed = rec["routed"] + routed
                rec = {**rec, "routed": jnp.zeros_like(routed)}
            state = dataclasses.replace(state, rec=rec)
        else:
            hidden, new_stack = self._forward(
                params, tokens, positions, write, kv.stacked(), mask,
                attn=attn)
        last_h = jax.lax.dynamic_index_in_dim(hidden[0, :bucket], length - 1,
                                              keepdims=True)
        logits = mdl.logits_from_hidden(
            self.cfg, params, jnp.concatenate([hidden[0, bucket:], last_h]))
        state, step = self._decode_tail(params, state, None, logits[:S])
        state, tok = self._first_token(
            params, state, None, length, slot, offset, counts_row,
            logits=logits[S:])
        return (self.layout.from_stacked(new_stack), state,
                jnp.concatenate([step, tok] + ([] if routed is None
                                               else [routed])))

    def chunk_rows(self, bucket: int, last: bool = True) -> tuple[int, ...]:
        """The row counts a chunk program of ``bucket`` rows can run BEHIND
        THE ATTEND (the out product, the MLP, their reductions and
        residuals: ``models.llama._behind_attend``), the bucket last; the
        projections, the pool write and the attend always run the bucket.
        Under ``CHUNK_QUARTERED`` rows, over the contiguous cache and for a
        routed model (its family's own forward), the bucket alone.
        Otherwise, on one chip as over a mesh's 'model' axis, the program of
        a prompt's LAST chunk (the one that samples) runs as many QUARTERS
        of a bucket of ``CHUNK_QUARTERED`` rows or more as hold a real
        token, two at least (the ladder's buckets stand 1 : 4: a chunk with
        one live quarter took the bucket below). The rows behind them are
        padding: no row in front attends them and nothing downstream reads
        them. A branch a row count inside the one program
        (``models.llama._layer``), so no program is added; a chunk that is
        not the last holds ``prefill_chunk`` tokens, and where those fill
        the bucket's last quarter its program stays whole: the branches' way
        through HBM costs a full chunk ~1 ms of 41 on four chips and 0.5 of
        47 on one (PERF.md 6, PR 52 and PR 54)."""
        if (not self.paged or self.own_forward
                or bucket < CHUNK_QUARTERED or bucket % 4
                or not last and self.prefill_chunk > bucket // 4 * 3):
            return (bucket,)
        return tuple(bucket // 4 * q for q in (2, 3, 4))

    def chunk_parts(self, bucket: int, tokens: int, last: bool = True) -> int:
        """Quarters of its bucket a chunk of ``tokens`` real tokens runs
        behind the attend: host integers, by the expression of the program's
        switch (``kvcache.attend_rung``; the flight ring's ``chunk_parts``).
        1 where the program is not quartered."""
        rows = self.chunk_rows(bucket, last)
        if len(rows) == 1:
            return 1
        return rows[kvc.attend_rung(tokens, rows)] * 4 // bucket

    def chunk_span(self, offset: int, bucket: int) -> int:
        """Positions the attend of ``_prefill_paged_fn`` spans for a chunk of
        ``bucket`` rows behind ``offset`` cached tokens: the rung the program
        takes on the device, by the same arithmetic (the flight ring's
        ``chunk_ctx``)."""
        return self.layout.chunk_span(offset, bucket)

    def _prefill_paged_mm_fn(self, params, kv, state, tokens, length,
                             table_row, slot, mm_embeds, mm_positions,
                             counts_row, *, bucket: int):
        """Multimodal paged prefill: single-dispatch (never chunked — the
        scattered image embeddings must ride one program, mirroring
        _prefill_mm_fn), offset 0, always samples."""
        from localai_tpu.models import quant as qnt

        dtype = jnp.dtype(self.cfg.dtype)
        x = qnt.embed_rows(params["embed"], tokens, dtype)  # [1, bucket, D]
        x = x.at[0, mm_positions].set(mm_embeds.astype(dtype))
        return self._prefill_paged_fn(
            params, kv, state, tokens, length, jnp.zeros((), jnp.int32),
            table_row, slot, counts_row, bucket=bucket, sample=True,
            embeds=x,
        )

    @scoped("prefill")
    def _prefill_paged_sp_fn(self, params, kv, state, tokens, length,
                             table_row, slot, counts_row, *, bucket: int):
        """Sequence-parallel paged prefill: the prompt chunks over the
        'seq' mesh axis, each device runs blockwise ring attention
        (parallel.ring — composes with 'model'-sharded weights), and the
        resulting per-layer K/V scatters straight into the slot's reserved
        blocks through its table row. One dispatch, all chips, no gathered
        context. Always the FINAL (only) dispatch of its admission —
        samples and arms the slot exactly like the final chunk of
        _prefill_paged_fn. tokens: [bucket] i32 (1-D, like _prefill_sp_fn);
        only fresh admissions route here (offset 0 — shared/loaded prefix
        rows fall back to the chunked path)."""
        from localai_tpu.parallel import ring

        cfg = self.cfg
        # the ring attends the prompt's own bucket and gathers nothing from
        # the pool: no span to cut (the ring row's chunk_ctx is the bucket)
        hidden, (ks, vs) = ring.sp_prefill_forward(
            cfg, params, tokens, length, self.mesh, self.rope
        )
        # ks/vs [L, T, Hkv, hd] → scatter through the table row; padding
        # rows (t >= length) redirect to the trash block exactly like
        # kvcache.paged_prefill_write
        bt = self.block_tokens
        MB = table_row.shape[0]
        T = tokens.shape[0]
        t = jnp.arange(T)
        valid = t < length
        blk = jnp.where(valid, table_row[jnp.minimum(t // bt, MB - 1)], 0)
        off = t % bt
        # advanced indices (blk, off) around the head slice broadcast to
        # the FRONT: the set value is row-major [T, L, H, ...]
        if kv.quantized:
            # int4 pools (packed hd/2 last dim) take the nibble packer
            quant = (kvc._quant_chunk4
                     if kv.k.shape[-1] * 2 == ks.shape[-1]
                     else kvc._quant_chunk)
            kq, kscale = quant(ks)   # [L,T,H,hd or hd/2], [L,T,H]
            vq, vscale = quant(vs)
            new_kv = kvc.PagedKVCache(
                k=kv.k.at[:, blk, :, off].set(kq.transpose(1, 0, 2, 3)),
                v=kv.v.at[:, blk, :, off].set(vq.transpose(1, 0, 2, 3)),
                k_scale=kv.k_scale.at[:, blk, :, off].set(
                    kscale.transpose(1, 0, 2)),
                v_scale=kv.v_scale.at[:, blk, :, off].set(
                    vscale.transpose(1, 0, 2)),
            )
        else:
            kdt = kv.k.dtype
            new_kv = kvc.PagedKVCache(
                k=kv.k.at[:, blk, :, off].set(
                    ks.transpose(1, 0, 2, 3).astype(kdt)),
                v=kv.v.at[:, blk, :, off].set(
                    vs.transpose(1, 0, 2, 3).astype(kdt)),
            )
        new_state, tok = self._first_token(
            params, state, hidden, length, slot, counts_row=counts_row)
        return new_kv, new_state, tok[0]

    def _embed_fn(self, params, tokens, length, *, bucket: int):
        """Mean-pooled final hidden state over the real tokens — the LLM
        embeddings path (parity: llama.cpp embeddings mode behind the
        Embedding RPC, backend.proto:16; reference core/backend/
        embeddings.go:13). Uses a throwaway single-sequence KV so it never
        touches serving slots."""
        cfg = self.cfg
        if self.latent:
            # a throwaway latent pool of the bucket's blocks, one chunk at
            # offset 0 through the layout's own policy and attend
            kv, table_row = self.layout.scratch(bucket)
            positions = jnp.arange(bucket, dtype=jnp.int32)[None, :]
            write, attn, mask = self.layout.chunk(
                table_row, jnp.int32(0), positions, jnp.int32(0), length)
            hidden, *_ = self._forward_rec(
                params, tokens, positions, write, kv, mask,
                self._init_rec(1), (jnp.arange(bucket) < length)[None, :],
                attn=attn, slot=jnp.int32(0))
            return self._mean_pool(hidden, bucket, length)
        # throwaway scratch cache stays in the compute dtype even when the
        # serving cache is int8 — it is read back within the same program
        kv_shape = (cfg.cache_layers, 1, cfg.num_kv_heads, bucket, cfg.hd)
        kv = (jnp.zeros(kv_shape, jnp.dtype(cfg.dtype)),
              jnp.zeros(kv_shape, jnp.dtype(cfg.dtype)))
        positions = jnp.arange(bucket, dtype=jnp.int32)[None, :]
        mask = kvc.prefill_mask(cfg, bucket, length)
        write = kvc.prefill_write(jnp.int32(0), jnp.zeros((), jnp.int32))
        attn = self._prefill_attn(length) or self._se_attn(
            positions, positions[0])
        if self.kinds:      # the XLA attend under each kind's mask
            mask = {kind: kvc.prefill_mask(view, bucket, length)
                    for kind, view in kvc.kind_views(cfg)}
            attn = None
        if self.own_forward:
            hidden, *_ = self._forward_rec(
                params, tokens, positions, write, kv, mask,
                self._init_rec(1),
                (jnp.arange(bucket) < length)[None, :], attn=attn,
                slot=jnp.int32(0))
        else:
            hidden, _ = self._forward(
                params, tokens, positions, write, kv, mask, attn=attn,
            )
        return self._mean_pool(hidden, bucket, length)

    @staticmethod
    def _mean_pool(hidden, bucket: int, length):
        valid = (jnp.arange(bucket) < length)[None, :, None]
        # pool in f32: a bf16 sum over thousands of positions loses the
        # precision the embeddings exist to provide
        summed = jnp.sum((hidden * valid).astype(jnp.float32), axis=1)
        pooled = summed / jnp.maximum(length, 1).astype(jnp.float32)
        return pooled[0]

    def _se_attn(self, qpos, kpos):
        """Self-extend attend for the XLA paths (None when ga_n == 1) —
        the single construction point: the fresh prefill and the embedding
        here, the decode step and the chunk through the contiguous layout,
        which is handed it."""
        if self.ga_n <= 1:
            return None
        from localai_tpu.engine import selfextend as se

        return se.build_attend(
            self.cfg, self._se_rope, self.ga_n, self.ga_w,
            qpos=qpos, kpos=kpos,
        )

    def _forward(self, params, tokens, positions, write, stack, mask,
                 attn=None, embeds=None, live=None):
        """models.llama.forward, or the pipeline-parallel stage chain
        when the mesh has a 'pipe' axis (layer-sharded capacity scaling —
        parallel.pipeline; attn overrides don't apply there: pp forces the
        XLA attend and gates self-extend/Pallas off at init, and serves the
        contiguous cache alone, so the paged chunk's span attend never
        meets it)."""
        if self.pp_enabled:
            from localai_tpu.parallel import pipeline as pp

            return pp.pp_forward(
                self.cfg, params, tokens, positions, write, stack, mask,
                self.rope, self.mesh, embeds=embeds,
            )
        return mdl.forward(
            self.cfg, params, tokens, positions, write, stack, mask,
            self.rope, attn=attn, embeds=embeds, live=live,
        )

    def _forward_rec(self, params, tokens, positions, write, stack, mask,
                     rec, valid, attn=None, embeds=None, slot=None,
                     fresh=None, ride: int = 0):
        """The forward of a family module (``models.llama.family_module``,
        which states the contract): every one takes these arguments and
        returns the hidden states and the K/V stack as ``_forward`` does,
        then ``rec`` with its own entries renewed and the launch's routed
        work [experts touched, token-expert pairs that landed here] (None
        from a family that routes nothing). One with ``cfg.attn_kinds`` is
        handed a mask and an attend a kind of layer; ``ride`` is passed to
        a family that ``rides``, in a ride, and to no other."""
        return mdl.family_module(self.cfg).forward(
            self.cfg, params, tokens, positions, write, stack, mask,
            self.rope, attn=attn, embeds=embeds, rec=rec, valid=valid,
            slot=slot, fresh=fresh, kernels=self.family_kernels,
            **({"ride": ride} if ride else {}))

    def _prefill_attn(self, length):
        """Pallas flash attention for the prefill/embed paths (None = XLA)."""
        if self.attn_impl != "pallas":
            return None
        from localai_tpu import ops

        cfg = self.cfg
        kernel = partial(
            ops.prefill_attention,
            sliding_window=cfg.sliding_window,
            interpret=self._attn_interpret,
        )
        if self.mesh is not None:
            from jax.sharding import PartitionSpec as P

            # single-sequence prefill: only the head dim shards ('model');
            # each device runs flash attention over its head group
            kernel = shard_map(
                kernel,
                mesh=self.mesh,
                in_specs=(P(None, "model", None), P("model", None, None),
                          P("model", None, None), P()),
                out_specs=P(None, "model", None),
                check_vma=False,
            )

        @scoped("attn.prefill")
        def attn(q, keys, values, _mask):  # q [1,T,Hq,hd], keys [1,Hkv,T,hd]
            out = kernel(q[0], keys[0], values[0], length)
            return out[None]

        return attn

    # -- host API --------------------------------------------------------

    def bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(
            f"prompt length {n} exceeds max prefill bucket {self.buckets[-1]}"
        )

    def acquire_slot(self, slot: Optional[int] = None) -> Optional[int]:
        """Claim a free slot — FIFO by default, or a specific free slot
        (the scheduler targets the slot with the longest reusable prefix)."""
        if not self._free_slots:
            return None
        if slot is not None and slot in self._free_slots:
            self._free_slots.remove(slot)
            return slot
        return self._free_slots.pop(0)

    def free_slots(self) -> list[int]:
        return list(self._free_slots)

    def admit(
        self,
        slot: int,
        prompt: list[int],
        *,
        temperature: Optional[float] = None,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        min_p: Optional[float] = None,
        repeat_penalty: Optional[float] = None,
        presence_penalty: Optional[float] = None,
        frequency_penalty: Optional[float] = None,
        seed: Optional[int] = None,
        logit_bias: Optional[dict[int, float]] = None,
        bias_row: Optional[np.ndarray] = None,
        mm_embeds: Optional[np.ndarray] = None,    # [n_mm, D] image embeds
        mm_positions: Optional[np.ndarray] = None,  # [n_mm] prompt positions
        resident: Optional[list[int]] = None,       # slot's previous tokens
                                                    # (enables prefix reuse)
        valid_n: Optional[int] = None,              # slot's KV frontier, from
                                                    # free_frontiers() (None →
                                                    # read it here)
        reserve_tokens: Optional[int] = None,       # paged mode: worst-case
                                                    # rows (prompt + max_new)
                                                    # to reserve; None → max_ctx
        spec_tokens: int = 0,                       # paged mode: extra
                                                    # speculation-lookahead rows
                                                    # (localai_tpu.spec);
                                                    # ignored contiguous
    ) -> int:
        """Prefill a prompt into a slot; returns the first sampled token.

        When ``resident`` is given and shares a long-enough prefix with the
        prompt, the prefix KV is kept and only the tail is prefilled
        (parity: llama.cpp common_part slot reuse, grpc-server.cpp:67-74).
        Callers that already hold a free_frontiers() snapshot pass
        ``valid_n``, so that a contiguous admission reads the device once."""
        if not prompt:
            prompt = [0]
        n = len(prompt)
        if n > self.max_ctx - 1:
            # context-exhaustion policy parity (grpc-server.cpp:1573-1592):
            # reject rather than silently shift context.
            raise ValueError(f"prompt ({n} tokens) exceeds context {self.max_ctx}")
        if self.paged:
            adm = self.begin_admit(
                slot, prompt,
                reserve_tokens=reserve_tokens,
                spec_tokens=spec_tokens,
                resident=resident, valid_n=valid_n,
                mm_embeds=mm_embeds, mm_positions=mm_positions,
                temperature=temperature, top_k=top_k, top_p=top_p,
                min_p=min_p, repeat_penalty=repeat_penalty,
                presence_penalty=presence_penalty,
                frequency_penalty=frequency_penalty,
                seed=seed, logit_bias=logit_bias, bias_row=bias_row,
            )
            if adm is None:
                raise RuntimeError(
                    "KV block pool exhausted: cannot reserve "
                    f"{len(prompt)} prompt tokens (direct admit has no "
                    "queue; size the pool via engine.kv_num_blocks or admit "
                    "through the scheduler)")
            while True:
                tok = adm.step_chunk()
                if tok is not None:
                    return tok
        lcp = 0
        if resident and mm_embeds is None:
            lcp = self.reusable_prefix(slot, resident, prompt, valid_n)
        self.last_prefix_reused = lcp
        self.total_prefix_reused += lcp
        tail = prompt[lcp:]
        bucket = (self._resume_bucket(len(tail), lcp) if lcp
                  else self.bucket_for(n))
        padded = np.zeros((1, bucket), np.int32)
        padded[0, : len(tail)] = tail
        self._arm(self._arm_args(
            slot, temperature=temperature, top_k=top_k, top_p=top_p,
            min_p=min_p, repeat_penalty=repeat_penalty,
            presence_penalty=presence_penalty,
            frequency_penalty=frequency_penalty,
            seed=seed, logit_bias=logit_bias, bias_row=bias_row,
        ))
        self.admit_programs += 1        # the prefill below
        n_seq = self.mesh.shape.get("seq", 1) if self.mesh is not None else 1
        use_sp = (
            self.sp_enabled and not lcp and mm_embeds is None
            and n >= self.sp_threshold and bucket % n_seq == 0
        )
        if use_sp:
            self.last_prefill_path = "sp"
            self.kv, self.state, tok = self._prefill_sp(
                self.params, self.kv, self.state,
                jnp.asarray(padded[0]), jnp.int32(n), jnp.int32(slot),
                bucket=bucket,
            )
        elif lcp:
            self.last_prefill_path = "resume"
            crow = _prompt_counts_row(self.cfg.vocab_size, prompt)
            self.kv, self.state, tok = self._prefill_paged(
                self.params, self.kv, self.state,
                jnp.asarray(padded), jnp.int32(len(tail)), jnp.int32(lcp),
                None, jnp.int32(slot), jnp.asarray(crow), bucket=bucket,
                sample=True,
            )
        elif mm_embeds is not None and len(mm_embeds):
            self.last_prefill_path = "mm"
            self.kv, self.state, tok = self._prefill_mm(
                self.params, self.kv, self.state,
                jnp.asarray(padded), jnp.int32(n), jnp.int32(slot),
                jnp.asarray(mm_embeds, jnp.float32),
                jnp.asarray(mm_positions, jnp.int32),
                bucket=bucket,
            )
        else:
            self.last_prefill_path = "full"
            self.kv, self.state, tok = self._prefill(
                self.params, self.kv, self.state,
                jnp.asarray(padded), jnp.int32(n), jnp.int32(slot),
                bucket=bucket,
            )
        self._active_slots.add(slot)
        # the first sampled token seeds the host-side stream state; this
        # one admit-time sync is the prefill/decode handoff point (guarded:
        # a device that never answers would otherwise hang here silently)
        with self.watchdog.guard("device"):
            return int(tok)  # jaxlint: disable=host-sync-in-hot-path

    def _arm_args(self, slot: int, *, seed=None, logit_bias=None,
                  bias_row=None, **sampling) -> tuple:
        """The host's half of arming a slot: ``_arm_slot_fn``'s arguments
        after the state and the tables, but for the table row. Sampling
        parameters left None reset to the engine's defaults, so a reused
        slot never inherits the previous request's; the seed is cut to
        32 bits as ``jax.random.key`` cuts a Python int."""
        ints, floats = smp.SamplingParams.pack(**sampling)
        head = [slot, seed is not None,
                np.int64(seed or 0).astype(np.int32)]
        if bias_row is not None:
            row = np.asarray(bias_row, np.float32).copy()
        else:
            row = np.zeros(self.cfg.vocab_size, np.float32)
        if logit_bias:
            for tid, b in logit_bias.items():
                if 0 <= int(tid) < self.cfg.vocab_size:
                    row[int(tid)] += b
        return (np.concatenate([np.array(head, np.int32), ints]), floats,
                row)

    def _arm(self, args: tuple, table_row=None) -> None:
        """Dispatch the one program that arms a slot (``_arm_slot_fn``)."""
        self.state, self.block_tables = self._arm_slot(
            self.state, self.block_tables, *args, table_row)
        self.admit_programs += 1

    # -- paged admission (chunked prefill; engine.paged) -----------------

    def begin_admit(
        self, slot: int, prompt: list[int], *,
        reserve_tokens: Optional[int] = None,
        spec_tokens: int = 0,
        resident: Optional[list[int]] = None,
        valid_n: Optional[int] = None,
        mm_embeds=None, mm_positions=None,
        **sampling,
    ) -> Optional["PagedAdmission"]:
        """Start a chunked paged admission: reserve blocks (sharing pooled
        prefix blocks where the prompt allows), prepare on the host what
        arms the slot (dispatched with the final chunk: nothing runs on the
        device here), and return a PagedAdmission whose ``launch_chunk()``
        the caller drives — interleaving chunk dispatches with decode
        dispatches so one long prompt never stalls other slots' TPOT.
        ``spec_tokens`` reserves extra speculation rows past the decode
        worst case (a draft window writes up to gamma rows beyond the
        frontier; see localai_tpu.spec) — recorded separately by the
        allocator so rollback accounting is auditable. Returns None when
        the pool cannot cover the reservation (the scheduler keeps the
        request queued)."""
        assert self.paged, "begin_admit requires a paged runner"
        snapshot = None
        if not prompt:
            prompt = [0]
        n = len(prompt)
        if n > self.max_ctx - 1:
            raise ValueError(
                f"prompt ({n} tokens) exceeds context {self.max_ctx}")
        reserve = min(self.max_ctx, max(n + 1, reserve_tokens
                                        or self.max_ctx))
        # the speculation lookahead never needs rows past max_ctx (the
        # write policy trash-redirects there and the scheduler gates
        # windows off near the edge)
        spec_tokens = max(0, min(int(spec_tokens), self.max_ctx - reserve))
        if self.allocator.blocks_for(
                reserve + spec_tokens) > self.allocator.num_blocks - 1:
            # can NEVER fit, even with an empty pool (an overcommitted
            # kv_num_blocks): reject like the prompt-exceeds-context
            # check — holding it would head-of-line block admission forever
            raise ValueError(
                f"reservation of {reserve + spec_tokens} tokens "
                f"({self.allocator.blocks_for(reserve + spec_tokens)} "
                f"blocks) exceeds the block pool "
                f"({self.allocator.num_blocks - 1} blocks); "
                "lower max_new_tokens or raise engine.kv_num_blocks")
        mm = mm_embeds is not None and len(mm_embeds) > 0
        lcp = 0
        if resident and not mm and self._loaded_rows.get(slot):
            # rows just loaded from the disk prompt cache (load_prefix) —
            # the only slot-resident reuse paged mode has; pool sharing
            # covers everything else
            lcp = self.reusable_prefix(slot, resident, prompt, valid_n)
        if lcp:
            if not self.allocator.extend(slot, reserve,
                                         spec_tokens=spec_tokens):
                self.allocator.release(slot)
                self._loaded_rows.pop(slot, None)
                return None
            self.last_prefill_path = "paged_resume"
        else:
            if slot in self.allocator.tables:  # stale loaded rows
                self.allocator.release(slot)
            self._loaded_rows.pop(slot, None)
            # blocks of a prefix are shared by the prompt's tokens; a model
            # with recurrent state shares a chain that ends on a snapshot
            # of that state (the allocator's ``match_prefix``)
            shared = self.allocator.allocate(
                slot, reserve, prompt=None if mm else prompt,
                spec_tokens=spec_tokens)
            if shared is None:
                return None
            lcp = shared
            if self.recurrent and not mm:
                snapshot = self.allocator.begin_snapshot(
                    slot, prompt, shared, self.prefill_chunk)
            self.last_prefill_path = ("paged_mm" if mm
                                      else "paged_shared" if shared
                                      else "paged")
        self.last_prefix_reused = lcp
        self.total_prefix_reused += lcp
        # long fresh prompts on a 'seq' mesh take the ring-attention path:
        # one dispatch over all chips writing straight into the reserved
        # blocks (shared/loaded prefix rows need the resume-style chunk
        # attention, so any lcp keeps the chunked path)
        n_seq = self.mesh.shape.get("seq", 1) if self.mesh is not None else 1
        use_sp = (self.sp_enabled and not mm and lcp == 0
                  and n >= self.sp_threshold
                  and self.bucket_for(n) % n_seq == 0)
        if use_sp:
            self.last_prefill_path = "paged_sp"
        return PagedAdmission(self, slot, list(prompt), lcp,
                              self._arm_args(slot, **sampling),
                              mm_embeds=mm_embeds,
                              mm_positions=mm_positions, sp=use_sp,
                              restore=self.allocator.restore_row.get(slot),
                              snapshot=snapshot)

    def _install_table_row(self, slot: int) -> None:
        self.block_tables = self.block_tables.at[slot].set(
            jnp.asarray(self.allocator.table_row(slot)))

    def _finish_paged_admit(self, slot: int, prompt: list[int],
                            mm: bool) -> None:
        """Final-chunk bookkeeping, all of it the host's (the arming update
        exposed the block table to the decode programs): publish the
        prompt's full blocks to the prefix pool (their contents are
        dispatched by now; token-keyed sharing is meaningless for
        multimodal prompts), mark the slot live."""
        # (a model with recurrent state: a prompt whose state was kept at
        # its boundary; blocks that end on no snapshot serve nobody)
        if not mm and (not self.recurrent
                       or self.allocator.snapshot_pending(slot)):
            self.allocator.register_prefix(slot, prompt)
        self._loaded_rows.pop(slot, None)
        self._active_slots.add(slot)

    def reusable_prefix(self, slot: int, resident: Optional[list[int]],
                        prompt: list[int],
                        valid_n: Optional[int] = None) -> int:
        """Tokens of ``resident`` (the slot's previous prompt+generation)
        that admit() would actually reuse for ``prompt`` — all feasibility
        gates applied: KV-validity clipping (the last sampled token's KV is
        never written), last-token recompute, minimum worthwhile length,
        and the tail bucket fitting inside the context. The scheduler ranks
        candidate slots with this same function so its choice can't
        collapse to zero at admit time. ``valid_n`` overrides the KV
        validity frontier (disk prompt-cache hits score their own row count
        instead of the slot's current position)."""
        if not resident or not prompt or self.recurrent:
            # a SLOT's resident rows, refused for recurrent state: the keys
            # of a prefix can be read again, the state after it was not
            # kept (the pool's shared prefixes keep it: engine.paged)
            return 0
        if valid_n is None:
            valid_n = (self._loaded_rows.get(slot, 0) if self.paged
                       else self.slot_position(slot))
        valid = resident[:valid_n]
        lcp = 0
        for a, b in zip(valid, prompt):
            if a != b:
                break
            lcp += 1
        # always recompute at least the last token (its logits seed sampling)
        lcp = min(lcp, len(prompt) - 1)
        if lcp < self.prefix_reuse_min:
            return 0
        if self.paged:
            # chunked writes redirect bucket overshoot to the trash block,
            # so any in-context tail is feasible — no bucket-fit gate
            return lcp
        if self._resume_bucket(len(prompt) - lcp, lcp) is None:
            return 0
        return lcp

    def resident_rows(self, slot: int, default: int) -> int:
        """KV rows of ``slot`` that are actually resident for prefix reuse.
        Contiguous mode: the device frontier the caller already read
        (``default``). Paged mode: blocks are freed at release, so only
        rows just loaded from the disk prompt cache count."""
        if not self.paged:
            return default
        return min(default, self._loaded_rows.get(slot, 0))

    def _resume_bucket(self, tail_len: int, offset: int) -> Optional[int]:
        """Smallest prefill bucket holding the tail that also fits in the
        cache past the kept prefix (dynamic_update_slice clamps start
        indices, so an overhanging bucket would silently shift the write)."""
        for b in self.buckets:
            if tail_len <= b and offset + b <= self.max_ctx:
                return b
        return None

    def step(self) -> np.ndarray:
        """One decode iteration over all slots; returns sampled tokens [S].

        Synchronous by contract — the blocking host read IS the API
        (constraint gating needs the token before the next dispatch);
        pipelined callers use step_async()."""
        t0 = time.perf_counter()
        tokens = self.step_async()
        t1 = time.perf_counter()
        with self.watchdog.guard("device"):
            out = np.asarray(tokens)  # jaxlint: disable=host-sync-in-hot-path
        self.last_launch_ms = (t1 - t0) * 1e3
        self.last_sync_ms = (time.perf_counter() - t1) * 1e3
        return out

    def step_async(self) -> jax.Array:
        """Like step() but returns the device array without synchronizing —
        callers overlap the host read with the next dispatch."""
        self.kv, self.state, tokens = self._decode_paged(
            self.params, self.kv, self.state, self.block_tables
        )
        return tokens

    def verify_async(self, proposals) -> jax.Array:
        """One speculative verify dispatch over all slots: score the
        [S, gamma] draft ``proposals`` with a single gamma+1-wide target
        forward, accept/sample on device, and return the [gamma+1, S]
        emitted-token device array (SKIP = nothing for that step/slot).
        Works on both KV layouts; over the pool the window is written
        through the block-table mirror and rejected tails roll back
        per slot. No host sync — callers overlap the read."""
        # (a rejected draft token has already moved the recurrent state it
        # met; the verify window has one attend for all layers, and none
        # over latent rows)
        mdl.refuse(self.cfg, [("speculative decoding", True)])
        proposals = jnp.asarray(proposals, jnp.int32)
        self.kv, self.state, emitted = self._verify_paged(
            self.params, self.kv, self.state, self.block_tables, proposals,
        )
        return emitted

    def step_n(self, n: int) -> np.ndarray:
        """n decode iterations in one dispatch; returns tokens [n, S].
        Synchronous by contract — see step(); hot callers use
        step_n_async()."""
        t0 = time.perf_counter()
        tokens = self.step_n_async(n)
        t1 = time.perf_counter()
        with self.watchdog.guard("device"):
            out = np.asarray(tokens)  # jaxlint: disable=host-sync-in-hot-path
        self.last_launch_ms = (t1 - t0) * 1e3
        self.last_sync_ms = (time.perf_counter() - t1) * 1e3
        return out

    def step_n_async(self, n: int) -> jax.Array:
        """Like step_n() but returns the [n, S] device array without
        synchronizing — callers overlap the host read with later dispatches."""
        self.kv, self.state, tokens = self._decode_paged_n(
            self.params, self.kv, self.state, self.block_tables, n=n
        )
        return tokens

    def step_frozen_n(self, freeze: np.ndarray, n: int) -> np.ndarray:
        """n decode iterations where ``freeze``-masked slots advance only on
        the first; returns tokens [n, S] (rows 1+ stale for frozen slots)."""
        t0 = time.perf_counter()
        self.kv, self.state, tokens = self._decode_paged_frozen_n(
            self.params, self.kv, self.state, self.block_tables,
            jnp.asarray(freeze, jnp.bool_), n=n,
        )
        # synchronous by contract: the frozen slots' constraint masks need
        # the sampled token on the host before the next dispatch
        t1 = time.perf_counter()
        with self.watchdog.guard("device"):
            out = np.asarray(tokens)  # jaxlint: disable=host-sync-in-hot-path
        self.last_launch_ms = (t1 - t0) * 1e3
        self.last_sync_ms = (time.perf_counter() - t1) * 1e3
        return out

    def embed(self, prompt: list[int]) -> np.ndarray:
        """[D] float32 embedding of a token sequence (bucketed like prefill)."""
        if not prompt:
            prompt = [0]
        n = len(prompt)
        if n > self.max_ctx:
            raise ValueError(f"input ({n} tokens) exceeds context {self.max_ctx}")
        bucket = self.bucket_for(n)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = prompt
        out = self._embed(
            self.params, jnp.asarray(padded), jnp.int32(n), bucket=bucket
        )
        return np.asarray(out, dtype=np.float32)

    def set_bias(self, slot: int, bias_row: Optional[np.ndarray]) -> None:
        """Replace one slot's [V] additive logit-bias row (grammar masks write
        -1e30 at disallowed ids; None clears)."""
        if bias_row is None:
            row = jnp.zeros(self.cfg.vocab_size, jnp.float32)
        else:
            row = jnp.asarray(bias_row, jnp.float32)
        self.state = dataclasses.replace(
            self.state, bias=self.state.bias.at[slot].set(row)
        )

    def release(self, slot: int) -> None:
        if self.allocator is not None:
            # free the slot's blocks (prompt blocks registered in the
            # prefix pool survive as reclaimable cache); the program below
            # points the device table row at the trash block
            self.allocator.release(slot)
            self._loaded_rows.pop(slot, None)
        self.state, self.block_tables = self._release_slot(
            self.state, self.block_tables, np.int32(slot))
        self._active_slots.discard(slot)
        if slot not in self._free_slots:
            self._free_slots.append(slot)

    @property
    def paged_kv_write_impl(self) -> str:
        """Who writes a decode step's new K/V rows into the cache (the
        layout's ``kv_write_impl``): ``kernel`` or the policy's
        ``scatter``."""
        return self.layout.kv_write_impl

    @property
    def state_bytes(self) -> int:
        """Bytes of per-slot recurrent state held beside the K/V pool (0
        for a model whose layers carry none)."""
        if not self.recurrent:
            return 0
        return sum(a.nbytes for a in self.state.rec.values())

    @property
    def any_active(self) -> bool:
        # host mirror — admit()/release() are the only transitions, so no
        # device round-trip (and no stall behind in-flight decodes)
        return bool(self._active_slots)

    def free_frontiers(self) -> np.ndarray:
        """[S] KV frontier of every FREE slot (entries of slots in use mean
        nothing): how many of a slot's rows a new prompt could reuse. The
        admission path ranks the free slots by it. A paged runner knows it
        on the host: a released slot's blocks are gone (0), and a free
        slot holds rows only where ``load_prefix`` has just put them
        (``_loaded_rows``), exactly what the device's ``positions`` say
        there. A contiguous runner's rows outlive a release, and only the
        device knows how far a finished stream got: the read stays."""
        if not self.paged:
            return self.slot_positions()
        out = np.zeros(self.num_slots, np.int32)
        for slot, n in self._loaded_rows.items():
            out[slot] = n
        return out

    def slot_positions(self) -> np.ndarray:
        """Every slot's KV frontier as the device holds it, in ONE [S]
        transfer: a BLOCKING read of the state the newest dispatch returns,
        so it waits for every dispatch in flight and leaves the device with
        nothing queued. For tests, tools and the contiguous layout's
        admission (``free_frontiers``); never on a paged admission."""
        with self.watchdog.guard("device"):
            return np.asarray(  # jaxlint: disable=host-sync-in-hot-path
                self.state.positions
            )

    def slot_position(self, slot: int) -> int:
        return int(self.slot_positions()[slot])

    # -- prompt-cache persistence (engine.promptcache) -------------------

    def pack_block(self, bid: int) -> Optional[dict]:
        """One pool block's raw rows as host numpy — the HBM→host spill
        payload (BlockAllocator tiering). Rows keep the pool dtype
        byte-exact: bf16 stays bf16, int4 stays nibble-packed (half the
        f32 bytes), so spill→reload is an identity round-trip."""
        if self.allocator is None:
            return None
        kv = self.kv
        if self.latent:
            return self.layout.pack_block(kv, bid)
        out = {"k": np.asarray(kv.k[:, bid]), "v": np.asarray(kv.v[:, bid])}
        if kv.quantized:
            out["k_scale"] = np.asarray(kv.k_scale[:, bid])
            out["v_scale"] = np.asarray(kv.v_scale[:, bid])
        return out

    def load_block(self, bid: int, payload: dict) -> None:
        """Scatter a spilled block's rows back into pool block ``bid``
        (tier re-onboarding; inverse of :meth:`pack_block`)."""
        kv = self.kv
        if self.latent:
            self.kv = self.layout.load_block(kv, bid, payload)
            return
        new = {
            "k": kv.k.at[:, bid].set(jnp.asarray(payload["k"], kv.k.dtype)),
            "v": kv.v.at[:, bid].set(jnp.asarray(payload["v"], kv.v.dtype)),
        }
        if kv.quantized:
            new["k_scale"] = kv.k_scale.at[:, bid].set(
                jnp.asarray(payload["k_scale"], jnp.float32))
            new["v_scale"] = kv.v_scale.at[:, bid].set(
                jnp.asarray(payload["v_scale"], jnp.float32))
        self.kv = kvc.PagedKVCache(**new)

    def snapshot_prefix(self, slot: int, n: Optional[int] = None) -> dict:
        """Device-array snapshot of one slot's first ``n`` KV rows.

        The slices are NEW device buffers enqueued in program order, so the
        snapshot is consistent even though later dispatches donate and
        overwrite the cache — callers may hand it to another thread and
        materialize it there (pack_prefix) without stalling the engine."""
        p = n if n is not None else self.slot_position(slot)
        out: dict = {"kv_dtype": str(self.kv_dtype),
                     # self-extend caches store UNroped K — a roped-cache
                     # runner must never load these rows (and vice versa)
                     "kv_rope": "raw" if self.ga_n > 1 else "roped"}
        if self.paged:
            # gather the slot's blocks back into contiguous [L, H, p, ...]
            # rows — the export format is layout-independent, so paged and
            # contiguous runners can share one disk prompt cache
            bt = self.block_tokens
            table = self.allocator.tables.get(slot, [])
            nb = min(max(1, -(-p // bt)), len(table)) if table else 0
            if nb == 0:
                p = 0
                blocks = np.zeros(1, np.int64)
            else:
                p = min(p, nb * bt)
                blocks = np.asarray(table[:nb], np.int64)

            if self.latent:     # the rows' real lanes, [L, p, W]
                out.update(self.layout.export_rows(self.kv, blocks, p))
                return out

            def rows(cache):  # [L, N, H, bt, hd] -> [L, H, p, hd]
                g = cache[:, blocks]
                L, _, H = g.shape[0], g.shape[1], g.shape[2]
                return g.transpose(0, 2, 1, 3, 4).reshape(
                    L, H, len(blocks) * bt, cache.shape[-1])[:, :, :p]

            def srows(sc):    # [L, N, H, bt] -> [L, H, p]
                g = sc[:, blocks]
                L, H = g.shape[0], g.shape[2]
                return g.transpose(0, 2, 1, 3).reshape(
                    L, H, len(blocks) * bt)[:, :, :p]

            out["k"] = rows(self.kv.k)
            out["v"] = rows(self.kv.v)
            if self.kv.quantized:
                out["k_scale"] = srows(self.kv.k_scale)
                out["v_scale"] = srows(self.kv.v_scale)
            return out
        out["k"] = self.kv.k[:, slot, :, :p]
        out["v"] = self.kv.v[:, slot, :, :p]
        if self.kv.quantized:
            out["k_scale"] = self.kv.k_scale[:, slot, :, :p]
            out["v_scale"] = self.kv.v_scale[:, slot, :, :p]
        return out

    @staticmethod
    def pack_prefix(snapshot: dict) -> dict:
        """Materialize a snapshot_prefix result as npz-serializable numpy.
        bfloat16 rows are stored as uint16 bit-views (numpy's npz format
        has no native bfloat16); scaled-int8 caches keep their scales."""
        out: dict = {"kv_dtype": np.asarray(snapshot["kv_dtype"]),
                     "kv_rope": np.asarray(snapshot.get("kv_rope", "roped"))}
        # k, v and their scales; a latent pool's arrays (c, w, i)
        for name in ("k", "v", "k_scale", "v_scale", "c", "w", "i"):
            if name not in snapshot:
                continue
            host = np.asarray(snapshot[name])
            if host.dtype.name == "bfloat16":
                out[name] = host.view(np.uint16)
                out[f"{name}_bf16"] = _ONE
            else:
                out[name] = host
        return out

    def export_prefix(self, slot: int, n: Optional[int] = None) -> dict:
        """snapshot_prefix + pack_prefix in one (synchronous) call."""
        return self.pack_prefix(self.snapshot_prefix(slot, n))

    def load_prefix(self, slot: int, arrays: dict, n: int) -> bool:
        """Write exported KV rows into a slot and set its frontier to ``n``
        (admit() then reuses them via the resident/resume path). Returns
        False on any mismatch (dtype, shape, context) — callers fall back
        to a full prefill."""
        if mdl.unserved(self.cfg, "the prompt cache's import"):
            log.warning("%s", mdl.refusal(
                self.cfg, "the prompt cache's import"))
            return False
        if str(arrays.get("kv_dtype")) != str(self.kv_dtype):
            return False
        want_rope = "raw" if self.ga_n > 1 else "roped"
        if str(arrays.get("kv_rope", "roped")) != want_rope:
            return False
        if n > self.max_ctx - 1:
            return False

        def unpack(name):
            host = arrays[name]
            if f"{name}_bf16" in arrays:
                import ml_dtypes

                host = host.view(ml_dtypes.bfloat16)
            return host

        if self.latent:
            names = self.layout.widths
            return all(k in arrays for k in names) and (
                self._load_prefix_paged(
                    slot, {k: unpack(k) for k in names}, n))
        k, v = unpack("k"), unpack("v")
        L, H, hd = self.cfg.cache_layers, self.cfg.num_kv_heads, self.cfg.hd
        if str(self.kv_dtype) == "int4":
            hd //= 2  # int4 exports stay nibble-packed along head_dim
        if k.shape != (L, H, n, hd) or v.shape != (L, H, n, hd):
            return False
        if self.paged:
            return self._load_prefix_paged(slot, arrays, n, k, v)
        kv = self.kv
        new = {
            "k": kv.k.at[:, slot, :, :n].set(jnp.asarray(k, kv.k.dtype)),
            "v": kv.v.at[:, slot, :, :n].set(jnp.asarray(v, kv.v.dtype)),
        }
        if kv.quantized:
            if "k_scale" not in arrays or "v_scale" not in arrays:
                return False
            new["k_scale"] = kv.k_scale.at[:, slot, :, :n].set(
                jnp.asarray(arrays["k_scale"], jnp.float32))
            new["v_scale"] = kv.v_scale.at[:, slot, :, :n].set(
                jnp.asarray(arrays["v_scale"], jnp.float32))
        self.kv = KVCache(**new)
        self.state = dataclasses.replace(
            self.state,
            positions=self.state.positions.at[slot].set(n),
            active=self.state.active.at[slot].set(False),
        )
        self._active_slots.discard(slot)
        return True

    @staticmethod
    def _import_rows(kv, blk, off, arrays: dict, k, v):
        """The block pool with exported rows at (``blk``, ``off``) [n]."""
        # advanced indices (blk, off) around the head slice broadcast to
        # the FRONT: the set value is row-major [n, L, H, ...]
        new = {
            "k": kv.k.at[:, blk, :, off].set(
                jnp.asarray(k, kv.k.dtype).transpose(2, 0, 1, 3)),
            "v": kv.v.at[:, blk, :, off].set(
                jnp.asarray(v, kv.v.dtype).transpose(2, 0, 1, 3)),
        }
        if kv.quantized:
            new["k_scale"] = kv.k_scale.at[:, blk, :, off].set(
                jnp.asarray(arrays["k_scale"],
                            jnp.float32).transpose(2, 0, 1))
            new["v_scale"] = kv.v_scale.at[:, blk, :, off].set(
                jnp.asarray(arrays["v_scale"],
                            jnp.float32).transpose(2, 0, 1))
        return kvc.PagedKVCache(**new)

    def _load_prefix_paged(self, slot: int, arrays: dict, n: int,
                           k: Optional[np.ndarray] = None,
                           v: Optional[np.ndarray] = None) -> bool:
        """Paged load_prefix tail: scatter the exported contiguous rows
        into freshly allocated blocks and mark them slot-resident
        (``_loaded_rows``) so begin_admit can resume past them."""
        kv = self.kv
        if kv.quantized and ("k_scale" not in arrays
                             or "v_scale" not in arrays):
            return False
        if slot in self.allocator.tables:
            self.allocator.release(slot)
        self._loaded_rows.pop(slot, None)
        if self.allocator.allocate(slot, n) is None:
            return False
        bt = self.block_tokens
        table = np.asarray(self.allocator.tables[slot], np.int64)
        pos = np.arange(n)
        blk = jnp.asarray(table[pos // bt], jnp.int32)
        off = jnp.asarray(pos % bt, jnp.int32)
        if self.latent:
            loaded = self.layout.import_rows(kv, blk, off, arrays, n)
            if loaded is None:      # not this pool's rows
                self.allocator.release(slot)
                return False
            self.kv = loaded
        else:
            self.kv = self._import_rows(kv, blk, off, arrays, k, v)
        self._install_table_row(slot)
        self._loaded_rows[slot] = n
        self.state = dataclasses.replace(
            self.state,
            positions=self.state.positions.at[slot].set(n),
            active=self.state.active.at[slot].set(False),
        )
        self._active_slots.discard(slot)
        return True


class PagedAdmission:
    """One in-flight chunked paged admission (ModelRunner.begin_admit).

    The scheduler drives ``launch_chunk()`` from its engine loop,
    interleaving chunk dispatches with decode dispatches; direct callers
    (tests, tools) loop ``step_chunk()``. Only the FINAL chunk samples: the
    one arming update goes out in front of it (sampling state, bias row,
    the slot's device block-table row), the chunk arms the slot on the
    device, and the first token's copy to the host starts at once
    (``first``). No call here waits for the device but ``first_token()``."""

    def __init__(self, runner: ModelRunner, slot: int, prompt: list[int],
                 start: int, arm_args: tuple, mm_embeds=None,
                 mm_positions=None, sp: bool = False,
                 restore: Optional[int] = None,
                 snapshot: Optional[tuple[int, int]] = None):
        self.runner = runner
        # a model with recurrent state: the snapshot row the shared prefix's
        # state is restored from in front of the first chunk, and (row,
        # position) of the snapshot this prompt leaves, where it leaves one
        # (``BlockAllocator.begin_snapshot``: its last whole prefill chunk):
        # a chunk ends at that position, as chunks behind a prefix of whole
        # chunks do anyway, and the state behind it is copied
        self.restore = restore
        self.snapshot = snapshot
        self.slot = slot
        self.prompt = prompt
        self.pos = start                     # next position to prefill
        self.prefix_reused = start           # shared/loaded rows (telemetry)
        self.path = runner.last_prefill_path
        self.arm_args = arm_args             # ModelRunner._arm_args
        self.mm = mm_embeds is not None and len(mm_embeds) > 0
        self.mm_embeds = mm_embeds
        self.mm_positions = mm_positions
        self.sp = sp                         # ring-attention one-shot path
        self.first: Optional[jax.Array] = None   # the final chunk's sample
        self.rode = False                    # ... behind a decode step's [S]
        self.done = False
        # dispatch-anatomy scratch: the last launch_chunk()'s enqueue span
        # (obs.anatomy)
        self.last_launch_ms = 0.0
        # what the last launch_chunk() held, under the flight ring's names
        # (obs.flight WORK_COLUMNS): real tokens, the rows the program
        # computes, the cached tokens in front of the chunk, the positions
        # its attend spans
        self.last_chunk: dict[str, int] = {}

    @property
    def chunks_remaining(self) -> int:
        if self.done:
            return 0
        if self.mm or self.sp:
            return 1
        chunk = self.runner.prefill_chunk
        cut = self._cut()
        if cut:     # the chunks up to the snapshot's position, then the rest
            return -(-cut // chunk) + -(
                -(len(self.prompt) - self.pos - cut) // chunk)
        return max(1, -(-(len(self.prompt) - self.pos) // chunk))

    def _cut(self) -> int:
        """Tokens from ``pos`` to the position a snapshot is taken at, while
        that lies ahead (0: no snapshot, or behind)."""
        return max(0, self.snapshot[1] - self.pos) if self.snapshot else 0

    @property
    def ride_bucket(self) -> Optional[int]:
        """The bucket of the next chunk where it is the prompt's last and
        small enough to ride a decode step (``launch_chunk(ride=True)``: at
        most ``RIDE_ROWS`` rows, on a runner whose programs can,
        ``ModelRunner.rides``; a multimodal or ring prefill has a program of
        its own), else None."""
        r = self.runner
        rem = len(self.prompt) - self.pos
        if (self.done or self.mm or self.sp or not r.rides
                or rem > r.prefill_chunk or self._cut()):
            return None
        bucket = r.bucket_for(rem)
        return bucket if bucket <= RIDE_ROWS else None

    def _counts_row(self) -> np.ndarray:
        return _prompt_counts_row(self.runner.cfg.vocab_size, self.prompt)

    def launch_chunk(self, ride: bool = False) -> bool:
        """Dispatch the next prefill chunk without waiting for it; True when
        it was the final one (``first`` then holds the sampled token, on its
        way to the host). Arguments go up as host arrays: the dispatch's
        own transfer, no program of their own. ``ride`` (the caller has
        asked ``ride_bucket``): the chunk and the decode step every slot would
        take next are ONE launch, and ``first`` holds the step's [S] tokens
        with the chunk's behind them."""
        assert not self.done and (not ride or self.ride_bucket)
        r = self.runner
        slot = np.int32(self.slot)
        n = len(self.prompt)
        t0 = time.perf_counter()
        table_row = np.asarray(r.allocator.table_row(self.slot), np.int32)
        rem = n - self.pos
        offset = self.pos
        # a chunk ends where a snapshot is taken (never the prompt's end)
        limit = min(r.prefill_chunk, self._cut() or r.prefill_chunk)
        last = self.sp or self.mm or rem <= limit
        chunk_state = 0
        if r.recurrent:
            # 1: from zero state; 2: from the slot's own; 3: from a
            # snapshot, laid into the slot's rows in front of this chunk
            chunk_state = 1 if offset == 0 else 2
            if self.restore is not None:
                r.restore_snapshot(self.slot, self.restore)
                self.restore, chunk_state = None, 3
        if last:
            # before the chunk that samples with them; not earlier, for a
            # decode step between two chunks must still find the slot's
            # device table row on the trash block
            r._arm(self.arm_args, table_row)
        if self.sp:
            # ring attention over the 'seq' mesh axis, scattered straight
            # into the reserved blocks — the whole prompt in one dispatch
            bucket = r.bucket_for(n)
            padded = np.zeros(bucket, np.int32)
            padded[:n] = self.prompt
            r.kv, r.state, tok = r._prefill_paged_sp(
                r.params, r.kv, r.state, padded, np.int32(n),
                table_row, slot, self._counts_row(), bucket=bucket,
            )
            take, ctx = n, bucket       # the ring attends the prompt alone
            self.pos = n
        elif self.mm:
            bucket = r.bucket_for(n)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :n] = self.prompt
            r.kv, r.state, tok = r._prefill_paged_mm(
                r.params, r.kv, r.state, padded, np.int32(n),
                table_row, slot,
                jnp.asarray(self.mm_embeds, jnp.float32),
                jnp.asarray(self.mm_positions, jnp.int32),
                self._counts_row(), bucket=bucket,
            )
            take, ctx = n, r.chunk_span(0, bucket)
            self.pos = n
        else:
            take = min(rem, limit)
            bucket = r.bucket_for(take)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :take] = self.prompt[self.pos:self.pos + take]
            crow = (self._counts_row() if last
                    else r._zero_counts)  # sample=False ignores counts
            if ride:
                r.kv, r.state, tok = r._decode_prefill_paged(
                    r.params, r.kv, r.state, r.block_tables, padded,
                    np.int32(take), np.int32(self.pos), table_row, slot,
                    crow, bucket=bucket,
                )
                self.rode = True
            else:
                r.kv, r.state, tok = r._prefill_paged(
                    r.params, r.kv, r.state, padded,
                    np.int32(take), np.int32(self.pos), table_row,
                    slot, crow, bucket=bucket,
                    sample=last,
                )
            ctx = r.chunk_span(offset, bucket)
            self.pos += take
            if self.snapshot and self.pos == self.snapshot[1]:
                r.take_snapshot(self.slot, self.snapshot[0])
                self.snapshot = None
        self.last_chunk = {"chunk_tokens": take, "chunk_bucket": bucket,
                           "chunk_offset": offset, "chunk_ctx": ctx,
                           **({"chunk_state": chunk_state}
                              if chunk_state else {}),
                           "chunk_parts": (1 if self.sp else r.chunk_parts(
                               bucket, take, last))}
        r.admit_programs += 1
        if last:
            self.done = True
            self.first = tok
            try:
                tok.copy_to_host_async()
            except AttributeError:
                pass
            r._finish_paged_admit(self.slot, self.prompt, mm=self.mm)
        self.last_launch_ms = (time.perf_counter() - t0) * 1e3
        return last

    def first_token(self) -> int:
        """The first sampled token, on the host: waits for the final chunk
        (guarded: a device that never answers would hang here silently)."""
        with self.runner.watchdog.guard("device"):
            # a model with routed experts sends the launch's routed work
            # behind the token (``_prefill_paged_fn``); a chunk that rode
            # has the step's [S] tokens in front of its own
            return int(np.asarray(  # jaxlint: disable=host-sync-in-hot-path
                self.first).reshape(-1)[
                    self.runner.num_slots if self.rode else 0])

    def step_chunk(self) -> Optional[int]:
        """Dispatch the next chunk; the first token once the admission is
        complete (waiting for it), else None. The one-call form for
        callers with nothing to overlap."""
        return self.first_token() if self.launch_chunk() else None

    def abort(self) -> None:
        """Abandon a part-way admission (client cancelled while chunks
        were queued): frees the blocks and leaves the slot inactive."""
        self.done = True
        self.runner.release(self.slot)


_ONE = np.asarray(1)

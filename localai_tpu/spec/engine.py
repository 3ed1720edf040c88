"""SpecEngine: block-native speculative decoding for the serving hot path.

Speculative sampling (Leviathan et al., "Fast Inference from Transformers
via Speculative Decoding") composed with block-granular paged KV (Kwon et
al., PagedAttention): a :class:`~localai_tpu.spec.drafter.Drafter`
proposes ``gamma`` tokens per slot, ONE batched target forward scores the
whole window per dispatch (``ModelRunner.verify_async`` — the verify-k
dispatch that amortizes the per-step host round-trip exactly like the
contiguous ``decode_n`` programs), and the on-device accept/sample scan
emits each slot's accepted prefix + correction while rolling that slot's
frontier back independently — co-batched slots never notice a neighbor's
rejection.

Paged targets write draft rows through the block-table mirror into
speculation blocks reserved at admission (``begin_admit(spec_tokens=)``);
a rejected tail is a per-slot position rollback — the table never
changes, the garbage rows (int8 scale rows included) are overwritten
before anything can attend to them. Contiguous targets use the same
verify API over slot rows, so there is exactly ONE speculation code path
for both KV layouts (the old ``engine.speculative.SpecDecoder`` is now a
shim over this class).

The scheduler drives :meth:`step_spec_async` exactly like multi-step
decode; each dispatch returns ``[gamma+1, S]`` token rows where SKIP (-1)
marks positions past a slot's accepted window, and ``observe_window``
folds the drained rows into acceptance telemetry + the drafter's
history."""

from __future__ import annotations

import logging
from typing import Any, Optional

import numpy as np

from localai_tpu.engine.runner import SKIP, ModelRunner
from localai_tpu.faults import registry as _faults
from localai_tpu.spec.drafter import Drafter, ModelDrafter, NGramDrafter

log = logging.getLogger(__name__)


class SpecEngine:
    """Couples a target ModelRunner (paged or contiguous) with a Drafter.

    Implements the scheduler's engine surface (slot lifecycle + spec
    windows) by delegating state ops to the target and proposal ops to
    the drafter. Single-writer threading model: every mutator runs on
    the scheduler's engine thread (or its single-owner recovery thread),
    same as ModelRunner — cross-thread readers (metrics scrapes) only
    see monotone counters."""

    # self-healing: a rebuild re-inits the target AND the drafter (both
    # expose reinit()), unlike the legacy draft-pair design
    supports_rebuild = True

    def __init__(self, target: ModelRunner, drafter: Drafter,
                 gamma: Optional[int] = None,
                 min_accept: Optional[float] = None,
                 cooldown: Optional[int] = None):
        import os
        from collections import deque

        self.target = target
        self.drafter = drafter
        self.gamma = int(gamma if gamma is not None else drafter.gamma)
        if self.gamma != drafter.gamma:
            raise ValueError(
                f"engine gamma {self.gamma} != drafter gamma "
                f"{drafter.gamma}")
        self.num_slots = target.num_slots
        self.max_ctx = target.max_ctx
        self.cfg = target.cfg
        self.paged = bool(getattr(target, "paged", False))
        # host drafters need the previous window drained before proposing
        self.pipeline_safe = bool(drafter.device_proposals)
        # acceptance-floor backoff: a drafter that keeps proposing but
        # never gets drafts accepted turns every dispatch into a
        # gamma+1-wide verify emitting ~1 token — strictly worse than
        # plain decode. When the accept ratio over the last
        # _accept_window windows drops below min_accept, speculation
        # self-suppresses for `cooldown` dispatches, then re-probes
        # (workloads change). LOCALAI_SPEC_MIN_ACCEPT=0 disables.
        if min_accept is None:
            try:
                min_accept = float(os.environ.get(
                    "LOCALAI_SPEC_MIN_ACCEPT", "0.1") or 0.1)
            except ValueError:
                min_accept = 0.1
        if cooldown is None:
            try:
                cooldown = int(os.environ.get(
                    "LOCALAI_SPEC_COOLDOWN", "64") or 64)
            except ValueError:
                cooldown = 64
        self.min_accept = max(0.0, float(min_accept))
        self.cooldown = max(1, int(cooldown))
        self._recent: "deque[tuple[int, int]]" = deque(maxlen=16)
        self._cooldown_left = 0
        # window telemetry (engine-thread writers, scrape readers)
        self.total_windows = 0          # verify dispatches
        self.total_emitted = 0          # tokens emitted across windows
        self.total_eligible = 0         # active slot-windows × (gamma+1)
        self.total_proposed = 0         # draft tokens scored
        self.total_accepted = 0         # draft tokens accepted
        self.total_declined = 0         # windows the drafter declined
        self.total_suppressed = 0       # windows skipped by the backoff
        self.last_skip_reason: Optional[str] = None
        # real-proposal row mask of the in-flight window (host drafters
        # serialize windows, so one pending mask suffices; device
        # drafters propose for every slot → None = all real)
        self._pending_hits: Optional[Any] = None
        self.last_prefix_reused = 0

    # -- spec windows (engine thread) ------------------------------------

    def step_spec_async(self) -> Optional[Any]:
        """One speculative window over all slots: propose, verify, roll
        back. Returns the [gamma+1, S] emitted-token device array (SKIP =
        nothing for that step/slot), or None when the drafter declined
        (the scheduler falls back to a plain dispatch)."""
        self.last_skip_reason = None
        if self.suppressed_tick():
            self.last_skip_reason = "suppressed"
            return None
        t = self.target
        props = self.drafter.propose(t.state.tokens, t.state.positions)
        if props is None:
            self.total_declined += 1
            self.last_skip_reason = "declined"
            return None
        self._pending_hits = getattr(self.drafter, "last_hits", None)
        if _faults.ACTIVE:
            spec = _faults.apply("spec.draft", key=self.drafter.name)
            if spec is not None:
                # divergent-draft chaos: replace every proposal with
                # deterministic garbage — acceptance collapses, rollback
                # and co-batched streams must stay byte-correct
                props = (np.asarray(props) * 31 + 17) % t.cfg.vocab_size
        return t.verify_async(props)

    def suppressed_tick(self) -> bool:
        """True while the acceptance backoff is suppressing windows; each
        call consumes one cooldown tick. The scheduler calls this BEFORE
        any drain/resync so a suppressed dispatch costs exactly plain
        decode; direct window drivers hit the same check inside
        step_spec_async (never both — a False here means the cooldown is
        already spent)."""
        if self._cooldown_left > 0:
            self._cooldown_left -= 1
            self.total_suppressed += 1
            return True
        return False

    def has_candidate(self, residents: dict) -> bool:
        """Cheap pre-gate: could the drafter propose for any of these
        slots right now? ``residents`` maps slot → current
        prompt+generation token record (exactly what a resync would seed
        the drafter with). Device drafters always can; host lookup
        drafters peek the records directly — a False lets the scheduler
        skip the pipeline drain AND the per-slot resync entirely, so
        self-drafting costs nothing on workloads it cannot predict."""
        peek = getattr(self.drafter, "has_candidate", None)
        if peek is None:
            return True
        return bool(peek(residents))

    def step_spec(self) -> np.ndarray:
        """Synchronous window (telemetry + tests); the scheduler's hot
        path uses step_spec_async + copy_to_host_async. Raises when the
        drafter declines — direct callers pick the window cadence."""
        emitted = self.step_spec_async()
        if emitted is None:
            raise RuntimeError(
                "speculative window skipped: "
                + ("acceptance backoff is suppressing windows"
                   if self.last_skip_reason == "suppressed"
                   else f"drafter {self.drafter.name!r} declined "
                        "(no proposals)"))
        with self.target.watchdog.guard("device"):
            rows = np.asarray(emitted)  # jaxlint: disable=host-sync-in-hot-path
        self.observe_window(rows)
        return rows

    def observe_window(self, rows: np.ndarray) -> dict:
        """Fold one drained [T, S] window into acceptance telemetry and
        the drafter's per-slot history. An active slot always emits ≥1
        token, so active columns are the ones with any non-SKIP entry.
        Returns this window's counts for the flight ring."""
        T = rows.shape[0]
        gamma = T - 1
        # sentinels are not tokens: SKIP (window ended earlier) and the
        # NaN-guard's NAN_TOKEN (the scheduler fails that request) are
        # both negative — neither counts as emitted nor enters history
        emitted_per = (rows >= 0).sum(axis=0)         # [S]
        active = emitted_per > 0
        emitted = int(emitted_per.sum())
        windows = int(active.sum())
        # each active window's last emitted token is the correction (or
        # the full-acceptance bonus sample) — everything before it is an
        # accepted draft token. Only REAL proposal rows count toward the
        # draft arithmetic: a host drafter pads no-hit slots with
        # guaranteed-reject filler for the static-shape verify, and
        # counting those would dilute accept_rate and trip the backoff
        # against a drafter that is actually working.
        hits, self._pending_hits = self._pending_hits, None
        real = active if hits is None else (active & hits)
        proposed = int(real.sum()) * gamma
        accepted = int(np.maximum(emitted_per - 1, 0)[real].sum())
        self.total_windows += 1
        self.total_emitted += emitted
        self.total_eligible += windows * T
        self.total_proposed += proposed
        self.total_accepted += accepted
        for slot in np.nonzero(active)[0]:
            col = rows[:, slot]
            self.drafter.observe(
                int(slot), [int(x) for x in col[col >= 0]])
        if proposed and self.min_accept > 0:
            self._recent.append((proposed, accepted))
            if len(self._recent) == self._recent.maxlen:
                props = sum(p for p, _ in self._recent)
                accs = sum(a for _, a in self._recent)
                if props and accs / props < self.min_accept:
                    self._cooldown_left = self.cooldown
                    self._recent.clear()
                    log.info(
                        "speculation accept rate %.3f < %.2f over the "
                        "last %d windows; suppressing for %d dispatches",
                        accs / props, self.min_accept,
                        self._recent.maxlen, self.cooldown)
        return {"emitted": emitted, "windows": windows,
                "proposed": proposed, "accepted": accepted}

    def resync_draft(self, slot: int, resident: list[int]) -> None:
        """Rebuild one slot's draft state after non-speculative dispatches
        advanced the target without it (grammar-constrained interludes,
        plain fallbacks, chunked admissions)."""
        self.drafter.resync(slot, resident, self.target.state.positions)

    # -- slot lifecycle (scheduler-facing, mirrors ModelRunner) ----------

    def admit(self, slot: int, prompt: list[int], **kw) -> int:
        """Prefill the target; the first sampled token seeds the drafter.
        The speculation-row lookahead is reserved on top of any caller
        reservation (a contiguous target's rows reach ``max_ctx`` and its
        ``admit`` ignores it; the scheduler's chunked path does the same
        through begin_admit)."""
        kw.setdefault("spec_tokens", self.gamma + 1)
        first = self.target.admit(slot, prompt, **kw)
        self.last_prefix_reused = self.target.last_prefix_reused
        self.drafter.admit(slot, list(prompt) or [0], first,
                           self.target.state.positions)
        return first

    def begin_admit(self, slot: int, prompt: list[int], **kw):
        """Chunked paged admission passthrough; the speculation-row
        reservation rides the allocator call (spec_tokens)."""
        kw.setdefault("spec_tokens", self.gamma + 1)
        return self.target.begin_admit(slot, prompt, **kw)

    def acquire_slot(self, slot: Optional[int] = None) -> Optional[int]:
        got = self.target.acquire_slot(slot)
        if got is not None and hasattr(self.drafter, "acquire_slot"):
            self.drafter.acquire_slot(got)
        return got

    def free_slots(self) -> list[int]:
        return self.target.free_slots()

    def release(self, slot: int) -> None:
        self.target.release(slot)
        self.drafter.release(slot)

    def set_bias(self, slot: int, bias_row) -> None:
        self.target.set_bias(slot, bias_row)

    def reusable_prefix(self, slot: int, resident, prompt,
                        valid_n=None) -> int:
        return self.target.reusable_prefix(slot, resident, prompt, valid_n)

    def resident_rows(self, slot: int, default: int) -> int:
        return self.target.resident_rows(slot, default)

    def load_prefix(self, slot: int, arrays: dict, n: int) -> bool:
        return self.target.load_prefix(slot, arrays, n)

    def free_frontiers(self) -> np.ndarray:
        return self.target.free_frontiers()

    def slot_positions(self) -> np.ndarray:
        return self.target.slot_positions()

    def slot_position(self, slot: int) -> int:
        return self.target.slot_position(slot)

    def reinit(self) -> None:
        """Self-healing rebuild hook: the scheduler re-inits the target
        runner itself; this resets the drafter (draft KV / history) and
        the acceptance-backoff state."""
        self.drafter.reinit()
        self._recent.clear()
        self._cooldown_left = 0

    # -- telemetry --------------------------------------------------------

    @property
    def acceptance_rate(self) -> float:
        """Emitted tokens per active slot-window / (gamma+1): 1.0 = every
        window fully accepted for every active slot (window efficiency —
        the historical series; ``accept_rate`` is the per-draft ratio)."""
        if not self.total_eligible:
            return 0.0
        return self.total_emitted / self.total_eligible

    @property
    def accept_rate(self) -> float:
        """Draft tokens accepted / proposed — the localai_spec_accept_rate
        series."""
        if not self.total_proposed:
            return 0.0
        return self.total_accepted / self.total_proposed

    @property
    def tokens_per_dispatch(self) -> float:
        """Mean emitted tokens per active slot-window — >1 means the
        verify-k dispatch beats single-step decode on dispatch count."""
        if not self.total_eligible:
            return 0.0
        windows = self.total_eligible / (self.gamma + 1)
        return self.total_emitted / windows if windows else 0.0

    def stats(self) -> dict:
        """Window telemetry snapshot (obs /metrics + GetMetrics surface)."""
        return {
            "gamma": self.gamma,
            "windows": self.total_windows,
            "emitted": self.total_emitted,
            "eligible": self.total_eligible,
            "proposed": self.total_proposed,
            "accepted": self.total_accepted,
            "declined": self.total_declined,
            "suppressed": self.total_suppressed,
            "acceptance_rate": self.acceptance_rate,
            "accept_rate": self.accept_rate,
            "tokens_per_dispatch": self.tokens_per_dispatch,
            **self.drafter.stats(),
        }


def build_spec_engine(target: ModelRunner, *,
                      drafter: str = "auto",
                      draft_ref: Optional[str] = None,
                      model_path: str = "models",
                      gamma: Optional[int] = None,
                      dtype: str = "bfloat16") -> SpecEngine:
    """Resolve a drafter and couple it to ``target`` (manager entry).

    ``drafter``: ``"model"`` loads ``draft_ref`` as a co-located draft
    model (contiguous KV, target's mesh/slots); ``"ngram"`` self-drafts
    via prompt lookup; ``"auto"`` picks model when a draft_ref is
    configured, ngram otherwise. Env knobs: ``LOCALAI_SPEC_GAMMA``
    (window size), ``LOCALAI_SPEC_NGRAM_MAX`` (longest lookup n-gram)."""
    import os

    if getattr(target, "pp_enabled", False):
        # the verify forward calls mdl.forward directly — it would GSPMD
        # over pipe-sharded stacked weights, all-gathering the full
        # weight set per window (defeating capacity mode)
        raise ValueError(
            "speculative decoding is not supported with pipeline "
            "parallelism")
    if getattr(target, "ga_n", 1) > 1:
        # self-extend targets carry an UNroped KV cache + identity rope
        # table; the verify forward would compute position-blind
        # attention — reject rather than emit garbage
        raise ValueError(
            "speculative decoding is not supported with self-extend "
            "(grp_attn_n > 1)")
    if gamma is None:
        try:
            gamma = int(os.environ.get("LOCALAI_SPEC_GAMMA", "4") or 4)
        except ValueError:
            gamma = 4
    gamma = max(1, int(gamma))
    kind = drafter
    if kind in ("auto", "", None):
        kind = "model" if draft_ref else "ngram"
    if kind == "ngram":
        try:
            max_n = int(os.environ.get("LOCALAI_SPEC_NGRAM_MAX", "4") or 4)
        except ValueError:
            max_n = 4
        try:
            min_n = int(os.environ.get("LOCALAI_SPEC_NGRAM_MIN", "2") or 2)
        except ValueError:
            min_n = 2
        return SpecEngine(
            target,
            NGramDrafter(target.num_slots, gamma, max_n=max_n,
                         min_n=min_n),
        )
    if kind != "model":
        raise ValueError(f"unknown drafter {drafter!r} "
                         "(want auto | ngram | model)")
    if not draft_ref:
        raise ValueError("drafter 'model' needs a draft_model reference")
    from localai_tpu.models.registry import resolve_config, resolve_model
    from localai_tpu.parallel.sharding import ParamPlacement

    dcfg = resolve_config(draft_ref, model_path, dtype)
    if dcfg.vocab_size != target.cfg.vocab_size:
        raise ValueError(
            f"draft vocab {dcfg.vocab_size} != target vocab "
            f"{target.cfg.vocab_size} — speculative decoding needs a "
            "shared tokenizer")
    # leaves land on the target's mesh as they load (never whole on chip 0)
    draft = resolve_model(draft_ref, model_path=model_path, dtype=dtype,
                          placement=ParamPlacement(dcfg, target.mesh))
    runner = ModelRunner(
        draft.cfg, draft.params,
        num_slots=target.num_slots,
        max_ctx=target.max_ctx,
        prefill_buckets=list(target.buckets[:-1]) or None,
        # int4 is a paged-pool-only layout; a contiguous draft cache
        # falls back to the scaled-int8 scheme (same bandwidth class)
        kv_dtype=("int8" if target.kv_dtype == "int4"
                  else target.kv_dtype),
        mesh=target.mesh,
        # the draft serves window scans over slot rows only — contiguous
        paged=False,
    )
    return SpecEngine(target, ModelDrafter(runner, gamma))

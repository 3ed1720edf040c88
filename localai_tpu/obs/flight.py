"""Engine flight recorder: a fixed-size ring of per-dispatch records.

The failure mode this exists for (BENCH r5): the engine stalls or a bench
round expires and the only artifact is ``decode_throughput 0.0`` — no
record of what the engine was doing for the preceding seconds, how far it
got, or what step times looked like right before the silence. Production
continuous-batching stacks (Orca, OSDI '22) treat the per-iteration
timeline as the primary debugging artifact; this is that timeline.

Design constraints (same contract as :mod:`obs.trace`):

  * **Zero device syncs.** Every field is a host-side mirror the scheduler
    already holds (slot dict sizes, queue depth, token counters, monotonic
    clocks). Nothing here ever touches a jax array.
  * **Lock-light, allocation-light.** The ring is column-major over
    preallocated numpy arrays; :meth:`record` writes one row in place
    under a short lock — no per-dispatch list/dict/object allocation, so
    feeding it from the drain loop costs a few scalar stores.
  * **Windowed percentiles from the ring.** Per-token step time
    (``dispatch_ms / steps``) percentiles (p50/p90/p99) are computed on
    demand from the resident rows, excluding compile-bearing first
    dispatches (``compile=True``), so the numbers answer "what is decode
    doing NOW", which the lifetime EMA cannot. Speculative windows record
    their MEASURED yield (mean emitted tokens per active slot-window) as
    ``steps`` plus per-dispatch ``spec_proposed``/``spec_accepted``
    counts — with speculation the default lane they are part of the
    decode timeline, not an exclusion.

One instance per Scheduler (``Scheduler.flight``); bench phases build
their own. Surfaced at ``GET /debug/flight`` and attached to every stall
forensic trace via the watchdog's context providers.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

import numpy as np

from localai_tpu.obs.trace import mono_to_wall


# what a launch held, as columns of the ring and keys of /debug/flight, in
# the order of record()'s keywords
WORK_COLUMNS = ("launch", "live_slots", "attended_tokens", "window_tokens",
                "selected_tokens", "chunk_tokens", "chunk_bucket", "chunk_offset", "chunk_ctx",
                "chunk_parts", "experts_touched", "local_assignments",
                "sparse_rows", "chunk_state")


# the measured parts of what ``gap_ms`` holds (obs.anatomy.PARTS), and the
# engine thread's own clocks for the span since the previous row: columns of
# the ring and keys of /debug/flight, in the order of record()'s keywords
PART_COLUMNS = ("process_ms", "book_ms", "free_ms")
CLOCK_COLUMNS = ("span_ms", "wait_ms", "idle_ms", "cpu_ms", "runq_ms",
                 "blocked_ms", "proc_cpu_ms")
# the states that tile a row's span (``wait + idle + cpu + runq + blocked =
# span``), as the ``state`` label of localai_engine_thread_seconds_total
THREAD_STATES = ("cpu", "runq", "blocked", "wait", "idle")


# the calling thread's scheduler statistics: "<ns on a cpu> <ns runnable and
# waiting for one> <timeslices>" (Documentation/scheduler/sched-stats)
SCHEDSTAT = "/proc/thread-self/schedstat"


# the most CPU a ThreadClock owes its next rows: ten ticks of the coarsest
# clock met. More than that is no tick but a clock that reads high, and rows
# long after it would pay for it
OWED_MAX_S = 0.1


class ThreadClock:
    """One thread's own clocks, read by that thread once a ring row.

    A wall clock cannot tell a thread that computed from one that was
    runnable with no core (the machine), one that slept on the GIL or a lock
    (this process's other threads) or one whose device answered late. The
    kernel keeps them apart for every thread: its CPU time
    (``time.thread_time``) and its run-queue delay (the second field of
    ``SCHEDSTAT``). :meth:`take` turns them into the ``CLOCK_COLUMNS`` of a
    row: the differences since the previous take, with the wall the thread
    spent inside its two waits (:meth:`enter` / :meth:`leave` around each)
    and the CPU and run-queue delay of those waits taken out of ``cpu`` and
    ``runq``, so that ``wait + idle + cpu + runq + blocked = span``.

    A CPU clock may tick coarser than a row is long (10 ms on the chip's
    machine, under rows of 8): a tick is credited to ``cpu`` as far as the
    span has room for it and the rest is OWED to the next rows, so that a
    window's ``cpu`` sums to what the clock read and ``blocked`` takes none
    of it; one row's ``cpu_ms`` is good to a tick.

    Build it ON the thread it reads: ``thread-self`` resolves when the file
    is opened, and ``thread_time`` is the caller's. About half a microsecond
    a read, four a take, three at each end of a wait; a file that cannot be
    opened or read gives ``runq_ms`` None and nothing raises."""

    def __init__(self):
        try:
            self._fd: Optional[int] = os.open(SCHEDSTAT, os.O_RDONLY)
        except OSError:
            self._fd = None
        self._t = time.monotonic()
        self._cpu = time.thread_time()
        self._runq = self._runq_ns()
        self._proc = time.process_time()
        # inside the waits since the last take: wall by kind, CPU, delay
        self._wait_s = 0.0
        self._idle_s = 0.0
        self._in_cpu = 0.0
        self._in_runq = 0
        # CPU the clock read that no span had room for yet (see take())
        self._cpu_owed = 0.0

    def _runq_ns(self) -> Optional[int]:
        if self._fd is None:
            return None
        try:
            return int(os.pread(self._fd, 64, 0).split()[1])
        except (OSError, IndexError, ValueError):
            return None

    def enter(self) -> tuple:
        """At a wait's start; hand the mark to :meth:`leave`."""
        return time.thread_time(), self._runq_ns()

    def leave(self, mark: Optional[tuple], wall_s: float,
              idle: bool = False) -> None:
        """At a wait's end: ``wall_s`` inside ``sched.wait_device``, or with
        ``idle`` inside ``sched.idle``; what the thread burnt and queued in
        there is the wait's, not ``cpu``'s or ``runq``'s. A wait whose two
        ends the caller cannot reach (inside the runner's synchronous step)
        passes no mark: its wall alone."""
        if mark is not None:
            cpu0, runq0 = mark
            self._in_cpu += time.thread_time() - cpu0
            runq1 = self._runq_ns()
            if runq0 is not None and runq1 is not None:
                self._in_runq += runq1 - runq0
        if idle:
            self._idle_s += wall_s
        else:
            self._wait_s += wall_s

    def take(self, now: float) -> dict:
        """The ``CLOCK_COLUMNS`` (ms) of the row whose span ends at ``now``
        (``time.monotonic``, just read): the span since the previous take."""
        cpu_t, runq_t, proc_t = (time.thread_time(), self._runq_ns(),
                                 time.process_time())
        span = max(0.0, now - self._t)
        wait, idle, runq = self._wait_s, self._idle_s, 0.0
        known = runq_t is not None and self._runq is not None
        if known:
            runq = max(0.0, (runq_t - self._runq - self._in_runq) * 1e-9)
        # the clocks are not read at one instant: what the walls and the
        # delay overshoot the span by comes off them, the delay first
        over = max(0.0, wait + idle + runq - span)
        cut = min(runq, over)
        runq, over = runq - cut, over - cut
        cut = min(wait, over)
        wait, idle = wait - cut, idle - (over - cut)
        # what the CPU clock read since the last take and what it still
        # owes, as far as the span has room; the rest is owed on
        read = max(0.0, cpu_t - self._cpu - self._in_cpu) + self._cpu_owed
        cpu = min(read, max(0.0, span - wait - idle - runq))
        self._cpu_owed = min(read - cpu, OWED_MAX_S)
        blocked = max(0.0, span - wait - idle - runq - cpu)
        proc = proc_t - self._proc
        self._t, self._cpu, self._runq, self._proc = now, cpu_t, runq_t, proc_t
        self._wait_s = self._idle_s = self._in_cpu = 0.0
        self._in_runq = 0
        return {"span_ms": span * 1e3, "wait_ms": wait * 1e3,
                "idle_ms": idle * 1e3, "cpu_ms": cpu * 1e3,
                "runq_ms": runq * 1e3 if known else None,
                "blocked_ms": blocked * 1e3, "proc_cpu_ms": proc * 1e3}

    def close(self) -> None:
        if self._fd is not None:
            os.close(self._fd)
            self._fd = None


def _default_capacity() -> int:
    try:
        return max(1, int(os.environ.get("LOCALAI_FLIGHT_CAPACITY", "512")))
    except ValueError:
        return 512


class FlightRecorder:
    """Column-major ring of per-dispatch engine records."""

    def __init__(self, capacity: Optional[int] = None):
        self.capacity = int(capacity) if capacity else _default_capacity()
        n = self.capacity
        self._lock = threading.Lock()
        self._ts = np.zeros(n)
        self._steps = np.zeros(n, np.int64)
        self._passes = np.zeros(n, np.int64)
        self._dispatch_ms = np.zeros(n)
        self._occupancy = np.zeros(n)
        self._batch_slots = np.zeros(n, np.int64)
        self._queue_depth = np.zeros(n, np.int64)
        self._kv_utilization = np.zeros(n)
        self._tokens = np.zeros(n, np.int64)
        self._preemptions = np.zeros(n, np.int64)
        self._spec_accept = np.full(n, np.nan)
        self._spec_proposed = np.zeros(n, np.int64)
        self._spec_accepted = np.zeros(n, np.int64)
        self._gap_ms = np.zeros(n)
        self._sched_ms = np.zeros(n)
        self._launch_ms = np.zeros(n)
        self._sync_ms = np.zeros(n)
        self._compile = np.zeros(n, bool)
        self._program: list[str] = [""] * n
        # what the launch held, taken when it was enqueued (see record())
        self._work = np.zeros((n, len(WORK_COLUMNS)), np.int64)
        self._parts = np.zeros((n, len(PART_COLUMNS)))
        # NaN in ``runq_ms``: the kernel's run-queue delay could not be read
        self._clock = np.zeros((n, len(CLOCK_COLUMNS)))
        self._n = 0                # records ever written (ring head = n % cap)
        self.total_tokens = 0      # cumulative, survives wraparound
        # ms the engine thread spent in each state, over every row ever
        # written (survives wraparound): localai_engine_thread_seconds_total
        self.thread_ms_total = dict.fromkeys(THREAD_STATES, 0.0)

    # -- hot path (engine thread) -----------------------------------------

    def record(self, *, program: str, steps: int, dispatch_ms: float,
               occupancy: float, queue_depth: int, kv_utilization: float,
               tokens: int, preemptions: int = 0,
               spec_accept: Optional[float] = None,
               spec_proposed: int = 0, spec_accepted: int = 0,
               compile: bool = False, ts: Optional[float] = None,
               batch_slots: int = 0, gap_ms: float = 0.0,
               sched_ms: float = 0.0, launch_ms: float = 0.0,
               sync_ms: float = 0.0, passes: int = 0, launch: int = 0,
               live_slots: int = 0, attended_tokens: int = 0,
               window_tokens: int = 0, selected_tokens: int = 0,
               chunk_tokens: int = 0,
               chunk_bucket: int = 0, chunk_offset: int = 0,
               chunk_ctx: int = 0, chunk_parts: int = 0,
               experts_touched: int = 0, local_assignments: int = 0,
               sparse_rows: int = 0, chunk_state: int = 0,
               process_ms: float = 0.0, book_ms: float = 0.0,
               free_ms: float = 0.0,
               span_ms: float = 0.0, wait_ms: float = 0.0,
               idle_ms: float = 0.0, cpu_ms: float = 0.0,
               runq_ms: Optional[float] = None, blocked_ms: float = 0.0,
               proc_cpu_ms: float = 0.0) -> None:
        """Append one dispatch record (host scalars only).

        ``batch_slots`` tags the record with the lane mix: how many of the
        occupied slots were background batch-lane requests at drain time
        (0 = pure interactive dispatch). ``spec_proposed``/
        ``spec_accepted`` are THIS dispatch's draft-token counts (0 for
        non-speculative dispatches) — the per-window accept trace the
        cumulative ``spec_accept`` ratio can't show.

        ``gap_ms``/``sched_ms``/``launch_ms``/``sync_ms`` decompose the
        wall interval ``dispatch_ms`` accounts for (see
        :mod:`obs.anatomy` for phase semantics). The scheduler guarantees
        their sum never exceeds ``dispatch_ms``; callers that cannot
        attribute phases pass the zero defaults and the record degrades
        to the undifferentiated pre-anatomy shape.

        ``passes`` counts the passes over the layer stack the dispatch ran:
        its forwards times the model's passes a forward (a looped decoder
        runs its stack several times a token; every other model once).

        ``launch`` and the counts after it say what work the launch
        held, taken when its program was ENQUEUED and not at the drain
        (``WORK_COLUMNS``): ``launch`` is the scheduler's launch number,
        which the host trace carries as ``sched.launch/<n>`` around the
        same enqueue, so that a row is tied to its device execution
        without clock arithmetic. A decode row holds ``live_slots`` (slots
        with a stream at the launch) and ``attended_tokens`` (cached tokens
        its steps attend, summed over steps and live slots) and
        ``window_tokens`` (the same sum with each stream's context cut to the
        model's attention window: what a window layer's call reads; 0 for a
        model with no window) and ``selected_tokens`` (the same sum with each
        stream's context cut to ``index_topk``: the rows a layer with an
        indexer attends of those it scored; 0 for a model with none); a
        prefill row holds ``chunk_tokens`` (real
        tokens), ``chunk_bucket`` (rows of its program),
        ``chunk_offset`` (cached tokens in front of the chunk),
        ``chunk_ctx`` (positions its attend spans) and ``chunk_parts`` (1: the
        program computes every row of its bucket behind the attend; 2 to 4:
        the quarters of the bucket a prompt's last chunk ran there, on one
        chip as on a mesh, ``ModelRunner.chunk_rows``). A decode row of a
        model whose streams attend a SELECTION of their blocks past a dense
        length holds ``sparse_rows`` (of its (live slot, step) pairs, those
        at or past that length); a prefill row of a model with recurrent
        state holds ``chunk_state`` (what the chunk went on from: 1 zero
        state, 2 the slot's own, 3 a snapshot restored in front of it: an
        admission's first chunk reads 1 or 3). 0 wherever a row's kind
        has no such count.

        ``experts_touched`` and ``local_assignments`` are the two counts
        NOT taken at the enqueue: a model with routed experts counts them on
        the device and sends them behind the sampled tokens, so a decode row
        has them at its drain: held experts with at least one token, summed
        over the expert blocks and the steps, and token-expert pairs that
        landed on the experts held here. A chunk's row is written at its
        launch, before its counts exist: 0 there (the scheduler's totals
        have them).

        ``process_ms``, ``book_ms`` and ``free_ms`` are measured PARTS of the
        interval ``gap_ms`` accounts for (token processing; the loop's own
        bookkeeping; dropping the drained dispatch's device arrays: see
        :mod:`obs.anatomy`), never more than it together.

        ``span_ms`` and the six columns after it are the engine thread's own
        clocks for the wall since the previous row's span ended: the spans
        tile the thread's life. A row's span ends where the interval its
        ``dispatch_ms`` accounts for ends (a drain's end of wait, a chunk's
        end of launch: the scheduler reads the clocks there, not at the
        write), so all of a row's time columns speak of one stretch; it is
        still NOT ``dispatch_ms``, which for a pipelined row runs from drain
        to drain over the chunk rows written between. Inside ``sched.wait_device``
        (``wait_ms``), inside ``sched.idle`` (``idle_ms``), on a CPU outside
        those two (``cpu_ms``), runnable with no core (``runq_ms``, the
        kernel's run-queue delay; None where it cannot be read), and asleep
        on the GIL, a lock or a file (``blocked_ms``, what is left): the five
        sum to the span. ``proc_cpu_ms`` is the CPU of ALL threads of the
        process in the span. Writers that read no clocks pass the zero
        defaults."""
        now = time.monotonic() if ts is None else ts
        with self._lock:
            i = self._n % self.capacity
            self._ts[i] = now
            self._steps[i] = steps
            self._passes[i] = passes
            self._dispatch_ms[i] = dispatch_ms
            self._occupancy[i] = occupancy
            self._batch_slots[i] = batch_slots
            self._queue_depth[i] = queue_depth
            self._kv_utilization[i] = kv_utilization
            self._tokens[i] = tokens
            self._preemptions[i] = preemptions
            self._spec_accept[i] = (np.nan if spec_accept is None
                                    else spec_accept)
            self._spec_proposed[i] = spec_proposed
            self._spec_accepted[i] = spec_accepted
            self._gap_ms[i] = gap_ms
            self._sched_ms[i] = sched_ms
            self._launch_ms[i] = launch_ms
            self._sync_ms[i] = sync_ms
            self._compile[i] = compile
            self._program[i] = program
            self._work[i] = (launch, live_slots, attended_tokens,
                             window_tokens, selected_tokens, chunk_tokens,
                             chunk_bucket,
                             chunk_offset, chunk_ctx, chunk_parts,
                             experts_touched, local_assignments,
                             sparse_rows, chunk_state)
            self._parts[i] = (process_ms, book_ms, free_ms)
            self._clock[i] = (span_ms, wait_ms, idle_ms, cpu_ms,
                              np.nan if runq_ms is None else runq_ms,
                              blocked_ms, proc_cpu_ms)
            self._n += 1
            self.total_tokens += int(tokens)
            tot = self.thread_ms_total
            tot["cpu"] += cpu_ms
            tot["runq"] += runq_ms or 0.0
            tot["blocked"] += blocked_ms
            tot["wait"] += wait_ms
            tot["idle"] += idle_ms

    # -- read side ---------------------------------------------------------

    @property
    def count(self) -> int:
        """Records ever written (resident rows = min(count, capacity))."""
        # monotone int, torn reads impossible under the GIL; observability
        # readers tolerate being one record behind the engine thread
        return self._n  # jaxlint: disable=lock-guarded-attr

    def _order(self) -> np.ndarray:  # jaxlint: guarded-by(_lock)
        """Resident row indices, oldest → newest (caller holds the lock)."""
        if self._n <= self.capacity:
            return np.arange(self._n)
        head = self._n % self.capacity
        return np.concatenate([np.arange(head, self.capacity),
                               np.arange(head)])

    def snapshot(self, since: float = 0.0,
                 limit: Optional[int] = None) -> list[dict]:
        """Resident records oldest → newest as JSON-able dicts.

        ``since`` filters on the record's monotonic timestamp (pollers pass
        the ``ts`` of the last record they saw). ``limit`` keeps N records:
        the newest N of the ring (the tail view), or with ``since`` the
        OLDEST N after it, so that a poller pages forward and misses
        nothing however many dispatches fell between two of its reads.
        """
        # copy the selected rows under the lock, format after releasing
        # it: building (up to capacity) dicts must not block the engine
        # thread's per-dispatch record() behind a scrape
        with self._lock:
            order = self._order()
            if since:
                order = order[self._ts[order] > since]
            if limit is not None and len(order) > limit:
                order = order[:limit] if since else order[-limit:]
            cols = {
                "ts": self._ts[order].tolist(),
                "steps": self._steps[order].tolist(),
                "passes": self._passes[order].tolist(),
                "ms": self._dispatch_ms[order].tolist(),
                "occ": self._occupancy[order].tolist(),
                "batch": self._batch_slots[order].tolist(),
                "queue": self._queue_depth[order].tolist(),
                "kv": self._kv_utilization[order].tolist(),
                "tokens": self._tokens[order].tolist(),
                "preempt": self._preemptions[order].tolist(),
                "acc": self._spec_accept[order].tolist(),
                "proposed": self._spec_proposed[order].tolist(),
                "accepted": self._spec_accepted[order].tolist(),
                "gap": self._gap_ms[order].tolist(),
                "sched": self._sched_ms[order].tolist(),
                "launch": self._launch_ms[order].tolist(),
                "sync": self._sync_ms[order].tolist(),
                "compile": self._compile[order].tolist(),
                "program": [self._program[i] for i in order],
            }
            work = self._work[order].tolist()
            # 0.1 us: the five states still sum to the span to a microsecond
            timed = np.round(np.hstack(
                [self._parts[order], self._clock[order]]), 4).tolist()
        timed_keys = PART_COLUMNS + CLOCK_COLUMNS
        out = []
        for j in range(len(cols["ts"])):
            steps = cols["steps"][j]
            ms = cols["ms"][j]
            acc = cols["acc"][j]
            out.append({
                # ts stays unrounded: pollers feed it back as ?since=
                # and a rounded-up value would exclude its own record
                "ts": cols["ts"][j],
                "ts_unix": round(mono_to_wall(cols["ts"][j]), 6),
                "program": cols["program"][j],
                "steps": steps,
                "passes": cols["passes"][j],
                "dispatch_ms": round(ms, 3),
                "step_ms": (round(ms / steps, 4) if steps > 0 else None),
                "occupancy": round(cols["occ"][j], 4),
                "batch_slots": cols["batch"][j],
                "queue_depth": cols["queue"][j],
                "kv_utilization": round(cols["kv"][j], 4),
                "tokens": cols["tokens"][j],
                "preemptions": cols["preempt"][j],
                "spec_accept": (None if np.isnan(acc) else round(acc, 4)),
                "spec_proposed": cols["proposed"][j],
                "spec_accepted": cols["accepted"][j],
                "gap_ms": round(cols["gap"][j], 3),
                "sched_ms": round(cols["sched"][j], 3),
                "launch_ms": round(cols["launch"][j], 3),
                "sync_ms": round(cols["sync"][j], 3),
                "compile": cols["compile"][j],
                **dict(zip(WORK_COLUMNS, work[j])),
                **{k: (None if v != v else v)       # NaN: not read
                   for k, v in zip(timed_keys, timed[j])},
            })
        return out

    def percentiles(self, window_s: Optional[float] = None,
                    now: Optional[float] = None) -> dict:
        """Per-token step-time percentiles over the ring.

        The default window is the RING — the last ``capacity`` dispatches,
        however old (an idle engine keeps reporting its most recent
        activity rather than going blank); pass ``window_s`` to restrict
        to recent wall time. Compile-bearing first dispatches and
        speculative windows are excluded (see module docstring). Returns
        ``step_ms_p50/p90/p99`` (None when no eligible sample) plus the
        sample count.
        """
        with self._lock:
            order = self._order()
            mask = (self._steps[order] > 0) & ~self._compile[order]
            if window_s is not None:
                cutoff = (time.monotonic() if now is None else now) - window_s
                mask &= self._ts[order] >= cutoff
            rows = order[mask]
            per_step = (self._dispatch_ms[rows]
                        / np.maximum(self._steps[rows], 1))
        if len(per_step) == 0:
            return {"step_ms_p50": None, "step_ms_p90": None,
                    "step_ms_p99": None, "samples": 0}
        p50, p90, p99 = np.percentile(per_step, (50, 90, 99))
        return {
            "step_ms_p50": round(float(p50), 4),
            "step_ms_p90": round(float(p90), 4),
            "step_ms_p99": round(float(p99), 4),
            "samples": int(len(per_step)),
        }

    def phases(self, window_s: Optional[float] = None,
               now: Optional[float] = None) -> dict:
        """Per-phase dispatch-anatomy percentiles + windowed fractions.

        Same window semantics as :meth:`percentiles` (ring by default,
        ``window_s`` to restrict; compile-bearing rows excluded — a
        compile's minutes of tracing would drown every phase). For each
        phase in gap/sched/launch/sync: ``{phase}_ms_p50/p90/p99`` and
        ``{phase}_ms_total`` over the window, plus ``dispatch_ms_total``,
        ``host_ms_total`` (gap+sched+launch) and the derived gauge
        ``host_overhead_fraction`` = host_ms_total / dispatch_ms_total —
        the share of accounted wall time the host spent NOT blocked on
        the device. How idle the DEVICE was is not in these columns: the
        profiler measures it (``POST /backend/trace``).

        The measured parts of gap (process/book/free) get the same
        quantiles and totals; they lie INSIDE gap and are in no sum here.
        ``thread`` is the engine thread's own account of the same rows:
        ``span_ms_total`` and each state's share of it (cpu/runq/blocked/
        wait/idle; ``runq`` None where no row could read it).
        """
        with self._lock:
            order = self._order()
            mask = ~self._compile[order]
            if window_s is not None:
                cutoff = (time.monotonic() if now is None else now) - window_s
                mask &= self._ts[order] >= cutoff
            rows = order[mask]
            ph_cols = {
                "gap": self._gap_ms[rows].copy(),
                "sched": self._sched_ms[rows].copy(),
                "launch": self._launch_ms[rows].copy(),
                "sync": self._sync_ms[rows].copy(),
                **{k[:-3]: self._parts[rows, j].copy()
                   for j, k in enumerate(PART_COLUMNS)},
            }
            dispatch = self._dispatch_ms[rows].copy()
            clock = dict(zip(CLOCK_COLUMNS, self._clock[rows].T.copy()))
        out: dict = {"samples": int(len(dispatch))}
        span = float(clock["span_ms"].sum())
        out["thread"] = {
            "span_ms_total": round(span, 3),
            "share": {
                st: (None if span <= 0 or (
                        st == "runq" and np.isnan(clock["runq_ms"]).all())
                     else round(float(np.nansum(clock[f"{st}_ms"])) / span, 4))
                for st in THREAD_STATES},
        }
        if len(dispatch) == 0:
            for ph in (*ph_cols, "host"):
                out[f"{ph}_ms_p50"] = None
                out[f"{ph}_ms_p90"] = None
                out[f"{ph}_ms_p99"] = None
            for ph in ph_cols:
                out[f"{ph}_ms_total"] = 0.0
            out["dispatch_ms_total"] = 0.0
            out["host_ms_total"] = 0.0
            out["host_overhead_fraction"] = None
            return out
        for ph, arr in ph_cols.items():
            p50, p90, p99 = np.percentile(arr, (50, 90, 99))
            out[f"{ph}_ms_p50"] = round(float(p50), 4)
            out[f"{ph}_ms_p90"] = round(float(p90), 4)
            out[f"{ph}_ms_p99"] = round(float(p99), 4)
            out[f"{ph}_ms_total"] = round(float(arr.sum()), 3)
        host = ph_cols["gap"] + ph_cols["sched"] + ph_cols["launch"]
        # host percentiles are computed on the per-record SUM, not a sum
        # of per-phase percentiles (those don't compose)
        p50, p90, p99 = np.percentile(host, (50, 90, 99))
        out["host_ms_p50"] = round(float(p50), 4)
        out["host_ms_p90"] = round(float(p90), 4)
        out["host_ms_p99"] = round(float(p99), 4)
        total = float(dispatch.sum())
        out["dispatch_ms_total"] = round(total, 3)
        out["host_ms_total"] = round(float(host.sum()), 3)
        out["host_overhead_fraction"] = (
            round(float(host.sum()) / total, 4) if total > 0 else None)
        return out

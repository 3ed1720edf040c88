"""Host-side block allocator for the paged KV cache.

vLLM-style PagedAttention bookkeeping (Kwon et al., SOSP 2023) adapted to
this engine's static-shape XLA model: HBM holds one block pool
``[L, num_blocks, Hkv, block_tokens, hd]`` (engine.kvcache.PagedKVCache;
int4 pools nibble-pack head_dim so their last dim is ``hd/2`` — the
allocator is deliberately dtype-blind, a block id maps the same rows
whatever the pool stores) and
every slot owns a *block table* — a [max_blocks] i32 row mapping logical
context blocks to physical pool blocks. All allocation state (free list,
refcounts, prefix-sharing pool) lives here on the host; the device only
ever sees the tables as a small [S, max_blocks] i32 array.

Design points:

  * **Reservation, not preemption.** A sequence is admitted only when the
    pool can cover its worst case (``min(prompt + max_new, max_ctx)``
    tokens), so a mid-decode dispatch can never run out of blocks — there
    is no preemption/recompute path to get wrong. Capacity overcommit
    comes from ``max_new_tokens`` being far below ``max_ctx`` for real
    traffic, and from prefix sharing.
  * **Whole-block prefix sharing.** When a finished admission's prompt is
    registered, each *full* block of the prompt is keyed by a running hash
    of the tokens it covers and kept in a pool (refcounted). A later
    prompt sharing the same leading blocks maps them into its table
    read-only and computes only the tail — chunked prefill then starts at
    a block boundary. Writes never touch a shared block: a sequence's
    write frontier always lies past its shared prefix.
  * **A prefix of a model with recurrent state ends on a SNAPSHOT.** The
    keys of a prefix can be read again; the per-slot state that is not keys
    (a linear-attention sum, a convolution's rows) stands only where it was
    kept. With ``snapshots`` > 0 the allocator also owns that many rows of
    the runner's snapshot arrays: a prompt whose prefill is worth keeping
    (``begin_snapshot``) has the state at its last whole prefill chunk (a
    block boundary: prompts that open with one document and add less than a
    chunk keep ONE state, the document's) copied into a row, bound to the
    chain's key at that boundary when the prompt is registered; ``match_prefix`` then returns the longest chain
    THAT ENDS ON A SNAPSHOT (else nothing) and ``allocate`` records the row
    the admission restores from. A snapshot lives and dies with its chain's
    last block: pinned while a sequence maps the block, evicted with it
    (LRU, as the pool's). With ``snapshots`` = 0 nothing of this runs.
  * **Block 0 is the trash block.** The decode program writes a KV row for
    every slot each step, active or not (static shapes). Released slots'
    device table rows are reset to all-zeros so those garbage writes land
    in a reserved scratch block that no table maps for real data.

All mutation happens on the scheduler's engine thread; the lock only
guards the read side (metrics scrapes from API threads).

Topology-blindness: under a device mesh the pool shards its kv-head
axis over 'model' (parallel.sharding.paged_kv_spec) while THIS allocator
stays host-side with its block ids global — every device walks any
slot's table against its own head shard, so admission, refcounts, and
prefix sharing are identical on one chip and on eight. Nothing in this
module may ever depend on the mesh.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import threading
from collections import OrderedDict
from typing import Optional

import numpy as np

from localai_tpu.faults import registry as _faults


def block_tokens_default() -> int:
    """Tokens per KV block (``LOCALAI_KV_BLOCK_TOKENS``, default 64)."""
    try:
        v = int(os.environ.get("LOCALAI_KV_BLOCK_TOKENS", "64"))
    except ValueError:
        return 64
    return max(8, v)


def overcommit_default() -> float:
    """Default pool size as a ratio of the contiguous layout's footprint
    (``LOCALAI_KV_OVERCOMMIT``, default 1.0; ``engine.kv_num_blocks`` sets
    an absolute count and wins)."""
    try:
        v = float(os.environ.get("LOCALAI_KV_OVERCOMMIT", "") or 1.0)
    except ValueError:
        return 1.0
    return max(0.01, v)


@dataclasses.dataclass
class BlockStats:
    total: int          # allocatable blocks (pool minus the trash block)
    free: int           # immediately free
    cached: int         # prefix-pool blocks reclaimable on demand
    used: int           # referenced by at least one live sequence
    high_watermark: int  # max concurrently-used blocks since init
    spec_reserved: int = 0  # blocks held purely for speculative lookahead

    @property
    def available(self) -> int:
        return self.free + self.cached

    @property
    def utilization(self) -> float:
        return self.used / self.total if self.total else 0.0


class BlockAllocator:
    """Free list + per-sequence block tables + refcounted prefix pool."""

    def __init__(self, num_blocks: int, block_tokens: int,
                 max_blocks_per_seq: int, snapshots: int = 0):
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (one is the trash block)")
        self.num_blocks = num_blocks
        self.block_tokens = block_tokens
        self.max_blocks_per_seq = max_blocks_per_seq
        self._lock = threading.Lock()
        # block 0 reserved: the garbage-write target for inactive slots
        self._free: list[int] = list(range(num_blocks - 1, 0, -1))
        self._ref = np.zeros(num_blocks, np.int32)
        self._ref[0] = 1  # trash never allocated
        # seq (slot) -> list of physical block ids in logical order
        self.tables: dict[int, list[int]] = {}
        # how many leading blocks of each table are shared (read-only)
        self.shared_blocks: dict[int, int] = {}
        # speculation reservation: trailing blocks of a table held ONLY so
        # a draft window can overshoot the decode frontier (localai_tpu.
        # spec). Rollback is a runner-side position rollback — the blocks
        # stay reserved for the slot's lifetime and never enter the
        # prefix pool (register_prefix is prompt-keyed), so rejection
        # can't leak or share a speculation row.
        self.spec_blocks: dict[int, int] = {}
        # prefix pool: chain-hash of covered tokens -> block id, LRU order
        self._prefix: "OrderedDict[str, int]" = OrderedDict()
        self._block_key: dict[int, str] = {}
        self._watermark = 0
        # lifetime counters (telemetry)
        self.shared_tokens_total = 0
        self.evictions_total = 0
        # optional HBM→host spill tier under the prefix pool (fleet.
        # kveconomy.tiering.HostTier, attached by the runner): LRU pool
        # evictions pack their rows to host RAM instead of vanishing,
        # and a chain-walk miss re-onboards them. The allocator stays
        # device-blind — pack/load are runner callbacks.
        self._tier = None
        self._tier_pack = None
        self._tier_load = None
        self.spills_total = 0
        self.reloads_total = 0
        # state snapshots (the module docstring): chain key -> row of the
        # runner's snapshot arrays; rows no chain holds; a row an admission
        # is filling (bound at register_prefix); the row an admission
        # restores from. ``snapshots`` 0: keys alone, none of it is touched
        self.snapshots = snapshots
        self._snap: dict[str, int] = {}
        self._snap_at: dict[str, int] = {}      # ... -> tokens it stands for
        self._snap_free: list[int] = list(range(snapshots - 1, -1, -1))
        self._snap_pending: dict[int, int] = {}
        self.restore_row: dict[int, int] = {}
        self.snapshots_taken = 0
        self.snapshots_restored = 0
        self.snapshot_evictions = 0

    # -- sizing -----------------------------------------------------------

    def blocks_for(self, tokens: int) -> int:
        return max(1, -(-tokens // self.block_tokens))

    def _reclaimable(self) -> int:  # jaxlint: guarded-by(_lock)
        """Prefix-pool blocks held only by the pool (evictable). Caller
        holds the lock."""
        return sum(1 for b in self._prefix.values() if self._ref[b] == 1)

    # -- HBM→host tiering -------------------------------------------------

    def attach_tier(self, tier, *, pack, load) -> None:
        """Wire the host-RAM spill tier under the prefix pool.

        ``pack(bid) -> payload dict`` gathers one pool block's raw rows
        to host numpy; ``load(bid, payload)`` scatters them back —
        both are runner-owned so this module never touches the device.
        Call before serving starts (engine-thread mutation discipline
        applies once traffic flows)."""
        with self._lock:
            self._tier = tier
            self._tier_pack = pack
            self._tier_load = load

    def _spill(self, key: str, bid: int) -> None:  # jaxlint: guarded-by(_lock)
        """Best-effort park of an evicted pool block in the host tier.
        Caller holds the lock; the device gather is the price of not
        losing host-RAM-sized cache capacity — eviction is already the
        slow path."""
        try:
            payload = self._tier_pack(bid)  # jaxlint: disable=blocking-under-lock
            if payload is not None and self._tier.put(key, payload):
                self.spills_total += 1
        except Exception:  # noqa: BLE001 — a failed spill is a plain evict
            pass

    def _reload(self, key: str,
                exclude: list[int]) -> Optional[int]:  # jaxlint: guarded-by(_lock)
        """Re-onboard a spilled chain block into a free (or freshly
        evicted) pool block; returns its id as a pool-referenced prefix
        entry, or None. Caller holds the lock. ``exclude`` protects
        blocks already matched this walk from being picked as eviction
        victims (they carry only the pool reference until allocate()
        pins them)."""
        if not self._tier.contains(key):
            return None
        if self._free:
            bid = self._free.pop()
        else:
            bid = self._evict_one(exclude=exclude)
            if bid is None:
                return None
        payload = self._tier.take(key)
        if payload is None:  # raced away (budget churn)
            self._free.append(bid)
            return None
        try:
            self._tier_load(bid, payload)  # jaxlint: disable=blocking-under-lock
        except Exception:  # noqa: BLE001 — corrupt spill = miss, not error
            self._free.append(bid)
            return None
        self._prefix[key] = bid
        self._block_key[bid] = key
        self._ref[bid] = 1
        self.reloads_total += 1
        return bid

    def tier_stats(self) -> Optional[dict]:
        """The spill tier's accounting pane (None when tiering is off)."""
        with self._lock:
            tier = self._tier
            spills = self.spills_total
            reloads = self.reloads_total
        if tier is None:
            return None
        s = tier.stats()
        s["spills_total"] = spills
        s["reloads_total"] = reloads
        return s

    # -- prefix sharing ---------------------------------------------------

    @staticmethod
    def _chain(tokens: list[int], nb: int, bt: int) -> list[str]:
        """Running hash per full block: key i covers tokens[:(i+1)*bt]."""
        keys = []
        h = hashlib.sha1()
        for i in range(nb):
            # host token lists only — no device array ever enters here
            h.update(np.asarray(  # jaxlint: disable=host-sync-in-hot-path
                tokens[i * bt:(i + 1) * bt], np.int64).tobytes())
            keys.append(h.hexdigest())
        return keys

    def match_prefix(self, prompt: Optional[list[int]]) -> list[int]:
        """Physical block ids of the longest pool-cached full-block prefix
        of ``prompt``. Never covers the final prompt token (its logits must
        be recomputed to seed sampling), so at most (n-1)//bt blocks."""
        if not prompt:
            return []
        bt = self.block_tokens
        nb = (len(prompt) - 1) // bt
        if nb <= 0:
            return []
        out: list[int] = []
        kept = 0
        with self._lock:
            for key in self._chain(prompt, nb, bt):
                bid = self._prefix.get(key)
                if bid is None and self._tier is not None:
                    # HBM miss, maybe a host-RAM hit: re-onboard the
                    # spilled block and keep walking the chain
                    bid = self._reload(key, exclude=out)
                if bid is None:
                    break
                out.append(bid)
                if key in self._snap:
                    kept = len(out)
        # recurrent state: the chain as far as its last snapshot
        return out[:kept] if self.snapshots else out

    def register_prefix(self, seq: int, prompt: list[int]) -> int:
        """Insert ``seq``'s full prompt blocks into the prefix pool (each
        gains a pool reference). Call only after the blocks' contents have
        been dispatched to the device. Returns blocks registered."""
        if not prompt:
            return 0
        added = 0
        with self._lock:
            table = self.tables.get(seq)
            if table is None:
                return 0
            bt = self.block_tokens
            nb = min((len(prompt) - 1) // bt, len(table))
            keys = self._chain(prompt, nb, bt)
            for i, key in enumerate(keys):
                if key in self._prefix:
                    self._prefix.move_to_end(key)
                    continue
                bid = table[i]
                if bid in self._block_key:  # already caches another chain
                    continue
                self._prefix[key] = bid
                self._block_key[bid] = key
                self._ref[bid] += 1
                added += 1
                if self._tier is not None:
                    # this chain just re-materialized in HBM from a fresh
                    # prefill — any spilled copy is now stale (a block is
                    # HBM-resident XOR spilled, audited by
                    # check_invariants)
                    self._tier.discard(key)
            row, at = self._snap_pending.pop(seq, (None, 0))
            if row is not None:
                # the state ``begin_snapshot`` had copied: the chain's now,
                # if the block it was taken behind is (else the row goes
                # back)
                key = keys[at // bt - 1] if 0 < at // bt <= nb else None
                if key in self._prefix and key not in self._snap:
                    self._snap[key], self._snap_at[key] = row, at
                    self.snapshots_taken += 1
                else:
                    self._snap_free.append(row)
        return added

    def begin_snapshot(self, seq: int, prompt: list[int], shared: int,
                       chunk: int) -> Optional[tuple[int, int]]:
        """Whether ``seq``'s admission keeps the state of its prompt: (the
        snapshot row to copy it into, the position it is taken at), or
        None. The position is the prompt's last multiple of ``chunk`` (the
        engine's prefill chunk) in whole blocks, short of its last token:
        prompts that open with one long document and go on for less than a
        chunk then keep ONE state, the document's, under one key. Kept
        where that saves a later prompt at least a chunk of prefill behind
        the ``shared`` this one was itself served from, no chain holds it
        yet, and a row is free or the LRU snapshot that no sequence pins
        and that is no longer than this one can go (with its block): a
        short prompt never costs a long document its state."""
        bt = self.block_tokens
        at = (len(prompt) - 1) // max(chunk, 1) * max(chunk, 1) // bt * bt
        if not self.snapshots or at - shared < max(chunk, 1):
            return None
        key = self._chain(prompt, at // bt, bt)[-1]
        with self._lock:
            if key in self._snap or seq not in self.tables:
                return None
            if not self._snap_free:
                victim = next((k for k, b in self._prefix.items()
                               if self._ref[b] == 1
                               and self._snap_at.get(k, at + 1) <= at), None)
                if victim is None:
                    return None
                self._free.append(self._drop(victim))
            self._snap_pending[seq] = (self._snap_free.pop(), at)
            return self._snap_pending[seq]

    def snapshot_pending(self, seq: int) -> bool:
        """Whether ``seq``'s admission is filling a snapshot row."""
        with self._lock:
            return seq in self._snap_pending

    def _drop(self, key: str) -> int:  # jaxlint: guarded-by(_lock)
        """Take chain entry ``key`` out of the pool, its snapshot with it;
        returns the block, unreferenced. Caller holds the lock."""
        bid = self._prefix.pop(key)
        del self._block_key[bid]
        self._ref[bid] = 0
        self.evictions_total += 1
        row = self._snap.pop(key, None)
        if row is not None:
            del self._snap_at[key]
            self._snap_free.append(row)
            self.snapshot_evictions += 1
        return bid

    def _evict_one(self, exclude: Optional[list[int]] = None,
                   ) -> Optional[int]:  # jaxlint: guarded-by(_lock)
        """Drop the LRU pool-only block; returns its id. Caller holds the
        lock. With a tier attached the victim's rows spill to host RAM
        first (best effort). ``exclude`` shields blocks a concurrent
        chain walk already claimed (pool-ref-only until allocate pins
        them) from victim selection."""
        shielded = set(exclude or ())
        victim = next((k for k, b in self._prefix.items()
                       if self._ref[b] == 1 and b not in shielded), None)
        if victim is None:
            return None
        bid = self._drop(victim)
        if self._tier is not None:
            self._spill(victim, bid)
        return bid

    # -- allocate / release ----------------------------------------------

    def allocate(self, seq: int, tokens: int,
                 prompt: Optional[list[int]] = None,
                 spec_tokens: int = 0) -> Optional[int]:
        """Build ``seq``'s block table covering ``tokens + spec_tokens``
        rows, sharing pool-cached prompt prefix blocks where possible.
        ``spec_tokens`` extra rows are the slot's speculative-decoding
        lookahead (a draft window writes up to gamma rows past the decode
        frontier); the blocks they add beyond the base reservation are
        recorded as speculation blocks — pure reservation, audited by
        :meth:`check_invariants`, freed with the table at release.
        Returns the shared-token count, or None when the pool cannot
        cover the reservation (the caller queues the request). ``seq``
        must not already hold a table."""
        if _faults.ACTIVE and _faults.apply("paged.allocate",
                                            key=str(seq)) is not None:
            return None  # injected exhaustion: report the pool full
        nb = self.blocks_for(tokens + spec_tokens)
        nb_spec = nb - self.blocks_for(tokens)
        shared = self.match_prefix(prompt) if prompt else []
        if self.snapshots and len(shared) > max(0, nb - 1):
            shared = []     # a chain cut short would end on no snapshot
        shared = shared[: max(0, nb - 1)]  # at least one writable block
        with self._lock:
            assert seq not in self.tables, f"seq {seq} already has a table"
            # reference the shared blocks FIRST: a pool-only shared block
            # (ref==1) would otherwise be an eligible LRU eviction victim
            # in the fresh loop below and end up in the table twice —
            # once read-only, once writable
            for bid in shared:
                self._ref[bid] += 1
                key = self._block_key.get(bid)
                if key is not None:
                    self._prefix.move_to_end(key)
            need = nb - len(shared)
            if need > len(self._free) + self._reclaimable():
                for bid in shared:  # roll the reservation back
                    self._ref[bid] -= 1
                return None
            fresh: list[int] = []
            for _ in range(need):
                if not self._free:
                    evicted = self._evict_one()
                    assert evicted is not None
                    self._free.append(evicted)
                fresh.append(self._free.pop())
            for bid in fresh:
                self._ref[bid] = 1
            self.tables[seq] = shared + fresh
            self.shared_blocks[seq] = len(shared)
            if self.snapshots and shared:
                self.restore_row[seq] = self._snap[
                    self._block_key[shared[-1]]]
                self.snapshots_restored += 1
            if nb_spec:
                self.spec_blocks[seq] = nb_spec
            used = self.num_blocks - 1 - len(self._free) - self._reclaimable()
            self._watermark = max(self._watermark, used)
        n_shared = len(shared) * self.block_tokens
        self.shared_tokens_total += n_shared
        return n_shared

    def extend(self, seq: int, tokens: int, spec_tokens: int = 0) -> bool:
        """Grow ``seq``'s existing table to cover ``tokens + spec_tokens``
        rows (used when an admission resumes past disk-loaded rows);
        ``spec_tokens`` records the speculative lookahead exactly like
        :meth:`allocate`. False on exhaustion."""
        with self._lock:
            table = self.tables.get(seq)
            if table is None:
                return False
            nb = self.blocks_for(tokens + spec_tokens)
            nb_spec = nb - self.blocks_for(tokens)
            need = nb - len(table)
            if need <= 0:
                # the retained table already covers the reservation and
                # any lookahead: there is no distinct speculation tail to
                # account (recording one would make check_invariants
                # audit unrelated old tail blocks)
                self.spec_blocks.pop(seq, None)
                return True
            if need > len(self._free) + self._reclaimable():
                return False  # nothing recorded — nothing was reserved
            if nb_spec:
                self.spec_blocks[seq] = nb_spec
            else:
                self.spec_blocks.pop(seq, None)
            for _ in range(need):
                if not self._free:
                    evicted = self._evict_one()
                    assert evicted is not None
                    self._free.append(evicted)
                bid = self._free.pop()
                self._ref[bid] = 1
                table.append(bid)
            used = self.num_blocks - 1 - len(self._free) - self._reclaimable()
            self._watermark = max(self._watermark, used)
        return True

    def release(self, seq: int) -> None:
        with self._lock:
            table = self.tables.pop(seq, None)
            self.shared_blocks.pop(seq, None)
            self.spec_blocks.pop(seq, None)
            self.restore_row.pop(seq, None)
            row, _ = self._snap_pending.pop(seq, (None, 0))
            if row is not None:     # an admission abandoned part-way
                self._snap_free.append(row)
            if table is None:
                return
            for bid in table:
                self._ref[bid] -= 1
                if self._ref[bid] == 0:
                    self._free.append(bid)

    # -- views ------------------------------------------------------------

    def table_row(self, seq: int) -> np.ndarray:
        """[max_blocks_per_seq] i32 device-shaped table row (trash-padded)."""
        row = np.zeros(self.max_blocks_per_seq, np.int32)
        with self._lock:
            t = list(self.tables.get(seq, []))
        row[: len(t)] = t[: self.max_blocks_per_seq]
        return row

    def tables_snapshot(self) -> dict[int, int]:
        """{seq: table length} under the lock — the /debug/kv view (the
        engine thread inserts/pops tables concurrently; iterating the
        live dict from an API thread would race the mutation)."""
        with self._lock:
            return {seq: len(t) for seq, t in self.tables.items()}

    def stats(self) -> BlockStats:
        with self._lock:
            free = len(self._free)
            cached = self._reclaimable()
            total = self.num_blocks - 1
            return BlockStats(
                total=total,
                free=free,
                cached=cached,
                used=total - free - cached,
                high_watermark=self._watermark,
                spec_reserved=sum(self.spec_blocks.values()),
            )

    def check_invariants(self) -> list[str]:
        """Block-conservation audit from refcount ground truth. Returns
        violation strings (empty = healthy). Invariants:

          * every allocatable block is exactly one of {free, referenced};
            free blocks carry refcount 0, referenced ones ≥ 1 — so
            ``free + used + cached == total`` by construction;
          * the free list holds no duplicates and never the trash block;
          * every table block id is a live (ref ≥ 1) non-trash block, and
            a table's shared leading blocks are also pool-referenced
            (ref ≥ 2);
          * every prefix-pool chain entry maps to a live block and the
            key↔block indices agree.

        O(blocks + table rows) under the lock — called from scheduler
        drains only behind ``LOCALAI_KV_CHECK`` and from every chaos
        scenario, surfaced at ``/debug/kv``."""
        problems: list[str] = []
        with self._lock:
            free_set = set(self._free)
            if len(free_set) != len(self._free):
                problems.append("free list holds duplicate block ids")
            if 0 in free_set:
                problems.append("trash block 0 is on the free list")
            if self._ref[0] < 1:
                problems.append("trash block 0 lost its standing reference")
            for bid in range(1, self.num_blocks):
                ref = int(self._ref[bid])
                if bid in free_set and ref != 0:
                    problems.append(
                        f"block {bid} is free but has refcount {ref}")
                if bid not in free_set and ref < 1:
                    problems.append(
                        f"block {bid} leaked: refcount {ref}, not free")
            for seq, table in self.tables.items():
                shared = self.shared_blocks.get(seq, 0)
                for i, bid in enumerate(table):
                    if bid == 0:
                        problems.append(f"seq {seq} table maps trash block")
                        continue
                    if bid in free_set:
                        problems.append(
                            f"seq {seq} table block {bid} is on the "
                            "free list")
                    want = 2 if i < shared else 1
                    if int(self._ref[bid]) < want:
                        problems.append(
                            f"seq {seq} {'shared ' if i < shared else ''}"
                            f"block {bid} refcount {int(self._ref[bid])} "
                            f"< {want}")
            for seq, nspec in self.spec_blocks.items():
                table = self.tables.get(seq)
                if table is None:
                    problems.append(
                        f"seq {seq} holds a speculation reservation "
                        f"({nspec} blocks) but no table")
                    continue
                if nspec < 0 or nspec > len(table):
                    problems.append(
                        f"seq {seq} speculation reservation {nspec} "
                        f"outside its table of {len(table)} blocks")
                    continue
                # speculation blocks are the table TAIL and must never be
                # shared through the prefix pool (a rejected draft row in
                # a shared block would poison every sharer)
                for bid in table[len(table) - nspec:]:
                    if bid in self._block_key:
                        problems.append(
                            f"seq {seq} speculation block {bid} leaked "
                            "into the prefix pool")
            for key, bid in self._prefix.items():
                if int(self._ref[bid]) < 1:
                    problems.append(
                        f"cached chain block {bid} refcount "
                        f"{int(self._ref[bid])} < 1")
                if self._block_key.get(bid) != key:
                    problems.append(
                        f"prefix pool and block-key index disagree on "
                        f"block {bid}")
            if len(self._block_key) != len(self._prefix):
                problems.append("block-key index size != prefix pool size")
            # snapshots: every one hangs on a chain entry of the pool, and
            # every row is free, a chain's or an admission's, exactly once
            for key in self._snap:
                if key not in self._prefix:
                    problems.append(
                        f"snapshot of chain {key[:12]}… outlived its block")
            rows = (self._snap_free + list(self._snap.values())
                    + [row for row, _ in self._snap_pending.values()])
            if set(self._snap_at) != set(self._snap):
                problems.append("snapshot lengths and rows disagree on "
                                "which chains hold one")
            if sorted(rows) != list(range(self.snapshots)):
                problems.append(
                    f"snapshot rows {sorted(rows)} are not the "
                    f"{self.snapshots} rows once each")
            for seq, row in self.restore_row.items():
                if seq not in self.tables or not self.shared_blocks.get(seq):
                    problems.append(
                        f"seq {seq} restores snapshot row {row} behind no "
                        "shared prefix")
            if self._tier is not None:
                # tier residency: a chain lives in the HBM pool XOR the
                # host tier — double residency means a reload forgot to
                # consume the spill (stale host rows would shadow newer
                # HBM contents on the next churn cycle)
                hbm_keys = set(self._prefix)
                for key in self._tier.keys():
                    if key in hbm_keys:
                        problems.append(
                            f"chain {key[:12]}… resident in the HBM pool "
                            "AND spilled to the host tier")
                # host-side accounting under the tier's own fine lock,
                # not a device/RPC round-trip
                ts = self._tier.stats()  # jaxlint: disable=blocking-under-lock
                if ts["bytes"] > ts["budget_bytes"]:
                    problems.append(
                        f"host tier over budget: {ts['bytes']} bytes "
                        f"held vs {ts['budget_bytes']} budgeted")
            # conservation, derived INDEPENDENTLY of stats() (whose
            # ``used`` is total - free - cached by construction): every
            # live block must be reachable from a table or the prefix
            # pool, and the reachable census must add up block by block
            table_ids = {bid for t in self.tables.values() for bid in t}
            pool_ids = set(self._prefix.values())
            live = {bid for bid in range(1, self.num_blocks)
                    if int(self._ref[bid]) > 0 and bid not in free_set}
            for bid in sorted(live - table_ids - pool_ids):
                problems.append(
                    f"block {bid} leaked: refcount {int(self._ref[bid])} "
                    "but referenced by no table or pool entry")
            free = len(self._free)
            cached = self._reclaimable()
            total = self.num_blocks - 1
            used = total - free - cached
            used_census = len(
                (table_ids | pool_ids)
                - {bid for bid in pool_ids if int(self._ref[bid]) == 1})
            if used_census != used:
                problems.append(
                    f"conservation broken: {used_census} blocks live in "
                    f"tables/pool vs used {used} "
                    f"(free {free}, cached {cached}, total {total})")
        return problems

"""On-device batched sampling — one fused kernel chain per decode step.

Replaces llama.cpp's per-slot CPU sampler chain (repetition penalties,
top-k/top-p/min-p/temperature — applied per token per slot on host) with a
vectorized device implementation over all slots at once: no host round-trip
between logits and sampled token. Parity surface: the sampler options the
reference plumbs via PredictOptions (/root/reference/backend/backend.proto
PredictOptions: TopK/TopP/MinP/Temperature/Penalty/PresencePenalty/
FrequencyPenalty/Seed/NKeep) minus mirostat (CPU-sequential by construction;
accepted in config, mapped to plain temperature sampling).

Design notes (TPU):
  * no op sorts the vocabulary: the K = 256 candidates (covers llama.cpp's
    default top_k=40 and caps tail work) are found in exact stages, all by
    one lemma that holds for any partition of a row: a row's K largest lie
    in the K parts with the largest maxima, since a part that holds a winner
    has a maximum no smaller than the K-th value and at most K parts can.
    Two stages over chunks (TOPK_CHUNK consecutive logits): one pass takes
    every chunk's maximum, a sort of the V / C maxima picks K chunks, and
    their K x C elements are ranked in TOPK_GROUPS groups side by side, then
    the groups' K each once more (the TPU unrolls a sort into its code:
    narrow rows sort in less time and less code than one wide one). Over a
    row of more than TILE_FROM chunks a third stage stands in FRONT of the
    two: the maximum of every tile (TOPK_TILE = 128 consecutive logits, a
    whole lane row: a dense reduction where a chunk fills an eighth of the
    lanes), the K tiles with the largest maxima by the same sort, and those
    tiles' K x 128 logits gathered as 512-byte rows IN THE ROW'S ORDER
    (the chosen tile numbers sorted ascending), over which the two stages
    run as they run over a vocabulary of 32768; a candidate's place in that
    block gives its index back through its tile's number. The depth follows
    the width the program sees (a shard under a mesh decides by ITS width)
    and nothing else. Every sort orders by (value, index) in the total
    order XLA's top-k compares floats by, so the result is
    ``lax.top_k(logits, K)``'s values and indices to the bit, ties, -0.0
    and rows that are mostly -inf (a grammar's mask) included: among equal
    maxima a stage prefers the lower part, which is where the lowest tied
    indices lie (which is why the gathered block keeps the row's order:
    position stands for index in the stages behind it), and padding (-inf
    at the highest indices) stands behind every real entry.
    Where V / C <= K (vocabularies of a few thousand) the stages select
    nothing and one ``lax.top_k`` is the program. Under a mesh that shards
    the vocabulary each chip takes the stages over its own shard (padding
    it to whole chunks itself) and the K of all shards are merged by the
    same (value, index) sort: the only collectives are two all-gathers of
    [S, tp x K].
  * a head's product in bfloat16 is read as bfloat16: its float32 copy is
    rounded explicitly, because a compiler that fuses the product into the
    penalties (as the chunks' maxima now let it) may keep the accumulator's
    precision, and the tokens would go by which operations were fused.
  * top-p/min-p/temperature run on the [S, K] candidate matrix.
  * greedy (temperature<=0) is a select on the same path — no branch.
  * PRNG: per-slot counter-based keys (threefry) so slots are independent
    and reproducible under fixed seed regardless of batch composition.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from localai_tpu.obs.profiler import scoped

MAX_TOPK = 256  # candidate cap; llama.cpp default top_k=40
# stage-one chunk width: 8, 16 and 32 were timed on the chip at the served
# widths, and 16 makes the sorts (V / 16 and 256 x 16 wide) cost least
TOPK_CHUNK = 16
# groups the chunks' elements are ranked in: 1, 2, 4 and 8 were timed there
TOPK_GROUPS = 4
# the front stage's tile, a whole row of the chip's 128 lanes (512 bytes, what
# the gather moves at a time); 256 and 512 were timed and gather too much
TOPK_TILE = 128
# a row of more chunks than this takes the front stage: ``sample`` alone was
# timed on the chip without | with it at the cells' (slots, V): 1593 | 644 us
# at (64, 261120), 715 | 480 at (64, 131072), 398 | 214 at (32, 73448), where
# the sort of the chunks' maxima is 8192 wide or more; ties at (96, 65536)
# (602 | 590: 4096 maxima sort as they are) and (8, 49152) (66 | 68); no
# stage at 32768 and under (a row has no more tiles than the K it would take)
TILE_FROM = 4096


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SamplingParams:
    """Per-slot sampling parameters, stored as [S] arrays on device."""

    temperature: jax.Array      # f32; <=0 → greedy
    top_k: jax.Array            # i32; 0 → disabled (use MAX_TOPK pool)
    top_p: jax.Array            # f32; 1.0 → disabled
    min_p: jax.Array            # f32; 0.0 → disabled
    repeat_penalty: jax.Array   # f32; 1.0 → disabled
    presence_penalty: jax.Array # f32
    frequency_penalty: jax.Array# f32

    @staticmethod
    def init(num_slots: int) -> "SamplingParams":
        # each field gets its own buffer — aliased leaves break jit donation
        def full(v):
            return jnp.full(num_slots, v, jnp.float32)

        return SamplingParams(
            temperature=full(1.0),
            top_k=jnp.full(num_slots, 40, jnp.int32),
            top_p=full(1.0),
            min_p=full(0.0),
            repeat_penalty=full(1.0),
            presence_penalty=full(0.0),
            frequency_penalty=full(0.0),
        )

    DEFAULTS = {
        "temperature": 1.0,
        "top_k": 40,
        "top_p": 1.0,
        "min_p": 0.0,
        "repeat_penalty": 1.0,
        "presence_penalty": 0.0,
        "frequency_penalty": 0.0,
    }

    def with_slot(self, slot: int, **kw) -> "SamplingParams":
        """Functional single-slot update (host-side, at admit time).

        Unspecified (None) fields reset to engine defaults so a reused slot
        never inherits the previous request's sampling options.
        """
        out = {}
        for f in dataclasses.fields(self):
            arr = getattr(self, f.name)
            val = kw.get(f.name)
            if val is None:
                val = self.DEFAULTS[f.name]
            out[f.name] = arr.at[slot].set(jnp.asarray(val, arr.dtype))
        return SamplingParams(**out)

    @classmethod
    def pack(cls, **kw) -> tuple[np.ndarray, np.ndarray]:
        """One slot's values as two host vectors, (i32, f32), each in field
        order, None -> the engine default: the arguments of ONE program
        that arms a slot (``with_packed``) in place of a dispatch a field.
        A value its field cannot hold raises here, on the host."""
        ints, floats = [], []
        for f in dataclasses.fields(cls):
            val = kw.get(f.name)
            if val is None:
                val = cls.DEFAULTS[f.name]
            if isinstance(cls.DEFAULTS[f.name], int):
                ints.append(np.int32(val))
            else:
                floats.append(np.float32(val))
        return np.array(ints, np.int32), np.array(floats, np.float32)

    def with_packed(self, slot, ints, floats) -> "SamplingParams":
        """``with_slot`` from ``pack``'s vectors (traced, inside a program)."""
        ints, floats = iter(ints), iter(floats)
        return self.with_slot(slot, **{
            f.name: next(ints if isinstance(self.DEFAULTS[f.name], int)
                         else floats)
            for f in dataclasses.fields(self)})


def apply_penalties(
    logits: jax.Array,        # [S, V] f32
    counts: jax.Array,        # [S, V] i32 — token occurrence counts (prompt+generated)
    params: SamplingParams,
) -> jax.Array:
    """llama.cpp-style repetition penalty + OpenAI frequency/presence
    penalties, vectorized over slots."""
    seen = counts > 0
    rp = params.repeat_penalty[:, None]
    penalized = jnp.where(logits > 0, logits / rp, logits * rp)
    logits = jnp.where(seen, penalized, logits)
    logits = logits - params.frequency_penalty[:, None] * counts.astype(jnp.float32)
    logits = logits - params.presence_penalty[:, None] * seen.astype(jnp.float32)
    return logits


def _flip(bits: jax.Array) -> jax.Array:
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _ordered(x: jax.Array) -> jax.Array:
    """float32 -> int32 that compares as XLA's top-k compares the floats:
    their total order (-0.0 under 0.0, NaN outermost). ``_floats`` undoes
    it to the bit."""
    return _flip(jax.lax.bitcast_convert_type(x, jnp.int32))


def _floats(keys: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(_flip(keys), jnp.float32)


def _largest(keys: jax.Array, idx: jax.Array, k: int
             ) -> tuple[jax.Array, jax.Array]:
    """Each row's k largest int32 ``keys`` with their ``idx``, largest first
    and among equals the lowest index first: ``lax.top_k``'s order, as a
    sort on (key, index), so that no position and no backend's top-k
    lowering decides a tie."""
    inv, idx = jax.lax.sort((~keys, idx), dimension=1, num_keys=2,
                            is_stable=False)      # no two keys are equal
    return ~inv[:, :k], idx[:, :k]


def _chunk_stages(keys: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """(keys, positions) of the k largest of each row of [S, n x TOPK_CHUNK]
    keys: the k chunks with the largest maxima, then the k largest of those
    chunks' elements (ranked in TOPK_GROUPS groups side by side, whose k
    each are ranked once more)."""
    S = keys.shape[0]
    C, G = TOPK_CHUNK, TOPK_GROUPS
    n = keys.shape[1] // C
    with jax.named_scope("chunk_max"):
        chunks = keys.reshape(S, n, C)
        peaks = jnp.max(chunks, axis=-1)
    with jax.named_scope("topk"):
        _, cid = _largest(
            peaks, jax.lax.broadcasted_iota(jnp.int32, (S, n), 1), k)
        cand = jnp.take_along_axis(chunks, cid[:, :, None], axis=1,
                                   mode="promise_in_bounds")
        where = cid[:, :, None] * C + jnp.arange(C, dtype=jnp.int32)
        keys, idx = _largest(cand.reshape(S * G, k * C // G),
                             where.reshape(S * G, k * C // G), k)
        return _largest(keys.reshape(S, G * k), idx.reshape(S, G * k), k)


def _best_tiles(logits: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """The k tiles (TOPK_TILE consecutive logits) of each row with the
    largest maxima, laid side by side in the order of the row:
    ([S, k x TOPK_TILE] keys, [S, k] tile numbers, ascending)."""
    S, V = logits.shape
    T = TOPK_TILE
    m = -(-V // T)
    # the chip lays a 32-bit [S, V] out in tiles of 8 rows x 128 lanes: read
    # as (row // 8, tile, row % 8) its 512-byte rows are one linear array,
    # which the reduction and the gather both take as it lies (as
    # [S, m, T] the compiler copies the whole block into that order first)
    R = 8 if S % 8 == 0 else 1
    with jax.named_scope("tile_max"):
        if m * T != V:
            logits = jnp.pad(logits, ((0, 0), (0, m * T - V)),
                             constant_values=-jnp.inf)
        # two passes, held apart: fused into the pass that writes the keys
        # the reduction lays 8 maxima to a row of 128 lanes and that pass
        # goes by its packing (491 us at [64, 261120] on the chip); alone,
        # over 128 rows at a time, it fills the lanes (224 + 49 us)
        rows = jax.lax.optimization_barrier(
            _ordered(logits).reshape(S // R, R, m, T).transpose(
                0, 2, 1, 3).reshape(S * m, T))
        L = math.gcd(S * m, 128)
        peaks = jax.lax.optimization_barrier(
            jnp.max(rows.reshape(S * m // L, L, T), axis=-1))
        peaks = peaks.reshape(S // R, m, R).transpose(0, 2, 1).reshape(S, m)
    with jax.named_scope("tile_topk"):
        _, tid = _largest(
            peaks, jax.lax.broadcasted_iota(jnp.int32, (S, m), 1), k)
        # back into the row's order, so that a position in the block still
        # orders equal values as their indices do
        tid = jnp.sort(tid, axis=1)
        s = jnp.arange(S, dtype=jnp.int32)[:, None]
        at = ((s // R) * m + tid) * R + s % R
        block = jnp.take_along_axis(rows, at.reshape(S * k, 1), axis=0,
                                    mode="promise_in_bounds")
        return block.reshape(S, k * T), tid


def _two_stage(logits: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """(keys, indices) of a row's k largest by the chunk stages, over the
    whole row or, where it is wide, over its k best tiles."""
    S, V = logits.shape
    C, T = TOPK_CHUNK, TOPK_TILE
    n = -(-V // C)
    if n <= k:      # a narrow shard: every chunk would be taken
        return _largest(_ordered(logits),
                        jax.lax.broadcasted_iota(jnp.int32, (S, V), 1), k)
    if n > TILE_FROM:
        block, tid = _best_tiles(logits, k)
        keys, at = _chunk_stages(block, k)
        with jax.named_scope("tile_topk"):
            # a candidate's tile number by comparison with every place in
            # the block (a gather of 16384 single numbers took 166 us on the
            # chip, this 9)
            place = jax.lax.broadcasted_iota(jnp.int32, (S, k, k), 2)
            tile = jnp.sum(jnp.where((at // T)[:, :, None] == place,
                                     tid[:, None, :], 0), axis=-1)
            return keys, tile * T + at % T
    # (scope and order as before the front stage came: at these widths the
    # lowered program is what it was, to the letter)
    with jax.named_scope("chunk_max"):
        if n * C != V:
            logits = jnp.pad(logits, ((0, 0), (0, n * C - V)),
                             constant_values=-jnp.inf)
        keys = _ordered(logits)
    return _chunk_stages(keys, k)


def top_candidates(logits: jax.Array, k: int, mesh=None
                   ) -> tuple[jax.Array, jax.Array]:
    """``jax.lax.top_k(logits, k)``'s (values, indices) of a [S, V] float32
    block, bit for bit, without a sort of V: see the module's design notes.
    Under a ``mesh`` whose 'model' axis divides V (which is when the logits
    arrive sharded over the vocabulary), each chip finds its own shard's k
    and the best k of those are taken."""
    S, V = logits.shape
    if -(-V // TOPK_CHUNK) <= k:    # small vocabularies: the one top_k
        return jax.lax.top_k(logits, k)
    tp = 1 if mesh is None else mesh.shape["model"]
    if tp == 1 or V % tp:
        keys, idx = _two_stage(logits, k)
    else:
        rows = "data" if S % mesh.shape["data"] == 0 else None

        def shard(x):                              # [S, V / tp]
            keys, idx = _two_stage(x, min(k, x.shape[1]))
            with jax.named_scope("merge"):
                idx = idx + jax.lax.axis_index("model") * x.shape[1]
                keys = jax.lax.all_gather(keys, "model", axis=1, tiled=True)
                idx = jax.lax.all_gather(idx, "model", axis=1, tiled=True)
                return _largest(keys, idx, k)

        keys, idx = shard_map(
            shard, mesh=mesh, in_specs=P(rows, "model"),
            out_specs=(P(rows), P(rows)), check_vma=False)(logits)
    return _floats(keys), idx


@scoped("sample")
def sample(
    logits: jax.Array,        # [S, V] (any float dtype)
    params: SamplingParams,
    counts: jax.Array,        # [S, V] i32
    keys: jax.Array,          # [S] jax PRNG keys
    bias: jax.Array | None = None,  # [S, V] f32 additive logit bias
                                    # (OpenAI logit_bias + grammar masks as -inf)
    mesh=None,                # the runner's Mesh: where its 'model' axis
                              # shards V the candidates are found a shard
) -> tuple[jax.Array, jax.Array]:
    """Returns (tokens [S] i32, new_keys [S])."""
    S, V = logits.shape
    if logits.dtype != jnp.float32:
        # the head's product in the model's dtype: its float32 copy is held
        # to that dtype's numbers, which a compiler that fuses the product
        # into the penalties is free to skip (XLA allows excess precision)
        fi = jnp.finfo(logits.dtype)
        logits = jax.lax.reduce_precision(logits.astype(jnp.float32),
                                          fi.nexp, fi.nmant)
    if bias is not None:
        logits = logits + bias
    logits = apply_penalties(logits, counts, params)

    k = min(MAX_TOPK, V)
    vals, idx = top_candidates(logits, k, mesh)        # [S, K] desc
    j = jnp.arange(k)[None, :]

    # per-slot top_k limit within the candidate pool (0 → disabled)
    tk = jnp.where(params.top_k[:, None] > 0, params.top_k[:, None], k)
    keep = j < tk

    temp = jnp.maximum(params.temperature, 1e-6)[:, None]
    scaled = jnp.where(keep, vals / temp, -jnp.inf)
    probs = jax.nn.softmax(scaled, axis=-1)

    # top-p (nucleus): keep the smallest prefix with cumulative prob >= top_p
    csum = jnp.cumsum(probs, axis=-1)
    keep_p = (csum - probs) < params.top_p[:, None]
    # min-p: drop candidates below min_p * p_max
    keep_mp = probs >= params.min_p[:, None] * probs[:, :1]
    scaled = jnp.where(keep_p & keep_mp, scaled, -jnp.inf)

    new_keys = jax.vmap(lambda kk: jax.random.split(kk, 2))(keys)
    sub, carry = new_keys[:, 0], new_keys[:, 1]
    sampled_j = jax.vmap(lambda kk, l: jax.random.categorical(kk, l))(sub, scaled)

    greedy = params.temperature <= 0.0
    chosen_j = jnp.where(greedy, 0, sampled_j)
    tokens = jnp.take_along_axis(idx, chosen_j[:, None], axis=1)[:, 0]
    return tokens.astype(jnp.int32), carry


def update_counts(
    counts: jax.Array, tokens: jax.Array, active: jax.Array
) -> jax.Array:
    """Scatter-add sampled tokens into the occurrence counts (inactive slots
    add to a scratch row... no — they add 0)."""
    S = counts.shape[0]
    inc = active.astype(counts.dtype)
    return counts.at[jnp.arange(S), tokens].add(inc)


def count_prompt_tokens(
    counts: jax.Array, slot: jax.Array, tokens: jax.Array, length: jax.Array
) -> jax.Array:
    """Initialize a slot's counts from its prompt (so repetition penalties see
    the prompt, matching llama.cpp's penalty window over context)."""
    V = counts.shape[1]
    t = jnp.arange(tokens.shape[-1])
    valid = t < length
    row = jnp.zeros((V,), counts.dtype).at[tokens.reshape(-1)].add(
        valid.reshape(-1).astype(counts.dtype)
    )
    return counts.at[slot].set(row)

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
    default top_k=40 and caps tail work) are found in two exact stages.
    A row's K largest lie in the K chunks (TOPK_CHUNK consecutive logits)
    with the largest maxima, since a chunk that holds a winner has a
    maximum no smaller than the K-th value and at most K chunks can; so one
    pass takes every chunk's maximum, a sort of the V / C maxima picks K
    chunks, and their K x C elements are ranked in TOPK_GROUPS groups side
    by side, then the groups' K each once more (the TPU unrolls a sort into
    its code: narrow rows sort in less time and less code than one wide
    one). Every sort orders by (value, index) in the total order XLA's
    top-k compares floats by, so the result is ``lax.top_k(logits, K)``'s
    values and indices to the bit, ties, -0.0 and rows that are mostly
    -inf (a grammar's mask) included: among equal maxima stage one prefers
    the lower chunk, which is where the lowest tied indices lie, and
    padding (-inf at the highest indices) stands behind every real entry.
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


def _two_stage(logits: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """(keys, indices) of a row's k largest: the k chunks with the largest
    maxima, then the k largest of those chunks' elements (ranked in
    TOPK_GROUPS groups side by side, whose k each are ranked once more)."""
    S, V = logits.shape
    C, G = TOPK_CHUNK, TOPK_GROUPS
    n = -(-V // C)
    if n <= k:      # a narrow shard: every chunk would be taken
        return _largest(_ordered(logits),
                        jax.lax.broadcasted_iota(jnp.int32, (S, V), 1), k)
    with jax.named_scope("chunk_max"):
        if n * C != V:
            logits = jnp.pad(logits, ((0, 0), (0, n * C - V)),
                             constant_values=-jnp.inf)
        chunks = _ordered(logits).reshape(S, n, C)
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

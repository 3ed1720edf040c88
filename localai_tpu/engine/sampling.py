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
  * full-vocab ops are avoided after one ``lax.top_k`` to K=64..256
    candidates (covers llama.cpp's default top_k=40 and caps tail work);
    top-p/min-p/temperature run on the [S, K] candidate matrix.
  * greedy (temperature<=0) is a select on the same path — no branch.
  * PRNG: per-slot counter-based keys (threefry) so slots are independent
    and reproducible under fixed seed regardless of batch composition.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from localai_tpu.obs.profiler import scoped

MAX_TOPK = 256  # candidate cap; llama.cpp default top_k=40


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


@scoped("sample")
def sample(
    logits: jax.Array,        # [S, V] (any float dtype)
    params: SamplingParams,
    counts: jax.Array,        # [S, V] i32
    keys: jax.Array,          # [S] jax PRNG keys
    bias: jax.Array | None = None,  # [S, V] f32 additive logit bias
                                    # (OpenAI logit_bias + grammar masks as -inf)
) -> tuple[jax.Array, jax.Array]:
    """Returns (tokens [S] i32, new_keys [S])."""
    S, V = logits.shape
    logits = logits.astype(jnp.float32)
    if bias is not None:
        logits = logits + bias
    logits = apply_penalties(logits, counts, params)

    k = min(MAX_TOPK, V)
    vals, idx = jax.lax.top_k(logits, k)           # [S, K] desc
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

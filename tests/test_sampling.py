"""The sampler's candidates are ``lax.top_k``'s, bit for bit (PR 45).

``sampling.top_candidates`` finds the K = ``MAX_TOPK`` largest logits of a
row in two stages (the K chunks with the largest maxima, then the K largest
of those chunks' elements, ranked in groups and the groups' K once more)
where the parent sorted the whole vocabulary; over a row of more than
``TILE_FROM`` chunks a third stage stands in front (PR 63: the K tiles of
128 with the largest maxima, and the two stages over those).
(a) holds its (values, indices) to ``jax.lax.top_k``'s over the benchmark
configurations' sampler widths and over logits with every kind of tie;
(b) holds ``sample`` to a copy of the parent's ``sample`` kept here: the
same tokens and the same carried keys, step after step. Under a mesh that
shards the vocabulary the stages run a shard and the shards' candidates are
merged: the same two checks over a 1 x 4 mesh of the CPU's devices.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.engine import sampling as smp

K = smp.MAX_TOPK
# the sampler widths of the benchmark's ten configurations (the 24B's is a
# chip's quarter of 131072 under tp = 4, and the whole of it without a mesh),
# a width TOPK_CHUNK does not divide, one that keeps the direct path, and one
# that takes the front stage and is no whole number of its tiles
WIDTHS = {"mistral-7b": 32768, "mistral-small-24b": 131072,
          "qwen3-next-ep8": 18992, "trinity-ep8": 25024, "ouro": 49152,
          "ragged": 4099, "direct": K * smp.TOPK_CHUNK,
          "falcon-h1": 261120, "lfm2": 65536, "minicpm-sala": 73448,
          "axk1": 20480, "dots3": 19008,
          "wide_ragged": (smp.TILE_FROM + 3) * smp.TOPK_CHUNK + 5}
KINDS = ("normal", "bfloat16", "integer", "masked_26", "masked_300", "equal",
         "signed_zeros")


def logits_of(kind: str, S: int, V: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((S, V)) * 3).astype(np.float32)
    if kind == "normal":
        return x
    if kind == "bfloat16":      # the served logits: ~8 bits, many ties
        return np.asarray(jnp.asarray(x).astype(jnp.bfloat16)
                          .astype(jnp.float32))
    if kind == "integer":       # ties everywhere
        return np.round(x)
    if kind.startswith("masked_"):
        # the reference check's probes and a grammar: all but a few letters
        # biased to -inf, so most of the K candidates are -inf ties
        keep = int(kind.split("_")[1])
        mask = np.full((S, V), -np.inf, np.float32)
        for s in range(S):
            mask[s, rng.choice(V, keep, replace=False)] = 0.0
        return np.round(x) + mask
    if kind == "equal":
        return np.zeros((S, V), np.float32)
    assert kind == "signed_zeros"
    return np.where(rng.random((S, V)) < 0.5, 0.0, -0.0).astype(np.float32)


candidates = jax.jit(smp.top_candidates, static_argnums=(1, 2))


def front(V: int) -> bool:
    """Whether a row of V logits takes the front stage."""
    return -(-V // smp.TOPK_CHUNK) > smp.TILE_FROM


def bits(a) -> np.ndarray:
    return np.asarray(a).view(np.uint32)


def assert_top_ks(x, vals, idx):
    """(vals, idx) are ``lax.top_k``'s of the whole rows of ``x``, bit for
    bit."""
    want_vals, want_idx = jax.lax.top_k(jnp.asarray(x), K)
    assert np.array_equal(bits(vals), bits(want_vals))
    assert np.array_equal(np.asarray(idx), np.asarray(want_idx))


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_candidates_are_top_ks(width, kind):
    """(Three rows; eight where the front stage runs, whose tiles follow the
    chip's rows of eight. ``equal``, ``signed_zeros`` and ``masked_300`` are
    the rows in which more than K tiles tie.)"""
    V = WIDTHS[width]
    x = jnp.asarray(logits_of(kind, 8 if front(V) else 3, V))
    vals, idx = candidates(x, K)
    assert_top_ks(x, vals, idx)
    assert int(jnp.max(idx)) < V            # padding is never a candidate


def test_direct_path_is_one_top_k():
    """Where the chunks would all be taken (the tier-1 models' vocabularies
    of 256-512) the program is the parent's: one ``top_k`` of the row, and
    the hashed lowered texts of tests/test_qwen3_next.py and test_afmoe.py
    hold as they are."""
    def lowered(fn, V):
        return jax.jit(fn).lower(
            jax.ShapeDtypeStruct((4, V), jnp.float32)).as_text()

    for V in (256, 384, 512, K * smp.TOPK_CHUNK):
        k = min(K, V)
        assert (lowered(lambda x: smp.top_candidates(x, k), V)
                == lowered(lambda x: jax.lax.top_k(x, k), V))
    two = lowered(lambda x: smp.top_candidates(x, K), K * smp.TOPK_CHUNK + 1)
    # the chunks' maxima, the groups of candidates, the groups' K
    assert "top_k" not in two and two.count("stablehlo.sort") == 3


def parent_candidates(logits, k):
    """``top_candidates`` without a mesh as the parent commit of PR 63 had
    it, to the letter: the two stages over the whole row."""
    S, V = logits.shape
    C, G = smp.TOPK_CHUNK, smp.TOPK_GROUPS
    n = -(-V // C)
    if n <= k:
        return jax.lax.top_k(logits, k)
    with jax.named_scope("chunk_max"):
        if n * C != V:
            logits = jnp.pad(logits, ((0, 0), (0, n * C - V)),
                             constant_values=-jnp.inf)
        chunks = smp._ordered(logits).reshape(S, n, C)
        peaks = jnp.max(chunks, axis=-1)
    with jax.named_scope("topk"):
        _, cid = smp._largest(
            peaks, jax.lax.broadcasted_iota(jnp.int32, (S, n), 1), k)
        cand = jnp.take_along_axis(chunks, cid[:, :, None], axis=1,
                                   mode="promise_in_bounds")
        where = cid[:, :, None] * C + jnp.arange(C, dtype=jnp.int32)
        keys, idx = smp._largest(cand.reshape(S * G, k * C // G),
                                 where.reshape(S * G, k * C // G), k)
        keys, idx = smp._largest(keys.reshape(S, G * k),
                                 idx.reshape(S, G * k), k)
    return smp._floats(keys), idx


@pytest.mark.parametrize("width", sorted(WIDTHS) + ["threshold"])
def test_front_stage_engages_by_width(width):
    """The depth follows the width the program sees and nothing else. Over
    ``TILE_FROM`` chunks the lowered text holds the front stage's scopes,
    five sorts (the tiles' maxima, the chosen tiles back in the row's order,
    and the three that were there) and no tensor of a row's V / 16 maxima;
    at the threshold and below it the text is what the parent's stages
    lower, to the letter: the eight cells whose vocabularies stand there
    compile what they compiled."""
    V = (smp.TILE_FROM * smp.TOPK_CHUNK if width == "threshold"
         else WIDTHS[width])
    S, n = 8, -(-V // smp.TOPK_CHUNK)

    def lowered(fn, **kw):
        return jax.jit(lambda x: fn(x, K)).lower(
            jax.ShapeDtypeStruct((S, V), jnp.float32)).as_text(**kw)

    text = lowered(smp.top_candidates)
    if not front(V):
        assert n <= smp.TILE_FROM
        assert text == lowered(parent_candidates)
        assert "tile_" not in lowered(smp.top_candidates, debug_info=True)
        return
    scopes = lowered(smp.top_candidates, debug_info=True)
    for scope in ("tile_max", "tile_topk", "chunk_max", "topk"):
        assert f"/{scope}/" in scopes, scope
    assert "top_k" not in text and text.count("stablehlo.sort") == 5
    assert f"x{n}xi32>" not in text and f"x{n}x{smp.TOPK_CHUNK}xi32>" not in (
        text)
    # the stages behind it see K tiles: a 7B's width, whatever V was
    assert f"tensor<{S}x{K * smp.TOPK_TILE // smp.TOPK_CHUNK}xi32>" in text


def test_the_front_stage_needs_more_tiles_than_it_takes():
    """The constants stand in one relation: a row that takes the front
    stage has more than K tiles (the stage would select nothing else), and
    a tile is whole chunks."""
    assert smp.TILE_FROM * smp.TOPK_CHUNK >= K * smp.TOPK_TILE
    assert smp.TOPK_TILE % smp.TOPK_CHUNK == 0


@pytest.mark.parametrize("kind", ["normal", "equal", "masked_300"])
@pytest.mark.parametrize("rows", [1, 3, 12, 16])
def test_front_stage_is_exact_at_any_row_count(rows, kind):
    """The tiles are read eight rows at a time where the block has whole
    eights (the cells' slots) and a row at a time where it has not (the one
    row of ``_first_token``, a batch of three or twelve)."""
    V = WIDTHS["wide_ragged"]
    x = jnp.asarray(logits_of(kind, rows, V, seed=rows))
    assert_top_ks(x, *candidates(x, K))


# four shards that each take the front stage, of 16 columns past whole tiles
WIDE_SHARDS = 4 * (smp.TILE_FROM + 9) * smp.TOPK_CHUNK


@pytest.fixture(scope="module")
def mesh():
    from localai_tpu.parallel.mesh import MeshPlan, build_mesh

    return build_mesh(MeshPlan(model=4), devices=jax.devices()[:4])


def sharded(mesh, x):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.device_put(jnp.asarray(x), NamedSharding(mesh, P(None, "model")))


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("V", [131072, 8192, 18992, WIDE_SHARDS],
                         ids=["tp4_cell", "narrow", "ragged", "wide_shard"])
def test_candidates_are_top_ks_over_a_sharded_vocabulary(mesh, V, kind):
    """The four-chip cell's width (a shard of 32768 takes both stages), one
    whose shards are too narrow for them (2048: each shard sorts its own),
    one whose shards are no whole number of chunks (4748 = 296.75 x 16:
    each shard pads itself) and one whose SHARD takes the front stage (a
    shard decides by its own width; no whole number of tiles either): the
    merged candidates are ``lax.top_k``'s of the whole row."""
    assert V % 4 == 0 and (V // 4) % smp.TOPK_CHUNK == (12 if V == 18992
                                                        else 0)
    assert front(V // 4) == (V == WIDE_SHARDS)
    x = logits_of(kind, 8 if front(V // 4) else 3, V)
    assert_top_ks(x, *candidates(sharded(mesh, x), K, mesh))


def parent_sample(logits, params, counts, keys, bias=None):
    """``sample`` as the parent commit of PR 45 had it, to the letter."""
    S, V = logits.shape
    logits = logits.astype(jnp.float32)
    if bias is not None:
        logits = logits + bias
    logits = smp.apply_penalties(logits, counts, params)

    k = min(smp.MAX_TOPK, V)
    vals, idx = jax.lax.top_k(logits, k)           # [S, K] desc
    j = jnp.arange(k)[None, :]

    tk = jnp.where(params.top_k[:, None] > 0, params.top_k[:, None], k)
    keep = j < tk

    temp = jnp.maximum(params.temperature, 1e-6)[:, None]
    scaled = jnp.where(keep, vals / temp, -jnp.inf)
    probs = jax.nn.softmax(scaled, axis=-1)

    csum = jnp.cumsum(probs, axis=-1)
    keep_p = (csum - probs) < params.top_p[:, None]
    keep_mp = probs >= params.min_p[:, None] * probs[:, :1]
    scaled = jnp.where(keep_p & keep_mp, scaled, -jnp.inf)

    new_keys = jax.vmap(lambda kk: jax.random.split(kk, 2))(keys)
    sub, carry = new_keys[:, 0], new_keys[:, 1]
    sampled_j = jax.vmap(
        lambda kk, l: jax.random.categorical(kk, l))(sub, scaled)

    greedy = params.temperature <= 0.0
    chosen_j = jnp.where(greedy, 0, sampled_j)
    tokens = jnp.take_along_axis(idx, chosen_j[:, None], axis=1)[:, 0]
    return tokens.astype(jnp.int32), carry


SETTINGS = {
    "top_k_0": dict(top_k=0),
    "top_k_1": dict(top_k=1),
    "top_k_40": dict(top_k=40),
    "top_p": dict(top_k=0, top_p=0.95),
    "min_p": dict(top_k=0, min_p=0.05),
    "penalties": dict(top_k=0, repeat_penalty=1.3, presence_penalty=0.5,
                      frequency_penalty=0.25),
    "hot": dict(top_k=0, temperature=4.0),
    "greedy": dict(temperature=0.0),
    "greedy_masked": dict(temperature=0.0),
}


def streams_agree(S, V, kind, setting, steps, put=jnp.asarray, on=None):
    """``sample`` and the parent's ``sample`` over seeded rows, ``steps``
    steps with the counts and keys carried: the same tokens and the same
    keys at every step. Returns the last step's tokens."""
    params = smp.SamplingParams.init(S)
    for s in range(S):
        params = params.with_slot(s, **SETTINGS[setting])
    bias = None
    if setting == "greedy_masked":      # the reference check's probes
        bias = put(np.where(
            np.isinf(logits_of("masked_26", S, V, seed=9)), -np.inf, 0.0
        ).astype(np.float32))
    fns = {"new": jax.jit(lambda *a: smp.sample(*a, mesh=on)),
           "old": jax.jit(parent_sample)}
    state = {name: (put(np.zeros((S, V), np.int32)),
                    jax.vmap(jax.random.key)(jnp.arange(S) + 42))
             for name in fns}
    for step in range(steps):
        logits = put(logits_of(kind, S, V, seed=step))
        out = {}
        for name, fn in fns.items():
            counts, keys = state[name]
            tokens, keys = fn(logits, params, counts, keys, bias)
            counts = smp.update_counts(counts, tokens, jnp.ones(S, bool))
            state[name] = (counts, keys)
            out[name] = (np.asarray(tokens),
                         np.asarray(jax.random.key_data(keys)))
        assert np.array_equal(out["new"][0], out["old"][0]), step
        assert np.array_equal(out["new"][1], out["old"][1]), step
        assert out["new"][0].max() < V
    return out["new"][0]


@pytest.mark.parametrize("setting", sorted(SETTINGS))
@pytest.mark.parametrize("kind", ["bfloat16", "integer"])
@pytest.mark.parametrize("tp", [1, 4])
def test_sample_returns_the_parents_tokens_and_keys(setting, kind, tp, mesh):
    """Seeded rows, four steps with the counts and keys carried: the tokens
    and the keys of both samplers are the same arrays, at a vocabulary wide
    enough to take the two stages (qn80-ep8-decode's; ``tp`` 4: a quarter of
    it a device, which is no whole number of chunks, the logits, counts and
    bias sharded as the runner's)."""
    S, V = 4, WIDTHS["qwen3-next-ep8"]
    assert -(-V // smp.TOPK_CHUNK) > K
    put, on = jnp.asarray, None
    if tp > 1:
        on = mesh

        def put(x):
            return sharded(mesh, x)
    tokens = streams_agree(S, V, kind, setting, 4, put, on)
    if setting in ("top_k_0", "hot"):   # the draws are draws: rows differ
        assert len(set(tokens.tolist())) > 1


@pytest.mark.parametrize("setting", ["top_k_40", "penalties", "hot",
                                     "greedy_masked"])
def test_sample_over_a_wide_vocabulary_returns_the_parents_tokens(setting):
    """The same check where the front stage runs (eight slots, a width that
    is no whole number of tiles, the served bfloat16 logits): tokens and
    carried keys are the full sort's, step after step."""
    S, V = 8, WIDTHS["wide_ragged"]
    assert front(V)
    streams_agree(S, V, "bfloat16", setting, 3)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_sample_holds_a_low_precision_head_to_its_numbers(dtype):
    """The head's product arrives in the model's dtype. ``sample`` holds its
    float32 copy to that dtype's numbers with a ``reduce_precision``, which
    a compiler may not drop as it may the conversion pair (PR 45: fused into
    the penalties, the chip's program read the product unrounded and one
    greedy probe in 64 chose another letter than the parent's); float32
    logits pass as they are, and the tokens are the parent's either way."""
    S, V = 4, WIDTHS["qwen3-next-ep8"]
    args = (smp.SamplingParams.init(S), jnp.zeros((S, V), jnp.int32),
            jax.vmap(jax.random.key)(jnp.arange(S)))
    x = jnp.asarray(logits_of("normal", S, V)).astype(dtype)
    new, old = jax.jit(smp.sample), jax.jit(parent_sample)
    text = new.lower(x, *args).as_text()
    assert ("reduce_precision" in text) == (dtype == "bfloat16")
    tokens, _ = new(x, *args)
    want, _ = old(x, *args)
    assert np.array_equal(np.asarray(tokens), np.asarray(want))

"""ops/moe.py ``moe_experts``, the routed experts of one expert block as one
grouped Pallas kernel, in the interpreter on the CPU against the loop it
replaces on a TPU (models/experts.py ``experts_loop``, which stays the XLA
path and is the oracle here): seeded weights at small widths, stacked over
(period, block) as the served leaves are. ``_moe`` itself, routed through
either, has to return the same output and the same counts."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu import ops
from localai_tpu.models import experts as xp
from localai_tpu.models import qwen3_next as qn
from localai_tpu.models.llama import LlamaConfig
from localai_tpu.ops import moe

P, M, E, D, F = 2, 3, 8, 128, 64
# float32 operands: what is left is the order inside a dot. bfloat16: the
# loop rounds each dot's result to bfloat16 and the kernel keeps float32 up
# to the third dot's operand (values up to ~6 here: half an ulp is 0.016)
TOL = {"float32": 1e-5, "bfloat16": 0.06}


def leaves(dtype, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(0.1 * rng.standard_normal(shape), dtype)
                 for shape in ((P, M, E, D, F), (P, M, E, D, F),
                               (P, M, E, F, D)))


def routed(n_rows, touched, seed):
    """Routing weights [N, E] float32 that are 0 off ``touched``'s experts,
    and the order ``_moe`` walks them in."""
    rng = np.random.default_rng(seed)
    on = np.zeros(E, bool)
    on[touched] = True
    weights = jnp.asarray(rng.random((n_rows, E)) * on, jnp.float32)
    order = jnp.argsort(~jnp.asarray(on), stable=True).astype(jnp.int32)
    return weights, order


@pytest.mark.parametrize("at", [(0, 0), (1, 2)])
@pytest.mark.parametrize("touched", [[], [5], [6, 1, 3], list(range(E))],
                         ids=["none", "one", "some", "all"])
@pytest.mark.parametrize("n_rows", [1, 32, moe.ROW_TILE + 40])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_kernel_is_the_loop(dtype, n_rows, touched, at):
    """Every row against the touched experts of block ``at`` = (p, m), summed
    in ``order``: the kernel's [N, D] float32 is the loop's. More rows than a
    row tile are an outer grid axis (the last tile padded); an expert no token
    chose is not read (its weights are NaN here), and another block's experts
    are other numbers."""
    experts = leaves(dtype)
    rng = np.random.default_rng(n_rows + len(touched))
    h = jnp.asarray(rng.standard_normal((n_rows, D)), dtype)
    weights, order = routed(n_rows, touched, seed=n_rows)
    off = jnp.asarray(np.isin(np.arange(E), touched, invert=True))
    poisoned = tuple(
        w.at[at[0], at[1]].set(jnp.where(off[:, None, None], jnp.nan,
                                         w[at[0], at[1]]))
        for w in experts)
    n = jnp.int32(len(touched))
    @jax.jit    # the block's index traced, as the period scan hands it over
    def kernel(p):
        return moe.moe_experts(h, weights, order, n, poisoned, p, at[1],
                               interpret=True)

    got = kernel(jnp.int32(at[0]))
    want = xp.experts_loop(h, weights, order, n, experts, *at)
    assert got.shape == (n_rows, D) and got.dtype == jnp.float32
    assert np.isfinite(np.asarray(got)).all()
    if touched:
        assert np.abs(np.asarray(want)).max() > 0.5
        elsewhere = xp.experts_loop(h, weights, order, n, experts,
                                     (at[0] + 1) % P, at[1])
        assert np.abs(np.asarray(want - elsewhere)).max() > 0.5
    else:
        assert not np.asarray(got).any()
    assert np.abs(np.asarray(got - want)).max() < TOL[dtype]


GATES = {"silu": jax.nn.silu, "relu": jax.nn.relu}


@pytest.mark.parametrize("touched", [[5], [6, 1, 3], list(range(E))],
                         ids=["one", "some", "all"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("gate", sorted(GATES))
def test_the_gates_function_is_the_familys(gate, dtype, touched):
    """PR 65: the function on an expert's gate is an argument of kernel and
    loop (SiLU for five families, ReLU for models.smallthinker): under each
    the kernel's [N, D] is the loop's under the same one, and the other
    function's is another number."""
    experts = leaves(dtype)
    h = jnp.asarray(np.random.default_rng(len(touched)).standard_normal(
        (32, D)), dtype)
    weights, order = routed(32, touched, seed=32)
    n = jnp.int32(len(touched))

    @jax.jit
    def kernel(p):
        return moe.moe_experts(h, weights, order, n, experts, p, 2,
                               interpret=True, act=GATES[gate])

    got = kernel(jnp.int32(1))
    want = xp.experts_loop(h, weights, order, n, experts, 1, 2, GATES[gate])
    other = xp.experts_loop(h, weights, order, n, experts, 1, 2,
                            GATES["relu" if gate == "silu" else "silu"])
    assert np.abs(np.asarray(want)).max() > 0.5
    assert np.abs(np.asarray(want - other)).max() > 0.2
    assert np.abs(np.asarray(got - want)).max() < TOL[dtype]
    if gate == "silu":      # the default of both is what it was
        assert np.array_equal(np.asarray(want), np.asarray(xp.experts_loop(
            h, weights, order, n, experts, 1, 2)))


@pytest.mark.parametrize("touched", [[], [6, 1, 3], list(range(E))],
                         ids=["none", "some", "all"])
@pytest.mark.parametrize("n_rows", [32, moe.ROW_TILE + 40])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_an_expert_too_large_for_vmem_goes_through_in_tiles_of_f(
        monkeypatch, dtype, n_rows, touched):
    """PR 44: an expert whose three matrices do not lie in VMEM whole, twice
    (3072 x 3072 at the new configuration's widths), is walked in tiles of
    its intermediate width, an innermost grid axis. Forced here at small
    widths by the budget: F 256 in two tiles of 128. The loop stays the
    oracle; an expert that fits takes no such axis."""
    F2 = 256
    rng = np.random.default_rng(7)
    experts = tuple(jnp.asarray(0.1 * rng.standard_normal(shape), dtype)
                    for shape in ((P, M, E, D, F2), (P, M, E, D, F2),
                                  (P, M, E, F2, D)))
    itemsize = jnp.dtype(dtype).itemsize
    assert moe.f_tile(D, F2, itemsize) == F2        # it fits: one block
    assert moe.f_tile(2048, 512, 2) == 512          # the hybrid's: whole
    assert moe.f_tile(3072, 3072, 2) == 512         # the new widths: six
    monkeypatch.setattr(moe, "BLOCK_BYTES", 2 * 3 * D * itemsize * 128)
    assert moe.f_tile(D, F2, itemsize) == 128
    h = jnp.asarray(rng.standard_normal((n_rows, D)), dtype)
    weights, order = routed(n_rows, touched, seed=n_rows)
    n = jnp.int32(len(touched))
    @jax.jit    # the block's index traced, as the row scan hands it over
    def kernel(p):
        return moe.moe_experts(h, weights, order, n, experts, p, 2,
                               interpret=True)

    got = kernel(jnp.int32(1))
    want = xp.experts_loop(h, weights, order, n, experts, 1, 2)
    assert got.shape == (n_rows, D) and got.dtype == jnp.float32
    if touched:
        assert np.abs(np.asarray(want)).max() > 0.5
    else:
        assert not np.asarray(got).any()
    assert np.abs(np.asarray(got - want)).max() < 2 * TOL[dtype]
    monkeypatch.setattr(moe, "BLOCK_BYTES", 1)
    with pytest.raises(ValueError, match="no 128-aligned tile"):
        moe.f_tile(D, F2, itemsize)


HF = {"model_type": "qwen3_next", "vocab_size": 64, "hidden_size": D,
      "num_hidden_layers": 4, "num_attention_heads": 2,
      "num_key_value_heads": 1, "head_dim": 32, "linear_num_key_heads": 1,
      "linear_num_value_heads": 2, "linear_key_head_dim": 16,
      "linear_value_head_dim": 16, "num_experts": E,
      "num_experts_per_tok": 2, "moe_intermediate_size": F,
      "shared_expert_intermediate_size": F,
      "expert_parallel": {"size": 2, "rank": 1}}


@pytest.mark.parametrize("real", [0, 1, 5, 12], ids=lambda n: f"real{n}")
def test_the_block_returns_the_loops_output_and_counts(real):
    """``_moe`` with the kernel against ``_moe`` with the loop on a [2, 6, D]
    batch whose first ``real`` tokens are real: the same output, and the same
    [experts touched, token-expert pairs here]: a row that is not real has
    weight 0 everywhere and is counted nowhere."""
    cfg = dataclasses.replace(LlamaConfig.from_hf(HF), dtype="float32")
    rng = np.random.default_rng(real)
    experts = leaves("float32", seed=1)
    lp = {"moe_gate": jnp.asarray(
              rng.standard_normal((M, D, cfg.router_width)), jnp.float32),
          "shared_gate": experts[0][0, :, 0], "shared_up": experts[1][0, :, 0],
          "shared_down": experts[2][0, :, 0],
          "shared_router": jnp.asarray(rng.standard_normal((M, D)),
                                       jnp.float32)}
    h = jnp.asarray(rng.standard_normal((2, 6, D)), jnp.float32)
    valid = (jnp.arange(12) < real).reshape(2, 6)
    out_k, counts_k = qn._moe(cfg, h, lp, 1, experts, 1, valid,
                              experts_kernel=True)
    out_l, counts_l = qn._moe(cfg, h, lp, 1, experts, 1, valid)
    assert np.array_equal(np.asarray(counts_k), np.asarray(counts_l))
    # two choices of 16 experts a real token, about half of them held here
    touched, pairs = map(int, counts_l)
    assert 0 <= touched <= pairs <= 2 * real
    if real == 12:
        assert touched > 1 and np.abs(np.asarray(out_l)).max() > 0.5
    assert np.abs(np.asarray(out_k - out_l)).max() < 1e-5


@pytest.mark.parametrize("requested, backend, want", [
    ("auto", "tpu", ("pallas", False)), ("auto", "cpu", ("xla", False)),
    ("xla", "tpu", ("xla", False)),
    ("pallas_interpret", "cpu", ("pallas", True))])
def test_the_experts_go_where_attention_goes(requested, backend, want):
    assert ops.select_moe_impl(requested, hidden=2048, intermediate=512,
                               backend=backend) == want
    assert ops.resolve_attn_impl(requested, backend) == want


def test_widths_mosaic_cannot_tile_are_refused_by_name():
    with pytest.raises(ValueError, match="attn_impl: xla"):
        ops.select_moe_impl("auto", hidden=2048, intermediate=96,
                            backend="tpu")
    with pytest.raises(ValueError, match="pallas_interpret"):
        ops.select_moe_impl("pallas", hidden=2048, intermediate=512,
                            backend="cpu")
    # the interpreter takes any width (the tests' own)
    assert ops.select_moe_impl("pallas_interpret", hidden=64,
                               intermediate=32, backend="cpu")[1]

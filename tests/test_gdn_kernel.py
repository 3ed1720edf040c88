"""ops/gdn.py ``gdn_state_step``, the Gated DeltaNet's decode step as one
Pallas kernel a layer on the carried state, in the interpreter on the CPU
against the step it replaces on a TPU (models/qwen3_next.py ``gdn_step``,
which stays the XLA path and is the oracle here): random float32 state at
small widths, stacked over (period, layer, slot) as the served array is. The
kernel has to return the oracle's numbers in the layer's rows and every other
row of the array bit for bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.models import qwen3_next as qn
from localai_tpu.ops import gdn

P, G, HV, DK, DV = 2, 3, 4, 16, 16
# float32 on both sides: what is left is the order of a sum over dk
TOL = 1e-5


def state(slots, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((P, G, slots, HV, DK, DV)),
                       jnp.float32)


def step_inputs(slots, seed, dead=()):
    """One token's (q, k, v, g, beta) as ``_gdn`` prepares them: q and k
    normalised, g < 0, beta in (0, 1); a ``dead`` slot's g and beta are 0."""
    rng = np.random.default_rng(seed)
    q, k = (qn._l2norm(jnp.asarray(rng.standard_normal((slots, HV, DK)),
                                   jnp.float32)) for _ in range(2))
    v = jnp.asarray(rng.standard_normal((slots, HV, DV)), jnp.float32)
    live = ~np.isin(np.arange(slots), dead)[:, None]
    g = jnp.asarray(-rng.random((slots, HV)) * live, jnp.float32)
    beta = jnp.asarray(rng.random((slots, HV)) * live, jnp.float32)
    return q * DK ** -0.5, k, v, g, beta


def kernel_at(at, head_block):
    @jax.jit    # the period traced, as the period scan hands it over
    def kernel(S_all, p, *step):
        return gdn.gdn_state_step(S_all, p, at[1], *step,
                                  head_block=head_block, interpret=True)

    return lambda S_all, *step: kernel(S_all, jnp.int32(at[0]), *step)


def elsewhere(S_all, at):
    """Every row but layer ``at``'s."""
    return np.asarray(S_all.at[at].set(0.0))


@pytest.mark.parametrize("head_block", [2, 3, gdn.HEAD_BLOCK],
                         ids=["divides", "ragged", "whole"])
@pytest.mark.parametrize("slots", [1, 5, 32])
@pytest.mark.parametrize("at", [(0, 0), (1, 2)])
def test_the_kernel_is_the_step(at, slots, head_block):
    """Layer ``at`` = (p, g) of a [2, 3, slots, ...] carry after one token:
    the oracle's state and output; a dead row (g = 0, beta = 0) keeps its
    state bit for bit; every other layer's rows come back bit for bit (the
    array is written in place and nothing else of it is touched). A head
    block that does not divide the 4 heads leaves a ragged last block."""
    dead = [s for s in range(slots) if s % 3 == 1]
    S_all = state(slots)
    step = step_inputs(slots, seed=slots, dead=dead)
    got, o = kernel_at(at, head_block)(S_all, *step)
    want, want_o = qn.gdn_step(S_all[at], *step)
    assert got.shape == S_all.shape and got.dtype == jnp.float32
    assert o.shape == (slots, HV, DV) and o.dtype == jnp.float32
    assert np.abs(np.asarray(want_o)).max() > 0.1
    assert np.abs(np.asarray(got[at] - S_all[at])).max() > 0.1
    assert np.abs(np.asarray(got[at] - want)).max() < TOL
    assert np.abs(np.asarray(o - want_o)).max() < TOL
    np.testing.assert_array_equal(np.asarray(got[at])[dead],
                                  np.asarray(S_all[at])[dead])
    np.testing.assert_array_equal(elsewhere(got, at), elsewhere(S_all, at))


def run_steps(at, slots, n, head_block=gdn.HEAD_BLOCK):
    """(state by the kernel, by the oracle, the outputs' largest difference)
    after ``n`` tokens on layer ``at``; slot 1 sits out the odd steps."""
    S_k = S_o = state(slots, seed=3)
    kernel, worst = kernel_at(at, head_block), 0.0
    for i in range(n):
        step = step_inputs(slots, seed=10 + i, dead=[1] if i % 2 else [])
        S_k, o_k = kernel(S_k, *step)
        layer, o_o = qn.gdn_step(S_o[at], *step)
        S_o = S_o.at[at].set(layer)
        worst = max(worst, float(jnp.abs(o_k - o_o).max()))
    return S_k, S_o, worst


@pytest.mark.parametrize("head_block", [3, gdn.HEAD_BLOCK],
                         ids=["ragged", "whole"])
def test_sixteen_steps_in_a_row_stay_with_the_oracle(head_block):
    """The state is the recurrence's memory: an error a step would grow.
    After 16 tokens the kernel's state is the oracle's within the tolerance
    of one, and the rest of the array is what it was."""
    at = (1, 1)
    S_k, S_o, worst = run_steps(at, 5, 16, head_block)
    assert np.abs(np.asarray(S_k[at] - S_o[at])).max() < TOL
    assert worst < TOL
    np.testing.assert_array_equal(elsewhere(S_k, at), elsewhere(S_o, at))


def test_products_formed_in_bfloat16_fail_the_tolerance(monkeypatch):
    """The guard of the float32 products: ``S^T k`` and ``S^T q`` with their
    operands rounded to bfloat16 (what one MXU pass of a float32 dot is) is
    another result, and the same 16 steps say so."""
    def rounded(S, kc, qc, v, decay, beta, kq):
        def r(x):
            return x.astype(jnp.bfloat16).astype(jnp.float32)

        Sk = jnp.sum(r(S) * r(kc), axis=0, keepdims=True)
        Sq = jnp.sum(r(S) * r(qc), axis=0, keepdims=True)
        d = beta * (v - decay * Sk)
        return decay * S + kc * d, decay * Sq + kq * d

    monkeypatch.setattr(gdn, "head_step", rounded)
    at = (0, 2)
    S_k, S_o, worst = run_steps(at, 5, 16)
    assert np.abs(np.asarray(S_k[at] - S_o[at])).max() > 10 * TOL
    assert worst > 10 * TOL


@pytest.mark.parametrize("left_out", ["decay", "outer"])
def test_a_term_left_out_of_the_head_fails_the_tolerance(monkeypatch,
                                                         left_out):
    def head(S, kc, qc, v, decay, beta, kq):
        if left_out == "decay":
            decay = jnp.ones_like(decay)
        S_new, o = real(S, kc, qc, v, decay, beta, kq)
        return (decay * S if left_out == "outer" else S_new), o

    real = gdn.head_step
    monkeypatch.setattr(gdn, "head_step", head)
    at = (1, 0)
    S_k, S_o, _ = run_steps(at, 5, 2)
    assert np.abs(np.asarray(S_k[at] - S_o[at])).max() > 1e3 * TOL


def test_what_the_compiled_kernel_cannot_take_is_refused_by_name():
    S_all, step = state(2), step_inputs(2, seed=0)
    with pytest.raises(ValueError, match="attn_impl: xla"):
        gdn.gdn_state_step(S_all, 0, 0, *step)      # 16-wide heads, compiled
    with pytest.raises(ValueError, match="float32"):
        gdn.gdn_state_step(S_all.astype(jnp.bfloat16), 0, 0, *step,
                           interpret=True)

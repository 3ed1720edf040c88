"""What the family files (tests/test_ouro.py, test_qwen3_next.py,
test_afmoe.py, test_deepseek.py, test_dots3.py, test_falcon_h1.py) share: a
small configuration from published keys, the program's seeded weights redrawn
so that every branch weighs on the logits, the runner's own programs tapped
for the logits they sample from, the benchmark's plain reference over the
same tokens, and the lowered text of a runner's programs
(tests/test_neighbour_texts.py holds the hashes)."""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "benchmark")]

from harness import refcheck, spec  # noqa: E402
from localai_tpu.engine.runner import ModelRunner  # noqa: E402
from localai_tpu.models import llama as mdl  # noqa: E402
from localai_tpu.models.llama import LlamaConfig  # noqa: E402


def reference_family(name: str, test_file: str):
    """The benchmark's plain float32 family ``benchmark/reference/<name>.py``,
    loaded as the harness loads it."""
    return spec.load_family(spec.family_file(
        {"reference": {"family": name}}, test_file))


def config(hf: dict, dtype="float32", **changed) -> LlamaConfig:
    return dataclasses.replace(LlamaConfig.from_hf({**hf, **changed}),
                               dtype=dtype)


def redrawn(params: dict, redraw) -> dict:
    """``redraw(name, leaf)`` over the top level's leaves and the layers'."""
    out = {k: redraw(k, v) for k, v in params.items() if k != "layers"}
    out["layers"] = {k: redraw(k, v) for k, v in params["layers"].items()}
    return out


def tripled(a):
    return (3.0 * a.astype(jnp.float32)).astype(a.dtype)


def gain(rng, a, centre=1.0):
    return jnp.asarray(centre + 0.3 * rng.standard_normal(a.shape), a.dtype)


def tap(runner: ModelRunner) -> list:
    """The runner's own prefill and decode programs, each also returning the
    logits it samples from (``logits_from_hidden``'s result, taken inside
    the same trace); the list they are appended to."""
    seen: list = []

    def wrap(fn, **jit_kw):
        def with_logits(*a, **k):
            inside: list = []
            real = mdl.logits_from_hidden

            def spy(cfg, params, x):
                inside.append(real(cfg, params, x))
                return inside[-1]

            mdl.logits_from_hidden = spy
            try:
                out = fn(*a, **k)
            finally:
                mdl.logits_from_hidden = real
            return out, (inside[0] if inside else None)

        jitted = jax.jit(with_logits, **jit_kw)

        def call(*a, **k):
            out, logits = jitted(*a, **k)
            if logits is not None:
                seen.append(np.asarray(logits, np.float32))
            return out

        return call

    # one family of programs over both layouts; the fresh whole-prompt
    # prefill is the contiguous rows' own
    runner._prefill_paged = wrap(runner._prefill_paged_fn,
                                 static_argnames=("bucket", "sample"))
    runner._decode_paged = wrap(runner._decode_paged_fn)
    if not runner.paged:
        runner._prefill = wrap(runner._prefill_fn, static_argnames=("bucket",))
    return seen


def served_logits(r: ModelRunner, seen: list, slot: int, prompt, steps: int,
                  **admit):
    """Prefill then ``steps`` decode steps through pool and state: ([1 +
    steps, V] logits, the greedy tokens)."""
    mark = len(seen)
    tokens = [r.admit(slot, prompt, temperature=0.0, **admit)]
    tokens += [int(r.step()[slot]) for _ in range(steps)]
    logits = np.stack([seen[mark][0]] + [row[slot] for row in seen[mark + 1:]])
    return logits, tokens


def reference_logits(family, params, hf, prompt, tokens, monkeypatch):
    """The family's full forward over prompt + served tokens, no cache, no
    state carried: [n, V]."""
    monkeypatch.setattr(refcheck, "LETTERS", slice(0, hf["vocab_size"]))
    seq = np.array([prompt + tokens[:-1]], np.int32)
    return refcheck.reference_logits(params, family, hf, seq, len(tokens))[0]


def agree(served, ref, tol, spread=0.2):
    assert np.abs(ref).max() > spread       # logits, not zeros
    assert np.abs(served - ref).max() < tol, np.abs(served - ref).max()


def lowered_texts(r: ModelRunner, programs, debug_info: bool = False) -> dict:
    """The lowered text (StableHLO) of a paged runner's programs by name:
    ``decode``, ``decode_n`` (4 steps), ``prefill_1`` / ``prefill_0`` (a chunk
    of 5 tokens in a bucket of 32, sampling or not), ``arm``, ``ride`` (that
    chunk and the step as one program, where the runner's can)."""
    chunk = (jnp.zeros((1, 32), jnp.int32), jnp.int32(5), jnp.int32(0),
             r.block_tables[0], jnp.int32(0),
             jnp.zeros(r.cfg.vocab_size, jnp.int32))
    prefill = jax.jit(r._prefill_paged_fn,
                      static_argnames=("bucket", "sample"))

    def arm():
        ints, floats = r.state.params.pack()
        return jax.jit(r._arm_slot_fn).lower(
            r.state, r.block_tables,
            np.concatenate([np.array([0, 0, 0], np.int32), ints]), floats,
            jnp.zeros(r.cfg.vocab_size, jnp.float32), r.block_tables[0])

    lower = {
        "decode": lambda: jax.jit(r._decode_paged_fn).lower(
            r.params, r.kv, r.state, r.block_tables),
        "decode_n": lambda: jax.jit(
            r._decode_paged_n_fn, static_argnames=("n",)).lower(
                r.params, r.kv, r.state, r.block_tables, n=4),
        "prefill_1": lambda: prefill.lower(
            r.params, r.kv, r.state, *chunk, bucket=32, sample=True),
        "prefill_0": lambda: prefill.lower(
            r.params, r.kv, r.state, *chunk, bucket=32, sample=False),
        "arm": arm,
        "ride": lambda: jax.jit(
            r._decode_prefill_paged_fn, static_argnames=("bucket",)).lower(
                r.params, r.kv, r.state, r.block_tables, *chunk, bucket=32)}
    return {name: lower[name]().as_text(debug_info=debug_info)
            for name in programs}


def host_state(r: ModelRunner) -> list:
    """Every leaf of a runner's pool, of its decode state and of its
    family's own (``rec``: the per-slot rows of EVERY slot, the routed
    count), on the host: what a ride and the two programs it stands for
    are compared by."""
    st = r.state
    return [np.asarray(a) for a in jax.tree.leaves(
        (r.kv, st.tokens, st.positions, st.active, st.counts, st.bias,
         st.params, jax.random.key_data(st.keys), st.rec))]


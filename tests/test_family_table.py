"""A model family is one row of ``models.llama.FAMILIES`` and one file: the
row's module holds the contract ``family_module`` states, ``from_hf`` enters
it by the table, every forward takes one set of keywords, and what the family
does not serve is data that a bare ``ModelRunner`` (and ``synthetic_params``)
refuses in the family's one sentence. A case a row, over the small published
configurations the family files test with; no program runs (leaves of the
right shapes are all a runner asks)."""

import importlib
import inspect
import logging
import re

import families
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_afmoe import HF as AFMOE
from test_deepseek import HF as AXK1
from test_dots3 import HF as DOTS3
from test_falcon_h1 import HF as FALCON_H1
from test_lfm2 import HF as LFM2
from test_minicpm_sala import HF as MINICPM_SALA
from test_qwen3_next import HF as QWEN3_NEXT
from test_smallthinker import HF as SMALLTHINKER

from localai_tpu.engine.runner import ModelRunner
from localai_tpu.models import llama as mdl
from localai_tpu.models.llama import LlamaConfig
from localai_tpu.models.registry import synthetic_params

HF = {"qwen3_next": QWEN3_NEXT, "afmoe": AFMOE, "axk1": AXK1,
      "dots3_note": DOTS3, "falcon_h1": FALCON_H1, "lfm2_moe": LFM2,
      "minicpm_sala": MINICPM_SALA, "smallthinker": SMALLTHINKER}
CONTRACT = ("CONFIG", "param_shapes", "init_leaf", "checkpoint_leaves",
            "init_rec", "forward", "UNSERVED", "WEIGHTS", "WHY")
KEYWORDS = ("rec", "valid", "slot", "fresh", "kernels")
SLOTS = 2
# how a bare runner is asked for each of the engine's features (a mesh by its
# axis: two devices, the pipe the smallest count that divides the layers)
ASKED = {
    "self-extend": {"ga_n": 2, "ga_w": 8},
    "pipeline parallelism": {"mesh": "pipe"},
    "the ring prefill": {"mesh": "seq"},
    "a device mesh": {"mesh": "model"},
    "the contiguous K/V layout": {"paged": False},
    "a int8 K/V pool": {"kv_dtype": "int8"},
    "a int4 K/V pool": {"kv_dtype": "int4"},
}


def runner_for(cfg, **kw):
    from localai_tpu.parallel.mesh import MeshPlan, build_mesh

    if "mesh" in kw:
        axis = kw["mesh"]
        n = next(n for n in (2, 3, 5) if cfg.num_layers % n == 0) \
            if axis == "pipe" else 2
        kw["mesh"] = build_mesh(MeshPlan(**{axis: n}),
                                devices=jax.devices()[:n])
    zeros = jax.tree.map(lambda shape: jnp.zeros(shape, jnp.float32),
                         mdl.param_shapes(cfg),
                         is_leaf=lambda x: isinstance(x, tuple))
    return ModelRunner(cfg, zeros, **{
        "num_slots": SLOTS, "max_ctx": 64, "paged": True,
        # (a family that selects blocks of the pool says how large one is)
        "kv_block_tokens": (cfg.select_blocks or (32,))[0],
        "attn_impl": "xla", "kv_dtype": "float32",
        **kw})


def test_every_row_is_tested_here():
    assert sorted(HF) == sorted(mdl.FAMILIES)


@pytest.mark.parametrize("model_type", sorted(mdl.FAMILIES))
def test_a_row_of_the_table_is_a_family(model_type, caplog):
    name = mdl.FAMILIES[model_type]
    module = importlib.import_module(f"localai_tpu.models.{name}")
    for held in CONTRACT:
        assert hasattr(module, held), held
    cfg = families.config(HF[model_type])
    assert type(cfg) is type(LlamaConfig.from_hf(HF[model_type])) \
        is module.CONFIG
    assert cfg.family == name and mdl.family_module(cfg) is module
    # one forward contract: the keywords by name alone, four values back
    takes = inspect.signature(module.forward).parameters
    assert [takes[k].kind for k in KEYWORDS] == [
        inspect.Parameter.KEYWORD_ONLY] * len(KEYWORDS)
    assert inspect.signature(module.forward).return_annotation.count(
        ",") == 3

    def says(what):
        return "^" + re.escape(f"{what} is not served for {module.WHY}") + "$"

    assert module.WHY.startswith(f"model_type {model_type}: ")
    assert module.UNSERVED <= set(ASKED) | {
        "speculative decoding", "the prompt cache's import"}
    # what the family does not serve, a bare runner refuses in its sentence
    for feature, kw in ASKED.items():
        if feature in module.UNSERVED:
            with pytest.raises(ValueError, match=says(feature)):
                runner_for(cfg, **kw)
    r = runner_for(cfg)     # ... and what it serves is built
    assert (r.recurrent, r.routed, r.latent) == (
        cfg.recurrent, cfg.routed, cfg.latent)
    # a family that says ``RIDES`` (PR 61: ``lfm2_moe``, the first; PR 64:
    # ``qwen3_next``) takes the contract's one keyword more, off unless
    # passed; no other holds it
    rides = getattr(module, "RIDES", False)
    assert r.own_forward and r.rides == rides == (
        model_type in ("lfm2_moe", "qwen3_next"))
    assert ("ride" in takes) == rides
    if rides:
        assert (takes["ride"].kind, takes["ride"].default) == (
            inspect.Parameter.KEYWORD_ONLY, 0)
    rec = module.init_rec(cfg, SLOTS)
    assert jax.tree.structure(r.state.rec) == jax.tree.structure(rec)
    assert ("routed" in rec) == cfg.routed
    if "speculative decoding" in module.UNSERVED:
        with pytest.raises(ValueError, match=says("speculative decoding")):
            r.verify_async(np.zeros((SLOTS, 2), np.int32))
    if "the prompt cache's import" in module.UNSERVED:
        with caplog.at_level(logging.WARNING):
            assert r.load_prefix(0, {}, 4) is False
        assert re.search(says("the prompt cache's import"),
                         caplog.messages[-1])
    for mode in ("int8", "int4"):
        if mode not in module.WEIGHTS:
            with pytest.raises(ValueError, match=says(
                    f"engine.quantization {mode!r}")):
                synthetic_params(cfg, mode)
    assert not module.WEIGHTS or hasattr(module, "leaf_std")


def test_a_type_in_no_row_with_a_mixers_keys_is_still_refused():
    hf = {**FALCON_H1, "model_type": "zamba3"}
    assert hf["model_type"] not in mdl.FAMILIES
    with pytest.raises(ValueError, match="model_type 'zamba3' is not "
                                         "served: its config carries a "
                                         "state-space mixer's keys"):
        LlamaConfig.from_hf(hf)
    # ... and one without them is a dense llama stack of the file's widths
    plain = {k: v for k, v in hf.items() if not k.startswith("mamba_")}
    assert type(LlamaConfig.from_hf(plain)) is LlamaConfig
    assert mdl.family_module(LlamaConfig.from_hf(plain)) is None

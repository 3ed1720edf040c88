"""With another ``model_type`` nothing new is traced: sha256 of the lowered
text (StableHLO, no debug info) of the programs of each configuration that
shares code with the newer families, at small sizes, under this installation
(jax 0.9.0). A row's hashes were taken from the PARENT commit of the PR that
added it, by ``families.lowered_texts`` on the runner the row builds: a change
that leaves a row standing left that configuration's programs as they were,
to the letter, which is what lets a PR say "no other cell's program changed"
before a chip run. A change that MEANS to alter a row retakes it from its own
parent and says why, here. The next family adds a row, not a function.

What each row has seen:

- the three older cells (PR 41, when ``qwen3_next`` came): PR 45 retook the
  nine of programs that sample (``arm`` stands as taken): ``sample`` holds a
  bfloat16 head's float32 copy to bfloat16 with ONE
  ``stablehlo.reduce_precision``; with that line taken out and the numbered
  values aside, each text is PR 45's parent's line for line (the two-stage
  candidates leave them alone: these vocabularies take the one ``top_k``).
  PR 49 retook the four-chip trunk's two (``decode``, ``decode_n``): each is
  the text PR 49's parent lowers under ``LOCALAI_MESH_OVERLAP=psum``, to the
  letter (one ``psum`` a row-parallel product; the chunked form is gone). PR
  50 retook the six ``decode`` / ``decode_n`` (the paged kernel's body
  changed: it does no work for a slot on the trash block); ``prefill_1`` and
  ``arm``, which hold no kernel, stand as taken.
- ``qwen3_next`` (PR 44: the routing and the expert dispatch moved out of
  models/qwen3_next.py into models/experts.py): PR 45 retook the six of
  programs that sample (``prefill_0`` stands as taken) for the same one
  ``stablehlo.reduce_precision``. PR 46 retook the kernel path's two DECODE
  programs, which now hold ops/gdn.py's kernel where they sliced the state
  and stepped it as XLA; its chunks and all four of the XLA path stand as
  taken: the step they run moved into ``recur`` and lowers to the letter it
  did. PR 50 retook the same two (the paged kernel of its full-attention
  layers); the chunks and the XLA path stand.
- ``afmoe`` (PR 48, parent fdb73cd: the scoring rule both sigmoid families
  call gained its group limit and the runner a third layout): PR 50 retook
  the kernel path's ``decode``; its chunks and the XLA path stand as taken.
- ``axk1`` (PR 51, parent 4d619ad: ``LatentKVCache`` gained two optional
  arrays, the latent writes a ``state`` argument, ``latent_span_attend`` its
  window and its marks, ``models.deepseek._attention`` its hooks): as taken.
- ``dots3_note`` (PR 56, this tree: the newest family with a cell, which no
  row held; in the place of a second ``afmoe`` XLA row that tests/test_dots3.py
  ran beside tests/test_deepseek.py's): as taken.
- ``falcon_h1`` (PR 57, parent 69627c5: ``lfm2_moe`` came through the door
  beside it, shares ``_forward_rec`` and the refusals of recurrent state with
  it and calls its ``rec_read``, ``rec_write`` and ``conv_rows``, the first
  two under names that lost an underscore; ``moe_block``'s shared expert and
  ``sigmoid_scores``' epsilon became arguments that every older family
  leaves as they were): as taken. Its chunk holds no kernel, so the two
  rows' ``prefill`` texts are one text.
- PR 59 (a prompt's small last chunk rides the decode step,
  ``_decode_prefill_paged_fn``): NO row retaken. The two cells that ride
  (the 7B and Ouro: ``models.llama.forward`` on one chip) gain a ``ride``
  text, taken from this PR's own tree as a new program is; their four older
  texts stand as taken (``_decode_tail`` and ``_first_token`` trace what they
  traced where no caller hands them logits). The configurations that step
  aside (the four-chip trunk by ``overlap_mode``; ``qwen3_next``, ``afmoe``,
  ``axk1``, ``dots3_note``, ``falcon_h1`` and, with no row here, ``lfm2_moe``
  by ``own_forward``) have no such program (``ModelRunner.rides`` is False,
  held below) and every text of theirs is the parent's.
- ``lfm2_moe`` (PR 60, parent 8fddbb0, taken BEFORE any file under
  localai_tpu/models/ was touched: the six family forwards came to call one
  frame in models.llama (``rope_rows``, ``embed``, ``xla_attend``,
  ``attend_through``), one ``rec_read`` / ``rec_write`` / ``conv_rows`` there
  and one ``swiglu`` / ``shared_expert`` in models.experts, to take one set
  of keywords and to return ``(hidden, stack, rec, routed)``; the families
  came through one table, their refusals from data): as taken, experts of
  128 under the family's own heads of 64 (two to a pool row). NO row
  retaken: every text above is what it was.
- PR 61 (a FAMILY's forward takes the ride: ``ModelRunner.rides`` asks the
  family's module for ``RIDES``, ``models.lfm2.forward`` gains the keyword
  ``ride`` and ``_conv_mixer`` its two halves): NO row retaken. ``lfm2_moe``
  gains ONE program, its ``ride`` text (under either ``attn_impl``: the
  kernels in it or their XLA forms), taken from this PR's own tree as a new
  program is; its ``decode`` and both chunks stand as taken (the keyword
  defaults to no ride and the convolution's one half traces what the whole
  did), and so does every other row: the five families that do not say
  ``RIDES`` are never passed the keyword and hold no such program
  (``rides`` is False, held below), the 7B's and Ouro's ``ride`` texts are
  PR 59's.
- ``minicpm_sala`` (PR 62, this tree: the family came through the door with
  a selecting attend in ``PagedLayout.decode`` / ``.chunk`` behind
  ``cfg.select_blocks``, a snapshot of recurrent state a registered prompt
  in engine.paged and the runner's admission, two columns of the flight
  ring and ``w_ogate`` / the lone layers' names in ``models.quant``'s
  plan): NO row retaken: the layouts' other branches trace what they
  traced, the snapshot's copies are programs of their own and ``DecodeState``
  holds what it held. The new family's two rows are taken from this PR's own
  tree, heads of 128 (what the compiled kernels take): its chunk holds no
  kernel, so the two rows' ``prefill`` texts are one text.
- PR 64 (``models.qwen3_next.forward`` takes the ride: the module says
  ``RIDES``, ``_gdn`` runs the convolution and the recurrence in two halves,
  and ``recur`` walks a chunk's REAL rows, a loop whose trip count is read
  from ``valid``, where it scanned the bucket): ``qwen3_next``'s FOUR chunk
  texts (``prefill_1`` / ``prefill_0`` of both rows) are retaken ON PURPOSE
  from this PR's own tree. With the values' numbers aside each differs from
  its parent's (b7d4e76) in the token loop alone: ``lax.scan``'s ``while``
  over 32 rows with the step a called function became ``fori_loop``'s over
  the most real rows of ``valid`` with the step inline, its reads and its
  one write at a clamped index, and the count in front of it (``valid``
  summed where a layer's state is read); nothing else moved. The rows gain ONE program
  each, their ``ride`` text, taken from this PR's own tree as a new program
  is; their ``decode`` / ``decode_n`` stand as taken (the keyword defaults
  to no ride and the one half traces what the whole did, in the order it
  did). The four families that do not say ``RIDES`` and ``minicpm_sala``
  never enter models/qwen3_next.py, and the 7B's and Ouro's ``ride`` texts
  are PR 59's. FOUR texts of three OTHER kernel rows are retaken for the
  NUMBER behind a private function's name and for nothing else (``afmoe``
  ``decode``; ``lfm2_moe`` ``decode`` and ``ride``; ``minicpm_sala``
  ``decode``: ``@threefry2x32_<n>`` and its like): the chip forced a mend of
  set-up in general terms (the ride program's first dispatch put the hybrid
  cell's ``setup_s`` at its bound, and a quarter of it was the paged
  kernel's body traced again): ``ops.paged_decode_attention`` keeps ONE
  trace a shape (``_paged_decode_call``, a ``jax.jit`` inlined where it is
  lowered), and a program that calls the kernel more than once now emits
  fewer private functions in front of the sampler's, whose numbers move
  down. With those numbers aside every one of the 34 kernel-path texts
  (these four included) is its parent's (b7d4e76) line for line
  (``re.sub(r"@([A-Za-z0-9_]*?)_[0-9]+\b", ...)`` over both, taken by this
  PR on both trees); the eight rows whose programs call it once stand as
  taken, and so does every XLA row. ``test_one_trace_of_the_paged_kernel_a_
  runner`` below holds the mend.
"""

import functools
import hashlib
import json

import families
import jax
import pytest
from families import ROOT
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
from localai_tpu.models.registry import synthetic_params

# a head of 128 lanes and experts of 128 (what the compiled kernels take)
WIDE = {"head_dim": 128, "moe_intermediate_size": 128}
# ... under the names ``smallthinker`` publishes
WIDE_SMT = {"head_dim": 128, "moe_ffn_hidden_size": 128}
# ... and a mixer head of 128 with a state of 128
WIDE_SSM = {"head_dim": 128, "mamba_d_head": 128, "mamba_d_ssm": 512,
            "mamba_d_state": 128}
# ... and a Lightning head of 128 beside an attention head of 128
WIDE_LIGHTNING = {"head_dim": 128, "lightning_head_dim": 128,
                  "hidden_size": 128}


def cell_runner(name: str) -> ModelRunner:
    """``benchmark/configs/<name>.json`` cut to two layers of small widths,
    int8, the paged kernel in the interpreter; ``tp4`` over four devices."""
    from localai_tpu.parallel.mesh import MeshPlan, build_mesh
    from localai_tpu.parallel.sharding import ParamPlacement

    doc = json.loads((ROOT / f"benchmark/configs/{name}.json").read_text())
    cut = {"hidden_size": 256, "intermediate_size": 512,
           "num_hidden_layers": 2, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 128, "vocab_size": 512,
           "max_position_embeddings": 512}
    mesh = None
    if name.endswith("tp4"):
        cut.update(num_attention_heads=8, num_key_value_heads=4)
        mesh = build_mesh(MeshPlan(model=4), devices=jax.devices()[:4])
    if name.startswith("ouro"):
        cut.update(num_key_value_heads=4)
    cfg = families.config({**doc, **cut}, "bfloat16")
    params = synthetic_params(cfg, "int8", seed=0,
                              placement=ParamPlacement(cfg, mesh))
    r = ModelRunner(cfg, params, num_slots=4, max_ctx=128, paged=True,
                    kv_block_tokens=16, attn_impl="pallas_interpret",
                    mesh=mesh)
    assert r.recurrent is False and r.state.rec is None
    named = families.lowered_texts(r, ("decode",), debug_info=True)["decode"]
    for scope in ("gdn/", "moe/", "attn_gate"):
        assert scope not in named
    return r


def family_runner(hf: dict, block: int, attn_impl: str) -> ModelRunner:
    cfg = families.config(hf, "bfloat16")
    return ModelRunner(cfg, mdl.init_params(jax.random.key(0), cfg),
                       num_slots=4, max_ctx=128, paged=True,
                       kv_block_tokens=block, attn_impl=attn_impl)


def cell(name, hashes):
    return pytest.param(functools.partial(cell_runner, name), hashes, id=name)


def family(hf, block, attn_impl, hashes):
    return pytest.param(
        functools.partial(family_runner, hf, block, attn_impl), hashes,
        id=f"{hf['model_type']}-{attn_impl}")


TAKEN = [
    cell("mistral-7b-v0.3-int8", {
        "decode":
            "f2c5a0fbb56f1413911c177e72bed6bd30887e59922fd68ca56da7d902c4fc86",
        "decode_n":
            "aeb400bc8401e521c6fc5dd310790dd772aa96efba2835f42adbf4fff9eaba66",
        "prefill_1":
            "5da338912ddb6924ef2ad7c994a1a6db2a1d233d941281a99b3c37a6affe5b12",
        "arm":
            "ae443047b695987e2d6d6a07f968496b8a8cf5eb35dc10355e2fdafe7d166499",
        "ride":
            "8aea657dd172f22494f4fbe9cf4edaf8334c01488f34430091cbb3294315397f"}),
    cell("mistral-small-24b-int8-tp4", {
        "decode":
            "17d1318cb3353870a3a30acc6c055c94e692ac446f0dabaf22599ca0d218224b",
        "decode_n":
            "0da1864b409456ed8239270fcd37a4c5c16fe1430aa48cc9bc85a50c62867bb2",
        "prefill_1":
            "72bfe1e112c7a68df594347a5684c47074932e0da55929f006b4730ebfa37af5",
        "arm":
            "3efff5c076c6e3e16b28936ccc7fd25d87950dbbcfc6726e4694f537db26b136"}),
    cell("ouro-2.6b-int8", {
        "decode":
            "9f2f46c6133afb09f824ec5c6f79b8cadbb3617761ae1f7a0aa20fc39877c25a",
        "decode_n":
            "433dc057083dd983c5cc6f4b5c503965f67da4b1ff872a18a4450f40c6be0dd0",
        "prefill_1":
            "a6e0180316c86e3f77a14a239cb921c62f4bac783543a9b790d6eed56d97be94",
        "arm":
            "ae443047b695987e2d6d6a07f968496b8a8cf5eb35dc10355e2fdafe7d166499",
        "ride":
            "fa04f314f83073a65bab61e8dbe1fae3c9adc52f250f84c9ba6a6badb9038bd6"}),
    family({**QWEN3_NEXT, **WIDE}, 16, "pallas_interpret", {
        "decode":
            "e4e6c6be85c5f816aba14559c96f7c5a34c73b67a84fdec4b4c1ce496ba7baea",
        "decode_n":
            "f0d7dea69dcd89fcef6c498b8b8448527f27ec335ee2c3c8bc9a4a4c95caf330",
        "prefill_1":
            "3ac2b60f6091b4f8ad8437e376bc1f268ecce61b3ea49b69bf812454671e59a7",
        "prefill_0":
            "5f1693a46745f57ddef2c74f456cf14f8c4abce1bbe388f0866df2b4ad30cf80",
        "ride":
            "fe343be08dd224b83aebc9c7c77d86bfa8d400c9fa5f3c879a026be980e72636"}),
    family({**QWEN3_NEXT, **WIDE}, 16, "xla", {
        "decode":
            "e03b0cae3c794183ef5b23d3bcf79397bbc84f6ff63a830980e9b553ba43d173",
        "decode_n":
            "3aaf8ca9f6fb5474663d4cf30601161f90b86f2391b608129d12e13ebc461e08",
        "prefill_1":
            "690e2f7a718ea243ee7030641fa01eaa823ba0950c1d7b7a7146fa5f3fdfe789",
        "prefill_0":
            "91dea89764dd9bad78aff6deac31a8e019ddf2d7aa27b39724679dd5098d47e4",
        "ride":
            "029b436bac6517488247cc746e7f74b49869295bbc2941f66e0e379d44c964c0"}),
    family({**AFMOE, **WIDE}, 16, "pallas_interpret", {
        "decode":
            "e0c46668cd703de1cad7e5a120e7d5610ea857c0b34aab47947647596e281af6",
        "prefill_1":
            "189c6e43b3a847679d68d877a305967c95504f428a42e3730bbeb7aa183b4259",
        "prefill_0":
            "1cce56d6e5a0511bafb613df2491ad4b765f08a4ff89fb10d6d5359f2f2a5ae0"}),
    family({**AFMOE, **WIDE}, 16, "xla", {
        "decode":
            "1bb6388ecbf095a4630444bc8df62a50933f131d6bae279be4abe39538cabb4f",
        "prefill_1":
            "04536ef99315d04002ebb520178a17cf5e4ce1a4e134c6fb448dbf3f6c485205",
        "prefill_0":
            "7b26cacc9110cc1afa89e3dc26bbdc09def606e4a4e38a87e1f838d74122ee0c"}),
    family(AXK1, 32, "pallas_interpret", {
        "decode":
            "f5d4281f4d63c43b523aa2cfb605e7be023b0a7619373a045d54f32689d1b048",
        "prefill_1":
            "f193eb88ea2d0088837ce2736de3a3e4264560810215d62789ae1993d81ee02b",
        "prefill_0":
            "f8435bf0075356551b7ce0db16502d80079dd23d56a5a17f927b69d4ee42a447"}),
    family(AXK1, 32, "xla", {
        "decode":
            "7237f1b5326f8e9029871b878b780dacbfd1c14a75640f188bdcc41a190e2cab",
        "prefill_1":
            "2428dc28c173ea72bb07592160c5a91824c59991f11929c3085adfbda9797469",
        "prefill_0":
            "bb13789487d6c403c0e58d26662203bf47b6bbe8d303da9e8442e6d75cb1b318"}),
    family(DOTS3, 32, "xla", {
        "decode":
            "8c4db33a351256b2c48607c64735ca82dad6ed3ca9443d391c957f38c114cfbe",
        "prefill_1":
            "c84221f04fe52131fbd26b46c8c604e945d57ecb884f7c363ccf492875cccb43",
        "prefill_0":
            "9d92593db19120a980fc7fc7d7a6e486c06fcfbe9e90d275022a77c141cf1e64"}),
    family({**FALCON_H1, **WIDE_SSM}, 16, "pallas_interpret", {
        "decode":
            "58da9ea3a4476534b5a365616fa537faceb29f5f808281c4f47b82cbab09449c",
        "prefill_1":
            "f8bb71eacc0d156f53b9d45ff6b87a7c3dd05760495e886fe3258da0aec36505",
        "prefill_0":
            "17b891ddebad6bda6c4f4405b8ff5229d91abe82860db6871ca3b728c0a30cf9"}),
    family({**FALCON_H1, **WIDE_SSM}, 16, "xla", {
        "decode":
            "3c8fb5d2f187c89425b000b0615a95018b3531b978bbe85ec3141ab6fcdfc215",
        "prefill_1":
            "f8bb71eacc0d156f53b9d45ff6b87a7c3dd05760495e886fe3258da0aec36505",
        "prefill_0":
            "17b891ddebad6bda6c4f4405b8ff5229d91abe82860db6871ca3b728c0a30cf9"}),
    family({**LFM2, "moe_intermediate_size": 128}, 16, "pallas_interpret", {
        "decode":
            "855071bda9153d90d0a1a03017774c3452eb05744d9cb34a3df60e595d55f71a",
        "prefill_1":
            "3d6d32682ea7a7f56f1c8ea8fc82b8aac5e68a0ce8006b468feca80277c1aaa0",
        "prefill_0":
            "2da814dd8c3f07ec6cb357ed0597d433cbb48fe0ae69759b82057b0e4f5d3f53",
        "ride":
            "18a06790a41432ec17ea83aa97d3b6e4bc2bf6419366c2ea45310456c6f82c3e"}),
    family({**LFM2, "moe_intermediate_size": 128}, 16, "xla", {
        "decode":
            "77ec6134d68aa05f2982e8792d8378b43be97f0f8fcc4a1952fe8b2e63386223",
        "prefill_1":
            "80d407a77ab80489093713371f7ef2007233d6f58d9b831573651d88f5ca5e6f",
        "prefill_0":
            "0572c404742d7340a0aad104edae774b8f6caeb8b004aa19967d3bfb35d66acc",
        "ride":
            "5c4f4aebea0a1eb2b7ff5f9547ba8a263eae5aebb6c127553a545328f868301b"}),
    family({**MINICPM_SALA, **WIDE_LIGHTNING}, 16, "pallas_interpret", {
        "decode":
            "482400c46cd5a15c5e324a4bf60f9aba3875956f31b1094a1a0750d14df80759",
        "prefill_1":
            "4310671fe5e13920280e9991b4edb573c2e75ae46775fd0e2b2a77571ca471c3",
        "prefill_0":
            "cfa6079c1431318847d1fa804ef2fa707cbc57b809581547d04c76870f0f57c4"}),
    family({**MINICPM_SALA, **WIDE_LIGHTNING}, 16, "xla", {
        "decode":
            "f1eb54131f9a574b813c392b770a0efa20e6e1a515e9323346e0f1c73ded562c",
        "prefill_1":
            "4310671fe5e13920280e9991b4edb573c2e75ae46775fd0e2b2a77571ca471c3",
        "prefill_0":
            "cfa6079c1431318847d1fa804ef2fa707cbc57b809581547d04c76870f0f57c4"}),
    family({**SMALLTHINKER, **WIDE_SMT}, 16, "pallas_interpret", {
        "decode":
            "c9f00a69a262b137a9c2466e8f702a496aabfbbcab517ea8cd6f77de6198c809",
        "prefill_1":
            "bc538ca9cc7f1c6a62e3c9636a1f4b3668d983b79f1b5730699c13dd650c3a30",
        "prefill_0":
            "701a8bb556a2619f3fd9e69b52a706078bd0cf3a5b6585e1d12ade8a574739b3"}),
    family({**SMALLTHINKER, **WIDE_SMT}, 16, "xla", {
        "decode":
            "542c7dd3fe9688b6a07cb5038dca1ef70c75384fc7b33cb47db54e554d21f43e",
        "prefill_1":
            "0f2dd2302fb062c8c306da6e58ccc1cd48a7c8cd36854881258782dc1e7620c6",
        "prefill_0":
            "53c6491e8a96ac18fe0e0667d82fdb1fc97ff58773084e293d10a89b46ad3418"}),
]


@pytest.mark.parametrize("build, taken", TAKEN)
def test_the_programs_lower_to_the_text_taken(build, taken):
    r = build()
    # a row with a ``ride`` text is a configuration whose last chunk can ride
    # the step; every other one steps aside and holds no such program
    assert r.rides == ("ride" in taken)
    now = {name: hashlib.sha256(text.encode()).hexdigest()
           for name, text in families.lowered_texts(r, taken).items()}
    assert now == taken


def test_one_trace_of_the_paged_kernel_a_runner(monkeypatch,
                                                fresh_kernel_traces):
    """PR 64 (set-up, in general terms): the paged decode kernel's BODY is
    traced once for all the programs of a runner that hold a decode step:
    the multi-step program and a ride reuse the decode step's trace
    (``ops.attention._paged_decode_call``: a ``jax.jit`` keeps a trace by
    shape, and ``inline=True`` leaves a program's operations as they were,
    which the rows above hold). The kept traces are dropped around this case
    (``fresh_kernel_traces``): it counts its own runner's and no other's."""
    from localai_tpu.ops import attention as att

    traced = []
    real = att._paged_decode_kernel
    monkeypatch.setattr(att, "_paged_decode_kernel", lambda *a, **kw: (
        traced.append(1), real(*a, **kw))[1])
    r = cell_runner("mistral-7b-v0.3-int8")     # (lowers ``decode`` itself)
    families.lowered_texts(r, ("decode",))
    assert len(traced) == 1
    families.lowered_texts(r, ("decode_n", "ride"))
    assert len(traced) == 1


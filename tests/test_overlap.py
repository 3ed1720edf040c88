"""The manual-TP meshed decode trunk (parallel.overlap): one all-reduce a
row-parallel product, under ``mesh.reduce``.

The load-bearing pins: the reduction IS ``lax.psum`` on 2 and on 4 virtual
devices for the trunk's ``[S, 1, D]`` messages, and on the 2-virtual-device
CPU mesh (one addition an element: no summation-tree freedom) the trunk's
greedy output matches the GSPMD path token for token, whatever the pool's
dtype.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from localai_tpu.engine.runner import ModelRunner
from localai_tpu.models.registry import resolve_model
from localai_tpu.parallel import overlap as ovl
from localai_tpu.parallel import sharding as shd
from localai_tpu.parallel.mesh import MeshPlan, build_mesh

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs >=2 virtual devices")


def _tp_mesh(n=2):
    return build_mesh(MeshPlan(model=n), devices=jax.devices()[:n])


@pytest.mark.parametrize("shape", [(4, 1, 64), (32, 1, 10), (3, 1, 7)])
@pytest.mark.parametrize("tp", [2, 4])
def test_make_reduce_is_psum(tp, shape):
    """The trunk's reduction against ``lax.psum`` under the same shard_map,
    byte for byte, and against the sum written out: D divisible by the axis
    and not."""
    if len(jax.devices()) < tp:
        pytest.skip(f"needs {tp} virtual devices")
    mesh = _tp_mesh(tp)
    x = jnp.asarray(np.random.default_rng(0).normal(size=shape), jnp.float32)

    def run(reduce_fn):
        return shard_map(
            lambda v: reduce_fn(v * (1.0 + jax.lax.axis_index("model"))),
            mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False)(x)

    got = run(ovl.make_reduce(tp))
    plain = run(lambda v: jax.lax.psum(v, "model"))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(plain))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(x) * sum(range(1, tp + 1)), rtol=1e-6)


def test_make_reduce_names_its_scope():
    """One device has nothing to reduce; on a mesh the one collective of a
    product sits under ``mesh.reduce`` (what the trace's rows are named by:
    ``decode/layers/attn.out/mesh.reduce``)."""
    assert ovl.make_reduce(1) is None
    mesh = _tp_mesh(2)
    text = jax.jit(shard_map(
        ovl.make_reduce(2), mesh=mesh, in_specs=(P(),), out_specs=P(),
        check_vma=False)).lower(jnp.ones((4, 1, 8))).as_text(debug_info=True)
    assert "mesh.reduce" in text
    assert "all_reduce" in text and "all_gather" not in text \
        and "reduce_scatter" not in text


def test_resolve_mode_gates():
    tiny = resolve_model("debug:tiny", dtype="float32").cfg
    mesh = _tp_mesh(2)
    assert ovl.resolve_mode(tiny, mesh, "auto") == ("manual", "")
    assert ovl.resolve_mode(tiny, mesh, "0") == ("", "")
    assert ovl.resolve_mode(tiny, None, "auto") == ("", "")
    # dp>1 meshes stay on GSPMD (pool writes of distinct data shards
    # cannot be reconciled manually)
    if len(jax.devices()) >= 4:
        dp_mesh = build_mesh(MeshPlan(data=2, model=2),
                             devices=jax.devices()[:4])
        mode, why = ovl.resolve_mode(tiny, dp_mesh, "auto")
        assert mode == "" and "data" in why
    # MoE stays on GSPMD
    moe = resolve_model("debug:tiny-moe", dtype="float32").cfg
    mode, why = ovl.resolve_mode(moe, mesh, "auto")
    assert mode == "" and "MoE" in why
    # indivisible heads
    import dataclasses

    odd = dataclasses.replace(tiny, num_heads=3, num_kv_heads=3)
    mode, why = ovl.resolve_mode(odd, mesh, "auto")
    assert mode == "" and "divisible" in why


@pytest.mark.parametrize("value, mode", [
    (None, "manual"), ("", "manual"), ("auto", "manual"), ("1", "manual"),
    ("0", ""), ("off", ""), ("psum", ""), ("overlap", "")])
def test_knob_is_on_or_off(monkeypatch, value, mode):
    """``LOCALAI_MESH_OVERLAP`` says whether the manual trunk serves the mesh
    or GSPMD does, and nothing else: the names of the reductions it once
    chose between are refused with a reason, to GSPMD."""
    tiny = resolve_model("debug:tiny", dtype="float32").cfg
    if value is None:
        monkeypatch.delenv("LOCALAI_MESH_OVERLAP", raising=False)
    else:
        monkeypatch.setenv("LOCALAI_MESH_OVERLAP", value)
    got, why = ovl.resolve_mode(tiny, _tp_mesh(2))
    assert got == mode
    assert ("unknown" in why) == (value in ("psum", "overlap"))


def _meshed_tokens(monkeypatch, mode, kv_dtype="float32", steps=12):
    monkeypatch.setenv("LOCALAI_MESH_OVERLAP", mode)
    model = resolve_model("debug:tiny", dtype="float32")
    mesh = _tp_mesh(2)
    params = shd.shard_params(model.params, model.cfg, mesh)
    runner = ModelRunner(
        model.cfg, params, num_slots=2, max_ctx=128,
        prefill_buckets=[64], kv_dtype=kv_dtype, paged=True,
        kv_block_tokens=16, mesh=mesh)
    assert runner.overlap_mode == {"0": "", "auto": "manual"}[mode]
    slot = runner.acquire_slot()
    toks = [runner.admit(slot, list(range(1, 40)), temperature=0.0)]
    for _ in range(steps // 4):
        toks.extend(np.asarray(runner.step_n(4))[:, slot].tolist())
    return toks


@pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16", "int8"])
def test_trunk_vs_gspmd_greedy_parity(monkeypatch, kv_dtype):
    """THE parity pin: the manual trunk emits GSPMD's greedy tokens on the
    2-device mesh, over an unscaled pool and over a scaled one (whose
    scales ride the trunk's specs beside the rows)."""
    gspmd = _meshed_tokens(monkeypatch, "0", kv_dtype=kv_dtype)
    trunk = _meshed_tokens(monkeypatch, "auto", kv_dtype=kv_dtype)
    assert gspmd == trunk


def test_overlap_int4_pool(monkeypatch):
    """int4 composes with the manual trunk (packed pool sharded on its
    kv-head axis, scales riding the same specs)."""
    i4 = _meshed_tokens(monkeypatch, "auto", kv_dtype="int4")
    f32 = _meshed_tokens(monkeypatch, "auto", kv_dtype="float32")
    assert i4 == f32  # debug-model argmax margins dwarf int4 noise


def test_overlap_multi_slot_and_release(monkeypatch):
    """The manual trunk serves the multi-slot lifecycle (admit, decode,
    release, re-admit) identically to GSPMD."""

    def run(mode):
        monkeypatch.setenv("LOCALAI_MESH_OVERLAP", mode)
        model = resolve_model("debug:tiny", dtype="float32")
        mesh = _tp_mesh(2)
        params = shd.shard_params(model.params, model.cfg, mesh)
        r = ModelRunner(model.cfg, params, num_slots=2, max_ctx=128,
                        prefill_buckets=[64], kv_dtype="float32",
                        paged=True, kv_block_tokens=16, mesh=mesh)
        s0, s1 = r.acquire_slot(), r.acquire_slot()
        out = [r.admit(s0, list(range(1, 30)), temperature=0.0),
               r.admit(s1, list(range(5, 40)), temperature=0.0)]
        out.extend(np.asarray(r.step_n(4)).ravel().tolist())
        r.release(s0)
        s2 = r.acquire_slot()
        out.append(r.admit(s2, list(range(9, 60)), temperature=0.0))
        out.extend(np.asarray(r.step_n(4)).ravel().tolist())
        return out

    assert run("auto") == run("0")


# ---------------------------------------------------------------------------
# PR 38: over an unscaled pool the paged kernel writes the step's rows


def test_kernel_under_shard_map_writes_its_own_heads():
    """``ops.paged_decode_attention`` with the step's rows, wrapped as the
    meshed runner wraps it (heads of q, pool and rows on 'model'): each
    shard's kernel lays the rows of ITS heads into its shard of the pool.
    The pool that comes back equals the scatter's bit for bit outside the
    trash block, the output the kernel's over the scattered pool."""
    from functools import partial

    from localai_tpu import ops
    from localai_tpu.engine import kvcache as kvc

    mesh = _tp_mesh(2)
    rng = np.random.default_rng(11)
    S, Hq, Hkv, hd, bt, MB, L, layer = 3, 8, 4, 128, 16, 3, 2, 1
    N = S * MB + 1
    k, v = (jnp.asarray(rng.normal(size=(L, N, Hkv, bt, hd)), jnp.bfloat16)
            for _ in range(2))
    tables = jnp.asarray([[1, 4, 7], [0, 0, 0], [9, 2, 5]], jnp.int32)
    positions = jnp.asarray([15, 0, 40], jnp.int32)
    q = jnp.asarray(rng.normal(size=(S, Hq, hd)), jnp.bfloat16)
    k_new, v_new = (jnp.asarray(rng.normal(size=(S, Hkv, hd)), jnp.bfloat16)
                    for _ in range(2))
    rows, pool = P(None, "model", None), P(None, None, "model", None, None)
    kernel = partial(ops.paged_decode_attention, interpret=True)
    meshed = jax.jit(shard_map(
        kernel, mesh=mesh,
        in_specs=(rows, pool, pool, P(), P(), P(), None, None, rows, rows),
        out_specs=(rows, pool, pool), check_vma=False))
    out, k_out, v_out = meshed(q, k, v, jnp.int32(layer), tables, positions,
                               None, None, k_new, v_new)
    blk = tables[jnp.arange(S), positions // bt]
    want_k, want_v = kvc._write_rows((k, v), jnp.int32(layer), blk,
                                     positions % bt, k_new, v_new)
    for got, want, was in ((k_out, want_k, k), (v_out, want_v, v)):
        got, want, was = (np.asarray(a, np.float32) for a in (got, want, was))
        np.testing.assert_array_equal(got[:, 1:], want[:, 1:])
        np.testing.assert_array_equal(got[:, 0], was[:, 0])
    parent = kernel(q, want_k, want_v, jnp.int32(layer), tables, positions)
    live = np.asarray([True, False, True])
    np.testing.assert_array_equal(np.asarray(out, np.float32)[live],
                                  np.asarray(parent, np.float32)[live])


@pytest.mark.parametrize("mode, kv_dtype", [
    ("0", "float32"), ("auto", "float32"), ("0", "int8")])
def test_meshed_runner_kernel_writes_what_the_scatter_wrote(monkeypatch,
                                                            mode, kv_dtype):
    """The meshed paged runner with the Pallas kernel (under GSPMD inside
    the runner's ``shard_map`` around the kernel, under ``auto`` inside the
    manual-TP trunk) against the same runner on the XLA attend, whose
    policy scatters: the same greedy tokens, and the same pool outside the
    trash block (another attend's float order moves later layers' rows in
    their last bits). A scaled pool keeps the scatter under the kernel too
    (its scale stacks ride where the rows would): the same tokens."""
    monkeypatch.setenv("LOCALAI_MESH_OVERLAP", mode)
    model = resolve_model("debug:tiny", dtype="float32")
    mesh = _tp_mesh(2)
    params = shd.shard_params(model.params, model.cfg, mesh)

    def run(attn_impl):
        r = ModelRunner(model.cfg, params, num_slots=4, max_ctx=128,
                        prefill_buckets=[64], kv_dtype=kv_dtype,
                        paged=True, kv_block_tokens=16, mesh=mesh,
                        attn_impl=attn_impl)
        assert bool(r.overlap_mode) == (mode == "auto")
        assert r.paged_kv_write_impl == (
            "kernel" if (attn_impl, kv_dtype) == (
                "pallas_interpret", "float32") else "scatter")
        s0, s1 = r.acquire_slot(), r.acquire_slot()
        toks = [r.admit(s0, list(range(1, 30)), temperature=0.0),
                r.admit(s1, list(range(5, 40)), temperature=0.0)]
        toks.extend(np.asarray(r.step_n(4)).ravel().tolist())
        toks.extend(np.asarray(r.step()).ravel().tolist())
        return toks, np.asarray(r.kv.k), np.asarray(r.kv.v)

    scatter, kernel = run("xla"), run("pallas_interpret")
    assert kernel[0] == scatter[0]
    if kv_dtype == "int8":
        return
    for got, want in zip(kernel[1:], scatter[1:]):
        # the decode rows are there (values of ~1e-2 at this model)
        assert (np.abs(want[:, 1:]).sum(axis=(0, 2, 4)) > 0).sum() >= 39 + 10
        np.testing.assert_allclose(got[:, 1:], want[:, 1:],
                                   rtol=1e-4, atol=1e-7)


# ---------------------------------------------------------------------------
# PR 52, PR 54: a prompt's last chunk runs the live quarters of its bucket,
# on a mesh and on one device


def _chunk_runner(monkeypatch, tp, quartered=True, ref="debug:small", **kw):
    """A paged runner over debug:small (8 q / 4 kv heads; or ``ref``) on a
    1 x ``tp`` 'model' mesh (one device: no mesh), buckets 128 and 512 as
    the cells have them; ``quartered`` False takes the rule away (every
    chunk computes its whole bucket: the parent's program)."""
    from localai_tpu.engine import runner as rmod

    if not quartered:
        monkeypatch.setattr(rmod, "CHUNK_QUARTERED", 1 << 30)
    model = resolve_model(ref, dtype="float32")
    mesh = _tp_mesh(tp) if tp > 1 else None
    params = (shd.shard_params(model.params, model.cfg, mesh)
              if mesh is not None else model.params)
    kw = {"num_slots": 2, "max_ctx": 1024, "prefill_buckets": [128, 512],
          "kv_dtype": "float32", "paged": True, "kv_block_tokens": 16,
          "mesh": mesh, **kw}
    return ModelRunner(model.cfg, params, **kw)


def _admit_in_chunks(r, prompt):
    """(first token, K pool, V pool, the chunks' flight rows)."""
    adm = r.begin_admit(r.acquire_slot(), prompt, temperature=0.0)
    rows = []
    while True:
        last = adm.launch_chunk()
        rows.append(dict(adm.last_chunk))
        if last:
            break
    return adm.first_token(), np.asarray(r.kv.k), np.asarray(r.kv.v), rows


def _same_token_and_pool(cut, whole):
    """The first token, and the pool outside the trash block, EXACTLY."""
    assert cut[0] == whole[0]
    for got, want in zip(cut[1:3], whole[1:3]):
        assert np.abs(want[:, 1:]).sum() > 0
        np.testing.assert_array_equal(got[:, 1:], want[:, 1:])


@pytest.mark.parametrize("tail, parts", [(100, 1), (200, 2), (300, 3),
                                         (500, 4)])
@pytest.mark.parametrize("tp", [1, 4])
def test_chunk_runs_its_live_quarters(monkeypatch, tp, tail, parts):
    """A prompt of 512 + ``tail`` tokens on one device and on a 1 x 4
    'model' mesh: a full 512-row chunk that is not the last (its program is
    whole: ``chunk_parts`` 1), then the tail in the 128 bucket (whole too)
    or in the 512 bucket, whose part behind the attend is cut to the
    quarters that hold a real token. Against the same runner
    with the rule taken away: the same first token and the same pool
    outside the trash block, EXACTLY (rows of a matmul do not mix; the rows
    left out are padding, written nowhere and attended by nothing); the
    attend stays whole, so the ring row states the span it did."""
    if len(jax.devices()) < tp:
        pytest.skip(f"needs {tp} virtual devices")
    prompt = np.random.default_rng(3).integers(1, 500, 512 + tail).tolist()
    cut = _admit_in_chunks(_chunk_runner(monkeypatch, tp), prompt)
    whole = _admit_in_chunks(_chunk_runner(monkeypatch, tp, False), prompt)
    _same_token_and_pool(cut, whole)
    assert [row["chunk_parts"] for row in cut[3]] == [1, parts]
    assert [row["chunk_parts"] for row in whole[3]] == [1, 1]
    assert [row["chunk_bucket"] for row in cut[3]] == [
        row["chunk_bucket"] for row in whole[3]] == [512, 128 if parts == 1
                                                     else 512]
    assert cut[3][1]["chunk_ctx"] == whole[3][1]["chunk_ctx"] == 1024


@pytest.mark.parametrize("tokens, parts", [(200, 2), (300, 3)])
def test_looped_chunk_runs_its_live_quarters(monkeypatch, tokens, parts):
    """A looped decoder (debug:tiny-loop: 2 layers run 3 times a token, a
    cache layer a (pass, layer) pair) on one device: the layer scan and its
    switch sit inside the loop over the passes, the switch cuts the weights
    of layer ``cache layer - pass * layers`` from their stacks, and every
    pass leaves the dead rows out. The same first token and the same pool,
    all six cache layers of it, as the whole program's."""
    prompt = np.random.default_rng(5).integers(1, 500, tokens).tolist()
    kw = {"ref": "debug:tiny-loop", "max_ctx": 512}
    r = _chunk_runner(monkeypatch, 1, **kw)
    assert r.cfg.num_passes == 3 and r.kv.k.shape[0] == 6
    assert r.chunk_rows(512) == (256, 384, 512)
    cut = _admit_in_chunks(r, prompt)
    whole = _admit_in_chunks(_chunk_runner(monkeypatch, 1, False, **kw),
                             prompt)
    _same_token_and_pool(cut, whole)
    for got in cut[1:3]:        # every pass wrote its own cache layers
        assert (np.abs(got[:, 1:]).sum(axis=(1, 2, 3, 4)) > 0).all()
    assert [row["chunk_parts"] for row in cut[3]] == [parts]
    assert [row["chunk_parts"] for row in whole[3]] == [1]


@pytest.mark.parametrize("tp, bucket, rows", [
    (1, 512, (256, 384, 512)), (1, 2048, (1024, 1536, 2048)),
    (1, 128, (128,)), (2, 128, (128,)),
    (2, 512, (256, 384, 512)), (4, 512, (256, 384, 512)),
    (4, 2048, (1024, 1536, 2048))])
def test_chunk_rows_rule(monkeypatch, tp, bucket, rows):
    """The rule reads the bucket and the chunk, not the device count: a
    bucket under 512 rows runs every row, on one device as on a mesh (the
    128 bucket is bound by the weights' bytes); 512 rows or more run in
    quarters, and the host's arithmetic (the ring's ``chunk_parts``) is the
    program's switch."""
    if len(jax.devices()) < tp:
        pytest.skip(f"needs {tp} virtual devices")
    r = _chunk_runner(monkeypatch, tp)
    assert r.chunk_rows(bucket) == rows
    # a chunk that is not a prompt's last holds ``prefill_chunk`` (512)
    # tokens: in the 512 bucket every quarter is live, its program is whole
    assert r.chunk_rows(bucket, last=False) == (
        rows if bucket > 512 else (bucket,))
    for tokens in (bucket // 4 + 1, bucket // 2, bucket // 2 + 1,
                   3 * bucket // 4 + 1, bucket):
        assert r.chunk_parts(bucket, tokens) == (
            1 if len(rows) == 1 else -(-4 * tokens // bucket))


@pytest.mark.parametrize("why", ["contiguous", "routed"])
def test_chunk_rows_rule_refuses(monkeypatch, why):
    """What else the runner observes: over the contiguous cache
    (``paged=False``) and for a model with routed experts (its family's own
    forward takes no ``live``) every chunk computes its whole bucket."""
    if why == "contiguous":
        r = _chunk_runner(monkeypatch, 1, paged=False)
    else:
        from localai_tpu.models import llama as mdl
        from localai_tpu.models.llama import LlamaConfig

        cfg = dataclasses.replace(LlamaConfig.from_hf({
            "model_type": "afmoe", "vocab_size": 384, "hidden_size": 64,
            "intermediate_size": 96, "num_hidden_layers": 5,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "max_position_embeddings": 1024,
            "sliding_window": 8, "global_attn_every_n_layers": 4,
            "layer_types": ["full_attention" if (i + 1) % 4 == 0
                            else "sliding_attention" for i in range(5)],
            "num_dense_layers": 1, "num_experts": 8,
            "num_experts_per_tok": 2, "moe_intermediate_size": 32,
            "num_shared_experts": 1, "score_func": "sigmoid",
            "route_norm": True, "route_scale": 2.448, "mup_enabled": True,
            "expert_parallel": {"size": 2, "rank": 1}}), dtype="float32")
        r = ModelRunner(cfg, mdl.init_params(jax.random.key(0), cfg),
                        num_slots=2, max_ctx=1024, paged=True,
                        kv_block_tokens=16, prefill_buckets=[128, 512],
                        kv_dtype="float32")
        assert r.routed
    assert (r.paged, r.routed) == (why == "routed", why == "routed")
    for bucket in (128, 512, 2048):
        assert r.chunk_rows(bucket) == (bucket,)
        assert r.chunk_parts(bucket, bucket // 2) == 1


@pytest.mark.parametrize("tp", [1, 4])
def test_chunk_parts_reach_the_ring_and_metrics(monkeypatch, tp):
    """Through the scheduler: the prefill rows of ``/debug/flight`` say how
    many quarters each chunk ran (``chunk_parts``), and ``/metrics`` counts
    the chunk launches by them."""
    from localai_tpu.engine.scheduler import GenRequest, Scheduler
    from localai_tpu.obs import metrics as obs_metrics
    from localai_tpu.utils.tokenizer import ByteTokenizer

    if len(jax.devices()) < tp:
        pytest.skip(f"needs {tp} virtual devices")
    s = Scheduler(_chunk_runner(monkeypatch, tp), ByteTokenizer())
    try:
        for n in (512 + 200, 300, 100):     # no two share a first block
            s.generate(GenRequest(prompt=[65 + (n + i) % 26 for i in range(n)],
                                  max_new_tokens=2, temperature=0.0),
                       timeout=300)
        chunks = [x for x in s.flight.snapshot()
                  if x["program"] == "prefill_chunk"]
        assert [x["chunk_parts"] for x in chunks] == [1, 2, 3, 1]
        assert [x["chunk_bucket"] for x in chunks] == [512, 512, 512, 128]
        m = s.metrics()
        assert m["prefill_chunk_parts"] == {1: 2, 2: 1, 3: 1}
        obs_metrics.update_engine_gauges("q4", m)
        text = obs_metrics.REGISTRY.render()
        for parts, n in ((1, 2), (2, 1), (3, 1)):
            assert ('localai_prefill_chunk_parts_total{model="q4",parts="'
                    f'{parts}"}} {n}') in text
    finally:
        s.shutdown()

"""Per-shape kernel tuning table (ops.tuning) + its consumers.

Pins: JSON round-trip, corrupt-file → defaults (never an error), the
select_paged_attn_impl consult order (explicit > env > tuned > backend
default, hard shape gates over everything), and the runner picking up
tuned block_tokens / num_buffers at construction.
"""

import json

import pytest

from localai_tpu import ops
from localai_tpu.engine.runner import ModelRunner
from localai_tpu.models.registry import resolve_model
from localai_tpu.ops import tuning


@pytest.fixture(autouse=True)
def _fresh_table(monkeypatch, tmp_path):
    """Each test gets its own cache path and a cleared singleton."""
    path = tmp_path / "tuning.json"
    monkeypatch.setenv(tuning.ENV_CACHE, str(path))
    tuning.reset()
    yield path
    tuning.reset()


def test_table_roundtrip(_fresh_table):
    t = tuning.TuningTable(path=str(_fresh_table))
    key = tuning.shape_key(128, 8, "int8", 2)
    assert key == "hd128_kv8_int8_tp2"
    t.put(key, tuning.TuneEntry(impl="pallas", block_tokens=64,
                                num_buffers=3, us=412.5))
    t.save()
    back = tuning.TuningTable.load(str(_fresh_table))
    e = back.lookup(key)
    assert e == tuning.TuneEntry(impl="pallas", block_tokens=64,
                                 num_buffers=3, us=412.5)
    # the singleton sees the saved file too
    assert tuning.lookup(128, 8, "int8", 2) == e
    assert tuning.lookup(128, 8, "int4", 2) is None


def test_corrupt_file_falls_back_to_defaults(_fresh_table):
    _fresh_table.write_text("{ not json !!!")
    t = tuning.TuningTable.load(str(_fresh_table))
    assert t.entries == {}
    assert tuning.lookup(128, 8, "int8", 1) is None  # no crash

    # a valid file with one malformed entry drops ONLY that entry
    _fresh_table.write_text(json.dumps({
        "hd128_kv8_int8_tp1": {"impl": "pallas", "block_tokens": 64},
        "bad1": {"impl": "warp-drive"},
        "bad2": {"block_tokens": "lots"},
        "bad3": [1, 2, 3],
    }))
    tuning.reset()
    t = tuning.TuningTable.load(str(_fresh_table))
    assert set(t.entries) == {"hd128_kv8_int8_tp1"}


def test_missing_and_disabled_paths(_fresh_table, monkeypatch):
    assert tuning.TuningTable.load(str(_fresh_table)).entries == {}
    monkeypatch.setenv(tuning.ENV_CACHE, "0")
    tuning.reset()
    assert tuning.cache_path() == ""
    assert tuning.lookup(128, 8, "int8", 1) is None


def _write_table(path, key, **entry):
    path.write_text(json.dumps({key: entry}))
    tuning.reset()


def test_select_consults_tuned_impl(_fresh_table):
    """A tuned impl drives the auto decision on the shape it was measured
    for — and ONLY that shape. Off-TPU a tuned "pallas" is IGNORED (the
    table is an automatic source; off-TPU "pallas" is an error and the
    interpreter is only ever an explicit choice), while a tuned "xla" is
    honored anywhere."""
    _write_table(_fresh_table, tuning.shape_key(128, 8, "bfloat16", 1),
                 impl="pallas", block_tokens=64)
    assert ops.select_paged_attn_impl(
        "auto", num_heads=32, num_kv_heads=8, head_dim=128,
        block_tokens=64, kv_dtype="bfloat16",
        backend="tpu") == ("pallas", False)
    # the same tuned "pallas" off-TPU falls back to the backend default
    assert ops.select_paged_attn_impl(
        "auto", num_heads=32, num_kv_heads=8, head_dim=128,
        block_tokens=64, kv_dtype="bfloat16",
        backend="cpu") == ("xla", False)
    # a tuned "xla" overrides the TPU default
    _write_table(_fresh_table, tuning.shape_key(128, 8, "bfloat16", 1),
                 impl="xla")
    impl, _ = ops.select_paged_attn_impl(
        "auto", num_heads=32, num_kv_heads=8, head_dim=128,
        block_tokens=64, kv_dtype="bfloat16", backend="tpu")
    assert impl == "xla"
    # a different shape misses the table → backend default (xla on cpu)
    impl, _ = ops.select_paged_attn_impl(
        "auto", num_heads=32, num_kv_heads=4, head_dim=128,
        block_tokens=64, kv_dtype="bfloat16", backend="cpu")
    assert impl == "xla"


def test_select_reuses_caller_tuned_entry(_fresh_table):
    """A caller-supplied TuneEntry (the runner's single-lookup path)
    bypasses the internal table consult entirely."""
    from localai_tpu.obs.metrics import REGISTRY

    def lookups():
        s = REGISTRY.autotune_lookups._series  # noqa: SLF001
        return sum(s.values())

    n0 = lookups()
    impl, _ = ops.select_paged_attn_impl(
        "auto", num_heads=32, num_kv_heads=8, head_dim=128,
        block_tokens=64, kv_dtype="bfloat16", backend="tpu",
        tuned=tuning.TuneEntry(impl="xla"))
    assert impl == "xla"
    impl, _ = ops.select_paged_attn_impl(
        "auto", num_heads=32, num_kv_heads=8, head_dim=128,
        block_tokens=64, kv_dtype="bfloat16", backend="tpu",
        tuned=tuning.TuneEntry())  # empty = looked up, no preference
    assert impl == "pallas"
    assert lookups() == n0  # no second receipt from either call


def test_hard_gates_override_tuned_pallas(_fresh_table):
    """A tuned "pallas" on a Mosaic-untileable shape is refused like any
    other — the table can prefer, never force, a kernel the hardware
    rejects."""
    _write_table(_fresh_table, tuning.shape_key(100, 8, "bfloat16", 1),
                 impl="pallas")
    with pytest.raises(ValueError, match="tileable"):
        ops.select_paged_attn_impl(
            "auto", num_heads=32, num_kv_heads=8, head_dim=100,
            block_tokens=64, kv_dtype="bfloat16", backend="tpu")


def test_env_override_beats_tuned(_fresh_table, monkeypatch):
    _write_table(_fresh_table, tuning.shape_key(128, 8, "bfloat16", 1),
                 impl="pallas")
    monkeypatch.setenv("LOCALAI_PAGED_ATTN_IMPL", "xla")
    impl, _ = ops.select_paged_attn_impl(
        "auto", num_heads=32, num_kv_heads=8, head_dim=128,
        block_tokens=64, kv_dtype="bfloat16", backend="tpu")
    assert impl == "xla"


def test_explicit_request_beats_everything(_fresh_table):
    _write_table(_fresh_table, tuning.shape_key(128, 8, "bfloat16", 1),
                 impl="pallas")
    impl, _ = ops.select_paged_attn_impl(
        "xla", num_heads=32, num_kv_heads=8, head_dim=128,
        block_tokens=64, kv_dtype="bfloat16", backend="tpu")
    assert impl == "xla"


def test_runner_consults_tuned_block_tokens(_fresh_table, monkeypatch):
    model = resolve_model("debug:tiny", dtype="float32")
    cfg = model.cfg
    _write_table(_fresh_table,
                 tuning.shape_key(cfg.hd, cfg.num_kv_heads, "float32", 1),
                 impl="xla", block_tokens=32, num_buffers=3)
    monkeypatch.delenv("LOCALAI_KV_BLOCK_TOKENS", raising=False)
    r = ModelRunner(cfg, model.params, num_slots=2, max_ctx=128,
                    prefill_buckets=[64], kv_dtype="float32", paged=True)
    assert r.block_tokens == 32
    assert r.paged_num_buffers == 3
    # explicit kwarg wins over the table
    r2 = ModelRunner(cfg, model.params, num_slots=2, max_ctx=128,
                     prefill_buckets=[64], kv_dtype="float32", paged=True,
                     kv_block_tokens=16)
    assert r2.block_tokens == 16
    # env wins over the table too
    monkeypatch.setenv("LOCALAI_KV_BLOCK_TOKENS", "64")
    r3 = ModelRunner(cfg, model.params, num_slots=2, max_ctx=128,
                     prefill_buckets=[64], kv_dtype="float32", paged=True)
    assert r3.block_tokens == 64


def test_lookup_metric_receipts(_fresh_table):
    from localai_tpu.obs.metrics import REGISTRY

    _write_table(_fresh_table, tuning.shape_key(64, 8, "int8", 1),
                 impl="xla", block_tokens=64)

    def total(result):
        return REGISTRY.autotune_lookups._series.get(  # noqa: SLF001
            (("result", result),), 0.0)

    h0, m0 = total("hit"), total("miss")
    assert tuning.lookup(64, 8, "int8", 1) is not None
    assert tuning.lookup(64, 8, "int4", 1) is None
    assert total("hit") == h0 + 1
    assert total("miss") == m0 + 1


def test_autotune_smoke_cli(tmp_path, monkeypatch):
    """The CI smoke path end-to-end: a tiny sweep produces a loadable
    table whose entries the gate machinery accepts."""
    import tools.autotune as at

    out = tmp_path / "table.json"
    monkeypatch.setenv(tuning.ENV_CACHE, str(out))
    tuning.reset()
    argv = ["--preset", "tiny", "--kv-dtypes", "float32", "--tp", "1",
            "--blocks", "8", "--buffers", "2", "--ctx", "32",
            "--out", str(out)]
    # it tunes a TPU: on this backend it refuses, unless told explicitly
    # that this is the interpreter-mode machinery smoke
    with pytest.raises(SystemExit) as refused:
        at.main(argv)
    assert refused.value.code == 2 and not out.exists()
    with pytest.raises(SystemExit):     # and never into the default table
        at.main([a for a in argv if a not in ("--out", str(out))]
                + ["--interpret"])
    rc = at.main(argv + ["--interpret"])
    assert rc == 0
    table = tuning.TuningTable.load(str(out))
    key = tuning.shape_key(16, 2, "float32", 1)
    entry = table.lookup(key)
    assert entry is not None and entry.block_tokens == 8
    assert entry.impl in ("xla", "pallas")

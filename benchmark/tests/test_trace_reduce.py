"""The reduction from the profiler's trace to numbers: on hand-made traces
with known intervals (written as real XSpace protobufs, read back through
``jax.profiler.ProfileData``) and on a small trace recorded on a v5e chip."""

from pathlib import Path

import pytest

import xspace
from conftest import ROOT
from harness import spec
from harness import trace_reduce as tr

FIXTURE = Path(__file__).parent / "data" / "v5e_small.xplane.pb"
T0 = 1_790_000_000_000_000_000      # Unix ns


def write(tmp_path, planes, window_ns=1000):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(xspace.space(planes + [xspace.plane(
        "Task Environment", {}, {"profile_start_time": T0,
                                 "profile_stop_time": T0 + window_ns})]))
    return path


def test_interval_arithmetic():
    assert tr.union_seconds([(0, 1), (0.5, 2), (3, 4)]) == 3.0
    assert tr.union_seconds([]) == 0.0
    assert tr.subtract_seconds([(0, 4)], [(1, 2), (3, 5)]) == 2.0
    assert tr.gaps([(1, 2), (4, 5)], 0, 10, top=2) == [(5, 5), (2, 2)]
    # a while around two fusions is charged only what they do not cover
    self_s = tr.self_times([(0, 10, "while.1"), (1, 3, "fusion.1"),
                            (4, 8, "fusion.2"), (4, 5, "inner"),
                            (12, 13, "fusion.1")])
    assert self_s == {"while.1": 4, "fusion.1": 3, "fusion.2": 3, "inner": 1}


def test_busy_union_idle_share_and_per_name_sums(tmp_path):
    ops = [("fusion.1", 100, 50), ("while.2", 200, 300),
           ("custom-call.3", 220, 100), ("fusion.1", 400, 50),
           ("fusion.9", 700, 100)]
    path = write(tmp_path, [xspace.plane("/device:TPU:0", {
        "XLA Ops": ops,
        "XLA Modules": [("jit__decode_paged_n_fn(1)", 100, 400),
                        ("jit__prefill_paged_fn(2)", 700, 100)]})])
    out = tr.reduce(path)
    # busy = [100,150) + [200,500) + [700,800) = 450 ns; the window is what
    # the profiler recorded of the device, first operation to last: 700 ns
    assert out["busy_s"] == pytest.approx(450e-9)
    assert out["window_s"] == pytest.approx(700e-9)
    assert out["window_at_s"] == pytest.approx((100e-9, 800e-9))
    assert out["idle_share"] == pytest.approx(250 / 700)
    assert out["start_unix"] == pytest.approx(T0 * 1e-9)
    assert out["ops"] == pytest.approx({
        "fusion.1": 100e-9, "while.2": 150e-9, "custom-call.3": 100e-9,
        "fusion.9": 100e-9})
    assert out["breakdown"]["device_ops"][0] == ["while.2",
                                                 pytest.approx(150e-9)]
    # the gaps inside the window: [500,700), [150,200)
    assert [g[1] for g in out["breakdown"]["idle_gaps"]] == pytest.approx(
        [200e-9, 50e-9])
    assert {g[0] for g in out["breakdown"]["idle_gaps"]} == {"unattributed"}
    assert tr.module_seconds(out, "decode") == (pytest.approx(400e-9), 1)
    assert tr.module_seconds(out, "prefill") == (pytest.approx(100e-9), 1)
    assert tr.op_seconds(out, r"custom-call")[0] == pytest.approx(100e-9)


def test_chips_are_averaged_and_collectives_split(tmp_path):
    chip0 = {"XLA Ops": [("fusion.1", 0, 400), ("all-reduce.1", 400, 100),
                         ("all-gather-start.2", 600, 100),
                         ("fusion.2", 650, 150)]}
    chip1 = {"XLA Ops": [("fusion.1", 0, 200)]}
    path = write(tmp_path, [xspace.plane("/device:TPU:0", chip0),
                            xspace.plane("/device:TPU:1", chip1)])
    out = tr.reduce(path)
    assert out["chips"] == 2
    assert out["busy_by_chip"] == pytest.approx([700e-9, 200e-9])
    assert out["busy_s"] == pytest.approx(450e-9)
    assert out["window_s"] == pytest.approx(800e-9)     # over all chips
    assert out["idle_share_worst_chip"] == pytest.approx(0.75)
    # chip 0: 200 ns in collectives, of which [650,700) ran under fusion.2
    assert out["collective_s"] == pytest.approx((200e-9 + 0) / 2)
    assert out["collective_exposed_s"] == pytest.approx((150e-9 + 0) / 2)


def test_a_trace_with_no_device_operation_is_refused(tmp_path):
    path = write(tmp_path, [xspace.plane("/host:CPU", {
        "python3": [("PjitFunction(f)", 0, 10)]})])
    with pytest.raises(RuntimeError, match="no operation"):
        tr.reduce(path)


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace")
def test_recorded_v5e_trace():
    """The planes, lines and clock of a real TPU trace are found."""
    out = tr.reduce(FIXTURE)
    assert out["chips"] == 1 and out["notes"]["planes"] == ["/device:TPU:0"]
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["start_unix"] > 1.7e9
    lo, hi = out["window_at_s"]
    assert out["modules"] and all(
        lo - 1e-3 <= s < e <= hi + 1e-3 for s, e, _ in out["modules"])
    assert sum(out["ops"].values()) == pytest.approx(out["busy_s"], rel=1e-6)


# ---------------------------------------------------------------------------
# the reader of what only a mesh has


def meshed(tmp_path, collective=True):
    """Two chips, each: a fusion in [0,400), an all-reduce (or, without
    ``collective``, a fusion) in [400,600), a fusion in [600,900)."""
    chip = {"XLA Ops": [
        ("%fusion.1 = bf16[32,5120]{1,0} fusion(bf16[32,5120]{1,0} %x)",
         0, 400),
        ("%all-reduce.7 = bf16[32,5120]{1,0} all-reduce(bf16[32,5120]{1,0} "
         "%fusion.1), replica_groups={{0,1}}" if collective else
         "%fusion.7 = bf16[32,5120]{1,0} fusion(bf16[32,5120]{1,0} %x)",
         400, 200),
        ("%fusion.2 = bf16[32,8192]{1,0} fusion(bf16[32,5120]{1,0} %y)",
         600, 300)]}
    return tr.reduce(write(tmp_path, [
        xspace.plane("/device:TPU:0", chip),
        xspace.plane("/device:TPU:1", chip)]))


def test_the_mesh_reader_reads_the_collectives_share_of_busy_time(tmp_path):
    read = spec.load_reader("mesh.collective_share", ROOT)
    assert read({"trace": meshed(tmp_path)}) == pytest.approx(
        100.0 * 200 / 900)
    # a mesh whose trace holds no collective reads 0: a rename or a removal
    # shows, the metric does not vanish
    assert read({"trace": meshed(tmp_path, collective=False)}) == 0.0
    # one chip runs no collective, and without a trace there is nothing to
    # read: the metric is left out
    one = tr.reduce(write(tmp_path, [xspace.plane("/device:TPU:0", {
        "XLA Ops": [("fusion.1", 0, 400)]})]))
    assert read({"trace": one}) is None
    assert read({"trace": None}) is None and read({}) is None

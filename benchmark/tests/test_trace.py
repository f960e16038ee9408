"""The trace reduction gives known answers on synthetic event lists."""

import os

import pytest

from benchmark import profile_trace as T

HERE = os.path.dirname(os.path.abspath(__file__))


def _data():
    # window 0..100 ns; chip A busy 10-40 (a while loop spanning two ops),
    # an all-reduce at 40-60 overlapped by compute at 50-55; chip B idle
    a = T.leaf_ops([
        (10, 40, "%while.62 = (f32[1]{0}) while(f32[1]{0} %t), body=%b"),
        (10, 25, "%fusion.1 = f32[8]{0:T(1024)} fusion(f32[8] %x), kind=kLoop"),
        (25, 40, '%body.13 = (f32[1,8]) custom-call(f32[8,8] %h), '
                 'custom_call_target="tpu_custom_call"'),
        (40, 60, "%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %p)"),
        (50, 55, "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %q)"),
    ])
    b = [(0, 5, "fusion.1")]
    host = [(0, 12, "frames.stage"), (60, 90, "solution.fetch"),
            (90, 100, "solve.dispatch")]
    return T.TraceData(window=(0, 100), chips={"A": a, "B": b}, host=host)


def test_leaf_ops_drop_control_flow_and_shorten_names():
    names = [e[2] for e in _data().chips["A"]]
    assert names == ["%fusion.1 fusion",
                     "%body.13 custom-call tpu_custom_call",
                     "%all-reduce.3 all-reduce", "%fusion.2 fusion"]


def test_merge_subtract_gaps():
    m = T.merge([(5, 10), (0, 3), (8, 12), (20, 25)])
    assert m == [(0, 3), (5, 12), (20, 25)]
    assert T.total(m) == 15
    assert T.subtract([(0, 30)], [(5, 10), (20, 25)]) == [(0, 5), (10, 20),
                                                          (25, 30)]
    assert T.gaps(m, 0, 30) == [(3, 5), (12, 20), (25, 30)]


def test_busy_idle_and_breakdown():
    d = _data()
    assert T.busy_ns(d, "A") == 50
    assert T.busiest_chip(d) == "A"
    ops = dict((n, s) for n, s in T.top_ops(d))
    assert ops["%all-reduce.3 all-reduce"] == pytest.approx(20e-9 / 2)
    gaps = T.idle_gaps(d)
    assert gaps[0] == ["solution.fetch", pytest.approx(40e-9)]
    assert gaps[1] == ["frames.stage", pytest.approx(10e-9)]


def test_metric_readers_on_synthetic_trace():
    from benchmark import spec

    class Run:
        trace = _data()

    d_idle = spec.load_module(os.path.join(spec.BENCH_DIR, "metrics",
                                           "device_idle_pct.py"))
    assert d_idle.read(Run) == pytest.approx(50.0)
    Run.trace = None
    assert d_idle.read(Run) is None  # no trace: nothing to read


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    """Two warm chains of ``wide.warm-series`` traced on one v5e, at PR
    22's first-round shape (12288 x 262144, 2-D grid; my chip run, PR 22),
    kept gzipped."""
    import gzip
    import shutil

    path = tmp_path_factory.mktemp("trace") / "wide.xplane.pb"
    with gzip.open(os.path.join(HERE, "data", "wide_trace.xplane.pb.gz")) as f:
        with open(path, "wb") as out:
            shutil.copyfileobj(f, out)
    return T.load(str(path))


def test_recorded_chip_trace(chip_trace):
    d = chip_trace
    assert list(d.chips) == ["/device:TPU:0"]
    assert d.window_s == pytest.approx(5.040077454)
    # no operation of the chain's while loop is lost: the device is busy
    # but for the chains' hand-offs and the last fetch
    busy = T.busy_ns(d, "/device:TPU:0") * 1e-9
    assert busy == pytest.approx(4.997412691)
    ops = dict(T.top_ops(d))
    kernel = ops["%body.13 custom-call tpu_custom_call"]
    assert kernel == pytest.approx(2.28969479)
    assert sum(ops.values()) <= busy + 1e-9
    gaps = T.idle_gaps(d)
    assert gaps[0][0] == "solution.fetch" and gaps[0][1] < 0.05
    assert {n for n, _ in gaps} <= set(T.HOST_SPANS) | {"none"}

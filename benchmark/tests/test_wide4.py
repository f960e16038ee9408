"""The four-chip cell ``wide4.warm-series`` in miniature: a tiny stand-in on
four virtual CPU devices with the Pallas kernel interpreted, so that the
program takes the chip's voxel-major 1x4 mesh, its forward-projection psum
and its Laplacian halo exchange; and the reader of the exchange's device
time, ``exchange_ms_per_iteration``, on synthetic events."""

import json
import os

import pytest

from benchmark import spec
from conftest import REPO, TINY_LIMITS, make_root, run_harness

CELL = "tiny-wide4.warm-series"
# 4 voxel shards of 256 voxels, two planes of the 4x8x32 grid each: every
# middle shard's Laplacian reaches both neighbours through the halo
TINY_WIDE4 = {"npixel": 96, "nvoxel": 1024, "grid": [4, 8, 32]}
INTERPRET = ("--fused_sweep", "interpret")

# the all_gather of the halo's export table planted to return this chip's
# own boundary values in every other chip's place
LOCAL_HALO = """
import jax
import jax.numpy as jnp
def _local(x, axis_name, *, axis=0, tiled=False, **kw):
    n = jax.lax.axis_size(axis_name)
    return (jnp.concatenate([x] * n, axis=axis) if tiled
            else jnp.stack([x] * n, axis=axis))
jax.lax.all_gather = _local
"""


@pytest.fixture(scope="module")
def wide4_root(tmp_path_factory):
    """``make_root``'s tiny checkout plus a stand-in of ``wide4.warm-series``
    added as files: the cell's configuration at a tiny size, its workload
    file with the tiny cells' limits, on four chips."""
    root = make_root(str(tmp_path_factory.mktemp("wide4")))
    bench_dir = os.path.join(root, "benchmark")
    with open(os.path.join(REPO, "benchmark", "configs",
                           "rtm-wide-fp32-4chip.json")) as f:
        cfg = dict(json.load(f), name="tiny-wide4", **TINY_WIDE4)
    with open(os.path.join(bench_dir, "configs", "tiny-wide4.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(REPO, "benchmark", "workloads",
                           "wide4.warm-series.json")) as f:
        wl = dict(json.load(f), rate_cap_frames_per_s=20000,
                  limits=TINY_LIMITS)
    with open(os.path.join(bench_dir, "workloads", CELL + ".json"), "w") as f:
        json.dump(wl, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-wide4", "source": "tiny",
                             "file": "benchmark/configs/tiny-wide4.json",
                             "reduced": [], "why": "CPU test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-wide4",
                               "traffic": "warm_series", "chips": 4,
                               "why": "CPU test"})
    for m in bench["per_layer"]:
        m.setdefault("workloads", []).append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)
    return root


def test_stand_in_takes_the_voxel_major_mesh_and_is_correct(wide4_root):
    r, proc = run_harness(wide4_root, CELL, seed=4_000_000_007,
                          flags=INTERPRET)
    assert r is not None, proc.stderr[-3000:]
    assert "mesh=1x4 engaged=interpret" in proc.stderr
    assert "compiles_in_window=0 " in proc.stderr
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 16


def test_halo_left_local_is_not_correct(wide4_root):
    r, proc = run_harness(wide4_root, CELL, seed=4_000_000_009,
                          flags=INTERPRET, prelude=LOCAL_HALO)
    assert r is not None, proc.stderr[-3000:]
    assert "mesh=1x4" in proc.stderr
    assert not r["correct"], r["checks"]


def _reader():
    return spec.load_module(os.path.join(spec.BENCH_DIR, "metrics",
                                         "exchange_ms_per_iteration.py"))


class _Frame:
    def __init__(self, index, iterations):
        self.index, self.iterations, self.phase = index, iterations, "trace"


W = "jit(run)/while/body/closed_call/while/body/"
# window 0..100 ns: the halo's all-gather nested in the penalty (0-10), the
# penalty's own work (10-20), the sweep (20-60) with a psum nested in it
# (40-45), the forward projection's psum alone (60-70, and 65-72 again,
# overlapping), an op past the window's end, one wholly after it
OPS = [
    (0, 10, W + "sart.laplacian/sart.exchange/all_gather:"),
    (10, 20, W + "sart.laplacian/dynamic_slice:"),
    (20, 60, W + "sart.sweep/pallas_call:"),
    (40, 45, W + "sart.sweep/sart.exchange/psum:"),
    (60, 70, W + "sart.exchange/psum:"),
    (65, 72, W + "sart.exchange/psum:"),
    (95, 130, W + "sart.exchange/psum:"),
    (150, 160, W + "sart.exchange/psum:"),
]
FRAMES = [_Frame(16, 3), _Frame(17, 2)]


def test_exchange_reader_on_synthetic_events():
    reader = _reader()
    # 10 + 5 + 12 + 5 ns inside the window over 5 iterations
    assert reader.reading(OPS, (0, 100), FRAMES) == pytest.approx(
        32e-6 / 5)
    nested = [op for op in OPS if "/sart.laplacian/sart.exchange/" in op[2]]
    assert reader.reading(nested, (0, 100), FRAMES) == pytest.approx(
        10e-6 / 5)
    # a program without the scope, or one chip, gives nothing to read
    bare = [(s, e, p.replace("sart.exchange/", "")) for s, e, p in OPS]
    assert reader.reading(bare, (0, 100), FRAMES) is None
    assert reader.reading(OPS, (0, 100), []) is None

    class Run:
        trace = None

    assert reader.read(Run) is None

"""Whole runs of tiny cells on the CPU: the window, the rate, discovery by
name, and the refusals."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import REPO, make_root, run_harness


@pytest.mark.parametrize("cell", ["tiny1.warm-series", "tiny4.warm-series"])
def test_window_ends_on_a_chain_and_counts_every_frame(tiny_root, cell):
    r, proc = run_harness(tiny_root, cell)
    assert r is not None, proc.stderr[-3000:]
    assert r["correct"], r["checks"]
    assert "compiles_in_window=0 " in proc.stderr
    # the window holds whole chains of 8, all of them delivered
    assert r["attempted"] % 8 == 0 and r["attempted"] >= 16
    assert r["failed"] == 0
    assert r["metrics"]["frames_per_s"]["value"] > 0
    assert set(r["metrics"]) == {"frames_per_s", "setup_s"}  # no peak on CPU
    assert list(r)[-1] == "checks"


def test_rate_is_delivered_frames_over_the_whole_window(tiny_root):
    code = f"""
import sys; sys.path.insert(0, {tiny_root!r})
from benchmark import run
import jax
cell = run.spec.load_cell('tiny1.warm-series', {tiny_root!r})
rt = run.Runtime(7, 0.3, False, jax.devices()[:1], memory_stats=False)
o = cell.driver().run(cell, rt)
win = [f for f in o.frames if f.phase == 'window']
chains = sorted({{f.chain for f in win}})
assert chains == list(range(2, 2 + len(chains))), chains
assert sorted(f.index for f in win) == list(range(16, 16 + len(win)))
assert o.window_s >= 0.3
rec = run.RunRecord(cell, o, None, 'cpu', 1)
rate = cell.reader('frames_per_s').read(rec)
assert abs(rate - len(win) / o.window_s) < 1e-9
print('OK', len(win))
"""
    p = subprocess.run([sys.executable, "-c", code], cwd=tiny_root,
                       capture_output=True, text=True, timeout=300)
    assert "OK" in p.stdout, p.stderr[-3000:]


def test_cell_config_and_metric_added_as_files_only(tmp_path):
    root = make_root(str(tmp_path / "r"))
    b = os.path.join(root, "benchmark")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(b, "configs", "tiny1.json")) as f:
        cfg = json.load(f)
    cfg.update(name="tiny-new", grid=[2, 8, 16])
    with open(os.path.join(b, "configs", "tiny-new.json"), "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(b, "workloads", "tiny1.warm-series.json"),
                os.path.join(b, "workloads", "new.warm-series.json"))
    with open(os.path.join(b, "metrics", "frames_seen.py"), "w") as f:
        f.write("def read(run):\n"
                "    return float(len(run.frames('window')))\n")
    bench["configs"].append({"name": "tiny-new", "source": "tiny",
                             "file": "benchmark/configs/tiny-new.json",
                             "reduced": [], "why": "added as a file"})
    bench["workloads"].append({"name": "new.warm-series",
                               "config": "tiny-new",
                               "traffic": "warm_series", "chips": 1,
                               "why": "added as a file"})
    bench["end_to_end"].append({"name": "frames_seen", "unit": "frames",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["new.warm-series"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    r, proc = run_harness(root, "new.warm-series")
    assert r is not None, proc.stderr[-3000:]
    assert r["correct"]
    assert r["metrics"]["frames_seen"]["value"] == r["attempted"]


def _main(root, cell, cwd=None):
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", cell, "--seed", "5", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=cwd or root)


def test_no_tpu_means_no_result(tiny_root):
    p = _main(tiny_root, "tiny1.warm-series")
    assert p.returncode != 0 and "{" not in p.stdout
    assert "TPU" in p.stderr


def test_benchmark_alone_means_no_result(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns(".jax_cache", ".out",
                                                  "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    p = _main(root, "wide.warm-series")
    assert p.returncode != 0 and "{" not in p.stdout

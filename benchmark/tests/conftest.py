"""CPU tests of the benchmark: ``python -m pytest benchmark/tests``.

They run the harness on tiny copies of the cells (``tiny_root``), on JAX's
CPU backend with four virtual devices, each run in a child process so that
a fault planted in one run stays there."""

import json
import os
import shutil
import subprocess
import sys

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

# tiny stand-ins for the two cells: same traffic, same code. Their limits
# are their own, set from CPU readings as the chip cells' are from chip
# runs (PR 22; the readings are in PERF.md §2)
TINY = {"tiny1": (96, 256, (4, 8, 8), 1), "tiny4": (192, 256, (4, 8, 8), 4)}
TINY_LIMITS = {"cold_gap": 1e-5, "warm_gap": 1e-5, "carried_gap": 1e-5,
               "stop_rule": 2.0, "failed_frames": 0}


def make_root(dst, template_cell="wide.warm-series"):
    """A checkout of the benchmark with tiny cells added as files only."""
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns(".jax_cache", ".out",
                                                  "__pycache__", "tests"))
    os.symlink(os.path.join(REPO, "sartsolver_tpu"),
               os.path.join(dst, "sartsolver_tpu"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "rtm-wide-fp32.json")) as f:
        base_cfg = json.load(f)
    with open(os.path.join(REPO, "benchmark", "workloads",
                           template_cell + ".json")) as f:
        base_wl = json.load(f)
    for name, (P, V, grid, chips) in TINY.items():
        cfg = dict(base_cfg, name=name, npixel=P, nvoxel=V, grid=list(grid))
        with open(os.path.join(dst, "benchmark", "configs",
                               name + ".json"), "w") as f:
            json.dump(cfg, f)
        bench["configs"].append({
            "name": name, "source": "tiny", "why": "CPU test",
            "file": f"benchmark/configs/{name}.json", "reduced": []})
        cell = f"{name}.warm-series"
        bench["workloads"].append({"name": cell, "config": name,
                                   "traffic": "warm_series", "chips": chips,
                                   "why": "CPU test"})
        with open(os.path.join(dst, "benchmark", "workloads",
                               cell + ".json"), "w") as f:
            json.dump(dict(base_wl, rate_cap_frames_per_s=20000,
                           limits=TINY_LIMITS), f)
        for m in bench["per_layer"]:
            m.setdefault("workloads", []).append(cell)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return dst


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("bench")))


def run_harness(root, cell, seed=3_000_000_019, seconds=0.4, trace=0,
                prelude="", control=False, flags=()):
    """Run one cell in a child process on the CPU, skipping only the
    harness's look for a chip; ``prelude`` plants a fault first,
    ``flags`` are added to the configuration's. Returns the result line
    (None if none was printed) and the process."""
    flags = tuple(flags) + (("--rtm_dtype", "bfloat16") if control else ())
    code = f"""
import sys
sys.path.insert(0, {root!r})
{prelude}
from benchmark import run
import jax
devs = jax.devices()[:run.spec.load_cell({cell!r}, {root!r}).chips]
cell = run.spec.load_cell({cell!r}, {root!r})
r = run.run_cell(cell, {seed}, {seconds}, {bool(trace)}, devices=devs,
                 device_kind='cpu', extra_flags={flags}, memory_stats=False)
import json
print(json.dumps(r))
"""
    proc = subprocess.run([sys.executable, "-c", code], cwd=root,
                          capture_output=True, text=True, timeout=600)
    lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
    return (json.loads(lines[-1]) if lines else None), proc

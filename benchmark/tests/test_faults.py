"""``correct`` comes out false for the control and for each fault a
warm-series cell can have, planted under the timed path, while the rest of
a run (world, window, reference, comparison) runs as on the chip.

The control is the program's own lower-precision path, RTM storage in
bfloat16 (``calibrate.CONTROL_FLAGS``); the stop rule's control is a
convergence test ten times looser than the configuration states. The
faults: a solve that returns its state unchanged, an answer altered where
it is produced, and (on the four-device cell) the exchange between chips
left out. "Half of the batch
left out" has no place here: every solve is a chain of single frames."""

import pytest

from conftest import run_harness

STUCK = """
import sartsolver_tpu.models.sart as S
_orig = S._solve_normalized_batch_impl
def _stuck(problem, g, msq, f0, **kw):
    res, fit = _orig(problem, g, msq, f0, **kw)
    if kw.get("use_guess"):
        return res, fit
    return res._replace(solution=f0.astype(res.solution.dtype)), fit
S._solve_normalized_batch_impl = _stuck
"""

ALTERED = """
import sartsolver_tpu.models.sart as S
_orig = S._solve_normalized_batch_impl
def _altered(*a, **kw):
    res, fit = _orig(*a, **kw)
    return res._replace(solution=res.solution * 1.001), fit
S._solve_normalized_batch_impl = _altered
"""

NO_EXCHANGE = """
import jax
jax.lax.psum = lambda x, axis_name, **kw: x
"""


@pytest.mark.parametrize("cell", ["tiny1.warm-series", "tiny4.warm-series"])
def test_sound_run_is_correct(tiny_root, cell):
    r, proc = run_harness(tiny_root, cell, seed=11)
    assert r is not None, proc.stderr[-3000:]
    assert r["correct"], r["checks"]


@pytest.mark.parametrize("cell", ["tiny1.warm-series", "tiny4.warm-series"])
def test_control_is_not_correct(tiny_root, cell):
    r, proc = run_harness(tiny_root, cell, seed=12, control=True)
    assert r is not None, proc.stderr[-3000:]
    assert not r["correct"], r["checks"]
    c = r["checks"]
    assert c["warm_gap"]["value"] > c["warm_gap"]["limit"]


@pytest.mark.parametrize("cell,fault", [
    ("tiny1.warm-series", STUCK), ("tiny1.warm-series", ALTERED),
    ("tiny4.warm-series", STUCK), ("tiny4.warm-series", ALTERED),
    ("tiny4.warm-series", NO_EXCHANGE),
], ids=["1-stuck", "1-altered", "4-stuck", "4-altered", "4-no-exchange"])
def test_planted_fault_is_not_correct(tiny_root, cell, fault):
    r, proc = run_harness(tiny_root, cell, seed=13, prelude=fault)
    assert r is not None, proc.stderr[-3000:]
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell", ["tiny1.warm-series", "tiny4.warm-series"])
def test_loose_stop_is_not_correct(tiny_root, cell):
    r, proc = run_harness(tiny_root, cell, seed=14, flags=("-c", "1e-4"))
    assert r is not None, proc.stderr[-3000:]
    assert not r["correct"], r["checks"]
    c = r["checks"]["stop_rule"]
    assert c["value"] > c["limit"]


def test_stop_reading():
    from benchmark.check import stop_reading

    tol = 1e-5
    conv = [0.5, 0.1, 0.05, 0.05 - 5e-5, 0.05 - 5.5e-5]
    assert stop_reading(conv, 5, 0, tol) == pytest.approx(0.5)  # stops here
    assert stop_reading(conv, 4, 0, tol) == pytest.approx(5.0)  # too early
    assert stop_reading(conv + [0.05 - 5.6e-5], 6, 0, tol) == (
        pytest.approx(2.0))  # too late: it would have stopped at 5
    assert stop_reading(conv[:4], 4, -1, tol) == pytest.approx(0.2)  # cap

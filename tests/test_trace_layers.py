"""One clock for host and device (docs/OBSERVABILITY.md §4).

While ``jax.profiler`` captures, ``obs.trace.span`` is also a
``TraceAnnotation`` on the profiler's host plane; the warm-chain path's
spans carry the solver's chain serial; and the solve programs name their
device work by layer with the ``sart.laplacian`` / ``sart.sweep`` scopes.
"""

import glob
import re
import warnings

import numpy as np
import pytest

import jax

from sartsolver_tpu.config import SolverOptions
from sartsolver_tpu.models.sart import SCOPE_LAPLACIAN, SCOPE_SWEEP
from sartsolver_tpu.obs import trace
from sartsolver_tpu.ops.laplacian import make_laplacian
from sartsolver_tpu.parallel.mesh import make_mesh
from sartsolver_tpu.parallel.sharded import DistributedSARTSolver

from test_sart_core import laplacian_1d_chain, make_case

# names the benchmark harness gives its own host spans and its traced
# window (benchmark/profile_trace.py): no program span may take one
HARNESS_SPANS = {"frames.stage", "solve.dispatch", "solution.fetch",
                 "bench.traced"}


def _host_events(log_dir):
    """[(name, {stat: value})] of every host-plane event of the one
    ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    with warnings.catch_warnings():  # jaxlib's event_stats type
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        out.append((e.name, dict(e.stats)))
    return out


def _solver(V=64, mesh=(1, 1), **opts):
    H, g, _ = make_case(seed=17, P=64, V=V)
    lap = make_laplacian(*laplacian_1d_chain(V, 0.05), dtype="float32")
    solver = DistributedSARTSolver(
        H, lap, mesh=make_mesh(*mesh),
        opts=SolverOptions(max_iterations=5, conv_tolerance=1e-10, **opts))
    return solver, np.stack([g, g * 1.1])


def test_span_is_null_without_buffer_or_profiler():
    assert trace.active_buffer() is None
    assert trace.span("anything", key=1) is trace._NULL_SPAN


def test_span_reaches_profiler_and_buffer(tmp_path):
    buf = trace.install(trace.TraceBuffer())
    try:
        with jax.profiler.trace(str(tmp_path)):
            with trace.span("unit.work", frame=3):
                pass
    finally:
        trace.uninstall()
    names = [e["name"] for e in buf.to_chrome()["traceEvents"]]
    assert "unit.work" in names
    events = dict(_host_events(tmp_path))
    assert events["unit.work"]["frame"] == 3


def test_chain_spans_share_a_serial_on_the_host_plane(tmp_path):
    solver, frames = _solver()
    warm = solver.solve_chain(frames)  # compiles outside the trace
    warm.fetch_solutions()
    with jax.profiler.trace(str(tmp_path)):
        res = solver.solve_chain(frames, warm=warm)
        res.status
        res.fetch_solutions()
    events = _host_events(tmp_path)
    assert not {n for n, _ in events} & HARNESS_SPANS
    dispatch = [s for n, s in events if n == "chain.dispatch"]
    assert dispatch == [{"frames": 2, "warm": 1, "chain": 2}]
    fetches = sorted((s["what"], s.get("part", "")) for n, s in events
                     if n == "result.fetch" and s.get("chain") == 2)
    assert fetches == [("denormalize", ""), ("transfer", "scalars"),
                       ("transfer", "solution")]
    assert [s for n, s in events if n == "device.put"] == [{"chain": 2}]
    # the fp64 host solution is the device's fp32 times the frame norms
    np.testing.assert_allclose(
        res.fetch_solutions(),
        np.asarray(res.solution_norm, np.float64)[:, :solver.nvoxel]
        * res.norms[:, None], rtol=0, atol=0)


def _chain_program_text(solver, frames):
    """The compiled text of the cold chain program ``solve_chain`` runs."""
    calls = []
    build = solver._chain_fn

    def spy(*a, **k):
        fn = build(*a, **k)

        def run(*args):
            calls.append((fn, args))
            return fn(*args)

        return run

    solver._chain_fn = spy
    solver.solve_chain(frames).status
    fn, args = calls[0]
    return fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("path,opts", [
    ("two-matmul", {"fused_sweep": "off"}),
    ("pallas-interpret", {"fused_sweep": "interpret"}),
    ("panel-scan", {"fused_sweep": "interpret", "mesh": (4, 1)}),
    ("ordered-subsets", {"fused_sweep": "off", "os_subsets": 2}),
])
def test_chain_program_names_its_layers(path, opts):
    solver, frames = _solver(V=256, **opts)
    names = set(re.findall(r'op_name="([^"]*)"',
                           _chain_program_text(solver, frames)))
    laplacian = {n for n in names if f"/{SCOPE_LAPLACIAN}/" in n}
    sweep = {n for n in names if f"/{SCOPE_SWEEP}/" in n}
    # the chain stencil's triplets take the diagonal form: shifted slices
    # in place of the COO gather and scatter-add (ops/laplacian.py)
    assert solver.problem.laplacian.diag_offsets.shape[-1] == 3, path
    assert any(n.endswith("dynamic_slice") for n in laplacian), path
    # the contraction under the scope: dots (two matmuls, panel scans,
    # the MXU kernel), or the VPU kernel's multiply-adds, which fp32
    # panels at B = 1 take, inside the interpreted kernel's grid loop
    in_loop = {n.rsplit("/", 1)[-1] for n in sweep if "/while/body/" in n}
    assert (any("dot_general" in n or "pallas_call" in n for n in sweep)
            or {"mul", "reduce_sum"} <= in_loop), path

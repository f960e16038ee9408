"""The voxel-major 1x4 mesh the program picks for a dense matrix that no
single chip holds (the benchmark's ``wide4.warm-series``: 48 GiB over one
four-chip host), here at a tiny size on four virtual CPU devices with the
fused kernel interpreted, so that the mesh and the kernel are the chip's.

Each chip holds a column block of the matrix and of the solution; the
loop's forward projection is psummed over the voxel axis and the grid
Laplacian's cross-block triplets read their neighbours' boundary planes
through the halo all_gather. The chains are held to the benchmark's plain
reference (``benchmark/references/sart_linear.py``) and to the same solve
on one device, and every collective carries the scope ``sart.exchange``.
"""

import os
import re

import numpy as np
import pytest

import jax.numpy as jnp

from sartsolver_tpu.config import SolverOptions
from sartsolver_tpu.models.sart import SCOPE_EXCHANGE
from sartsolver_tpu.obs import metrics as obs_metrics
from sartsolver_tpu.ops.laplacian import make_laplacian
from sartsolver_tpu.parallel.mesh import make_mesh
from sartsolver_tpu.parallel.sharded import DistributedSARTSolver

from test_trace_layers import _chain_program_text

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# four voxel blocks of 512 = two 16x16 planes each: the 7-point stencil's
# ±256 offset crosses all three block boundaries
GRID = (8, 16, 16)
P, V = 64, 2048
K = 3  # frames a chain
SOLVER = {"max_iterations": 2000, "conv_tolerance": 1e-5,
          "beta_laplace": 0.02, "relaxation": 1.0,
          "ray_density_threshold": 1e-6, "ray_length_threshold": 1e-6}
# fp32 rounding differs between the program (blocked sums, a psum over
# four partial forward projections, the kernel's own order) and the
# reference: the CPU readings of the benchmark's tiny cells are ~1e-7,
# while bfloat16 storage of the matrix moves a solution by ~2e-4 (PERF.md
# §2 gives the same split at full size), so 1e-5 sits between the two
GAP = 1e-5


def _reference():
    from benchmark import spec

    return spec.load_module(os.path.join(REPO, "benchmark", "references",
                                         "sart_linear.py")).Reference


def _grid_laplacian():
    from benchmark import world

    return make_laplacian(*world.grid_laplacian(GRID), dtype="float32")


@pytest.fixture(scope="module")
def world():
    """A seeded banded random matrix and 2K frames of a drifting blob."""
    rng = np.random.default_rng(20251016)
    i = np.arange(P)[:, None] / P
    j = np.arange(V)[None, :] / V
    H = ((0.9 * rng.uniform(size=(P, V)) + 0.1)
         * (np.exp(-20.0 * (i - j) ** 2) + 0.02)).astype(np.float32)
    z, y, x = np.meshgrid(*(np.arange(n) for n in GRID), indexing="ij")
    frames = []
    for t in range(2 * K):
        blob = np.exp(-((z - 2 - 0.5 * t) ** 2 + (y - 8) ** 2
                        + (x - 4 - t) ** 2) / 8.0)
        f = 0.5 + (1.0 + 0.1 * t) * blob.ravel()
        frames.append(H.astype(np.float64) @ f)
    return H, np.stack(frames)


def _solver(H, mesh, **opts):
    return DistributedSARTSolver(
        H, _grid_laplacian(), mesh=make_mesh(*mesh),
        opts=SolverOptions(fused_sweep="interpret",
                           beta_laplace=SOLVER["beta_laplace"], **opts))


def _chains(solver, frames):
    """Statuses, iterations and solutions of a cold chain (frame 0 from the
    Eq. 4 guess) and the warm chain carried from its last frame."""
    cold = solver.solve_chain(frames[:K])
    warm = solver.solve_chain(frames[K:], warm=cold)
    return (np.concatenate([cold.status, warm.status]),
            np.concatenate([cold.iterations, warm.iterations]),
            np.concatenate([cold.fetch_solutions(), warm.fetch_solutions()]))


@pytest.fixture(scope="module")
def stated(world):
    """Both meshes at the stated tolerance, stopped by the program's test."""
    H, frames = world
    return {mesh: _chains(_solver(H, mesh), frames)
            for mesh in ((1, 4), (1, 1))}


def _rel(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("mesh", [(1, 4), (1, 1)], ids=["1x4", "1x1"])
def test_chains_match_the_plain_reference(world, stated, mesh):
    """Each frame replayed by the reference from the start the program had
    (the Eq. 4 guess, else the previous frame's delivered solution), for
    the program's own iteration count."""
    H, frames = world
    status, iters, sols = stated[mesh]
    assert set(status.tolist()) == {0}
    ref = _reference()(jnp.asarray(H), {"npixel": P, "nvoxel": V,
                                        "grid": list(GRID)}, SOLVER)
    for t in range(2 * K):
        start = None if t == 0 else sols[t - 1]
        f_ref, _ = ref.replay(frames[t], start, int(iters[t]))
        assert _rel(sols[t], f_ref) < GAP, (mesh, t)


def test_voxel_shards_match_one_device(world):
    """At a fixed iteration count (a zero tolerance stops no frame), the 1x4
    solve is the 1x1 solve up to fp32 summation order."""
    H, frames = world
    fixed = {"max_iterations": 25, "conv_tolerance": 0.0}
    s4, i4, f4 = _chains(_solver(H, (1, 4), **fixed), frames)
    s1, i1, f1 = _chains(_solver(H, (1, 1), **fixed), frames)
    assert (i4 == 25).all() and (i1 == 25).all()
    assert (s4 == s1).all()
    for t in range(2 * K):
        assert _rel(f4[t], f1[t]) < GAP, t


_COLLECTIVE = re.compile(
    r"= \S+ (all-reduce|all-gather)(?:-start)?\(.*?op_name=\"([^\"]*)\"")


@pytest.mark.parametrize("mesh,kinds", [
    ((1, 4), {"all-reduce", "all-gather"}),
    # pixel-sharded: the panel scan's per-panel back-projection psums
    # (ops/fused_sweep.py), one voxel shard and so no halo
    ((4, 1), {"all-reduce"}),
    ((1, 1), set()),
], ids=["1x4", "4x1", "1x1"])
def test_collectives_carry_the_exchange_scope(world, mesh, kinds):
    H, frames = world
    text = _chain_program_text(_solver(H, mesh), frames[:K])
    ops = _COLLECTIVE.findall(text)
    assert {kind for kind, _ in ops} == kinds
    for kind, name in ops:
        assert f"/{SCOPE_EXCHANGE}/" in name, (kind, name)
    # the scope holds the collectives alone (and a psum's reduction): the
    # halo's export table is packed by a local gather outside it
    scoped = re.findall(rf'op_name="[^"]*{re.escape(SCOPE_EXCHANGE)}/(\w+)',
                        text)
    assert set(scoped) <= {"psum", "all_gather", "add"}, set(scoped)
    if not kinds:
        assert SCOPE_EXCHANGE not in text


@pytest.mark.parametrize("mesh,want", [((1, 4), 512), ((1, 1), 0)],
                         ids=["1x4", "1x1"])
def test_halo_triplet_gauge(world, mesh, want):
    """The most cross-block triplets a shard holds: a middle shard reads
    one 16x16 plane from each neighbour; one device holds none."""
    _solver(world[0], mesh)
    gauge = obs_metrics.get_registry().gauge("laplacian_halo_triplets")
    assert gauge.value == want

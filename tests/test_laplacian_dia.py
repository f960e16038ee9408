"""The Laplacian's diagonal form (ops/laplacian.py).

A grid stencil indexed row-major lies on a few diagonals; the solver's
penalty then applies it as shifted slices instead of a COO gather and
scatter-add. Checked here against the dense product and the COO path, on
one voxel shard and on four (local diagonals plus the halo's COO), and
through the distributed solver against the single-device COO solve."""

import math

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from sartsolver_tpu.config import SolverOptions
from sartsolver_tpu.models.sart import make_problem, solve
from sartsolver_tpu.ops.laplacian import (
    ShardedLaplacian, coo_matvec, diagonal_share, make_laplacian,
    shard_laplacian_halo, sharded_penalty,
)
from sartsolver_tpu.parallel.mesh import make_mesh
from sartsolver_tpu.parallel.sharded import DistributedSARTSolver

from test_sart_core import make_case


def grid_laplacian(grid):
    """Graph Laplacian of the grid's nearest neighbours (row-major voxel
    index): the degree on the diagonal, -1 to each neighbour."""
    nv = math.prod(grid)
    idx = np.arange(nv).reshape(grid)
    rows, cols = [], []
    for ax, n in enumerate(grid):
        a = idx.take(np.arange(1, n), axis=ax).ravel()
        b = idx.take(np.arange(n - 1), axis=ax).ravel()
        rows += [a, b]
        cols += [b, a]
    off_r, off_c = np.concatenate(rows), np.concatenate(cols)
    deg = np.bincount(off_r, minlength=nv).astype(np.float64)
    return (np.r_[np.arange(nv), off_r], np.r_[np.arange(nv), off_c],
            np.r_[deg, -np.ones(off_r.size)])


def random_coupling(V, nnz=300, seed=3):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, V, nnz)
    cols = np.clip(rows + rng.integers(-40, 41, nnz), 0, V - 1)
    return rows, cols, rng.standard_normal(nnz)


def dense(rows, cols, vals, V):
    L = np.zeros((V, V))
    np.add.at(L, (rows, cols), vals)
    return L


def run_penalty(slap, x, S):
    """``sharded_penalty`` on a 1xS mesh, each shard on its own block."""
    spec = ShardedLaplacian(*(P("voxels", None),) * len(ShardedLaplacian._fields))
    return np.asarray(jax.jit(jax.shard_map(
        lambda sl, xb: sharded_penalty(
            ShardedLaplacian(*(a[0] for a in sl)), xb, "voxels"),
        mesh=make_mesh(1, S), in_specs=(spec, P(None, "voxels")),
        out_specs=P(None, "voxels"), check_vma=False,
    ))(slap, x))


def padded_block(V, S):
    """Per-shard block, padded past V/S as the solver pads it."""
    return -(-V // S // 16) * 16 + 16


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("batch", [1, 3])
@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("grid", [(8, 12), (4, 6, 8)])
def test_diagonal_penalty_matches_dense(grid, shards, batch, dtype):
    rows, cols, vals = grid_laplacian(grid)
    V = math.prod(grid)
    block = padded_block(V, shards)
    lap = make_laplacian(rows, cols, vals, dtype=dtype)
    slap = shard_laplacian_halo(lap, shards, block, dtype)
    assert slap.diag_offsets.shape[-1] > 0
    assert slap.rows_loc.shape[-1] == 0  # the local COO is not kept
    if shards > 1:
        assert slap.rows_halo.shape[-1] > 0  # the halo stays COO
    x = np.zeros((batch, shards * block), dtype)
    x[:, :V] = np.random.default_rng(1).standard_normal((batch, V))
    want = x[:, :V] @ dense(rows, cols, vals, V).T
    got = run_penalty(slap, x, shards)
    tol = 1e-5 if dtype == np.float32 else 1e-12
    np.testing.assert_allclose(got[:, :V], want, rtol=tol, atol=tol)
    assert not got[:, V:].any()


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_duplicates_and_padding_sum_like_scatter_add(dtype):
    """Each neighbour triplet given twice at half weight, and ``pad_to``'s
    inert ``(0, 0, 0.0)`` triplets: the diagonals sum them as the COO
    scatter-add does."""
    rows, cols, vals = grid_laplacian((4, 6, 8))
    V = rows.max() + 1
    off = rows != cols
    rows = np.r_[rows, rows[off]]
    cols = np.r_[cols, cols[off]]
    vals = np.r_[np.where(off, vals / 2, vals), vals[off] / 2]
    lap = make_laplacian(rows, cols, vals, dtype=dtype, pad_to=rows.size + 37)
    slap = shard_laplacian_halo(lap, 1, V, dtype)
    assert slap.diag_offsets.shape[-1] == 7
    x = np.random.default_rng(2).standard_normal((2, V)).astype(dtype)
    got = run_penalty(slap, x, 1)
    scatter = np.stack([np.asarray(coo_matvec(lap, xb, V)) for xb in x])
    tol = 1e-5 if dtype == np.float32 else 1e-13
    np.testing.assert_allclose(got, scatter, rtol=tol, atol=tol)
    np.testing.assert_allclose(
        got, x @ dense(rows, cols, vals, V).T, rtol=tol, atol=tol)


def test_log_zero_neighbours_match_scatter_add():
    """``x = log f`` is ``-inf`` where ``f`` is 0: a row coupled to such a
    voxel reads what the scatter-add reads, and a row that is not, stays
    finite (a zero coefficient adds nothing)."""
    rows, cols, vals = grid_laplacian((4, 6, 8))
    V = rows.max() + 1
    lap = make_laplacian(rows, cols, vals, dtype=np.float64)
    x = np.random.default_rng(4).standard_normal((1, V + 64))
    x[0, [0, 13, V - 1]] = -np.inf
    x[0, V:] = -np.inf  # padding past the grid, coupled to nothing
    got = run_penalty(shard_laplacian_halo(lap, 1, V + 64, np.float64), x, 1)
    want = np.asarray(coo_matvec(lap, x[0, :V], V))
    np.testing.assert_array_equal(np.isfinite(got[0, :V]), np.isfinite(want))
    fin = np.isfinite(want)
    np.testing.assert_allclose(got[0, :V][fin], want[fin], rtol=1e-12)


def _boundary_case(n_main):
    """``n_main`` triplets on the main diagonal and one each on ±1, in a
    12-voxel block: D = 3 offsets take 3 * 12 * 4 = 144 bytes in float32,
    the triplets (n_main + 2) * 12."""
    r = np.r_[np.arange(n_main), 1, 0]
    c = np.r_[np.arange(n_main), 0, 1]
    return make_laplacian(r, c, np.ones(r.size), dtype="float32")


@pytest.mark.parametrize("case, diagonal", [
    ("random", False),
    ("boundary_equal", True),   # 12 triplets: 144 <= 144 bytes
    ("boundary_below", False),  # 11 triplets: 144 > 132 bytes
])
def test_byte_criterion_picks_each_form(case, diagonal):
    if case == "random":
        S, block = 4, 32
        lap = make_laplacian(*random_coupling(S * block), dtype="float64")
        slap = shard_laplacian_halo(lap, S, block, np.float64)
    else:
        block = 12
        lap = _boundary_case(10 if case == "boundary_equal" else 9)
        slap = shard_laplacian_halo(lap, 1, block, np.float32)
    assert (slap.diag_offsets.shape[-1] > 0) == diagonal
    assert (slap.rows_loc.shape[-1] == 0) == diagonal
    if not diagonal:
        assert slap.diag_vals.shape[1] == 0
        assert diagonal_share(lap, slap, block) == 0.0
    else:
        assert slap.diag_offsets[0].tolist() == [-1, 0, 1]
        assert diagonal_share(lap, slap, block) == 1.0


def _gauge_value():
    from sartsolver_tpu.obs import metrics as obs_metrics

    return obs_metrics.get_registry().gauge("laplacian_diagonal_share").value


@pytest.mark.parametrize("case", ["grid", "random", "grid_halo"])
def test_diagonal_share_gauge(case):
    """The gauge the solver sets at construction: 1.0 on one voxel shard,
    0.0 where COO is kept, the local share where a halo remains."""
    grid = (4, 6, 8)
    V = math.prod(grid)
    rows, cols, vals = (random_coupling(V) if case == "random"
                        else grid_laplacian(grid))
    H = np.random.default_rng(5).random((16, V)).astype(np.float32)
    mesh = make_mesh(1, 4) if case == "grid_halo" else make_mesh(1, 1)
    s = DistributedSARTSolver(
        H, make_laplacian(rows, cols, vals, dtype="float32"),
        opts=SolverOptions(max_iterations=2), mesh=mesh)
    want = {"grid": 1.0, "random": 0.0,
            "grid_halo": np.mean(rows // s.voxel_block
                                 == cols // s.voxel_block)}[case]
    if case == "grid_halo":
        assert 0.5 < want < 1.0
    assert _gauge_value() == pytest.approx(want)


@pytest.mark.parametrize("mesh_shape", [(8, 1), (2, 4)])
@pytest.mark.parametrize("logarithmic", [False, True])
def test_solver_diagonal_form_equals_single_device(logarithmic, mesh_shape):
    """The distributed solver's diagonal penalty (with the halo's COO on a
    voxel-sharded mesh) against the single-device COO solve."""
    grid = (4, 6, 8)
    H, g, _ = make_case(seed=11, P=52, V=math.prod(grid))
    opts = SolverOptions.cpu_parity(
        logarithmic=logarithmic, max_iterations=25, conv_tolerance=1e-12,
        beta_laplace=0.05)
    lap = make_laplacian(*grid_laplacian(grid), dtype="float64")
    res_single = solve(make_problem(H, lap, opts=opts), g, opts=opts)
    solver = DistributedSARTSolver(H, lap, opts=opts,
                                   mesh=make_mesh(*mesh_shape))
    assert solver.problem.laplacian.diag_offsets.shape[-1] > 0
    res = solver.solve(g)
    np.testing.assert_allclose(res.solution, np.asarray(res_single.solution),
                               rtol=1e-9, atol=1e-12)
    assert res.status == int(res_single.status)
    assert res.iterations == int(res_single.iterations)

"""Sharded-vs-single-device equivalence on a virtual 8-device CPU mesh.

The reference's math guarantees mpirun -np 1 == -np N but never asserts it;
here it is asserted (SURVEY §4.3)."""

import numpy as np
import pytest

import jax

from sartsolver_tpu.config import SolverOptions
from sartsolver_tpu.models.sart import make_problem, solve
from sartsolver_tpu.ops.laplacian import make_laplacian
from sartsolver_tpu.parallel.mesh import make_mesh, row_block_partition
from sartsolver_tpu.parallel.sharded import DistributedSARTSolver

from test_sart_core import laplacian_1d_chain, make_case


def test_halo_laplacian_partition_matches_dense():
    """shard_laplacian_halo + sharded_penalty == dense L @ x, shard by
    shard, on a random sparse L with cross-block couplings; and the export
    table stays boundary-sized (the whole point vs a full gather)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from sartsolver_tpu.ops.laplacian import (
        make_laplacian, shard_laplacian_halo, sharded_penalty,
    )

    rng = np.random.default_rng(3)
    S, vb = 4, 32
    V = S * vb
    nnz = 300
    rows = rng.integers(0, V, nnz)
    # mostly-local couplings plus some genuine cross-block ones
    cols = np.clip(rows + rng.integers(-40, 41, nnz), 0, V - 1)
    vals = rng.standard_normal(nnz)
    lap = make_laplacian(rows, cols, vals, dtype="float64")
    slap = shard_laplacian_halo(lap, S, vb, np.float64)

    L = np.zeros((V, V))
    np.add.at(L, (rows, cols), vals)
    x = rng.standard_normal((2, V))
    want = x @ L.T  # [B, V]

    mesh = make_mesh(1, S)

    got = jax.jit(jax.shard_map(
        lambda sl, xb: sharded_penalty(
            type(slap)(*(a[0] for a in sl)), xb, "voxels"
        ),
        mesh=mesh,
        in_specs=(type(slap)(*(P("voxels", None),) * len(type(slap)._fields)),
                  P(None, "voxels")),
        out_specs=P(None, "voxels"),
        check_vma=False,
    ))(slap, x)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-12, atol=1e-12)
    # export table is the set of boundary cols, far smaller than a block
    assert 0 < slap.export_idx.shape[1] < vb


def test_halo_laplacian_block_diagonal_needs_no_exchange():
    """A block-diagonal L (all couplings within one shard's block) must
    partition with an empty halo — sharded_penalty then issues no
    collective at all."""
    from sartsolver_tpu.ops.laplacian import make_laplacian, shard_laplacian_halo

    S, vb = 4, 16
    rows = np.arange(S * vb)
    lap = make_laplacian(rows, rows, np.ones(S * vb), dtype="float32")
    slap = shard_laplacian_halo(lap, S, vb, np.float32)
    assert slap.rows_halo.shape[1] == 0
    assert slap.export_idx.shape[1] == 0


def test_row_block_partition_matches_reference_formula():
    """main.cpp:67-68: offset = r*(n/P) + min(r, n%P); count = n/P (+1)."""
    for npixel, nshards in [(100, 8), (17, 4), (8, 8), (7, 3)]:
        parts = row_block_partition(npixel, nshards)
        assert sum(c for _, c in parts) == npixel
        for rank, (off, cnt) in enumerate(parts):
            base, rem = divmod(npixel, nshards)
            assert off == rank * base + min(rank, rem)
            assert cnt == base + (1 if rank < rem else 0)
        # contiguous
        for (o1, c1), (o2, _) in zip(parts, parts[1:]):
            assert o1 + c1 == o2


@pytest.mark.parametrize("logarithmic", [False, True])
@pytest.mark.parametrize("with_laplacian", [False, True])
def test_sharded_equals_single_device(logarithmic, with_laplacian):
    H, g, _ = make_case(seed=11, P=52, V=40)  # 52 % 8 != 0 => padding path
    lap_np = laplacian_1d_chain(H.shape[1], 0.1) if with_laplacian else None
    opts = SolverOptions.cpu_parity(
        logarithmic=logarithmic, max_iterations=25, conv_tolerance=1e-12
    )
    lap = make_laplacian(*lap_np, dtype="float64") if lap_np else None

    res_single = solve(make_problem(H, lap, opts=opts), g, opts=opts)

    solver = DistributedSARTSolver(H, lap, opts=opts, mesh=make_mesh(8))
    res_shard = solver.solve(g)

    np.testing.assert_allclose(
        res_shard.solution, np.asarray(res_single.solution), rtol=1e-9, atol=1e-12
    )
    assert res_shard.status == int(res_single.status)
    assert res_shard.iterations == int(res_single.iterations)


def test_sharded_warm_start():
    H, g, _ = make_case(seed=12, P=48, V=32)
    opts = SolverOptions.cpu_parity(max_iterations=15, conv_tolerance=1e-12)
    f0 = np.full(H.shape[1], 0.5)
    res_single = solve(make_problem(H, opts=opts), g, f0=f0, opts=opts)
    solver = DistributedSARTSolver(H, opts=opts, mesh=make_mesh(8))
    res_shard = solver.solve(g, f0=f0)
    np.testing.assert_allclose(
        res_shard.solution, np.asarray(res_single.solution), rtol=1e-9
    )


def test_sharded_fp32_profile():
    """Device-default (fp32 + normalization) profile under sharding."""
    H, g, _ = make_case(seed=13, P=52, V=40)
    opts = SolverOptions(max_iterations=10, conv_tolerance=1e-12)
    res_single = solve(make_problem(H, opts=opts), g, opts=opts)
    solver = DistributedSARTSolver(H, opts=opts, mesh=make_mesh(8))
    res_shard = solver.solve(g)
    np.testing.assert_allclose(
        res_shard.solution, np.asarray(res_single.solution), rtol=2e-4, atol=1e-5
    )


def test_sharded_multiple_frames_warm_chain():
    """Frame loop with warm start (main.cpp:131-140) under sharding."""
    H, g, _ = make_case(seed=14, P=48, V=32)
    opts = SolverOptions.cpu_parity(max_iterations=10, conv_tolerance=1e-12)
    solver = DistributedSARTSolver(H, opts=opts, mesh=make_mesh(8))
    f = None
    for scale in (1.0, 1.1, 0.9):
        res = solver.solve(g * scale, f0=f)
        f = res.solution
        assert np.isfinite(f).all()


def _chain_device_vs_host(H, g, opts, scales, host_mesh, dev_mesh, *,
                          rtol, atol, iteration_parity):
    """Shared harness: warm-chain frames through the host round-trip path
    on ``host_mesh`` and through DeviceSolveResult chaining on
    ``dev_mesh``; assert statuses (and optionally iteration counts) match
    and solutions agree to tolerance. Returns the final device result."""
    from sartsolver_tpu.parallel.mesh import VOXEL_AXIS

    host_solver = DistributedSARTSolver(H, opts=opts, mesh=host_mesh)
    f = None
    refs = []
    for s in scales:
        res = host_solver.solve(g * s, f0=f)
        f = res.solution
        refs.append(res)

    dev_solver = DistributedSARTSolver(H, opts=opts, mesh=dev_mesh)
    warm = None
    for s, ref in zip(scales, refs):
        dres = dev_solver.solve_batch(
            (g * s)[None, :], device_result=True, warm=warm)
        assert int(dres.status[0]) == ref.status
        if iteration_parity:
            assert int(dres.iterations[0]) == ref.iterations
        # the chained carry must stay sharded over the device mesh's axes
        # (a regression gathering it to one device would still pass the
        # numeric checks)
        spec = dres.solution_norm.sharding.spec
        if dev_mesh.shape[VOXEL_AXIS] > 1:
            assert VOXEL_AXIS in jax.tree.leaves(tuple(spec))
        np.testing.assert_allclose(
            dres.fetch_solutions()[0], ref.solution, rtol=rtol, atol=atol)
        warm = dres
    return dev_solver, warm


def test_device_result_chain_matches_host_chain():
    """Device-resident warm chaining (DeviceSolveResult + warm=) must
    reproduce the host round-trip chain: same statuses/iterations, same
    solutions up to the one-fp32-ulp rescale difference in the initial
    guess. Also pins the packed scalar fetch and the lazy fetcher."""
    H, g, _ = make_case(seed=15, P=48, V=32)
    opts = SolverOptions(max_iterations=12, conv_tolerance=1e-12)
    # atol covers the guess-floor contract split: the host round-trip path
    # floors its seed at guess_floor (1e-7) while the carried device path
    # enters unfloored (models/sart fitted0 docs) — near-zero voxels then
    # differ by up to ~guess_floor-scale absolutely after a few iterations
    dev_solver, last = _chain_device_vs_host(
        H, g, opts, (1.0, 1.3, 0.8), make_mesh(8), make_mesh(8),
        rtol=2e-5, atol=1e-5, iteration_parity=True)
    # cached: second fetch returns the same host array
    assert last.fetch_solutions() is last.fetch_solutions()
    with pytest.raises(ValueError, match="not both"):
        dev_solver.solve_batch(g[None, :], f0=np.ones((1, H.shape[1])),
                               device_result=True, warm=last)


def test_device_result_chain_voxel_major_mesh():
    """Device chaining on a voxel-major (1, 8) mesh: the chained solution
    and the on-device rescale stay voxel-sharded across frames (asserted
    on the carry's sharding spec) and match the host-chained pixel-major
    reference."""
    H, g, _ = make_case(seed=16, P=48, V=256)
    opts = SolverOptions(max_iterations=10, conv_tolerance=1e-12)
    _chain_device_vs_host(
        H, g, opts, (1.0, 1.2), make_mesh(8, 1), make_mesh(1, 8),
        # psum reduction-order differences across mesh layouts perturb the
        # fp32 near-stall test: compare solutions loosely, not iterations
        rtol=2e-4, atol=1e-5, iteration_parity=False)


@pytest.mark.parametrize("mesh_shape", [(8, 1), (1, 8)])
@pytest.mark.parametrize("with_lap", [False, True])
@pytest.mark.parametrize("seed_mode", ["guess", "host_f0", "warm"])
def test_solve_chain_matches_per_frame_solves(mesh_shape, with_lap, seed_mode):
    """solve_chain (scan-over-frames, one device program) must reproduce
    the per-frame warm-start loop EXACTLY: same statuses, same iteration
    counts, same solutions — the chain is the same math dispatched once
    (VERDICT r2 next #1)."""
    H, g, _ = make_case(seed=17, P=48, V=64)
    lap = (make_laplacian(*laplacian_1d_chain(H.shape[1], 0.05),
                          dtype="float32") if with_lap else None)
    opts = SolverOptions(max_iterations=15, conv_tolerance=1e-10)
    solver = DistributedSARTSolver(H, lap, opts=opts, mesh=make_mesh(*mesh_shape))
    frames = np.stack([g, g * 1.2, g * 0.7, g * 1.05])

    f0_host = np.full(H.shape[1], 0.5) if seed_mode == "host_f0" else None
    warm0 = (solver.solve_chain(frames[:1] * 0.9)
             if seed_mode == "warm" else None)

    # reference: the per-frame device_result warm chain
    refs = []
    warm = warm0
    f0 = f0_host
    for k in range(frames.shape[0]):
        dres = solver.solve_batch(frames[k][None],
                                  None if f0 is None else f0[None],
                                  device_result=True, warm=warm)
        f0 = None
        warm = dres
        refs.append(dres)

    chained = solver.solve_chain(frames, f0=f0_host, warm=warm0)
    assert chained.status.shape == (4,)
    for k, ref in enumerate(refs):
        assert int(chained.status[k]) == int(ref.status[0]), k
        assert int(chained.iterations[k]) == int(ref.iterations[0]), k
        np.testing.assert_allclose(
            chained.fetch_solutions()[k], ref.fetch_solutions()[0],
            rtol=2e-6, atol=1e-8, err_msg=f"frame {k}",
        )

    # chain-to-chain warm handoff == one long chain
    two = solver.solve_chain(frames[2:], warm=solver.solve_chain(frames[:2],
                                                                 f0=f0_host,
                                                                 warm=warm0))
    for k in (2, 3):
        assert int(two.status[k - 2]) == int(chained.status[k])
        np.testing.assert_allclose(
            two.fetch_solutions()[k - 2], chained.fetch_solutions()[k],
            rtol=2e-6, atol=1e-8,
        )


def test_solve_chain_single_frame_and_errors():
    H, g, _ = make_case(seed=18, P=24, V=32)
    opts = SolverOptions(max_iterations=8, conv_tolerance=1e-10)
    solver = DistributedSARTSolver(H, opts=opts, mesh=make_mesh(8))
    one = solver.solve_chain(g[None])
    ref = solver.solve_batch(g[None], device_result=True)
    assert int(one.status[0]) == int(ref.status[0])
    assert int(one.iterations[0]) == int(ref.iterations[0])
    np.testing.assert_allclose(one.fetch_solutions()[0],
                               ref.fetch_solutions()[0], rtol=1e-7)
    with pytest.raises(ValueError, match="not both"):
        solver.solve_chain(g[None], f0=np.ones(H.shape[1]), warm=one)


@pytest.mark.parametrize("mesh_shape", [(4, 2), (2, 4), (1, 8)])
@pytest.mark.parametrize("logarithmic", [False, True])
def test_2d_mesh_equals_single_device(mesh_shape, logarithmic):
    """Column (voxel-axis) sharding: 2-D mesh result == single device.

    The voxel dimension deliberately doesn't divide the shard count in one
    case (40 voxels over 4x2 -> padding path on both axes)."""
    H, g, _ = make_case(seed=15, P=52, V=40)
    lap_np = laplacian_1d_chain(H.shape[1], 0.1)
    opts = SolverOptions.cpu_parity(
        logarithmic=logarithmic, max_iterations=20, conv_tolerance=1e-12
    )
    lap = make_laplacian(*lap_np, dtype="float64")

    res_single = solve(make_problem(H, lap, opts=opts), g, opts=opts)
    solver = DistributedSARTSolver(H, lap, opts=opts, mesh=make_mesh(*mesh_shape))
    res_shard = solver.solve(g)

    np.testing.assert_allclose(
        res_shard.solution, np.asarray(res_single.solution), rtol=1e-9, atol=1e-12
    )
    assert res_shard.status == int(res_single.status)
    assert res_shard.iterations == int(res_single.iterations)


def test_2d_mesh_warm_start_chain():
    H, g, _ = make_case(seed=16, P=48, V=32)
    opts = SolverOptions.cpu_parity(max_iterations=10, conv_tolerance=1e-12)
    solver_1d = DistributedSARTSolver(H, opts=opts, mesh=make_mesh(8, 1))
    solver_2d = DistributedSARTSolver(H, opts=opts, mesh=make_mesh(2, 4))
    f1 = f2 = None
    for scale in (1.0, 1.2):
        f1 = solver_1d.solve(g * scale, f0=f1).solution
        f2 = solver_2d.solve(g * scale, f0=f2).solution
        np.testing.assert_allclose(f2, f1, rtol=1e-9)


def test_choose_mesh_shape_heuristic():
    """VERDICT r1 #2: auto mesh goes voxel-major iff the fused sweep would
    engage on the per-device block; otherwise the reference's row-block
    layout."""
    from sartsolver_tpu.parallel.mesh import choose_mesh_shape

    # 'interpret'/'on' engage on any backend => voxel-major when aligned
    assert choose_mesh_shape(8, 800, 4096, SolverOptions(fused_sweep="interpret")) == (1, 8)
    assert choose_mesh_shape(8, 800, 4096, SolverOptions(fused_sweep="on")) == (1, 8)
    # fused off => pixel-major
    assert choose_mesh_shape(8, 800, 4096, SolverOptions(fused_sweep="off")) == (8, 1)
    # fp64 parity profile cannot fuse => pixel-major
    assert choose_mesh_shape(8, 800, 4096, SolverOptions.cpu_parity()) == (8, 1)
    # 'auto' on the CPU test backend never fuses => pixel-major
    assert choose_mesh_shape(8, 800, 4096, SolverOptions(fused_sweep="auto")) == (8, 1)
    # bf16 RTM storage composes with fusion
    assert choose_mesh_shape(
        8, 800, 4096, SolverOptions(fused_sweep="on", rtm_dtype="bfloat16")
    ) == (1, 8)
    # single device: trivial mesh
    assert choose_mesh_shape(1, 800, 4096, SolverOptions(fused_sweep="on")) == (1, 1)


@pytest.mark.parametrize("logarithmic", [False, True])
def test_voxel_major_fused_equals_unfused(logarithmic):
    """Fused sweep + voxel sharding at mesh>1 == unfused single device.

    The flagship multi-chip fusion configuration (VERDICT r1 #2): a (1, 8)
    voxel-major mesh where each shard runs the fused panel sweep over its
    column block and only the forward-projection psum crosses shards."""
    H, g, _ = make_case(seed=17, P=16, V=256, neg_pixels=2, zero_voxels=0,
                        zero_pixels=1)
    lap = make_laplacian(*laplacian_1d_chain(H.shape[1], 0.1), dtype="float32")
    opts_ref = SolverOptions(
        logarithmic=logarithmic, max_iterations=15, conv_tolerance=1e-12,
        fused_sweep="off",
    )
    opts_fused = SolverOptions(
        logarithmic=logarithmic, max_iterations=15, conv_tolerance=1e-12,
        fused_sweep="interpret",
    )
    res_ref = solve(make_problem(H, lap, opts=opts_ref), g, opts=opts_ref)
    solver = DistributedSARTSolver(H, lap, opts=opts_fused, mesh=make_mesh(1, 8))
    res = solver.solve(g)
    np.testing.assert_allclose(
        res.solution, np.asarray(res_ref.solution), rtol=2e-4, atol=1e-5
    )
    assert res.status == int(res_ref.status)
    assert res.iterations == int(res_ref.iterations)


@pytest.mark.parametrize("mesh_shape", [(8, 1), (2, 4)])
@pytest.mark.parametrize("profile", ["parity", "fp32"])
def test_local_measurement_staging_equals_global(mesh_shape, profile):
    """VERDICT r1 #5: per-process measurement staging (sharded g, global
    norm/||g||^2 from scalar reductions) == the replicated staging path."""
    H, g, _ = make_case(seed=18, P=52, V=40)
    if profile == "parity":
        opts = SolverOptions.cpu_parity(max_iterations=15, conv_tolerance=1e-12)
        rtol = 1e-9
    else:
        opts = SolverOptions(max_iterations=15, conv_tolerance=1e-12)
        rtol = 2e-4
    solver = DistributedSARTSolver(H, opts=opts, mesh=make_mesh(*mesh_shape))
    res_global = solver.solve(g)
    rng = solver.local_pixel_range()
    assert rng == (0, H.shape[0])  # single process owns every row block
    res_local = solver.solve(g, local=True)
    np.testing.assert_allclose(res_local.solution, res_global.solution,
                               rtol=rtol, atol=1e-12)
    assert res_local.status == res_global.status
    assert res_local.iterations == res_global.iterations


def test_process_pixel_range_partition():
    """Range arithmetic across simulated processes (device stubs carry the
    process_index a pod would assign)."""
    from sartsolver_tpu.parallel.multihost import process_pixel_range

    from fixtures import FakeMesh

    # this test process is jax.process_index() == 0: it sees the range of
    # the blocks labeled 0
    npixel = 52  # padded to 4 shards * ROW_ALIGN 8 -> 64, row_block 16
    assert process_pixel_range(FakeMesh([0, 0, 1, 1]), npixel) == (0, 32)
    assert process_pixel_range(FakeMesh([1, 0, 0, 1]), npixel) == (16, 32)
    # last block is partly padding: logical range clips at npixel
    assert process_pixel_range(FakeMesh([1, 1, 1, 0]), npixel) == (48, 4)
    # non-contiguous ownership -> None (caller falls back to full frames)
    assert process_pixel_range(FakeMesh([0, 1, 0, 1]), npixel) is None
    # no blocks owned -> empty range
    assert process_pixel_range(FakeMesh([1, 1, 1, 1]), npixel) == (0, 0)


def test_process_pixel_runs_partition():
    """Run-list arithmetic for non-contiguous device layouts (VERDICT r2
    #8): adjacent blocks merge, padding clips, gaps split runs."""
    from sartsolver_tpu.parallel.multihost import process_pixel_runs

    from fixtures import FakeMesh

    npixel = 52  # padded to 4 shards * ROW_ALIGN 8 -> 64, row_block 16
    assert process_pixel_runs(FakeMesh([0, 0, 1, 1]), npixel) == [(0, 32)]
    # interleaved ownership: two runs, nothing read in between
    assert process_pixel_runs(FakeMesh([0, 1, 0, 1]), npixel) == [
        (0, 16), (32, 16),
    ]
    # trailing block partly padding: clipped at npixel
    assert process_pixel_runs(FakeMesh([1, 0, 1, 0]), npixel) == [
        (16, 16), (48, 4),
    ]
    # padding-only ownership: no runs
    assert process_pixel_runs(FakeMesh([1, 1, 1, 0]), 8) == []


def test_all_processes_local_capable():
    """The relaxed slicing gate: non-contiguous layouts now stay local
    (multi-run); only a padding-only process forces replicated staging."""
    from sartsolver_tpu.parallel.multihost import all_processes_local_capable

    from fixtures import FakeMesh

    assert all_processes_local_capable(FakeMesh([0, 0, 1, 1]), 52)
    # non-contiguous ownership is fine now
    assert all_processes_local_capable(FakeMesh([0, 1, 0, 1]), 52)
    # process 1 owns only padding blocks (npixel=8 -> blocks 1..3 empty)
    assert not all_processes_local_capable(FakeMesh([0, 1, 1, 1]), 8)


def test_local_staging_multi_run_equals_full():
    """_stage_measurement_local over a split run list must stage the same
    sharded measurement as full-frame staging — the multi-run buffer
    lookup is what non-contiguous multihost layouts rely on."""
    H, g, _ = make_case(seed=21, P=48, V=32)
    opts = SolverOptions(max_iterations=6, conv_tolerance=1e-10)
    solver = DistributedSARTSolver(H, opts=opts, mesh=make_mesh(8))
    ref = solver.solve_batch(g[None], device_result=True)

    # simulate a non-contiguous layout: same coverage, split into three
    # unmerged runs (process_pixel_runs would merge these; the staging
    # code must not care)
    runs = [(0, 16), (16, 8), (24, 24)]
    solver.local_pixel_runs = lambda: runs
    got = solver.solve_batch(g[None], local=True, device_result=True)
    assert int(got.status[0]) == int(ref.status[0])
    assert int(got.iterations[0]) == int(ref.iterations[0])
    np.testing.assert_allclose(got.fetch_solutions()[0],
                               ref.fetch_solutions()[0], rtol=1e-7)


def test_close_releases_device_memory():
    """close() deletes the staged device arrays immediately, is
    idempotent, works as a context manager, and a closed solver refuses
    further solves with a clear error (VERDICT r3 next #5: a long-lived
    operator process must be able to load a second near-HBM-limit matrix
    into the same process)."""
    H, g, _ = make_case(seed=17, P=48, V=32)
    opts = SolverOptions.cpu_parity(max_iterations=10, conv_tolerance=1e-12)
    solver = DistributedSARTSolver(H, opts=opts, mesh=make_mesh(8))
    res = solver.solve(g)  # fetched to host before close
    arrays = [leaf for leaf in jax.tree_util.tree_leaves(solver.problem)
              if isinstance(leaf, jax.Array)]
    assert arrays
    solver.close()
    assert all(a.is_deleted() for a in arrays)
    solver.close()  # idempotent
    with pytest.raises(ValueError, match="closed"):
        solver.solve(g)
    assert np.isfinite(res.solution).all()  # host result survives

    # context-manager form, and a reload into the same process works
    with DistributedSARTSolver(H, opts=opts, mesh=make_mesh(8)) as s2:
        res2 = s2.solve(g)
    np.testing.assert_allclose(res2.solution, res.solution, rtol=1e-12)
    assert s2.problem is None


def test_dead_warm_buffers_rejected_closed_producer_ok():
    """A warm= seed with DELETED device buffers fails with an actionable
    ValueError, not an opaque dead-buffer XLA error deep inside dispatch
    (VERDICT r4 next #6). A merely CLOSED producer is not an error:
    close() releases the solver's staged problem arrays, not its results'
    buffers, so the still-alive result stays a legitimate foreign-warm
    seed."""
    H, g, _ = make_case(seed=18, P=48, V=32)
    opts = SolverOptions.cpu_parity(max_iterations=5, conv_tolerance=1e-12)
    producer = DistributedSARTSolver(H, opts=opts, mesh=make_mesh(8))
    warm = producer.solve_chain(g[None])
    host_seed = warm.fetch_solutions()
    consumer = DistributedSARTSolver(H, opts=opts, mesh=make_mesh(8))
    producer.close()
    # closed producer, alive buffers: works, and matches the host-f0 path
    res = consumer.solve_chain(g[None] * 1.1, warm=warm)
    ref = consumer.solve_chain(g[None] * 1.1, f0=host_seed[-1])
    np.testing.assert_allclose(
        res.fetch_solutions(), ref.fetch_solutions(), rtol=1e-9, atol=1e-12)
    # deleted device buffers: caught with a clear error on both paths
    warm2 = consumer.solve_chain(g[None])
    _ = warm2.fetch_solutions()  # materialize before deleting the source
    warm2.solution_norm.delete()
    with pytest.raises(ValueError, match="buffers have been deleted"):
        consumer.solve_chain(g[None], warm=warm2)
    with pytest.raises(ValueError, match="buffers have been deleted"):
        consumer.solve_batch(g[None], warm=warm2, device_result=True)
    consumer.close()


def test_foreign_warm_result_recomputes_fitted():
    """A warm result from a DIFFERENT solver (same shapes, different RTM)
    is a legitimate solution seed, but its carried fitted belongs to the
    other matrix — the receiving solver must recompute its setup sweep
    (guarded by `warm._solver is self`), matching the host-f0 path."""
    H_a, g, _ = make_case(seed=40, P=48, V=32)
    H_b = H_a * 1.7 + 0.05  # different matrix, same shape
    opts = SolverOptions(max_iterations=10, conv_tolerance=1e-12)
    solver_a = DistributedSARTSolver(H_a, opts=opts, mesh=make_mesh(8))
    solver_b = DistributedSARTSolver(H_b, opts=opts, mesh=make_mesh(8))

    res_a = solver_a.solve_batch(g[None], device_result=True)
    assert res_a.fitted_norm is not None
    cross = solver_b.solve_batch(g[None] * 1.1, device_result=True,
                                 warm=res_a)
    ref = solver_b.solve_batch(g[None] * 1.1,
                               f0=res_a.fetch_solutions(),
                               device_result=True)
    assert int(cross.status[0]) == int(ref.status[0])
    # both recompute fitted from their (floored) f0 — a foreign warm has
    # fitted0=None, so no floor is skipped; the only difference is the
    # fp64 host round trip vs the device rescale of the seed — solutions
    # agree to fp32 tolerance
    np.testing.assert_allclose(
        cross.fetch_solutions()[0], ref.fetch_solutions()[0],
        rtol=2e-4, atol=1e-5,
    )

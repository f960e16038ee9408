"""Compiled-HLO regression guards, driven by the compile-audit API.

The SART loop's performance envelope is set by exactly two streams of the
RTM per iteration (one with the fused sweep). Round 2 found XLA
materializing a full transposed COPY of the RTM inside the while body —
``solution @ rtm.T`` does not get its transpose folded when the RTM is a
loop parameter — costing ~30x the matmul pair. These tests lower the real
solver and assert no matrix-sized transpose/copy (nor oversized gather or
convert) lives inside the loop, so the pathology cannot silently return
with a refactor or a JAX upgrade.

The HLO parsing and invariant checks that used to be hand-rolled here now
live in ``sartsolver_tpu.analysis`` (hlo.py + audit.py): each test builds
the same lowering as before, declares its invariants as an
:class:`~sartsolver_tpu.analysis.registry.AuditEntry`, and asserts
``check_invariants`` finds nothing — the exact machinery ``sartsolve lint
--self`` runs over the registered hot entry points.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sartsolver_tpu.analysis import hlo
from sartsolver_tpu.analysis.audit import check_invariants
from sartsolver_tpu.analysis.registry import AuditEntry
from sartsolver_tpu.config import SolverOptions
from sartsolver_tpu.models.sart import (
    SARTProblem, compute_ray_stats, solve_normalized_batch,
)
from sartsolver_tpu.ops.laplacian import make_laplacian

P, V = 128, 1024


def _spec(name, **invariants) -> AuditEntry:
    """Ad-hoc audit entry for a test-local lowering (build never called).

    ``allow_f64=True``: the test harness enables x64 process-wide
    (conftest.py), which legitimately routes the precise-convergence
    accumulation through f64; the *registered* entries pin the no-f64
    invariant under the production fp32 profile (audit.py disables x64
    while lowering them)."""
    invariants.setdefault("allow_f64", True)
    return AuditEntry(
        name=name, build=lambda: None, description=name, **invariants
    )


def _chain_laplacian(dtype=np.float32):
    li = np.arange(V)
    return make_laplacian(
        np.r_[li, li[1:]], np.r_[li, li[:-1]],
        np.r_[np.full(V, 2.0), np.full(V - 1, -1.0)].astype(dtype),
    )


@pytest.mark.parametrize("logarithmic", [False, True])
@pytest.mark.parametrize("batch", [1, 8])
def test_no_rtm_copy_inside_iteration_loop(logarithmic, batch):
    rng = np.random.default_rng(0)
    rtm = jnp.asarray(rng.random((P, V), np.float32))
    dens, length = compute_ray_stats(rtm, dtype=jnp.float32)
    prob = SARTProblem(rtm, dens, length, _chain_laplacian())
    opts = SolverOptions(
        max_iterations=4, conv_tolerance=1e-30, fused_sweep="off",
        logarithmic=logarithmic,
    )
    g = jnp.ones((batch, P), jnp.float32)
    msq = jnp.ones(batch, jnp.float32)
    f0 = jnp.zeros((batch, V), jnp.float32)
    fn = jax.jit(functools.partial(
        solve_normalized_batch, opts=opts, axis_name=None, voxel_axis=None,
        use_guess=True,
    ))
    txt = fn.lower(prob, g, msq, f0).compile().as_text()
    violations = check_invariants(txt, _spec(
        "iteration-loop", loop_copy_threshold=P * V,
    ))
    assert not violations, (
        "matrix-sized transpose/copy inside the iteration loop "
        "(each one re-streams the tens-of-GB RTM every iteration):\n"
        + "\n".join(violations)
    )


@pytest.mark.parametrize("mesh_shape", [(8, 1), (1, 8)])
def test_no_rtm_copy_inside_sharded_loop(mesh_shape):
    from jax.sharding import NamedSharding, PartitionSpec as PS

    from sartsolver_tpu.parallel.mesh import make_mesh
    from sartsolver_tpu.parallel.sharded import DistributedSARTSolver

    H = np.random.default_rng(1).random((P, V), np.float32)
    opts = SolverOptions(max_iterations=4, conv_tolerance=1e-30,
                         fused_sweep="off")
    s = DistributedSARTSolver(H, opts=opts, mesh=make_mesh(*mesh_shape))
    g = jax.device_put(
        np.ones((1, s.padded_npixel), np.float32),
        NamedSharding(s.mesh, PS(None, "pixels")),
    )
    f0 = jax.device_put(
        np.zeros((1, s.padded_nvoxel), np.float32),
        NamedSharding(s.mesh, PS(None, "voxels")),
    )
    txt = s._batch_fn(True).lower(
        s.problem, g, jnp.ones(1, jnp.float32), f0
    ).compile().as_text()
    local = (s.padded_npixel // mesh_shape[0]) * (s.padded_nvoxel // mesh_shape[1])
    violations = check_invariants(txt, _spec(
        "sharded-loop", loop_copy_threshold=local,
    ))
    assert not violations, "\n".join(violations)


def test_no_full_solution_gather_inside_voxel_sharded_loop():
    """Voxel sharding exists to shed the replicated-solution footprint; the
    Laplacian penalty must therefore not all_gather [B, V_global] every
    iteration (VERDICT r2 weak #1). The halo partition's boundary table for
    a chain Laplacian is [B, 2*n_shards] — budget the loop at zero
    V_global-sized all-gathers via the audit's sized-op search."""
    from jax.sharding import NamedSharding, PartitionSpec as PS

    from sartsolver_tpu.parallel.mesh import make_mesh
    from sartsolver_tpu.parallel.sharded import DistributedSARTSolver

    H = np.random.default_rng(1).random((P, V), np.float32)
    opts = SolverOptions(max_iterations=4, conv_tolerance=1e-30,
                         fused_sweep="off")
    s = DistributedSARTSolver(
        H, _chain_laplacian(), opts=opts, mesh=make_mesh(1, 8)
    )
    g = jax.device_put(
        np.ones((1, s.padded_npixel), np.float32),
        NamedSharding(s.mesh, PS(None, "pixels")),
    )
    f0 = jax.device_put(
        np.zeros((1, s.padded_nvoxel), np.float32),
        NamedSharding(s.mesh, PS(None, "voxels")),
    )
    txt = s._batch_fn(True).lower(
        s.problem, g, jnp.ones(1, jnp.float32), f0
    ).compile().as_text()
    assert hlo.while_body_names(txt), "no while loop found in HLO"
    bad = hlo.sized_loop_ops(txt, ("all-gather",), s.padded_nvoxel)
    assert not bad, (
        "V_global-sized all-gather inside the voxel-sharded iteration "
        "loop (the halo Laplacian exists to remove this):\n" + "\n".join(bad[:5])
    )


@pytest.mark.parametrize("coupling, diagonal", [
    ("grid", True), ("random", False),
])
def test_grid_laplacian_loop_has_no_gather_or_scatter(coupling, diagonal):
    """A grid stencil's penalty is applied as diagonals: the one-shard
    solver's compiled while body holds no gather and no scatter. A random
    coupling keeps the COO scatter-add, which the same check sees."""
    from jax.sharding import NamedSharding, PartitionSpec as PS

    from sartsolver_tpu.parallel.mesh import make_mesh
    from sartsolver_tpu.parallel.sharded import DistributedSARTSolver

    if coupling == "grid":  # 8 x 8 x 16 = V voxels, 7-point stencil
        idx = np.arange(V).reshape(8, 8, 16)
        pairs = [(idx.take(np.arange(1, n), axis=ax).ravel(),
                  idx.take(np.arange(n - 1), axis=ax).ravel())
                 for ax, n in enumerate(idx.shape)]
        a = np.concatenate([p[0] for p in pairs] + [p[1] for p in pairs])
        b = np.concatenate([p[1] for p in pairs] + [p[0] for p in pairs])
        rows = np.r_[np.arange(V), a]
        cols = np.r_[np.arange(V), b]
        vals = np.r_[np.bincount(a, minlength=V), -np.ones(a.size)]
    else:
        rng = np.random.default_rng(2)
        rows = rng.integers(0, V, 600)
        cols = rng.integers(0, V, 600)
        vals = rng.standard_normal(600)
    H = np.random.default_rng(1).random((P, V), np.float32)
    opts = SolverOptions(max_iterations=4, conv_tolerance=1e-30,
                         fused_sweep="off", beta_laplace=0.01)
    s = DistributedSARTSolver(
        H, make_laplacian(rows, cols, vals.astype(np.float32)), opts=opts,
        mesh=make_mesh(1, 1))
    g = jax.device_put(
        np.ones((1, s.padded_npixel), np.float32),
        NamedSharding(s.mesh, PS(None, "pixels")),
    )
    f0 = jax.device_put(
        np.zeros((1, s.padded_nvoxel), np.float32),
        NamedSharding(s.mesh, PS(None, "voxels")),
    )
    txt = s._batch_fn(True).lower(
        s.problem, g, jnp.ones(1, jnp.float32), f0
    ).compile().as_text()
    assert hlo.while_body_names(txt), "no while loop found in HLO"
    loop_ops = hlo.op_histogram(txt, loop_only=True)
    found = sorted(op for op in loop_ops if op in ("gather", "scatter"))
    assert found == ([] if diagonal else ["gather", "scatter"]), found


def test_no_codes_copy_inside_int8_loop():
    """The int8 loop must stream only the 1-byte codes: no matrix-sized
    transpose/copy (s8 or dequantized f32/bf16) may live inside the while
    body — a dequantized matrix copy would erase the 4x bandwidth win."""
    from sartsolver_tpu.models.sart import make_problem

    opts = SolverOptions(
        max_iterations=4, conv_tolerance=0.0,
        rtm_dtype="int8", fused_sweep="interpret",
    )
    rng = np.random.default_rng(0)
    prob = make_problem(
        rng.random((P, V)).astype(np.float32), None, opts=opts)
    g = jnp.ones((1, P), jnp.float32)
    msq = jnp.ones(1, jnp.float32)
    f0 = jnp.zeros((1, V), jnp.float32)
    fn = jax.jit(functools.partial(
        solve_normalized_batch, opts=opts, axis_name=None, voxel_axis=None,
        use_guess=True,
    ))
    txt = fn.lower(prob, g, msq, f0).compile().as_text()
    violations = check_invariants(txt, _spec(
        "int8-loop", loop_copy_threshold=P * V,
    ))
    assert not violations, (
        "matrix-sized transpose/copy inside the int8 iteration loop:\n"
        + "\n".join(violations)
    )


def test_sweep_has_no_loop_collectives_single_device():
    """The single-device sweep must not compile collectives into the loop
    at all — the budget mechanism the registered entries declare, exercised
    here end to end against a fresh lowering."""
    rng = np.random.default_rng(0)
    rtm = jnp.asarray(rng.random((P, V), np.float32))
    dens, length = compute_ray_stats(rtm, dtype=jnp.float32)
    prob = SARTProblem(rtm, dens, length, None)
    opts = SolverOptions(max_iterations=4, conv_tolerance=1e-30,
                         fused_sweep="off")
    fn = jax.jit(functools.partial(
        solve_normalized_batch, opts=opts, axis_name=None, voxel_axis=None,
        use_guess=True,
    ))
    txt = fn.lower(
        prob, jnp.ones((1, P), jnp.float32), jnp.ones(1, jnp.float32),
        jnp.zeros((1, V), jnp.float32),
    ).compile().as_text()
    violations = check_invariants(txt, _spec(
        "single-device-sweep",
        loop_collective_budget={
            "all-reduce": 0, "all-gather": 0, "all-to-all": 0,
            "collective-permute": 0,
        },
    ))
    assert not violations, "\n".join(violations)

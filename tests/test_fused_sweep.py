"""Fused Pallas sweep (ops/fused_sweep.py) vs the two-matmul reference path.

The fused kernel must be a pure re-scheduling: identical masking, update
rules and convergence behavior as the unfused solver (which itself is
oracle-tested against NumPy fp64 in test_sart_core.py). These tests run the
kernel in Pallas interpreter mode on CPU and assert near-bitwise agreement.
"""

import dataclasses

import numpy as np
import pytest

from sartsolver_tpu.config import SolverOptions
from sartsolver_tpu.ops.fused_sweep import (
    _VPU_MAX_BATCH, fused_available, pick_block_voxels,
)
from sartsolver_tpu.ops.laplacian import make_laplacian


P, V = 24, 256  # tile-aligned: P % 8 == 0, V % 128 == 0


def _case(seed=0, saturated=True):
    rng = np.random.default_rng(seed)
    H = rng.uniform(0.1, 1.0, (P, V)).astype(np.float32)
    H[:, :3] = 0.0  # masked voxels (zero ray density)
    H[3, :] = 0.0  # masked pixel (zero ray length)
    f_true = rng.uniform(0.5, 2.0, V)
    g = H.astype(np.float64) @ f_true
    if saturated:
        g[5] = -1.0  # saturated detector
    return H, g


def _laplacian(seed=1):
    rng = np.random.default_rng(seed)
    rows = np.arange(V)
    cols = (rows + 1) % V
    vals = rng.uniform(-0.2, 0.2, V)
    rows = np.concatenate([rows, np.arange(V)])
    cols = np.concatenate([cols, np.arange(V)])
    vals = np.concatenate([vals, np.full(V, 0.3)])
    return make_laplacian(rows, cols, vals, dtype="float32")


def _solve(H, g, opts, lap=None, batch=None):
    import jax.numpy as jnp

    from sartsolver_tpu.models.sart import (
        make_problem, solve, solve_normalized_batch, prepare_measurement,
    )

    problem = make_problem(H, lap, opts=opts)
    if batch is None:
        return solve(problem, g, opts=opts)
    G = np.stack([g] * batch) * np.linspace(1.0, 1.5, batch)[:, None]
    gs, msqs, norms = [], [], []
    for b in range(batch):
        g64, msq, norm = prepare_measurement(G[b], opts)
        gs.append(g64)
        msqs.append(msq)
        norms.append(norm)
    res = solve_normalized_batch(
        problem,
        jnp.asarray(np.stack(gs), jnp.float32),
        jnp.asarray(msqs, jnp.float32),
        jnp.zeros((batch, H.shape[1]), jnp.float32),
        opts=opts, axis_name=None, voxel_axis=None, use_guess=True,
    )
    return res._replace(
        solution=np.asarray(res.solution) * np.asarray(norms)[:, None]
    )


def _retrace():
    """Drop compiled solvers, so the next solve traces the kernel anew
    (the contraction is chosen at trace time)."""
    import jax

    from sartsolver_tpu.models import sart

    sart._jitted_solver.cache_clear()
    jax.clear_caches()


def _assert_fused_tracks_unfused(monkeypatch, H, g, base, lap=None):
    """Both forms of the fused kernel against the two-matmul path, element
    by element: the MXU form (``_VPU_MAX_BATCH`` 0) and the VPU form, which
    sums each contraction in another order than XLA's dot (it reads at most
    7.6e-6 from the two-matmul solve here, on entries up to 2.5)."""
    from sartsolver_tpu.ops import fused_sweep as fs

    ref = _solve(H, g, dataclasses.replace(base, fused_sweep="off"), lap)
    for vpu_limit in (0, fs._VPU_MAX_BATCH):
        monkeypatch.setattr(fs, "_VPU_MAX_BATCH", vpu_limit)
        _retrace()
        fus = _solve(H, g, dataclasses.replace(base, fused_sweep="interpret"),
                     lap)
        assert int(ref.iterations) == int(fus.iterations)
        assert int(ref.status) == int(fus.status)
        np.testing.assert_allclose(
            np.asarray(fus.solution), np.asarray(ref.solution),
            rtol=2e-5, atol=2e-6,
        )


@pytest.mark.parametrize("logarithmic", [False, True])
@pytest.mark.parametrize("with_lap", [False, True])
def test_fused_matches_unfused(monkeypatch, logarithmic, with_lap):
    H, g = _case()
    base = SolverOptions(
        max_iterations=30, conv_tolerance=1e-12, logarithmic=logarithmic,
        beta_laplace=1e-3 if with_lap else 0.0, relaxation=0.7,
    )
    _assert_fused_tracks_unfused(
        monkeypatch, H, g, base, _laplacian() if with_lap else None)


@pytest.mark.parametrize("logarithmic", [False, True])
def test_fused_matches_unfused_batched(logarithmic):
    H, g = _case(seed=3)
    base = SolverOptions(
        max_iterations=25, conv_tolerance=1e-4, logarithmic=logarithmic,
    )
    ref = _solve(H, g, dataclasses.replace(base, fused_sweep="off"), batch=3)
    fus = _solve(H, g, dataclasses.replace(base, fused_sweep="interpret"), batch=3)
    np.testing.assert_array_equal(np.asarray(ref.iterations), np.asarray(fus.iterations))
    np.testing.assert_array_equal(np.asarray(ref.status), np.asarray(fus.status))
    np.testing.assert_allclose(fus.solution, ref.solution, rtol=2e-5, atol=2e-6)


def test_fused_convergence_freeze_parity():
    """Early-converging frames freeze identically under the fused path."""
    H, g = _case(seed=4, saturated=False)
    base = SolverOptions(max_iterations=60, conv_tolerance=1e-3)
    ref = _solve(H, g, dataclasses.replace(base, fused_sweep="off"))
    fus = _solve(H, g, dataclasses.replace(base, fused_sweep="interpret"))
    assert int(ref.status) == 0
    assert int(ref.iterations) == int(fus.iterations)
    np.testing.assert_allclose(
        float(fus.convergence), float(ref.convergence), rtol=1e-4, atol=1e-7
    )


def test_unaligned_shapes_fall_back():
    assert not fused_available(23, 256, 4)  # pixels not sublane-aligned
    assert not fused_available(24, 200, 4)  # voxels not lane-aligned
    assert fused_available(24, 256, 4)
    H, g = _case()
    Hu, gu = H[:23], g[:23]
    opts = SolverOptions(max_iterations=5, conv_tolerance=1e-12, fused_sweep="auto")
    res = _solve(Hu, gu, opts)  # auto on CPU backend -> unfused; must just run
    assert np.isfinite(np.asarray(res.solution)).all()
    with pytest.raises(ValueError, match="tile-aligned"):
        _solve(Hu, gu, dataclasses.replace(opts, fused_sweep="interpret"))


def test_block_picker():
    assert pick_block_voxels(8192, 65536, 4) % 128 == 0
    assert 65536 % pick_block_voxels(8192, 65536, 4) == 0
    # bf16 halves the panel bytes -> at least as wide a block
    assert pick_block_voxels(8192, 65536, 2) >= pick_block_voxels(8192, 65536, 4)
    assert pick_block_voxels(8, 128, 4) == 128


def test_auto_declines_off_tpu_and_raises_on_tpu_kernel_failure(monkeypatch):
    """'auto' never demotes a broken kernel in silence: off-TPU and under
    pixel sharding it declines without compiling; on a TPU backend a
    Mosaic failure raises with the compiler's message."""
    import jax

    from sartsolver_tpu.ops import fused_sweep as fs

    opts = SolverOptions(fused_sweep="auto")
    monkeypatch.setattr(fs, "_selftest_passed", set())
    assert fs.resolve_fused_auto(opts) is opts  # cpu: nothing compiled
    assert not fs._selftest_passed

    def mosaic_refuses(*a, **k):
        raise ValueError("Mosaic failed to compile TPU kernel: bad slice")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fs, "fused_sweep", mosaic_refuses)
    assert fs.resolve_fused_auto(opts, pixel_sharded=True) is opts
    off = dataclasses.replace(opts, fused_sweep="off")
    assert fs.resolve_fused_auto(off) is off
    with pytest.raises(RuntimeError, match="Mosaic failed.*bad slice"):
        fs.resolve_fused_auto(opts)
    assert not fs._selftest_passed  # a failure is never remembered


def test_selftest_is_one_program(monkeypatch):
    """The self-test lowers one program, which makes its own inputs: an
    eager input would leave a cached program loaded on the device ahead
    of the matrix, laid out differently from run to run."""
    import jax
    import jax.numpy as jnp
    from jax import monitoring

    from sartsolver_tpu.ops import fused_sweep as fs

    def sweep(rtm, w, f, aux, update):  # the kernel's sums, in plain jnp
        f_new = update(f, w @ rtm)
        return f_new, f_new @ rtm.T

    monkeypatch.setattr(fs, "_selftest_passed", set())
    monkeypatch.setattr(fs, "fused_sweep", sweep)
    jax.clear_caches()
    lowered = []

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(event)

    monitoring.register_event_duration_secs_listener(on_event)
    try:
        fs.fused_selftest()
    finally:
        monitoring.unregister_event_duration_listener(on_event)
    assert len(lowered) == 1
    assert fs._selftest_passed == {jax.default_backend()}


def test_scoped_vmem_model():
    """Pin the scoped-VMEM accounting to what TPU v5e measurements showed
    (2026-07-29): the benchmark shapes must be eligible, with the raised
    compiler limit requested exactly when XLA's 16 MiB default would OOM."""
    from sartsolver_tpu.ops.fused_sweep import (
        _SCOPED_VMEM_RAISED_KIB, fused_compile_options,
    )

    P, V = 8192, 65536
    # bf16 B=32 OOMed at the default limit in round 2; it must stay eligible
    # and request the raised limit rather than being declined or crashing.
    assert fused_available(P, V, 2, batch=32)
    opt = fused_compile_options(P, V, 2, batch=32)
    assert opt == {"xla_tpu_scoped_vmem_limit_kib": str(_SCOPED_VMEM_RAISED_KIB)}
    # the B=1 headline configs also clear the raise cap
    assert fused_available(P, V, 4, batch=1)
    assert fused_available(P, V, 2, batch=1)
    # fp32 at B=1 contracts on the VPU: 12.07M at this shape (described
    # v5e compile), under the default, but the estimate's margin still
    # asks for the raise
    assert fused_compile_options(P, V, 4, batch=1) == opt
    # a tiny problem fits the default budget: no options needed
    assert fused_compile_options(8, 256, 4, batch=1) is None
    # absurd batch blows past the raise cap -> ineligible (two-matmul path)
    assert not fused_available(P, V, 4, batch=4096)


def test_scoped_vmem_model_vpu_path(monkeypatch):
    """The VPU form's estimate (fitted accumulator and weight column in
    place of HIGHEST's four panels) stays above what Mosaic charged in
    described-v5e compiles (the smallest scoped-VMEM limit that
    compiles). The cell's block asks for the raised limit, a bound and
    not an allocation, though its 15.09M fit the default."""
    from sartsolver_tpu.ops import fused_sweep as fs

    MiB = 1 << 20
    charged = {  # (P, V, B) -> MiB
        (6144, 524288, 1): 15.09, (6144, 524288, 2): 18.10,
        (8192, 65536, 1): 12.07, (8192, 65536, 2): 16.07,
    }
    for (P_, V_, B_), mib in charged.items():
        assert fs._contracts_on_vpu(4, B_)
        bs = fs.pick_block_voxels(P_, V_, 4, B_)
        assert fs._scoped_vmem_estimate(P_, V_, bs, 4, B_) >= mib * MiB
    assert fs.pick_block_voxels(6144, 524288, 4) == 256
    assert fs.fused_compile_options(6144, 524288, 4) == fs.raised_vmem_options()
    vpu = fs._scoped_vmem_estimate(6144, 524288, 256, 4, 1)
    monkeypatch.setattr(fs, "_VPU_MAX_BATCH", 0)
    highest = fs._scoped_vmem_estimate(6144, 524288, 256, 4, 1)
    assert highest >= 36.25 * MiB > vpu  # the HIGHEST kernel's charge


@pytest.mark.parametrize("rtm_dtype,batch,want", [
    ("float32", 1, "no dot"),
    ("float32", _VPU_MAX_BATCH, "no dot"),
    ("float32", _VPU_MAX_BATCH + 1, "HIGHEST"),
    ("bfloat16", 1, None),
    ("int8", 1, None),
])
def test_kernel_contracts_fp32_panels_at_fp32(rtm_dtype, batch, want):
    """An fp32 panel contracts at fp32. Up to ``_VPU_MAX_BATCH`` frames
    the kernel contracts it on the VPU in fp32 multiply-adds, with no dot
    at all; above it both MXU dots ask for HIGHEST, since Mosaic's default
    is one bf16 pass (a 2.3e-3 relative back-projection error on v5e).
    bf16 and int8 panels keep the default-precision dots."""
    import jax
    import jax.numpy as jnp

    from sartsolver_tpu.ops.fused_sweep import fused_sweep

    jaxpr = jax.make_jaxpr(
        lambda r, w, f: fused_sweep(r, w, f, [], lambda fp, bp: fp + bp,
                                    interpret=True)
    )(jnp.ones((8, 256), rtm_dtype), jnp.ones((batch, 8), jnp.float32),
      jnp.zeros((batch, 256), jnp.float32))
    def dots(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "dot_general":
                yield eqn.params["precision"]
            for v in eqn.params.values():  # the pallas_call kernel body
                sub = getattr(v, "jaxpr", v)
                if hasattr(sub, "eqns"):
                    yield from dots(sub)

    found = list(dots(jaxpr.jaxpr))
    if want == "no dot":
        assert found == []
    else:
        prec = (jax.lax.Precision.HIGHEST,) * 2 if want else None
        assert found == [prec, prec]


def _sweep64(H, w, f, aux, logarithmic, eps=1e-30):
    """One SART sweep in float64 NumPy, in the solver's update forms
    (models/sart.py ``_lin_update`` / ``_log_update``, alpha 1)."""
    H = H.astype(np.float64)
    w, f = w.astype(np.float64), f.astype(np.float64)
    aux = [a.astype(np.float64) for a in aux]
    bp = w @ H
    if logarithmic:
        vm, obs, *pen = aux
        f_new = f * (obs + eps) / (bp * vm + eps)
        if pen:
            f_new = f_new * np.exp(-pen[0])
    else:
        invd, *pen = aux
        f_new = f + invd * bp
        if pen:
            f_new = f_new - pen[0]
        f_new = np.maximum(f_new, 0.0)
    return f_new, f_new @ H.T


@pytest.mark.parametrize("P_", [136, 256])
@pytest.mark.parametrize("logarithmic", [False, True])
@pytest.mark.parametrize("with_lap", [False, True])
def test_vpu_contraction_matches_float64(monkeypatch, P_, logarithmic,
                                         with_lap):
    """The VPU contraction (fp32, B = 1) over a multi-panel grid: f_new
    and fitted within 2e-6 of float64 NumPy, relative to their largest
    entry. 136 pixel rows take one step of the 128-row loop and an 8-row
    tail, 256 two steps and no tail; the shrunk panel target gives eight
    128-voxel panels."""
    import jax
    import jax.numpy as jnp

    from sartsolver_tpu.ops import fused_sweep as fs

    V_ = 1024
    monkeypatch.setattr(fs, "_PANEL_BYTES_TARGET", 16 << 10)
    assert fs.pick_block_voxels(P_, V_, 4) == 128
    assert fs._contracts_on_vpu(4, 1) and fs._VPU_ROWS == 128
    rng = np.random.default_rng(P_ + 2 * logarithmic + with_lap)
    H = rng.uniform(0.0, 1.0, (P_, V_)).astype(np.float32)
    f = rng.uniform(0.5, 2.0, (1, V_)).astype(np.float32)
    if logarithmic:
        w = rng.uniform(0.5, 1.5, (1, P_)).astype(np.float32)
        aux = [np.ones((1, V_), np.float32),
               rng.uniform(0.5, 1.5, (1, V_)).astype(np.float32) * P_ / 2]
        eps = 1e-30

        def update(f_p, bp_p, vm_p, obs_p, *pen_p):
            ratio = (obs_p + eps) / (bp_p * vm_p + eps)
            return f_p * ratio * jnp.exp(-pen_p[0]) if pen_p else f_p * ratio
    else:
        w = rng.normal(0.0, 1.0, (1, P_)).astype(np.float32)
        aux = [np.full((1, V_), 1.0 / P_, np.float32)]

        def update(f_p, bp_p, invd_p, *pen_p):
            upd = f_p + invd_p * bp_p
            if pen_p:
                upd = upd - pen_p[0]
            return jnp.maximum(upd, 0)
    if with_lap:
        aux.append(rng.uniform(-1e-2, 1e-2, (1, V_)).astype(np.float32))
    f_new, fitted = jax.jit(
        lambda r, w_, f_, *a: fs.fused_sweep(r, w_, f_, list(a), update,
                                             interpret=True)
    )(H, w, f, *aux)
    ref_f, ref_fit = _sweep64(H, w, f, aux, logarithmic)
    for got, ref in ((f_new, ref_f), (fitted, ref_fit)):
        err = np.abs(np.asarray(got, np.float64) - ref).max()
        assert err <= 2e-6 * np.abs(ref).max()


def test_vpu_contraction_batched(monkeypatch):
    """The VPU form's batch lanes (each its own bp accumulator and fitted
    scratch row) agree with float64 NumPy at the largest batch it takes;
    64 pixel rows are the row loop's tail alone."""
    import jax
    import jax.numpy as jnp

    from sartsolver_tpu.ops import fused_sweep as fs

    monkeypatch.setattr(fs, "_PANEL_BYTES_TARGET", 16 << 10)
    B_ = _VPU_MAX_BATCH
    assert fs._contracts_on_vpu(4, B_)
    rng = np.random.default_rng(11)
    H = rng.uniform(0.0, 1.0, (64, 512)).astype(np.float32)
    w = rng.normal(0.0, 1.0, (B_, 64)).astype(np.float32)
    f = rng.uniform(0.5, 2.0, (B_, 512)).astype(np.float32)
    invd = np.full((1, 512), 1.0 / 64, np.float32)
    f_new, fitted = jax.jit(lambda r, w_, f_, a: fs.fused_sweep(
        r, w_, f_, [a], lambda fp, bp, i: jnp.maximum(fp + i * bp, 0),
        interpret=True))(H, w, f, invd)
    ref_f, ref_fit = _sweep64(H, w, f, [invd], False)
    for got, ref in ((f_new, ref_f), (fitted, ref_fit)):
        err = np.abs(np.asarray(got, np.float64) - ref).max()
        assert err <= 2e-6 * np.abs(ref).max()


def _contraction_gauge():
    from sartsolver_tpu.obs import metrics as obs_metrics

    reg = obs_metrics.get_registry()
    return {p: reg.gauge("fused_sweep_contraction", path=p).value
            for p in ("vpu", "mxu")}


@pytest.mark.parametrize("rtm_dtype,batch,path", [
    ("float32", 1, "vpu"),
    ("bfloat16", 1, "mxu"),
    ("float32", _VPU_MAX_BATCH + 1, "mxu"),
])
def test_contraction_gauge(rtm_dtype, batch, path):
    """``fused_sweep_contraction{path}`` names the unit the compiled
    kernel contracts its panels on: 1 for that path, 0 for the other."""
    H, g = _case(seed=5)
    opts = SolverOptions(max_iterations=3, conv_tolerance=0.0,
                         rtm_dtype=rtm_dtype, fused_sweep="interpret")
    _solve(H, g, opts, batch=None if batch == 1 else batch)
    other = "mxu" if path == "vpu" else "vpu"
    assert _contraction_gauge() == {path: 1.0, other: 0.0}


def test_block_picker_steps_down_to_fit_vmem_cap():
    """The panel width must narrow when the byte-target width would push the
    whole-kernel scoped-VMEM estimate past the raise cap — int8 has NO
    two-matmul fallback, so large batches must keep fusing at a narrower
    panel instead of erroring (the 12 MiB int8 target picks bs=1024 at the
    headline shape, which at B>=40 estimates past the 48 MiB cap where
    bs=512 still fits)."""
    from sartsolver_tpu.ops.fused_sweep import (
        _SCOPED_VMEM_EST_CAP_BYTES, _scoped_vmem_estimate,
    )

    P, V = 8192, 65536
    assert pick_block_voxels(P, V, 1, batch=1) == 1024
    for batch in (32, 40, 48, 64):
        bs = pick_block_voxels(P, V, 1, batch=batch)
        assert bs > 0, f"int8 batch={batch} lost the fused sweep"
        assert _scoped_vmem_estimate(P, V, bs, 1, batch) <= _SCOPED_VMEM_EST_CAP_BYTES
        assert fused_available(P, V, 1, batch=batch)
    assert pick_block_voxels(P, V, 1, batch=40) < 1024


def test_minimum_panel_solve_matches_unfused(monkeypatch):
    """Numerics of the minimum-panel fallback path: shrink the panel-bytes
    target so the target-derived width is 0 and the picker's 128-voxel
    clamp engages, then assert the fused (interpret) solve still tracks
    the unfused reference (both contraction forms) — the same path a tall RTM
    (or a tall per-chip shard of a voxel-major mesh) takes on hardware."""
    from sartsolver_tpu.ops import fused_sweep as fs

    monkeypatch.setattr(fs, "_PANEL_BYTES_TARGET", 16 << 10)
    assert (16 << 10) // (P * 4 + fs._VOXEL_PANEL_OPERANDS * 4) // 128 == 0
    assert pick_block_voxels(P, V, 4) == 128

    H, g = _case(seed=7)
    base = SolverOptions(max_iterations=25, conv_tolerance=1e-12)
    _assert_fused_tracks_unfused(monkeypatch, H, g, base)


def test_block_picker_tall_matrices_keep_minimum_panel():
    """A tall matrix (large pixel count — the per-chip shard shape of a
    voxel-major mesh) must fall back to the minimum 128-wide panel when
    even that exceeds the panel-bytes target, as long as the scoped-VMEM
    estimate cap still fits: losing fusion entirely would drop such shards
    to the ~8x-slower two-matmul gemv path."""
    # bf16 at 49152 pixels: a 128-panel is 12.6 MiB (> the 8 MiB target)
    # but the kernel estimate is ~26 MiB, well under the 48 MiB cap
    assert pick_block_voxels(49152, 131072, 2) == 128
    assert fused_available(49152, 131072, 2)
    # fp32 at the same height: the 128-panel estimate alone is ~50 MiB,
    # past the cap -> genuinely ineligible
    assert pick_block_voxels(49152, 131072, 4) == 0
    assert not fused_available(49152, 131072, 4)


def test_compiler_options_dispatch_cpu_safe():
    """The dispatch wrapper must never attach the TPU-only flag off-TPU
    (auto resolves unfused on CPU) and must stay callable under an outer
    trace (sharded path inlines the core)."""
    import jax

    from sartsolver_tpu.models import sart

    H, g = _case()
    opts = SolverOptions(max_iterations=3, conv_tolerance=1e-12, fused_sweep="auto")
    res = _solve(H, g, opts)
    assert np.isfinite(np.asarray(res.solution)).all()
    # the CPU path must have dispatched through the option-less jit core
    assert sart._jitted_solver.cache_info().currsize >= 1
    assert sart._jitted_solver(None) is sart._jitted_solver(None)
    # and the tracer branch (sharded path) inlines without a fresh jit
    @jax.jit
    def traced(rtm, gv):
        from sartsolver_tpu.models.sart import (
            SARTProblem, compute_ray_stats, solve_normalized_batch,
        )

        dens, length = compute_ray_stats(rtm, dtype=np.float32)
        problem = SARTProblem(rtm, dens, length, None)
        import jax.numpy as jnp

        return solve_normalized_batch(
            problem, gv[None, :], jnp.ones((1,), np.float32),
            jnp.zeros((1, rtm.shape[1]), np.float32),
            opts=opts, axis_name=None, voxel_axis=None, use_guess=True,
        )

    res2 = traced(np.asarray(H, np.float32), np.asarray(g, np.float32))
    assert np.isfinite(np.asarray(res2.solution)).all()


@pytest.mark.parametrize("logarithmic", [False, True])
def test_fused_matches_unfused_bf16(logarithmic):
    """The fused kernel feeds a bf16 panel to the dot directly (mixed
    f32xbf16 contraction, no conversion scratch); interpreter mode must
    agree with the unfused two-matmul path on the same bf16 RTM, pinning
    the mixed-dtype semantics off-TPU."""
    H, g = _case()
    lap = _laplacian()
    base = SolverOptions(
        max_iterations=30, conv_tolerance=1e-12, beta_laplace=1e-3,
        rtm_dtype="bfloat16", logarithmic=logarithmic,
    )
    res_f = _solve(H, g, dataclasses.replace(base, fused_sweep="interpret"), lap)
    res_u = _solve(H, g, dataclasses.replace(base, fused_sweep="off"), lap)
    np.testing.assert_allclose(
        np.asarray(res_f.solution), np.asarray(res_u.solution),
        rtol=2e-5, atol=1e-6,
    )
    assert int(res_f.iterations) == int(res_u.iterations)


def test_auto_declines_raise_needing_shapes_without_options():
    """VERDICT-r2 contract: auto-fusion must degrade, not break. A shape
    that only compiles at the raised scoped-VMEM limit resolves fused only
    when the caller claims it attached the limit (vmem_raised); under a
    user's outer jit (no options attachable) it falls back to two-matmul."""
    import jax
    import jax.numpy as jnp

    from sartsolver_tpu.models.sart import _resolve_fused

    opts = SolverOptions(fused_sweep="auto", rtm_dtype="bfloat16")
    big = jax.ShapeDtypeStruct((8192, 65536), jnp.bfloat16)
    small = jax.ShapeDtypeStruct((24, 256), jnp.float32)
    orig = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        assert _resolve_fused(opts, None, big, 32, vmem_raised=True) == "compiled"
        assert _resolve_fused(opts, None, big, 32, vmem_raised=False) is None
        # shapes inside the default budget fuse either way
        assert _resolve_fused(opts, None, small, 1, vmem_raised=False) == "compiled"
    finally:
        jax.default_backend = orig


@pytest.mark.parametrize("P_,V_,B_,logarithmic,rtm_dtype,with_lap", [
    # explicit corners: every dtype x variant x laplacian combination is
    # exercised at least once, at shapes away from the fixture's 24x256 —
    # notably int8 with logarithmic/laplacian pins the aux-panel ordering
    # of the int8 update closures (scale, [vm, obs,] penalty)
    (8, 128, 1, False, "float32", False),
    (40, 384, 3, True, "float32", True),
    (16, 256, 2, False, "bfloat16", True),
    (32, 128, 1, True, "bfloat16", False),
    (24, 384, 2, False, "int8", True),
    (40, 256, 3, True, "int8", True),
    (8, 128, 2, True, "int8", False),
])
def test_fused_matches_unfused_config_sweep(
    P_, V_, B_, logarithmic, rtm_dtype, with_lap
):
    """Interpreter-mode fused must track the unfused path across shapes,
    variants, storage dtypes and regularization — not just the fixture
    shape. int8 has no unfused loop; it is compared loosely against the
    fp32 unfused solve (quantized-system perturbation only)."""
    rng = np.random.default_rng(P_ * 1000 + V_)
    H = rng.uniform(0.05, 1.0, (P_, V_)).astype(np.float32)
    H[:, 0] = 0.0  # one dead voxel
    g = H.astype(np.float64) @ rng.uniform(0.5, 2.0, V_)
    lap = None
    if with_lap:
        li = np.arange(V_)
        lap = make_laplacian(
            np.r_[li, li[1:]], np.r_[li, li[:-1]],
            np.r_[np.full(V_, 1.0), np.full(V_ - 1, -0.5)].astype(np.float32),
        )
    base = SolverOptions(
        max_iterations=12, conv_tolerance=0.0, logarithmic=logarithmic,
        beta_laplace=1e-3 if with_lap else 0.0, rtm_dtype=rtm_dtype,
    )
    fus = _solve(H, g, dataclasses.replace(base, fused_sweep="interpret"),
                 lap, batch=B_)
    if rtm_dtype == "int8":
        ref = _solve(H, g, dataclasses.replace(
            base, fused_sweep="off", rtm_dtype="float32"), lap, batch=B_)
        a, b = np.asarray(fus.solution), np.asarray(ref.solution)
        assert np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30) < 0.08
    else:
        ref = _solve(H, g, dataclasses.replace(base, fused_sweep="off"),
                     lap, batch=B_)
        np.testing.assert_allclose(
            np.asarray(fus.solution), np.asarray(ref.solution),
            rtol=3e-5, atol=3e-6,
        )

"""Constrained SART solvers, TPU-native.

Implements the reference's two solver families (manual Eq. 2-6):

- **Linear SART** (additive, non-negativity-constrained) — reference CPU path
  sartsolver.cpp:133-232, CUDA path sartsolver_cuda.cpp:197-274.
- **Logarithmic SART** (multiplicative) — sartsolver.cpp:235-339,
  sartsolver_cuda.cpp:277-354.

Design: one code path with a swappable update rule (the reference maintains
four near-duplicate solvers). The entire iteration loop is a single
jit-compiled ``lax.while_loop``; per-iteration global reductions are
``lax.psum`` over the ``'pixels'`` mesh axis when running sharded (the
reference's 16 ``MPI_Allreduce`` sites, e.g. sartsolver.cpp:206,222), and
identity when running on one device. Unlike the reference's CUDA path there
is **no** per-iteration device->host->network->device staging
(sartsolver_cuda.cpp:242-244) — reductions ride the ICI.

Precision policy mirrors the CUDA path by default: fp32 on device, with the
measurement normalized by its global max to keep ``||Hf||^2`` inside fp32
range (sartsolver_cuda.cpp:146-157); ``SolverOptions.cpu_parity()`` instead
reproduces the fp64 CPU path (requires x64).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array, lax

from sartsolver_tpu.config import (
    DIVERGED,
    MAX_ITERATIONS_EXCEEDED,
    SDC_DETECTED,
    SUCCESS,
    SolverOptions,
)
from sartsolver_tpu.ops.fused_sweep import (
    SPARSE_STATIC_UNROLL_MAX,
    fused_available,
    fused_sweep,
    os_subset_back,
    os_subset_forward,
    os_subset_pixels,
    os_subset_rows,
    panel_available,
    pick_panel_voxels,
    sharded_panel_sweep,
    sparse_gather_sweep,
    sparse_os_back,
    sparse_os_forward,
    sparse_panel_sweep,
)
from sartsolver_tpu.ops.laplacian import (
    LaplacianCOO,
    ShardedLaplacian,
    coo_matvec,
    sharded_penalty,
)
from sartsolver_tpu.ops.projection import back_project, forward_project
from sartsolver_tpu.operators.implicit import (
    ImplicitSpec,
    implicit_back,
    implicit_forward,
    implicit_ray_stats,
    implicit_subset_density,
)
from sartsolver_tpu.operators.lowrank import (
    LowRankSpec,
    lowrank_back,
    lowrank_forward,
    lowrank_ray_stats,
    lowrank_subset_density,
)


class SARTProblem(NamedTuple):
    """Device-resident problem state (the reference's solver-ctor uploads,
    sartsolver_cuda.cpp:103-124).

    ``rtm`` is the local row block ``[npixel_local, nvoxel]`` of the global
    RTM (row-block distribution, main.cpp:67-68). ``ray_density`` is the
    *global* per-voxel column sum (allreduced, sartsolver.cpp:38-47);
    ``ray_length`` is the *local* per-pixel row sum (sartsolver.cpp:49-56).
    """

    rtm: Array  # [P_local, V], opts.rtm_dtype
    ray_density: Array  # [V], opts.dtype
    ray_length: Array  # [P_local], opts.dtype
    # COO over [V, V] (unsharded), this device's ShardedLaplacian slice
    # (voxel-sharded meshes), or None
    laplacian: Optional[LaplacianCOO | ShardedLaplacian]
    # Per-voxel dequantization scales when the RTM is int8-quantized
    # (H_ij = rtm_scale[j] * rtm[i, j]); None for fp32/bf16 storage.
    rtm_scale: Optional[Array] = None  # [V], fp32
    # Low-rank factor term of the factored operator H ~= S + U V^T
    # (operators/lowrank.py; the rtm leaf then holds the sparse core S).
    # None on every other backend — the trailing defaults keep the
    # pytree structure, and hence every compiled program and audit
    # golden, byte-identical when the factored path is not engaged.
    factor_u: Optional[Array] = None  # [P_local, r]
    factor_v: Optional[Array] = None  # [V, r]
    # Per-rank-component dequantization scales when the factors are
    # int8-quantized (row 0: U's, row 1: V's); None for fp storage.
    factor_scale: Optional[Array] = None  # [2, r], fp32


class SolveResult(NamedTuple):
    solution: Array  # [V] (denormalized, opts.dtype)
    status: Array  # int32 scalar: SUCCESS / MAX_ITERATIONS_EXCEEDED
    iterations: Array  # int32 scalar: completed iterations
    convergence: Array  # final residual metric C^k (Eq. 5)


def _psum(x, axis_name):
    """``lax.psum`` over ``axis_name`` under the scope ``sart.exchange``;
    ``x`` itself where no axis is given (a size-1 mesh axis)."""
    if axis_name is None:
        return x
    with jax.named_scope(SCOPE_EXCHANGE):
        return lax.psum(x, axis_name)


def _resolve_fused(
    opts: SolverOptions, axis_name, rtm, batch: int, *, vmem_raised: bool = False
) -> Optional[str]:
    """Trace-time decision for the fused sweep (ops/fused_sweep.py).

    Returns None (two-matmul path), "compiled"/"interpret" (the Pallas
    kernel — full pixel extent on-device, i.e. no pixel-axis sharding), or
    "panel" (the pixel-sharded voxel-panel scan with per-panel psum,
    :func:`~sartsolver_tpu.ops.fused_sweep.sharded_panel_sweep`). All
    variants need fp32 compute; "auto" additionally requires a TPU backend
    and tile-aligned shapes. An explicitly requested mode that cannot be
    honoured raises instead of silently degrading. Under pixel sharding
    "on" and "interpret" both select the panel scan — it is plain XLA, so
    there is no interpreter to choose.

    ``vmem_raised`` says the caller attached the raised scoped-VMEM
    compiler limit (fused_compile_options) to the jit that will compile
    this trace. Without it, "auto" declines shapes that only compile at
    the raised limit — e.g. under a user's own outer jit, where nothing
    can attach compiler options — instead of failing the compile. Only the
    Pallas kernel is affected; the panel scan needs no compiler options.
    """
    mode = opts.fused_sweep
    if mode == "off":
        return None
    explicit = mode in ("on", "interpret")
    if opts.divergence_recovery and opts.logarithmic:
        # the guard's per-frame relaxation scale enters the LOG update as
        # a traced exponent, which the fused kernel's literal-constant
        # closure cannot carry (the LINEAR update folds the scale into the
        # pixel weights, so it fuses fine). The panel scan shares the
        # update closures, so the restriction is kept uniform across both
        # fused variants.
        if explicit:
            raise ValueError(
                f"fused_sweep='{mode}' requested but divergence_recovery "
                "is enabled on the logarithmic solver; the per-frame "
                "relaxation scale cannot enter the fused kernel's literal "
                "exponent. Use fused_sweep='auto'/'off' or the linear "
                "solver."
            )
        return None
    if jnp.dtype(opts.dtype) != jnp.float32 or rtm.dtype not in (
        jnp.float32, jnp.bfloat16, jnp.int8
    ):
        if explicit:
            raise ValueError(
                f"fused_sweep='{mode}' requested but dtype={opts.dtype} / "
                f"rtm dtype={rtm.dtype}; the fused sweep computes in fp32 "
                "(fp32, bfloat16 or quantized int8 RTM storage)."
            )
        return None
    if axis_name is not None:
        # Pixel-sharded: the voxel-panel scan with a per-panel psum keeps
        # the one-HBM-read structure on the row-sharded layout. No Pallas
        # involved, so no self-test/VMEM gating — just tile alignment,
        # which the sharded driver's padding guarantees.
        from sartsolver_tpu.ops.fused_sweep import panel_available

        pv = opts.fused_panel_voxels
        # an explicit panel width must divide the per-shard voxel extent,
        # or the sweep would raise mid-trace — after the driver staged the
        # (possibly tens-of-GB) RTM; check it here where "auto declines,
        # explicit raises with the actual reason" still holds
        ok = panel_available(
            rtm.shape[0], rtm.shape[1], rtm.dtype.itemsize, batch
        ) and (pv is None or rtm.shape[1] % pv == 0)
        if mode == "auto":
            return "panel" if ok and jax.default_backend() == "tpu" else None
        if not ok:
            raise ValueError(
                f"fused_sweep='{mode}' requested but the per-shard RTM "
                f"block {tuple(rtm.shape)} is not tile-aligned "
                "(pixels % 8 == 0, voxels % 128 == 0"
                + (f", voxels % fused_panel_voxels={pv} == 0"
                   if pv is not None else "")
                + ") for the pixel-sharded panel sweep."
            )
        return "panel"
    ok = fused_available(rtm.shape[0], rtm.shape[1], rtm.dtype.itemsize, batch)
    if mode == "auto":
        if ok and not vmem_raised:
            from sartsolver_tpu.ops.fused_sweep import fused_compile_options

            ok = fused_compile_options(
                rtm.shape[0], rtm.shape[1], rtm.dtype.itemsize, batch
            ) is None
        return "compiled" if ok and jax.default_backend() == "tpu" else None
    if not ok:
        raise ValueError(
            f"fused_sweep='{mode}' requested but RTM shape {tuple(rtm.shape)} "
            f"(batch {batch}) is not tile-aligned (pixels % 8 == 0, "
            "voxels % 128 == 0) or does not fit the VMEM budget."
        )
    return "interpret" if mode == "interpret" else "compiled"


# Trace-time record of the sweep path the most recently traced solver core
# selected in this process ("compiled" / "interpret" / "panel" / "off";
# None before any trace). Observability only — lets the CLI's --timing summary and
# bench artifacts state which path actually engaged instead of inferring it
# (VERDICT r3 next #4); a cached jit does not re-trace, so this reflects
# the last *compilation*, which is what provenance needs.
FUSED_ENGAGEMENT = {"last": None}

# Named scopes of the loop's device work (docs/OBSERVABILITY.md §4): every
# operation traced inside one carries the name in its ``op_name`` metadata,
# which the profiler's device trace keeps as each operation's ``tf_op``
# path, so a trace reader charges device time to the Laplacian penalty or
# to the RTM sweep whatever backend implements them. Metadata only: the
# compiled programs run the same instructions with or without them (the
# Pallas kernel's custom call takes the scope as its instruction name).
# ``sart.exchange`` names the cross-chip collectives (the psums, the
# Laplacian's halo all_gather). Inside the sweep or the penalty it nests
# under their scope, so a reader keyed on either still counts it there;
# the forward projection's voxel-axis psum and the convergence psums sit
# under it alone. A size-1 mesh axis has no collective, so no op of a
# one-chip program carries it.
SCOPE_LAPLACIAN = "sart.laplacian"
SCOPE_SWEEP = "sart.sweep"
SCOPE_EXCHANGE = "sart.exchange"


def _momentum_carries_fitted(opts: SolverOptions) -> bool:
    """Whether the momentum state includes the previous iterate's forward
    projection. Only the linear solver on the classic (os_subsets == 1)
    sweep carries it: ``H y = H f + beta (H f - H f_prev)`` is exact by
    linearity, so the extrapolated point's projection costs no RTM read.
    The log solver's extrapolation is multiplicative (no such identity —
    it pays one forward projection per iteration instead), and the OS
    cycle recomputes every subset's residual fresh anyway."""
    return (opts.momentum != "off" and not opts.logarithmic
            and opts.os_subsets == 1)


# The TPU backend emulates float64 as float32 pairs: ~2x-fp32 precision but
# *fp32 range* (measured on a v5e, jax 0.9: 1e-100 reads back as 0.0,
# 1e-39 survives only as an fp32 subnormal, 1 + 1e-12 is kept). The
# reference's EPSILON_LOG = 1e-100 (sartsolver.cpp:14) is therefore
# unrepresentable there; positive tiny constants are clamped to the
# smallest safe normal. (The CPU backend has native float64.)
MIN_POSITIVE = 1.2e-37


def _tiny(value: float, dtype) -> Array:
    if 0.0 < value < MIN_POSITIVE:
        value = MIN_POSITIVE
    return jnp.asarray(value, dtype)


def _ff_add(ah, al, bh, bl):
    """Float-float addition (Knuth TwoSum + error fold): exact-to-~eps^2
    sum of two (hi, lo) pairs. All plain fp32 adds/subs — XLA must not
    re-associate them, which it does not (it preserves FP semantics unless
    fast-math flags are set, which JAX never sets)."""
    s = ah + bh
    v = s - ah
    t = (ah - (s - v)) + (bh - v)
    t = t + al + bl
    hi = s + t
    lo = t - (hi - s)
    return hi, lo


def _sumsq_precise(x: Array, dtype) -> Array:
    """Within-shard ``sum(x**2, axis=1)`` with ~fp64-quality accumulation,
    rounded back to the compute dtype.

    The convergence metric ``C = (||g||^2 - ||Hf||^2)/||g||^2`` (Eq. 5)
    subtracts two nearly-equal O(1) quantities near the stall threshold; the
    fp32 accumulation error of the sum over npixel elements (~eps*sqrt(P))
    is what makes the stop iteration drift with storage dtype. Compensated
    accumulation pins the summation error at ~one fp32 ulp of the result;
    the final fp32 subtraction is then exact by Sterbenz's lemma whenever
    ``||Hf||^2`` is within 2x of ``||g||^2``. The cross-shard psum stays
    fp32 — summing a handful of already-rounded partials adds no meaningful
    error and avoids wide collectives.

    Implementation (public API only — VERDICT r3 weak #3 retired the
    private ``jax._src.config.enable_x64`` import): each square is split
    exactly as ``x^2 = p + e`` (Veltkamp split + Dekker mul12 residual;
    both products of 12-bit halves are exact in fp32), then the (p, e)
    pairs are reduced by a pairwise float-float tree — the same float32-
    pair arithmetic this TPU build's emulated fp64 uses, with fp32 range
    (inputs are normalized O(1), see module docstring precision policy).
    Under x64 the plain fp64 accumulation is equivalent and cheaper.
    ``tests/test_sart_core.py`` pins the accumulation quality so a future
    regression to plain fp32 summation fails CI rather than silently
    degrading the dtype-stability property.
    """
    if jnp.dtype(dtype) == jnp.float64 or jax.config.jax_enable_x64:
        x64 = x.astype(jnp.float64)
        return jnp.sum(x64 * x64, axis=1).astype(dtype)
    x = x.astype(jnp.float32)
    c = x * jnp.float32(4097.0)  # Veltkamp constant 2^12 + 1 for fp32
    hi = c - (c - x)
    lo = x - hi
    p = x * x
    e = ((hi * hi - p) + 2.0 * (hi * lo)) + lo * lo  # x^2 - p, exactly
    n = x.shape[1]
    m = 1 << max(n - 1, 0).bit_length()
    if m != n:  # pad to a power of two; (0, 0) terms are inert
        pad = ((0, 0), (0, m - n))
        p, e = jnp.pad(p, pad), jnp.pad(e, pad)
    while m > 1:  # static-shape pairwise tree, log2(n) fused steps
        m //= 2
        p, e = _ff_add(p[:, :m], e[:, :m], p[:, m:], e[:, m:])
    return (p[:, 0] + e[:, 0]).astype(dtype)


def compute_ray_stats(
    rtm: Array, *, dtype, axis_name=None, voxel_axis=None
) -> Tuple[Array, Array]:
    """Per-voxel ray density (global) and per-pixel ray length.

    Reference: sartsolver.cpp:38-56 — column sums allreduced over ranks, row
    sums kept local. Under a 2-D mesh the row sums additionally reduce over
    the voxel (column-shard) axis.
    """
    dens = _psum(jnp.sum(rtm, axis=0, dtype=dtype), axis_name)
    length = _psum(jnp.sum(rtm, axis=1, dtype=dtype), voxel_axis)
    return dens, length.astype(dtype)


# int8 x int8 dots accumulate in int32: |codes| <= 127 on both sides bounds
# the contraction extent at 2^31 / 127^2 (~133k); enforced in make_problem.
INT8_MAX_CONTRACTION = (2**31 - 1) // (127 * 127)


def _quantize_sym(x: Array, axis: int) -> Tuple[Array, Array]:
    """Symmetric int8 quantization along ``axis``: ``x ~= scale * codes``
    with ``|codes| <= 127``; all-zero slices get scale 1 (codes stay 0).
    The single source of the recipe shared by the RTM storage quantizer
    and the per-call vector quantization of the integer projections."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    codes = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return codes, scale


def int8_back_project(codes, scale, w, *, accum_dtype=jnp.float32):
    """``H^T w`` for an int8-quantized RTM, without dequantizing it.

    ``w`` is quantized per batch row (max-abs/127) so the contraction runs
    as an integer MXU dot and is rescaled exactly afterwards; the only
    approximation is the ~1/254 relative rounding of ``w``. Used outside
    the iteration loop (initial guess, log-mode ``obs``); the loop itself
    dequantizes codes exactly (ops/fused_sweep.py).
    """
    wq, ws = _quantize_sym(w, axis=-1)
    acc = lax.dot_general(
        wq, codes, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return acc.astype(accum_dtype) * (ws * scale[None, :]).astype(accum_dtype)


def int8_forward_project(codes, scale, f, *, accum_dtype=jnp.float32):
    """``H f`` for an int8-quantized RTM; counterpart of
    :func:`int8_back_project` (same quantize-rescale scheme applied to
    ``f * scale``)."""
    yq, ys = _quantize_sym(f * scale[None, :], axis=-1)
    acc = lax.dot_general(
        yq, codes, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return acc.astype(accum_dtype) * ys.astype(accum_dtype)


def quantize_rtm(rtm: Array) -> Tuple[Array, Array]:
    """Per-voxel (column) symmetric int8 quantization of an RTM block.

    Returns ``(codes int8 [P, V], scale fp32 [V])`` with
    ``H ~= scale[None, :] * codes`` and ``|codes| <= 127``. RTM entries are
    physically non-negative line integrals, so the codes use [0, 127]; the
    per-column relative error bound is 1/254 of the column maximum — below
    the bf16 per-entry bound for the large entries that dominate both
    projections.
    """
    codes, scale = _quantize_sym(jnp.asarray(rtm, jnp.float32), axis=0)
    return codes, scale[0]


def compute_ray_stats_int8(
    codes: Array, scale: Array, *, dtype, axis_name=None, voxel_axis=None
) -> Tuple[Array, Array]:
    """Ray stats of a quantized RTM ``H = scale * codes``, both exact:
    column sums accumulate the int8 codes in int32 before scaling; row sums
    contract the codes against the fp32 scales. Reductions mirror
    :func:`compute_ray_stats`."""
    dens = _psum(
        scale.astype(dtype)
        * jnp.sum(codes, axis=0, dtype=jnp.int32).astype(dtype),
        axis_name,
    )
    length = _psum(
        lax.dot_general(
            codes, scale.astype(dtype),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=dtype,
        ),
        voxel_axis,
    )
    return dens, length.astype(dtype)


def make_problem(
    rtm,
    laplacian: Optional[LaplacianCOO] = None,
    *,
    opts: SolverOptions,
    axis_name=None,
) -> SARTProblem:
    """Build device problem state from a (local block of the) RTM.

    With ``opts.rtm_dtype == "int8"`` the matrix is stored as per-voxel-
    scaled int8 codes (see :func:`quantize_rtm`); ray stats are computed
    from the quantized matrix so the solver is self-consistent with what
    the sweeps actually multiply by.
    """
    dtype = jnp.dtype(opts.dtype)
    if (opts.rtm_dtype or "") == "int8":
        P_, V_ = np.shape(rtm)
        if max(P_, V_) > INT8_MAX_CONTRACTION:
            raise ValueError(
                f"rtm_dtype='int8': RTM extent {max(P_, V_)} exceeds the "
                f"int32-accumulation bound {INT8_MAX_CONTRACTION} of the "
                "integer projections (int8_back_project); use "
                "fp32/bfloat16 storage."
            )
        codes, scale = quantize_rtm(rtm)
        # stats of the QUANTIZED matrix (what the sweeps multiply by)
        dens, length = compute_ray_stats_int8(
            codes, scale, dtype=dtype, axis_name=axis_name
        )
        return SARTProblem(codes, dens, length, laplacian, scale)
    rtm_dtype = jnp.dtype(opts.rtm_dtype or opts.dtype)
    rtm = jnp.asarray(rtm)
    dens, length = compute_ray_stats(rtm, dtype=dtype, axis_name=axis_name)
    return SARTProblem(rtm.astype(rtm_dtype), dens, length, laplacian)


def make_sparse_problem(
    rtm,
    laplacian: Optional[LaplacianCOO] = None,
    *,
    opts: SolverOptions,
    axis_name=None,
):
    """:func:`make_problem` plus the block-sparse tile-occupancy pass
    (docs/PERFORMANCE.md §10): returns ``(problem, occupancy)``.

    With ``opts.sparse_rtm`` active the host matrix is indexed at
    8x128-tile granularity and — for a nonzero threshold — every tile
    whose entries all satisfy ``|H_ij| <= eps * max|H|`` is ZEROED before
    the problem is built, so rho/lambda and the Eq. 6 masks come from the
    thresholded operator the sweeps actually multiply by (the solve is
    self-consistent; parity vs dense is residual-matched at eps > 0 and
    bit-exact at eps == 0, where nothing is dropped). The returned
    occupancy is the jit-static index the solver cores take as
    ``tile_occupancy=``; ``(problem, None)`` when sparse mode is off.
    The chunked-ingest equivalent lives in ``parallel/multihost.py``
    (``TileMaxStats`` fed by the striped read).

    Representation note: THIS path indexes the pre-storage fp32 values,
    so with reduced-precision storage (bf16/int8) a tile whose every
    entry rounds to zero in storage stays marked occupied — strictly
    conservative (a missed skip, never a skipped live tile), but the
    digest can differ from the ingest-built index of the same matrix,
    which covers the PACKED representation (docs/FORMATS.md).
    """
    eps = opts.sparse_epsilon()
    if eps is None:
        return make_problem(rtm, laplacian, opts=opts,
                            axis_name=axis_name), None
    from sartsolver_tpu.ops.sparse import (
        build_tile_occupancy,
        threshold_matrix,
    )

    mat = np.asarray(rtm, np.float32)
    occ = build_tile_occupancy(mat, epsilon=eps)
    if eps > 0:
        mat = threshold_matrix(mat, occ)
    return make_problem(mat, laplacian, opts=opts,
                        axis_name=axis_name), occ


def make_implicit_problem(
    rays,
    spec: ImplicitSpec,
    *,
    opts: SolverOptions,
    axis_name=None,
) -> SARTProblem:
    """Matrix-free analogue of :func:`make_problem`: stage the packed
    ``[P_local, 6]`` ray table as the problem's ``rtm`` leaf and derive
    rho/lambda from the SAME traced slab kernel the sweeps multiply by
    (operators/implicit.py) — Eq. 6 masking is self-consistent with the
    on-the-fly operator exactly as the dense stats are with the stored
    matrix. The problem pytree STRUCTURE is identical to the dense one
    (only the rtm leaf's shape differs), which is what lets the solver
    cores stay one program family; the spec rides separately as the
    ``operator_spec`` static argument.
    """
    dtype = jnp.dtype(opts.dtype)
    if (opts.rtm_dtype or "") == "int8":
        raise ValueError(
            "rtm_dtype='int8' quantizes a stored matrix; the implicit "
            "operator stores no matrix (its rays stay fp32). Drop "
            "rtm_dtype or use a materialized RTM."
        )
    rays = jnp.asarray(rays, jnp.float32)
    dens, length = implicit_ray_stats(
        rays, spec, dtype=dtype, axis_name=axis_name
    )
    return SARTProblem(rays, dens, length, None)


def make_lowrank_problem(
    s_matrix,
    u,
    v,
    spec: LowRankSpec,
    *,
    opts: SolverOptions,
    axis_name=None,
) -> SARTProblem:
    """Factored-operator analogue of :func:`make_problem`: stage the
    sparse core ``S`` as the problem's ``rtm`` leaf with the skinny
    factors ``U``/``V`` riding as the trailing leaves, and derive
    rho/lambda from the COMPOSED operator ``S + U V^T`` — the Eq. 6
    masks are self-consistent with what the sweeps multiply by.

    Inputs are already padded to ``spec.nvoxel`` columns (zero voxel
    padding, like every staged matrix block). On the int8 path ``S`` is
    quantized per voxel (:func:`quantize_rtm`, exact in-loop panel
    dequant) and each factor per rank component (``factor_scale[0]`` =
    U's scales, ``[1]`` = V's); stats come from the QUANTIZED operator.
    """
    dtype = jnp.dtype(opts.dtype)
    s_matrix = jnp.asarray(s_matrix, jnp.float32)
    u = jnp.asarray(u, jnp.float32)
    v = jnp.asarray(v, jnp.float32)
    if s_matrix.ndim != 2 or s_matrix.shape[1] != spec.nvoxel:
        raise ValueError(
            f"sparse core has shape {tuple(s_matrix.shape)} — expected "
            f"[P_local, {spec.nvoxel}] (pad voxel columns first)."
        )
    if u.shape != (s_matrix.shape[0], spec.rank) or v.shape != (
        spec.nvoxel, spec.rank
    ):
        raise ValueError(
            f"factor shapes {tuple(u.shape)} / {tuple(v.shape)} do not "
            f"match the [{s_matrix.shape[0]}, {spec.nvoxel}] core at "
            f"rank {spec.rank}."
        )
    if (opts.rtm_dtype or "") == "int8":
        P_, V_ = s_matrix.shape
        if max(P_, V_) > INT8_MAX_CONTRACTION:
            raise ValueError(
                f"rtm_dtype='int8': RTM extent {max(P_, V_)} exceeds "
                f"the int32-accumulation bound {INT8_MAX_CONTRACTION} "
                "of the integer projections; use fp32/bfloat16 storage."
            )
        codes, scale = quantize_rtm(s_matrix)
        u_codes, su = _quantize_sym(u, axis=0)
        v_codes, sv = _quantize_sym(v, axis=0)
        factor_scale = jnp.concatenate([su, sv], axis=0)  # [2, r]
        dens, length = lowrank_ray_stats(
            codes,
            u_codes.astype(jnp.float32) * su,
            v_codes.astype(jnp.float32) * sv,
            spec, scale=scale, dtype=dtype, axis_name=axis_name,
        )
        return SARTProblem(codes, dens, length, None, scale,
                           u_codes, v_codes, factor_scale)
    rtm_dtype = jnp.dtype(opts.rtm_dtype or opts.dtype)
    staged = s_matrix.astype(rtm_dtype)
    dens, length = lowrank_ray_stats(
        staged, u, v, spec, dtype=dtype, axis_name=axis_name
    )
    return SARTProblem(staged, dens, length, None, None, u, v)


def solve_normalized(
    problem: SARTProblem,
    g: Array,
    msq: Array,
    f0: Array,
    *,
    opts: SolverOptions,
    axis_name=None,
    voxel_axis=None,
    use_guess: bool,
    tile_occupancy=None,
    operator_spec=None,
) -> SolveResult:
    """Jit-compiled solver core on a pre-normalized measurement.

    ``g``/``f0`` are already divided by the global norm; ``msq`` is the
    normalized ``||g||^2`` with negative (saturated) measurements excluded
    (sartsolver.cpp:161-164).

    Sharding: under ``shard_map``, ``axis_name`` names the pixel (row-block)
    mesh axis — ``g``, ``problem.rtm`` and ``problem.ray_length`` hold this
    device's pixel block. With ``voxel_axis`` additionally set (2-D mesh),
    the RTM is also column-sharded: ``f0``/``ray_density`` and the returned
    solution hold this device's voxel block, the Laplacian must be a
    halo-partitioned :class:`ShardedLaplacian` (this device's slice), and
    the forward projection reduces over the voxel axis while the
    back-projection reduces over the pixel axis. The replicated-solution memory footprint of the reference
    (every rank holds all of f, sartsolver.hpp) drops to 1/n_voxel_shards.

    Implemented as the B=1 case of :func:`solve_normalized_batch` — a batch
    of one freezes exactly when the serial loop would exit, so the semantics
    (per-iteration updates, convergence test from iteration 1, status and
    iteration counts) are identical by construction.
    """
    dtype = jnp.dtype(opts.dtype)
    res = solve_normalized_batch(
        problem,
        g[None, :],
        jnp.reshape(jnp.asarray(msq, dtype), (1,)),
        f0[None, :],
        opts=opts, axis_name=axis_name, voxel_axis=voxel_axis,
        use_guess=use_guess, tile_occupancy=tile_occupancy,
        operator_spec=operator_spec,
    )
    return SolveResult(
        res.solution[0], res.status[0], res.iterations[0], res.convergence[0]
    )


_SOLVER_STATIC_ARGS = (
    "opts", "axis_name", "voxel_axis", "use_guess", "return_fitted",
    "_vmem_raised", "tile_occupancy", "operator_spec",
)


@functools.lru_cache(maxsize=None)
def _jitted_solver(options_items):
    """Jitted solver core, cached per frozen compiler-options dict.

    The fused Pallas sweep can need a raised XLA scoped-VMEM limit at large
    shapes (ops/fused_sweep.py:fused_compile_options); compiler options must
    be fixed at jit time, so each distinct option set gets its own cached
    jit wrapper."""
    return functools.partial(
        jax.jit,
        static_argnames=_SOLVER_STATIC_ARGS,
        compiler_options=dict(options_items) if options_items else None,
    )(_solve_normalized_batch_impl)


def solve_normalized_batch(
    problem: SARTProblem,
    g: Array,  # [B, P_local]
    msq: Array,  # [B]
    f0: Array,  # [B, V_local]
    *,
    opts: SolverOptions,
    axis_name=None,
    voxel_axis=None,
    use_guess: bool,
    fitted0: Optional[Array] = None,
    return_fitted: bool = False,
    _vmem_raised: bool = False,
    tile_occupancy=None,
    operator_spec=None,
) -> "SolveResult | Tuple[SolveResult, Array]":
    """Batched solver core: B independent frames in one while_loop.

    The reference solves frames strictly one at a time (main.cpp:131-140),
    so its GPU hot path is a gemv (sartsolver_cuda.cpp:248). Batching turns
    both sweeps into gemms ([B,P]x[P,V]), which is what the MXU wants —
    the RTM is read from HBM once per iteration *for the whole batch*
    instead of once per frame, a ~Bx cut in the bandwidth bill.

    Semantics per frame are identical to the serial path: each frame has its
    own masks, convergence metric and status, and a converged frame's state
    freezes (its update is masked out) while the rest continue, so results
    match frame-by-frame solves exactly. Intended for ``--no_guess``
    workloads, where frames carry no warm-start dependency.

    ``fitted0`` (valid only with ``use_guess=False``): the caller already
    knows ``H @ f0`` — e.g. a warm start carried from a previous solve,
    whose loop exited with exactly this product — so the pre-loop setup
    forward projection (one full HBM read of the RTM, the reference's
    per-frame ``cublasSgemv`` setup, sartsolver_cuda.cpp:185-189) is
    skipped. ``return_fitted=True`` additionally returns the loop-exit
    ``fitted == H @ solution`` as ``(SolveResult, fitted [B, P_local])``
    for the caller to carry forward.
    """
    kwargs = dict(
        opts=opts, axis_name=axis_name, voxel_axis=voxel_axis,
        use_guess=use_guess, fitted0=fitted0, return_fitted=return_fitted,
        tile_occupancy=tile_occupancy, operator_spec=operator_spec,
    )
    if any(
        isinstance(leaf, jax.core.Tracer)
        for leaf in jax.tree_util.tree_leaves((problem, g, msq, f0, fitted0))
    ):
        # Some input is being traced by an outer jit/shard_map: inline the
        # core; compiler options belong on the outermost jit there. Only a
        # caller that actually attached them may claim _vmem_raised
        # (parallel/sharded.py does; a user's own jit typically has not, so
        # the default makes auto-fusion decline needs-raised-limit shapes
        # instead of failing their compile). With all-concrete inputs a
        # nested call still compiles separately, so the options path below
        # stays honored.
        return _solve_normalized_batch_impl(
            problem, g, msq, f0, _vmem_raised=_vmem_raised, **kwargs
        )
    rtm = problem.rtm
    options = None
    if (
        operator_spec is None  # implicit/factored projectors never fuse
        and jax.default_backend() == "tpu"  # raised limit: TPU-only flag
        and _resolve_fused(opts, axis_name, rtm, g.shape[0], vmem_raised=True)
        == "compiled"
    ):
        from sartsolver_tpu.ops.fused_sweep import fused_compile_options

        opt_dict = fused_compile_options(
            rtm.shape[0], rtm.shape[1], rtm.dtype.itemsize, g.shape[0]
        )
        options = tuple(sorted(opt_dict.items())) if opt_dict else None
    # The dispatcher attaches whatever options the shape needs, so the core
    # may always treat the raised limit as available.
    return _jitted_solver(options)(
        problem, g, msq, f0, _vmem_raised=True, **kwargs
    )


def solve_chain_normalized(
    problem: SARTProblem,
    g: Array,  # [K, P_local]
    msq: Array,  # [K]
    f0: Array,  # [1, V_local] — seed for frame 0 (ignored when guessing)
    rescale: Array,  # [K] — warm-start renormalization factors
    *,
    opts: SolverOptions,
    axis_name=None,
    voxel_axis=None,
    use_guess_first: bool,
    fitted0: Optional[Array] = None,
    _vmem_raised: bool = False,
    tile_occupancy=None,
    operator_spec=None,
) -> Tuple[SolveResult, Array]:
    """K warm-chained frames in ONE device program.

    The reference's core workload is the serial warm-started frame loop
    (main.cpp:131-140, previous solution as next initial guess at :139).
    Dispatching it one frame at a time costs a synchronous host round trip
    per frame (~68 ms on the remote-attached chip of 2026-07; not
    re-measured on a local host) against ~9 ms of device work —
    host-latency-bound by ~10x there (BASELINE.md E2E table). This runs the loop
    itself on device: frame 0 solves with the Eq. 4 initial guess (or the
    supplied seed), then ``lax.scan`` carries the solution through the
    remaining frames with the full ``while_loop`` inside the scan body —
    semantics identical to K separate solves by construction, one packed
    scalar fetch for the whole chain.

    The scan also carries each frame's loop-exit ``fitted == H @ f_final``
    into the next frame's setup (rescaled alongside the solution), so a
    warm frame's iteration loop streams the RTM exactly once per iteration
    with NO per-frame setup sweep — the reference pays a full ``Sgemv``
    setup per frame (sartsolver_cuda.cpp:185-189). ``fitted0`` seeds frame
    0's product when the caller chains from a previous result (same
    contract as ``_solve_normalized_batch_impl``).

    ``rescale[k]`` converts the carry between per-frame normalizations
    (``norm_{k-1}/norm_k``; ``rescale[0]`` rescales the incoming seed).
    Returns ``(SolveResult with a leading K axis, fitted [1, P_local] of
    the last frame)``; ``solution[-1]`` + the returned fitted are the
    device-resident warm start for a following chain.
    """
    impl = functools.partial(
        _solve_normalized_batch_impl,
        problem,
        opts=opts, axis_name=axis_name, voxel_axis=voxel_axis,
        return_fitted=True, _vmem_raised=_vmem_raised,
        tile_occupancy=tile_occupancy, operator_spec=operator_spec,
    )
    K = g.shape[0]
    if use_guess_first and fitted0 is not None:
        # mirror _solve_normalized_batch_impl's guard: a stale carried
        # product alongside a fresh Eq. 4 guess is a caller bug, not
        # something to drop silently
        raise ValueError(
            "fitted0 carries a warm start's forward projection; it cannot "
            "be combined with use_guess_first=True."
        )
    if use_guess_first:
        res0, fit0 = impl(
            g[0][None], msq[0:1], jnp.zeros_like(f0), use_guess=True
        )
    else:
        res0, fit0 = impl(
            g[0][None], msq[0:1], f0 * rescale[0].astype(f0.dtype),
            use_guess=False,
            fitted0=(None if fitted0 is None
                     else fitted0 * rescale[0].astype(fitted0.dtype)),
        )
    if K == 1:
        return res0, fit0

    def step(carry, xs):
        sol_c, fit_c = carry
        g_k, msq_k, r_k = xs
        res, fit = impl(
            g_k[None], msq_k[None], sol_c * r_k.astype(sol_c.dtype),
            use_guess=False, fitted0=fit_c * r_k.astype(fit_c.dtype),
        )
        out = SolveResult(
            res.solution[0], res.status[0], res.iterations[0],
            res.convergence[0],
        )
        return (res.solution, fit), out

    (_, fit_last), rest = lax.scan(
        step, (res0.solution, fit0), (g[1:], msq[1:], rescale[1:])
    )
    return SolveResult(
        jnp.concatenate([res0.solution, rest.solution], axis=0),
        jnp.concatenate([res0.status, rest.status]),
        jnp.concatenate([res0.iterations, rest.iterations]),
        jnp.concatenate([res0.convergence, rest.convergence]),
    ), fit_last


class _SweepContext:
    """The measurement-independent iteration machinery, shared between the
    batched solver core (:func:`_solve_normalized_batch_impl`) and the
    continuous-batching stepped core (:func:`sched_step_normalized`): the
    masks and inverse ray stats, the (possibly int8) projection closures,
    the Laplacian penalty, the fused-sweep resolution with its update
    closures, and :meth:`run_sweep` — one iteration's two RTM sweeps.

    Extracted so the stepped core runs *exactly* the ops the batched loop
    runs (same closures, same trace paths): retired-lane solutions must be
    byte-identical to the non-scheduled path at matched iteration counts
    (docs/PERFORMANCE.md §8), which only holds if there is one definition
    of the iteration math.
    """

    def __init__(self, problem: SARTProblem, opts: SolverOptions,
                 axis_name, voxel_axis, B: int, _vmem_raised: bool,
                 tile_occupancy=None, operator_spec=None):
        dtype = self.dtype = jnp.dtype(opts.dtype)
        rtm = self.rtm = problem.rtm
        self.opts = opts
        self.axis_name = axis_name
        self.voxel_axis = voxel_axis
        # Matrix-free mode (operators/implicit.py): the problem's rtm
        # leaf carries the packed [P_local, 6] ray table and the static
        # spec names the grid — the voxel extent comes from the spec,
        # never from the staged array. Factored mode (operators/
        # lowrank.py): the rtm leaf holds the sparse core S and the
        # skinny factors ride as the problem's trailing leaves; the spec
        # carries the static panel-skip predicate and the rank. None =
        # the dense contraction, traced exactly as before the operator
        # layer existed.
        self.lowrank = (
            operator_spec if isinstance(operator_spec, LowRankSpec)
            else None
        )
        self.implicit = (
            None if self.lowrank is not None else operator_spec
        )
        if self.lowrank is not None:
            if rtm.ndim != 2 or rtm.shape[1] != operator_spec.nvoxel:
                raise ValueError(
                    f"lowrank operator_spec expects the [P_local, "
                    f"{operator_spec.nvoxel}] sparse core as problem."
                    f"rtm, got shape {tuple(rtm.shape)} "
                    "(make_lowrank_problem)."
                )
            if problem.factor_u is None or problem.factor_v is None:
                raise ValueError(
                    "lowrank operator_spec given but the problem "
                    "carries no factor_u/factor_v leaves — build it "
                    "with make_lowrank_problem."
                )
            nvoxel = self.nvoxel = int(operator_spec.nvoxel)
        elif operator_spec is not None:
            if rtm.ndim != 2 or rtm.shape[1] != 6:
                raise ValueError(
                    f"implicit operator_spec given but problem.rtm has "
                    f"shape {tuple(rtm.shape)} — expected the packed "
                    "[P_local, 6] ray table (make_implicit_problem)."
                )
            nvoxel = self.nvoxel = int(operator_spec.nvoxel)
        else:
            nvoxel = self.nvoxel = rtm.shape[1]
        self.eps = _tiny(opts.log_epsilon, dtype)
        self.beta = jnp.asarray(opts.beta_laplace, dtype)
        self.problem = problem
        self.has_pen = problem.laplacian is not None
        if self.has_pen and operator_spec is not None:
            backend = (
                "factored (lowrank)" if self.lowrank is not None
                else "implicit (matrix-free)"
            )
            raise ValueError(
                f"beta_laplace smoothing is not supported by the "
                f"{backend} operator; drop the Laplacian or use a "
                "materialized RTM."
            )

        self.vmask = problem.ray_density > opts.ray_density_threshold  # [V]
        self.safe_dens = jnp.where(self.vmask, problem.ray_density, 1)
        self.inv_density = jnp.where(
            self.vmask, opts.relaxation / self.safe_dens, 0
        ).astype(dtype)
        lmask = problem.ray_length > opts.ray_length_threshold  # [P]
        self.inv_length = jnp.where(
            lmask, 1 / jnp.where(lmask, problem.ray_length, 1), 0
        ).astype(dtype)

        # In-solve ABFT integrity check (docs/RESILIENCE.md §8,
        # resilience/integrity.py): the identities sum(Hf) == rho.f and
        # sum(H^T w) == lambda.w hold exactly for the stored matrix, so a
        # per-iteration residual against an fp-derived tolerance detects
        # resident-RTM corruption / a bad MXU product the iteration it
        # happens. Python-gated: integrity=False traces byte-identically.
        self.integrity = bool(opts.integrity)
        if self.integrity and operator_spec is not None:
            if self.lowrank is not None:
                raise ValueError(
                    "integrity=True (in-solve ABFT) is not supported by "
                    "the factored (lowrank) operator: the checksum "
                    "tolerance model certifies a single stored-matrix "
                    "contraction, not the composed S + U V^T products. "
                    "Disable integrity or use a materialized RTM."
                )
            raise ValueError(
                "integrity=True (in-solve ABFT) is not supported by the "
                "implicit operator: the checksummed identities certify a "
                "STORED matrix against corruption, and the matrix-free "
                "projector stores none. Disable integrity or use a "
                "materialized RTM."
            )
        if self.integrity:
            from sartsolver_tpu.resilience.integrity import abft_tolerance

            # the compared reductions are GLOBAL psums, so the tolerance
            # must use the global reduction lengths — under shard_map
            # rtm.shape holds the per-shard block, which would tighten
            # the band ~sqrt(n_shards)x and let a clean large-pod solve
            # trip the check. lax.psum of a Python int is static at
            # trace time (the mesh axis size), so this stays a host float.
            n_pix = rtm.shape[0] * (
                int(lax.psum(1, axis_name)) if axis_name else 1
            )
            n_vox = nvoxel * (
                int(lax.psum(1, voxel_axis)) if voxel_axis else 1
            )
            self.abft_tol = abft_tolerance(
                opts.dtype, opts.rtm_dtype, n_pix, n_vox
            )
            self.dens_row = problem.ray_density.astype(dtype)[None, :]
            self.length_row = problem.ray_length.astype(dtype)[None, :]

        # int8-quantized storage: the iteration loop dequantizes codes
        # exactly inside the fused kernel; the handful of out-of-loop
        # projections below run as integer dots with per-row quantization
        # of the vector operand.
        self.is_int8 = rtm.dtype == jnp.int8
        if self.is_int8:
            if problem.rtm_scale is None:
                raise ValueError(
                    "int8 RTM needs SARTProblem.rtm_scale; build the "
                    "problem with make_problem(..., opts with "
                    "rtm_dtype='int8')."
                )
            self.scale = problem.rtm_scale.astype(dtype)

        # Factored operator: dequantize the skinny factors ONCE, here —
        # loop-invariant (O(r * (P + V)) elements, so holding them fp
        # costs nothing next to S), which keeps the iteration body free
        # of factor-sized converts (the lowrank_sweep audit pins this).
        if self.lowrank is not None:
            u, v = problem.factor_u, problem.factor_v
            if problem.factor_scale is not None:
                su = problem.factor_scale[0].astype(dtype)
                sv = problem.factor_scale[1].astype(dtype)
                u = u.astype(dtype) * su[None, :]
                v = v.astype(dtype) * sv[None, :]
            self.u = u.astype(dtype)
            self.v = v.astype(dtype)

        # Ordered-subsets cycle (docs/PERFORMANCE.md §9): per-subset ray
        # densities and masks. Subset t is the INTERLEAVED row set
        # ``t::os`` of this device's pixel rows (ops/fused_sweep.py
        # os_subset_rows — interleaving is what makes every subset sample
        # the full geometry; contiguous stripes of a spatially-coherent
        # RTM measure NO acceleration). Each subset's column sums — its
        # own rho — normalize that sub-step's update (normalizing by the
        # FULL rho would scale every sub-update by ~1/s and erase the
        # acceleration). A voxel a subset barely sees keeps the Eq. 6
        # masking per subset: the subset mask is the same absolute
        # threshold intersected with the global vmask, so no sub-step
        # ever updates a globally-masked voxel. Loop-invariant: XLA
        # hoists these out of the while body.
        self.os = int(opts.os_subsets)
        if self.os > 1:
            P_local = rtm.shape[0]
            if P_local % self.os:
                raise ValueError(
                    f"os_subsets={self.os} must divide the (per-shard, "
                    f"padded) pixel extent {P_local}."
                )
            if self.lowrank is not None:
                # same interleave on both terms: subset t's column sums
                # are the occupied-panel sums of S's rows t::os plus the
                # factor term's U-row subset against V^T
                dens_sub = lowrank_subset_density(
                    rtm, self.u, self.v, operator_spec, self.os,
                    scale=self.scale if self.is_int8 else None,
                    dtype=dtype, axis_name=axis_name,
                )
            elif operator_spec is not None:
                # same interleave (subset t = ray rows t::os), column
                # sums rebuilt panel-by-panel from the slab kernel
                dens_sub = implicit_subset_density(
                    rtm, operator_spec, self.os, dtype=dtype,
                    axis_name=axis_name,
                )
            else:
                # [P/os, os, V]; axis 1 is the subset index (rows t::os)
                stacked = rtm.reshape(P_local // self.os, self.os, nvoxel)
                if self.is_int8:
                    dens_sub = _psum(
                        self.scale[None, :]
                        * jnp.sum(
                            stacked, axis=0, dtype=jnp.int32
                        ).astype(dtype),
                        axis_name,
                    )
                else:
                    dens_sub = _psum(
                        jnp.sum(stacked, axis=0, dtype=dtype), axis_name
                    )
            self.vmask_sub = (  # [os, V]
                (dens_sub > opts.ray_density_threshold) & self.vmask[None, :]
            )
            self.inv_density_sub = jnp.where(
                self.vmask_sub,
                opts.relaxation / jnp.where(self.vmask_sub, dens_sub, 1),
                0,
            ).astype(dtype)

        # Block-sparse RTM mode (docs/PERFORMANCE.md §10): when the
        # options request it AND the caller supplied the RTM's static
        # tile-occupancy index (ops/sparse.py), the iteration sweep is
        # hosted on the voxel-panel scan with all-zero column panels'
        # dots skipped entirely — FLOPs/bytes scale with occupancy. The
        # index is per-RTM static state (hashable, jit-static), so the
        # skip pattern is baked at trace time and lanes/occupancies never
        # recompile. Resolution mirrors the fused contract: "auto"
        # declines quietly where the sparse sweep cannot engage, an
        # explicit numeric threshold raises with the actual reason.
        self.sparse = None
        self._sparse_gather = False
        self._sparse_occ_panels = None
        self._sparse_bs = 0
        sparse_eps = opts.sparse_epsilon()
        if sparse_eps is not None and operator_spec is not None:
            # the tile index skips stored-matrix panels; the implicit
            # projector stores none and the factored backend already
            # tile-thresholds its own sparse core — auto declines,
            # explicit raises (SolverOptions rejects explicit sparse +
            # lowrank at construction, so only implicit reaches here
            # explicitly)
            if opts.sparse_explicit():
                raise ValueError(
                    f"sparse_rtm='{opts.sparse_rtm}' requested but the "
                    "operator is implicit (matrix-free): there is no "
                    "stored matrix to tile-index. Use sparse_rtm='auto'/"
                    "off or a materialized RTM."
                )
            sparse_eps = None
        if sparse_eps is not None:
            pv = opts.fused_panel_voxels
            bs = pv or pick_panel_voxels(
                rtm.shape[0], nvoxel, rtm.dtype.itemsize, B
            )
            from sartsolver_tpu.ops.sparse import occupancy_matches

            reasons = []
            if tile_occupancy is None:
                reasons.append(
                    "no tile-occupancy index was supplied (build one at "
                    "ingest, or via models.sart.make_sparse_problem)"
                )
            if voxel_axis is not None:
                reasons.append(
                    "the voxel axis is sharded (per-shard column panels "
                    "map to different global panels, so the static skip "
                    "is not SPMD-uniform)"
                )
            if dtype != jnp.float32 or rtm.dtype not in (
                jnp.float32, jnp.bfloat16, jnp.int8
            ):
                reasons.append(
                    f"dtype={opts.dtype} / rtm dtype={rtm.dtype} (the "
                    "sparse panel sweep computes in fp32 over fp32/"
                    "bfloat16/int8 storage)"
                )
            if (opts.divergence_recovery and opts.logarithmic
                    and self.os == 1):
                # the OS cycle applies the guard's exponent in plain XLA,
                # so only the closure-hosted classic sweep is restricted
                reasons.append(
                    "divergence_recovery on the logarithmic solver (the "
                    "panel closures cannot carry the per-frame traced "
                    "exponent)"
                )
            if bs <= 0 or nvoxel % bs or not panel_available(
                rtm.shape[0], nvoxel, rtm.dtype.itemsize, B
            ):
                reasons.append(
                    f"RTM block {tuple(rtm.shape)} (batch {B}, panel "
                    f"{bs}) is not tile-aligned for the panel sweep"
                )
            elif tile_occupancy is not None and not occupancy_matches(
                tile_occupancy, nvoxel, bs
            ):
                reasons.append(
                    f"the occupancy index covers "
                    f"[{tile_occupancy.rows}, {tile_occupancy.cols}] and "
                    f"cannot drive {bs}-wide panels over this "
                    f"{nvoxel}-column block"
                )
            if reasons:
                if opts.sparse_explicit():
                    raise ValueError(
                        f"sparse_rtm='{opts.sparse_rtm}' requested but "
                        "the block-sparse sweep cannot engage: "
                        + "; ".join(reasons) + "."
                    )
            occ_panels = (
                tile_occupancy.col_panel_occupied(bs)
                if not reasons and tile_occupancy is not None else None
            )
            if occ_panels is not None and self.os > 1 and (
                int(occ_panels.sum()) > SPARSE_STATIC_UNROLL_MAX
            ):
                # the OS cycle's subset dots unroll per occupied panel
                # (no gather form there); past the unroll cap that would
                # bloat the traced program by orders of magnitude for
                # little skip benefit — decline instead
                reasons.append(
                    f"os_subsets > 1 with {int(occ_panels.sum())} "
                    "occupied panels exceeds SART_SPARSE_UNROLL_MAX="
                    f"{SPARSE_STATIC_UNROLL_MAX} (the subset cycle has "
                    "no gather fallback; raise the env or widen "
                    "fused_panel_voxels)"
                )
                if opts.sparse_explicit():
                    raise ValueError(
                        f"sparse_rtm='{opts.sparse_rtm}' requested but "
                        "the block-sparse sweep cannot engage: "
                        + "; ".join(reasons) + "."
                    )
            elif not reasons:
                tile_occupancy.verify()
                self.sparse = tile_occupancy
                self._sparse_bs = bs
                self._sparse_occ_panels = occ_panels
                n_occupied = int(occ_panels.sum())
                # gather-of-occupied-panels fallback: a huge occupied-
                # panel count would bloat the unrolled static-skip
                # program; the fori_loop form is bit-identical
                self._sparse_gather = (
                    self.os == 1 and n_occupied > SPARSE_STATIC_UNROLL_MAX
                )
                if self._sparse_gather:
                    self._sparse_panel_ids = jnp.asarray(
                        np.nonzero(occ_panels)[0].astype(np.int32)
                    )

        # Fused sweep: one HBM pass over the RTM per iteration instead of
        # two (ops/fused_sweep.py) — the Pallas kernel when the pixel
        # extent is whole on-device, the per-panel-psum scan ("panel")
        # when the pixel axis is sharded. The elementwise update closures
        # use Python float constants (Pallas kernels cannot capture traced
        # values; the panel scan shares the closures for exact path
        # parity). The OS cycle (os_subsets > 1) replaces the whole-matrix
        # sweep with the subset cycle (run_os_sweep) — plain-XLA subset
        # dots with the panel scan's int8 dequant idiom — so the fused
        # resolution is skipped there (SolverOptions rejects an explicit
        # 'on'/'interpret' with os_subsets > 1 at construction).
        if self.lowrank is not None:
            # The factored sweep is its own one-pass composition: the
            # occupied-panel dots over S plus two skinny factor matmuls
            # replace both the Pallas kernel and the dense two-matmul
            # path (SolverOptions already rejects an explicit
            # fused_sweep='on'/'interpret' with lowrank_rtm).
            if opts.fused_sweep in ("on", "interpret"):
                raise ValueError(
                    f"fused_sweep='{opts.fused_sweep}' requested but "
                    "the operator is factored (lowrank); the composed "
                    "S + U V^T sweep replaces the fused kernel. Use "
                    "fused_sweep='auto'/'off'."
                )
            fused = self.fused = None
            FUSED_ENGAGEMENT["last"] = (
                "lowrank-os" if self.os > 1 else "lowrank"
            )
        elif operator_spec is not None:
            # The implicit projector IS a one-pass panel sweep: it
            # rebuilds H panel-by-panel inside the loop, so the fused
            # machinery (which reads a stored matrix) never engages.
            # Auto composes silently; an explicit request fails loudly.
            if opts.fused_sweep in ("on", "interpret"):
                raise ValueError(
                    f"fused_sweep='{opts.fused_sweep}' requested but the "
                    "operator is implicit (matrix-free); the slab "
                    "projector replaces the fused sweep. Use "
                    "fused_sweep='auto'/'off'."
                )
            fused = self.fused = None
            FUSED_ENGAGEMENT["last"] = (
                "implicit-os" if self.os > 1 else "implicit"
            )
        elif self.os > 1:
            fused = self.fused = None
            FUSED_ENGAGEMENT["last"] = (
                "os-subset-sparse" if self.sparse is not None
                else "os-subset"
            )
        elif self.sparse is not None:
            # the sparse panel scan replaces both the Pallas kernel and
            # the two-matmul path (SolverOptions already rejects an
            # explicit fused_sweep='on'/'interpret' with sparse_rtm)
            fused = self.fused = "sparse"
            FUSED_ENGAGEMENT["last"] = (
                "sparse-gather" if self._sparse_gather else "sparse-panel"
            )
        else:
            fused = self.fused = _resolve_fused(
                opts, axis_name, rtm, B, vmem_raised=_vmem_raised
            )
            FUSED_ENGAGEMENT["last"] = fused or "off"
        if (self.is_int8 and fused is None and self.os == 1
                and self.lowrank is None):
            # (the factored path is exempt: its panel dots dequantize S
            # exactly in-loop like the panel scan, and the factors were
            # dequantized once above — no per-iteration requantization)
            # The two-matmul loop would have to re-quantize w/f every
            # iteration (extra error) or dequantize the matrix (4x the
            # memory the user chose int8 to avoid) — int8 storage is a
            # fused-sweep feature. Both sharding layouts fuse (Pallas
            # kernel on unsharded/voxel-sharded pixels, panel scan on
            # sharded pixels), so resolving off here means the
            # mode/backend/shape gates declined, not the mesh.
            raise ValueError(
                "rtm_dtype='int8' requires the fused sweep, but it "
                f"resolved off (fused_sweep='{opts.fused_sweep}'). Use "
                "fused_sweep='on'/'interpret' (or 'auto' on TPU with "
                "tile-aligned shapes) — pixel- and voxel-sharded meshes "
                "both fuse — or fp32/bfloat16 storage."
            )
        # Geometric relaxation schedule alpha_k = alpha * decay^k. decay
        # is a Python float, so `scheduled` is a trace-time constant: the
        # default (decay == 1) traces byte-identical HLO to the
        # unscheduled solver.
        self.decay = float(opts.relaxation_decay)
        self.scheduled = self.decay != 1.0
        if fused is not None:
            alpha = float(opts.relaxation)
            # same clamping rule as the unfused path's `eps` (_tiny leaves
            # log_epsilon <= 0 alone), so fused and unfused log solves
            # agree for every log_epsilon value; computed in Python
            # because Pallas update closures need literal constants
            eps_f = float(opts.log_epsilon)
            if 0.0 < eps_f < MIN_POSITIVE:
                eps_f = MIN_POSITIVE
            scheduled = self.scheduled
            # int8 variants: the raw kernel bp is in integer-code space;
            # the per-voxel scale panel (aux 0) dequantizes it inside the
            # update, and the same panel pre-scales the forward operand
            # (fwd_scale=0) so ``fitted`` comes out in physical units.
            if opts.logarithmic:
                self.vm32 = self.vmask.astype(dtype)[None, :]

                # scheduled log solves pass alpha_k as an extra [b_i, V]
                # aux panel (a traced value cannot be captured by the
                # kernel closure); fixed-alpha solves keep the literal
                # exponent
                def _log_update(f_p, bp_p, vm_p, obs_p, *rest):
                    if scheduled:
                        a_p, *pen_p = rest
                    else:
                        pen_p = rest
                    fit = bp_p * vm_p
                    ratio = (obs_p + eps_f) / (fit + eps_f)
                    if scheduled:
                        ratio = ratio ** a_p
                    elif alpha != 1.0:
                        ratio = ratio ** alpha
                    return f_p * ratio * jnp.exp(-pen_p[0]) if pen_p else f_p * ratio

                if self.is_int8:
                    def update_fn(f_p, bp_p, s_p, vm_p, obs_p, *rest):
                        return _log_update(f_p, bp_p * s_p, vm_p, obs_p, *rest)
                else:
                    update_fn = _log_update
            else:

                def _lin_update(f_p, bp_p, invd_p, *pen_p):
                    upd = f_p + invd_p * bp_p
                    if pen_p:
                        upd = upd - pen_p[0]
                    return jnp.maximum(upd, 0)

                if self.is_int8:
                    def update_fn(f_p, bp_p, s_p, invd_p, *pen_p):
                        return _lin_update(f_p, bp_p * s_p, invd_p, *pen_p)
                else:
                    update_fn = _lin_update
            self.update_fn = update_fn

    def bp_any(self, w_):
        """LOCAL ``H^T w`` on whatever operator the problem carries —
        the single back-projection seam every core path routes through
        (the caller psums over the pixel axis, identically for every
        backend)."""
        if self.lowrank is not None:
            return lowrank_back(
                self.rtm, self.u, self.v, w_, self.lowrank,
                scale=self.scale if self.is_int8 else None,
                accum_dtype=self.dtype,
            )
        if self.implicit is not None:
            return implicit_back(self.rtm, w_, self.implicit,
                                 accum_dtype=self.dtype)
        if self.is_int8:
            return int8_back_project(self.rtm, self.scale, w_,
                                     accum_dtype=self.dtype)
        return back_project(self.rtm, w_, accum_dtype=self.dtype)

    def fp_any(self, f_):
        """``H f`` on whatever operator the problem carries (pre-voxel-
        psum under 2-D meshes) — the forward-projection seam."""
        if self.lowrank is not None:
            return lowrank_forward(
                self.rtm, self.u, self.v, f_, self.lowrank,
                scale=self.scale if self.is_int8 else None,
                accum_dtype=self.dtype,
            )
        if self.implicit is not None:
            return implicit_forward(self.rtm, f_, self.implicit,
                                    accum_dtype=self.dtype)
        if self.is_int8:
            return int8_forward_project(self.rtm, self.scale, f_,
                                        accum_dtype=self.dtype)
        return forward_project(self.rtm, f_, accum_dtype=self.dtype)

    @jax.named_scope(SCOPE_SWEEP)
    def loop_fp(self, f_):
        """An iteration's own forward sweep ``H f``, voxel-axis reduced:
        the unfused paths' second RTM read, and the log solver's
        projection of the extrapolated point."""
        return _psum(self.fp_any(f_), self.voxel_axis)

    @jax.named_scope(SCOPE_LAPLACIAN)
    def compute_penalty(self, x):  # x: [B, V_local] (f, or log f — log variant)
        """``beta * L @ x`` for this device's voxel block.

        With a :class:`ShardedLaplacian` (2-D mesh driver) the penalty is
        halo-exchanged: block-diagonal triplets read only the local block
        (as shifted diagonals where they lie on few, as a grid stencil
        does) and boundary values travel in a compact export table — no
        ``[B, V_global]`` all_gather lives in the loop (VERDICT r2 weak #1).
        A plain :class:`LaplacianCOO` (single shard) indexes x directly.
        """
        lap = self.problem.laplacian
        if isinstance(lap, ShardedLaplacian):
            return self.beta * sharded_penalty(lap, x, self.voxel_axis)
        if self.voxel_axis is not None and lap is not None:
            with jax.named_scope(SCOPE_EXCHANGE):
                x = lax.all_gather(x, self.voxel_axis, tiled=True, axis=1)
        return self.beta * jax.vmap(
            lambda xb: coo_matvec(lap, xb, self.nvoxel)
        )(x)

    def make_obs(self, g, meas_mask):
        """Log-variant observation back-projection (one RTM read; computed
        once per measurement, outside the iteration loop)."""
        obs = _psum(
            self.bp_any(jnp.where(meas_mask, g, 0) * self.inv_length),
            self.axis_name,
        )
        return jnp.where(self.vmask[None, :], obs, 0)

    def make_obs_sub(self, g, meas_mask):
        """Log-variant per-subset observation back-projections for the OS
        cycle: ``[B, os, V_local]``, subset t (rows ``t::os``) masked by
        its own vmask. Computed once per measurement outside the iteration
        loop (one full RTM read in subset dots), like :meth:`make_obs`."""
        scale = self.scale if self.is_int8 else None
        outs = []
        for t in range(self.os):  # setup-time unroll, static subset index
            panel = os_subset_rows(self.rtm, t, self.os)
            g_t = os_subset_pixels(g, t, self.os)
            m_t = os_subset_pixels(meas_mask, t, self.os)
            il_t = os_subset_pixels(self.inv_length, t, self.os)[None, :]
            w_t = jnp.where(m_t, g_t, 0) * il_t
            if self.lowrank is not None:
                # subset t of S + U V^T is S's rows t::os plus U's rows
                # t::os against V^T — os_subset_rows slices both (S's
                # int8 codes come back bf16, the panel dots' dequant
                # idiom; the scales still apply inside lowrank_back)
                obs_t = _psum(
                    lowrank_back(
                        panel, os_subset_rows(self.u, t, self.os),
                        self.v, w_t, self.lowrank,
                        scale=scale, accum_dtype=self.dtype,
                    ),
                    self.axis_name,
                )
            elif self.implicit is not None:
                # the subset's ray rows drive the same slab kernel —
                # os_subset_rows slices [P, 6] as readily as [P, V]
                obs_t = _psum(
                    implicit_back(panel, w_t, self.implicit,
                                  accum_dtype=self.dtype),
                    self.axis_name,
                )
            else:
                obs_t = os_subset_back(
                    panel, w_t, scale, axis_name=self.axis_name,
                )
            outs.append(jnp.where(self.vmask_sub[t][None, :], obs_t, 0))
        return jnp.stack(outs, axis=1)

    @jax.named_scope(SCOPE_SWEEP)
    def run_os_sweep(self, f, dk, ascale, g, meas_mask, obs_sub):
        """(f_upd, fitted_upd): one OUTER iteration of the ordered-subsets
        cycle (docs/PERFORMANCE.md §9) — ``os_subsets`` sub-updates, each
        against one interleaved pixel-row subset (rows ``t::os``) with a
        FRESH subset residual (subset t sees the iterate subsets 0..t-1
        already updated; that compounding is the OS acceleration), then
        one full forward projection of the final iterate so the
        convergence metric and the warm-start carry stay exact
        (``fitted_upd == H @ f_upd``, this device's rows, pre-voxel-psum
        like the fused paths).

        ``dk``/``ascale`` compose exactly as in :meth:`run_sweep` (the
        documented relaxation precedence: relaxation * decay^k * ascale;
        the subset's own inverse density carries the base relaxation for
        the linear update). The Laplacian penalty is re-evaluated per
        sub-step at the current iterate and scaled by 1/os_subsets, so
        one outer iteration applies the classic iteration's full
        regularization strength, distributed over the cycle. The ABFT
        back-projection checksum is not folded into sub-steps (that would
        add os_subsets collectives per iteration past the audited
        budget); the outer-level sum(Hf) == rho.f check still runs on the
        exact full projection below.
        """
        opts = self.opts
        dtype = self.dtype
        scale = self.scale if self.is_int8 else None
        pen_scale = 1.0 / self.os
        # Block-sparse composition (docs/PERFORMANCE.md §10): with the
        # tile index resolved, every subset dot decomposes over voxel
        # panels and skips the all-zero ones — the occupancy is a COLUMN
        # property, so a panel empty in the full matrix is empty in
        # every interleaved row subset. Collective counts are unchanged
        # (sparse_os_back psums the reassembled [B, V] vector once).
        occ_sp, bs_sp = self._sparse_occ_panels, self._sparse_bs
        if self.sparse is None:
            occ_sp = None
        if occ_sp is not None:
            from sartsolver_tpu.ops.fused_sweep import _sparse_trace_obs

            _sparse_trace_obs(
                self.sparse, len(occ_sp), int((~occ_sp).sum()), bs_sp,
                "sparse_os",
            )

        def subset_fwd(panel, x, t):
            if self.lowrank is not None:
                # subset t of the composed operator: S's rows t::os
                # (the panel) plus U's rows t::os against V^T
                return lowrank_forward(
                    panel, os_subset_rows(self.u, t, self.os), self.v,
                    x, self.lowrank, scale=scale, accum_dtype=dtype,
                )
            if self.implicit is not None:
                # `panel` holds the subset's ray rows; the slab kernel
                # projects any ray set
                return implicit_forward(panel, x, self.implicit,
                                        accum_dtype=dtype)
            if occ_sp is not None:
                return sparse_os_forward(
                    panel, x, scale, occ_panels=occ_sp, panel_voxels=bs_sp
                )
            return os_subset_forward(panel, x, scale)

        def subset_back(panel, w_, t):
            if self.lowrank is not None:
                return _psum(
                    lowrank_back(
                        panel, os_subset_rows(self.u, t, self.os),
                        self.v, w_, self.lowrank, scale=scale,
                        accum_dtype=dtype,
                    ),
                    self.axis_name,
                )
            if self.implicit is not None:
                return _psum(
                    implicit_back(panel, w_, self.implicit,
                                  accum_dtype=dtype),
                    self.axis_name,
                )
            if occ_sp is not None:
                return sparse_os_back(
                    panel, w_, scale, occ_panels=occ_sp,
                    panel_voxels=bs_sp, axis_name=self.axis_name,
                )
            return os_subset_back(panel, w_, scale,
                                  axis_name=self.axis_name)

        def substep(t, f):
            panel = os_subset_rows(self.rtm, t, self.os)
            g_t = os_subset_pixels(g, t, self.os)
            m_t = os_subset_pixels(meas_mask, t, self.os)
            il_t = os_subset_pixels(self.inv_length, t, self.os)[None, :]
            vm_t = lax.dynamic_index_in_dim(
                self.vmask_sub, t, axis=0, keepdims=False
            )[None, :]
            fitted_t = _psum(subset_fwd(panel, f, t), self.voxel_axis)
            if opts.logarithmic:
                w = jnp.where(m_t, fitted_t, 0) * il_t
                fit = subset_back(panel, w, t)
                fit = jnp.where(vm_t, fit, 0)
                obs_t = lax.dynamic_index_in_dim(
                    obs_sub, t, axis=1, keepdims=False
                )
                exponent = jnp.asarray(opts.relaxation, dtype)
                if self.scheduled:
                    exponent = exponent * dk
                if ascale is not None:
                    exponent = exponent * ascale[:, None]
                ratio = ((obs_t + self.eps) / (fit + self.eps)) ** exponent
                f_new = f * ratio
                if self.has_pen:
                    pen = self.compute_penalty(jnp.log(f)) * pen_scale
                    f_new = f_new * jnp.exp(-pen)
                return f_new
            w = jnp.where(m_t, g_t - fitted_t, 0) * il_t
            if self.scheduled:
                w = w * dk
            if ascale is not None:
                w = w * ascale[:, None]
            bp = subset_back(panel, w, t)
            invd_t = lax.dynamic_index_in_dim(
                self.inv_density_sub, t, axis=0, keepdims=False
            )[None, :]
            upd = f + invd_t * bp
            if self.has_pen:
                upd = upd - self.compute_penalty(f) * pen_scale
            return jnp.maximum(upd, 0)

        f_upd = lax.fori_loop(0, self.os, substep, f)
        # Full forward projection of the final iterate — EXACT (int8:
        # subset-wise dequantized dots, never int8_forward_project's
        # quantized-vector approximation, which would perturb the ABFT
        # sum(Hf) == rho.f identity and the warm-start carry in-loop).
        # The subset results interleave back: row i = q * os + t lives at
        # parts[t][:, q], i.e. stack on a trailing subset axis + reshape.
        if self.is_int8:
            parts = [
                subset_fwd(os_subset_rows(self.rtm, t, self.os), f_upd, t)
                for t in range(self.os)
            ]
            fitted_upd = jnp.stack(parts, axis=2).reshape(
                f_upd.shape[0], self.rtm.shape[0]
            )
        elif occ_sp is not None:
            # panel-decomposed full projection: same occupancy skips as
            # the sub-steps, so the exact-projection contract holds on
            # exactly the operator the loop multiplies by
            fitted_upd = sparse_os_forward(
                self.rtm, f_upd, None, occ_panels=occ_sp,
                panel_voxels=bs_sp,
            )
        else:
            # dense two-matmul and implicit operators share the seam
            # (trace-identical to the direct dense call)
            fitted_upd = self.fp_any(f_upd)
        return f_upd, fitted_upd

    def extrapolate(self, f, f_prev, tk, mom_floor):
        """(y, beta, t_next): the Nesterov/FISTA extrapolation shared by
        the batched and stepped cores — one definition, like
        :meth:`run_sweep` (docs/PERFORMANCE.md §9). Additive for the
        linear solver; multiplicative (log-space, positivity-preserving,
        floored against fp-underflowed iterates) for the log solver."""
        t_next = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * tk * tk))
        beta = ((tk - 1.0) / t_next).astype(self.dtype)[:, None]
        if self.opts.logarithmic:
            y = jnp.maximum(
                f * (jnp.maximum(f, mom_floor)
                     / jnp.maximum(f_prev, mom_floor)) ** beta,
                mom_floor,
            )
        else:
            y = f + beta * (f - f_prev)
        return y, beta, t_next

    def momentum_tk(self, y, f_new, f, t_next, reset):
        """Next FISTA t_k: gradient-based adaptive restart (O'Donoghue &
        Candes — the update moved against the extrapolation direction)
        OR'd with the caller's reset mask (divergence-recovery rollback,
        SDC freeze); restart resets only the momentum state, never the
        relaxation product (the §9 precedence contract)."""
        rs = _psum(jnp.sum((y - f_new) * (f_new - f), axis=1),
                   self.voxel_axis) > 0
        return jnp.where(rs | reset, 1.0, t_next).astype(self.dtype)

    def run_fused(self, w, f, aux):
        if self.is_int8:
            aux = [self.scale[None, :]] + aux
        if self.fused == "sparse":
            # block-sparse voxel-panel scan (docs/PERFORMANCE.md §10):
            # same update closures; all-zero column panels' dots are
            # skipped (statically, or via the gather fallback when the
            # occupied-panel count would bloat the unrolled program)
            if self._sparse_gather:
                from sartsolver_tpu.ops.fused_sweep import _sparse_trace_obs

                occ_p = self._sparse_occ_panels
                _sparse_trace_obs(
                    self.sparse, len(occ_p), int((~occ_p).sum()),
                    self._sparse_bs, "sparse_gather",
                )
                if self.axis_name is not None:
                    # the gather loop issues one bp psum per occupied
                    # panel, exactly like the static scan — keep the
                    # collective-plan observability identical
                    from sartsolver_tpu.obs import metrics as _obs_metrics

                    _obs_metrics.get_registry().counter(
                        "collectives_planned_total", collective="psum",
                        site="sparse_panel_bp",
                    ).inc(int(occ_p.sum()))
                return sparse_gather_sweep(
                    self.rtm, w, f, aux, self.update_fn,
                    panel_ids=self._sparse_panel_ids,
                    panel_voxels=self._sparse_bs,
                    axis_name=self.axis_name,
                    fwd_scale=0 if self.is_int8 else None,
                )
            return sparse_panel_sweep(
                self.rtm, w, f, aux, self.update_fn,
                occupancy=self.sparse, axis_name=self.axis_name,
                fwd_scale=0 if self.is_int8 else None,
                panel_voxels=self._sparse_bs,
            )
        if self.fused == "panel":
            # pixel-sharded voxel-panel scan: same update closures, but
            # the back-projection panel arrives already psummed over the
            # pixel axis and the returned fitted holds this device's
            # local rows
            return sharded_panel_sweep(
                self.rtm, w, f, aux, self.update_fn,
                axis_name=self.axis_name,
                fwd_scale=0 if self.is_int8 else None,
                panel_voxels=self.opts.fused_panel_voxels,
            )
        return fused_sweep(self.rtm, w, f, aux, self.update_fn,
                           fwd_scale=0 if self.is_int8 else None,
                           interpret=self.fused == "interpret")

    def abft_residual(self, s_a, s_b):
        """[B] bool: |s_a - s_b| beyond the dtype tolerance (or non-finite
        — a NaN residual means a non-finite product, which is corruption
        or divergence either way; the guard's own check still classifies
        divergence first when both layers are armed)."""
        err = jnp.abs(s_a - s_b)
        ref = jnp.maximum(jnp.abs(s_a), jnp.abs(s_b)) + 1.0
        return ~(err <= self.abft_tol * ref)

    def abft_check(self, fsq_local, fitted_new, f_new, bp_chk, done,
                   axis_name, voxel_axis):
        """(fsq, tripped): the folded ABFT reductions shared by both
        iteration cores (docs/RESILIENCE.md §8). The forward checksum
        sum(Hf) stacks with the metric's ||Hf||^2 (and, unfused, with
        lambda.w) into ONE pixel-axis psum — the per-iteration collective
        budget stays at the audited count (``sharded_integrity_batch``
        golden). The rho.f side reduces over the voxel axis — a no-op on
        1-D pixel meshes, one extra scalar-stack psum on 2-D meshes.
        ``tripped`` is already masked to live (``~done``) frames."""
        pix_parts = [fsq_local, jnp.sum(fitted_new, axis=1)]
        if bp_chk is not None:
            pix_parts.append(bp_chk[1])  # lambda_local . w
        red = _psum(jnp.stack(pix_parts), axis_name)
        fsq, s_fwd = red[0], red[1]
        vox_parts = [jnp.sum(f_new * self.dens_row, axis=1)]
        if bp_chk is not None:
            vox_parts.append(bp_chk[0])  # sum_v(H^T w) local
        vred = _psum(jnp.stack(vox_parts), voxel_axis)
        tripped = self.abft_residual(s_fwd, vred[0])
        if bp_chk is not None:
            tripped = tripped | self.abft_residual(vred[1], red[2])
        return fsq, (~done) & tripped

    @jax.named_scope(SCOPE_SWEEP)
    def run_sweep(self, f, fitted, penalty, dk, ascale, g, meas_mask, obs):
        """(f_upd, fitted_upd or None, bp_chk): the iteration's two RTM
        sweeps. ``dk`` is the schedule factor decay^k — a traced scalar in
        the batched core, a per-lane ``[B, 1]`` column in the stepped core
        (lanes age independently there), 1/None when the schedule is off
        (never materialized); ``ascale`` is the divergence guard's
        per-frame [B] relaxation scale (None when the guard is off).
        ``obs`` is :meth:`make_obs`'s result (log variant only).

        ``bp_chk`` carries the ABFT back-projection checksum operands
        (integrity on, two-matmul path only — the fused kernels never
        materialize the bp product): ``(sum_v(H^T w) local [B],
        lambda_local . w [B])``; the caller reduces the first over the
        voxel axis and folds the second into the pixel-axis convergence
        psum, then compares (sum(H^T w) == lambda . w holds exactly).
        None when integrity is off or the sweep is fused."""
        opts = self.opts
        dtype = self.dtype
        if opts.logarithmic:
            w = jnp.where(meas_mask, fitted, 0) * self.inv_length
            if self.fused is not None:
                aux = [self.vm32, obs]
                if self.scheduled:
                    a_k = jnp.asarray(opts.relaxation, dtype) * dk
                    if jnp.ndim(a_k) == 0:
                        aux.append(jnp.full((1, self.nvoxel), a_k, dtype))
                    else:  # per-lane schedule factor: [B, 1] -> [B, V]
                        aux.append(jnp.broadcast_to(
                            a_k.astype(dtype), (f.shape[0], self.nvoxel)
                        ))
                f_upd, fitted_upd = self.run_fused(
                    w, f, aux + ([penalty] if self.has_pen else [])
                )
                return f_upd, fitted_upd, None
            fit = _psum(self.bp_any(w), self.axis_name)
            bp_chk = None
            if self.integrity:
                # checksum the RAW psummed product (before the vmask zeroes
                # masked voxels — the identity holds for the full H^T w)
                bp_chk = (jnp.sum(fit, axis=1),
                          jnp.sum(self.length_row * w, axis=1))
            fit = jnp.where(self.vmask[None, :], fit, 0)
            exponent = jnp.asarray(opts.relaxation, dtype)
            if self.scheduled:
                exponent = exponent * dk
            if ascale is not None:
                # per-frame guard scale enters the multiplicative update
                # through the exponent: ratio ** (alpha * ascale_b)
                exponent = exponent * ascale[:, None]
            ratio = ((obs + self.eps) / (fit + self.eps)) ** exponent
            return f * ratio * jnp.exp(-penalty), None, bp_chk
        w = jnp.where(meas_mask, g - fitted, 0) * self.inv_length
        if self.scheduled:
            # the linear update is linear in w, so alpha_k = alpha * dk
            # folds into the pixel weights (inv_density keeps the base
            # alpha) — the same fold for the fused and two-matmul paths
            w = w * dk
        if ascale is not None:
            # same fold for the guard's per-frame scale (exact when 1.0)
            w = w * ascale[:, None]
        if self.fused is not None:
            f_upd, fitted_upd = self.run_fused(
                w, f,
                [self.inv_density[None, :]]
                + ([penalty] if self.has_pen else [])
            )
            return f_upd, fitted_upd, None
        bp = _psum(self.bp_any(w), self.axis_name)
        bp_chk = None
        if self.integrity:
            bp_chk = (jnp.sum(bp, axis=1),
                      jnp.sum(self.length_row * w, axis=1))
        return jnp.maximum(
            f + self.inv_density[None, :] * bp - penalty, 0
        ), None, bp_chk


def _solve_normalized_batch_impl(
    problem: SARTProblem,
    g: Array,
    msq: Array,
    f0: Array,
    *,
    opts: SolverOptions,
    axis_name=None,
    voxel_axis=None,
    use_guess: bool,
    fitted0: Optional[Array] = None,
    return_fitted: bool = False,
    _vmem_raised: bool = False,
    tile_occupancy=None,
    operator_spec=None,
) -> "SolveResult | Tuple[SolveResult, Array]":
    dtype = jnp.dtype(opts.dtype)
    B = g.shape[0]

    kit = _SweepContext(problem, opts, axis_name, voxel_axis, B,
                        _vmem_raised, tile_occupancy=tile_occupancy,
                        operator_spec=operator_spec)
    vmask, safe_dens = kit.vmask, kit.safe_dens
    bp_any, fp_any = kit.bp_any, kit.fp_any
    meas_mask = g >= 0  # [B, P]

    if fitted0 is not None and use_guess:
        raise ValueError(
            "fitted0 carries a warm start's forward projection; it cannot "
            "be combined with use_guess=True (the Eq. 4 guess is computed "
            "here, so its projection must be too)."
        )
    if use_guess:
        # f0 = H^T g / rho on unmasked voxels (Eq. 4; sartsolver.cpp:144-159);
        # the device path excludes negative measurements (sart_kernels.cu:34),
        # the CPU-parity profile does not (sartsolver.cpp:153).
        g_guess = jnp.where(g > 0, g, 0) if opts.mask_negative_guess else g
        accum = _psum(bp_any(g_guess), axis_name)
        f0 = jnp.where(vmask[None, :], accum / safe_dens[None, :], 0)
    if fitted0 is None or opts.logarithmic:
        # Linear carried warm starts (fitted0 supplied) skip this floor:
        # the floor guards arbitrary user seeds, while a carried start is
        # this solver's own loop-exit solution, and flooring it would break
        # the exact ``fitted0 == H @ f0`` consistency of the carried pair
        # (shifting near-stall stop iterations for nothing) — the linear
        # update handles exact zeros fine (additive, clamped at 0). The
        # LOG variant keeps the full floor even for carried starts: its
        # multiplicative update can drive a voxel toward fp32 underflow,
        # and entering at 1e-38 instead of 1e-7 would put ``log(0) = -inf``
        # a few shrinking iterations away; the resulting (f0, fitted0)
        # inconsistency is bounded by ``floor * ||H||_col`` on iteration
        # 1's residual only (the loop recomputes fitted every iteration).
        if opts.guess_floor > 0:
            # CUDA path floors *any* starting solution at 1e-7 for both
            # variants (sartsolver_cuda.cpp:180); CPU log path floors at
            # 1e-100 (sartsolver.cpp:263); CPU linear path does not floor.
            f0 = jnp.maximum(f0, _tiny(opts.guess_floor, dtype))
        if opts.logarithmic:
            # The log path must floor unconditionally (both reference
            # backends do): a zero voxel would give log(0) = -inf in the
            # penalty and can never recover under the multiplicative update.
            f0 = jnp.maximum(
                f0, _tiny(max(opts.guess_floor, opts.log_epsilon), dtype)
            )
    f0 = f0.astype(dtype)

    if fitted0 is None:
        fitted0 = _psum(fp_any(f0), voxel_axis)
    else:
        # Warm-start carry: the previous solve's loop exited with exactly
        # ``fitted == H @ f_final``, and a warm start is a scalar rescale of
        # ``f_final``, so the caller rescales that product instead of paying
        # this frame's setup sweep — one fewer full HBM read of the RTM per
        # warm frame. Linear: the skipped guess floor above keeps the
        # (f0, fitted0) pair exactly consistent (rescale reassociation
        # aside, ~1 ulp). Log: the kept floor bounds the inconsistency at
        # ``floor * ||H||_col`` on iteration 1's residual (see above).
        fitted0 = fitted0.astype(dtype)

    tol = jnp.asarray(opts.conv_tolerance, dtype)
    msq = jnp.asarray(msq, dtype)

    if opts.logarithmic:
        obs = (kit.make_obs_sub(g, meas_mask) if kit.os > 1
               else kit.make_obs(g, meas_mask))
    else:
        obs = None

    # Convergence accelerators (docs/PERFORMANCE.md §9), both Python-gated:
    # the default path (os_subsets=1, momentum off) traces byte-identically
    # to the unaccelerated solver — no extra carries, no extra ops.
    momentum = opts.momentum != "off"
    carry_fit = _momentum_carries_fitted(opts)
    mom_n = 3 if carry_fit else 2
    os_cycle = kit.os > 1
    mom_floor = (_tiny(max(opts.log_epsilon, 1e-30), dtype)
                 if (momentum and opts.logarithmic) else None)

    # In-solve divergence recovery (docs/RESILIENCE.md): with R > 0 the
    # loop carries a per-frame relaxation scale, a recovery counter and a
    # diverged flag; an iteration whose residual metric goes non-finite or
    # explodes rolls the frame back to its entering state (the rollback
    # target is simply the carry — the candidate is discarded before it is
    # ever stored), halves its relaxation scale, and retries. After R
    # recoveries the frame freezes with status DIVERGED, holding its last
    # finite iterate, while the rest of the batch continues. R == 0 traces
    # the original program byte-for-byte (every guard op is skipped at
    # Python level), so goldens/parity are untouched by default.
    recovery = int(opts.divergence_recovery)
    explode = float(opts.divergence_threshold)
    integ = kit.integrity

    def body(carry):
        if integ:
            carry, sdc = carry[:-1], carry[-1]
        if momentum:
            mom = carry[-mom_n:]
            carry = carry[:-mom_n]
            if carry_fit:
                f_prev, fitted_prev, tk = mom
            else:
                f_prev, tk = mom
        if recovery:
            f, fitted, conv_prev, it, done, iters, ascale, recov, div = carry
        else:
            f, fitted, conv_prev, it, done, iters = carry
            ascale = None
        # Nesterov/FISTA extrapolation: the sweep runs AT the extrapolated
        # point y (additive linear — y may dip below 0, standard FISTA,
        # the update's clamp restores feasibility of x); the carry always
        # holds the post-update iterate x_k, never y — so the divergence
        # guard's rollback target is never an extrapolated iterate, by
        # construction.
        if momentum:
            y, beta, t_next = kit.extrapolate(f, f_prev, tk, mom_floor)
            base = y
        else:
            base = f
        dk = (jnp.asarray(kit.decay, dtype) ** it.astype(dtype)
              if kit.scheduled else None)
        if os_cycle:
            f_upd, fitted_upd = kit.run_os_sweep(base, dk, ascale, g,
                                                 meas_mask, obs)
            bp_chk = None
        else:
            if momentum:
                if opts.logarithmic:
                    # no linearity to exploit — one forward projection of
                    # the extrapolated point per iteration
                    fitted_base = kit.loop_fp(y)
                else:
                    # H y = H f + beta (H f - H f_prev), exact: the
                    # extrapolated residual costs no RTM read
                    fitted_base = fitted + beta * (fitted - fitted_prev)
            else:
                fitted_base = fitted
            if opts.logarithmic:
                penalty = kit.compute_penalty(jnp.log(base))
            else:
                penalty = kit.compute_penalty(base)
            f_upd, fitted_upd, bp_chk = kit.run_sweep(
                base, fitted_base, penalty, dk, ascale, g, meas_mask, obs
            )

        f_new = jnp.where(done[:, None], f, f_upd)  # converged frames freeze
        if fitted_upd is not None:
            fitted_new = jnp.where(
                done[:, None], fitted, _psum(fitted_upd, voxel_axis)
            )
        else:
            fitted_new = kit.loop_fp(f_new)
        if opts.precise_convergence:
            fsq_local = _sumsq_precise(fitted_new, dtype)
        else:  # the reference CUDA path's fp32 dot (sartsolver_cuda.cpp:253)
            fsq_local = jnp.sum(fitted_new * fitted_new, axis=1)
        if integ:
            fsq, tripped = kit.abft_check(fsq_local, fitted_new, f_new,
                                          bp_chk, done, axis_name,
                                          voxel_axis)
        else:
            fsq = _psum(fsq_local, axis_name)
        conv = (msq - fsq) / msq
        if integ and recovery:
            # a non-finite checksum trips the ABFT compare vacuously, but
            # that signature belongs to the divergence guard — rollback /
            # DIVERGED, not quarantine (abft_residual's contract: the
            # guard classifies divergence first when both layers are on)
            tripped = tripped & (jnp.isfinite(fsq) & jnp.isfinite(conv))
        if integ:
            # a tripped frame FREEZES on its entering state — the last
            # iterate whose checksums were consistent; the host escalation
            # (resilience/integrity.py) takes it from there
            f_new = jnp.where(tripped[:, None], f, f_new)
            fitted_new = jnp.where(tripped[:, None], fitted, fitted_new)
            conv = jnp.where(tripped, conv_prev, conv)
            sdc = sdc | tripped
        if recovery:
            # the candidate update is judged BEFORE it is stored: a bad
            # frame keeps its entering (f, fitted, conv) — the rollback —
            # so the carry always holds the last good iterate
            bad = (~done) & (
                ~(jnp.isfinite(fsq) & jnp.isfinite(conv))
                | (fsq > explode * jnp.maximum(msq, 1.0))
            )
            if integ:
                # finite-mismatch SDC outranks the rollback ladder (an
                # explode-test coincidence stays classified as SDC)
                bad = bad & ~tripped
            exhausted = bad & (recov >= recovery)
            f_new = jnp.where(bad[:, None], f, f_new)
            fitted_new = jnp.where(bad[:, None], fitted, fitted_new)
            conv = jnp.where(bad, conv_prev, conv)
            ascale = jnp.where(bad & ~exhausted, ascale * 0.5, ascale)
            recov = recov + bad.astype(jnp.int32)
            # a rolled-back frame must not trip the stall test (its conv
            # equals conv_prev by construction, not by convergence)
            newly = ((~done) & ~bad & (it >= 1)
                     & (jnp.abs(conv - conv_prev) < tol))
            if integ:
                # same reasoning for a frozen SDC frame's unchanged conv
                newly = newly & ~tripped
            ended = newly | exhausted
            if integ:
                ended = ended | tripped
            iters = jnp.where(ended, it + 1, iters)
            out = (f_new, fitted_new, conv, it + 1, done | ended, iters,
                   ascale, recov, div | exhausted)
            if momentum:
                # restart OR'd with rollback / SDC freeze — the documented
                # precedence: restart never touches relaxation, the
                # ladder never touches t_k except through this reset
                tk_new = kit.momentum_tk(
                    y, f_new, f, t_next,
                    (bad | tripped) if integ else bad,
                )
                out = out + ((f,) + ((fitted,) if carry_fit else ())
                             + (tk_new,))
            return out + (sdc,) if integ else out
        newly = (~done) & (it >= 1) & (jnp.abs(conv - conv_prev) < tol)
        if integ:
            newly = newly & ~tripped
            ended = newly | tripped
        else:
            ended = newly
        iters = jnp.where(ended, it + 1, iters)
        out = (f_new, fitted_new, conv, it + 1, done | ended, iters)
        if momentum:
            tk_new = kit.momentum_tk(y, f_new, f, t_next,
                                     tripped if integ else False)
            out = out + ((f,) + ((fitted,) if carry_fit else ())
                         + (tk_new,))
        return out + (sdc,) if integ else out

    def cond(carry):
        it, done = carry[3], carry[4]
        return (it < opts.max_iterations) & ~jnp.all(done)

    if recovery:
        # Pre-flight input guard: a frame whose measurement, seed or
        # ||g||^2 is already non-finite (a NaN-poisoned sensor frame, a
        # corrupted warm start) has no good iterate to roll back to — the
        # rollback ladder cannot help it. Such frames are marked DIVERGED
        # at iteration 0 with a zero solution instead of burning the
        # ladder (or, guard off, spinning to the iteration cap with NaN
        # output). Cheap [B]-wise bookkeeping, only traced in recovery
        # mode; reductions mirror the solver's sharding.
        gbad = _psum(
            jnp.sum(jnp.where(jnp.isfinite(g), 0, 1), axis=1,
                    dtype=jnp.int32),
            axis_name,
        )
        fbad = _psum(
            jnp.sum(jnp.where(jnp.isfinite(f0), 0, 1), axis=1,
                    dtype=jnp.int32),
            voxel_axis,
        )
        input_bad = (gbad > 0) | (fbad > 0) | ~jnp.isfinite(msq)
        f0 = jnp.where(input_bad[:, None], 0, f0)
        fitted0 = jnp.where(input_bad[:, None], 0, fitted0)
        init = (
            f0, fitted0, jnp.zeros(B, dtype), jnp.asarray(0, jnp.int32),
            input_bad,
            jnp.where(input_bad, 0, opts.max_iterations).astype(jnp.int32),
            jnp.ones(B, dtype),  # per-frame relaxation scale
            jnp.zeros(B, jnp.int32),  # recoveries consumed
            input_bad,  # diverged (pre-failed, or ladder exhausted later)
        )
        if momentum:
            # t_1 = 1 -> beta = 0: iteration 1 extrapolates nothing
            init = init + ((f0,) + ((fitted0,) if carry_fit else ())
                           + (jnp.ones(B, dtype),))
        if integ:
            init = init + (jnp.zeros(B, bool),)  # SDC-tripped frames
        out = lax.while_loop(cond, body, init)
        if integ:
            out, sdc = out[:-1], out[-1]
        if momentum:
            out = out[:-mom_n]
        f, fitted_fin, conv, it, done, iters, _, _, div = out
        status = jnp.where(
            div, DIVERGED,
            jnp.where(done, SUCCESS, MAX_ITERATIONS_EXCEEDED),
        ).astype(jnp.int32)
        if integ:
            status = jnp.where(sdc, SDC_DETECTED, status).astype(jnp.int32)
    else:
        init = (
            f0, fitted0, jnp.zeros(B, dtype), jnp.asarray(0, jnp.int32),
            jnp.zeros(B, bool), jnp.full(B, opts.max_iterations, jnp.int32),
        )
        if momentum:
            init = init + ((f0,) + ((fitted0,) if carry_fit else ())
                           + (jnp.ones(B, dtype),))
        if integ:
            init = init + (jnp.zeros(B, bool),)
        out = lax.while_loop(cond, body, init)
        if integ:
            out, sdc = out[:-1], out[-1]
        if momentum:
            out = out[:-mom_n]
        f, fitted_fin, conv, it, done, iters = out
        status = jnp.where(done, SUCCESS, MAX_ITERATIONS_EXCEEDED).astype(jnp.int32)
        if integ:
            status = jnp.where(sdc, SDC_DETECTED, status).astype(jnp.int32)
    res = SolveResult(f, status, iters, conv)
    return (res, fitted_fin) if return_fitted else res


# --------------------------------------------------------------------------
# Continuous batching (sartsolver_tpu/sched/, docs/PERFORMANCE.md §8): the
# stepped masked-lane solver core. The batched loop above runs a frame
# group until its SLOWEST frame converges — converged lanes pad the MXU
# with dead work (BENCH_r05: per-lane loop-iter/s drops ~30% at B=32).
# Here the batch is a set of B persistent LANES: each lane independently
# carries one frame's iteration state, the while loop runs at most
# ``opts.schedule_stride`` iterations per call, and between calls the host
# scheduler retires converged/diverged lanes and backfills them from the
# frame queue. The batch shape is FIXED, so ONE compiled program serves
# every occupancy — no per-occupancy recompiles (pinned by the
# ``sharded_sched_step`` compile-audit entry and tests/test_sched.py).
#
# Per-lane math is EXACTLY the batched loop's (same _SweepContext closures,
# same freeze-by-where masking the batched loop already applies to
# converged frames), with the scalar iteration counter replaced by a
# per-lane one (lanes enter at different times): a lane that runs k
# iterations here produces byte-identical state to the same frame running
# k iterations in the non-scheduled batch — the parity the scheduler's
# retired results are gated on.


class SchedState(NamedTuple):
    """Device-resident lane state carried across scheduler strides.

    All leading dimensions are the fixed lane count B. Inert lanes
    (nothing assigned, or retired and awaiting backfill) hold
    ``done=True`` with benign placeholder data (``g=-1`` — all pixels
    saturated/masked, ``f=1`` — log-safe, ``msq=1``): their sweeps still
    execute (fixed shape) but every result is discarded by the same
    ``where(done, ...)`` freeze the batched loop applies to converged
    frames.
    """

    g: Array  # [B, P_local] normalized measurement (-1 rows = inert)
    msq: Array  # [B] normalized ||g||^2 (1 for inert lanes)
    f: Array  # [B, V_local] current iterate
    fitted: Array  # [B, P_local] H @ f (this device's pixel rows)
    conv: Array  # [B] previous convergence metric C^k
    it: Array  # [B] int32 — iterations completed by the current occupant
    done: Array  # [B] bool — frozen (converged/diverged/capped/inert)
    status: Array  # [B] int32 — SUCCESS / MAX_ITERATIONS_EXCEEDED / DIVERGED
    iters: Array  # [B] int32 — latched iteration count at retirement
    ascale: Array  # [B] divergence-guard relaxation scale (1 when off)
    recov: Array  # [B] int32 recoveries consumed (0 when off)
    # [B, V_local] log-variant observation back-projection, recomputed per
    # refill (one RTM read); None for the linear solver. With os_subsets
    # > 1 it holds the per-subset stack [B, os, V_local] instead
    # (_SweepContext.make_obs_sub).
    obs: Optional[Array]
    # Per-lane momentum state (SolverOptions.momentum='nesterov'): the
    # previous post-update iterate, its forward projection (carried only
    # when _momentum_carries_fitted — the linear classic sweep), and the
    # FISTA t_k scalar; lanes age/restart independently, so the state
    # lives here, keeping the stepped program's shape fixed at every
    # occupancy (the one-compiled-program contract). All None when
    # momentum is off — the default state tree is unchanged.
    f_prev: Optional[Array] = None  # [B, V_local]
    fitted_prev: Optional[Array] = None  # [B, P_local]
    tk: Optional[Array] = None  # [B]


def sched_step_normalized(
    problem: SARTProblem,
    state: SchedState,
    g_new: Array,  # [B, P_local] normalized rows for refilled lanes
    msq_new: Array,  # [B]
    refill: Array,  # [B] bool — lanes to (re)load before stepping
    *,
    opts: SolverOptions,
    axis_name=None,
    voxel_axis=None,
    use_guess: bool = True,
    _vmem_raised: bool = False,
    tile_occupancy=None,
    operator_spec=None,
) -> SchedState:
    """One scheduler stride: backfill the ``refill`` lanes, then run at
    most ``opts.schedule_stride`` masked iterations.

    Refill semantics mirror the batched entry's ``use_guess`` path op for
    op: the Eq. 4 initial guess (``H^T g / rho`` with the same negative-
    measurement masking and floors), its forward projection, and — in
    recovery mode — the non-finite-input pre-flight guard. The guess
    sweeps live under a ``lax.cond`` on ``any(refill)``, so pure drain
    strides (tail of the queue) skip the two extra RTM reads.

    The while loop exits early when every lane is done, so a stride never
    burns dead iterations past the last active lane's retirement.
    """
    dtype = jnp.dtype(opts.dtype)
    B = state.g.shape[0]
    kit = _SweepContext(problem, opts, axis_name, voxel_axis, B,
                        _vmem_raised, tile_occupancy=tile_occupancy,
                        operator_spec=operator_spec)
    recovery = int(opts.divergence_recovery)
    explode = float(opts.divergence_threshold)
    tol = jnp.asarray(opts.conv_tolerance, dtype)
    stride = int(opts.schedule_stride)
    maxit = jnp.asarray(opts.max_iterations, jnp.int32)
    # convergence accelerators — Python-gated exactly like the batched
    # core; the default path's carries and trace are unchanged
    momentum = opts.momentum != "off"
    carry_fit = _momentum_carries_fitted(opts)
    mom_n = 3 if carry_fit else 2
    os_cycle = kit.os > 1
    mom_floor = (_tiny(max(opts.log_epsilon, 1e-30), dtype)
                 if (momentum and opts.logarithmic) else None)

    def merge_refill(st: SchedState) -> SchedState:
        g = jnp.where(refill[:, None], g_new.astype(dtype), st.g)
        msq = jnp.where(refill, jnp.asarray(msq_new, dtype), st.msq)
        # Eq. 4 initial guess — the exact ops of the batched use_guess
        # path (parity requires one definition of the guess math)
        if use_guess:
            g_guess = jnp.where(g > 0, g, 0) if opts.mask_negative_guess else g
            accum = _psum(kit.bp_any(g_guess), axis_name)
            f0 = jnp.where(
                kit.vmask[None, :], accum / kit.safe_dens[None, :], 0
            )
        else:
            f0 = jnp.zeros_like(st.f)
        if opts.guess_floor > 0:
            f0 = jnp.maximum(f0, _tiny(opts.guess_floor, dtype))
        if opts.logarithmic:
            f0 = jnp.maximum(
                f0, _tiny(max(opts.guess_floor, opts.log_epsilon), dtype)
            )
        f0 = f0.astype(dtype)
        fitted0 = _psum(kit.fp_any(f0), voxel_axis)
        f = jnp.where(refill[:, None], f0, st.f)
        fitted = jnp.where(refill[:, None], fitted0, st.fitted)
        obs = st.obs
        if opts.logarithmic:
            if os_cycle:
                obs = jnp.where(refill[:, None, None],
                                kit.make_obs_sub(g, g >= 0), st.obs)
            else:
                obs = jnp.where(refill[:, None], kit.make_obs(g, g >= 0),
                                st.obs)
        f_prev, fitted_prev, tk = st.f_prev, st.fitted_prev, st.tk
        if momentum:
            # a refilled lane starts its FISTA sequence over: t_1 = 1,
            # previous iterate = its own initial guess (beta = 0)
            f_prev = jnp.where(refill[:, None], f0, f_prev)
            if carry_fit:
                fitted_prev = jnp.where(refill[:, None], fitted0,
                                        fitted_prev)
            tk = jnp.where(refill, jnp.ones((), dtype), tk)
        conv = jnp.where(refill, jnp.zeros((), dtype), st.conv)
        it = jnp.where(refill, 0, st.it)
        done = st.done & ~refill
        status = jnp.where(
            refill, jnp.asarray(MAX_ITERATIONS_EXCEEDED, jnp.int32),
            st.status,
        )
        iters = jnp.where(refill, maxit, st.iters)
        ascale = jnp.where(refill, jnp.ones((), dtype), st.ascale)
        recov = jnp.where(refill, 0, st.recov)
        if recovery:
            # pre-flight input guard on the refilled lanes only (the
            # batched entry's guard, per lane): non-finite measurement /
            # guess / ||g||^2 has no good iterate to roll back to
            gbad = _psum(
                jnp.sum(jnp.where(jnp.isfinite(g), 0, 1), axis=1,
                        dtype=jnp.int32),
                axis_name,
            )
            fbad = _psum(
                jnp.sum(jnp.where(jnp.isfinite(f), 0, 1), axis=1,
                        dtype=jnp.int32),
                voxel_axis,
            )
            input_bad = refill & (
                (gbad > 0) | (fbad > 0) | ~jnp.isfinite(msq)
            )
            f = jnp.where(input_bad[:, None], 0, f)
            fitted = jnp.where(input_bad[:, None], 0, fitted)
            done = done | input_bad
            status = jnp.where(
                input_bad, jnp.asarray(DIVERGED, jnp.int32), status
            )
            iters = jnp.where(input_bad, 0, iters)
        return SchedState(g, msq, f, fitted, conv, it, done, status,
                          iters, ascale, recov, obs, f_prev, fitted_prev,
                          tk)

    state = lax.cond(jnp.any(refill), merge_refill, lambda st: st, state)

    g, msq, obs = state.g, state.msq, state.obs
    meas_mask = g >= 0

    integ = kit.integrity

    def body(carry):
        if momentum:
            mom = carry[-mom_n:]
            carry = carry[:-mom_n]
            if carry_fit:
                f_prev, fitted_prev, tk = mom
            else:
                f_prev, tk = mom
        (step, f, fitted, conv_prev, itl, done, status, iters,
         ascale, recov) = carry
        # Nesterov/FISTA extrapolation per lane — the batched body's
        # helper with per-lane t_k (lanes age and restart independently)
        if momentum:
            y, beta, t_next = kit.extrapolate(f, f_prev, tk, mom_floor)
            base = y
        else:
            base = f
        # per-lane schedule factor decay^k — lanes age independently
        dk = ((jnp.asarray(kit.decay, dtype) ** itl.astype(dtype))[:, None]
              if kit.scheduled else None)
        if os_cycle:
            f_upd, fitted_upd = kit.run_os_sweep(
                base, dk, ascale if recovery else None, g, meas_mask, obs
            )
            bp_chk = None
        else:
            if momentum:
                if opts.logarithmic:
                    fitted_base = kit.loop_fp(y)
                else:
                    fitted_base = fitted + beta * (fitted - fitted_prev)
            else:
                fitted_base = fitted
            if opts.logarithmic:
                penalty = kit.compute_penalty(jnp.log(base))
            else:
                penalty = kit.compute_penalty(base)
            f_upd, fitted_upd, bp_chk = kit.run_sweep(
                base, fitted_base, penalty, dk,
                ascale if recovery else None, g, meas_mask, obs,
            )
        f_new = jnp.where(done[:, None], f, f_upd)  # frozen lanes freeze
        if fitted_upd is not None:
            fitted_new = jnp.where(
                done[:, None], fitted, _psum(fitted_upd, voxel_axis)
            )
        else:
            fitted_new = kit.loop_fp(f_new)
        if opts.precise_convergence:
            fsq_local = _sumsq_precise(fitted_new, dtype)
        else:
            fsq_local = jnp.sum(fitted_new * fitted_new, axis=1)
        if integ:
            # same folded ABFT reductions as the batched core (the check
            # is per lane; a tripped lane retires with SDC_DETECTED and
            # the scheduler's escalation decides recompute-vs-fail)
            fsq, tripped = kit.abft_check(fsq_local, fitted_new, f_new,
                                          bp_chk, done, axis_name,
                                          voxel_axis)
        else:
            fsq = _psum(fsq_local, axis_name)
        conv = (msq - fsq) / msq
        if integ and recovery:
            # divergence classifies first — see the batched core
            tripped = tripped & (jnp.isfinite(fsq) & jnp.isfinite(conv))
        if integ:
            f_new = jnp.where(tripped[:, None], f, f_new)
            fitted_new = jnp.where(tripped[:, None], fitted, fitted_new)
            conv = jnp.where(tripped, conv_prev, conv)
            status = jnp.where(
                tripped, jnp.asarray(SDC_DETECTED, jnp.int32), status
            )
        if recovery:
            bad = (~done) & (
                ~(jnp.isfinite(fsq) & jnp.isfinite(conv))
                | (fsq > explode * jnp.maximum(msq, 1.0))
            )
            if integ:
                bad = bad & ~tripped  # finite-mismatch SDC outranks
            exhausted = bad & (recov >= recovery)
            f_new = jnp.where(bad[:, None], f, f_new)
            fitted_new = jnp.where(bad[:, None], fitted, fitted_new)
            conv = jnp.where(bad, conv_prev, conv)
            ascale = jnp.where(bad & ~exhausted, ascale * 0.5, ascale)
            recov = recov + bad.astype(jnp.int32)
            newly = ((~done) & ~bad & (itl >= 1)
                     & (jnp.abs(conv - conv_prev) < tol))
            if integ:
                newly = newly & ~tripped
            ended = newly | exhausted
            if integ:
                ended = ended | tripped
            status = jnp.where(
                exhausted, jnp.asarray(DIVERGED, jnp.int32), status
            )
        else:
            newly = (~done) & (itl >= 1) & (jnp.abs(conv - conv_prev) < tol)
            if integ:
                newly = newly & ~tripped
                ended = newly | tripped
            else:
                ended = newly
        # per-lane iteration cap: the batched loop's `it < max_iterations`
        # exit, applied lane-wise (capped lanes keep the refill-time
        # MAX_ITERATIONS_EXCEEDED status and latch iters = max_iterations)
        capped = (~done) & ~ended & (itl + 1 >= maxit)
        status = jnp.where(newly, jnp.asarray(SUCCESS, jnp.int32), status)
        iters = jnp.where(ended | capped, itl + 1, iters)
        done_new = done | ended | capped
        itl = jnp.where(done, itl, itl + 1)
        out = (step + 1, f_new, fitted_new, conv, itl, done_new, status,
               iters, ascale, recov)
        if momentum:
            # gradient restart + the documented resets (rollback / SDC
            # freeze kill the momentum state) — the batched body's rule
            reset = False
            if recovery:
                reset = bad
            if integ:
                reset = reset | tripped
            tk_new = kit.momentum_tk(y, f_new, f, t_next, reset)
            out = out + ((f,) + ((fitted,) if carry_fit else ())
                         + (tk_new,))
        return out

    def cond(carry):
        return (carry[0] < stride) & ~jnp.all(carry[5])

    init = (jnp.asarray(0, jnp.int32), state.f, state.fitted, state.conv,
            state.it, state.done, state.status, state.iters, state.ascale,
            state.recov)
    if momentum:
        init = init + ((state.f_prev,)
                       + ((state.fitted_prev,) if carry_fit else ())
                       + (state.tk,))
    out = lax.while_loop(cond, body, init)
    f_prev_fin = fitted_prev_fin = tk_fin = None
    if momentum:
        mom_fin = out[-mom_n:]
        out = out[:-mom_n]
        if carry_fit:
            f_prev_fin, fitted_prev_fin, tk_fin = mom_fin
        else:
            f_prev_fin, tk_fin = mom_fin
    (_, f, fitted, conv, itl, done, status, iters, ascale, recov) = out
    return SchedState(g, msq, f, fitted, conv, itl, done, status, iters,
                      ascale, recov, obs, f_prev_fin, fitted_prev_fin,
                      tk_fin)


# --------------------------------------------------------------------------
# compile-audit self-registration (analysis/registry.py). The iteration
# sweep is THE hot program of the whole design; this pins its compiled
# structure — no f64, no matrix-sized copy/convert inside the while body,
# zero collectives on the single-device path, and a donated warm start
# actually aliased to the solution output — plus a golden op-histogram
# signature (analysis/goldens/sweep.*.json) that any structural drift must
# consciously update. Shapes are small but tile-aligned; the invariants
# are size-independent.

from sartsolver_tpu.analysis.registry import (  # noqa: E402
    AUDIT_P as _AUDIT_P,
    AUDIT_V as _AUDIT_V,
    register_audit_entry as _register_audit_entry,
)


def _audit_problem(rtm_dtype=None, with_scale: bool = False) -> SARTProblem:
    """Abstract fixture problem for AOT audit lowerings (no device data)."""
    return SARTProblem(
        jax.ShapeDtypeStruct((_AUDIT_P, _AUDIT_V), rtm_dtype or jnp.float32),
        jax.ShapeDtypeStruct((_AUDIT_V,), jnp.float32),
        jax.ShapeDtypeStruct((_AUDIT_P,), jnp.float32),
        None,
        jax.ShapeDtypeStruct((_AUDIT_V,), jnp.float32) if with_scale else None,
    )


def _audit_batch_args(batch: int = 1):
    return (
        jax.ShapeDtypeStruct((batch, _AUDIT_P), jnp.float32),
        jax.ShapeDtypeStruct((batch,), jnp.float32),
        jax.ShapeDtypeStruct((batch, _AUDIT_V), jnp.float32),
    )


@_register_audit_entry(
    "sweep",
    description="Eq. 2/3 batched iteration sweep (two-matmul path, fp32), "
                "warm-started with a donated f0",
    loop_copy_threshold=_AUDIT_P * _AUDIT_V,
    loop_convert_threshold=_AUDIT_P * _AUDIT_V,
    loop_collective_budget={
        "all-reduce": 0, "all-gather": 0, "all-to-all": 0,
        "collective-permute": 0,
    },
    min_donated_args=1,
)
def _audit_sweep():
    opts = SolverOptions(
        max_iterations=8, conv_tolerance=1e-30, fused_sweep="off"
    )
    fn = jax.jit(
        functools.partial(
            _solve_normalized_batch_impl, opts=opts, axis_name=None,
            voxel_axis=None, use_guess=False,
        ),
        # the warm-start pattern: f0 is the previous frame's (rescaled)
        # solution, same shape/dtype/layout as this frame's solution
        # output — donation must alias them or the state footprint doubles
        donate_argnums=3,
    )
    return fn.lower(_audit_problem(), *_audit_batch_args())


@_register_audit_entry(
    "log_sweep",
    description="logarithmic (Eq. 3) iteration sweep "
                "(two-matmul path, fp32)",
    loop_copy_threshold=_AUDIT_P * _AUDIT_V,
    loop_convert_threshold=_AUDIT_P * _AUDIT_V,
    loop_collective_budget={
        "all-reduce": 0, "all-gather": 0, "all-to-all": 0,
        "collective-permute": 0,
    },
)
def _audit_log_sweep():
    opts = SolverOptions(
        max_iterations=8, conv_tolerance=1e-30, fused_sweep="off",
        logarithmic=True,
    )
    fn = jax.jit(functools.partial(
        _solve_normalized_batch_impl, opts=opts, axis_name=None,
        voxel_axis=None, use_guess=True,
    ))
    return fn.lower(_audit_problem(), *_audit_batch_args())


@_register_audit_entry(
    "recovery_sweep",
    description="iteration sweep with the in-solve divergence guard "
                "(rollback + relaxation halving; two-matmul path, fp32)",
    loop_copy_threshold=_AUDIT_P * _AUDIT_V,
    loop_convert_threshold=_AUDIT_P * _AUDIT_V,
    loop_collective_budget={
        "all-reduce": 0, "all-gather": 0, "all-to-all": 0,
        "collective-permute": 0,
    },
)
def _audit_recovery_sweep():
    # The guard's hot-path cost must stay elementwise [B]/[B, P] bookkeeping:
    # no matrix-sized copies/converts may appear in the loop body, and the
    # single-device program stays collective-free — the same invariants as
    # the plain sweep, pinned separately because the guard re-traces the
    # body with three extra carries and a second where-select per state.
    opts = SolverOptions(
        max_iterations=8, conv_tolerance=1e-30, fused_sweep="off",
        divergence_recovery=2,
    )
    fn = jax.jit(functools.partial(
        _solve_normalized_batch_impl, opts=opts, axis_name=None,
        voxel_axis=None, use_guess=False,
    ))
    return fn.lower(_audit_problem(), *_audit_batch_args(2))


@_register_audit_entry(
    "integrity_sweep",
    description="iteration sweep with the in-solve ABFT integrity check "
                "(sum(Hf) == rho.f and sum(H^T w) == lambda.w residuals; "
                "two-matmul path, fp32)",
    # the check must stay O(B x (P+V)) bookkeeping on the existing
    # products: no matrix-sized copies/converts in the loop, and the
    # single-device program stays collective-free like the plain sweep
    loop_copy_threshold=_AUDIT_P * _AUDIT_V,
    loop_convert_threshold=_AUDIT_P * _AUDIT_V,
    loop_collective_budget={
        "all-reduce": 0, "all-gather": 0, "all-to-all": 0,
        "collective-permute": 0,
    },
)
def _audit_integrity_sweep():
    opts = SolverOptions(
        max_iterations=8, conv_tolerance=1e-30, fused_sweep="off",
        integrity=True,
    )
    fn = jax.jit(functools.partial(
        _solve_normalized_batch_impl, opts=opts, axis_name=None,
        voxel_axis=None, use_guess=False,
    ))
    return fn.lower(_audit_problem(), *_audit_batch_args(2))


@_register_audit_entry(
    "os_sweep",
    description="ordered-subsets (OS-SART) subset-cycle iteration sweep "
                "(linear, 4 subsets, fp32): fori_loop over pixel-row "
                "subsets + one full forward projection per outer "
                "iteration — the cost golden pins the subset loop's FLOP "
                "shape (~1.5x the classic sweep per iteration)",
    # the subset cycle's slices are [P/os, V] — a FULL-matrix copy or
    # convert in the loop would erase the subset structure
    loop_copy_threshold=_AUDIT_P * _AUDIT_V,
    loop_convert_threshold=_AUDIT_P * _AUDIT_V,
    loop_collective_budget={
        "all-reduce": 0, "all-gather": 0, "all-to-all": 0,
        "collective-permute": 0,
    },
)
def _audit_os_sweep():
    opts = SolverOptions(
        max_iterations=8, conv_tolerance=1e-30, fused_sweep="off",
        os_subsets=4,
    )
    fn = jax.jit(functools.partial(
        _solve_normalized_batch_impl, opts=opts, axis_name=None,
        voxel_axis=None, use_guess=False,
    ))
    return fn.lower(_audit_problem(), *_audit_batch_args(2))


@_register_audit_entry(
    "momentum_sweep",
    description="Nesterov/FISTA-accelerated linear iteration sweep "
                "(momentum='nesterov', fp32): extrapolation + gradient "
                "restart must stay O(B x (P+V)) elementwise bookkeeping — "
                "the extrapolated point's projection is the exact linear "
                "combination of carried products, never a third RTM sweep",
    loop_copy_threshold=_AUDIT_P * _AUDIT_V,
    loop_convert_threshold=_AUDIT_P * _AUDIT_V,
    loop_collective_budget={
        "all-reduce": 0, "all-gather": 0, "all-to-all": 0,
        "collective-permute": 0,
    },
)
def _audit_momentum_sweep():
    opts = SolverOptions(
        max_iterations=8, conv_tolerance=1e-30, fused_sweep="off",
        momentum="nesterov",
    )
    fn = jax.jit(functools.partial(
        _solve_normalized_batch_impl, opts=opts, axis_name=None,
        voxel_axis=None, use_guess=False,
    ))
    return fn.lower(_audit_problem(), *_audit_batch_args(2))


@_register_audit_entry(
    "log_accel_sweep",
    description="fully-accelerated logarithmic sweep (os_subsets=4 + "
                "momentum='nesterov', fp32) — the headline convergence-"
                "acceleration combination for the slow log path "
                "(docs/PERFORMANCE.md §9)",
    loop_copy_threshold=_AUDIT_P * _AUDIT_V,
    loop_convert_threshold=_AUDIT_P * _AUDIT_V,
    loop_collective_budget={
        "all-reduce": 0, "all-gather": 0, "all-to-all": 0,
        "collective-permute": 0,
    },
)
def _audit_log_accel_sweep():
    opts = SolverOptions(
        max_iterations=8, conv_tolerance=1e-30, fused_sweep="off",
        logarithmic=True, os_subsets=4, momentum="nesterov",
    )
    fn = jax.jit(functools.partial(
        _solve_normalized_batch_impl, opts=opts, axis_name=None,
        voxel_axis=None, use_guess=True,
    ))
    return fn.lower(_audit_problem(), *_audit_batch_args(2))


# Once-per-RUN latch for the non-finite-pixel warning below. The old
# behavior leaned on Python's per-location warning dedup, which fires once
# per PROCESS — a resident `sartsolve serve` session silently swallowed
# the warning for every request after the first. The latch is ours now
# (warn_explicit with a fresh registry bypasses Python's dedup entirely)
# and the drivers re-arm it per run/request; the per-pixel count still
# lands in the nonfinite_pixels_total counter on every call either way.
_NONFINITE_WARN_STATE = {"latched": False}


def reset_nonfinite_warning() -> None:
    """Re-arm the once-per-run non-finite-pixel warning. Called at the
    start of every CLI run and of every serving-engine request, so a
    resident process warns once per unit of user-visible work instead of
    once per process lifetime."""
    _NONFINITE_WARN_STATE["latched"] = False


def _warn_nonfinite(n_bad: int) -> None:
    if _NONFINITE_WARN_STATE["latched"]:
        return
    _NONFINITE_WARN_STATE["latched"] = True
    import warnings

    # warn_explicit with a throwaway registry: Python's own per-location
    # dedup never latches, so OUR latch is the only once-per-run gate
    warnings.warn_explicit(
        f"measurement frames contain {n_bad} non-finite pixel(s); they "
        "are excluded from normalization, ||g||^2 and the solve "
        "(counted in the nonfinite_pixels_total metric)",
        RuntimeWarning, __file__, 0, registry={},
    )


def prepare_measurement(measurement, opts: SolverOptions):
    """Host-side pre-step shared by the single-device and sharded drivers —
    the reference's ``pre_iteration_setup`` (sartsolver_cuda.cpp:138-194).

    Returns ``(g64_normalized, msq, norm)`` with everything computed in fp64:

    - ``norm``: global max of the measurement (fp32-overflow guard,
      sartsolver_cuda.cpp:146-150); 1.0 when normalization is off or the
      frame is fully dark/saturated (max <= 0).
    - ``msq``: normalized ``||g||^2`` with non-positive measurements
      excluded (sartsolver.cpp:161-164). A fully dark frame gives
      ``msq == 0``, which would make the convergence metric 0/0 and spin
      max_iterations; it is remapped to 1.0 so the metric degrades to
      ``-||Hf||^2`` and the stall test still terminates.
    """
    g64 = np.asarray(measurement, dtype=np.float64)
    n_bad = int(np.count_nonzero(~np.isfinite(g64)))
    if n_bad:
        # Non-finite pixels used to be *silently* excluded (from the
        # normalization max, ||g||^2 and — NaN compares false — the Eq. 6
        # measurement mask). They still are, but visibly now: counted
        # into the nonfinite_pixels_total obs counter on EVERY call and
        # warned once per run/request (the _NONFINITE_WARN_STATE latch,
        # re-armed by reset_nonfinite_warning — never Python's
        # once-per-process warning dedup).
        from sartsolver_tpu.obs import metrics as obs_metrics

        obs_metrics.get_registry().counter("nonfinite_pixels_total").inc(
            n_bad
        )
        _warn_nonfinite(n_bad)
    if opts.normalize:
        norm = float(np.max(g64, initial=0.0))
        if not np.isfinite(norm):
            # a NaN/inf-poisoned pixel must not poison the whole frame's
            # normalization: the finite pixels still define the scale, the
            # poisoned ones stay non-finite for the solver's input guard
            # (divergence_recovery) to flag — and the frame's solution row
            # denormalizes by a finite factor either way
            norm = float(np.max(g64[np.isfinite(g64)], initial=0.0))
        if norm <= 0:
            norm = 1.0
    else:
        norm = 1.0
    msq = float(np.sum(np.where(g64 > 0, g64, 0.0) ** 2)) / (norm * norm)
    if msq <= 0:
        msq = 1.0
    return g64 / norm, msq, norm


def solve(
    problem: SARTProblem,
    measurement,
    f0=None,
    *,
    opts: SolverOptions,
    tile_occupancy=None,
    operator_spec=None,
) -> SolveResult:
    """Single-device solve on a full (unsharded) problem. The sharded
    equivalent lives in ``sartsolver_tpu.parallel.sharded``."""
    from sartsolver_tpu.resilience import watchdog

    # host-side progress beacon (docs/RESILIENCE.md §6): library users
    # running under a watchdog get hang detection on this entry too; the
    # beacon never enters the trace, so compiled programs are unchanged
    watchdog.beacon(watchdog.PHASE_DISPATCH)
    dtype = jnp.dtype(opts.dtype)
    g64, msq, norm = prepare_measurement(measurement, opts)

    g = jnp.asarray(g64, dtype)
    use_guess = f0 is None
    nvoxel = (operator_spec.nvoxel if operator_spec is not None
              else problem.rtm.shape[1])
    if use_guess:
        f0 = jnp.zeros((nvoxel,), dtype)
    else:
        f0 = jnp.asarray(np.asarray(f0, np.float64) / norm, dtype)

    res = solve_normalized(
        problem, g, jnp.asarray(msq, dtype), f0,
        opts=opts, axis_name=None, use_guess=use_guess,
        tile_occupancy=tile_occupancy, operator_spec=operator_spec,
    )
    return SolveResult(res.solution * jnp.asarray(norm, dtype), res.status, res.iterations, res.convergence)

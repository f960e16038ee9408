"""Distributed SART solve over a device mesh.

This replaces the reference's entire MPI layer (its only distributed
strategy: 1-D row-block distribution of the RTM over ranks with a replicated
solution vector, main.cpp:67-68) with ``jax.shard_map`` over a
``('pixels', 'voxels')`` mesh:

- RTM sharded ``P('pixels', 'voxels')`` — each device holds a (row, column)
  block; with one voxel shard this degenerates to the reference's layout
  (one MPI rank's ``RayTransferMatrix``, raytransfer.hpp:20).
- measurement / ray_length sharded ``P('pixels')``; solution / ray_density
  sharded ``P('voxels')``. With >1 voxel shards the reference's
  replicated-f memory cost (every rank holds all nvoxel state) drops to
  1/n_voxel_shards — the axis to grow when nvoxel outruns one chip's HBM.
- every ``MPI_Allreduce`` site (16 in the reference, SURVEY §2) is a
  ``lax.psum`` *inside* the jitted while_loop, riding ICI with no
  per-iteration host staging (contrast sartsolver_cuda.cpp:242-244);
  the 2-D path adds a forward-projection psum over 'voxels', and the
  Laplacian penalty is halo-exchanged (compact boundary all_gather,
  ops/laplacian.py:ShardedLaplacian) — no [B, V_global] traffic in
  the loop.

Unequal MPI-style blocks become equal SPMD blocks by padding (see
``parallel.mesh``): padded pixels are excluded by the solver's own masking
rules, padded voxels have zero ray density and are masked identically.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from sartsolver_tpu.config import MAX_ITERATIONS_EXCEEDED, SolverOptions
from sartsolver_tpu.models.sart import (
    SARTProblem,
    SchedState,
    SolveResult,
    _momentum_carries_fitted,
    compute_ray_stats,
    prepare_measurement,
    sched_step_normalized,
    solve_chain_normalized,
    solve_normalized_batch,
)
from sartsolver_tpu.ops.laplacian import (
    LaplacianCOO,
    ShardedLaplacian,
    diagonal_share,
    shard_laplacian_halo,
)
from sartsolver_tpu.parallel.mesh import (
    COL_ALIGN,
    PIXEL_AXIS,
    ROW_ALIGN,
    VOXEL_AXIS,
    make_mesh,
    pad_measurement,
    padded_size,
)


def _stage(host_array, mesh, spec, **span_args) -> jax.Array:
    """Host -> global sharded array; multi-host safe (device_put cannot
    target non-addressable devices). Named fault site ``device.put``: a
    staging failure (device OOM, a preempted/hung device runtime) is a
    per-solve-call hazard the CLI's frame isolation absorbs into FAILED
    frames. ``span_args`` ride the ``device.put`` span (a chain's
    ``chain`` serial)."""
    from sartsolver_tpu.obs import trace as obs_trace
    from sartsolver_tpu.resilience import faults, watchdog

    watchdog.beacon(watchdog.PHASE_STAGE)  # staging-phase progress beacon
    faults.fire(faults.SITE_DEVICE_PUT)
    with obs_trace.span("device.put", **span_args):
        if jax.process_count() == 1:
            return jax.device_put(host_array, NamedSharding(mesh, spec))
        from sartsolver_tpu.parallel.multihost import make_global

        return make_global(np.asarray(host_array), mesh, spec)


def _fetch(x) -> np.ndarray:
    if jax.process_count() == 1:
        return np.asarray(x)
    from sartsolver_tpu.parallel.multihost import fetch

    return fetch(x)




class DeviceSolveResult:
    """Batch result whose solution stays ON DEVICE.

    Motivation (measured on a remote-attached v5e, 2026-07-30; not
    re-measured on a local host): one synchronous host<->device round
    trip cost ~68 ms, and the host-side
    :class:`SolveResult` path pays ~6 per frame (f0 staging, four result
    fetches) — dwarfing a warm-started solve's ~9 ms of device work. Here
    dispatch is fully asynchronous: the status/iterations/convergence
    scalars live in ONE packed device array materialized lazily on first
    access (so a caller can dispatch the NEXT chain — which only needs
    the device-resident ``solution_norm`` and host-side ``norms`` — before
    paying this chain's fetch, overlapping the D2H with the next chain's
    compute), and the solution transfer happens lazily via
    :meth:`solution_fetcher` (intended for the async writer's worker
    thread). The normalized device solution doubles as the next frame's
    warm start without ever visiting the host (``solve_batch(warm=...)``).

    Multi-host runs work the same way: the packed scalars are fully
    replicated, so each process reads them from its own devices (a local
    D2H, no host collective), and the solution arrives via
    ``solution_fetch`` — an asynchronously dispatched device-side
    all-gather to a replicated layout — so the lazy fetch on process 0's
    writer thread is also a purely local D2H. No collective ever leaves
    the main thread (the constraint that kept round 3's implementation
    single-process).
    """

    def __init__(self, solver, solution_norm, norms, packed,
                 solution_fetch=None, fitted_norm=None, chain=None):
        self._solver = solver
        # the producing solve_chain's serial: its fetch spans carry it,
        # tying each fetch to the chain.dispatch span that caused it
        self._span_args = {} if chain is None else {"chain": chain}
        self.solution_norm = solution_norm  # [B, padded_nvoxel] fp32, device
        # loop-exit ``H @ solution_norm`` ([B or 1, padded_npixel], device,
        # P('pixels')-sharded): carried into the next warm-started solve so
        # it skips its setup forward projection (models/sart fitted0)
        self.fitted_norm = fitted_norm
        # replicated copy for cross-process-safe fetching (multi-host);
        # same array as solution_norm on a single process
        self._solution_fetch = (
            solution_fetch if solution_fetch is not None else solution_norm
        )
        self.norms = np.asarray(norms, np.float64)  # [B]
        # [3, B] fp32 device array (replicated in multi-host runs, so its
        # materialization is a local D2H on any process); fetched once
        self._packed = packed
        self._scalars: Optional[tuple] = None
        self._host: Optional[np.ndarray] = None

    def _fetch_scalars(self) -> tuple:
        """Blocks until the solve completed; one D2H, cached. Scalars pack
        as fp32 exactly: status (0/-1) and iterations (<= 2000) are small
        integers; convergence was computed in the device dtype."""
        if self._scalars is None:
            from sartsolver_tpu.obs import trace as obs_trace
            from sartsolver_tpu.resilience import watchdog

            # result-fetch beacon: this D2H blocks until the device work
            # completed — the watchdog's canary for a wedged runtime
            watchdog.beacon(watchdog.PHASE_FETCH)
            with obs_trace.span("result.fetch", what="transfer",
                                part="scalars", **self._span_args):
                packed = np.asarray(self._packed)
            self._scalars = (
                packed[0].astype(np.int32),
                packed[1].astype(np.int32),
                packed[2].astype(np.float64),
            )
        return self._scalars

    @property
    def status(self) -> np.ndarray:
        return self._fetch_scalars()[0]

    @property
    def iterations(self) -> np.ndarray:
        return self._fetch_scalars()[1]

    @property
    def convergence(self) -> np.ndarray:
        return self._fetch_scalars()[2]

    def fetch_solutions(self) -> np.ndarray:
        """[B, nvoxel] fp64 physical-units solutions; one device fetch,
        cached. Host-side fp64 denormalization — numerics identical to the
        synchronous path (and the reference's D2H-then-multiply,
        sartsolver_cuda.cpp:264-265)."""
        if self._host is None:
            from sartsolver_tpu.obs import trace as obs_trace
            from sartsolver_tpu.resilience import watchdog

            watchdog.beacon(watchdog.PHASE_FETCH)
            with obs_trace.span("result.fetch", what="transfer",
                                part="solution", **self._span_args):
                sol = np.asarray(self._solution_fetch)
            with obs_trace.span("result.fetch", what="denormalize",
                                **self._span_args):
                self._host = (
                    sol[:, : self._solver.nvoxel].astype(np.float64)
                    * self.norms[:, None]
                )
        return self._host

    def solution_fetcher(self, b: int):
        """Zero-arg callable resolving frame ``b``'s solution — hand to
        AsyncSolutionWriter so the device fetch runs on the writer thread,
        overlapped with the next frame's solve."""
        return lambda: self.fetch_solutions()[b]


class SchedLaneState:
    """Host handle for the continuous-batching lane state
    (:class:`~sartsolver_tpu.models.sart.SchedState` on device, plus the
    per-lane host bookkeeping the device cannot carry: each occupant's
    fp64 measurement norm for denormalization at fetch time).

    Produced by :meth:`DistributedSARTSolver.sched_lanes`, advanced by
    :meth:`DistributedSARTSolver.sched_step`; the scheduler
    (sartsolver_tpu/sched/) owns the retire/backfill policy on top.
    """

    def __init__(self, solver: "DistributedSARTSolver", state: SchedState,
                 lanes: int):
        self._solver = solver
        self.state = state
        self.lanes = int(lanes)
        self.norms = np.ones(lanes, np.float64)  # per-lane occupant norm
        self._packed = None
        self._scalars = None
        self._drain_args = None  # cached no-refill operands (sched_step)

    def _repack(self) -> None:
        """Asynchronously dispatch the packed per-lane scalar array
        (done/status/iters/conv/it as one replicated [5, B] fp32 — all
        exact: see DeviceSolveResult._fetch_scalars). Called by
        sched_step after each stride; the host fetch stays lazy."""
        st = self.state
        self._packed = self._solver._sched_pack_fn()(
            st.done, st.status, st.iters, st.conv, st.it
        )
        self._scalars = None

    def scalars(self):
        """(done bool[B], status int32[B], iters int32[B], conv f64[B],
        it int32[B]) — ONE D2H per stride, cached until the next step;
        blocks until the stride's device work completed."""
        if self._scalars is None:
            from sartsolver_tpu.obs import trace as obs_trace
            from sartsolver_tpu.resilience import watchdog

            watchdog.beacon(watchdog.PHASE_FETCH)
            with obs_trace.span("result.fetch", what="sched_scalars"):
                packed = np.asarray(self._packed)
            self._scalars = (
                packed[0] > 0.5,
                packed[1].astype(np.int32),
                packed[2].astype(np.int32),
                packed[3].astype(np.float64),
                packed[4].astype(np.int32),
            )
        return self._scalars

    def lane_solution_fetcher(self, b: int):
        """Zero-arg callable resolving lane ``b``'s denormalized solution
        row — the async writer's contract (solution_fetcher twin).

        The ``[1, padded_nvoxel]`` slice program is DISPATCHED NOW (the
        lane's buffer will be overwritten by the next backfill; the slice
        result is an independent replicated array, safe to fetch lazily
        on the writer thread — a local D2H on any process of a
        multi-host run), and the occupant's norm is snapshotted now for
        the same reason."""
        solver = self._solver
        row_dev = solver._sched_lane_fn()(self.state.f, jnp.asarray(b, jnp.int32))
        norm = float(self.norms[b])
        nvoxel = solver.nvoxel

        def fetch() -> np.ndarray:
            from sartsolver_tpu.obs import trace as obs_trace
            from sartsolver_tpu.resilience import watchdog

            watchdog.beacon(watchdog.PHASE_FETCH)
            with obs_trace.span("result.fetch", what="sched_lane"):
                row = np.asarray(row_dev).astype(np.float64)
            return row[0, :nvoxel] * norm

        return fetch


class DistributedSARTSolver:
    """Upload-once / solve-many-frames driver (the reference's solver object
    lifecycle: matrix uploaded in the ctor, ``solve`` called per frame,
    sartsolver_cuda.cpp:78-126 + main.cpp:131-140)."""

    def __init__(
        self,
        rtm=None,
        laplacian: Optional[LaplacianCOO] = None,
        *,
        opts: SolverOptions,
        mesh=None,
        npixel: Optional[int] = None,
        nvoxel: Optional[int] = None,
        rtm_scale=None,
        tile_occupancy=None,
        operator=None,
    ):
        """``rtm`` is either a host ``np.ndarray`` (padded, cast and
        device_put here — single-host path) or an already-sharded global
        ``jax.Array`` built by ``parallel.multihost.read_and_shard_rtm``
        (multi-host path: pass the logical ``npixel``/``nvoxel`` since the
        device array carries only the padded shape). With
        ``opts.rtm_dtype == "int8"`` a pre-quantized codes array from
        ``multihost.read_and_quantize_rtm`` may be passed together with its
        ``rtm_scale``; otherwise the matrix is staged fp32 and quantized on
        device here (a 5-bytes/element transient — use the two-pass ingest
        when the matrix only fits as int8).

        ``tile_occupancy`` (``opts.sparse_rtm`` active): the RTM's
        tile-occupancy index, built by the chunked ingest
        (``multihost.make_tile_stats`` fed through
        ``read_and_shard_rtm``). Host-staged matrices may omit it — the
        index is built (and, for a nonzero threshold, the dropped tiles
        zeroed) from the padded host buffer here, BEFORE the ray stats,
        so rho/lambda and the Eq. 6 masks always describe the thresholded
        operator the sweeps multiply by. ``sparse_rtm='auto'`` declines
        quietly on voxel-sharded meshes and index-less pre-sharded
        matrices; an explicit numeric threshold raises.

        ``operator`` (mutually exclusive with ``rtm``): a
        :class:`~sartsolver_tpu.operators.base.ProjectionOperator`. A
        dense/tileskip operator unwraps to the host-staging path above
        (its tile-occupancy index riding along); an IMPLICIT operator
        switches the whole driver matrix-free — the staged "RTM" leaf is
        the ``[padded_npixel, 6]`` ray table sharded over pixel rows, the
        ray stats come from the same traced line-integral kernel the
        sweeps use, and every compiled program threads the operator's
        :class:`~sartsolver_tpu.operators.implicit.ImplicitSpec` as a
        static argument (see :meth:`_init_implicit` for the mode's
        restrictions)."""
        self.opts = opts
        self.mesh = mesh if mesh is not None else make_mesh()
        if PIXEL_AXIS not in self.mesh.shape or VOXEL_AXIS not in self.mesh.shape:
            raise ValueError(
                "Mesh must have ('pixels', 'voxels') axes; build it with "
                "parallel.mesh.make_mesh()."
            )
        self.n_pixel_shards = self.mesh.shape[PIXEL_AXIS]
        self.n_voxel_shards = self.mesh.shape.get(VOXEL_AXIS, 1)

        self.operator = operator
        self._operator_spec = None
        if operator is not None:
            if rtm is not None:
                raise ValueError(
                    "Pass either a matrix (rtm) or operator=, not both."
                )
            if operator.kind == "implicit":
                self._init_implicit(operator, laplacian)
                self._init_result_helpers()
                return
            if operator.kind == "lowrank":
                self._init_lowrank(operator, laplacian)
                self._init_result_helpers()
                return
            # dense / tileskip operators unwrap onto the host-staging
            # path: the matrix is their payload, and a tile-skip
            # operator's occupancy index rides into the sparse plumbing
            if tile_occupancy is None:
                tile_occupancy = operator.tile_occupancy()
            rtm = operator.payload()
        elif rtm is None:
            raise ValueError(
                "DistributedSARTSolver needs a matrix (rtm) or operator=."
            )

        dtype = jnp.dtype(opts.dtype)
        is_int8 = opts.rtm_dtype == "int8"
        if is_int8 and opts.fused_sweep == "off":
            from sartsolver_tpu.config import SartInputError

            # reachable from CLI flags -> polite exit(1), not a traceback.
            # This is a MODE refusal, not a mesh refusal: pixel- and
            # voxel-sharded meshes both run the fused sweep now (the panel-
            # psum scan on sharded pixels, the Pallas kernel otherwise) —
            # only fused_sweep='off' leaves int8 with no loop that can
            # dequantize in-flight.
            raise SartInputError(
                "rtm_dtype='int8' requires the fused sweep on any mesh "
                "(fused_sweep='auto'/'on'/'interpret'), but fused_sweep="
                "'off' was requested; drop --fused_sweep off or use "
                "fp32/bfloat16 storage."
            )
        if not is_int8 and rtm_scale is not None:
            raise ValueError("rtm_scale is only valid with rtm_dtype='int8'.")
        if (
            rtm_scale is not None
            and np.dtype(getattr(rtm, "dtype", np.float32)) != np.dtype(np.int8)
        ):
            # checked BEFORE staging: the staging cast would silently
            # truncate non-code data to garbage int8 values
            raise ValueError(
                "rtm_scale implies pre-quantized int8 codes "
                "(multihost.read_and_quantize_rtm); got a "
                f"{np.dtype(getattr(rtm, 'dtype', np.float32))} matrix."
            )
        if is_int8 and rtm_scale is None:
            # int8 codes are staged as fp32 and quantized on device below
            # (the per-voxel scales need global column maxima, which only
            # exist once the full matrix is assembled); pre-quantized codes
            # (read_and_quantize_rtm) arrive with their scale and stay int8.
            rtm_dtype = jnp.dtype("float32")
        else:
            rtm_dtype = jnp.dtype(
                "int8" if is_int8 else (opts.rtm_dtype or opts.dtype)
            )

        # Pre-sharded means the caller already distributed the (padded)
        # matrix (multihost.read_and_shard_rtm) — marked either by passing
        # the logical sizes explicitly (a 1x1 mesh yields an ordinary
        # single-device array, indistinguishable by sharding alone) or by a
        # multi-device/cross-process sharding. A plain single-device JAX
        # array without explicit sizes is host-stageable data, as before.
        presharded = (
            isinstance(rtm, jax.Array)
            and not isinstance(rtm, np.ndarray)
            and (
                (npixel is not None and nvoxel is not None)
                or not rtm.is_fully_addressable
                or len(rtm.sharding.device_set) > 1
            )
        )
        if presharded:
            if npixel is None or nvoxel is None:
                raise ValueError(
                    "A pre-sharded RTM needs explicit npixel/nvoxel (the "
                    "device array holds only the padded shape)."
                )
            self.npixel, self.nvoxel = npixel, nvoxel
        else:
            self.npixel, self.nvoxel = np.asarray(rtm).shape

        target_rows = padded_size(self.npixel, self.n_pixel_shards * ROW_ALIGN)
        target_cols = padded_size(self.nvoxel, self.n_voxel_shards * COL_ALIGN)
        self.padded_npixel = target_rows
        self.padded_nvoxel = target_cols
        self.voxel_block = target_cols // self.n_voxel_shards

        # Block-sparse RTM mode (docs/PERFORMANCE.md §10): resolve whether
        # THIS driver can carry a tile-occupancy index at all. The sparse
        # panel sweep's skip predicate must be SPMD-uniform, which a
        # voxel-sharded mesh breaks (each shard's local panels map to
        # different global panels), and a pre-sharded matrix has no host
        # bytes to index unless the ingest built the index (the padding
        # tiles of the padded grid are zero, so padded panels skip free).
        sparse_eps = opts.sparse_epsilon()
        self._tile_occupancy = None
        if sparse_eps is not None:
            from sartsolver_tpu.config import SartInputError

            reason = None
            if self.n_voxel_shards > 1:
                reason = (
                    "the mesh shards the voxel axis; the block-sparse "
                    "panel skip is not SPMD-uniform there — use a "
                    "pixel-major mesh (--voxel_shards 1) or dense storage"
                )
            elif presharded and tile_occupancy is None:
                reason = (
                    "the RTM is pre-sharded and no ingest-built "
                    "tile-occupancy index was supplied (thread "
                    "multihost.make_tile_stats through the chunked read)"
                )
            if reason is not None:
                if opts.sparse_explicit():
                    # reachable from CLI flags -> polite exit(1) contract
                    raise SartInputError(
                        f"Argument sparse_rtm={opts.sparse_rtm}: {reason}."
                    )
                sparse_eps = None  # auto declines; the dense paths run

        if presharded:
            if rtm.shape != (target_rows, target_cols):
                raise ValueError(
                    f"Pre-sharded RTM has shape {tuple(rtm.shape)}, expected "
                    f"padded {(target_rows, target_cols)} for "
                    f"{self.npixel}x{self.nvoxel} on this mesh."
                )
            rtm_dev = rtm if rtm.dtype == rtm_dtype else rtm.astype(rtm_dtype)
            if sparse_eps is not None:
                tile_occupancy.verify()
                self._tile_occupancy = tile_occupancy
                if sparse_eps > 0 and not tile_occupancy.mask.all():
                    # nonzero threshold on an ingest-staged matrix: zero
                    # the dropped tiles ON DEVICE (donated, sharding
                    # preserved) before the ray stats are computed, so
                    # the solve is self-consistent with what the sweeps
                    # multiply by — the host never holds the matrix here
                    from sartsolver_tpu.parallel.multihost import (
                        make_global,
                    )

                    occ = tile_occupancy
                    tr, tc = occ.tile_rows, occ.tile_cols
                    tm = make_global(occ.mask, self.mesh, P())

                    def _apply_tile_mask(m, keep):
                        # blocked-reshape + broadcast select: fusible,
                        # never materializes a matrix-sized mask (the
                        # padded shape is whole tiles by construction)
                        gr, gc = keep.shape
                        blocked = jnp.where(
                            keep[:, None, :, None],
                            m.reshape(gr, tr, gc, tc),
                            jnp.zeros((), m.dtype),
                        )
                        return blocked.reshape(gr * tr, gc * tc)

                    rtm_dev = jax.jit(
                        _apply_tile_mask, donate_argnums=0,
                        out_shardings=NamedSharding(
                            self.mesh, P(PIXEL_AXIS, VOXEL_AXIS)
                        ),
                    )(rtm_dev, tm)
        else:
            # Single-copy staging: the RTM is the dominant host allocation
            # (the reference targets tens-to-hundreds of GB), so pad+cast in
            # one buffer, and skip the copy when layout already matches.
            rtm_np = np.asarray(rtm)
            owns_buf = (
                (target_rows, target_cols) != rtm_np.shape
                or rtm_np.dtype != np.dtype(rtm_dtype)
            )
            if owns_buf:
                buf = np.zeros((target_rows, target_cols), dtype=np.dtype(rtm_dtype))
                buf[: self.npixel, : self.nvoxel] = rtm_np
                rtm_np = buf
            if sparse_eps is not None:
                # ingest-time occupancy pass over the PADDED storage-dtype
                # buffer (the packed representation the device will hold);
                # a nonzero threshold zeroes the dropped tiles BEFORE
                # staging, so the ray stats below describe the thresholded
                # operator (Eq. 6 self-consistency)
                from sartsolver_tpu.ops.sparse import (
                    TileMaxStats,
                    accumulate_tile_max,
                    threshold_matrix,
                )

                occ = tile_occupancy
                if occ is None:
                    # banded accumulation: no matrix-sized fp32
                    # transient on the path whose dominant allocation
                    # is the matrix itself
                    occ = accumulate_tile_max(
                        TileMaxStats(*rtm_np.shape), rtm_np
                    ).occupancy(sparse_eps)
                occ.verify()
                if sparse_eps > 0:
                    # in place when we own the padded staging buffer —
                    # the RTM is the dominant host allocation, so the
                    # threshold pass must not add a matrix-sized copy
                    rtm_np = threshold_matrix(rtm_np, occ,
                                              inplace=owns_buf)
                self._tile_occupancy = occ
            rtm_dev = jax.device_put(
                rtm_np, NamedSharding(self.mesh, P(PIXEL_AXIS, VOXEL_AXIS))
            )

        # Size-1 mesh axes carry no reductions; dropping their names lets the
        # solver pick the fused Pallas sweep (no pixel-axis psum in the loop).
        self._pixel_axis = PIXEL_AXIS if self.n_pixel_shards > 1 else None
        self._voxel_axis = VOXEL_AXIS if self.n_voxel_shards > 1 else None

        if is_int8:
            from sartsolver_tpu.models.sart import (
                INT8_MAX_CONTRACTION, compute_ray_stats_int8, quantize_rtm,
            )

            if max(self.padded_npixel, self.padded_nvoxel) > INT8_MAX_CONTRACTION:
                from sartsolver_tpu.config import SartInputError

                raise SartInputError(
                    f"rtm_dtype='int8': padded RTM extent "
                    f"{max(self.padded_npixel, self.padded_nvoxel)} exceeds "
                    f"the int32-accumulation bound {INT8_MAX_CONTRACTION} "
                    "of the integer projections; use fp32/bfloat16 storage."
                )
            if rtm_scale is not None:
                if rtm_dev.dtype != jnp.int8 or rtm_scale.shape != (
                    self.padded_nvoxel,
                ):
                    raise ValueError(
                        "Pre-quantized int8 RTM needs int8 codes and a "
                        f"[{self.padded_nvoxel}] rtm_scale (got "
                        f"{rtm_dev.dtype}, {tuple(rtm_scale.shape)})."
                    )
            else:
                # On-device quantization of the assembled fp32 matrix
                # (GSPMD inserts the cross-shard column-max reduction); the
                # fp32 staging copy is freed afterwards, so peak device
                # footprint is the 5-bytes/element transient.
                quant = jax.jit(
                    quantize_rtm,
                    out_shardings=(
                        NamedSharding(self.mesh, P(PIXEL_AXIS, VOXEL_AXIS)),
                        NamedSharding(self.mesh, P(VOXEL_AXIS)),
                    ),
                    donate_argnums=0,
                )
                import warnings

                with warnings.catch_warnings():
                    # the donated fp32 staging buffer cannot ALIAS the
                    # int8 outputs (dtype change), which JAX reports as
                    # "donated buffers were not usable" — but freeing it
                    # is the entire point of the donation here, and that
                    # still happens; silence the by-design mismatch
                    warnings.filterwarnings(
                        "ignore", message="Some donated buffers were not "
                        "usable", category=UserWarning,
                    )
                    rtm_dev, rtm_scale = quant(rtm_dev)
            stats_core = functools.partial(
                compute_ray_stats_int8, dtype=dtype,
                axis_name=self._pixel_axis, voxel_axis=self._voxel_axis,
            )
            stats_in = (P(PIXEL_AXIS, VOXEL_AXIS), P(VOXEL_AXIS))
            stats_args = (rtm_dev, rtm_scale)
        else:
            stats_core = functools.partial(
                compute_ray_stats, dtype=dtype,
                axis_name=self._pixel_axis, voxel_axis=self._voxel_axis,
            )
            stats_in = P(PIXEL_AXIS, VOXEL_AXIS)
            stats_args = (rtm_dev,)
        stats_fn = jax.jit(
            jax.shard_map(
                stats_core,
                mesh=self.mesh,
                in_specs=stats_in,
                out_specs=(P(VOXEL_AXIS), P(PIXEL_AXIS)),
                check_vma=False,
            )
        )
        ray_density, ray_length = stats_fn(*stats_args)

        if laplacian is not None:
            # Halo-exchange partition over the voxel shards: block-diagonal
            # triplets read the local block; boundary values travel in a
            # compact export table instead of a [B, V_global] all_gather of
            # the solution every iteration (ops/laplacian.py). A 1-shard
            # mesh degenerates to all-local triplets, no communication.
            # Block-local triplets on few diagonals (a grid stencil) are
            # stored as those diagonals: no gather, no scatter.
            from sartsolver_tpu.obs import flight as _obs_flight
            from sartsolver_tpu.obs import metrics as _obs_metrics

            sharded_lap = shard_laplacian_halo(
                laplacian, self.n_voxel_shards, self.voxel_block, dtype
            )
            share = diagonal_share(laplacian, sharded_lap, self.voxel_block)
            # the most cross-shard triplets any shard holds (the halo's COO)
            halo = int(sharded_lap.rows_halo.shape[-1])
            registry = _obs_metrics.get_registry()
            registry.gauge("laplacian_diagonal_share").set(share)
            registry.gauge("laplacian_halo_triplets").set(halo)
            n_diag = sharded_lap.diag_offsets.shape[-1]
            _obs_flight.record_event(
                "laplacian",
                f"{n_diag} diagonals serve {share:.4f} of the triplets"
                if n_diag else "COO: the offsets fail the byte criterion",
                n_diagonals=int(n_diag), diagonal_share=share,
                halo_triplets=halo,
            )
            lap_spec = P(VOXEL_AXIS, None)
            laplacian = ShardedLaplacian(
                *(_stage(f, self.mesh, lap_spec) for f in sharded_lap)
            )

        self.problem = SARTProblem(
            rtm_dev, ray_density, ray_length, laplacian, rtm_scale
        )
        if self._tile_occupancy is not None:
            # run-artifact provenance: the resident operator's occupancy
            # (the sweeps additionally record their per-compile skip plan)
            from sartsolver_tpu.obs import metrics as _obs_metrics

            _obs_metrics.get_registry().gauge("rtm_tile_occupancy").set(
                self._tile_occupancy.occupancy_fraction()
            )
        self._init_result_helpers()
        # Integrity layer (docs/RESILIENCE.md §8): keep the stats program
        # and an upload-time host snapshot of rho/lambda so the resident
        # matrix can be re-audited between frames (reaudit_ray_stats) and
        # the upload verified against ingest-accumulated host sums
        # (verify_ray_stats). Off by default: no snapshot, no fetch.
        self._ray_stats_fn = None
        self._ray_stats_snapshot = None
        if opts.integrity:
            self._ray_stats_fn = stats_fn
            self._ray_stats_snapshot = (
                _fetch(ray_density).copy(), _fetch(ray_length).copy()
            )

    def _init_result_helpers(self) -> None:
        """Shared tail of both construction paths (dense and implicit):
        the compiled-program cache and the tiny device helpers for the
        DeviceSolveResult path. The helpers' dispatch is asynchronous, so
        none adds a synchronous host round trip. Scalars pack to fp32:
        status (0/-1) and iterations (<= max 2000) are exact; convergence
        is already computed in the device dtype. The pack output is pinned
        fully replicated so every process of a multi-host run reads it
        from its own devices (no host collective). The rescale helper is
        NOT donated: the input is warm.solution_norm, whose buffer the
        producing DeviceSolveResult must stay able to fetch afterwards
        (the writer thread's lazy solution fetch)."""
        self._solve_fns = {}
        self._chains = 0  # solve_chain serial, carried by the chain's spans
        self._ray_stats_fn = None
        self._ray_stats_snapshot = None
        self._rescale_fn = jax.jit(  # sart-lint: disable=SL004
            lambda f, s: f * s[:, None].astype(f.dtype))
        self._pack_fn = jax.jit(
            lambda s, i, c: jnp.stack([
                s.astype(jnp.float32), i.astype(jnp.float32),
                c.astype(jnp.float32)]),
            out_shardings=NamedSharding(self.mesh, P()),
        )
        # last frame of a chain result, kept sharded on device — the next
        # chain's frame-0 seed (rescale folded into the chain's rescale[0])
        self._last_row_fn = jax.jit(lambda sol: sol[-1:])
        # Device-side reshard of the [B, padded_nvoxel] solution to a fully
        # replicated layout (an all_gather over the voxel axis riding ICI).
        # Dispatched asynchronously by every process of a multi-host run so
        # DeviceSolveResult's lazy fetch is a local D2H on any process —
        # the collective stays on the main thread.
        self._replicate_fn = jax.jit(
            lambda sol: sol, out_shardings=NamedSharding(self.mesh, P())
        )

    def _init_implicit(self, operator, laplacian) -> None:
        """Matrix-free construction: stage the ray table, derive the
        padded :class:`ImplicitSpec`, and compute rho/lambda with the
        SAME traced line-integral kernel the sweeps will use (Eq. 6
        self-consistency without a matrix).

        Mode restrictions (every one a polite ``SartInputError`` — all
        reachable from CLI flags): pixel-sharded meshes only (the panel
        back-projection's psum composition assumes whole voxel rows per
        device), single-process only, and no int8 storage / integrity
        ABFT / Laplacian smoothing / explicit block-sparse threshold /
        forced Pallas fusion — each of those is a property OF the
        materialized matrix."""
        from sartsolver_tpu.config import SartInputError
        from sartsolver_tpu.operators.implicit import implicit_ray_stats

        opts = self.opts
        if self.n_voxel_shards > 1:
            raise SartInputError(
                "The implicit (matrix-free) operator shards pixel rows "
                "only; voxel-sharded meshes are not supported — use a "
                "pixel-major mesh (--voxel_shards 1) or a materialized "
                "matrix."
            )
        if jax.process_count() > 1:
            raise SartInputError(
                "The implicit (matrix-free) operator does not support "
                "multi-host meshes; run single-process or materialize "
                "the matrix."
            )
        if opts.rtm_dtype == "int8":
            raise SartInputError(
                "rtm_dtype='int8' quantizes a materialized matrix; the "
                "implicit (matrix-free) operator has none — drop "
                "--rtm_dtype int8 or materialize the matrix."
            )
        if opts.integrity:
            raise SartInputError(
                "integrity=True re-audits a resident matrix; the "
                "implicit (matrix-free) operator holds none — drop "
                "--integrity or materialize the matrix."
            )
        if opts.sparse_epsilon() is not None and opts.sparse_explicit():
            raise SartInputError(
                f"Argument sparse_rtm={opts.sparse_rtm}: the block-"
                "sparse tile skip indexes a materialized matrix; the "
                "implicit (matrix-free) operator has none."
            )
        if opts.fused_sweep in ("on", "interpret"):
            raise SartInputError(
                f"fused_sweep='{opts.fused_sweep}' forces the Pallas "
                "matrix sweep, which needs a materialized matrix; the "
                "implicit operator traces its own panel loop — use "
                "fused_sweep='auto' or 'off'."
            )
        if laplacian is not None:
            raise SartInputError(
                "beta_laplace smoothing is not supported by the "
                "implicit (matrix-free) operator."
            )
        self.npixel = int(operator.npixel)
        self.nvoxel = int(operator.nvoxel)
        self.padded_npixel = padded_size(
            self.npixel, self.n_pixel_shards * ROW_ALIGN
        )
        self.padded_nvoxel = padded_size(self.nvoxel, COL_ALIGN)
        self.voxel_block = self.padded_nvoxel
        self._tile_occupancy = None
        self._pixel_axis = PIXEL_AXIS if self.n_pixel_shards > 1 else None
        self._voxel_axis = None
        spec = operator.spec(padded_nvoxel=self.padded_nvoxel)
        self._operator_spec = spec
        # padding rows are all-zero rays: direction norm 0 fails the
        # kernel's live-ray test, so they contribute nothing to rho and
        # get lambda = 0 — inert under the solver's own Eq. 6 masking,
        # exactly like a padded zero row of a materialized matrix
        rays = np.zeros((self.padded_npixel, 6), np.float32)
        rays[: self.npixel] = operator.payload()
        rays_dev = _stage(rays, self.mesh, P(PIXEL_AXIS, None))
        dtype = jnp.dtype(opts.dtype)
        stats_fn = jax.jit(
            jax.shard_map(
                functools.partial(
                    implicit_ray_stats, spec=spec, dtype=dtype,
                    axis_name=self._pixel_axis,
                ),
                mesh=self.mesh,
                in_specs=P(PIXEL_AXIS, None),
                out_specs=(P(VOXEL_AXIS), P(PIXEL_AXIS)),
                check_vma=False,
            )
        )
        ray_density, ray_length = stats_fn(rays_dev)
        self.problem = SARTProblem(rays_dev, ray_density, ray_length, None)

    def _init_lowrank(self, operator, laplacian) -> None:
        """Factored construction (operators/lowrank.py): stage the
        sparse core ``S`` row-sharded like any matrix block, the skinny
        factors ``U`` (row-sharded — its rows are pixel rows) and ``V``
        (replicated: O(V * r) bytes, and every shard's back-projection
        needs all of it), and compute rho/lambda with the SAME composed
        kernel the sweeps will use. The bp psum already folds the factor
        term's contribution (lowrank_back returns the local composed
        partial), so the collective budget is the audited dense
        ``sharded_batch`` one — ``sharded_lowrank_batch`` pins it.

        Mode restrictions mirror the implicit backend's (pixel-sharded,
        single-process, no integrity / explicit sparse / forced fusion /
        Laplacian), EXCEPT int8: the factored path quantizes ``S`` per
        voxel and each factor per rank component, host-side (the global
        column maxima are in hand here)."""
        from sartsolver_tpu.config import SartInputError
        from sartsolver_tpu.operators.lowrank import lowrank_ray_stats

        opts = self.opts
        if self.n_voxel_shards > 1:
            raise SartInputError(
                "The factored (lowrank) operator shards pixel rows "
                "only; voxel-sharded meshes are not supported — use a "
                "pixel-major mesh (--voxel_shards 1) or a materialized "
                "matrix."
            )
        if jax.process_count() > 1:
            raise SartInputError(
                "The factored (lowrank) operator does not support "
                "multi-host meshes; run single-process or materialize "
                "the matrix."
            )
        if opts.integrity:
            raise SartInputError(
                "integrity=True certifies a single stored-matrix "
                "contraction; the factored (lowrank) operator composes "
                "S + U V^T products — drop --integrity or materialize "
                "the matrix."
            )
        if opts.sparse_epsilon() is not None and opts.sparse_explicit():
            raise SartInputError(
                f"Argument sparse_rtm={opts.sparse_rtm}: the factored "
                "(lowrank) operator already tile-thresholds its sparse "
                "core — drop the explicit threshold."
            )
        if opts.fused_sweep in ("on", "interpret"):
            raise SartInputError(
                f"fused_sweep='{opts.fused_sweep}' forces the Pallas "
                "matrix sweep; the factored (lowrank) operator traces "
                "its own composed sweep — use fused_sweep='auto' or "
                "'off'."
            )
        if laplacian is not None:
            raise SartInputError(
                "beta_laplace smoothing is not supported by the "
                "factored (lowrank) operator."
            )
        self.npixel = int(operator.npixel)
        self.nvoxel = int(operator.nvoxel)
        self.padded_npixel = padded_size(
            self.npixel, self.n_pixel_shards * ROW_ALIGN
        )
        self.padded_nvoxel = padded_size(self.nvoxel, COL_ALIGN)
        self.voxel_block = self.padded_nvoxel
        self._tile_occupancy = None
        self._pixel_axis = PIXEL_AXIS if self.n_pixel_shards > 1 else None
        self._voxel_axis = None
        spec = operator.spec(padded_nvoxel=self.padded_nvoxel)
        self._operator_spec = spec
        # zero padding everywhere: zero S rows and zero U rows are inert
        # (lambda = 0, no rho contribution), zero S/V columns pad the
        # voxel extent exactly like a padded materialized matrix
        s_host = np.zeros(
            (self.padded_npixel, self.padded_nvoxel), np.float32
        )
        s_host[: self.npixel, : self.nvoxel] = operator.payload()
        u_raw, v_raw = operator.factors()
        u_host = np.zeros((self.padded_npixel, spec.rank), np.float32)
        u_host[: self.npixel] = u_raw
        v_host = np.zeros((self.padded_nvoxel, spec.rank), np.float32)
        v_host[: self.nvoxel] = v_raw
        dtype = jnp.dtype(opts.dtype)
        is_int8 = opts.rtm_dtype == "int8"
        scale_dev = fscale_dev = None
        if is_int8:
            # host-side quantization: the global per-voxel column maxima
            # exist here (single-process), so the scales match the
            # unsharded models.sart.quantize_rtm recipe exactly
            def _q(x):
                amax = np.max(np.abs(x), axis=0)
                s = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
                codes = np.clip(
                    np.round(x / s[None, :]), -127, 127
                ).astype(np.int8)
                return codes, s

            s_host, s_scale = _q(s_host)
            u_host, su = _q(u_host)
            v_host, sv = _q(v_host)
            f_scale = np.stack([su, sv])  # [2, r]
            scale_dev = _stage(s_scale, self.mesh, P(VOXEL_AXIS))
            fscale_dev = _stage(f_scale, self.mesh, P())
        else:
            # reduced-precision storage applies to the core only; the
            # factors are O(r * (P + V)) bytes and stay fp32
            store = jnp.dtype(opts.rtm_dtype or opts.dtype)
            if store != jnp.float32:
                s_host = s_host.astype(store)
        s_dev = _stage(s_host, self.mesh, P(PIXEL_AXIS, VOXEL_AXIS))
        u_dev = _stage(u_host, self.mesh, P(PIXEL_AXIS, None))
        v_dev = _stage(v_host, self.mesh, P())

        def stats(s_blk, u_blk, v_rep, *scales):
            if is_int8:
                s_scale_rep, f_scale_rep = scales
                u_fp = u_blk.astype(jnp.float32) * f_scale_rep[0]
                v_fp = v_rep.astype(jnp.float32) * f_scale_rep[1]
                return lowrank_ray_stats(
                    s_blk, u_fp, v_fp, spec, scale=s_scale_rep,
                    dtype=dtype, axis_name=self._pixel_axis,
                )
            return lowrank_ray_stats(
                s_blk, u_blk, v_rep, spec, dtype=dtype,
                axis_name=self._pixel_axis,
            )

        stats_fn = jax.jit(
            jax.shard_map(
                stats,
                mesh=self.mesh,
                in_specs=(
                    P(PIXEL_AXIS, VOXEL_AXIS), P(PIXEL_AXIS, None), P(),
                    *((P(VOXEL_AXIS), P()) if is_int8 else ()),
                ),
                out_specs=(P(VOXEL_AXIS), P(PIXEL_AXIS)),
                check_vma=False,
            )
        )
        stats_args = (s_dev, u_dev, v_dev) + (
            (scale_dev, fscale_dev) if is_int8 else ()
        )
        ray_density, ray_length = stats_fn(*stats_args)
        self.problem = SARTProblem(
            s_dev, ray_density, ray_length, None, scale_dev,
            u_dev, v_dev, fscale_dev,
        )

    # Replicating [B, padded_nvoxel] fp32 on every device is the fast fetch
    # path, but above this per-device byte budget it would reintroduce the
    # replicated-solution footprint that voxel sharding exists to remove
    # (module docstring) — there the solution is instead allgathered to the
    # HOST on the main thread (synchronous, but still once per solve group).
    _REPLICATE_FETCH_LIMIT = 1 << 30

    def _fetch_handle(self, solution) -> Optional[object]:
        """Cross-process-safe fetch handle for a device solution (None on a
        single process: the sharded array itself is locally fetchable)."""
        if jax.process_count() == 1:
            return None
        import os

        limit = int(os.environ.get(
            "SART_REPLICATE_FETCH_LIMIT", self._REPLICATE_FETCH_LIMIT
        ))
        nbytes = int(np.prod(solution.shape)) * solution.dtype.itemsize
        if nbytes <= limit:
            return self._replicate_fn(solution)  # async dispatch
        from sartsolver_tpu.parallel.multihost import fetch

        return fetch(solution)  # collective now, on the main thread

    def close(self) -> None:
        """Release the solver's device memory (VERDICT r3 next #5).

        Deletes the staged RTM/stats/Laplacian/scale arrays immediately
        (instead of waiting for GC of a possibly reference-cycled Python
        object) and drops the cached compiled functions. A long-lived
        operator process can then load a second near-HBM-limit matrix into
        the same process; ``benchmarks/capacity_demo.py`` measures how
        close a close()+reload cycle gets to fresh-process throughput.
        Idempotent. The solver is unusable afterwards; results already
        produced stay valid (a :class:`DeviceSolveResult`'s buffers are
        independent arrays, not views of the problem arrays, so they
        survive close() and remain fetchable — and usable as ``warm=``
        seeds for another same-layout solver).
        """
        if self.problem is None:
            return
        for leaf in jax.tree_util.tree_leaves(self.problem):
            if isinstance(leaf, jax.Array):
                try:
                    leaf.delete()
                except RuntimeError:
                    pass  # already deleted elsewhere
        self.problem = None
        self._solve_fns.clear()

    def __enter__(self) -> "DistributedSARTSolver":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ---- numerical integrity (docs/RESILIENCE.md §8) --------------------

    def _maybe_corrupt_resident(self) -> None:
        """Probe the ``device.buffer`` *corrupt* fault: a trip perturbs one
        element of the device-resident RTM in place (dtype preserved) while
        the uploaded ray stats stay stale — exactly the resident-bit-rot
        signature the ABFT check and the rho/lambda re-audit exist to
        catch. Zero work (one dict lookup) when nothing is armed."""
        from sartsolver_tpu.resilience import faults

        if not faults.take_corrupt(faults.SITE_DEVICE_BUFFER):
            return
        rtm = self.problem.rtm
        # the implicit "rtm" leaf is the ray table (pixel-sharded, its 6
        # columns whole); perturbing element [0, 0] bends ray 0's origin
        # while the uploaded stats stay stale — the same resident-rot
        # signature
        sharding = NamedSharding(self.mesh, (
            P(PIXEL_AXIS, None) if self._operator_spec is not None
            else P(PIXEL_AXIS, VOXEL_AXIS)
        ))
        if rtm.dtype == jnp.int8:
            # codes live in [-127, 127]: reflect around 127 guarantees a
            # changed, in-range value for any code but 63 (the fixture
            # matrices never quantize element 0 to exactly 63)
            upd = jax.jit(lambda m: m.at[0, 0].set(127 - m[0, 0]),
                          out_shardings=sharding)
        else:
            upd = jax.jit(lambda m: m.at[0, 0].set(m[0, 0] * 256 + 1),
                          out_shardings=sharding)
        self.problem = self.problem._replace(rtm=upd(rtm))

    def _ray_stats_args(self):
        if self.problem.rtm_scale is not None:
            return (self.problem.rtm, self.problem.rtm_scale)
        return (self.problem.rtm,)

    def verify_ray_stats(self, ingest_stats) -> list:
        """Post-upload integrity verification: the device-computed
        rho/lambda against the host sums accumulated during the chunked
        ingest (``resilience.integrity.IngestStats``). Returns mismatch
        descriptions (empty = verified). Requires ``opts.integrity``."""
        from sartsolver_tpu.resilience import integrity

        if self._ray_stats_snapshot is None:
            raise ValueError(
                "verify_ray_stats needs SolverOptions.integrity=True "
                "(the upload-time rho/lambda snapshot is not kept "
                "otherwise)."
            )
        dens, length = self._ray_stats_snapshot
        return integrity.verify_ray_stats(
            ingest_stats, dens[: self.nvoxel], length[: self.npixel],
            rtm_dtype=self.opts.rtm_dtype,
        )

    def reaudit_ray_stats(self) -> list:
        """Recompute rho/lambda from the RESIDENT matrix and compare
        bit-for-bit against the upload-time snapshot — the same compiled
        program on the same data is deterministic, so ANY difference is
        resident bit rot. Returns mismatch descriptions (empty = clean).
        Requires ``opts.integrity``; cost is one column+row reduction pass
        over the RTM, intended every ``SART_INTEGRITY_REAUDIT`` frames."""
        if self._ray_stats_fn is None:
            raise ValueError(
                "reaudit_ray_stats needs SolverOptions.integrity=True."
            )
        dens, length = self._ray_stats_fn(*self._ray_stats_args())
        out = []
        for name, now, ref in (
            ("ray_density", _fetch(dens), self._ray_stats_snapshot[0]),
            ("ray_length", _fetch(length), self._ray_stats_snapshot[1]),
        ):
            if not np.array_equal(np.asarray(now), ref):
                diff = np.flatnonzero(np.asarray(now) != ref)
                out.append(
                    f"{name}: {diff.size} element(s) changed since upload "
                    f"(first at index {int(diff[0])})"
                )
        return out

    def _problem_spec(self) -> SARTProblem:
        has_lap = self.problem.laplacian is not None
        lap_spec = ShardedLaplacian(
            *(P(VOXEL_AXIS, None),) * len(ShardedLaplacian._fields)
        ) if has_lap else None
        # the implicit problem's "rtm" leaf is the [P, 6] ray table
        # (sharded over pixel rows, its 6 coordinate columns whole); the
        # factored problem's is the sparse core S — an ordinary matrix
        # block, row-sharded like the dense RTM
        from sartsolver_tpu.operators.lowrank import LowRankSpec

        is_lowrank = isinstance(self._operator_spec, LowRankSpec)
        rtm_spec = (
            P(PIXEL_AXIS, None)
            if self._operator_spec is not None and not is_lowrank
            else P(PIXEL_AXIS, VOXEL_AXIS)
        )
        return SARTProblem(
            rtm_spec, P(VOXEL_AXIS), P(PIXEL_AXIS),
            lap_spec,
            P(VOXEL_AXIS) if self.problem.rtm_scale is not None else None,
            # U's rows are pixel rows (sharded with S); V and the factor
            # scales are replicated — the bp psum folds U^T w's reduced
            # contribution with S^T w's, no extra collective
            P(PIXEL_AXIS, None) if is_lowrank else None,
            P() if is_lowrank else None,
            P() if self.problem.factor_scale is not None else None,
        )

    def _compiler_options(self):
        """The per-shard fused Pallas sweep can need a raised scoped-VMEM
        limit (ops/fused_sweep.py); the option must sit on the outer jit
        (the solver core is inlined under shard_map). Attaching the raised
        limit when fusion is merely possible is harmless — it is a bound,
        not an allocation (measured throughput unchanged). Pixel-sharded
        meshes need no options: their fused path is the plain-XLA panel
        scan (sharded_panel_sweep), whose operands XLA buffers itself —
        only the Pallas kernel (pixel axis whole on-device) is charged
        against the scoped-VMEM limit."""
        if (
            self._pixel_axis is None
            and self._operator_spec is None
            and self.opts.fused_sweep != "off"
            and jax.default_backend() == "tpu"
        ):
            from sartsolver_tpu.ops.fused_sweep import raised_vmem_options

            return raised_vmem_options()
        return None

    @staticmethod
    def _drop_lap_shard_dim(problem: SARTProblem) -> SARTProblem:
        lap = problem.laplacian
        if lap is None:
            return problem
        # drop the leading per-shard dim added by shard_laplacian_halo
        return problem._replace(
            laplacian=ShardedLaplacian(*(a[0] for a in lap))
        )

    def _batch_fn(self, use_guess: bool, with_fitted0: bool = False):
        """Compiled batched solve over the mesh (one program per
        (use_guess, with_fitted0); XLA re-specializes per batch size on
        call). Every variant returns ``(SolveResult, fitted)`` so the
        loop-exit forward projection is available to chain into the next
        warm-started solve."""
        key = (use_guess, with_fitted0)
        if key not in self._solve_fns:
            opts = self.opts
            pixel_axis = self._pixel_axis
            voxel_axis = self._voxel_axis
            options = self._compiler_options()
            vmem_raised = options is not None

            def run(problem, g, msq, f0, *fitted0):
                return solve_normalized_batch(
                    self._drop_lap_shard_dim(problem), g, msq, f0,
                    opts=opts, axis_name=pixel_axis, voxel_axis=voxel_axis,
                    use_guess=use_guess,
                    fitted0=fitted0[0] if with_fitted0 else None,
                    return_fitted=True, _vmem_raised=vmem_raised,
                    tile_occupancy=self._tile_occupancy,
                    operator_spec=self._operator_spec,
                )

            fn = jax.shard_map(
                run,
                mesh=self.mesh,
                in_specs=(
                    self._problem_spec(), P(None, PIXEL_AXIS), P(),
                    P(None, VOXEL_AXIS),
                    *((P(None, PIXEL_AXIS),) if with_fitted0 else ()),
                ),
                out_specs=(
                    SolveResult(P(None, VOXEL_AXIS), P(), P(), P()),
                    P(None, PIXEL_AXIS),
                ),
                check_vma=False,
            )
            # f0 is always a call-fresh buffer (staged, or the rescale
            # helper's output — never warm.solution_norm itself) with the
            # same shape/sharding as the solution output, so donating it
            # would be sound — but this JAX version cannot alias donations
            # through shard_map (it either drops them silently or warns
            # "donated buffers were not usable" on every solve). The
            # compile audit's donation-aliasing invariant runs on the
            # plain-jit core ("sweep" entry), where aliasing is
            # verifiable; revisit donating here when shard_map supports
            # it.
            self._solve_fns[key] = jax.jit(fn, compiler_options=options)
        return self._solve_fns[key]

    def _chain_fn(self, use_guess_first: bool, with_fitted0: bool = False):
        """Compiled K-frame warm chain over the mesh (lax.scan over frames
        with the while_loop inside; models/sart.solve_chain_normalized).
        Returns ``(SolveResult, last frame's fitted)`` — the fitted rides
        the scan carry, so warm frames skip their setup sweep."""
        key = ("chain", use_guess_first, with_fitted0)
        if key not in self._solve_fns:
            opts = self.opts
            pixel_axis = self._pixel_axis
            voxel_axis = self._voxel_axis
            options = self._compiler_options()
            vmem_raised = options is not None

            def run(problem, g, msq, f0, rescale, *fitted0):
                return solve_chain_normalized(
                    self._drop_lap_shard_dim(problem), g, msq, f0, rescale,
                    opts=opts, axis_name=pixel_axis, voxel_axis=voxel_axis,
                    use_guess_first=use_guess_first,
                    fitted0=fitted0[0] if with_fitted0 else None,
                    _vmem_raised=vmem_raised,
                    tile_occupancy=self._tile_occupancy,
                    operator_spec=self._operator_spec,
                )

            fn = jax.shard_map(
                run,
                mesh=self.mesh,
                in_specs=(
                    self._problem_spec(), P(None, PIXEL_AXIS), P(),
                    P(None, VOXEL_AXIS), P(),
                    *((P(None, PIXEL_AXIS),) if with_fitted0 else ()),
                ),
                out_specs=(
                    SolveResult(P(None, VOXEL_AXIS), P(), P(), P()),
                    P(None, PIXEL_AXIS),
                ),
                check_vma=False,
            )
            self._solve_fns[key] = jax.jit(fn, compiler_options=options)
        return self._solve_fns[key]

    def local_pixel_range(self):
        """See :func:`multihost.process_pixel_range`."""
        from sartsolver_tpu.parallel.multihost import process_pixel_range

        return process_pixel_range(self.mesh, self.npixel)

    def local_pixel_runs(self):
        """See :func:`multihost.process_pixel_runs` — the general
        (possibly non-contiguous) form of :meth:`local_pixel_range`;
        ``local`` measurements are the concatenation of these runs."""
        from sartsolver_tpu.parallel.multihost import process_pixel_runs

        return process_pixel_runs(self.mesh, self.npixel)

    def _stage_measurement_local(self, G: np.ndarray, norms: np.ndarray,
                                 dtype) -> jax.Array:
        """Per-device staging of process-local measurement slices.

        ``G`` holds only this process's pixel rows — the concatenation of
        its ``local_pixel_runs`` (one contiguous slice in the common
        case). Each device gets its padded row block directly (padding
        rows are -1 = saturated, excluded everywhere, Eq. 6); the global
        array is assembled sharded ``P(None, 'pixels')`` with no
        replicated [B, padded_npixel] host copy (the reference's per-rank
        measurement slice, image.cpp:282-321)."""
        from sartsolver_tpu.parallel.multihost import _device_grid

        runs = self.local_pixel_runs()
        starts = np.cumsum([0] + [cnt for _, cnt in runs])
        B = G.shape[0]
        rb = self.padded_npixel // self.n_pixel_shards
        arrays = []
        for (i, _j), dev in np.ndenumerate(_device_grid(self.mesh)):
            if dev.process_index != jax.process_index():
                continue
            r0 = i * rb
            block = np.full((B, rb), -1.0, dtype)
            n_log = max(0, min(self.npixel - r0, rb))
            if n_log > 0:
                # locate this device block inside the run buffer; a block
                # with logical rows always starts inside one run (runs are
                # unions of whole blocks clipped at npixel) and its logical
                # rows never extend past that run's end
                for (off, cnt), s in zip(runs, starts):
                    if off <= r0 < off + cnt:
                        pos = int(s) + (r0 - off)
                        block[:, :n_log] = G[:, pos:pos + n_log] / norms[:, None]
                        break
                else:
                    raise AssertionError(
                        f"device row block at {r0} not covered by local "
                        f"pixel runs {runs}"
                    )
            arrays.append(jax.device_put(block, dev))
        return jax.make_array_from_single_device_arrays(
            (B, self.padded_npixel),
            NamedSharding(self.mesh, P(None, PIXEL_AXIS)),
            arrays,
        )

    def _check_frames(self, measurements, local: bool) -> np.ndarray:
        G = np.asarray(measurements, np.float64)
        if local:
            runs = self.local_pixel_runs()
            if not runs:
                raise ValueError(
                    "local measurement staging needs this process to own "
                    "at least one logical pixel row; pass full frames "
                    "instead."
                )
            expected = sum(cnt for _, cnt in runs)
        else:
            expected = self.npixel
        if G.ndim != 2 or G.shape[1] != expected:
            raise ValueError(
                f"Measurements must be [B, {expected}], got {G.shape}."
            )
        return G

    def _stage_frames(self, G: np.ndarray, local: bool, **span_args):
        """Stage B frames onto the mesh: ``(g_dev, norms [B], msqs [B])``.

        Shared by :meth:`solve_batch` and :meth:`solve_chain`;
        ``span_args`` ride the ``device.put`` span.
        """
        if self.problem is None:
            raise ValueError(
                "This solver has been closed (close() released its device "
                "memory); build a new DistributedSARTSolver."
            )
        opts = self.opts
        dtype = jnp.dtype(opts.dtype)
        B = G.shape[0]
        if local:
            # prepare_measurement semantics over process-local slices:
            # global max (the fp32 normalization guard, MPI_Allreduce MAX
            # parity, sartsolver_cuda.cpp:146-150) and global masked
            # ||g||^2 (sartsolver.cpp:161-164) from cheap scalar gathers.
            lmax = G.max(axis=1, initial=0.0)
            lsum = np.sum(np.where(G > 0, G, 0.0) ** 2, axis=1)
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils as mhu

                allv = np.asarray(mhu.process_allgather(np.stack([lmax, lsum])))
                gmax = allv[:, 0].max(axis=0)
                gsum = allv[:, 1].sum(axis=0)
            else:
                gmax, gsum = lmax, lsum
            if opts.normalize:
                norms = np.where(gmax > 0, gmax, 1.0)
            else:
                norms = np.ones(B)
            msqs = gsum / norms ** 2
            msqs = np.where(msqs > 0, msqs, 1.0)
            g_dev = self._stage_measurement_local(G, norms, dtype)
        else:
            norms = np.empty(B)
            msqs = np.empty(B)
            g_stage = np.empty((B, self.padded_npixel), dtype)
            for b in range(B):
                g64, msq, norm = prepare_measurement(G[b], opts)
                g_stage[b] = pad_measurement(
                    g64, self.n_pixel_shards, target=self.padded_npixel
                )
                norms[b], msqs[b] = norm, msq

            g_dev = _stage(g_stage, self.mesh, P(None, PIXEL_AXIS),
                           **span_args)
        return g_dev, norms, msqs

    def _check_warm_alive(self, warm: DeviceSolveResult) -> None:
        """A ``warm=`` seed whose device buffers have been deleted (an
        explicit ``.delete()``, or any teardown that released them) would
        otherwise surface as an opaque XLA runtime error deep inside
        dispatch — fail here with an actionable message instead. Note a
        CLOSED producing solver is fine by itself: close() releases the
        solver's staged problem arrays, not its results' buffers, so a
        still-alive result remains a legitimate seed (the foreign-warm
        pattern)."""
        # jax.Array.is_deleted is called directly (ADVICE r5): the former
        # getattr(..., lambda: False) probe would silently skip the check
        # forever after a jax API rename, resurfacing the opaque XLA
        # dispatch error this guard exists to pre-empt — an AttributeError
        # here is the loud signal that the guard needs porting.
        dead = [
            name for name, arr in (
                ("solution", warm.solution_norm),
                ("fitted", warm.fitted_norm),
            )
            if arr is not None and arr.is_deleted()
        ]
        if dead:
            raise ValueError(
                f"warm= result's device {'/'.join(dead)} buffers have been "
                "deleted; fetch the result to host (fetch_solutions()) "
                "while it is alive and pass it as f0= instead."
            )

    def solve_chain(
        self,
        measurements,
        f0=None,
        *,
        warm: Optional[DeviceSolveResult] = None,
        local: bool = False,
    ) -> DeviceSolveResult:
        """Solve K serially warm-chained frames in ONE device program.

        The reference's core workload (main.cpp:131-140: each frame
        warm-starts from the previous solution) dispatched per frame costs
        one synchronous host round trip per frame; this chains the frames
        on device (``lax.scan`` carrying the warm start, the while_loop
        inside) so the whole chain pays ONE packed scalar fetch — per-frame
        semantics identical to K separate :meth:`solve` calls by
        construction.

        Multi-host runs chain too (every process calls this collectively,
        like :meth:`solve`): the packed scalars come back replicated so
        each process's fetch is a local D2H, and the solution is
        asynchronously all-gathered to a replicated layout for process 0's
        lazy writer fetch — the reference's serial warm-started loop keeps
        its one-round-trip-per-K-frames cost at any rank count
        (main.cpp:131-140 runs identically under any `mpirun -np`).

        Frame 0 seeds from ``warm`` (a previous chain's result — its LAST
        frame carries over, staying on device), else from host ``f0``,
        else from the Eq. 4 initial guess. Returns a
        :class:`DeviceSolveResult` over the K frames.

        Warm-started frames also inherit the previous frame's loop-exit
        ``fitted == H @ f`` (rescaled alongside the solution, both inside
        the chain's scan and across ``warm=`` handoffs), skipping the
        per-frame setup forward projection — one full RTM read saved per
        warm frame (models/sart fitted0 docs).
        """
        from sartsolver_tpu.obs import trace as obs_trace

        self._chains += 1
        ids = {"chain": self._chains}
        with obs_trace.span("chain.dispatch", frames=len(measurements),
                            warm=int(warm is not None), **ids):
            return self._dispatch_chain(measurements, f0, warm, local, ids)

    def _dispatch_chain(self, measurements, f0, warm, local, ids):
        """:meth:`solve_chain`'s host body, inside its ``chain.dispatch``
        span; ``ids`` (the chain serial) ride every span it opens."""
        from sartsolver_tpu.resilience import faults, watchdog

        watchdog.beacon(watchdog.PHASE_DISPATCH)
        faults.fire(faults.SITE_SOLVE)  # named site: solve-dispatch hazard
        self._maybe_corrupt_resident()  # device.buffer corrupt-fault drill
        opts = self.opts
        dtype = jnp.dtype(opts.dtype)
        if warm is not None and f0 is not None:
            raise ValueError("Pass either warm= (device) or f0= (host), not both.")
        if warm is not None:
            self._check_warm_alive(warm)
        if warm is not None and warm.solution_norm.shape[-1] != self.padded_nvoxel:
            raise ValueError(
                f"warm result has {warm.solution_norm.shape[-1]} padded "
                f"voxels, expected {self.padded_nvoxel}; it must come from "
                "a solver with the same voxel layout."
            )
        G = self._check_frames(measurements, local)
        K = G.shape[0]
        g_dev, norms, msqs = self._stage_frames(G, local, **ids)
        # carry renormalization between per-frame measurement norms
        rescale = np.ones(K)
        rescale[1:] = norms[:-1] / norms[1:]
        use_guess_first = f0 is None and warm is None
        fitted0_dev = None
        if warm is not None:
            rescale[0] = warm.norms[-1] / norms[0]
            f0_dev = self._last_row_fn(warm.solution_norm)
            if warm._solver is self and warm.fitted_norm is not None:
                # the carried product is H @ f for THIS solver's matrix —
                # a warm result from a different solver (legitimate as a
                # solution seed: any f0 is just an initial guess) must
                # recompute its setup sweep instead of injecting a stale
                # H_other @ f
                fitted0_dev = self._last_row_fn(warm.fitted_norm)
        else:
            f0_np = np.zeros((1, self.padded_nvoxel), dtype)
            if f0 is not None:
                f0_np[0, : self.nvoxel] = np.asarray(f0, np.float64) / norms[0]
            f0_dev = _stage(f0_np, self.mesh, P(None, VOXEL_AXIS), **ids)
        res, fitted_fin = self._chain_fn(
            use_guess_first, with_fitted0=fitted0_dev is not None
        )(
            self.problem, g_dev, jnp.asarray(msqs, dtype), f0_dev,
            jnp.asarray(rescale, dtype),
            *(() if fitted0_dev is None else (fitted0_dev,)),
        )
        sol_fetch = self._fetch_handle(res.solution)
        return DeviceSolveResult(
            self, res.solution, norms,
            self._pack_fn(res.status, res.iterations, res.convergence),
            solution_fetch=sol_fetch, fitted_norm=fitted_fin,
            chain=ids["chain"],
        )

    def solve_batch(
        self,
        measurements,
        f0=None,
        *,
        local: bool = False,
        device_result: bool = False,
        warm: Optional[DeviceSolveResult] = None,
    ) -> SolveResult | DeviceSolveResult:
        """Solve B independent frames in one batched device program.

        Per-frame semantics are identical to :meth:`solve`; intended for
        ``no_guess`` workloads (no warm-start dependency between frames).
        Returns a SolveResult of arrays: solution [B, nvoxel], status [B],
        iterations [B], convergence [B].

        ``local=True``: ``measurements`` hold only this process's pixel
        rows (``local_pixel_range``); the measurement max/'norm' and
        ``||g||^2`` are combined across processes, and staging is
        per-device-sharded instead of replicated per host.

        ``device_result=True`` returns a :class:`DeviceSolveResult`: the
        solution stays on device, the status/iterations/convergence scalars
        arrive in one packed fetch (replicated, so multi-host processes
        each read their local copy). ``warm`` chains a previous frame's
        device result as this frame's initial guess — the normalized
        solution is rescaled on device by ``norm_prev/norm_new`` (the host
        path's fp64 round trip through physical units is numerically a
        no-op up to one ulp of the compute dtype, and a warm start is only
        an initial guess).
        """
        from sartsolver_tpu.resilience import faults, watchdog

        watchdog.beacon(watchdog.PHASE_DISPATCH)
        faults.fire(faults.SITE_SOLVE)  # named site: solve-dispatch hazard
        self._maybe_corrupt_resident()  # device.buffer corrupt-fault drill
        opts = self.opts
        dtype = jnp.dtype(opts.dtype)
        if warm is not None and f0 is not None:
            raise ValueError("Pass either warm= (device) or f0= (host), not both.")
        if warm is not None:
            self._check_warm_alive(warm)
        G = self._check_frames(measurements, local)
        B = G.shape[0]
        g_dev, norms, msqs = self._stage_frames(G, local)
        use_guess = f0 is None and warm is None
        fitted0_dev = None
        if warm is not None:
            if warm.solution_norm.shape != (B, self.padded_nvoxel):
                raise ValueError(
                    f"warm result has shape {tuple(warm.solution_norm.shape)}, "
                    f"expected {(B, self.padded_nvoxel)}."
                )
            # fp64 norm ratio cast to the compute dtype on device — the
            # same rounding the chain path applies (solve_chain_normalized
            # rescale), so per-frame warm dispatch and chained dispatch
            # produce bit-identical warm starts
            scale = warm.norms / norms
            f0_dev = self._rescale_fn(
                warm.solution_norm, jnp.asarray(scale, dtype)
            )
            if (warm._solver is self
                    and warm.fitted_norm is not None
                    and warm.fitted_norm.shape
                    == (B, self.padded_npixel)):
                # carried loop-exit H @ f — skips this solve's setup sweep.
                # Only valid for THIS solver's matrix (a foreign warm result
                # is a legitimate solution seed but its fitted belongs to a
                # different H); a shape mismatch (e.g. a chain result, which
                # keeps only its last frame's fitted) also recomputes
                fitted0_dev = self._rescale_fn(
                    warm.fitted_norm, jnp.asarray(scale, dtype)
                )
        else:
            f0_np = np.zeros((B, self.padded_nvoxel), dtype)
            if not use_guess:
                f0_np[:, : self.nvoxel] = np.asarray(f0, np.float64) / norms[:, None]
            f0_dev = _stage(f0_np, self.mesh, P(None, VOXEL_AXIS))

        res, fitted_fin = self._batch_fn(
            use_guess, with_fitted0=fitted0_dev is not None
        )(
            self.problem, g_dev, jnp.asarray(msqs, dtype), f0_dev,
            *(() if fitted0_dev is None else (fitted0_dev,)),
        )
        if device_result:
            sol_fetch = self._fetch_handle(res.solution)
            return DeviceSolveResult(
                self, res.solution, norms,
                self._pack_fn(res.status, res.iterations, res.convergence),
                solution_fetch=sol_fetch, fitted_norm=fitted_fin,
            )
        solution = _fetch(res.solution).astype(np.float64)[:, : self.nvoxel] * norms[:, None]
        return SolveResult(
            solution,
            _fetch(res.status),
            _fetch(res.iterations),
            _fetch(res.convergence).astype(np.float64),
        )

    # ---- continuous batching (sartsolver_tpu/sched/) ---------------------

    def _sched_state_spec(self) -> SchedState:
        opts = self.opts
        momentum = opts.momentum != "off"
        return SchedState(
            g=P(None, PIXEL_AXIS), msq=P(), f=P(None, VOXEL_AXIS),
            fitted=P(None, PIXEL_AXIS), conv=P(), it=P(), done=P(),
            status=P(), iters=P(), ascale=P(), recov=P(),
            # os_subsets > 1 stacks the per-subset observations on a
            # middle axis ([B, os, V_local]); the voxel sharding moves
            # with the last axis either way
            obs=((P(None, None, VOXEL_AXIS) if opts.os_subsets > 1
                  else P(None, VOXEL_AXIS))
                 if opts.logarithmic else None),
            f_prev=P(None, VOXEL_AXIS) if momentum else None,
            fitted_prev=(P(None, PIXEL_AXIS)
                         if _momentum_carries_fitted(opts) else None),
            tk=P() if momentum else None,
        )

    def _sched_state_sharding(self) -> SchedState:
        spec = self._sched_state_spec()
        return SchedState(*(
            None if s is None else NamedSharding(self.mesh, s)
            for s in spec
        ))

    def _sched_fn(self):
        """Compiled scheduler stride over the mesh — ONE program for every
        lane occupancy (the fixed batch shape is the whole point:
        continuous batching must never recompile as lanes retire and
        backfill; tests/test_sched.py pins the cache size at 1)."""
        key = "sched"
        if key not in self._solve_fns:
            opts = self.opts
            pixel_axis = self._pixel_axis
            voxel_axis = self._voxel_axis
            options = self._compiler_options()
            vmem_raised = options is not None

            def run(problem, state, g_new, msq_new, refill):
                return sched_step_normalized(
                    self._drop_lap_shard_dim(problem), state, g_new,
                    msq_new, refill,
                    opts=opts, axis_name=pixel_axis, voxel_axis=voxel_axis,
                    use_guess=True, _vmem_raised=vmem_raised,
                    tile_occupancy=self._tile_occupancy,
                    operator_spec=self._operator_spec,
                )

            state_spec = self._sched_state_spec()
            fn = jax.shard_map(
                run,
                mesh=self.mesh,
                in_specs=(
                    self._problem_spec(), state_spec,
                    P(None, PIXEL_AXIS), P(), P(),
                ),
                out_specs=state_spec,
                check_vma=False,
            )
            # out_shardings pinned to the exact NamedShardings sched_lanes
            # stages: the returned state feeds the NEXT stride's call, and
            # any spec normalization drift (GSPMD rewrites trivial axes)
            # between fresh and cycled state would key a SECOND jit cache
            # entry — the one-compiled-program contract forbids that
            # (pinned by tests/test_sched.py's cache-size assertion)
            self._solve_fns[key] = jax.jit(
                fn, out_shardings=self._sched_state_sharding(),
                compiler_options=options,
            )
        return self._solve_fns[key]

    def _sched_pack_fn(self):
        key = "sched_pack"
        if key not in self._solve_fns:
            self._solve_fns[key] = jax.jit(
                lambda d, s, i, c, it: jnp.stack([
                    d.astype(jnp.float32), s.astype(jnp.float32),
                    i.astype(jnp.float32), c.astype(jnp.float32),
                    it.astype(jnp.float32)]),
                out_shardings=NamedSharding(self.mesh, P()),
            )
        return self._solve_fns[key]

    def _sched_lane_fn(self):
        """[1, padded_nvoxel] replicated slice of one lane's solution —
        the lane index is a TRACED scalar, so every lane shares one
        compiled program."""
        key = "sched_lane"
        if key not in self._solve_fns:
            self._solve_fns[key] = jax.jit(
                lambda f, b: jax.lax.dynamic_slice_in_dim(f, b, 1, axis=0),
                out_shardings=NamedSharding(self.mesh, P()),
            )
        return self._solve_fns[key]

    def sched_lanes(self, lanes: int) -> SchedLaneState:
        """Fresh all-inert lane state for :meth:`sched_step`.

        Inert lanes hold ``g = -1`` (every pixel saturated — masked by
        Eq. 6 everywhere), ``f = 1`` (log-safe: the log variant's
        ``log f`` penalty needs a positive iterate even on dead lanes,
        whose updates are discarded by the ``done`` freeze anyway) and
        ``msq = 1`` (the convergence ratio stays finite)."""
        if self.problem is None:
            raise ValueError(
                "This solver has been closed (close() released its device "
                "memory); build a new DistributedSARTSolver."
            )
        B = int(lanes)
        if B < 1:
            raise ValueError("Lane count must be positive.")
        dtype = jnp.dtype(self.opts.dtype)
        pix = P(None, PIXEL_AXIS)
        vox = P(None, VOXEL_AXIS)
        # every component is staged with its state-spec sharding UP FRONT
        # (the replicated scalars included): an uncommitted first-call
        # operand would key a second jit cache entry once the stride's
        # own committed outputs come back around — exactly the
        # per-occupancy recompile the fixed shape exists to avoid
        rep = P()
        state = SchedState(
            g=_stage(np.full((B, self.padded_npixel), -1.0, dtype),
                     self.mesh, pix),
            msq=_stage(np.ones(B, dtype), self.mesh, rep),
            f=_stage(np.ones((B, self.padded_nvoxel), dtype),
                     self.mesh, vox),
            fitted=_stage(np.zeros((B, self.padded_npixel), dtype),
                          self.mesh, pix),
            conv=_stage(np.zeros(B, dtype), self.mesh, rep),
            it=_stage(np.zeros(B, np.int32), self.mesh, rep),
            done=_stage(np.ones(B, bool), self.mesh, rep),
            status=_stage(np.full(B, MAX_ITERATIONS_EXCEEDED, np.int32),
                          self.mesh, rep),
            iters=_stage(np.zeros(B, np.int32), self.mesh, rep),
            ascale=_stage(np.ones(B, dtype), self.mesh, rep),
            recov=_stage(np.zeros(B, np.int32), self.mesh, rep),
            obs=(_stage(
                np.zeros((B, self.opts.os_subsets, self.padded_nvoxel),
                         dtype)
                if self.opts.os_subsets > 1
                else np.zeros((B, self.padded_nvoxel), dtype),
                self.mesh,
                P(None, None, VOXEL_AXIS) if self.opts.os_subsets > 1
                else vox,
            ) if self.opts.logarithmic else None),
            # momentum state: f_prev = 1 matches the inert-lane iterate
            # (log-safe); every refill overwrites it before use
            f_prev=(_stage(np.ones((B, self.padded_nvoxel), dtype),
                           self.mesh, vox)
                    if self.opts.momentum != "off" else None),
            fitted_prev=(_stage(np.zeros((B, self.padded_npixel), dtype),
                                self.mesh, pix)
                         if _momentum_carries_fitted(self.opts) else None),
            tk=(_stage(np.ones(B, dtype), self.mesh, rep)
                if self.opts.momentum != "off" else None),
        )
        return SchedLaneState(self, state, B)

    def sched_step(self, lane_state: SchedLaneState, refills) -> None:
        """Advance the lanes one scheduler stride.

        ``refills`` is a list of ``(lane_index, measurement)`` pairs —
        full physical-unit frames (``[npixel]``); each is normalized host-
        side exactly like :meth:`solve_batch`'s staging
        (prepare_measurement + padding) and loaded into its lane before
        the stride runs. An empty list is a pure drain stride. Updates
        ``lane_state`` in place (state swap on success only — a failed
        dispatch leaves the previous stride's state intact for the
        caller's failure policy)."""
        from sartsolver_tpu.resilience import faults, watchdog

        watchdog.beacon(watchdog.PHASE_DISPATCH)
        faults.fire(faults.SITE_SOLVE)  # named site: solve-dispatch hazard
        self._maybe_corrupt_resident()  # device.buffer corrupt-fault drill
        if self.problem is None:
            raise ValueError(
                "This solver has been closed (close() released its device "
                "memory); build a new DistributedSARTSolver."
            )
        opts = self.opts
        dtype = jnp.dtype(opts.dtype)
        B = lane_state.lanes
        norms = lane_state.norms.copy()
        if refills:
            refill = np.zeros(B, bool)
            g_new = np.full((B, self.padded_npixel), -1.0, dtype)
            msq_new = np.ones(B)
            for b, meas in refills:
                meas = np.asarray(meas, np.float64)
                if meas.shape != (self.npixel,):
                    raise ValueError(
                        f"Refill measurement for lane {b} has shape "
                        f"{meas.shape}, expected ({self.npixel},)."
                    )
                if refill[b]:
                    raise ValueError(
                        f"Lane {b} refilled twice in one stride.")
                g64, msq, norm = prepare_measurement(meas, opts)
                g_new[b] = pad_measurement(
                    g64, self.n_pixel_shards, target=self.padded_npixel
                )
                msq_new[b] = msq
                norms[b] = norm
                refill[b] = True
            g_dev = _stage(g_new, self.mesh, P(None, PIXEL_AXIS))
            msq_dev = _stage(msq_new.astype(dtype), self.mesh, P())
            refill_dev = _stage(refill, self.mesh, P())
        else:
            # pure drain stride (queue exhausted, in-flight lanes running
            # out): reuse one cached device-resident no-refill operand
            # set instead of staging [B, P] of inert rows every stride —
            # the tail of every run is drain strides, and the refill
            # merge is skipped on device anyway (cond on any(refill))
            if lane_state._drain_args is None:
                lane_state._drain_args = (
                    _stage(np.full((B, self.padded_npixel), -1.0, dtype),
                           self.mesh, P(None, PIXEL_AXIS)),
                    _stage(np.ones(B, dtype), self.mesh, P()),
                    _stage(np.zeros(B, bool), self.mesh, P()),
                )
            g_dev, msq_dev, refill_dev = lane_state._drain_args
        new_state = self._sched_fn()(
            self.problem, lane_state.state, g_dev, msq_dev, refill_dev,
        )
        # commit only after a successful dispatch: an OOM/fault above must
        # leave the previous stride's state intact for the caller
        lane_state.state = new_state
        lane_state.norms = norms
        lane_state._repack()

    def _sched_ckpt_sig(self) -> str:
        """Configuration signature stored in solve checkpoints: a resume
        under different solver/mesh knobs would restore lane state whose
        meaning changed (dtype, momentum carries, subset stacking, padded
        shapes) — the restore refuses instead of corrupting."""
        opts = self.opts
        return "|".join(str(v) for v in (
            opts.dtype, opts.rtm_dtype, opts.momentum,
            int(opts.logarithmic), opts.os_subsets, opts.schedule_stride,
            self.padded_npixel, self.padded_nvoxel,
        ))

    def export_sched_lanes(self, lane_state: SchedLaneState) -> dict:
        """Host snapshot of the full lane state for the in-solve pod
        checkpoint (resilience/podckpt.py): every ``SchedState``
        component materialized host-side bit-exactly, plus the per-lane
        fp64 norms the device cannot carry. Addressable-shards only
        (``np.asarray``) — exactly the scheduler path's domain: the
        continuous batcher is single-process per pod worker, and the
        real-multihost frame loop is the classic (non-sched) path."""
        st = lane_state.state
        return {
            "sig": self._sched_ckpt_sig(),
            "lanes": int(lane_state.lanes),
            "norms": np.asarray(lane_state.norms, np.float64),
            "state": {
                name: (None if getattr(st, name) is None
                       else np.asarray(getattr(st, name)))
                for name in SchedState._fields
            },
        }

    def restore_sched_lanes(self, exported: dict,
                            kill_lanes=()) -> SchedLaneState:
        """Re-stage an :meth:`export_sched_lanes` snapshot as live lane
        state — the resume-side half of the in-solve checkpoint.

        Staging mirrors :meth:`sched_lanes` exactly (same ``_stage``
        calls, same specs, same dtypes — the exported arrays carry the
        device dtypes bit-exactly), so the restored state keys the SAME
        compiled stride program: the one-compiled-program contract holds
        across a resume. ``kill_lanes`` are reset to the inert-lane
        values before staging — lanes whose occupant the killed run
        already retired *and wrote* (re-running them would duplicate
        output rows). Raises ValueError when the snapshot's
        configuration signature does not match this solver."""
        if exported.get("sig") != self._sched_ckpt_sig():
            raise ValueError(
                "Solve checkpoint does not match this solver "
                f"configuration (checkpoint {exported.get('sig')!r}, "
                f"solver {self._sched_ckpt_sig()!r})."
            )
        B = int(exported["lanes"])
        st = {k: (None if v is None else np.asarray(v))
              for k, v in exported["state"].items()}
        norms = np.array(exported["norms"], np.float64, copy=True)
        for b in kill_lanes:
            st["g"][b] = -1.0
            st["msq"][b] = 1
            st["f"][b] = 1
            st["fitted"][b] = 0
            st["conv"][b] = 0
            st["it"][b] = 0
            st["done"][b] = True
            st["status"][b] = MAX_ITERATIONS_EXCEEDED
            st["iters"][b] = 0
            st["ascale"][b] = 1
            st["recov"][b] = 0
            if st["obs"] is not None:
                st["obs"][b] = 0
            if st["f_prev"] is not None:
                st["f_prev"][b] = 1
            if st["fitted_prev"] is not None:
                st["fitted_prev"][b] = 0
            if st["tk"] is not None:
                st["tk"][b] = 1
            norms[b] = 1.0
        pix = P(None, PIXEL_AXIS)
        vox = P(None, VOXEL_AXIS)
        rep = P()
        state = SchedState(
            g=_stage(st["g"], self.mesh, pix),
            msq=_stage(st["msq"], self.mesh, rep),
            f=_stage(st["f"], self.mesh, vox),
            fitted=_stage(st["fitted"], self.mesh, pix),
            conv=_stage(st["conv"], self.mesh, rep),
            it=_stage(st["it"], self.mesh, rep),
            done=_stage(st["done"], self.mesh, rep),
            status=_stage(st["status"], self.mesh, rep),
            iters=_stage(st["iters"], self.mesh, rep),
            ascale=_stage(st["ascale"], self.mesh, rep),
            recov=_stage(st["recov"], self.mesh, rep),
            obs=(None if st["obs"] is None else _stage(
                st["obs"], self.mesh,
                P(None, None, VOXEL_AXIS) if self.opts.os_subsets > 1
                else vox,
            )),
            f_prev=(None if st["f_prev"] is None
                    else _stage(st["f_prev"], self.mesh, vox)),
            fitted_prev=(None if st["fitted_prev"] is None
                         else _stage(st["fitted_prev"], self.mesh, pix)),
            tk=(None if st["tk"] is None
                else _stage(st["tk"], self.mesh, rep)),
        )
        lane_state = SchedLaneState(self, state, B)
        lane_state.norms = norms
        lane_state._repack()
        return lane_state

    def solve(self, measurement, f0=None, *, local: bool = False) -> SolveResult:
        """Solve one frame — the B=1 case of :meth:`solve_batch`."""
        if local:
            expected = sum(cnt for _, cnt in self.local_pixel_runs())
        else:
            expected = self.npixel
        if np.shape(measurement)[0] != expected:
            raise ValueError(
                f"Measurement has {np.shape(measurement)[0]} pixels, "
                f"expected {expected}."
            )
        res = self.solve_batch(
            np.asarray(measurement)[None, :],
            None if f0 is None else np.asarray(f0)[None, :],
            local=local,
        )
        return SolveResult(
            res.solution[0], int(res.status[0]),
            int(res.iterations[0]), float(res.convergence[0]),
        )


# --------------------------------------------------------------------------
# compile-audit self-registration (analysis/registry.py). The sharded
# batch step is where a collective creeping into the iteration body costs
# ICI latency every iteration: the UNFUSED pixel-sharded loop is budgeted
# at its two designed all-reduces (back-projection psum + convergence-
# metric psum) and zero gathers; the FUSED panel-scan loop at exactly
# panel-count + 1 (one bp psum per voxel panel + the metric psum). Both
# forbid any local-block-sized copy/convert inside the loop — the sharded
# twins of the "sweep" entry's guarantees, plus golden signatures.

from sartsolver_tpu.analysis.registry import (  # noqa: E402
    AUDIT_P as _AUDIT_P,
    AUDIT_V as _AUDIT_V,
    register_audit_entry as _register_audit_entry,
)

_AUDIT_SHARDS = 2
# Deterministic panel width for the fused panel-scan entry: pins the
# per-iteration collective count at a known value (independent of the
# SART_FUSED_PANEL_BYTES env, which would otherwise leak into the golden).
_AUDIT_PANEL_VOXELS = 256
_AUDIT_PANELS = _AUDIT_V // _AUDIT_PANEL_VOXELS


def _audit_sharded_lowering(opts: SolverOptions, H=None):
    """Shared fixture: lower the batched solve step of a 2x1 pixel-sharded
    mesh under the given options (the unfused and fused-panel entries
    differ only in their SolverOptions; the sparse entry additionally
    supplies a half-empty matrix)."""
    if H is None:
        rng = np.random.default_rng(7)
        H = rng.random((_AUDIT_P, _AUDIT_V)).astype(np.float32)
    solver = DistributedSARTSolver(
        H, opts=opts, mesh=make_mesh(_AUDIT_SHARDS, 1)
    )
    g = jax.device_put(
        np.ones((1, solver.padded_npixel), np.float32),
        NamedSharding(solver.mesh, P(None, PIXEL_AXIS)),
    )
    f0 = jax.device_put(
        np.zeros((1, solver.padded_nvoxel), np.float32),
        NamedSharding(solver.mesh, P(None, VOXEL_AXIS)),
    )
    return solver._batch_fn(True).lower(
        solver.problem, g, jnp.ones(1, jnp.float32), f0
    )


@_register_audit_entry(
    "sharded_batch",
    description=f"pixel-sharded batched solve step "
                f"({_AUDIT_SHARDS}x1 mesh, fp32)",
    loop_copy_threshold=(_AUDIT_P // _AUDIT_SHARDS) * _AUDIT_V,
    loop_convert_threshold=(_AUDIT_P // _AUDIT_SHARDS) * _AUDIT_V,
    loop_collective_budget={
        "all-reduce": 2, "all-gather": 0, "all-to-all": 0,
        "collective-permute": 0,
    },
    min_devices=_AUDIT_SHARDS,
)
def _audit_sharded_batch():
    return _audit_sharded_lowering(SolverOptions(
        max_iterations=8, conv_tolerance=1e-30, fused_sweep="off"
    ))


@_register_audit_entry(
    "sharded_fused_batch",
    description=f"pixel-sharded FUSED panel-scan solve step "
                f"({_AUDIT_SHARDS}x1 mesh, fp32, {_AUDIT_PANELS} panels): "
                "one RTM read per iteration, one psum per panel",
    # per-shard thresholds: a whole-block copy/convert in the loop would be
    # the second HBM sweep the panel scan exists to remove; panel-sized
    # slices (1/_AUDIT_PANELS of the block) stay legal
    loop_copy_threshold=(_AUDIT_P // _AUDIT_SHARDS) * _AUDIT_V,
    loop_convert_threshold=(_AUDIT_P // _AUDIT_SHARDS) * _AUDIT_V,
    # collective budget parameterized by the panel count: one back-
    # projection psum PER PANEL plus the convergence-metric psum — the
    # golden's exact-match histogram additionally pins equality, proving
    # the per-panel reduction structure (ISSUE 5 acceptance)
    loop_collective_budget={
        "all-reduce": _AUDIT_PANELS + 1, "all-gather": 0, "all-to-all": 0,
        "collective-permute": 0,
    },
    min_devices=_AUDIT_SHARDS,
)
def _audit_sharded_fused_batch():
    return _audit_sharded_lowering(SolverOptions(
        max_iterations=8, conv_tolerance=1e-30, fused_sweep="on",
        fused_panel_voxels=_AUDIT_PANEL_VOXELS,
    ))


@_register_audit_entry(
    "sharded_integrity_batch",
    description=f"pixel-sharded batched solve step WITH the in-solve ABFT "
                f"integrity check ({_AUDIT_SHARDS}x1 mesh, fp32): the "
                "forward checksum and lambda.w dot are STACKED into the "
                "convergence metric's all-reduce, so the per-iteration "
                "collective budget stays at the plain sharded_batch count",
    loop_copy_threshold=(_AUDIT_P // _AUDIT_SHARDS) * _AUDIT_V,
    loop_convert_threshold=(_AUDIT_P // _AUDIT_SHARDS) * _AUDIT_V,
    # THE invariant of the fold (ISSUE 7 acceptance): integrity on adds
    # ZERO collectives to the audited loop — the back-projection psum and
    # the (now checksum-carrying) metric psum, nothing else
    loop_collective_budget={
        "all-reduce": 2, "all-gather": 0, "all-to-all": 0,
        "collective-permute": 0,
    },
    min_devices=_AUDIT_SHARDS,
)
def _audit_sharded_integrity_batch():
    return _audit_sharded_lowering(SolverOptions(
        max_iterations=8, conv_tolerance=1e-30, fused_sweep="off",
        integrity=True,
    ))


@_register_audit_entry(
    "sharded_sched_step",
    description=f"continuous-batching scheduler stride "
                f"({_AUDIT_SHARDS}x1 mesh, fp32, 2 lanes): masked-lane "
                "stepped sweep + refill branch — THE one compiled program "
                "serving every lane occupancy",
    loop_copy_threshold=(_AUDIT_P // _AUDIT_SHARDS) * _AUDIT_V,
    loop_convert_threshold=(_AUDIT_P // _AUDIT_SHARDS) * _AUDIT_V,
    # the stepped while body carries per-lane bookkeeping but must issue
    # exactly the batched loop's two designed all-reduces (back-projection
    # psum + convergence-metric psum); the refill branch's guess psums sit
    # OUTSIDE the loop, amortized over schedule_stride iterations
    loop_collective_budget={
        "all-reduce": 2, "all-gather": 0, "all-to-all": 0,
        "collective-permute": 0,
    },
    min_devices=_AUDIT_SHARDS,
)
def _audit_sched_step():
    rng = np.random.default_rng(11)
    H = rng.random((_AUDIT_P, _AUDIT_V)).astype(np.float32)
    solver = DistributedSARTSolver(
        H,
        opts=SolverOptions(max_iterations=8, conv_tolerance=1e-30,
                           fused_sweep="off", schedule_stride=4),
        mesh=make_mesh(_AUDIT_SHARDS, 1),
    )
    lanes = solver.sched_lanes(2)
    g_new = jax.device_put(
        np.ones((2, solver.padded_npixel), np.float32),
        NamedSharding(solver.mesh, P(None, PIXEL_AXIS)),
    )
    return solver._sched_fn().lower(
        solver.problem, lanes.state, g_new,
        jnp.ones(2, jnp.float32),
        jnp.asarray(np.asarray([True, False])),
    )


# 50% panel occupancy on the sparse entries' shared fixture: the first
# half of the voxel extent carries data, the second half is exactly zero
# — 2 of 4 256-wide panels occupied at eps=0 (lossless).
_AUDIT_SPARSE_PANELS_OCCUPIED = 2


@_register_audit_entry(
    "sharded_sparse_panel_sweep",
    description=f"pixel-sharded BLOCK-SPARSE panel-scan solve step "
                f"({_AUDIT_SHARDS}x1 mesh, fp32, {_AUDIT_PANELS} panels, "
                f"{_AUDIT_SPARSE_PANELS_OCCUPIED} occupied): one psum per "
                "OCCUPIED panel — the cost golden pins FLOPs/bytes "
                "scaling with occupancy, and the collective budget pins "
                "that skipped panels skip their psum too",
    loop_copy_threshold=(_AUDIT_P // _AUDIT_SHARDS) * _AUDIT_V,
    loop_convert_threshold=(_AUDIT_P // _AUDIT_SHARDS) * _AUDIT_V,
    # one back-projection psum PER OCCUPIED PANEL plus the convergence-
    # metric psum; a silent densification would issue _AUDIT_PANELS + 1
    # and fail this budget before it even reaches the cost band
    loop_collective_budget={
        "all-reduce": _AUDIT_SPARSE_PANELS_OCCUPIED + 1, "all-gather": 0,
        "all-to-all": 0, "collective-permute": 0,
    },
    min_devices=_AUDIT_SHARDS,
    # densification must trip the band (see sparse_panel_sweep)
    cost_rtol=0.25,
)
def _audit_sharded_sparse_panel_sweep():
    rng = np.random.default_rng(7)
    H = rng.random((_AUDIT_P, _AUDIT_V)).astype(np.float32)
    H[:, _AUDIT_SPARSE_PANELS_OCCUPIED * _AUDIT_PANEL_VOXELS:] = 0.0
    return _audit_sharded_lowering(SolverOptions(
        max_iterations=8, conv_tolerance=1e-30, fused_sweep="off",
        sparse_rtm="auto", fused_panel_voxels=_AUDIT_PANEL_VOXELS,
    ), H=H)


@_register_audit_entry(
    "sharded_implicit_batch",
    description=f"pixel-sharded MATRIX-FREE batched solve step "
                f"({_AUDIT_SHARDS}x1 mesh, fp32, geometry-traced "
                "projections): the implicit panel loops replace both "
                "matrix contractions, yet the loop must issue exactly the "
                "dense sharded_batch's two designed all-reduces (back-"
                "projection psum + convergence-metric psum) — the psum "
                "composition invariant of the matrix-free backend",
    # no matrix exists, so a matrix-block copy/convert cannot either —
    # the thresholds keep the dense entries' bound, pinning that the
    # traced kernel never materializes anything H-sized in the loop
    loop_copy_threshold=(_AUDIT_P // _AUDIT_SHARDS) * _AUDIT_V,
    loop_convert_threshold=(_AUDIT_P // _AUDIT_SHARDS) * _AUDIT_V,
    # MUST equal sharded_batch's budget (ISSUE 19 acceptance): switching
    # backends changes what a "sweep" reads, never how often devices talk
    loop_collective_budget={
        "all-reduce": 2, "all-gather": 0, "all-to-all": 0,
        "collective-permute": 0,
    },
    min_devices=_AUDIT_SHARDS,
)
def _audit_sharded_implicit_batch():
    from sartsolver_tpu.operators.geometry import Camera, GeometryRecord
    from sartsolver_tpu.operators.implicit import ImplicitOperator

    # one 8x16 camera = AUDIT_P rays; an (8, 8, 16) grid = AUDIT_V voxels
    # (both already tile-aligned, so padding is the identity and the
    # thresholds above describe the staged shapes exactly)
    rec = GeometryRecord(
        grid_shape=(8, 8, 16), origin=(0.0, 0.0, 0.0),
        spacing=(1.0, 1.0, 1.0),
        cameras=(Camera(
            name="cam0", rows=8, cols=16,
            position=(-20.0, 4.1, 8.2), target=(4.0, 4.0, 8.0),
            pitch=0.9,
        ),),
    )
    solver = DistributedSARTSolver(
        opts=SolverOptions(max_iterations=8, conv_tolerance=1e-30,
                           fused_sweep="off"),
        mesh=make_mesh(_AUDIT_SHARDS, 1),
        operator=ImplicitOperator(rec),
    )
    g = jax.device_put(
        np.ones((1, solver.padded_npixel), np.float32),
        NamedSharding(solver.mesh, P(None, PIXEL_AXIS)),
    )
    f0 = jax.device_put(
        np.zeros((1, solver.padded_nvoxel), np.float32),
        NamedSharding(solver.mesh, P(None, VOXEL_AXIS)),
    )
    return solver._batch_fn(True).lower(
        solver.problem, g, jnp.ones(1, jnp.float32), f0
    )


@_register_audit_entry(
    "sharded_lowrank_batch",
    description=f"pixel-sharded FACTORED (S + U V^T) batched solve step "
                f"({_AUDIT_SHARDS}x1 mesh, fp32, rank 8, "
                f"{_AUDIT_SPARSE_PANELS_OCCUPIED} of {_AUDIT_PANELS} "
                "core panels occupied): the sparse-core panel dots skip "
                "empty panels and the factor term rides two skinny "
                "matmuls, yet the loop must issue exactly the dense "
                "sharded_batch's two designed all-reduces — "
                "lowrank_back returns the composed LOCAL partial, so "
                "the one back-projection psum folds the factor term's "
                "contribution (no extra collective for the fill)",
    # the composed sweep touches the row-sharded core block plus two
    # skinny factors; a matrix-block copy/convert in the loop would be
    # a silent densification of exactly what the factorization removed
    loop_copy_threshold=(_AUDIT_P // _AUDIT_SHARDS) * _AUDIT_V,
    loop_convert_threshold=(_AUDIT_P // _AUDIT_SHARDS) * _AUDIT_V,
    # MUST equal sharded_batch's budget (the implicit entry's psum
    # composition invariant): factoring the matrix changes what a sweep
    # multiplies, never how often devices talk
    loop_collective_budget={
        "all-reduce": 2, "all-gather": 0, "all-to-all": 0,
        "collective-permute": 0,
    },
    min_devices=_AUDIT_SHARDS,
)
def _audit_sharded_lowrank_batch():
    from sartsolver_tpu.operators.lowrank import (
        LowRankOperator, split_sparse_core,
    )

    # the sparse entries' 50%-occupancy fixture as the core, plus a
    # dense rank-8 fill — the shape build_lowrank_operator produces,
    # constructed directly so the audit pins the compiled program, not
    # the host-side factorization gates
    rng = np.random.default_rng(7)
    S = rng.random((_AUDIT_P, _AUDIT_V)).astype(np.float32)
    S[:, _AUDIT_SPARSE_PANELS_OCCUPIED * _AUDIT_PANEL_VOXELS:] = 0.0
    S, occ = split_sparse_core(S, epsilon=0.0)
    u = (0.01 * rng.standard_normal((_AUDIT_P, 8))).astype(np.float32)
    v = rng.standard_normal((_AUDIT_V, 8)).astype(np.float32)
    solver = DistributedSARTSolver(
        opts=SolverOptions(max_iterations=8, conv_tolerance=1e-30,
                           fused_sweep="off"),
        mesh=make_mesh(_AUDIT_SHARDS, 1),
        operator=LowRankOperator(S, u, v, occupancy=occ),
    )
    g = jax.device_put(
        np.ones((1, solver.padded_npixel), np.float32),
        NamedSharding(solver.mesh, P(None, PIXEL_AXIS)),
    )
    f0 = jax.device_put(
        np.zeros((1, solver.padded_nvoxel), np.float32),
        NamedSharding(solver.mesh, P(None, VOXEL_AXIS)),
    )
    return solver._batch_fn(True).lower(
        solver.problem, g, jnp.ones(1, jnp.float32), f0
    )

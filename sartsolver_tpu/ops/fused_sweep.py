"""Fused SART iteration sweep — one HBM read of the RTM per iteration.

The SART loop body is two dense sweeps over the RTM separated by cheap
elementwise math (reference: PropagateKernel then cublasSgemv,
sartsolver_cuda.cpp:239-249):

    bp     = H^T w                 (back-projection,   reads H)
    f_new  = update(f, bp, ...)    (elementwise, O(nvoxel))
    fitted = H f_new               (forward projection, reads H again)

As two XLA matmuls the RTM — the tens-to-hundreds-of-GB operand the whole
design revolves around — is streamed from HBM **twice** per iteration, and
both sweeps are gemv-shaped (a handful of flops per matrix element), so
the HBM stream is the bound to reach. This Pallas kernel tiles the voxel
axis and keeps each column panel ``H[:, j*bs:(j+1)*bs]`` resident in VMEM
for *both* uses:

    for each voxel panel j:              (grid, panels DMA-pipelined)
        bp_j      = w @ H_panel          (contraction over pixels)
        f_new_j   = update(f_j, bp_j, aux_j...)   (VPU)
        fitted   += f_new_j @ H_panel^T  (accumulated in VMEM)

halving the HBM bill of the hot loop. The elementwise ``update`` is a
trace-time closure, so the linear (Eq. 2) and logarithmic (Eq. 3) variants
specialize the same kernel the way the reference specializes
UpdateSolutionKernel / UpdateLogSolutionKernel (sart_kernels.cu:205-224).

Which unit contracts a panel depends on what the MXU would have to do
with it. An fp32 panel at a small batch (up to ``_VPU_MAX_BATCH``
frames) contracts on the VPU, in fp32 multiply-adds over row chunks: four
VPU operations an element, which hide under the panel's DMA. On the MXU the
same matvec needs fp32 precision, that is ``Precision.HIGHEST``, a
multi-pass bf16 emulation that re-splits the panel in both dots every
iteration and left the kernel contraction-bound at 69% of the HBM bound
(measured on v5e at 6144x524288, PERF.md §6). Larger batches, where
the MXU's cost does not grow with the batch and the VPU's does, and bf16
and int8 panels (one MXU pass, no split), stay on the MXU.

The Pallas kernel requires the full pixel extent of the panel on this
device (the back-projection psum would have to run between the panel's
two contractions). Voxel-axis sharding composes fine: each device fuses
over its column block and the forward-projection psum runs on the
kernel's output.
Pixel-axis sharding gets the same one-HBM-read structure from
:func:`sharded_panel_sweep` instead: a plain-XLA voxel-panel scan that
psums each panel's back-projection over the pixel axis *between* the
panel's two dots — the per-panel ICI reduction overlaps with the next
panel's MXU work instead of a whole-vector psum serializing two full HBM
sweeps.

Layout note (measured on TPU v5e, 2026-07-29): the column panels of the
row-major [P, V] RTM are strided in HBM (P short bursts per panel), but a
voxel-major [V, P] layout with fully contiguous panels measured *identical*
throughput (fp32 306 vs 307 iter/s, bf16 569 vs 572 at 8192x65536) — the
DMA engine hides the stride, so the storage layout stays row-major for
parity with the reference (raytransfer.hpp:20) and ingest simplicity.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Per-panel VMEM footprint target for the RTM panel (double-buffered by the
# Pallas pipeline, so actual use is ~2x this plus the pixel-axis residents).
# Env-tunable for on-hardware sweeps: larger panels = fewer grid steps and
# longer DMA bursts, at the cost of VMEM headroom. Validated and clamped so
# a bad value degrades to the default / a safe bound instead of pushing
# fused_available() past what VMEM can hold (the compile self-test runs a
# toy shape and would not catch an oversized real-shape panel).
import os as _os


def _env_bytes(name: str, default: int, lo: int, hi: int) -> int:
    try:
        v = int(_os.environ.get(name, default))
    except ValueError:
        return default
    return max(lo, min(v, hi))


# hi: 2x panel (double-buffered) + residents must stay inside the VMEM the
# compiler will grant the kernel (see the scoped-VMEM model below).
_PANEL_BYTES_TARGET = _env_bytes(
    "SART_FUSED_PANEL_BYTES", 8 << 20, 1 << 20, 12 << 20)
# int8 panels carry a per-element VPU dequant cost, so fewer/larger panels
# win (measured v5e 2026-07-30, 8192x65536: bs 512 -> 1024 is +1.7% at B=1
# and +12% at B=32), while bf16 at batch shapes *loses* from the added VMEM
# pressure (B=32: 390 iter/s at bs=256 vs 306 at bs=512) — hence a separate,
# larger default target for 1-byte storage only. Its own env var overrides
# first, so tuning SART_FUSED_PANEL_BYTES no longer silently collapses the
# measured int8-vs-bf16 split into one value.
_PANEL_BYTES_TARGET_INT8 = _env_bytes(
    "SART_FUSED_PANEL_BYTES_INT8",
    _env_bytes("SART_FUSED_PANEL_BYTES", 12 << 20, 1 << 20, 12 << 20),
    1 << 20, 12 << 20)
_MIN_BLOCK_VOXELS = 128  # lane width
_SUBLANE = 8  # fp32 sublane width
# Largest batch whose fp32 panels contract on the VPU (_sweep_kernel_vpu);
# above it, and for bf16/int8 panels, the MXU contracts them. Measured on
# v5e at the 6144x524288 block (PERF.md §6): the VPU form took 18.6 ms at
# B=1 and at B=2, 28.3 ms at B=4 and 85 ms at B=8, where the MXU form
# took 22.8-23.0 ms at every B.
_VPU_MAX_BATCH = 2
# Pixel rows a step of the VPU form's row loop takes (a multiple of 8).
_VPU_ROWS = 128

# XLA charges a Pallas kernel's entire VMEM footprint (double-buffered
# operand/output blocks, scratch, plus any operands/results XLA itself
# decides to stack-allocate in VMEM) against --xla_tpu_scoped_vmem_limit_kib,
# which defaults to 16 MiB — NOT against the chip's full physical VMEM
# (~128 MiB on v5e). Measured on TPU v5e 2026-07-29: an 8192x256 fp32 panel
# (2x8.4 MiB double-buffered) already fails to compile at the default limit.
# When the estimated footprint exceeds the default, the solver passes a
# raised limit via jit(compiler_options=...); raising it is a bound, not an
# allocation, and measured throughput is unchanged (306 iter/s at bs=128
# default vs bs=512 with a 64 MiB limit). Estimates above the raise cap make
# the shape ineligible for fusion instead.
_SCOPED_VMEM_DEFAULT_BYTES = 16 << 20
_SCOPED_VMEM_RAISED_KIB = 65536  # 64 MiB
_SCOPED_VMEM_EST_CAP_BYTES = 48 << 20


# Conservative count of [B, bs] voxel-panel operands cycling through VMEM
# alongside the RTM panel: f, f_new, and up to three aux inputs, each
# double-buffered by the Pallas pipeline.
_VOXEL_PANEL_OPERANDS = 10


def _contracts_on_vpu(itemsize: int, batch: int) -> bool:
    """Whether the kernel contracts its panels on the VPU: fp32 panels
    (the only 4-byte storage) at ``batch <= _VPU_MAX_BATCH``. Otherwise
    the MXU does (see :func:`_sweep_kernel_vpu`)."""
    return itemsize == 4 and batch <= _VPU_MAX_BATCH


def _scoped_vmem_estimate(
    npixel: int, nvoxel: int, bs: int, itemsize: int, batch: int
) -> int:
    """Upper-bound estimate of the kernel's scoped-VMEM charge, bytes.

    Over-estimating is safe (the solver just requests the raised limit);
    under-estimating would reproduce the round-2 compile failure, so every
    term XLA has been observed charging is included: double-buffered RTM
    panels, double-buffered voxel-panel operands, the pixel-axis residents,
    and the [B, V]/[B, P] outputs XLA stack-allocates in VMEM (observed
    S(1) placement). bf16 panels feed the MXU directly (no conversion
    scratch — see _sweep_kernel); int8 panels dequantize to a bf16 scratch
    copy in VMEM (measured: int8 bs=512 needs 16.39M at B=1, over the
    16M default). fp32 panels on the MXU contract at HIGHEST precision,
    which Mosaic charges about four more fp32 panels for (compiled for a
    described v5e, PR 21: 23.96M at 8192 pixels, bs=128, B=1, where the
    default precision fit the 16M default). fp32 panels on the VPU need
    instead the [B, P, 128] fitted accumulator and the lane-padded [P, B]
    weight column (described v5e, 2026-10: at 6144 pixels, bs=256,
    15.09M at B=1 against 36.25M at HIGHEST, 18.10M at B=2; at 8192
    pixels, bs=128, 12.07M at B=1 and 16.07M at B=2)."""
    vpu = _contracts_on_vpu(itemsize, batch)
    return (
        2 * npixel * bs * itemsize
        + (npixel * bs * 2 if itemsize == 1 else 0)
        + (4 * npixel * bs * 4 if itemsize == 4 and not vpu else 0)
        + ((batch + 1) * npixel * _MIN_BLOCK_VOXELS * 4 if vpu else 0)
        + 2 * _VOXEL_PANEL_OPERANDS * batch * bs * 4
        + 2 * batch * npixel * 4
        + batch * (nvoxel + npixel) * 4
    )


def raised_vmem_options() -> dict:
    """The compiler-options dict that raises XLA's scoped-VMEM limit —
    single source of truth for the flag name/value (used by the solver
    dispatcher and the sharded driver's outer jit). TPU-only flag: attach
    only when ``jax.default_backend() == "tpu"``."""
    return {"xla_tpu_scoped_vmem_limit_kib": str(_SCOPED_VMEM_RAISED_KIB)}


def fused_compile_options(
    npixel: int, nvoxel: int, itemsize: int, batch: int = 1
) -> dict | None:
    """XLA compiler options the fused sweep needs at these shapes.

    Returns :func:`raised_vmem_options` when the estimated kernel footprint
    exceeds XLA's default scoped-VMEM budget, else None. TPU-only flag —
    callers must additionally gate on a TPU default backend (explicit
    ``fused_sweep="on"`` can engage the kernel off-TPU).
    """
    bs = pick_block_voxels(npixel, nvoxel, itemsize, batch)
    if bs <= 0:
        return None
    est = _scoped_vmem_estimate(npixel, nvoxel, bs, itemsize, batch)
    if est <= _SCOPED_VMEM_DEFAULT_BYTES - (512 << 10):
        return None
    return raised_vmem_options()


def _seed_panel_width(
    npixel: int, nvoxel: int, itemsize: int, batch: int
) -> int:
    """Initial voxel-panel width for both pickers: the largest multiple of
    128 under the ``SART_FUSED_PANEL_BYTES`` target (``_INT8`` variant for
    1-byte storage) for one RTM panel plus the batch-scaled operand
    panels, clamped to [128, nvoxel]. The single source of the byte-target
    math — the Pallas and panel-scan pickers differ only in the predicate
    their divisor walk applies."""
    target = _PANEL_BYTES_TARGET_INT8 if itemsize == 1 else _PANEL_BYTES_TARGET
    per_voxel = npixel * itemsize + _VOXEL_PANEL_OPERANDS * batch * 4
    bs = (target // max(per_voxel, 1)) // 128 * 128
    return min(max(bs, _MIN_BLOCK_VOXELS), nvoxel)


def pick_block_voxels(
    npixel: int, nvoxel: int, itemsize: int, batch: int = 1
) -> int:
    """Voxel-panel width (multiple of 128, dividing nvoxel) for the fused
    sweep: the largest width under the panel-bytes target — a throughput
    heuristic — whose whole-kernel scoped-VMEM estimate also fits the raise
    cap, the hard constraint (a panel at the byte target can push a large
    batch past the cap, where a narrower panel still fuses). Tall matrices
    (npixel so large even a 128-wide panel exceeds the byte target — e.g.
    the per-chip shard of a voxel-major mesh) fall back to the minimum
    width rather than losing fusion, since only the estimate cap is load-
    bearing. 0 if no width fits the cap (or nvoxel is not a multiple of
    128)."""
    if nvoxel % _MIN_BLOCK_VOXELS:
        return 0
    bs = _seed_panel_width(npixel, nvoxel, itemsize, batch)
    while bs >= _MIN_BLOCK_VOXELS:
        if nvoxel % bs == 0 and (
            _scoped_vmem_estimate(npixel, nvoxel, bs, itemsize, batch)
            <= _SCOPED_VMEM_EST_CAP_BYTES
        ):
            return bs
        bs -= _MIN_BLOCK_VOXELS
    return 0


def fused_available(npixel: int, nvoxel: int, rtm_itemsize: int, batch: int = 1) -> bool:
    """Shapes aligned for the fused sweep: pixel rows fill fp32 sublanes, a
    voxel panel (RTM + batch-scaled operand panels) fits the panel budget,
    and the kernel's estimated scoped-VMEM footprint stays within the raise
    cap (see :func:`fused_compile_options`)."""
    if npixel % _SUBLANE:
        return False
    # the picker already enforces the scoped-VMEM raise cap on its result,
    # so a positive width IS eligibility
    return pick_block_voxels(npixel, nvoxel, rtm_itemsize, batch) > 0


# --------------------------------------------------------------------------
# Pixel-sharded variant: voxel-panel scan with a per-panel collective.
#
# With the pixel axis sharded, each device owns a row stripe H_r and the
# back-projection needs a psum over the pixel shards. Running that psum on
# the whole [B, V] vector between two full-matrix matmuls (the unfused
# sharded path) costs a second HBM read of the stripe AND serializes the
# collective against both sweeps. Here the stripe is streamed through once
# in voxel panels: each panel's local back-projection contribution is
# psummed over the pixel axis *while the panel is still resident*, the
# elementwise update runs on the reduced panel, and the locally-complete
# forward-projection contribution accumulates with no collective (each
# device owns its own pixel rows of `fitted`). The panel loop is unrolled
# at trace time, so XLA's latency-hiding scheduler can overlap panel j's
# all-reduce with panel j+1's MXU work — and the compile audit can count
# one dot pair + one all-reduce per panel in the HLO
# (parallel/sharded.py: sharded_fused_batch).


def _psum(x, axis_name):
    """The solver core's psum (models/sart.py, imported here late: it
    imports this module), named by its collective scope ``sart.exchange``."""
    from sartsolver_tpu.models.sart import _psum as psum

    return psum(x, axis_name)


def pick_panel_voxels(
    npixel: int, nvoxel: int, itemsize: int, batch: int = 1
) -> int:
    """Voxel-panel width for :func:`sharded_panel_sweep` — the largest
    multiple of 128 dividing ``nvoxel`` whose RTM panel (plus batch-scaled
    operand panels) stays under the ``SART_FUSED_PANEL_BYTES`` target
    (``_INT8`` variant for 1-byte storage). Unlike :func:`pick_block_voxels`
    there is no scoped-VMEM cap: the panels are plain XLA dot operands, not
    a Pallas kernel's blocks. The byte target doubles as the psum
    granularity knob: ``nvoxel / width`` panels means that many per-
    iteration all-reduces, each overlappable with the next panel's compute
    (docs/MANUAL.md §mesh choice). 0 when ``nvoxel % 128 != 0``."""
    if nvoxel % _MIN_BLOCK_VOXELS:
        return 0
    bs = _seed_panel_width(npixel, nvoxel, itemsize, batch)
    while nvoxel % bs:
        bs -= _MIN_BLOCK_VOXELS
    return bs


def panel_available(
    npixel: int, nvoxel: int, rtm_itemsize: int, batch: int = 1
) -> bool:
    """Shapes aligned for the pixel-sharded panel sweep (per-device block
    sizes): pixel rows fill fp32 sublanes, voxel extent tiles into 128-wide
    panels. The sharded driver's padding (parallel/mesh.py ROW_ALIGN/
    COL_ALIGN) guarantees both on every mesh, so this only declines
    hand-built unpadded blocks."""
    return npixel % _SUBLANE == 0 and pick_panel_voxels(
        npixel, nvoxel, rtm_itemsize, batch
    ) > 0


def sharded_panel_sweep(
    rtm: Array,  # [P_local, V_local] — this device's RTM block
    w: Array,  # [B, P_local] fp32 — local back-projection pixel weights
    f: Array,  # [B, V_local] fp32 — current solution (this voxel block)
    aux: Sequence[Array],  # each [b_i, V_local] (b_i in {1, B}) fp32
    update_fn: Callable[..., Array],
    *,
    axis_name,
    fwd_scale: Optional[int] = None,
    panel_voxels: Optional[int] = None,
):
    """One SART sweep on a pixel-sharded RTM block with ONE local HBM read.

    Returns ``(f_new [B, V_local], fitted [B, P_local])``. ``fitted`` holds
    this device's own pixel rows and is complete as returned — the forward
    projection needs no pixel-axis collective (each device owns its rows);
    a voxel-axis psum, if the mesh also column-shards, is the caller's.

    ``update_fn`` / ``fwd_scale`` follow the :func:`fused_sweep` contract
    exactly (the same linear/log/int8 closures specialize both), except the
    back-projection panel handed to ``update_fn`` is already psummed over
    ``axis_name`` — globally reduced, like the unfused path's ``bp``.
    ``panel_voxels`` overrides the picker (the compile audit pins a
    deterministic panel count with it; None derives from the
    ``SART_FUSED_PANEL_BYTES`` target).
    """
    P, V = rtm.shape
    B = w.shape[0]
    bs = panel_voxels or pick_panel_voxels(P, V, rtm.dtype.itemsize, B)
    if bs <= 0 or V % bs or not panel_available(P, V, rtm.dtype.itemsize, B):
        raise ValueError(
            f"sharded_panel_sweep: shapes [{P}, {V}] (batch {B}, panel "
            f"{bs}) not tile-aligned; gate calls with panel_available()"
            + (" and a panel_voxels override dividing the voxel extent"
               if panel_voxels else "")
            + "."
        )
    n_panels = V // bs
    # Observability (host-side, trace-time — runs once per compilation):
    # the panel/collective plan behind this compiled sweep, so the per-
    # panel psum granularity is visible in --metrics_out without parsing
    # HLO (docs/OBSERVABILITY.md §collective).
    from sartsolver_tpu.obs import metrics as _obs_metrics

    reg = _obs_metrics.get_registry()
    reg.gauge("fused_panel_count", path="sharded_panel").set(n_panels)
    reg.gauge("fused_panel_voxels", path="sharded_panel").set(bs)
    reg.counter(
        "collectives_planned_total", collective="psum", site="panel_bp"
    ).inc(n_panels)

    fitted = None
    f_new_parts = []
    for j in range(n_panels):
        panel = jax.lax.slice_in_dim(rtm, j * bs, (j + 1) * bs, axis=1)
        if panel.dtype == jnp.int8:
            # same in-flight dequantization as the Pallas kernel: exact
            # (|codes| <= 127 in bf16), panel-sized — never a full-matrix
            # convert (the audit's loop_convert_threshold pins this)
            panel = panel.astype(jnp.bfloat16)
        bp = _psum(
            jax.lax.dot_general(
                w, panel,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ),
            axis_name,
        )  # [B, bs] — globally reduced back-projection of this panel
        aux_p = [a[:, j * bs:(j + 1) * bs] for a in aux]
        f_new_p = update_fn(f[:, j * bs:(j + 1) * bs], bp, *aux_p)
        f_new_parts.append(f_new_p)
        fwd = f_new_p if fwd_scale is None else f_new_p * aux_p[fwd_scale]
        contrib = jax.lax.dot_general(
            fwd, panel,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [B, P_local] — local rows, no collective
        fitted = contrib if fitted is None else fitted + contrib
    return jnp.concatenate(f_new_parts, axis=1), fitted


# --------------------------------------------------------------------------
# Block-sparse variants (docs/PERFORMANCE.md §10): the voxel-panel scan
# hosts the sparse path — the panel loop consults the RTM's static
# tile-occupancy index (ops/sparse.py) and SKIPS every all-zero column
# panel's dots entirely. A skipped panel's back-projection is exactly the
# zero the dense dot over a zero panel would produce, and its forward
# contribution is exactly the zero the dense accumulation would add, so
# at eps=0 the sparse sweep is bit-identical to the dense panel scan —
# FLOPs and bytes now scale with occupancy instead of matrix shape.
#
# The occupancy is per-RTM static state (a hashable index the solver
# cores take as a jit-static argument), so the skip pattern is baked at
# trace time: one RTM -> one compiled program, and the continuous-
# batching scheduler's one-compiled-program contract is untouched. The
# skip predicate is COLUMN-GLOBAL (a panel skips only when empty across
# every pixel-block row of the whole matrix), which keeps it SPMD-uniform
# under pixel sharding: every shard of a row-sharded mesh traces the same
# skips and the per-panel psum count stays consistent across shards.
#
# Two hosts for the same skip set:
# - sparse_panel_sweep — the occupancy-driven Python loop (static skip):
#   occupied panels get the sharded_panel_sweep body, skipped panels get
#   only the elementwise update with a zero back-projection. Unrolled at
#   trace time like the sharded panel scan.
# - sparse_gather_sweep — the plain-XLA gather-of-occupied-panels
#   fallback: a fori_loop over the occupied-panel id vector with
#   dynamic_slice panel fetches, engaged when the occupied-panel count
#   would make the unrolled program large (SPARSE_STATIC_UNROLL_MAX).
#   Bit-identical to the static form by construction (same panel order,
#   same elementwise base update).

SPARSE_STATIC_UNROLL_MAX = _env_bytes("SART_SPARSE_UNROLL_MAX", 64, 1, 4096)


def _sparse_trace_obs(occupancy, n_panels: int, n_skipped: int,
                      bs: int, host: str) -> None:
    """Host-side trace-time observability of the sparse plan (runs once
    per compilation, like the sharded panel scan's collective plan):
    the occupancy fraction and the tiles each sweep will skip land in
    --metrics_out without parsing HLO."""
    from sartsolver_tpu.obs import metrics as _obs_metrics

    n_row_tiles = occupancy.grid_shape[0]
    tiles_per_panel = (bs // occupancy.tile_cols) * n_row_tiles
    reg = _obs_metrics.get_registry()
    reg.gauge("rtm_tile_occupancy").set(occupancy.occupancy_fraction())
    reg.gauge("fused_panel_count", path=host).set(n_panels)
    reg.gauge("fused_panel_voxels", path=host).set(bs)
    reg.counter("sparse_tiles_skipped_total", path=host).inc(
        n_skipped * tiles_per_panel
    )


def sparse_panel_sweep(
    rtm: Array,  # [P_local, V] — this device's RTM block
    w: Array,  # [B, P_local] fp32
    f: Array,  # [B, V] fp32
    aux: Sequence[Array],  # each [b_i, V] fp32
    update_fn: Callable[..., Array],
    *,
    occupancy,  # ops.sparse.TileOccupancy over the (padded) global matrix
    axis_name=None,
    fwd_scale: Optional[int] = None,
    panel_voxels: Optional[int] = None,
):
    """One SART sweep skipping all-zero voxel panels — the static-skip
    host of the block-sparse path. Returns ``(f_new [B, V], fitted
    [B, P_local])``; the ``update_fn`` / ``fwd_scale`` contract is
    :func:`sharded_panel_sweep`'s exactly (same closures specialize
    both), and with ``axis_name`` set the occupied panels' back-
    projections psum over the pixel axis like the sharded scan. A
    skipped panel still runs the elementwise update (with the exact-zero
    back-projection dense would compute) — only its two dots and, when
    sharded, its psum are elided.
    """
    P, V = rtm.shape
    B = w.shape[0]
    bs = panel_voxels or pick_panel_voxels(P, V, rtm.dtype.itemsize, B)
    if bs <= 0 or V % bs or not panel_available(P, V, rtm.dtype.itemsize, B):
        raise ValueError(
            f"sparse_panel_sweep: shapes [{P}, {V}] (batch {B}, panel "
            f"{bs}) not tile-aligned; gate calls with panel_available()."
        )
    from sartsolver_tpu.ops.sparse import occupancy_matches

    if not occupancy_matches(occupancy, V, bs):
        raise ValueError(
            f"sparse_panel_sweep: occupancy index covers "
            f"[{occupancy.rows}, {occupancy.cols}] at "
            f"{occupancy.tile_rows}x{occupancy.tile_cols} tiles — it "
            f"cannot drive {bs}-wide panels over a {V}-column block."
        )
    occ_panels = occupancy.col_panel_occupied(bs)
    n_panels = V // bs
    _sparse_trace_obs(occupancy, n_panels, int((~occ_panels).sum()), bs,
                      "sparse_panel")
    if axis_name is not None:
        from sartsolver_tpu.obs import metrics as _obs_metrics

        _obs_metrics.get_registry().counter(
            "collectives_planned_total", collective="psum",
            site="sparse_panel_bp",
        ).inc(int(occ_panels.sum()))

    fitted = None
    f_new_parts = []
    zero_bp = None
    for j in range(n_panels):
        aux_p = [a[:, j * bs:(j + 1) * bs] for a in aux]
        f_p = f[:, j * bs:(j + 1) * bs]
        if not bool(occ_panels[j]):
            # all-zero panel: the dense back-projection over it is
            # exactly zero — run only the elementwise update
            if zero_bp is None:
                zero_bp = jnp.zeros((B, bs), jnp.float32)
            f_new_parts.append(update_fn(f_p, zero_bp, *aux_p))
            continue
        panel = jax.lax.slice_in_dim(rtm, j * bs, (j + 1) * bs, axis=1)
        if panel.dtype == jnp.int8:
            # panel-sized in-flight dequantization — the fused sweeps'
            # int8 idiom (never a full-matrix convert)
            panel = panel.astype(jnp.bfloat16)
        bp = jax.lax.dot_general(
            w, panel,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if axis_name is not None:
            bp = _psum(bp, axis_name)
        f_new_p = update_fn(f_p, bp, *aux_p)
        f_new_parts.append(f_new_p)
        fwd = f_new_p if fwd_scale is None else f_new_p * aux_p[fwd_scale]
        contrib = jax.lax.dot_general(
            fwd, panel,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        fitted = contrib if fitted is None else fitted + contrib
    if fitted is None:  # every panel empty (an all-dark operator)
        fitted = jnp.zeros((B, P), jnp.float32)
    return jnp.concatenate(f_new_parts, axis=1), fitted


def sparse_gather_sweep(
    rtm: Array,
    w: Array,
    f: Array,
    aux: Sequence[Array],
    update_fn: Callable[..., Array],
    *,
    panel_ids: Array,  # int32 [K] — ascending occupied voxel-panel ids
    panel_voxels: int,
    axis_name=None,
    fwd_scale: Optional[int] = None,
):
    """Gather-of-occupied-panels fallback: the same sweep as
    :func:`sparse_panel_sweep` as ONE compact ``fori_loop`` over the
    occupied-panel id vector (dynamic_slice panel fetches) instead of a
    trace-time unroll — for operators whose occupied-panel count would
    bloat the unrolled program. The base update (every voxel with the
    exact-zero back-projection) runs once full-width; occupied panels
    overwrite their slice inside the loop, so results are bit-identical
    to the static form.
    """
    P, V = rtm.shape
    B = w.shape[0]
    bs = int(panel_voxels)
    K = panel_ids.shape[0]

    def body(k, carry):
        f_new, fitted = carry
        start = panel_ids[k] * bs
        panel = jax.lax.dynamic_slice_in_dim(rtm, start, bs, axis=1)
        if panel.dtype == jnp.int8:
            panel = panel.astype(jnp.bfloat16)
        bp = jax.lax.dot_general(
            w, panel,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if axis_name is not None:
            bp = _psum(bp, axis_name)
        f_p = jax.lax.dynamic_slice_in_dim(f, start, bs, axis=1)
        aux_p = [jax.lax.dynamic_slice_in_dim(a, start, bs, axis=1)
                 for a in aux]
        f_new_p = update_fn(f_p, bp, *aux_p)
        f_new = jax.lax.dynamic_update_slice_in_dim(
            f_new, f_new_p, start, axis=1
        )
        fwd = f_new_p if fwd_scale is None else f_new_p * aux_p[fwd_scale]
        contrib = jax.lax.dot_general(
            fwd, panel,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return f_new, fitted + contrib

    # base: every panel updated as if its back-projection were the exact
    # zero a dense dot over a zero panel produces; the loop overwrites
    # the occupied slices with their real updates
    f_base = update_fn(f, jnp.zeros_like(f), *aux)
    f_new, fitted = jax.lax.fori_loop(
        0, K, body, (f_base, jnp.zeros((B, P), jnp.float32))
    )
    return f_new, fitted


def sparse_os_forward(
    panel: Array,  # [Q, V] — one (dequantized) pixel-row subset block
    f: Array,  # [B, V]
    scale: Optional[Array] = None,
    *,
    occ_panels,  # numpy bool [n_panels] — static skip predicate
    panel_voxels: int,
) -> Array:
    """:func:`os_subset_forward` with all-zero voxel panels skipped —
    the OS-SART composition of the block-sparse path. The contraction
    over voxels decomposes into per-panel partial dots accumulated in
    ascending panel order."""
    bs = int(panel_voxels)
    fwd = f if scale is None else f * scale[None, :]
    out = None
    for j in range(len(occ_panels)):
        if not bool(occ_panels[j]):
            continue
        contrib = jax.lax.dot_general(
            fwd[:, j * bs:(j + 1) * bs],
            jax.lax.slice_in_dim(panel, j * bs, (j + 1) * bs, axis=1),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        out = contrib if out is None else out + contrib
    if out is None:
        out = jnp.zeros((f.shape[0], panel.shape[0]), jnp.float32)
    return out


def sparse_os_back(
    panel: Array,  # [Q, V]
    w: Array,  # [B, Q]
    scale: Optional[Array] = None,
    *,
    occ_panels,
    panel_voxels: int,
    axis_name=None,
) -> Array:
    """:func:`os_subset_back` with all-zero voxel panels skipped: the
    skipped panels' columns are the exact zeros the dense dot would
    produce, concatenated back so the result stays ``[B, V]``. ONE psum
    over the whole vector (the OS cycle's audited per-substep collective
    count is unchanged); int8 scales apply after the psum, as in the
    dense subset path."""
    bs = int(panel_voxels)
    B = w.shape[0]
    parts = []
    for j in range(len(occ_panels)):
        if not bool(occ_panels[j]):
            parts.append(jnp.zeros((B, bs), jnp.float32))
            continue
        parts.append(jax.lax.dot_general(
            w, jax.lax.slice_in_dim(panel, j * bs, (j + 1) * bs, axis=1),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ))
    bp = jnp.concatenate(parts, axis=1)
    if axis_name is not None:
        bp = _psum(bp, axis_name)
    if scale is not None:
        bp = bp * scale[None, :]
    return bp


# --------------------------------------------------------------------------
# Ordered-subsets (OS-SART) subset primitives (docs/PERFORMANCE.md §9).
#
# The OS cycle updates against one PIXEL-ROW subset at a time — the
# transpose of the voxel-panel decomposition above, reusing its int8 idiom:
# an int8 subset block is dequantized to bf16 codes (exact, |codes| <= 127)
# and the per-voxel scales are applied around the dot, never to the matrix
# (a subset-sized convert per sub-step, one full-matrix-equivalent per outer
# iteration — budgeted by the ``os_sweep`` audit entries).
#
# Subset t is the INTERLEAVED row set {i : i mod n_subsets == t} — not a
# contiguous stripe. The classic OS prescription (arxiv 1705.07497) needs
# every subset to sample the full measurement geometry so each sub-update
# approximates a full-data update at 1/s of the rows; contiguous stripes of
# a spatially-coherent RTM (adjacent pixels view adjacent voxels) degrade
# into block Gauss-Seidel with NO iteration-count win — measured on the
# bench's banded+background response, stripes were 5x SLOWER than classic
# while interleaving accelerates. Each row is still a contiguous V-length
# HBM burst, so the strided read costs the same bytes as a stripe. Under
# pixel sharding the interleave is over each device's LOCAL rows (the
# global subset is the union over shards), so the subset back-projection
# psums over the pixel axis exactly like the unfused path's bp. The subset
# index is a traced loop counter (the cycle runs as a ``fori_loop``), hence
# reshape + dynamic index with a static subset count.


def os_subset_rows(rtm: Array, t, n_subsets: int) -> Array:
    """Interleaved pixel-row subset ``t`` of this device's RTM block,
    MXU-ready: ``[P_local/n_subsets, V_local]`` (rows ``t::n_subsets``),
    int8 codes dequantized to bf16. ``t`` may be traced."""
    P, V = rtm.shape
    panel = jax.lax.dynamic_index_in_dim(
        rtm.reshape(P // n_subsets, n_subsets, V), t, axis=1,
        keepdims=False,
    )
    if panel.dtype == jnp.int8:
        panel = panel.astype(jnp.bfloat16)
    return panel


def os_subset_pixels(x: Array, t, n_subsets: int) -> Array:
    """Rows ``t::n_subsets`` of a per-pixel vector/batch: ``[P] ->
    [P/n]`` or ``[B, P] -> [B, P/n]``; ``t`` may be traced."""
    if x.ndim == 1:
        return jax.lax.dynamic_index_in_dim(
            x.reshape(x.shape[0] // n_subsets, n_subsets), t, axis=1,
            keepdims=False,
        )
    B, P = x.shape
    return jax.lax.dynamic_index_in_dim(
        x.reshape(B, P // n_subsets, n_subsets), t, axis=2, keepdims=False,
    )


def os_subset_forward(
    panel: Array, f: Array, scale: Optional[Array] = None
) -> Array:
    """``H_t @ f`` for one subset — ``[B, P/n]``, this device's rows
    (a voxel-axis psum, if the mesh column-shards, is the caller's).
    ``scale``: per-voxel int8 dequantization scales (``H = scale * codes``),
    folded into the forward operand so the contraction is exact."""
    fwd = f if scale is None else f * scale[None, :]
    return jax.lax.dot_general(
        fwd, panel,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def os_subset_back(
    panel: Array, w: Array, scale: Optional[Array] = None, *, axis_name=None
) -> Array:
    """``H_t^T w`` for one subset — ``[B, V_local]``, psummed over the
    pixel axis when sharded (subsets span every pixel shard). int8: the
    reduction runs in code space; the per-voxel scales apply once, after
    the psum — the panel scan's dequantization order."""
    bp = jax.lax.dot_general(
        w, panel,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if axis_name is not None:
        bp = _psum(bp, axis_name)
    if scale is not None:
        bp = bp * scale[None, :]
    return bp


_selftest_passed: set = set()


def fused_selftest() -> None:
    """Compile and run a minimal fused sweep on the default backend.

    The kernel is validated in interpreter mode by tests, but Mosaic (the
    TPU Pallas compiler) can still reject a construct at compile time.
    Drivers that *auto*-select the fused path on a TPU call this once,
    before staging the matrix: a kernel that fails to compile or computes
    a wrong sweep raises here with the compiler's message, so a broken
    kernel stops the run instead of quietly demoting it to the two-matmul
    path. A pass is remembered per backend.

    The inputs are made inside the one program, which is released with
    it. Eager ``jnp`` inputs would each leave a cached program loaded on
    the device, laid out differently from process to process, ahead of
    the matrix staged next; the matrix's place in HBM, and with it the
    sweep's speed, then changed between runs of the same command (v5e).
    """
    backend = jax.default_backend()
    if backend in _selftest_passed:
        return

    def sweep():
        rtm = jnp.ones((8, 256), jnp.float32)
        w = jnp.full((1, 8), 0.5, jnp.float32)
        f = jnp.zeros((1, 256), jnp.float32)
        return fused_sweep(rtm, w, f, [], lambda fp, bp: fp + bp)

    try:
        f_new, fitted = jax.jit(sweep)()
        f_new, fitted = np.asarray(f_new), np.asarray(fitted)
    except Exception as err:  # sart-lint: disable=SL006
        raise RuntimeError(
            f"fused Pallas sweep failed to compile or run on backend "
            f"'{backend}': {err}"
        ) from err
    if not (np.allclose(f_new, 4.0) and np.allclose(fitted, 4.0 * 256)):
        raise RuntimeError(
            f"fused Pallas sweep computed a wrong sweep on backend "
            f"'{backend}' (f_new {f_new.ravel()[:4]}, fitted "
            f"{fitted.ravel()[:4]}; expected 4.0 and 1024.0)"
        )
    _selftest_passed.add(backend)


def resolve_fused_auto(opts, *, pixel_sharded: bool = False):
    """Driver-level check of ``fused_sweep='auto'`` before staging.

    Returns ``opts`` unchanged. Where auto would engage the Pallas kernel
    (a TPU backend, no pixel-axis sharding) it first runs
    :func:`fused_selftest`, which raises when the kernel does not compile
    there. Elsewhere it declines without compiling anything: off-TPU the
    solver never selects the kernel, and under pixel sharding auto
    engages the plain-XLA :func:`sharded_panel_sweep`, which needs no
    kernel self-test.
    """
    if (opts.fused_sweep == "auto" and jax.default_backend() == "tpu"
            and not pixel_sharded):
        fused_selftest()
    return opts


def _sweep_kernel(update_fn, n_aux, fwd_scale, rtm_ref, w_ref, f_ref, *rest):
    aux_refs = rest[:n_aux]
    f_new_ref, fitted_ref = rest[n_aux:]
    # A reduced-precision (bf16) panel feeds the MXU directly: Mosaic
    # handles the mixed f32xbf16 contraction with fp32 accumulation, and an
    # explicit astype would materialize an f32 copy of the panel in VMEM —
    # measured on v5e 2026-07-29 as the allocation that pushed large-batch
    # bf16 shapes past the scoped-VMEM limit, for no throughput gain.
    panel = rtm_ref[...]
    if panel.dtype == jnp.int8:
        # int8-quantized storage: dequantize the integer codes to bf16
        # (exact — |codes| <= 127) for the MXU; the per-voxel scales are the
        # `fwd_scale` aux panel, applied to bp inside update_fn and to the
        # forward operand below, so the loop's math is exactly fp32 SART on
        # the quantized matrix.
        panel = panel.astype(jnp.bfloat16)
    # An fp32 panel here (a batch above _VPU_MAX_BATCH) contracts at fp32.
    # Mosaic's default precision is one bf16 MXU pass: on v5e it left a
    # 2.3e-3 relative back-projection error, against 3.7e-7 for XLA's fp32
    # dot, and moved frame 0 of the headline series from 103 to 72
    # iterations. HIGHEST emulates fp32 in several bf16 passes.
    precision = (jax.lax.Precision.HIGHEST
                 if panel.dtype == jnp.float32 else None)
    # Back-projection of this panel: contraction over the full pixel axis.
    # The fp32 operands stay fp32: casting w / f_new to bf16 to match the
    # panel measured *slower* at every shape tried (v5e 2026-07-30 — B=32
    # bf16 390 -> 365 iter/s, B=32 int8 526 -> 507, B=1 unchanged), so the
    # mixed f32xbf16 contraction is the fastest Mosaic lowering available.
    bp = jax.lax.dot_general(
        w_ref[...], panel,
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32,
    )  # [B, bs]
    f_new = update_fn(f_ref[...], bp, *[a[...] for a in aux_refs])
    f_new_ref[...] = f_new
    # Forward-projection contribution of the same panel, while it is still
    # in VMEM — this is the read the two-matmul formulation pays twice for.
    fwd = f_new if fwd_scale is None else f_new * aux_refs[fwd_scale][...]
    contrib = jax.lax.dot_general(
        fwd, panel,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32,
    )  # [B, P]

    @pl.when(pl.program_id(0) == 0)
    def _():
        fitted_ref[...] = contrib

    @pl.when(pl.program_id(0) > 0)
    def _():
        fitted_ref[...] += contrib


# The VPU form of the kernel: fp32 panels at batch <= _VPU_MAX_BATCH. Both
# contractions are matvecs, so each is one fp32 multiply and one add an
# element on the VPU, with no bf16 split and no transpose of the panel.
# Four VPU operations an element hide under the panel's DMA: at the
# 6144x524288 block the kernel times as a body that only touches the
# panel, 18.1-18.6 ms against 22.8 ms at HIGHEST on the MXU (v5e, PERF.md
# §6). The rows run in _VPU_ROWS-row steps of a loop; shorter steps
# measured slower than the MXU (the loop's own cost: 40 ms at 32 rows).
def _sweep_kernel_vpu(update_fn, n_aux, fwd_scale, rtm_ref, wcol_ref, f_ref,
                      *rest):
    aux_refs = rest[:n_aux]
    f_new_ref, fitted_ref, acc_ref = rest[n_aux:]
    P, bs = rtm_ref.shape
    B = f_ref.shape[0]
    n_steps, tail = divmod(P, _VPU_ROWS)  # the tail is a multiple of 8

    def over_rows(step, carry):
        """``step(rows, carry)`` over the panel's pixel rows: the loop's
        full steps, then the tail rows."""
        if n_steps:
            carry = jax.lax.fori_loop(0, n_steps, lambda i, c: step(pl.ds(
                pl.multiple_of(i * _VPU_ROWS, _VPU_ROWS), _VPU_ROWS), c),
                carry)
        return step(pl.ds(n_steps * _VPU_ROWS, tail), carry) if tail else carry

    # Back-projection: bp[b] = sum over pixel rows of panel * w[b], the
    # pixel weights broadcast along lanes from the [P, B] column. Each
    # sublane of an [8, bs] accumulator sums every eighth row.
    def bp_step(rows, accs):
        panel = rtm_ref[rows, :]
        w = wcol_ref[rows, :]
        return tuple(
            acc + (panel * w[:, b:b + 1]).reshape(
                -1, _SUBLANE, bs).sum(axis=0)
            for b, acc in enumerate(accs)
        )

    zero = jnp.zeros((_SUBLANE, bs), jnp.float32)
    accs = over_rows(bp_step, (zero,) * B)
    bp = jnp.concatenate([a.sum(axis=0, keepdims=True) for a in accs])
    f_new = update_fn(f_ref[...], bp, *[a[...] for a in aux_refs])
    f_new_ref[...] = f_new
    fwd = f_new if fwd_scale is None else f_new * aux_refs[fwd_scale][...]

    @pl.when(pl.program_id(0) == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Forward projection: the panel times fwd[b], its 128-lane blocks
    # summed in registers, added to acc[b] — one add a lane a panel. The
    # lanes are summed once, after the last panel, down the sublanes of
    # acc[b]^T (a lane reduction straight to [1, P] took Mosaic 13.2 s to
    # compile at 6144 pixels, the transpose 0.2 s: PERF.md §6).
    def fwd_step(rows, carry):
        panel = rtm_ref[rows, :]
        for b in range(B):
            prod = panel * fwd[b:b + 1, :]
            part = prod[:, :_MIN_BLOCK_VOXELS]
            for k in range(1, bs // _MIN_BLOCK_VOXELS):
                part = part + prod[:, k * _MIN_BLOCK_VOXELS:
                                   (k + 1) * _MIN_BLOCK_VOXELS]
            acc_ref[b, rows, :] += part
        return carry

    over_rows(fwd_step, 0)

    @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
    def _():
        for b in range(B):
            fitted_ref[b:b + 1, :] = jnp.sum(acc_ref[b].T, axis=0,
                                             keepdims=True)


def fused_sweep(
    rtm: Array,  # [P, V]
    w: Array,  # [B, P] fp32 — back-projection pixel weights
    f: Array,  # [B, V] fp32 — current solution
    aux: Sequence[Array],  # each [b_i, V] (b_i in {1, B}) fp32
    update_fn: Callable[..., Array],
    *,
    fwd_scale: Optional[int] = None,
    interpret: bool = False,
):
    """Run one fused SART sweep; returns ``(f_new [B, V], fitted [B, P])``.

    ``update_fn(f_panel, bp_panel, *aux_panels) -> f_new_panel`` is applied
    elementwise per voxel panel. Shapes must satisfy :func:`fused_available`.
    ``fwd_scale`` names an aux index whose panel scales the forward-
    projection operand (``fitted += (f_new * aux[fwd_scale]) @ panel^T``) —
    the per-voxel dequantization scales of an int8 RTM.
    """
    P, V = rtm.shape
    B = w.shape[0]
    bs = pick_block_voxels(P, V, rtm.dtype.itemsize, B)
    if bs <= 0 or not fused_available(P, V, rtm.dtype.itemsize, B):
        raise ValueError(
            f"fused_sweep: shapes [{P}, {V}] (batch {B}) not aligned/"
            "VMEM-fittable; gate calls with fused_available()."
        )
    grid = (V // bs,)
    vpu = _contracts_on_vpu(rtm.dtype.itemsize, B)
    # Observability (host-side, trace-time): which unit contracts the
    # compiled kernel's panels.
    from sartsolver_tpu.obs import metrics as _obs_metrics

    reg = _obs_metrics.get_registry()
    reg.gauge("fused_sweep_contraction", path="vpu").set(float(vpu))
    reg.gauge("fused_sweep_contraction", path="mxu").set(float(not vpu))

    voxel_panel = lambda b: pl.BlockSpec((b, bs), lambda j: (0, j))
    resident = lambda shape: pl.BlockSpec(shape, lambda j: (0,) * len(shape))
    in_specs = [
        pl.BlockSpec((P, bs), lambda j: (0, j)),  # RTM column panel
        # w: resident across panels; a [P, B] column on the VPU path
        resident((P, B) if vpu else (B, P)),
        voxel_panel(B),  # f
        *[voxel_panel(a.shape[0]) for a in aux],
    ]
    out_specs = (
        voxel_panel(B),  # f_new
        resident((B, P)),  # fitted
    )
    if vpu:
        kernel, w_in = _sweep_kernel_vpu, w.T
        scratch = [pltpu.VMEM((B, P, _MIN_BLOCK_VOXELS), jnp.float32)]
    else:
        kernel, w_in, scratch = _sweep_kernel, w, []
    return pl.pallas_call(
        functools.partial(kernel, update_fn, len(aux), fwd_scale),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
        out_shape=(
            jax.ShapeDtypeStruct((B, V), jnp.float32),
            jax.ShapeDtypeStruct((B, P), jnp.float32),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * B * P * V,
            bytes_accessed=P * V * rtm.dtype.itemsize + 2 * B * (P + V) * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(rtm, w_in, f, *aux)


# --------------------------------------------------------------------------
# compile-audit self-registration (analysis/registry.py). Interpret-mode
# lowerings compile on any backend, so the fused loop's structure — the
# while body must not grow a matrix-sized copy (fp32) or a full-matrix
# dequantized convert (int8: only *panel*-sized dequant is legal, the whole
# point of in-VMEM dequantization) — is pinned off-TPU too, alongside
# golden op-histogram signatures. The builders import models.sart lazily:
# that module imports this one at its top level.

from sartsolver_tpu.analysis.registry import (  # noqa: E402
    AUDIT_P as _AUDIT_P,
    AUDIT_V as _AUDIT_V,
    register_audit_entry as _register_audit_entry,
)


def _audit_fused_solver(rtm_dtype):
    from sartsolver_tpu.config import SolverOptions
    from sartsolver_tpu.models.sart import (
        _audit_batch_args,
        _audit_problem,
        _solve_normalized_batch_impl,
    )

    opts = SolverOptions(
        max_iterations=8, conv_tolerance=1e-30, fused_sweep="interpret",
        rtm_dtype=("int8" if rtm_dtype == jnp.int8 else None),
    )
    fn = jax.jit(functools.partial(
        _solve_normalized_batch_impl, opts=opts, axis_name=None,
        voxel_axis=None, use_guess=True,
    ))
    return fn.lower(
        _audit_problem(rtm_dtype, with_scale=rtm_dtype == jnp.int8),
        *_audit_batch_args(),
    )


@_register_audit_entry(
    "fused_sweep",
    description="fused Pallas iteration sweep inside the solver loop "
                "(fp32, interpret mode)",
    loop_copy_threshold=_AUDIT_P * _AUDIT_V,
    loop_convert_threshold=_AUDIT_P * _AUDIT_V,
    loop_collective_budget={
        "all-reduce": 0, "all-gather": 0, "all-to-all": 0,
        "collective-permute": 0,
    },
)
def _audit_fused_sweep():
    return _audit_fused_solver(jnp.float32)


def audit_occupancy(occupied_panels: int = 4, n_panels: int = 8):
    """Deterministic 50%-by-default occupancy index over the shared audit
    fixture shape: the first ``occupied_panels`` of ``n_panels`` 128-wide
    voxel panels carry data, the rest are empty. Exposed (not underscored)
    so tests build the same fixture the goldens were pinned with."""
    from sartsolver_tpu.ops.sparse import TILE_COLS, TILE_ROWS, TileOccupancy

    n_tr = _AUDIT_P // TILE_ROWS
    n_tc = _AUDIT_V // TILE_COLS
    per_panel = n_tc // n_panels
    mask = np.zeros((n_tr, n_tc), bool)
    mask[:, : occupied_panels * per_panel] = True
    return TileOccupancy.from_mask(mask, rows=_AUDIT_P, cols=_AUDIT_V)


@_register_audit_entry(
    "sparse_panel_sweep",
    description="block-sparse voxel-panel sweep at 50% panel occupancy "
                "(8x128 panels, 4 occupied; static skip, fp32): the cost "
                "golden pins FLOPs/bytes scaling with OCCUPANCY, not "
                "matrix shape — a silent densification (~2x FLOPs) "
                "fails the audit's tolerance band",
    loop_copy_threshold=_AUDIT_P * _AUDIT_V,
    loop_convert_threshold=_AUDIT_P * _AUDIT_V,
    loop_collective_budget={
        "all-reduce": 0, "all-gather": 0, "all-to-all": 0,
        "collective-permute": 0,
    },
    # tighter than the default 0.5 band: a silent densification raises
    # the module total by ~the one-time setup-adjusted loop doubling
    # (~+47% at this fixture) and MUST fail; fusion jitter stays well
    # inside 25%
    cost_rtol=0.25,
)
def _audit_sparse_panel_sweep():
    from sartsolver_tpu.config import SolverOptions
    from sartsolver_tpu.models.sart import (
        _audit_batch_args,
        _audit_problem,
        _solve_normalized_batch_impl,
    )

    opts = SolverOptions(
        max_iterations=8, conv_tolerance=1e-30, fused_sweep="off",
        sparse_rtm="auto", fused_panel_voxels=128,
    )
    fn = jax.jit(functools.partial(
        _solve_normalized_batch_impl, opts=opts, axis_name=None,
        voxel_axis=None, use_guess=False, tile_occupancy=audit_occupancy(),
    ))
    return fn.lower(_audit_problem(), *_audit_batch_args())


@_register_audit_entry(
    "int8_fused_sweep",
    description="int8-quantized fused sweep (per-voxel-scaled codes, "
                "interpret mode)",
    loop_copy_threshold=_AUDIT_P * _AUDIT_V,
    # dequantizing the codes panel in VMEM is the design; only a copy of
    # the matrix would erase the 4x bandwidth win, so converts go
    # unbudgeted here (the panel can legitimately be the whole fixture
    # matrix at these small audit shapes)
    loop_convert_threshold=None,
    loop_collective_budget={
        "all-reduce": 0, "all-gather": 0, "all-to-all": 0,
        "collective-permute": 0,
    },
)
def _audit_int8_fused_sweep():
    return _audit_fused_solver(jnp.int8)

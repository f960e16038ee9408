"""Sparse Laplacian regularizer ops.

The reference stores the Laplacian as flattened-index COO sorted by
``i*nvoxel + j`` (laplacian.cpp:67-82) and gathers it with scalar loops
(CPU, sartsolver.cpp:183-189) or an atomicAdd grid-stride kernel
(GradPenaltyKernel, sart_kernels.cu:179-202). The TPU-native equivalent is a
static-shape COO scatter-add: XLA lowers ``.at[rows].add`` to an on-device
scatter; rows/cols/vals are padded to a static size so the op stays
jit-stable across frames.

A regular voxel grid indexed row-major puts its stencil's triplets on a
handful of diagonals (the 7-point Laplacian of an nx×ny×nz grid: offsets
0, ±1, ±nz and ±ny·nz). For the solver's penalty
(:func:`shard_laplacian_halo` / :func:`sharded_penalty`) the block-local
triplets are then stored as those diagonals instead — one coefficient row
per distinct ``col − row`` offset — and the product becomes shifted slices
and multiply-adds, with no gather and no scatter. The diagonal form is
chosen at set-up whenever it takes no more bytes than the triplets it
replaces (``D × block × itemsize ≤ nnz_loc × (8 + itemsize)``, ``D``
distinct offsets); compactly indexed or random couplings keep COO.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array, lax


class LaplacianCOO(NamedTuple):
    """Static-shape COO triplets (padded entries have ``vals == 0``)."""

    rows: Array  # [nnz] int32
    cols: Array  # [nnz] int32
    vals: Array  # [nnz] float

    @property
    def nnz(self) -> int:
        return self.rows.shape[0]


def make_laplacian(rows, cols, vals, *, dtype=jnp.float32, pad_to: int | None = None) -> LaplacianCOO:
    """Build a device-ready COO Laplacian from host triplets.

    Padding keeps the nnz static under jit when streams of problems have
    slightly different sparsity (pad entries scatter 0 into row 0).
    """
    rows = np.asarray(rows, dtype=np.int32)
    cols = np.asarray(cols, dtype=np.int32)
    vals = np.asarray(vals)
    if pad_to is not None and pad_to > rows.shape[0]:
        pad = pad_to - rows.shape[0]
        rows = np.concatenate([rows, np.zeros(pad, np.int32)])
        cols = np.concatenate([cols, np.zeros(pad, np.int32)])
        vals = np.concatenate([vals, np.zeros(pad, vals.dtype)])
    return LaplacianCOO(jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(vals, dtype=dtype))


class ShardedLaplacian(NamedTuple):
    """Halo-exchange partition of a COO Laplacian over voxel (column) shards.

    Replaces the per-iteration ``all_gather`` of the full solution that a
    row-partitioned COO with global column indices forces (the round-2
    design): real regularizers are local stencils (the reference's Laplacian
    couples a voxel to its grid neighbors, laplacian.cpp), so after
    partitioning both rows and columns by voxel block, almost every triplet
    is block-diagonal — computable purely from the shard's own solution
    block. The few cross-block triplets only need the *boundary* values,
    which travel in a compact static export table: one all_gather of
    ``[B, n_shards * n_export]`` per penalty instead of ``[B, V_global]``,
    ``n_export`` being the most boundary values any shard publishes (two
    grid planes for a middle shard of a 3-D stencil). The cross-block
    triplets stay COO (a gather from the table and a scatter-add) even
    where the block-local ones take the diagonal form. A worst-case dense
    coupling degrades gracefully toward the full gather; a block-diagonal
    split needs no communication at all.

    Host-built by :func:`shard_laplacian_halo` with a leading shard
    dimension on every field; inside ``shard_map`` each device slices its
    own row (leading dim dropped) and calls :func:`sharded_penalty`.

    Fields (S = voxel shards, padded per shard to the max count with inert
    ``(0, 0, 0.0)`` triplets / index-0 exports):

    - ``rows_loc, cols_loc, vals_loc`` — block-diagonal triplets; rows and
      cols are block-local. Zero length when the diagonal form holds them.
    - ``rows_halo, gidx_halo, vals_halo`` — cross-block triplets; rows are
      block-local, ``gidx_halo`` indexes the gathered export table
      (``owner_shard * n_export + position``).
    - ``export_idx`` — block-local solution indices this shard publishes
      (the union of what every other shard needs from it).
    - ``diag_offsets, diag_vals`` — the block-diagonal triplets in diagonal
      form: ``diag_vals[k, i]`` sums the triplets ``(i, i + diag_offsets[k])``
      (the same offsets on every shard). ``D = 0`` when COO is kept.
    """

    rows_loc: Array  # [S, nnz_loc] int32
    cols_loc: Array  # [S, nnz_loc] int32
    vals_loc: Array  # [S, nnz_loc] float
    rows_halo: Array  # [S, nnz_halo] int32
    gidx_halo: Array  # [S, nnz_halo] int32
    vals_halo: Array  # [S, nnz_halo] float
    export_idx: Array  # [S, n_export] int32
    diag_offsets: Array  # [S, D] int32
    diag_vals: Array  # [S, D, block] float


def shard_laplacian_halo(
    lap: LaplacianCOO, n_shards: int, block: int, dtype
) -> ShardedLaplacian:
    """Partition COO triplets into block-diagonal + halo sets (host-side).

    ``block`` is the padded per-shard voxel count; triplet indices are
    global and must lie in ``[0, n_shards * block)``. The block-diagonal
    set takes the diagonal form when its ``D`` distinct offsets (over all
    shards) take no more bytes than its triplets:
    ``D * block * itemsize <= nnz_loc * (8 + itemsize)``.
    """
    rows = np.asarray(lap.rows, np.int64)
    cols = np.asarray(lap.cols, np.int64)
    vals = np.asarray(lap.vals)
    np_dtype = np.dtype(dtype)

    own_r = rows // block
    own_c = cols // block
    is_loc = own_r == own_c

    # Export sets: for each publishing shard t, the sorted unique
    # block-local indices any OTHER shard reads from it.
    exports = []
    for t in range(n_shards):
        sel = (~is_loc) & (own_c == t)
        exports.append(np.unique(cols[sel] - t * block).astype(np.int64))
    n_export = max((len(e) for e in exports), default=0)

    def padded(mats, n, fill=0, dt=np.int32):
        out = np.full((n_shards, n), fill, dt)
        for s, m in enumerate(mats):
            out[s, : len(m)] = m
        return out

    loc_r, loc_c, loc_v = [], [], []
    halo_r, halo_g, halo_v = [], [], []
    for s in range(n_shards):
        sel = is_loc & (own_r == s)
        loc_r.append(rows[sel] - s * block)
        loc_c.append(cols[sel] - s * block)
        loc_v.append(vals[sel])
        sel = (~is_loc) & (own_r == s)
        halo_r.append(rows[sel] - s * block)
        t = own_c[sel]
        c_loc = cols[sel] - t * block
        # vectorized per owner shard (a per-triplet searchsorted loop is
        # O(nnz) interpreter work in the dense-coupling worst case)
        pos = np.zeros(len(t), np.int64)
        for ti in np.unique(t):
            m = t == ti
            pos[m] = np.searchsorted(exports[ti], c_loc[m])
        halo_g.append(t * n_export + pos)
        halo_v.append(vals[sel])

    nnz_loc = max(1, max((len(v) for v in loc_v), default=0))
    nnz_halo = max((len(v) for v in halo_v), default=0)
    # offsets present anywhere, by a count over [-(block-1), block-1]
    loc_d = [c - r for r, c in zip(loc_r, loc_c)]
    present = np.zeros(2 * block - 1, bool)
    for d in loc_d:
        present[d + block - 1] = True
    offsets = np.flatnonzero(present) - (block - 1)
    n_diag = len(offsets)
    diag_form = 0 < n_diag and (
        n_diag * block * np_dtype.itemsize
        <= nnz_loc * (8 + np_dtype.itemsize)
    )
    if diag_form:
        # duplicates sum into one coefficient; bincount is O(nnz)
        slot = np.cumsum(present) - 1  # offset + block - 1 -> diagonal k
        diag_vals = np.stack([
            np.bincount(slot[d + block - 1] * block + r,
                        weights=v.astype(np.float64),
                        minlength=n_diag * block).reshape(n_diag, block)
            for r, d, v in zip(loc_r, loc_d, loc_v)
        ]).astype(np_dtype)
        nnz_loc = 0
        loc_r = loc_c = loc_v = [np.empty(0)] * n_shards
    else:
        offsets = offsets[:0]
        diag_vals = np.zeros((n_shards, 0, block), np_dtype)
    return ShardedLaplacian(
        padded(loc_r, nnz_loc),
        padded(loc_c, nnz_loc),
        padded(loc_v, nnz_loc, 0.0, np_dtype),
        padded(halo_r, nnz_halo),
        padded(halo_g, nnz_halo),
        padded(halo_v, nnz_halo, 0.0, np_dtype),
        padded(exports, n_export),
        np.tile(offsets.astype(np.int32), (n_shards, 1)),
        diag_vals,
    )


def diagonal_share(lap: LaplacianCOO, slap: ShardedLaplacian,
                   block: int) -> float:
    """Share of ``lap``'s triplets that ``slap`` applies in diagonal form:
    the block-local ones where it took that form, else none."""
    if slap.diag_offsets.shape[-1] == 0 or lap.nnz == 0:
        return 0.0
    rows = np.asarray(lap.rows, np.int64)
    cols = np.asarray(lap.cols, np.int64)
    return float(np.count_nonzero(rows // block == cols // block)) / lap.nnz


def sharded_penalty(slap: ShardedLaplacian, x: Array, axis_name) -> Array:
    """``(L @ x_global)`` restricted to this shard's voxel block.

    ``x`` is the batched local solution block ``[B, voxel_block]``; fields
    of ``slap`` are this device's slices (no leading shard dim). The only
    communication is the boundary all_gather, ``[B, n_shards * n_export]``
    under the scope ``sart.exchange``, skipped entirely when the partition
    has no cross-block triplets. Its triplets are applied as COO (gather
    and scatter-add) whether or not the block-local ones are diagonals.
    """
    if slap.diag_offsets.shape[-1]:
        pen = _diagonal_matvec(slap.diag_offsets, slap.diag_vals, x)
    else:
        pen = jnp.zeros_like(x)
    if slap.rows_loc.shape[-1]:
        pen = pen.at[:, slap.rows_loc].add(
            slap.vals_loc.astype(x.dtype)[None, :] * x[:, slap.cols_loc]
        )
    if slap.rows_halo.shape[-1] == 0 or axis_name is None:
        return pen
    from sartsolver_tpu.models.sart import SCOPE_EXCHANGE

    # the export table is packed by a local gather, outside the scope: the
    # scope names the collective alone
    export = x[:, slap.export_idx]  # [B, n_export]
    with jax.named_scope(SCOPE_EXCHANGE):
        table = lax.all_gather(export, axis_name, axis=1, tiled=True)
    return pen.at[:, slap.rows_halo].add(
        slap.vals_halo.astype(x.dtype)[None, :] * table[:, slap.gidx_halo]
    )


def _diagonal_matvec(offsets: Array, vals: Array, x: Array) -> Array:
    """``pen[:, i] = sum_k vals[k, i] * x[:, i + offsets[k]]``: one shifted
    slice of the zero-padded block per diagonal (|offset| < block). A
    coefficient of zero adds nothing, as an absent triplet adds nothing to
    the scatter-add, even where ``x`` holds ``-inf`` (``log 0``)."""
    n = x.shape[-1]
    xp = jnp.pad(x, ((0, 0), (n, n)))
    pen = jnp.zeros_like(x)
    for k in range(vals.shape[0]):
        v = vals[k].astype(x.dtype)[None, :]
        xs = lax.dynamic_slice_in_dim(xp, n + offsets[k], n, axis=1)
        pen = pen + jnp.where(v != 0, v * xs, 0)
    return pen


def coo_matvec(lap: LaplacianCOO | None, x: Array, nvoxel: int) -> Array:
    """``L @ x`` for the COO Laplacian; zeros when no regularizer is set.

    Matches the gather semantics of sartsolver.cpp:184-189: for every stored
    triplet ``(i, j, v)``, accumulate ``v * x[j]`` into output row ``i``.
    """
    if lap is None:
        return jnp.zeros((nvoxel,), dtype=x.dtype)
    contrib = lap.vals.astype(x.dtype) * x[lap.cols]
    return jnp.zeros((nvoxel,), dtype=x.dtype).at[lap.rows].add(contrib)

"""The benchmark's world, made on the device from ``--seed``.

The RTM is e2e_world's banded construction,
``H[i, j] = (0.9 u + 0.1) (exp(-band (i/P - j/V)^2) + floor)``, with ``u`` a
uniform hash of ``(seed, i, j)``: the value of an element depends on the seed
and its place only, so a matrix filled in row blocks equals one built at once,
on any layout. Each device's block of the matrix is filled in place, one row
block at a time, so set-up holds the matrix plus one block.

Frames are ``g_t = H (s_t f_a + d_t)`` with 1% multiplicative noise:
``s_t = 1 + A sin(2 pi t / period)`` scales a base profile ``f_a``, and
``d_t`` is a Gaussian blob that drifts across the voxel grid (of any rank,
``config["grid"]``), so the emissivity changes shape between frames and
not only scale. The profile,
the blob's path and the noise are the traffic's, the same for every seed:
the seed moves the matrix only, so every seed asks for the same work. The
noiseless frames are computed from fp32 row blocks of the matrix before the
matrix itself is filled (a matrix stored in a narrower type still sees fp32
frames, and set-up never holds both), and go to the host as float64, the
way images arrive.

Nothing here imports the program: the reference regenerates the matrix with
the same functions after the program's state is freed.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
ROW_BLOCK = 64  # rows per fill step: 128 MiB at 524288 fp32 voxels a chip


def seed_words(seed: int) -> tuple[int, int]:
    """The low and high 32 bits of a seed (the driver's exceed int32)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF


def _mix32(x):
    """lowbias32 (C. Wellons): a full-avalanche uint32 -> uint32 hash."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def _key(seed_lo, seed_hi, stream: int):
    return _mix32(_mix32(jnp.uint32(seed_lo) ^ jnp.uint32(stream))
                  + jnp.uint32(seed_hi) * jnp.uint32(0x9E3779B9))


def _uniform(key, a, b):
    """Uniform [0, 1) float32 from a 2-D index ``(a, b)`` (uint32 arrays)."""
    h = _mix32(_mix32(a * jnp.uint32(0x9E3779B9) + key) ^ b)
    return (h >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)


def rtm_rows(rows, col0: int, ncols: int, world: dict, seed_lo, seed_hi):
    """fp32 RTM rows ``rows`` (global indices, [r]) x columns
    ``col0 .. col0 + ncols``; rows/columns past the logical size are 0."""
    P, V = world["npixel"], world["nvoxel"]
    cols = col0 + jnp.arange(ncols, dtype=jnp.int32)
    u = _uniform(_key(seed_lo, seed_hi, 1),
                 rows.astype(jnp.uint32)[:, None],
                 cols.astype(jnp.uint32)[None, :])
    d = (rows.astype(jnp.float32)[:, None] / P
         - cols.astype(jnp.float32)[None, :] / V)
    h = (0.9 * u + 0.1) * (jnp.exp(-world["band"] * d * d) + world["floor"])
    keep = (rows[:, None] < P) & (cols[None, :] < V)
    return jnp.where(keep, h, 0.0).astype(jnp.float32)


def _row_block(nrows: int) -> int:
    rb = min(ROW_BLOCK, nrows)
    while nrows % rb:
        rb -= 1
    return rb


@functools.partial(jax.jit, static_argnums=(3, 4, 5), donate_argnums=0)
def _fill_step(buf, r_local, r_global, col0, rb, world_items, seed_lo,
               seed_hi, F):
    """Rows ``r_local .. r_local + rb`` of this device's block: the RTM's
    rows themselves (``F`` None), or the frames' rows ``H_rows @ F``."""
    rows = r_global + jnp.arange(rb, dtype=jnp.int32)
    ncols = buf.shape[1] if F is None else F.shape[0]
    h = rtm_rows(rows, col0, ncols, dict(world_items), seed_lo, seed_hi)
    if F is not None:
        h = jnp.dot(h, F, precision=HIGHEST)  # [rb, n_frames]
    return lax.dynamic_update_slice(buf, h.astype(buf.dtype), (r_local, 0))


def _zeros(shape, dtype, device):
    return jax.jit(lambda: jnp.zeros(shape, dtype),
                   out_shardings=jax.sharding.SingleDeviceSharding(device))()


def world_items(config: dict) -> tuple:
    """The hashable sizes the generator reads from a configuration."""
    return (("npixel", int(config["npixel"])), ("nvoxel", int(config["nvoxel"])),
            ("band", float(config["band"])), ("floor", float(config["floor"])))


def _fill(config, seed, sharding, shape, dtype, frames_F):
    items = world_items(config)
    lo, hi = seed_words(seed)
    index_map = sharding.addressable_devices_indices_map(shape)
    parts = []
    # dispatch is asynchronous, so the devices fill their blocks in parallel
    for dev in sorted(sharding.addressable_devices, key=lambda d: d.id):
        rs, cs = index_map[dev]
        r0, r1, _ = rs.indices(shape[0])
        c0, c1, _ = cs.indices(shape[1])
        rb = _row_block(r1 - r0)
        F = None
        if frames_F is None:
            buf = _zeros((r1 - r0, c1 - c0), dtype, dev)
        else:
            F = jax.device_put(frames_F[c0:c1], dev)
            buf = _zeros((r1 - r0, frames_F.shape[1]), jnp.float32, dev)
        for r in range(0, r1 - r0, rb):
            buf = _fill_step(buf, r, r0 + r, c0, rb, items, np.uint32(lo),
                             np.uint32(hi), F)
        parts.append((r0, r1, buf))
    return parts


def build_rtm(config: dict, seed: int, sharding, shape, *, dtype=jnp.float32):
    """The RTM laid out as ``sharding`` over the padded ``shape``. Each
    device's block is allocated on that device and filled in place, one
    row block at a time, so nothing but the matrix and one block is held."""
    parts = _fill(config, seed, sharding, shape, dtype, None)
    return jax.make_array_from_single_device_arrays(
        shape, sharding, [buf for _, _, buf in parts])


def noiseless_frames(config: dict, seed: int, sharding, shape, frames_F):
    """``H @ F`` as a host ``[P_pad, N]`` float32 array, from fp32 RTM rows
    made block by block and never held whole. ``frames_F`` is
    ``[V_pad, N]`` fp32; column-sharded layouts sum their parts."""
    G = np.zeros((shape[0], frames_F.shape[1]), np.float32)
    for r0, r1, gbuf in _fill(config, seed, sharding, shape, None, frames_F):
        G[r0:r1] += np.asarray(gbuf)
    return G


# ---- frames ------------------------------------------------------------


class Series:
    """The frame series of one run.

    The base profile (blobs at fixed places) and the drifting blob's path
    (a fixed start and direction, bouncing off the grid's edges) come from
    the traffic's parameters alone, and so does the noise. Every blob is
    a Gaussian, a product of one 1-D Gaussian per grid axis, so a frame's
    emissivity costs one outer product per blob on a grid of any rank."""

    def __init__(self, config: dict, traffic: dict, seed: int, n_frames: int):
        self.config, self.traffic = config, traffic
        self.n_frames = n_frames
        self.seed = seed
        self.grid = grid_shape(config)
        dims = np.array(self.grid, np.float64)
        layout = np.random.default_rng(int(traffic["layout_seed"]))
        nb = int(traffic["base_blobs"])
        self.base_centres = layout.uniform(0.2, 0.8, (nb, dims.size)) * dims
        self.base_amps = layout.uniform(0.5, 1.5, nb)
        start = np.array(traffic["drift_start"], np.float64)
        heading = np.array(traffic["drift_direction"], np.float64)
        if start.size != dims.size or heading.size != dims.size:
            raise ValueError(f"traffic drift_start/drift_direction have "
                             f"{start.size}/{heading.size} axes, the grid "
                             f"{self.grid} has {dims.size}")
        heading /= np.linalg.norm(heading)
        speed = float(traffic["drift_voxels_per_frame"])
        t = np.arange(n_frames)[:, None]
        raw = start[None, :] * dims + t * speed * heading[None, :]
        # bounce off the grid's edges: a triangle wave on each axis
        span = dims - 1
        m = np.mod(raw, 2 * span)
        self.blob_path = np.where(m > span, 2 * span - m, m)  # [N, ndim]

    def scales(self) -> np.ndarray:
        tr = self.traffic
        t = np.arange(self.n_frames)
        return 1.0 + tr["scale_amplitude"] * np.sin(
            2 * math.pi * t / tr["scale_period_frames"])

    def _blob(self, centre, sigma) -> np.ndarray:
        axes = [np.exp(-(np.arange(n) - c) ** 2 / (2 * sigma * sigma))
                for n, c in zip(self.grid, centre)]
        return functools.reduce(np.multiply.outer, axes).ravel()

    def emissivity(self, v_pad: int) -> np.ndarray:
        """[V_pad, N] float32 true emissivity of every frame (0 on pads)."""
        tr = self.traffic
        nv = math.prod(self.grid)
        sig = tr["base_sigma_frac"] * min(self.grid)
        base = np.full(nv, tr["base_level"], np.float64)
        for centre, a in zip(self.base_centres, self.base_amps):
            base += a * self._blob(centre, sig)
        F = np.zeros((v_pad, self.n_frames), np.float32)
        scales = self.scales()
        for t in range(self.n_frames):
            F[:nv, t] = scales[t] * base + tr["blob_amplitude"] * self._blob(
                self.blob_path[t], tr["blob_sigma_voxels"])
        return F

    def noisy(self, G: np.ndarray, npixel: int) -> np.ndarray:
        """[N, npixel] float64 frames: the noiseless ``G`` ([P_pad, N])
        with the traffic's multiplicative normal noise, drawn per frame
        from the traffic's layout seed. The noise is the same for every
        run seed: a frame's fresh noise is what its warm start must fit,
        so noise drawn from the run seed moved the window's iteration
        count, and with it the rate, by some 2% from seed to seed."""
        out = np.empty((self.n_frames, npixel), np.float64)
        layout = int(self.traffic["layout_seed"])
        for t in range(self.n_frames):
            rng = np.random.default_rng([layout, 11, t])
            out[t] = G[:npixel, t].astype(np.float64) * (
                1.0 + self.traffic["noise"] * rng.standard_normal(npixel))
        return out


# ---- the Laplacian ------------------------------------------------------


def grid_shape(config: dict) -> tuple:
    """The voxel grid's shape (``config["grid"]``, any rank, the last axis
    fastest in the voxel index); its cells number ``nvoxel``."""
    grid = tuple(int(n) for n in config["grid"])
    if math.prod(grid) != int(config["nvoxel"]):
        raise ValueError(f"grid {grid} holds {math.prod(grid)} voxels, "
                         f"nvoxel is {config['nvoxel']}")
    return grid


def grid_laplacian(grid):
    """COO triplets of the graph Laplacian of the grid's nearest
    neighbours (5-point in 2-D, 7-point in 3-D; voxel index row-major):
    the degree on the diagonal, -1 to each neighbour inside the grid."""
    grid = tuple(int(n) for n in grid)
    nv = math.prod(grid)
    idx = np.arange(nv).reshape(grid)
    rows, cols = [], []
    for ax, n in enumerate(grid):
        a = idx.take(np.arange(1, n), axis=ax).ravel()
        b = idx.take(np.arange(n - 1), axis=ax).ravel()
        rows += [a, b]
        cols += [b, a]
    off_r = np.concatenate(rows)
    off_c = np.concatenate(cols)
    deg = np.bincount(off_r, minlength=nv)
    diag = np.arange(nv)
    r = np.concatenate([diag, off_r]).astype(np.int32)
    c = np.concatenate([diag, off_c]).astype(np.int32)
    v = np.concatenate([deg.astype(np.float64),
                        -np.ones(off_r.size)]).astype(np.float32)
    return r, c, v

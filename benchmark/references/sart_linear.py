"""Plain reference: linear SART with a Laplacian penalty, as the reference
solver defines it (vsnever/mpi-cuda-sartsolver, sartsolver.cpp:133-339, with
the CUDA path's fp32 measures: the frame normalised by its maximum, negative
pixels left out of the Eq. 4 guess, a cold guess floored at 1e-7).

Written in ``jax.numpy`` at ``Precision.HIGHEST`` and float32, the precision
the configuration states. It imports nothing of the program and takes
nothing the program made: the matrix comes from ``benchmark/world.py``
regenerated from the seed, the Laplacian is a stencil on the voxel grid, and
the ray sums, masks and normalisation are its own. What it is given of the
program's output is what a user's series feeds forward: the previous
frame's solution, as the warm start of the frame it checks.

One iteration (Eq. 2-5): with ``fitted = H f``,
``w = mask_p (g - fitted) / lambda``,
``f <- max(f + mask_v relaxation / rho * H^T w - beta L f, 0)``, then
``C = (|g|^2 - |H f|^2) / |g|^2``; a frame stops at iteration ``n >= 2``
when ``|C_n - C_(n-1)| < tol``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
GUESS_FLOOR = 1.0e-7


def _sumsq(x):
    """sum(x^2) in float32 by blocks of 128, so rounding stays near one ulp
    of the total (the convergence test differences it)."""
    n = x.shape[0]
    pad = (-n) % 128
    x = jnp.pad(x, (0, pad)).reshape(-1, 128)
    return jnp.sum(jnp.sum(x * x, axis=1))


def _stencil(f, grid):
    """``L f`` for the graph Laplacian of the voxel grid's nearest
    neighbours (degree on the diagonal, -1 per in-grid neighbour; grid of
    any rank, row-major); pad voxels 0."""
    nv = math.prod(grid)
    x = f[:nv].reshape(grid)
    z = jnp.pad(x, 1)
    zo = jnp.pad(jnp.ones_like(x), 1)
    inner = tuple(slice(1, -1) for _ in grid)
    nbr = jnp.zeros_like(x)
    deg = jnp.zeros_like(x)
    for ax in range(len(grid)):
        for lo, hi in ((None, -2), (2, None)):
            sl = inner[:ax] + (slice(lo, hi),) + inner[ax + 1:]
            nbr = nbr + z[sl]
            deg = deg + zo[sl]
    out = (deg * x - nbr).reshape(-1)
    return jnp.pad(out, (0, f.shape[0] - nv))


class Reference:
    """SART on a resident matrix ``H`` ([P_pad, V_pad] fp32, any layout the
    caller shards it in; pads are zero)."""

    def __init__(self, H, config: dict, solver: dict):
        self.H = H
        self.P, self.V = int(config["npixel"]), int(config["nvoxel"])
        self.grid = tuple(int(n) for n in config["grid"])
        self.s = solver
        self.rho = jnp.sum(H, axis=0)  # ray density, per voxel
        self.lam = jnp.sum(H, axis=1)  # ray length, per pixel
        self._run = jax.jit(self._iterate, static_argnums=(7,))

    def _iterate(self, H, rho, lam, g, msq, f0, n_iter, use_guess: bool):
        s = self.s
        vmask = rho > s["ray_density_threshold"]
        pmask = (lam > s["ray_length_threshold"]) & (g >= 0)
        inv_rho = jnp.where(vmask, s["relaxation"] / jnp.where(vmask, rho, 1),
                            0.0)
        inv_lam = jnp.where(pmask, 1.0 / jnp.where(pmask, lam, 1), 0.0)
        if use_guess:  # Eq. 4
            bp = jnp.dot(jnp.where(g > 0, g, 0.0), H, precision=HIGHEST)
            f0 = jnp.where(vmask, bp / jnp.where(vmask, rho, 1), 0.0)
            f0 = jnp.maximum(f0, GUESS_FLOOR)
        beta = s["beta_laplace"]
        max_it = s["max_iterations"]

        def body(k, carry):
            f, fitted, conv = carry
            w = jnp.where(pmask, g - fitted, 0.0) * inv_lam
            bp = jnp.dot(w, H, precision=HIGHEST)
            f = jnp.maximum(f + inv_rho * bp - beta * _stencil(f, self.grid), 0.0)
            fitted = jnp.dot(H, f, precision=HIGHEST)
            conv = conv.at[k].set((msq - _sumsq(fitted)) / msq)
            return f, fitted, conv

        fitted0 = jnp.dot(H, f0, precision=HIGHEST)
        f, _, conv = lax.fori_loop(
            0, n_iter, body, (f0, fitted0, jnp.zeros(max_it, jnp.float32)))
        return f, conv

    def project(self, f: np.ndarray) -> np.ndarray:
        """``H f`` ([P] float64) for a solution ``f`` ([V])."""
        fp = np.zeros(self.H.shape[1], np.float32)
        fp[: self.V] = f
        out = jnp.dot(self.H, jnp.asarray(fp), precision=HIGHEST)
        return np.asarray(out, np.float64)[: self.P]

    def replay(self, g: np.ndarray, f_start, n_iter: int):
        """Run ``n_iter`` iterations on frame ``g`` ([P] float64) from the
        Eq. 4 guess (``f_start`` None) or from ``f_start`` ([V] float64,
        physical units). Returns the solution ([V] float64, physical) and
        the convergence measure of each iteration."""
        g = np.asarray(g, np.float64)
        norm = float(np.max(g, initial=0.0))
        norm = norm if norm > 0 else 1.0
        gn = g / norm
        msq = float(np.sum(np.where(gn > 0, gn, 0.0) ** 2))
        msq = msq if msq > 0 else 1.0
        Pp, Vp = self.H.shape
        g_pad = np.full(Pp, -1.0, np.float32)
        g_pad[: self.P] = gn
        f0 = np.zeros(Vp, np.float32)
        if f_start is not None:
            f0[: self.V] = np.asarray(f_start, np.float64) / norm
        f, conv = self._run(self.H, self.rho, self.lam, jnp.asarray(g_pad),
                            jnp.float32(msq),
                            jnp.asarray(f0), jnp.int32(n_iter),
                            f_start is None)
        f = np.asarray(f, np.float64)[: self.V] * norm
        return f, np.asarray(conv, np.float64)[:n_iter]

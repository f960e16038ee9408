"""The algorithm's work, computed from shapes, and the chip's least time.

One SART loop iteration needs the chip's RTM block once, at least (a fused
sweep reads each element once for both projections), and 4 B P V flops over
the whole matrix: 2 B P V for the back-projection ``H^T w`` and 2 B P V for
the forward projection ``H f``. An Eq. 4 guess is one more read and 2 B P V.
The same work is charged whatever implements the sweep, so a PR that swaps
the implementation is judged on the same yardstick.
"""

from __future__ import annotations

import json
import math
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")
ITEMSIZE = {"float32": 4, "bfloat16": 2, "int8": 1}


def peaks(device_kind: str) -> dict:
    """The table's peaks for ``device_kind``; an unknown part is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]


def chip_block(npixel: int, nvoxel: int, mesh: tuple) -> tuple:
    """Rows and columns of the RTM one chip holds on a (pixels, voxels)
    mesh."""
    return math.ceil(npixel / mesh[0]), math.ceil(nvoxel / mesh[1])


def sweep_iteration(npixel, nvoxel, mesh, storage: str, batch: int = 1):
    """(bytes, flops) one chip needs for one loop iteration."""
    rows, cols = chip_block(npixel, nvoxel, mesh)
    return rows * cols * ITEMSIZE[storage], 4 * batch * rows * cols


def guess(npixel, nvoxel, mesh, storage: str, batch: int = 1):
    """(bytes, flops) one chip needs for one Eq. 4 guess."""
    rows, cols = chip_block(npixel, nvoxel, mesh)
    return rows * cols * ITEMSIZE[storage], 2 * batch * rows * cols


def least_seconds(nbytes: float, flops: float, peak: dict) -> float:
    """The larger of the bandwidth and the compute bound."""
    return max(nbytes / peak["hbm_bytes_per_s"], flops / peak["flops_per_s"])

"""Traffic driver ``warm_series``: the reference's core workload
(main.cpp:131-140), frames solved in order, each warm-started from the last.

It builds the program's objects the way ``sartsolve`` (cli.py) does with no
shard flags: ``SolverOptions`` from the CLI parser's defaults plus the
configuration's flags, the mesh ``choose_mesh_shape`` picks, the auto-fused
self-test, and ``DistributedSARTSolver`` over a device RTM laid out as
``read_and_shard_rtm`` lays it out. It then drives ``solve_chain`` as the
CLI's grouped loop does: K frames per chain (the parser's
``--chain_frames`` default), each chain warm-started from the last through
``warm=``, pipelined one deep (chain k+1 is dispatched before chain k's
results are fetched), and every frame's solution fetched to host memory.

Set-up solves chain 0 (frame 0 cold, from the Eq. 4 guess) and chain 1
(warm), which compiles both programs the window uses. The window starts at
a dispatch with nothing in flight and ends at the first chain delivery at
or after ``seconds``; the chain then in flight is drained and not counted.
"""

from __future__ import annotations

import math
import os
import sys
from dataclasses import dataclass

import numpy as np

from benchmark import world as W


@dataclass
class Frame:
    index: int
    chain: int
    status: int
    iterations: int
    phase: str  # "setup", "window", "drain" or "trace"


@dataclass
class Outcome:
    frames: list
    solutions: dict  # frame index -> [V] float64 solution, physical units
    inputs: np.ndarray  # [N, P] float64 frames as the program got them
    setup_s: float
    window_s: float
    compiles_in_window: int
    backend_compiles_in_window: int
    peak_bytes: int
    mesh: tuple
    chain_frames: int
    storage_dtype: str
    engaged: str
    traced: bool = False
    exhausted: bool = False


def _solver_options(args):
    """``SolverOptions`` exactly as ``cli.main`` builds them on the device
    profile (no ``--use_cpu``)."""
    from sartsolver_tpu.config import SolverOptions

    stride = args.schedule_stride or int(
        os.environ.get("SART_SCHEDULE_STRIDE", "16"))
    return SolverOptions(
        logarithmic=args.logarithmic,
        ray_density_threshold=args.ray_density_threshold,
        ray_length_threshold=args.ray_length_threshold,
        conv_tolerance=args.conv_tolerance,
        beta_laplace=args.beta_laplace,
        relaxation=args.relaxation,
        relaxation_decay=args.relaxation_decay,
        max_iterations=args.max_iterations,
        divergence_recovery=args.divergence_recovery,
        schedule_stride=stride,
        integrity=bool(args.integrity),
        os_subsets=args.os_subsets,
        momentum=args.momentum,
        rtm_dtype=args.rtm_dtype,
        fused_sweep=args.fused_sweep,
        sparse_rtm=args.sparse_rtm or os.environ.get("SART_SPARSE_RTM", "off"),
        lowrank_rtm=(args.lowrank_rtm
                     or os.environ.get("SART_LOWRANK_RTM", "off")),
    )


def series_length(cell, seconds: float, trace: bool, K: int) -> int:
    """Frames the run may need: set-up's two chains, the window at the
    cell's rate cap (ten times the rate measured when the cell was made),
    the chain in flight at its close, and the traced chains."""
    cap = float(cell.workload["rate_cap_frames_per_s"])
    window = K * math.ceil(cap * seconds / K) + K
    traced = K * int(cell.workload["trace_chains"]) if trace else 0
    return 2 * K + window + K + traced


def run(cell, rt) -> Outcome:
    """Drive one run. ``rt`` is the harness's runtime (seed, seconds,
    trace, devices, clock, span annotation, profiler, compile counter,
    extra CLI flags)."""
    from jax.sharding import NamedSharding, PartitionSpec
    from sartsolver_tpu.cli import build_parser
    from sartsolver_tpu.models import sart as sart_mod
    from sartsolver_tpu.ops.fused_sweep import resolve_fused_auto
    from sartsolver_tpu.ops.laplacian import make_laplacian
    from sartsolver_tpu.parallel.mesh import (
        COL_ALIGN, PIXEL_AXIS, ROW_ALIGN, VOXEL_AXIS, choose_mesh_shape,
        make_mesh, padded_size,
    )
    from sartsolver_tpu.parallel.sharded import DistributedSARTSolver

    cfg = cell.config
    P, V = int(cfg["npixel"]), int(cfg["nvoxel"])
    stated = _solver_options(build_parser().parse_args(list(cfg["flags"])))
    for key, want in cfg["solver"].items():  # the reference reads these
        if getattr(stated, key) != want:
            raise ValueError(f"flags give {key}={getattr(stated, key)}, the "
                             f"configuration's solver block {want}")
    # a control or a planted fault adds flags after the configuration's
    args = build_parser().parse_args(list(cfg["flags"]) + list(rt.extra_flags))
    K = args.chain_frames
    opts = _solver_options(args)
    storage = opts.rtm_dtype or opts.dtype
    if storage not in ("float32", "bfloat16"):
        raise ValueError(f"world.build_rtm makes float32/bfloat16, not "
                         f"{storage}")
    devices = rt.devices
    resolve_fused_auto(opts, pixel_sharded=False)
    n_pix, n_vox = choose_mesh_shape(len(devices), P, V, opts,
                                     args.batch_frames)
    mesh = make_mesh(n_pix, n_vox, devices=devices[: n_pix * n_vox])
    shape = (padded_size(P, n_pix * ROW_ALIGN),
             padded_size(V, n_vox * COL_ALIGN))

    phases = {"start": rt.clock() - rt.t_start}
    n_frames = series_length(cell, rt.seconds, rt.trace, K)
    series = W.Series(cfg, cell.traffic, rt.seed, n_frames)
    sharding = NamedSharding(mesh, PartitionSpec(PIXEL_AXIS, VOXEL_AXIS))
    with rt.span("world.build"):
        frames = series.noisy(W.noiseless_frames(
            cfg, rt.seed, sharding, shape, series.emissivity(shape[1])), P)
        phases["frames"] = rt.clock() - rt.t_start
        rtm = W.build_rtm(cfg, rt.seed, sharding, shape, dtype=storage)
        rtm.block_until_ready()
        phases["matrix"] = rt.clock() - rt.t_start
    lap = None
    if args.laplacian_file:
        lap = make_laplacian(*W.grid_laplacian(W.grid_shape(cfg)),
                             dtype=opts.dtype)
    solver = DistributedSARTSolver(rtm, lap, opts=opts, mesh=mesh,
                                   npixel=P, nvoxel=V)
    del rtm
    phases["solver"] = rt.clock() - rt.t_start

    records, solutions = [], {}

    def deliver(res, chain, first, phase):
        status, iters = res.status, res.iterations
        sols = res.fetch_solutions()
        for b in range(K):
            records.append(Frame(first + b, chain, int(status[b]),
                                 int(iters[b]), phase))
            solutions[first + b] = sols[b]

    # set-up: chain 0 from the Eq. 4 guess, chain 1 warm (both programs)
    warm = solver.solve_chain(frames[0:K])
    deliver(warm, 0, 0, "setup")
    phases["chain0"] = rt.clock() - rt.t_start
    warm = solver.solve_chain(frames[K:2 * K], warm=warm)
    deliver(warm, 1, K, "setup")
    phases["chain1"] = rt.clock() - rt.t_start
    print("set-up (s since start): " + " ".join(
        f"{k}={v:.3f}" for k, v in phases.items()) + f" series={n_frames}",
        file=sys.stderr)
    engaged = str(sart_mod.FUSED_ENGAGEMENT["last"])

    # the window
    nxt, chain, prev, res = 2 * K, 2, None, None
    exhausted = False
    rt.compiles.reset()
    t0 = rt.clock()
    setup_s = t0 - rt.t_start
    while True:
        if nxt + K > n_frames:
            exhausted = True
            break
        with rt.span("frames.stage"):
            stack = frames[nxt:nxt + K]
        with rt.span("solve.dispatch"):
            res = solver.solve_chain(stack, warm=warm)
        warm = res
        if prev is not None:
            with rt.span("solution.fetch"):
                deliver(*prev, "window")
            if rt.clock() - t0 >= rt.seconds:
                break
        prev = (res, chain, nxt)
        nxt, chain = nxt + K, chain + 1
    t1 = rt.clock()
    compiles, backend = rt.compiles.count, rt.compiles.backend
    if not exhausted:
        deliver(res, chain, nxt, "drain")
        nxt, chain = nxt + K, chain + 1

    # the traced segment: the window's next chains, pipelined the same way,
    # from a dispatch with nothing in flight to a delivery with none left
    traced = False
    if rt.trace and not exhausted:
        n = int(cell.workload["trace_chains"])
        with rt.profile():
            pending = None
            for _ in range(n):
                with rt.span("frames.stage"):
                    stack = frames[nxt:nxt + K]
                with rt.span("solve.dispatch"):
                    res = solver.solve_chain(stack, warm=warm)
                warm = res
                if pending is not None:
                    with rt.span("solution.fetch"):
                        deliver(*pending, "trace")
                pending = (res, chain, nxt)
                nxt, chain = nxt + K, chain + 1
            with rt.span("solution.fetch"):
                deliver(*pending, "trace")
        traced = True

    peak = max(d.memory_stats()["peak_bytes_in_use"]
               for d in mesh.devices.flat) if rt.memory_stats else 0
    solver.close()
    del warm, res, prev
    return Outcome(
        frames=records, solutions=solutions, inputs=frames, setup_s=setup_s,
        window_s=t1 - t0, compiles_in_window=compiles,
        backend_compiles_in_window=backend, peak_bytes=int(peak),
        mesh=(n_pix, n_vox), chain_frames=K, storage_dtype=storage,
        engaged=engaged, traced=traced, exhausted=exhausted,
    )

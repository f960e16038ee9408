"""``sartsolve`` — end-to-end CLI entrypoint.

Replicates the reference binary's orchestration (main.cpp:25-151): parse and
validate flags, classify and cross-validate input files, build the composite
measurement stream, load the RTM and optional Laplacian, construct the
solver, then run the frame loop (warm-starting each frame from the previous
solution unless ``--no_guess``) and write the incrementally-flushed solution
file plus the voxel-map round trip.

Flag set and defaults match the reference CLI (arguments.cpp:86-171);
``--use_cpu`` selects the fp64 CPU-parity profile on the host CPU backend
(the reference's fp64 CPU solver), the default profile is fp32 on
accelerator devices (the reference's CUDA path). TPU-specific extensions are
grouped under "tpu options".
"""

from __future__ import annotations

import argparse
import math
import sys
import time as _time
from typing import List, Optional

import numpy as np


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="sartsolve",
        description="Impurity flux reconstruction for ITER: emissivity",
        epilog="subcommands: `sartsolve lint` — static analysis for JAX "
               "hazards (AST rules + compile audit; see `sartsolve lint "
               "--help` and docs/STATIC_ANALYSIS.md); `sartsolve metrics` "
               "— validate, summarize and diff --metrics_out artifacts "
               "(see `sartsolve metrics --help` and "
               "docs/OBSERVABILITY.md); `sartsolve top FILE` — refreshing "
               "one-screen view of a live run from its heartbeat / "
               "Prometheus textfile / status snapshot; `sartsolve serve` "
               "/ `sartsolve submit` — resident serving engine with "
               "admission control, deadlines and a crash-recoverable "
               "request journal (docs/SERVING.md; `serve --supervised` "
               "adds self-healing restarts); `sartsolve fleet` — M "
               "serve workers behind one controller with "
               "tenant-affinity routing and journal-backed failover "
               "(docs/SERVING.md §10); `sartsolve chaos` — "
               "randomized fault/kill campaign proving the supervised "
               "engine's exactly-once and byte-identity invariants. "
               "A running solve "
               "answers SIGUSR1 with a status snapshot on stderr and "
               "<output>.status.json, and flushes a flight bundle "
               "(<output>.crash.json) on abnormal exits. "
               "exit codes: 0 success; 1 input/flag error; 2 run completed "
               "with FAILED/DIVERGED frames; 3 aborted on an unrecoverable "
               "infrastructure failure after retries or a watchdog hard "
               "abort (file resumable); 4 stopped gracefully on "
               "SIGTERM/SIGINT after draining the in-flight frame group "
               "(file resumable; second signal aborts immediately) — "
               "see docs/RESILIENCE.md.",
    )
    p.add_argument("-o", "--output_file", default="solution.h5",
                   help="Filename to save the solution.")
    p.add_argument("-t", "--time_range", default="",
                   help="Time intervals in s to process in a form: "
                        "start:stop:(step):(synch_threshold), e.g. "
                        "'20.5:40.1, 45.2:51:1.5:0.05'. The step and the "
                        "synchronization threshold are optional.")
    p.add_argument("-w", "--wavelength_threshold", type=float, default=50.0,
                   help="An RTM is considered valid if its wavelength is within "
                        "this threshold of the image wavelength (in nm).")
    p.add_argument("-d", "--ray_density_threshold", type=float, default=1.0e-6,
                   help="Voxels with ray density lesser than this threshold are ignored.")
    p.add_argument("-r", "--ray_length_threshold", type=float, default=1.0e-6,
                   help="Pixels with ray length lesser than this threshold are ignored.")
    p.add_argument("-m", "--max_iterations", type=int, default=2000,
                   help="Maximum number of SART iterations.")
    p.add_argument("-c", "--conv_tolerance", type=float, default=1.0e-5,
                   help="SART convolution relative tolerance.")
    p.add_argument("-l", "--laplacian_file", default="",
                   help="File with laplacian regularization matrix.")
    p.add_argument("-b", "--beta_laplace", type=float, default=2.0e-2,
                   help="Weight of the regularization factor.")
    p.add_argument("-R", "--relaxation", type=float, default=1.0,
                   help="Relaxation parameter.")
    p.add_argument("--relaxation_decay", type=float, default=1.0,
                   help="Geometric relaxation schedule: iteration k uses "
                        "relaxation * decay^k. Default 1.0 (fixed "
                        "relaxation, reference behavior).")
    p.add_argument("--os_subsets", type=int, default=1,
                   help="Ordered-subsets SART: cycle each iteration's "
                        "update over N interleaved pixel-row subsets "
                        "(docs/PERFORMANCE.md §9); must divide the padded "
                        "per-shard pixel extent. Default 1 (classic sweep, "
                        "byte-identical).")
    p.add_argument("--momentum", default="off",
                   choices=["off", "nesterov"],
                   help="Nesterov/FISTA momentum over the SART update "
                        "with gradient-based restart; resets on every "
                        "divergence-recovery rollback "
                        "(docs/PERFORMANCE.md §9). Default off "
                        "(byte-identical).")
    p.add_argument("-n", "--raytransfer_name", default="with_reflections",
                   help="Ray transfer matrix dataset name.")
    p.add_argument("-L", "--logarithmic", action="store_true",
                   help="Use logarithmic SART solver.")
    p.add_argument("--max_cached_frames", type=int, default=100,
                   help="Maximum number of cached image frames.")
    p.add_argument("--max_cached_solutions", type=int, default=100,
                   help="Maximum number of cached solutions.")
    p.add_argument("--no_guess", action="store_true",
                   help="Do not use solution found on previous time moment as "
                        "initial guess for the next one.")
    p.add_argument("--resume", action="store_true",
                   help="Resume an interrupted run: skip frames already "
                        "present in the output file, warm-start from its "
                        "last solution and append (requires the same inputs "
                        "and flags as the original run).")
    p.add_argument("--use_cpu", action="store_true",
                   help="Perform all calculations on CPUs (fp64 parity profile).")
    p.add_argument("--parallel_read", action="store_true",
                   help="All hosts read their RTM stripes simultaneously "
                        "(multi-host runs serialize reads host-by-host by "
                        "default, matching the reference's HDD-friendly "
                        "round-robin; single-host reads are always direct).")
    p.add_argument("input_files", nargs="*",
                   help="List of ray transfer matrix and camera image hdf5 files.")

    tpu = p.add_argument_group("tpu options")
    tpu.add_argument("--geometry", default=None, metavar="FILE",
                     help="Matrix-free implicit operator: derive the "
                          "projections H f / H^T w on the fly from the "
                          "versioned geometry record FILE "
                          "(docs/FORMATS.md §geometry) instead of "
                          "reading ray-transfer matrix files — inputs "
                          "are image files only, and device memory "
                          "holds the ray table instead of the RTM "
                          "(docs/PERFORMANCE.md §11). Single-process, "
                          "pixel-sharded meshes only; incompatible "
                          "with --laplacian_file and rtm_dtype=int8.")
    tpu.add_argument("--pixel_shards", type=int, default=None,
                     help="Number of mesh shards along the pixel axis "
                          "(default: auto — all visible devices, unless the "
                          "fused sweep prefers a voxel-major mesh).")
    tpu.add_argument("--voxel_shards", type=int, default=None,
                     help="Number of mesh shards along the voxel axis "
                          "(column sharding; shrinks per-chip solution-state "
                          "memory when nvoxel outgrows one chip). Default: "
                          "auto — all devices on the voxel axis when the "
                          "fused Pallas sweep is eligible per-shard (it "
                          "needs the full pixel extent on each device), "
                          "else 1.")
    tpu.add_argument("--batch_frames", type=int, default=1,
                     help="Solve N composite frames per device program "
                          "(gemv->gemm on the MXU; the RTM is read once per "
                          "iteration for the whole batch). Requires "
                          "--no_guess, since batched frames carry no "
                          "warm-start dependency. Single-host runs use N "
                          "continuously-batched lanes by default (see "
                          "--no_continuous_batching).")
    tpu.add_argument("--schedule_stride", type=int, default=None,
                     help="Continuous batching: iterations between "
                          "scheduler control returns — converged lanes "
                          "retire and backfill from the frame queue every "
                          "N iterations (docs/PERFORMANCE.md §8: larger "
                          "strides amortize the per-stride host sync, "
                          "smaller strides track convergence tighter). "
                          "Default: SART_SCHEDULE_STRIDE env, else 16.")
    tpu.add_argument("--no_continuous_batching", action="store_true",
                     help="Disable the convergence-aware lane scheduler "
                          "for --batch_frames > 1 and run the classic "
                          "run-to-slowest group loop (each batch waits "
                          "for its slowest frame; converged lanes pad "
                          "the device until the batch drains). Multihost "
                          "runs always use the classic loop.")
    tpu.add_argument("--chain_frames", type=int, default=8,
                     help="Warm-started frames dispatched per device "
                          "program (lax.scan carrying the previous "
                          "solution, the solver loop inside): one host "
                          "round trip per N frames instead of per frame, "
                          "with per-frame results identical to serial "
                          "dispatch. 1 dispatches one frame per program "
                          "through the same grouped loop. Applies to the "
                          "default warm-start loop, including --multihost "
                          "runs; ignored with --no_guess/--batch_frames.")
    tpu.add_argument("--rtm_dtype", default=None,
                     choices=["float32", "bfloat16", "float64", "int8"],
                     help="On-device RTM storage dtype. bfloat16 halves the "
                          "HBM traffic of the two dominant sweeps; int8 "
                          "quarters it via per-voxel-scaled quantized codes "
                          "(opt-in: solves the quantized system; needs the "
                          "fused sweep — available on pixel- and voxel-"
                          "sharded meshes alike).")
    tpu.add_argument("--profile_dir", default=None,
                     help="Write a jax.profiler trace of the frame loop "
                          "here. Each dispatched frame group (one frame "
                          "at group size 1) / scheduler stride "
                          "(continuous batching) is wrapped in a "
                          "StepTraceAnnotation, so the XLA device trace "
                          "aligns with obs spans and frame serials instead "
                          "of one undifferentiated blob.")
    tpu.add_argument("--fused_sweep", default="auto",
                     choices=["auto", "on", "off", "interpret"],
                     help="Fused iteration sweep: one HBM read of the RTM "
                          "per iteration instead of two — the Pallas kernel "
                          "when the pixel axis is whole per device, the "
                          "panel-psum scan when it is sharded (see "
                          "SART_FUSED_PANEL_BYTES). 'interpret' runs the "
                          "kernel in the Pallas interpreter (works off-TPU; "
                          "slow, for validation).")
    tpu.add_argument("--sparse_rtm", default=None, metavar="auto|off|EPS",
                     help="Block-sparse RTM mode (PERFORMANCE.md §10): "
                          "'auto' builds a lossless tile-occupancy index "
                          "at ingest and skips all-zero (pixel-block x "
                          "voxel-panel) tiles in the iteration sweep — "
                          "bit-identical results, FLOPs/bytes scale with "
                          "occupancy. A numeric EPS in [0, 1) drops tiles "
                          "whose entries are all <= EPS*max|H| (lossy; "
                          "rho/lambda and the Eq. 6 masks come from the "
                          "thresholded operator). 'auto' declines on "
                          "voxel-sharded meshes and multi-process runs; "
                          "an explicit EPS fails loudly there. Also via "
                          "SART_SPARSE_RTM.")
    tpu.add_argument("--lowrank_rtm", default=None, metavar="auto|off|RANK",
                     help="Factored RTM mode (PERFORMANCE.md §12): "
                          "approximate H ~= S + U V^T at ingest — a "
                          "tile-thresholded sparse core S plus a "
                          "rank-RANK randomized-SVD factorization of the "
                          "sub-threshold residual — and run every solve "
                          "on the composed factored operator (the fill "
                          "costs RANK*(npixel+nvoxel) MACs per "
                          "projection instead of npixel*nvoxel). 'auto' "
                          "walks a doubling rank ladder and declines "
                          "loudly to dense when no rank passes the "
                          "Frobenius + solve-parity quality gate; an "
                          "explicit RANK that fails the gate aborts "
                          "before staging. Also via SART_LOWRANK_RTM.")
    tpu.add_argument("--debug_nans", action="store_true",
                     help="Enable jax debug-NaN checking: abort with a "
                          "traceback at the first NaN-producing op instead "
                          "of propagating it into the solution (slow; "
                          "debugging only).")
    tpu.add_argument("--timing", action="store_true",
                     help="Print a per-phase wall-clock summary (validation, "
                          "RTM ingest, per-frame solve — the first frame "
                          "includes XLA compilation — and output writes) at "
                          "the end of the run.")
    o11y = p.add_argument_group(
        "observability options",
        "structured telemetry (docs/OBSERVABILITY.md): host-side only, "
        "zero-cost when disabled. Environment sinks: SART_METRICS_PROM "
        "writes a Prometheus textfile at end of run, SART_TRACE_EVENTS "
        "writes Chrome trace-event JSON (Perfetto) of the pipeline's "
        "host phases alongside --profile_dir's XLA traces.")
    o11y.add_argument("--metrics_out", default=None, metavar="FILE",
                      help="Write the run's telemetry artifact here as "
                           "JSONL (meta, per-frame solve records, "
                           "availability events, end-of-run metrics, "
                           "summary); validate/summarize/diff it with "
                           "`sartsolve metrics`.")
    res = p.add_argument_group(
        "resilience options",
        "fault handling (docs/RESILIENCE.md): retry/backoff knobs are "
        "environment variables (SART_RETRY_ATTEMPTS/_BASE_DELAY/"
        "_MAX_DELAY/_DEADLINE); fault injection for testing via "
        "SART_FAULT=site:kind:prob[:count]. Availability knobs: "
        "SART_WATCHDOG_TIMEOUT seconds arms the hang watchdog "
        "(stack dump + stuck-frame escalation; SART_WATCHDOG_GRACE "
        "before the hard abort), SART_HEARTBEAT_FILE is touched on "
        "every completed frame for external supervisors; SIGTERM/SIGINT "
        "stop gracefully at a frame-group boundary (exit 4, resumable), "
        "and RESOURCE_EXHAUSTED dispatch failures halve the frame-group "
        "size before failing frames.")
    res.add_argument("--divergence_recovery", type=int, default=0,
                     help="In-solve divergence guard: a frame whose "
                          "residual metric goes non-finite or exploding "
                          "rolls back to its last good iterate and "
                          "retries with halved relaxation, up to N "
                          "escalations; exhaustion (or non-finite input "
                          "data) marks the frame DIVERGED (status -2) "
                          "and the run continues. 0 (default) disables "
                          "the guard (reference behavior: divergence "
                          "spins to the iteration cap or NaNs the "
                          "output).")
    res.add_argument("--integrity", action="store_true",
                     help="End-to-end numerical-integrity layer "
                          "(docs/RESILIENCE.md §8; also SART_INTEGRITY=1): "
                          "per-iteration ABFT checksums in the solve cores "
                          "(sum(Hf)=rho.f / sum(H^T w)=lambda.w, folded "
                          "into the existing convergence all-reduce), RTM "
                          "stripe read-verify digests with re-read on "
                          "mismatch, post-upload rho/lambda verification, "
                          "and a periodic resident re-audit every "
                          "SART_INTEGRITY_REAUDIT frames. A detected frame "
                          "is recomputed once, then FAILED; "
                          "SART_SDC_ABORT_THRESHOLD terminal frames (or a "
                          "resident mismatch) quarantine the run with "
                          "exit 3. Default off: every traced program and "
                          "ingest byte is identical to a build without "
                          "the layer.")
    res.add_argument("--fail_fast", action="store_true",
                     help="Disable per-frame failure isolation: the first "
                          "frame whose ingest or solve fails aborts the "
                          "run (the reference's behavior) instead of "
                          "being recorded as a FAILED status row (-3) "
                          "while the run continues. Multihost runs "
                          "always fail fast (a per-process frame skip "
                          "would desynchronize the collective loop).")
    res.add_argument("--solve_ckpt_stride", type=int, default=0,
                     metavar="N",
                     help="In-solve checkpointing (docs/RESILIENCE.md §11; "
                          "continuous-batching path only): every N "
                          "scheduler strides, append a CRC-checksummed "
                          "snapshot of the full lane state — warm chain, "
                          "momentum carries, divergence-ladder level, "
                          "iteration counters, reorder buffer — to "
                          "<output>.solveckpt (SART_SOLVE_CKPT_FILE "
                          "overrides). --resume then restores the run "
                          "mid-frame at the newest consistent checkpoint "
                          "instead of re-running the initial guess and "
                          "every prior sweep. 0 (default) disables: the "
                          "run is byte-identical to a build without the "
                          "layer.")
    tpu.add_argument("--multihost", action="store_true",
                     help="Multi-host run (one process per host, e.g. a TPU "
                          "pod slice): initialize the JAX multi-controller "
                          "runtime, mesh over ALL hosts' devices, each "
                          "process reads only its RTM row stripes, process "
                          "0 writes the output (with --resume the output "
                          "file must be on a filesystem visible to every "
                          "host).")
    return p


def _validate(args) -> None:
    """Range validation mirroring arguments.cpp:184-236."""
    def fail(msg: str) -> None:
        print(msg, file=sys.stderr)
        raise SystemExit(1)

    if args.ray_density_threshold < 0:
        fail(f"Argument ray_density_threshold must be >= 0, {args.ray_density_threshold} given.")
    if args.ray_length_threshold < 0:
        fail(f"Argument ray_length_threshold must be >= 0, {args.ray_length_threshold} given.")
    if args.max_iterations < 1:
        fail(f"Argument max_iterations must be >= 1, {args.max_iterations} given.")
    if args.max_iterations > 2**24:
        fail(f"Argument max_iterations must be <= {2**24}, "
             f"{args.max_iterations} given.")
    if args.conv_tolerance <= 0:
        fail(f"Argument conv_tolerance must be > 0, {args.conv_tolerance} given.")
    if not (0 < args.relaxation <= 1.0):
        fail(f"Argument relaxation must be within (0, 1] interval, {args.relaxation} given.")
    if not (0 < args.relaxation_decay <= 1.0):
        fail("Argument relaxation_decay must be within (0, 1] interval, "
             f"{args.relaxation_decay} given.")
    if args.beta_laplace < 0:
        fail("Argument beta_laplace must be positive.")
    if args.rtm_dtype == "int8" and args.use_cpu:
        fail("Argument rtm_dtype='int8' needs the fp32 device profile; "
             "it cannot be combined with --use_cpu.")
    if args.max_cached_frames <= 0:
        fail("Argument max_cached_frames must be positive.")
    if args.max_cached_solutions <= 0:
        fail("Argument max_cached_solutions must be positive.")
    if getattr(args, "geometry", None):
        # matrix-free mode: the geometry record replaces the RTM files,
        # so a single image file is a complete input set
        if len(args.input_files) < 1:
            fail("At least one image input file is required with "
                 "--geometry, 0 given.")
        if getattr(args, "multihost", False):
            fail("Argument geometry is single-process: the implicit "
                 "operator's rays are staged whole per host; drop "
                 "--multihost or materialize the matrix.")
        if getattr(args, "laplacian_file", None):
            fail("Argument geometry cannot be combined with "
                 "--laplacian_file: beta_laplace smoothing needs the "
                 "materialized operator.")
    elif len(args.input_files) < 2:
        fail("At least two input file, one with RTM and one with image, are "
             f"required, {len(args.input_files)} given.")
    if args.pixel_shards is not None and args.pixel_shards < 1:
        fail(f"Argument pixel_shards must be >= 1, {args.pixel_shards} given.")
    if args.voxel_shards is not None and args.voxel_shards < 1:
        fail(f"Argument voxel_shards must be >= 1, {args.voxel_shards} given.")
    if args.batch_frames < 1:
        fail(f"Argument batch_frames must be >= 1, {args.batch_frames} given.")
    if args.batch_frames > 1 and not args.no_guess:
        fail("Argument batch_frames > 1 requires --no_guess (batched frames "
             "have no warm-start dependency).")
    if args.chain_frames < 1:
        fail(f"Argument chain_frames must be >= 1, {args.chain_frames} given.")
    if args.schedule_stride is not None and args.schedule_stride < 1:
        fail(f"Argument schedule_stride must be >= 1, "
             f"{args.schedule_stride} given.")
    if args.divergence_recovery < 0:
        fail("Argument divergence_recovery must be >= 0, "
             f"{args.divergence_recovery} given.")
    if args.solve_ckpt_stride < 0:
        fail(f"Argument solve_ckpt_stride must be >= 0, "
             f"{args.solve_ckpt_stride} given.")
    if args.solve_ckpt_stride and (args.batch_frames <= 1
                                   or args.no_continuous_batching
                                   or args.multihost):
        fail("Argument solve_ckpt_stride snapshots the continuous-batching "
             "scheduler's lane state; it needs --batch_frames > 1 without "
             "--no_continuous_batching (multihost runs use the classic "
             "grouped loop and cannot checkpoint mid-frame).")
    if (args.divergence_recovery and args.logarithmic
            and args.fused_sweep in ("on", "interpret")):
        fail("Argument divergence_recovery cannot combine --logarithmic "
             f"with --fused_sweep {args.fused_sweep}: the per-frame "
             "relaxation scale cannot enter the fused kernel's literal "
             "exponent; use --fused_sweep auto/off.")
    if args.os_subsets < 1:
        fail(f"Argument os_subsets must be >= 1, {args.os_subsets} given.")
    if args.os_subsets > 1 and args.fused_sweep in ("on", "interpret"):
        fail(f"Argument os_subsets > 1 runs the subset-cycle sweep; "
             f"--fused_sweep {args.fused_sweep} cannot be honored there — "
             "use auto or off.")
    if args.sparse_rtm is None:
        # flag > SART_SPARSE_RTM env > off (the schedule_stride pattern)
        import os as _os_sparse

        args.sparse_rtm = _os_sparse.environ.get("SART_SPARSE_RTM", "off")
    if args.sparse_rtm not in ("auto", "off"):
        try:
            eps = float(args.sparse_rtm)
            ok = 0.0 <= eps < 1.0 and math.isfinite(eps)
        except ValueError:
            ok = False
        if not ok:
            fail("Argument sparse_rtm must be 'auto', 'off' or a relative "
                 f"threshold in [0, 1), {args.sparse_rtm!r} given.")
        if args.use_cpu:
            fail("Argument sparse_rtm needs the fp32 device profile; an "
                 "explicit threshold cannot be combined with --use_cpu "
                 "(use 'auto', which declines there).")
    if args.sparse_rtm != "off" and args.fused_sweep in ("on", "interpret"):
        fail("Argument sparse_rtm engages the block-sparse panel sweep; "
             f"--fused_sweep {args.fused_sweep} cannot be honored there — "
             "use auto or off.")
    if args.lowrank_rtm is None:
        # flag > SART_LOWRANK_RTM env > off (the sparse_rtm pattern)
        import os as _os_lowrank

        args.lowrank_rtm = _os_lowrank.environ.get("SART_LOWRANK_RTM", "off")
    if args.lowrank_rtm not in ("auto", "off"):
        try:
            ok = int(args.lowrank_rtm) >= 1
        except ValueError:
            ok = False
        if not ok:
            fail("Argument lowrank_rtm must be 'auto', 'off' or a "
                 f"positive integer factorization rank, "
                 f"{args.lowrank_rtm!r} given.")
        if args.use_cpu:
            fail("Argument lowrank_rtm needs the fp32 device profile; an "
                 "explicit rank cannot be combined with --use_cpu "
                 "(use 'auto', which declines there).")
    if args.lowrank_rtm != "off":
        if args.fused_sweep in ("on", "interpret"):
            fail("Argument lowrank_rtm runs the factored (S + U V^T) "
                 f"sweep; --fused_sweep {args.fused_sweep} cannot be "
                 "honored there — use auto or off.")
        if getattr(args, "geometry", None):
            fail("Argument lowrank_rtm factorizes a stored matrix; "
                 "--geometry has none to factorize.")
        if args.sparse_rtm not in ("auto", "off"):
            fail("Arguments lowrank_rtm and an explicit sparse_rtm "
                 "threshold both claim the stored matrix; the factored "
                 "core already thresholds it — drop one.")


def schedule_stride(args) -> int:
    """Continuous-batching stride: flag > SART_SCHEDULE_STRIDE env > 16.
    Resolved here (not in SolverOptions) so the env override is CLI
    policy, like the other SART_* knobs."""
    import os

    from sartsolver_tpu.config import SartInputError

    if args.schedule_stride is not None:
        stride = args.schedule_stride
    else:
        env = os.environ.get("SART_SCHEDULE_STRIDE", "16")
        try:
            stride = int(env)
        except ValueError:
            # fail loudly like --schedule_stride would — a silently
            # ignored operator typo on a perf knob is worse than exit 1
            raise SartInputError(
                f"SART_SCHEDULE_STRIDE must be an integer >= 1, "
                f"{env!r} given."
            )
    if stride < 1:
        raise SartInputError(
            f"SART_SCHEDULE_STRIDE must be >= 1, {stride} given."
        )
    return stride


def solver_options(args, *, schedule_stride: int, integrity: bool):
    """``SolverOptions`` from validated args (``_validate`` has resolved
    the sparse/lowrank env defaults): the fp64 CPU-parity profile under
    ``--use_cpu``, else the fp32 device profile."""
    from sartsolver_tpu.config import SolverOptions

    common = dict(
        logarithmic=args.logarithmic,
        ray_density_threshold=args.ray_density_threshold,
        ray_length_threshold=args.ray_length_threshold,
        conv_tolerance=args.conv_tolerance,
        beta_laplace=args.beta_laplace,
        relaxation=args.relaxation,
        relaxation_decay=args.relaxation_decay,
        max_iterations=args.max_iterations,
        divergence_recovery=args.divergence_recovery,
        schedule_stride=schedule_stride,
        integrity=integrity,
        os_subsets=args.os_subsets,
        momentum=args.momentum,
        # forwarded on both profiles, so an explicit --fused_sweep on fails
        # loudly under --use_cpu (the fused sweep is fp32-only) instead of
        # silently degrading to the unfused path
        fused_sweep=args.fused_sweep,
    )
    if args.use_cpu:
        return SolverOptions.cpu_parity(**common)
    return SolverOptions(
        **common,
        rtm_dtype=args.rtm_dtype,
        sparse_rtm=args.sparse_rtm,
        lowrank_rtm=args.lowrank_rtm,
    )


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:]) if argv is None else list(argv)
    if argv and argv[0] == "lint":
        # static-analysis subcommand (docs/STATIC_ANALYSIS.md): AST lint
        # rules + compile audit of the registered hot entry points. The
        # solver CLI itself keeps the reference's flat flag set, so the
        # subcommand is dispatched before the solver parser ever sees it
        # ("lint" would otherwise parse as an input file).
        from sartsolver_tpu.analysis.cli import lint_main

        return lint_main(argv[1:])
    if argv and argv[0] == "metrics":
        # artifact tooling subcommand (docs/OBSERVABILITY.md): validate,
        # summarize and diff --metrics_out JSONL artifacts; dispatched
        # like `lint`, before the solver parser sees the argv
        from sartsolver_tpu.obs.cli import metrics_main

        return metrics_main(argv[1:])
    if argv and argv[0] == "top":
        # live-run viewer (docs/OBSERVABILITY.md §9): a refreshing
        # one-screen render of the heartbeat / Prometheus textfile /
        # SIGUSR1 status snapshot a running solve publishes
        from sartsolver_tpu.obs.cli import top_main

        return top_main(argv[1:])
    if argv and argv[0] == "serve":
        # resident serving engine (docs/SERVING.md): session held warm,
        # requests from an ingest dir / local socket, crash-recoverable
        # request journal; dispatched like `lint`, before the solver
        # parser sees the argv
        from sartsolver_tpu.engine.cli import serve_main

        return serve_main(argv[1:])
    if argv and argv[0] == "fleet":
        # fleet controller (docs/SERVING.md §10): M serve workers,
        # tenant-affinity routing table, journal-backed failover;
        # dispatched like `serve`, before the solver parser sees argv
        from sartsolver_tpu.engine.cli import fleet_cli_main

        return fleet_cli_main(argv[1:])
    if argv and argv[0] == "submit":
        # serving-engine client (docs/SERVING.md): submit a request to
        # a running `sartsolve serve` and optionally await its outcome
        from sartsolver_tpu.engine.cli import submit_main

        return submit_main(argv[1:])
    if argv and argv[0] == "chaos":
        # chaos campaign harness (docs/SERVING.md §9): seeded fault
        # schedules + SIGKILLs against a real supervised serve, judged
        # on the exactly-once / byte-identity / restart-budget /
        # state-continuity invariants
        from sartsolver_tpu.resilience.chaos import chaos_main

        return chaos_main(argv[1:])
    try:
        args = build_parser().parse_args(argv)
    except SystemExit as err:
        # argparse exits 2 on unknown/malformed flags, which would collide
        # with EXIT_PARTIAL in the documented exit-code contract (a
        # scheduler would read a typo'd flag as "completed with failed
        # frames"); remap to the input-error code (EXIT_INPUT_ERROR = 1,
        # literal here so --help never pays the import). --help's exit 0
        # passes through.
        raise SystemExit(1 if err.code else 0) from None
    _validate(args)

    # Per-RUN warning scope: re-arm the once-per-run non-finite-pixel
    # warning so repeated runs in one interpreter (tests, notebooks)
    # each surface it (models/sart.py latch; the serving engine re-arms
    # per request instead).
    from sartsolver_tpu.models.sart import reset_nonfinite_warning

    reset_nonfinite_warning()

    # Heavy imports deferred so `--help` stays instant.
    import jax

    if args.debug_nans:
        jax.config.update("jax_debug_nans", True)

    # Persistent XLA compilation cache (utils/cache.py:
    # JAX_COMPILATION_CACHE_DIR as given, else <repo>/.jax_cache; empty
    # string disables).
    from sartsolver_tpu.utils.cache import configure_compilation_cache

    configure_compilation_cache()

    from sartsolver_tpu.resilience import shutdown, watchdog
    from sartsolver_tpu.resilience import integrity as integ_mod
    from sartsolver_tpu.resilience.failures import (
        EXIT_INFRASTRUCTURE, EXIT_INTERRUPTED, RECOVERABLE_FRAME_ERRORS,
        OutputWriteError, RunSummary, WatchdogTimeout,
    )
    from sartsolver_tpu.resilience.retry import (
        RetriesExhausted, reset_retry_stats,
    )
    # imported up here (not with the other writer imports inside the try):
    # the except clause below must be able to name it even when the
    # failure happens before the frame-loop imports ran
    from sartsolver_tpu.utils.asyncwriter import DeferredWriteError

    # per-run accounting: the retry counters feed this run's end-of-run
    # summary, not a process-lifetime total
    reset_retry_stats()

    # Observability (docs/OBSERVABILITY.md): a fresh per-run metrics
    # registry (--timing's PhaseTimer is a view over it) and, when
    # --metrics_out / SART_METRICS_PROM / SART_TRACE_EVENTS ask for them,
    # the artifact sinks. Host-side only; with no sink configured the run
    # is byte-identical to a build without the layer.
    from sartsolver_tpu.obs import trace as obs_trace
    from sartsolver_tpu.obs.run import RunTelemetry

    telem = RunTelemetry.from_cli(args.metrics_out)

    # Graceful preemption (docs/RESILIENCE.md §5): SIGTERM/SIGINT sets a
    # stop flag honored at frame-group boundaries (drain, flush, exit 4);
    # a second signal aborts immediately. Installed before the (possibly
    # long) ingest so a preemption during it is at least remembered —
    # the first boundary check then stops the run before any solve.
    shutdown.install()

    # imported unconditionally (it is jax-only, already paid above): the
    # pod fault-tolerance seams — identity, liveness, barriers, the
    # PodBarrierTimeout exit mapping — also serve FAKE pods, where N
    # single-process workers coordinate over SART_POD_BARRIER_DIR
    # without --multihost (docs/RESILIENCE.md §11)
    from sartsolver_tpu.parallel import multihost as mh

    if args.multihost:
        try:
            mh.initialize()
        except RetriesExhausted as err:
            # the coordinator never came up within the retry budget; this
            # is infrastructure, not user input — distinct exit code so a
            # scheduler can tell "fix the flags" from "requeue the job"
            print(f"Unrecoverable after retries: {err}", file=sys.stderr)
            shutdown.uninstall()
            return EXIT_INFRASTRUCTURE
    # Pod identity + liveness (docs/RESILIENCE.md §11): publish k/n into
    # the env so jax-free consumers (the heartbeat's host= field, the
    # site@i SART_FAULT qualifier) agree with the runtime, and start
    # refreshing this host's file-mode liveness beacon from the beacon
    # stream. Both are no-ops on plain single-process runs.
    mh.export_pod_identity()
    mh.install_pod_liveness()

    from sartsolver_tpu.config import (
        SartInputError, parse_time_intervals,
    )
    from sartsolver_tpu.io import hdf5files as hf
    from sartsolver_tpu.io.image import CompositeImage
    from sartsolver_tpu.io.laplacian_io import read_laplacian
    from sartsolver_tpu.io.solution import SolutionWriter
    from sartsolver_tpu.io.voxelgrid import make_voxel_grid
    from sartsolver_tpu.ops.laplacian import make_laplacian
    from sartsolver_tpu.parallel.mesh import make_mesh
    from sartsolver_tpu.parallel.sharded import DistributedSARTSolver

    from sartsolver_tpu.utils.timing import PhaseTimer

    # Created before the ingest so availability events anywhere in the
    # run (a watchdog fire during solver construction included) land in
    # the end-of-run accounting.
    summary = RunSummary()

    # Live introspection (docs/OBSERVABILITY.md §9): the flight ring taps
    # the beacon stream (in-memory, bounded), SIGUSR1 dumps a status
    # snapshot to stderr + <output>.status.json, and every abnormal exit
    # path flushes a flight bundle to <output>.crash.json — including the
    # watchdog's stage-3 os._exit, via its crash hook (the one abort no
    # `finally` survives). Output is byte-identical unless signaled or
    # aborting. Primary-process-only where files are written: the bundle
    # and status paths are shared, like the other sinks.
    from sartsolver_tpu.obs import flight as obs_flight

    obs_flight.install()
    flight_primary = (not args.multihost) or mh.is_primary()
    status_path = obs_flight.default_status_path(args.output_file)
    bundle_path = obs_flight.default_bundle_path(args.output_file)
    # the SIGUSR1 handler installs on EVERY process: SIGUSR1's default
    # disposition is terminate, so a handler-less worker would die to a
    # status poke (pkill -USR1 across a pod must be a read-only query,
    # never fatal). Writes are atomic renames and the record carries the
    # pid — whichever process was poked last owns the file's content.
    # The handler snapshots with blocking=False: it runs between
    # bytecodes of the main thread, which may be mid-record_frame
    # holding the very metric lock a blocking snapshot would wait on
    # forever (obs/flight.py; the signal-under-lock drill pins this).
    prev_usr1 = obs_flight.install_status_handler(status_path)
    from sartsolver_tpu.utils import locking

    if locking.debug_enabled():
        # drill/triage mode (docs/RESILIENCE.md runbook): every named
        # lock feeds the acquisition-order detector — real per-acquire
        # cost, so an armed production run should be a conscious choice
        print("sartsolve: SART_LOCK_DEBUG=1 — lock-order detector armed "
              "(acquisition-order cycles raise LockOrderViolation; hold "
              "times land in lock_hold_seconds)", file=sys.stderr)
    abort = {"reason": None}
    if flight_primary:
        watchdog.set_crash_hook(
            lambda reason: obs_flight.write_crash_bundle(
                bundle_path, reason, summary
            )
        )

    def note_event(message: str) -> None:
        # availability events land in ALL THREE accountings: the printed
        # end-of-run summary, the typed telemetry records, and the
        # flight ring (the crash bundle's recent-event tail)
        summary.record_event(message)
        telem.record_event(message)
        obs_flight.record_event("event", message)

    # Hang watchdog (docs/RESILIENCE.md §6): armed by
    # SART_WATCHDOG_TIMEOUT and scoped to the WHOLE expensive body —
    # RTM ingest, solver construction (device staging beacons), frame
    # loop and the writer drain on exit — a hang anywhere must escalate
    # (FRAME_FAILED inside the frame loop, a resumable exit-3 abort
    # elsewhere), never wedge. No-op when disabled.
    wd = watchdog.Watchdog.from_env(on_event=note_event)
    if wd is not None:
        wd.start()

    timer = PhaseTimer(registry=telem.registry)
    _t = _time.perf_counter()

    def _mark(phase: str) -> None:
        nonlocal _t
        now = _time.perf_counter()
        timer.add(phase, now - _t)
        _t = now

    try:
        time_intervals = parse_time_intervals(args.time_range)

        # ---- pre-flight validation gate (main.cpp:30-59) -----------------
        geometry_record = None
        if getattr(args, "geometry", None):
            from sartsolver_tpu.operators.geometry import load_geometry

            geometry_record = load_geometry(args.geometry)
        matrix_files, image_files = hf.categorize_input_files(args.input_files)
        rtm_name = args.raytransfer_name
        if geometry_record is not None:
            # matrix-free gate: image files only, cameras exactly the
            # geometry record's (same set equality the RTM gate checks)
            if matrix_files:
                raise SartInputError(
                    "--geometry replaces the ray-transfer matrix files; "
                    f"drop {', '.join(matrix_files)} from the inputs "
                    "(image files only)."
                )
            hf.check_group_attribute_consistency(image_files, "image", ["wavelength"])
            sorted_image_files = hf.sort_image_files(image_files)
            camera_names = list(sorted_image_files)
            cams = set(geometry_record.camera_names)
            if cams != set(camera_names):
                raise SartInputError(
                    "Geometry/image mismatch: geometry cameras "
                    f"{sorted(cams)} vs image files {camera_names}."
                )
            sorted_matrix_files = {}
            npixel, nvoxel = geometry_record.npixel, geometry_record.nvoxel
            rtm_frame_masks = geometry_record.frame_masks()
        else:
            hf.check_group_attribute_consistency(matrix_files, f"rtm/{rtm_name}", ["wavelength"])
            hf.check_group_attribute_consistency(matrix_files, "rtm/voxel_map", ["nx", "ny", "nz"])
            sorted_matrix_files = hf.sort_rtm_files(matrix_files)
            hf.check_rtm_frame_consistency(sorted_matrix_files)
            hf.check_rtm_voxel_consistency(sorted_matrix_files)
            hf.check_group_attribute_consistency(image_files, "image", ["wavelength"])
            sorted_image_files = hf.sort_image_files(image_files)
            camera_names = list(sorted_image_files)
            hf.check_rtm_image_consistency(
                sorted_matrix_files, sorted_image_files, rtm_name, args.wavelength_threshold
            )
            npixel, nvoxel = hf.get_total_rtm_size(sorted_matrix_files)
            rtm_frame_masks = hf.read_rtm_frame_masks(sorted_matrix_files)

        # Resume compatibility is checkable from metadata alone — fail now,
        # before the (potentially tens-of-GB) RTM ingest, not after. In a
        # multi-host run only process 0 reads the file (it may be on a
        # filesystem the other hosts can't see) and broadcasts its view so
        # every process skips the same frames.
        from sartsolver_tpu.io.solution import read_resume_state

        resume_state = None
        if args.resume:
            resume_error = None
            if (not args.multihost) or mh.is_primary():
                try:
                    resume_state = read_resume_state(
                        args.output_file, camera_names, nvoxel
                    )
                except (SartInputError, OSError, KeyError) as err:
                    if not args.multihost:
                        raise
                    # broadcast the failure so every process exits cleanly
                    # instead of the others hanging in the collective
                    resume_error = str(err) or type(err).__name__
            if args.multihost:
                resume_state = mh.broadcast_resume_state(
                    resume_state, nvoxel, error=resume_error
                )

        _mark("validate + index inputs")

        # Numerical-integrity layer (docs/RESILIENCE.md §8): flag or env.
        # configure() switches the ingest-side digests (library code has
        # no opts object at stripe level); the in-solve ABFT check rides
        # SolverOptions.integrity below.
        integrity_on = bool(args.integrity) or integ_mod.env_enabled()
        integ_mod.configure(integrity_on)
        sdc_policy = (
            integ_mod.SdcEscalation(on_event=note_event)
            if integrity_on else None
        )
        opts = solver_options(args, schedule_stride=schedule_stride(args),
                              integrity=integrity_on)
        if args.use_cpu:
            jax.config.update("jax_enable_x64", True)
            devices = jax.devices("cpu")
        else:
            devices = jax.devices()

        lap = None
        if args.laplacian_file:
            rows, cols, vals = read_laplacian(args.laplacian_file, nvoxel)
            lap = make_laplacian(rows, cols, vals, dtype=opts.dtype)

        # Explicit-flag mesh shape (None, None = auto-select below).
        explicit_mesh = not (args.pixel_shards is None and args.voxel_shards is None)
        if explicit_mesh:
            n_vox = args.voxel_shards or 1
            if args.pixel_shards is not None:
                n_pix = args.pixel_shards
            elif args.rtm_dtype == "int8":
                # int8 fuses on either layout now, but voxel-major stays
                # the better default for it (one psum per iteration vs one
                # per panel, and int8's fatter panels favor fewer shards):
                # --voxel_shards alone means a voxel-major mesh, not
                # fill-the-devices-with-pixel-shards
                n_pix = 1
            else:
                n_pix = max(len(devices) // n_vox, 1)

        # auto-fused path: compile self-test before the ingest, skipped
        # when fusion is ineligible anyway (fp64 --use_cpu profile,
        # explicitly sharded pixel axis — no compile wasted). A kernel
        # that does not compile on this TPU raises with the compiler's
        # message instead of demoting the run to the two-matmul path.
        if not args.use_cpu and geometry_record is None:
            from sartsolver_tpu.ops.fused_sweep import resolve_fused_auto

            resolve_fused_auto(
                opts, pixel_sharded=explicit_mesh and n_pix > 1
            )

        if not explicit_mesh:
            if geometry_record is not None:
                # the implicit operator shards rays along pixels only
                # (its back-projection psums over the one pixel axis) —
                # pixel-major is the only eligible auto layout
                n_pix, n_vox = len(devices), 1
            else:
                from sartsolver_tpu.parallel.mesh import choose_mesh_shape

                n_pix, n_vox = choose_mesh_shape(
                    len(devices), npixel, nvoxel, opts, args.batch_frames
                )
        if (not args.use_cpu and opts.rtm_dtype == "int8"
                and geometry_record is None):
            # preflight BEFORE the (possibly tens-of-GB, two-pass) ingest:
            # everything here is knowable from sizes + flags. Pixel-sharded
            # meshes are no longer refused — the panel-psum scan fuses
            # there too — and the probe runs AFTER the auto mesh choice so
            # it checks the per-shard block of the mesh the run will
            # actually build (choose_mesh_shape's pixel-major fallback
            # included), not a hypothetical voxel-major layout.
            from sartsolver_tpu.models.sart import INT8_MAX_CONTRACTION
            from sartsolver_tpu.parallel.mesh import (
                sharded_fused_would_engage,
            )

            if max(npixel, nvoxel) > INT8_MAX_CONTRACTION:
                raise SartInputError(
                    f"Argument rtm_dtype='int8': RTM extent "
                    f"{max(npixel, nvoxel)} exceeds the int32-"
                    f"accumulation bound {INT8_MAX_CONTRACTION}; use "
                    "fp32/bfloat16 storage."
                )
            if opts.os_subsets == 1 and not sharded_fused_would_engage(
                # the ordered-subsets cycle dequantizes int8 subset
                # blocks itself (ops/fused_sweep.py os_subset_rows), so
                # int8 + os_subsets > 1 does not need the fused sweep
                opts, npixel, nvoxel, n_pix, max(n_vox, 1),
                args.batch_frames or 1,
            ):
                raise SartInputError(
                    "Argument rtm_dtype='int8' needs the fused sweep, "
                    "which cannot engage here (fused_sweep="
                    f"'{opts.fused_sweep}', backend "
                    f"'{jax.default_backend()}', or shape ineligible "
                    f"on the {n_pix}x{max(n_vox, 1)} mesh); pass "
                    "--fused_sweep interpret (slow, any backend) or "
                    "use fp32/bfloat16 storage."
                )
        if n_pix * n_vox < len(devices) and args.pixel_shards is None:
            print(
                f"Warning: {len(devices)} devices visible but the "
                f"{n_pix}x{n_vox} mesh uses only {n_pix * n_vox}; pick "
                "--voxel_shards dividing the device count (or set "
                "--pixel_shards) to use them all.",
                file=sys.stderr,
            )
        mesh = make_mesh(n_pix, n_vox, devices=devices[: n_pix * n_vox])

        # One-line run provenance at startup (VERDICT r4 next #6): the
        # mesh/layout/dtype/fused decision in plain sight, not inferred
        # from --timing after the fact. (engaged= stays in --timing — the
        # fused kernel's actual compile state is only known post-trace.)
        if (not args.multihost) or mh.is_primary():
            layout = ("single-device" if n_pix == 1 and n_vox == 1 else
                      "voxel-major" if n_pix == 1 else
                      "pixel-major" if n_vox == 1 else "2-D")
            print(
                f"solver: mesh={n_pix}x{n_vox} (pixels x voxels, {layout}) "
                f"backend={jax.default_backend()} "
                f"rtm_dtype={opts.rtm_dtype or opts.dtype} "
                f"compute={opts.dtype} "
                f"fused_sweep={args.fused_sweep}->{opts.fused_sweep} "
                f"sparse_rtm={opts.sparse_rtm} "
                f"lowrank_rtm={opts.lowrank_rtm} "
                f"os_subsets={opts.os_subsets} momentum={opts.momentum} "
                f"processes={jax.process_count()} "
                f"devices={len(devices)} kind={devices[0].device_kind}"
            )
        # artifact provenance: the same decision line, as typed meta. The
        # solver-variant fields (os_subsets/momentum/logarithmic) also ride
        # every frame record (obs/run.py) so `sartsolve metrics --diff`
        # can refuse to compare convergence behavior across variants.
        telem.set_run_info(
            backend=jax.default_backend(),
            mesh=f"{n_pix}x{n_vox}",
            processes=int(jax.process_count()),
            rtm_dtype=str(opts.rtm_dtype or opts.dtype),
            compute_dtype=str(opts.dtype),
            fused_sweep=str(opts.fused_sweep),
            logarithmic=bool(args.logarithmic),
            os_subsets=int(opts.os_subsets),
            momentum=str(opts.momentum),
        )
        # convergence-accelerator gauges (docs/OBSERVABILITY.md): the
        # variant in the metric snapshot, next to the iterations_to_
        # converge trajectory it changes
        telem.registry.gauge("solver_os_subsets").set(
            float(opts.os_subsets)
        )
        telem.registry.gauge("solver_momentum_on").set(
            1.0 if opts.momentum != "off" else 0.0
        )

        # ---- data model (main.cpp:70-86) ---------------------------------
        # Multi-host: each process reads and caches only its own devices'
        # pixel rows of every frame (the reference's per-rank measurement
        # slice, image.cpp:282-321) — as a list of runs when its row
        # blocks are non-contiguous — and the solver stages the
        # measurement sharded. The local and replicated staging paths
        # issue different collectives, so the choice is made from the full
        # device grid (deterministic, unanimous across processes); only a
        # degenerate process owning nothing but padding rows forces the
        # replicated fallback.
        use_local = args.multihost and mh.all_processes_local_capable(
            mesh, npixel
        )
        pixel_runs = (
            mh.process_pixel_runs(mesh, npixel) if use_local
            else [(0, npixel)]
        )
        composite_image = CompositeImage(
            sorted_image_files, rtm_frame_masks, time_intervals,
            npixel, max_cache_size=args.max_cached_frames,
            pixel_runs=pixel_runs,
        )

        # Striped chunked ingest on every path (the reference's per-rank
        # read, main.cpp:76-86): each process streams only the row chunks
        # its devices hold straight into device memory, so peak host
        # allocation is one bounded chunk — never the full matrix
        # (raytransfer.cpp:49 parity; see multihost.read_and_shard_rtm).
        from sartsolver_tpu.parallel.multihost import read_and_shard_rtm

        rtm_scale = None
        # Integrity: host-side rho/lambda accumulation during the chunked
        # ingest, verified against the device-computed stats right after
        # the upload (docs/RESILIENCE.md §8). Single-process only — a
        # pod's processes each see only their own rows/columns; they rely
        # on the stripe digests plus the periodic resident re-audit.
        ingest_stats = (
            integ_mod.IngestStats(npixel, nvoxel)
            if integrity_on and not args.multihost else None
        )
        # Block-sparse layer (docs/PERFORMANCE.md §10): the tile-occupancy
        # pass rides the chunked ingest — the accumulator is fed the same
        # storage-rounded (double-read/CRC32-verified) pieces the
        # integrity layer sums, so the index covers the packed matrix at
        # no extra read. Single-process + pixel-major only; 'auto'
        # declines elsewhere (an explicit threshold fails loudly in the
        # solver/make_tile_stats with the actual reason).
        # the one shared gate (multihost.sparse_tile_stats_or_decline):
        # explicit thresholds fail loudly BEFORE the ingest with the
        # actual reason, 'auto' warns and runs dense, voxel-sharded
        # meshes defer to the solver ctor's refusal
        from sartsolver_tpu.parallel.multihost import (
            sparse_tile_stats_or_decline,
        )

        # Factored path (docs/PERFORMANCE.md §12): the whole-matrix host
        # read + thresholded-core split + randomized SVD happen behind
        # the shared gate — 'auto' declines loudly to the dense branch
        # (lowrank_op stays None), an explicit rank fails before
        # anything is staged.
        lowrank_op = None
        if geometry_record is None and opts.lowrank_rank() is not None:
            from sartsolver_tpu.parallel.multihost import (
                lowrank_operator_or_decline,
            )

            with obs_trace.span("ingest.lowrank_factorize",
                                npixel=npixel, nvoxel=nvoxel):
                lowrank_op = lowrank_operator_or_decline(
                    opts, sorted_matrix_files, rtm_name, npixel,
                    nvoxel, n_vox, laplacian=lap,
                )

        if geometry_record is not None:
            # matrix-free path: no RTM ingest at all — the operator's
            # whole device state is the [npixel, 6] ray table
            from sartsolver_tpu.operators.implicit import ImplicitOperator

            operator = ImplicitOperator(geometry_record)
            tile_occ = None
            ingest_stats = None
            with obs_trace.span("ingest.geometry", npixel=npixel,
                                nvoxel=nvoxel):
                solver = DistributedSARTSolver(
                    operator=operator, opts=opts, mesh=mesh
                )
            print(
                f"implicit: ray table resident "
                f"({operator.resident_nbytes()} bytes; a materialized "
                f"RTM would stage "
                f"{npixel * nvoxel * np.dtype(np.float32).itemsize})"
            )
        elif lowrank_op is not None:
            tile_occ = None
            ingest_stats = None
            with obs_trace.span("ingest.lowrank", npixel=npixel,
                                nvoxel=nvoxel, rank=lowrank_op.rank):
                solver = DistributedSARTSolver(
                    operator=lowrank_op, opts=opts, mesh=mesh
                )
            occ = lowrank_op.tile_occupancy()
            print(
                f"lowrank: factored operator H ~= S + U V^T "
                f"rank={lowrank_op.rank} (core occupancy "
                f"{occ.occupancy_fraction():.3f}, eps {occ.epsilon:g}, "
                f"digest {occ.digest:#010x}; the residual fill costs "
                f"{lowrank_op.rank}*(npixel+nvoxel) MACs per projection "
                f"instead of npixel*nvoxel)"
            )
        else:
            tile_stats = sparse_tile_stats_or_decline(
                opts, mesh, npixel, nvoxel, n_vox
            )
            with obs_trace.span("ingest.rtm", npixel=npixel,
                                nvoxel=nvoxel):
                if opts.rtm_dtype == "int8":
                    # two-pass ingest: quantize fp32 chunks host-side
                    # into int8 device buffers, so peak device footprint
                    # is 1 byte/element — a matrix that only fits as
                    # int8 loads (multihost.py)
                    from sartsolver_tpu.parallel.multihost import (
                        read_and_quantize_rtm,
                    )

                    rtm, rtm_scale = read_and_quantize_rtm(
                        sorted_matrix_files, rtm_name, npixel, nvoxel,
                        mesh, ingest_stats=ingest_stats,
                        tile_stats=tile_stats,
                    )
                else:
                    rtm = read_and_shard_rtm(
                        sorted_matrix_files, rtm_name, npixel, nvoxel,
                        mesh, dtype=opts.rtm_dtype or opts.dtype,
                        serialize=(args.multihost
                                   and not args.parallel_read),
                        ingest_stats=ingest_stats,
                        tile_stats=tile_stats,
                    )
                tile_occ = (
                    tile_stats.occupancy(opts.sparse_epsilon())
                    if tile_stats is not None else None
                )
                solver = DistributedSARTSolver(
                    rtm, lap, opts=opts, mesh=mesh, npixel=npixel,
                    nvoxel=nvoxel, rtm_scale=rtm_scale,
                    tile_occupancy=tile_occ,
                )
        if tile_occ is not None:
            # this is the INDEX, known at ingest; whether the sweep
            # engaged it is a trace-time decision — --timing's engaged=
            # line (FUSED_ENGAGEMENT) is the post-compile provenance
            print(
                f"sparse: tile occupancy "
                f"{tile_occ.occupancy_fraction():.3f} "
                f"(threshold {tile_occ.threshold:g}, eps "
                f"{tile_occ.epsilon:g}, digest {tile_occ.digest:#010x}; "
                "engagement in --timing)"
            )
        if ingest_stats is not None:
            if (opts.sparse_epsilon() or 0) > 0 and tile_occ is not None \
                    and not tile_occ.mask.all():
                # a nonzero sparse threshold zeroes dropped tiles ON
                # DEVICE after ingest, so host sums (which include the
                # dropped entries) can no longer match the device's
                # rho/lambda — comparing them would quarantine a healthy
                # run with a bogus corruption diagnosis. The stripe
                # digests, in-solve ABFT and the resident re-audit (all
                # self-consistent with the thresholded operator) still
                # run.
                print(
                    "Warning: post-upload ray-stats verification "
                    "skipped: sparse_rtm threshold zeroed tiles after "
                    "the host sums were accumulated (stripe digests, "
                    "in-solve ABFT and the resident re-audit still "
                    "cover the matrix).", file=sys.stderr,
                )
            else:
                # post-upload verification: the device's rho/lambda must
                # match the host sums the ingest just accumulated — a
                # mismatch means the staging DMA or on-device layout
                # corrupted the matrix, and every solve it would serve
                # is poisoned: quarantine now
                issues = solver.verify_ray_stats(ingest_stats)
                if issues:
                    sdc_policy.resident_failure(
                        "post-upload ray-stats verification: "
                        + "; ".join(issues)
                    )
        # operator-kind provenance, resolved only now (gates may have
        # declined): rides the meta record AND every frame record, so
        # `sartsolve metrics --diff` refuses to compare solve-ms /
        # convergence behavior across operator backends (the solver-
        # variant contract) even on sliced artifacts
        telem.set_run_info(
            operator=("implicit" if geometry_record is not None else
                      "lowrank" if lowrank_op is not None else
                      "tileskip" if tile_occ is not None else "dense"),
        )
        _mark("ingest RTM + upload")

        if geometry_record is not None:
            from sartsolver_tpu.operators.geometry import GeometryVoxelGrid

            grid = GeometryVoxelGrid(geometry_record)
        else:
            grid = make_voxel_grid(
                next(iter(sorted_matrix_files.values())), "rtm/voxel_map"
            )

        written_times = (
            resume_state.times if resume_state is not None else np.empty(0)
        )

        def already_written(t: float) -> bool:
            return bool(np.any(np.abs(written_times - t) <= 1e-12))

        # ---- frame loop (main.cpp:131-140) -------------------------------
        import contextlib

        profiler_ctx = (
            jax.profiler.trace(args.profile_dir) if args.profile_dir
            else contextlib.nullcontext()
        )

        from sartsolver_tpu.frameloop import FrameLoop
        from sartsolver_tpu.utils.prefetch import FramePrefetcher

        # Multi-host: every process runs the (collective) frame loop, only
        # process 0 writes output and prints (the reference's rank-0 gating,
        # main.cpp:134-137).
        primary = (not args.multihost) or mh.is_primary()

        class _NullWriter:
            def add(self, *a, **kw):
                pass

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                pass

        from sartsolver_tpu.utils.asyncwriter import AsyncSolutionWriter

        # Each queued entry holds (or lazily fetches) one nvoxel fp64 row,
        # so the queue depth bounds host memory the writer may hold behind
        # a slow filesystem; SART_WRITER_QUEUE=1 makes the solve loop run
        # lockstep with the writer (the SIGTERM drills use that to pin
        # group-boundary stops deterministically).
        import os as _os

        writer_queue = max(1, int(_os.environ.get("SART_WRITER_QUEUE", "16")))
        writer_ctx = (
            # write off-thread so periodic HDF5 flushes never stall the
            # solve loop (read / solve / write pipeline)
            AsyncSolutionWriter(SolutionWriter(
                args.output_file, camera_names, nvoxel,
                max_cache_size=args.max_cached_solutions,
                # pass the already-read state so the file is inspected once
                resume=resume_state if resume_state is not None else False,
            ), max_pending=writer_queue)
            if primary else _NullWriter()
        )

        # Per-frame failure isolation (docs/RESILIENCE.md): a frame whose
        # ingest retries are exhausted arrives as a FrameFailure item, and
        # a frame whose staging/solve dispatch fails with a recoverable
        # error is caught below — either way the frame is recorded as a
        # FAILED status row (-3, zeros) and the run continues. Off with
        # --fail_fast; multihost runs always fail fast: each process reads
        # frames independently, so a per-process skip would desynchronize
        # the collective frame loop (the in-solve divergence guard stays
        # active there — it runs inside the jitted program, identically on
        # every process).
        isolate = not (args.fail_fast or args.multihost)

        def stop_now() -> bool:
            """Group-boundary stop poll. Multihost (and file-mode fake
            pods): a one-int agreement so every process stops at the SAME
            boundary (the scheduler's signals land at different instants;
            parallel/multihost.agree_stop). A fake-pod worker honoring
            only its local flag would drain while its peers kept
            arriving at stride barriers — a graceful preemption must
            never present as a dead-peer timeout."""
            local = shutdown.stop_requested()
            if args.multihost or mh.pod_identity()[1] > 1:
                return mh.agree_stop(local)
            return local

        with profiler_ctx, writer_ctx as writer, FramePrefetcher(
            composite_image, isolate_failures=isolate
        ) as frames:
            if resume_state is not None:
                frames = (
                    item for item in frames if not already_written(item[1])
                )

            # Periodic resident re-audit (--integrity, RESILIENCE.md §8):
            # recompute rho/lambda from the device-resident RTM every N
            # completed frames and compare bit-for-bit to the upload-time
            # snapshot — resident bit rot between solves is caught even
            # when no frame's ABFT check has tripped yet. A mismatch is
            # unrecoverable by construction: quarantine (exit 3).
            reaudit_every = int(
                _os.environ.get("SART_INTEGRITY_REAUDIT", "64")
            )
            audit_state = {"since": 0}

            def integ_tick(n_frames: int) -> None:
                if sdc_policy is None or reaudit_every <= 0:
                    return
                audit_state["since"] += n_frames
                if audit_state["since"] < reaudit_every:
                    return
                audit_state["since"] = 0
                issues = solver.reaudit_ray_stats()
                if issues:
                    sdc_policy.resident_failure(
                        "resident re-audit: " + "; ".join(issues)
                    )

            loop = FrameLoop(
                writer=writer, nvoxel=nvoxel, summary=summary, telem=telem,
                timer=timer, stop_poll=stop_now, isolate=isolate,
                primary=primary, multihost=args.multihost,
                sdc_policy=sdc_policy, on_event=note_event,
                heartbeat=lambda: watchdog.beacon(watchdog.PHASE_FRAME_DONE),
                reaudit=integ_tick, step_trace=bool(args.profile_dir),
            )

            # Solutions stay ON DEVICE on every path: one packed scalar
            # fetch per solve group, solution transfer deferred to the
            # async writer's thread, warm starts chained device-side
            # (parallel/sharded.DeviceSolveResult — a synchronous
            # host<->device round trip cost ~68 ms on the remote-attached
            # chip of 2026-07, vs ~9 ms of device work for a warm-started
            # frame). In multi-host runs the packed scalars are replicated
            # (each process reads its local copy) and the solution is
            # asynchronously all-gathered for process 0's writer; all
            # collectives stay on the main thread.

            def solve_cold(stack, f0, warm):
                # independent frames (--no_guess): no seed, no carry
                return solver.solve_batch(stack, local=use_local,
                                          device_result=True)

            def solve_warm(stack, f0, warm):
                if args.chain_frames == 1:
                    # one frame a program: the serial warm-start program
                    return solver.solve_batch(
                        stack, None if f0 is None else f0[None, :],
                        local=use_local, device_result=True, warm=warm,
                    )
                # K frames a program (lax.scan carrying the previous
                # solution): ONE packed scalar fetch per chain, per-frame
                # results identical to serial dispatch (solve_chain docs)
                return solver.solve_chain(stack, f0=f0, warm=warm,
                                          local=use_local)

            def run_scheduled(K):
                """Continuous batching (docs/PERFORMANCE.md §8): K lanes,
                convergence-aware retirement + backfill every
                schedule_stride iterations — sustained occupancy at the
                fixed batch shape instead of run-to-slowest padding. On a
                device OOM the scheduler hands its un-emitted frames back
                and the classic grouped loop (whose halving ladder CAN
                shrink the batch — the scheduler's fixed lane count
                cannot without recompiling) finishes the run at half
                size. Returns True when a stop request ended the run
                early."""
                from sartsolver_tpu.sched import ContinuousBatcher

                def sched_result(ftime, cam_times, status, iterations,
                                 convergence, fetcher, per_frame_ms):
                    writer.add(fetcher, status, ftime, cam_times,
                               iterations=iterations)
                    summary.record_status(status, ftime)
                    telem.record_frame(ftime, status, iterations,
                                       convergence, per_frame_ms, "sched")
                    watchdog.beacon(watchdog.PHASE_FRAME_DONE)
                    integ_tick(1)
                    # detail=: inside the frame-loop phase, like the
                    # grouped loop's pipelined-wall rows
                    timer.add("solve sched (pipelined wall)",
                              per_frame_ms / 1e3, detail=True)
                    if primary:
                        print(f"Processed in: {per_frame_ms} ms "
                              f"(continuous batch of {K} lanes; "
                              f"{iterations} iterations)")

                # In-solve checkpointing + per-stride pod rendezvous
                # (docs/RESILIENCE.md §11). File-mode pods barrier every
                # stride (the fake-pod lockstep contract — and the chaos
                # harness's dead-peer detection point); real pods already
                # rendezvous inside the sharded dispatch collectives, so
                # no extra per-stride sync is imposed there.
                from sartsolver_tpu.resilience import podckpt
                from sartsolver_tpu.sched.scheduler import (
                    sched_held_ftimes,
                )

                pod_idx, pod_count = mh.pod_identity()
                pod_markers = bool(
                    _os.environ.get("SART_TEST_POD_MARKERS")
                )
                ckpt_base = (_os.environ.get("SART_SOLVE_CKPT_FILE")
                             or f"{args.output_file}.solveckpt")
                store = None
                ckpt_sink = None
                if args.solve_ckpt_stride:
                    store = podckpt.SolveCheckpointStore(
                        ckpt_base, pod_idx, pod_count
                    )
                    ckpt_sink = store.save
                stride_barrier = None
                if pod_count > 1 and _os.environ.get(
                        "SART_POD_BARRIER_DIR"):
                    def stride_barrier(serial: int) -> None:
                        if pod_markers:
                            # chaos-harness kill window: mid-stride
                            sys.stderr.write(
                                f"SART_POD_POINT stride serial={serial}\n"
                            )
                            sys.stderr.flush()
                        mh.pod_barrier(f"stride.{serial}")

                # Elastic resume: the newest checkpoint serial that is
                # consistent across EVERY pod host AND not ahead of this
                # output file (the killed run's writer may not have
                # flushed the snapshot's rows — fall back a stride; a
                # torn host file drops out of the intersection the same
                # way). No usable checkpoint degrades to the plain
                # --resume path: rows in the file are skipped and
                # everything else recomputes.
                restore = None
                restore_serial = None
                W = 0 if resume_state is None else len(resume_state.times)
                if args.resume and store is not None:
                    newest = podckpt.newest_consistent_serial(
                        ckpt_base, pod_count
                    )
                    for serial in sorted(store.serials(), reverse=True):
                        if newest is None or serial > newest:
                            continue
                        snap = store.load(serial)
                        if (snap is None
                                or int(snap.get("lanes", -1)) != K
                                or int(snap["next_emit"]) > W):
                            continue
                        restore, restore_serial = snap, serial
                        break
                    if pod_count > 1 and _os.environ.get(
                            "SART_POD_BARRIER_DIR"):
                        # lockstep pins the PICK, not just the files: a
                        # host whose writer lost its unflushed tail picks
                        # an older serial than its peers, and divergent
                        # picks desync every later stride barrier. Agree
                        # on the minimum usable serial — next_emit is
                        # monotone in serial, so the minimum satisfies
                        # every host's next_emit <= rows-on-disk bound.
                        # Any host with NO usable checkpoint (-1) drags
                        # the whole pod to the plain-resume path.
                        picks = mh.pod_barrier(
                            "resume_pick",
                            payload=(-1 if restore_serial is None
                                     else int(restore_serial)),
                        )
                        agreed = min(
                            (-1 if row is None else int(row)
                             for row in picks),
                            default=-1,
                        )
                        if agreed < 0:
                            restore, restore_serial = None, None
                        elif agreed != restore_serial:
                            restore = store.load(agreed)
                            restore_serial = (
                                None if restore is None else agreed
                            )
                    if restore is not None:
                        telem.registry.counter(
                            "solve_ckpt_resumed_total"
                        ).inc()
                        note_event(
                            f"resumed from solve checkpoint serial "
                            f"{restore_serial} ({W} row(s) already "
                            "written)"
                        )
                        if pod_markers:
                            sys.stderr.write(
                                f"SART_POD_POINT resume "
                                f"serial={restore_serial}\n"
                            )
                            sys.stderr.flush()

                batcher = ContinuousBatcher(
                    solver, lanes=K,
                    on_result=sched_result, on_failed=loop.record_failed,
                    stop_check=stop_now, on_event=loop.announce,
                    isolate=isolate, integrity_policy=sdc_policy,
                    step_trace=bool(args.profile_dir),
                    ckpt_stride=args.solve_ckpt_stride or None,
                    ckpt_sink=ckpt_sink, stride_barrier=stride_barrier,
                    restore=restore,
                    restore_emitted=W if restore is not None else 0,
                )
                # ONE shared iterator: the OOM fallback must continue the
                # same stream the batcher was draining, not re-iterate the
                # prefetcher — a fresh FramePrefetcher generator would
                # block forever on the already-consumed end sentinel
                if restore is not None:
                    # frames the checkpoint holds in-flight (restored
                    # lanes, awaiting-recompute slots, buffered results)
                    # must not re-enter from the stream — they would be
                    # solved twice and the reorder buffer would jam
                    held = np.asarray(
                        sched_held_ftimes(restore, W), np.float64
                    )
                    frames_iter = iter(
                        item for item in frames
                        if not (held.size and np.any(
                            np.abs(held - item[1]) <= 1e-12))
                    )
                else:
                    frames_iter = iter(frames)
                stats = batcher.run(frames_iter)
                if stats.leftover is None:
                    return stats.interrupted
                import itertools

                return loop.run(
                    itertools.chain(stats.leftover, frames_iter),
                    max(K // 2, 1), solve_cold,
                ) or stats.interrupted

            if not args.no_guess:
                # the reference's loop: each frame warm-starts from the
                # previous solution, --chain_frames of them a program; a
                # chain's tail pads are copies of its last real frame
                interrupted = loop.run(
                    frames, args.chain_frames, solve_warm, warm_start=True,
                    f0=(resume_state.last_solution
                        if resume_state is not None else None),
                )
            elif (args.batch_frames > 1 and not args.no_continuous_batching
                  and not args.multihost):
                interrupted = run_scheduled(args.batch_frames)
            else:
                # classic run-to-slowest grouping; multihost keeps it
                # because the scheduler's per-stride retire/backfill
                # decisions would have to be replicated across processes
                # in lockstep with per-process prefetch streams — the same
                # desynchronization hazard that forces frame-level
                # fail-fast there
                interrupted = loop.run(frames, args.batch_frames, solve_cold)

        _mark("frame loop (solve + prefetch + flush)")
        if primary:
            import h5py

            # fresh beacon: the voxel-map write gets its own watchdog
            # budget instead of inheriting whatever silence preceded it
            watchdog.beacon(watchdog.PHASE_FLUSH)
            with obs_trace.span("flush.voxel_map"):
                with h5py.File(args.output_file, "a") as f:
                    has_grid = "voxel_map" in f
                if not has_grid:  # resumed runs already wrote the grid
                    grid.write_hdf5(args.output_file, "voxel_map")
        _mark("write voxel map")
        if args.timing and primary:
            print(timer.summary())
            # provenance: which sweep path the solver actually compiled
            # (VERDICT r3 next #4 — a silent degrade to the two-matmul
            # path must be visible in the artifact, not only on stderr)
            from sartsolver_tpu.models.sart import FUSED_ENGAGEMENT

            print(f"fused sweep: requested={args.fused_sweep} "
                  f"resolved={opts.fused_sweep} "
                  f"engaged={FUSED_ENGAGEMENT['last'] or 'not traced'}")
        # End-of-run resilience accounting: printed whenever anything
        # degraded or recovered (always under --timing), and a run with
        # FAILED/DIVERGED frames exits with the partial code so a
        # scheduler can see "completed, but look at the statuses" without
        # opening the file.
        # Only a stop that actually truncated the run (a boundary check
        # broke out of the frame loop) exits 4. A signal that lands after
        # the last boundary check can only mean every frame completed —
        # reporting THAT as "interrupted, requeue me" would make a
        # scheduler re-run a finished job (and mask EXIT_PARTIAL).
        if primary and (summary.n_failed or summary.had_retries()
                        or summary.events or interrupted or args.timing):
            print(summary.format())
        # End-of-run pod rendezvous (file-mode pods): a worker that died
        # after its last frame — or between the frame loop and here —
        # must surface as PodBarrierTimeout naming the host, not leave
        # the survivors' summaries silently unaccounted.
        if _os.environ.get("SART_POD_BARRIER_DIR") \
                and mh.pod_identity()[1] > 1:
            mh.pod_barrier("finalize")
        # Telemetry artifact fan-out: every process reaches this point on
        # the completed path (interrupted runs included — the stop
        # boundary is agreed collectively), so the multi-host counter
        # aggregation — ONE host allgather, and only when a sink is
        # configured (sink config must be pod-uniform, like the rest of
        # the command line) — is safe here and only here; exception
        # paths write a local-only artifact from the finally block
        # below. With no sink configured this is a true no-op. The
        # allgather is deadline-bounded (the end-of-run collective is a
        # pod rendezvous like any other).
        telem.finalize(
            summary, multihost=args.multihost, primary=primary,
            allgather=(mh.deadline_allgather() if args.multihost
                       else None),
        )
        if interrupted:
            # graceful preemption stop (docs/RESILIENCE.md §5): the
            # in-flight group drained, the writer flushed, the voxel map
            # is in place — the file is a consistent prefix of the run
            sig = shutdown.stop_signal() or "a stop request"
            # exit 4 is an abnormal exit too: the bundle records where
            # the run was truncated, for triage before the requeue
            abort["reason"] = f"interrupted by {sig} (exit 4)"
            if primary:
                print(
                    f"Interrupted by {sig}: {summary.n_frames} frame(s) "
                    "written; the output file is resumable (--resume).",
                    file=sys.stderr,
                )
            return EXIT_INTERRUPTED
        if summary.n_failed:
            return summary.exit_code()
    except RetriesExhausted as err:
        # a retried site (RTM ingest, multihost init, a non-isolated
        # frame read) failed permanently: infrastructure, not input
        abort["reason"] = f"retries exhausted: {err}"
        print(f"Unrecoverable after retries: {err}", file=sys.stderr)
        return EXIT_INFRASTRUCTURE
    except WatchdogTimeout as err:
        # the hang watchdog interrupted a stall that per-frame isolation
        # could not absorb (--fail_fast, multihost, or a stall outside
        # the frame scope): the process is saved, the run is not —
        # infrastructure exit, file resumable
        abort["reason"] = f"watchdog abort: {err}"
        print(f"Aborted by the hang watchdog: {err}", file=sys.stderr)
        return EXIT_INFRASTRUCTURE
    except mh.PodBarrierTimeout as err:
        # a pod rendezvous gave up on a dead or wedged peer: every
        # survivor converges to the same infrastructure exit within the
        # barrier deadline, and the crash bundle (written in the finally
        # below from abort["reason"]) names the missing host — the
        # runbook's first question (docs/RESILIENCE.md §11)
        abort["reason"] = f"pod barrier failure: {err}"
        print(f"Aborted at a pod barrier: {err}", file=sys.stderr)
        return EXIT_INFRASTRUCTURE
    except OutputWriteError as err:
        # a solution-file flush failed mid-run; the file is resumable up
        # to its last committed flush
        abort["reason"] = f"output write failure: {err}"
        print(err, file=sys.stderr)
        return EXIT_INFRASTRUCTURE
    except integ_mod.PersistentCorruptionError as err:
        # the integrity layer quarantined the session: corruption that a
        # recompute cannot clear (resident matrix / staged state). The
        # quarantine event is already in the telemetry; the file is
        # resumable up to its last committed flush — requeue on healthy
        # hardware with --resume (docs/RESILIENCE.md §8)
        abort["reason"] = f"SDC quarantine: {err}"
        print(f"Quarantined: {err}", file=sys.stderr)
        return EXIT_INFRASTRUCTURE
    except DeferredWriteError as err:
        # the async writer latched an infrastructure-class failure (a
        # wedged lazy device fetch interrupted by the watchdog, an
        # I/O error outside the flush path); an internal bug as the
        # cause still tracebacks loudly
        if isinstance(err.__cause__, RECOVERABLE_FRAME_ERRORS):
            abort["reason"] = f"async writer failure: {err}"
            print(f"Asynchronous writer failed: {err}", file=sys.stderr)
            return EXIT_INFRASTRUCTURE
        raise
    except KeyError as err:
        # h5py raises KeyError for missing datasets/attributes in otherwise
        # openable files; surface it as the fail-fast message + exit 1 the
        # reference contract promises.
        print(f"Missing dataset or attribute in input files: {err}", file=sys.stderr)
        return 1
    except (SartInputError, OSError) as err:
        # Only *input* problems get the reference's polite message + exit(1)
        # (hdf5files.cpp contract); an internal ValueError is a bug and
        # tracebacks loudly instead of being swallowed.
        print(err, file=sys.stderr)
        return 1
    except BaseException as err:
        # anything else is an internal bug (or a second-signal abort):
        # it tracebacks exactly as before, but the flight bundle still
        # lands first — an OOM-ladder exhaustion under --fail_fast or an
        # unhandled dispatch error is triaged from the same file as the
        # named abort paths
        abort["reason"] = f"unhandled {type(err).__name__}: {err}"
        raise
    finally:
        # Crash bundle (docs/OBSERVABILITY.md §9): one JSON file with
        # the status snapshot, the flight ring's recent-event tail and
        # the partial-run accounting, flushed on every abnormal exit
        # path that reaches this frame (the watchdog's stage-3 os._exit
        # bypasses finally — its crash hook wrote the bundle already).
        if flight_primary and abort["reason"] is not None:
            obs_flight.write_crash_bundle(
                bundle_path, abort["reason"], summary
            )
        watchdog.set_crash_hook(None)
        obs_flight.uninstall_status_handler(prev_usr1)
        obs_flight.uninstall()
        if wd is not None:
            wd.stop()
        shutdown.uninstall()
        # Best-effort artifact on abort paths (collective-free: a peer
        # that died never reaches an allgather). No-op when finalize
        # already ran above or no sink is configured; in multihost only
        # process 0 writes (the sinks are its paths).
        try:
            write_here = (not args.multihost) or mh.is_primary()
        except Exception:  # a torn runtime must not mask the real error
            write_here = False
        if write_here:
            telem.finalize_local(summary)

    return 0


if __name__ == "__main__":
    sys.exit(main())

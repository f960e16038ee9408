"""End-to-end CLI integration: synthetic multi-camera fixtures -> sartsolve
-> solution file contents (SURVEY §4.4)."""

import numpy as np
import h5py
import pytest

from sartsolver_tpu.cli import main

import fixtures as fx


@pytest.fixture
def world(tmp_path):
    return fx.write_world(tmp_path, with_laplacian=True)


def run_cli(paths, *extra):
    args = [
        "-o", paths["output"],
        paths["rtm_a1"], paths["rtm_a2"], paths["rtm_b"],
        paths["img_a"], paths["img_b"],
        "--use_cpu",  # fp64 parity profile on the CPU backend
        "-m", "300", "-c", "1e-6",
        *extra,
    ]
    return main(args)


def test_end_to_end_reconstruction(world, capsys):
    paths, H, f_true, times, scales = world
    assert run_cli(paths) == 0

    out = capsys.readouterr().out
    assert out.count("Processed in:") == len(times)

    with h5py.File(paths["output"], "r") as f:
        value = f["solution/value"][:]
        status = f["solution/status"][:]
        t = f["solution/time"][:]
        assert value.shape == (len(times), fx.NVOXEL)
        assert set(f["solution"]) >= {"value", "time", "status",
                                      f"time_{fx.CAM_A}", f"time_{fx.CAM_B}"}
        # voxel map round-trip (main.cpp:143)
        assert "voxel_map" in f
        assert f["voxel_map/value"].shape[0] == fx.NVOXEL

    # reconstructions reproduce the measurements
    for i, s in enumerate(scales):
        fitted = H @ value[i]
        np.testing.assert_allclose(fitted, H @ (f_true * s), rtol=0.05)
    np.testing.assert_allclose(t, times, atol=0.05)
    assert (status == 0).all()


def test_no_guess_flag(world):
    paths, *_ = world
    assert run_cli(paths, "--no_guess") == 0
    with h5py.File(paths["output"], "r") as f:
        assert f["solution/value"].shape[0] > 0


def test_relaxation_decay_flag(world, capsys):
    paths, H, f_true, times, scales = world
    assert run_cli(paths, "-R", "0.9", "--relaxation_decay", "0.9") == 0
    with h5py.File(paths["output"], "r") as f:
        value = f["solution/value"][:]
    # heavily damped but still reconstructing (geometric schedule shrinks
    # late steps; quality bound is looser than the fixed-alpha test's)
    for i, s in enumerate(scales):
        np.testing.assert_allclose(H @ value[i], H @ (f_true * s), rtol=0.25)
    capsys.readouterr()
    # out-of-range decay takes the polite validation exit
    with pytest.raises(SystemExit):
        run_cli(paths, "--relaxation_decay", "0")
    assert "relaxation_decay" in capsys.readouterr().err


def test_logarithmic_mode(world):
    paths, H, f_true, times, scales = world
    assert run_cli(paths, "-L") == 0
    with h5py.File(paths["output"], "r") as f:
        value = f["solution/value"][:]
    fitted = H @ value[0]
    np.testing.assert_allclose(fitted, H @ (f_true * scales[0]), rtol=0.05)


def test_laplacian_flag(world):
    paths, *_ = world
    assert run_cli(paths, "-l", paths["laplacian"], "-b", "0.001") == 0


def test_time_range_flag(world):
    paths, H, f_true, times, scales = world
    assert run_cli(paths, "-t", "0.15:0.35") == 0
    with h5py.File(paths["output"], "r") as f:
        assert f["solution/value"].shape[0] == 2


def test_pixel_shards_flag(world):
    """Sharded run (4 virtual CPU devices) produces the same solutions."""
    paths, H, f_true, times, scales = world
    assert run_cli(paths) == 0
    with h5py.File(paths["output"], "r") as f:
        ref = f["solution/value"][:]
    assert run_cli(paths, "--pixel_shards", "4") == 0
    with h5py.File(paths["output"], "r") as f:
        sharded = f["solution/value"][:]
    np.testing.assert_allclose(sharded, ref, rtol=1e-8, atol=1e-10)


def test_batch_frames_flag(world):
    """Batched no-guess run matches the serial no-guess run exactly."""
    paths, H, f_true, times, scales = world
    assert run_cli(paths, "--no_guess") == 0
    with h5py.File(paths["output"], "r") as f:
        serial = f["solution/value"][:]
        serial_status = f["solution/status"][:]
    assert run_cli(paths, "--no_guess", "--batch_frames", "3") == 0
    with h5py.File(paths["output"], "r") as f:
        batched = f["solution/value"][:]
        batched_status = f["solution/status"][:]
        t = f["solution/time"][:]
    np.testing.assert_allclose(batched, serial, rtol=1e-9, atol=1e-12)
    np.testing.assert_array_equal(batched_status, serial_status)
    assert t.shape[0] == len(times)  # partial final batch flushed too


def test_chain_frames_matches_serial(world, capsys):
    """--chain_frames K (device-chained warm-start loop, the default) must
    write byte-identical results to serial dispatch (--chain_frames 1):
    same statuses, same iteration counts, same solutions. K=3 over 4
    frames also exercises the padded tail (one duplicated frame whose
    output is discarded)."""
    paths, *_ = world
    assert run_cli(paths, "--chain_frames", "1") == 0
    with h5py.File(paths["output"], "r") as f:
        val_serial = f["solution/value"][:]
        st_serial = f["solution/status"][:]
        it_serial = f["solution/iterations"][:]

    assert run_cli(paths, "--chain_frames", "3") == 0
    out = capsys.readouterr().out
    assert "average over chain" in out
    with h5py.File(paths["output"], "r") as f:
        val_chain = f["solution/value"][:]
        st_chain = f["solution/status"][:]
        it_chain = f["solution/iterations"][:]

    np.testing.assert_array_equal(st_chain, st_serial)
    np.testing.assert_array_equal(it_chain, it_serial)
    np.testing.assert_allclose(val_chain, val_serial, rtol=1e-12, atol=1e-14)


def test_chain_frames_validation(world, capsys):
    paths, *_ = world
    with pytest.raises(SystemExit):
        main(["-o", paths["output"], paths["rtm_a1"], paths["img_a"],
              "--chain_frames", "0"])
    assert "chain_frames" in capsys.readouterr().err


def test_batch_frames_requires_no_guess(world):
    paths, *_ = world
    with pytest.raises(SystemExit):
        run_cli(paths, "--batch_frames", "2")


def test_invalid_args_exit_1(world, capsys):
    paths, *_ = world
    with pytest.raises(SystemExit):
        main(["-R", "2.0", paths["rtm_b"], paths["img_b"]])
    with pytest.raises(SystemExit):
        main(["-m", "0", paths["rtm_b"], paths["img_b"]])
    with pytest.raises(SystemExit):
        main([paths["rtm_b"]])  # fewer than two inputs


def test_bad_input_file_returns_1(world, tmp_path, capsys):
    paths, *_ = world
    bad = str(tmp_path / "bad.h5")
    with h5py.File(bad, "w") as f:
        f.create_group("mystery")
    assert main([bad, paths["img_a"]]) == 1
    assert "neither an RTM" in capsys.readouterr().err


def test_resume_appends_remaining_frames(world, capsys):
    """--resume skips already-written frames, warm-starts from the last
    solution and appends — the final file matches a single full run."""
    paths, H, f_true, times, scales = world

    # reference: one uninterrupted run
    ref_out = paths["output"] + ".ref.h5"
    assert run_cli({**paths, "output": ref_out}) == 0
    with h5py.File(ref_out, "r") as f:
        ref_value = f["solution/value"][:]
        ref_times = f["solution/time"][:]

    # "interrupted" run: only the first half of the time range...
    assert run_cli(paths, "-t", "0.05:0.25") == 0
    with h5py.File(paths["output"], "r") as f:
        assert f["solution/value"].shape[0] == 2
    # ...then resume over the full range
    capsys.readouterr()
    assert run_cli(paths, "--resume") == 0
    assert capsys.readouterr().out.count("Processed in:") == len(times) - 2

    with h5py.File(paths["output"], "r") as f:
        value = f["solution/value"][:]
        t = f["solution/time"][:]
        assert "voxel_map" in f
    np.testing.assert_allclose(t, ref_times)
    np.testing.assert_allclose(value, ref_value, rtol=1e-10, atol=1e-13)

    # resuming a complete file is a no-op, not an error or a duplicate
    capsys.readouterr()
    assert run_cli(paths, "--resume") == 0
    assert capsys.readouterr().out.count("Processed in:") == 0
    with h5py.File(paths["output"], "r") as f:
        assert f["solution/value"].shape[0] == len(times)


def test_resume_rejects_incompatible_file(world, capsys):
    paths, *_ = world
    with h5py.File(paths["output"], "w") as f:
        f.create_dataset("solution/value", data=np.zeros((1, 3)),
                         maxshape=(None, 3), chunks=(1, 3))
        f.create_dataset("solution/time", data=np.asarray([0.1]),
                         maxshape=(None,), chunks=(1,))
        f.create_dataset("solution/status", data=np.asarray([0], np.int32),
                         maxshape=(None,), chunks=(1,))
    assert run_cli(paths, "--resume") == 1
    assert "Cannot resume" in capsys.readouterr().err


def test_resume_truncates_torn_flush(world, capsys):
    """A crash mid-flush leaves per-frame datasets at different lengths;
    resume must trust only fully-written frames and redo the torn one."""
    paths, H, f_true, times, scales = world
    assert run_cli(paths) == 0
    with h5py.File(paths["output"], "r+") as f:
        # simulate _update killed after extending time/status but before
        # writing the value rows for a 5th frame
        f["solution/time"].resize((5,))
        f["solution/time"][4] = 0.9
        f["solution/status"].resize((5,))
    capsys.readouterr()
    assert run_cli(paths, "--resume") == 0
    assert capsys.readouterr().out.count("Processed in:") == 0  # 4 complete
    with h5py.File(paths["output"], "r") as f:
        assert f["solution/time"].shape == (4,)  # torn tail truncated
        assert f["solution/status"].shape == (4,)
        assert f["solution/value"].shape[0] == 4


def test_resume_recreates_torn_first_flush(world):
    """status is created last; a file without it is a torn first flush and
    must be rebuilt from scratch rather than resumed or rejected."""
    paths, H, f_true, times, scales = world
    with h5py.File(paths["output"], "w") as f:
        f.create_dataset("solution/value", data=np.zeros((1, fx.NVOXEL)),
                         maxshape=(None, fx.NVOXEL), chunks=(1, fx.NVOXEL))
    assert run_cli(paths, "--resume") == 0
    with h5py.File(paths["output"], "r") as f:
        assert f["solution/value"].shape[0] == len(times)
        assert f["solution/status"].shape[0] == len(times)


def test_timing_flag_prints_summary(world, capsys):
    paths, *_ = world
    assert run_cli(paths, "--timing") == 0
    out = capsys.readouterr().out
    assert "timing summary" in out
    for phase in ("validate + index inputs", "ingest RTM + upload",
                  "solve chain",  # the default device-chained frame loop
                  "write voxel map"):
        assert phase in out
    # sweep-path provenance in the artifact (VERDICT r3 next #4); on the
    # CPU test backend 'auto' resolves to the two-matmul path
    assert "fused sweep: requested=auto" in out
    assert "engaged=off" in out


def test_provenance_line_printed(world, capsys):
    """Every run prints one startup provenance line with the chosen
    mesh/layout/dtype/fused decision (VERDICT r4 next #6) — no --timing
    needed."""
    paths, *_ = world
    assert run_cli(paths) == 0
    out = capsys.readouterr().out
    line = next(l for l in out.splitlines() if l.startswith("solver: "))
    # the conftest forces 8 virtual CPU devices; the fp64 profile's auto
    # mesh is the reference-style pixel-major row-block layout over all 8
    assert "mesh=8x1" in line
    assert "pixel-major" in line
    assert "compute=float64" in line  # --use_cpu parity profile
    assert "fused_sweep=auto->" in line
    assert "processes=1" in line


def test_internal_error_propagates(world, monkeypatch):
    """VERDICT r1 #7: the polite exit-1 funnel is for input errors only —
    an internal bug (e.g. a shape error in the solver) must traceback."""
    paths, *_ = world
    from sartsolver_tpu.parallel import sharded

    def boom(self, *a, **kw):
        raise ValueError("internal solver bug")

    monkeypatch.setattr(sharded.DistributedSARTSolver, "solve_batch", boom)
    monkeypatch.setattr(sharded.DistributedSARTSolver, "solve_chain", boom)
    with pytest.raises(ValueError, match="internal solver bug"):
        run_cli(paths)


def test_multihost_resume_appends(world, capsys):
    """--multihost --resume single-process: process-0 read + broadcast path."""
    paths, *_ = world
    assert run_cli(paths, "-t", "0:0.25", "-m", "50") == 0
    n_first = capsys.readouterr().out.count("Processed in:")
    assert run_cli(paths, "--resume", "--multihost", "-m", "50") == 0
    n_second = capsys.readouterr().out.count("Processed in:")
    assert n_first >= 1 and n_second >= 1
    import h5py
    with h5py.File(paths["output"], "r") as f:
        assert f["solution/value"].shape[0] == n_first + n_second


def test_mesh_flag_error_is_polite(world, capsys):
    """--pixel_shards beyond the device count is a flag mistake: message +
    exit(1), not a traceback (SartInputError funnel)."""
    paths, *_ = world
    assert run_cli(paths, "--pixel_shards", "4096") == 1
    assert "devices" in capsys.readouterr().err


def test_multihost_resume_error_raises_everywhere(world):
    """A broken resume file in --multihost must fail the job cleanly (the
    error is broadcast before any process can hang in the collective)."""
    paths, *_ = world
    from sartsolver_tpu.config import SartInputError
    from sartsolver_tpu.parallel import multihost as mh

    with pytest.raises(SartInputError, match="corrupt"):
        mh.broadcast_resume_state(None, 16, error="resume file corrupt")


def test_int8_flag_combinations(world, tmp_path, capsys):
    """--rtm_dtype int8 combos: polite exit 1 where it cannot run (CPU
    'auto' backend, --use_cpu), end-to-end solve with
    --fused_sweep interpret — including under --multihost, which is now
    allowed (voxel-major meshes stripe ingest by column, round 3)."""
    paths, H, f_true, times, scales = world
    out = str(tmp_path / "i8.h5")
    inputs = [paths["rtm_a1"], paths["rtm_a2"], paths["rtm_b"],
              paths["img_a"], paths["img_b"]]
    with pytest.raises(SystemExit):
        main(["-o", out, "--rtm_dtype", "int8", "--use_cpu", *inputs])
    capsys.readouterr()
    # auto on the CPU backend cannot engage the fused sweep -> polite error
    assert main(["-o", out, "--rtm_dtype", "int8", *inputs]) == 1
    assert "fused sweep" in capsys.readouterr().err
    # interpret mode runs anywhere, multihost flag included
    assert main(["-o", out, "--rtm_dtype", "int8", "--multihost",
                 "--fused_sweep", "interpret", "-m", "100", *inputs]) == 0
    with h5py.File(out) as f:
        v = f["solution/value"][...]
    for i in range(v.shape[0]):
        fit = H @ v[i]
        ref = H @ (f_true * scales[i])
        assert np.abs(fit - ref).max() / np.abs(ref).max() < 0.05


def test_pipelined_chain_drains_inflight_group_on_error(world, monkeypatch):
    """A frame-read failure mid-run must not discard the already-solved
    in-flight group: the pipelined loop (round 4) defers group k's write
    until group k+1 dispatches, so the error path has to drain it. Here
    the prefetcher yields the first 2 frames (= one full chain of 2) and
    then dies; the run exits 1, but those 2 frames are in the file."""
    import sartsolver_tpu.cli as cli_mod
    from sartsolver_tpu.utils.prefetch import FramePrefetcher

    paths, H, f_true, times, scales = world
    orig_iter = FramePrefetcher.__iter__

    def broken_iter(self):
        it = orig_iter(self)
        count = 0
        for item in it:
            if count >= 2:
                raise OSError("simulated frame-read failure")
            count += 1
            yield item

    monkeypatch.setattr(FramePrefetcher, "__iter__", broken_iter)
    rc = run_cli(paths, "--chain_frames", "2")
    assert rc == 1  # OSError -> polite input-error exit
    with h5py.File(paths["output"], "r") as f:
        assert f["solution/value"].shape[0] == 2
        assert (f["solution/status"][:] == 0).all()


def test_minimal_cache_sizes_match_default(world):
    """--max_cached_frames 1 --max_cached_solutions 1 forces the image
    block cache to evict every frame and the writer to flush per frame
    (solution.cpp:55 cadence at its minimum) — outputs must be identical
    to the default cache sizes."""
    paths, H, f_true, times, scales = world
    assert run_cli(paths) == 0
    with h5py.File(paths["output"], "r") as f:
        ref_value = f["solution/value"][:]
        ref_status = f["solution/status"][:]

    assert run_cli(paths, "--max_cached_frames", "1",
                   "--max_cached_solutions", "1") == 0
    with h5py.File(paths["output"], "r") as f:
        np.testing.assert_array_equal(f["solution/value"][:], ref_value)
        np.testing.assert_array_equal(f["solution/status"][:], ref_status)


def test_debug_nans_clean_run(world):
    """--debug_nans on a healthy solve completes normally (the flag turns
    on jax_debug_nans; a clean pipeline must not trip it)."""
    import jax

    paths, *_ = world
    try:
        assert run_cli(paths, "--debug_nans", "-m", "20") == 0
    finally:
        jax.config.update("jax_debug_nans", False)  # don't leak to other tests


def test_profile_dir_writes_trace(world, tmp_path):
    """--profile_dir wraps the frame loop in jax.profiler.trace and leaves
    a trace artifact behind."""
    import os

    paths, *_ = world
    prof = str(tmp_path / "prof")
    assert run_cli(paths, "--profile_dir", prof, "-m", "10") == 0
    found = []
    for root, _dirs, files in os.walk(prof):
        found += files
    assert found, "profiler trace directory is empty"


def test_timing_summary_printed(world, capsys):
    paths, *_ = world
    assert run_cli(paths, "--timing", "-m", "10") == 0
    out = capsys.readouterr().out
    assert "timing summary" in out
    assert "ingest RTM + upload" in out
    # fused-path provenance line (VERDICT r3 next #4); the fp64 CPU
    # profile never fuses, so 'off'/'not traced' variants are acceptable
    assert "fused sweep: requested=" in out


_SART_ENV = ("SART_SCHEDULE_STRIDE", "SART_SPARSE_RTM", "SART_LOWRANK_RTM",
             "SART_INTEGRITY")


def _parsed(flags):
    """Flags parsed and validated as ``main`` does (``_validate`` gives
    --sparse_rtm and --lowrank_rtm their env-or-off defaults); two
    placeholder inputs satisfy the input-count check."""
    from sartsolver_tpu.cli import _validate, build_parser

    args = build_parser().parse_args(list(flags) + ["rtm.h5", "img.h5"])
    _validate(args)
    return args


@pytest.mark.parametrize("config", ["rtm-wide-fp32", "rtm-wide-fp32-4chip"])
def test_solver_options_matches_benchmark_mirror(config, monkeypatch):
    """The benchmark builds its SolverOptions with its own mirror of the
    CLI; for each benchmark configuration's flags the mirror must equal
    what the CLI builds."""
    import json
    import os

    from benchmark.traffic.warm_series import _solver_options
    from sartsolver_tpu.cli import schedule_stride, solver_options

    for name in _SART_ENV:
        monkeypatch.delenv(name, raising=False)
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "benchmark", "configs",
                           f"{config}.json")) as f:
        flags = json.load(f)["flags"]
    args = _parsed(flags)
    cli_opts = solver_options(args, schedule_stride=schedule_stride(args),
                              integrity=bool(args.integrity))
    assert _solver_options(args) == cli_opts


@pytest.mark.parametrize("flags,profile", [
    ((), "device"),
    (("--use_cpu", "-L", "-m", "40"), "cpu"),
    (("--rtm_dtype", "bfloat16", "--sparse_rtm", "auto",
      "--schedule_stride", "4"), "device"),
])
def test_solver_options_profiles(flags, profile, monkeypatch):
    """One builder for both profiles: --use_cpu gives the fp64 CPU-parity
    options, the device profile forwards the storage and sparsity flags;
    the stride and integrity arguments land as given."""
    from sartsolver_tpu.cli import schedule_stride, solver_options
    from sartsolver_tpu.config import SolverOptions

    for name in _SART_ENV:
        monkeypatch.delenv(name, raising=False)
    args = _parsed(flags)
    opts = solver_options(args, schedule_stride=schedule_stride(args),
                          integrity=True)
    assert opts.integrity is True
    assert opts.schedule_stride == (args.schedule_stride or 16)
    assert opts.max_iterations == args.max_iterations
    assert opts.logarithmic == args.logarithmic
    if profile == "cpu":
        assert opts == SolverOptions.cpu_parity(
            logarithmic=True, max_iterations=40, integrity=True)
    else:
        assert opts.dtype == "float32"
        assert opts.rtm_dtype == args.rtm_dtype
        assert opts.sparse_rtm == args.sparse_rtm
        assert opts.lowrank_rtm == "off"

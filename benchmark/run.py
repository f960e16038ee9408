"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and, with
``--trace 1``, ``breakdown``); the numbers compared against their limits are
the last lines of standard error and the result's last key, ``checks``.
Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for, or where the program is not beside the benchmark.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import spec  # noqa: E402

EXIT_NO_CHIP = 3
EXIT_NO_PROGRAM = 4
OUT_DIR = os.path.join(spec.BENCH_DIR, ".out")
CACHE_DIR = os.path.join(spec.BENCH_DIR, ".jax_cache")


class CompileCounter:
    """Counts programs lowered (new to this process, compiled or loaded
    from the persistent cache) and backend compiles since ``reset``. JAX's
    listeners are process-wide and stay registered, so there is one."""

    _instance = None

    def __new__(cls):
        if cls._instance is None:
            from jax import monitoring

            cls._instance = super().__new__(cls)
            cls._instance.count = cls._instance.backend = 0
            monitoring.register_event_duration_secs_listener(
                cls._instance._on)
        return cls._instance

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.count += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.backend += 1

    def reset(self):
        self.count = self.backend = 0


class Runtime:
    """What a traffic driver gets from the harness."""

    def __init__(self, seed, seconds, trace, devices, extra_flags=(),
                 memory_stats=True, trace_dir=None):
        import jax

        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices = devices
        self.extra_flags = list(extra_flags)
        self.memory_stats = memory_stats
        self.t_start = T_START
        self.clock = time.perf_counter
        self.compiles = CompileCounter()
        self.trace_dir = trace_dir
        self._annotation = jax.profiler.TraceAnnotation

    def span(self, name):
        return self._annotation(name)

    @contextlib.contextmanager
    def profile(self):
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(self.trace_dir)
        try:
            with self.span("bench.traced"):
                yield
        finally:
            jax.profiler.stop_trace()


def _configure_jax():
    """The persistent compile cache lives inside the checkout at a fixed
    path, for the program too, and caches every program it compiles."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


class RunRecord:
    """What a metric reader gets: the cell, the driver's outcome, the
    reduced trace (``None`` without ``--trace 1``) and the device."""

    def __init__(self, cell, outcome, trace, device_kind, n_chips):
        self.cell, self.outcome, self.trace = cell, outcome, trace
        self.device_kind, self.n_chips = device_kind, n_chips

    def frames(self, phase):
        return [f for f in self.outcome.frames if f.phase == phase]


def run_cell(cell, seed, seconds, trace, *, devices, device_kind,
             extra_flags=(), memory_stats=True, diagnose=False):
    """Drive the cell once and check it; returns the result dict."""
    from benchmark import check, profile_trace, world

    trace_dir = os.path.join(OUT_DIR, "trace", cell.name)
    rt = Runtime(seed, seconds, trace, devices, extra_flags, memory_stats,
                 trace_dir)
    outcome = cell.driver().run(cell, rt)
    if outcome.exhausted:
        raise RuntimeError(
            f"the series of {len(outcome.inputs)} frames ran out before "
            f"{seconds} s: raise rate_cap_frames_per_s")
    print(f"compiles_in_window={outcome.compiles_in_window} "
          f"backend_compiles_in_window={outcome.backend_compiles_in_window} "
          f"mesh={outcome.mesh[0]}x{outcome.mesh[1]} "
          f"engaged={outcome.engaged} chain_frames={outcome.chain_frames}",
          file=sys.stderr)
    reduced = None
    if outcome.traced:
        reduced = profile_trace.load(profile_trace.find_xplane(trace_dir))
    gc.collect()

    # the reference, on a matrix of its own after the program's is freed
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    n = len(devices)
    P, V = int(cell.config["npixel"]), int(cell.config["nvoxel"])
    shape = (-(-P // (8 * n)) * 8 * n, -(-V // 128) * 128)
    sharding = NamedSharding(Mesh(devices, ("rows",)),
                             PartitionSpec("rows", None))
    H = world.build_rtm(cell.config, seed, sharding, shape)
    checks, info = check.run(cell, outcome, H, seed, diagnose)
    H.delete()
    del H

    rec = RunRecord(cell, outcome, reduced, device_kind, n)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = cell.reader(m["name"]).read(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    correct = all(v <= lim for _, v, lim in checks)
    window = rec.frames("window")
    result = {
        "correct": bool(correct),
        "attempted": len(window),
        "failed": sum(f.status not in check.FAILED_OK for f in window),
        "metrics": metrics,
        "device": {"platform": devices[0].platform, "kind": device_kind,
                   "count": n, "memory_peak_bytes": outcome.peak_bytes},
    }
    if reduced is not None and reduced.chips:
        busy = [profile_trace.busy_ns(reduced, c) for c in reduced.chips]
        result["device"]["busy_s"] = sum(busy) / max(len(busy), 1) * 1e-9
        result["device"]["window_s"] = reduced.window_s
        result["breakdown"] = {
            "device_ops": profile_trace.top_ops(reduced),
            "idle_gaps": profile_trace.idle_gaps(reduced),
        }
    print("checked frames [index, iterations, solution gap, fitted gap, "
          "stop reading]: " + json.dumps(info.pop("frames")), file=sys.stderr)
    print("carried run [index, gap]: " + json.dumps(info.pop("carried")),
          file=sys.stderr)
    result["check_info"] = info
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}
    return result


def main(argv=None, *, require_accelerator=True, root=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)

    cell = spec.load_cell(a.workload, root or ROOT)
    if not os.path.isdir(os.path.join(root or ROOT, "sartsolver_tpu")):
        print("run.py: the program (sartsolver_tpu/) is not beside the "
              "benchmark", file=sys.stderr)
        return EXIT_NO_PROGRAM
    jax = _configure_jax()
    devices = jax.devices()
    platform = devices[0].platform
    if require_accelerator and (platform != "tpu" or len(devices) < cell.chips):
        print(f"run.py: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {platform} device(s)", file=sys.stderr)
        return EXIT_NO_CHIP
    devices = devices[: cell.chips]
    kind = devices[0].device_kind
    result = run_cell(cell, a.seed, a.seconds, bool(a.trace),
                      devices=devices, device_kind=kind,
                      memory_stats=devices[0].memory_stats() is not None)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

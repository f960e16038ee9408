"""Readings that set a cell's limits: many seeds of the program, or of its
control, in one process (set-up is long; the benchmark's own runs never
run this).

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3 \
        --seconds 4 [--control]

``--control`` switches on the program's own lower-precision path, RTM
storage in bfloat16 (``--rtm_dtype bfloat16``): the configuration states
float32, and bfloat16 storage is the step that would tempt a later PR (it
halves the bytes a sweep reads). ``--loose-stop`` runs the program with a
convergence tolerance ten times the stated one (``-c 1e-4`` for
``1e-5``): the stop rule's own control, an early stop that would pass for
a faster one. The reference keeps the stated tolerance. Prints one JSON
line per seed with the numbers compared and the run's iteration counts.
"""

import argparse
import json
import sys

import run as harness  # benchmark/run.py, this script's neighbour

CONTROL_FLAGS = ("--rtm_dtype", "bfloat16")


def loose_stop_flags(cell) -> tuple:
    return ("-c", repr(10 * float(cell.config["solver"]["conv_tolerance"])))


def main(argv=None, *, require_accelerator=True):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--control", action="store_true")
    p.add_argument("--loose-stop", action="store_true")
    p.add_argument("--diagnose", action="store_true",
                   help="also replay each checked frame from a start "
                        "moved by 1e-7, to see the replay's own spread")
    a = p.parse_args(argv)
    cell = harness.spec.load_cell(a.workload)
    jax = harness._configure_jax()
    devices = jax.devices()
    if require_accelerator and (devices[0].platform != "tpu"
                                or len(devices) < cell.chips):
        print(f"calibrate.py: {cell.name} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return harness.EXIT_NO_CHIP
    devices = devices[: cell.chips]
    flags = CONTROL_FLAGS if a.control else ()
    if a.loose_stop:
        flags += loose_stop_flags(cell)
    for seed in (int(s) for s in a.seeds.split(",")):
        r = harness.run_cell(
            cell, seed, a.seconds, False, devices=devices,
            device_kind=devices[0].device_kind,
            extra_flags=flags,
            memory_stats=devices[0].memory_stats() is not None,
            diagnose=a.diagnose)
        print(json.dumps({
            "seed": seed, "control": a.control, "loose_stop": a.loose_stop,
            "correct": r["correct"],
            "checks": {k: v["value"] for k, v in r["checks"].items()},
            "frames_per_s": r["metrics"].get("frames_per_s", {}).get("value"),
            "check_info": r["check_info"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

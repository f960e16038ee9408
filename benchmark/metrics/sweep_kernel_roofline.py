"""sweep_kernel_roofline (device trace): the least time the traced chains'
SART iterations could take on the chip, as ``sweep_roofline`` charges it
(benchmark/work.py: one read of the chip's RTM block and 4 P V flops per
iteration, plus one read per Eq. 4 guess), over the busiest chip's device
time of the operations the program traced under the named scope
``sart.sweep`` (whatever implements the sweep), clipped to the traced
segment, x 100. Nothing to read where the program has no such scope."""

from benchmark import work, xplane_meta

SCOPE = "sart.sweep"


def least_seconds(run) -> float:
    """The traced frames' least time, as ``sweep_roofline`` charges it."""
    o, cfg = run.outcome, run.cell.config
    P, V = int(cfg["npixel"]), int(cfg["nvoxel"])
    peak = work.peaks(run.device_kind)
    it_bytes, it_flops = work.sweep_iteration(P, V, o.mesh, o.storage_dtype)
    g_bytes, g_flops = work.guess(P, V, o.mesh, o.storage_dtype)
    least = 0.0
    for f in run.frames("trace"):
        least += f.iterations * work.least_seconds(it_bytes, it_flops, peak)
        if f.index == 0:
            least += work.least_seconds(g_bytes, g_flops, peak)
    return least


def reading(ops, window, least_s):
    """The metric from the busiest chip's ``[(start_ns, end_ns, tf_op)]``,
    the traced window and the least time."""
    ns = xplane_meta.scope_ns(ops, window, SCOPE)
    if ns <= 0:
        return None
    return 100.0 * least_s / (ns * 1e-9)


def read(run):
    traced = xplane_meta.traced_ops(run)
    if traced is None:
        return None
    return reading(*traced, least_seconds(run))

"""sweep_roofline (device trace): the least time the traced chains' SART
iterations could take on the chip (benchmark/work.py: one read of the chip's
RTM block and 4 P V flops per iteration, plus one read per Eq. 4 guess),
over the busiest chip's busy time in the traced segment, x 100."""

from benchmark import profile_trace, work


def read(run):
    data = run.trace
    if data is None or not data.chips:
        return None
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
    busy = max(profile_trace.busy_ns(data, c) for c in data.chips) * 1e-9
    return 100.0 * least / busy if busy > 0 else None

"""exchange_ms_per_iteration (device trace): on the busiest chip, the
device time of the operations the program traced under the named scope
``sart.exchange`` (its cross-chip collectives: the forward projection's
voxel-axis psum, the convergence and guess psums, the Laplacian's halo
all_gather, wherever they nest), clipped to the traced segment, over the
summed iterations of the traced chains' frames, in ms. Nothing to read
where no operation carries the scope: one chip, or a program without it."""

from benchmark import profile_trace, xplane_meta

SCOPE = "sart.exchange"


def reading(ops, window, frames):
    """The metric from the busiest chip's ``[(start_ns, end_ns, tf_op)]``,
    the traced window and the traced frames."""
    iterations = sum(f.iterations for f in frames)
    hits = [(s, e) for s, e, path in ops if f"/{SCOPE}/" in path]
    ns = profile_trace.total(profile_trace.clip(profile_trace.merge(hits),
                                                *window))
    if ns <= 0 or iterations <= 0:
        return None
    return ns * 1e-6 / iterations


def read(run):
    traced = xplane_meta.traced_ops(run)
    if traced is None:
        return None
    return reading(*traced, run.frames("trace"))

"""laplacian_ms_per_iteration (device trace): on the busiest chip, the
device time of the operations the program traced under the named scope
``sart.laplacian`` (the penalty: the COO gather and scatter-add, its halo
exchange, the beta scale), clipped to the traced segment, over the summed
iterations of the traced chains' frames, in ms. Nothing to read where the
program has no such scope."""

from benchmark import xplane_meta

SCOPE = "sart.laplacian"


def reading(ops, window, frames):
    """The metric from the busiest chip's ``[(start_ns, end_ns, tf_op)]``,
    the traced window and the traced frames."""
    iterations = sum(f.iterations for f in frames)
    ns = xplane_meta.scope_ns(ops, window, SCOPE)
    if ns <= 0 or iterations <= 0:
        return None
    return ns * 1e-6 / iterations


def read(run):
    traced = xplane_meta.traced_ops(run)
    if traced is None:
        return None
    return reading(*traced, run.frames("trace"))

"""frames_per_s (host clock): converged frames whose solution reached host
memory in the window, over the window's whole span. Status -1 (the
reference's iteration cap) is a result; other statuses are failures."""


def read(run):
    o = run.outcome
    done = [f for f in run.frames("window") if f.status in (0, -1)]
    return len(done) / o.window_s

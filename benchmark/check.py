"""The comparison that decides ``correct``.

Once the window has closed, the peak read and the program's state freed,
the configuration's plain reference (``references/<name>.py``) regenerates
the matrix from the seed and solves frames the run delivered. Numbers
compared, each against the cell's limit:

- ``cold_gap``: ``|f_prog - f_ref| / |f_ref|`` (2-norms) of frame 0, both
  solved from the Eq. 4 guess (some 85 iterations from far away, so
  rounding grows further than in a warm frame);
- ``warm_gap``: the largest such gap over sampled warm frames, each
  replayed from the start the program had (the previous frame's delivered
  solution), for as many iterations as the program reported;
- ``carried_gap``: the largest such gap over a run of consecutive frames
  across the window's last chain boundary, in which the reference carries
  its own solution from frame to frame (only the run's first start is the
  program's), so error that builds up over a series shows;
- ``stop_rule``: whether each frame stopped where the reference's own test
  (Eq. 5: stop at iteration ``n >= 2`` once ``|C_n - C_(n-1)| < tol``)
  stops it, read from the reference's ``C`` on the replays of frame 0 and
  the sampled warm frames: the largest of ``|C_n - C_(n-1)| / tol`` at the
  program's count ``n`` and of ``tol / |C_m - C_(m-1)|`` at every earlier
  ``m``. Under 1 where the test stops exactly there; a frame stopped early
  (a looser test) reads its ``|C_n - C_(n-1)| / tol``, one stopped late
  the inverse at the count where it should have stopped;
- ``failed_frames``: frames of the run whose status is neither 0
  (converged) nor -1 (the iteration cap, a result), or that never came.

The sample holds frame 0, the window's first frame (a warm start carried
across chains), its frame with the most iterations, the first frame of its
last chain, and further window frames drawn from the seed while the
workload's ``check_iterations`` budget lasts.
"""

from __future__ import annotations

import numpy as np

FAILED_OK = (0, -1)


def sample(frames, budget: int, seed: int) -> list:
    window = [f for f in frames if f.phase == "window"]
    by_index = {f.index: f for f in frames}
    picks = [0]
    if window:
        first = min(window, key=lambda f: f.index)
        longest = max(window, key=lambda f: (f.iterations, f.index))
        last_chain = max(f.chain for f in window)
        last = min((f for f in window if f.chain == last_chain),
                   key=lambda f: f.index)
        for f in (first, longest, last):
            if f.index not in picks:
                picks.append(f.index)
    spent = sum(by_index[i].iterations for i in picks)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 13])
    for i in rng.permutation([f.index for f in window]):
        i = int(i)
        if i in picks:
            continue
        if spent + by_index[i].iterations > budget:
            break
        picks.append(i)
        spent += by_index[i].iterations
    return sorted(picks)


def carried(frames, chain_frames: int, length: int) -> list:
    """``length`` consecutive delivered frames around the first frame of
    the window's last chain: half before that chain boundary, half from
    it on."""
    window = [f for f in frames if f.phase == "window"]
    last_chain = max(f.chain for f in window)
    b = min(f.index for f in window if f.chain == last_chain)
    first = max(1, b - length // 2)
    got = {f.index for f in frames}
    return [i for i in range(first, first + length) if i in got]


def stop_reading(conv, n: int, status: int, tol: float) -> float:
    """How far the program's stop at ``n`` iterations lies from the stop
    the reference's test makes on its own ``C_1 .. C_n``: under 1 where it
    is the same (see the module's ``stop_rule``)."""
    d = np.abs(np.diff(np.asarray(conv[:n], np.float64))) / tol  # m = 2..n
    if status == 0 and d.size == 0:
        return float("inf")  # the test cannot stop after one iteration
    before = d[:-1] if status == 0 else d
    r = float(np.max(1.0 / np.maximum(before, 1e-300), initial=0.0))
    return max(r, float(d[-1])) if status == 0 else r


def _rel(a, b) -> float:
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300))


def run(cell, outcome, reference_matrix, seed: int, diagnose=False):
    """``([(name, value, limit)], info)`` for the cell;
    ``reference_matrix`` is the regenerated RTM the reference reads. The
    info holds each checked frame's ``[index, iterations, solution gap,
    fitted gap, stop reading]``, the carried run's ``[index, gap]`` and,
    with ``diagnose``, the gap between two reference replays whose starts
    differ by one part in 10^7 (how far the replay itself amplifies
    rounding)."""
    limits = cell.workload["limits"]
    tol = float(cell.config["solver"]["conv_tolerance"])
    frames = outcome.frames
    expected = set(range(max(f.index for f in frames) + 1))
    got = {f.index for f in frames}
    failed = sum(f.status not in FAILED_OK for f in frames)
    failed += len(expected - got)

    ref = cell.reference().Reference(reference_matrix, cell.config,
                                     cell.config["solver"])
    by_index = {f.index: f for f in frames}
    cold = warm = stop = 0.0
    detail = []
    picks = sample(frames, int(cell.workload["check_iterations"]), seed)
    for i in picks:
        f = by_index[i]
        start = None if i == 0 else outcome.solutions[i - 1]
        f_ref, conv = ref.replay(outcome.inputs[i], start, f.iterations)
        f_prog = outcome.solutions[i]
        sol = _rel(f_prog, f_ref)
        if i == 0:
            cold = sol
        else:
            warm = max(warm, sol)
        rule = stop_reading(conv, f.iterations, f.status, tol)
        stop = max(stop, rule)
        row = [i, f.iterations, sol,
               _rel(ref.project(f_prog), ref.project(f_ref)), rule]
        if diagnose and start is not None:
            f_b, _ = ref.replay(outcome.inputs[i], start * (1 + 1e-7),
                                f.iterations)
            row.append(_rel(f_b, f_ref))
        detail.append(row)

    run_ = carried(frames, outcome.chain_frames,
                   int(cell.workload["carried_frames"]))
    f_ref = outcome.solutions[run_[0] - 1]
    carried_gap, carried_rows = 0.0, []
    for i in run_:
        f_ref, _ = ref.replay(outcome.inputs[i], f_ref,
                              by_index[i].iterations)
        gap = _rel(outcome.solutions[i], f_ref)
        carried_gap = max(carried_gap, gap)
        carried_rows.append([i, gap])
    return [
        ("cold_gap", cold, float(limits["cold_gap"])),
        ("warm_gap", warm, float(limits["warm_gap"])),
        ("carried_gap", carried_gap, float(limits["carried_gap"])),
        ("stop_rule", stop, float(limits["stop_rule"])),
        ("failed_frames", failed, int(limits["failed_frames"])),
    ], {"checked_frames": len(picks) + len(run_),
        "reference_iterations": sum(by_index[i].iterations
                                    for i in picks + run_),
        "frames": detail, "carried": carried_rows}

"""The CLI's frame loop (sartsolver_tpu/frameloop.py) driven by a fake solver.

The fake solve function returns statuses the test chooses and records the
stack and the seed of every dispatch, so the protocol — grouping, padding,
pipelining, the warm carry, failure isolation, the SDC recompute, the OOM
ladder, stops and their poll cadence — is checked with no device. Every
case runs at group size 1 (the reference's serial form) and 3.
"""

import numpy as np
import pytest

from sartsolver_tpu.config import SDC_DETECTED
from sartsolver_tpu.frameloop import FrameLoop
from sartsolver_tpu.resilience.failures import (
    FRAME_FAILED, FrameFailure, RunSummary,
)
from sartsolver_tpu.resilience.faults import InjectedFault, InjectedOOM

NPIX, NVOX = 4, 5
SIZES = pytest.mark.parametrize("size", [1, 3])


class FakeResult:
    """What a DeviceSolveResult shows the loop: per-frame scalars and a
    lazy solution fetcher. ``ids`` are the frames it solved (pads too)."""

    def __init__(self, ids, statuses, serial):
        self.ids = list(ids)
        self.status = np.asarray(statuses, np.int32)
        self.iterations = np.asarray([10 + i for i in self.ids], np.int32)
        self.convergence = np.full(len(self.ids), 1e-6)
        self.serial = serial  # dispatch number, to tell attempts apart

    def solution_fetcher(self, b):
        return ("solution", self.ids[b], self.serial)


class FakeSolver:
    """``solve(stack, f0, warm)``: frame ids are each row's first pixel.
    ``status(fid, attempt)`` picks a status; ``raises(n)`` may return an
    exception for the n-th dispatch."""

    def __init__(self, status=None, raises=None):
        self.calls = []  # (ids, f0, warm) per dispatch
        self.attempts = {}
        self._status = status or (lambda fid, attempt: 0)
        self._raises = raises or (lambda n: None)

    def __call__(self, stack, f0, warm):
        n = len(self.calls)
        ids = [int(row[0]) for row in stack]
        self.calls.append((ids, f0, warm))
        err = self._raises(n)
        if err is not None:
            raise err
        statuses = []
        for fid in ids:
            self.attempts[fid] = self.attempts.get(fid, 0) + 1
            statuses.append(self._status(fid, self.attempts[fid]))
        return FakeResult(ids, statuses, n)


class Writer:
    def __init__(self):
        self.rows = []

    def add(self, row, status, ftime, cam_times, iterations):
        self.rows.append((row, status, ftime, iterations))


class Telem:
    def __init__(self):
        self.frames = []

    def record_frame(self, ftime, status, iterations, convergence, ms,
                     label, error=None):
        self.frames.append((ftime, status, label, error))


class Timer:
    def __init__(self):
        self.rows = []

    def add(self, name, seconds, detail=False):
        self.rows.append((name, detail))


class SdcPolicy:
    def __init__(self):
        self.detected_n = self.recomputed_n = 0
        self.terminal = []

    def detected(self, n=1):
        self.detected_n += n

    def note_recompute(self, n=1):
        self.recomputed_n += n

    def record_terminal(self, ftime):
        self.terminal.append(ftime)


def frames(n, fail_at=()):
    """Frame ``i`` is constant ``i`` at time ``i / 10``; indices in
    ``fail_at`` arrive as prefetcher failures."""
    for i in range(n):
        if i in fail_at:
            yield FrameFailure(None, i / 10, {"cam": i / 10},
                               OSError("unreadable"))
        else:
            yield np.full(NPIX, float(i)), i / 10, {"cam": i / 10}


def make_loop(**kw):
    parts = dict(writer=Writer(), summary=RunSummary(), telem=Telem(),
                 timer=Timer(), events=[], beats=[], audits=[])
    loop = FrameLoop(
        writer=parts["writer"], nvoxel=NVOX, summary=parts["summary"],
        telem=parts["telem"], timer=parts["timer"],
        stop_poll=kw.pop("stop_poll", lambda: False),
        isolate=kw.pop("isolate", True), primary=True,
        on_event=parts["events"].append,
        heartbeat=lambda: parts["beats"].append(1),
        reaudit=parts["audits"].append, **kw,
    )
    return loop, parts


def written(parts):
    """(frame time, status, solved frame id or None for a FAILED row)."""
    out = []
    for row, status, ftime, _ in parts["writer"].rows:
        fid = row[1] if isinstance(row, tuple) else None
        out.append((ftime, status, fid))
    return out


@SIZES
@pytest.mark.parametrize("warm_start", [True, False])
def test_lines_labels_and_rows(size, warm_start, capsys):
    """Rows in frame order; at size 1 the reference's serial form (one
    bare "Processed in:" line, label ``frame``, timer row ``solve
    frame``), above it the group form."""
    loop, parts = make_loop()
    solve = FakeSolver()
    assert loop.run(frames(6), size, solve, warm_start=warm_start) is False
    assert written(parts) == [(i / 10, 0, i) for i in range(6)]
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("Processed in:")]
    assert len(lines) == 6
    labels = {label for *_, label, _ in parts["telem"].frames}
    timer_rows = {name for name, _ in parts["timer"].rows}
    if size == 1:
        assert all(ln.endswith(" ms") for ln in lines)
        assert labels == {"frame"} and timer_rows == {"solve frame"}
    else:
        kind = "chain" if warm_start else "batch"
        assert all(f"(average over {kind} of 3; " in ln for ln in lines)
        assert labels == {kind}
        assert timer_rows == {f"solve {kind} (pipelined wall)"}
    assert all(detail for _, detail in parts["timer"].rows)
    assert len(parts["beats"]) == 6
    assert parts["audits"] == [size] * (6 // size)
    assert [ids for ids, _, _ in solve.calls] == [
        list(range(i, i + size)) for i in range(0, 6, size)]


@SIZES
@pytest.mark.parametrize("warm_start", [True, False])
def test_partial_tail_padded_and_discarded(size, warm_start):
    """4 frames: the last group is padded up to the size (copies of its
    last frame when warm, dark frames when cold) and only real frames are
    written."""
    loop, parts = make_loop()
    solve = FakeSolver()
    loop.run(frames(4), size, solve, warm_start=warm_start)
    assert written(parts) == [(i / 10, 0, i) for i in range(4)]
    last = solve.calls[-1][0]
    assert len(last) == size
    pad = 3 if warm_start else 0
    assert last == [3] + [pad] * (size - 1)


@SIZES
def test_dead_frame_keeps_warm_carry(size):
    """A prefetch failure and a failed dispatch both become FAILED rows in
    frame order; the warm carry skips them, and the resume seed is spent
    only by the first dispatch that succeeds."""
    f0 = np.full(NVOX, 7.0)
    loop, parts = make_loop()
    # the second dispatch fails (frame 2 at size 1, frames 2-4 at size 3)
    solve = FakeSolver(raises=lambda n: OSError("device.put") if n == 1
                       else None)
    seed_first = FakeSolver(raises=lambda n: InjectedFault("dispatch")
                            if n == 0 else None)
    loop.run(frames(7, fail_at=(1,)), size, solve, warm_start=True, f0=f0)
    rows = written(parts)
    failed = [t for t, st, _ in rows if st == FRAME_FAILED]
    if size == 1:
        assert failed == [0.1, 0.2]
    else:
        # frame 1 fails in the prefetcher: frame 0 goes out as a padded
        # tail first; then [2, 3, 4] fails to dispatch
        assert failed == [0.1, 0.2, 0.3, 0.4]
    assert [t for t, _, _ in rows] == [i / 10 for i in range(7)]
    # every dispatch after the dead one seeds from the last good result
    calls = solve.calls
    assert calls[0][1] is f0 and calls[0][2] is None
    assert calls[1][1] is None and calls[1][2].serial == 0
    assert calls[2][2].serial == 0  # the dead dispatch left the carry
    assert parts["summary"].n_failed == len(failed)
    errors = {err for _, st, _, err in parts["telem"].frames
              if st == FRAME_FAILED}
    assert errors == {"OSError"}

    # a first dispatch that fails keeps the resume seed for the next one
    loop, parts = make_loop()
    loop.run(frames(2 * size), size, seed_first, warm_start=True, f0=f0)
    assert seed_first.calls[1][1] is f0 and seed_first.calls[1][2] is None


@SIZES
def test_sdc_reproduced_fails_row_and_carry(size):
    """Frame 1 trips the in-solve check on both attempts: the group is
    solved twice from the same seed, frame 1 becomes a FAILED row and
    the others are written. At size 1 the next frame keeps the previous
    warm seed; a chain seeds from the recompute (the chain rule)."""
    policy = SdcPolicy()
    loop, parts = make_loop(sdc_policy=policy)
    solve = FakeSolver(status=lambda fid, attempt: SDC_DETECTED
                       if fid == 1 else 0)
    loop.run(frames(6), size, solve, warm_start=True)
    rows = written(parts)
    assert [(t, st) for t, st, _ in rows] == [
        (i / 10, FRAME_FAILED if i == 1 else 0) for i in range(6)]
    assert policy.terminal == [0.1]
    assert policy.detected_n == 2 and policy.recomputed_n == 1
    ids = [c[0] for c in solve.calls]
    first = ids.index([1] if size == 1 else [0, 1, 2])
    assert ids[first + 1] == ids[first]  # the recompute: same frames
    assert solve.calls[first + 1][2] is solve.calls[first][2]  # same seed
    nxt = solve.calls[first + 2]
    if size == 1:
        assert nxt[2].serial == first - 1  # frame 0's result, kept
    else:
        assert nxt[2].serial == first + 1  # the recomputed chain
    assert any("IntegrityError" == err for *_, err in parts["telem"].frames)


@SIZES
def test_stop_at_boundary_drains_inflight(size):
    """A stop request at a group boundary dispatches nothing new, writes
    the in-flight group, returns True and records the agreed boundary."""
    polls = []

    def stop_poll():
        polls.append(1)
        return len(polls) == 3  # the third boundary

    loop, parts = make_loop(stop_poll=stop_poll)
    solve = FakeSolver()
    assert loop.run(frames(12), size, solve, warm_start=True) is True
    n = 2 * size  # two groups dispatched before the stop
    assert [ids for ids, _, _ in solve.calls] == [
        list(range(i, i + size)) for i in range(0, n, size)]
    assert written(parts) == [(i / 10, 0, i) for i in range(n)]
    assert parts["events"] == [f"stop agreed at frame boundary {n}"]


@SIZES
@pytest.mark.parametrize("multihost", [False, True])
def test_multihost_poll_cadence(size, multihost):
    """Each poll is a host allgather on multihost: a size-1 loop polls
    every 4th frame there, every frame otherwise; larger groups poll at
    every group boundary."""
    polls = []

    def stop_poll():
        polls.append(len(polls))
        return False

    loop, _ = make_loop(stop_poll=stop_poll, multihost=multihost,
                        isolate=not multihost)
    loop.run(frames(12), size, FakeSolver(), warm_start=True)
    want = 12 // size
    if size == 1 and multihost:
        want = 3
    assert len(polls) == want


@SIZES
def test_oom_halves_and_resolves_same_frames(size, capsys):
    """An OOM on the first dispatch halves the group size and re-solves
    the same frames (single-process); at size 1 the ladder is exhausted
    and the frame becomes a FAILED row. On multihost the ladder is off
    and the OOM propagates."""
    oom_first = lambda n: InjectedOOM("RESOURCE_EXHAUSTED") if n == 0 \
        else None  # noqa: E731
    loop, parts = make_loop()
    solve = FakeSolver(raises=oom_first)
    loop.run(frames(4), size, solve, warm_start=True)
    rows = written(parts)
    assert [t for t, _, _ in rows] == [i / 10 for i in range(4)]
    if size == 1:
        assert [st for _, st, _ in rows] == [FRAME_FAILED, 0, 0, 0]
        assert parts["events"] == []
    else:
        assert [st for _, st, _ in rows] == [0] * 4
        assert [c[0] for c in solve.calls[:2]] == [[0, 1, 2], [0]]
        assert all(len(ids) == 1 for ids, _, _ in solve.calls[1:])
        assert "re-solving the same frames at 1" in parts["events"][0]
        assert "re-solving the same frames at 1" in capsys.readouterr().err
        assert any("oom degradation: frame-group size 3 -> 1" in e
                   for e in parts["summary"].events)

    loop, _ = make_loop(multihost=True, isolate=False)
    with pytest.raises(InjectedOOM):
        loop.run(frames(4), size, FakeSolver(raises=oom_first),
                 warm_start=True)


@SIZES
def test_step_trace_marks_each_group(size, monkeypatch):
    """--profile_dir: each dispatched group is one profiler step named
    ``frame``, numbered by group."""
    import jax

    steps = []

    class Step:
        def __init__(self, name, step_num):
            steps.append((name, step_num))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", Step)
    loop, _ = make_loop(step_trace=True)
    loop.run(frames(6), size, FakeSolver(), warm_start=True)
    assert steps == [("frame", i) for i in range(6 // size)]

"""The CLI's frame loop: a stream of frames becomes device dispatches, and
each dispatch's results become rows, failures, stops and telemetry.

Every ``sartsolve`` run that is not on the continuous-batching scheduler
goes through :meth:`FrameLoop.run`, the reference's frame loop
(main.cpp:131-140) in groups of ``--chain_frames`` warm or
``--batch_frames`` cold frames, pipelined one deep, with the availability
rules of docs/RESILIENCE.md §4-§8: OOM halving, FAILED rows, the SDC
recompute, group-boundary stops. A group of 1 is reported in the
reference's serial form. The solver is reached only through the ``solve``
function handed to :meth:`FrameLoop.run`, so a test can drive the loop
with a fake one.
"""

from __future__ import annotations

import contextlib
import dataclasses
import sys
import time
from typing import Any, Callable, Iterable, Optional

import numpy as np

from sartsolver_tpu.config import SDC_DETECTED
from sartsolver_tpu.resilience import degrade
from sartsolver_tpu.resilience.failures import (
    FRAME_FAILED, RECOVERABLE_FRAME_ERRORS, FrameFailure, failed_row,
)
from sartsolver_tpu.resilience.integrity import IntegrityError, SDC_REPRODUCED

# Multihost stop polls are a host allgather; per frame that round trip
# would rival a ~9 ms warm-frame solve, so a size-1 loop polls every 4th
# boundary there (the frame streams are identical on every process, so the
# collective cadence stays agreed).
MULTIHOST_SERIAL_POLL_STRIDE = 4


@dataclasses.dataclass(kw_only=True)
class FrameLoop:
    """One run's frame-group protocol over its collaborators.

    ``writer.add(row_or_fetcher, status, ftime, cam_times, iterations=)``
    takes each row in frame order; ``summary`` (RunSummary), ``telem``
    (RunTelemetry) and ``timer`` (PhaseTimer) take the accounting.
    ``stop_poll()`` says whether to stop at this boundary, ``sdc_policy``
    (integrity.SdcEscalation or None) escalates SDC statuses, ``on_event``
    records an availability event, ``heartbeat()`` marks a completed frame
    and ``reaudit(n)`` is told after each group's ``n`` rows.
    ``isolate`` turns failed frames into FAILED rows instead of raising;
    ``multihost`` keeps the OOM ladder off (a per-process halving would
    deadlock the pod's collectives) and strides a size-1 loop's polls;
    ``step_trace`` marks each dispatched group as a profiler step.
    """

    writer: Any
    nvoxel: int
    summary: Any
    telem: Any
    timer: Any
    stop_poll: Callable[[], bool]
    on_event: Callable[[str], None]
    heartbeat: Callable[[], None]
    reaudit: Callable[[int], None]
    isolate: bool
    primary: bool
    multihost: bool = False
    sdc_policy: Any = None
    step_trace: bool = False

    def announce(self, message: str) -> None:
        """An availability event that is also printed (OOM halvings, the
        scheduler's degradations)."""
        self.on_event(message)
        if self.primary:
            print(f"sartsolve: {message}", file=sys.stderr)

    def record_failed(self, ftime, cam_times, err) -> None:
        """One FAILED row (-3, zeros) for a frame that produced nothing."""
        self.writer.add(failed_row(self.nvoxel), FRAME_FAILED, ftime,
                        cam_times, iterations=-1)
        self.summary.record_status(FRAME_FAILED, ftime)
        # the failure counter is keyed by the error class, so injected
        # faults (SART_FAULT) and their real counterparts share a series
        self.telem.record_frame(ftime, FRAME_FAILED, -1, None, None,
                                "failed", error=type(err).__name__)
        self.heartbeat()
        if self.primary:
            print(f"Frame at t={ftime}: FAILED "
                  f"({type(err).__name__}: {err})", file=sys.stderr)

    def _step(self, index: int):
        if not self.step_trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.StepTraceAnnotation("frame", step_num=index)

    def _solve_guarded(self, solve, stack, seed):
        """Recompute-once (docs/RESILIENCE.md §8): a group whose statuses
        carry SDC_DETECTED is solved again from the SAME seed — a transient
        fault does not reproduce, a resident one does. The status fetch
        synchronizes the pipeline, the documented host-side cost of
        --integrity. Returns the result and whether any frame's SDC
        reproduced; those frames become FAILED rows at write time."""
        result = solve(stack, *seed)
        if self.sdc_policy is None:
            return result, False
        sdc = np.asarray(result.status) == SDC_DETECTED
        if not sdc.any():
            return result, False
        self.sdc_policy.detected(int(sdc.sum()))
        self.sdc_policy.note_recompute(int(sdc.sum()))
        result = solve(stack, *seed)
        repeat = np.asarray(result.status) == SDC_DETECTED
        if repeat.any():
            self.sdc_policy.detected(int(repeat.sum()))
        return result, bool(repeat.any())

    def run(
        self,
        frames: Iterable,
        size: int,
        solve: Callable,
        *,
        warm_start: bool = False,
        f0: Optional[np.ndarray] = None,
    ) -> bool:
        """Solve ``frames`` (``(frame, ftime, cam_times)`` items or
        :class:`FrameFailure`) in groups of ``size``; returns True when a
        stop request ended the run early.

        ``solve(stack, f0, warm)`` dispatches one ``[size, npixel]`` stack
        and returns its device result (parallel/sharded.DeviceSolveResult).
        With ``warm_start`` each dispatch gets the previous group's result
        as ``warm`` (``f0``, the resume seed, until the first solve), a
        partial tail is padded with copies of its last frame, and a group
        that produced nothing leaves the carry where it was. Without it
        ``f0`` and ``warm`` are None and the tail pads are dark frames.

        The printed value is the group's incremental wall clock over the
        pipeline divided by its size — the steady-state per-frame cost,
        not one frame's own time — and each frame's exact iteration count.
        """
        serial = size == 1
        label = "frame" if serial else ("chain" if warm_start else "batch")
        timer_row = ("solve frame" if serial
                     else f"solve {label} (pipelined wall)")
        poll_stride = (MULTIHOST_SERIAL_POLL_STRIDE
                       if serial and self.multihost else 1)
        ladder = degrade.GroupSizeLadder(size, on_event=self.announce)
        # a multihost OOM aborts fail-fast like any other device error:
        # requeue with --resume and a smaller --chain_frames/--batch_frames
        active_ladder = None if self.multihost else ladder
        carry = {"f0": f0 if warm_start else None, "warm": None}
        pending = []
        # the dispatched group whose scalars are fetched only after the
        # next group is dispatched (which needs just the device-resident
        # warm solution), so the fetch and the next staging overlap the
        # device's work: (result, metas, t_dispatch, reproduced)
        prev = None
        last_done = None
        write_ok = True  # False while a write_group is mid-flight
        groups = 0  # dispatches so far, the profiler's step number
        interrupted = False

        def pad_tail(stack, n):
            if warm_start:
                # each pad warm-starts from its own converged solution and
                # stalls in ~1 iteration
                return np.repeat(stack[-1:], n, axis=0)
            return np.zeros((n, stack.shape[1]))  # inert dark frames

        def write_group(result, metas, t_dispatch, reproduced):
            nonlocal last_done, write_ok
            write_ok = False  # set True again only on completion
            start = (t_dispatch if last_done is None
                     else max(t_dispatch, last_done))
            # the first scalar access blocks until THIS group's device work
            # completed (the next group is already dispatched)
            statuses = result.status
            now = time.perf_counter()
            dt = now - start
            last_done = now
            # the interval spans everything since the previous group
            # finished, the next group's staging and any frame-read stall
            # included. detail=: it lies inside the frame-loop phase, so it
            # must not also feed the total line
            self.timer.add(timer_row, dt, detail=True)
            per_frame_ms = dt * 1e3 / len(metas)
            for b, (_, ftime, cam_times) in enumerate(metas):
                if reproduced and int(statuses[b]) == SDC_DETECTED:
                    # the group was already solved twice: this frame's
                    # corruption reproduced — FAILED row; the terminal
                    # accounting may quarantine the run (exit 3)
                    self.sdc_policy.record_terminal(ftime)
                    self.record_failed(ftime, cam_times,
                                       IntegrityError(SDC_REPRODUCED))
                    continue
                iterations = int(result.iterations[b])
                self.writer.add(result.solution_fetcher(b),
                                int(statuses[b]), ftime, cam_times,
                                iterations=iterations)
                self.summary.record_status(int(statuses[b]), ftime)
                self.telem.record_frame(
                    ftime, int(statuses[b]), iterations,
                    float(result.convergence[b]), per_frame_ms, label,
                )
                self.heartbeat()
                if self.primary:
                    if serial:
                        print(f"Processed in: {per_frame_ms} ms")
                    else:
                        print(f"Processed in: {per_frame_ms} ms "
                              f"(average over {label} of {len(metas)}; "
                              f"{iterations} iterations)")
            self.reaudit(len(metas))
            write_ok = True

        def drain_inflight():
            # write the already-dispatched group now, so rows recorded
            # after it stay in frame order
            nonlocal prev
            if prev is not None and write_ok:
                to_write, prev = prev, None
                write_group(*to_write)

        def flush(final=False):
            """Dispatch pending frames in ladder-sized groups. Mid-run this
            is called exactly when a full group accumulated; ``final`` also
            dispatches a partial tail. The while-loop re-reads
            ``ladder.size`` so an OOM halving re-solves the SAME frames."""
            nonlocal prev, groups
            while pending and (final or len(pending) >= ladder.size):
                group = pending[:ladder.size]
                stack = np.stack([fr for fr, _, _ in group])
                if len(group) < ladder.size:
                    stack = np.concatenate(
                        [stack, pad_tail(stack, ladder.size - len(group))])
                seed = (carry["f0"], carry["warm"])
                t0 = time.perf_counter()
                try:
                    # beacon + OOM classification against the ladder
                    with self._step(groups):
                        groups += 1
                        out, _oom = degrade.dispatch_guarded(
                            lambda: self._solve_guarded(solve, stack, seed),
                            ladder=active_ladder,
                        )
                except RECOVERABLE_FRAME_ERRORS as err:
                    if not self.isolate:
                        raise
                    # the group produced nothing: its frames all fail, in
                    # order, after the in-flight group's rows; the carry
                    # skips the dead group
                    drain_inflight()
                    for _, ftime, cam_times in group:
                        self.record_failed(ftime, cam_times, err)
                    del pending[:len(group)]
                    continue
                if out is None:
                    # OOM halved the ladder: re-solve the SAME frames at
                    # the smaller size (the failed dispatch left the carry)
                    continue
                result, reproduced = out
                if warm_start and not (serial and reproduced):
                    # a size-1 loop keeps the previous seed after an SDC
                    # that reproduced; a chain seeds from the recompute
                    carry["f0"], carry["warm"] = None, result
                # swap BEFORE writing: if write_group raises, `prev`
                # already holds the new unwritten group for the drain
                # below (never the just-written one — no double write)
                to_write, prev = prev, (result, group, t0, reproduced)
                del pending[:len(group)]
                if to_write is not None:
                    write_group(*to_write)

        seen = 0  # items taken from the stream
        try:
            for item in frames:
                if (not pending and seen % poll_stride == 0
                        and self.stop_poll()):
                    # frame-group boundary stop: no new group is started;
                    # the in-flight group drains below. The agreed
                    # boundary is pinned into every host's summary (the
                    # signal lands at different instants per host)
                    interrupted = True
                    self.on_event(f"stop agreed at frame boundary {seen}")
                    break
                seen += 1
                if isinstance(item, FrameFailure):
                    # keep rows frame-ordered: dispatch what is pending,
                    # drain the in-flight group, then record the dead frame
                    if pending:
                        flush(final=True)
                    drain_inflight()
                    self.record_failed(item.time, item.camera_times,
                                       item.error)
                    continue
                pending.append(item)
                if len(pending) >= ladder.size:
                    flush()
            if pending and not interrupted:
                flush(final=True)
        except BaseException as err:
            # Best-effort drain of the in-flight group: a frame-read or
            # solve error must not silently discard up to ``size`` solved
            # frames. Skipped when the failure was a write itself (writing
            # the NEXT group would punch a frame hole into the file — the
            # non-contiguity that corrupts --resume) or a
            # KeyboardInterrupt (the drain's blocking device fetch would
            # make an abort appear ignored on a wedged backend); its own
            # errors never mask the one already propagating.
            if (prev is not None and write_ok
                    and not isinstance(err, KeyboardInterrupt)):
                try:
                    write_group(*prev)
                except BaseException:
                    pass
            raise
        else:
            if prev is not None:
                write_group(*prev)  # normal path: errors propagate
        finally:
            # the degradation line is recorded on success AND aborts (a
            # degraded run that later dies must still show the reduction)
            ladder_line = ladder.summary()
            if ladder_line:
                self.summary.record_event(ladder_line)
        return interrupted

"""Convergence-aware batch scheduler for the grouped solve loop.

The run-to-slowest batch loop (cli.py ``run_grouped``) dispatches K
frames and waits for the SLOWEST to converge — BENCH_r05 measured the
cost: per-lane loop-iter/s at int8 B=32 drops to ~556 against ~824 at
B=1 because converged lanes pad the MXU with dead work until the last
straggler stalls. Per-frame iteration counts genuinely vary (the
optimization-based-CT literature documents the variance; arxiv
1705.07497), so the padding is structural, not a tuning artifact.

:class:`ContinuousBatcher` borrows the LLM-serving continuous-batching
idea: the compiled batch is a set of B persistent *lanes*
(models/sart.py ``SchedState``), the device program runs at most
``SolverOptions.schedule_stride`` iterations per dispatch, and between
strides the host retires converged/diverged/capped lanes and backfills
them from the frame queue — ONE fixed-shape compiled program serves
every occupancy, and the queue drains its tail through the same
program with the leftover lanes inert.

Contracts kept from the dense grouped loop:

- **Parity** — a retired lane's solution/status/iteration count is
  byte-identical to the same frame solved by the non-scheduled batch
  path (the stepped core shares the batched loop's ``_SweepContext``
  closures; pinned by tests/test_sched.py and the straggler bench's
  parity gate).
- **Row order** — results are emitted to the writer in FRAME ORDER via
  a reorder buffer (retirement order is convergence order; the solution
  file's ``--resume`` contract assumes appended rows are the run's
  prefix in time order).
- **Failure isolation** — prefetcher :class:`FrameFailure` items flow
  through as ordered FAILED rows without occupying a lane; a
  recoverable dispatch failure fails the in-flight lanes (the dense
  loop's "the group produced nothing" semantics) and continues on fresh
  lanes; a device OOM hands the un-emitted frames back to the caller
  for the classic loop's halving ladder (``SchedRunStats.leftover``) —
  the scheduler cannot halve its own lane count without recompiling,
  which would break the one-program contract.
- **Graceful stop** — ``stop_check`` is polled at stride boundaries: a
  stop request ends backfilling and the in-flight lanes drain to
  completion, exactly like the dense loop draining its dispatched
  group.
- **Deadline shed** (serving engine, docs/SERVING.md) — a stream item
  may carry a 4th element: an absolute ``time.monotonic()`` deadline.
  At each stride boundary, occupied lanes past their deadline are
  force-retired with status ``DEADLINE_EXCEEDED`` (-5) and the last
  iterate reached, freeing the lane for backfill while co-batched
  lanes run on. CLI frames carry no deadline; the sweep is inert there.

Observability (docs/OBSERVABILITY.md): ``sched_lane_occupancy`` gauge
(useful lane-iterations / lane capacity over the run — THE number
continuous batching exists to raise), ``sched_lanes_retired_total`` /
``sched_lanes_backfilled_total`` / ``sched_strides_total`` counters,
and per-stride occupancy samples in the ``sched_stride_occupancy``
histogram.
"""

from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import numpy as np

from sartsolver_tpu.config import SDC_DETECTED
from sartsolver_tpu.obs import metrics as obs_metrics
from sartsolver_tpu.obs import trace as obs_trace
from sartsolver_tpu.resilience import watchdog
from sartsolver_tpu.resilience.degrade import (
    dispatch_guarded,
    is_resource_exhausted,
)
from sartsolver_tpu.resilience.failures import (
    DEADLINE_EXCEEDED,
    RECOVERABLE_FRAME_ERRORS,
    FrameFailure,
)


@dataclass
class SchedRunStats:
    """End-of-run scheduler accounting (plus the OOM fallback payload)."""

    frames: int = 0  # results emitted (FAILED rows included)
    solved: int = 0  # lanes retired with a solver status
    failed: int = 0  # FrameFailure rows + isolation-failed lanes
    backfilled: int = 0  # lane loads (initial fill included)
    strides: int = 0  # device dispatches
    loop_steps: int = 0  # solver iterations the device executed
    useful_iters: int = 0  # per-frame iterations summed over retirees
    deadline_shed: int = 0  # lanes force-retired past their deadline
    interrupted: bool = False  # a stop request truncated the queue
    # un-emitted frames (in frame order, FrameFailure items included)
    # after a device OOM: the caller re-solves them on the classic
    # grouped loop at a halved group size; None on every other path
    leftover: Optional[List] = None
    oom_error: Optional[BaseException] = None
    events: List[str] = field(default_factory=list)

    @property
    def occupancy(self) -> float:
        """Useful lane-iterations / lane capacity actually dispatched."""
        cap = self.loop_steps and self._capacity
        return (self.useful_iters / cap) if cap else 0.0

    _capacity: int = 0


class _Slot:
    """One occupied lane's host-side bookkeeping."""

    __slots__ = ("seq", "frame", "ftime", "cam_times", "it_prev",
                 "sdc_retries", "deadline", "trace")

    def __init__(self, seq, frame, ftime, cam_times, deadline=None,
                 trace=None):
        self.seq = seq
        self.frame = frame  # kept for OOM requeue (one [npixel] fp64 row)
        self.ftime = ftime
        self.cam_times = cam_times
        self.it_prev = 0
        # absolute time.monotonic() deadline (serving engine,
        # docs/SERVING.md), or None — the one-shot CLI's frames carry
        # none and the deadline sweep never touches them
        self.deadline = deadline
        # request trace id (serving engine, docs/OBSERVABILITY.md §10):
        # per-stride solve spans land on this request's trace track;
        # None on CLI frames, where the trace hooks are inert
        self.trace = trace
        # SDC escalation (docs/RESILIENCE.md §8): how many times this
        # frame was re-queued after an ABFT trip — recompute-once, then
        # the lane fails through the ordered FAILED-row path
        self.sdc_retries = 0


class ContinuousBatcher:
    """Drive a :class:`DistributedSARTSolver`'s lane state over a frame
    stream with convergence-aware retirement and backfill.

    ``on_result(ftime, cam_times, status, iterations, convergence,
    fetcher, per_frame_ms)`` receives each retired frame in FRAME ORDER
    (``fetcher`` is a zero-arg callable resolving the denormalized
    solution row — the async-writer contract);
    ``on_failed(ftime, cam_times, error)`` receives FAILED frames in the
    same ordered stream. ``stop_check`` is polled at stride boundaries;
    ``isolate`` mirrors the CLI's per-frame isolation flag (False:
    recoverable dispatch errors raise instead of failing the in-flight
    lanes).
    """

    def __init__(
        self,
        solver,
        *,
        lanes: int,
        on_result: Callable,
        on_failed: Callable,
        stop_check: Optional[Callable[[], bool]] = None,
        on_event: Optional[Callable[[str], None]] = None,
        isolate: bool = True,
        refill_quantum: Optional[int] = None,
        integrity_policy=None,
        step_trace: bool = False,
        ckpt_stride: Optional[int] = None,
        ckpt_sink: Optional[Callable[[int, dict], None]] = None,
        stride_barrier: Optional[Callable[[int], None]] = None,
        restore: Optional[dict] = None,
        restore_emitted: int = 0,
    ):
        if lanes < 1:
            raise ValueError("Lane count must be positive.")
        # In-solve checkpointing (docs/RESILIENCE.md §11): every
        # ``ckpt_stride`` strides the full run state — lane SchedState,
        # host bookkeeping, reorder buffer — is snapshotted and handed
        # to ``ckpt_sink(serial, snapshot)``. ``restore`` re-enters a
        # prior snapshot; ``restore_emitted`` is the number of rows the
        # output file already holds (the killed run kept writing past
        # the snapshot — anything written is dropped from the restored
        # state, never re-emitted). ``stride_barrier(serial)`` is the
        # per-stride pod rendezvous hook (None: single-host, no-op).
        self._ckpt_stride = int(ckpt_stride) if ckpt_stride else None
        self._ckpt_sink = ckpt_sink
        self._stride_barrier = stride_barrier
        self._restore = restore
        self._restore_emitted = int(restore_emitted)
        # resilience.integrity.SdcEscalation (or None): a lane retiring
        # with SDC_DETECTED is re-queued once (recompute), then failed as
        # an ordered row; the policy's terminal accounting may raise
        # PersistentCorruptionError to quarantine the whole session —
        # deliberately NOT a recoverable error, it propagates to the CLI
        self._integrity = integrity_policy
        self._solver = solver
        self._lanes = int(lanes)
        # A refill stride pays the Eq. 4 guess branch — two extra RTM
        # sweeps — however many lanes it loads, so refilling lanes one
        # by one as they trickle out costs ~2B lane-iteration-equivalents
        # PER FRAME. Waiting until a quarter of the lanes are free
        # amortizes the guess 4x+ for, at worst, a quantum of briefly
        # idle lanes (comparable padding to one retirement's stride
        # rounding). The tail still drains: an empty batch always
        # refills immediately.
        if refill_quantum is None:
            refill_quantum = max(1, self._lanes // 4)
        self._refill_quantum = max(1, min(int(refill_quantum), self._lanes))
        self._on_result = on_result
        self._on_failed = on_failed
        self._stop_check = stop_check
        self._on_event = on_event
        self._isolate = isolate
        # --profile_dir: wrap every stride dispatch in a
        # jax.profiler.StepTraceAnnotation so the XLA device trace
        # aligns with stride boundaries instead of one undifferentiated
        # blob; zero-cost (a shared nullcontext) when off
        self._step_trace = bool(step_trace)
        registry = obs_metrics.get_registry()
        self._occ_gauge = registry.gauge("sched_lane_occupancy")
        self._occ_hist = registry.histogram("sched_stride_occupancy")
        self._retired_ctr = registry.counter("sched_lanes_retired_total")
        self._backfill_ctr = registry.counter("sched_lanes_backfilled_total")
        self._stride_ctr = registry.counter("sched_strides_total")
        self._deadline_ctr = registry.counter("sched_deadline_shed_total")

    # ---- ordered emission ------------------------------------------------

    def _emit_ready(self) -> None:
        """Flush the reorder buffer's contiguous prefix to the callbacks
        (frame order, never retirement order)."""
        while self._next_emit in self._emit_buf:
            kind, payload, _frame = self._emit_buf.pop(self._next_emit)
            self._next_emit += 1
            if kind == "failed":
                ftime, cam_times, err = payload
                self._stats.failed += 1
                self._stats.frames += 1
                self._on_failed(ftime, cam_times, err)
            else:
                self._stats.frames += 1
                self._on_result(*payload)

    def _event(self, message: str) -> None:
        self._stats.events.append(message)
        if self._on_event is not None:
            self._on_event(message)

    # ---- live introspection ----------------------------------------------

    def _live_status(self) -> Optional[dict]:
        """Occupancy + in-flight lane serials for the heartbeat file and
        the SIGUSR1 status snapshot (watchdog.set_sched_status_provider).
        Reads the run's own bookkeeping under the GIL, deliberately
        lock-free — this runs from the heartbeat thread and from signal
        context, where blocking on the scheduler would be the SL103
        hazard. The lane listing iterates a dict the main thread mutates
        per retirement; a racing insert raises RuntimeError, which must
        not silently cost the snapshot its lane view — bounded retry
        (each attempt is atomic-or-raises under the GIL), degrading to
        lanes=None, never an exception out of a status poke."""
        from sartsolver_tpu.utils.locking import stale_read

        occupied = getattr(self, "_occupied", None)
        stats = getattr(self, "_stats", None)
        if occupied is None or stats is None:
            return None
        lanes = stale_read(
            lambda: sorted(slot.seq for slot in occupied.values())
        )
        return {
            "occupancy": round(stats.occupancy, 3),
            "lanes": lanes,
            "strides": stats.strides,
            "frames_emitted": stats.frames,
        }

    def _step_span(self, step: int):
        if not self._step_trace:
            return contextlib.nullcontext()
        import jax.profiler

        return jax.profiler.StepTraceAnnotation("sched.stride",
                                                step_num=step)

    # ---- main loop -------------------------------------------------------

    def run(self, items) -> SchedRunStats:
        """Consume the ``(frame, time, camera_times) | FrameFailure``
        stream until it is drained (or a stop request truncates it).
        Returns the run stats; ``stats.leftover`` is non-None exactly
        when a device OOM forced the classic-loop fallback."""
        # publish the live lane view for the duration of the run: the
        # heartbeat line gains occupancy= / lanes= and SIGUSR1 snapshots
        # see the scheduler (docs/OBSERVABILITY.md §9)
        watchdog.set_sched_status_provider(self._live_status)
        try:
            return self._run(items)
        finally:
            watchdog.set_sched_status_provider(None)

    def _run(self, items) -> SchedRunStats:
        solver = self._solver
        B = self._lanes
        stats = self._stats = SchedRunStats()
        self._emit_buf = {}
        self._next_emit = 0
        it = iter(items)
        exhausted = False
        self._sdc_retry = deque()  # slots awaiting their SDC recompute
        if self._restore is not None:
            lane_state, free, seq = self._apply_restore(stats, B)
            occupied = self._occupied
        else:
            lane_state = solver.sched_lanes(B)
            free = deque(range(B))
            occupied = self._occupied = {}  # lane index -> _Slot
            seq = 0
        t_last = time.perf_counter()
        # request-scoped tracing (serving engine): resolved once per run
        # — None (the CLI default) keeps the stride loop span-free
        tracebuf = obs_trace.active_buffer()

        def intake():
            """Fill free lanes from the stream; FrameFailure items take a
            sequence slot and go straight to the reorder buffer. Below
            the refill quantum (and with work still in flight) the free
            lanes ride empty one more stride instead of paying the
            guess branch for a single lane."""
            nonlocal exhausted, seq
            refills = []
            # SDC recomputes first, bypassing the refill quantum: the
            # frame is already in flight (its seq slot blocks the ordered
            # emission) — delaying its recompute stalls the reorder buffer
            while self._sdc_retry and free:
                slot = self._sdc_retry.popleft()
                slot.it_prev = 0
                lane = free.popleft()
                occupied[lane] = slot
                refills.append((lane, slot.frame))
            if occupied and len(free) < self._refill_quantum:
                return refills
            while free and not exhausted and not stats.interrupted:
                try:
                    item = next(it)
                except StopIteration:
                    exhausted = True
                    break
                if isinstance(item, FrameFailure):
                    self._emit_buf[seq] = (
                        "failed", (item.time, item.camera_times,
                                   item.error), None,
                    )
                    seq += 1
                    continue
                # items are (frame, time, camera_times) from the CLI's
                # prefetcher, or the serving engine's extended form with
                # a 4th element (absolute monotonic deadline) and a 5th
                # (request trace id)
                frame, ftime, cam_times = item[0], item[1], item[2]
                deadline = item[3] if len(item) > 3 else None
                trace_id = item[4] if len(item) > 4 else None
                lane = free.popleft()
                occupied[lane] = _Slot(seq, np.asarray(frame), ftime,
                                       cam_times, deadline=deadline,
                                       trace=trace_id)
                refills.append((lane, occupied[lane].frame))
                seq += 1
            return refills

        while True:
            if (self._stop_check is not None and not stats.interrupted
                    and not exhausted and self._stop_check()):
                # stride-boundary stop: no new frames enter; the in-flight
                # lanes drain to completion below (the dense loop's
                # drain-the-dispatched-group semantics). Once the queue is
                # exhausted a stop cannot truncate anything — the drain
                # completes every frame, and reporting THAT as interrupted
                # (exit 4) would make a supervisor requeue a finished job
                stats.interrupted = True
            refills = intake()
            self._seq = seq  # mirrored for the stride-boundary snapshot
            if not occupied and not refills:
                self._emit_ready()  # trailing FrameFailure rows
                break
            t_stride0 = time.perf_counter()
            try:
                # the availability wrappers the classic loop gets from
                # cli.py's dispatch_guarded call: dispatch-phase beacon +
                # group.dispatch trace span (ladder=None — the fixed lane
                # count cannot halve, OOM handling is the leftover path)
                with self._step_span(stats.strides):
                    dispatch_guarded(
                        lambda: solver.sched_step(lane_state, refills),
                        ladder=None,
                    )
            except RECOVERABLE_FRAME_ERRORS as err:
                if is_resource_exhausted(err):
                    # the one failure the scheduler cannot absorb at a
                    # fixed lane count: hand every un-emitted frame back
                    # (frame order) for the classic loop's halving ladder
                    self._emit_ready()
                    stats.leftover = self._requeue(occupied)
                    stats.oom_error = err
                    self._event(
                        f"device OOM in the continuous-batching scheduler "
                        f"({type(err).__name__}); handing "
                        f"{len(stats.leftover)} in-flight/buffered "
                        "frame(s) back to the fixed-group loop"
                    )
                    self._finalize()
                    return stats
                if not self._isolate:
                    raise
                # dispatch failed with no result: every in-flight lane's
                # frame fails, in order (the dense loop's "the group
                # produced nothing"), and the run continues on fresh lanes
                for lane in sorted(occupied, key=lambda b: occupied[b].seq):
                    slot = occupied[lane]
                    self._emit_buf[slot.seq] = (
                        "failed", (slot.ftime, slot.cam_times, err), None,
                    )
                for slot in self._sdc_retry:  # awaiting-recompute frames
                    self._emit_buf[slot.seq] = (
                        "failed", (slot.ftime, slot.cam_times, err), None,
                    )
                self._sdc_retry.clear()
                occupied.clear()
                free = deque(range(B))
                lane_state = solver.sched_lanes(B)
                self._emit_ready()
                continue
            stats.strides += 1
            self._stride_ctr.inc()
            stats.backfilled += len(refills)
            self._backfill_ctr.inc(len(refills))
            done, status, iters, conv, itv = lane_state.scalars()
            # device-side stride length: the while loop exits early once
            # every lane is done, so measure what actually ran
            steps = 0
            useful = 0
            deltas = {}
            for lane, slot in occupied.items():
                delta = int(itv[lane]) - slot.it_prev
                slot.it_prev = int(itv[lane])
                deltas[lane] = delta
                steps = max(steps, delta)
                useful += delta
            stats.loop_steps += steps
            stats._capacity += steps * B
            stats.useful_iters += useful
            if steps:
                self._occ_hist.observe(useful / (steps * B))
            if tracebuf is not None:
                # per-request per-stride solve spans (docs §10): one
                # complete event per traced lane on its request's track,
                # covering this dispatch+fetch, with the lane index, the
                # iterations the lane actually advanced this stride, and
                # the stride's occupancy
                t_stride1 = time.perf_counter()
                occ = (useful / (steps * B)) if steps else 0.0
                for lane, slot in occupied.items():
                    if slot.trace:
                        tracebuf.add_request_complete(
                            slot.trace, "sched.stride", t_stride0,
                            t_stride1,
                            {"lane": lane, "iters": deltas[lane],
                             "stride": stats.strides,
                             "occupancy": round(occ, 3)},
                        )
            # retire: convergence order on device, frame order out
            now = time.perf_counter()
            retired_now = [
                lane for lane in occupied if done[lane]
            ]
            for lane in sorted(retired_now,
                               key=lambda b: occupied[b].seq):
                if (self._integrity is not None
                        and int(status[lane]) == SDC_DETECTED):
                    # ABFT trip (docs/RESILIENCE.md §8): recompute once by
                    # re-queuing the frame onto a fresh lane; a repeat is
                    # a FAILED row in the same ordered stream. The
                    # terminal accounting may raise
                    # PersistentCorruptionError — quarantine the session.
                    slot = occupied.pop(lane)
                    free.append(lane)
                    self._integrity.detected()
                    if slot.sdc_retries == 0:
                        slot.sdc_retries = 1
                        self._integrity.note_recompute()
                        self._sdc_retry.append(slot)
                        continue
                    from sartsolver_tpu.resilience.integrity import (
                        SDC_REPRODUCED,
                        IntegrityError,
                    )

                    self._integrity.record_terminal(slot.ftime)
                    self._emit_buf[slot.seq] = (
                        "failed",
                        (slot.ftime, slot.cam_times,
                         IntegrityError(SDC_REPRODUCED)),
                        None,
                    )
                    continue
                slot = occupied.pop(lane)
                fetcher = lane_state.lane_solution_fetcher(lane)
                stats.solved += 1
                self._retired_ctr.inc()
                if tracebuf is not None and slot.trace:
                    tracebuf.add_request_instant(
                        slot.trace, "lane.retire",
                        {"lane": lane, "status": int(status[lane]),
                         "iterations": int(iters[lane])},
                    )
                per_frame_ms = ((now - t_last) * 1e3
                                / max(len(retired_now), 1))
                self._emit_buf[slot.seq] = (
                    "result",
                    (slot.ftime, slot.cam_times, int(status[lane]),
                     int(iters[lane]), float(conv[lane]), fetcher,
                     per_frame_ms),
                    # the raw frame rides along until emission: an OOM
                    # requeue must be able to re-solve an out-of-order
                    # completion stuck behind a still-in-flight lane
                    slot.frame,
                )
                free.append(lane)
            if retired_now:
                t_last = now
            # Deadline sweep (serving engine, docs/SERVING.md): lanes
            # whose slot carries an absolute deadline that has passed are
            # force-retired HERE, at the stride boundary — the one place
            # the host holds control between device dispatches — with the
            # distinct DEADLINE_EXCEEDED status and the last iterate
            # reached. Co-batched lanes are untouched: the fixed-shape
            # program keeps running them; the shed lane is simply freed
            # for backfill. CLI frames carry no deadline, so this loop
            # never fires there (byte-identical behavior).
            now_mono = time.monotonic()
            overdue = [
                lane for lane, slot in occupied.items()
                if slot.deadline is not None and now_mono > slot.deadline
            ]
            for lane in sorted(overdue, key=lambda b: occupied[b].seq):
                slot = occupied.pop(lane)
                fetcher = lane_state.lane_solution_fetcher(lane)
                stats.deadline_shed += 1
                self._deadline_ctr.inc()
                if tracebuf is not None and slot.trace:
                    tracebuf.add_request_instant(
                        slot.trace, "deadline.shed",
                        {"lane": lane, "iterations": int(itv[lane])},
                    )
                self._emit_buf[slot.seq] = (
                    "result",
                    (slot.ftime, slot.cam_times, DEADLINE_EXCEEDED,
                     int(itv[lane]), float(conv[lane]), fetcher, 0.0),
                    slot.frame,
                )
                free.append(lane)
            self._emit_ready()
            # stride boundary: checkpoint first (a host killed after the
            # barrier passes has its record durable; one killed inside
            # the append falls back a stride — the torn-tail contract),
            # then the pod rendezvous
            if (self._ckpt_sink is not None and self._ckpt_stride
                    and stats.strides % self._ckpt_stride == 0):
                self._ckpt_sink(stats.strides,
                                self._snapshot(lane_state, stats.strides))
            if self._stride_barrier is not None:
                self._stride_barrier(stats.strides)
        self._finalize()
        return stats

    # ---- in-solve checkpointing (docs/RESILIENCE.md §11) -----------------

    @staticmethod
    def _slot_entry(slot, lane=None) -> dict:
        ent = {"seq": int(slot.seq), "ftime": slot.ftime,
               "cam_times": slot.cam_times,
               "it_prev": int(slot.it_prev),
               "sdc_retries": int(slot.sdc_retries),
               "frame": np.asarray(slot.frame)}
        if lane is not None:
            ent["lane"] = int(lane)
        return ent

    def _snapshot(self, lane_state, serial: int) -> dict:
        """The run state a resume needs, as one checkpoint payload.

        Captured at a stride boundary, where the host holds everything:
        occupied/awaiting-recompute slots (with their raw frames — a
        restored lane may still OOM into the classic-loop requeue),
        the reorder buffer (result entries MATERIALIZED via their
        idempotent fetchers — the lane buffers they slice are
        overwritten by later strides), the ordering counters, the stats
        counters (so serials stay monotonic across incarnations), and
        the solver's exported lane state. CLI-path only: serving-engine
        deadlines/trace ids are not carried (the engine's durability is
        the request journal, not this checkpoint)."""
        stats = self._stats
        emit = []
        for seq_i, (kind, payload, frame) in self._emit_buf.items():
            if kind == "failed":
                ftime, cam_times, err = payload
                emit.append({"seq": int(seq_i), "kind": "failed",
                             "ftime": ftime, "cam_times": cam_times,
                             "error": str(err)})
            else:
                ftime, cam_times, status, iters, conv, fetcher, ms = payload
                emit.append({
                    "seq": int(seq_i), "kind": "result", "ftime": ftime,
                    "cam_times": cam_times, "status": int(status),
                    "iters": int(iters), "conv": float(conv),
                    "row": np.asarray(fetcher()), "ms": float(ms),
                    "frame": None if frame is None else np.asarray(frame),
                })
        return {
            "serial": int(serial),
            "lanes": int(self._lanes),
            "seq": int(self._seq),
            "next_emit": int(self._next_emit),
            "stats": {
                "frames": stats.frames, "solved": stats.solved,
                "failed": stats.failed, "backfilled": stats.backfilled,
                "strides": stats.strides, "loop_steps": stats.loop_steps,
                "useful_iters": stats.useful_iters,
                "deadline_shed": stats.deadline_shed,
                "capacity": stats._capacity,
            },
            "occupied": [self._slot_entry(slot, lane)
                         for lane, slot in self._occupied.items()],
            "sdc_retry": [self._slot_entry(slot)
                          for slot in self._sdc_retry],
            "emit": emit,
            "solver": self._solver.export_sched_lanes(lane_state),
        }

    def _apply_restore(self, stats, B: int):
        """Re-enter a :meth:`_snapshot` payload: returns
        ``(lane_state, free, seq)`` and seeds the emit buffer, occupied
        map, SDC-retry queue and stats counters.

        ``self._restore_emitted`` (W) reconciles the snapshot with the
        output file the killed run kept appending to: rows the file
        already holds are the run's frame-order prefix (the reorder
        buffer guarantees it), so every restored entry with seq < W is
        dropped — its lane reset to inert via ``kill_lanes`` — and
        emission resumes at W. The CLI guarantees W >= the snapshot's
        next_emit by flushing the writer before each checkpoint append
        and by falling back a stride otherwise."""
        snap = self._restore
        W = self._restore_emitted
        if int(snap.get("lanes", B)) != B:
            raise ValueError(
                f"Solve checkpoint has {snap.get('lanes')} lanes; this "
                f"run was started with {B} — resume with the same "
                "--schedule_lanes."
            )
        if int(snap["next_emit"]) > W:
            raise ValueError(
                f"Solve checkpoint is ahead of the output file "
                f"({snap['next_emit']} emitted vs {W} rows written) — "
                "pick an earlier checkpoint."
            )
        st = snap["stats"]
        stats.frames = int(st["frames"])
        stats.solved = int(st["solved"])
        stats.failed = int(st["failed"])
        stats.backfilled = int(st["backfilled"])
        stats.strides = int(st["strides"])
        stats.loop_steps = int(st["loop_steps"])
        stats.useful_iters = int(st["useful_iters"])
        stats.deadline_shed = int(st["deadline_shed"])
        stats._capacity = int(st["capacity"])
        occupied = self._occupied = {}
        kill_lanes = []
        for ent in snap["occupied"]:
            lane = int(ent["lane"])
            if int(ent["seq"]) < W:
                # retired AND written by the killed run post-checkpoint
                kill_lanes.append(lane)
                stats.frames += 1
                stats.solved += 1
                continue
            slot = _Slot(int(ent["seq"]), np.asarray(ent["frame"]),
                         ent["ftime"], ent["cam_times"])
            slot.it_prev = int(ent["it_prev"])
            slot.sdc_retries = int(ent["sdc_retries"])
            occupied[lane] = slot
        for ent in snap["sdc_retry"]:
            if int(ent["seq"]) < W:
                stats.frames += 1
                stats.solved += 1
                continue
            slot = _Slot(int(ent["seq"]), np.asarray(ent["frame"]),
                         ent["ftime"], ent["cam_times"])
            slot.it_prev = int(ent["it_prev"])
            slot.sdc_retries = int(ent["sdc_retries"])
            self._sdc_retry.append(slot)
        for ent in snap["emit"]:
            seq_i = int(ent["seq"])
            if ent["kind"] == "failed":
                if seq_i < W:
                    stats.frames += 1
                    stats.failed += 1
                    continue
                self._emit_buf[seq_i] = (
                    "failed",
                    (ent["ftime"], ent["cam_times"],
                     RuntimeError(ent["error"])),
                    None,
                )
            else:
                if seq_i < W:
                    stats.frames += 1
                    continue
                row = np.asarray(ent["row"])
                frame = ent.get("frame")
                self._emit_buf[seq_i] = (
                    "result",
                    (ent["ftime"], ent["cam_times"], int(ent["status"]),
                     int(ent["iters"]), float(ent["conv"]),
                     (lambda r=row: r), float(ent["ms"])),
                    None if frame is None else np.asarray(frame),
                )
        self._next_emit = max(int(snap["next_emit"]), W)
        seq = max(int(snap["seq"]), W)
        lane_state = self._solver.restore_sched_lanes(
            snap["solver"], kill_lanes=kill_lanes
        )
        free = deque(b for b in range(B) if b not in occupied)
        return lane_state, free, seq

    def _requeue(self, occupied) -> List:
        """Un-emitted frames in frame order for the classic-loop
        fallback. Completed-but-unemitted results (out-of-order
        completions stuck behind a still-in-flight lane) are discarded
        and RE-SOLVED from their buffered raw frames — emitting a
        device result after the fallback re-solves an earlier frame
        would break row order; OOM is rare, row order is the
        contract."""
        entries = []
        for seq_i, (kind, payload, frame) in self._emit_buf.items():
            if kind == "failed":
                ftime, cam_times, err = payload
                entries.append((seq_i, FrameFailure(None, ftime,
                                                    cam_times, err)))
            else:
                ftime, cam_times = payload[0], payload[1]
                entries.append((seq_i, (frame, ftime, cam_times)))
        for lane, slot in occupied.items():
            entries.append((slot.seq, self._requeue_item(slot)))
        for slot in getattr(self, "_sdc_retry", ()):  # awaiting recompute
            entries.append((slot.seq, self._requeue_item(slot)))
        self._emit_buf.clear()
        return [item for _, item in sorted(entries, key=lambda e: e[0])]

    @staticmethod
    def _requeue_item(slot):
        """An in-flight slot back in stream-item form; the engine's
        deadline (4th element) and trace id (5th) survive the requeue so
        the fallback run can still shed and attribute it."""
        if slot.trace is not None:
            return (slot.frame, slot.ftime, slot.cam_times, slot.deadline,
                    slot.trace)
        if slot.deadline is not None:
            return (slot.frame, slot.ftime, slot.cam_times, slot.deadline)
        return (slot.frame, slot.ftime, slot.cam_times)

    def _finalize(self) -> None:
        self._occ_gauge.set(round(self._stats.occupancy, 6))


def sched_held_ftimes(snapshot: dict, emitted: int) -> List:
    """Frame times a restored run serves from checkpoint state (in-flight
    lanes, awaiting-recompute slots, buffered out-of-order results) —
    the resume path must skip these in the fresh frame stream on top of
    the already-written filter, or they would be solved twice. Entries
    below ``emitted`` are dropped at restore (already written), so they
    are not held either."""
    W = int(emitted)
    held = []
    for key in ("occupied", "sdc_retry", "emit"):
        for ent in snapshot.get(key, ()):
            if int(ent["seq"]) >= W:
                held.append(ent["ftime"])
    return held

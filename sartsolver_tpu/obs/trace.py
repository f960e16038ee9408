"""Trace spans: the watchdog beacon stream + explicit ``span()`` blocks.

Two event sources, one buffer:

- **Beacons** — resilience/watchdog.py already instruments every host
  phase of the pipeline (prefetch, device.put, solve.dispatch,
  result.fetch, io.flush, frame.done) with progress beacons. When a
  trace sink is active this module taps that stream
  (:func:`watchdog.set_beacon_tap`): each beacon closes the previous
  phase span of its thread and opens the next, so the existing
  instrumentation yields a complete per-thread phase timeline for free.
- **Spans** — :func:`span` wraps host work that has a natural duration
  (RTM ingest, a frame-group write, a lazy device fetch) in an explicit
  begin/end pair, with optional key=value args carried into the event.

The buffer renders to Chrome trace-event JSON (``ph: "X"`` complete
events, microsecond timestamps) loadable in Perfetto / chrome://tracing.

One clock with the device: while ``jax.profiler`` is capturing
(``--profile_dir``, or any caller's ``jax.profiler.trace``), every
``span()`` is also a ``jax.profiler.TraceAnnotation`` with the same name
and args, so it lands on the host plane of the profiler's xplane, on the
clock of the device operations it dispatched or waited for. jax is looked
up in ``sys.modules``, never imported: this package stays stdlib-only.
Beacons and request-track spans stay in the buffer only.

Cost model: with no buffer installed (the default) a beacon pays one
module-global ``None`` check; ``span()`` pays that and, once jax is
imported, one ``TraceAnnotation.is_enabled()`` call, and returns a shared
no-op context manager while the profiler is off — nothing is recorded,
nothing allocated per call. The CLI installs a buffer only when
``SART_TRACE_EVENTS`` is set.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from sartsolver_tpu.utils.locking import named_lock


class _NullSpan:
    """Shared no-op context manager for the disabled path."""

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    def __init__(self, buffer: "TraceBuffer", name: str, cat: str,
                 args: Dict[str, object], annotation=None):
        self._buffer = buffer
        self._name = name
        self._cat = cat
        self._args = args
        self._annotation = annotation  # profiler twin, or None
        self._t0 = 0.0

    def __enter__(self) -> "_Span":
        if self._annotation is not None:
            self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self._buffer.add_complete(
            self._name, self._cat, self._t0,
            time.perf_counter() - self._t0,
            threading.get_ident(), self._args,
        )
        if self._annotation is not None:
            self._annotation.__exit__(*exc)


class TraceBuffer:
    """Thread-safe in-memory store of trace events.

    Bounded: a long run emits ~6 beacon events per frame, and an
    unbounded buffer would turn the trace sink into exactly the host
    memory pressure the resilience layer guards against. Past
    ``max_events`` (default 1e6, ~hundreds of MB worst case; env
    ``SART_TRACE_MAX_EVENTS``) new events are dropped and counted — the
    trace keeps its *head* (ingest, compile, steady-state onset: the
    part that attributes a slow run) and the export records how many
    tail events were dropped.
    """

    def __init__(self, max_events: Optional[int] = None) -> None:
        self._lock = named_lock("obs.trace.buffer")
        self._events: List[dict] = []  # guarded by: self._lock
        self._epoch = time.perf_counter()
        self._max = max_events if max_events is not None else int(
            os.environ.get("SART_TRACE_MAX_EVENTS", "1000000")
        )
        self._dropped = 0  # guarded by: self._lock
        # per-thread open phase span from the beacon stream:
        # ident -> (phase, perf_counter at its beacon)
        self._open: Dict[int, Tuple[str, float]] = {}  # guarded by: self._lock
        # request-scoped tracks (docs/OBSERVABILITY.md §10): trace id ->
        # synthetic tid, so every request renders as its own named row in
        # Perfetto, separate from the real host-thread phase timelines.
        # Synthetic tids start at 1 — pthread idents are large, so the
        # ranges never collide in practice.
        self._tracks: Dict[str, int] = {}  # guarded by: self._lock
        self._next_track = 1  # guarded by: self._lock
        # per-track event index (same dicts as _events): request_events
        # runs on EVERY request completion, so it must read the
        # request's own events, not scan the whole buffer under the lock
        self._track_events: Dict[int, List[dict]] = {}  # guarded by: self._lock

    def _us(self, t: float) -> float:
        return (t - self._epoch) * 1e6

    def _append_locked(self, event: dict,
                       track: Optional[int] = None) -> None:
        if len(self._events) >= self._max:
            self._dropped += 1
            return
        self._events.append(event)
        if track is not None:
            self._track_events.setdefault(track, []).append(event)

    def add_complete(self, name: str, cat: str, start: float, dur: float,
                     tid: int, args: Optional[Dict[str, object]] = None,
                     _track: bool = False) -> None:
        event = {"name": name, "cat": cat, "ph": "X", "pid": os.getpid(),
                 "tid": tid, "ts": self._us(start), "dur": dur * 1e6}
        if args:
            event["args"] = dict(args)
        with self._lock:
            self._append_locked(event, track=tid if _track else None)

    def add_instant(self, name: str, cat: str, tid: int,
                    args: Optional[Dict[str, object]] = None,
                    _track: bool = False) -> None:
        event = {"name": name, "cat": cat, "ph": "i", "s": "t",
                 "pid": os.getpid(), "tid": tid,
                 "ts": self._us(time.perf_counter())}
        if args:
            event["args"] = dict(args)
        with self._lock:
            self._append_locked(event, track=tid if _track else None)

    # ---- request-scoped tracks (serving engine) --------------------------

    def request_track(self, trace_id: str) -> Optional[int]:
        """The synthetic tid of ``trace_id``'s track, allocated (with a
        Perfetto ``thread_name`` metadata event) on first use.

        Bounded like everything else in the buffer: a resident server
        sees one NEW track per request forever, so past the event cap
        no further tracks (or their metadata rows) are allocated —
        returns None and the would-be events count as dropped. An
        unbounded track table would be exactly the slow host-memory
        leak the cap exists to prevent."""
        trace_id = str(trace_id)
        with self._lock:
            tid = self._tracks.get(trace_id)
            if tid is None:
                if len(self._events) >= self._max:
                    self._dropped += 1
                    return None
                tid = self._tracks[trace_id] = self._next_track
                self._next_track += 1
                # the name row is what makes the track readable; it is
                # appended under the same cap check above
                meta = {
                    "name": "thread_name", "ph": "M", "pid": os.getpid(),
                    "tid": tid, "args": {"name": f"request {trace_id}"},
                }
                self._events.append(meta)
                self._track_events[tid] = [meta]
            return tid

    def add_request_complete(self, trace_id: str, name: str, start: float,
                             end: float, args: Optional[dict] = None
                             ) -> None:
        """A complete span on ``trace_id``'s track, from perf_counter
        ``start`` to ``end`` (retroactive emission is fine — queue-wait
        spans are only known complete at dispatch)."""
        tid = self.request_track(trace_id)
        if tid is None:  # buffer saturated: already counted as dropped
            return
        merged = {"trace": str(trace_id)}
        if args:
            merged.update(args)
        self.add_complete(name, "request", start, max(end - start, 0.0),
                          tid, merged, _track=True)

    def add_request_instant(self, trace_id: str, name: str,
                            args: Optional[dict] = None) -> None:
        tid = self.request_track(trace_id)
        if tid is None:
            return
        merged = {"trace": str(trace_id)}
        if args:
            merged.update(args)
        self.add_instant(name, "request", tid, merged, _track=True)

    def request_events(self, trace_id: str) -> Optional[dict]:
        """One trace id's section of the buffer as a standalone Chrome
        trace-event object (Perfetto-loadable), or None when the trace
        id owns no track. Reads the per-track index — O(this track's
        events), never a scan of the whole buffer. Note the unit is the
        TRACE id: a client that deliberately reuses one id across
        requests (distributed-tracing propagation) gets all of them on
        one track, and every per-request publish of that id carries the
        whole track — that is the grouping semantics trace propagation
        asks for, not a leak."""
        with self._lock:
            tid = self._tracks.get(str(trace_id))
            if tid is None:
                return None
            events = [dict(e) for e in self._track_events.get(tid, ())]
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {"tool": "sartsolve", "pid": os.getpid(),
                          "trace": str(trace_id)},
        }

    def beacon(self, phase: str, serial: int, _t: float, ident: int) -> None:
        """Beacon-tap target: fold the watchdog's phase stream into
        per-thread phase spans. The beacon's own monotonic clock is not
        reused — spans need perf_counter deltas on this buffer's epoch —
        a beacon marks "phase X starts now", which is also "previous
        phase of this thread ends now"."""
        now = time.perf_counter()
        with self._lock:
            prev = self._open.get(ident)
            if prev is not None:
                name, t0 = prev
                self._append_locked({
                    "name": name, "cat": "beacon", "ph": "X",
                    "pid": os.getpid(), "tid": ident,
                    "ts": self._us(t0), "dur": (now - t0) * 1e6,
                })
            self._open[ident] = (phase, now)

    def close_open_spans(self) -> None:
        """Flush still-open per-thread phase spans (end-of-run)."""
        now = time.perf_counter()
        with self._lock:
            for ident, (name, t0) in self._open.items():
                self._append_locked({
                    "name": name, "cat": "beacon", "ph": "X",
                    "pid": os.getpid(), "tid": ident,
                    "ts": self._us(t0), "dur": (now - t0) * 1e6,
                })
            self._open.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def to_chrome(self) -> dict:
        """Chrome trace-event JSON object (Perfetto-loadable)."""
        with self._lock:
            events = list(self._events)
            dropped = self._dropped
        other = {"tool": "sartsolve", "pid": os.getpid()}
        if dropped:
            other["dropped_events"] = dropped
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": other,
        }

    def write_json(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)


# Module-global active buffer; None = tracing disabled (the default).
_buffer: Optional[TraceBuffer] = None


def active_buffer() -> Optional[TraceBuffer]:
    return _buffer


def install(buffer: TraceBuffer) -> TraceBuffer:
    """Activate ``buffer`` and tap the watchdog beacon stream into it."""
    global _buffer
    _buffer = buffer
    from sartsolver_tpu.resilience import watchdog

    watchdog.set_beacon_tap(buffer.beacon)
    return buffer


def uninstall() -> None:
    global _buffer
    _buffer = None
    from sartsolver_tpu.resilience import watchdog

    watchdog.set_beacon_tap(None)


def _profiler_annotation():
    """``jax.profiler.TraceAnnotation`` while jax is imported and its
    profiler is capturing, else None."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return None
    annotation = profiler.TraceAnnotation
    return annotation if annotation.is_enabled() else None


def span(name: str, cat: str = "host", **args):
    """Context manager recording ``name`` as a complete trace event in
    the buffer and, while the profiler captures, on its host plane.

    Returns a shared no-op object when both are off — safe (and cheap)
    to leave in production code paths, like the beacons.
    """
    buf = _buffer
    annotation = _profiler_annotation()
    if annotation is not None:
        annotation = annotation(name, **args)
    if buf is None:
        return _NULL_SPAN if annotation is None else annotation
    return _Span(buf, name, cat, args, annotation)


class _RequestSpan:
    """Span recorded on one request's track (serving engine)."""

    def __init__(self, buffer: "TraceBuffer", trace_id: str, name: str,
                 args: Dict[str, object]):
        self._buffer = buffer
        self._trace_id = trace_id
        self._name = name
        self._args = args
        self._t0 = 0.0

    def __enter__(self) -> "_RequestSpan":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self._buffer.add_request_complete(
            self._trace_id, self._name, self._t0, time.perf_counter(),
            self._args,
        )


def request_span(trace_id: Optional[str], name: str, **args):
    """Context manager recording ``name`` on ``trace_id``'s request
    track; the shared no-op when tracing is disabled or the id is
    falsy (one None check on the hot path, like :func:`span`)."""
    buf = _buffer
    if buf is None or not trace_id:
        return _NULL_SPAN
    return _RequestSpan(buf, str(trace_id), name, args)


def request_instant(trace_id: Optional[str], name: str, **args) -> None:
    """Instant event on a request track; no-op when disabled."""
    buf = _buffer
    if buf is not None and trace_id:
        buf.add_request_instant(str(trace_id), name, args)


def request_complete(trace_id: Optional[str], name: str, start: float,
                     end: float, **args) -> None:
    """Retroactive complete span on a request track from perf_counter
    ``start`` to ``end`` (queue-wait is only known at dispatch);
    no-op when disabled."""
    buf = _buffer
    if buf is not None and trace_id:
        buf.add_request_complete(str(trace_id), name, start, end, args)


def request_trace(trace_id: Optional[str]) -> Optional[dict]:
    """The active buffer's section for ``trace_id`` as a standalone
    Chrome trace object, or None (disabled / unknown id)."""
    buf = _buffer
    if buf is None or not trace_id:
        return None
    return buf.request_events(str(trace_id))

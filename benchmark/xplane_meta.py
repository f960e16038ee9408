"""What an ``.xplane.pb`` says about each device operation, read with the
standard library alone.

Every event of a device plane points at an event-metadata entry whose name
is the operation's HLO text (what ``jax.profiler.ProfileData`` gives as
``e.name``). That entry carries a ``tf_op`` stat: the JAX ``op_name`` path of
the operation, with the ``jax.named_scope`` names it was traced under, e.g.
``jit(run)/while/body/sart.laplacian/scatter-add:``. ``ProfileData`` does
not expose these stats, so this module decodes the protobuf wire format of
the few messages it needs (tensorflow/tsl ``xplane.proto``):

    XSpace         planes=1
    XPlane         id=1 name=2 lines=3 event_metadata=4 (map) stat_metadata=5 (map)
    XLine          name=2 timestamp_ns=3 events=4
    XEvent         metadata_id=1 offset_ps=2 duration_ps=3
    XEventMetadata id=1 name=2 stats=5
    XStatMetadata  id=1 name=2
    XStat          metadata_id=1 str_value=5 ref_value=7

A ``ref_value`` names an ``XStatMetadata`` entry whose name is the string.
"""

from __future__ import annotations

import os
import struct

from benchmark import profile_trace

TF_OP = "tf_op"
DEVICE_PREFIX = "/device:"
# the solver core's named scopes (models/sart.py), one per layer
SCOPES = ("sart.laplacian", "sart.sweep")


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo=0, hi=None):
    """``(field number, wire type, value)`` of one message's fields; a
    length-delimited value is a ``(start, end)`` slice of ``buf``."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 1:
            v, i = struct.unpack_from("<q", buf, i)[0], i + 8
        elif wt == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wt == 5:
            v, i = struct.unpack_from("<i", buf, i)[0], i + 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}")
        yield num, wt, v


def _str(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, span):
    """The value message of one protobuf map entry (key=1, value=2)."""
    for num, wt, v in _fields(buf, *span):
        if num == 2 and wt == 2:
            return v
    return None


def _plane(buf, span):
    """(name, {metadata id: (name, {stat id: str or ref})}, {stat id:
    name}, [line slices])."""
    name, events, stats, lines = "", {}, {}, []
    for num, wt, v in _fields(buf, *span):
        if num == 2 and wt == 2:
            name = _str(buf, v)
        elif num == 3 and wt == 2:
            lines.append(v)
        elif num == 4 and wt == 2:
            m = _map_values(buf, v)
            if m is None:
                continue
            mid, mname, mstats = 0, "", {}
            for n2, w2, v2 in _fields(buf, *m):
                if n2 == 1 and w2 == 0:
                    mid = v2
                elif n2 == 2 and w2 == 2:
                    mname = _str(buf, v2)
                elif n2 == 5 and w2 == 2:
                    sid, val = 0, None
                    for n3, w3, v3 in _fields(buf, *v2):
                        if n3 == 1 and w3 == 0:
                            sid = v3
                        elif n3 == 5 and w3 == 2:
                            val = _str(buf, v3)
                        elif n3 == 7 and w3 == 0:
                            val = ("ref", v3)
                    mstats[sid] = val
            events[mid] = (mname, mstats)
        elif num == 5 and wt == 2:
            m = _map_values(buf, v)
            if m is None:
                continue
            sid, sname = 0, ""
            for n2, w2, v2 in _fields(buf, *m):
                if n2 == 1 and w2 == 0:
                    sid = v2
                elif n2 == 2 and w2 == 2:
                    sname = _str(buf, v2)
            stats[sid] = sname
    return name, events, stats, lines


def _tf_ops(events, stats):
    """{metadata id: (HLO text, tf_op path)} of one plane's event
    metadata that carries a ``tf_op`` stat."""
    tf_ids = {sid for sid, n in stats.items() if n == TF_OP}
    out = {}
    for mid, (mname, mstats) in events.items():
        for sid, val in mstats.items():
            if sid in tf_ids and val is not None:
                if isinstance(val, tuple):
                    val = stats.get(val[1])
                if val is not None:
                    out[mid] = (mname, val)
    return out


def _device_planes(buf):
    for num, wt, v in _fields(buf):
        if num == 1 and wt == 2:
            plane = _plane(buf, v)
            if plane[0].startswith(DEVICE_PREFIX):
                yield plane


def _read(path):
    with open(path, "rb") as f:
        return memoryview(f.read())


def tf_ops(path: str) -> dict:
    """{event-metadata name (an operation's HLO text) -> ``tf_op`` path}
    over every device plane of the ``.xplane.pb`` at ``path``."""
    out = {}
    for _, events, stats, _ in _device_planes(_read(path)):
        out.update(_tf_ops(events, stats).values())
    return out


def scoped_ops(path: str) -> dict:
    """{device plane -> [(start_ns, end_ns, tf_op path)]}: each plane's
    operations on its ``XLA Ops`` line, control flow left out (a while
    loop's event spans its body's), on the clock of ``ProfileData``'s
    ``start_ns``. An operation with no ``tf_op`` stat gives ``""``."""
    buf = _read(path)
    out = {}
    for name, events, stats, lines in _device_planes(buf):
        named = _tf_ops(events, stats)
        ops = []
        for span in lines:
            lname, ts, evs = "", 0, []
            for num, wt, v in _fields(buf, *span):
                if num == 2 and wt == 2:
                    lname = _str(buf, v)
                elif num == 3 and wt == 0:
                    ts = v
                elif num == 4 and wt == 2:
                    evs.append(v)
            if lname != profile_trace.OPS_LINE:
                continue
            for ev in evs:
                mid = off = dur = 0
                for num, wt, v in _fields(buf, *ev):
                    if num == 1 and wt == 0:
                        mid = v
                    elif num == 2 and wt == 0:
                        off = v
                    elif num == 3 and wt == 0:
                        dur = v
                hlo, tf_op = named.get(mid, (events.get(mid, ("",))[0], ""))
                if profile_trace.op_kind(hlo) in profile_trace.CONTAINERS:
                    continue
                start = ts + off // 1000  # whole ns, as ProfileData has it
                ops.append((start, start + dur // 1000, tf_op))
        out[name] = ops
    return out


def layer_of(path: str):
    """The layer scope an operation's ``tf_op`` path was traced under:
    the innermost of ``SCOPES`` in it (the ordered-subsets sweep
    computes the penalty inside the sweep), or None."""
    best, at = None, -1
    for scope in SCOPES:
        i = path.rfind(f"/{scope}/")
        if i > at:
            best, at = scope, i
    return best


def scope_ns(ops, window, scope: str) -> float:
    """Device time, in ns, inside ``window`` of the operations in ``ops``
    (``[(start_ns, end_ns, tf_op path)]``) of the layer ``scope``;
    overlapping operations count once."""
    hits = [(s, e) for s, e, path in ops if layer_of(path) == scope]
    return profile_trace.total(profile_trace.clip(profile_trace.merge(hits),
                                                  *window))


def traced_ops(run):
    """(the busiest chip's operations, the traced window) of a
    ``--trace 1`` run, or None without a trace."""
    data = run.trace
    if data is None or not data.chips:
        return None
    trace_dir = os.path.join(run.cell.bench_dir, ".out", "trace",
                             run.cell.name)
    ops = scoped_ops(profile_trace.find_xplane(trace_dir))
    return ops.get(profile_trace.busiest_chip(data), []), data.window

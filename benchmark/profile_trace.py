"""Reduction of a profiler trace to device intervals and host spans.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
Its device planes (``/device:TPU:<n>``) carry an ``XLA Ops`` line with one
event per operation run; its host planes carry the harness's own spans,
written with ``jax.profiler.TraceAnnotation``. Everything here works on
plain ``(start_ns, end_ns, name)`` tuples, so tests feed it synthetic lists.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.traced"
HOST_SPANS = ("frames.stage", "solve.dispatch", "solution.fetch")
OPS_LINE = "XLA Ops"
# control flow whose event spans the operations of its body
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r"\b([a-z][a-z0-9-]*)\(")


@dataclass
class TraceData:
    window: tuple  # (start_ns, end_ns) of the traced segment
    chips: dict  # device plane name -> [(start_ns, end_ns, op name)]
    host: list = field(default_factory=list)  # [(start_ns, end_ns, span)]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def short_name(name: str) -> str:
    """``%fusion.102 fusion`` for the HLO text the trace names an op by:
    the instruction, its opcode and, for a custom call, its target."""
    head, _, rest = name.partition(" = ")
    m = _OPCODE.search(rest)
    out = f"{head} {m.group(1)}" if m else head
    t = re.search(r'custom_call_target="([^"]+)"', rest)
    return f"{out} {t.group(1)}" if t else out


def op_kind(name: str) -> str:
    return name.partition(" = ")[0].lstrip("%").split(".")[0]


def leaf_ops(events):
    """Drop the control-flow events (a while loop spans its body's
    operations), keeping the operations that ran, under short names."""
    return [(s, e, short_name(n)) for s, e, n in events
            if op_kind(n) not in CONTAINERS]


def merge(intervals):
    """Union of ``(start, end, ...)`` intervals as sorted ``[(s, e)]``."""
    out = []
    for s, e, *_ in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(merged, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def total(merged) -> float:
    return float(sum(e - s for s, e in merged))


def subtract(a, b):
    """Merged intervals ``a`` minus merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(merged, lo, hi):
    """The idle intervals of ``[lo, hi]`` between merged busy intervals."""
    out, cur = [], lo
    for s, e in clip(merged, lo, hi):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def busy_ns(data: TraceData, chip: str) -> float:
    lo, hi = data.window
    return total(clip(merge(data.chips[chip]), lo, hi))


def busiest_chip(data: TraceData) -> str:
    return max(data.chips, key=lambda c: busy_ns(data, c))


def top_ops(data: TraceData, n: int = 10):
    """[[op name, seconds]] of the operations that took most device time,
    averaged over the chips."""
    acc = {}
    lo, hi = data.window
    for ops in data.chips.values():
        for s, e, name in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                acc[name] = acc.get(name, 0.0) + d
    k = max(len(data.chips), 1)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9 / k] for name, ns in ranked]


def idle_gaps(data: TraceData, n: int = 10):
    """[[host span, seconds]] of the longest idle gaps of the busiest chip,
    each named by the harness span that overlaps it most ("none" where the
    host was in none of them)."""
    lo, hi = data.window
    chip = busiest_chip(data)
    out = []
    for s, e in gaps(merge(data.chips[chip]), lo, hi):
        best, name = 0, "none"
        for hs, he, hn in data.host:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, name = ov, hn
        out.append([name, (e - s) * 1e-9])
    out.sort(key=lambda x: -x[1])
    return out[:n]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> TraceData:
    """Read an ``.xplane.pb``: every device plane's operations and the
    harness's host spans; the window is the ``bench.traced`` span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    chips, host, window = {}, [], None
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    chips[plane.name] = leaf_ops(
                        [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name in HOST_SPANS:
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span")
    return TraceData(window=window, chips=chips, host=host)

"""Reduce a profiler trace of the measured window to device times.

The trace is first cut down to :class:`Event` records: every operation
on a device plane's ``XLA Ops`` line, named by its HLO instruction, and
the harness's own host spans (``jax.profiler.TraceAnnotation``:
``window``, ``call``, ``wait``, ...). :func:`reduce_events` then works
per device, inside the ``window`` span, on the ops that hold no other
(a ``while`` spans its body's ops on the same line):

- busy: the union of the operations' intervals;
- kernel: the summed time of operations whose name holds the kernel's;
- collective: the union of collective operations (``collective-permute``,
  ``all-reduce``, ...); exposed is the part of it during which no other
  operation runs on that device;
- other: busy time outside the kernel and outside collectives;
- idle gaps: the window minus busy, each named by the innermost host
  span open at its middle;
- device ops: the summed time of each op name.

Times come back in seconds, averaged over the devices traced.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
HOST_SPANS = ("window", "setup", "call", "wait", "readback")
COLLECTIVE = re.compile(r"^(collective-permute|all-reduce|all-gather|"
                        r"reduce-scatter|all-to-all|send|recv)")
HLO_NAME = re.compile(r"^%?([^\s=%]+) = ")


def short_name(name: str) -> str:
    """An op's HLO instruction name: ``%copy.9 = f32[...] copy(...)``
    gives ``copy.9``; other names are kept."""
    m = HLO_NAME.match(name)
    return m.group(1) if m else name


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Reduction:
    """Per-device means over the traced window, in seconds."""
    devices: int
    window_s: float
    busy_s: float
    kernel_s: float
    collective_s: float
    exposed_collective_s: float
    other_s: float
    device_ops: list    # [[name, seconds], ...], most time first
    idle_gaps: list     # [[host span, seconds], ...], longest first


def load_xplane(path) -> list[Event]:
    """The events of one ``.xplane.pb`` that the reduction reads."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        host = plane.name.startswith("/host:")
        if not (device or host):
            continue
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for e in line.events:
                if device or e.name in HOST_SPANS:
                    out.append(Event(plane.name, line.name, short_name(e.name),
                                     float(e.start_ns), float(e.duration_ns)))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged, non-empty (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(merged) -> float:
    return sum(e - s for s, e in merged)


def minus(a, b) -> list[tuple[float, float]]:
    """Merged intervals ``a`` with merged intervals ``b`` cut out."""
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


def _clip(events, lo, hi):
    return [(max(ev.start_ns, lo), min(ev.end_ns, hi), ev.name)
            for ev in events if ev.end_ns > lo and ev.start_ns < hi]


def leaves(clipped):
    """The ops that hold no other op: a ``while`` or ``conditional``
    spans the ops of its body on the same line, and counting it would
    cover their gaps and the collectives among them."""
    order = sorted(clipped, key=lambda iv: (iv[0], -iv[1]))
    holds = [False] * len(order)
    stack: list[int] = []
    for i, (s, e, _) in enumerate(order):
        while stack and order[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= order[stack[-1]][1]:
            holds[stack[-1]] = True
        stack.append(i)
    return [iv for i, iv in enumerate(order) if not holds[i]]


def _span_at(spans, t) -> str:
    """Innermost host span holding time ``t``."""
    best = None
    for ev in spans:
        if ev.start_ns <= t < ev.end_ns and (best is None or ev.dur_ns < best.dur_ns):
            best = ev
    return best.name if best is not None else "none"


def reduce_events(events, kernel: str, top: int = 10) -> Reduction:
    """Reduce the events of one traced window (see the module docstring)."""
    spans = [e for e in events if not DEVICE_PLANE.match(e.plane)]
    ops = defaultdict(list)
    for e in events:
        if DEVICE_PLANE.match(e.plane):
            ops[e.plane].append(e)
    windows = [e for e in spans if e.name == "window"]
    if windows:
        lo, hi = windows[0].start_ns, windows[0].end_ns
    else:
        dev = [e for evs in ops.values() for e in evs]
        if not dev:
            raise ValueError("the trace holds neither a window span nor a device op")
        lo, hi = min(e.start_ns for e in dev), max(e.end_ns for e in dev)
    n = max(len(ops), 1)
    busy = kern = coll = exposed = other = 0.0
    by_name: dict[str, float] = defaultdict(float)
    gaps = []
    for plane_ops in ops.values():
        clipped = leaves(_clip(plane_ops, lo, hi))
        every = union((s, e) for s, e, _ in clipped)
        k_iv = [(s, e) for s, e, name in clipped if kernel in name]
        c_iv = union((s, e) for s, e, name in clipped if COLLECTIVE.match(name))
        rest = union((s, e) for s, e, name in clipped if not COLLECTIVE.match(name))
        busy += measure(every)
        kern += sum(e - s for s, e in k_iv)
        coll += measure(c_iv)
        exposed += measure(minus(c_iv, rest))
        other += measure(minus(every, union(k_iv + c_iv)))
        for s, e, name in clipped:
            by_name[name] += e - s
        gaps += minus([(lo, hi)], every)
    gaps.sort(key=lambda g: g[0] - g[1])
    ns = 1e-9
    return Reduction(
        devices=len(ops), window_s=(hi - lo) * ns,
        busy_s=busy * ns / n, kernel_s=kern * ns / n,
        collective_s=coll * ns / n, exposed_collective_s=exposed * ns / n,
        other_s=other * ns / n,
        device_ops=[[name, t * ns / n] for name, t in
                    sorted(by_name.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[_span_at(spans, (s + e) / 2), (e - s) * ns]
                   for s, e in gaps[:top]])

"""Device time of a traced window by the program's named scopes, and the
bytes its collectives carry.

The mesh program names its device work with ``jax.named_scope``
(``sfc.pack``, ``sfc.unpack``, ``sfc.shell``). The profiler records no
``op_name`` on a device op, so an op's scope comes from the compiled
program's HLO text (``compiled.as_text()``), joined on the instruction
name for the ops that run inside that program's executions (the
device's ``XLA Modules`` line). A collective's payload is its result
array, counted once, at the ``-done`` of an asynchronous pair.

``split`` shares out ``tracered``'s ``other`` time (busy, outside the
kernel and outside collectives) among the innermost ``sfc.*`` scopes,
``""`` for the unscoped rest, per device mean. It leaves out the ops of
no duration first: the profiler records some (``custom-call.*``) at the
very start of a real op, and ``tracered.leaves`` takes such a pair for a
loop holding its body and drops the real op.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import asdict, dataclass

from . import tracered

MODULES_LINE = "XLA Modules"
SCOPE_PREFIX = "sfc."
# one instruction of HLO text: name, result shape, opcode
HLO_OP = re.compile(r"^\s*(?:ROOT )?%?([^\s=%]+) = (.*?) ([\w-]+)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
HLO_MODULE = re.compile(r"^HloModule ([^\s,]+)")
ARRAY = re.compile(r"^([a-z]+\d*)\[([\d,]*)\]")
DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
               "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8}


def scope_of(op_name: str) -> str:
    """The innermost ``sfc.*`` scope of an ``op_name``
    (``jit(f)/sfc.shell/sfc.pack/gather`` gives ``sfc.pack``), or ``""``.
    Where XLA merged the metadata of several ops (``a;b``) the first,
    which a fusion takes from its root, decides."""
    parts = op_name.split(";")[0].split("/")
    return next((p for p in reversed(parts) if p.startswith(SCOPE_PREFIX)), "")


def payload_bytes(shape: str, opcode: str) -> float:
    """Bytes a collective instruction carries: its result array. The
    ``-start`` half of an asynchronous pair counts nothing, so that each
    transfer counts once; other ops carry none."""
    m = ARRAY.match(shape)
    if not tracered.COLLECTIVE.match(opcode) or opcode.endswith("-start") or not m:
        return 0.0
    n = 1
    for d in filter(None, m.group(2).split(",")):
        n *= int(d)
    return float(n * DTYPE_BYTES[m.group(1)])


def hlo_ops(text: str) -> dict[str, tuple[str, float]]:
    """``{instruction: (scope, payload bytes)}`` of HLO text."""
    out = {}
    for line in text.splitlines():
        m = HLO_OP.match(line)
        if m:
            op = OP_NAME.search(line)
            out[m.group(1)] = (scope_of(op.group(1)) if op else "",
                               payload_bytes(m.group(2), m.group(3)))
    return out


@dataclass(frozen=True)
class ScopedEvent(tracered.Event):
    scope: str = ""         # innermost ``sfc.*`` scope of a device op
    bytes: float = 0.0      # payload of a collective op


@dataclass
class Split:
    trace: tracered.Reduction   # ``tracered``'s reduction, ops of no duration left out
    scope_s: dict               # {scope: seconds}, "" the unscoped rest
    collective_bytes: float


def load(path, hlo_text: str) -> list[tracered.Event]:
    """``tracered.load_xplane``'s events of one ``.xplane.pb``, each device
    op that ran inside an execution of the program of ``hlo_text`` with
    its scope and payload."""
    from jax.profiler import ProfileData

    program = hlo_ops(hlo_text)
    m = HLO_MODULE.match(hlo_text)
    runs = defaultdict(list)
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            if m and line.name == MODULES_LINE:
                runs[plane.name] += [(e.start_ns, e.start_ns + e.duration_ns)
                                     for e in line.events
                                     if e.name.startswith(m.group(1) + "(")]
    out = []
    for ev in tracered.load_xplane(path):
        if any(lo <= ev.start_ns < hi for lo, hi in runs[ev.plane]):
            scope, size = program.get(ev.name, ("", 0.0))
            ev = ScopedEvent(**asdict(ev), scope=scope, bytes=size)
        out.append(ev)
    return out


def split(events, kernel: str) -> Split:
    """Share out one traced window's ``other`` time by scope (see the
    module docstring)."""
    ops = defaultdict(list)
    host = []
    for e in events:
        if not tracered.DEVICE_PLANE.match(e.plane):
            host.append(e)
        elif e.dur_ns > 0:
            ops[e.plane].append(e)
    red = tracered.reduce_events(host + [e for evs in ops.values() for e in evs], kernel)
    windows = [e for e in host if e.name == "window"]
    if windows:
        lo, hi = windows[0].start_ns, windows[0].end_ns
    else:
        lo = min(e.start_ns for evs in ops.values() for e in evs)
        hi = max(e.end_ns for evs in ops.values() for e in evs)
    by_scope: dict[str, float] = defaultdict(float)
    sent = 0.0
    for plane_ops in ops.values():
        clipped = tracered.leaves([(max(e.start_ns, lo), min(e.end_ns, hi), e)
                                   for e in plane_ops if e.end_ns > lo and e.start_ns < hi])
        not_other = tracered.union(
            (s, t) for s, t, e in clipped
            if kernel in e.name or tracered.COLLECTIVE.match(e.name))
        scoped = defaultdict(list)
        for s, t, e in clipped:
            scoped[getattr(e, "scope", "")].append((s, t))
            sent += getattr(e, "bytes", 0.0)
        for scope, iv in scoped.items():
            by_scope[scope] += tracered.measure(tracered.minus(tracered.union(iv), not_other))
    n = max(len(ops), 1)
    return Split(trace=red,
                 scope_s={k: t * 1e-9 / n for k, t in by_scope.items() if t > 0},
                 collective_bytes=sent / n)

"""Reduce a profiler trace (``.xplane.pb``) to busy time, kernel time and
host self time.

The benchmark wraps each ``Engine.step`` in a ``TraceAnnotation`` named
``bench.step`` and the traced part of the window in one named
``bench.window``; both land on the host plane, on the profiler's clock,
beside the device's operations.  Everything below works on intervals
``(start_ns, end_ns)`` of that one clock.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

TPU_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Op:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    host: List[Op]              # every event of every host thread
    device: List[Op]            # operations that ran on the device
    planes: List[str]


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def tpu_ops(plane_name: str, line_name: str, event) -> bool:
    """Device operations on a TPU: the ``XLA Ops`` line of each device
    plane."""
    return plane_name.startswith(TPU_PLANE) and line_name == OPS_LINE


def load(path: str, is_device_op: Callable[[str, str, object], bool] = tpu_ops
         ) -> Trace:
    """Read a trace.  ``is_device_op(plane, line, event)`` says which
    events are device operations; the rest of the host planes' events are
    host spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host, device, planes = [], [], []
    for plane in data.planes:
        planes.append(plane.name)
        on_host = plane.name.startswith("/host:")
        for line in plane.lines:
            for ev in line.events:
                op = Op(ev.name, float(ev.start_ns), float(ev.end_ns))
                if is_device_op(plane.name, line.name, ev):
                    device.append(op)
                elif on_host:
                    host.append(op)
    device.sort(key=lambda o: o.start)
    host.sort(key=lambda o: o.start)
    return Trace(host=host, device=device, planes=planes)


def spans(trace: Trace, name: str) -> List[Interval]:
    return [(o.start, o.end) for o in trace.host if o.name == name]


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint union of ``intervals``."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        elif e > s:
            out.append((s, e))
    return out


def union_within(intervals: Sequence[Interval], lo: float, hi: float
                 ) -> float:
    """Length of the union of ``intervals`` clipped to ``[lo, hi]``."""
    return sum(min(e, hi) - max(s, lo) for s, e in merge(intervals)
               if e > lo and s < hi)


def busy_in(ops: Sequence[Op], windows: Sequence[Interval]) -> List[float]:
    """Per window: the union of device operation time inside it (ns)."""
    merged = merge([(o.start, o.end) for o in ops])
    ends = [e for _, e in merged]
    out = []
    for lo, hi in windows:
        total = 0.0
        for s, e in merged[bisect.bisect_right(ends, lo):]:
            if s >= hi:
                break
            total += min(e, hi) - max(s, lo)
        out.append(total)
    return out


def time_in(ops: Sequence[Op], windows: Sequence[Interval],
            match: Callable[[str], bool]) -> float:
    """Total duration (ns) of the matching operations that start inside
    one of ``windows`` (sorted and disjoint)."""
    starts = [lo for lo, _ in windows]
    total = 0.0
    for o in ops:
        if not match(o.name):
            continue
        j = bisect.bisect_right(starts, o.start) - 1
        if j >= 0 and o.start < windows[j][1]:
            total += o.end - o.start
    return total


_HLO = re.compile(r"%?(?P<name>[^ ]+) = (?P<rest>.*)")
_OPCODE = re.compile(r" (?P<op>[a-z][a-z0-9-]*)\(")
# control flow holds other operations: its time is theirs
CONTAINERS = ("while", "conditional", "call")


def _parse(name: str):
    m = _HLO.match(name)
    if m is None:
        return None
    rest = m.group("rest")
    op = _OPCODE.search(rest)
    shape = "tuple" if rest.startswith("(") else rest.split(" ")[0].split("{")[0]
    return m.group("name"), (op.group("op") if op else "?"), shape


def short_name(name: str) -> str:
    """``closed_call.13 custom-call bf16[512,3,3,64]`` for an operation the
    TPU trace names by its whole HLO instruction; other names as they are."""
    p = _parse(name)
    return name if p is None else " ".join(p)


def opcode(name: str) -> str:
    p = _parse(name)
    return "" if p is None else p[1]


def top_ops(ops: Sequence[Op], lo: float, hi: float, k: int = 10
            ) -> List[list]:
    """The ``k`` operations that took the most device time in ``[lo, hi]``,
    by short name, as ``[name, seconds]``; loops and calls, whose time is
    that of the operations inside them, are left out."""
    acc: Dict[str, float] = {}
    for o in ops:
        if o.start >= lo and o.end <= hi and opcode(o.name) not in CONTAINERS:
            n = short_name(o.name)
            acc[n] = acc.get(n, 0.0) + (o.end - o.start)
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, v * 1e-9] for n, v in best]


def idle_gaps(trace: Trace, lo: float, hi: float, k: int = 10,
              min_gap_ns: float = 1e3) -> List[list]:
    """Device idle time in ``[lo, hi]``, summed by what the host was doing
    at each gap's midpoint (the innermost host event covering it), as
    ``[name, seconds]`` for the ``k`` largest sums."""
    ivs = sorted((max(o.start, lo), min(o.end, hi)) for o in trace.device
                 if o.end > lo and o.start < hi)
    gaps, cur = [], lo
    for s, e in ivs:
        if s > cur + min_gap_ns:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur + min_gap_ns:
        gaps.append((cur, hi))
    host = [o for o in trace.host if o.end >= lo and o.start <= hi]
    host_starts = [o.start for o in host]
    acc: Dict[str, float] = {}
    for s, e in gaps:
        mid = 0.5 * (s + e)
        name = _innermost(host, host_starts, mid) or "(no host event)"
        acc[name] = acc.get(name, 0.0) + (e - s)
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, v * 1e-9] for n, v in best]


def _innermost(host: Sequence[Op], starts: Sequence[float], t: float
               ) -> Optional[str]:
    """The shortest host event covering ``t``, among the 256 host events
    that start last before it (nested events start late, so the innermost
    is among them)."""
    best = None
    j = bisect.bisect_right(starts, t)
    for o in reversed(host[max(0, j - 256):j]):
        if o.end >= t and (best is None or o.end - o.start
                           < best.end - best.start):
            best = o
    return best.name if best is not None else None

"""From the profiler's trace to device intervals, busy and idle time.

The trace is the ``.xplane.pb`` that ``jax.profiler.trace`` writes, read
through ``jax.profiler.ProfileData``. Device operations are the events
of the "XLA Ops" line of each ``/device:TPU:<n>`` plane; they nest (a
loop's event holds its body's), so busy time is a union and an
operation's own time excludes its children. Host spans are the
benchmark's ``bench.*`` annotations on the ``/host:CPU`` plane. Both are
on the profiler's clock, in nanoseconds from the trace's start.
"""

from __future__ import annotations

import collections
import dataclasses
import re
from pathlib import Path

from jax.profiler import ProfileData

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
SPAN_PREFIX = "bench."

Interval = tuple[float, float, str]  # (start_ns, end_ns, name)


@dataclasses.dataclass
class Trace:
    devices: dict[str, list[Interval]]  # plane name -> XLA ops
    spans: list[Interval]  # the benchmark's host spans

    @property
    def window(self) -> tuple[float, float]:
        """From the first host span's start to the last one's end."""
        return (min(s for s, _, _ in self.spans), max(e for _, e, _ in self.spans))

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) * 1e-9


def op_name(event_name: str) -> str:
    """'%fusion.12 = f32[..] fusion(..)' -> 'fusion.12'."""
    head = event_name.split(" ", 1)[0]
    return head[1:] if head.startswith("%") else head


def load(path: str | Path) -> Trace:
    data = ProfileData.from_file(str(path))
    devices, spans = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (e.start_ns, e.start_ns + e.duration_ns, op_name(e.name))
                        for e in line.events
                    ]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events if e.name.startswith(SPAN_PREFIX)]
    if not spans:
        raise ValueError(f"{path}: no {SPAN_PREFIX}* host spans")
    return Trace(devices, sorted(spans))


def merge(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Disjoint sorted union of ``intervals`` clipped to [lo, hi]."""
    out: list[list[float]] = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(merged) -> float:
    return sum(e - s for s, e in merged)


def subtract(a, b) -> list[tuple[float, float]]:
    """Parts of the disjoint sorted union ``a`` that ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def leaves_and_self(ops: list[Interval]) -> tuple[list[Interval], dict[str, float]]:
    """Operations that hold no other operation, and each name's own time
    (its duration less its direct children's) in nanoseconds. An operation
    that only overlaps another is its sibling, not its child."""
    order = sorted(ops, key=lambda o: (o[0], -o[1]))
    self_ns: dict[str, float] = collections.Counter()
    has_child = [False] * len(order)
    stack: list[int] = []
    for i, (s, e, name) in enumerate(order):
        while stack and order[stack[-1]][1] < e:  # not held by the top
            stack.pop()
        self_ns[name] += e - s
        if stack:
            p = stack[-1]
            has_child[p] = True
            self_ns[order[p][2]] -= e - s
        stack.append(i)
    leaves = [o for o, c in zip(order, has_child) if not c]
    return leaves, self_ns


def busy_s(trace: Trace) -> float:
    """Seconds in the window in which some operation ran, mean over devices."""
    lo, hi = trace.window
    per = [length(merge(ops, lo, hi)) for ops in trace.devices.values()]
    return sum(per) / len(per) * 1e-9


def idle_share(trace: Trace) -> float:
    return 1.0 - busy_s(trace) / trace.window_s


def exposed_share(trace: Trace, prefix: str) -> float:
    """Share of the window in which an operation named ``prefix``* runs on
    a device and no other operation does, mean over devices."""
    lo, hi = trace.window
    shares = []
    for ops in trace.devices.values():
        leaves, _ = leaves_and_self(ops)
        mine = merge([o for o in leaves if o[2].startswith(prefix)], lo, hi)
        other = merge([o for o in leaves if not o[2].startswith(prefix)], lo, hi)
        shares.append(length(subtract(mine, other)) / (hi - lo))
    return sum(shares) / len(shares)


def top_ops(trace: Trace, k: int = 10) -> list[list]:
    """The ``k`` operations with the most own time, seconds per device."""
    total: dict[str, float] = collections.Counter()
    for ops in trace.devices.values():
        lo, hi = trace.window
        _, own = leaves_and_self([o for o in ops if o[0] >= lo and o[1] <= hi])
        for name, ns in own.items():
            total[name] += ns
    n = len(trace.devices)
    return [[name, ns / n * 1e-9] for name, ns in
            sorted(total.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(trace: Trace, k: int = 10) -> list[list]:
    """The ``k`` longest idle gaps over all devices, each named by the host
    span that holds its midpoint ("none" where no span does)."""
    lo, hi = trace.window
    gaps = []
    for ops in trace.devices.values():
        gaps += subtract([(lo, hi)], merge(ops, lo, hi))
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = (s + e) / 2
        name = next((n for a, b, n in trace.spans if a <= mid < b), "none")
        out.append([name, (e - s) * 1e-9])
    return out

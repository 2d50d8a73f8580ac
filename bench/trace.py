"""Reduction of a profiler trace to device intervals and host spans.

A trace is read from the ``.xplane.pb`` file that ``jax.profiler`` writes.
Every device plane (``/device:TPU:<n>``) gives the operations that ran on
that chip, from its ``XLA Ops`` line; the host plane gives the benchmark's
own spans (``jax.profiler.TraceAnnotation``), on the same clock. The
functions below work on plain ``(name, start_ns, end_ns)`` tuples, so that
they can be checked on hand-made cases.

On a TPU the ``XLA Ops`` line nests: a ``while`` (a microbatch scan, a
layer loop) is one event, and the operations of its body are events inside
it. Busy time is the union of all of them; what ran inside what is read
from the leaves, the events that hold no other.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

# the compiler's op kinds that move data between chips
COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")
HOST_SPANS = ("input", "dispatch", "sync", "metrics_get")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass
class Trace:
    """Device operations per chip and host spans, in nanoseconds."""

    ops: dict  # chip index -> list of (name, start_ns, end_ns, category)
    spans: list  # list of (name, start_ns, end_ns)


def op_name(text: str) -> str:
    """An operation's name from the event's name, which on a TPU is the
    whole HLO instruction (``%fusion.3 = f32[...] fusion(%all-reduce.1,
    ...)``): the part before `` = ``, without the ``%``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def leaves(ops) -> list:
    """The operations that hold no other operation of the list."""
    order = sorted(ops, key=lambda o: (o[1], -o[2]))
    parent = [False] * len(order)
    stack = []  # indices of open operations, innermost last
    for i, (_, s, e, _) in enumerate(order):
        while stack and order[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= order[stack[-1]][2]:
            parent[stack[-1]] = True
        stack.append(i)
    return [o for o, p in zip(order, parent) if not p]


def is_collective(name: str, category: str = "") -> bool:
    """Whether an operation moves data between chips, by its op kind."""
    text = f"{category} {name}".lower()
    return any(k in text for k in COLLECTIVE_KINDS)


def load(path: str | Path, platform: str) -> Trace:
    """Read an ``.xplane.pb`` into a ``Trace``. On ``platform`` "cpu" (the
    tests), XLA's operations run on host threads and are read as one
    device's; on any other platform a trace without device planes is an
    error."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops, spans, host_ops = {}, [], []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            evs = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    stats = dict(e.stats)
                    cat = str(stats.get("hlo_category", ""))
                    evs.append((op_name(e.name), float(e.start_ns),
                                float(e.start_ns + e.duration_ns), cat))
            ops[int(m.group(1))] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    iv = (float(e.start_ns), float(e.start_ns + e.duration_ns))
                    if e.name in HOST_SPANS:
                        spans.append((e.name, *iv))
                    elif "hlo_op" in dict(e.stats):
                        host_ops.append((e.name, *iv, ""))
    if platform == "cpu":
        ops = {0: host_ops}
    elif not ops:
        raise ValueError(f"{path}: no plane /device:TPU:<n> in the trace")
    return Trace(ops=ops, spans=sorted(spans, key=lambda s: s[1]))


def describe(path: str | Path, per_line: int = 5) -> str:
    """Planes, lines and a few events of a trace, to look at by hand."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r} events={len(evs)}")
            for e in evs[:per_line]:
                out.append(f"    {e.name!r} start={e.start_ns} "
                           f"dur={e.duration_ns} stats={dict(e.stats)}")
    return "\n".join(out)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def merge(intervals) -> list:
    """Union of ``(start, end)`` pairs as sorted, disjoint pairs."""
    out: list = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(merged) -> float:
    return sum(e - s for s, e in merged)


def clip(intervals, lo: float, hi: float) -> list:
    """Intervals cut to the window ``[lo, hi]``."""
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def intersect(a, b) -> list:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(merged, lo: float, hi: float) -> list:
    """The parts of ``[lo, hi]`` that ``merged`` does not cover."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


# ---------------------------------------------------------------------------
# per-chip reductions over a window [lo, hi]
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ChipTimes:
    busy: float  # union of all operations
    compute: float  # busy time outside the exposed collective time
    collective: float  # union of collective operations
    exposed: float  # collective time during which no other leaf runs


def chip_times(ops, lo: float, hi: float) -> ChipTimes:
    """Busy, compute and collective time of one chip inside ``[lo, hi]``."""
    coll = merge(clip([(s, e) for n, s, e, c in ops if is_collective(n, c)],
                      lo, hi))
    comp = merge(clip([(s, e) for n, s, e, c in leaves(ops)
                       if not is_collective(n, c)], lo, hi))
    busy = length(merge(clip([(s, e) for _, s, e, _ in ops], lo, hi)))
    exposed = length(coll) - length(intersect(coll, comp))
    return ChipTimes(busy=busy, compute=busy - exposed,
                     collective=length(coll), exposed=exposed)


def mean_chip_times(trace: Trace, lo: float, hi: float) -> ChipTimes:
    """``chip_times`` averaged over the chips in the trace."""
    per = [chip_times(ops, lo, hi) for ops in trace.ops.values()]
    if not per:
        raise ValueError("the trace holds no device operations")
    n = len(per)
    return ChipTimes(*(sum(getattr(p, f.name) for p in per) / n
                       for f in dataclasses.fields(ChipTimes)))


def top_ops(trace: Trace, lo: float, hi: float, k: int = 10) -> list:
    """The ``k`` leaf operations that took most device time, in seconds per
    chip, summed over the window."""
    tot: dict = {}
    for ops in trace.ops.values():
        for n, s, e, _ in leaves(ops):
            d = min(e, hi) - max(s, lo)
            if d > 0:
                tot[n] = tot.get(n, 0.0) + d
    n_chips = max(len(trace.ops), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / n_chips * 1e-9] for name, ns in best]


def idle_gaps(trace: Trace, lo: float, hi: float, k: int = 10) -> list:
    """The ``k`` longest gaps in which chip 0 ran nothing, each named by the
    host span that covered most of it (``"none"`` where no span did)."""
    chip = min(trace.ops)
    busy = merge(clip([(s, e) for _, s, e, _ in trace.ops[chip]], lo, hi))
    out = []
    for gs, ge in gaps(busy, lo, hi):
        best, label = 0.0, "none"
        for name, s, e in trace.spans:
            ov = min(e, ge) - max(s, gs)
            if ov > best:
                best, label = ov, name
        out.append([label, (ge - gs) * 1e-9])
    return sorted(out, key=lambda g: -g[1])[:k]

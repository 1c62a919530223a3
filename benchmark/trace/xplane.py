"""Reduction of a profiler trace (`.xplane.pb`) to device time.

`read(path)` takes the device operations and the host's anchor event from
the trace with `jax.profiler.ProfileData`; everything after that works on
plain tuples, so the tests can hand-build traces. Times are nanoseconds
on the trace's clock. The benchmark writes an anchor (`ANCHOR`, a
`TraceAnnotation`) at a `time.perf_counter()` it records, which puts the
program's spans on the same clock.
"""

from __future__ import annotations

import json
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

ANCHOR = "deequ_bench_anchor"
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
# the line of a device plane that holds one event per operation run
OPS_LINE = "XLA Ops"
_COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?$"
)
_HLO = re.compile(r"^(%\S+) = .*?\b([a-z][a-z0-9_-]*)\(")

Interval = Tuple[float, float]


class Op(NamedTuple):
    name: str
    start: float
    end: float


def op_name(text: str) -> str:
    """An operation as the trace prints it (the whole HLO instruction)
    cut to its name and kind: `%fused.36 custom-call`."""
    m = _HLO.match(text)
    return f"{m.group(1)} {m.group(2)}" if m else text


def op_kind(name: str) -> str:
    return name.rsplit(" ", 1)[-1]


class Trace(NamedTuple):
    ops: Dict[int, List[Op]]  # device id -> operations, by start
    anchor_ns: Optional[float]  # the anchor event's start


def from_profile(pd) -> Trace:
    ops: Dict[int, List[Op]] = {}
    anchor = None
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(2))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(dev, []).extend(
                        Op(op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == ANCHOR and anchor is None:
                        anchor = e.start_ns
    for dev in ops:
        ops[dev].sort(key=lambda o: o.start)
    return Trace(ops, anchor)


def read(directory: str) -> Trace:
    """The trace `jax.profiler.start_trace(directory)` wrote."""
    from jax.profiler import ProfileData

    found = []
    for root, _dirs, files in os.walk(directory):
        found += [os.path.join(root, f) for f in files if f.endswith(".xplane.pb")]
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {directory}, found {found}")
    return from_profile(ProfileData.from_file(found[0]))


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    out = []
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint, sorted cover of the intervals."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def covered(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in union(intervals))


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The stretches of [lo, hi] that no busy interval covers."""
    out, t = [], lo
    for a, b in union(clip(busy, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


class Window(NamedTuple):
    """One device's reduction over the measured window."""

    busy_ns: float
    collective_ns: float
    op_ns: Dict[str, float]
    busy: List[Interval]


def reduce(trace: Trace, lo: float, hi: float) -> Dict[int, Window]:
    """Per device: busy time (union of operation intervals), time of
    collective operations (their union), and time per operation name,
    all within [lo, hi]."""
    out = {}
    for dev, ops in trace.ops.items():
        spans = clip(((o.start, o.end) for o in ops), lo, hi)
        per_op: Dict[str, float] = {}
        for o in ops:
            for a, b in clip([(o.start, o.end)], lo, hi):
                per_op[o.name] = per_op.get(o.name, 0.0) + (b - a)
        coll = clip(((o.start, o.end) for o in ops if _COLLECTIVE.match(op_kind(o.name))),
                    lo, hi)
        busy = union(spans)
        out[dev] = Window(covered(busy), covered(coll), per_op, busy)
    return out


def peaks(device_kind: str) -> dict:
    """The published peaks of `device_kind`; a device not in the table is
    an error, never a default."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table["devices"][device_kind]


def attribute(gap_list: Sequence[Interval],
              host_spans: Sequence[Tuple[str, float, float, int]]) -> List[Tuple[str, float]]:
    """Each gap as (what the host was doing, nanoseconds): the innermost
    host span (the deepest, then the latest to open) that covers the
    gap's midpoint, or "(no program span)" where none does.
    `host_spans` are (name, start, end, depth) on the trace's clock."""
    out = []
    for a, b in gap_list:
        mid = (a + b) / 2
        best = None
        for name, s, e, depth in host_spans:
            if s <= mid <= e and (best is None or (depth, s) >= (best[2], best[1])):
                best = (name, s, depth)
        out.append((best[0] if best else "(no program span)", b - a))
    return out

"""Reductions of the program's span forest (`deequ_tpu.observe` spans:
`name`, `cat`, `t0`/`t1` on `time.perf_counter`, `attrs`, `children`),
kept with the benchmark so every PR reads the spans the same way. They
read only those attributes, so the tests build forests of plain objects.
"""

from __future__ import annotations

from typing import Callable, Iterator, Tuple


def walk(roots, depth: int = 0) -> Iterator[Tuple[object, int]]:
    for s in roots:
        yield s, depth
        yield from walk(s.children, depth + 1)


def _self(s) -> float:
    own = max(s.t1 - s.t0, 0.0)
    return max(own - sum(max(c.t1 - c.t0, 0.0) for c in s.children), 0.0)


def self_seconds(roots, keep: Callable[[object], bool]):
    """Self time (duration less the children's) summed over the spans
    `keep` accepts; None when no span matched, so a reader finds
    nothing rather than a zero."""
    total, found = 0.0, False
    for s, _depth in walk(roots):
        if keep(s):
            total += _self(s)
            found = True
    return total if found else None


def of_category(*cats: str) -> Callable[[object], bool]:
    return lambda s: s.cat in cats


def attr_sum(roots, keep: Callable[[object], bool], key: str):
    """Sum of attribute `key` over the spans `keep` accepts that carry it;
    None when none does."""
    vals = [s.attrs[key] for s, _d in walk(roots) if keep(s) and key in s.attrs]
    return float(sum(vals)) if vals else None


def by_category(roots) -> dict:
    """Self seconds per span category ("other" where none is given)."""
    out: dict = {}
    for s, _depth in walk(roots):
        key = s.cat or "other"
        out[key] = out.get(key, 0.0) + _self(s)
    return out


def decoding(s) -> bool:
    """The read and decode stages: `read` and `decode` spans, and the
    stream pipeline's items of those stages."""
    return s.cat in ("read", "decode") or (
        s.name == "pipe_item" and s.attrs.get("stage") in ("read", "decode")
    )


def ms_per_mrow(run, seconds):
    """Milliseconds per million rows verified in the window; None where
    nothing was read."""
    return None if seconds is None or not run.rows else seconds * 1e3 / (run.rows / 1e6)


def ms_per_call(run, seconds):
    """Milliseconds per call (verdict) of the window; None where nothing
    was read."""
    return None if seconds is None or not run.calls else seconds * 1e3 / len(run.calls)

"""The plain reference for a chain of days whose states are carried: after
`add` of chain days 0..k it answers for all of them at once, as one pass
over their concatenation would, without keeping or re-reading the rows.

The same interface as `Reference` (`value`, `expected`, `rank_window`), so
`Scorecard.metric` holds the program to it unchanged. Numpy only; it
imports nothing of deequ_tpu. A wrap of the chain around its pool of days
adds a day again, and its rows count again. Running totals per column:

  counts, minima and maxima;
  mean and second central moment merged by Chan's formula (float64);
  for distinct counts, a boolean bitmap over each key's declared domain;
  for ranks, each pool day's sorted values and the times it was added, so
  a rank is the sum of multiplicity x searchsorted, and the value at a
  rank is found in one bin of a histogram over the column's domain.

`dtype=np.float32` computes every floating total one precision below the
float64 the guarantees are stated in: the control (`control_card`).
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np

from benchmark.data.tpch import Coded
from benchmark.reference.reference import Metric, Scorecard, assertion, expand

_TOTALS = ("Mean", "Sum", "StandardDeviation", "Minimum", "Maximum")
_BINS = 4096


class _Ranks:
    """Sorted values of each pool day, the times the chain added it, and
    a histogram of the chain's values over fixed bins."""

    def __init__(self, lo: float, hi: float):
        self.edges = np.concatenate([[-np.inf], np.linspace(lo, hi, _BINS - 1), [np.inf]])
        self.days: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        self.mult: Dict[int, int] = {}
        self.hist = np.zeros(_BINS, np.int64)

    def add(self, day: int, values: np.ndarray) -> None:
        if day not in self.days:
            s = np.sort(values)
            self.days[day] = (s, np.searchsorted(s, self.edges, "left"))
        self.mult[day] = self.mult.get(day, 0) + 1
        self.hist += np.diff(self.days[day][1])

    def count(self, value: float, side: str) -> int:
        return sum(m * int(np.searchsorted(self.days[d][0], value, side))
                   for d, m in self.mult.items())

    def kth(self, r: int) -> float:
        """The smallest value with at least `r` values at or below it."""
        cum = np.cumsum(self.hist)
        b = int(np.searchsorted(cum, r, "left"))
        below = int(cum[b - 1]) if b else 0
        parts = []
        for d, m in self.mult.items():
            s, starts = self.days[d]
            parts.extend([s[starts[b]:starts[b + 1]]] * m)
        return float(np.sort(np.concatenate(parts))[r - below - 1])


class CumulativeReference:
    """Exact answers for the concatenation of the days added so far."""

    def __init__(self, domains: Dict[str, dict], metrics: Sequence[Metric],
                 dtype=np.float64):
        self.domains = domains
        self.dtype = np.dtype(dtype)
        self.n = 0
        self.totals: Dict[str, list] = {}  # column -> [n, mean, m2, sum, min, max]
        self.seen: Dict[str, np.ndarray] = {}  # key column -> bitmap over its domain
        self.distinct: Dict[str, int] = {}
        self.ranks: Dict[str, _Ranks] = {}
        for m in metrics:
            fam, col = m.family, (m.columns[0] if m.columns else None)
            dom = domains.get(col, {})
            if fam in _TOTALS:
                self.totals.setdefault(col, None)
            elif fam == "ApproxCountDistinct":
                size = len(dom["values"]) if "values" in dom else dom["max"] - dom["min"] + 1
                self.seen.setdefault(col, np.zeros(int(size), bool))
                self.distinct.setdefault(col, 0)
            elif fam == "ApproxQuantile":
                self.ranks.setdefault(col, _Ranks(float(dom["min"]), float(dom["max"])))
            elif fam not in ("Size", "Completeness"):
                raise ValueError(f"no cumulative answer for {m}")

    def add(self, day: int, cols: dict) -> None:
        """Chain the rows of pool day `day` (columns as the generator makes
        them) onto the days added before."""
        self.n += len(next(iter(cols.values())))
        f = self.dtype.type
        for col in self.totals:
            x = np.asarray(cols[col], dtype=self.dtype)
            nb, mb = len(x), f(np.mean(x, dtype=self.dtype))
            m2b = f(np.sum((x - mb) ** 2, dtype=self.dtype))
            day_stats = [nb, mb, m2b, f(np.sum(x, dtype=self.dtype)), x.min(), x.max()]
            prev = self.totals[col]
            if prev is None:
                self.totals[col] = day_stats
                continue
            na, ma, m2a, sa, lo, hi = prev
            n = na + nb
            delta = f(mb - ma)
            self.totals[col] = [
                n, f(ma + delta * f(nb / n)), f(m2a + m2b + delta * delta * f(na * nb / n)),
                f(sa + day_stats[3]), min(lo, day_stats[4]), max(hi, day_stats[5]),
            ]
        for col, bitmap in self.seen.items():
            c = cols[col]
            if isinstance(c, Coded):
                idx = np.unique(c.codes)
            else:
                idx = np.unique(np.asarray(c)) - int(self.domains[col]["min"])
            new = idx[~bitmap[idx]]
            bitmap[new] = True
            self.distinct[col] += len(new)
        for col, ranks in self.ranks.items():
            ranks.add(day, np.asarray(cols[col], dtype=self.dtype))

    def value(self, m: Metric) -> float:
        fam = m.family
        if fam == "Size":
            return float(self.n)
        if fam == "Completeness":  # the generator makes no nulls
            return 1.0
        col = m.columns[0]
        if fam == "ApproxCountDistinct":
            return float(self.distinct[col])
        if fam == "ApproxQuantile":  # the value at rank ceil(q n)
            return self.ranks[col].kth(max(int(np.ceil(m.param * self.n)), 1))
        n, mean, m2, total, lo, hi = self.totals[col]
        return float({"Mean": mean, "Sum": total,
                      "StandardDeviation": np.sqrt(m2 / self.dtype.type(n)),
                      "Minimum": lo, "Maximum": hi}[fam])

    def expected(self, m: Metric, domains: Dict[str, dict]) -> bool:
        """Whether the reference passes `m`'s assertion."""
        return assertion(m, domains)(self.value(m))

    def rank_window(self, column: str, value: float) -> Tuple[float, float]:
        """Fraction of rows strictly below, and at or below, `value`."""
        r = self.ranks[column]
        return r.count(value, "left") / self.n, r.count(value, "right") / self.n


def chain_days(config: dict, seed: int, verdicts: int):
    """(pool day, columns) of chain days 0..verdicts-1: the gate's days,
    walked in order and wrapped around the pool."""
    from benchmark.data import tpch

    pool = int(config["days"])
    sizes = tpch.day_sizes(int(config["rows"]), pool)
    for k in range(verdicts):
        d = k % pool
        yield d, tpch.lineitem_day(int(sizes[d]), seed, d, float(config["scale"]))


def control_card(config: dict, traffic: dict, seed: int, verdicts: int) -> Scorecard:
    """The control: this reference with float32 totals put in the
    program's place for `verdicts` chain days, each answer held to the
    float64 reference by the configuration's limits."""
    domains = config["columns"]
    metrics = expand(traffic["check"], list(domains))
    exact = CumulativeReference(domains, metrics)
    low = CumulativeReference(domains, metrics, np.float32)
    card = Scorecard(config["guarantees"])
    for k, (d, cols) in enumerate(chain_days(config, seed, verdicts)):
        exact.add(d, cols)
        low.add(d, cols)
        for m in metrics:
            card.metric(m, low.value(m), exact, exact.value(m), f"verdict {k} {m.family}{m.columns}")
    return card

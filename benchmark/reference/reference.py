"""The plain reference and the scorecard that decides `correct`.

Copied from `chip_smoke.py` (PR 21) and cut loose from the program: it
imports nothing of deequ_tpu and reads only the generator's columns. Each
metric is named by a `Metric` (family, columns, parameter) that the
traffic drivers also use to build the program's analyzers, so the two
sides name one number the same way.

The limits are the configuration's stated guarantees (its `guarantees`
key): exact metrics exact, moments and correlation within a relative
tolerance, HyperLogLog within a multiple of its declared relative standard
deviation, KLL quantiles within their declared rank error.
"""

from __future__ import annotations

from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from benchmark.data.tpch import Coded

EXACT = ("Size", "Completeness", "Minimum", "Maximum", "Uniqueness", "Compliance")
MOMENTS = ("Mean", "Sum", "StandardDeviation")


class Metric(NamedTuple):
    """One metric as a check spec names it: `family` is the analyzer's
    class name; `param` is a quantile, or the allowed values of a
    Compliance (`is_contained_in`)."""

    family: str
    columns: Tuple[str, ...]
    param: object = None


def expand(spec: Sequence[dict], columns: Sequence[str]) -> List[Metric]:
    """A workload's check spec, one entry per metric family, as the list
    of metrics it names. `"columns": "*"` is every column of the table."""
    out: List[Metric] = []
    for item in spec:
        fam = item["metric"]
        cols = list(columns) if item.get("columns") == "*" else item.get("columns", [])
        if fam == "Size":
            out.append(Metric("Size", ()))
        elif fam in ("Correlation", "Uniqueness"):
            for group in item["groups"]:
                out.append(Metric(fam, tuple(group)))
        elif fam == "ApproxQuantile":
            for col, q in item["quantiles"]:
                out.append(Metric(fam, (col,), float(q)))
        elif fam == "Compliance":
            for col in cols:
                out.append(Metric(fam, (col,), "domain"))
        else:
            out.extend(Metric(fam, (c,)) for c in cols)
    return out


def assertion(metric: Metric, domains: Dict[str, dict]) -> Callable[[float], bool]:
    """The constraint a check puts on `metric`: what a data engineer
    asserts from the TPC-H domains in the configuration, so every sound
    table passes it."""
    fam = metric.family
    dom = domains.get(metric.columns[0], {}) if metric.columns else {}
    lo, hi = dom.get("min"), dom.get("max")
    if fam in ("Completeness", "Uniqueness", "Compliance"):
        return lambda v: v == 1.0
    if fam == "Size":
        return lambda v: v >= 1
    if fam in ("Mean", "Minimum", "Maximum", "ApproxQuantile"):
        return lambda v, lo=lo, hi=hi: lo <= v <= hi
    if fam == "StandardDeviation":
        return lambda v, span=hi - lo: 0.0 <= v <= span
    if fam == "Correlation":
        return lambda v: -1.0 <= v <= 1.0
    if fam == "ApproxCountDistinct":
        return lambda v: v >= 1
    if fam == "Sum":
        return lambda v: np.isfinite(v)
    raise ValueError(f"no assertion for {metric}")


# ---------------------------------------------------------------------------
# Reference values
# ---------------------------------------------------------------------------


def _f64(col) -> np.ndarray:
    return np.asarray(col, dtype=np.float64)


def _distinct(col) -> int:
    if isinstance(col, Coded):
        return int(np.count_nonzero(np.bincount(col.codes, minlength=len(col.values))))
    import pandas as pd

    return int(pd.unique(np.asarray(col)).size)


class Reference:
    """Exact answers from the raw columns of one table."""

    def __init__(self, cols: dict, domains: Dict[str, dict]):
        self.cols = cols
        self.domains = domains
        self.n = len(next(iter(cols.values())))
        self._sorted: Dict[str, np.ndarray] = {}
        self._memo: Dict[Metric, float] = {}

    def value(self, m: Metric) -> float:
        if m not in self._memo:
            self._memo[m] = self._value(m)
        return self._memo[m]

    def _value(self, m: Metric) -> float:
        fam, cols = m.family, m.columns
        if fam == "Size":
            return float(self.n)
        col = self.cols[cols[0]]
        if fam == "Completeness":  # the generator makes no nulls
            return 1.0
        if fam == "Compliance":
            allowed = set(self.domains[cols[0]]["values"])
            ok = np.array([v in allowed for v in col.values])
            return float(np.count_nonzero(ok[col.codes])) / self.n
        if fam == "ApproxCountDistinct":
            return float(_distinct(col))
        if fam == "Uniqueness":  # rows whose key no other row has
            import pandas as pd

            keys = pd.DataFrame({c: np.asarray(self.cols[c]) for c in cols})
            return float(np.count_nonzero(~keys.duplicated(keep=False))) / self.n
        if fam == "Correlation":
            return float(np.corrcoef(_f64(col), _f64(self.cols[cols[1]]))[0, 1])
        if fam == "ApproxQuantile":  # the value at rank ceil(q n)
            self.rank_window(cols[0], 0.0)
            s = self._sorted[cols[0]]
            return float(s[max(int(np.ceil(m.param * self.n)) - 1, 0)])
        x = _f64(col)
        return float(
            {"Mean": np.mean, "Sum": np.sum, "StandardDeviation": np.std,
             "Minimum": np.min, "Maximum": np.max}[fam](x)
        )

    def expected(self, m: Metric, domains: Dict[str, dict]) -> bool:
        """Whether the reference passes `m`'s assertion."""
        return assertion(m, domains)(self.value(m))

    def rank_window(self, column: str, value: float) -> Tuple[float, float]:
        """Fraction of rows strictly below, and at or below, `value`."""
        if column not in self._sorted:
            self._sorted[column] = np.sort(_f64(self.cols[column]))
        s = self._sorted[column]
        return (
            np.searchsorted(s, value, "left") / self.n,
            np.searchsorted(s, value, "right") / self.n,
        )


# ---------------------------------------------------------------------------
# Scorecard
# ---------------------------------------------------------------------------

# the numbers compared, each with the guarantee key that is its limit
COMPARED = (
    ("exact_abs_err", "exact"),
    ("moment_rel_err", "moments_rel"),
    ("corr_rel_err", "correlation_rel"),
    ("hll_rel_err", "hll_rel"),
    ("kll_rank_err", "kll_rank"),
    ("verdicts_wrong", "verdicts_wrong"),
    ("calls_failed", "calls_failed"),
)


class Scorecard:
    """Worst error per compared number; misses collected, not raised, so
    one run reports every one. A number no metric fed stays None."""

    def __init__(self, limits: Dict[str, float]):
        self.limits = {name: float(limits[key]) for name, key in COMPARED}
        self.worst: Dict[str, Optional[float]] = {name: None for name, _ in COMPARED}
        self.where: Dict[str, str] = {}
        self.failures: List[str] = []
        self.compared = 0

    def note(self, name: str, what: str, err: float) -> None:
        err = float(err)
        self.compared += 1
        if self.worst[name] is None or err > self.worst[name] or err != err:
            self.worst[name] = err
            self.where[name] = what
        if not err <= self.limits[name]:  # NaN fails
            self.failures.append(f"{name} {what}: {err!r} > {self.limits[name]!r}")

    def count(self, name: str, n: int, what: str = "") -> None:
        """Add `n` to a count, such as verdicts that differ."""
        self.compared += 1
        self.worst[name] = float(n) + (self.worst[name] or 0.0)
        if n:
            self.where.setdefault(name, what)
            if self.worst[name] > self.limits[name]:
                self.failures.append(f"{name}: {n} at {what}")

    def metric(self, m: Metric, got, ref: Reference, want: float, what: str,
               rel_err: Optional[float] = None) -> None:
        """Hold one program value to the reference by its family.
        `rel_err` is the KLL sketch's declared rank error."""
        fam = m.family
        if got is None:
            self.failures.append(f"{what}: no value")
            return
        got = float(got)
        if fam in EXACT:
            self.note("exact_abs_err", what, abs(got - want))
        elif fam in MOMENTS:
            self.note("moment_rel_err", what, abs(got - want) / max(abs(want), 1e-300))
        elif fam == "Correlation":
            self.note("corr_rel_err", what, abs(got - want) / max(abs(want), 1e-300))
        elif fam == "ApproxCountDistinct":
            self.note("hll_rel_err", what, abs(got - want) / max(want, 1.0))
        elif fam == "ApproxQuantile":
            below, at_or_below = ref.rank_window(m.columns[0], got)
            q = float(m.param)
            self.note("kll_rank_err", what, max(0.0, below - q, q - at_or_below))
        else:
            raise ValueError(f"no comparison for {m}")

    @property
    def ok(self) -> bool:
        return not self.failures and self.compared > 0

    def lines(self) -> List[str]:
        return [
            f"compared {name}: {self.worst[name]!r} limit {self.limits[name]!r}"
            + (f" ({self.where[name]})" if name in self.where else "")
            for name, _ in COMPARED
        ]

    def as_json(self) -> Dict[str, dict]:
        return {
            name: {"value": self.worst[name], "limit": self.limits[name]}
            for name, _ in COMPARED
        }


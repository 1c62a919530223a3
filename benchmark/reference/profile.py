"""The plain reference of a default column profile, and its comparison.

Upstream deequ's `ColumnProfilerRunner` with every default
(`profiles/ColumnProfiler.scala:81-188`): for every column its record
count, completeness and distinct count, its data type (the schema's, or
for a string column the one `DataType` infers) with the type-detection
counts; for numeric columns minimum, maximum, mean, sum, population
standard deviation and the percentiles 0.01..1.00; exact value histograms
for string columns with at most 120 distinct values.

Computed with numpy and pandas from the generator's columns; it imports
nothing of deequ_tpu. `fields` reads a program's `ColumnProfiles` by
attribute alone, so the comparison holds plain values.

The comparison feeds the benchmark's `Scorecard` under the
configuration's guarantees: exact (`exact_abs_err`) for counts,
completeness, min, max, data types, type counts, histogram entries and
ratios and which columns have a histogram; `moments_rel` for mean, sum
and standard deviation; `hll_rel` for distinct counts; `kll_rank` for the
rank of each percentile.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional

import numpy as np

from benchmark.data.tpch import Coded
from benchmark.reference.reference import Scorecard

THRESHOLD = 120  # low_cardinality_histogram_threshold's default
PERCENTILES = tuple(i / 100 for i in range(1, 101))

UNKNOWN, FRACTIONAL, INTEGRAL, BOOLEAN, STRING = (
    "Unknown", "Fractional", "Integral", "Boolean", "String",
)
NUMERIC = (INTEGRAL, FRACTIONAL)

# upstream catalyst/StatefulDataType.scala's patterns, tried in this order
# (a null is Unknown, a value none matches is String); Spark's `rlike` is
# Java's regex, whose \d is ASCII alone
_PATTERNS = (
    (FRACTIONAL, re.compile(r"^(-|\+)? ?\d*\.\d*$", re.ASCII)),
    (INTEGRAL, re.compile(r"^(-|\+)? ?\d*$", re.ASCII)),
    (BOOLEAN, re.compile(r"^(true|false)$", re.ASCII)),
)


def value_type(value: str) -> str:
    for name, pattern in _PATTERNS:
        if pattern.match(value):
            return name
    return STRING


def determine_type(counts: Dict[str, int]) -> str:
    """Upstream `DataType.determineType` (analyzers/DataType.scala),
    on counts rather than ratios: the decisions only ask which are
    nonzero."""
    total = sum(counts.values())
    if counts.get(UNKNOWN, 0) == total:
        return UNKNOWN
    if counts.get(STRING, 0) > 0 or (
        counts.get(BOOLEAN, 0) > 0
        and (counts.get(INTEGRAL, 0) > 0 or counts.get(FRACTIONAL, 0) > 0)
    ):
        return STRING
    if counts.get(BOOLEAN, 0) > 0:
        return BOOLEAN
    if counts.get(FRACTIONAL, 0) > 0:
        return FRACTIONAL
    return INTEGRAL


class _Column:
    """The exact profile of one column."""

    def __init__(self, col):
        n = len(col)
        if isinstance(col, Coded):  # a string column: codes into values
            counts = np.bincount(col.codes, minlength=len(col.values))
            types = {UNKNOWN: 0, FRACTIONAL: 0, INTEGRAL: 0, BOOLEAN: 0, STRING: 0}
            entries: Dict[str, int] = {}
            for value, count in zip(col.values, counts):
                if count:
                    types[value_type(value)] += int(count)
                    entries[str(value)] = entries.get(str(value), 0) + int(count)
            self.data_type = determine_type(types)
            self.inferred = True
            self.type_counts = types
            self.non_null = n  # the generator makes no nulls
            self.distinct = len(entries)
            # upstream takes the histogram columns by the HLL estimate; this
            # takes them by the exact count (section "histogram" of compare)
            self.histogram: Optional[Dict[str, int]] = (
                entries if self.data_type in (STRING, BOOLEAN)
                and self.distinct <= THRESHOLD else None
            )
            x = None
            if self.data_type in NUMERIC:  # an inferred-numeric string column
                x = np.array([float(v) for v in col.values])[col.codes]
        else:
            x = np.asarray(col)
            self.data_type = INTEGRAL if np.issubdtype(x.dtype, np.integer) else FRACTIONAL
            self.inferred = False
            self.type_counts = {}
            x = x.astype(np.float64)
            x = x[~np.isnan(x)]
            self.non_null = len(x)
            self.histogram = None
        self.completeness = self.non_null / n
        self.sorted = None
        if x is not None:
            self.sorted = np.sort(x)
            if not self.inferred:
                self.distinct = int(np.count_nonzero(np.diff(self.sorted))) + 1
            self.minimum = float(self.sorted[0])
            self.maximum = float(self.sorted[-1])
            self.sum = float(np.sum(x))
            self.mean = float(np.mean(x))
            self.std_dev = float(np.std(x))  # population, as upstream

    def rank_error(self, value: float, q: float) -> float:
        """How far `value`'s rank window (the share of values strictly
        below it, and at or below it) lies from `q`."""
        s = self.sorted
        below = np.searchsorted(s, value, "left") / len(s)
        at_or_below = np.searchsorted(s, value, "right") / len(s)
        return float(max(0.0, below - q, q - at_or_below))


def fields(profiles) -> dict:
    """The numbers a `ColumnProfiles` holds, as plain values: by
    attribute alone, so this reads any version of the program."""
    columns = {}
    for name, p in profiles.profiles.items():
        hist = None
        if p.histogram is not None:
            hist = {k: (int(v.absolute), float(v.ratio))
                    for k, v in p.histogram.values.items()}
        f = {
            "completeness": p.completeness,
            "distinct": p.approximate_num_distinct_values,
            "data_type": p.data_type,
            "inferred": p.is_data_type_inferred,
            "type_counts": dict(p.type_counts or {}),
            "histogram": hist,
        }
        for key in ("minimum", "maximum", "mean", "sum", "std_dev", "approx_percentiles"):
            if hasattr(p, key):
                f[key] = getattr(p, key)
        columns[name] = f
    return {"num_records": profiles.num_records, "columns": columns}


class ProfileReference:
    """Exact answers for every column of one table."""

    def __init__(self, cols: dict):
        self.n = len(next(iter(cols.values())))
        self.columns = {name: _Column(col) for name, col in cols.items()}

    def compare(self, got: dict, card: Scorecard, what: str) -> None:
        """Hold one profile (`fields` of the program's result) to the
        reference."""
        card.note("exact_abs_err", f"{what} num_records",
                  abs(float(got["num_records"]) - self.n))
        for name in sorted(set(got["columns"]) - set(self.columns)):
            card.failures.append(f"{what}: a profile of unknown column {name}")
        for name, ref in self.columns.items():
            p = got["columns"].get(name)
            if p is None:
                card.failures.append(f"{what}: no profile of column {name}")
                continue
            self._column(ref, p, card, f"{what} {name}")

    def _column(self, ref: _Column, p: dict, card: Scorecard, what: str) -> None:
        card.note("exact_abs_err", f"{what} completeness",
                  abs(float(p["completeness"]) - ref.completeness))
        card.note("hll_rel_err", f"{what} distinct",
                  abs(float(p["distinct"]) - ref.distinct) / max(ref.distinct, 1))
        card.note("exact_abs_err", f"{what} data_type {p['data_type']} inferred {p['inferred']}",
                  float(p["data_type"] != ref.data_type or p["inferred"] != ref.inferred))
        card.note("exact_abs_err", f"{what} type_counts", _count_diff(p["type_counts"],
                                                                      ref.type_counts))
        # histogram: upstream chooses the columns by their HLL estimate, the
        # reference by the exact distinct count. The two agree on lineitem:
        # its string columns hold 2-7 or at least 2,048 distinct values, far
        # from the threshold of 120 on either side for an estimate within
        # the guarantee's 0.30
        got_hist = p["histogram"]
        card.note("exact_abs_err", f"{what} has a histogram",
                  float((got_hist is None) != (ref.histogram is None)))
        if got_hist is not None and ref.histogram is not None:
            want = {k: (c, c / self.n) for k, c in ref.histogram.items()}
            differ = set(got_hist.items()) ^ set(want.items())
            card.note("exact_abs_err", f"{what} histogram entries that differ",
                      float(len(differ)))
        if ref.sorted is None:
            return
        for key in ("minimum", "maximum"):
            v = p.get(key)
            if v is None:
                card.failures.append(f"{what} {key}: no value")
            else:
                card.note("exact_abs_err", f"{what} {key}", abs(float(v) - getattr(ref, key)))
        for key in ("mean", "sum", "std_dev"):
            v = p.get(key)
            want = getattr(ref, key)
            if v is None:
                card.failures.append(f"{what} {key}: no value")
            else:
                card.note("moment_rel_err", f"{what} {key}",
                          abs(float(v) - want) / max(abs(want), 1e-300))
        got_q: List[float] = list(p.get("approx_percentiles") or ())
        if len(got_q) != len(PERCENTILES):
            card.failures.append(f"{what}: {len(got_q)} percentiles, not {len(PERCENTILES)}")
            return
        for q, v in zip(PERCENTILES, got_q):
            card.note("kll_rank_err", f"{what} percentile {q:g}", ref.rank_error(float(v), q))


def _count_diff(got: Dict[str, int], want: Dict[str, int]) -> float:
    """Counts apart, summed over both sides' keys; a key on one side
    alone counts at least one."""
    keys = set(got) | set(want)
    return float(sum(max(abs(int(got.get(k, 0)) - int(want.get(k, 0))),
                         int((k in got) != (k in want))) for k in keys))

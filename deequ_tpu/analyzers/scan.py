"""Scan-shareable analyzers: single-pass masked reductions.

Each analyzer's heavy work is a per-batch reduction expressed once, generic
over the array namespace (jnp on device, numpy float64 on the host fold) —
the same code path serves the fused XLA pass, the cross-device collective
merge, and the driver-side cross-batch fold. This replaces the reference's
Catalyst aggregate kernels (reference: analyzers/catalyst/, SURVEY.md §2.6)
and its per-analyzer `aggregationFunctions()` offsets
(reference: analyzers/Analyzer.scala:159-216).

Aggregate pytrees are dicts of scalars; all masks enter reductions as
multiplicative 0/1 factors so padded rows and filtered rows contribute
exactly nothing.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from deequ_tpu.analyzers.base import (
    InputSpec,
    Preconditions,
    ScanShareableAnalyzer,
    col_valid_spec,
    col_values_spec,
    predicate_input,
    render_where,
    where_key,
    where_spec,
)
from deequ_tpu.analyzers.states import (
    CorrelationState,
    DataTypeHistogram,
    MaxState,
    MeanState,
    MinState,
    NumMatches,
    NumMatchesAndCount,
    State,
    StandardDeviationState,
    SumState,
)
from deequ_tpu.core.maybe import Success
from deequ_tpu.core.metrics import (
    Distribution,
    DistributionValue,
    DoubleMetric,
    Entity,
    HistogramMetric,
    Metric,
)
from deequ_tpu.data.table import ColumnType, Table


def _f(xp, x):
    """Cast mask/ints to the float dtype reductions run in (no copy when
    already that dtype on the host path)."""
    if xp is np:
        return np.asarray(x).astype(np.result_type(0.0), copy=False)
    return xp.asarray(x).astype(xp.result_type(0.0))


# ---------------------------------------------------------------------------
# Size
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Size(ScanShareableAnalyzer):
    """# rows, optionally filtered (reference: analyzers/Size.scala:36)."""

    discrete_inputs = True  # mask-only: host-foldable under placement
    where: Optional[str] = None

    @property
    def name(self) -> str:
        return "Size"

    @property
    def instance(self) -> str:
        return "*"

    @property
    def entity(self) -> Entity:
        return Entity.DATASET

    def input_specs(self) -> List[InputSpec]:
        return [where_spec(self.where)]

    def device_reduce(self, inputs: Dict[str, Any], xp) -> Any:
        if xp is np and self.where is None:
            # host fold: unfiltered size is the (unpadded) batch length
            return {"n": float(inputs[where_key(None)].shape[0])}
        w = inputs[where_key(self.where)]
        if xp is np and np.asarray(w).dtype == np.bool_:
            return {"n": float(np.count_nonzero(w))}  # host fold fast path
        return {"n": xp.sum(_f(xp, w))}

    def merge_agg(self, a: Any, b: Any, xp) -> Any:
        return {"n": a["n"] + b["n"]}

    def state_from_aggregates(self, agg: Any) -> Optional[State]:
        return NumMatches(int(agg["n"]))

    def compute_metric_from(self, state: Optional[State]) -> Metric:
        if state is None:
            return self.empty_state_failure()
        return DoubleMetric(
            self.entity, self.name, self.instance, Success(state.metric_value())
        )

    def __repr__(self) -> str:
        return f"Size({render_where(self.where)})"


# ---------------------------------------------------------------------------
# Ratio analyzers: Completeness / Compliance / PatternMatch
# ---------------------------------------------------------------------------


class _RatioAnalyzer(ScanShareableAnalyzer):
    """matches/count with a guard leaf for the empty-state rule.

    The guard mirrors SQL `sum` nullability in the reference's aggregation
    expressions: the state is empty (None -> EmptyStateException) exactly
    when every row's criterion was NULL. For Completeness the criterion
    (`isNotNull(...)`) is never NULL, so the guard is "any row scanned"; for
    Compliance/PatternMatch non-matching `where` rows and NULL inputs make
    the criterion NULL, so the guard is "any row with where ∧ non-null
    input" (reference: analyzers/Completeness.scala:36-41,
    Compliance.scala:50, PatternMatch.scala:42-50)."""

    discrete_inputs = True  # mask-only: host-foldable under placement

    def _match_mask_key(self) -> str:
        raise NotImplementedError

    def _extra_specs(self) -> List[InputSpec]:
        raise NotImplementedError

    def _guard(self, inputs: Dict[str, Any], xp):
        """Mask of rows whose criterion is non-NULL."""
        raise NotImplementedError

    def input_specs(self) -> List[InputSpec]:
        return self._extra_specs() + [where_spec(self.where), where_spec(None)]

    def device_reduce(self, inputs: Dict[str, Any], xp) -> Any:
        w_raw = inputs[where_key(self.where)]
        m_raw = inputs[self._match_mask_key()]
        if (
            xp is np
            and np.asarray(w_raw).dtype == np.bool_
            and np.asarray(m_raw).dtype == np.bool_
        ):
            # host fold fast path: popcounts, no float materialization
            w_b = np.asarray(w_raw)
            guard = np.asarray(self._guard(inputs, np), dtype=bool)
            return {
                "matches": float(np.count_nonzero(np.asarray(m_raw) & w_b)),
                "count": float(np.count_nonzero(w_b)),
                "guard": float(np.count_nonzero(guard)),
            }
        w = _f(xp, w_raw)
        m = _f(xp, m_raw)
        return {
            "matches": xp.sum(m * w),
            "count": xp.sum(w),
            "guard": xp.sum(_f(xp, self._guard(inputs, xp))),
        }

    def merge_agg(self, a: Any, b: Any, xp) -> Any:
        return {k: a[k] + b[k] for k in ("matches", "count", "guard")}

    def state_from_aggregates(self, agg: Any) -> Optional[State]:
        if int(agg["guard"]) == 0:
            return None
        return NumMatchesAndCount(int(agg["matches"]), int(agg["count"]))

    def compute_metric_from(self, state: Optional[State]) -> Metric:
        if state is None:
            return self.empty_state_failure()
        return DoubleMetric(
            self.entity, self.name, self.instance, Success(state.metric_value())
        )


@dataclass(frozen=True)
class Completeness(_RatioAnalyzer):
    """Fraction non-NULL (reference: analyzers/Completeness.scala:26)."""

    column: str
    where: Optional[str] = None

    @property
    def name(self) -> str:
        return "Completeness"

    @property
    def instance(self) -> str:
        return self.column

    def preconditions(self) -> List[Callable[[Table], None]]:
        return [Preconditions.has_column(self.column)]

    def _match_mask_key(self) -> str:
        return f"valid:{self.column}"

    def _extra_specs(self) -> List[InputSpec]:
        return [col_valid_spec(self.column)]

    def _guard(self, inputs: Dict[str, Any], xp):
        # isNotNull(...) is never NULL: empty only when nothing was scanned
        return inputs[where_key(None)]

    def device_reduce(self, inputs: Dict[str, Any], xp) -> Any:
        if xp is np:
            # Completeness's counts are exactly the (column, where)
            # family's fused-moment counts: matches = valid∧where,
            # count = where-true, guard = rows scanned — free when a
            # quantile sketch already ran the combined family kernel
            mom = inputs.get(f"__moments:{self.column}:{where_key(self.where)}")
            if mom is not None and "n_rows" in mom:
                return {
                    "matches": mom["count"],
                    "count": mom["n_where"],
                    "guard": mom["n_rows"],
                }
            if self.where is None:
                # string/bool column counted by _LowCardCounts this
                # batch: null count is already known
                nulls = inputs.get(f"__lccnulls:{self.column}")
                if nulls is not None:
                    null_count, n = nulls
                    return {
                        "matches": float(n - null_count),
                        "count": float(n),
                        "guard": float(n),
                    }
        return super().device_reduce(inputs, xp)

    def __repr__(self) -> str:
        return f"Completeness({self.column},{render_where(self.where)})"


def _pred_spec(predicate: str) -> InputSpec:
    from deequ_tpu.data.expr import Predicate

    pred = Predicate(predicate)
    return InputSpec(
        key=f"pred:{predicate}",
        build=lambda t: predicate_input(pred, t),
        columns=tuple(sorted(set(pred.referenced_columns()))),
    )


def _pred_nonnull_spec(predicate: str) -> InputSpec:
    from deequ_tpu.data.expr import Predicate

    pred = Predicate(predicate)
    return InputSpec(
        key=f"prednn:{predicate}",
        build=lambda t: predicate_input(pred, t, nonnull=True),
        columns=tuple(sorted(set(pred.referenced_columns()))),
    )


@dataclass(frozen=True)
class Compliance(_RatioAnalyzer):
    """Fraction of rows satisfying an arbitrary SQL predicate
    (reference: analyzers/Compliance.scala:37)."""

    instance_name: str
    predicate: str
    where: Optional[str] = None

    @property
    def name(self) -> str:
        return "Compliance"

    @property
    def instance(self) -> str:
        return self.instance_name

    @property
    def entity(self) -> Entity:
        return Entity.COLUMN

    def _match_mask_key(self) -> str:
        return f"pred:{self.predicate}"

    def _extra_specs(self) -> List[InputSpec]:
        return [_pred_spec(self.predicate), _pred_nonnull_spec(self.predicate)]

    def _guard(self, inputs: Dict[str, Any], xp):
        # criterion NULL on where-misses and NULL predicate results
        return xp.logical_and(
            xp.asarray(inputs[where_key(self.where)]),
            xp.asarray(inputs[f"prednn:{self.predicate}"]),
        )

    def __repr__(self) -> str:
        return f"Compliance({self.instance_name},{self.predicate},{render_where(self.where)})"


class Patterns:
    """Built-in patterns (reference: analyzers/PatternMatch.scala:57-70;
    the regexes are cited third-party public constants)."""

    # http://emailregex.com
    EMAIL = (
        r"""(?:[a-z0-9!#$%&'*+/=?^_`{|}~-]+(?:\.[a-z0-9!#$%&'*+/=?^_`{|}~-]+)*"""
        r"""|"(?:[\x01-\x08\x0b\x0c\x0e-\x1f\x21\x23-\x5b\x5d-\x7f]|\\[\x01-\x09\x0b\x0c\x0e-\x7f])*")"""
        r"""@(?:(?:[a-z0-9](?:[a-z0-9-]*[a-z0-9])?\.)+[a-z0-9](?:[a-z0-9-]*[a-z0-9])?"""
        r"""|\[(?:(?:25[0-5]|2[0-4][0-9]|[01]?[0-9][0-9]?)\.){3}"""
        r"""(?:25[0-5]|2[0-4][0-9]|[01]?[0-9][0-9]?|[a-z0-9-]*[a-z0-9]:"""
        r"""(?:[\x01-\x08\x0b\x0c\x0e-\x1f\x21-\x5a\x53-\x7f]|\\[\x01-\x09\x0b\x0c\x0e-\x7f])+)\])"""
    )

    # https://mathiasbynens.be/demo/url-regex (@stephenhay)
    URL = r"""(https?|ftp)://[^\s/$.?#].[^\s]*"""

    SOCIAL_SECURITY_NUMBER_US = (
        r"""((?!219-09-9999|078-05-1120)(?!666|000|9\d{2})\d{3}-(?!00)\d{2}-(?!0{4})\d{4})"""
        r"""|((?!219 09 9999|078 05 1120)(?!666|000|9\d{2})\d{3} (?!00)\d{2} (?!0{4})\d{4})"""
        r"""|((?!219099999|078051120)(?!666|000|9\d{2})\d{3}(?!00)\d{2}(?!0{4})\d{4})"""
    )

    # http://www.richardsramblings.com/regex/credit-card-numbers/
    CREDITCARD = (
        r"""\b(?:3[47]\d{2}([\ \-]?)\d{6}\1\d|(?:(?:4\d|5[1-5]|65)\d{2}|6011)"""
        r"""([\ \-]?)\d{4}\2\d{4}\2)\d{4}\b"""
    )


def _match_spec(column: str, pattern: str) -> InputSpec:
    re.compile(pattern)  # fail fast on a bad pattern, at spec-build time

    def compute(col) -> np.ndarray:
        from deequ_tpu.data.table import gather_with_null
        from deequ_tpu.ops.strings import match_pattern

        # regex only the unique values (typically << rows), gather to
        # rows; null rows map to False
        codes, uniques = col.dict_encode()
        return gather_with_null(match_pattern(uniques, pattern), codes, False)

    def build(t: Table) -> np.ndarray:
        from deequ_tpu.data.table import cached_column_encode

        return cached_column_encode(
            t.column(column), f"match:{pattern}", compute
        )

    return InputSpec(key=f"match:{column}:{pattern}", build=build, columns=(column,))


@dataclass(frozen=True)
class PatternMatch(_RatioAnalyzer):
    """Fraction of values matching a regex
    (reference: analyzers/PatternMatch.scala:37)."""

    column: str
    pattern: str
    where: Optional[str] = None

    @property
    def name(self) -> str:
        return "PatternMatch"

    @property
    def instance(self) -> str:
        return self.column

    def preconditions(self) -> List[Callable[[Table], None]]:
        return [
            Preconditions.has_column(self.column),
            Preconditions.is_string(self.column),
        ]

    def _match_mask_key(self) -> str:
        return f"match:{self.column}:{self.pattern}"

    def _extra_specs(self) -> List[InputSpec]:
        return [_match_spec(self.column, self.pattern), col_valid_spec(self.column)]

    def _guard(self, inputs: Dict[str, Any], xp):
        # regexp_extract(NULL) is NULL: criterion non-NULL iff where ∧ value present
        return xp.logical_and(
            xp.asarray(inputs[where_key(self.where)]),
            xp.asarray(inputs[f"valid:{self.column}"]),
        )

    def __repr__(self) -> str:
        return f"PatternMatch({self.column},{self.pattern},{render_where(self.where)})"


# ---------------------------------------------------------------------------
# Numeric moments: Mean / Min / Max / Sum / StdDev / Correlation
# ---------------------------------------------------------------------------


class _NumericScanAnalyzer(ScanShareableAnalyzer):
    def preconditions(self) -> List[Callable[[Table], None]]:
        return [
            Preconditions.has_column(self.column),
            Preconditions.is_numeric(self.column),
        ]

    @property
    def instance(self) -> str:
        return self.column

    def input_specs(self) -> List[InputSpec]:
        return [
            col_values_spec(self.column),
            col_valid_spec(self.column),
            where_spec(self.where),
        ]

    def _masked(self, inputs: Dict[str, Any], xp):
        if xp is np:
            # host fold: several analyzers share (column, where) — memo
            # the mask product in the per-batch inputs dict
            memo_key = f"__masked:{self.column}:{where_key(self.where)}"
            cached = inputs.get(memo_key)
            if cached is None:
                x = np.asarray(inputs[f"num:{self.column}"])
                m = _f(np, inputs[f"valid:{self.column}"]) * _f(
                    np, inputs[where_key(self.where)]
                )
                cached = (x, m)
                inputs[memo_key] = cached
            return cached
        x = xp.asarray(inputs[f"num:{self.column}"])
        m = _f(xp, inputs[f"valid:{self.column}"]) * _f(
            xp, inputs[where_key(self.where)]
        )
        return x, m

    def _moments(self, inputs: Dict[str, Any]) -> Dict[str, float]:
        """Host-fold fast path: ONE fused traversal per (column, where)
        family per batch computes count/sum/min/max/m2, shared by
        Mean/Sum/Minimum/Maximum/StandardDeviation via a per-batch memo —
        the host analogue of the device pass where XLA CSE shares the
        masked subexpressions. Native C when available, compacted numpy
        otherwise; both match the generic formulas within 1e-12."""
        memo_key = f"__moments:{self.column}:{where_key(self.where)}"
        cached = inputs.get(memo_key)
        if cached is None:
            from deequ_tpu.ops import native

            x = np.asarray(inputs[f"num:{self.column}"])
            valid = np.asarray(inputs[f"valid:{self.column}"])
            where = (
                None
                if self.where is None
                else np.asarray(inputs[where_key(self.where)])
            )
            out = None
            if x.dtype == np.float64 and valid.dtype == np.bool_ and (
                where is None or where.dtype == np.bool_
            ):
                out = native.masked_moments(x, valid, where)
            if out is not None:
                cached = {
                    "count": float(out[0]),
                    "sum": float(out[1]),
                    "min": float(out[2]),
                    "max": float(out[3]),
                    "m2": float(out[4]),
                    "n_where": float(out[5]),
                    "n_rows": float(len(x)),
                }
            else:
                mask = (
                    valid.astype(bool)
                    if where is None
                    else (valid.astype(bool) & where.astype(bool))
                )
                xm = np.asarray(x, dtype=np.float64)[mask]
                count = float(xm.size)
                total = float(xm.sum()) if xm.size else 0.0
                avg = total / max(count, 1.0)
                cached = {
                    "count": count,
                    "sum": total,
                    "min": float(xm.min()) if xm.size else float("inf"),
                    "max": float(xm.max()) if xm.size else float("-inf"),
                    "m2": float(((xm - avg) ** 2).sum()) if xm.size else 0.0,
                }
            inputs[memo_key] = cached
        return cached

    def compute_metric_from(self, state: Optional[State]) -> Metric:
        if state is None:
            return self.empty_state_failure()
        return DoubleMetric(
            self.entity, self.name, self.instance, Success(state.metric_value())
        )


def _pallas_moments(x, m):
    """(count, sum) via the single-HBM-pass pallas fold when
    the knob/platform/shape allow, else None — the caller then runs its
    XLA fold. Blocked summation is a different float order, so whenever
    this fires the plan signature carries the "pallas-kahan" variant
    (runtime.fold_variant()) and cached states never cross arithmetics."""
    from deequ_tpu.ops import pallas_kernels

    return pallas_kernels.fold_moments_or_none(x, m)


@dataclass(frozen=True)
class Mean(_NumericScanAnalyzer):
    """reference: analyzers/Mean.scala:36."""

    column: str
    where: Optional[str] = None

    @property
    def name(self) -> str:
        return "Mean"

    def device_reduce(self, inputs: Dict[str, Any], xp) -> Any:
        if xp is np:
            mom = self._moments(inputs)
            return {"total": mom["sum"], "count": mom["count"]}
        x, m = self._masked(inputs, xp)
        folded = _pallas_moments(x, m)
        if folded is not None:
            count, total = folded
            return {"total": total, "count": count}
        return {"total": xp.sum(x * m), "count": xp.sum(m)}

    def merge_agg(self, a: Any, b: Any, xp) -> Any:
        return {"total": a["total"] + b["total"], "count": a["count"] + b["count"]}

    def unshift_agg(self, agg: Any, shifts: Dict[str, float]) -> Any:
        s = shifts.get(f"num:{self.column}", 0.0)
        if s == 0.0:
            return agg
        return {"total": agg["total"] + s * agg["count"], "count": agg["count"]}

    def state_from_aggregates(self, agg: Any) -> Optional[State]:
        if int(agg["count"]) == 0:
            return None
        return MeanState(float(agg["total"]), int(agg["count"]))

    def __repr__(self) -> str:
        return f"Mean({self.column},{render_where(self.where)})"


@dataclass(frozen=True)
class Sum(_NumericScanAnalyzer):
    """reference: analyzers/Sum.scala:36."""

    column: str
    where: Optional[str] = None

    @property
    def name(self) -> str:
        return "Sum"

    def device_reduce(self, inputs: Dict[str, Any], xp) -> Any:
        if xp is np:
            mom = self._moments(inputs)
            return {"sum": mom["sum"], "count": mom["count"]}
        x, m = self._masked(inputs, xp)
        folded = _pallas_moments(x, m)
        if folded is not None:
            count, total = folded
            return {"sum": total, "count": count}
        return {"sum": xp.sum(x * m), "count": xp.sum(m)}

    def merge_agg(self, a: Any, b: Any, xp) -> Any:
        return {"sum": a["sum"] + b["sum"], "count": a["count"] + b["count"]}

    def unshift_agg(self, agg: Any, shifts: Dict[str, float]) -> Any:
        s = shifts.get(f"num:{self.column}", 0.0)
        if s == 0.0:
            return agg
        return {"sum": agg["sum"] + s * agg["count"], "count": agg["count"]}

    def state_from_aggregates(self, agg: Any) -> Optional[State]:
        if int(agg["count"]) == 0:
            return None
        return SumState(float(agg["sum"]))

    def __repr__(self) -> str:
        return f"Sum({self.column},{render_where(self.where)})"


@dataclass(frozen=True)
class Minimum(_NumericScanAnalyzer):
    """reference: analyzers/Minimum.scala:36."""

    column: str
    where: Optional[str] = None
    # the metric is one of the column's values, exact only in float64:
    # folds on the host when the device wire is float32
    value_exact = True

    @property
    def name(self) -> str:
        return "Minimum"

    def device_reduce(self, inputs: Dict[str, Any], xp) -> Any:
        if xp is np:
            mom = self._moments(inputs)
            return {"min": mom["min"], "count": mom["count"]}
        x, m = self._masked(inputs, xp)
        masked = xp.where(m > 0, x, xp.inf)
        return {"min": xp.min(masked), "count": xp.sum(m)}

    def merge_agg(self, a: Any, b: Any, xp) -> Any:
        return {"min": xp.minimum(a["min"], b["min"]), "count": a["count"] + b["count"]}

    def unshift_agg(self, agg: Any, shifts: Dict[str, float]) -> Any:
        s = shifts.get(f"num:{self.column}", 0.0)
        if s == 0.0:
            return agg
        return {"min": agg["min"] + s, "count": agg["count"]}

    def state_from_aggregates(self, agg: Any) -> Optional[State]:
        if int(agg["count"]) == 0:
            return None
        return MinState(float(agg["min"]))

    def __repr__(self) -> str:
        return f"Minimum({self.column},{render_where(self.where)})"


@dataclass(frozen=True)
class Maximum(_NumericScanAnalyzer):
    """reference: analyzers/Maximum.scala:36."""

    column: str
    where: Optional[str] = None
    # the metric is one of the column's values, exact only in float64:
    # folds on the host when the device wire is float32
    value_exact = True

    @property
    def name(self) -> str:
        return "Maximum"

    def device_reduce(self, inputs: Dict[str, Any], xp) -> Any:
        if xp is np:
            mom = self._moments(inputs)
            return {"max": mom["max"], "count": mom["count"]}
        x, m = self._masked(inputs, xp)
        masked = xp.where(m > 0, x, -xp.inf)
        return {"max": xp.max(masked), "count": xp.sum(m)}

    def merge_agg(self, a: Any, b: Any, xp) -> Any:
        return {"max": xp.maximum(a["max"], b["max"]), "count": a["count"] + b["count"]}

    def unshift_agg(self, agg: Any, shifts: Dict[str, float]) -> Any:
        s = shifts.get(f"num:{self.column}", 0.0)
        if s == 0.0:
            return agg
        return {"max": agg["max"] + s, "count": agg["count"]}

    def state_from_aggregates(self, agg: Any) -> Optional[State]:
        if int(agg["count"]) == 0:
            return None
        return MaxState(float(agg["max"]))

    def __repr__(self) -> str:
        return f"Maximum({self.column},{render_where(self.where)})"


@dataclass(frozen=True)
class StandardDeviation(_NumericScanAnalyzer):
    """Population stddev via per-batch centered moments + Chan merge
    (reference: analyzers/StandardDeviation.scala:47, kernel
    catalyst/StatefulStdDevPop.scala:24). The batch pass computes the mean
    first, then sums centered squares — two reads of HBM, full accuracy in
    f32."""

    column: str
    where: Optional[str] = None

    @property
    def name(self) -> str:
        return "StandardDeviation"

    def device_reduce(self, inputs: Dict[str, Any], xp) -> Any:
        if xp is np:
            mom = self._moments(inputs)
            n = mom["count"]
            return {
                "n": n,
                "avg": mom["sum"] / n if n > 0 else 0.0,
                "m2": mom["m2"],
            }
        x, m = self._masked(inputs, xp)
        folded = _pallas_moments(x, m)
        if folded is not None:
            from deequ_tpu.ops import pallas_kernels

            n, total = folded
            safe_n = xp.maximum(n, 1.0)
            avg = total / safe_n
            m2 = pallas_kernels.masked_centered_sumsq(x, m, avg)
            return {"n": n, "avg": xp.where(n > 0, avg, 0.0), "m2": m2}
        n = xp.sum(m)
        safe_n = xp.maximum(n, 1.0)
        avg = xp.sum(x * m) / safe_n
        m2 = xp.sum(((x - avg) * m) ** 2)
        return {"n": n, "avg": xp.where(n > 0, avg, 0.0), "m2": m2}

    def merge_agg(self, a: Any, b: Any, xp) -> Any:
        n = a["n"] + b["n"]
        safe_n = xp.maximum(n, 1.0)
        delta = b["avg"] - a["avg"]
        avg = (a["n"] * a["avg"] + b["n"] * b["avg"]) / safe_n
        m2 = a["m2"] + b["m2"] + delta * delta * a["n"] * b["n"] / safe_n
        return {"n": n, "avg": xp.where(n > 0, avg, 0.0), "m2": m2}

    def unshift_agg(self, agg: Any, shifts: Dict[str, float]) -> Any:
        s = shifts.get(f"num:{self.column}", 0.0)
        if s == 0.0:
            return agg
        return {"n": agg["n"], "avg": agg["avg"] + s, "m2": agg["m2"]}

    def state_from_aggregates(self, agg: Any) -> Optional[State]:
        if float(agg["n"]) == 0:
            return None
        return StandardDeviationState(float(agg["n"]), float(agg["avg"]), float(agg["m2"]))

    def __repr__(self) -> str:
        return f"StandardDeviation({self.column},{render_where(self.where)})"


@dataclass(frozen=True)
class Correlation(ScanShareableAnalyzer):
    """Pearson r via per-batch centered co-moments + pairwise merge
    (reference: analyzers/Correlation.scala:65, kernel
    catalyst/StatefulCorrelation.scala:24). Rows enter only when BOTH
    columns are non-null."""

    first_column: str
    second_column: str
    where: Optional[str] = None

    @property
    def name(self) -> str:
        return "Correlation"

    @property
    def instance(self) -> str:
        return f"{self.first_column},{self.second_column}"

    @property
    def entity(self) -> Entity:
        return Entity.MULTICOLUMN

    def preconditions(self) -> List[Callable[[Table], None]]:
        return [
            Preconditions.has_column(self.first_column),
            Preconditions.is_numeric(self.first_column),
            Preconditions.has_column(self.second_column),
            Preconditions.is_numeric(self.second_column),
        ]

    def input_specs(self) -> List[InputSpec]:
        return [
            col_values_spec(self.first_column),
            col_valid_spec(self.first_column),
            col_values_spec(self.second_column),
            col_valid_spec(self.second_column),
            where_spec(self.where),
        ]

    def device_reduce(self, inputs: Dict[str, Any], xp) -> Any:
        x = xp.asarray(inputs[f"num:{self.first_column}"])
        y = xp.asarray(inputs[f"num:{self.second_column}"])
        m = (
            _f(xp, inputs[f"valid:{self.first_column}"])
            * _f(xp, inputs[f"valid:{self.second_column}"])
            * _f(xp, inputs[where_key(self.where)])
        )
        n = xp.sum(m)
        safe_n = xp.maximum(n, 1.0)
        x_avg = xp.sum(x * m) / safe_n
        y_avg = xp.sum(y * m) / safe_n
        xc = (x - x_avg) * m
        yc = (y - y_avg) * m
        return {
            "n": n,
            "x_avg": xp.where(n > 0, x_avg, 0.0),
            "y_avg": xp.where(n > 0, y_avg, 0.0),
            "ck": xp.sum(xc * yc),
            "x_mk": xp.sum(xc * xc),
            "y_mk": xp.sum(yc * yc),
        }

    def merge_agg(self, a: Any, b: Any, xp) -> Any:
        n = a["n"] + b["n"]
        safe_n = xp.maximum(n, 1.0)
        dx = b["x_avg"] - a["x_avg"]
        dy = b["y_avg"] - a["y_avg"]
        frac = b["n"] / safe_n
        cross = a["n"] * b["n"] / safe_n
        return {
            "n": n,
            "x_avg": a["x_avg"] + dx * frac,
            "y_avg": a["y_avg"] + dy * frac,
            "ck": a["ck"] + b["ck"] + dx * dy * cross,
            "x_mk": a["x_mk"] + b["x_mk"] + dx * dx * cross,
            "y_mk": a["y_mk"] + b["y_mk"] + dy * dy * cross,
        }

    def unshift_agg(self, agg: Any, shifts: Dict[str, float]) -> Any:
        sx = shifts.get(f"num:{self.first_column}", 0.0)
        sy = shifts.get(f"num:{self.second_column}", 0.0)
        if sx == 0.0 and sy == 0.0:
            return agg
        out = dict(agg)
        out["x_avg"] = agg["x_avg"] + sx
        out["y_avg"] = agg["y_avg"] + sy
        return out

    def state_from_aggregates(self, agg: Any) -> Optional[State]:
        if float(agg["n"]) == 0:
            return None
        return CorrelationState(
            float(agg["n"]),
            float(agg["x_avg"]),
            float(agg["y_avg"]),
            float(agg["ck"]),
            float(agg["x_mk"]),
            float(agg["y_mk"]),
        )

    def compute_metric_from(self, state: Optional[State]) -> Metric:
        if state is None:
            return self.empty_state_failure()
        return DoubleMetric(
            self.entity, self.name, self.instance, Success(state.metric_value())
        )

    def __repr__(self) -> str:
        return (
            f"Correlation({self.first_column},{self.second_column},"
            f"{render_where(self.where)})"
        )


# ---------------------------------------------------------------------------
# DataType
# ---------------------------------------------------------------------------


class DataTypeInstances:
    UNKNOWN = "Unknown"
    FRACTIONAL = "Fractional"
    INTEGRAL = "Integral"
    BOOLEAN = "Boolean"
    STRING = "String"


# class codes used on device: order matches DataTypeHistogram fields
# (value classification itself — the reference's regexes
# catalyst/StatefulDataType.scala:36-38 — is the vectorized kernel in
# deequ_tpu/ops/strings.py:classify, run over unique values only)
from deequ_tpu.ops.strings import (  # noqa: E402
    CODE_BOOLEAN as _CODE_BOOLEAN,
    CODE_FRACTIONAL as _CODE_FRACTIONAL,
    CODE_INTEGRAL as _CODE_INTEGRAL,
    CODE_NULL as _CODE_NULL,
    CODE_STRING as _CODE_STRING,
)


def _classified_dict(col) -> np.ndarray:
    """int8 class code per dictionary entry, memoized on the ROOT column
    and across stream batches via the dictionary content digest (one
    classify pass per distinct dictionary — consumed by both the
    per-row dtclass codes and the counts-based DataType shortcut)."""
    from deequ_tpu.data.table import cached_dictionary_encode
    from deequ_tpu.ops.strings import classify

    return cached_dictionary_encode(
        col,
        "dtclassdict",
        lambda c: classify(np.asarray(c.dict_encode()[1])).astype(np.int8),
    )


def _dtclass_spec(column: str) -> InputSpec:
    def compute(col) -> np.ndarray:
        from deequ_tpu.ops.strings import classify

        if col.ctype == ColumnType.STRING:
            # classify unique strings only; null rows map to the NULL
            # class. int8: 5 classes, and the narrow dtype is both the
            # wire format and the host bincount fast path
            from deequ_tpu.data.table import gather_with_null

            dict_codes, _uniques = col.dict_encode()
            return gather_with_null(
                _classified_dict(col), dict_codes, _CODE_NULL
            )
        # typed columns classify statically from the stringified form
        static = {
            ColumnType.LONG: _CODE_INTEGRAL,
            ColumnType.DOUBLE: _CODE_FRACTIONAL,
            ColumnType.DECIMAL: _CODE_FRACTIONAL,
            ColumnType.BOOLEAN: _CODE_BOOLEAN,
            ColumnType.TIMESTAMP: _CODE_STRING,
        }[col.ctype]
        return np.where(col.valid, np.int8(static), np.int8(_CODE_NULL))

    def build(t: Table) -> np.ndarray:
        from deequ_tpu.data.table import cached_column_encode

        # column-deterministic: memoized per table, sliced per batch
        return cached_column_encode(t.column(column), "dtclass", compute)

    return InputSpec(key=f"dtclass:{column}", build=build, columns=(column,))


@dataclass(frozen=True)
class DataType(ScanShareableAnalyzer):
    """Histogram over inferred value types + majority-type inference
    (reference: analyzers/DataType.scala:32-183). Rows excluded by `where`
    become NULL before classification (exactly like conditionalSelection
    feeding the reference UDAF), so they count as Unknown."""

    discrete_inputs = True  # code-only: host-foldable under placement
    column: str
    where: Optional[str] = None

    @property
    def name(self) -> str:
        return "Histogram"

    @property
    def instance(self) -> str:
        return self.column

    def preconditions(self) -> List[Callable[[Table], None]]:
        return [Preconditions.has_column(self.column)]

    def input_specs(self) -> List[InputSpec]:
        return [_dtclass_spec(self.column), where_spec(self.where), where_spec(None)]

    def device_reduce(self, inputs: Dict[str, Any], xp) -> Any:
        labels = ("null", "fractional", "integral", "boolean", "string")
        if xp is np and self.where is None:
            # a _LowCardCounts member counted this column's dictionary
            # this batch: classify the DICTIONARY and weigh the classes
            # by the per-entry counts — O(#uniques), and the per-row
            # class-code input is never built at all (lazy HostInputs)
            from deequ_tpu.ops import counts_family

            lcc = inputs.get(f"__lcccounts:{self.column}")
            if lcc is not None and counts_family.enabled():
                counts, uniques, n_batch = lcc
                rows_arr = np.asarray(inputs[where_key(None)], dtype=bool)
                if n_batch == len(rows_arr) and bool(rows_arr.all()):
                    cls = self._classified_dictionary(inputs, uniques)
                    counts_vec = np.zeros(len(labels), dtype=np.int64)
                    np.add.at(counts_vec, cls, np.asarray(counts[1:]))
                    counts_vec[_CODE_NULL] += int(counts[0])
                    return {
                        label: float(counts_vec[code])
                        for code, label in enumerate(labels)
                    }
        codes = xp.asarray(inputs[f"dtclass:{self.column}"])
        w = inputs[where_key(self.where)]
        rows = inputs[where_key(None)]
        if xp is np:
            # host fold: one bincount pass instead of 5 comparison scans;
            # where-filtered rows count as NULL class (conditionalSelection
            # semantics), padded rows (rows=False) drop out entirely
            from deequ_tpu.ops import native

            sel_codes = np.asarray(codes)
            w_arr = np.asarray(w, dtype=bool)
            rows_arr = np.asarray(rows, dtype=bool)
            w_all = bool(w_arr.all())
            rows_all = bool(rows_arr.all())
            if w_all and rows_all:
                mask = None
            elif w_all:
                mask = rows_arr
            elif rows_all:
                mask = w_arr
            else:
                mask = w_arr & rows_arr
            counts_vec = native.bincount(sel_codes, len(labels), where=mask)
            if counts_vec is None:
                if mask is not None:
                    sel_codes = sel_codes[mask]
                counts_vec = np.bincount(sel_codes, minlength=len(labels))
            if not w_all:
                # rows present but excluded by `where` classify as NULL
                n_rows = int(np.count_nonzero(rows_arr)) if not rows_all else len(rows_arr)
                n_in = int(counts_vec.sum())
                counts_vec = counts_vec.copy()
                counts_vec[_CODE_NULL] += n_rows - n_in
            return {
                label: float(counts_vec[code]) for code, label in enumerate(labels)
            }
        rows_f = _f(xp, rows)
        # where-filtered rows -> NULL class; padded rows excluded via `rows`
        codes = xp.where(xp.asarray(w), codes, _CODE_NULL)
        counts = {}
        for code, label in enumerate(labels):
            counts[label] = xp.sum(_f(xp, codes == code) * rows_f)
        return counts

    def _classified_dictionary(self, inputs, uniques) -> np.ndarray:
        """int8 class code per dictionary entry via the shared
        `_classified_dict` memo when the batch is reachable (one
        classify per table, shared with the per-row dtclass spec);
        plain classify otherwise."""
        from deequ_tpu.ops.strings import classify

        batch = getattr(inputs, "batch", None)
        if batch is not None:
            try:
                cls = _classified_dict(batch.column(self.column))
                if len(cls) == len(uniques):
                    return cls
            except Exception:  # noqa: BLE001 - fall back to direct classify
                pass
        return classify(np.asarray(uniques)).astype(np.int8)

    def merge_agg(self, a: Any, b: Any, xp) -> Any:
        return {k: a[k] + b[k] for k in a}

    def state_from_aggregates(self, agg: Any) -> Optional[State]:
        return DataTypeHistogram(
            int(agg["null"]),
            int(agg["fractional"]),
            int(agg["integral"]),
            int(agg["boolean"]),
            int(agg["string"]),
        )

    def compute_metric_from(self, state: Optional[State]) -> Metric:
        if state is None:
            return self.to_failure_metric_histogram()
        return HistogramMetric(
            Entity.COLUMN,
            self.name,
            self.column,
            Success(to_distribution(state)),
        )

    def to_failure_metric(self, exception: BaseException) -> Metric:
        from deequ_tpu.core.exceptions import wrap_if_necessary
        from deequ_tpu.core.maybe import Failure

        return HistogramMetric(
            Entity.COLUMN, self.name, self.column, Failure(wrap_if_necessary(exception))
        )

    def to_failure_metric_histogram(self) -> Metric:
        from deequ_tpu.core.exceptions import EmptyStateException

        return self.to_failure_metric(
            EmptyStateException(
                f"Empty state for analyzer {self!r}, all input values were NULL."
            )
        )

    def __repr__(self) -> str:
        return f"DataType({self.column},{render_where(self.where)})"


def to_distribution(hist: DataTypeHistogram) -> Distribution:
    """reference: analyzers/DataType.scala:100-115."""
    total = hist.total
    ratio = (lambda c: c / total) if total > 0 else (lambda c: float("nan"))
    return Distribution(
        {
            DataTypeInstances.UNKNOWN: DistributionValue(hist.num_null, ratio(hist.num_null)),
            DataTypeInstances.FRACTIONAL: DistributionValue(
                hist.num_fractional, ratio(hist.num_fractional)
            ),
            DataTypeInstances.INTEGRAL: DistributionValue(
                hist.num_integral, ratio(hist.num_integral)
            ),
            DataTypeInstances.BOOLEAN: DistributionValue(
                hist.num_boolean, ratio(hist.num_boolean)
            ),
            DataTypeInstances.STRING: DistributionValue(
                hist.num_string, ratio(hist.num_string)
            ),
        },
        number_of_bins=5,
    )


def determine_type(dist: Distribution) -> str:
    """Majority-type decision tree (reference: analyzers/DataType.scala:116-146)."""

    def ratio_of(key: str) -> float:
        v = dist.values.get(key)
        return v.ratio if v is not None else 0.0

    if ratio_of(DataTypeInstances.UNKNOWN) == 1.0:
        return DataTypeInstances.UNKNOWN
    if ratio_of(DataTypeInstances.STRING) > 0.0 or (
        ratio_of(DataTypeInstances.BOOLEAN) > 0.0
        and (
            ratio_of(DataTypeInstances.INTEGRAL) > 0.0
            or ratio_of(DataTypeInstances.FRACTIONAL) > 0.0
        )
    ):
        return DataTypeInstances.STRING
    if ratio_of(DataTypeInstances.BOOLEAN) > 0.0:
        return DataTypeInstances.BOOLEAN
    if ratio_of(DataTypeInstances.FRACTIONAL) > 0.0:
        return DataTypeInstances.FRACTIONAL
    return DataTypeInstances.INTEGRAL

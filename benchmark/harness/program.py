"""What the benchmark asks of the program: a `Check` or a list of
analyzers built from a workload's check spec, and the plain numbers read
back from what a call returned. The only module here, with the traffic
drivers, that imports deequ_tpu."""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

from benchmark.reference.reference import Metric, assertion


def analyzer(m: Metric):
    from deequ_tpu import analyzers as A

    fam, cols = m.family, m.columns
    if fam == "Size":
        return A.Size()
    if fam == "Correlation":
        return A.Correlation(*cols)
    if fam == "Uniqueness":
        return A.Uniqueness(list(cols))
    if fam == "ApproxQuantile":
        return A.ApproxQuantile(cols[0], m.param)
    return getattr(A, fam)(cols[0])


def analyzers(metrics: Sequence[Metric]) -> list:
    return [analyzer(m) for m in metrics]


def check(name: str, metrics: Sequence[Metric], domains: Dict[str, dict]):
    """One `Check` with one constraint per metric, in order, each
    asserting what `reference.assertion` asserts."""
    from deequ_tpu import Check, CheckLevel

    c = Check(CheckLevel.ERROR, name)
    for m in metrics:
        fam, cols, ok = m.family, m.columns, assertion(m, domains)
        if fam == "Size":
            c = c.has_size(ok)
        elif fam == "Completeness":
            c = c.is_complete(cols[0])
        elif fam == "Uniqueness":
            c = c.is_primary_key(*cols)
        elif fam == "Compliance":
            c = c.is_contained_in(cols[0], list(domains[cols[0]]["values"]))
        elif fam == "Correlation":
            c = c.has_correlation(cols[0], cols[1], ok)
        elif fam == "ApproxQuantile":
            c = c.has_approx_quantile(cols[0], m.param, ok)
        else:
            method = {
                "Mean": c.has_mean, "Sum": c.has_sum,
                "StandardDeviation": c.has_standard_deviation,
                "Minimum": c.has_min, "Maximum": c.has_max,
                "ApproxCountDistinct": c.has_approx_count_distinct,
            }[fam]
            c = method(cols[0], ok)
    return c


def _value(metric):
    if metric is None or not metric.value.is_success:
        return None
    return float(metric.value.get())


def verdict(result) -> Tuple[str, List[Tuple[object, str]]]:
    """A VerificationResult as (overall status, [(value, status)] per
    constraint in the check's order)."""
    rows: List[Tuple[object, str]] = []
    for cr in result.check_results.values():
        for r in cr.constraint_results:
            rows.append((_value(r.metric), r.status.name))
    return result.status.name, rows


def metric_values(ctx, analyzer_list) -> List[object]:
    """An AnalyzerContext's values for `analyzer_list`, in order."""
    return [_value(ctx.metric_map.get(a)) for a in analyzer_list]


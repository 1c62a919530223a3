"""ColumnProfiler: full single-column profiles in AT MOST three scans.

reference: profiles/ColumnProfiler.scala:54-669. The reference's pass
structure is:
  1. Size + per-column Completeness + ApproxCountDistinct (+ DataType for
     strings) — ONE fused device pass;
  2. numeric columns (schema-numeric or inferred-numeric strings, cast
     host-side) get Minimum/Maximum/Mean/StandardDeviation/Sum/
     ApproxQuantiles(0.01..1.00) — ONE fused pass (device + host-reduced
     quantile sketches share it);
  3. exact histograms for low-cardinality string/bool columns — one
     group-by pass.

Pass-budget improvement over the reference: a SCHEMA-numeric column's
pass-2 analyzer set does not depend on pass-1 results (only
inferred-numeric STRING columns need the post-inference cast), so its
numeric statistics fuse into pass 1. Pass 2 then runs only for the
string-cast columns — and, under column pruning, decodes ONLY those
columns from a streaming source. A table with no numeric-looking string
columns profiles in 2 scans; the reference's 3 is the ceiling either
way (the reference itself always pays 3:
ColumnProfiler.scala:103-153).

Traced, a profile is one `profile` span (category `profile`; attrs
`columns`, `passes`) holding a `profile_pass` span (attrs `pass`,
`analyzers`) for each scan it makes and one `profile_finish` span for the
work after pass 1: statistics extraction, type inference, the synthesized
numeric metrics, the histograms and the profiles themselves; a pass-2 or
straggler-histogram scan, where one runs, is a `profile_pass` child of
it. `observe.counters.record_profile` counts the scans, histograms,
straggler columns and cast columns of every profile."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np

from deequ_tpu import observe
from deequ_tpu.analyzers import (
    ApproxCountDistinct,
    ApproxQuantiles,
    Completeness,
    DataType,
    Maximum,
    Mean,
    Minimum,
    Size,
    StandardDeviation,
    Sum,
)
from deequ_tpu.analyzers.scan import DataTypeInstances, determine_type
from deequ_tpu.core.exceptions import MetricCalculationRuntimeException
from deequ_tpu.core.metrics import Distribution, DistributionValue
from deequ_tpu.data.table import Column, ColumnType, Table
from deequ_tpu.profiles.column_profile import (
    ColumnProfiles,
    NumericColumnProfile,
    StandardColumnProfile,
)
from deequ_tpu.runners.analysis_runner import AnalysisRunner

DEFAULT_CARDINALITY_THRESHOLD = 120

_PERCENTILES = tuple(i / 100 for i in range(1, 101))


def _numeric_stat_analyzers(name: str) -> List:
    """The numeric-statistics bundle of the reference's pass 2
    (ColumnProfiler.scala:219-235)."""
    return [
        Minimum(name),
        Maximum(name),
        Mean(name),
        StandardDeviation(name),
        Sum(name),
        ApproxQuantiles(name, _PERCENTILES),
    ]


@dataclass
class GenericColumnStatistics:
    num_records: int
    inferred_types: Dict[str, str]
    known_types: Dict[str, str]
    type_detection_histograms: Dict[str, Dict[str, int]]
    approximate_num_distincts: Dict[str, int]
    completenesses: Dict[str, float]
    # string columns whose DataType metric failed: the metric's exception
    type_failures: Dict[str, BaseException] = field(default_factory=dict)

    def type_of(self, column: str) -> str:
        """The column's inferred type, else its schema type. A string
        column whose DataType metric failed has neither, and the profile
        fails here, as upstream's `typeOf` does, with the metric's own
        exception as the cause."""
        if column in self.inferred_types:
            return self.inferred_types[column]
        if column in self.known_types:
            return self.known_types[column]
        cause = self.type_failures.get(column)
        raise MetricCalculationRuntimeException(
            f"cannot profile column {column!r}: its data type is unknown, "
            + (f"its DataType metric failed: {cause}" if cause is not None
               else "no DataType metric was computed for it")
        ) from cause


class ColumnProfiler:
    @staticmethod
    def profile(
        data: Table,
        restrict_to_columns: Optional[Sequence[str]] = None,
        print_status_updates: bool = False,
        low_cardinality_histogram_threshold: int = DEFAULT_CARDINALITY_THRESHOLD,
        metrics_repository=None,
        reuse_existing_results_for_key=None,
        fail_if_results_missing: bool = False,
        save_in_metrics_repository_using_key=None,
        engine: str = "auto",
        mesh=None,
    ) -> ColumnProfiles:
        """reference: ColumnProfiler.scala:81-188."""
        relevant = (
            list(restrict_to_columns)
            if restrict_to_columns is not None
            else data.column_names
        )
        for name in relevant:
            data.column(name)  # raises NoSuchColumnException early

        with observe.span("profile", cat="profile", columns=len(relevant)) as prof:
            # ---- Pass 1 (reference: :103-126) --------------------------------
            # Schema-numeric columns also get their full numeric statistics
            # HERE: their pass-2 analyzer choice never depends on pass-1
            # inference, so fusing them saves a whole scan (see module
            # docstring). Only inferred-numeric strings still need pass 2.
            # NOTE for repository reuse: the analyzer-per-pass assignment
            # changed when this fusion landed, so a key saved by an older
            # version misses the numeric metrics — reuse still works
            # analyzer-by-analyzer unless fail_if_results_missing demands
            # completeness.
            may_need_pass2 = any(
                data.column(name).ctype == ColumnType.STRING for name in relevant
            )

            def _with_repository(builder):
                if metrics_repository is not None:
                    builder = builder.use_repository(metrics_repository)
                    if reuse_existing_results_for_key is not None:
                        builder = builder.reuse_existing_results_for_key(
                            reuse_existing_results_for_key, fail_if_results_missing
                        )
                    if save_in_metrics_repository_using_key is not None:
                        builder = builder.save_or_append_result(
                            save_in_metrics_repository_using_key
                        )
                return builder

            total_passes = 3 if may_need_pass2 else 2
            if print_status_updates:
                print(
                    "### PROFILING: Computing generic column statistics in "
                    f"pass (1/{total_passes})..."
                )
            from deequ_tpu.profiles.internal_analyzers import (
                LowCardCountsState,
                OptimisticNumericState,
                _LowCardCounts,
                _OptimisticNumericStats,
                synthesize_numeric_metrics,
            )

            # optimistic members fold passes 2 and 3 into pass 1 (see
            # internal_analyzers module docstring); the count cap leaves HLL
            # estimation error (rsd 0.05) generous headroom over the
            # histogram threshold
            lcc_cap = max(4 * low_cardinality_histogram_threshold, 256)
            analyzers_pass1 = [Size()]
            for name in relevant:
                analyzers_pass1.append(Completeness(name))
                analyzers_pass1.append(ApproxCountDistinct(name))
                ctype = data.column(name).ctype
                if ctype == ColumnType.STRING:
                    analyzers_pass1.append(DataType(name))
                    analyzers_pass1.append(_LowCardCounts(name, lcc_cap))
                    analyzers_pass1.append(_OptimisticNumericStats(name))
                elif ctype == ColumnType.BOOLEAN:
                    analyzers_pass1.append(_LowCardCounts(name, lcc_cap))
                elif ctype.is_numeric:
                    analyzers_pass1.extend(_numeric_stat_analyzers(name))

            with observe.span(
                "profile_pass",
                cat="profile",
                **{"pass": 1, "analyzers": len(analyzers_pass1)},
            ):
                results_pass1 = _with_repository(
                    AnalysisRunner.on_data(data)
                    .add_analyzers(analyzers_pass1)
                    .with_engine(engine, mesh)
                ).run()
            passes = 1

            with observe.span("profile_finish", cat="profile"):
                generic_stats = _extract_generic_statistics(
                    relevant, data, results_pass1
                )
                low_card_counts: Dict[str, LowCardCountsState] = {}
                optimistic_numeric: Dict[str, OptimisticNumericState] = {}
                for analyzer, metric in results_pass1.metric_map.items():
                    if not metric.value.is_success:
                        continue
                    state = metric.value.get()
                    if isinstance(analyzer, _LowCardCounts) and isinstance(
                        state, LowCardCountsState
                    ):
                        if not state.aborted:
                            low_card_counts[analyzer.column] = state
                    elif isinstance(analyzer, _OptimisticNumericStats) and isinstance(
                        state, OptimisticNumericState
                    ):
                        if state.usable:
                            optimistic_numeric[analyzer.column] = state

                # ---- Pass 2 (reference: :128-153, cast at :399-417) --------------
                # runs ONLY for inferred-numeric STRING columns, which need the
                # post-inference cast; schema-numeric stats came from pass 1
                numeric_columns = [
                    name
                    for name in relevant
                    if generic_stats.type_of(name)
                    in (DataTypeInstances.INTEGRAL, DataTypeInstances.FRACTIONAL)
                ]
                cast_columns = [
                    name
                    for name in numeric_columns
                    if name in generic_stats.inferred_types
                ]
                combined = results_pass1
                # optimistic pass-1 stats replace pass 2 for columns where they
                # survived (every value cast cleanly — guaranteed whenever
                # inference landed numeric, see internal_analyzers). With a reuse
                # key the classic pass keeps its repository short-circuit
                # semantics instead.
                synthesized: Dict = {}
                if reuse_existing_results_for_key is None:
                    for name in list(cast_columns):
                        state = optimistic_numeric.get(name)
                        if state is not None:
                            synthesized.update(
                                synthesize_numeric_metrics(name, state, _PERCENTILES)
                            )
                            cast_columns.remove(name)
                if synthesized:
                    from deequ_tpu.runners.context import AnalyzerContext

                    synthesized_ctx = AnalyzerContext(synthesized)
                    combined = combined + synthesized_ctx
                    if (
                        metrics_repository is not None
                        and save_in_metrics_repository_using_key is not None
                    ):
                        AnalysisRunner._save_or_append(
                            metrics_repository,
                            save_in_metrics_repository_using_key,
                            synthesized_ctx,
                        )
                analyzers_pass2 = []
                for name in cast_columns:
                    analyzers_pass2.extend(_numeric_stat_analyzers(name))
                if analyzers_pass2:
                    if print_status_updates:
                        print(
                            "### PROFILING: Computing numeric column statistics "
                            f"in pass (2/{total_passes})..."
                        )
                    with observe.span(
                        "profile_pass",
                        cat="profile",
                        **{"pass": 2, "analyzers": len(analyzers_pass2)},
                    ):
                        casted_data = _cast_numeric_string_columns(
                            cast_columns, data, generic_stats
                        )
                        # same repository options as every other pass
                        # (reference: ColumnProfiler.scala:128-153 threads them through)
                        combined = combined + _with_repository(
                            AnalysisRunner.on_data(casted_data)
                            .add_analyzers(analyzers_pass2)
                            .with_engine(engine, mesh)
                        ).run()
                    passes += 1
                numeric_stats = _extract_numeric_statistics(combined)

                # ---- Pass 3 (reference: :487-565) --------------------------------
                # Normally already answered by the pass-1 _LowCardCounts fold; a
                # separate counting pass runs only for stragglers (column whose
                # exact distinct blew the optimistic cap while its HLL estimate
                # still cleared the threshold — possible but rare at rsd 0.05).
                target_columns = _find_target_columns_for_histograms(
                    data, generic_stats, low_cardinality_histogram_threshold
                )
                histograms: Dict[str, Distribution] = {}
                stragglers = []
                for name in target_columns:
                    state = low_card_counts.get(name)
                    if state is None:
                        stragglers.append(name)
                        continue
                    histograms[name] = _distribution_from_counts(
                        data.column(name).ctype,
                        state.as_dict(),
                        state.null_count,
                        generic_stats.num_records,
                    )
                if stragglers:
                    if print_status_updates:
                        print(
                            "### PROFILING: Computing histograms of low-cardinality "
                            f"columns in pass ({total_passes}/{total_passes})..."
                        )
                    with observe.span(
                        "profile_pass",
                        cat="profile",
                        **{"pass": 3, "analyzers": len(stragglers)},
                    ):
                        histograms.update(
                            _compute_histograms(
                                data, stragglers, generic_stats.num_records
                            )
                        )
                    passes += 1

                profiles = _create_profiles(
                    relevant, generic_stats, numeric_stats, histograms
                )
            prof.set(passes=passes)
            observe.counters.record_profile(
                passes, len(histograms), len(stragglers), len(cast_columns)
            )
        return profiles


def _extract_generic_statistics(
    columns: Sequence[str], data: Table, results
) -> GenericColumnStatistics:
    """reference: ColumnProfiler.scala:341-396."""
    num_records = 0
    inferred_types: Dict[str, str] = {}
    type_detection: Dict[str, Dict[str, int]] = {}
    approx_distincts: Dict[str, int] = {}
    completenesses: Dict[str, float] = {}
    type_failures: Dict[str, BaseException] = {}

    for analyzer, metric in results.metric_map.items():
        if isinstance(analyzer, Size) and metric.value.is_success:
            num_records = int(metric.value.get())
        elif isinstance(analyzer, DataType):
            if not metric.value.is_success:
                type_failures[analyzer.column] = metric.value.exception
                continue
            dist = metric.value.get()
            inferred_types[analyzer.column] = determine_type(dist)
            type_detection[analyzer.column] = {
                key: dv.absolute for key, dv in dist.values.items()
            }
        elif isinstance(analyzer, ApproxCountDistinct) and metric.value.is_success:
            approx_distincts[analyzer.column] = int(metric.value.get())
        elif isinstance(analyzer, Completeness) and metric.value.is_success:
            completenesses[analyzer.column] = metric.value.get()

    known_types: Dict[str, str] = {}
    for name, ctype in data.schema:
        if name not in columns or ctype == ColumnType.STRING:
            continue
        known_types[name] = {
            ColumnType.LONG: DataTypeInstances.INTEGRAL,
            ColumnType.DOUBLE: DataTypeInstances.FRACTIONAL,
            ColumnType.DECIMAL: DataTypeInstances.FRACTIONAL,
            ColumnType.BOOLEAN: DataTypeInstances.BOOLEAN,
            ColumnType.TIMESTAMP: DataTypeInstances.STRING,
        }[ctype]

    return GenericColumnStatistics(
        num_records,
        inferred_types,
        known_types,
        type_detection,
        approx_distincts,
        completenesses,
        type_failures,
    )


def _cast_numeric_string_columns(
    columns: Sequence[str], data: Table, stats: GenericColumnStatistics
) -> Table:
    """Cast the given inferred-numeric string columns for pass 2
    (reference: ColumnProfiler.scala:329-339, 399-417); the caller passes
    exactly the columns whose inferred type is Integral/Fractional. On a
    streaming source the cast is a lazy per-batch transform."""
    to_cast = list(columns)
    if not to_cast:
        return data

    def cast_batch(batch: Table) -> Table:
        out = batch
        for name in to_cast:
            if not batch.has_column(name):
                continue  # column-pruned batch: nothing to cast
            col = batch.column(name)
            values, valid = col.numeric_values()
            out = out.with_column(Column(name, ColumnType.DOUBLE, values, valid))
        return out

    if getattr(data, "is_streaming", False):
        from deequ_tpu.data.source import MappedSource

        return MappedSource(
            data,
            cast_batch,
            schema_overrides=[(name, ColumnType.DOUBLE) for name in to_cast],
            # cast_batch is an IN-PLACE transform (reads only the columns
            # it rewrites, and skips pruned-away ones), so it needs no
            # extra base columns beyond whatever the consumer requests
            fn_columns=(),
        )
    return cast_batch(data)


@dataclass
class NumericColumnStatistics:
    means: Dict[str, float] = field(default_factory=dict)
    maxima: Dict[str, float] = field(default_factory=dict)
    minima: Dict[str, float] = field(default_factory=dict)
    sums: Dict[str, float] = field(default_factory=dict)
    std_devs: Dict[str, float] = field(default_factory=dict)
    approx_percentiles: Dict[str, List[float]] = field(default_factory=dict)


def _extract_numeric_statistics(results) -> NumericColumnStatistics:
    stats = NumericColumnStatistics()
    for analyzer, metric in results.metric_map.items():
        if not metric.value.is_success:
            continue
        if isinstance(analyzer, Mean):
            stats.means[analyzer.column] = metric.value.get()
        elif isinstance(analyzer, Maximum):
            stats.maxima[analyzer.column] = metric.value.get()
        elif isinstance(analyzer, Minimum):
            stats.minima[analyzer.column] = metric.value.get()
        elif isinstance(analyzer, Sum):
            stats.sums[analyzer.column] = metric.value.get()
        elif isinstance(analyzer, StandardDeviation):
            stats.std_devs[analyzer.column] = metric.value.get()
        elif isinstance(analyzer, ApproxQuantiles):
            keyed = metric.value.get()
            ordered = [keyed[k] for k in sorted(keyed, key=float)]
            stats.approx_percentiles[analyzer.column] = ordered
    return stats


def _find_target_columns_for_histograms(
    data: Table, stats: GenericColumnStatistics, threshold: int
) -> List[str]:
    """string/bool columns with approx distinct <= threshold
    (reference: ColumnProfiler.scala:487-516)."""
    out = []
    for name, count in stats.approximate_num_distincts.items():
        ctype = data.column(name).ctype
        if ctype not in (ColumnType.STRING, ColumnType.BOOLEAN):
            continue
        if stats.type_of(name) not in (
            DataTypeInstances.STRING,
            DataTypeInstances.BOOLEAN,
        ):
            continue
        if count <= threshold:
            out.append(name)
    return out


def _distribution_from_counts(
    ctype: ColumnType,
    counts: Dict,
    null_count: int,
    num_records: int,
) -> Distribution:
    """Shared rendering of exact value counts into the reference's
    Distribution shape (null bucket name 'NullValue', booleans as
    'true'/'false' — reference: Histogram.scala:108, ColumnProfiler.scala
    :523-565)."""
    values: Dict[str, DistributionValue] = {}
    if null_count > 0:
        values["NullValue"] = DistributionValue(
            null_count, null_count / num_records
        )
    for unique, count in counts.items():
        if ctype == ColumnType.BOOLEAN:
            key = "true" if unique else "false"
        else:
            key = str(unique)
        prev = values.get(key)
        if prev is not None:
            count = count + prev.absolute
        values[key] = DistributionValue(count, count / num_records)
    return Distribution(values, number_of_bins=len(values))


def _compute_histograms(
    data: Table, target_columns: Sequence[str], num_records: int
) -> Dict[str, Distribution]:
    """One exact counting pass over all target columns
    (reference: ColumnProfiler.scala:523-565). Streaming sources fold
    per-batch count maps — host memory is O(#distinct), and only
    low-cardinality columns are targeted here."""
    if not target_columns:
        return {}
    from deequ_tpu.ops import runtime

    runtime.record_group_pass("profiler-histograms:" + ",".join(target_columns))
    if hasattr(data, "with_columns"):
        data = data.with_columns(list(target_columns))

    totals: Dict[str, Dict[str, int]] = {name: {} for name in target_columns}
    null_counts: Dict[str, int] = {name: 0 for name in target_columns}

    def accumulate(batch: Table) -> None:
        from deequ_tpu.ops import native

        for name in target_columns:
            col = batch.column(name)
            codes, uniques = col.dict_encode()
            counts = native.bincount(codes, len(uniques) + 1, base=1)
            if counts is None:
                counts = np.bincount(codes + 1, minlength=len(uniques) + 1)
            null_counts[name] += int(counts[0])
            bucket = totals[name]
            for i, unique in enumerate(uniques):
                count = int(counts[i + 1])
                if count == 0:
                    continue
                if col.ctype == ColumnType.BOOLEAN:
                    key = "true" if unique else "false"
                else:
                    key = str(unique)
                bucket[key] = bucket.get(key, 0) + count

    if getattr(data, "is_streaming", False):
        for batch in data.batches(getattr(data, "batch_rows", 1 << 22)):
            accumulate(batch)
    else:
        accumulate(data)

    histograms: Dict[str, Distribution] = {}
    for name in target_columns:
        values: Dict[str, DistributionValue] = {}
        if null_counts[name] > 0:
            values["NullValue"] = DistributionValue(
                null_counts[name], null_counts[name] / num_records
            )
        for key, count in totals[name].items():
            values[key] = DistributionValue(count, count / num_records)
        histograms[name] = Distribution(values, number_of_bins=len(values))
    return histograms


def _create_profiles(
    columns: Sequence[str],
    generic_stats: GenericColumnStatistics,
    numeric_stats: NumericColumnStatistics,
    histograms: Dict[str, Distribution],
) -> ColumnProfiles:
    """reference: ColumnProfiler.scala:617-669."""
    profiles = {}
    for name in columns:
        completeness = generic_stats.completenesses.get(name, 0.0)
        approx_distinct = generic_stats.approximate_num_distincts.get(name, 0)
        data_type = generic_stats.type_of(name)
        is_inferred = name in generic_stats.inferred_types
        type_counts = generic_stats.type_detection_histograms.get(name, {})
        histogram = histograms.get(name)

        if data_type in (DataTypeInstances.INTEGRAL, DataTypeInstances.FRACTIONAL):
            profile = NumericColumnProfile(
                name,
                completeness,
                approx_distinct,
                data_type,
                is_inferred,
                type_counts,
                histogram,
                mean=numeric_stats.means.get(name),
                maximum=numeric_stats.maxima.get(name),
                minimum=numeric_stats.minima.get(name),
                sum=numeric_stats.sums.get(name),
                std_dev=numeric_stats.std_devs.get(name),
                approx_percentiles=numeric_stats.approx_percentiles.get(name),
            )
        else:
            profile = StandardColumnProfile(
                name,
                completeness,
                approx_distinct,
                data_type,
                is_inferred,
                type_counts,
                histogram,
            )
        profiles[name] = profile
    return ColumnProfiles(profiles, generic_stats.num_records)

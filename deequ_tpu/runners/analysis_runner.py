"""AnalysisRunner: the scheduler/optimizer of the metrics engine.

Pipeline (reference: runners/AnalysisRunner.scala:98-193):
  1. skip analyzers whose metrics already exist in the repository,
  2. partition out analyzers with failing preconditions -> failure metrics,
  3. split grouping vs scanning analyzers,
  4. run ALL scan-shareable analyzers in ONE fused device pass,
  5. one frequency computation per distinct grouping-column-set, shared by
     every grouping analyzer over it,
  6. merge with previous results; save to repository.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Dict, List, Optional, Sequence

from deequ_tpu import observe
from deequ_tpu.analyzers.base import Analyzer, Preconditions, ScanShareableAnalyzer
from deequ_tpu.core.metrics import Metric
from deequ_tpu.data.table import Table
from deequ_tpu.ops.fused import FusedScanPass
from deequ_tpu.runners.context import AnalyzerContext

if TYPE_CHECKING:
    from deequ_tpu.analyzers.state_provider import StateLoader, StatePersister
    from deequ_tpu.repository.base import MetricsRepository, ResultKey


class AnalysisRunner:
    @staticmethod
    def on_data(table: Table) -> "AnalysisRunBuilder":
        from deequ_tpu.runners.analysis_run_builder import AnalysisRunBuilder

        return AnalysisRunBuilder(table)

    # ------------------------------------------------------------------
    @staticmethod
    def do_analysis_run(
        data: Table,
        analyzers: Sequence[Analyzer],
        aggregate_with: Optional["StateLoader"] = None,
        save_states_with: Optional["StatePersister"] = None,
        metrics_repository: Optional["MetricsRepository"] = None,
        reuse_existing_results_for_key: Optional["ResultKey"] = None,
        fail_if_results_missing: bool = False,
        save_or_append_results_with_key: Optional["ResultKey"] = None,
        engine: str = "auto",
        mesh=None,
        validation: Optional[str] = None,
        tracing=None,
        state_repository=None,
        dataset_name: str = "default",
        forensics=None,
        controller=None,
    ) -> AnalyzerContext:
        if not analyzers:
            return AnalyzerContext.empty()

        # `tracing`: True/False/an output path/None (= the
        # DEEQU_TPU_TRACE env knob). The finished RunTrace attaches to
        # the returned context as `run_trace` (the validation_warnings
        # pattern); nested under a traced verification run this becomes
        # a child subtree of the suite's trace.
        with observe.traced_run(
            "analysis_run", enable=tracing, analyzers=len(analyzers)
        ) as run:
            context = AnalysisRunner._do_analysis_run(
                data,
                analyzers,
                aggregate_with,
                save_states_with,
                metrics_repository,
                reuse_existing_results_for_key,
                fail_if_results_missing,
                save_or_append_results_with_key,
                engine,
                mesh,
                validation,
                state_repository,
                dataset_name,
                forensics,
                controller,
            )
        if run:
            context.run_trace = run.trace
        return context

    # ------------------------------------------------------------------
    @staticmethod
    def _do_analysis_run(
        data: Table,
        analyzers: Sequence[Analyzer],
        aggregate_with: Optional["StateLoader"] = None,
        save_states_with: Optional["StatePersister"] = None,
        metrics_repository: Optional["MetricsRepository"] = None,
        reuse_existing_results_for_key: Optional["ResultKey"] = None,
        fail_if_results_missing: bool = False,
        save_or_append_results_with_key: Optional["ResultKey"] = None,
        engine: str = "auto",
        mesh=None,
        validation: Optional[str] = None,
        state_repository=None,
        dataset_name: str = "default",
        forensics=None,
        controller=None,
    ) -> AnalyzerContext:
        # partition-state cache (repository/states.py): only partitioned
        # sources have a per-partition fold to cache; the context rides
        # the fused pass (the distributed/mesh path always scans)
        state_cache = None
        if (
            state_repository is not None
            and getattr(data, "partitions", None) is not None
        ):
            from deequ_tpu.repository.states import StateCacheContext

            state_cache = StateCacheContext(state_repository, dataset_name)

        # plan-time static analysis (see deequ_tpu/lint): strict raises
        # before any kernel dispatch, lenient attaches diagnostics to the
        # returned context as `validation_warnings`
        with observe.span("plan_validate", cat="plan"):
            validation_diagnostics, plan_cost = AnalysisRunner._validate_plan(
                data, analyzers, validation, state_cache
            )

        from deequ_tpu.runners.engine import resolve_engine

        mesh = resolve_engine(engine, mesh, num_rows=data.num_rows)

        # deduplicate, preserving order
        seen = set()
        unique: List[Analyzer] = []
        for a in analyzers:
            if a not in seen:
                seen.add(a)
                unique.append(a)
        analyzers = unique

        # 1. repository reuse (reference: AnalysisRunner.scala:116-135)
        reused = AnalyzerContext.empty()
        if metrics_repository is not None and reuse_existing_results_for_key is not None:
            existing = metrics_repository.load_by_key(reuse_existing_results_for_key)
            if existing is not None:
                reused_map = {
                    a: existing.metric_map[a]
                    for a in analyzers
                    if a in existing.metric_map
                }
                reused = AnalyzerContext(reused_map)
            if fail_if_results_missing:
                # internal (profiler pass-fusion) analyzers are never
                # repository-backed; their absence is not "missing"
                missing = [
                    a
                    for a in analyzers
                    if a not in reused.metric_map
                    and not getattr(a, "internal", False)
                ]
                if missing:
                    raise RuntimeError(
                        "Could not find all necessary results in the "
                        "MetricsRepository, the calculation of the metrics "
                        f"for these analyzers would be needed: "
                        f"{', '.join(repr(a) for a in missing)}"
                    )
        analyzers = [a for a in analyzers if a not in reused.metric_map]

        # 2. preconditions (reference: AnalysisRunner.scala:137-147)
        passed: List[Analyzer] = []
        failure_map: Dict[Analyzer, Metric] = {}
        for a in analyzers:
            err = Preconditions.find_first_failing(data, a.preconditions())
            if err is None:
                passed.append(a)
            else:
                failure_map[a] = a.to_failure_metric(err)
        precondition_failures = AnalyzerContext(failure_map)

        # 3. grouping vs scanning (reference: AnalysisRunner.scala:148-150)
        from deequ_tpu.analyzers.grouping import GroupingAnalyzer

        grouping = [a for a in passed if isinstance(a, GroupingAnalyzer)]
        scanning = [a for a in passed if not isinstance(a, GroupingAnalyzer)]

        # 4. fused scan pass (reference: AnalysisRunner.scala:279-326)
        scanning_results = AnalysisRunner._run_scanning_analyzers(
            data, scanning, aggregate_with, save_states_with, mesh,
            state_cache, forensics, controller,
        )

        # 5. one frequency pass per grouping-column-set
        #    (reference: AnalysisRunner.scala:164-180, 249-277)
        grouping_results = AnalyzerContext.empty()
        if grouping:
            from deequ_tpu.runners.grouping_runner import run_grouping_analyzers

            grouping_results = run_grouping_analyzers(
                data, grouping, aggregate_with, save_states_with, mesh=mesh
            )

        context = (
            reused + precondition_failures + scanning_results + grouping_results
        )
        context.validation_warnings = validation_diagnostics
        context.plan_cost = plan_cost

        # 6. save (reference: AnalysisRunner.scala:182-230)
        if metrics_repository is not None and save_or_append_results_with_key is not None:
            AnalysisRunner._save_or_append(
                metrics_repository, save_or_append_results_with_key, context
            )
        return context

    # ------------------------------------------------------------------
    @staticmethod
    def _validate_plan(data, analyzers, validation, state_cache=None):
        """-> (diagnostics, PlanCost | None). The cost prediction rides
        the same static pass and lands on the context as `plan_cost`."""
        from deequ_tpu.lint import PlanValidationError, SchemaInfo, validate_plan
        from deequ_tpu.lint.planlint import resolve_validation_mode

        mode = resolve_validation_mode(validation)
        if mode == "off":
            return [], None
        try:
            schema = SchemaInfo.from_table(data)
            streaming = bool(getattr(data, "is_streaming", False))
            cap = getattr(data, "batch_rows", None) if streaming else None
            # parquet sources expose row-group statistics: the cost pass
            # then predicts the pushdown outcome (skipped groups, batch
            # replay) the runtime will produce, trace-verifiably
            row_groups = None
            stats_fn = getattr(data, "row_group_stats", None)
            if stats_fn is not None:
                try:
                    row_groups = stats_fn()
                except Exception:  # noqa: BLE001 — stats are advisory
                    row_groups = None
            # partitioned sources: predict the state-cache split by
            # probing the repository with the SAME fingerprint + plan
            # signature the fused pass will use — so
            # `drift.partitions_cached` pins to zero on a warm run
            partitions = None
            parts_fn = getattr(data, "partitions", None)
            if parts_fn is not None:
                partitions = AnalysisRunner._predict_partitions(
                    data, analyzers, state_cache
                )
            report = validate_plan(
                schema,
                checks=(),
                required_analyzers=analyzers,
                mode=mode,
                num_rows=int(data.num_rows),
                streaming=streaming,
                stream_batch_rows=int(cap) if cap else None,
                row_groups=row_groups,
                partitions=partitions,
            )
            return list(report.diagnostics), report.plan_cost
        except PlanValidationError:
            raise
        except Exception:  # noqa: BLE001 — lint must never break a run
            return [], None

    # ------------------------------------------------------------------
    @staticmethod
    def _predict_partitions(data, analyzers, state_cache):
        """Per-partition cache prediction records for `analyze_plan`:
        `{"cached": bool, "bytes": int}` per partition, in partition
        order. Mirrors the runner's own filtering (dedupe, grouping
        split, scan-shareable only) so the probe signature matches the
        one `FusedScanPass._run_partitioned` computes."""
        import os

        from deequ_tpu.analyzers.grouping import GroupingAnalyzer
        from deequ_tpu.ops import runtime

        probe = None
        if state_cache is not None and runtime.state_cache_enabled():
            from deequ_tpu.repository.states import plan_signature_for

            seen: set = set()
            shareable = []
            for a in analyzers:
                if a in seen:
                    continue
                seen.add(a)
                if isinstance(a, ScanShareableAnalyzer) and not isinstance(
                    a, GroupingAnalyzer
                ):
                    shareable.append(a)
            probe = plan_signature_for(shareable, data)
        records = []
        for part in data.partitions():
            cached = bool(
                probe is not None
                and state_cache.repository.has_states(
                    state_cache.dataset, part.fingerprint, probe
                )
            )
            try:
                nbytes = int(os.path.getsize(part.path))
            except OSError:
                nbytes = 0
            records.append({"cached": cached, "bytes": nbytes})
        return records

    # ------------------------------------------------------------------
    @staticmethod
    def _run_scanning_analyzers(
        data: Table,
        analyzers: Sequence[Analyzer],
        aggregate_with: Optional["StateLoader"],
        save_states_with: Optional["StatePersister"],
        mesh=None,
        state_cache=None,
        forensics=None,
        controller=None,
    ) -> AnalyzerContext:
        if not analyzers:
            return AnalyzerContext.empty()

        shareable = [a for a in analyzers if isinstance(a, ScanShareableAnalyzer)]
        others = [a for a in analyzers if not isinstance(a, ScanShareableAnalyzer)]

        metrics: Dict[Analyzer, Metric] = {}
        if shareable:
            if mesh is not None:
                # the distributed pass shards batches across devices —
                # there is no per-partition fold to cache, so the mesh
                # path always scans (documented fallback); forensics
                # capture likewise degrades to provenance-only there
                from deequ_tpu.parallel.distributed import DistributedScanPass

                results = DistributedScanPass(shareable, mesh=mesh).run(data)
            else:
                results = FusedScanPass(
                    shareable, state_cache=state_cache, forensics=forensics,
                    controller=controller,
                ).run(data)
            for result in results:
                analyzer = result.analyzer
                if result.error is not None:
                    metrics[analyzer] = analyzer.to_failure_metric(result.error)
                else:
                    metrics[analyzer] = analyzer.calculate_metric(
                        result.state, aggregate_with, save_states_with
                    )
        for analyzer in others:
            metrics[analyzer] = analyzer.calculate(
                data, aggregate_with, save_states_with
            )
        return AnalyzerContext(metrics)

    # ------------------------------------------------------------------
    @staticmethod
    def run_on_aggregated_states(
        schema_table: Table,
        analyzers: Sequence[Analyzer],
        state_loaders: Sequence["StateLoader"],
        save_states_with: Optional["StatePersister"] = None,
        metrics_repository: Optional["MetricsRepository"] = None,
        save_or_append_results_with_key: Optional["ResultKey"] = None,
    ) -> AnalyzerContext:
        """Metrics purely from merged states — NO data scan
        (reference: runners/AnalysisRunner.scala:375-446)."""
        from deequ_tpu.analyzers.state_provider import InMemoryStateProvider

        if not analyzers or not state_loaders:
            return AnalyzerContext.empty()

        # precondition check against the schema
        passed: List[Analyzer] = []
        failure_map: Dict[Analyzer, Metric] = {}
        for a in analyzers:
            err = Preconditions.find_first_failing(schema_table, a.preconditions())
            if err is None:
                passed.append(a)
            else:
                failure_map[a] = a.to_failure_metric(err)

        aggregated = InMemoryStateProvider()
        with observe.span(
            "state_merge", cat="state",
            analyzers=len(passed), loaders=len(state_loaders),
        ):
            for analyzer in passed:
                for loader in state_loaders:
                    state = loader.load(analyzer)
                    if state is None:
                        continue
                    existing = aggregated.load(analyzer)
                    merged = (
                        existing.merge(state) if existing is not None else state
                    )
                    aggregated.persist(analyzer, merged)

        metrics: Dict[Analyzer, Metric] = dict(failure_map)
        for analyzer in passed:
            state = aggregated.load(analyzer)
            if save_states_with is not None and state is not None:
                save_states_with.persist(analyzer, state)
            metrics[analyzer] = analyzer.compute_metric_from(state)

        context = AnalyzerContext(metrics)
        if metrics_repository is not None and save_or_append_results_with_key is not None:
            AnalysisRunner._save_or_append(
                metrics_repository, save_or_append_results_with_key, context
            )
        return context

    # ------------------------------------------------------------------
    @staticmethod
    def _save_or_append(
        repository: "MetricsRepository",
        key: "ResultKey",
        context: AnalyzerContext,
    ) -> None:
        """Upsert semantics (reference: AnalysisRunner.scala:195-213).
        Internal analyzers (profiler pass-fusion members) never reach the
        repository: their metrics carry raw states and have no serde."""
        internal = [
            a
            for a in context.metric_map
            if getattr(a, "internal", False)
        ]
        if internal:
            context = AnalyzerContext(
                {
                    a: m
                    for a, m in context.metric_map.items()
                    if not getattr(a, "internal", False)
                }
            )
        existing = repository.load_by_key(key)
        combined = (existing + context) if existing is not None else context
        repository.save(key, combined)

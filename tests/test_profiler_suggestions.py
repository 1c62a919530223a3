"""Profiler, suggestion, applicability and schema-validator tests
(mirrors reference ColumnProfilerTest, ConstraintRulesTest,
ConstraintSuggestionsIntegrationTest, ApplicabilityTest,
RowLevelSchemaValidatorTest)."""

import json

import numpy as np
import pytest

from deequ_tpu.data.table import ColumnType, Table
from deequ_tpu.ops import runtime
from deequ_tpu.profiles import (
    ColumnProfilerRunner,
    NumericColumnProfile,
    StandardColumnProfile,
)
from deequ_tpu.suggestions import (
    CategoricalRangeRule,
    CompleteIfCompleteRule,
    ConstraintSuggestionRunner,
    NonNegativeNumbersRule,
    RetainCompletenessRule,
    RetainTypeRule,
    Rules,
    UniqueIfApproximatelyUniqueRule,
)


def example_table(n=120):
    rng = np.random.default_rng(0)
    return Table.from_pydict(
        {
            "id": list(range(n)),
            "name": [f"name_{i}" for i in range(n)],
            "status": [["active", "inactive", "pending"][i % 3] for i in range(n)],
            "amountStr": [str(i * 10) for i in range(n)],
            "score": [float(i) / 2 if i % 10 != 0 else None for i in range(n)],
            "flag": [bool(i % 2) for i in range(n)],
        }
    )


class TestColumnProfiler:
    def test_pass_budget(self):
        # the reference always pays 3 scans; ours pays ONE: pass-2
        # numeric stats for inferred-numeric strings (amountStr) ride
        # pass 1 optimistically (_OptimisticNumericStats — sound because
        # a numeric inference verdict implies every value cast cleanly)
        # and pass-3 histogram counting folds in via _LowCardCounts
        data = example_table()
        with runtime.monitored() as stats:
            profiles = ColumnProfilerRunner.on_data(data).run()
        assert stats.jobs == 1
        assert profiles.num_records == 120

    def test_repository_reuse_covers_both_passes(self):
        """Every pass threads the repository options (the reference does
        too, ColumnProfiler.scala:128-153): a saved key holds metrics
        from pass 1 AND the cast pass, and a strict reuse-run against it
        recomputes nothing."""
        from deequ_tpu.repository.base import ResultKey
        from deequ_tpu.repository.memory import InMemoryMetricsRepository

        data = example_table()
        repo = InMemoryMetricsRepository()
        key = ResultKey(1234, {"run": "a"})
        first = (
            ColumnProfilerRunner.on_data(data)
            .use_repository(repo)
            .save_or_append_result(key)
            .run()
        )
        # amountStr is an inferred-numeric STRING column -> its stats come
        # from the cast pass and must have been saved too
        saved = repo.load_by_key(key)
        assert any(
            getattr(a, "column", None) == "amountStr" and a.name == "Mean"
            for a in saved.metric_map
        )
        with runtime.monitored() as stats:
            second = (
                ColumnProfilerRunner.on_data(data)
                .use_repository(repo)
                .reuse_existing_results_for_key(key, fail_if_results_missing=True)
                .run()
            )
        assert stats.device_launches == 0  # everything served from the repo
        assert second.profiles["amountStr"].mean == first.profiles["amountStr"].mean
        assert second.profiles["id"].mean == first.profiles["id"].mean

    def test_two_passes_without_numeric_strings(self):
        # no inferred-numeric string columns -> still one fused pass
        # (histograms fold into pass 1 via _LowCardCounts)
        data = Table.from_pydict(
            {
                "id": list(range(50)),
                "score": [float(i) for i in range(50)],
                "status": [["a", "b"][i % 2] for i in range(50)],
            }
        )
        with runtime.monitored() as stats:
            profiles = ColumnProfilerRunner.on_data(data).run()
        assert stats.jobs == 1
        # schema-numeric stats still fully populated from pass 1
        assert profiles.profiles["id"].mean == pytest.approx(24.5)
        assert profiles.profiles["score"].maximum == 49.0

    def test_profile_contents(self):
        data = example_table()
        profiles = ColumnProfilerRunner.on_data(data).run()

        id_profile = profiles.profiles["id"]
        assert isinstance(id_profile, NumericColumnProfile)
        assert id_profile.data_type == "Integral"
        assert not id_profile.is_data_type_inferred
        assert id_profile.completeness == 1.0
        assert id_profile.minimum == 0.0
        assert id_profile.maximum == 119.0
        assert id_profile.mean == pytest.approx(59.5)
        assert id_profile.sum == pytest.approx(7140.0)
        assert len(id_profile.approx_percentiles) == 100

        # string column inferred integral -> numeric profile with stats
        amount = profiles.profiles["amountStr"]
        assert isinstance(amount, NumericColumnProfile)
        assert amount.data_type == "Integral"
        assert amount.is_data_type_inferred
        assert amount.minimum == 0.0
        assert amount.maximum == 1190.0

        status = profiles.profiles["status"]
        assert isinstance(status, StandardColumnProfile)
        assert status.data_type == "String"
        assert status.histogram is not None
        assert status.histogram["active"].absolute == 40

        score = profiles.profiles["score"]
        assert score.completeness == pytest.approx(108 / 120)

        flag = profiles.profiles["flag"]
        assert flag.data_type == "Boolean"
        assert flag.histogram is not None
        assert flag.histogram["true"].absolute == 60

    def test_restrict_to_columns(self):
        data = example_table()
        profiles = (
            ColumnProfilerRunner.on_data(data).restrict_to_columns(["id", "status"]).run()
        )
        assert set(profiles.profiles) == {"id", "status"}

    def test_failed_data_type_names_the_column_and_its_cause(self, monkeypatch):
        """A string column whose DataType metric failed has no type: the
        profile fails, as upstream's `typeOf` does, naming the column and
        chaining the metric's own exception (not a bare KeyError)."""
        from deequ_tpu.analyzers import DataType
        from deequ_tpu.core.exceptions import MetricCalculationRuntimeException

        planted = RuntimeError("planted DataType failure")

        def fail(self, state):
            if self.column == "status":
                return self.to_failure_metric(planted)
            return real(self, state)

        real = DataType.compute_metric_from
        monkeypatch.setattr(DataType, "compute_metric_from", fail)
        with pytest.raises(MetricCalculationRuntimeException) as err:
            ColumnProfilerRunner.on_data(example_table()).run()
        assert not isinstance(err.value, KeyError)
        message = str(err.value)
        assert "'status'" in message and "planted DataType failure" in message
        cause = err.value.__cause__
        assert isinstance(cause, MetricCalculationRuntimeException)
        assert cause.__cause__ is planted

    def test_cardinality_threshold(self):
        data = example_table()
        profiles = (
            ColumnProfilerRunner.on_data(data)
            .with_low_cardinality_histogram_threshold(2)
            .run()
        )
        assert profiles.profiles["status"].histogram is None

    def test_json_export(self, tmp_path):
        data = example_table()
        path = str(tmp_path / "profiles.json")
        ColumnProfilerRunner.on_data(data).save_column_profiles_json_to_path(path).run()
        with open(path) as f:
            parsed = json.load(f)
        by_column = {c["column"]: c for c in parsed["columns"]}
        assert by_column["id"]["dataType"] == "Integral"
        assert "histogram" in by_column["status"]


class TestSuggestionRules:
    def profile_for(self, data):
        from deequ_tpu.profiles import ColumnProfiler

        return ColumnProfilerRunner.on_data(data).run()

    def test_complete_if_complete(self):
        profiles = self.profile_for(example_table())
        rule = CompleteIfCompleteRule()
        assert rule.should_be_applied(profiles.profiles["id"], 120)
        assert not rule.should_be_applied(profiles.profiles["score"], 120)
        suggestion = rule.candidate(profiles.profiles["id"], 120)
        assert suggestion.code_for_constraint == '.is_complete("id")'

    def test_retain_completeness(self):
        profiles = self.profile_for(example_table())
        rule = RetainCompletenessRule()
        assert rule.should_be_applied(profiles.profiles["score"], 120)
        suggestion = rule.candidate(profiles.profiles["score"], 120)
        assert ".has_completeness" in suggestion.code_for_constraint

    def test_retain_type(self):
        profiles = self.profile_for(example_table())
        rule = RetainTypeRule()
        assert rule.should_be_applied(profiles.profiles["amountStr"], 120)
        assert not rule.should_be_applied(profiles.profiles["id"], 120)  # not inferred
        suggestion = rule.candidate(profiles.profiles["amountStr"], 120)
        assert "ConstrainableDataTypes.INTEGRAL" in suggestion.code_for_constraint

    def test_categorical_range(self):
        profiles = self.profile_for(example_table())
        rule = CategoricalRangeRule()
        assert rule.should_be_applied(profiles.profiles["status"], 120)
        suggestion = rule.candidate(profiles.profiles["status"], 120)
        assert '"active"' in suggestion.code_for_constraint

    def test_non_negative(self):
        profiles = self.profile_for(example_table())
        rule = NonNegativeNumbersRule()
        assert rule.should_be_applied(profiles.profiles["id"], 120)
        suggestion = rule.candidate(profiles.profiles["id"], 120)
        assert suggestion.code_for_constraint == '.is_non_negative("id")'

    def test_unique_if_approximately_unique(self):
        profiles = self.profile_for(example_table())
        rule = UniqueIfApproximatelyUniqueRule()
        assert rule.should_be_applied(profiles.profiles["id"], 120)
        assert not rule.should_be_applied(profiles.profiles["status"], 120)


class TestSuggestionRunner:
    def test_end_to_end(self):
        data = example_table()
        result = (
            ConstraintSuggestionRunner.on_data(data)
            .add_constraint_rules(Rules.DEFAULT)
            .run()
        )
        codes = [s.code_for_constraint for s in result.all_suggestions()]
        assert '.is_complete("id")' in codes
        assert any(".is_contained_in" in c for c in codes)
        parsed = json.loads(result.suggestions_as_json())
        assert len(parsed["constraint_suggestions"]) == len(codes)

    def test_train_test_split_evaluation(self):
        data = example_table(400)
        result = (
            ConstraintSuggestionRunner.on_data(data)
            .add_constraint_rules(Rules.DEFAULT)
            .use_train_test_split_with_test_set_ratio(0.25, seed=7)
            .run()
        )
        assert result.verification_result is not None
        # generated constraints should mostly hold on the test split
        check_result = list(result.verification_result.check_results.values())[0]
        from deequ_tpu.constraints.constraint import ConstraintStatus

        statuses = [r.status for r in check_result.constraint_results]
        assert statuses.count(ConstraintStatus.SUCCESS) >= len(statuses) - 1


class TestApplicability:
    def test_applicable_check(self):
        from deequ_tpu import Check, CheckLevel
        from deequ_tpu.applicability import Applicability
        from deequ_tpu.applicability.applicability import SchemaField

        schema = [
            SchemaField("item", ColumnType.STRING),
            SchemaField("count", ColumnType.LONG, nullable=False),
        ]
        check = (
            Check(CheckLevel.ERROR, "c")
            .is_complete("count")
            .has_min("count", lambda v: v > -(2**32))
        )
        result = Applicability().is_applicable(check, schema)
        assert result.is_applicable

    def test_detects_missing_column(self):
        from deequ_tpu import Check, CheckLevel
        from deequ_tpu.applicability import Applicability
        from deequ_tpu.applicability.applicability import SchemaField

        schema = [SchemaField("item", ColumnType.STRING)]
        check = Check(CheckLevel.ERROR, "c").is_complete("notHere")
        result = Applicability().is_applicable(check, schema)
        assert not result.is_applicable
        assert len(result.failures) == 1

    def test_detects_invalid_sql(self):
        from deequ_tpu import Check, CheckLevel
        from deequ_tpu.applicability import Applicability
        from deequ_tpu.applicability.applicability import SchemaField

        schema = [SchemaField("item", ColumnType.STRING)]
        check = Check(CheckLevel.ERROR, "c").satisfies("!!invalid sql!!", "bad")
        result = Applicability().is_applicable(check, schema)
        assert not result.is_applicable

    def test_generated_data_shapes(self):
        from deequ_tpu.applicability.applicability import SchemaField, generate_random_data

        schema = [
            SchemaField("s", ColumnType.STRING),
            SchemaField("i", ColumnType.LONG),
            SchemaField("f", ColumnType.DOUBLE),
            SchemaField("b", ColumnType.BOOLEAN),
            SchemaField("d", ColumnType.DECIMAL, precision=6, scale=2),
            SchemaField("t", ColumnType.TIMESTAMP),
            SchemaField("nn", ColumnType.LONG, nullable=False),
        ]
        data = generate_random_data(schema, 1000, seed=1)
        assert data.num_rows == 1000
        assert data["nn"].null_count == 0
        # ~1% nulls for nullable fields
        assert 0 <= data["s"].null_count <= 50


class TestRowLevelSchemaValidator:
    def test_valid_invalid_split(self):
        from deequ_tpu.schema import RowLevelSchema, RowLevelSchemaValidator

        data = Table.from_pydict(
            {
                "id": ["1", "2", "x", "4", None],
                "name": ["a", "bb", "ccc", "", "e"],
                "ts": [
                    "2024-01-01 10:00:00",
                    "2024-02-30 10:00:00",  # invalid date
                    "2024-03-01 11:00:00",
                    "2024-04-01 12:00:00",
                    "2024-05-01 13:00:00",
                ],
            }
        )
        schema = (
            RowLevelSchema()
            .with_int_column("id", is_nullable=False, min_value=1)
            .with_string_column("name", min_length=1)
            .with_timestamp_column("ts", mask="yyyy-MM-dd HH:mm:ss")
        )
        result = RowLevelSchemaValidator.validate(data, schema)
        # row0 ok; row1 bad ts; row2 bad int; row3 empty name; row4 null id
        assert result.num_valid_rows == 1
        assert result.num_invalid_rows == 4
        assert result.valid_rows["id"].ctype == ColumnType.LONG
        assert int(result.valid_rows["id"].values[0]) == 1

    def test_int_bounds(self):
        from deequ_tpu.schema import RowLevelSchema, RowLevelSchemaValidator

        data = Table.from_pydict({"v": ["5", "15", "25"]})
        schema = RowLevelSchema().with_int_column("v", min_value=10, max_value=20)
        result = RowLevelSchemaValidator.validate(data, schema)
        assert result.num_valid_rows == 1
        assert int(result.valid_rows["v"].values[0]) == 15

    def test_string_regex(self):
        from deequ_tpu.schema import RowLevelSchema, RowLevelSchemaValidator

        data = Table.from_pydict({"code": ["AB-1", "XY-2", "bad"]})
        schema = RowLevelSchema().with_string_column("code", matches=r"^[A-Z]{2}-\d$")
        result = RowLevelSchemaValidator.validate(data, schema)
        assert result.num_valid_rows == 2


class TestLowCardCountsCap:
    def test_cumulative_distinct_cap_aborts_merge(self):
        """A stream whose batches each stay under the cap but whose
        cumulative dictionary does not must abort (bounded memory), not
        grow without bound (reviewer finding, round 4)."""
        from deequ_tpu.profiles.internal_analyzers import LowCardCountsState

        state = None
        for batch in range(10):
            partial = LowCardCountsState(
                tuple((f"v{batch}_{i}", 1) for i in range(100)), 0, False, 300
            )
            state = partial if state is None else state.merge(partial)
        assert state.aborted
        assert state.counts == ()

    def test_streamed_rotating_values_fall_back_to_straggler_pass(self, tmp_path):
        """End-to-end: rotating per-batch dictionaries abort the fused
        counting; the profiler's straggler pass never runs because the
        HLL estimate exceeds the threshold (no histogram wanted)."""
        import pyarrow as pa
        import pyarrow.parquet as pq

        rows = []
        for g in range(6):
            rows.extend([f"g{g}_v{i}" for i in range(200)] * 5)
        table = pa.table({"s": rows, "x": list(range(len(rows)))})
        path = str(tmp_path / "rot.parquet")
        pq.write_table(table, path, row_group_size=1000)
        profiles = ColumnProfilerRunner.on_data(
            Table.scan_parquet(path, batch_rows=1000)
        ).run()
        assert profiles.profiles["s"].histogram is None  # 1200 distinct > 120


class TestOptimisticPass2Fallback:
    def test_regex_numeric_but_uncastable_falls_back_to_pass2(self):
        """THE soundness edge: '+ 5' matches the Integral regex
        (reference: StatefulDataType.scala:37 allows one space after the
        sign) but float() cannot parse it. Inference says Integral, the
        optimistic state dies, and the profiler must pay a real pass 2
        whose cast nulls the unparseable value — same as the reference's
        cast semantics."""
        data = Table.from_pydict(
            {"v": ["+ 5", "3", "7", None] * 30}
        )
        with runtime.monitored() as stats:
            profiles = ColumnProfilerRunner.on_data(data).run()
        p = profiles.profiles["v"]
        assert p.data_type == "Integral"  # regex-based inference
        # cast: '+ 5' -> null; mean over {3,7}
        assert p.mean == pytest.approx(5.0)
        assert stats.jobs == 2  # optimistic died -> classic pass 2 ran

    def test_differential_profile_vs_pandas(self):
        """Randomized differential: the one-pass profile must match a
        straightforward pandas ground truth on exact statistics for
        mixed schemas with nulls, numeric strings, empty strings and
        unicode."""
        import pandas as pd

        rng = np.random.default_rng(123)
        for trial in range(5):
            n = int(rng.integers(200, 3000))
            num = rng.normal(10, 3, n)
            num[rng.random(n) < 0.1] = np.nan
            codes = np.array(
                [str(v) for v in rng.integers(-50, 50, n)], dtype=object
            )
            cats = np.array(
                ["α", "beta", "", "Ωmega", None], dtype=object
            )[rng.integers(0, 5, n)]
            flags = np.where(rng.random(n) > 0.2, rng.random(n) < 0.5, None)
            t = Table.from_numpy(
                {"num": num, "code": codes, "cat": cats, "flag": flags}
            )
            profiles = ColumnProfilerRunner.on_data(t).run()

            s = pd.Series(num)
            p = profiles.profiles["num"]
            assert p.completeness == pytest.approx(s.notna().mean())
            assert p.mean == pytest.approx(s.mean(), rel=1e-9)
            assert p.minimum == s.min() and p.maximum == s.max()
            assert p.std_dev == pytest.approx(s.std(ddof=0), rel=1e-9)

            pc = profiles.profiles["code"]
            cast = pd.to_numeric(pd.Series(codes), errors="coerce")
            assert pc.data_type == "Integral"
            assert pc.mean == pytest.approx(cast.mean(), rel=1e-9)
            assert pc.sum == pytest.approx(cast.sum(), rel=1e-9)

            pcat = profiles.profiles["cat"]
            counts = pd.Series(cats).value_counts(dropna=False)
            hist = {k: v.absolute for k, v in pcat.histogram.values.items()}
            want = {
                ("NullValue" if pd.isna(k) else str(k)): int(c)
                for k, c in counts.items()
            }
            assert hist == want, (trial, hist, want)

            pf = profiles.profiles["flag"]
            fs = pd.Series(list(flags))
            assert pf.completeness == pytest.approx(fs.notna().mean())

"""Out-of-core streaming input: every pass over a ParquetSource must
produce exactly the metrics of the same data held in memory
(reference scale claim: README.md:43 — "billions of rows" via streamed
partitions; here streamed Arrow batches)."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from deequ_tpu.analyzers import (
    ApproxCountDistinct,
    ApproxQuantile,
    Completeness,
    CountDistinct,
    DataType,
    Distinctness,
    Entropy,
    Histogram,
    Maximum,
    Mean,
    MutualInformation,
    PatternMatch,
    Size,
    StandardDeviation,
    Uniqueness,
)
from deequ_tpu.checks import Check, CheckLevel
from deequ_tpu.data.table import Table
from deequ_tpu.profiles.column_profiler import ColumnProfiler
from deequ_tpu.runners.analysis_runner import AnalysisRunner
from deequ_tpu.verification import VerificationSuite


@pytest.fixture(scope="module")
def parquet_path(tmp_path_factory):
    rng = np.random.default_rng(7)
    n = 30_000
    x = rng.normal(5.0, 2.0, n)
    x[rng.random(n) < 0.05] = np.nan
    cats = np.array(["red", "green", "blue", None], dtype=object)
    table = pa.table(
        {
            "x": x,
            "qty": rng.integers(0, 50, n),
            "cat": cats[rng.integers(0, 4, n)],
            "code": [str(v) for v in rng.integers(0, 500, n)],
        }
    )
    path = str(tmp_path_factory.mktemp("pq") / "data.parquet")
    pq.write_table(table, path, row_group_size=4096)
    return path


ANALYZERS = [
    Size(),
    Completeness("x"),
    Mean("x"),
    Maximum("x"),
    StandardDeviation("x"),
    ApproxCountDistinct("qty"),
    ApproxQuantile("x", 0.5),
    DataType("code"),
    PatternMatch("cat", r"^re"),
    Uniqueness(["cat"]),
    Distinctness(["cat"]),
    Entropy("cat"),
    CountDistinct(["cat", "qty"]),
    MutualInformation("cat", "qty"),
    Histogram("cat"),
]


class TestStreamingParity:
    def test_all_analyzers_match_in_memory(self, parquet_path):
        source = Table.scan_parquet(parquet_path, batch_rows=4096)
        memory = Table.from_parquet(parquet_path)
        ctx_s = AnalysisRunner.on_data(source).add_analyzers(ANALYZERS).run()
        ctx_m = AnalysisRunner.on_data(memory).add_analyzers(ANALYZERS).run()
        for analyzer in ANALYZERS:
            ms, mm = ctx_s.metric_map[analyzer], ctx_m.metric_map[analyzer]
            assert ms.value.is_success, (analyzer, ms.value)
            assert mm.value.is_success, (analyzer, mm.value)
            vs, vm = ms.value.get(), mm.value.get()
            if isinstance(vs, float):
                if repr(analyzer).startswith("ApproxQuantile"):
                    # KLL partials differ by batching; equal within error
                    assert vs == pytest.approx(vm, abs=0.1), analyzer
                else:
                    assert vs == pytest.approx(vm, rel=1e-9), analyzer
            else:
                assert vs == vm, analyzer

    def test_profiler_matches_in_memory(self, parquet_path):
        source = Table.scan_parquet(parquet_path, batch_rows=4096)
        memory = Table.from_parquet(parquet_path)
        ps = ColumnProfiler.profile(source)
        pm = ColumnProfiler.profile(memory)
        assert ps.num_records == pm.num_records
        for name in ("x", "qty", "cat", "code"):
            s, m = ps.profiles[name], pm.profiles[name]
            assert s.data_type == m.data_type, name
            assert s.completeness == pytest.approx(m.completeness, rel=1e-9)
            assert s.approximate_num_distinct_values == (
                m.approximate_num_distinct_values
            )
            if getattr(s, "mean", None) is not None:
                assert s.mean == pytest.approx(m.mean, rel=1e-9)
        # histogram for the low-cardinality string column, incl. nulls
        hs = ps.profiles["cat"].histogram
        hm = pm.profiles["cat"].histogram
        assert hs is not None and hm is not None
        assert {k: v.absolute for k, v in hs.values.items()} == {
            k: v.absolute for k, v in hm.values.items()
        }

    def test_verification_suite_on_source(self, parquet_path):
        source = Table.scan_parquet(parquet_path, batch_rows=8192)
        check = (
            Check(CheckLevel.ERROR, "stream checks")
            .has_size(lambda s: s == 30_000)
            .has_completeness("x", lambda v: 0.9 < v < 1.0)
            .has_entropy("cat", lambda v: v > 0.5)
        )
        result = VerificationSuite.on_data(source).add_check(check).run()
        assert result.status.name == "SUCCESS", [
            (cr.constraint, cr.message)
            for cr in result.check_results[check].constraint_results
        ]

    def test_source_schema_and_preconditions(self, parquet_path):
        from deequ_tpu.core.exceptions import NoSuchColumnException

        source = Table.scan_parquet(parquet_path)
        assert source.num_rows == 30_000
        assert set(source.column_names) == {"x", "qty", "cat", "code"}
        with pytest.raises(NoSuchColumnException):
            source.column("nope")
        ctx = AnalysisRunner.on_data(source).add_analyzers([Mean("cat")]).run()
        assert ctx.metric_map[Mean("cat")].value.is_failure  # not numeric

    def test_empty_parquet(self, tmp_path):
        path = str(tmp_path / "empty.parquet")
        pq.write_table(pa.table({"a": pa.array([], type=pa.float64())}), path)
        source = Table.scan_parquet(path)
        ctx = AnalysisRunner.on_data(source).add_analyzers([Size(), Mean("a")]).run()
        assert ctx.metric_map[Size()].value.get() == 0.0
        assert ctx.metric_map[Mean("a")].value.is_failure  # empty state

    def test_bounded_prefetch(self, parquet_path):
        """Decode stays at most (queue=2)+1 batches ahead of the
        consumer — the structural bound behind constant host memory."""
        import time

        from deequ_tpu.data.source import ParquetSource

        class Counting(ParquetSource):
            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                self.decoded = 0

            def _iter_tables(self, batch_size):
                for t in super()._iter_tables(batch_size):
                    self.decoded += 1
                    yield t

        source = Counting(parquet_path, batch_rows=1024)  # ~30 batches
        gen = source.batches(1024)
        next(gen)
        time.sleep(0.3)  # give the producer every chance to run ahead
        assert source.decoded <= 4  # 1 consumed + queue(2) + 1 in-flight
        consumed = 1 + sum(1 for _ in gen)
        assert consumed == 30  # all batches arrive
        assert source.decoded == 30

    def test_column_projection(self, parquet_path):
        source = Table.scan_parquet(parquet_path, columns=["x", "cat"])
        assert set(source.column_names) == {"x", "cat"}
        ctx = AnalysisRunner.on_data(source).add_analyzers([Completeness("cat")]).run()
        assert ctx.metric_map[Completeness("cat")].value.is_success

    def test_mapped_source_undeclared_fn_is_not_pruned(self, parquet_path):
        """A MappedSource whose fn derives one column from another must
        not have its base pruned to the analyzer-consumed columns: the
        derivation input would go missing and silently skew the metric
        (advisor finding, round 3). Undeclared read set => no pruning;
        declared => base keeps names ∪ fn_columns."""
        from deequ_tpu.data.source import MappedSource
        from deequ_tpu.data.table import Column, ColumnType

        base = Table.scan_parquet(parquet_path)

        def scale_x_by_qty(batch):
            x = batch.column("x")
            qty = batch.column("qty")  # NOT analyzed below: prune bait
            return batch.with_column(
                Column(
                    "x",
                    ColumnType.DOUBLE,
                    np.asarray(x.values, dtype=np.float64)
                    * np.asarray(qty.values, dtype=np.float64),
                    x.valid & qty.valid,
                )
            )

        expected = (
            AnalysisRunner.on_data(
                MappedSource(Table.scan_parquet(parquet_path), scale_x_by_qty)
            )
            .add_analyzers([Mean("x")])
            .run()
            .metric_map[Mean("x")]
            .value.get()
        )

        # undeclared: with_columns must be a no-op (fn still sees qty)
        undeclared = MappedSource(base, scale_x_by_qty)
        pruned = undeclared.with_columns(["x"])
        got = (
            AnalysisRunner.on_data(pruned)
            .add_analyzers([Mean("x")])
            .run()
            .metric_map[Mean("x")]
            .value.get()
        )
        assert got == pytest.approx(expected, rel=1e-12)

        # declared: base is pruned to names ∪ fn_columns, fn still works
        declared = MappedSource(
            Table.scan_parquet(parquet_path),
            scale_x_by_qty,
            fn_columns=["x", "qty"],
        )
        got2 = (
            AnalysisRunner.on_data(declared.with_columns(["x"]))
            .add_analyzers([Mean("x")])
            .run()
            .metric_map[Mean("x")]
            .value.get()
        )
        assert got2 == pytest.approx(expected, rel=1e-12)
        assert "cat" not in declared.with_columns(["x"]).base.column_names

    def test_timestamp_and_decimal_parity(self, tmp_path):
        """Timestamp and decimal columns behave identically in-memory
        and streamed through the (round-4) zero-copy materialization:
        decimals compute numerics, Min/Max on timestamps raise the
        reference's WrongColumnTypeException (isNumeric precondition),
        completeness counts nulls exactly."""
        import decimal

        from deequ_tpu.analyzers import Minimum
        from deequ_tpu.core.exceptions import WrongColumnTypeException

        rng = np.random.default_rng(5)
        n = 20_000
        ts = pa.array(
            [
                None
                if i % 17 == 0
                else v
                for i, v in enumerate(
                    (
                        rng.integers(1_500_000_000, 1_700_000_000, n)
                        * 1_000_000
                    ).astype("datetime64[us]")
                )
            ]
        )
        dec = pa.array(
            [
                None
                if i % 13 == 0
                else decimal.Decimal(
                    f"{rng.integers(0, 10000)}.{rng.integers(0, 100):02d}"
                )
                for i in range(n)
            ],
            type=pa.decimal128(12, 2),
        )
        path = str(tmp_path / "tsdec.parquet")
        pq.write_table(pa.table({"ts": ts, "dec": dec}), path, row_group_size=4096)

        analyzers = [
            Completeness("ts"),
            Completeness("dec"),
            Mean("dec"),
            Minimum("dec"),
            Maximum("dec"),
            Minimum("ts"),
        ]  # Mean/Maximum come from the module-level import
        results = {}
        for label, tab in (
            ("mem", Table.from_parquet(path)),
            ("stream", Table.scan_parquet(path)),
        ):
            ctx = AnalysisRunner.on_data(tab).add_analyzers(analyzers).run()
            results[label] = ctx.metric_map
        for analyzer in analyzers:
            m, s = results["mem"][analyzer], results["stream"][analyzer]
            assert m.value.is_success == s.value.is_success, repr(analyzer)
            if m.value.is_success:
                assert m.value.get() == pytest.approx(s.value.get(), rel=1e-12)
        assert results["mem"][Completeness("ts")].value.get() == pytest.approx(
            sum(1 for i in range(n) if i % 17 != 0) / n
        )
        failure = results["mem"][Minimum("ts")].value
        assert not failure.is_success
        assert isinstance(failure.exception, WrongColumnTypeException)

    def test_tiny_row_groups_coalesce(self, tmp_path):
        """Files written with tiny row groups (incremental writers)
        coalesce into batch-sized chunks — per-batch fold machinery must
        not multiply 100x — while ~batch-sized groups pass through
        without the dictionary-unifying concat (reviewer finding +
        measured tradeoff, round 4)."""
        import collections

        rng = np.random.default_rng(1)
        n = 200_000
        table = pa.table(
            {
                "x": rng.normal(0, 1, n),
                "c": np.array(["p", "q", "r"], dtype=object)[
                    rng.integers(0, 3, n)
                ],
            }
        )
        path = str(tmp_path / "tiny_groups.parquet")
        pq.write_table(table, path, row_group_size=2000)  # 100 tiny groups

        source = Table.scan_parquet(path, batch_rows=1 << 20)
        batches = list(source.batches(1 << 20))
        assert len(batches) <= 2  # coalesced, not 100
        assert sum(b.num_rows for b in batches) == n

        ctx = (
            AnalysisRunner.on_data(Table.scan_parquet(path))
            .add_analyzers([Size(), Mean("x"), Histogram("c")])
            .run()
        )
        assert ctx.metric_map[Size()].value.get() == n
        hist = {
            k: v.absolute
            for k, v in ctx.metric_map[Histogram("c")].value.get().values.items()
        }
        assert hist == dict(collections.Counter(table.column("c").to_pylist()))

    def test_source_stall_knob_is_inert_on_results(self, tmp_path, monkeypatch):
        """DEEQU_TPU_SOURCE_STALL_MS (the object-store latency model)
        delays the decoding thread but must
        never change what the stream yields — same batches, same metrics
        — and malformed values fall back to off."""
        from deequ_tpu.ops import runtime

        monkeypatch.setenv("DEEQU_TPU_SOURCE_STALL_MS", "garbage")
        assert runtime.source_stall_s() == 0.0
        monkeypatch.setenv("DEEQU_TPU_SOURCE_STALL_MS", "-5")
        assert runtime.source_stall_s() == 0.0
        monkeypatch.setenv("DEEQU_TPU_SOURCE_STALL_MS", "2.5")
        assert runtime.source_stall_s() == 0.0025

        rng = np.random.default_rng(3)
        n = 30_000
        table = pa.table(
            {
                "x": rng.normal(0, 1, n),
                "c": np.array(["p", "q"], dtype=object)[rng.integers(0, 2, n)],
            }
        )
        path = str(tmp_path / "stalled.parquet")
        pq.write_table(table, path, row_group_size=10_000)

        def metrics():
            ctx = (
                AnalysisRunner.on_data(Table.scan_parquet(path))
                .add_analyzers([Size(), Mean("x")])
                .run()
            )
            return (
                ctx.metric_map[Size()].value.get(),
                ctx.metric_map[Mean("x")].value.get(),
            )

        stalled = metrics()  # 3 row groups x 2.5ms, exercises the sleep
        monkeypatch.delenv("DEEQU_TPU_SOURCE_STALL_MS")
        assert metrics() == stalled

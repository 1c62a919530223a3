"""Mesh × streaming integration: the production shape of the 1B-row
target — DistributedScanPass and the grouping path fed by a ParquetSource
on the 8-device CPU mesh, asserted against the in-memory single-device
run (the streaming analogue of StateAggregationIntegrationTest)."""

from __future__ import annotations

import numpy as np
import pytest

from deequ_tpu.analyzers import (
    ApproxCountDistinct,
    Completeness,
    CountDistinct,
    Entropy,
    Maximum,
    Mean,
    Minimum,
    Size,
    StandardDeviation,
    Sum,
    Uniqueness,
)
from deequ_tpu.analyzers.sketch import ApproxQuantile
from deequ_tpu.data.source import ParquetSource
from deequ_tpu.data.table import Table
from deequ_tpu.parallel.distributed import DistributedScanPass, data_mesh
from deequ_tpu.runners.analysis_runner import AnalysisRunner

N_ROWS = 200_000


@pytest.fixture(scope="module")
def parquet_path(tmp_path_factory):
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(3)
    x = rng.normal(5.0, 3.0, N_ROWS)
    x[::17] = np.nan
    cat = np.array(["red", "green", "blue", None], dtype=object)[
        rng.integers(0, 4, N_ROWS)
    ]
    g = rng.integers(0, 500, N_ROWS)
    path = tmp_path_factory.mktemp("streammesh") / "data.parquet"
    table = pa.table(
        {
            "x": pa.array(x, mask=np.isnan(x)),
            "cat": pa.array(list(cat)),
            "g": pa.array(g),
        }
    )
    # several row groups so streaming actually iterates
    pq.write_table(table, str(path), row_group_size=50_000)
    return str(path)


@pytest.fixture(scope="module")
def in_memory(parquet_path):
    return Table.from_parquet(parquet_path)


SCAN_ANALYZERS = [
    Size(),
    Completeness("x"),
    Mean("x"),
    Minimum("x"),
    Maximum("x"),
    Sum("x"),
    StandardDeviation("x"),
    ApproxCountDistinct("g"),
    ApproxCountDistinct("cat"),
]


def test_distributed_scan_over_parquet_source(parquet_path, in_memory):
    """DistributedScanPass fed by a ParquetSource (stream + shard) equals
    the in-memory single-device run."""
    source = ParquetSource(parquet_path, batch_rows=1 << 16)
    mesh = data_mesh()
    sharded = DistributedScanPass(
        SCAN_ANALYZERS, mesh=mesh, batch_size_per_device=1 << 13
    ).run(source)
    single = AnalysisRunner.do_analysis_run(
        in_memory, SCAN_ANALYZERS, engine="single"
    )
    for result in sharded:
        got = result.analyzer.compute_metric_from(result.state_or_raise())
        want = single.metric_map[result.analyzer]
        assert got.value.is_success and want.value.is_success, result.analyzer
        assert got.value.get() == pytest.approx(want.value.get(), rel=1e-9), (
            result.analyzer
        )


def test_grouping_over_parquet_source_on_mesh(parquet_path, in_memory):
    """Uniqueness/Entropy/CountDistinct (the frequency family) streamed
    from Parquet under the mesh engine equal the in-memory run."""
    grouping = [
        Uniqueness(("g",)),
        Entropy("cat"),
        CountDistinct(("cat",)),
        Uniqueness(("cat", "g")),
    ]
    source = ParquetSource(parquet_path, batch_rows=1 << 16)
    mesh = data_mesh()
    ctx_stream = AnalysisRunner.do_analysis_run(
        source, grouping, engine="distributed", mesh=mesh
    )
    ctx_mem = AnalysisRunner.do_analysis_run(in_memory, grouping, engine="single")
    for analyzer in grouping:
        assert ctx_stream.metric_map[analyzer].value.get() == pytest.approx(
            ctx_mem.metric_map[analyzer].value.get(), rel=1e-9
        ), analyzer


def test_quantile_stream_mesh_within_rank_bound(parquet_path, in_memory):
    """ApproxQuantile streamed+sharded stays within the KLL rank-error
    bound of the true data (eps·n ranks, ops/sketches/kll.py)."""
    analyzer = ApproxQuantile("x", 0.5)
    source = ParquetSource(parquet_path, batch_rows=1 << 16)
    ctx = AnalysisRunner.do_analysis_run(
        source, [analyzer], engine="distributed", mesh=data_mesh()
    )
    got = ctx.metric_map[analyzer].value.get()

    col = in_memory.column("x")
    x_sorted = np.sort(np.asarray(col.values, dtype=np.float64)[col.valid])
    n = len(x_sorted)
    eps = analyzer.relative_error
    # 2*eps: one eps for the sketch, one for the shard merge tree
    lo = x_sorted[max(0, int(np.floor((0.5 - 2 * eps) * n)))]
    hi = x_sorted[min(n - 1, int(np.ceil((0.5 + 2 * eps) * n)))]
    assert lo <= got <= hi


def test_where_predicates_survive_column_pruning(parquet_path, in_memory):
    """A where clause's referenced columns join the pruned read set even
    when no analyzer consumes them directly; filtered metrics over the
    streamed source equal the in-memory run."""
    analyzers = [
        Size(where="g >= 250"),
        Mean("x", where="g >= 250"),
        Completeness("x", where="g < 100"),
    ]
    source = ParquetSource(parquet_path, batch_rows=1 << 16)
    ctx_stream = AnalysisRunner.do_analysis_run(source, analyzers, engine="single")
    ctx_mem = AnalysisRunner.do_analysis_run(in_memory, analyzers, engine="single")
    for analyzer in analyzers:
        assert ctx_stream.metric_map[analyzer].value.get() == pytest.approx(
            ctx_mem.metric_map[analyzer].value.get(), rel=1e-12
        ), analyzer


def test_stream_profile_equals_in_memory(parquet_path, in_memory):
    """Full ColumnProfiler over the streaming source == over the
    in-memory table (the parity spot-check backing the 100M-row bench
    run at smaller scale)."""
    from deequ_tpu.profiles.column_profiler import ColumnProfiler

    p_stream = ColumnProfiler.profile(ParquetSource(parquet_path, batch_rows=1 << 16))
    p_mem = ColumnProfiler.profile(in_memory)
    assert p_stream.num_records == p_mem.num_records == N_ROWS
    for name in ("x", "cat", "g"):
        s, m = p_stream.profiles[name], p_mem.profiles[name]
        assert s.completeness == pytest.approx(m.completeness, rel=1e-12)
        assert s.approximate_num_distinct_values == m.approximate_num_distinct_values
        assert s.data_type == m.data_type
        if getattr(m, "mean", None) is not None:
            assert s.mean == pytest.approx(m.mean, rel=1e-9)
            assert s.minimum == pytest.approx(m.minimum, rel=1e-9)
            assert s.maximum == pytest.approx(m.maximum, rel=1e-9)
        if m.histogram is not None:
            assert s.histogram is not None
            assert {
                (k, v.absolute) for k, v in s.histogram.values.items()
            } == {(k, v.absolute) for k, v in m.histogram.values.items()}


@pytest.mark.parametrize("streamed", [True, False])
def test_mesh_program_failure_keeps_host_members(
    parquet_path, in_memory, streamed, monkeypatch
):
    """The sharded program failing mid-scan fails only the analyzers in
    it: the host-folded members still return the in-memory single-device
    results, over a stream (staged driver) as over a table (inline)."""
    from deequ_tpu.parallel import distributed

    monkeypatch.setenv("DEEQU_TPU_PLACEMENT", "host-discrete")
    real = distributed._get_distributed_fn
    calls = []

    def failing_fn(*args, **kwargs):
        fn = real(*args, **kwargs)

        def run(inputs):
            calls.append(1)
            if len(calls) == 2:
                raise RuntimeError("injected program failure")
            return fn(inputs)

        return run

    monkeypatch.setattr(distributed, "_get_distributed_fn", failing_fn)
    host = [Size(), Completeness("x"), ApproxCountDistinct("cat")]
    device = [Mean("x"), Sum("x")]
    data = ParquetSource(parquet_path, batch_rows=1 << 15) if streamed else in_memory
    results = DistributedScanPass(
        host + device, mesh=data_mesh(), batch_size_per_device=1 << 12
    ).run(data)
    assert len(calls) >= 2, "the program never ran a second batch"

    single = AnalysisRunner.do_analysis_run(in_memory, host, engine="single")
    for result in results[: len(host)]:
        got = result.analyzer.compute_metric_from(result.state_or_raise())
        assert got.value.get() == pytest.approx(
            single.metric_map[result.analyzer].value.get(), rel=1e-12
        ), result.analyzer
    for result in results[len(host):]:
        assert isinstance(result.error, RuntimeError), result.analyzer
        assert "injected program failure" in str(result.error)

"""Bounded-memory high-cardinality grouping: the hash-partitioned disk
spill behind the frequency family (the engine-level MEMORY_AND_DISK
escape hatch, reference: runners/AnalysisRunner.scala:75,479-483).

Every test forces a tiny in-memory group cap so the spill machinery is
exercised at test scale, and asserts metric equality against the plain
in-memory path — the spill must be an execution detail, never a
semantics change."""

from __future__ import annotations

import os

import numpy as np
import pytest

from deequ_tpu.analyzers import (
    CountDistinct,
    Distinctness,
    Entropy,
    Histogram,
    MutualInformation,
    Uniqueness,
    UniqueValueRatio,
)
from deequ_tpu.analyzers.freq_spill import GroupCountAccumulator, SpilledFrequencies
from deequ_tpu.analyzers.frequency import FrequenciesAndNumRows, compute_frequencies
from deequ_tpu.data.source import ParquetSource
from deequ_tpu.data.table import Table
from deequ_tpu.runners.analysis_runner import AnalysisRunner

N_ROWS = 120_000


@pytest.fixture(autouse=True)
def tiny_group_cap(monkeypatch):
    # spill after 10k in-RAM groups: the ~unique id column (120k groups)
    # must go to disk
    monkeypatch.setenv("DEEQU_TPU_MAX_GROUPS_IN_MEMORY", "10000")


@pytest.fixture(scope="module")
def high_card_parquet(tmp_path_factory):
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(11)
    ids = np.array([f"id_{i:08d}" for i in range(N_ROWS)], dtype=object)
    rng.shuffle(ids)
    ids[::1000] = "dup_key"  # a few repeats so uniqueness < 1
    cat = np.array(["x", "y", "z"], dtype=object)[rng.integers(0, 3, N_ROWS)]
    path = tmp_path_factory.mktemp("spill") / "high_card.parquet"
    pq.write_table(
        pa.table({"id": pa.array(list(ids)), "cat": pa.array(list(cat))}),
        str(path),
        row_group_size=20_000,
    )
    return str(path)


GROUPING = [
    Uniqueness(("id",)),
    Distinctness(("id",)),
    UniqueValueRatio(("id",)),
    CountDistinct(("id",)),
    Entropy("id"),
]


def test_streaming_high_card_spills_and_matches_in_memory(high_card_parquet):
    source = ParquetSource(high_card_parquet, batch_rows=1 << 14)
    ctx_stream = AnalysisRunner.do_analysis_run(source, GROUPING, engine="single")
    ctx_mem = AnalysisRunner.do_analysis_run(
        Table.from_parquet(high_card_parquet), GROUPING, engine="single"
    )
    for analyzer in GROUPING:
        got = ctx_stream.metric_map[analyzer].value.get()
        want = ctx_mem.metric_map[analyzer].value.get()
        assert got == pytest.approx(want, rel=1e-12), analyzer


def test_streaming_high_card_mesh_engine(high_card_parquet):
    source = ParquetSource(high_card_parquet, batch_rows=1 << 14)
    from deequ_tpu.parallel.distributed import data_mesh

    ctx = AnalysisRunner.do_analysis_run(
        source, GROUPING, engine="distributed", mesh=data_mesh()
    )
    ctx_mem = AnalysisRunner.do_analysis_run(
        Table.from_parquet(high_card_parquet), GROUPING, engine="single"
    )
    for analyzer in GROUPING:
        assert ctx.metric_map[analyzer].value.get() == pytest.approx(
            ctx_mem.metric_map[analyzer].value.get(), rel=1e-12
        ), analyzer


def test_spilled_state_is_actually_used(high_card_parquet):
    source = ParquetSource(high_card_parquet, batch_rows=1 << 14)
    state = compute_frequencies(source, ["id"])
    assert isinstance(state, SpilledFrequencies)
    assert state.num_rows == N_ROWS
    # exact group count survives partition compaction: dup_key overwrote
    # every 1000th id (120 ids gone, 1 new key)
    assert state.num_groups == N_ROWS - N_ROWS // 1000 + 1


def test_spill_accumulator_peak_memory_stays_bounded(high_card_parquet):
    """The fold's resident group count never exceeds cap + one batch:
    proxy assertion via the accumulator internals (the RSS-level
    evidence lives in the 100M bench artifact)."""
    acc = GroupCountAccumulator(["id"], max_groups_in_memory=10_000)
    source = ParquetSource(high_card_parquet, batch_rows=1 << 14)
    max_resident = 0
    for batch in source.batches(1 << 14):
        partial = compute_frequencies(batch, ["id"])
        acc.add(partial)
        if acc._buffer is not None:
            max_resident = max(max_resident, acc._buffer.num_groups)
    state = acc.finalize()
    assert isinstance(state, SpilledFrequencies)
    # once spilled, nothing accumulates in RAM; before, bounded by
    # cap + one batch of new groups
    assert max_resident <= 10_000 + (1 << 14)


def test_histogram_over_spilled_state(high_card_parquet):
    source = ParquetSource(high_card_parquet, batch_rows=1 << 14)
    analyzer = Histogram("id", max_detail_bins=5)
    ctx = AnalysisRunner.do_analysis_run(source, [analyzer], engine="single")
    dist = ctx.metric_map[analyzer].value.get()
    # top bin must be the repeated key, with its exact count
    assert dist.values["dup_key"].absolute == N_ROWS // 1000
    assert dist.number_of_bins == N_ROWS - N_ROWS // 1000 + 1
    assert len(dist.values) == 5


def test_histogram_streaming_state_actually_spills(high_card_parquet):
    source = ParquetSource(high_card_parquet, batch_rows=1 << 14)
    state = Histogram("id").compute_state_from(source)
    assert isinstance(state, SpilledFrequencies)
    assert state.num_rows == N_ROWS


def test_spill_writer_cleans_up_on_abandonment():
    """A fold that dies after spilling must not leak the spill dir."""
    import gc
    import os

    from deequ_tpu.analyzers.freq_spill import _SpillWriter

    writer = _SpillWriter(["c"])
    writer.append(
        FrequenciesAndNumRows(
            ["c"],
            [np.array(["a", "b"], dtype=object)],
            np.array([1, 2], dtype=np.int64),
            2,
        )
    )
    directory = writer.directory
    assert os.path.isdir(directory)
    del writer
    gc.collect()
    assert not os.path.exists(directory)


def test_mutual_information_over_spilled_state(high_card_parquet):
    mi = MutualInformation("id", "cat")
    source = ParquetSource(high_card_parquet, batch_rows=1 << 14)
    ctx_stream = AnalysisRunner.do_analysis_run(source, [mi], engine="single")
    ctx_mem = AnalysisRunner.do_analysis_run(
        Table.from_parquet(high_card_parquet), [mi], engine="single"
    )
    assert ctx_stream.metric_map[mi].value.get() == pytest.approx(
        ctx_mem.metric_map[mi].value.get(), rel=1e-9
    )


def test_histogram_top_n_tie_break_is_deterministic():
    """(count desc, key asc): with max_detail_bins below the number of
    tied groups, the selected detail set must be identical in-memory and
    streamed/spilled (the reference's rdd.top leaves this partition-
    dependent; we define it)."""
    from deequ_tpu.analyzers.frequency import top_n_order

    keys = np.array(["b", "d", "a", "c", "e"], dtype=object)
    counts = np.array([2, 1, 2, 2, 1], dtype=np.int64)
    order = top_n_order(keys, counts, 4)
    assert list(keys[order]) == ["a", "b", "c", "d"]  # 2s by key, then 1s

    # cross-path: all-tied counts, cap smaller than the group count
    import pyarrow as pa
    import pyarrow.parquet as pq
    import tempfile

    n = 60_000  # all-unique -> every count ties at 1
    ids = np.array([f"k{i:06d}" for i in range(n)], dtype=object)
    with tempfile.TemporaryDirectory() as tmp:
        path = f"{tmp}/ties.parquet"
        pq.write_table(
            pa.table({"id": pa.array(list(ids))}), path, row_group_size=10_000
        )
        analyzer = Histogram("id", max_detail_bins=7)
        mem = AnalysisRunner.do_analysis_run(
            Table.from_parquet(path), [analyzer], engine="single"
        ).metric_map[analyzer].value.get()
        stream = AnalysisRunner.do_analysis_run(
            ParquetSource(path, batch_rows=1 << 13), [analyzer], engine="single"
        ).metric_map[analyzer].value.get()
    assert list(mem.values) == list(stream.values) == [
        f"k{i:06d}" for i in range(7)
    ]


def test_multi_column_spill_matches_in_memory(high_card_parquet):
    """Spill routing hashes ALL key columns; a (near-unique, low-card)
    pair must produce the same metrics as the in-memory path."""
    grouping = [
        Uniqueness(("id", "cat")),
        CountDistinct(("id", "cat")),
        UniqueValueRatio(("cat", "id")),  # declared order differs from sorted
    ]
    source = ParquetSource(high_card_parquet, batch_rows=1 << 14)
    ctx_stream = AnalysisRunner.do_analysis_run(source, grouping, engine="single")
    ctx_mem = AnalysisRunner.do_analysis_run(
        Table.from_parquet(high_card_parquet), grouping, engine="single"
    )
    # the joint key is ~unique: the state must actually have spilled
    state = compute_frequencies(
        ParquetSource(high_card_parquet, batch_rows=1 << 14), ["cat", "id"]
    )
    assert isinstance(state, SpilledFrequencies)
    for analyzer in grouping:
        assert ctx_stream.metric_map[analyzer].value.get() == pytest.approx(
            ctx_mem.metric_map[analyzer].value.get(), rel=1e-12
        ), analyzer


def test_spilled_merge_with_in_memory_partial():
    rng = np.random.default_rng(5)
    keys_a = np.array([f"k{i}" for i in range(30_000)], dtype=object)
    keys_b = np.array([f"k{i}" for i in range(15_000, 45_000)], dtype=object)

    acc = GroupCountAccumulator(["c"], max_groups_in_memory=5_000)
    acc.add(
        FrequenciesAndNumRows(
            ["c"], [keys_a], np.ones(len(keys_a), dtype=np.int64), len(keys_a)
        )
    )
    acc.add(
        FrequenciesAndNumRows(
            ["c"], [keys_b], np.ones(len(keys_b), dtype=np.int64), len(keys_b)
        )
    )
    spilled = acc.finalize()
    assert isinstance(spilled, SpilledFrequencies)
    assert spilled.num_groups == 45_000
    assert spilled.num_rows == 60_000

    extra = FrequenciesAndNumRows(
        ["c"],
        [np.array(["k0", "new"], dtype=object)],
        np.array([7, 3], dtype=np.int64),
        10,
    )
    merged = spilled.merge(extra)
    assert merged.num_groups == 45_001
    assert merged.num_rows == 60_010
    # merge must not mutate its operands (num_rows is the metric
    # denominator downstream)
    assert extra.num_rows == 10
    assert spilled.num_rows == 60_000
    # commutes through the in-memory side too
    merged2 = extra.merge(spilled)
    assert merged2.num_groups == 45_001
    assert merged2.num_rows == 60_010
    assert extra.num_rows == 10

    # the overlapping key's count actually summed (k0: 1 from the first
    # partial + 7 from the merged extra; keys_b starts at k15000)
    total = 0
    for part in merged.partitions():
        for key, count in zip(part.key_columns[0], part.counts):
            if key == "k0":
                total += int(count)
    assert total == 1 + 7


def test_spilled_state_serializes_for_multihost_envelope(high_card_parquet):
    """The DCN state envelope must handle spilled frequencies: serialize
    streams partitions, deserialize re-spills on the receiving host."""
    from deequ_tpu.analyzers.state_provider import (
        deserialize_state,
        serialize_state,
    )

    source = ParquetSource(high_card_parquet, batch_rows=1 << 14)
    state = compute_frequencies(source, ["id"])
    assert isinstance(state, SpilledFrequencies)
    analyzer = Uniqueness(("id",))
    blob = serialize_state(analyzer, state)
    restored = deserialize_state(analyzer, blob)
    assert restored.num_rows == state.num_rows
    assert restored.num_groups == state.num_groups
    # metric computed from the round-tripped state matches
    a = analyzer.compute_metric_from(state).value.get()
    b = analyzer.compute_metric_from(restored).value.get()
    assert a == pytest.approx(b, rel=0, abs=0)


def _key_counts(state) -> dict:
    """{key tuple: count} over every partition of a state; a key met
    twice (in two partitions, or twice in one) fails. NaN keys compare
    as one key."""
    parts = (
        list(state.partitions()) if getattr(state, "is_spilled", False) else [state]
    )
    out = {}
    for part in parts:
        for key, count in zip(part.keys, part.counts.tolist()):
            key = tuple("NaN" if v != v else v for v in key)
            assert key not in out, key
            out[key] = count
    return out


@pytest.fixture(scope="module")
def typed_keys_parquet(tmp_path_factory):
    """Key columns of each kind, every one able to spill at the 10k cap:
    a near-unique int64, (orderkey, linenumber) shaped like TPC-H
    lineitem, a bool, a float64 whose zeros are -0.0 in the first half
    of the file and 0.0 in the second (batches disagree on the sign),
    and a near-unique string."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(17)
    ik = rng.permutation(N_ROWS).astype(np.int64)
    ik[::1000] = -1
    lines = rng.integers(1, 8, N_ROWS)
    orderkey = np.repeat(np.arange(1, N_ROWS + 1, dtype=np.int64) * 4, lines)[:N_ROWS]
    linenumber = np.concatenate([np.arange(1, n + 1) for n in lines])[:N_ROWS]
    f = np.round(rng.random(N_ROWS) * 40_000.0, 1)
    f[::7] = 0.0
    f[: N_ROWS // 2][::7] = -0.0
    f[5::11] = np.nan
    path = tmp_path_factory.mktemp("typed") / "typed_keys.parquet"
    pq.write_table(
        pa.table({
            "ik": ik,
            "ok": orderkey,
            "ln": linenumber,
            "flag": rng.random(N_ROWS) < 0.5,
            "f": f,
            "s": pa.array([f"s{v}" for v in ik]),
        }),
        str(path),
        row_group_size=20_000,
    )
    return str(path)


@pytest.mark.parametrize("columns, typed", [
    (["ik"], True),
    (["ok", "ln"], True),
    (["flag", "ik"], True),
    (["f"], True),
    (["s"], False),
], ids=["int64", "lineitem_pair", "bool_int64", "float64", "string"])
def test_spill_by_key_kind_matches_in_memory(typed_keys_parquet, columns, typed):
    """Every key kind spills at the 10k cap and gives the in-memory
    per-key counts and metrics; int, bool and float keys stay typed
    through routing and compaction, strings take the object path."""
    source = ParquetSource(typed_keys_parquet, batch_rows=1 << 14)
    spilled = compute_frequencies(source, columns)
    in_memory = compute_frequencies(Table.from_parquet(typed_keys_parquet), columns)
    assert isinstance(spilled, SpilledFrequencies)
    assert spilled.typed is typed and in_memory.typed is typed
    assert _key_counts(spilled) == _key_counts(in_memory)
    assert spilled.num_groups == in_memory.num_groups

    analyzers = [
        Uniqueness(columns),
        Distinctness(columns),
        UniqueValueRatio(columns),
        CountDistinct(columns),
    ] + ([Entropy(columns[0])] if len(columns) == 1 else [MutualInformation(*columns)])
    ctx_stream = AnalysisRunner.do_analysis_run(source, analyzers, engine="single")
    ctx_mem = AnalysisRunner.do_analysis_run(
        Table.from_parquet(typed_keys_parquet), analyzers, engine="single"
    )
    for analyzer in analyzers:
        assert ctx_stream.metric_map[analyzer].value.get() == pytest.approx(
            ctx_mem.metric_map[analyzer].value.get(), rel=1e-12
        ), analyzer


def _lineitem_state(first_order: int, n_orders: int, seed: int):
    rng = np.random.default_rng(seed)
    lines = rng.integers(1, 8, n_orders)
    orderkey = np.repeat(np.arange(first_order, first_order + n_orders), lines)
    linenumber = np.concatenate([np.arange(1, n + 1) for n in lines])
    counts = rng.integers(1, 4, len(orderkey))
    return FrequenciesAndNumRows(
        ["l_linenumber", "l_orderkey"],
        [linenumber.astype(np.int64), orderkey.astype(np.int64)],
        counts,
        int(counts.sum()),
    )


@pytest.mark.parametrize("other_orders", [1_000, 6_000], ids=["object_in_memory", "object_spilled"])
@pytest.mark.parametrize("typed_first", [True, False], ids=["typed_first", "object_first"])
def test_spilled_merge_of_typed_and_object_keys(tmp_path, other_orders, typed_first):
    """A typed spilled state merged with the same keys carried as objects
    (a state_provider round trip) gives the in-memory merge's per-key
    counts, with no key in two partitions, in either order."""
    from deequ_tpu.analyzers.state_provider import FileSystemStateProvider

    typed_mem = _lineitem_state(1, 8_000, seed=1)
    other_mem = _lineitem_state(5_000, other_orders, seed=2)  # overlaps 5000..
    acc = GroupCountAccumulator(typed_mem.columns, max_groups_in_memory=5_000)
    half = typed_mem.num_groups // 2
    for sl in (slice(0, half), slice(half, None)):
        acc.add(FrequenciesAndNumRows(
            typed_mem.columns,
            [kc[sl] for kc in typed_mem.key_columns],
            typed_mem.counts[sl],
            int(typed_mem.counts[sl].sum()),
        ))
    typed_spilled = acc.finalize()
    assert isinstance(typed_spilled, SpilledFrequencies) and typed_spilled.typed

    provider = FileSystemStateProvider(str(tmp_path))
    analyzer = Uniqueness(other_mem.columns)
    provider.persist(analyzer, other_mem)
    other = provider.load(analyzer)
    carried = next(other.partitions()) if getattr(other, "is_spilled", False) else other
    assert carried.key_columns[0].dtype == object  # the state came back boxed
    assert getattr(other, "is_spilled", False) is (other_orders > 1_000)

    merged = typed_spilled.merge(other) if typed_first else other.merge(typed_spilled)
    assert isinstance(merged, SpilledFrequencies)
    assert merged.typed  # the object side converted exactly
    want = typed_mem.merge(other_mem)
    assert _key_counts(merged) == _key_counts(want)
    assert merged.num_groups == want.num_groups
    assert merged.num_rows == want.num_rows


@pytest.mark.parametrize("typed, boxed", [
    (np.array([5, -1, 1 << 40], dtype=np.int64), [5, -1, 1 << 40]),
    (np.array([True, False]), [True, False]),
    (np.array([-0.0, 0.0, np.nan, 2.5, 3.0]), [0.0, -0.0, float("nan"), 2.5, 3.0]),
], ids=["int64", "bool", "float64"])
def test_route_hash_follows_the_value_not_the_carrier(typed, boxed):
    from deequ_tpu.analyzers.freq_spill import _key_hashes

    want = _key_hashes(typed)
    assert np.array_equal(_key_hashes(np.array(boxed, dtype=object)), want)
    # in a column mixing families a number still hashes by its value
    mixed = _key_hashes(np.array(boxed + ["text"], dtype=object))
    assert np.array_equal(mixed[:-1], want)
    if typed.dtype.kind == "f":  # -0.0 and 0.0, NaN and NaN: one key each
        assert want[0] == want[1] and want[2] == _key_hashes(np.array([-np.nan]))[0]


@pytest.mark.parametrize("columns", [
    [np.array([3, 1, 3, 2, 1, 3], dtype=np.int64)],
    [np.array([1, 1, 2, 2, 1, 1]), np.array([7, 7, 7, 8, 7, 7], dtype=np.int64)],
    [np.array([True, False, True, True, False, False])],
    [np.array([-0.0, 0.0, np.nan, 1.5, -np.nan, 1.5])],
    [np.array([1.0, 1.0, np.nan, np.nan, 0.0, -0.0]), np.array([True, True, False, False, True, True])],
], ids=["int64", "int_pair", "bool", "float64", "float_bool"])
def test_typed_group_sum_matches_the_pandas_merge(columns):
    """The typed sort-and-reduce groups as pandas `dropna=False` does over
    the same keys as objects: -0.0 with 0.0, every NaN together."""
    from deequ_tpu.analyzers.frequency import _group_sum

    counts = np.arange(1, len(columns[0]) + 1, dtype=np.int64)
    typed_keys, typed_counts = _group_sum(columns, counts)
    boxed_keys, boxed_counts = _group_sum([c.astype(object) for c in columns], counts)
    assert all(k.dtype != object for k in typed_keys)
    assert _key_counts(FrequenciesAndNumRows(["c"] * len(columns), typed_keys, typed_counts, 0)) == (
        _key_counts(FrequenciesAndNumRows(["c"] * len(columns), boxed_keys, boxed_counts, 0))
    )


def test_spilled_state_persists_via_state_provider(tmp_path, high_card_parquet):
    from deequ_tpu.analyzers.state_provider import FileSystemStateProvider

    source = ParquetSource(high_card_parquet, batch_rows=1 << 14)
    state = compute_frequencies(source, ["id"])
    assert isinstance(state, SpilledFrequencies)
    provider = FileSystemStateProvider(str(tmp_path))
    analyzer = Uniqueness(("id",))
    provider.persist(analyzer, state)
    loaded = provider.load(analyzer)
    assert loaded.num_rows == state.num_rows
    assert loaded.num_groups == state.num_groups

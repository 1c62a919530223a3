"""Dictionary-domain predicate evaluation (data/expr.py `Predicate`): a
predicate over one dictionary-coded STRING column is evaluated once per
dictionary entry plus a null slot and gathered to rows through the codes.

Differential against the row path: the same values as a plain object
column (no codes) must give bit-equal `(values, null, kind)` and
`eval_mask`, on a dictionary-decoded Parquet column through both decode
routes (the native buffer-level decode and the pyarrow fallback). Also:
a gate-shaped Compliance build never materializes the column's per-row
strings, and `record_predicate_eval` counts builds by route."""

from __future__ import annotations

import numpy as np
import pyarrow.parquet as pq
import pytest

from deequ_tpu import Check, CheckLevel, Table, VerificationSuite, observe
from deequ_tpu.analyzers.base import where_spec
from deequ_tpu.analyzers.scan import Compliance
from deequ_tpu.data.expr import ExpressionParseError, Predicate
from deequ_tpu.data import table as table_mod
from deequ_tpu.data.table import Column
from deequ_tpu.ops import native, runtime

PREDICATES = [
    "c IN ('a', 'b', '')",
    "c NOT IN ('a', 'b')",
    "c = 'a'",
    "c <> 'a'",
    "c < 'b'",
    "c = 1",
    "c LIKE 'a%'",
    "c RLIKE '^ ?[Aa]'",
    "c IS NULL",
    "c IS NOT NULL",
    "COALESCE(c, 'zz')",
    "COALESCE(c, 'zz') = 'zz'",
    "LOWER(c) = 'ab'",
    "UPPER(c)",
    "TRIM(c) = 'a'",
    "LENGTH(c) > 1",
    "LENGTH(c)",
    "CASE WHEN c = 'a' THEN 1 WHEN c IS NULL THEN 2 ELSE 3 END",
    "CASE WHEN c IS NULL THEN c ELSE 'x' END",
    "`c` IS NULL OR `c` IN ('a', '1.0', ' A ')",
]

POOL = ["a", "b", "ab", " A ", "1", "1.0", "xyz", "a b"]


@pytest.fixture(autouse=True)
def _fresh_dictionary_memo(monkeypatch):
    """Each test starts with an empty cross-batch dictionary memo, so the
    entries a route evaluates do not depend on which test ran before."""
    monkeypatch.setattr(table_mod, "_DICT_DERIVED_CACHE", None)
    monkeypatch.setattr(table_mod, "_DICT_DERIVED_BYTES", 0)


def _values(case: str, rng) -> list:
    n = 64
    if case == "all_null":
        return [None] * n
    pool = POOL + ([""] if case == "empty_string" else [])
    vals = [pool[i] for i in rng.integers(0, len(pool), n)]
    if case == "unused_entries":
        # rows 8-40, the batch the test slices, use three entries only
        vals[8:40] = [pool[i] for i in rng.integers(0, 3, 32)]
    if case in ("nulls", "empty_string", "unused_entries"):
        vals = [None if u < 0.25 else v for u, v in zip(rng.random(n), vals)]
    return vals


def _dictionary_table(vals, tmp_path, route: str) -> Table:
    """`vals` as column `c`, written to Parquet with dictionary pages and
    read back dictionary-decoded, then decoded by `route`."""
    path = str(tmp_path / "c.parquet")
    Table.from_pydict({"c": vals}).to_parquet(path, dictionary_encode_strings=True)
    arrow = pq.read_table(path, read_dictionary=["c"])
    table = Table.from_arrow(
        arrow, fastpath_columns={"c"} if route == "native" else None
    )
    if route == "native" and native.available():
        # the native decode took the column: codes, no arrow backing
        assert "arrow" not in table.column("c")._cache
    return table


def _bit_equal(a: np.ndarray, b: np.ndarray) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    if a.dtype != b.dtype or a.shape != b.shape:
        return False
    if a.dtype == object:
        return all(type(x) is type(y) and x == y for x, y in zip(a, b))
    return a.tobytes() == b.tobytes()


@pytest.mark.parametrize("route", ["native", "fallback"])
@pytest.mark.parametrize(
    "case", ["no_nulls", "nulls", "empty_string", "unused_entries", "all_null"]
)
@pytest.mark.parametrize("expression", PREDICATES)
def test_dictionary_route_matches_row_path(expression, case, route, tmp_path):
    vals = _values(case, np.random.default_rng(len(expression)))
    dict_table = _dictionary_table(vals, tmp_path, route)
    plain = Table.from_pydict({"c": vals})
    if case == "unused_entries":
        # a batch of the table: its rows use only part of the dictionary
        dict_table, plain = dict_table.slice(8, 40), plain.slice(8, 40)
        codes, uniques = dict_table.column("c").dict_encode()
        assert len(set(codes[codes >= 0].tolist())) < len(uniques)
    pred = Predicate(expression)

    got, route_taken, entries = pred.eval_routed(dict_table)
    want, row_route, _ = pred.eval_routed(plain)

    assert route_taken == "dictionary" and row_route == "rows"
    assert entries == len(dict_table.column("c").dict_encode()[1])
    assert got[2] == want[2]
    assert _bit_equal(got[0], want[0]), expression
    assert _bit_equal(got[1], want[1]), expression
    assert _bit_equal(pred.eval_mask(dict_table), pred.eval_mask(plain))
    # lazy per-row strings stay unbuilt on the dictionary route
    assert dict_table.column("c")._values is None


@pytest.mark.parametrize("route", ["native", "fallback"])
@pytest.mark.parametrize(
    "expression", ["c LIKE c", "FOO(c) = 1", "c RLIKE '('"]
)
def test_raising_predicate_raises_alike(expression, route, tmp_path):
    vals = _values("nulls", np.random.default_rng(7))
    dict_table = _dictionary_table(vals, tmp_path, route)
    plain = Table.from_pydict({"c": vals})
    pred = Predicate(expression)
    with pytest.raises(Exception) as row_err:
        pred.eval(plain)
    with pytest.raises(type(row_err.value)) as dict_err:
        pred.eval(dict_table)
    assert str(dict_err.value) == str(row_err.value)
    if expression != "c RLIKE '('":
        assert isinstance(row_err.value, ExpressionParseError)


@pytest.mark.parametrize("route", ["native", "fallback"])
def test_unused_entry_that_raises_keeps_the_row_answer(route, tmp_path):
    """An entry the batch does not use may raise once evaluated (ABS of
    'inf' cast back to a string): the batch gets the row path's answer,
    and a batch that holds the entry raises as the row path does."""
    vals = ["1", "2", None] * 16 + ["inf"]
    whole = _dictionary_table(vals, tmp_path, route)
    plain = Table.from_pydict({"c": vals})
    pred = Predicate("LOWER(ABS(c)) = '1'")

    got, route_taken, entries = pred.eval_routed(whole.slice(0, 48))
    want = pred.eval(plain.slice(0, 48))
    assert (route_taken, entries) == ("rows", 0)
    assert all(_bit_equal(g, w) for g, w in zip(got[:2], want[:2]))
    with pytest.raises(OverflowError) as row_err:
        pred.eval(plain)
    with pytest.raises(OverflowError) as dict_err:
        pred.eval(whole)
    assert str(dict_err.value) == str(row_err.value)


@pytest.mark.parametrize("route", ["native", "fallback"])
def test_gate_shaped_compliance_never_materializes_rows(route, tmp_path, monkeypatch):
    """Compliance's `pred:` and `prednn:` builds over a dictionary-decoded
    column read codes only (ops/fused.py PACKED_SAFE_PREFIXES): the lazy
    per-row string gather never fires, through a streamed Parquet scan as
    through an in-memory decoded table."""
    rng = np.random.default_rng(3)
    flags = ["A", "N", "R"]
    n = 4096
    path = str(tmp_path / "day.parquet")
    Table.from_pydict(
        {
            "l_returnflag": [flags[i] for i in rng.integers(0, 3, n)],
            "l_quantity": [int(x) for x in rng.integers(1, 51, n)],
        }
    ).to_parquet(path, dictionary_encode_strings=True)
    check = Check(CheckLevel.ERROR, "gate").is_contained_in("l_returnflag", flags)

    materialized = []
    plain_values = Column.values

    def values(self):
        if self._values is None and self.name == "l_returnflag":
            materialized.append(self)
        return plain_values.fget(self)

    monkeypatch.setattr(Column, "values", property(values))
    arrow = pq.read_table(path, read_dictionary=["l_returnflag"])
    table = Table.from_arrow(
        arrow, fastpath_columns={"l_returnflag"} if route == "native" else None
    )
    for data in (table, Table.scan_parquet(path)):
        with runtime.monitored() as stats, observe.tracing() as tracer:
            result = VerificationSuite().on_data(data).add_check(check).run()
        assert result.status.name == "SUCCESS"
        assert tracer.counters.get("pred_builds_dictionary") == 2
        assert "pred_builds_rows" not in tracer.counters
        assert stats.pred_builds_rows == 0
    assert materialized == []
    assert table.column("l_returnflag")._values is None


def _counted(build, table):
    with runtime.monitored() as stats, observe.tracing() as tracer:
        with observe.span("build", cat="build") as sp:
            build(table)
    return stats, tracer, sp


def test_counter_records_dictionary_route(tmp_path):
    vals = _values("nulls", np.random.default_rng(11))
    table = _dictionary_table(vals, tmp_path, "fallback")
    entries = len(table.column("c").dict_encode()[1])
    specs = Compliance("c", "c IN ('a', 'b')")._extra_specs()

    stats, tracer, sp = _counted(specs[0].build, table)
    assert (stats.pred_builds_dictionary, stats.pred_builds_rows) == (1, 0)
    assert stats.pred_dict_entries == entries
    assert tracer.counters["pred_builds_dictionary"] == 1
    assert tracer.counters["pred_dict_entries"] == entries
    assert sp.attrs["route"] == "dictionary"

    # prednn: shares the per-entry result: a memo hit evaluates nothing
    stats, tracer, sp = _counted(specs[1].build, table)
    assert (stats.pred_builds_dictionary, stats.pred_dict_entries) == (1, 0)
    assert "pred_dict_entries" not in tracer.counters
    assert sp.attrs["route"] == "dictionary"


@pytest.mark.parametrize(
    "data, where, source",
    [
        ({"c": ["a", "b"] * 32, "d": ["a", "c"] * 32}, "c = d", "dictionary"),
        ({"n": list(range(64))}, "n > 3", "dictionary"),
        ({"c": [f"v{i}" for i in range(64)]}, "c = 'v3'", "dictionary"),
        ({"c": ["a", "b"] * 32}, "c = 'a'", "plain"),
    ],
    ids=["multi_column", "long_column", "high_cardinality", "plain_no_codes"],
)
def test_counter_records_row_route(data, where, source, tmp_path):
    if source == "plain":
        table = Table.from_pydict(data)
    else:
        path = str(tmp_path / "t.parquet")
        Table.from_pydict(data).to_parquet(path, dictionary_encode_strings=True)
        strings = [k for k, v in data.items() if isinstance(v[0], str)]
        table = Table.from_arrow(pq.read_table(path, read_dictionary=strings))
    stats, tracer, sp = _counted(where_spec(where).build, table)
    assert (stats.pred_builds_dictionary, stats.pred_builds_rows) == (0, 1)
    assert stats.pred_dict_entries == 0
    assert tracer.counters == {"pred_builds_rows": 1}
    assert sp.attrs["route"] == "rows"

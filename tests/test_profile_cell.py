"""Upstream's default column profile of TPC-H lineitem, held to the plain
reference (`benchmark/reference/profile.py`) under the profile
configuration's guarantees, as the `lineitem-sf1-profile` benchmark cell
holds every call.

Each call profiles a fresh `Table` built from the Arrow table, with x64
off (the chip's float32 wire) and on. Every field the reference knows is
compared; each planted fault must make the scorecard fail; the traced
profile shows its spans and counters, and the benchmark's `profile_scans`
reader counts one scan a call.
"""

import copy
import json
import os

import jax
import pytest

from benchmark.data import tpch
from benchmark.harness.core import HERE, Call, Run, load_module
from benchmark.reference.profile import PERCENTILES, ProfileReference, fields
from benchmark.reference.reference import Scorecard
from deequ_tpu import Table, observe
from deequ_tpu.ops import runtime
from deequ_tpu.profiles.runner import ColumnProfilerRunner

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS = 20_000

with open(os.path.join(ROOT, "benchmark", "configs", "tpch-lineitem-sf1-profile.json")) as f:
    CONFIG = json.load(f)

NUMERIC = ("l_orderkey", "l_partkey", "l_suppkey", "l_linenumber", "l_quantity",
           "l_extendedprice", "l_discount", "l_tax")
HISTOGRAMS = ("l_returnflag", "l_linestatus", "l_shipinstruct", "l_shipmode")


@pytest.fixture(scope="module", params=[2**31 + 31, 3_131_000_001])
def lineitem(request):
    cols = tpch.lineitem(ROWS, request.param, float(CONFIG["scale"]))
    return cols, tpch.to_arrow(cols, dictionary=False), ProfileReference(cols)


def _profile(arrow):
    return ColumnProfilerRunner.on_data(Table.from_arrow(arrow)).run()


class _Card(Scorecard):
    """A scorecard that also keeps the label of every number compared."""

    def __init__(self, limits):
        super().__init__(limits)
        self.labels = []

    def note(self, name, what, err):
        self.labels.append(what)
        super().note(name, what, err)


@pytest.fixture(scope="module")
def sound(lineitem):
    """The profile of two calls, each over a fresh Table, x64 off."""
    _cols, arrow, _ref = lineitem
    with jax.enable_x64(False):
        return [fields(_profile(arrow)) for _ in range(2)]


@pytest.mark.parametrize("x64", [False, True], ids=["x64_off", "x64_on"])
def test_default_profile_matches_reference(lineitem, sound, x64):
    _cols, arrow, ref = lineitem
    if x64:
        with jax.enable_x64(True):
            calls = [fields(_profile(arrow)) for _ in range(2)]
    else:
        calls = sound
    card = _Card(CONFIG["guarantees"])
    for i, got in enumerate(calls):
        ref.compare(got, card, f"call {i}")
    assert card.ok, card.failures

    # every field of every column was compared, in both calls
    per_column = {"completeness", "distinct", "type_counts", "has a histogram"}
    for i in range(2):
        labels = [w for w in card.labels if w.startswith(f"call {i} ")]
        assert f"call {i} num_records" in labels
        for name in ref.columns:
            mine = {w[len(f"call {i} {name} "):] for w in labels
                    if w.startswith(f"call {i} {name} ")}
            assert per_column <= mine, name
            assert any(w.startswith("data_type ") for w in mine), name
            if name in HISTOGRAMS:
                assert "histogram entries that differ" in mine
            if name in NUMERIC:
                assert {"minimum", "maximum", "mean", "sum", "std_dev"} <= mine
                assert {f"percentile {q:g}" for q in PERCENTILES} <= mine
    assert len(card.labels) == 2 * (1 + 5 * 16 + len(HISTOGRAMS) + len(NUMERIC) * 105)

    # the reference's answers that the lineitem schema fixes
    assert {n for n, c in ref.columns.items() if c.histogram is not None} == set(HISTOGRAMS)
    assert {n for n, c in ref.columns.items() if c.sorted is not None} == set(NUMERIC)
    assert all(c.data_type == "String" for n, c in ref.columns.items() if n not in NUMERIC)


def _rank_shifted(ref, column, q, by):
    """A value whose rank lies `by` above `q` in the column."""
    s = ref.columns[column].sorted
    return float(s[int((q + by) * len(s))])


def _plant(got, fault, ref):
    col = got["columns"]
    if fault == "histogram_count":
        hist = col["l_shipmode"]["histogram"]
        key = sorted(hist)[0]
        count, ratio = hist[key]
        hist[key] = (count + 1, ratio)
    elif fault == "data_type":
        col["l_returnflag"]["data_type"] = "Integral"
    elif fault == "percentile":
        q = PERCENTILES[49]
        col["l_extendedprice"]["approx_percentiles"][49] = _rank_shifted(
            ref, "l_extendedprice", q, 0.012
        )
    elif fault == "mean":
        col["l_quantity"]["mean"] *= 1 + 1e-5
    elif fault == "missing_profile":
        del col["l_comment"]


@pytest.mark.parametrize("fault, number", [
    ("histogram_count", "exact_abs_err"),
    ("data_type", "exact_abs_err"),
    ("percentile", "kll_rank_err"),
    ("mean", "moment_rel_err"),
    ("missing_profile", None),
])
def test_planted_fault_fails_the_scorecard(lineitem, sound, fault, number):
    _cols, _arrow, ref = lineitem
    got = copy.deepcopy(sound[0])
    _plant(got, fault, ref)
    card = Scorecard(CONFIG["guarantees"])
    ref.compare(got, card, "call 0")
    assert not card.ok
    if number is None:
        assert card.failures == ["call 0: no profile of column l_comment"]
    else:
        assert all(f.startswith(number) for f in card.failures), card.failures
        assert card.worst[number] > card.limits[number]


def test_profile_spans_counters_and_one_scan(lineitem):
    _cols, arrow, _ref = lineitem
    with jax.enable_x64(False):
        with observe.tracing() as tracer, runtime.monitored() as stats:
            _profile(arrow)
    roots = {s.name: s for s in tracer.roots}
    assert set(roots) == {"table_from_arrow", "profile"}
    ingest, prof = roots["table_from_arrow"], roots["profile"]
    assert ingest.cat == "ingest"
    assert ingest.attrs["rows"] == ROWS and ingest.attrs["columns"] == 16
    assert prof.cat == "profile"
    assert prof.attrs["columns"] == 16 and prof.attrs["passes"] == 1
    names = [c.name for c in prof.children]
    assert names == ["profile_pass", "profile_finish"]
    scan, finish = prof.children
    assert scan.attrs["pass"] == 1 and scan.attrs["analyzers"] > 16
    assert [c.name for c in scan.children] == ["analysis_run"]
    assert all(s.cat == "profile" for s in (scan, finish)) and not finish.children

    counts = {"profile_passes": 1, "profile_histograms": len(HISTOGRAMS),
              "profile_stragglers": 0, "profile_cast_columns": 0}
    for name, n in counts.items():
        assert getattr(stats, name) == n
        assert tracer.counters[name] == n
    assert stats.device_passes == 1 and stats.group_passes == 0

    reader = load_module(os.path.join(HERE, "layers", "profile_scans.py"), "t_profile_scans")
    run = Run([Call(ROWS, 0.0, 1.0)], 0, (0.0, 1.0), 1.0, 1, "cpu", tracer.roots)
    assert reader.read(run) == 1.0


def test_reference_type_inference():
    """The reference's DataType follows upstream's regexes and decision
    tree, also where the lineitem data never goes."""
    from benchmark.reference.profile import determine_type, value_type

    assert [value_type(v) for v in ("1.5", "-3", "", ".", "+ 7", "true", "True", "1e3")] == [
        "Fractional", "Integral", "Integral", "Fractional", "Integral", "Boolean",
        "String", "String",
    ]
    zero = dict.fromkeys(("Unknown", "Fractional", "Integral", "Boolean", "String"), 0)
    assert determine_type(dict(zero, Unknown=3)) == "Unknown"
    assert determine_type(dict(zero, Integral=2, Fractional=1)) == "Fractional"
    assert determine_type(dict(zero, Integral=2, Unknown=1)) == "Integral"
    assert determine_type(dict(zero, Boolean=2, Integral=1)) == "String"
    assert determine_type(dict(zero, Boolean=2, Unknown=5)) == "Boolean"

"""Full-suite differential fuzz (round-4 verdict item 8): random tables
× random Check DSL programs through THREE execution paths — the
single-device engine, the 8-device mesh engine, and the pure host fold
— asserting end-to-end agreement of the VerificationSuite outputs:
overall status, per-check status, per-constraint status, and the
underlying metric values (exact for counts/statuses, 1e-9 for scalar
floats, rank-error-loose for sketches).

This is the VerificationSuite-level generalization of
tests/test_differential_random.py (which fuzzes analyzers directly).
Assertion thresholds for SKETCH-backed constraints are drawn far from
plausible metric values so legitimate sketch randomization across merge
trees can never flip a constraint status (the reference makes no
cross-engine bit-equality promise for approximate metrics either).

Reference end-to-end behavior being preserved:
checks/CheckTest.scala (status semantics per DSL method),
VerificationSuite.scala:263-281 (overall status = max over checks).
"""

from __future__ import annotations

import numpy as np
import pytest

from deequ_tpu.checks import Check, CheckLevel
from deequ_tpu.constraints import ConstrainableDataTypes
from deequ_tpu.data.table import ColumnType, Table
from deequ_tpu.parallel.distributed import data_mesh
from deequ_tpu.verification import VerificationSuite

N_SEEDS = 44


def random_table(rng: np.random.Generator) -> Table:
    n = int(rng.integers(1, 2500))
    null_density = float(rng.choice([0.0, 0.02, 0.4, 0.9]))
    x = rng.normal(rng.uniform(-50, 50), rng.uniform(0.0, 20.0), n)
    x[rng.random(n) < null_density] = np.nan
    y = x * rng.uniform(0.5, 2.0) + rng.normal(0, 1.0, n)
    cardinality = int(rng.choice([1, 2, 23, 900]))
    pool = np.array(
        ["", "x", "-3", "7.5", "true", "a b", "it's", "user@example.com"][
            : max(1, min(8, cardinality))
        ]
        + [f"v{i}" for i in range(max(0, cardinality - 8))],
        dtype=object,
    )
    s = pool[rng.integers(0, len(pool), n)]
    s[rng.random(n) < null_density] = None
    g = rng.integers(0, max(1, cardinality), n)
    # low-cardinality float (discount/tax-style): exercises the
    # hash-count family fast path across every engine, with explicit
    # -0.0 keys (a distinct bit pattern the f64_key order must place
    # before +0.0)
    r = rng.integers(-2, 11, n) / 100.0
    r[rng.random(n) < 0.1] = -0.0
    r[rng.random(n) < null_density] = np.nan
    return Table.from_pydict(
        {
            "x": list(x),
            "y": list(y),
            "s": list(s),
            "g": [int(v) for v in g],
            "r": list(r),
        },
        types={
            "x": ColumnType.DOUBLE,
            "y": ColumnType.DOUBLE,
            "s": ColumnType.STRING,
            "g": ColumnType.LONG,
            "r": ColumnType.DOUBLE,
        },
    )


def wide_table(rng: np.random.Generator) -> Table:
    """50-column layout (the wide stream shape, shrunk): 20
    doubles, 15 longs at mixed cardinalities, 10 dictionary-encoded
    strings, 5 low-cardinality floats — so the counts fast paths,
    dictionary memos, int narrowing and the stream pipeline's packing
    all interact across many columns at once."""
    n = int(rng.integers(500, 2500))
    null_density = float(rng.choice([0.0, 0.05, 0.3]))
    cols: dict = {}
    types: dict = {}
    for i in range(20):
        v = rng.normal(rng.uniform(-50, 50), rng.uniform(0.1, 10.0), n)
        v[rng.random(n) < null_density] = np.nan
        cols[f"d{i:02d}"] = list(v)
        types[f"d{i:02d}"] = ColumnType.DOUBLE
    for i in range(15):
        card = int(rng.choice([2, 100, 10_000]))
        cols[f"l{i:02d}"] = [int(v) for v in rng.integers(0, card, n)]
        types[f"l{i:02d}"] = ColumnType.LONG
    for i in range(10):
        card = int(rng.choice([1, 3, 50]))
        pool = np.array(
            [f"s{i}_{j}" for j in range(card)] + ["v1"], dtype=object
        )
        sv = pool[rng.integers(0, len(pool), n)]
        sv[rng.random(n) < null_density] = None
        cols[f"s{i:02d}"] = list(sv)
        types[f"s{i:02d}"] = ColumnType.STRING
    for i in range(5):
        v = rng.integers(-2, 11, n) / 100.0
        v[rng.random(n) < null_density] = np.nan
        cols[f"r{i}"] = list(v)
        types[f"r{i}"] = ColumnType.DOUBLE
    return Table.from_pydict(cols, types=types)


def lineitem_table(rng: np.random.Generator) -> Table:
    """TPC-H lineitem-like layout: quantities, prices, the canonical
    low-cardinality .00-.10 discount/tax floats (the hash-count family
    fast path), tiny-alphabet flag strings, a high-cardinality comment
    column (dictionary memos under pressure), and skewed join keys."""
    n = int(rng.integers(500, 3000))
    qty = rng.integers(1, 51, n).astype(np.float64)
    price = np.round(qty * rng.uniform(900.0, 1100.0, n), 2)
    null_density = float(rng.choice([0.0, 0.02]))
    price[rng.random(n) < null_density] = np.nan
    flags = np.array(["A", "N", "R"], dtype=object)
    status = np.array(["O", "F"], dtype=object)
    modes = np.array(
        ["AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB", "REG AIR"],
        dtype=object,
    )
    comments = np.array(
        [f"comment {i} about v1" for i in range(max(16, n // 3))],
        dtype=object,
    )
    return Table.from_pydict(
        {
            "l_orderkey": [int(v) for v in rng.integers(0, max(1, n // 4), n)],
            "l_suppkey": [int(v) for v in rng.integers(0, 100, n)],
            "l_quantity": list(qty),
            "l_extendedprice": list(price),
            "l_discount": list(rng.integers(0, 11, n) / 100.0),
            "l_tax": list(rng.integers(0, 9, n) / 100.0),
            "l_returnflag": list(flags[rng.integers(0, 3, n)]),
            "l_linestatus": list(status[rng.integers(0, 2, n)]),
            "l_shipmode": list(modes[rng.integers(0, 7, n)]),
            "l_comment": list(comments[rng.integers(0, len(comments), n)]),
        },
        types={
            "l_orderkey": ColumnType.LONG,
            "l_suppkey": ColumnType.LONG,
            "l_quantity": ColumnType.DOUBLE,
            "l_extendedprice": ColumnType.DOUBLE,
            "l_discount": ColumnType.DOUBLE,
            "l_tax": ColumnType.DOUBLE,
            "l_returnflag": ColumnType.STRING,
            "l_linestatus": ColumnType.STRING,
            "l_shipmode": ColumnType.STRING,
            "l_comment": ColumnType.STRING,
        },
    )


LAYOUTS = {
    "narrow": random_table,
    "wide": wide_table,
    "lineitem": lineitem_table,
}


def layout_roles(layout: str, rng: np.random.Generator) -> tuple:
    """Map a layout's columns onto `random_check`'s five roles
    (num1, num2, string, int, lowcard_float)."""
    if layout == "narrow":
        return ("x", "y", "s", "g", "r")
    if layout == "wide":
        return (
            f"d{int(rng.integers(0, 20)):02d}",
            f"d{int(rng.integers(0, 20)):02d}",
            f"s{int(rng.integers(0, 10)):02d}",
            f"l{int(rng.integers(0, 15)):02d}",
            f"r{int(rng.integers(0, 5))}",
        )
    return (
        "l_extendedprice",
        str(rng.choice(["l_quantity", "l_tax"])),
        str(rng.choice(["l_returnflag", "l_shipmode", "l_comment"])),
        str(rng.choice(["l_suppkey", "l_orderkey"])),
        "l_discount",
    )


def random_check(
    rng: np.random.Generator,
    cols: tuple = ("x", "y", "s", "g", "r"),
) -> Check:
    """3-9 random DSL constraints over role-mapped columns
    `(num1, num2, string, int, lowcard_float)` — ("x","y","s","g","r")
    in the canonical narrow layout; the wide/lineitem layouts map their
    own columns onto the same roles. Exact-metric constraints use
    thresholds drawn continuously (probability ~0 of landing within
    engine FP jitter of the metric); sketch-backed constraints use
    far-out bounds so rank-error randomization cannot flip them."""
    x, y, s, g, r = cols
    size_t = float(rng.uniform(0, 3000))
    frac_t = float(rng.uniform(0, 1))
    stat_t = float(rng.uniform(-120, 120))
    far = float(rng.choice([-1e15, 1e15]))

    builders = [
        lambda c: c.has_size(lambda v, t=size_t: v >= t),
        lambda c: c.has_size(lambda v, t=size_t: v >= t).where(f"{g} > 1"),
        lambda c: c.is_complete(x),
        lambda c: c.is_complete(s),
        lambda c: c.has_completeness(x, lambda v, t=frac_t: v >= t),
        lambda c: c.has_completeness(
            s, lambda v, t=frac_t: v >= t
        ).where(f"{g} >= 0"),
        lambda c: c.is_unique(g),
        lambda c: c.has_uniqueness((g,), lambda v, t=frac_t: v >= t),
        lambda c: c.has_distinctness((s,), lambda v, t=frac_t: v >= t),
        lambda c: c.has_unique_value_ratio(
            (g,), lambda v, t=frac_t: v >= t
        ),
        lambda c: c.has_number_of_distinct_values(
            g, lambda v, t=size_t: v <= max(t, 1)
        ),
        lambda c: c.has_entropy(g, lambda v, t=frac_t: v >= t),
        lambda c: c.has_mutual_information(
            s, g, lambda v, t=frac_t: v >= t * 0.1
        ),
        lambda c: c.has_min(x, lambda v, t=stat_t: v <= t),
        lambda c: c.has_max(x, lambda v, t=stat_t: v >= t),
        lambda c: c.has_mean(x, lambda v, t=stat_t: v >= t),
        # low-card float column: the hash-count family path
        lambda c: c.has_mean(r, lambda v, t=frac_t: v >= t * 0.1),
        lambda c: c.has_min(r, lambda v: v >= -0.02),
        lambda c: c.has_standard_deviation(
            r, lambda v, t=frac_t: v <= max(t, 0.2)
        ),
        lambda c: c.has_approx_quantile(
            r, 0.5, lambda v, t=far: (v >= t) if t < 0 else (v <= t)
        ),
        lambda c: c.has_approx_count_distinct(
            r, lambda v, t=far: (v >= t) if t < 0 else (v <= t)
        ),
        lambda c: c.has_sum(x, lambda v, t=stat_t: v >= t),
        lambda c: c.has_standard_deviation(x, lambda v, t=frac_t: v >= t),
        lambda c: c.has_correlation(
            x, y, lambda v, t=frac_t: abs(v) >= t * 0.5
        ),
        # sketch-backed: far-out bounds, immune to rank-error jitter
        lambda c: c.has_approx_quantile(
            x, 0.5, lambda v, t=far: (v >= t) if t < 0 else (v <= t)
        ),
        lambda c: c.has_approx_count_distinct(
            g, lambda v, t=far: (v >= t) if t < 0 else (v <= t)
        ),
        lambda c: c.satisfies(f"{x} > 0", "pos", lambda v, t=frac_t: v >= t),
        lambda c: c.has_pattern(
            s, r"^v\d+$", lambda v, t=frac_t: v >= t
        ),
        lambda c: c.contains_email(s, lambda v, t=frac_t: v <= max(t, 0.5)),
        lambda c: c.has_data_type(
            s,
            ConstrainableDataTypes.INTEGRAL,
            lambda v, t=frac_t: v <= max(t, 0.5),
        ),
        lambda c: c.is_non_negative(x),
        lambda c: c.is_positive(x).where(f"{g} >= 1"),
        lambda c: c.is_less_than(x, y),
        lambda c: c.is_greater_than_or_equal_to(y, x),
        lambda c: c.is_contained_in(s, ["x", "-3", "7.5", "v1"]),
        lambda c: c.is_contained_in(
            g, lower_bound=0.0, upper_bound=1000.0
        ),
    ]
    level = CheckLevel.ERROR if rng.random() < 0.5 else CheckLevel.WARNING
    check = Check(level, f"fuzz-{rng.integers(1 << 30)}")
    k = int(rng.integers(3, 10))
    for i in rng.choice(len(builders), size=k, replace=False):
        check = builders[int(i)](check)
    return check


def suite_snapshot(result):
    """Engine-comparable projection of a VerificationResult: overall
    status, per-check status, per-constraint status, and the metric
    values keyed by analyzer repr."""
    checks = []
    for check, cres in result.check_results.items():
        checks.append(
            (
                check.description,
                cres.status.name,
                tuple(
                    (str(cr.constraint), cr.status.name)
                    for cr in cres.constraint_results
                ),
            )
        )
    metrics = {}
    for analyzer, metric in result.metrics.items():
        v = metric.value
        if v.is_failure:
            metrics[repr(analyzer)] = ("FAIL", type(v.exception).__name__)
        else:
            value = v.get()
            if hasattr(value, "values"):  # Distribution
                value = tuple(
                    sorted(
                        (k, dv.absolute) for k, dv in value.values.items()
                    )
                )
            elif isinstance(value, dict):
                value = tuple(sorted(value.items()))
            metrics[repr(analyzer)] = ("OK", value)
    return result.status.name, tuple(checks), metrics


def assert_snapshots_agree(a, b, context: str) -> None:
    status_a, checks_a, metrics_a = a
    status_b, checks_b, metrics_b = b
    assert status_a == status_b, (context, status_a, status_b)
    assert checks_a == checks_b, (context, checks_a, checks_b)
    assert metrics_a.keys() == metrics_b.keys(), context
    for key in metrics_a:
        sa, va = metrics_a[key]
        sb, vb = metrics_b[key]
        assert sa == sb, (context, key, metrics_a[key], metrics_b[key])
        if sa == "FAIL":
            assert va == vb, (context, key)
        elif key.startswith(("ApproxQuantile", "ApproxCountDistinct")):
            # sketch merge trees differ across engines: rank-error loose
            if isinstance(va, tuple):
                assert len(va) == len(vb), (context, key)
                for (ka, xa), (kb, xb) in zip(va, vb):
                    assert ka == kb, (context, key)
                    assert xb == pytest.approx(xa, rel=0.25, abs=2.0), (
                        context, key,
                    )
            else:
                assert vb == pytest.approx(va, rel=0.25, abs=2.0), (
                    context, key, va, vb,
                )
        elif isinstance(va, float):
            assert vb == pytest.approx(va, rel=1e-9, abs=1e-12), (
                context, key, va, vb,
            )
        else:
            assert va == vb, (context, key)


@pytest.mark.parametrize("seed", range(N_SEEDS))
def test_suite_agrees_across_engines(seed, monkeypatch):
    rng = np.random.default_rng(7000 + seed)
    table = random_table(rng)
    checks = [random_check(rng) for _ in range(int(rng.integers(1, 3)))]

    def run(engine, mesh=None, placement=None):
        if placement is None:
            monkeypatch.delenv("DEEQU_TPU_PLACEMENT", raising=False)
        else:
            monkeypatch.setenv("DEEQU_TPU_PLACEMENT", placement)
        builder = VerificationSuite().on_data(table)
        for check in checks:
            builder = builder.add_check(check)
        return suite_snapshot(builder.with_engine(engine, mesh).run())

    host_fold = run("single", placement="host")
    single_dev = run("single", placement="device")
    mesh = run("distributed", mesh=data_mesh())

    assert_snapshots_agree(host_fold, single_dev, "host-vs-device")
    assert_snapshots_agree(host_fold, mesh, "host-vs-mesh")


@pytest.mark.parametrize("seed", range(0, N_SEEDS, 4))
def test_suite_agrees_streamed_vs_in_memory(seed, monkeypatch, tmp_path):
    """The STREAMED engine dimension: the same random table written to
    Parquet with tiny row groups (many batches — the counts fast paths,
    dictionary memos and per-batch folds all cross batch boundaries)
    must produce the same VerificationSuite outcome as the in-memory
    host fold."""
    from deequ_tpu.data.table import Table as TableCls

    rng = np.random.default_rng(9000 + seed)
    table = random_table(rng)
    checks = [random_check(rng) for _ in range(int(rng.integers(1, 3)))]

    path = str(tmp_path / "fuzz.parquet")
    table.to_parquet(
        path,
        row_group_size=max(64, len(table.column("x")) // 7),
        dictionary_encode_strings=True,
    )

    def run(data):
        monkeypatch.setenv("DEEQU_TPU_PLACEMENT", "host")
        builder = VerificationSuite().on_data(data)
        for check in checks:
            builder = builder.add_check(check)
        return suite_snapshot(builder.with_engine("single").run())

    in_memory = run(table)
    streamed = run(
        TableCls.scan_parquet(path, batch_rows=max(64, len(table.column("x")) // 5))
    )
    assert_snapshots_agree(in_memory, streamed, "memory-vs-stream")


# -- layout fuzz + the streamed vs in-memory differential --------------------


@pytest.mark.parametrize(
    "layout,seed",
    [(layout, seed) for layout in ("narrow", "wide", "lineitem") for seed in range(4)],
)
def test_streamed_scan_bit_identical_to_in_memory(layout, seed, monkeypatch, tmp_path):
    """A streamed scan (the staged driver: prep a batch ahead on its own
    thread) must be BIT-identical to the in-memory Table of the same
    rows (the inline driver) — exact equality, sketches included: the
    same per-batch step folds the same inputs in the same order. Over
    one batch the whole suite is compared; over five, the fused pass
    alone at an explicit batch size, so the sticky wire shifts, the
    cross-batch family memo and the ordered device fold all cross the
    stage boundary. Runs every layout so wide packing, dictionary memos
    and the lineitem fast paths all take both drivers. Also pins
    tracing-inertness: running under a tracer must not change one bit
    of the result, and the trace must show the pipeline actually
    engaged (pipe_stage spans for every stage)."""
    from deequ_tpu import observe
    from deequ_tpu.analyzers.base import ScanShareableAnalyzer
    from deequ_tpu.data.table import Table as TableCls
    from deequ_tpu.ops.fused import FusedScanPass

    rng = np.random.default_rng(11_000 + seed)
    table = LAYOUTS[layout](rng)
    n = table.num_rows
    roles = layout_roles(layout, rng)
    checks = [random_check(rng, roles) for _ in range(int(rng.integers(1, 3)))]
    # alternate placements so both the H2D prep path (device) and the
    # family-kernel host path cross the pipeline's stage boundary
    monkeypatch.setenv("DEEQU_TPU_PLACEMENT", "device" if seed % 2 else "host")

    one_batch = str(tmp_path / "one.parquet")
    table.to_parquet(one_batch, row_group_size=n, dictionary_encode_strings=True)

    def run(data):
        builder = VerificationSuite().on_data(data)
        for check in checks:
            builder = builder.add_check(check)
        return suite_snapshot(builder.with_engine("single").run())

    in_memory = run(table)
    streamed = run(TableCls.scan_parquet(one_batch, batch_rows=n))
    assert streamed == in_memory, (layout, seed)

    with observe.tracing() as tracer:
        traced = run(TableCls.scan_parquet(one_batch, batch_rows=n))
    assert traced == streamed, ("tracing changed results", layout, seed)
    stages = {
        sp.attrs.get("stage")
        for root in tracer.roots
        for sp in _iter_spans(root)
        if sp.name == "pipe_stage"
    }
    assert {"decode", "prep", "fold"} <= stages, (
        "pipeline did not engage under tracing",
        stages,
    )

    # many batches: an explicit batch size holds both drivers to the
    # same batch boundaries (one row group per batch)
    batch = max(64, n // 5)
    many = str(tmp_path / "many.parquet")
    table.to_parquet(many, row_group_size=batch, dictionary_encode_strings=True)
    analyzers = list(
        {
            a
            for check in checks
            for a in check.required_analyzers()
            if isinstance(a, ScanShareableAnalyzer)
        }
    )

    def scan(data):
        out = {}
        for r in FusedScanPass(analyzers, batch_size=batch).run(data):
            value = (
                r.analyzer.compute_metric_from(r.state).value
                if r.error is None
                else None
            )
            out[repr(r.analyzer)] = (
                repr(value.get())
                if value is not None and value.is_success
                else repr(r.error)
            )
        return out

    assert scan(TableCls.scan_parquet(many, batch_rows=batch)) == scan(table), (
        layout,
        seed,
    )


def _iter_spans(root):
    stack = [root]
    while stack:
        sp = stack.pop()
        yield sp
        stack.extend(sp.children)


# -- row-group pushdown on/off differential (ISSUE 7) ------------------------


def pushdown_table(rng: np.random.Generator) -> Table:
    """Sorted-key layout: parquet row-group min/max over `k` are disjoint
    ranges, so comparison wheres are genuinely selective. `v` carries
    NaN (runtime nulls invisible to parquet stats) and real nulls; `s`
    is a string column (never stats-decidable)."""
    n = int(rng.integers(1200, 4000))
    k = np.sort(rng.integers(0, 10_000, n))
    v = rng.normal(0.0, 50.0, n)
    v[rng.random(n) < 0.05] = np.nan
    v_list = [None if rng.random() < 0.02 else float(x) for x in v]
    s = np.array(["a", "b", "v1", "zz"], dtype=object)[rng.integers(0, 4, n)]
    s[rng.random(n) < 0.1] = None
    return Table.from_pydict(
        {"k": [int(x) for x in k], "v": v_list, "s": list(s)},
        types={
            "k": ColumnType.LONG,
            "v": ColumnType.DOUBLE,
            "s": ColumnType.STRING,
        },
    )


def random_pushdown_where(rng: np.random.Generator) -> str:
    """Mixed eligibility: selective sorted-key comparisons, NaN-hampered
    float ranges, stats-opaque string predicates, and/or combinations."""
    cut = int(rng.integers(-100, 10_100))
    roll = rng.random()
    if roll < 0.5:
        op = str(rng.choice(["<", "<=", ">", ">=", "=", "!="]))
        return f"k {op} {cut}"
    if roll < 0.7:
        return f"k < {cut} and v > {float(rng.uniform(-100, 100)):.1f}"
    if roll < 0.85:
        lo = int(rng.integers(0, 2500))
        hi = int(rng.integers(7500, 10_000))
        return f"k < {lo} or k > {hi}"
    return str(rng.choice(["s != 'zz'", "v is not null", f"k >= {cut}"]))


def pushdown_check(rng: np.random.Generator, wheres) -> Check:
    """Every constraint filters (an unfiltered fused member disables all
    skipping), drawn from scan-shareable, exactly-folded builders —
    sketch metrics are excluded because pruning changes decode batch
    boundaries and sketch compaction is partition-sensitive."""
    frac_t = float(rng.uniform(0, 1))
    stat_t = float(rng.uniform(-120, 120))
    builders = [
        lambda c: c.has_size(lambda v, t=stat_t: v >= t),
        lambda c: c.has_completeness("v", lambda v, t=frac_t: v >= t),
        lambda c: c.has_completeness("s", lambda v, t=frac_t: v >= t),
        lambda c: c.has_mean("v", lambda v, t=stat_t: v >= t),
        lambda c: c.has_min("v", lambda v, t=stat_t: v <= t),
        lambda c: c.has_max("k", lambda v, t=stat_t: v >= t),
        lambda c: c.has_sum("v", lambda v, t=stat_t: v >= t),
        lambda c: c.has_standard_deviation("v", lambda v, t=frac_t: v >= t),
        lambda c: c.satisfies("v > 0", "pos", lambda v, t=frac_t: v >= t),
    ]
    check = Check(CheckLevel.ERROR, f"pushdown-{rng.integers(1 << 30)}")
    k = int(rng.integers(3, 8))
    for i in rng.choice(len(builders), size=k, replace=False):
        check = builders[int(i)](check).where(str(rng.choice(wheres)))
    return check


@pytest.mark.parametrize("seed", range(8))
def test_pushdown_on_off_bit_identical(seed, monkeypatch, tmp_path):
    """DEEQU_TPU_PUSHDOWN=0 must be BIT-identical to the pruning path —
    exact snapshot equality (same engine, same surviving rows, masked
    folds are exact): statically skipping a row group may never change
    one bit of any metric. Even seeds share one aggressively selective
    where across all constraints and assert groups actually skipped;
    odd seeds draw independent mixed-eligibility wheres (string
    predicates, NaN floats, or-clauses) where skipping is incidental."""
    from deequ_tpu import observe
    from deequ_tpu.data.table import Table as TableCls

    rng = np.random.default_rng(13_000 + seed)
    table = pushdown_table(rng)
    n = table.num_rows
    if seed % 2 == 0:
        wheres = [f"k < {int(rng.integers(500, 2500))}"]
    else:
        wheres = [random_pushdown_where(rng) for _ in range(3)]
    checks = [
        pushdown_check(rng, wheres) for _ in range(int(rng.integers(1, 3)))
    ]

    path = str(tmp_path / "pushdown.parquet")
    table.to_parquet(
        path, row_group_size=max(64, n // 7), dictionary_encode_strings=True
    )

    def run(pushdown_env):
        monkeypatch.setenv("DEEQU_TPU_PLACEMENT", "device" if seed % 4 >= 2 else "host")
        monkeypatch.setenv("DEEQU_TPU_PUSHDOWN", pushdown_env)
        data = TableCls.scan_parquet(path, batch_rows=max(64, n // 5))
        builder = VerificationSuite().on_data(data)
        for check in checks:
            builder = builder.add_check(check)
        return suite_snapshot(builder.with_engine("single").run())

    off = run("0")
    on = run("1")
    assert off == on, (seed, wheres)

    with observe.tracing() as tracer:
        traced = run("1")
    assert traced == on, ("tracing changed results", seed)
    prunes = [
        sp
        for root in tracer.roots
        for sp in _iter_spans(root)
        if sp.name == "prune"
    ]
    assert prunes, "pushdown never produced a prune decision"
    if seed % 2 == 0:
        assert sum(sp.attrs["groups_skipped"] for sp in prunes) > 0, (
            "selective shared where skipped nothing",
            wheres,
        )


# -- decode fast path + workers on/off differential (ISSUE 8) ----------------


@pytest.mark.parametrize(
    "layout,seed",
    [(layout, seed) for layout in ("narrow", "wide", "lineitem") for seed in range(3)],
)
def test_decode_fastpath_workers_bit_identical(layout, seed, monkeypatch, tmp_path):
    """DEEQU_TPU_DECODE_FASTPATH=0 (the host from_arrow chain) and
    DEEQU_TPU_DECODE_WORKERS at 1 vs 3 must all be BIT-identical —
    exact snapshot equality, sketches included: the fast path and the
    worker pool change WHERE and HOW columns decode, never one bit of
    any value, mask, or dictionary code. Runs every layout so numeric
    primitives, bool bitmaps, dictionary codes, NaN folds and the
    tiny-group coalescer all cross both decode routes. Also pins that
    under a tracer the decode planner actually engaged (decode_fastpath
    span with fast columns) and the worker pool actually fanned out
    (decode_unit spans)."""
    from deequ_tpu import observe
    from deequ_tpu.data.table import Table as TableCls

    rng = np.random.default_rng(14_000 + seed)
    table = LAYOUTS[layout](rng)
    n = table.num_rows
    roles = layout_roles(layout, rng)
    checks = [random_check(rng, roles) for _ in range(int(rng.integers(1, 3)))]
    placement = "device" if seed % 2 else "host"

    path = str(tmp_path / "decode.parquet")
    table.to_parquet(
        path, row_group_size=max(64, n // 7), dictionary_encode_strings=True
    )

    def run(fastpath_env, workers_env):
        monkeypatch.setenv("DEEQU_TPU_PLACEMENT", placement)
        monkeypatch.setenv("DEEQU_TPU_DECODE_FASTPATH", fastpath_env)
        monkeypatch.setenv("DEEQU_TPU_DECODE_WORKERS", workers_env)
        data = TableCls.scan_parquet(path, batch_rows=max(64, n // 5))
        builder = VerificationSuite().on_data(data)
        for check in checks:
            builder = builder.add_check(check)
        return suite_snapshot(builder.with_engine("single").run())

    baseline = run("0", "1")
    for fp, workers in (("1", "1"), ("0", "3"), ("1", "3")):
        assert run(fp, workers) == baseline, (layout, seed, fp, workers)

    with observe.tracing() as tracer:
        traced = run("1", "3")
    assert traced == baseline, ("tracing changed results", layout, seed)
    spans = [
        sp for root in tracer.roots for sp in _iter_spans(root)
    ]
    plans = [sp for sp in spans if sp.name == "decode_fastpath"]
    assert plans, "decode planner never produced a plan"
    assert all(sp.attrs["workers"] == 3 for sp in plans)
    assert sum(sp.attrs["cols_fast"] for sp in plans) > 0, (
        "no column took the fast path",
        layout,
    )
    # the pool's per-unit span is decode_unit on the pyarrow parallel
    # path and page_decode on the native-reader path (ISSUE 11), which
    # takes over the scan whenever any column has a native page recipe
    assert any(sp.name in ("decode_unit", "page_decode") for sp in spans), (
        "parallel decode workers never engaged"
    )


# -- decode-to-wire fusion on/off differential (ISSUE 9) ---------------------


@pytest.mark.parametrize(
    "layout,seed",
    [(layout, seed) for layout in ("narrow", "wide", "lineitem") for seed in range(2)],
)
def test_wire_fusion_bit_identical(layout, seed, monkeypatch, tmp_path):
    """DEEQU_TPU_WIRE_FUSED=0 (Column intermediate + numpy pack) vs =1
    (decode straight into packed wire slices) must be BIT-identical —
    exact snapshot equality, sketches included — across worker counts 1
    vs 3 and BOTH placements: the wire kernels change where masks pack
    and values narrow/shift, never one bit of any metric. Every layout
    runs so bitpacked NaN folds, narrowed ints, f32 shift handshakes and
    valid-only bool masks all cross both routes. Under a tracer the wire
    verdict must actually have run (wire_cols_total counter recorded,
    cols_wire_fused attr on the decode plan span)."""
    from deequ_tpu import observe
    from deequ_tpu.data.table import Table as TableCls

    rng = np.random.default_rng(15_000 + seed)
    table = LAYOUTS[layout](rng)
    n = table.num_rows
    roles = layout_roles(layout, rng)
    checks = [random_check(rng, roles) for _ in range(int(rng.integers(1, 3)))]

    path = str(tmp_path / "wire.parquet")
    table.to_parquet(
        path, row_group_size=max(64, n // 7), dictionary_encode_strings=True
    )

    def run(wire_env, workers_env, placement):
        monkeypatch.setenv("DEEQU_TPU_PLACEMENT", placement)
        monkeypatch.setenv("DEEQU_TPU_WIRE_FUSED", wire_env)
        monkeypatch.setenv("DEEQU_TPU_DECODE_WORKERS", workers_env)
        data = TableCls.scan_parquet(path, batch_rows=max(64, n // 5))
        builder = VerificationSuite().on_data(data)
        for check in checks:
            builder = builder.add_check(check)
        return suite_snapshot(builder.with_engine("single").run())

    for placement in ("host", "device"):
        baseline = run("0", "1", placement)
        for wire, workers in (("1", "1"), ("0", "3"), ("1", "3")):
            assert run(wire, workers, placement) == baseline, (
                layout, seed, placement, wire, workers,
            )

    device_baseline = run("0", "1", "device")
    with observe.tracing() as tracer:
        traced = run("1", "3", "device")
    assert traced == device_baseline, ("tracing changed results", layout, seed)
    plans = [
        sp
        for root in tracer.roots
        for sp in _iter_spans(root)
        if sp.name == "decode_fastpath"
    ]
    assert plans, "decode planner never produced a plan"
    assert all("cols_wire_fused" in sp.attrs for sp in plans), (
        "wire verdict missing from the decode plan span"
    )
    assert tracer.counters.get("wire_cols_total", 0) > 0, (
        "wire planning never recorded its verdict"
    )


# -- native parquet reader on/off differential (ISSUE 11) --------------------


@pytest.mark.parametrize(
    "layout,seed",
    [(layout, seed) for layout in ("narrow", "wide", "lineitem") for seed in range(2)],
)
def test_native_reader_bit_identical(layout, seed, monkeypatch, tmp_path):
    """DEEQU_TPU_NATIVE_READER=0 (pyarrow produces every buffer) vs =1
    (planner-approved chunks pread and page-decoded by parquet_read.c)
    must be BIT-identical — exact snapshot equality, sketches included —
    across worker counts 1 vs 3, BOTH placements, and BOTH parquet
    format versions (V1 and V2 data pages): the reader changes who
    produces the bytes, never one bit of any value, mask or dictionary
    code. NaN/NULL-heavy layouts run so validity bitmaps and NaN folds
    cross both producers. Under a tracer the reader must actually
    engage (page_read/page_decode spans, reader_chunks_native > 0) and
    the traced per-unit chunk counts must sum to exactly the planner's
    static prediction — the runtime twin of drift.reader_chunks_native
    staying pinned at 0."""
    import pyarrow.parquet as pq

    from deequ_tpu import observe
    from deequ_tpu.data.table import Table as TableCls
    from deequ_tpu.ops import native

    if not native.available():
        pytest.skip("native library unavailable")

    rng = np.random.default_rng(16_000 + seed)
    table = LAYOUTS[layout](rng)
    n = table.num_rows
    roles = layout_roles(layout, rng)
    checks = [random_check(rng, roles) for _ in range(int(rng.integers(1, 3)))]
    version = "1.0" if seed % 2 == 0 else "2.6"

    path = str(tmp_path / "reader.parquet")
    table.to_parquet(
        path, row_group_size=max(64, n // 7), dictionary_encode_strings=True
    )
    # rewrite at the target format version: V1 data pages compress the
    # definition levels with the values, V2 pages carry them raw — the
    # native page parser must take both to the same bits
    pq.write_table(
        pq.read_table(path),
        path,
        version=version,
        row_group_size=max(64, n // 7),
        data_page_size=4096,
    )

    def run(reader_env, workers_env, placement):
        monkeypatch.setenv("DEEQU_TPU_PLACEMENT", placement)
        monkeypatch.setenv("DEEQU_TPU_NATIVE_READER", reader_env)
        monkeypatch.setenv("DEEQU_TPU_DECODE_WORKERS", workers_env)
        data = TableCls.scan_parquet(path, batch_rows=max(64, n // 5))
        builder = VerificationSuite().on_data(data)
        for check in checks:
            builder = builder.add_check(check)
        return suite_snapshot(builder.with_engine("single").run())

    for placement in ("host", "device"):
        baseline = run("0", "1", placement)
        for reader, workers in (("1", "1"), ("0", "3"), ("1", "3")):
            assert run(reader, workers, placement) == baseline, (
                layout, seed, placement, reader, workers,
            )

    host_baseline = run("0", "1", "host")
    with observe.tracing() as tracer:
        traced = run("1", "3", "host")
    assert traced == host_baseline, ("tracing changed results", layout, seed)
    spans = [sp for root in tracer.roots for sp in _iter_spans(root)]
    reads = [sp for sp in spans if sp.name == "page_read"]
    decodes = [sp for sp in spans if sp.name == "page_decode"]
    assert reads, "read-ahead fetch thread never produced a page_read span"
    assert decodes, "native reader never produced a page_decode span"
    runtime_native = sum(sp.attrs.get("chunks_native", 0) for sp in decodes)
    assert runtime_native > 0, ("no chunk decoded natively", layout, seed)
    planned_native = tracer.counters.get("reader_chunks_native", 0)
    assert tracer.counters.get("reader_chunks_total", 0) > 0, (
        "reader verdict never recorded"
    )
    assert runtime_native == planned_native, (
        "runtime chunk split drifted from the static plan",
        layout, seed, runtime_native, planned_native,
    )


# -- encoded fold on/off differential (ISSUE 20) -----------------------------


@pytest.mark.parametrize(
    "layout,seed",
    [(layout, seed) for layout in ("narrow", "wide", "lineitem") for seed in range(2)],
)
def test_encoded_fold_bit_identical(layout, seed, monkeypatch, tmp_path):
    """DEEQU_TPU_ENCODED_FOLD=0 (every planner-approved chunk expands
    to row width before folding) vs =1 (eligible columns fold moments
    over (run_len, value) streams and roll dictionary codes up into the
    sketch families) must be BIT-identical — exact snapshot equality,
    sketches included — across worker counts 1 vs 3, BOTH placements,
    BOTH parquet format versions (V1/V2 data pages) and all three
    reader codecs (uncompressed/snappy/zstd): the encoded fold changes
    the arithmetic ORDER, never one bit of any published metric (the
    planner only approves columns whose memo publication it can prove
    exact). A pinned anchor check keeps the low-cardinality float role
    a sketch consumer so at least one column is provably eligible in
    every draw; under a tracer the fold must actually engage
    (encfold_cols > 0, run/fallback chunk counters flowing) and the
    per-span runs_native counts must sum to the traced encfold_runs
    counter — the runtime twin of drift.encfold_columns staying 0."""
    import pyarrow.parquet as pq

    from deequ_tpu import observe
    from deequ_tpu.data.table import Table as TableCls
    from deequ_tpu.ops import native

    if not native.available():
        pytest.skip("native library unavailable")

    rng = np.random.default_rng(20_000 + seed)
    table = LAYOUTS[layout](rng)
    n = table.num_rows
    roles = layout_roles(layout, rng)
    checks = [random_check(rng, roles) for _ in range(int(rng.integers(1, 3)))]
    # the low-cardinality float role with a far-out sketch constraint
    # and no where filter: a memo-servable consumer the classifier must
    # approve, whatever the random checks drew
    lowcard = roles[4]
    checks.append(
        Check(CheckLevel.WARNING, "encfold-anchor")
        .has_approx_count_distinct(lowcard, lambda v: v >= -1e15)
        .has_mean(lowcard, lambda v: v >= -1e15)
    )
    version = "1.0" if seed % 2 == 0 else "2.6"
    codec = ("none", "snappy", "zstd")[
        ({"narrow": 0, "wide": 1, "lineitem": 2}[layout] + seed) % 3
    ]

    path = str(tmp_path / "encfold.parquet")
    table.to_parquet(
        path, row_group_size=max(64, n // 7), dictionary_encode_strings=True
    )
    pq.write_table(
        pq.read_table(path),
        path,
        version=version,
        compression=codec,
        row_group_size=max(64, n // 7),
        data_page_size=4096,
    )

    def run(encfold_env, workers_env, placement):
        monkeypatch.setenv("DEEQU_TPU_PLACEMENT", placement)
        monkeypatch.setenv("DEEQU_TPU_NATIVE_READER", "1")
        monkeypatch.setenv("DEEQU_TPU_ENCODED_FOLD", encfold_env)
        monkeypatch.setenv("DEEQU_TPU_DECODE_WORKERS", workers_env)
        data = TableCls.scan_parquet(path, batch_rows=max(64, n // 5))
        builder = VerificationSuite().on_data(data)
        for check in checks:
            builder = builder.add_check(check)
        return suite_snapshot(builder.with_engine("single").run())

    for placement in ("host", "device"):
        baseline = run("0", "1", placement)
        for encfold, workers in (("1", "1"), ("0", "3"), ("1", "3")):
            assert run(encfold, workers, placement) == baseline, (
                layout, seed, placement, encfold, workers,
            )

    host_baseline = run("0", "1", "host")
    with observe.tracing() as tracer:
        traced = run("1", "3", "host")
    assert traced == host_baseline, ("tracing changed results", layout, seed)
    assert tracer.counters.get("encfold_cols_total", 0) > 0, (
        "encoded-fold verdict never recorded"
    )
    assert tracer.counters.get("encfold_cols", 0) > 0, (
        "the anchored sketch consumer was never approved", layout, seed,
    )
    folded = tracer.counters.get("encfold_chunks", 0)
    fallback = tracer.counters.get("encfold_chunks_fallback", 0)
    assert folded + fallback > 0, (
        "no chunk of an approved column reached the run decoder",
        layout, seed,
    )
    spans = [sp for root in tracer.roots for sp in _iter_spans(root)]
    decodes = [sp for sp in spans if sp.name == "page_decode"]
    assert decodes, "native reader never produced a page_decode span"
    span_runs = sum(sp.attrs.get("runs_native", 0) for sp in decodes)
    assert span_runs == tracer.counters.get("encfold_runs", 0), (
        "per-span run counts drifted from the traced total",
        layout, seed,
    )


@pytest.mark.parametrize(
    "layout,seed",
    [("wide", 0), ("wide", 1), ("lineitem", 0), ("lineitem", 1)],
)
def test_suite_layouts_agree_across_engines(layout, seed, monkeypatch):
    """Wide/lineitem layouts through the three in-memory engines — the
    layout generalization of `test_suite_agrees_across_engines`."""
    rng = np.random.default_rng(12_000 + seed)
    table = LAYOUTS[layout](rng)
    roles = layout_roles(layout, rng)
    checks = [random_check(rng, roles) for _ in range(int(rng.integers(1, 3)))]

    def run(engine, mesh=None, placement=None):
        if placement is None:
            monkeypatch.delenv("DEEQU_TPU_PLACEMENT", raising=False)
        else:
            monkeypatch.setenv("DEEQU_TPU_PLACEMENT", placement)
        builder = VerificationSuite().on_data(table)
        for check in checks:
            builder = builder.add_check(check)
        return suite_snapshot(builder.with_engine(engine, mesh).run())

    host_fold = run("single", placement="host")
    single_dev = run("single", placement="device")
    mesh = run("distributed", mesh=data_mesh())

    assert_snapshots_agree(host_fold, single_dev, f"{layout}:host-vs-device")
    assert_snapshots_agree(host_fold, mesh, f"{layout}:host-vs-mesh")


# ---------------------------------------------------------------------------
# persistent partition-state cache: incremental scans (repository/states.py)
# ---------------------------------------------------------------------------


def _write_partition(table, path: str) -> None:
    table.to_parquet(
        path,
        row_group_size=max(64, table.num_rows // 5),
        dictionary_encode_strings=True,
    )


@pytest.mark.parametrize("seed", range(3))
def test_state_cache_on_off_bit_identical(seed, monkeypatch, tmp_path):
    """The persistent partition-state cache is a pure scan-for-load
    swap: with a repository attached, every run must be BIT-identical
    to a cache-off full rescan — exact snapshot equality, sketches
    included — through the whole dataset lifecycle (cold fill, all-hit
    warm run, appended partition, mutated partition, renamed files that
    reorder the partition merge) and on BOTH placements. Placement is
    part of the plan signature, so each placement fills and hits its
    own namespace."""
    from deequ_tpu.data.table import Table as TableCls
    from deequ_tpu.repository.states import FileSystemStateRepository

    rng = np.random.default_rng(17_000 + seed)
    checks = [random_check(rng) for _ in range(int(rng.integers(1, 3)))]
    data_dir = tmp_path / "dataset"
    data_dir.mkdir()
    for i in range(3):
        _write_partition(random_table(rng), str(data_dir / f"part-{i}.parquet"))

    repo = FileSystemStateRepository(str(tmp_path / "cache"))

    def run(placement, cached):
        monkeypatch.setenv("DEEQU_TPU_PLACEMENT", placement)
        monkeypatch.setenv("DEEQU_TPU_STATE_CACHE", "1" if cached else "0")
        data = TableCls.scan_parquet_dataset(str(data_dir))
        builder = VerificationSuite().on_data(data)
        for check in checks:
            builder = builder.add_check(check)
        if cached:
            builder = builder.with_state_repository(repo, "fuzz")
        return suite_snapshot(builder.with_engine("single").run())

    def check_step(step):
        for placement in ("host", "device"):
            baseline = run(placement, False)
            assert run(placement, True) == baseline, (step, seed, placement)

    check_step("cold")  # first cache-on run fills the repository
    check_step("warm")  # second is all hits: merge of loaded states only

    _write_partition(random_table(rng), str(data_dir / "part-3.parquet"))
    check_step("append")  # only the new partition lacks an entry

    _write_partition(random_table(rng), str(data_dir / "part-1.parquet"))
    check_step("mutate")  # rewritten fingerprint self-invalidates

    (data_dir / "part-0.parquet").rename(data_dir / "part-9.parquet")
    check_step("reorder")  # new basename = new fingerprint AND new merge order


def test_state_cache_drift_pins_zero_and_traces(monkeypatch, tmp_path):
    """Warm incremental run end to end: the planner's cached/scanned
    prediction must pin observed drift to exactly zero, the trace must
    carry the state_cache spans and partition counters, and the engine
    telemetry record must expose `engine.state_cache_hit_ratio == 1`."""
    from deequ_tpu.data.table import Table as TableCls
    from deequ_tpu.lint.cost import cost_drift
    from deequ_tpu.observe.telemetry import engine_metric_record
    from deequ_tpu.repository.states import FileSystemStateRepository

    rng = np.random.default_rng(23)
    data_dir = tmp_path / "dataset"
    data_dir.mkdir()
    for i in range(4):
        _write_partition(random_table(rng), str(data_dir / f"p{i}.parquet"))
    check = (
        Check(CheckLevel.ERROR, "incremental")
        .has_size(lambda s: s > 0)
        .is_complete("x")
        .has_mean("x", lambda m: True)
        .has_standard_deviation("x", lambda s: True)
        .has_approx_quantile("x", 0.5, lambda q: True)
    )
    repo = FileSystemStateRepository(str(tmp_path / "cache"))
    monkeypatch.delenv("DEEQU_TPU_STATE_CACHE", raising=False)
    monkeypatch.setenv("DEEQU_TPU_PLACEMENT", "device")

    def run():
        return (
            VerificationSuite()
            .on_data(TableCls.scan_parquet_dataset(str(data_dir)))
            .add_check(check)
            .with_state_repository(repo, "drift")
            .with_engine("single")
            .with_tracing(True)
            .run()
        )

    cold = run()
    assert cold.run_trace.counters["partitions_scanned"] == 4
    assert cold.run_trace.counters["partitions_total"] == 4

    warm = run()
    counters = warm.run_trace.counters
    assert counters["partitions_cached"] == 4
    assert counters["partitions_total"] == 4
    assert "partitions_scanned" not in counters

    # predicted == observed, both directions, exactly zero
    drift = cost_drift(warm.plan_cost, warm.run_trace)
    assert drift["drift.partitions_cached"] == 0.0
    assert drift["drift.partitions_scanned"] == 0.0

    cache_spans = [sp for sp in warm.run_trace.spans() if sp.name == "state_cache"]
    assert len(cache_spans) == 4
    assert all(sp.attrs.get("hit") for sp in cache_spans)

    rec = engine_metric_record(warm.run_trace, warm.plan_cost)
    assert rec["engine.state_cache_hit_ratio"] == 1.0
    assert rec["engine.drift.partitions_cached"] == 0.0


# -- forensics on/off differential (ISSUE 12) --------------------------------


@pytest.mark.parametrize("seed", range(3))
def test_forensics_on_off_bit_identical(seed, monkeypatch, tmp_path):
    """with_forensics() must be provably inert: exact snapshot equality
    (metrics, check statuses, sketches included) with row-level capture
    on vs off — on both placements, over the streamed dataset and the
    same rows in memory, and through a state-cache cold fill and all-hit warm run
    (cached partitions reduce forensics to provenance, never change
    results). Capture reads the decoded batch through its own masks and
    never touches the fold inputs, so nothing may diverge by one bit."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from deequ_tpu.data.table import Table as TableCls
    from deequ_tpu.repository.states import FileSystemStateRepository

    rng = np.random.default_rng(19_000 + seed)
    checks = [random_check(rng) for _ in range(int(rng.integers(1, 3)))]
    data_dir = tmp_path / "dataset"
    data_dir.mkdir()
    for i in range(3):
        _write_partition(random_table(rng), str(data_dir / f"part-{i}.parquet"))
    repo = FileSystemStateRepository(str(tmp_path / "cache"))
    # the same rows in memory: the inline driver's side of the matrix
    in_memory = TableCls.from_arrow(
        pa.concat_tables(
            [pq.read_table(str(p)) for p in sorted(data_dir.iterdir())]
        )
    )

    def run(placement, streamed, forensics, cached=False):
        monkeypatch.setenv("DEEQU_TPU_PLACEMENT", placement)
        monkeypatch.setenv("DEEQU_TPU_STATE_CACHE", "1" if cached else "0")
        data = (
            TableCls.scan_parquet_dataset(str(data_dir))
            if streamed
            else in_memory
        )
        builder = VerificationSuite().on_data(data)
        for check in checks:
            builder = builder.add_check(check)
        if cached:
            builder = builder.with_state_repository(repo, "forensics-fuzz")
        if forensics:
            builder = builder.with_forensics()
        result = builder.with_engine("single").run()
        # the report rides the result exactly when capture was on
        assert (result.forensics() is not None) == forensics
        return suite_snapshot(result)

    for placement in ("host", "device"):
        for streamed in (True, False):
            off = run(placement, streamed, False)
            on = run(placement, streamed, True)
            assert off == on, (seed, placement, streamed)

    baseline = run("host", True, False)
    # cold: capture rides the scans that fill the cache
    assert run("host", True, True, cached=True) == baseline, (seed, "cold")
    # warm: every partition merges from cache; capture sees no batches
    assert run("host", True, True, cached=True) == baseline, (seed, "warm-on")
    assert run("host", True, False, cached=True) == baseline, (seed, "warm-off")


# -- chaos differential: injected faults change nothing (ISSUE 13) -----------


#: the fault matrix `make chaos` also sweeps: transient IO errors,
#: short reads, corrupt pages, decode failures, worker deaths, stage
#: faults and stalls — every containment path must stay bit-identical
CHAOS_MATRIX = [
    "seed=101,read.pread:0.4:4",
    "seed=102,read.short:0.5:3",
    "seed=103,read.corrupt:0.5:2",
    "seed=104,decode.chunk:0.6:3",
    "seed=105,decode.worker:1.0:1",
    "seed=106,pipeline.stage:1.0:1",
    "seed=107,stall=0.005,pipeline.stall:1.0:2",
    "seed=108,stall=0.005,read.latency:1.0:3",
]


@pytest.mark.parametrize("spec", CHAOS_MATRIX)
def test_chaos_faults_bit_identical_both_placements(spec, monkeypatch, tmp_path):
    """The chaos differential: a seeded fault plan injecting IO errors,
    short reads, corrupt pages, worker deaths or stalls into the scan
    must produce EXACTLY the clean run's snapshot on both placements —
    every containment path (retry, inline redo, pyarrow fallback)
    degrades to the same bits, never a wrong answer."""
    from deequ_tpu.data.table import Table as TableCls
    from deequ_tpu.testing import faults

    rng = np.random.default_rng(23_000)
    table = random_table(rng)
    checks = [random_check(rng) for _ in range(2)]
    path = str(tmp_path / "chaos.parquet")
    table.to_parquet(
        path, row_group_size=max(64, table.num_rows // 7),
        dictionary_encode_strings=True,
    )

    def run(placement):
        monkeypatch.setenv("DEEQU_TPU_PLACEMENT", placement)
        # the worker-pool decode path needs >1 worker on a 1-core box
        monkeypatch.setenv("DEEQU_TPU_DECODE_WORKERS", "2")
        data = TableCls.scan_parquet(
            path, batch_rows=max(64, table.num_rows // 5)
        )
        builder = VerificationSuite().on_data(data)
        for check in checks:
            builder = builder.add_check(check)
        return suite_snapshot(builder.with_engine("single").run())

    for placement in ("host", "device"):
        clean = run(placement)
        with faults.install(spec) as plan:
            faulted = run(placement)
        assert sum(plan.injected.values()) >= 1, (
            f"spec {spec!r} never fired on {placement} — the matrix "
            f"entry exercises nothing"
        )
        assert clean == faulted, (spec, placement, plan.injected)


def test_sigkill_resume_scans_only_remaining_partitions(tmp_path):
    """Crash-safe partial progress end to end: SIGKILL a scan subprocess
    after its first partition-state commit; the in-process rerun loads
    the committed partitions from the FileSystemStateRepository, scans
    ONLY the remainder, and lands bit-equal to a clean full run."""
    import glob
    import signal
    import struct
    import subprocess
    import sys
    import time

    from deequ_tpu.analyzers import Completeness, Mean, Size, StandardDeviation
    from deequ_tpu.data.table import Table as TableCls
    from deequ_tpu.repository.states import FileSystemStateRepository
    from deequ_tpu.runners.analysis_runner import AnalysisRunner

    rng = np.random.default_rng(31_000)
    data_dir = tmp_path / "dataset"
    data_dir.mkdir()
    n_parts = 3
    for i in range(n_parts):
        _write_partition(random_table(rng), str(data_dir / f"part-{i}.parquet"))
    cache_dir = str(tmp_path / "cache")

    child_src = (
        "from deequ_tpu.analyzers import Completeness, Mean, Size, StandardDeviation\n"
        "from deequ_tpu.data.table import Table\n"
        "from deequ_tpu.repository.states import FileSystemStateRepository\n"
        "from deequ_tpu.runners.analysis_runner import AnalysisRunner\n"
        f"repo = FileSystemStateRepository({cache_dir!r})\n"
        f"AnalysisRunner.do_analysis_run(\n"
        f"    Table.scan_parquet_dataset({str(data_dir)!r}),\n"
        "    [Size(), Mean('x'), StandardDeviation('x'), Completeness('x')],\n"
        "    state_repository=repo, dataset_name='sigkill',\n"
        ")\n"
    )
    import os as _os

    env = dict(_os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("DEEQU_TPU_STATE_CACHE", None)
    # slow every row-group read so the kill lands mid-run, not post-run
    env["DEEQU_TPU_SOURCE_STALL_MS"] = "400"
    repo_root = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + _os.pathsep + env.get("PYTHONPATH", "")

    child = subprocess.Popen(
        [sys.executable, "-c", child_src],
        env=env, cwd=repo_root,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    try:
        deadline = time.monotonic() + 120.0
        committed = []
        while time.monotonic() < deadline:
            committed = glob.glob(cache_dir + "/**/*.dqstate", recursive=True)
            if committed:
                break
            if child.poll() is not None:
                pytest.fail("scan subprocess exited before any commit")
            time.sleep(0.02)
        assert committed, "no partition state committed within the window"
    finally:
        if child.poll() is None:
            child.send_signal(signal.SIGKILL)
        child.wait(timeout=30)

    cached_n = len(
        glob.glob(cache_dir + "/**/*.dqstate", recursive=True)
    )
    assert 1 <= cached_n < n_parts, (
        f"kill landed outside the run: {cached_n}/{n_parts} committed"
    )

    analyzers = [Size(), Mean("x"), StandardDeviation("x"), Completeness("x")]
    clean = AnalysisRunner.do_analysis_run(
        TableCls.scan_parquet_dataset(str(data_dir)), analyzers
    )
    resumed = AnalysisRunner.do_analysis_run(
        TableCls.scan_parquet_dataset(str(data_dir)), analyzers,
        state_repository=FileSystemStateRepository(cache_dir),
        dataset_name="sigkill", tracing=True,
    )
    counters = resumed.run_trace.counters
    assert counters["partitions_cached"] == cached_n
    assert counters["partitions_scanned"] == n_parts - cached_n
    for a in analyzers:
        assert struct.pack(">d", clean.metric_map[a].value.get()) == struct.pack(
            ">d", resumed.metric_map[a].value.get()
        ), repr(a)


# ---------------------------------------------------------------------------
# fleet-wide scan sharing: shared vs solo (ISSUE 17)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(3))
def test_scan_sharing_shared_vs_solo_bit_identical(seed, monkeypatch, tmp_path):
    """N randomly-overlapping suites submitted to the DQService over
    one table must land BIT-identical to each suite's solo run — exact
    snapshot equality, sketches included — whether the scheduler put
    them on a shared superset scan or not, on BOTH placements. Every
    shared participant must carry a CONTAINED subsumption proof pinned
    with zero drift."""
    import time as _time

    from deequ_tpu.data.table import Table as TableCls
    from deequ_tpu.service import DQService

    rng = np.random.default_rng(41_000 + seed)
    data_dir = tmp_path / "dataset"
    data_dir.mkdir()
    for i in range(3):
        _write_partition(random_table(rng), str(data_dir / f"part-{i}.parquet"))

    def factory():
        return TableCls.scan_parquet_dataset(str(data_dir))

    # overlapping suites: constraints drawn from one pool, so tenants
    # randomly share analyzers (the union-dedup path) and randomly
    # bring their own (the superset path)
    n_tenants = int(rng.integers(2, 5))
    checks = {
        f"tenant{i}": random_check(rng) for i in range(n_tenants)
    }

    for placement in ("host", "device"):
        monkeypatch.setenv("DEEQU_TPU_PLACEMENT", placement)
        solo = {}
        for tenant, check in checks.items():
            builder = VerificationSuite().on_data(factory()).add_check(check)
            solo[tenant] = suite_snapshot(builder.with_engine("single").run())

        blocker_table = TableCls.from_pydict({"k": ["a"]})
        blocker_check = Check(CheckLevel.ERROR, "blocker").has_size(
            lambda v: (_time.sleep(0.8) or v >= 0)
        )
        with DQService(workers=1) as svc:
            blocker = svc.submit(
                "blocker", "other", lambda: blocker_table,
                checks=[blocker_check],
            )
            _time.sleep(0.25)
            handles = {
                tenant: svc.submit(tenant, "ds", factory, checks=[check])
                for tenant, check in checks.items()
            }
            assert blocker.wait(120)
            for tenant, handle in handles.items():
                assert handle.wait(120), (placement, tenant)
                assert handle.status == "done", (
                    placement, tenant, handle.reason, handle.error,
                )
                assert suite_snapshot(handle.result) == solo[tenant], (
                    placement, tenant,
                )
                if handle.sharing is not None and handle.sharing["shared"]:
                    assert handle.sharing["proof"]["verdict"] == "CONTAINED"
                    assert all(
                        v == 0 for v in handle.sharing["drift"].values()
                    ), (placement, tenant, handle.sharing["drift"])
            shared_n = sum(
                1
                for h in handles.values()
                if h.sharing is not None and h.sharing["shared"]
            )
            assert shared_n >= 2, f"group never formed on {placement}"


# -- windowed state algebra: window query vs full rescan (ISSUE 18) -----------


def _context_bits(context) -> dict:
    """Bit-exact snapshot of an AnalyzerContext's metric map: floats
    compare by their f64 bit pattern (NaN payloads and -0.0 included),
    everything else by value."""
    import struct as _struct

    snap = {}
    for analyzer, metric in context.metric_map.items():
        v = (
            metric.value.get()
            if metric.value.is_success
            else type(metric.value.exception).__name__
        )
        if isinstance(v, float):
            v = _struct.pack(">d", v)
        snap[repr(analyzer)] = v
    return snap


@pytest.mark.parametrize("seed", range(6))
def test_window_query_vs_full_rescan_bit_identical(seed, monkeypatch, tmp_path):
    """A window query answered from the segment-merge tree must be
    BIT-identical to scanning exactly the window's member partitions —
    across random specs (tumbling/sliding/last-N), sparse calendars,
    cold and warm repositories, a late-arriving partition, and a
    re-stated (rewritten) partition. The merge is the engine's own
    sequential name-order fold, so equality here is exact snapshot
    equality, sketches included."""
    import datetime as _dt

    from deequ_tpu.analyzers import (
        ApproxCountDistinct,
        ApproxQuantile,
        Completeness,
        Maximum,
        Mean,
        Minimum,
        Size,
        StandardDeviation,
    )
    from deequ_tpu.data.table import Table as TableCls
    from deequ_tpu.repository.states import FileSystemStateRepository
    from deequ_tpu.runners.analysis_runner import AnalysisRunner
    from deequ_tpu.windows import LastN, Sliding, Tumbling, WindowQuery

    rng = np.random.default_rng(18_000 + seed)
    monkeypatch.setenv(
        "DEEQU_TPU_PLACEMENT", str(rng.choice(["host", "device"]))
    )
    monkeypatch.delenv("DEEQU_TPU_STATE_CACHE", raising=False)

    day0 = _dt.date(2026, 3, 1)
    n_parts = int(rng.integers(8, 17))
    if rng.random() < 0.5:  # sparse calendar: gaps inside the cover
        days = sorted(
            int(d)
            for d in rng.choice(n_parts * 2, size=n_parts, replace=False)
        )
    else:
        days = list(range(n_parts))

    data_dir = tmp_path / "dataset"
    data_dir.mkdir()

    def day_path(d: int) -> str:
        name = f"part-{(day0 + _dt.timedelta(days=d)).isoformat()}.parquet"
        return str(data_dir / name)

    for d in days:
        _write_partition(random_table(rng), day_path(d))

    analyzers = [
        Size(),
        Completeness("x"),
        Mean("x"),
        StandardDeviation("x"),
        Minimum("x"),
        Maximum("y"),
        ApproxCountDistinct("g"),
        ApproxQuantile("x", 0.5),
    ]
    span = int(rng.integers(2, 9))
    spec = [
        Tumbling(span),
        Sliding(span),
        LastN(span, unit=str(rng.choice(["days", "partitions"]))),
    ][int(rng.integers(0, 3))]
    repo = FileSystemStateRepository(str(tmp_path / "cache"))

    def check_step(step):
        source = TableCls.scan_parquet_dataset(str(data_dir))
        query = WindowQuery(
            source, analyzers, repository=repo, dataset="fuzz"
        )
        frame = spec.resolve(query.timeline())
        if not frame.indices:
            return
        window_ctx = query.run(frame)
        parts = source.partitions()
        rescan_ctx = AnalysisRunner.do_analysis_run(
            source.subset([parts[i].path for i in frame.indices]), analyzers
        )
        assert _context_bits(window_ctx) == _context_bits(rescan_ctx), (
            step,
            seed,
            repr(spec),
        )

    check_step("cold")  # rescan-fill + segment publish
    check_step("warm")  # pure segment merges

    late = max(days) + int(rng.integers(1, 4))
    _write_partition(random_table(rng), day_path(late))
    days.append(late)
    check_step("late")  # late arrival invalidates only covering spans

    _write_partition(
        random_table(rng), day_path(days[int(rng.integers(0, len(days)))])
    )
    check_step("restate")  # rewritten fingerprint self-invalidates
    check_step("warm2")  # the rebuilt covers serve the repeat

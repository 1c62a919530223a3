"""Benchmark harness: ColumnProfiler throughput on one chip.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "rows/s", "vs_baseline": N}

Workload (BASELINE.md bottom row / BASELINE.json configs): a full
ColumnProfiler run — the reference's 3-pass profile
(reference: profiles/ColumnProfiler.scala:81-188) — over a wide mixed
table (numeric, boolean, low-cardinality string, numeric-string columns),
the shape of the TPC-H-style profiling workloads the reference targets.

Baseline: Spark local-mode deequ profiling throughput. Spark is not in
this image, so the number is a documented proxy (see BENCH.md): 2.0M
rows/s for a full profile of a ~6-column mixed table on a modern
multi-core host — deliberately generous to Spark. vs_baseline is
our rows/s divided by that proxy; the build target is >=10.

Knobs (env):
    BENCH_ROWS      rows to profile           (default 10_000_000)
    BENCH_MODE      "profiler" | "scan" | "stream" | "wide" | "lineitem"
                    | "pushdown" | "decode" (default "profiler")
                    stream = full profile over an on-disk Parquet file via
                    Table.scan_parquet (out-of-core; constant host memory)
                    wide = the BASELINE.json 50-column north-star shape;
                    lineitem = 16-column TPC-H lineitem-like (both use a
                    best-of-3 measured SAME-SHAPE pandas denominator)
                    pushdown = row-group pruning A/B (BENCH_PUSHDOWN.json
                    methodology, BENCH.md round 8): the same where-heavy
                    fused scan over a sorted-key Parquet file with
                    DEEQU_TPU_PUSHDOWN=0 then =1, page cache dropped
                    before each timed pass; skipped-group counts come
                    from a traced pass. Refreshes BENCH_PUSHDOWN.json
                    decode = buffer-level decode fast path A/B
                    (BENCH_DECODE.json, BENCH.md round 9): a decode-bound
                    fused scan over the 50-column wide stream shape with
                    DEEQU_TPU_DECODE_FASTPATH=0 then =1, page cache
                    dropped before each timed pass; decode self-seconds
                    come from traced warm passes. Refreshes
                    BENCH_DECODE.json
                    incremental = persistent partition-state cache A/B
                    (BENCH_INCREMENTAL.json, BENCH.md round 11): cold
                    full scan fills the repository, ONE partition is
                    appended, then a cache-off full rescan races the
                    warm incremental pass that loads every unchanged
                    partition's states and scans only the new file;
                    aborts unless metrics are bit-identical and exactly
                    one partition scanned. BENCH_INCR_PARTS sets the
                    partition count (default 12, min 10)
                    window = windowed state algebra A/B
                    (BENCH_WINDOW.json, BENCH.md round 18): a
                    30-partition daily dataset is cold-filled, then a
                    warm 7-day sliding window query PLUS a week-over-week
                    drift check (all segment merges, zero data rows)
                    races cache-off full rescans of the same
                    current+prior week partitions; a traced proof pass
                    pins partitions_scanned == 0 and every cover span a
                    segment hit, and any metric mismatch aborts.
                    BENCH_WINDOW_PARTS sets the day count (default 30)
                    reader = native parquet page->wire reader A/B
                    (BENCH_READER.json, BENCH.md round 12): the decode
                    bench's 50-column wide-stream scan under a 50 ms
                    per-row-group source stall (DEEQU_TPU_SOURCE_STALL_MS)
                    with DEEQU_TPU_NATIVE_READER=0 then =1, page cache
                    dropped before each timed pass; decode-stage busy
                    seconds come from traced warm passes. Refreshes
                    BENCH_READER.json
                    forensics = failure-forensics capture A/B
                    (BENCH_FORENSICS.json, ISSUE 12): the same
                    50-column wide-stream verification run with
                    .with_forensics() off then on — a completeness
                    constraint failing on every column-null (~3% of
                    rows) makes the capture side churn its reservoirs
                    on every batch, the worst case. Aborts unless
                    check statuses and metrics are bit-identical;
                    reports best-of-reps wall per side and the
                    enabled-side overhead pct
    BENCH_TIMED     timed repetitions, best-of (default 5: shared-vCPU
                     boxes show 20-30% run-to-run noise; best-of-5 reads
                     the machine's actual capability. Compile happens
                     during the warmup run)
    BENCH_PARQUET   path for the stream-mode file (default /tmp/bench.parquet;
                     reused if it already has BENCH_ROWS rows)
    BENCH_SHAPES    "0" skips the shape regression loop (default on: a
                     profiler-mode run also times the wide @4M and
                     lineitem @10M shapes in this process and writes
                     BENCH_WIDE.json / BENCH_LINEITEM.json)
    BENCH_COLD      "1" + mode=stream: ONE cold pass (no warmup, no reps)
                    timed end-to-end incl. jit compile — the methodology
                    behind BENCH_STREAM_100M/1B.json; adds rows/elapsed_s/
                    peak_rss_mb fields to the JSON line
    BENCH_STREAM_SHAPE  "default" (6-col) | "wide" (50-col stream shape,
                    build_wide_stream_table): the table the stream-mode
                    parquet file holds. wide defaults BENCH_PARQUET to
                    /tmp/bench_wide.parquet and measures a same-shape
                    pandas denominator (BENCH_STREAM_1B_WIDE.json)
    BENCH_PIPELINE_AB  "1" + mode=stream + BENCH_COLD=1: run the cold
                    pass TWICE — DEEQU_TPU_PIPELINE=0 (fully serial:
                    synchronous decode, inline prep) then =1 (staged
                    pipeline) — dropping the OS page cache before each
                    (best-effort, needs root) so both pay real disk IO.
                    A traced pipelined warm-up pass runs first (jit +
                    imports + the occupancy rows), then both timed
                    passes run warm-jit/cold-IO and UNTRACED (equal
                    footing). The JSON gains a pipeline_ab
                    field: serial_s, pipelined_s, speedup, occupancy
                    (bottleneck first). Headline value = PIPELINED pass
    BENCH_SOURCE_STALL_MS  with BENCH_PIPELINE_AB: inject this many ms
                    of source wait per row-group read into BOTH sides
                    (DEEQU_TPU_SOURCE_STALL_MS; object-store latency
                    model) — measures how much source wait the pipeline
                    hides when local disk+readahead are too fast for
                    decode/IO overlap to show
    BENCH_TRACE     "1" (or the --trace flag): after the timed reps, run
                     ONE extra traced pass (deequ_tpu.observe) — adds
                     trace_file plus a trace_phases_s breakdown
                     (plan/dispatch/transfer/merge self-time seconds) to
                     the JSON record. The Chrome trace itself lands at
                     DEEQU_TPU_TRACE_OUT or a tempdir default; load it
                     in https://ui.perfetto.dev.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from deequ_tpu.testing.tpch import build_lineitem_table

# Spark local-mode full-profile proxy, rows/s (justification: BENCH.md).
# Used as a FLOOR under the measured single-core pandas/numpy reference
# implementation (measure_reference_profile_rows_per_sec): the
# denominator is max(measured, proxy), i.e. always at least as generous
# to Spark as the documented proxy.
SPARK_LOCAL_PROFILE_ROWS_PER_SEC = 2.0e6
# Spark local-mode fused scalar-scan proxy, rows/s (BENCH.md)
SPARK_LOCAL_SCAN_ROWS_PER_SEC = 10.0e6

CATEGORIES = np.array(
    ["auto", "beauty", "books", "garden", "grocery", "home", "music",
     "office", "outdoors", "pets", "sports", "tools", "toys", "video"],
    dtype=object,
)


def build_table(n_rows: int, seed: int = 0):
    """Wide mixed table: 3 numeric, 1 bool, 2 string (low + mid card)."""
    from deequ_tpu.data.table import Table

    rng = np.random.default_rng(seed)
    price = rng.lognormal(3.0, 1.0, n_rows)
    price[rng.random(n_rows) < 0.02] = np.nan  # 2% nulls
    qty = rng.integers(1, 100, n_rows)
    discount = rng.random(n_rows)
    flag = rng.random(n_rows) < 0.5
    category = CATEGORIES[rng.integers(0, len(CATEGORIES), n_rows)]
    # numeric-looking string column (profiler infers Integral, casts, and
    # runs the numeric pass on it — the reference's pass-2 cast path)
    code_dict = np.array([str(v) for v in rng.integers(0, 100_000, 4096)],
                        dtype=object)
    code = code_dict[rng.integers(0, len(code_dict), n_rows)]
    return Table.from_numpy(
        {"price": price, "qty": qty, "discount": discount,
         "flag": flag, "category": category, "code": code}
    )


def build_wide_table(n_rows: int, seed: int = 0):
    """BASELINE.json north-star shape: a 50-column mixed table (the 1B
    config at reduced rows). 20 float64 (2 with nulls), 10 int64 (6
    low-range, 4 wide), 5 bool, 10 low-cardinality string, 5
    numeric-string — the mix exercises every profiler path at width
    (per-column Python dispatch is the thing this measures)."""
    from deequ_tpu.data.table import Table

    rng = np.random.default_rng(seed)
    data = {}
    for i in range(20):
        col = (
            rng.lognormal(2.0, 1.0, n_rows)
            if i % 2
            else rng.random(n_rows) * (i + 1)
        )
        if i < 2:
            col[rng.random(n_rows) < 0.03] = np.nan
        data[f"f{i:02d}"] = col
    for i in range(10):
        if i < 6:
            data[f"i{i:02d}"] = rng.integers(0, 100 * (i + 1), n_rows)
        else:
            data[f"i{i:02d}"] = rng.integers(0, 10**9, n_rows)
    for i in range(5):
        data[f"b{i}"] = rng.random(n_rows) < (0.2 + 0.15 * i)
    for i in range(10):
        pool = CATEGORIES[: 3 + i]
        data[f"s{i:02d}"] = pool[rng.integers(0, len(pool), n_rows)]
    for i in range(5):
        pool = np.array(
            [str(v) for v in rng.integers(0, 2000 * (i + 1), 4096)],
            dtype=object,
        )
        data[f"c{i}"] = pool[rng.integers(0, len(pool), n_rows)]
    return Table.from_numpy(data)


def build_wide_stream_table(n_rows: int, seed: int = 0):
    """The 50-column wide shape for the OUT-OF-CORE stream bench: same
    column mix as build_wide_table (floats / ints / bools / low-card
    strings / numeric-strings) but with parquet-compact value
    distributions — quantized decimals (integers/100, the TPC-H money
    shape) and windowed ints, which dictionary-encode to ~1-2 bytes per
    value. The in-memory wide shape's 20 continuous f64 columns alone
    would make a 1B-row file ~160GB (incompressible entropy), which
    does not fit this box; one column (f00) stays continuous lognormal
    with nulls so the select-kernel family path rides the stream too."""
    from deequ_tpu.data.table import Table

    rng = np.random.default_rng(seed)
    data = {}
    f00 = rng.lognormal(2.0, 1.0, n_rows)
    f00[rng.random(n_rows) < 0.03] = np.nan
    data["f00"] = f00
    for i in range(1, 20):
        r = (200, 1_000, 2_000, 10_000)[i % 4]
        data[f"f{i:02d}"] = rng.integers(0, r, n_rows) / 100.0
    for i in range(10):
        if i < 6:
            data[f"i{i:02d}"] = rng.integers(0, 100 * (i + 1), n_rows)
        else:
            data[f"i{i:02d}"] = rng.integers(0, 50_000, n_rows)
    for i in range(5):
        data[f"b{i}"] = rng.random(n_rows) < (0.2 + 0.15 * i)
    for i in range(10):
        pool = CATEGORIES[: 3 + i]
        data[f"s{i:02d}"] = pool[rng.integers(0, len(pool), n_rows)]
    for i in range(5):
        pool = np.array(
            [str(v) for v in rng.integers(0, 2000 * (i + 1), 4096)],
            dtype=object,
        )
        data[f"c{i}"] = pool[rng.integers(0, len(pool), n_rows)]
    return Table.from_numpy(data)


def run_profiler(table):
    from deequ_tpu.profiles.column_profiler import ColumnProfiler

    return ColumnProfiler.profile(table)


def scan_analyzers():
    """The BASELINE.json config-2 analyzer plan, exposed so `make
    analyze` (tools/explain_bench.py) can EXPLAIN the exact plan the
    benchmark executes."""
    from deequ_tpu.analyzers import (
        ApproxCountDistinct,
        Completeness,
        Maximum,
        Mean,
        Minimum,
        Size,
        StandardDeviation,
        Sum,
    )

    return [
        Size(),
        Completeness("price"),
        Mean("price"),
        Minimum("price"),
        Maximum("price"),
        Sum("price"),
        StandardDeviation("price"),
        ApproxCountDistinct("qty"),
        Mean("discount"),
        StandardDeviation("discount"),
    ]


def run_scan(table):
    """BASELINE.json config 2: fused scalar scan (Mean/StdDev/Min/Max +
    friends) on numeric columns — one pass."""
    from deequ_tpu.ops.fused import FusedScanPass

    results = FusedScanPass(scan_analyzers()).run(table)
    for r in results:
        r.state_or_raise()
    return results


PUSHDOWN_SELECTIVITY = 0.1  # fraction of the key range the where keeps


def pushdown_where(n_rows: int) -> str:
    """The selective filter every pushdown-mode member carries: k is
    globally sorted on disk, so row-group min/max windows prove ~90% of
    the groups all-false before any Arrow decode."""
    return f"k < {int(n_rows * PUSHDOWN_SELECTIVITY)}"


def pushdown_analyzers(n_rows: int):
    """The where-heavy plan for BENCH_MODE=pushdown (BENCH.md round 8):
    every member carries the SAME selective predicate — the row-group
    pruner only skips a group when every fused member filters it, so a
    single unfiltered member would silently disable the A/B."""
    from deequ_tpu.analyzers import (
        Completeness,
        Compliance,
        Maximum,
        Mean,
        Minimum,
        Size,
        StandardDeviation,
        Sum,
    )

    w = pushdown_where(n_rows)
    return [
        Size(where=w),
        Completeness("v", where=w),
        Mean("v", where=w),
        Minimum("v", where=w),
        Maximum("v", where=w),
        Sum("v", where=w),
        StandardDeviation("v", where=w),
        Compliance("v above -200", "v >= -200", where=w),
    ]


def write_pushdown_parquet(
    n_rows: int,
    path: str,
    chunk: int = 2_000_000,
    row_group_size: int = 250_000,
) -> None:
    """Sorted-key Parquet for the pushdown A/B: k is globally sorted so
    row-group min/max are disjoint windows (maximally prunable); v
    carries 2% NaN so the DOUBLE null-bound soundness rules run on the
    hot path; s is a low-cardinality string column the stats never
    judge."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    writer = None
    done = 0
    while done < n_rows:
        rows = min(chunk, n_rows - done)
        rng = np.random.default_rng(done)
        v = rng.normal(0.0, 50.0, rows)
        v[rng.random(rows) < 0.02] = np.nan
        at = pa.table(
            {
                "k": np.arange(done, done + rows, dtype=np.int64),
                "v": v,
                "s": pa.array(
                    CATEGORIES[rng.integers(0, len(CATEGORIES), rows)],
                    type=pa.string(),
                ),
            }
        )
        if writer is None:
            writer = pq.ParquetWriter(path, at.schema)
        writer.write_table(at, row_group_size=row_group_size)
        done += rows
    if writer is not None:
        writer.close()


def run_pushdown_bench(n_rows: int) -> None:
    """BENCH_MODE=pushdown: A/B the static row-group pruner
    (deequ_tpu.lint.pushdown) on a where-heavy fused scan over a
    sorted-key Parquet file. Same discipline as the pipeline A/B: a
    traced warm-up pass first (jit + imports; its prune spans carry the
    observed skipped-group counts), one traced pass per side for decode
    self-seconds (tracing is a thumb on the scale, so traced passes are
    never the timed ones), then two warm-jit cold-IO UNTRACED timed
    passes with DEEQU_TPU_PUSHDOWN=0 / =1, the page cache dropped
    before each. The run aborts if the two sides' metrics differ — a
    speedup that changes a result is worthless. Refreshes
    BENCH_PUSHDOWN.json next to this file (round/config preserved)."""
    import pyarrow.parquet as pq

    from deequ_tpu import observe
    from deequ_tpu.data.table import Table
    from deequ_tpu.ops.fused import FusedScanPass

    path = os.environ.get("BENCH_PARQUET", "/tmp/bench_pushdown.parquet")
    t_gen = time.perf_counter()
    if not (
        os.path.exists(path) and pq.ParquetFile(path).metadata.num_rows == n_rows
    ):
        write_pushdown_parquet(n_rows, path)
    gen_s = time.perf_counter() - t_gen

    analyzers = pushdown_analyzers(n_rows)

    def run_once():
        table = Table.scan_parquet(path)
        snapshot = {}
        for r in FusedScanPass(analyzers).run(table):
            value = r.analyzer.compute_metric_from(r.state_or_raise()).value
            v = (
                value.get()
                if value.is_success
                else type(value.exception).__name__
            )
            if isinstance(v, float) and v != v:
                v = "nan"  # nan != nan would defeat the A/B comparison
            snapshot[repr(r.analyzer)] = v
        return snapshot

    # warm-up FIRST (traced, pushdown ON): compiles every program, pays
    # the one-time imports, and its prune spans carry the observed
    # skipped-group counts
    os.environ["DEEQU_TPU_PUSHDOWN"] = "1"
    with observe.tracing() as tracer_warm:
        warm_snapshot = run_once()
    prune = {
        "groups_total": 0,
        "groups_skipped": 0,
        "rows_skipped": 0,
        "wheres_elided": 0,
    }

    def visit(span):
        if span.name == "prune":
            for key in prune:
                prune[key] += int(span.attrs.get(key, 0))
        for child in span.children:
            visit(child)

    for root in tracer_warm.roots:
        visit(root)

    # decode self-seconds per side from one traced pass each. The
    # warm-up above is NOT used for this: it pays cold imports and
    # file-cache misses, which would inflate the on side's decode time.
    # Both of these traced passes run warm (jit and page cache), so the
    # decode delta isolates the decode work pruning removed.
    os.environ["DEEQU_TPU_PUSHDOWN"] = "0"
    with observe.tracing() as tracer_off:
        run_once()
    os.environ["DEEQU_TPU_PUSHDOWN"] = "1"
    with observe.tracing() as tracer_on:
        run_once()

    def decode_busy_s(roots) -> float:
        return next(
            (
                row["busy_s"]
                for row in observe.pipeline_occupancy(roots)
                if row["stage"] == "decode"
            ),
            0.0,
        )

    os.environ["DEEQU_TPU_PUSHDOWN"] = "0"
    cache_dropped = _drop_page_cache()
    t0 = time.perf_counter()
    off_snapshot = run_once()
    off_s = time.perf_counter() - t0

    os.environ["DEEQU_TPU_PUSHDOWN"] = "1"
    _drop_page_cache()
    t0 = time.perf_counter()
    on_snapshot = run_once()
    on_s = time.perf_counter() - t0

    if off_snapshot != on_snapshot or warm_snapshot != on_snapshot:
        raise SystemExit(
            "pushdown A/B: metric mismatch between the pruned and "
            f"unpruned sides\noff: {off_snapshot}\non:  {on_snapshot}"
        )

    rec = {
        "metric": "pushdown_rows_per_sec_per_chip",
        "value": round(n_rows / on_s, 1),
        "unit": "rows/s",
        "rows": n_rows,
        "where": pushdown_where(n_rows),
        "pushdown_ab": {
            "off_s": round(off_s, 2),
            "on_s": round(on_s, 2),
            "speedup_pct": round(100.0 * (off_s - on_s) / off_s, 1),
            "decode_s_off": round(decode_busy_s(tracer_off.roots), 2),
            "decode_s_on": round(decode_busy_s(tracer_on.roots), 2),
            "rg_total": prune["groups_total"],
            "rg_skipped": prune["groups_skipped"],
            "rows_skipped": prune["rows_skipped"],
            "wheres_elided": prune["wheres_elided"],
            "bit_identical": True,
            "page_cache_dropped": cache_dropped,
            "passes": (
                "traced warm-up (on) for prune counts + one traced pass "
                "per side for decode self-seconds; both timed passes are "
                "warm-jit, cold-IO, untraced"
            ),
        },
    }
    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.path.join(here, "BENCH_PUSHDOWN.json")
    try:
        with open(out_path) as fh:
            old = json.load(fh)
        for key in ("round", "config"):
            if key in old and key not in rec:
                rec[key] = old[key]
    except Exception:  # noqa: BLE001 - first write: no fields to carry
        pass
    with open(out_path, "w") as fh:
        json.dump(rec, fh)
        fh.write("\n")
    print(
        f"# bench: pushdown A/B off={off_s:.2f}s on={on_s:.2f}s "
        f"(+{100.0 * (off_s - on_s) / off_s:.1f}%), "
        f"rg {prune['groups_skipped']}/{prune['groups_total']} skipped, "
        f"decode {rec['pushdown_ab']['decode_s_off']:.2f}s -> "
        f"{rec['pushdown_ab']['decode_s_on']:.2f}s; gen={gen_s:.1f}s",
        file=sys.stderr,
    )
    print(json.dumps(rec))


def write_decode_parquet(
    n_rows: int,
    path: str,
    chunk: int = 2_000_000,
    null_frac: float = 0.03,
    row_group_size: int = 0,
) -> None:
    """The decode-wall shape: the 50-column wide stream mix with ~3%
    nulls in EVERY column — the reason a data-quality engine scans a
    table at all. Null-free columns decode near-zero-copy on the host
    chain already; it is the validity handling (fill_null allocation +
    mask extraction + NaN fold, one pass each) that builds the decode
    wall the fast path collapses into a single buffer-level pass."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    writer = None
    done = 0
    seed = 0
    while done < n_rows:
        rows = min(chunk, n_rows - done)
        rng = np.random.default_rng(seed)

        def nullify(values):
            return pa.array(values, mask=rng.random(rows) < null_frac)

        data = {}
        f00 = rng.lognormal(2.0, 1.0, rows)
        f00[rng.random(rows) < 0.03] = np.nan  # NaN rides beside nulls
        data["f00"] = nullify(f00)
        for i in range(1, 20):
            r = (200, 1_000, 2_000, 10_000)[i % 4]
            data[f"f{i:02d}"] = nullify(rng.integers(0, r, rows) / 100.0)
        for i in range(10):
            hi = 100 * (i + 1) if i < 6 else 50_000
            data[f"i{i:02d}"] = nullify(rng.integers(0, hi, rows))
        for i in range(5):
            data[f"b{i}"] = nullify(rng.random(rows) < (0.2 + 0.15 * i))
        for i in range(10):
            pool = CATEGORIES[: 3 + i]
            data[f"s{i:02d}"] = nullify(pool[rng.integers(0, len(pool), rows)])
        for i in range(5):
            pool = np.array(
                [str(v) for v in rng.integers(0, 2000 * (i + 1), 4096)],
                dtype=object,
            )
            data[f"c{i}"] = nullify(pool[rng.integers(0, len(pool), rows)])
        at = pa.table(data)
        if writer is None:
            writer = pq.ParquetWriter(path, at.schema)
        writer.write_table(at, row_group_size=row_group_size or None)
        done += rows
        seed += 1
    if writer is not None:
        writer.close()


def decode_analyzers():
    """The decode-bound plan for BENCH_MODE=decode: Completeness over
    every one of the 50 wide-stream columns plus Mean over the numerics.
    Every consumer here is packed-wire-safe, so the planner proves the
    whole schema (floats, ints, bools, dictionary strings) onto the
    native buffer-level fast path; nothing filters rows, so the scan is
    pure decode + fold and the A/B isolates the decode wall."""
    from deequ_tpu.analyzers import Completeness, Mean

    names = (
        [f"f{i:02d}" for i in range(20)]
        + [f"i{i:02d}" for i in range(10)]
        + [f"b{i}" for i in range(5)]
        + [f"s{i:02d}" for i in range(10)]
        + [f"c{i}" for i in range(5)]
    )
    out = [Completeness(c) for c in names]
    out += [Mean(f"f{i:02d}") for i in range(20)]
    out += [Mean(f"i{i:02d}") for i in range(10)]
    return out


def _decode_stage_busy_s(roots) -> float:
    """Whole decode-stage busy seconds (parquet read + decompression +
    Arrow->Table) from the prefetch producer's pipe_item spans —
    context for the A/B, not its headline metric."""
    from deequ_tpu import observe

    return next(
        (
            row["busy_s"]
            for row in observe.pipeline_occupancy(roots)
            if row["stage"] == "decode"
        ),
        0.0,
    )


def _arrow_decode_self_s(roots) -> float:
    """Decode self-seconds from a traced pass: the sum of the
    `arrow_decode` spans (data/source.py), which wrap exactly the
    Arrow-buffer -> wire conversion the fast path replaces — parquet
    read/decompression stays outside them on both sides."""
    total = 0.0

    def visit(span):
        nonlocal total
        if span.name == "arrow_decode":
            total += span.duration_s
        for child in span.children:
            visit(child)

    for root in roots:
        visit(root)
    return total


def run_decode_bench(n_rows: int) -> None:
    """BENCH_MODE=decode: A/B the buffer-level native decode fast path
    (deequ_tpu.data.arrow_decode) and the row-group decode worker pool
    on a decode-bound fused scan over the 50-column wide stream shape.
    Same discipline as the pushdown A/B: a traced warm-up pass first
    (jit + imports; its decode_fastpath spans carry the planner's
    per-column verdicts), then one traced WARM pass per side for decode
    self-seconds (tracing is a thumb on the scale, so traced passes are
    never the timed ones), one traced pass at the default worker count,
    and finally two warm-jit cold-IO UNTRACED timed passes with
    DEEQU_TPU_DECODE_FASTPATH=0 / =1 at workers=1, the page cache
    dropped before each. The run aborts if any side's metrics differ —
    a decode speedup that changes a result is worthless. Refreshes
    BENCH_DECODE.json next to this file (round/config preserved)."""
    import pyarrow.parquet as pq

    from deequ_tpu import observe
    from deequ_tpu.data.table import Table
    from deequ_tpu.ops.fused import FusedScanPass

    path = os.environ.get("BENCH_PARQUET", "/tmp/bench_decode.parquet")
    t_gen = time.perf_counter()
    if not (
        os.path.exists(path) and pq.ParquetFile(path).metadata.num_rows == n_rows
    ):
        write_decode_parquet(n_rows, path)
    gen_s = time.perf_counter() - t_gen

    analyzers = decode_analyzers()

    def run_once():
        snapshot = {}
        for r in FusedScanPass(analyzers).run(
            Table.scan_parquet(path, batch_rows=1 << 20)
        ):
            value = r.analyzer.compute_metric_from(r.state_or_raise()).value
            v = (
                value.get()
                if value.is_success
                else type(value.exception).__name__
            )
            if isinstance(v, float) and v != v:
                v = "nan"  # nan != nan would defeat the A/B comparison
            snapshot[repr(r.analyzer)] = v
        return snapshot

    workers_n = min(os.cpu_count() or 1, 4)
    os.environ["DEEQU_TPU_DECODE_WORKERS"] = "1"

    # warm-up FIRST (traced, fast path ON): compiles every program, pays
    # the one-time imports, and its decode_fastpath spans carry the
    # planner's per-column verdicts
    os.environ["DEEQU_TPU_DECODE_FASTPATH"] = "1"
    with observe.tracing() as tracer_warm:
        warm_snapshot = run_once()
    plan = {"cols_total": 0, "cols_fast": 0, "cols_fallback": 0}

    def visit(span):
        if span.name == "decode_fastpath":
            for key in plan:
                plan[key] = max(plan[key], int(span.attrs.get(key, 0)))
        for child in span.children:
            visit(child)

    for root in tracer_warm.roots:
        visit(root)

    # decode self-seconds per side from one traced pass each. The
    # warm-up above is NOT used for this: it pays cold imports and
    # file-cache misses, which would inflate the on side's decode time.
    # Both of these traced passes run warm (jit and page cache), so the
    # decode delta isolates the work the fast path removed.
    os.environ["DEEQU_TPU_DECODE_FASTPATH"] = "0"
    with observe.tracing() as tracer_off:
        run_once()
    os.environ["DEEQU_TPU_DECODE_FASTPATH"] = "1"
    with observe.tracing() as tracer_on:
        run_once()
    decode_s_off = _arrow_decode_self_s(tracer_off.roots)
    decode_s_on = _arrow_decode_self_s(tracer_on.roots)
    stage_s_off = _decode_stage_busy_s(tracer_off.roots)
    stage_s_on = _decode_stage_busy_s(tracer_on.roots)

    # the worker pool on top of the fast path (traced, warm): on a
    # single-core box the default collapses to 1 and this re-measures
    # the on side; on multi-core it shows the pool's overlap
    os.environ["DEEQU_TPU_DECODE_WORKERS"] = str(workers_n)
    with observe.tracing() as tracer_pool:
        pool_snapshot = run_once()
    decode_s_pool = _arrow_decode_self_s(tracer_pool.roots)
    os.environ["DEEQU_TPU_DECODE_WORKERS"] = "1"

    os.environ["DEEQU_TPU_DECODE_FASTPATH"] = "0"
    cache_dropped = _drop_page_cache()
    t0 = time.perf_counter()
    off_snapshot = run_once()
    off_s = time.perf_counter() - t0

    os.environ["DEEQU_TPU_DECODE_FASTPATH"] = "1"
    _drop_page_cache()
    t0 = time.perf_counter()
    on_snapshot = run_once()
    on_s = time.perf_counter() - t0

    if not (warm_snapshot == off_snapshot == on_snapshot == pool_snapshot):
        raise SystemExit(
            "decode A/B: metric mismatch between the fast-path and "
            f"host-chain sides\noff: {off_snapshot}\non:  {on_snapshot}"
        )

    reduction = (
        100.0 * (decode_s_off - decode_s_on) / decode_s_off
        if decode_s_off > 0
        else 0.0
    )
    rec = {
        "metric": "decode_rows_per_sec_per_chip",
        "value": round(n_rows / on_s, 1),
        "unit": "rows/s",
        "rows": n_rows,
        "columns": plan["cols_total"],
        "decode_ab": {
            "off_s": round(off_s, 2),
            "on_s": round(on_s, 2),
            "speedup_pct": round(100.0 * (off_s - on_s) / off_s, 1),
            "decode_s_off": round(decode_s_off, 2),
            "decode_s_on": round(decode_s_on, 2),
            "decode_reduction_pct": round(reduction, 1),
            "decode_stage_s_off": round(stage_s_off, 2),
            "decode_stage_s_on": round(stage_s_on, 2),
            "decode_s_workers_n": round(decode_s_pool, 2),
            "workers_n": workers_n,
            "cols_fast": plan["cols_fast"],
            "cols_total": plan["cols_total"],
            "bit_identical": True,
            "page_cache_dropped": cache_dropped,
            "passes": (
                "traced warm-up (on) for planner verdicts + one traced "
                "warm pass per side for decode self-seconds + one traced "
                "pass at the default worker count; both timed passes "
                "are warm-jit, cold-IO, untraced, workers=1"
            ),
        },
    }
    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.path.join(here, "BENCH_DECODE.json")
    try:
        with open(out_path) as fh:
            old = json.load(fh)
        for key in ("round", "config"):
            if key in old and key not in rec:
                rec[key] = old[key]
    except Exception:  # noqa: BLE001 - first write: no fields to carry
        pass
    with open(out_path, "w") as fh:
        json.dump(rec, fh)
        fh.write("\n")
    print(
        f"# bench: decode A/B off={off_s:.2f}s on={on_s:.2f}s "
        f"(+{100.0 * (off_s - on_s) / off_s:.1f}%), decode self "
        f"{decode_s_off:.2f}s -> {decode_s_on:.2f}s (-{reduction:.1f}%), "
        f"{plan['cols_fast']}/{plan['cols_total']} cols fast; "
        f"gen={gen_s:.1f}s",
        file=sys.stderr,
    )
    print(json.dumps(rec))


def _dispatch_self_s(roots) -> float:
    """Prep self-seconds from a traced pass: the sum of the `dispatch`
    spans (ops/fused.py), which wrap exactly the host wire pack
    (`pack_batch_inputs`) + H2D put that decode-to-wire fusion moves
    into the decode workers — device compute stays async outside."""
    total = 0.0

    def visit(span):
        nonlocal total
        if span.name == "dispatch":
            total += span.duration_s
        for child in span.children:
            visit(child)

    for root in roots:
        visit(root)
    return total


def _occupancy_rows(roots):
    """Stage occupancy rows for the BENCH.md re-baseline table."""
    from deequ_tpu import observe

    return [
        {
            "stage": row["stage"],
            "busy_s": round(float(row["busy_s"]), 2),
            "occupancy": round(float(row["occupancy"]), 3),
        }
        for row in observe.pipeline_occupancy(roots)
    ]


def run_wire_bench(n_rows: int) -> None:
    """BENCH_MODE=wire: A/B decode-to-wire fusion (ISSUE 9) on the same
    50-column wide-stream shape and packed-wire-safe plan as the decode
    bench. DEEQU_TPU_WIRE_FUSED=0 decodes every column to a host Column
    and packs the wire serially in the prep stage; =1 has the decode
    workers emit packed wire slices directly and the prep pack splice
    them in. Same discipline as the decode A/B: a traced warm-up (jit +
    imports + the planner's wire verdict), one traced WARM pass per
    side for decode/prep self-seconds and the stage-occupancy
    re-baseline (traced passes are never the timed ones), then two
    warm-jit cold-IO UNTRACED timed passes. The headline is the
    decode+prep COMBINED self-time — fusion moves pack work between the
    stages, so either stage alone would miscount. Aborts on any metric
    mismatch. Refreshes BENCH_WIRE.json (round/config preserved)."""
    import pyarrow.parquet as pq

    from deequ_tpu import observe
    from deequ_tpu.data.table import Table
    from deequ_tpu.ops.fused import FusedScanPass

    path = os.environ.get("BENCH_PARQUET", "/tmp/bench_decode.parquet")
    t_gen = time.perf_counter()
    if not (
        os.path.exists(path) and pq.ParquetFile(path).metadata.num_rows == n_rows
    ):
        write_decode_parquet(n_rows, path)
    gen_s = time.perf_counter() - t_gen

    analyzers = decode_analyzers()
    # the wire verdict needs packed-only consumers, i.e. device members
    os.environ["DEEQU_TPU_PLACEMENT"] = "device"
    workers_n = min(os.cpu_count() or 1, 4)
    os.environ["DEEQU_TPU_DECODE_WORKERS"] = str(workers_n)

    def run_once():
        snapshot = {}
        for r in FusedScanPass(analyzers).run(
            Table.scan_parquet(path, batch_rows=1 << 20)
        ):
            value = r.analyzer.compute_metric_from(r.state_or_raise()).value
            v = (
                value.get()
                if value.is_success
                else type(value.exception).__name__
            )
            if isinstance(v, float) and v != v:
                v = "nan"  # nan != nan would defeat the A/B comparison
            snapshot[repr(r.analyzer)] = v
        return snapshot

    # warm-up FIRST (traced, fusion ON): compiles every program, pays
    # the one-time imports, and its decode_fastpath span carries the
    # planner's wire verdict
    os.environ["DEEQU_TPU_WIRE_FUSED"] = "1"
    with observe.tracing() as tracer_warm:
        warm_snapshot = run_once()
    plan = {"cols_total": 0, "cols_fast": 0, "cols_wire_fused": 0}

    def visit(span):
        if span.name == "decode_fastpath":
            for key in plan:
                plan[key] = max(plan[key], int(span.attrs.get(key, 0)))
        for child in span.children:
            visit(child)

    for root in tracer_warm.roots:
        visit(root)

    # decode+prep self-seconds per side from one traced WARM pass each
    # (jit and page cache hot, so the delta isolates the moved pack)
    os.environ["DEEQU_TPU_WIRE_FUSED"] = "0"
    with observe.tracing() as tracer_off:
        off_traced_snapshot = run_once()
    os.environ["DEEQU_TPU_WIRE_FUSED"] = "1"
    with observe.tracing() as tracer_on:
        on_traced_snapshot = run_once()
    decode_s_off = _arrow_decode_self_s(tracer_off.roots)
    decode_s_on = _arrow_decode_self_s(tracer_on.roots)
    prep_s_off = _dispatch_self_s(tracer_off.roots)
    prep_s_on = _dispatch_self_s(tracer_on.roots)
    combined_off = decode_s_off + prep_s_off
    combined_on = decode_s_on + prep_s_on
    occupancy_off = _occupancy_rows(tracer_off.roots)
    occupancy_on = _occupancy_rows(tracer_on.roots)

    # warm-jit cold-IO wall times, untraced, page cache dropped
    os.environ["DEEQU_TPU_WIRE_FUSED"] = "0"
    cache_dropped = _drop_page_cache()
    t0 = time.perf_counter()
    off_snapshot = run_once()
    off_s = time.perf_counter() - t0

    os.environ["DEEQU_TPU_WIRE_FUSED"] = "1"
    _drop_page_cache()
    t0 = time.perf_counter()
    on_snapshot = run_once()
    on_s = time.perf_counter() - t0

    if not (
        warm_snapshot == off_traced_snapshot == on_traced_snapshot
        == off_snapshot == on_snapshot
    ):
        raise SystemExit(
            "wire A/B: metric mismatch between the fused and Column "
            f"sides\noff: {off_snapshot}\non:  {on_snapshot}"
        )

    reduction = (
        100.0 * (combined_off - combined_on) / combined_off
        if combined_off > 0
        else 0.0
    )
    rec = {
        "metric": "wire_rows_per_sec_per_chip",
        "value": round(n_rows / on_s, 1),
        "unit": "rows/s",
        "rows": n_rows,
        "columns": plan["cols_total"],
        "wire_ab": {
            "off_s": round(off_s, 2),
            "on_s": round(on_s, 2),
            "speedup_pct": round(100.0 * (off_s - on_s) / off_s, 1),
            "decode_s_off": round(decode_s_off, 2),
            "decode_s_on": round(decode_s_on, 2),
            "prep_s_off": round(prep_s_off, 2),
            "prep_s_on": round(prep_s_on, 2),
            "combined_s_off": round(combined_off, 2),
            "combined_s_on": round(combined_on, 2),
            "combined_reduction_pct": round(reduction, 1),
            "occupancy_off": occupancy_off,
            "occupancy_on": occupancy_on,
            "cols_wire_fused": plan["cols_wire_fused"],
            "cols_fast": plan["cols_fast"],
            "cols_total": plan["cols_total"],
            "workers_n": workers_n,
            "bit_identical": True,
            "page_cache_dropped": cache_dropped,
            "passes": (
                "traced warm-up (on) for the wire verdict + one traced "
                "warm pass per side for decode/prep self-seconds and "
                "stage occupancy; both timed passes are warm-jit, "
                "cold-IO, untraced"
            ),
        },
    }
    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.path.join(here, "BENCH_WIRE.json")
    try:
        with open(out_path) as fh:
            old = json.load(fh)
        for key in ("round", "config"):
            if key in old and key not in rec:
                rec[key] = old[key]
    except Exception:  # noqa: BLE001 - first write: no fields to carry
        pass
    with open(out_path, "w") as fh:
        json.dump(rec, fh)
        fh.write("\n")
    print(
        f"# bench: wire A/B off={off_s:.2f}s on={on_s:.2f}s "
        f"(+{100.0 * (off_s - on_s) / off_s:.1f}%), decode+prep self "
        f"{combined_off:.2f}s -> {combined_on:.2f}s (-{reduction:.1f}%), "
        f"{plan['cols_wire_fused']}/{plan['cols_total']} cols fused; "
        f"gen={gen_s:.1f}s",
        file=sys.stderr,
    )
    print(json.dumps(rec))


def reader_analyzers():
    """The reader-bound plan for BENCH_MODE=reader: Completeness +
    Mean over the 35 numeric/boolean columns of the 50-column wide
    stream. Column pruning then drops the string columns from the scan
    altogether, so every scanned column-chunk has a native page recipe
    and the A/B isolates the page->wire reader + readahead against the
    pyarrow read chain under the stall model. (Scanning the strings
    too would measure the per-column arrow fallback instead — that
    path's bit-identity is pinned by the differential fuzz tests.)"""
    from deequ_tpu.analyzers import Completeness, Mean

    names = (
        [f"f{i:02d}" for i in range(20)]
        + [f"i{i:02d}" for i in range(10)]
        + [f"b{i}" for i in range(5)]
    )
    out = [Completeness(c) for c in names]
    out += [Mean(f"f{i:02d}") for i in range(20)]
    out += [Mean(f"i{i:02d}") for i in range(10)]
    return out


def _reader_span_stats(roots):
    """Runtime reader tallies from a traced pass: summed `page_decode`
    chunk verdicts + readahead hits and `page_read` bytes. The chunk
    sum is the runtime twin of the planner's reader_chunks_native
    counter — equal when no chunk silently fell off mid-scan."""
    stats = {
        "chunks_native": 0,
        "chunks_fallback": 0,
        "readahead_hits": 0,
        "decode_units": 0,
        "read_bytes": 0,
    }

    def visit(span):
        if span.name == "page_decode":
            stats["chunks_native"] += int(span.attrs.get("chunks_native", 0))
            stats["chunks_fallback"] += int(
                span.attrs.get("chunks_fallback", 0)
            )
            stats["readahead_hits"] += (
                1 if span.attrs.get("readahead_hit") else 0
            )
            stats["decode_units"] += 1
        elif span.name == "page_read":
            stats["read_bytes"] += int(span.attrs.get("bytes_read", 0))
        for child in span.children:
            visit(child)

    for root in roots:
        visit(root)
    return stats


def run_reader_bench(n_rows: int) -> None:
    """BENCH_MODE=reader: A/B the native parquet page->wire reader
    (ISSUE 11) on the decode bench's 50-column wide-stream shape under
    a 50 ms per-row-group source stall (the object-store latency model,
    DEEQU_TPU_SOURCE_STALL_MS). DEEQU_TPU_NATIVE_READER=0 reads every
    column chunk through pyarrow inside the decode workers, paying the
    stall serially with the decompress+decode work; =1 moves the stall
    and the preads onto the dedicated read-ahead fetch thread and
    page-decodes the planner-approved chunks through
    ops/native/parquet_read.c, so IO latency overlaps decode. Same
    discipline as the decode/wire A/Bs: a traced warm-up (jit + imports
    + the planner's reader verdict from its decode_fastpath span), one
    traced WARM pass per side for decode-stage busy seconds and the
    occupancy re-baseline (traced passes are never the timed ones),
    then two warm-jit cold-IO UNTRACED timed passes. The headline is
    the decode-STAGE busy time (pipe_item spans): the reader moves
    work out of the stage entirely, so stage busy — not any one span's
    self time — is what it shrinks. Aborts on any metric mismatch.
    Refreshes BENCH_READER.json (round/config preserved)."""
    import pyarrow.parquet as pq

    from deequ_tpu import observe
    from deequ_tpu.data.table import Table
    from deequ_tpu.ops.fused import FusedScanPass

    # own file, NOT the decode bench's: object-store parquet comes from
    # incremental writers in many small row groups (one ranged GET
    # each) — the layout the stall model charges for and the readahead
    # overlaps
    path = os.environ.get("BENCH_PARQUET", "/tmp/bench_reader.parquet")
    rg_rows = 1 << 15
    t_gen = time.perf_counter()
    if not (
        os.path.exists(path) and pq.ParquetFile(path).metadata.num_rows == n_rows
    ):
        write_decode_parquet(n_rows, path, row_group_size=rg_rows)
    gen_s = time.perf_counter() - t_gen

    analyzers = reader_analyzers()
    # the latency model the readahead overlaps: one 50 ms ranged GET
    # per row group, both sides pay it
    stall_ms = int(os.environ.get("BENCH_READER_STALL_MS", "50"))
    os.environ["DEEQU_TPU_SOURCE_STALL_MS"] = str(stall_ms)
    workers_n = min(os.cpu_count() or 1, 4)
    os.environ["DEEQU_TPU_DECODE_WORKERS"] = str(workers_n)

    def run_once():
        snapshot = {}
        for r in FusedScanPass(analyzers).run(
            Table.scan_parquet(path, batch_rows=1 << 20)
        ):
            value = r.analyzer.compute_metric_from(r.state_or_raise()).value
            v = (
                value.get()
                if value.is_success
                else type(value.exception).__name__
            )
            if isinstance(v, float) and v != v:
                v = "nan"  # nan != nan would defeat the A/B comparison
            snapshot[repr(r.analyzer)] = v
        return snapshot

    # warm-up FIRST (traced, reader ON): compiles every program, pays
    # the one-time imports, and its decode_fastpath span carries the
    # planner's per-chunk reader verdict
    os.environ["DEEQU_TPU_NATIVE_READER"] = "1"
    with observe.tracing() as tracer_warm:
        warm_snapshot = run_once()
    plan = {
        "cols_total": 0,
        "cols_fast": 0,
        "cols_reader": 0,
        "reader_groups": 0,
    }

    def visit(span):
        if span.name == "decode_fastpath":
            for key in plan:
                plan[key] = max(plan[key], int(span.attrs.get(key, 0)))
        for child in span.children:
            visit(child)

    for root in tracer_warm.roots:
        visit(root)

    # decode-stage busy seconds per side from one traced WARM pass each
    # (jit and page cache hot; the stall model still fires, so the
    # delta isolates stall overlap + native page decode)
    os.environ["DEEQU_TPU_NATIVE_READER"] = "0"
    with observe.tracing() as tracer_off:
        off_traced_snapshot = run_once()
    os.environ["DEEQU_TPU_NATIVE_READER"] = "1"
    with observe.tracing() as tracer_on:
        on_traced_snapshot = run_once()
    stage_s_off = _decode_stage_busy_s(tracer_off.roots)
    stage_s_on = _decode_stage_busy_s(tracer_on.roots)
    occupancy_off = _occupancy_rows(tracer_off.roots)
    occupancy_on = _occupancy_rows(tracer_on.roots)
    runtime_stats = _reader_span_stats(tracer_on.roots)
    counters = dict(tracer_on.counters)
    planned_native = int(counters.get("reader_chunks_native", 0))
    if runtime_stats["chunks_native"] != planned_native:
        raise SystemExit(
            "reader A/B: runtime chunk count drifted from the plan "
            f"(planned {planned_native}, page_decode spans saw "
            f"{runtime_stats['chunks_native']}) — a silent mid-scan "
            "fall-off would make the on side's numbers a lie"
        )

    # warm-jit cold-IO wall times, untraced, page cache dropped
    os.environ["DEEQU_TPU_NATIVE_READER"] = "0"
    cache_dropped = _drop_page_cache()
    t0 = time.perf_counter()
    off_snapshot = run_once()
    off_s = time.perf_counter() - t0

    os.environ["DEEQU_TPU_NATIVE_READER"] = "1"
    _drop_page_cache()
    t0 = time.perf_counter()
    on_snapshot = run_once()
    on_s = time.perf_counter() - t0

    if not (
        warm_snapshot == off_traced_snapshot == on_traced_snapshot
        == off_snapshot == on_snapshot
    ):
        raise SystemExit(
            "reader A/B: metric mismatch between the native-reader and "
            f"pyarrow sides\noff: {off_snapshot}\non:  {on_snapshot}"
        )

    reduction = (
        100.0 * (stage_s_off - stage_s_on) / stage_s_off
        if stage_s_off > 0
        else 0.0
    )
    speedup_x = stage_s_off / stage_s_on if stage_s_on > 0 else 0.0
    rec = {
        "metric": "reader_rows_per_sec_per_chip",
        "value": round(n_rows / on_s, 1),
        "unit": "rows/s",
        "rows": n_rows,
        "columns": plan["cols_total"],
        "reader_ab": {
            "off_s": round(off_s, 2),
            "on_s": round(on_s, 2),
            "speedup_pct": round(100.0 * (off_s - on_s) / off_s, 1),
            "decode_stage_s_off": round(stage_s_off, 2),
            "decode_stage_s_on": round(stage_s_on, 2),
            "decode_stage_reduction_pct": round(reduction, 1),
            "decode_stage_speedup_x": round(speedup_x, 2),
            "occupancy_off": occupancy_off,
            "occupancy_on": occupancy_on,
            "stall_ms": stall_ms,
            "cols_reader": plan["cols_reader"],
            "cols_total": plan["cols_total"],
            "reader_groups": plan["reader_groups"],
            "chunks_native": runtime_stats["chunks_native"],
            "chunks_fallback": runtime_stats["chunks_fallback"],
            "readahead_hits": runtime_stats["readahead_hits"],
            "decode_units": runtime_stats["decode_units"],
            "read_mb": round(runtime_stats["read_bytes"] / 1e6, 1),
            "workers_n": workers_n,
            "bit_identical": True,
            "page_cache_dropped": cache_dropped,
            "passes": (
                "traced warm-up (on) for the reader verdict + one "
                "traced warm pass per side for decode-stage busy "
                "seconds and stage occupancy; both timed passes are "
                "warm-jit, cold-IO, untraced"
            ),
        },
    }
    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.path.join(here, "BENCH_READER.json")
    try:
        with open(out_path) as fh:
            old = json.load(fh)
        for key in ("round", "config"):
            if key in old and key not in rec:
                rec[key] = old[key]
    except Exception:  # noqa: BLE001 - first write: no fields to carry
        pass
    with open(out_path, "w") as fh:
        json.dump(rec, fh)
        fh.write("\n")
    print(
        f"# bench: reader A/B off={off_s:.2f}s on={on_s:.2f}s "
        f"(+{100.0 * (off_s - on_s) / off_s:.1f}%), decode stage "
        f"{stage_s_off:.2f}s -> {stage_s_on:.2f}s "
        f"({speedup_x:.2f}x, -{reduction:.1f}%), "
        f"{runtime_stats['chunks_native']}/"
        f"{runtime_stats['chunks_native'] + runtime_stats['chunks_fallback']}"
        f" chunks native, {runtime_stats['readahead_hits']}/"
        f"{runtime_stats['decode_units']} readahead hits; "
        f"gen={gen_s:.1f}s",
        file=sys.stderr,
    )
    print(json.dumps(rec))


def encfold_analyzers():
    """The encoded-fold plan for BENCH_MODE=encfold: the LOW-CARDINALITY
    half of the 50-column wide stream — the 19 quantized-decimal f
    columns (200-10000 distinct values each, the TPC-H money shape) and
    the 10 windowed int columns. ApproxCountDistinct makes every f
    column a sketch consumer (dictionary-code rollup); Mean over the
    ints rides the footer-proven moments memos (Σ run_len × value over
    RLE runs); the median beside it makes each of those columns a
    select-family job, whose published qkey/rkey memos serve quantile
    AND distinct-count without a row in sight; Completeness everywhere
    folds definition-level runs.
    The i%4==3 f columns (10000 distinct values — past the per-batch
    DISTINCT_PUBLISH_CAP, so a sketch consumer would decline
    publication and expand the stub every batch) carry Completeness
    only: null counts come straight from the def-runs. Column pruning
    drops f00 (continuous lognormal), the bools and the strings, so
    the A/B isolates run-folding against row-width expansion of the
    exact columns the tentpole targets."""
    from deequ_tpu.analyzers import (
        ApproxCountDistinct,
        ApproxQuantile,
        Completeness,
        Mean,
    )

    names = [f"f{i:02d}" for i in range(1, 20)] + [
        f"i{i:02d}" for i in range(10)
    ]
    out = [Completeness(c) for c in names]
    out += [
        ApproxCountDistinct(f"f{i:02d}") for i in range(1, 20) if i % 4 != 3
    ]
    out += [
        ApproxQuantile(f"f{i:02d}", 0.5) for i in range(1, 20) if i % 4 != 3
    ]
    out += [Mean(f"i{i:02d}") for i in range(10)]
    return out


def _encfold_span_stats(roots):
    """Runtime encoded-fold tallies from a traced pass: summed
    `page_decode` run/chunk verdicts. The span sums are the runtime
    twin of the traced encfold_* counters — equal when no decode unit
    went uncounted."""
    stats = {
        "runs_native": 0,
        "chunks_runs": 0,
        "chunks_native": 0,
        "chunks_fallback": 0,
        "read_bytes": 0,
    }

    def visit(span):
        if span.name == "page_decode":
            stats["runs_native"] += int(span.attrs.get("runs_native", 0))
            stats["chunks_runs"] += int(span.attrs.get("chunks_runs", 0))
            stats["chunks_native"] += int(span.attrs.get("chunks_native", 0))
            stats["chunks_fallback"] += int(
                span.attrs.get("chunks_fallback", 0)
            )
        elif span.name == "page_read":
            stats["read_bytes"] += int(span.attrs.get("bytes_read", 0))
        for child in span.children:
            visit(child)

    for root in roots:
        visit(root)
    return stats


def write_encfold_parquet(
    n_rows: int,
    path: str,
    chunk: int = 2_000_000,
    null_frac: float = 0.03,
    row_group_size: int = 0,
) -> None:
    """The CLUSTERED wide-stream shape for the encoded-fold A/B: the
    same 50-column schema as write_decode_parquet, but the
    low-cardinality columns arrive in BURSTS (geometric run lengths,
    mean ~16) instead of a uniform shuffle — the event-stream /
    system-of-record layout parquet's RLE hybrid exists for, where a
    device emits the same status/price-bucket/partition-key for many
    consecutive rows. On this shape the dictionary-index streams
    actually run-length compress, so the run-fold kernels do O(runs)
    work where row expansion does O(rows). The uniform-shuffle worst
    case (runs of length 1, where folding is pure overhead) keeps its
    bit-identity pinned by the fuzz differentials; the planner's
    benefit gate is about consumers, not run shape, so that shape
    belongs to a falloff study, not this headline."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    writer = None
    done = 0
    seed = 0
    while done < n_rows:
        rows = min(chunk, n_rows - done)
        rng = np.random.default_rng(seed)

        def nullify(values):
            return pa.array(values, mask=rng.random(rows) < null_frac)

        def bursts(draw):
            """Clustered value stream: geometric-length runs (mean 16)
            of values drawn by `draw(k)`."""
            n_blocks = max(1, rows // 8)
            lens = rng.geometric(1.0 / 16.0, n_blocks)
            while int(lens.sum()) < rows:
                lens = np.concatenate(
                    [lens, rng.geometric(1.0 / 16.0, n_blocks)]
                )
            return np.repeat(draw(len(lens)), lens)[:rows]

        data = {}
        f00 = rng.lognormal(2.0, 1.0, rows)
        f00[rng.random(rows) < 0.03] = np.nan
        data["f00"] = nullify(f00)
        for i in range(1, 20):
            r = (200, 1_000, 2_000, 10_000)[i % 4]
            data[f"f{i:02d}"] = nullify(
                bursts(lambda k, r=r: rng.integers(0, r, k) / 100.0)
            )
        for i in range(10):
            hi = 100 * (i + 1) if i < 6 else 50_000
            data[f"i{i:02d}"] = nullify(
                bursts(lambda k, hi=hi: rng.integers(0, hi, k))
            )
        for i in range(5):
            data[f"b{i}"] = nullify(rng.random(rows) < (0.2 + 0.15 * i))
        for i in range(10):
            pool = CATEGORIES[: 3 + i]
            data[f"s{i:02d}"] = nullify(pool[rng.integers(0, len(pool), rows)])
        for i in range(5):
            pool = np.array(
                [str(v) for v in rng.integers(0, 2000 * (i + 1), 4096)],
                dtype=object,
            )
            data[f"c{i}"] = nullify(pool[rng.integers(0, len(pool), rows)])
        at = pa.table(data)
        if writer is None:
            writer = pq.ParquetWriter(path, at.schema)
        writer.write_table(at, row_group_size=row_group_size or None)
        done += rows
        seed += 1
    if writer is not None:
        writer.close()


def run_encfold_bench(n_rows: int) -> None:
    """BENCH_MODE=encfold: A/B the encoded-data fold (ISSUE 20) on the
    low-cardinality half of the decode bench's 50-column wide-stream
    shape. DEEQU_TPU_ENCODED_FOLD=0 expands every planner-approved
    chunk to row width (values + validity mask) before folding; =1
    decodes the same chunks to coalesced (run_len, dict_code) streams
    plus definition-level runs, folds moments as Σ(run_len × value),
    rolls dictionary codes up into the sketch families once per chunk,
    and takes null counts straight from the def-runs — no materialized
    rows, no validity mask. Both sides run the native page reader, so
    the delta isolates run-folding itself. Same discipline as the
    decode/wire/reader A/Bs: a traced warm-up (jit + the planner's
    encoded-fold verdict), one traced WARM pass per side for
    decode-stage busy seconds (traced passes are never the timed
    ones), then two warm untraced timed passes. The headline is the
    decode-STAGE busy time: run decoding does O(runs) work where row
    expansion does O(rows), so rows/s scales with ENCODED bytes, not
    logical rows. Aborts on any metric mismatch or plan/runtime drift.
    Refreshes BENCH_ENCFOLD.json (round/config preserved)."""
    import pyarrow.parquet as pq

    from deequ_tpu import observe
    from deequ_tpu.data.table import Table
    from deequ_tpu.ops.fused import FusedScanPass

    path = os.environ.get("BENCH_PARQUET", "/tmp/bench_encfold.parquet")
    rg_rows = 1 << 18
    t_gen = time.perf_counter()
    if not (
        os.path.exists(path) and pq.ParquetFile(path).metadata.num_rows == n_rows
    ):
        write_encfold_parquet(n_rows, path, row_group_size=rg_rows)
    gen_s = time.perf_counter() - t_gen

    analyzers = encfold_analyzers()
    workers_n = min(os.cpu_count() or 1, 4)
    os.environ["DEEQU_TPU_DECODE_WORKERS"] = str(workers_n)
    os.environ["DEEQU_TPU_NATIVE_READER"] = "1"
    # host fold: a device-packed column would expand its stub every
    # batch, so the classifier excludes it by design — the encoded
    # fold is a host-side decode optimization
    os.environ["DEEQU_TPU_PLACEMENT"] = "host"

    def run_once():
        snapshot = {}
        for r in FusedScanPass(analyzers).run(
            Table.scan_parquet(path, batch_rows=1 << 20)
        ):
            value = r.analyzer.compute_metric_from(r.state_or_raise()).value
            v = (
                value.get()
                if value.is_success
                else type(value.exception).__name__
            )
            if isinstance(v, float) and v != v:
                v = "nan"  # nan != nan would defeat the A/B comparison
            snapshot[repr(r.analyzer)] = v
        return snapshot

    # warm-up FIRST (traced, fold ON): compiles every program, pays the
    # one-time imports, and records the planner's encoded-fold verdict
    os.environ["DEEQU_TPU_ENCODED_FOLD"] = "1"
    with observe.tracing() as tracer_warm:
        warm_snapshot = run_once()
    cols_enc = int(tracer_warm.counters.get("encfold_cols", 0))
    cols_total = int(tracer_warm.counters.get("encfold_cols_total", 0))
    if cols_enc == 0:
        raise SystemExit(
            "encfold A/B: the planner approved no column on the "
            "low-cardinality shape — the on side would measure nothing"
        )

    # decode-stage busy seconds per side from one traced WARM pass each
    os.environ["DEEQU_TPU_ENCODED_FOLD"] = "0"
    with observe.tracing() as tracer_off:
        off_traced_snapshot = run_once()
    os.environ["DEEQU_TPU_ENCODED_FOLD"] = "1"
    with observe.tracing() as tracer_on:
        on_traced_snapshot = run_once()
    stage_s_off = _decode_stage_busy_s(tracer_off.roots)
    stage_s_on = _decode_stage_busy_s(tracer_on.roots)
    occupancy_off = _occupancy_rows(tracer_off.roots)
    occupancy_on = _occupancy_rows(tracer_on.roots)
    runtime_stats = _encfold_span_stats(tracer_on.roots)
    off_stats = _encfold_span_stats(tracer_off.roots)
    counters = dict(tracer_on.counters)
    if runtime_stats["runs_native"] != int(counters.get("encfold_runs", 0)):
        raise SystemExit(
            "encfold A/B: per-span run counts drifted from the traced "
            f"total ({runtime_stats['runs_native']} vs "
            f"{counters.get('encfold_runs', 0)})"
        )
    if runtime_stats["chunks_fallback"] > 0:
        raise SystemExit(
            "encfold A/B: a chunk of this all-dictionary shape fell "
            f"back to row width at decode "
            f"({runtime_stats['chunks_fallback']} chunks) — the on "
            "side's numbers would charge the row path to the fold"
        )
    if int(counters.get("encfold_chunks", 0)) == 0:
        raise SystemExit(
            "encfold A/B: no chunk reached the run decoder despite "
            f"{cols_enc} approved column(s)"
        )

    # warm-jit warm-IO wall times, untraced: the fold is decode-bound,
    # not IO-bound — cold-IO timing belongs to the reader A/B
    os.environ["DEEQU_TPU_ENCODED_FOLD"] = "0"
    t0 = time.perf_counter()
    off_snapshot = run_once()
    off_s = time.perf_counter() - t0

    os.environ["DEEQU_TPU_ENCODED_FOLD"] = "1"
    t0 = time.perf_counter()
    on_snapshot = run_once()
    on_s = time.perf_counter() - t0

    if not (
        warm_snapshot == off_traced_snapshot == on_traced_snapshot
        == off_snapshot == on_snapshot
    ):
        raise SystemExit(
            "encfold A/B: metric mismatch between the encoded-fold and "
            f"row-width sides\noff: {off_snapshot}\non:  {on_snapshot}"
        )

    reduction = (
        100.0 * (stage_s_off - stage_s_on) / stage_s_off
        if stage_s_off > 0
        else 0.0
    )
    speedup_x = stage_s_off / stage_s_on if stage_s_on > 0 else 0.0
    runs = int(counters.get("encfold_runs", 0))
    values = int(counters.get("encfold_values", 0))
    rec = {
        "metric": "encfold_rows_per_sec_per_chip",
        "value": round(n_rows / on_s, 1),
        "unit": "rows/s",
        "rows": n_rows,
        "columns": cols_total,
        "encfold_ab": {
            "off_s": round(off_s, 2),
            "on_s": round(on_s, 2),
            "speedup_pct": round(100.0 * (off_s - on_s) / off_s, 1),
            "decode_stage_s_off": round(stage_s_off, 2),
            "decode_stage_s_on": round(stage_s_on, 2),
            "decode_stage_reduction_pct": round(reduction, 1),
            "decode_stage_speedup_x": round(speedup_x, 2),
            "occupancy_off": occupancy_off,
            "occupancy_on": occupancy_on,
            "cols_encfold": cols_enc,
            "cols_total": cols_total,
            "chunks_runs": runtime_stats["chunks_runs"],
            "chunks_row_off": off_stats["chunks_native"],
            "runs": runs,
            "values": values,
            "run_ratio": round(values / runs, 2) if runs else 0.0,
            "codes_folded": int(counters.get("encfold_codes_folded", 0)),
            "bytes_saved_mb": round(
                int(counters.get("encfold_bytes_saved", 0)) / 1e6, 1
            ),
            "encoded_read_mb": round(runtime_stats["read_bytes"] / 1e6, 1),
            "logical_mb": round(n_rows * 8 * cols_total / 1e6, 1),
            "workers_n": workers_n,
            "bit_identical": True,
            "passes": (
                "traced warm-up (on) for the encoded-fold verdict + one "
                "traced warm pass per side for decode-stage busy "
                "seconds; both timed passes are warm-jit, untraced"
            ),
        },
    }
    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.path.join(here, "BENCH_ENCFOLD.json")
    try:
        with open(out_path) as fh:
            old = json.load(fh)
        for key in ("round", "config"):
            if key in old and key not in rec:
                rec[key] = old[key]
    except Exception:  # noqa: BLE001 - first write: no fields to carry
        pass
    with open(out_path, "w") as fh:
        json.dump(rec, fh)
        fh.write("\n")
    print(
        f"# bench: encfold A/B off={off_s:.2f}s on={on_s:.2f}s "
        f"(+{100.0 * (off_s - on_s) / off_s:.1f}%), decode stage "
        f"{stage_s_off:.2f}s -> {stage_s_on:.2f}s "
        f"({speedup_x:.2f}x, -{reduction:.1f}%), "
        f"{cols_enc}/{cols_total} cols folded, "
        f"{values}/{runs} values/runs "
        f"({(values / runs if runs else 0):.1f}x), "
        f"gen={gen_s:.1f}s",
        file=sys.stderr,
    )
    print(json.dumps(rec))


def write_incremental_dataset(n_rows: int, n_parts: int, dir_path: str) -> None:
    """A partitioned dataset (one parquet file per partition) with
    deterministic per-partition contents: two doubles (one with NaN
    holes), one long. Partition i is a pure function of i, so appending
    part N later never perturbs parts 0..N-1."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    os.makedirs(dir_path, exist_ok=True)
    per_part = max(1, n_rows // n_parts)
    for i in range(n_parts):
        path = os.path.join(dir_path, f"part-{i:04d}.parquet")
        if os.path.exists(path):
            continue
        rng = np.random.default_rng(1_000 + i)
        x = rng.normal(float(i), 10.0, per_part)
        x[rng.random(per_part) < 0.05] = np.nan
        table = pa.table(
            {
                "x": x,
                "y": x * 0.5 + rng.normal(0.0, 1.0, per_part),
                "g": rng.integers(0, 10_000, per_part),
            }
        )
        pq.write_table(table, path, row_group_size=max(4096, per_part // 8))


def incremental_analyzers():
    """Every cacheable scan family: counts, moments, HLL, KLL."""
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

    return [
        Size(),
        Completeness("x"),
        Mean("x"),
        StandardDeviation("x"),
        Minimum("x"),
        Maximum("y"),
        ApproxCountDistinct("g"),
        ApproxQuantile("x", 0.5),
    ]


def run_incremental_bench(n_rows: int) -> None:
    """BENCH_MODE=incremental: A/B the persistent partition-state cache
    (ISSUE 10) on an N-partition dataset. Cold pass: full scan with an
    empty state repository (fills it). Then ONE partition is appended
    and the warm incremental pass — which loads N cached partition
    states and scans only the new file — races a cache-off full rescan
    of the same N+1 partitions. A separate traced pass (against a
    pristine copy of the cold cache) proves partitions_scanned == 1;
    all timed passes are warm-jit, cold-IO, untraced. Aborts on any
    metric mismatch between the incremental merge and the full rescan.
    Refreshes BENCH_INCREMENTAL.json (round/config preserved)."""
    import shutil

    from deequ_tpu import observe
    from deequ_tpu.data.table import Table
    from deequ_tpu.repository.states import FileSystemStateRepository
    from deequ_tpu.runners.analysis_runner import AnalysisRunner

    n_parts = max(10, int(os.environ.get("BENCH_INCR_PARTS", "12")))
    data_dir = os.environ.get("BENCH_INCR_DIR", "/tmp/bench_incremental")
    appended = os.path.join(data_dir, f"part-{n_parts:04d}.parquet")

    t_gen = time.perf_counter()
    if os.path.exists(appended):
        os.remove(appended)  # a previous run's appended partition
    write_incremental_dataset(n_rows, n_parts, data_dir)
    gen_s = time.perf_counter() - t_gen

    analyzers = incremental_analyzers()
    os.environ["DEEQU_TPU_PLACEMENT"] = "device"
    os.environ.pop("DEEQU_TPU_STATE_CACHE", None)

    cache_dir = os.path.join(data_dir, "state-cache")
    proof_dir = os.path.join(data_dir, "state-cache-proof")
    for d in (cache_dir, proof_dir):
        shutil.rmtree(d, ignore_errors=True)

    def run_once(repository=None, tracing=None):
        context = AnalysisRunner.do_analysis_run(
            Table.scan_parquet_dataset(data_dir, batch_rows=1 << 20),
            analyzers,
            state_repository=repository,
            dataset_name="bench",
            tracing=tracing,
        )
        snapshot = {}
        for analyzer, metric in context.metric_map.items():
            v = (
                metric.value.get()
                if metric.value.is_success
                else type(metric.value.exception).__name__
            )
            if isinstance(v, float) and v != v:
                v = "nan"  # nan != nan would defeat the A/B comparison
            snapshot[repr(analyzer)] = v
        return snapshot, context

    # warm-up (no repository): jit + imports, never timed
    warm_snapshot, _ = run_once()

    # cold pass: full scan, fills the empty repository
    repo = FileSystemStateRepository(cache_dir)
    cache_dropped = _drop_page_cache()
    t0 = time.perf_counter()
    cold_snapshot, _ = run_once(repository=repo)
    cold_s = time.perf_counter() - t0

    # the increment: ONE new partition appears
    write_incremental_dataset(
        n_rows + max(1, n_rows // n_parts), n_parts + 1, data_dir
    )
    # pristine copy of the cold cache for the traced proof pass, so the
    # timed incremental pass still sees the appended partition as new
    shutil.copytree(cache_dir, proof_dir)

    # cache-off full rescan of the grown dataset (the A side)
    _drop_page_cache()
    t0 = time.perf_counter()
    full_snapshot, _ = run_once()
    full_s = time.perf_counter() - t0

    # warm incremental pass (the B side): N cached loads + 1 scan
    _drop_page_cache()
    t0 = time.perf_counter()
    incr_snapshot, _ = run_once(repository=repo)
    incr_s = time.perf_counter() - t0

    # traced proof pass against the pristine cache copy
    proof_snapshot, proof_context = run_once(
        repository=FileSystemStateRepository(proof_dir), tracing=True
    )
    counters = proof_context.run_trace.counters

    if not (
        warm_snapshot == cold_snapshot
        and full_snapshot == incr_snapshot == proof_snapshot
    ):
        raise SystemExit(
            "incremental A/B: metric mismatch between the cached merge "
            f"and the full rescan\nfull: {full_snapshot}\nincr: {incr_snapshot}"
        )
    if counters.get("partitions_scanned") != 1:
        raise SystemExit(
            "incremental A/B: expected exactly 1 partition scanned, "
            f"trace says {dict(counters)}"
        )

    speedup = full_s / incr_s if incr_s > 0 else float("inf")
    rec = {
        "metric": "incremental_speedup",
        "value": round(speedup, 1),
        "unit": "x",
        "rows": n_rows,
        "incremental_ab": {
            "n_partitions": n_parts + 1,
            "partitions_scanned": int(counters.get("partitions_scanned", 0)),
            "partitions_cached": int(counters.get("partitions_cached", 0)),
            "cold_s": round(cold_s, 2),
            "full_rescan_s": round(full_s, 2),
            "incremental_s": round(incr_s, 2),
            "speedup_vs_full_rescan": round(speedup, 1),
            "speedup_vs_cold": round(cold_s / incr_s, 1) if incr_s > 0 else None,
            "bit_identical": True,
            "page_cache_dropped": cache_dropped,
            "passes": (
                "untimed warm-up; cold fill pass; append 1 partition; "
                "cache-off full rescan vs warm incremental, both "
                "warm-jit cold-IO untraced; traced proof pass against "
                "a pristine cache copy pins partitions_scanned == 1"
            ),
        },
    }
    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.path.join(here, "BENCH_INCREMENTAL.json")
    try:
        with open(out_path) as fh:
            old = json.load(fh)
        for key in ("round", "config"):
            if key in old and key not in rec:
                rec[key] = old[key]
    except Exception:  # noqa: BLE001 - first write: no fields to carry
        pass
    with open(out_path, "w") as fh:
        json.dump(rec, fh)
        fh.write("\n")
    print(
        f"# bench: incremental A/B full={full_s:.2f}s incr={incr_s:.2f}s "
        f"({speedup:.1f}x), scanned {counters.get('partitions_scanned')}/"
        f"{n_parts + 1} partitions (cold fill {cold_s:.2f}s); gen={gen_s:.1f}s",
        file=sys.stderr,
    )
    print(json.dumps(rec))


def write_window_dataset(n_rows: int, n_parts: int, dir_path: str) -> None:
    """A daily-partitioned dataset (one parquet file per calendar day,
    date-named so windows/spec.py derives the time axis from the
    layout). Partition i is a pure function of i, like the incremental
    dataset, so re-running never perturbs existing days."""
    import datetime

    import pyarrow as pa
    import pyarrow.parquet as pq

    os.makedirs(dir_path, exist_ok=True)
    per_part = max(1, n_rows // n_parts)
    day0 = datetime.date(2026, 1, 1)
    for i in range(n_parts):
        day = day0 + datetime.timedelta(days=i)
        path = os.path.join(dir_path, f"part-{day.isoformat()}.parquet")
        if os.path.exists(path):
            continue
        rng = np.random.default_rng(2_000 + i)
        x = rng.normal(50.0 + 0.1 * i, 10.0, per_part)
        x[rng.random(per_part) < 0.05] = np.nan
        table = pa.table(
            {
                "x": x,
                "y": x * 0.5 + rng.normal(0.0, 1.0, per_part),
                "g": rng.integers(0, 10_000, per_part),
            }
        )
        pq.write_table(table, path, row_group_size=max(4096, per_part // 8))


def run_window_bench(n_rows: int) -> None:
    """BENCH_MODE=window: A/B the windowed state algebra (windows/) on a
    30-partition daily dataset. Cold fill commits per-partition states;
    an untimed first window query publishes the DQSG segment covers.
    Then the warm B side — a 7-day sliding-window metrics query PLUS a
    week-over-week drift check, all from segment merges — races the A
    side: cache-off full rescans of the same current-week and
    prior-week partitions. A traced proof pass pins
    partitions_scanned == 0 (zero data rows read warm) and every cover
    span a segment hit; aborts on any metric mismatch between the
    window merge and the full rescan. Refreshes BENCH_WINDOW.json
    (round/config preserved)."""
    import shutil

    from deequ_tpu.checks import CheckLevel, CheckStatus, DriftCheck
    from deequ_tpu.data.table import Table
    from deequ_tpu.repository.states import FileSystemStateRepository
    from deequ_tpu.runners.analysis_runner import AnalysisRunner
    from deequ_tpu.windows import Sliding, WindowQuery

    n_parts = max(30, int(os.environ.get("BENCH_WINDOW_PARTS", "30")))
    data_dir = os.environ.get("BENCH_WINDOW_DIR", "/tmp/bench_window")

    t_gen = time.perf_counter()
    write_window_dataset(n_rows, n_parts, data_dir)
    gen_s = time.perf_counter() - t_gen

    analyzers = incremental_analyzers()
    os.environ["DEEQU_TPU_PLACEMENT"] = "device"
    os.environ.pop("DEEQU_TPU_STATE_CACHE", None)

    cache_dir = os.path.join(data_dir, "state-cache")
    shutil.rmtree(cache_dir, ignore_errors=True)
    repo = FileSystemStateRepository(cache_dir)

    def snapshot_of(context):
        snap = {}
        for analyzer, metric in context.metric_map.items():
            v = (
                metric.value.get()
                if metric.value.is_success
                else type(metric.value.exception).__name__
            )
            if isinstance(v, float) and v != v:
                v = "nan"
            snap[repr(analyzer)] = v
        return snap

    source = Table.scan_parquet_dataset(data_dir, batch_rows=1 << 20)

    # cold fill: one full scan commits every partition's states
    t0 = time.perf_counter()
    AnalysisRunner.do_analysis_run(
        source, analyzers, state_repository=repo, dataset_name="bench",
    )
    cold_s = time.perf_counter() - t0

    query = WindowQuery(
        source, analyzers, repository=repo, dataset="bench",
    )
    timeline = query.timeline()
    current = Sliding(7).resolve(timeline)
    baseline = current.shifted(7, timeline)
    parts = source.partitions()

    drift_check = (
        DriftCheck(CheckLevel.ERROR, "week-over-week")
        .has_no_drift(
            "x",
            max_quantile_shift=0.2,
            max_mean_delta=0.2,
            max_completeness_delta=0.05,
        )
        .has_no_cardinality_drift("g", max_ratio_drift=0.5)
    )

    # untimed first query: publishes the segment covers (warm=True)
    query.run(current)
    query.run(baseline)

    # A side: answer the same question by rescanning — cache-off full
    # scans of the current-week and prior-week partitions
    def subset_for(frame):
        return source.subset([parts[i].path for i in frame.indices])

    _drop_page_cache()
    t0 = time.perf_counter()
    rescan_cur = AnalysisRunner.do_analysis_run(subset_for(current), analyzers)
    rescan_base = AnalysisRunner.do_analysis_run(subset_for(baseline), analyzers)
    rescan_s = time.perf_counter() - t0

    # B side: warm window metrics + week-over-week drift, segment merges
    # only (zero data rows)
    cache_dropped = _drop_page_cache()
    t0 = time.perf_counter()
    window_ctx = query.run(current)
    cur_bag = query.states(current)
    base_bag = query.states(baseline)
    drift_result = drift_check.evaluate(current=cur_bag, baseline=base_bag)
    window_s = time.perf_counter() - t0

    # traced proof pass: zero partitions scanned, every span a hit
    proof_ctx = query.run(current, tracing=True)
    counters = proof_ctx.run_trace.counters

    if snapshot_of(window_ctx) != snapshot_of(rescan_cur):
        raise SystemExit(
            "window A/B: metric mismatch between the segment merge and "
            f"the full rescan\nrescan: {snapshot_of(rescan_cur)}\n"
            f"window: {snapshot_of(window_ctx)}"
        )
    if snapshot_of(proof_ctx) != snapshot_of(rescan_cur):
        raise SystemExit("window A/B: traced proof pass diverged")
    # the drift inputs' provenance: the prior-week window merge must
    # also match ITS full rescan bit-for-bit
    if snapshot_of(query.run(baseline)) != snapshot_of(rescan_base):
        raise SystemExit(
            "window A/B: baseline-week metric mismatch between the "
            "segment merge and the full rescan"
        )
    if counters.get("partitions_scanned", 0) != 0:
        raise SystemExit(
            "window A/B: warm window query scanned data rows, "
            f"trace says {dict(counters)}"
        )
    if counters.get("window.segment_hits", 0) != counters.get(
        "window.spans", -1
    ):
        raise SystemExit(
            "window A/B: warm query missed segment covers, "
            f"trace says {dict(counters)}"
        )
    if drift_result.status != CheckStatus.SUCCESS:
        raise SystemExit(
            "window A/B: drift check failed on the stable dataset: "
            + "; ".join(
                str(r.message)
                for r in drift_result.constraint_results
                if r.message
            )
        )

    speedup = rescan_s / window_s if window_s > 0 else float("inf")
    rec = {
        "metric": "window_speedup",
        "value": round(speedup, 1),
        "unit": "x",
        "rows": n_rows,
        "window_ab": {
            "n_partitions": n_parts,
            "window": "sliding(7) + week-over-week drift",
            "segment_merges": int(counters.get("window.segments_merged", 0)),
            "segment_hits": int(counters.get("window.segment_hits", 0)),
            "partitions_scanned": int(counters.get("partitions_scanned", 0)),
            "cold_fill_s": round(cold_s, 2),
            "full_rescan_s": round(rescan_s, 3),
            "window_query_s": round(window_s, 3),
            "speedup_vs_full_rescan": round(speedup, 1),
            "drift_status": drift_result.status.value,
            "bit_identical": True,
            "page_cache_dropped": cache_dropped,
            "passes": (
                "cold fill commits per-partition states; untimed first "
                "queries publish segment covers; cache-off rescans of "
                "current+prior week vs warm window metrics + drift "
                "check; traced proof pass pins partitions_scanned == 0 "
                "and all covers hit"
            ),
        },
    }
    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.path.join(here, "BENCH_WINDOW.json")
    try:
        with open(out_path) as fh:
            old = json.load(fh)
        for key in ("round", "config"):
            if key in old and key not in rec:
                rec[key] = old[key]
    except Exception:  # noqa: BLE001 - first write: no fields to carry
        pass
    with open(out_path, "w") as fh:
        json.dump(rec, fh)
        fh.write("\n")
    print(
        f"# bench: window A/B rescan={rescan_s:.3f}s window={window_s:.3f}s "
        f"({speedup:.1f}x), {counters.get('window.segments_merged')} segment "
        f"merges, 0 rows read (cold fill {cold_s:.2f}s); gen={gen_s:.1f}s",
        file=sys.stderr,
    )
    print(json.dumps(rec))


def _stream_shape() -> str:
    return os.environ.get("BENCH_STREAM_SHAPE", "default")


def _builder_for_mode(mode: str):
    if mode == "stream" and _stream_shape() == "wide":
        return build_wide_stream_table
    return {
        "wide": build_wide_table,
        "lineitem": build_lineitem_table,
    }.get(mode, build_table)


def measure_reference_profile_rows_per_sec(
    probe_rows: int = 2_000_000, mode: str = "profiler"
) -> float:
    """Measured baseline denominator: a straightforward single-core
    pandas/numpy implementation of the SAME 3-pass profile deequ runs
    (pass 1: size/completeness/distinct/row-level regex DataType; pass 2:
    min/max/mean/std/sum + 100 percentiles per numeric column incl. cast
    numeric-string columns; pass 3: exact value counts for low-card
    columns), over the SAME table shape as the benched mode (schema
    discovered generically by dtype, so the wide/lineitem modes get a
    same-shape denominator). This is what a competent engineer gets from
    the standard Python stack on this box — a measured stand-in for
    "Spark local on this machine", which a JVM + row-shuffle engine
    would not beat on a single core. bench uses max(this, the
    documented 2.0M proxy) as the denominator so the ratio is never
    inflated by a slow box."""
    import re
    import pandas as pd

    df = _builder_for_mode(mode)(probe_rows).to_pandas()
    t0 = time.perf_counter()

    # ---- pass 1: size, completeness, distinct, DataType inference ----
    n = len(df)
    _ = df.notna().mean()
    nuniques = {c: df[c].nunique() for c in df.columns}
    frac = re.compile(r"^(-|\+)? ?\d*\.\d*$")
    integ = re.compile(r"^(-|\+)? ?\d*$")
    boolean = re.compile(r"^(true|false)$")
    string_cols = [
        c
        for c in df.columns
        if df[c].dtype == object and not isinstance(df[c].iloc[0], (bool, np.bool_))
    ]
    type_counts = {}
    numeric_casts = {}
    for c in string_cols:
        s = df[c].dropna().astype(str)
        matches_int = s.str.fullmatch(integ)
        type_counts[c] = (
            s.str.fullmatch(frac).sum(),
            matches_int.sum(),
            s.str.fullmatch(boolean).sum(),
        )
        if len(s) and bool(matches_int.all()):
            # inferred-numeric string column: pass 2 will cast it
            numeric_casts[c] = pd.to_numeric(df[c], errors="coerce")

    # ---- pass 2: numeric stats + percentiles (incl. cast strings) ----
    numeric = {
        c: df[c]
        for c in df.columns
        if df[c].dtype.kind in "if" and df[c].dtype != bool
    }
    numeric.update(numeric_casts)
    qs = np.arange(1, 101) / 100.0
    for c, s in numeric.items():
        _ = (s.min(), s.max(), s.mean(), s.std(), s.sum())
        vals = s.dropna().to_numpy(dtype=np.float64)
        if len(vals):
            _ = np.quantile(vals, qs)

    # ---- pass 3: exact histograms for low-cardinality columns ----
    for c in df.columns:
        if df[c].dtype == bool or (c in string_cols and nuniques[c] <= 120):
            _ = df[c].value_counts(dropna=False)

    elapsed = max(time.perf_counter() - t0, 1e-9)
    return probe_rows / elapsed


def measure_arrow_profile_rows_per_sec(probe_rows: int = 2_000_000) -> float:
    """Measured baseline denominator #2: the SAME 3-pass profile through
    pyarrow's C++ compute engine pinned to ONE thread — the strongest
    columnar engine available in this image, and a stricter stand-in for
    "Spark local on this box" than pandas.

    Provenance of the engine choice: the reference's own perf substrate
    is Spark local mode (SparkContextSpec.scala:25-95). Running actual
    Spark here was attempted and is impossible offline: pyspark is not
    installed, `pip install` is disallowed in this image, and there is
    no JRE (`java` not on PATH) to run it against. DuckDB and Polars are
    absent too. pyarrow 25's kernels (count_distinct, tdigest,
    value_counts, re2 regex match) cover the whole profile workload in
    vectorized C++, which a JVM row-engine would not beat single-core.
    """
    import pyarrow as pa
    import pyarrow.compute as pc

    old_cpu = pa.cpu_count()
    pa.set_cpu_count(1)  # single core, like our engine on this box
    try:
        df = build_table(probe_rows).to_pandas()
        at = pa.table(
            {name: pa.array(df[name]) for name in df.columns}
        )
        t0 = time.perf_counter()

        # ---- pass 1: size, completeness, distinct, DataType regexes ---
        _ = at.num_rows
        for name in at.column_names:
            col = at.column(name)
            _ = pc.count(col, mode="only_valid")
            _ = pc.count_distinct(col)
        for name in ("category", "code"):
            col = pc.cast(at.column(name), pa.string())
            _ = pc.sum(pc.match_substring_regex(col, r"^(-|\+)? ?\d*\.\d*$"))
            _ = pc.sum(pc.match_substring_regex(col, r"^(-|\+)? ?\d*$"))
            _ = pc.sum(pc.match_substring_regex(col, r"^(true|false)$"))

        # ---- pass 2: numeric stats + 100 approximate percentiles ------
        qs = [i / 100 for i in range(1, 101)]
        numeric = {
            "price": at.column("price"),
            "discount": at.column("discount"),
            "qty": at.column("qty"),
            "code": pc.cast(at.column("code"), pa.float64()),
        }
        for name, col in numeric.items():
            _ = pc.min_max(col)
            _ = pc.mean(col)
            _ = pc.stddev(col)
            _ = pc.sum(col)
            _ = pc.tdigest(col, q=qs)

        # ---- pass 3: exact histograms for low-cardinality columns -----
        for name in ("category", "flag"):
            _ = pc.value_counts(at.column(name))

        elapsed = max(time.perf_counter() - t0, 1e-9)
        return probe_rows / elapsed
    finally:
        pa.set_cpu_count(old_cpu)


def _measure_baseline_subprocess(mode: str = "profiler") -> float:
    """Run the reference profiles (pandas AND single-thread pyarrow
    Acero; the denominator takes the max) in a SUBPROCESS so their
    transient working sets never pollute the bench process's peak-RSS
    report and their wall time never mixes into the engine's timings.
    `mode` selects the table SHAPE the probe profiles (wide/lineitem
    must be measured against their own shape, not the 6-col table)."""
    import subprocess

    # JAX_PLATFORMS=cpu: the child never claims the chip this process holds
    env = dict(os.environ, BENCH_MODE=mode, JAX_PLATFORMS="cpu")
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--measure-baseline"],
            capture_output=True,
            text=True,
            timeout=600,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            env=env,
        )
        return float(out.stdout.strip().splitlines()[-1])
    except Exception:  # noqa: BLE001 - fall back to the in-process probe
        return measure_reference_profile_rows_per_sec(mode=mode)


def _refresh_shape_json(shape: str, n_rows: int, reps: int) -> None:
    """Time one north-star shape (wide/lineitem) IN THIS PROCESS, which
    holds the chip, and write BENCH_<SHAPE>.json next to this file. Part
    of the per-round regression loop: the headline profiler number and
    the shape numbers move together, so a regression in the batched
    family kernels shows up in the tracked artifacts, not just the
    default 6-col table. Called after the headline JSON line is printed,
    so it reports on stderr only."""
    table = _builder_for_mode(shape)(n_rows)
    baseline = _measure_baseline_subprocess(shape)
    run_profiler(table)  # warmup: compiles this shape's programs
    times, cpu_times = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        c0 = time.process_time()
        run_profiler(table)
        cpu_times.append(time.process_time() - c0)
        times.append(time.perf_counter() - t0)
    rows_per_sec = n_rows / min(times)
    rec = {
        "metric": f"{shape}_rows_per_sec_per_chip",
        "value": round(rows_per_sec, 1),
        "unit": "rows/s",
        "vs_baseline": round(rows_per_sec / baseline, 3),
        "cpu_s": round(min(cpu_times), 3),
        "rows": n_rows,
        "device": _device_record(),
    }
    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), f"BENCH_{shape.upper()}.json"
    )
    with open(out_path, "w") as fh:
        json.dump(rec, fh)
        fh.write("\n")
    print(
        f"# bench: wrote {os.path.basename(out_path)}: "
        f"{rec['value'] / 1e6:.2f}M rows/s, {rec['vs_baseline']}x",
        file=sys.stderr,
    )


def _device_record() -> dict:
    """The device every number in a record was measured on."""
    import jax

    device = jax.devices()[0]
    return {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(jax.devices()),
    }


def write_parquet(
    n_rows: int, path: str, chunk: int = 2_000_000, builder=build_table
) -> None:
    """Stream-generate the bench table to disk in chunks (bounded memory),
    so stream mode can exceed host RAM."""
    import pyarrow.parquet as pq

    writer = None
    done = 0
    seed = 0
    while done < n_rows:
        rows = min(chunk, n_rows - done)
        at = builder(rows, seed=seed).to_arrow()
        if writer is None:
            writer = pq.ParquetWriter(path, at.schema)
        writer.write_table(at)
        done += rows
        seed += 1
    if writer is not None:
        writer.close()


def _drop_page_cache() -> bool:
    """Best-effort OS page-cache drop (needs root) so a cold stream pass
    pays real disk IO instead of reading the just-written file from the
    125GB host RAM. Returns whether it worked — recorded in the JSON so
    a cached run is never mistaken for a disk-bound one."""
    try:
        with open("/proc/sys/vm/drop_caches", "w") as fh:
            fh.write("3\n")
        return True
    except OSError:
        return False


def pallas_onchip_check() -> str:
    """Run the Pallas HLL register-max kernel ON THE ATTACHED TPU and
    compare it against the XLA scatter path on the same device — the
    proof that the Pallas kernels produce correct results on real
    silicon. Returns 'ok', or 'skipped:platform=<p>' off the TPU; on a
    TPU a mismatch or a kernel failure raises."""
    import jax
    import jax.numpy as jnp

    from deequ_tpu.ops import pallas_kernels
    from deequ_tpu.ops.sketches import hll

    device = jax.devices()[0]
    if device.platform != "tpu":
        return f"skipped:platform={device.platform}"
    pallas_kernels.usable()  # raises when the chip refuses the kernel
    rng = np.random.default_rng(7)
    n = 1 << 16
    values = rng.integers(-(1 << 40), 1 << 40, n)
    valid = rng.random(n) > 0.1
    packed = jnp.asarray(hll.pack_codes(values, valid))
    on_chip = np.asarray(
        jax.jit(pallas_kernels.hll_register_max)(packed)
    ).astype(np.int32)
    idx = packed >> 6
    rank = packed & 0x3F
    xla = np.asarray(
        jax.jit(
            lambda i, r: jnp.zeros(hll.M, dtype=r.dtype).at[i].max(r)
        )(idx, rank)
    ).astype(np.int32)
    host = np.zeros(hll.M, dtype=np.int32)
    packed_np = np.asarray(packed)
    np.maximum.at(host, packed_np >> 6, packed_np & 0x3F)
    if not (np.array_equal(on_chip, xla) and np.array_equal(on_chip, host)):
        raise AssertionError("Pallas hll_register_max != XLA/host registers")
    # the MXU hist16 radix-select kernel, also on silicon: counts
    # must match a host bincount of the same sortable-key bins
    x32 = rng.lognormal(0.0, 2.0, n).astype(np.float32)
    live = rng.random(n) > 0.1
    bins = jax.jit(pallas_kernels.f32_sortable_bin16)(
        jnp.asarray(x32), jnp.asarray(live)
    )
    hist_chip = np.asarray(jax.jit(pallas_kernels.hist16)(bins)).reshape(
        65536
    )
    host_hist = np.bincount(
        np.asarray(bins).astype(np.int64) & 0xFFFF, minlength=65536
    )
    if not np.array_equal(hist_chip.astype(np.int64), host_hist):
        raise AssertionError("Pallas hist16 != host bincount")
    return "ok"


def run_forensics_bench(n_rows: int, reps: int) -> None:
    """BENCH_MODE=forensics: A/B row-level failure-forensics capture
    (ISSUE 12) on the decode bench's 50-column wide-stream shape. The
    check mixes a completeness constraint that FAILS on ~3% of rows in
    a hot column (every batch carries violations, so the capture side
    pays mask rebuild + reservoir churn on every batch — the worst
    case) with passing bound/compliance constraints (their capture is
    pure mask work). Both sides run the identical VerificationSuite;
    the run aborts unless statuses and metrics are bit-identical
    (forensics must be provably inert). Wall times are warm-jit
    best-of-reps, forensics OFF first. Refreshes BENCH_FORENSICS.json
    (round/config preserved)."""
    import pyarrow.parquet as pq

    from deequ_tpu.checks.check import Check, CheckLevel
    from deequ_tpu.data.table import Table
    from deequ_tpu.verification.suite import VerificationSuite

    path = os.environ.get("BENCH_PARQUET", "/tmp/bench_decode.parquet")
    t_gen = time.perf_counter()
    if not (
        os.path.exists(path) and pq.ParquetFile(path).metadata.num_rows == n_rows
    ):
        write_decode_parquet(n_rows, path)
    gen_s = time.perf_counter() - t_gen

    check = (
        Check(CheckLevel.ERROR, "forensics bench")
        # ~3% nulls: FAILS, violations in every batch (capture-heavy)
        .is_complete("f00")
        .is_complete("i00")
        .has_min("f01", lambda v: v >= 0.0)  # passes: bound capture only
        .has_max("f02", lambda v: v <= 1e6)  # passes
        .satisfies("f03 >= 0", "f03 nonneg", lambda r: r >= 0.9)  # passes
    )

    def run_once(forensics: bool):
        builder = (
            VerificationSuite()
            .on_data(Table.scan_parquet(path, batch_rows=1 << 20))
            .add_check(check)
        )
        if forensics:
            builder = builder.with_forensics()
        result = builder.run()
        snapshot = {}
        for analyzer, metric in result.metrics.items():
            value = metric.value
            v = value.get() if value.is_success else type(value.exception).__name__
            if isinstance(v, float) and v != v:
                v = "nan"
            snapshot[repr(analyzer)] = v
        statuses = tuple(
            (cr.status.name)
            for cres in result.check_results.values()
            for cr in cres.constraint_results
        )
        return (statuses, snapshot), result

    warm_key, _ = run_once(False)  # warm-up: jit + imports

    off_s = float("inf")
    off_key = None
    for _ in range(reps):
        t0 = time.perf_counter()
        off_key, _ = run_once(False)
        off_s = min(off_s, time.perf_counter() - t0)

    on_s = float("inf")
    on_key = None
    sampled = violations = 0
    for _ in range(reps):
        t0 = time.perf_counter()
        on_key, result = run_once(True)
        on_s = min(on_s, time.perf_counter() - t0)
        report = result.forensics()
        sampled = sum(len(c.samples) for c in report.constraints)
        violations = sum(c.violations_seen for c in report.constraints)

    if not (warm_key == off_key == on_key):
        raise SystemExit(
            "forensics A/B: result mismatch between capture-on and "
            f"capture-off sides\noff: {off_key}\non:  {on_key}"
        )

    overhead_pct = 100.0 * (on_s - off_s) / off_s if off_s > 0 else 0.0
    rec = {
        "metric": "forensics_overhead_pct",
        "value": round(overhead_pct, 1),
        "unit": "%",
        "rows": n_rows,
        "forensics_ab": {
            "off_s": round(off_s, 2),
            "on_s": round(on_s, 2),
            "overhead_pct": round(overhead_pct, 1),
            "rows_per_sec_off": round(n_rows / off_s, 1),
            "rows_per_sec_on": round(n_rows / on_s, 1),
            "violations_seen": violations,
            "rows_sampled": sampled,
            "constraints": 5,
            "failing_constraints": 2,
            "bit_identical": True,
            "reps": reps,
            "passes": (
                "one warm-up (off), then best-of-reps warm-jit timed "
                "passes per side, forensics OFF first"
            ),
        },
    }
    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.path.join(here, "BENCH_FORENSICS.json")
    try:
        with open(out_path) as fh:
            old = json.load(fh)
        for key in ("round", "config"):
            if key in old and key not in rec:
                rec[key] = old[key]
    except Exception:  # noqa: BLE001 - first write: no fields to carry
        pass
    with open(out_path, "w") as fh:
        json.dump(rec, fh)
        fh.write("\n")
    print(
        f"# bench: forensics A/B off={off_s:.2f}s on={on_s:.2f}s "
        f"(+{overhead_pct:.1f}%), {violations} violations seen, "
        f"{sampled} rows sampled; gen={gen_s:.1f}s",
        file=sys.stderr,
    )
    print(json.dumps(rec))


def run_chaos_bench(n_rows: int, reps: int) -> None:
    """BENCH_MODE=chaos: the resilience machinery's clean-path cost and
    its fault-mode correctness (ISSUE 13), on the decode bench's
    50-column wide-stream shape.

    A/B: the identical verification run PLAIN (no controller, chaos
    harness disarmed) vs ARMED — a RunController with a generous
    deadline doing per-batch checks/beats, plus an installed fault plan
    whose rates are all 0.0, so every `fault_point` seam takes the full
    decide() path (lock + counter + hash) without injecting. The armed
    side must stay within 2% of plain (the analytic companion bound
    lives in tests/test_observe_overhead.py).

    Then a seeded FAULT pass: transient pread errors, short reads,
    corrupt pages, decode failures and a stage fault all injected in
    one run — the bench aborts unless statuses and metrics are
    bit-identical to the plain side (containment never changes an
    answer). Refreshes BENCH_CHAOS.json (round/config preserved)."""
    import pyarrow.parquet as pq

    from deequ_tpu.checks.check import Check, CheckLevel
    from deequ_tpu.core.controller import RunController
    from deequ_tpu.data.table import Table
    from deequ_tpu.testing import faults
    from deequ_tpu.verification.suite import VerificationSuite

    path = os.environ.get("BENCH_PARQUET", "/tmp/bench_decode.parquet")
    t_gen = time.perf_counter()
    if not (
        os.path.exists(path) and pq.ParquetFile(path).metadata.num_rows == n_rows
    ):
        write_decode_parquet(n_rows, path)
    gen_s = time.perf_counter() - t_gen

    check = (
        Check(CheckLevel.ERROR, "chaos bench")
        .is_complete("f00")
        .has_min("f01", lambda v: v >= 0.0)
        .has_max("f02", lambda v: v <= 1e6)
        .satisfies("f03 >= 0", "f03 nonneg", lambda r: r >= 0.9)
    )

    def run_once(controller=None):
        builder = (
            VerificationSuite()
            .on_data(Table.scan_parquet(path, batch_rows=1 << 20))
            .add_check(check)
        )
        if controller is not None:
            builder = builder.with_controller(controller)
        result = builder.run()
        snapshot = {}
        for analyzer, metric in result.metrics.items():
            value = metric.value
            v = value.get() if value.is_success else type(value.exception).__name__
            if isinstance(v, float) and v != v:
                v = "nan"
            snapshot[repr(analyzer)] = v
        statuses = tuple(
            (cr.status.name)
            for cres in result.check_results.values()
            for cr in cres.constraint_results
        )
        return (statuses, snapshot)

    warm_key = run_once()  # warm-up: jit + imports

    plain_s = float("inf")
    plain_key = None
    for _ in range(reps):
        t0 = time.perf_counter()
        plain_key = run_once()
        plain_s = min(plain_s, time.perf_counter() - t0)

    # armed-but-quiet: every fault seam decides (rate 0), the controller
    # checks and beats every batch against a deadline that never trips
    quiet_spec = "seed=1," + ",".join(
        f"{point}:0.0" for point in sorted(faults.FAULT_POINTS)
    )
    armed_s = float("inf")
    armed_key = None
    with faults.install(quiet_spec):
        for _ in range(reps):
            t0 = time.perf_counter()
            armed_key = run_once(RunController(deadline_s=3600.0))
            armed_s = min(armed_s, time.perf_counter() - t0)

    # seeded fault pass: inject for real, demand the same bits
    fault_spec = (
        "seed=13,read.pread:0.3:5,read.short:0.3:3,read.corrupt:0.5:2,"
        "decode.chunk:0.5:4,pipeline.stage:1.0:1"
    )
    with faults.install(fault_spec) as plan:
        t0 = time.perf_counter()
        faulted_key = run_once(RunController(deadline_s=3600.0))
        faulted_s = time.perf_counter() - t0
        injected = dict(plan.injected)

    if not (warm_key == plain_key == armed_key == faulted_key):
        raise SystemExit(
            "chaos A/B: result mismatch across plain/armed/faulted sides\n"
            f"plain:   {plain_key}\narmed:   {armed_key}\n"
            f"faulted: {faulted_key}"
        )

    overhead_pct = (
        100.0 * (armed_s - plain_s) / plain_s if plain_s > 0 else 0.0
    )
    rec = {
        "metric": "chaos_overhead_pct",
        "value": round(overhead_pct, 1),
        "unit": "%",
        "rows": n_rows,
        "chaos_ab": {
            "plain_s": round(plain_s, 2),
            "armed_s": round(armed_s, 2),
            "overhead_pct": round(overhead_pct, 1),
            "rows_per_sec_plain": round(n_rows / plain_s, 1),
            "rows_per_sec_armed": round(n_rows / armed_s, 1),
            "bit_identical": True,
            "reps": reps,
            "passes": (
                "one warm-up (plain), then best-of-reps warm-jit timed "
                "passes per side, plain first; armed = RunController "
                "with a 3600s deadline + every fault point at rate 0"
            ),
        },
        "fault_pass": {
            "spec": fault_spec,
            "injected": injected,
            "wall_s": round(faulted_s, 2),
            "bit_identical": True,
        },
    }
    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.path.join(here, "BENCH_CHAOS.json")
    try:
        with open(out_path) as fh:
            old = json.load(fh)
        for key in ("round", "config"):
            if key in old and key not in rec:
                rec[key] = old[key]
    except Exception:  # noqa: BLE001 - first write: no fields to carry
        pass
    with open(out_path, "w") as fh:
        json.dump(rec, fh)
        fh.write("\n")
    total_injected = sum(injected.values())
    print(
        f"# bench: chaos A/B plain={plain_s:.2f}s armed={armed_s:.2f}s "
        f"(+{overhead_pct:.1f}%); fault pass {faulted_s:.2f}s with "
        f"{total_injected} injections, bit-identical; gen={gen_s:.1f}s",
        file=sys.stderr,
    )
    print(json.dumps(rec))


def main() -> None:
    from deequ_tpu.ops import runtime

    runtime.use_compile_cache()
    n_rows = int(os.environ.get("BENCH_ROWS", "10000000"))
    mode = os.environ.get("BENCH_MODE", "profiler")
    reps = max(1, int(os.environ.get("BENCH_TIMED", "5")))
    trace_enabled = "--trace" in sys.argv or os.environ.get(
        "BENCH_TRACE", ""
    ).lower() not in ("", "0", "false")

    if mode == "pushdown":
        # self-contained A/B with its own JSON record and artifact;
        # none of the baseline machinery below applies
        run_pushdown_bench(n_rows)
        return

    if mode == "decode":
        # self-contained A/B with its own JSON record and artifact
        run_decode_bench(n_rows)
        return

    if mode == "wire":
        # self-contained A/B with its own JSON record and artifact
        run_wire_bench(n_rows)
        return

    if mode == "incremental":
        # self-contained A/B with its own JSON record and artifact
        run_incremental_bench(n_rows)
        return

    if mode == "window":
        # self-contained A/B with its own JSON record and artifact
        run_window_bench(n_rows)
        return

    if mode == "reader":
        # self-contained A/B with its own JSON record and artifact
        run_reader_bench(n_rows)
        return

    if mode == "encfold":
        # self-contained A/B with its own JSON record and artifact
        run_encfold_bench(n_rows)
        return

    if mode == "forensics":
        # self-contained A/B with its own JSON record and artifact
        run_forensics_bench(n_rows, reps)
        return

    if mode == "chaos":
        # self-contained A/B with its own JSON record and artifact
        run_chaos_bench(n_rows, reps)
        return

    t_gen = time.perf_counter()
    if mode == "stream":
        import pyarrow.parquet as pq

        from deequ_tpu.data.table import Table

        default_path = (
            "/tmp/bench_wide.parquet"
            if _stream_shape() == "wide"
            else "/tmp/bench.parquet"
        )
        path = os.environ.get("BENCH_PARQUET", default_path)
        if not (
            os.path.exists(path) and pq.ParquetFile(path).metadata.num_rows == n_rows
        ):
            write_parquet(n_rows, path, builder=_builder_for_mode("stream"))
        table = Table.scan_parquet(path)
    elif mode == "wide":
        table = build_wide_table(n_rows)
    elif mode == "lineitem":
        table = build_lineitem_table(n_rows)
    else:
        table = build_table(n_rows)
    gen_s = time.perf_counter() - t_gen

    run = run_scan if mode == "scan" else run_profiler
    if mode == "scan":
        baseline = SPARK_LOCAL_SCAN_ROWS_PER_SEC
        baseline_note = "proxy"
    else:
        # measured denominator (BENCH_BASELINE=proxy skips; a float
        # overrides): single-core pandas/numpy equivalent profile,
        # floored at the documented proxy so a slow box can't inflate
        # the ratio
        baseline_env = os.environ.get("BENCH_BASELINE", "measured")
        if baseline_env == "proxy":
            baseline = SPARK_LOCAL_PROFILE_ROWS_PER_SEC
            baseline_note = "proxy"
        elif baseline_env == "measured":
            measured = _measure_baseline_subprocess(mode)
            if mode in ("wide", "lineitem") or (
                mode == "stream" and _stream_shape() == "wide"
            ):
                # same-shape measured denominator; the 2.0M floor was
                # calibrated for the 6-col table and would be absurdly
                # generous per-row at 16-50 columns
                baseline = measured
                baseline_note = (
                    f"measured same-shape single-core pandas profile "
                    f"{measured / 1e6:.2f}M rows/s (6-col 2.0M floor not "
                    "applied: calibrated for the default shape)"
                )
            else:
                baseline = max(measured, SPARK_LOCAL_PROFILE_ROWS_PER_SEC)
                baseline_note = (
                    f"max(measured best-of(pandas, 1-thread pyarrow Acero) "
                    f"{measured / 1e6:.2f}M rows/s, "
                    f"{SPARK_LOCAL_PROFILE_ROWS_PER_SEC / 1e6:.1f}M proxy; "
                    "Spark-local itself unmeasurable offline: no pyspark/JRE)"
                )
        else:
            baseline = float(baseline_env)
            baseline_note = "override"

    cold = mode == "stream" and os.environ.get("BENCH_COLD", "") in (
        "1",
        "true",
    )
    ab = cold and os.environ.get("BENCH_PIPELINE_AB", "") in ("1", "true")
    extra = {}
    if ab:
        # pipeline A/B: the SAME cold pass twice — fully serial
        # (DEEQU_TPU_PIPELINE=0: synchronous decode, inline prep) vs the
        # staged pipeline — page cache dropped before each so both pay
        # real disk IO. NEITHER timed pass is traced: tracing only the
        # pipelined side was measured as a multi-percent thumb on the
        # scale; the per-stage occupancy instead comes from the traced
        # warm-up pass that runs before the timing. With
        # BENCH_SOURCE_STALL_MS set, a per-row-group source stall
        # (object-store latency model, deequ_tpu.ops.runtime
        # .source_stall_s) applies identically to BOTH sides, measuring
        # how much source wait the pipeline hides.
        from deequ_tpu import observe

        stall_ms = os.environ.get("BENCH_SOURCE_STALL_MS", "")
        if stall_ms:
            os.environ["DEEQU_TPU_SOURCE_STALL_MS"] = stall_ms
        # warm-up pass FIRST (traced, pipelined): compiles every program
        # and pays the one-time imports so neither timed pass rides the
        # other's caches (serial-first was measured gifting the pipelined
        # side ~0.7s of jit/import at 4M rows), and its span tree yields
        # the per-stage occupancy rows. Both timed passes below are
        # warm-jit, cold-IO, untraced.
        with observe.tracing() as tracer:
            run(table)
        occupancy = observe.pipeline_occupancy(tracer.roots)
        os.environ["DEEQU_TPU_PIPELINE"] = "0"
        cache_dropped = _drop_page_cache()
        t0 = time.perf_counter()
        run(table)
        serial_s = time.perf_counter() - t0
        os.environ["DEEQU_TPU_PIPELINE"] = "1"
        _drop_page_cache()
        t0 = time.perf_counter()
        run(table)
        best = time.perf_counter() - t0
        best_cpu = None
        extra["pipeline_ab"] = {
            "serial_s": round(serial_s, 1),
            "pipelined_s": round(best, 1),
            "speedup_pct": round(100.0 * (serial_s - best) / serial_s, 1),
            "page_cache_dropped": cache_dropped,
            **(
                {"source_stall_ms": float(stall_ms)} if stall_ms else {}
            ),
            "occupancy_pass": (
                "from the traced warm-up pass; both timed passes are "
                "warm-jit, cold-IO, untraced"
            ),
            "occupancy": [
                {
                    "stage": row["stage"],
                    "occupancy_pct": round(row["occupancy"] * 100, 1),
                    "busy_s": round(row["busy_s"], 1),
                    "stall_s": round(row["stall_s"], 1),
                    "items": row["items"],
                }
                for row in occupancy
            ],
            "bottleneck": occupancy[0]["stage"] if occupancy else None,
        }
        print(
            f"# bench: pipeline A/B serial={serial_s:.1f}s "
            f"pipelined={best:.1f}s "
            f"(+{100.0 * (serial_s - best) / serial_s:.1f}%), "
            f"bottleneck={extra['pipeline_ab']['bottleneck']}",
            file=sys.stderr,
        )
    elif cold:
        # the BENCH_STREAM_*.json methodology: ONE cold end-to-end pass
        # incl. jit compile; every stream batch decodes fresh either way
        _drop_page_cache()
        t0 = time.perf_counter()
        run(table)
        best = time.perf_counter() - t0
        best_cpu = None
    else:
        # warmup: compiles every (analyzer-set, padded-shape) program
        t_warm = time.perf_counter()
        run(table)
        warm_s = time.perf_counter() - t_warm

        times = []
        cpu_times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            c0 = time.process_time()
            run(table)
            cpu_times.append(time.process_time() - c0)
            times.append(time.perf_counter() - t0)
        best = min(times)
        # CPU-seconds where wall-clock would mislead (shared-vCPU boxes)
        best_cpu = min(cpu_times)
    rows_per_sec = n_rows / best

    # --trace / BENCH_TRACE: one EXTRA traced pass after the timed reps
    # (tracing never overlaps the timed loop, so the headline numbers
    # are identical with and without it); phase self-time buckets from
    # the span tree land in the JSON record next to the trace path
    trace_fields = {}
    if trace_enabled:
        from deequ_tpu import observe

        trace_out = (
            os.environ.get(observe.ENV_OUT, "").strip()
            or observe.default_trace_path()
        )
        with observe.traced_run(
            f"bench_{mode}", enable=trace_out, rows=n_rows
        ) as traced:
            run(table)
        phases = traced.trace.phase_seconds()
        trace_fields = {
            "trace_file": traced.trace.path,
            "trace_phases_s": {
                phase: round(phases.get(phase, 0.0), 4)
                for phase in observe.PHASES
            },
        }

    # /proc-based accounting (observe.telemetry): peak RSS and major
    # page faults come from the process itself, not external measurement
    from deequ_tpu.observe import telemetry

    resources = telemetry.proc_resources()
    peak_rss_mb = resources.get("peak_rss_mb", 0.0)
    if cold:
        extra.update(
            rows=n_rows,
            elapsed_s=round(best, 1),
            peak_rss_mb=round(peak_rss_mb),
            major_faults=int(resources.get("major_faults", 0)),
        )
    # append this run to the engine-telemetry time series so
    # `make sentinel` can watch throughput/phase shares across rounds
    # (BENCH.md). BENCH_ENGINE_REPO overrides the path; 0/off disables.
    engine_repo_env = os.environ.get("BENCH_ENGINE_REPO", "")
    if engine_repo_env.lower() not in ("0", "off", "none"):
        engine_repo_path = engine_repo_env or os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "ENGINE_METRICS.json"
        )
        try:
            from deequ_tpu.repository import engine as engine_telemetry
            from deequ_tpu.repository.fs import FileSystemMetricsRepository

            engine_record = {
                "engine.rows_per_s": rows_per_sec,
                "engine.wall_s": best,
                "engine.rows": float(n_rows),
                "engine.peak_rss_mb": peak_rss_mb,
                "engine.major_faults": resources.get("major_faults", 0.0),
            }
            if best_cpu is not None:
                engine_record["engine.cpu_s"] = best_cpu
            for phase, secs in trace_fields.get("trace_phases_s", {}).items():
                engine_record[f"engine.phase.{phase}_s"] = secs
            engine_telemetry.persist_engine_record(
                FileSystemMetricsRepository(engine_repo_path),
                engine_record,
                engine_telemetry.engine_result_key(
                    suite="bench", dataset=f"{mode}:{n_rows}"
                ),
            )
            print(f"# bench: engine series -> {engine_repo_path}", file=sys.stderr)
        except Exception as e:  # noqa: BLE001 - telemetry must never fail the bench
            print(f"# bench: engine series persist failed: {e}", file=sys.stderr)

    warm_note = "none (single cold pass)" if cold else f"{warm_s:.1f}s"
    print(
        f"# bench: mode={mode}{' (cold)' if cold else ''} rows={n_rows} "
        f"gen={gen_s:.1f}s warmup={warm_note} timed={best:.2f}s "
        f"peak_rss={peak_rss_mb:.0f}MB "
        f"baseline={baseline / 1e6:.2f}M rows/s [{baseline_note}]",
        file=sys.stderr,
    )
    print(
        json.dumps(
            {
                "metric": f"{mode}_rows_per_sec_per_chip",
                "value": round(rows_per_sec, 1),
                "unit": "rows/s",
                "vs_baseline": round(rows_per_sec / baseline, 3),
                **({"cpu_s": round(best_cpu, 3)} if best_cpu is not None else {}),
                **extra,
                **trace_fields,
                "pallas_onchip": pallas_onchip_check(),
                "device": _device_record(),
            }
        )
    )

    # per-round regression loop: the default (headline) run also
    # refreshes the north-star shape artifacts so regressions in wider
    # tables are tracked, not just the 6-col headline. BENCH_SHAPES=0
    # skips.
    if mode == "profiler" and os.environ.get("BENCH_SHAPES", "1") not in (
        "0",
        "false",
    ):
        _refresh_shape_json("wide", 4_000_000, reps)
        _refresh_shape_json("lineitem", 10_000_000, reps)


if __name__ == "__main__":
    if "--measure-baseline" in sys.argv:
        probe_mode = os.environ.get("BENCH_MODE", "profiler")
        wide_shape = probe_mode == "wide" or (
            probe_mode == "stream" and _stream_shape() == "wide"
        )
        probe_rows = 500_000 if wide_shape else 2_000_000
        # best-of-3: the engine side is best-of-N timed reps, so the
        # baseline gets its best box phase too — a single-shot probe on
        # a drifting shared vCPU would randomly deflate the denominator
        # and inflate the ratio
        pandas_rate = max(
            measure_reference_profile_rows_per_sec(probe_rows, mode=probe_mode)
            for _ in range(3)
        )
        arrow_rate = 0.0
        # the Acero probe profiles the fixed 6-col shape: only a valid
        # denominator when that IS the benched shape
        if probe_mode not in ("wide", "lineitem") and not wide_shape:
            for _ in range(3):
                try:
                    arrow_rate = max(
                        arrow_rate, measure_arrow_profile_rows_per_sec()
                    )
                except Exception:  # noqa: BLE001 - acero is best-effort
                    pass  # keep any reps that already succeeded
        print(
            f"# pandas {pandas_rate / 1e6:.2f}M rows/s, "
            f"pyarrow-acero(1 thread) {arrow_rate / 1e6:.2f}M rows/s",
            file=sys.stderr,
        )
        print(max(pandas_rate, arrow_rate))
    else:
        main()

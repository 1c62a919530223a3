#!/usr/bin/env python3
"""Chip smoke: deequ_tpu's main path once on one TPU, checked against numpy.

Runs in ONE process and starts no process that touches JAX. On a seeded
TPC-H lineitem table at SF1 cardinality it runs, through the public API:

  (a) VerificationSuite with one Check over every scan family,
  (b) ColumnProfilerRunner (the three-pass profile),
  (c) the table written to Parquet and streamed through AnalysisRunner,

with placement forced to `device`, and compares every metric with a plain
numpy/pandas computation made here: counts, min and max exactly; mean,
sum, standard deviation and correlation within 1e-6 relative (the parity
target in BASELINE.json); HyperLogLog within 3x its declared relative
standard deviation; KLL quantiles within their declared rank error. It
asserts that the fused device program was dispatched and that the Pallas
kernels were in it.

`--chips 4` runs only the mesh phase: AnalysisRunner with
engine="distributed" over all devices against engine="single" on the same
table, each held to the same reference, and checks from the placed
inputs' shards that every device held an even share of the rows.

The last stdout line is {"ok": true, "device": {...}}. Exits non-zero
without a TPU; there is no CPU fallback.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import traceback

import numpy as np

REL_TOL = 1e-6  # BASELINE.json parity target for exact-arithmetic metrics
# HLL bound: 3x the sketch's declared relative SD (relativeSD=0.05, p=9:
# deequ_tpu/ops/sketches/hll.py, as the reference's default)
HLL_BOUND = 3 * 0.05
NUMERIC = ("l_quantity", "l_extendedprice", "l_discount", "l_tax")
DISTINCT = ("l_orderkey", "l_partkey", "l_suppkey", "l_shipdate", "l_comment")
QUANTILES = (("l_extendedprice", 0.5), ("l_quantity", 0.9))
KEY = ("l_orderkey", "l_linenumber")
PROFILE_KLL_EPS = 0.01  # the profiler's ApproxQuantiles relative_error
CORR = ("l_quantity", "l_extendedprice")
KERNELS = ("hll_register_max", "hist16", "masked_moments", "masked_centered_sumsq")


def log(msg: str) -> None:
    print(msg, flush=True)


def engine(label: str, call):
    """Run one public-API call, logging its wall time apart from the
    reference checks around it."""
    t0 = time.perf_counter()
    out = call()
    log(f"{label}: engine wall {time.perf_counter() - t0:.3f}s")
    return out


# ---------------------------------------------------------------------------
# Reference: plain numpy/pandas over the generator's arrays
# ---------------------------------------------------------------------------


class Reference:
    """Exact answers from the raw columns, computed without deequ_tpu."""

    def __init__(self, cols: dict):
        self.cols = cols
        self.n = len(next(iter(cols.values())))
        self._sorted: dict = {}

    def f64(self, name: str) -> np.ndarray:
        return np.asarray(self.cols[name], dtype=np.float64)

    def completeness(self, name: str) -> float:
        import pandas as pd

        return float(pd.notna(self.cols[name]).sum()) / self.n

    def distinct(self, name: str) -> int:
        import pandas as pd

        return int(pd.unique(self.cols[name]).size)

    def uniqueness(self, names) -> float:
        import pandas as pd

        counts = pd.DataFrame({c: self.cols[c] for c in names}).value_counts()
        return float((counts == 1).sum()) / self.n

    def correlation(self, a: str, b: str) -> float:
        return float(np.corrcoef(self.f64(a), self.f64(b))[0, 1])

    def histogram(self, name: str) -> dict:
        import pandas as pd

        return {
            str(k): int(v) for k, v in pd.Series(self.cols[name]).value_counts().items()
        }

    def rank_window(self, name: str, value: float):
        """Fraction of values strictly below and at or below `value`."""
        if name not in self._sorted:
            self._sorted[name] = np.sort(self.f64(name))
        s = self._sorted[name]
        return (
            np.searchsorted(s, value, "left") / self.n,
            np.searchsorted(s, value, "right") / self.n,
        )


class Scorecard:
    """Worst error per metric family; failures collected, not raised, so
    one run reports every miss."""

    def __init__(self):
        self.worst: dict = {}
        self.failures: list = []

    def _note(self, family: str, what: str, err: float, ok: bool) -> None:
        if err > self.worst.get(family, (-1.0, ""))[0]:
            self.worst[family] = (err, what)
        if not ok:
            self.failures.append(f"{family} {what}: error {err!r}")

    def exact(self, what: str, got, want) -> None:
        err = abs(float(got) - float(want))
        self._note("exact", what, err, float(got) == float(want))

    def rel(self, family: str, what: str, got, want, tol: float = REL_TOL) -> None:
        err = abs(float(got) - float(want)) / max(abs(float(want)), 1e-300)
        self._note(family, what, err, err <= tol)

    def hll(self, what: str, got, want: int) -> None:
        err = abs(float(got) - want) / max(want, 1)
        self._note("hll", what, err, err <= HLL_BOUND)

    def kll(self, what: str, ref: Reference, col: str, q: float, got, eps: float):
        below, at_or_below = ref.rank_window(col, float(got))
        # rank distance from q to the interval of ranks `got` occupies
        err = max(0.0, below - q, q - at_or_below)
        self._note("kll", what, err, err <= eps)

    def report(self, label: str) -> None:
        for family in sorted(self.worst):
            err, what = self.worst[family]
            log(f"{label}: worst {family} error {err!r} ({what})")


def check_metric(card: Scorecard, ref: Reference, analyzer, metric) -> None:
    """Hold one analyzer's metric to the reference by its family."""
    from deequ_tpu.analyzers import (
        ApproxCountDistinct,
        ApproxQuantile,
        Completeness,
        Correlation,
        Maximum,
        Mean,
        Minimum,
        Size,
        StandardDeviation,
        Sum,
        Uniqueness,
    )

    what = repr(analyzer)
    if not metric.value.is_success:
        card.failures.append(f"{what}: {metric.value}")
        return
    got = metric.value.get()
    a = analyzer
    if isinstance(a, Size):
        card.exact(what, got, ref.n)
    elif isinstance(a, Completeness):
        card.exact(what, got, ref.completeness(a.column))
    elif isinstance(a, Uniqueness):
        card.exact(what, got, ref.uniqueness(a.columns))
    elif isinstance(a, Minimum):
        card.exact(what, got, ref.f64(a.column).min())
    elif isinstance(a, Maximum):
        card.exact(what, got, ref.f64(a.column).max())
    elif isinstance(a, Mean):
        card.rel("moments", what, got, ref.f64(a.column).mean())
    elif isinstance(a, Sum):
        card.rel("moments", what, got, ref.f64(a.column).sum())
    elif isinstance(a, StandardDeviation):
        card.rel("moments", what, got, ref.f64(a.column).std())
    elif isinstance(a, Correlation):
        card.rel("correlation", what, got, ref.correlation(a.first_column, a.second_column))
    elif isinstance(a, ApproxCountDistinct):
        card.hll(what, got, ref.distinct(a.column))
    elif isinstance(a, ApproxQuantile):
        card.kll(what, ref, a.column, a.quantile, got, a.relative_error)
    else:
        raise TypeError(f"no reference for {what}")


def scan_analyzers() -> list:
    """One analyzer of each scan family over the lineitem columns."""
    from deequ_tpu.analyzers import (
        ApproxCountDistinct,
        ApproxQuantile,
        Completeness,
        Correlation,
        Maximum,
        Mean,
        Minimum,
        Size,
        StandardDeviation,
        Sum,
        Uniqueness,
    )

    out = [Size(), Completeness("l_orderkey"), Completeness("l_comment")]
    for c in NUMERIC:
        out += [Mean(c), Sum(c), StandardDeviation(c), Minimum(c), Maximum(c)]
    out.append(Correlation(*CORR))
    out += [ApproxCountDistinct(c) for c in DISTINCT]
    out += [ApproxQuantile(c, q) for c, q in QUANTILES]
    out.append(Uniqueness(list(KEY)))
    return out


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def phase_suite(table, ref: Reference, card: Scorecard) -> None:
    from deequ_tpu import Check, CheckLevel, CheckStatus, VerificationSuite

    check = Check(CheckLevel.ERROR, "lineitem SF1")
    for c in ref.cols:
        check = check.is_complete(c)
    for c in NUMERIC:
        x = ref.f64(c)
        check = (
            check.has_mean(c, lambda v, w=x.mean(): abs(v - w) <= REL_TOL * abs(w))
            .has_standard_deviation(
                c, lambda v, w=x.std(): abs(v - w) <= REL_TOL * abs(w)
            )
            .has_min(c, lambda v, w=x.min(): v == w)
            .has_max(c, lambda v, w=x.max(): v == w)
        )
    check = check.has_correlation(*CORR, lambda v: -1.0 <= v <= 1.0)
    for c in DISTINCT:
        check = check.has_approx_count_distinct(c, lambda v: v > 0)
    for c, q in QUANTILES:
        check = check.has_approx_quantile(c, q, lambda v: np.isfinite(v))
    check = check.is_primary_key(*KEY)
    result = engine(
        "suite", lambda: VerificationSuite().on_data(table).add_check(check).run()
    )
    for analyzer, metric in result.metrics.items():
        check_metric(card, ref, analyzer, metric)
    if result.status != CheckStatus.SUCCESS:
        failed = [
            str(r.constraint)
            for cr in result.check_results.values()
            for r in cr.constraint_results
            if r.status.name != "SUCCESS"
        ]
        card.failures.append(f"suite status {result.status}: {failed}")
    log(f"suite: {len(result.metrics)} metrics, status {result.status.name}")


def phase_profiler(table, ref: Reference, card: Scorecard) -> None:
    from deequ_tpu.profiles.column_profile import NumericColumnProfile
    from deequ_tpu.profiles.runner import ColumnProfilerRunner

    profiles = engine("profiler", lambda: ColumnProfilerRunner.on_data(table).run())
    if profiles.num_records != ref.n:
        card.failures.append(f"profiler num_records {profiles.num_records}")
    for name, p in profiles.profiles.items():
        card.exact(f"profile {name} completeness", p.completeness, ref.completeness(name))
        card.hll(
            f"profile {name} distinct", p.approximate_num_distinct_values,
            ref.distinct(name),
        )
        if p.histogram is not None:
            want = ref.histogram(name)
            got = {k: int(v.absolute) for k, v in p.histogram.values.items()}
            if isinstance(p, NumericColumnProfile):  # keys formatted by type
                want = {float(k): v for k, v in want.items()}
                got = {float(k): v for k, v in got.items()}
            differ = set(got.items()) ^ set(want.items())
            card.exact(f"profile {name} histogram entries that differ", len(differ), 0)
        if isinstance(p, NumericColumnProfile):
            x = ref.f64(name)
            card.exact(f"profile {name} min", p.minimum, x.min())
            card.exact(f"profile {name} max", p.maximum, x.max())
            card.rel("moments", f"profile {name} mean", p.mean, x.mean())
            card.rel("moments", f"profile {name} sum", p.sum, x.sum())
            card.rel("moments", f"profile {name} std_dev", p.std_dev, x.std())
            for i, v in enumerate(p.approx_percentiles or ()):
                q = (i + 1) / len(p.approx_percentiles)
                card.kll(f"profile {name} p{i + 1}", ref, name, q, v, PROFILE_KLL_EPS)
    log(f"profiler: {len(profiles.profiles)} column profiles")


def phase_parquet(table, ref: Reference, card: Scorecard, out_dir: str) -> None:
    from deequ_tpu import Table
    from deequ_tpu.runners.analysis_runner import AnalysisRunner

    path = os.path.join(out_dir, "lineitem.parquet")
    t0 = time.perf_counter()
    table.to_parquet(path, row_group_size=1 << 20)
    log(
        f"parquet: wrote {os.path.getsize(path)} bytes in "
        f"{time.perf_counter() - t0:.3f}s (set-up, not timed below)"
    )
    analyzers = scan_analyzers()
    ctx = engine(
        "parquet",
        lambda: AnalysisRunner.on_data(Table.scan_parquet(path))
        .add_analyzers(analyzers)
        .run(),
    )
    for analyzer in analyzers:
        check_metric(card, ref, analyzer, ctx.metric_map[analyzer])
    log(f"parquet: {len(analyzers)} metrics from the streamed scan")


def phase_mesh(table, ref: Reference, card: Scorecard, devices) -> None:
    from deequ_tpu.ops import runtime
    from deequ_tpu.parallel import data_mesh
    from deequ_tpu.runners.analysis_runner import AnalysisRunner

    analyzers = scan_analyzers()
    with runtime.monitored() as placed:
        ctx_d = engine(
            f"mesh distributed x{len(devices)}",
            lambda: AnalysisRunner.on_data(table)
            .add_analyzers(analyzers)
            .with_engine("distributed", data_mesh(devices))
            .run(),
        )
    # the sharded inputs' own shard indices: n rows spread as n/d rows on
    # each device, never whole on one device or replicated on all
    held = [placed.device_rows.get(str(d.id), 0) for d in devices]
    share = placed.placed_rows / len(devices)
    log(
        f"mesh: rows of sharded inputs held per device {held} of "
        f"{placed.placed_rows} placed (even share {share:g})"
    )
    if placed.placed_rows <= 0 or any(h != share for h in held):
        card.failures.append(f"mesh inputs not spread evenly over the devices: {held}")
    ctx_s = engine(
        "mesh single",
        lambda: AnalysisRunner.on_data(table)
        .add_analyzers(analyzers)
        .with_engine("single")
        .run(),
    )
    single, dist = Scorecard(), Scorecard()
    for analyzer in analyzers:
        check_metric(single, ref, analyzer, ctx_s.metric_map[analyzer])
        check_metric(dist, ref, analyzer, ctx_d.metric_map[analyzer])
    single.report("mesh single")
    dist.report(f"mesh distributed x{len(devices)}")
    card.failures += [f"single: {f}" for f in single.failures]
    card.failures += [f"distributed: {f}" for f in dist.failures]
    diffs = 0
    for analyzer in analyzers:
        a = ctx_s.metric_map[analyzer].value.get()
        b = ctx_d.metric_map[analyzer].value.get()
        diffs += a != b
    log(f"mesh: {diffs}/{len(analyzers)} metrics differ bitwise between engines")


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


class CompileClock:
    """Seconds JAX spent getting programs compiled (a compile, or a load
    from the persistent cache), and how many of its cache lookups hit,
    from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        self.lookups = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.lookups += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def run_phase(name: str, clock: CompileClock, card: Scorecard, call) -> dict:
    """Run one phase under the engine's counters; a phase that raises is
    a failure, and the next phase still runs."""
    from deequ_tpu.ops import runtime

    c0, n0 = clock.seconds, clock.count
    failed = len(card.failures)
    t0 = time.perf_counter()
    with runtime.monitored() as stats:
        try:
            call()
        except Exception:  # noqa: BLE001 - report, then run the next phase
            log(traceback.format_exc())
            card.failures.append(f"{name} raised (traceback above)")
    log(
        f"{name}: phase wall {time.perf_counter() - t0:.3f}s (engine + "
        f"reference checks), compile {clock.seconds - c0:.3f}s "
        f"({clock.count - n0} programs), device passes {stats.device_passes}, "
        f"device dispatches {stats.device_launches}, "
        f"kernels {dict(sorted(stats.kernel_traces.items()))}"
    )
    # a failed device program fails every metric in it: one short line each
    for f in card.failures[failed:]:
        log(f"{name}: FAIL {f[:300]}")
    return {"dispatches": stats.device_launches, "kernels": stats.kernel_traces}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--chips", type=int, default=1, choices=(1, 4),
        help="4: run only the mesh phase against the single-device engine",
    )
    parser.add_argument(
        "--out",
        default=os.path.join(os.path.dirname(os.path.abspath(__file__)), ".chip_smoke"),
        help="scratch directory for the Parquet phase (emptied at the end)",
    )
    args = parser.parse_args(argv)

    import jax

    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {devices}", file=sys.stderr)
        return 2
    return smoke(args, devices[: args.chips])


def smoke(args, devices) -> int:
    """Every phase on `devices`; the last stdout line on success."""
    import jax

    from deequ_tpu import Table
    from deequ_tpu.ops import native, runtime
    from deequ_tpu.testing.tpch import SF1_ROWS, lineitem_columns

    log(f"compile cache: {runtime.use_compile_cache()}")
    clock = CompileClock()
    device = devices[0]
    log(
        f"device: {device.platform} {device.device_kind} x{len(jax.devices())}, "
        f"native.available() {native.available()}"
    )
    log(
        f"data: TPC-H lineitem, 16 columns, {SF1_ROWS} rows, seed {args.seed} "
        "(BASELINE.json config 3 is 100M rows; cut to SF1 for the run's "
        "time limit)"
    )
    t0 = time.perf_counter()
    cols = lineitem_columns(SF1_ROWS, args.seed)
    table = Table.from_numpy(cols)
    ref = Reference(cols)
    log(f"data: generated in {time.perf_counter() - t0:.3f}s (set-up)")

    os.environ.pop("DEEQU_TPU_PLACEMENT", None)
    bandwidth = runtime.measure_device_bandwidth()
    log(
        f"placement: auto chose {runtime.placement_mode()}; probe measured "
        f"{bandwidth:.6g} B/s ({runtime.placement_for_bandwidth(bandwidth)})"
    )
    os.environ["DEEQU_TPU_PLACEMENT"] = "device"
    log("placement: forced to device for every phase below")

    card = Scorecard()
    if args.chips == 4:
        run_phase("mesh", clock, card, lambda: phase_mesh(table, ref, card, devices))
    else:
        os.makedirs(args.out, exist_ok=True)
        try:
            seen = [
                run_phase("suite", clock, card, lambda: phase_suite(table, ref, card)),
                run_phase(
                    "profiler", clock, card, lambda: phase_profiler(table, ref, card)
                ),
                run_phase(
                    "parquet",
                    clock,
                    card,
                    lambda: phase_parquet(table, ref, card, args.out),
                ),
            ]
        finally:
            shutil.rmtree(args.out, ignore_errors=True)
        for name, s in zip(("suite", "profiler", "parquet"), seen):
            if s["dispatches"] <= 0:
                card.failures.append(f"{name}: no device dispatch")
        for kernel in KERNELS:
            if not any(s["kernels"].get(kernel) for s in seen):
                card.failures.append(f"Pallas kernel {kernel} never in a program")
    card.report("all phases")
    log(
        f"compile: {clock.seconds:.3f}s over {clock.count} programs; "
        f"{clock.hits} of {clock.lookups} persistent-cache lookups hit"
    )
    if card.failures:
        log(f"{len(card.failures)} failures")
        return 1
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": device.platform,
                    "kind": device.device_kind,
                    "count": len(devices),
                },
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())

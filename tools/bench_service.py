#!/usr/bin/env python
"""Service scheduling benchmark: interactive latency under heavy load.

The fleet claim under test (ISSUE 14): with ONE worker fully occupied
by a heavy partitioned profile, interactive checks submitted against
the same service must see p99 latency within 2x of their solo p99 —
because every interactive arrival preempts the heavy run at its next
partition boundary (DQ405), runs immediately, and the heavy run
resumes from its committed partition states instead of restarting.

Two phases over the same interactive workload:

  solo        — K interactive submissions on an idle service;
  concurrent  — the same K submissions while a heavy profile scans a
                BENCH_SERVICE_ROWS-row partitioned dataset on the same
                single worker.

The heavy run must COMPLETE (from committed states — its preemption
count and final cached-partition split are recorded), and the ratio
concurrent_p99 / solo_p99 must be <= 2.0 for the bench to pass.

Writes BENCH_SERVICE.json to the repo root and prints it to stdout.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

N_PARTITIONS = 128
INTERACTIVE_RUNS = 20
# a realistic interactive check reads ~500k rows from parquet (file
# open included — that's what a user-facing check does); an in-memory
# toy probe would make ANY partition-boundary wait look like a
# violation
INTERACTIVE_ROWS = 524288
RATIO_BUDGET = 2.0


def build_partition(rows: int, seed: int):
    import numpy as np

    from deequ_tpu.data.table import Table

    rng = np.random.default_rng(seed)
    x = rng.normal(10.0, 3.0, rows)
    y = rng.uniform(0.0, 100.0, rows)
    g = rng.integers(0, 50, rows).astype(np.float64)
    return Table.from_pydict({"x": x, "y": y, "g": g})


def heavy_check():
    from deequ_tpu import Check, CheckLevel

    return (
        Check(CheckLevel.ERROR, "heavy-profile")
        .has_size(lambda s: s > 0)
        .is_complete("x")
        .has_mean("x", lambda m: 5.0 < m < 15.0)
        .has_standard_deviation("x", lambda s: s > 0)
        .is_complete("y")
        .has_mean("y", lambda m: m > 0)
    )


def interactive_check():
    from deequ_tpu import Check, CheckLevel

    return (
        Check(CheckLevel.ERROR, "interactive")
        .has_size(lambda s: s > 0)
        .is_complete("x")
        .has_mean("x", lambda m: 5.0 < m < 15.0)
    )


def percentile(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def run_interactive_round(svc, table, tag):
    # two untimed warmups so kernel compilation doesn't masquerade as
    # scheduling latency in either phase
    for i in range(2):
        h = svc.submit(
            "interactive-tenant", f"{tag}-warm-{i}", table,
            checks=[interactive_check()],
        )
        if not h.wait(timeout=300) or h.status != "done":
            raise SystemExit(f"bench_service: warmup {tag}-{i} failed")
    latencies = []
    for i in range(INTERACTIVE_RUNS):
        t0 = time.monotonic()
        h = svc.submit(
            "interactive-tenant", f"{tag}-{i}", table,
            checks=[interactive_check()],
        )
        if not h.wait(timeout=300):
            raise SystemExit(f"bench_service: interactive run {tag}-{i} hung")
        if h.status != "done":
            raise SystemExit(
                f"bench_service: interactive run {tag}-{i} "
                f"ended {h.status}: {h.reason}"
            )
        latencies.append(time.monotonic() - t0)
    return sorted(latencies)


def main() -> int:
    from deequ_tpu.data.table import Table
    from deequ_tpu.lint.explain import explain_plan
    from deequ_tpu.ops.runtime import use_compile_cache
    from deequ_tpu.repository.states import FileSystemStateRepository
    from deequ_tpu.service import DQService

    use_compile_cache()
    total_rows = int(os.environ.get("BENCH_SERVICE_ROWS", "2000000"))
    rows_per_part = max(1, total_rows // N_PARTITIONS)

    work = tempfile.mkdtemp(prefix="bench_service_")
    try:
        data_dir = os.path.join(work, "dataset")
        os.makedirs(data_dir)
        for i in range(N_PARTITIONS):
            build_partition(rows_per_part, seed=100 + i).to_parquet(
                os.path.join(data_dir, f"part-{i:03d}.parquet"),
                row_group_size=max(4096, rows_per_part // 4),
            )

        def heavy_data():
            return Table.scan_parquet_dataset(data_dir)

        # classify the bench dataset as heavy regardless of machine-
        # sized defaults: pin both tier boundaries around its predicted
        # scan (the operator override the tier doc describes). The
        # interactive probes predict ~3 orders of magnitude less and
        # stay interactive under the lowered boundary.
        predicted = explain_plan(
            heavy_data(), checks=[heavy_check()]
        ).cost.predicted_scan_bytes
        os.environ["DEEQU_TPU_TIER_INTERACTIVE_BYTES"] = str(
            max(1.0, predicted * 0.25)
        )
        os.environ["DEEQU_TPU_TIER_HEAVY_BYTES"] = str(max(1.0, predicted * 0.5))

        inter_path = os.path.join(work, "interactive.parquet")
        build_partition(INTERACTIVE_ROWS, seed=1).to_parquet(
            inter_path, row_group_size=INTERACTIVE_ROWS // 4
        )

        def inter_table():
            return Table.scan_parquet(inter_path)

        # -- phase 1: solo ---------------------------------------------------
        with DQService(workers=1) as svc:
            solo = run_interactive_round(svc, inter_table, "solo")

        # -- phase 2: concurrent with a heavy profile ------------------------
        repo = FileSystemStateRepository(os.path.join(work, "states"))
        with DQService(workers=1, state_repository=repo) as svc:
            heavy = svc.submit(
                "batch-tenant", "big", heavy_data, checks=[heavy_check()]
            )
            if heavy.tier != "heavy":
                raise SystemExit(
                    f"bench_service: dataset classified {heavy.tier}, "
                    "expected heavy"
                )
            deadline = time.monotonic() + 120
            while heavy.status != "running" and time.monotonic() < deadline:
                time.sleep(0.005)

            concurrent = run_interactive_round(svc, inter_table, "conc")

            if not heavy.wait(timeout=1800):
                raise SystemExit("bench_service: heavy profile never finished")
            if heavy.status != "done":
                raise SystemExit(
                    f"bench_service: heavy profile ended "
                    f"{heavy.status}: {heavy.reason}"
                )
            preemptions = heavy.preemptions
            attempts = heavy.attempts

        solo_p99 = percentile(solo, 0.99)
        conc_p99 = percentile(concurrent, 0.99)
        ratio = conc_p99 / solo_p99 if solo_p99 > 0 else float("inf")

        record = {
            "bench": "service",
            "rows": rows_per_part * N_PARTITIONS,
            "partitions": N_PARTITIONS,
            "interactive_runs": INTERACTIVE_RUNS,
            "interactive_rows": INTERACTIVE_ROWS,
            "solo_p50_s": round(percentile(solo, 0.5), 4),
            "solo_p99_s": round(solo_p99, 4),
            "concurrent_p50_s": round(percentile(concurrent, 0.5), 4),
            "concurrent_p99_s": round(conc_p99, 4),
            "p99_ratio": round(ratio, 3),
            "ratio_budget": RATIO_BUDGET,
            "heavy_completed": True,
            "heavy_preemptions": preemptions,
            "heavy_attempts": attempts,
            "predicted_heavy_scan_bytes": round(predicted, 0),
        }
        out_path = os.path.join(REPO, "BENCH_SERVICE.json")
        with open(out_path, "w", encoding="utf-8") as fh:
            json.dump(record, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(json.dumps(record, indent=2, sort_keys=True))

        if ratio > RATIO_BUDGET:
            print(
                f"bench_service: FAILED — concurrent p99 {conc_p99:.3f}s is "
                f"{ratio:.2f}x solo p99 {solo_p99:.3f}s (budget "
                f"{RATIO_BUDGET}x)",
                file=sys.stderr,
            )
            return 1
        return 0
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())

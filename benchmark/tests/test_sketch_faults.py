"""A KLL wrong at any size makes `correct` false in every cell: the fault
that gives `kll_rank_err` its upper reading at the cells' own sizes on
the chip, where a sketch sized for ten times its error (test_faults.py's
small sketch) can still answer within 0.01 on SF1 tables."""

import pytest


def _tiny_sketch(monkeypatch):
    """KLL, and with it the device sample, sized for forty times the
    declared rank error."""
    from deequ_tpu.analyzers import sketch

    real = sketch.k_for_error
    monkeypatch.setattr(sketch, "k_for_error", lambda e: real(e * 40))


@pytest.mark.parametrize("cell, rows", [
    ("lineitem-sf1-suite", 20_000),
    ("lineitem-sf1-parquet", 20_000),
    ("lineitem-daily-gate", 59_986_052),
    ("lineitem-daily-incremental", 59_986_052),
])
def test_tiny_sketch_is_not_correct(run_cell, monkeypatch, cell, rows):
    _tiny_sketch(monkeypatch)
    line, card = run_cell(cell, rows)
    assert not line["correct"]
    assert any(f.startswith("kll_rank_err") for f in card.failures), card.failures[:3]

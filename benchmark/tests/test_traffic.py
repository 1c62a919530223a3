"""CPU rehearsals of each traffic driver through the whole harness (set-up,
window, reference), at tiny sizes: both loops run, `attempted` and
`failed` count calls, and the reference agrees with the program."""

import pytest

CELLS = [
    ("lineitem-sf1-suite", 20_000),
    ("lineitem-sf1-parquet", 20_000),
    ("lineitem-daily-gate", 59_986_052),  # 4 days of about 25.5k rows
]


@pytest.mark.parametrize("cell, rows", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_is_correct(run_cell, cell, rows, trace):
    line, card = run_cell(cell, rows, trace=trace)
    assert line["correct"], card.failures
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert card.compared > 0
    want = {"lineitem-daily-gate": {"verdict_p50_ms", "verdict_p95_ms", "setup_s"}}
    if not trace:
        assert set(line["metrics"]) == want.get(cell, {"rows_per_s", "setup_s"})
    else:
        assert line["metrics"] and "busy_s" in line["device"]
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(line)[-1] == "compared"


def test_partition_chain_wraps_around_the_pool(run_cell):
    line, card = run_cell("lineitem-daily-gate", 59_986_052, days=2, seconds=1.5)
    assert line["correct"], card.failures
    assert line["attempted"] + 4 > 2  # warm-up verdicts plus the window's

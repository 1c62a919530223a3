"""Faults of the program that keep a cell out of BENCHMARK.json (PERF.md,
Open questions), pinned so that the PR that mends one sees it here."""

import numpy as np
import pytest

SEED = 4_100_000_010  # the seed on which the chip run found it (PR 22)


def _rank_error(sorted_values, v, q=0.5):
    n = len(sorted_values)
    below = np.searchsorted(sorted_values, v, "left") / n
    at_or_below = np.searchsorted(sorted_values, v, "right") / n
    return max(0.0, below - q, q - at_or_below)


@pytest.fixture(scope="module")
def days():
    from benchmark.data import tpch

    sizes = tpch.day_sizes(59_986_052, 256)
    return [tpch.lineitem_day(int(sizes[d]), SEED, d, 10.0)["l_extendedprice"]
            for d in range(7)]


def _quantile(table, loader=None, persister=None):
    from deequ_tpu.analyzers import ApproxQuantile
    from deequ_tpu.runners.analysis_runner import AnalysisRunner

    a = ApproxQuantile("l_extendedprice", 0.5)
    b = AnalysisRunner.on_data(table).add_analyzers([a])
    if loader is not None:
        b = b.aggregate_with(loader)
    if persister is not None:
        b = b.save_states_with(persister)
    return b.run().metric_map[a].value.get()


def _table(x):
    from benchmark.data import tpch
    from deequ_tpu import Table

    return Table.from_arrow(tpch.to_arrow({"l_extendedprice": x}))


def test_one_pass_quantile_keeps_its_declared_error(days):
    """The witness: the same rows, in the order the days land, in one pass."""
    rows = np.concatenate(days)
    assert _rank_error(np.sort(rows), _quantile(_table(rows))) <= 0.01


@pytest.mark.xfail(strict=True, reason="KLL states merged day by day exceed the "
                   "declared rank error 0.01 (0.0103 on this seed at the 7th day)")
def test_incremental_quantile_keeps_its_declared_error(days):
    from deequ_tpu.analyzers.state_provider import InMemoryStateProvider

    prev = None
    for d in days:
        new = InMemoryStateProvider()
        v = _quantile(_table(d), prev, new)
        prev = new
    assert _rank_error(np.sort(np.concatenate(days)), v) <= 0.01

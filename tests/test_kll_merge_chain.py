"""KLL states merged day after day keep the declared rank error.

An incremental deployment folds each landing day into a partial (the
fused pass's sorted, stride-decimated sample at one level, handed to
`host_consume`) and merges it into the stored cumulative state: one query
per day, for as many days as the table lives. The declared
`relative_error` (0.01) has to hold at every one of them, with margin.
"""

import numpy as np
import pytest

from deequ_tpu.analyzers import ApproxQuantile

DAY_ROWS = 25_504  # a TPC-H SF10 ship date: 59,986,052 rows over 2,352 days
DAYS = 64
WORST = 0.007  # the margin asked of relative_error 0.01


def _day(rng, n):
    """lineitem-shaped columns: quantity 1-50, extended price = quantity x
    a part price in 900.00-1099.99."""
    quantity = rng.integers(1, 51, n).astype(np.float64)
    return {
        "l_quantity": quantity,
        "l_extendedprice": quantity * (rng.integers(90_000, 110_000, n) / 100.0),
    }


def _partial(analyzer, x):
    """The fused pass's partial for one day-sized batch: the sorted sample
    at the decimation ranks of its level, folded by `host_consume`."""
    n = len(x)
    level = max(0, int(np.ceil(np.log2(n / analyzer._sample_size()))))
    sample = np.sort(x)[analyzer._ranks(n, level)]
    out = {"sample": sample, "n": np.asarray([n], np.float64),
           "level": np.asarray([level], np.int32)}
    return analyzer.host_consume(None, out)


def _rank_error(days_sorted, value, q):
    n = sum(len(d) for d in days_sorted)
    below = sum(np.searchsorted(d, value, "left") for d in days_sorted) / n
    at_or_below = sum(np.searchsorted(d, value, "right") for d in days_sorted) / n
    return max(0.0, below - q, q - at_or_below)


@pytest.mark.parametrize("seed", range(8))
def test_merge_chain_keeps_the_declared_rank_error(seed):
    rng = np.random.default_rng([0xC4A1, seed])
    analyzers = {
        "l_extendedprice": ApproxQuantile("l_extendedprice", 0.5),
        "l_quantity": ApproxQuantile("l_quantity", 0.9),
    }
    state = {c: None for c in analyzers}
    seen = {c: [] for c in analyzers}
    worst = 0.0
    for _ in range(DAYS):
        day = _day(rng, DAY_ROWS + int(rng.integers(-300, 300)))
        for column, a in analyzers.items():
            partial = _partial(a, day[column])
            state[column] = partial if state[column] is None else state[column].merge(partial)
            seen[column].append(np.sort(day[column]))
            got = a.compute_metric_from(state[column]).value.get()
            worst = max(worst, _rank_error(seen[column], got, a.quantile))
    assert worst <= WORST


def test_witness_chain_through_the_runner():
    """Seed 4,100,000,010, days 0..6 of the daily cells' generator: merged
    day by day through AnalysisRunner and an InMemoryStateProvider, the
    median of l_extendedprice read rank error 0.01032 with eager
    compaction at k = 2.3/eps."""
    from benchmark.data import tpch
    from deequ_tpu import Table
    from deequ_tpu.analyzers.state_provider import InMemoryStateProvider
    from deequ_tpu.runners.analysis_runner import AnalysisRunner

    sizes = tpch.day_sizes(59_986_052, 256)
    days = [tpch.lineitem_day(int(sizes[d]), 4_100_000_010, d, 10.0)["l_extendedprice"]
            for d in range(7)]
    a = ApproxQuantile("l_extendedprice", 0.5)
    prev = None
    for d in days:
        runner = AnalysisRunner.on_data(
            Table.from_arrow(tpch.to_arrow({"l_extendedprice": d}))
        ).add_analyzers([a])
        new = InMemoryStateProvider()
        if prev is not None:
            runner = runner.aggregate_with(prev)
        got = runner.save_states_with(new).run().metric_map[a].value.get()
        prev = new
    assert _rank_error([np.sort(np.concatenate(days))], got, 0.5) <= WORST


def test_compiled_plan_key_follows_the_sample_size(monkeypatch):
    """The fused program traces the quantile sample size in, and that size
    follows the KLL sizing rather than the analyzer's fields: a program
    compiled for one size is not reused for another."""
    from deequ_tpu.analyzers import sketch
    from deequ_tpu.ops.fused import plan_shape_key

    analyzers = [ApproxQuantile("l_quantity", 0.9)]
    before = plan_shape_key(analyzers)
    assert plan_shape_key(analyzers) == before
    monkeypatch.setattr(sketch, "k_for_error", lambda e: 8)
    assert plan_shape_key(analyzers) != before

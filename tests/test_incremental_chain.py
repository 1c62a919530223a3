"""Day-by-day incremental verification against one pass over every day.

Each landing day is verified with `aggregate_with(<yesterday's states>)`
and `save_states_with(<today's states>)` (upstream's
IncrementalMetricsExample): through a `FileSystemStateProvider` that a
fresh object reads back from disk each day, and through an
`InMemoryStateProvider`. The merged metrics of day k answer for days
0..k: exact metrics exactly, moments within 1e-9, sketches within their
declared bounds.
"""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from deequ_tpu import Check, CheckLevel, Table, VerificationSuite
from deequ_tpu.analyzers import (
    ApproxCountDistinct,
    ApproxQuantile,
    Completeness,
    Maximum,
    Mean,
    Minimum,
    Size,
    StandardDeviation,
)
from deequ_tpu.analyzers.state_provider import (
    FileSystemStateProvider,
    InMemoryStateProvider,
)

DAYS = 12


def _day(d):
    rng = np.random.default_rng([77, d])
    n = 1_500 + 97 * d
    return {
        "key": rng.integers(1, 40_000, n),
        "qty": rng.integers(1, 51, n),
        "price": rng.integers(90_000, 110_000, n) / 100.0,
    }


@pytest.fixture(scope="module")
def days(tmp_path_factory):
    folder = tmp_path_factory.mktemp("days")
    out = []
    for d in range(DAYS):
        cols = _day(d)
        path = str(folder / f"day-{d:02d}.parquet")
        pq.write_table(pa.table(cols), path)
        out.append((path, cols))
    return out


def _check():
    return (
        Check(CheckLevel.ERROR, "incremental")
        .has_size(lambda v: v >= 1)
        .is_complete("key")
        .has_mean("price", lambda v: v > 0)
        .has_standard_deviation("qty", lambda v: v >= 0)
        .has_min("price", lambda v: v >= 900)
        .has_max("qty", lambda v: v <= 50)
        .has_approx_count_distinct("key", lambda v: v >= 1)
        .has_approx_quantile("price", 0.5, lambda v: v > 0)
    )


def _expected(cols):
    key, qty, price = cols["key"], cols["qty"].astype(float), cols["price"]
    return {
        Size(): float(len(key)),
        Completeness("key"): 1.0,
        Mean("price"): float(np.mean(price)),
        StandardDeviation("qty"): float(np.std(qty)),
        Minimum("price"): float(np.min(price)),
        Maximum("qty"): float(np.max(qty)),
        ApproxCountDistinct("key"): float(np.unique(key).size),
    }


def _hold(metrics, cols):
    for analyzer, want in _expected(cols).items():
        got = metrics[analyzer].value.get()
        if isinstance(analyzer, (Mean, StandardDeviation)):
            assert abs(got - want) <= 1e-9 * abs(want), analyzer
        elif isinstance(analyzer, ApproxCountDistinct):
            assert abs(got - want) / want <= 0.15, analyzer  # 3 x RSD 0.05
        else:
            assert got == want, analyzer
    q = metrics[ApproxQuantile("price", 0.5)].value.get()
    s = np.sort(cols["price"])
    below, at = np.searchsorted(s, q, "left") / len(s), np.searchsorted(s, q, "right") / len(s)
    assert max(0.0, below - 0.5, 0.5 - at) <= 0.01


def _concat(days, upto):
    return {c: np.concatenate([cols[c] for _, cols in days[: upto + 1]])
            for c in days[0][1]}


def test_file_state_chain_equals_one_pass(days, tmp_path):
    for k, (path, _cols) in enumerate(days):
        suite = VerificationSuite().on_data(Table.scan_parquet(path)).add_check(_check())
        if k:
            suite = suite.aggregate_with(
                FileSystemStateProvider(str(tmp_path / f"chain-{k - 1:04d}")))
        result = suite.save_states_with(
            FileSystemStateProvider(str(tmp_path / f"chain-{k:04d}"))).run()
        assert result.status.name == "SUCCESS"
        _hold(result.metrics, _concat(days, k))


def test_in_memory_state_chain_equals_one_pass(days):
    prev = None
    for k, (path, _cols) in enumerate(days):
        suite = VerificationSuite().on_data(Table.scan_parquet(path)).add_check(_check())
        if prev is not None:
            suite = suite.aggregate_with(prev)
        prev = InMemoryStateProvider()
        result = suite.save_states_with(prev).run()
        _hold(result.metrics, _concat(days, k))

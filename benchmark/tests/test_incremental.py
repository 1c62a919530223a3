"""The incremental cell: its cumulative reference against `Reference` over
the concatenated days, its runs through the whole harness, and `correct`
false for each fault planted around `incremental_loop` and for the
control."""

import math

import numpy as np
import pytest

from benchmark.tests.test_faults import _small_sketch

CELL = "lineitem-daily-incremental"
SF10 = 59_986_052  # days of about 25.5k rows
WITNESS = 4_100_000_010  # the seed whose merged median read 0.01032 before the mend


def _config():
    import argparse

    import jax

    from benchmark.harness import core

    args = argparse.Namespace(workload=CELL, seed=0, seconds=0, trace=0)
    return core.context(args, jax.devices())


def test_cumulative_reference_equals_one_pass_over_the_chain():
    """Three pool days chained seven times (two wraps): every metric of
    the check, and ranks around each quantile, as `Reference` over the
    concatenation of chain days 0..k gives them."""
    from benchmark.reference.cumulative import CumulativeReference, chain_days
    from benchmark.reference.reference import EXACT, Reference, expand

    ctx = _config()
    config = dict(ctx.config, rows=2_352 * 600, days=3)
    domains = config["columns"]
    metrics = expand(ctx.traffic["check"], list(domains))
    cum = CumulativeReference(domains, metrics)
    days = []
    for d, cols in chain_days(config, 2**31 + 7, 7):
        cum.add(d, cols)
        days.append(cols)
        whole = {c: (np.concatenate([x[c] for x in days]) if not hasattr(cols[c], "codes")
                     else type(cols[c])(np.concatenate([x[c].codes for x in days]), cols[c].values))
                 for c in cols}
        ref = Reference(whole, domains)
        for m in metrics:
            got, want = cum.value(m), ref.value(m)
            if m.family in EXACT or m.family in ("ApproxCountDistinct", "ApproxQuantile"):
                assert got == want, m
            else:
                assert got == pytest.approx(want, rel=1e-12), m
            if m.family == "ApproxQuantile":
                for v in (want, np.nextafter(want, -np.inf), want + 0.5, want - 1.0):
                    assert cum.rank_window(m.columns[0], v) == ref.rank_window(m.columns[0], v)


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_is_correct(run_cell, trace):
    line, card = run_cell(CELL, SF10, days=3, seconds=1.5, trace=trace)
    assert line["correct"], card.failures
    assert line["attempted"] >= 1 and line["failed"] == 0
    if not trace:
        assert set(line["metrics"]) == {"verdict_p50_ms", "verdict_p95_ms", "setup_s"}
    else:
        for name in ("state_ms.partition", "state_merge_ms.partition", "state_kb.partition",
                     "plan_ms.partition", "fold_ms.partition"):
            assert line["metrics"][name]["value"] > 0, name
    assert list(line)[-1] == "compared"


def _stale_state(monkeypatch):
    """The loader reads the states of chain day k-2 instead of k-1."""
    from deequ_tpu.analyzers.state_provider import FileSystemStateProvider

    real = FileSystemStateProvider.load

    def load(self, analyzer):
        stem, k = self.location_prefix.rsplit("-", 1)
        return real(FileSystemStateProvider(f"{stem}-{int(k) - 1:06d}"), analyzer)

    monkeypatch.setattr(FileSystemStateProvider, "load", load)


def _float32_state(monkeypatch):
    """Persisted floats round-trip through float32."""
    import dataclasses

    from deequ_tpu.analyzers import state_provider

    real = state_provider.serialize_state

    def f32(v):
        return float(np.float32(v)) if isinstance(v, float) else v

    def serialize(analyzer, state):
        if dataclasses.is_dataclass(state):
            state = dataclasses.replace(state, **{
                f.name: f32(getattr(state, f.name)) for f in dataclasses.fields(state)})
        return real(analyzer, state)

    monkeypatch.setattr(state_provider, "serialize_state", serialize)


def _unmended_kll(monkeypatch):
    """KLL as it was: k sized at 2.3/eps and eager compaction, which
    compacts every level over its depth-scaled capacity at each merge.
    The device sample follows k (`_sample_size` reads `k_for_error`)."""
    from deequ_tpu.analyzers import sketch
    from deequ_tpu.ops.sketches.kll import KLLSketch

    def k_for_error(e):
        return max(8, int(math.ceil(2.3 / e)))

    def compress(self):
        level = 0
        while level < len(self.levels):
            if len(self.levels[level]) > self._capacity(level):
                buf = self.levels[level]
                keep, buf = (buf[:1], buf[1:]) if len(buf) % 2 else (np.empty(0), buf)
                promoted = buf[int(self._rng.integers(0, 2))::2]
                if level + 1 >= len(self.levels):
                    self.levels.append(np.empty(0))
                self.levels[level + 1] = np.sort(
                    np.concatenate([self.levels[level + 1], promoted]), kind="stable")
                self.levels[level] = keep
            level += 1

    monkeypatch.setattr(sketch, "k_for_error", k_for_error)
    monkeypatch.setattr(KLLSketch, "_compress", compress)


@pytest.mark.parametrize("fault, number", [
    (_stale_state, "exact_abs_err"),
    (_float32_state, "exact_abs_err"),
    (_unmended_kll, "kll_rank_err"),
    (_small_sketch, "kll_rank_err"),
])
def test_fault_is_not_correct(run_cell, monkeypatch, fault, number):
    fault(monkeypatch)
    # the witness chain: days 0..6 of seed 4,100,000,010 (warm-up 4 verdicts)
    line, card = run_cell(CELL, SF10, days=8, seconds=3.0, seed=WITNESS)
    assert line["attempted"] + 4 >= 7
    assert not line["correct"]
    assert any(f.startswith(number) for f in card.failures), card.failures[:3]


def test_control_is_not_correct():
    from benchmark.reference.cumulative import control_card

    ctx = _config()
    card = control_card(ctx.config, ctx.traffic, 2**31 + 3, verdicts=8)
    assert not card.ok
    assert card.worst["exact_abs_err"] > 0

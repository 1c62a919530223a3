"""The state layer's spans and counter: `state_load`, `state_merge` and
`state_save` (category `state`) around a loader's load, the merge and a
persister's persist, with the bytes each way; `record_state_io` in
`ExecutionStats`; and nothing of either without a loader or a persister."""

import os

import numpy as np
import pytest

from deequ_tpu import Table, observe
from deequ_tpu.analyzers import ApproxQuantile, Completeness, Mean, Size
from deequ_tpu.analyzers.state_provider import (
    FileSystemStateProvider,
    InMemoryStateProvider,
)
from deequ_tpu.ops import runtime
from deequ_tpu.runners.analysis_runner import AnalysisRunner

ANALYZERS = [Size(), Completeness("x"), Mean("x"), ApproxQuantile("x", 0.5)]
STATE_SPANS = ("state_load", "state_merge", "state_save")


def _table(seed):
    rng = np.random.default_rng(seed)
    return Table.from_pydict({"x": rng.normal(size=2_000)})


def _run(table, loader=None, persister=None):
    runner = AnalysisRunner.on_data(table).add_analyzers(ANALYZERS)
    if loader is not None:
        runner = runner.aggregate_with(loader)
    if persister is not None:
        runner = runner.save_states_with(persister)
    with observe.tracing() as tracer, runtime.monitored() as stats:
        runner.run()
    spans = [s for root in tracer.roots for s in root.walk() if s.name in STATE_SPANS]
    return spans, stats, tracer


def _sizes(prefix):
    folder, stem = os.path.split(prefix)
    return sorted(os.path.getsize(os.path.join(folder, f))
                  for f in os.listdir(folder) if f.startswith(stem + "-"))


def test_file_provider_spans_carry_the_files_bytes(tmp_path):
    first, second = str(tmp_path / "day0"), str(tmp_path / "day1")
    _run(_table(1), persister=FileSystemStateProvider(first))
    spans, stats, tracer = _run(
        _table(2), FileSystemStateProvider(first), FileSystemStateProvider(second))

    by_name = {n: [s for s in spans if s.name == n] for n in STATE_SPANS}
    assert {len(v) for v in by_name.values()} == {len(ANALYZERS)}
    assert {s.cat for s in spans} == {"state"}
    assert {s.attrs["analyzer"] for s in by_name["state_load"]} == {
        a.name for a in ANALYZERS}
    assert sorted(s.attrs["bytes"] for s in by_name["state_load"]) == _sizes(first)
    assert sorted(s.attrs["bytes"] for s in by_name["state_save"]) == _sizes(second)
    assert all("bytes" not in s.attrs for s in by_name["state_merge"])

    assert stats.states_loaded == stats.states_saved == len(ANALYZERS)
    assert stats.state_bytes_loaded == sum(_sizes(first))
    assert stats.state_bytes_saved == sum(_sizes(second))
    assert tracer.counters["states_loaded"] == len(ANALYZERS)
    assert tracer.counters["state_bytes_saved"] == sum(_sizes(second))


def test_first_day_opens_no_load_or_merge(tmp_path):
    spans, stats, _ = _run(_table(1), persister=FileSystemStateProvider(str(tmp_path / "d")))
    assert {s.name for s in spans} == {"state_save"}
    assert stats.states_loaded == 0 and stats.states_saved == len(ANALYZERS)


def test_in_memory_provider_spans_and_no_io():
    first = InMemoryStateProvider()
    _run(_table(1), persister=first)
    spans, stats, _ = _run(_table(2), first, InMemoryStateProvider())
    assert sorted({s.name for s in spans}) == sorted(STATE_SPANS)
    assert all("bytes" not in s.attrs for s in spans)
    assert stats.states_loaded == stats.states_saved == 0


@pytest.mark.parametrize("seed", [1, 2])
def test_no_state_span_without_loader_or_persister(seed):
    spans, stats, tracer = _run(_table(seed))
    assert spans == []
    assert stats.states_loaded == stats.states_saved == 0
    assert not any(s.cat == "state" for root in tracer.roots for s in root.walk())

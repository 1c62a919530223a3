"""The reader of the state layer's batch waits, `state_wait_ms.partition`,
against a hand-built span forest: the calling thread's `state_wait` self
time per verdict; nothing without such a span; and the stage wait and the
state layer's readers read as they did with the span there."""

import os

import pytest

from benchmark.harness.core import HERE, Call, Run, load_module

CALLER, WORKER = 1, 2


class S:
    def __init__(self, name, cat, t0, t1, children=(), tid=CALLER, **attrs):
        self.name, self.cat, self.t0, self.t1 = name, cat, t0, t1
        self.children, self.attrs, self.tid = list(children), attrs, tid


def reader(name):
    return load_module(os.path.join(HERE, "layers", f"{name}.py"), f"t_{name}")


def _run(children):
    calls = [Call(25_000, 0.0, 5.0), Call(25_000, 5.0, 10.0)]
    forest = [S("analysis_run", "run", 0.0, 10.0, children)]
    return Run(calls, 0, (0.0, 10.0), 10.0, 1, "TPU v5 lite", forest, None, 0)


def _batched():
    """Loads on a worker under the scan, the caller's waits on them and
    on the persists, merges on the caller; a stage wait in the scan."""
    return _run([
        S("state_load", "state", 0.0, 1.5, tid=WORKER, analyzer="Mean", bytes=16),
        S("fused_scan", "scan", 0.0, 3.0, [S("wait", "wait", 1.0, 2.0, on="prep")]),
        S("state_wait", "wait", 3.0, 3.25, phase="load", files=1),
        S("state_merge", "state", 3.25, 3.5, analyzer="Mean"),
        S("state_save", "state", 3.5, 4.5, tid=WORKER, analyzer="Mean", bytes=16),
        S("state_wait", "wait", 3.5, 4.5, phase="save", files=1),
        # a worker's wait is not the caller's
        S("state_wait", "wait", 4.5, 5.0, tid=WORKER, phase="save", files=1),
    ])


def test_reads_the_callers_state_waits_per_verdict():
    assert reader("state_wait_ms.partition").read(_batched()) == pytest.approx(
        (0.25 + 1.0) * 1e3 / 2)


def test_finds_nothing_without_a_state_wait_span():
    inline = _run([
        S("fused_scan", "scan", 0.0, 3.0, [S("wait", "wait", 1.0, 2.0, on="prep")]),
        S("state_load", "state", 3.0, 3.5, analyzer="Mean", bytes=16),
        S("state_merge", "state", 3.5, 3.75, analyzer="Mean"),
        S("state_save", "state", 3.75, 4.5, analyzer="Mean", bytes=16),
    ])
    assert reader("state_wait_ms.partition").read(inline) is None


def test_finds_nothing_from_other_threads_waits_alone():
    only_worker = _run([S("state_wait", "wait", 0.0, 1.0, tid=WORKER, phase="load", files=1)])
    assert reader("state_wait_ms.partition").read(only_worker) is None


@pytest.mark.parametrize("name, want", [
    # the scan's wait on the prep stage only
    ("stage_wait_ms.partition", 1.0e3 / 2),
    # load, merge and save, on whichever thread
    ("state_ms.partition", (1.5 + 0.25 + 1.0) * 1e3 / 2),
    ("state_merge_ms.partition", 0.25e3 / 2),
    ("state_kb.partition", 32 / 1e3 / 2),
])
def test_other_readers_do_not_read_state_wait(name, want):
    assert reader(name).read(_batched()) == pytest.approx(want)

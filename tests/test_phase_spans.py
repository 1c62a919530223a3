"""Spans of the time no layer used to own: input builds (`build`), a
consumer blocked on a pipeline stage (`wait`), the wire pack and its
device puts (`pack`, `h2d`), and the phases of a frequency pass
(`group_encode`, `group_count`, `group_merge`). Also: with no tracer
installed, none of these sites opens a `Span`."""

from __future__ import annotations

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from deequ_tpu import observe
from deequ_tpu.analyzers.frequency import compute_frequencies
from deequ_tpu.checks.check import Check, CheckLevel
from deequ_tpu.data.table import Table
from deequ_tpu.observe import spans as spans_mod
from deequ_tpu.verification.suite import VerificationSuite

ROWS = 6000
MODES = ["AIR", "MAIL", "SHIP", "TRUCK"]


@pytest.fixture
def parquet_path(tmp_path):
    rng = np.random.default_rng(3)
    table = pa.table({
        "mode": pa.array(rng.choice(MODES, ROWS)),
        "qty": pa.array(rng.integers(1, 51, ROWS)),
        "price": pa.array(rng.random(ROWS) * 1000.0),
        "key": pa.array(rng.integers(0, 500, ROWS)),
    })
    path = str(tmp_path / "day.parquet")
    pq.write_table(table, path, row_group_size=ROWS // 3)
    return path


@pytest.fixture
def on_device(monkeypatch):
    monkeypatch.setenv("DEEQU_TPU_PLACEMENT", "device")


def _check():
    return (
        Check(CheckLevel.ERROR, "day")
        .is_contained_in("mode", MODES)
        .has_mean("qty", lambda v: 1 <= v <= 50)
        .has_max("price", lambda v: v < 1000.0)
    )


def _verify(path, batch_rows=ROWS // 3):
    source = Table.scan_parquet(path, batch_rows=batch_rows)
    return VerificationSuite.on_data(source).add_check(_check()).with_engine("single").run()


def _walk(spans):
    for s in spans:
        yield s
        yield from _walk(s.children)


def _thread_self(span) -> float:
    """Self time on the span's own thread: less the children that ran on
    that thread, not those of worker threads attached under it."""
    same = sum(c.duration_s for c in span.children if c.tid == span.tid)
    return max(span.duration_s - same, 0.0)


@pytest.fixture
def traced_verify(parquet_path, on_device):
    _verify(parquet_path)  # compile outside the traced run
    with observe.tracing() as tracer:
        result = _verify(parquet_path)
    assert result.status.name == "SUCCESS"
    return tracer


@pytest.mark.parametrize("name, cat, attr", [
    ("build", "build", "key"),
    ("wait", "wait", "on"),
    ("pack", "dispatch", "rows"),
    ("h2d", "dispatch", "bytes"),
    ("launch", "dispatch", None),
])
def test_streamed_verify_emits_phase_spans(traced_verify, name, cat, attr):
    found = [s for s in _walk(traced_verify.roots) if s.name == name]
    assert found, name
    assert {s.cat for s in found} == {cat}
    assert attr is None or all(attr in s.attrs for s in found)


def test_build_keys_name_the_input_kind(traced_verify):
    kinds = {s.attrs["key"] for s in _walk(traced_verify.roots) if s.name == "build"}
    assert "pred" in kinds or "prednn" in kinds  # the Compliance predicate
    assert all(":" not in k for k in kinds)


def test_waits_name_their_upstream_stage(traced_verify):
    waits = [s for s in _walk(traced_verify.roots) if s.name == "wait"]
    caller = {r.tid for r in traced_verify.roots}
    assert {s.attrs["on"] for s in waits if s.tid in caller} == {"prep"}
    assert {s.attrs["on"] for s in waits if s.tid not in caller} == {"decode"}


def test_h2d_is_a_child_of_pack(traced_verify):
    for s in _walk(traced_verify.roots):
        if s.name == "pack":
            assert s.children and {c.name for c in s.children} == {"h2d"}
            assert s.attrs["rows"] == ROWS // 3


def _pipeline_share(tracer) -> float:
    (root,) = tracer.roots
    pipeline = sum(
        _thread_self(s) for s in _walk([root])
        if s.cat == "pipeline" and s.tid == root.tid
    )
    return pipeline / root.duration_s


def test_calling_thread_pipeline_self_time_is_small(traced_verify, parquet_path):
    """The caller's time in its stage spans is waits and launches, each
    spanned: what is left is starting and joining the stage threads. The
    least of three runs, as a loaded host can stall a thread's start."""
    shares = [_pipeline_share(traced_verify)]
    for _ in range(2):
        with observe.tracing() as tracer:
            _verify(parquet_path)
        shares.append(_pipeline_share(tracer))
    assert min(shares) < 0.10


def test_cpu_clock_is_read_on_roots_only(traced_verify):
    (root,) = traced_verify.roots
    assert root.cpu_s > 0.0
    run_spans = [s for s in _walk([root]) if s.cat == "run"]
    assert len(run_spans) >= 2  # verification_suite and analysis_run
    assert all(s.cpu_s > 0.0 for s in run_spans)
    assert all(s.cpu_s == 0.0 for s in _walk([root]) if s.cat != "run")


@pytest.mark.parametrize("streamed", [False, True], ids=["in_memory", "streamed"])
def test_frequency_pass_phases(parquet_path, streamed):
    data = (Table.scan_parquet(parquet_path, batch_rows=ROWS // 3) if streamed
            else Table.from_arrow(pq.read_table(parquet_path)))
    with observe.tracing() as tracer:
        state = compute_frequencies(data, ["mode", "key"])
    assert state.num_rows == ROWS
    (group_pass,) = tracer.roots
    assert group_pass.name == "group_pass"
    below = [s for s in _walk(group_pass.children) if s.cat == "group"]
    names = {s.name for s in below}
    want = {"group_encode", "group_count"} | ({"group_merge"} if streamed else set())
    assert names == want
    batches = 3 if streamed else 1
    assert sum(s.name == "group_encode" for s in below) == batches
    for s in below:
        if s.name == "group_encode":
            assert s.attrs["columns"] == 2 and s.attrs["rows"] == ROWS // batches
        elif s.name == "group_count":
            assert 0 < s.attrs["groups"] <= ROWS // batches
        else:
            assert s.attrs["spilled"] is False
    if streamed:  # one merge per batch, and the finish
        merges = [s for s in below if s.name == "group_merge"]
        assert len(merges) == batches + 1
        assert merges[-1].attrs["groups"] == state.num_groups


@pytest.mark.parametrize("columns, typed", [
    (["key"], True),
    (["qty", "key"], True),
    (["mode"], False),
    (["mode", "key"], False),
])
@pytest.mark.parametrize("spill", [False, True], ids=["in_memory", "spilled"])
def test_group_merge_spans_say_whether_keys_are_typed(
    parquet_path, monkeypatch, columns, typed, spill
):
    """Integer keys merge as typed arrays, string keys as objects: every
    `group_merge` span (per batch and the finish) says which."""
    if spill:
        monkeypatch.setenv("DEEQU_TPU_MAX_GROUPS_IN_MEMORY", "3")
    data = Table.scan_parquet(parquet_path, batch_rows=ROWS // 3)
    with observe.tracing() as tracer:
        compute_frequencies(data, columns)
    merges = [s for s in _walk(tracer.roots) if s.name == "group_merge"]
    assert len(merges) == 4
    assert all(s.attrs["typed"] is typed for s in merges)
    assert merges[-1].attrs["spilled"] is spill


def test_untraced_sites_open_no_span(parquet_path, on_device, monkeypatch):
    _verify(parquet_path)
    made = []
    real_init = spans_mod.Span.__init__

    def counting_init(self, *args, **kwargs):
        made.append(args[0] if args else kwargs.get("name"))
        real_init(self, *args, **kwargs)

    monkeypatch.setattr(spans_mod.Span, "__init__", counting_init)
    _verify(parquet_path)
    compute_frequencies(Table.scan_parquet(parquet_path, batch_rows=ROWS // 3),
                        ["mode", "key"])
    assert made == []
    with observe.tracing():  # the same counter sees a traced run's spans
        _verify(parquet_path)
    assert {"build", "wait", "pack", "h2d"} <= set(made)

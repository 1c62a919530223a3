"""Shutdown hardening for the stream pipeline (ISSUE 5, satellite).

Two failure directions, both previously untested:

  * EARLY CONSUMER EXIT — the consumer abandons the generator mid-stream
    (an error in the scan loop, a downstream stage shutting down). Every
    stage thread must terminate within the join timeout and the decode
    iterator must be closed ON its own thread, releasing file handles
    deterministically (no hang on a blocked `q.put`, no leaked
    ParquetFile fd).

  * MID-STREAM PRODUCER EXCEPTION — the decode iterator or a stage `fn`
    raises partway. The exception must re-raise in the consumer, after
    the same cleanup.

Covers `DataSource.batches` (data/source.py) and `pipeline.staged`
(ops/pipeline.py), separately and stacked (staged over batches —
the shape `ScanStep.run` drives for a streamed source).
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np
import pytest

from deequ_tpu.data.source import JOIN_TIMEOUT_S, DataSource, ParquetSource
from deequ_tpu.data.table import Column, ColumnType, Table
from deequ_tpu.ops import pipeline


def _threads(prefix: str):
    return [
        t
        for t in threading.enumerate()
        if t.name.startswith(prefix) and t.is_alive()
    ]


def _wait_no_threads(prefix: str, timeout: float = JOIN_TIMEOUT_S) -> bool:
    deadline = time.time() + timeout
    while time.time() < deadline:
        if not _threads(prefix):
            return True
        time.sleep(0.02)
    return False


def _open_fd_targets():
    fd_dir = "/proc/self/fd"
    if not os.path.isdir(fd_dir):  # pragma: no cover - non-Linux
        return None
    targets = []
    for fd in os.listdir(fd_dir):
        try:
            targets.append(os.readlink(os.path.join(fd_dir, fd)))
        except OSError:
            continue
    return targets


def _tiny_table(n=64):
    values = np.arange(n, dtype=np.float64)
    return Table([Column("x", ColumnType.DOUBLE, values, np.ones(n, bool))])


class _ScriptedSource(DataSource):
    """A DataSource whose decode iterator follows a script: yields
    `good` batches, then optionally raises; records whether its
    generator's finally (the close path) ran and on which thread."""

    def __init__(self, good: int, raise_after: bool = False):
        self.good = good
        self.raise_after = raise_after
        self.closed = threading.Event()
        self.close_thread: str = ""

    def _schema(self):
        return [("x", ColumnType.DOUBLE)]

    @property
    def num_rows(self):
        return self.good * 64

    def _iter_tables(self, batch_size):
        try:
            for _ in range(self.good):
                yield _tiny_table()
            if self.raise_after:
                raise RuntimeError("decode blew up mid-stream")
        finally:
            self.close_thread = threading.current_thread().name
            self.closed.set()


@pytest.fixture
def parquet_path(tmp_path):
    pa = pytest.importorskip("pyarrow")
    pq = pytest.importorskip("pyarrow.parquet")
    path = str(tmp_path / "shutdown.parquet")
    table = pa.table({"x": np.arange(200_000, dtype=np.float64)})
    pq.write_table(table, path, row_group_size=10_000)
    return path


# -- DataSource.batches: the decode stage ------------------------------------


def test_consumer_abandon_terminates_decode_thread(parquet_path):
    src = ParquetSource(parquet_path, batch_rows=10_000)
    gen = src.batches(10_000)
    first = next(gen)
    assert first.num_rows == 10_000
    assert _threads("deequ-decode"), "decode thread should be running"
    gen.close()  # early consumer exit, 19 batches unread
    assert _wait_no_threads("deequ-decode"), (
        "decode thread still alive after consumer abandoned the stream"
    )


def test_consumer_abandon_closes_parquet_file(parquet_path):
    targets = _open_fd_targets()
    if targets is None:
        pytest.skip("/proc/self/fd unavailable")
    src = ParquetSource(parquet_path, batch_rows=10_000)
    gen = src.batches(10_000)
    next(gen)
    gen.close()
    assert _wait_no_threads("deequ-decode")
    open_now = [t for t in _open_fd_targets() if t == parquet_path]
    assert not open_now, (
        f"parquet file handle leaked after consumer abandon: {open_now}"
    )


def test_consumer_abandon_closes_iterator_on_producer_thread():
    src = _ScriptedSource(good=50)
    gen = src.batches(64)
    next(gen)
    gen.close()
    assert src.closed.wait(JOIN_TIMEOUT_S), "decode iterator never closed"
    assert src.close_thread == "deequ-decode", (
        "iterator must close ON the producer thread (deterministic file "
        f"release), closed on {src.close_thread!r}"
    )
    assert _wait_no_threads("deequ-decode")


def test_producer_exception_propagates_and_thread_exits():
    src = _ScriptedSource(good=2, raise_after=True)
    seen = 0
    with pytest.raises(RuntimeError, match="decode blew up"):
        for _ in src.batches(64):
            seen += 1
    assert seen == 2
    assert src.closed.is_set()
    assert _wait_no_threads("deequ-decode")


# -- pipeline.staged: prep-style stages --------------------------------------


def test_staged_early_exit_unwinds_stage_and_upstream():
    """Closing the staged() generator must stop the stage thread AND
    close the upstream iterator (transitively: a DataSource.batches
    upstream unwinds its own decode thread the same way)."""
    upstream_closed = threading.Event()

    def upstream():
        try:
            for i in range(1000):
                yield i
        finally:
            upstream_closed.set()

    it = pipeline.staged(upstream(), lambda x: x * 2, name="t-early", depth=2)
    assert next(it) == 0
    it.close()
    assert _wait_no_threads("deequ-pipe-t-early"), "stage thread leaked"
    assert upstream_closed.wait(JOIN_TIMEOUT_S), "upstream never closed"


def test_staged_blocked_put_wakes_on_abandon():
    """The stage thread blocked on a full queue (consumer far behind)
    must wake and exit promptly when the consumer abandons — the
    drain-then-join shutdown path."""
    it = pipeline.staged(iter(range(1000)), lambda x: x, name="t-blocked", depth=1)
    next(it)
    time.sleep(0.2)  # let the stage fill the queue and block in put()
    t0 = time.time()
    it.close()
    assert _wait_no_threads("deequ-pipe-t-blocked", timeout=JOIN_TIMEOUT_S)
    assert time.time() - t0 < JOIN_TIMEOUT_S


def test_staged_fn_exception_propagates_and_unwinds():
    upstream_closed = threading.Event()

    def upstream():
        try:
            for i in range(100):
                yield i
        finally:
            upstream_closed.set()

    def fn(x):
        if x == 3:
            raise ValueError("prep blew up mid-stream")
        return x

    got = []
    with pytest.raises(ValueError, match="prep blew up"):
        for out in pipeline.staged(upstream(), fn, name="t-fnerr", depth=2):
            got.append(out)
    assert got == [0, 1, 2]
    assert _wait_no_threads("deequ-pipe-t-fnerr")
    assert upstream_closed.wait(JOIN_TIMEOUT_S)


def test_staged_upstream_exception_propagates():
    def upstream():
        yield 1
        yield 2
        raise OSError("upstream died")

    got = []
    with pytest.raises(OSError, match="upstream died"):
        for out in pipeline.staged(upstream(), lambda x: x, name="t-uperr"):
            got.append(out)
    assert got == [1, 2]
    assert _wait_no_threads("deequ-pipe-t-uperr")


# -- stacked: staged over DataSource.batches (the executor's shape) ----------


def test_stacked_abandon_unwinds_both_threads(parquet_path):
    src = ParquetSource(parquet_path, batch_rows=10_000)
    it = pipeline.staged(
        src.batches(10_000), lambda t: t.num_rows, name="t-stack", depth=2
    )
    assert next(it) == 10_000
    it.close()
    assert _wait_no_threads("deequ-pipe-t-stack"), "prep stage leaked"
    assert _wait_no_threads("deequ-decode"), "decode thread leaked"
    targets = _open_fd_targets()
    if targets is not None:
        assert parquet_path not in targets, "parquet fd leaked"


def test_stacked_decode_error_reaches_consumer_through_stage():
    src = _ScriptedSource(good=1, raise_after=True)
    got = []
    with pytest.raises(RuntimeError, match="decode blew up"):
        for out in pipeline.staged(
            src.batches(64), lambda t: t.num_rows, name="t-stkerr"
        ):
            got.append(out)
    assert got == [64]
    assert src.closed.is_set()
    assert _wait_no_threads("deequ-pipe-t-stkerr")
    assert _wait_no_threads("deequ-decode")


# -- chaos: injected faults ride the same shutdown contract (ISSUE 13) --------


def test_injected_worker_death_contained_and_leak_free(parquet_path, monkeypatch):
    """A decode worker killed mid-unit re-decodes inline: same batches,
    every thread joined, no parquet fd left open."""
    from deequ_tpu.testing import faults

    # the pool path (where decode.worker lives) needs >1 worker — the
    # single-core CI box would otherwise route through the serial loop
    monkeypatch.setenv("DEEQU_TPU_DECODE_WORKERS", "2")
    clean = [
        t.num_rows
        for t in ParquetSource(parquet_path, batch_rows=10_000).batches(10_000)
    ]
    with faults.install("seed=7,decode.worker:1.0:1") as plan:
        rows = [
            t.num_rows
            for t in ParquetSource(
                parquet_path, batch_rows=10_000
            ).batches(10_000)
        ]
    assert plan.injected.get("decode.worker", 0) >= 1, "fault never fired"
    assert rows == clean
    assert _wait_no_threads("deequ-decode")
    targets = _open_fd_targets()
    if targets is not None:
        assert parquet_path not in targets, "parquet fd leaked past fault"


def test_injected_stage_fault_contained_in_staged():
    """A stage fn raising once mid-batch redoes in place — the stream
    sees every item exactly once and the stage thread still joins."""
    from deequ_tpu.testing import faults

    with faults.install("seed=1,pipeline.stage:1.0:1") as plan:
        got = list(
            pipeline.staged(iter(range(50)), lambda x: x * 2, name="t-chaos")
        )
    assert plan.injected.get("pipeline.stage", 0) == 1
    assert got == [x * 2 for x in range(50)]
    assert _wait_no_threads("deequ-pipe-t-chaos")


def test_service_drain_on_sigterm_joins_all_and_closes_all(parquet_path):
    """SIGTERM drains the DQ service through the same shutdown contract
    as the pipeline: queued work is returned with DQ414, the running
    run either commits or is drained at a boundary, EVERY service /
    pipeline / decode thread joins, and no parquet fd stays open."""
    import signal

    from deequ_tpu.service import DQ_DRAINED, DQService

    gate = threading.Event()

    def slow_data():
        gate.wait(timeout=30)
        return ParquetSource(parquet_path, batch_rows=10_000)

    svc = DQService(workers=1)
    svc.install_sigterm()
    try:
        from deequ_tpu import Check, CheckLevel

        check = Check(CheckLevel.ERROR, "drain").has_size(lambda s: s > 0)
        running = svc.submit("t", "d0", slow_data, checks=[check])
        for _ in range(300):
            if running.status == "running":
                break
            time.sleep(0.01)
        queued = svc.submit("t", "d1", slow_data, checks=[check])
        gate.set()

        # deliver a real SIGTERM to this process; the installed handler
        # runs svc.drain() synchronously in the main thread
        os.kill(os.getpid(), signal.SIGTERM)

        assert queued.done()
        assert queued.status == "drained" and queued.code == DQ_DRAINED
        assert running.done()
        # the in-flight run either finished cleanly before the drain's
        # soft cancel reached a boundary, or was drained — never killed
        # into an undefined state
        assert running.status in ("done", "drained")
    finally:
        svc.uninstall_sigterm()
        gate.set()
        svc.close()

    assert _wait_no_threads("deequ-dq-service"), "service threads leaked"
    assert _wait_no_threads("deequ-pipe"), "pipeline threads leaked"
    assert _wait_no_threads("deequ-decode"), "decode threads leaked"
    targets = _open_fd_targets()
    if targets is not None:
        assert parquet_path not in targets, "parquet fd leaked past drain"

    # post-drain submissions are turned away with the drain code
    late = svc.submit("t", "d2", slow_data, checks=[])
    assert late.done() and late.code == DQ_DRAINED


def test_cancellation_joins_all_stages(parquet_path):
    """RunCancelled raised in the consumer loop (the fold-side
    controller check) unwinds the stacked staged-over-batches shape
    through the same shutdown contract as exhaustion: both threads
    join, fd released."""
    from contextlib import closing

    from deequ_tpu.core.controller import RunCancelled, RunController

    ctl = RunController()
    src = ParquetSource(parquet_path, batch_rows=10_000)
    with pytest.raises(RunCancelled) as exc_info:
        with closing(
            pipeline.staged(
                src.batches(10_000), lambda t: t.num_rows, name="t-cancel",
                depth=2,
            )
        ) as it:
            batches = 0
            for _ in it:
                batches += 1
                if batches == 2:
                    ctl.cancel()
                ctl.check(where="test fold", progress={"batches": batches})
    assert exc_info.value.progress == {"batches": 2}
    assert _wait_no_threads("deequ-pipe-t-cancel"), "prep stage leaked"
    assert _wait_no_threads("deequ-decode"), "decode thread leaked"
    targets = _open_fd_targets()
    if targets is not None:
        assert parquet_path not in targets, "parquet fd leaked past cancel"

"""The state layer's batch path (runners/state_io.py): where a persister
declares `concurrent_calls`, a scan's states persist concurrently while
the loads and merges go on inline. Metrics, state files, counters and
errors are the inline path's; a provider that does not declare it (a
user's provider, or a file provider over a user's filesystem), or a run
with none, runs inline on the calling thread."""

import os
import struct
import threading
import warnings
from dataclasses import dataclass

import numpy as np
import pytest

from deequ_tpu import Table, observe
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
    StateLoader,
    StatePersister,
)
from deequ_tpu.core.fsio import FileSystem, LocalFileSystem, MemoryFileSystem
from deequ_tpu.ops import runtime
from deequ_tpu.runners import state_io
from deequ_tpu.runners.analysis_runner import AnalysisRunner

ANALYZERS = [
    Size(),
    Completeness("x"),
    Completeness("k"),
    Mean("x"),
    StandardDeviation("x"),
    Minimum("x"),
    Maximum("k"),
    ApproxCountDistinct("k"),
    ApproxQuantile("x", 0.5),
]
DAYS = 6


def _day(seed, rows=1_500):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=rows)
    x[rng.random(rows) < 0.1] = np.nan
    return Table.from_pydict({"x": x, "k": rng.integers(0, 400, size=rows)})


class InlineFileProvider(FileSystemStateProvider):
    """The same files through the inline path."""

    concurrent_calls = False


def _run(table, loader=None, persister=None, analyzers=ANALYZERS):
    runner = AnalysisRunner.on_data(table).add_analyzers(analyzers)
    if loader is not None:
        runner = runner.aggregate_with(loader)
    if persister is not None:
        runner = runner.save_states_with(persister)
    return runner.run()


def _chain(provider, days=DAYS):
    """Day k merges the states of day k-1 and persists its own."""
    contexts = []
    for k in range(days):
        loader = provider(k - 1) if k else None
        contexts.append(_run(_day(k), loader, provider(k)))
    return contexts


def _values(context):
    return {repr(a): repr(m.value) for a, m in context.metric_map.items()}


def _files(folder):
    out = {}
    for name in sorted(os.listdir(folder)):
        with open(os.path.join(folder, name), "rb") as f:
            out[name] = f.read()
    return out


@pytest.mark.parametrize("backend", ["local", "memory"])
def test_chain_matches_inline_metrics_and_file_bytes(tmp_path, backend):
    stores = {}

    def provider(kind, cls):
        if backend == "local":
            folder = tmp_path / kind
            folder.mkdir(exist_ok=True)
            stores[kind] = folder
            return lambda k: cls(str(folder / f"day-{k}"))
        fs = stores.setdefault(kind, MemoryFileSystem())
        return lambda k: cls(f"mem/day-{k}", filesystem=fs)

    batch = _chain(provider("batch", FileSystemStateProvider))
    inline = _chain(provider("inline", InlineFileProvider))

    assert [_values(c) for c in batch] == [_values(c) for c in inline]
    if backend == "local":
        got, want = _files(stores["batch"]), _files(stores["inline"])
    else:
        got, want = stores["batch"]._objects, stores["inline"]._objects
    assert len(want) == DAYS * len(ANALYZERS)
    assert got == want


class InFlight:
    """Counts calls in flight; until two have met, each holds until
    another call is in flight or `hold_s` passes, so calls that may
    overlap do."""

    def __init__(self, hold_s):
        self.hold_s = hold_s
        self.now = self.most = self.calls = 0
        self.cond = threading.Condition()

    def __enter__(self):
        with self.cond:
            self.now += 1
            self.calls += 1
            self.most = max(self.most, self.now)
            self.cond.notify_all()
            self.cond.wait_for(lambda: self.most > 1, timeout=self.hold_s)
        return self

    def __exit__(self, *exc):
        with self.cond:
            self.now -= 1
        return False


class CountingFileSystem(MemoryFileSystem):
    def __init__(self, flight):
        super().__init__()
        self.flight = flight

    def exists(self, path):
        with self.flight:
            return super().exists(path)

    def read_bytes(self, path):
        with self.flight:
            return super().read_bytes(path)

    def write_bytes(self, path, data):
        with self.flight:
            super().write_bytes(path, data)


class CountingMemoryProvider(InMemoryStateProvider):
    def __init__(self, flight):
        super().__init__()
        self.flight = flight

    def load(self, analyzer):
        with self.flight:
            return super().load(analyzer)

    def persist(self, analyzer, state):
        with self.flight:
            super().persist(analyzer, state)


class UserFileSystem(FileSystem):
    """A user's filesystem: a plain dict, no lock."""

    def __init__(self, flight):
        self.flight = flight
        self.objects = {}

    def exists(self, path):
        with self.flight:
            return path in self.objects

    def read_bytes(self, path):
        with self.flight:
            return self.objects[path]

    def write_bytes(self, path, data):
        with self.flight:
            self.objects[path] = bytes(data)


class UserProvider(StateLoader, StatePersister):
    """A user's provider: a plain dict, no lock."""

    def __init__(self, flight):
        self.flight = flight
        self.states = {}

    def load(self, analyzer):
        with self.flight:
            return self.states.get(analyzer)

    def persist(self, analyzer, state):
        with self.flight:
            self.states[analyzer] = state


@pytest.mark.parametrize("kind", ["file", "in_memory", "user", "user_filesystem"])
def test_only_a_concurrent_provider_has_calls_in_flight_together(kind):
    # the file provider's calls meet at once; the others get a short hold
    # in which a concurrent call would show
    flight = InFlight(hold_s=2.0 if kind == "file" else 0.02)
    if kind == "file":
        fs = CountingFileSystem(flight)
        make = lambda k: FileSystemStateProvider(f"s/day-{k}", filesystem=fs)  # noqa: E731
    elif kind == "user_filesystem":
        fs = UserFileSystem(flight)
        make = lambda k: FileSystemStateProvider(f"s/day-{k}", filesystem=fs)  # noqa: E731
    elif kind == "in_memory":
        make = lambda k: CountingMemoryProvider(flight)  # noqa: E731
    else:
        make = lambda k: UserProvider(flight)  # noqa: E731
    first, second = make(0), make(1)
    _run(_day(0), persister=first)
    _run(_day(1), first, second)
    assert flight.calls >= 2 * len(ANALYZERS)
    if kind == "file":
        assert flight.most > 1
    else:
        assert flight.most == 1


def _state_path(provider, analyzer):
    return provider._path(provider._identifier(analyzer))


@pytest.mark.parametrize("corrupt", [Mean("x"), ApproxQuantile("x", 0.5)])
def test_corrupt_state_raises_the_inline_paths_error(tmp_path, corrupt):
    raised = []
    for cls in (FileSystemStateProvider, InlineFileProvider):
        folder = tmp_path / cls.__name__
        first = cls(str(folder / "day-0"))
        _run(_day(0), persister=first)
        with open(_state_path(first, corrupt), "wb") as f:
            f.write(b"\x00\x01\x02")
        with pytest.raises(Exception) as err:
            _run(_day(1), first, cls(str(folder / "day-1")))
        raised.append(type(err.value))
    assert raised[0] is raised[1]
    assert issubclass(raised[0], struct.error)


@dataclass(frozen=True)
class FailingMean(Mean):
    """A Mean whose scan fails."""

    def state_from_aggregates(self, agg):
        raise RuntimeError("scan failed")


def test_corrupt_state_of_an_analyzer_whose_scan_failed_raises_nothing(tmp_path):
    failing = FailingMean("k")
    first = FileSystemStateProvider(str(tmp_path / "day-0"))
    _run(_day(0), persister=first)
    # a stored state for the failing analyzer, unreadable
    with open(_state_path(first, failing), "wb") as f:
        f.write(b"\x00")
    second = FileSystemStateProvider(str(tmp_path / "day-1"))
    with runtime.monitored() as stats:
        context = _run(_day(1), first, second, ANALYZERS + [failing])
    assert context.metric_map[failing].value.is_failure
    assert "scan failed" in str(context.metric_map[failing].value.exception)
    assert all(context.metric_map[a].value.is_success for a in ANALYZERS)
    # its state is neither loaded nor saved
    assert stats.states_loaded == stats.states_saved == len(ANALYZERS)


class SlowWriteFileSystem(LocalFileSystem):
    def write_bytes(self, path, data):
        threading.Event().wait(0.02)
        super().write_bytes(path, data)


def test_every_state_is_written_when_run_returns(tmp_path):
    fs = SlowWriteFileSystem()
    first = FileSystemStateProvider(str(tmp_path / "day-0"), filesystem=fs)
    _run(_day(0), persister=first)
    assert all(os.path.exists(_state_path(first, a)) for a in ANALYZERS)
    second = FileSystemStateProvider(str(tmp_path / "day-1"), filesystem=fs)
    _run(_day(1), first, second)
    assert all(os.path.exists(_state_path(second, a)) for a in ANALYZERS)
    assert not [n for n in os.listdir(tmp_path) if n.endswith(".tmp")]


def test_failed_persists_all_finish_and_the_first_is_raised(tmp_path):
    first = FileSystemStateProvider(str(tmp_path / "day-0"))
    _run(_day(0), persister=first)
    second = FileSystemStateProvider(str(tmp_path / "day-1"))
    # two files already there, overwrite disabled: their persists fail
    taken = [ANALYZERS[2], ANALYZERS[5]]
    for a in taken:
        with open(_state_path(second, a), "wb") as f:
            f.write(b"")
    with pytest.raises(FileExistsError) as err:
        _run(_day(1), first, second)
    assert _state_path(second, taken[0]) in str(err.value)
    # the others all wrote; the two kept what was there
    for a in ANALYZERS:
        assert (os.path.getsize(_state_path(second, a)) == 0) == (a in taken), a


def _stats_and_counters(provider):
    first = provider("day-0")
    _run(_day(0), persister=first)
    with observe.tracing() as tracer, runtime.monitored() as stats:
        _run(_day(1), first, provider("day-1"))
    return (
        (stats.states_loaded, stats.states_saved,
         stats.state_bytes_loaded, stats.state_bytes_saved),
        {k: v for k, v in tracer.counters.items() if k.startswith("state")},
    )


@pytest.mark.parametrize("switch_s", [None, 1e-6])
def test_execution_stats_and_tracer_counters_match_inline(tmp_path, switch_s):
    """Also with the interpreter switching threads as often as it can,
    where a lost update between workers would show."""
    import sys

    before = sys.getswitchinterval()
    try:
        if switch_s is not None:
            sys.setswitchinterval(switch_s)
        batch = _stats_and_counters(
            lambda name: FileSystemStateProvider(str(tmp_path / "b" / name)))
    finally:
        sys.setswitchinterval(before)
    inline = _stats_and_counters(
        lambda name: InlineFileProvider(str(tmp_path / "i" / name)))
    assert batch == inline
    assert batch[0][:2] == (len(ANALYZERS), len(ANALYZERS))


def test_state_wait_spans_on_the_calling_thread(tmp_path):
    first = FileSystemStateProvider(str(tmp_path / "day-0"))
    _run(_day(0), persister=first)
    with observe.tracing() as tracer:
        _run(_day(1), first, FileSystemStateProvider(str(tmp_path / "day-1")))
    spans = [s for root in tracer.roots for s in root.walk()]
    caller = threading.get_ident()
    waits = [s for s in spans if s.name == "state_wait"]
    assert [(s.attrs["phase"], s.attrs["files"]) for s in waits] == [
        ("save", len(ANALYZERS))]
    assert {s.cat for s in waits} == {"wait"} and {s.tid for s in waits} == {caller}
    assert all(not s.children for s in waits)
    saves = [s for s in spans if s.name == "state_save"]
    assert len(saves) == len(ANALYZERS) and caller not in {s.tid for s in saves}
    # loads and merges stay on the calling thread
    loads = [s for s in spans if s.name == "state_load"]
    assert len(loads) == len(ANALYZERS) and {s.tid for s in loads} == {caller}
    assert all(s.cat == "state" and s.attrs["bytes"] > 0 for s in loads + saves)
    merges = [s for s in spans if s.name == "state_merge"]
    assert len(merges) == len(ANALYZERS) and {s.tid for s in merges} == {caller}


@pytest.mark.parametrize("provider", [None, "in_memory", "user", "user_filesystem"])
def test_an_inline_run_starts_no_thread(monkeypatch, provider):
    pools, started = [], []
    monkeypatch.setattr(state_io, "_pool", lambda: pools.append(1) or pytest.fail("pool used"))
    start = threading.Thread.start

    def recording_start(self):
        started.append(self.name)
        return start(self)

    monkeypatch.setattr(threading.Thread, "start", recording_start)
    fs = UserFileSystem(InFlight(0.0))
    make = {None: lambda k: None, "in_memory": lambda k: InMemoryStateProvider(),
            "user": lambda k: UserProvider(InFlight(0.0)),
            "user_filesystem": lambda k: FileSystemStateProvider(f"s/day-{k}", filesystem=fs),
            }[provider]
    first, second = make(0), make(1)
    _run(_day(0), persister=first)
    context = _run(_day(1), first, second)
    assert all(m.value.is_success for m in context.metric_map.values())
    assert pools == []
    assert not [n for n in started if n.startswith("deequ-state-io")]


def test_a_forked_child_makes_its_own_pool():
    """The parent's workers do not exist in a forked child: a call
    submitted there must still run."""
    assert state_io._pool().submit(int, "1").result() == 1
    read, write = os.pipe()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # forking a threaded process warns
        pid = os.fork()
    if pid == 0:  # the child touches no backend, and never returns
        try:
            ok = state_io._pool().submit(int, "2").result(timeout=10) == 2
            os.write(write, b"1" if ok else b"0")
        finally:
            os._exit(0)
    os.close(write)
    with os.fdopen(read, "rb") as f:
        said = f.read()
    os.waitpid(pid, 0)
    assert said == b"1"

"""A scan's state I/O: loads inline, persists concurrently where allowed.

`Analyzer.calculate_metric` loads, merges and persists one analyzer's
state after another on the calling thread. Where every provider call is
a latency-bound round trip (a network or 9p mount), a run's 2n calls
then follow each other with nothing to overlap them. `calculate_metrics`
takes a scan's results to the metrics `calculate_metric` per result
gives, and where the persister declares `concurrent_calls`
(analyzers/state_provider.py) it submits each persist to a small pool as
soon as its state is merged: the writes overlap each other and the
loads and merges still to come on the calling thread, which waits for
all of them before it returns, so every state is written before the
verdict. The loads stay on the calling thread: batched on the pool,
before the scan or after it, they measured no faster on a 9p mount,
since their Python then queues for the GIL behind the scan or the saves.

Each persist runs the step `calculate_metric` runs (`Analyzer.save_state`,
with its `state_save` span) under the trace context captured at submit;
its counts reach the caller's `monitored()` sinks when the caller takes
its result. Persists all finish, and the first failure in analyzer order
is raised. The calling thread's blocking wait is a `state_wait` span
(category `wait`; attrs `phase` save and `files`). A persister that does
not declare it runs inline: no pool, no thread.
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import wait as wait_all
from typing import TYPE_CHECKING, Dict, List, Optional, Sequence

from deequ_tpu import observe
from deequ_tpu.observe import counters
from deequ_tpu.ops import runtime

if TYPE_CHECKING:
    from concurrent.futures import Future

    from deequ_tpu.analyzers.base import Analyzer
    from deequ_tpu.analyzers.state_provider import StateLoader, StatePersister
    from deequ_tpu.core.metrics import Metric
    from deequ_tpu.ops.fused import AnalyzerRunResult

# the calls wait on a disk, not on a core: enough in flight to hide its
# round trips
MAX_WORKERS = 8

# one pool for the process, made on first use: a thread started per run
# would hand the GIL to each new worker and wait to get it back
_POOL: Optional[ThreadPoolExecutor] = None
_POOL_LOCK = threading.Lock()


def _pool() -> ThreadPoolExecutor:
    global _POOL
    with _POOL_LOCK:
        if _POOL is None:
            _POOL = ThreadPoolExecutor(
                max_workers=MAX_WORKERS, thread_name_prefix="deequ-state-io"
            )
        return _POOL


def _forget_pool() -> None:
    """In a forked child: the parent's workers do not exist there."""
    global _POOL, _POOL_LOCK
    _POOL = None
    _POOL_LOCK = threading.Lock()


os.register_at_fork(after_in_child=_forget_pool)


def _call(tracer, parent, step, *args):
    """One step of the state layer on a worker: (value, error, tally)."""
    with observe.attached(tracer, parent), runtime.monitored() as tally:
        try:
            return step(*args), None, tally
        except Exception as e:  # noqa: BLE001 - raised where the caller takes it
            return None, e, tally


def _submit(step, *args) -> "Future":
    return _pool().submit(
        _call, observe.current_tracer(), observe.current_span(), step, *args
    )


def _take(future: "Future"):
    value, error, tally = future.result()
    counters.replay(tally)
    if error is not None:
        raise error
    return value


def _wait(saves: Sequence["Future"]) -> None:
    if saves:
        with observe.span("state_wait", cat="wait", phase="save", files=len(saves)):
            wait_all(saves)


def calculate_metrics(
    results: Sequence["AnalyzerRunResult"],
    loader: Optional["StateLoader"],
    persister: Optional["StatePersister"],
) -> Dict["Analyzer", "Metric"]:
    """A scan's results -> metrics, as `calculate_metric` per result
    would give them."""
    batch = persister is not None and persister.concurrent_calls
    metrics: Dict["Analyzer", "Metric"] = {}
    saves: List["Future"] = []
    failure: Optional[BaseException] = None
    try:
        for r in results:
            a, state = r.analyzer, r.state
            if r.error is not None:
                metrics[a] = a.to_failure_metric(r.error)
                continue
            if loader is not None:
                state = a.merge_loaded(state, a.load_state(loader))
            if persister is not None and state is not None:
                if batch:
                    saves.append(_submit(a.save_state, persister, state))
                else:
                    a.save_state(persister, state)
            metrics[a] = a.compute_metric_from(state)
    except Exception as e:  # noqa: BLE001 - raised after the persists finish
        failure = e
    _wait(saves)
    # a failed persist came before whatever stopped the loop, as it
    # would inline; the first in analyzer order is raised
    save_failure: Optional[BaseException] = None
    for future in saves:
        try:
            _take(future)
        except Exception as e:  # noqa: BLE001 - raised below
            save_failure = save_failure or e
    failure = save_failure or failure
    if failure is not None:
        raise failure
    return metrics

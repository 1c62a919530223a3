"""Backpressured stream-pipeline stages: the staged streaming executor.

A streaming scan moves every batch through four kinds of work: decode
(Parquet -> Arrow -> Table, already overlapped by the prefetch thread in
data/source.py), host prep (input builds, wire packing + the H2D put,
family kernels), device compute (async XLA dispatch), and the ordered
fold (async D2H fetch + merge_agg + host member folds, see
`PipelinedAggFold`). Serially, everything between the prefetch thread
and the D2H fold shares one consumer thread; this module runs the prep
work on its own stage thread with a bounded queue to the fold stage:

    decode thread ──q──> prep thread ──q──> consumer (dispatch + fold)

  * batch N+1's H2D put (`jnp.asarray` inside `pack_batch_inputs` /
    `jax.device_put` in the mesh pass) overlaps batch N's device
    compute — the H2D twin of `PipelinedAggFold`'s async D2H, giving
    double-buffered device inputs at queue depth 1;
  * batch N+1's family kernels and input builds overlap batch N's host
    fold on multicore hosts.

Bit-identity with the serial path (`DEEQU_TPU_PIPELINE=0`): every fold
(`PipelinedAggFold` merges and `fold_host_batch` member folds) still
runs on the consumer thread in batch order over the same inputs, and
the sticky wire dict is only ever mutated by the single prep thread in
batch order — the pipeline changes WHERE per-batch work runs, never
what is computed. The one permitted divergence: liveness feedback lags
by at most the queue depth, so a member that errors mid-stream can have
its family kernel still run for the batches already in flight — wasted
work on an already-failing plan, not a results change on healthy
streams (the pipeline-on/off differential in
tests/test_suite_differential_fuzz.py pins bit-identical metrics).

Stage threads must never host-sync: `jax.device_get` /
`block_until_ready` belong to the fold stage only (the PIPELINE rule in
tools/lint.py bans them in this file and in data/source.py). Stage
threads adopt the dispatching thread's trace context
(`observe.attached`) and report a `pipe_stage` span with one
`pipe_item` child per batch — what the run report's pipeline-occupancy
section aggregates. The consumer's blocking take from a stage's queue
is a `wait` span (`on` = the stage it waits for), so time a consumer
spends idle is named and not left in its stage span's self time. With
tracing off, spans hit the no-op fast path.

Occupancy attribution under decode-to-wire fusion
(`DEEQU_TPU_WIRE_FUSED`): a fused column's bit-packing and value
narrowing/shifting run inside the decode workers' native kernels, so
that work leaves the prep stage's `pack_batch_inputs` bucket and lands
in the DECODE stage's busy time (where the arrow_decode spans live).
The occupancy report therefore re-baselines when fusion toggles —
decode busy_s rises by roughly the pack time that prep loses, and the
total stays accounted: time moves between stage buckets, it is never
dropped (BENCH.md's round-10 table shows the A/B).
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Iterable, Iterator, List

from deequ_tpu import observe
from deequ_tpu.ops import runtime
from deequ_tpu.testing import faults

_SENTINEL = object()

#: how long shutdown waits for a stage thread before giving up on it —
#: matches the decode thread's join timeout in data/source.py
JOIN_TIMEOUT_S = 10.0


def staged(
    iterable: Iterable[Any],
    fn: Callable[[Any], Any],
    *,
    name: str = "prep",
    depth: int | None = None,
    progress: Any = None,
) -> Iterator[Any]:
    """Run `fn` over `iterable`'s items on a dedicated stage thread,
    yielding `fn(item)` results in input order through a bounded queue.

    Backpressure: the stage blocks once `depth` (default
    `runtime.pipeline_depth()`) results wait unconsumed, so at most
    `depth` + 1 prepped batches are resident regardless of how far the
    consumer falls behind.

    Shutdown contract (pinned by tests/test_pipeline_shutdown.py):
      * early consumer exit (the generator is closed or abandoned
        mid-stream) signals the stage thread, drains the queue so a
        blocked put() wakes, and joins within `JOIN_TIMEOUT_S`;
      * the stage thread closes the upstream iterator ON the stage
        thread before exiting — a generator upstream (e.g.
        `DataSource.batches`) runs its own finally there, so decode
        threads and file handles unwind transitively;
      * an exception from `fn` or the upstream iterator terminates the
        stage and re-raises in the consumer, after the same cleanup.

    Trace context is captured when the consumer starts iterating and
    adopted by the stage thread, so `fn`'s spans stay under the
    dispatching scan's subtree.

    `progress` is an optional live-heartbeat handle
    (`observe.heartbeat.ScanProgress`): the stage accounts the upstream
    `next()` wait to the `decode` stage bucket and `fn`'s work to this
    stage's bucket, which is what the heartbeat's bottleneck/occupancy
    snapshot reads. Defaults to the no-op handle.
    """
    if depth is None:
        depth = runtime.pipeline_depth()
    if progress is None:
        progress = observe.heartbeat.NOOP_PROGRESS
    q: "queue.Queue[Any]" = queue.Queue(maxsize=max(1, depth))
    stop = threading.Event()
    error: List[BaseException] = []
    tracer = observe.current_tracer()
    parent = observe.current_span()

    def _put(item: Any) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _apply(item: Any) -> Any:
        faults.fault_point("pipeline.stage")
        return fn(item)

    def worker() -> None:
        it = iter(iterable)
        try:
            with observe.attached(tracer, parent):
                with observe.span(
                    "pipe_stage", cat="pipeline", stage=name
                ) as stage_sp:
                    items = 0
                    while not stop.is_set():
                        # the next() wait is upstream stall, not this
                        # stage's work — kept outside the item span so
                        # occupancy attributes it to the right stage
                        try:
                            with progress.timed("decode"):
                                item = next(it)
                        except StopIteration:
                            break
                        sp = observe.span(
                            "pipe_item", cat="pipeline", stage=name
                        )
                        with sp, progress.timed(name):
                            rows = getattr(item, "num_rows", None)
                            if sp and rows is not None:
                                sp.set(rows=int(rows))
                            faults.fault_point("pipeline.stall")
                            try:
                                out = _apply(item)
                            except Exception:  # noqa: BLE001 - one redo
                                # contained stage fault: fn is a pure
                                # per-batch prep, so one in-place redo
                                # is bit-identical; a second failure is
                                # a real error and propagates
                                runtime.record_fault(injected=1)
                                out = _apply(item)
                                runtime.record_retry(1, 1, 0)
                        if not _put(out):
                            return
                        items += 1
                    if stage_sp:
                        stage_sp.set(items=items)
        except BaseException as e:  # noqa: BLE001 - re-raised consumer-side
            error.append(e)
        finally:
            close = getattr(it, "close", None)
            if close is not None:
                try:
                    close()
                except BaseException as e:  # noqa: BLE001
                    if not error:
                        error.append(e)
            _put(_SENTINEL)

    # stack dumps / py-spy on a mesh worker must say WHICH shard's
    # pipeline a stage thread belongs to
    tag = runtime.shard_tag()
    thread = threading.Thread(
        target=worker,
        daemon=True,
        name=f"deequ-pipe-{name}" + (f"-shard{tag}" if tag else ""),
    )
    thread.start()
    try:
        while True:
            with observe.span("wait", cat="wait", on=name):
                out = q.get()
            if out is _SENTINEL:
                break
            yield out
    finally:
        stop.set()
        try:
            while True:
                q.get_nowait()
        except queue.Empty:  # fault-ok: drain-until-empty teardown
            pass
        thread.join(timeout=JOIN_TIMEOUT_S)
    if error:
        raise error[0]

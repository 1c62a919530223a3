"""Distributed fused scan: shard rows over a device mesh, merge states
with collectives.

This is the TPU-native form of the reference's partition-parallel
aggregation (reference: SURVEY.md §2.10 — Spark map-side partial
aggregation + driver merge): each device reduces its row shard with the
SAME fused computation the single-chip path uses, then the semigroup merge
(`State.sum`, analyzers/Analyzer.scala:34-48) runs IN-GRAPH as an
all_gather over the tiny state pytrees followed by a static fold of each
analyzer's `merge_agg` — sums lower to psum-like collectives, min/max to
pmin/pmax, HLL registers to an elementwise-max reduction, all riding ICI.

Multi-host (DCN) is the second tier: parallel/multihost.py runs this pass
per host on each host's partition and allgathers the serialized states —
only state pytrees (bytes to KB) ever cross host boundaries, never rows.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deequ_tpu import observe
from deequ_tpu.analyzers.base import ScanShareableAnalyzer
from deequ_tpu.data.table import Table
from deequ_tpu.ops import pipeline, runtime
from deequ_tpu.ops.fused import (
    AnalyzerRunResult,
    HostInputs,
    PipelinedAggFold,
    _pad_size,
    _precompute_family_kernels,
    apply_decode_plan,
    fold_host_batch,
    materialize_host_results,
    plan_decode_fastpath,
    plan_scan_members,
    prune_table_columns,
    resolve_shift,
)

DATA_AXIS = "data"

_DIST_CACHE: Dict[Any, Any] = {}
_DIST_CACHE_LOCK = threading.Lock()


def data_mesh(devices: Optional[Sequence] = None, axis_name: str = DATA_AXIS) -> Mesh:
    """1-D data-parallel mesh over all (or given) devices."""
    devices = list(devices) if devices is not None else jax.devices()
    return Mesh(np.array(devices), (axis_name,))


def _get_distributed_fn(analyzers, mesh: Mesh, axis_name: str, assisted=()):
    # Mesh hashes/compares by content (devices + axis names), giving a
    # stable cache identity — unlike id(mesh), which can be recycled
    # after GC and return a function compiled for a dead mesh.
    key = (
        tuple(repr(a) for a in analyzers),
        tuple(repr(a) for a in assisted),
        mesh,
        axis_name,
        bool(jax.config.jax_enable_x64),
    )
    with _DIST_CACHE_LOCK:
        fn = _DIST_CACHE.get(key)
    if fn is not None:
        return fn

    n_devices = mesh.shape[axis_name]

    def per_device(inputs):
        # wire-narrowed ints (1-2 B/row on the put) widen back to int32
        # before reduction, matching the fused engine's unpack stage
        inputs = {
            k: (
                v.astype(jnp.int32)
                if jnp.issubdtype(v.dtype, jnp.integer) and v.dtype.itemsize < 4
                else v
            )
            for k, v in inputs.items()
        }
        # local shard reduce: identical computation to the single-chip pass
        partials = tuple(a.device_reduce(inputs, jnp) for a in analyzers)

        # in-graph semigroup merge: all_gather the state pytrees (tiny),
        # then a static fold with each analyzer's merge law
        gathered = jax.tree_util.tree_map(
            lambda x: jax.lax.all_gather(x, axis_name), partials
        )

        merged = []
        for analyzer, tree in zip(analyzers, gathered):
            acc = jax.tree_util.tree_map(lambda x: x[0], tree)
            for d in range(1, n_devices):
                shard = jax.tree_util.tree_map(lambda x, d=d: x[d], tree)
                acc = analyzer.merge_agg(acc, shard, jnp)
            merged.append(acc)

        # device-assisted outputs (e.g. the quantile sort+decimate) stay
        # per-device: each shard's fixed-size artifact is gathered along
        # axis 0 and consumed host-side shard by shard
        assisted_out = tuple(a.device_batch(inputs, jnp) for a in assisted)
        return tuple(merged), assisted_out

    sharded = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(axis_name),),
        # merged states replicated; assisted artifacts concatenated per device
        out_specs=(P(), P(axis_name)),
        check_vma=False,
    )
    fn = jax.jit(sharded)
    with _DIST_CACHE_LOCK:
        fn = _DIST_CACHE.setdefault(key, fn)
    return fn


def _shard_rows(inputs: Dict[str, Any], n_devices: int) -> int:
    """Rows of the batch each device holds: every device key is padded to
    one length and split evenly along the mesh axis."""
    for arr in inputs.values():
        return int(arr.shape[0]) // n_devices
    return 0


class DistributedScanPass:
    """Mesh-sharded variant of FusedScanPass: device-reduced analyzers
    merge in-graph via collectives; device-assisted analyzers (quantile
    sketches) produce fixed-size per-shard artifacts gathered along the
    mesh axis and folded on the host shard by shard."""

    def __init__(
        self,
        analyzers: Sequence[ScanShareableAnalyzer],
        mesh: Optional[Mesh] = None,
        batch_size_per_device: int = 1 << 21,
        axis_name: str = DATA_AXIS,
    ):
        self.analyzers = list(analyzers)
        self.mesh = mesh if mesh is not None else data_mesh()
        self.axis_name = axis_name
        self.batch_size_per_device = batch_size_per_device

    def run(self, table: Table) -> List[AnalyzerRunResult]:
        with observe.span(
            "dist_scan",
            cat="scan",
            devices=int(self.mesh.shape[self.axis_name]),
            analyzers=len(self.analyzers),
        ):
            return self._run(table)

    def _run(self, table: Table) -> List[AnalyzerRunResult]:
        # same placement policy as FusedScanPass — the shared pure
        # planner partitions members: on a slow device link, discrete
        # (mask/code-only) analyzers — or under 'host-all', every
        # analyzer — fold on the host while the mesh reduces the rest
        plan = plan_scan_members(self.analyzers)
        results: Dict[int, AnalyzerRunResult] = {}
        for i, err in plan.spec_errors.items():
            results[i] = AnalyzerRunResult(self.analyzers[i], error=err)
        merge_idx = plan.merge_idx
        assisted_idx = plan.assisted_idx
        merge_analyzers = [self.analyzers[i] for i in merge_idx]
        assisted = [self.analyzers[i] for i in assisted_idx]
        host_members = [(i, self.analyzers[i]) for i in plan.host_idx]
        host_assisted = [(i, self.analyzers[i]) for i in plan.host_assisted_idx]
        host_member_keys = plan.host_keys
        specs = plan.specs
        device_keys = plan.device_keys

        table = prune_table_columns(table, specs)
        # decode routing after pruning, exactly as in FusedScanPass: the
        # mesh shards the packed wire arrays, so whether a column decoded
        # through the native kernels or the host chain is invisible to it
        decode_plan = plan_decode_fastpath(table, specs)
        if decode_plan is not None:
            table = apply_decode_plan(table, decode_plan)
        n_devices = self.mesh.shape[self.axis_name]
        global_batch = self.batch_size_per_device * n_devices
        dtype = runtime.compute_dtype()
        fn = (
            _get_distributed_fn(
                merge_analyzers, self.mesh, self.axis_name, assisted
            )
            if merge_analyzers or assisted
            else None
        )
        runtime.record_pass(
            f"dist-scan[{n_devices}x]:"
            + ",".join(a.name for a in self.analyzers)
        )
        in_sharding = jax.tree_util.tree_map(
            lambda _: NamedSharding(self.mesh, P(self.axis_name)), specs
        )

        host_aggs: Dict[int, Any] = {}
        host_assisted_states: Dict[int, Any] = {}
        host_errors: Dict[int, BaseException] = {}
        sticky: Dict[str, Any] = {}
        family_memo: Dict[Any, Any] = {}  # cross-batch, one scan's scope
        streaming = bool(getattr(table, "is_streaming", False))
        try:
            fold = PipelinedAggFold(
                merge_analyzers, assisted, n_dev=n_devices, sticky=sticky
            )

            all_host = list(host_members) + list(host_assisted)

            def _shard_inputs(batch, built) -> Dict[str, Any]:
                """Pad/narrow/shift each device key exactly like the
                single-chip wire, then place it with the mesh sharding —
                the H2D put the pipeline overlaps with compute."""
                for key in device_keys:
                    if key in built.build_errors:
                        raise built.build_errors[key]
                # pad to a multiple of n_devices (pow2 per shard)
                per_dev = _pad_size(
                    -(-batch.num_rows // n_devices),
                    self.batch_size_per_device,
                )
                padded = per_dev * n_devices
                inputs: Dict[str, Any] = {}
                for key in device_keys:
                    arr = runtime.pad_to(built[key], padded)
                    if np.issubdtype(arr.dtype, np.integer):
                        arr = runtime.narrow_int_wire(arr, key, sticky)
                    elif arr.dtype != np.bool_:
                        if (
                            np.dtype(dtype) == np.float32
                            and key.startswith("num:")
                        ):
                            # same f32 pre-centering as
                            # pack_batch_inputs (see fused.py)
                            shift = resolve_shift(key, arr, sticky, built.get)
                            if shift != 0.0:
                                arr = np.asarray(arr, dtype=np.float64) - shift
                        arr = arr.astype(dtype)
                    inputs[key] = jax.device_put(arr, in_sharding[key])
                return inputs

            device_error: Any = None
            if streaming and runtime.pipeline_enabled():
                device_error = self._scan_pipelined(
                    table, global_batch, fn, specs, device_keys, n_devices,
                    _shard_inputs, fold, all_host, host_members,
                    host_assisted, host_member_keys, host_aggs,
                    host_assisted_states, host_errors, family_memo,
                )
            else:
                for batch in table.batches(global_batch):
                    # per-key builds with error capture — same isolation
                    # contract as FusedScanPass._run_pass; host-only keys
                    # build lazily (fused.HostInputs)
                    device_live = fn is not None and device_error is None
                    host_live = any(
                        i not in host_errors for i, _m in all_host
                    )
                    if not device_live and not host_live:
                        break  # everything already failed; stop scanning
                    built = HostInputs(specs, batch)
                    build_errors = built.build_errors
                    if device_live:
                        for key in sorted(device_keys):
                            built.materialize(key)
                    if fn is not None and device_error is None:
                        try:
                            with observe.span(
                                "dispatch",
                                cat="dispatch",
                                rows=batch.num_rows,
                                devices=int(n_devices),
                            ) as dispatch_sp:
                                inputs = _shard_inputs(batch, built)
                                if dispatch_sp:
                                    dispatch_sp.set(
                                        wire_bytes=sum(
                                            int(getattr(v, "nbytes", 0))
                                            for v in inputs.values()
                                        )
                                    )
                                runtime.record_launch()
                                runtime.record_placement(inputs.values())
                                fold.submit(
                                    fn(inputs),
                                    host_ctx=built,
                                    shard_rows=_shard_rows(inputs, n_devices),
                                )
                        except Exception as e:  # noqa: BLE001
                            device_error = e
                    with observe.span(
                        "host_fold", cat="host", rows=batch.num_rows
                    ):
                        fold_host_batch(
                            built, build_errors, host_members, host_assisted,
                            host_member_keys, host_aggs, host_assisted_states,
                            host_errors,
                            batch=batch, streaming=streaming,
                            family_memo=family_memo,
                        )
            aggs, assisted_states = [], []
            if device_error is None:
                try:
                    aggs, assisted_states = fold.finish()
                    from deequ_tpu.ops.fused import wire_shifts

                    shifts = wire_shifts(sticky)
                    if shifts:
                        aggs = [
                            a.unshift_agg(agg, shifts)
                            for a, agg in zip(merge_analyzers, aggs)
                        ]
                except Exception as e:  # noqa: BLE001
                    device_error = e
            if device_error is not None:
                for i in merge_idx + assisted_idx:
                    results[i] = AnalyzerRunResult(
                        self.analyzers[i], error=device_error
                    )
            else:
                for i, analyzer, agg in zip(merge_idx, merge_analyzers, aggs):
                    try:
                        results[i] = AnalyzerRunResult(
                            analyzer, state=analyzer.state_from_aggregates(agg)
                        )
                    except Exception as e:  # noqa: BLE001
                        results[i] = AnalyzerRunResult(analyzer, error=e)
                for i, state in zip(assisted_idx, assisted_states):
                    results[i] = AnalyzerRunResult(self.analyzers[i], state=state)
            results.update(
                materialize_host_results(
                    host_members, host_assisted, host_aggs,
                    host_assisted_states, host_errors,
                )
            )
        except Exception as e:  # noqa: BLE001
            for i in range(len(self.analyzers)):
                results.setdefault(i, AnalyzerRunResult(self.analyzers[i], error=e))

        return [results[i] for i in range(len(self.analyzers))]

    def _scan_pipelined(
        self,
        table,
        global_batch,
        fn,
        specs,
        device_keys,
        n_devices,
        shard_inputs,
        fold,
        all_host,
        host_members,
        host_assisted,
        host_member_keys,
        host_aggs,
        host_assisted_states,
        host_errors,
        family_memo,
    ):
        """Sharded-stream twin of `FusedScanPass._scan_pipelined`: the
        per-batch prep — eager builds, pad/narrow/shift, the sharded
        `jax.device_put` — runs on a stage thread so batch N+1's H2D
        lands on the mesh while batch N's collectives run; every fold
        stays on this thread in batch order (bit-identical to serial)."""
        device_down = threading.Event()

        def _prep(batch):
            built = HostInputs(specs, batch)
            inputs = device_exc = None
            if fn is not None and not device_down.is_set():
                for key in sorted(device_keys):
                    built.materialize(key)
                try:
                    with observe.span(
                        "dispatch",
                        cat="dispatch",
                        rows=batch.num_rows,
                        devices=int(n_devices),
                    ) as dispatch_sp:
                        inputs = shard_inputs(batch, built)
                        if dispatch_sp:
                            dispatch_sp.set(
                                wire_bytes=sum(
                                    int(getattr(v, "nbytes", 0))
                                    for v in inputs.values()
                                )
                            )
                except Exception as e:  # noqa: BLE001
                    device_exc = e
                    inputs = None
                    device_down.set()
            if any(i not in host_errors for i, _m in all_host):
                with observe.span(
                    "host_prep", cat="host", rows=batch.num_rows
                ):
                    _precompute_family_kernels(
                        built, host_assisted, batch,
                        host_members=host_members, host_errors=host_errors,
                        streaming=True, family_memo=family_memo,
                    )
            return batch, built, inputs, device_exc

        device_error: Any = None
        items = pipeline.staged(table.batches(global_batch), _prep, name="prep")
        with contextlib.closing(items):
            with observe.span(
                "pipe_stage", cat="pipeline", stage="fold"
            ) as stage_sp:
                n_items = 0
                for batch, built, inputs, device_exc in items:
                    device_live = fn is not None and device_error is None
                    host_live = any(i not in host_errors for i, _m in all_host)
                    if not device_live and not host_live:
                        break  # everything already failed; stop scanning
                    with observe.span(
                        "pipe_item", cat="pipeline", stage="fold",
                        rows=batch.num_rows,
                    ):
                        if device_live:
                            if device_exc is not None:
                                device_error = device_exc
                            elif inputs is not None:
                                try:
                                    runtime.record_launch()
                                    runtime.record_placement(inputs.values())
                                    fold.submit(
                                        fn(inputs),
                                        host_ctx=built,
                                        shard_rows=_shard_rows(
                                            inputs, n_devices
                                        ),
                                    )
                                except Exception as e:  # noqa: BLE001
                                    device_error = e
                            if device_error is not None:
                                device_down.set()
                        with observe.span(
                            "host_fold", cat="host", rows=batch.num_rows
                        ):
                            fold_host_batch(
                                built, built.build_errors, host_members,
                                host_assisted, host_member_keys, host_aggs,
                                host_assisted_states, host_errors,
                                batch=batch, streaming=True,
                                family_memo=family_memo, precomputed=True,
                            )
                    n_items += 1
                if stage_sp:
                    stage_sp.set(items=n_items)
        return device_error


_BINCOUNT_CACHE: Dict[Any, Any] = {}
_BINCOUNT_CACHE_LOCK = threading.Lock()


def sharded_bincount(
    codes: np.ndarray, nbins: int, mesh: Mesh, axis_name: str = DATA_AXIS
) -> np.ndarray:
    """Row-sharded group counting: each device scatter-adds its shard of
    dense group codes into a fixed-size count table, merged in-graph with
    psum over the mesh — the device form of the reference's
    groupBy().agg(count) shuffle (reference: GroupingAnalyzers.scala:67-72).

    `codes` may contain -1 (null group) — counted into a trash bin and
    dropped. Returns int64 counts[nbins].
    """
    n_devices = mesh.shape[axis_name]
    nbins_p = _pad_size(nbins + 1, 1 << 30)
    per_dev = _pad_size(-(-len(codes) // n_devices), 1 << 30)
    padded_rows = per_dev * n_devices

    full = np.full(padded_rows, nbins, dtype=np.int64)  # pad/null -> trash
    np.copyto(full[: len(codes)], np.where(codes >= 0, codes, nbins))

    key = (padded_rows, nbins_p, mesh, axis_name)
    with _BINCOUNT_CACHE_LOCK:
        fn = _BINCOUNT_CACHE.get(key)
    if fn is None:

        def per_device(c):
            counts = jnp.zeros(nbins_p, dtype=jnp.int32).at[c].add(1)
            return jax.lax.psum(counts, axis_name)

        fn = jax.jit(
            jax.shard_map(
                per_device,
                mesh=mesh,
                in_specs=(P(axis_name),),
                out_specs=P(),
                check_vma=False,
            )
        )
        with _BINCOUNT_CACHE_LOCK:
            fn = _BINCOUNT_CACHE.setdefault(key, fn)
    with observe.span(
        "group_bincount",
        cat="dispatch",
        rows=len(codes),
        bins=nbins,
        devices=int(n_devices),
    ):
        runtime.record_launch()
        placed = jax.device_put(full, NamedSharding(mesh, P(axis_name)))
        runtime.record_placement([placed])
        counts = np.asarray(fn(placed))
    return counts[:nbins].astype(np.int64)


def run_distributed_analysis(
    table: Table,
    analyzers: Sequence[ScanShareableAnalyzer],
    mesh: Optional[Mesh] = None,
    batch_size_per_device: int = 1 << 21,
):
    """Convenience: sharded pass -> AnalyzerContext."""
    from deequ_tpu.runners.context import AnalyzerContext

    results = DistributedScanPass(
        analyzers, mesh=mesh, batch_size_per_device=batch_size_per_device
    ).run(table)
    metrics = {}
    for result in results:
        if result.error is not None:
            metrics[result.analyzer] = result.analyzer.to_failure_metric(result.error)
        else:
            metrics[result.analyzer] = result.analyzer.compute_metric_from(result.state)
    return AnalyzerContext(metrics)

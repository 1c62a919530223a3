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

import threading
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deequ_tpu import observe
from deequ_tpu.analyzers.base import ScanShareableAnalyzer
from deequ_tpu.data.table import Table
from deequ_tpu.ops import runtime
from deequ_tpu.ops.fused import (
    AnalyzerRunResult,
    PipelinedAggFold,
    ScanStep,
    _pad_size,
    apply_decode_plan,
    device_results,
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


class MeshPlacement:
    """How a batch's device inputs reach the mesh: each device key padded,
    narrowed and shifted exactly like the single-chip wire, then put with
    the mesh sharding, and one launch of the sharded program."""

    wire_plan = None  # the mesh packs no decode-fused wire

    def __init__(self, fn, mesh, axis_name, keys, batch_size_per_device, dtype, sticky):
        self.fn, self.keys, self.dtype, self.sticky = fn, keys, dtype, sticky
        self.n_devices = mesh.shape[axis_name]
        self.batch_size_per_device = batch_size_per_device
        self.sharding = NamedSharding(mesh, P(axis_name))

    def attrs(self, batch) -> Dict[str, Any]:
        return {"devices": int(self.n_devices)}

    def place(self, batch, built):
        """Pad, narrow and shift each device key, then one sharded put
        per key: a `pack` span (category `dispatch`) with an `h2d` child
        per put, as `pack_batch_inputs` on one chip."""
        # pad to a multiple of n_devices (pow2 per shard)
        per_dev = _pad_size(
            -(-batch.num_rows // self.n_devices), self.batch_size_per_device
        )
        padded = per_dev * self.n_devices
        inputs: Dict[str, Any] = {}
        with observe.span("pack", cat="dispatch", rows=int(batch.num_rows)):
            for key in self.keys:
                arr = runtime.pad_to(built[key], padded)
                if np.issubdtype(arr.dtype, np.integer):
                    arr = runtime.narrow_int_wire(arr, key, self.sticky)
                elif arr.dtype != np.bool_:
                    if np.dtype(self.dtype) == np.float32 and key.startswith("num:"):
                        # same f32 pre-centering as pack_batch_inputs
                        shift = resolve_shift(key, arr, self.sticky, built.get)
                        if shift != 0.0:
                            arr = np.asarray(arr, dtype=np.float64) - shift
                    arr = arr.astype(self.dtype)
                with observe.span(
                    "h2d", cat="dispatch", bytes=int(arr.nbytes),
                    devices=int(self.n_devices),
                ):
                    inputs[key] = jax.device_put(arr, self.sharding)
        return inputs, None

    def launch(self, inputs, _aux):
        """Start the program -> (device outputs, meta box, shard rows)."""
        runtime.record_launch()
        runtime.record_placement(inputs.values())
        return self.fn(inputs), None, _shard_rows(inputs, self.n_devices)


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
        specs = plan.specs

        table = prune_table_columns(table, specs)
        # decode routing after pruning, exactly as in FusedScanPass: the
        # mesh shards the packed wire arrays, so whether a column decoded
        # through the native kernels or the host chain is invisible to it
        decode_plan = plan_decode_fastpath(table, specs)
        if decode_plan is not None:
            table = apply_decode_plan(table, decode_plan)
        n_devices = self.mesh.shape[self.axis_name]
        sticky: Dict[str, Any] = {}
        placement = None
        if merge_analyzers or assisted:
            placement = MeshPlacement(
                _get_distributed_fn(
                    merge_analyzers, self.mesh, self.axis_name, assisted
                ),
                self.mesh, self.axis_name, sorted(plan.device_keys),
                self.batch_size_per_device, runtime.compute_dtype(), sticky,
            )
        runtime.record_pass(
            f"dist-scan[{n_devices}x]:"
            + ",".join(a.name for a in self.analyzers)
        )
        try:
            step = ScanStep(
                placement,
                PipelinedAggFold(
                    merge_analyzers, assisted, n_dev=n_devices, sticky=sticky
                ),
                specs, plan.device_keys,
                [(i, self.analyzers[i]) for i in plan.host_idx],
                [(i, self.analyzers[i]) for i in plan.host_assisted_idx],
                plan.host_keys,
                streaming=bool(getattr(table, "is_streaming", False)),
            )
            step.run(table, self.batch_size_per_device * n_devices)
            aggs, assisted_states, host_results, device_error = step.finish()
            results.update(
                device_results(
                    self.analyzers, merge_idx, assisted_idx, aggs,
                    assisted_states, device_error,
                )
            )
            results.update(host_results)
        except Exception as e:  # noqa: BLE001
            for i in range(len(self.analyzers)):
                results.setdefault(i, AnalyzerRunResult(self.analyzers[i], error=e))

        return [results[i] for i in range(len(self.analyzers))]


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

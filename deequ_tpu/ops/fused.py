"""The fused scan: N analyzers, ONE compiled XLA computation per pass.

This is the TPU-native analogue of the reference's scan-sharing optimizer
(reference: runners/AnalysisRunner.scala:279-326 — all scan-shareable
analyzers run in a single `df.agg(...)` with offset arithmetic). Here the
"offsets" are pytree structure: every analyzer contributes a device_reduce
over a shared, deduplicated set of input arrays, XLA CSE merges the common
subexpressions (masks, counts), and one program per batch produces every
partial state at once.

Cross-batch folding happens host-side in float64 via the same merge_agg
formulas (numpy namespace) — the driver-side semigroup fold, exactly the
role the reference's `State.sum` plays after Catalyst partial aggregation.
"""

from __future__ import annotations

import contextlib
import os
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deequ_tpu import observe
from deequ_tpu.analyzers.base import ScanShareableAnalyzer
from deequ_tpu.analyzers.states import State
from deequ_tpu.core.controller import RunCancelled, StallWatchdog
from deequ_tpu.data.table import Table
from deequ_tpu.ops import pipeline, runtime

DEFAULT_BATCH_SIZE = 1 << 22  # 4M rows: < 2^24 so f32 counts stay exact

_FUSED_CACHE: Dict[Any, Any] = {}
_FUSED_CACHE_MAX = 256  # insertion-order eviction; bounds memory on
# long heterogeneous streams (layouts are sticky per pass, so steady
# state is 1-2 entries per analyzer set)
_FUSED_CACHE_LOCK = threading.Lock()


def _pad_size(n: int, batch_size: int) -> int:
    """Round up to a power of two (min 8): few compiled shapes, no
    per-tail recompilation. Always a multiple of 8 so bitpacked masks
    (1 bit/row) decode to exactly `padded` rows. Delegates to
    runtime.wire_pad_size — the decode-to-wire workers size their
    pre-packed rows with the same function, so the two can never
    disagree on a batch's padded length."""
    return runtime.wire_pad_size(n, batch_size)


def _pack_outputs(tree):
    """Flatten a pytree of device arrays into ONE 1-D array.

    Every aggregate output is fixed-size (scalars, HLL registers, quantile
    samples), but each fetched array pays its own device-to-host round
    trip, and a profile has ~90 leaves: one transfer pays it once.
    Everything is cast to the compute float dtype for the
    single transfer: registers (≤ 63), class/level codes, and per-batch
    counts (≤ 2^24 rows/batch) are all exactly representable in float32.
    Returns (packed_array, meta) where meta unpacks host-side.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    specs = [(str(leaf.dtype), tuple(leaf.shape)) for leaf in leaves]
    if not leaves:
        return jnp.zeros(0, dtype=runtime.compute_dtype()), (treedef, specs)
    dt = runtime.compute_dtype()
    packed = jnp.concatenate([jnp.ravel(leaf).astype(dt) for leaf in leaves])
    return packed, (treedef, specs)


def unpack_outputs(packed: np.ndarray, meta):
    treedef, specs = meta
    buf = np.asarray(packed).reshape(-1)
    leaves: List[Any] = []
    off = 0
    for dtype_name, shape in specs:
        n = int(np.prod(shape)) if shape else 1
        leaves.append(buf[off : off + n].astype(dtype_name).reshape(shape))
        off += n
    return jax.tree_util.tree_unflatten(treedef, leaves)


def plan_shape_key(
    analyzers: Sequence[ScanShareableAnalyzer],
    assisted: Sequence[ScanShareableAnalyzer] = (),
    layout: Any = None,
) -> Tuple[Any, ...]:
    """The compiled-plan cache key: the plan-*shape* component of
    `repository.states.plan_signature` (analyzer reprs in pass order)
    plus the wire layout and the x64 flag — everything that changes the
    traced program. Two tenants whose suites reduce to the same shape
    share one jitted fused fn, so the jit/fuse cost is paid once per
    shape fleet-wide."""
    return (
        tuple(_traced_repr(a) for a in analyzers),
        tuple(_traced_repr(a) for a in assisted),
        layout,
        bool(jax.config.jax_enable_x64),
    )


def _traced_repr(a: ScanShareableAnalyzer) -> str:
    """An analyzer's repr, plus the sample size a quantile family traces
    into its program (`_sample_size` / `_cap` follow the KLL sizing, not
    the analyzer's fields)."""
    size = getattr(a, "_sample_size", None) or getattr(a, "_cap", None)
    return repr(a) if size is None else f"{a!r}/{size()}"


def get_fused_fn(
    analyzers: Sequence[ScanShareableAnalyzer],
    assisted: Sequence[ScanShareableAnalyzer] = (),
    layout: Any = None,
):
    """Compiled fused pass over packed inputs.

    `layout` maps each packed input buffer to its named rows:
    tuple of (dtype_name, (key, ...)); buffer `dtype_name` is a stacked
    (k, padded) array whose row i is input `key_i`. Returns (fn, meta_box);
    meta_box['meta'] (filled at trace time) drives unpack_outputs.
    """
    key = plan_shape_key(analyzers, assisted, layout)
    with _FUSED_CACHE_LOCK:
        cached = _FUSED_CACHE.get(key)
    runtime.record_plan_cache(cached is not None)
    if cached is None:
        meta_box: Dict[str, Any] = {}
        if layout is None:
            groups, const_keys, padded = None, (), 0
        else:
            groups, const_keys, padded = layout

        def fused(packed_inputs):
            if groups is None:
                inputs = packed_inputs
            else:
                # Unpack the wire format (see _run_pass): per-group 1-D
                # buffers (1-D H2D transfers avoid the host-side relayout
                # a 2-D put pays on this platform); bool masks arrive
                # bitpacked (1 bit/row) and all-true masks aren't
                # transferred at all — they're synthesized from the row
                # count. Decoding is a few VPU ops: compute is ~free next
                # to the bytes the host link moves.
                inputs = {}
                for group_name, entries in groups:
                    rows = packed_inputs[group_name].reshape(len(entries), -1)
                    for i, (in_key, kind) in enumerate(entries):
                        row = rows[i]
                        if kind == "bits":
                            shifts = jnp.arange(7, -1, -1, dtype=jnp.uint8)
                            bits = (row[:, None] >> shifts[None, :]) & jnp.uint8(1)
                            inputs[in_key] = bits.reshape(-1).astype(jnp.bool_)
                        elif kind == "ival":
                            # decode-to-wire narrowed int row for a num:
                            # key: widen to the compute dtype (the planner
                            # pinned a width whose every value is exact in
                            # float64, so this equals the f64 row the
                            # Column path would have shipped)
                            inputs[in_key] = row.astype(runtime.compute_dtype())
                        elif kind == "int" and row.dtype.itemsize < 4:
                            # widen wire-narrowed ints; int32/int64 as-is
                            inputs[in_key] = row.astype(jnp.int32)
                        else:
                            inputs[in_key] = row
                if const_keys:
                    n = packed_inputs["__nrows"][0]
                    all_rows = jnp.arange(padded, dtype=jnp.int32) < n
                    for in_key in const_keys:
                        inputs[in_key] = all_rows
            # trace-time marker: device-assisted members may use single-
            # device-only strategies (e.g. the pallas hist16 radix-select,
            # whose host finisher needs this batch's host inputs — not
            # available per-shard in the mesh pass)
            inputs["__single_device"] = True
            out = (
                tuple(a.device_reduce(inputs, jnp) for a in analyzers),
                tuple(a.device_batch(inputs, jnp) for a in assisted),
            )
            packed_out, meta = _pack_outputs(out)
            meta_box["meta"] = meta
            return packed_out

        cached = (jax.jit(fused), meta_box)
        with _FUSED_CACHE_LOCK:
            # two threads may have built concurrently: first insert wins
            # so both use the same meta_box the traced program fills
            cached = _FUSED_CACHE.setdefault(key, cached)
            while len(_FUSED_CACHE) > _FUSED_CACHE_MAX:
                _FUSED_CACHE.pop(next(iter(_FUSED_CACHE)))
    return cached


def resolve_shift(key: str, arr: np.ndarray, sticky, lookup) -> float:
    """Scan-constant pre-centering shift for a num: wire key. Picked
    from the first VALID row (null slots are 0.0-filled and would
    otherwise silently disable the centering); recorded sticky so every
    batch of the pass ships the same shift."""
    shift_key = f"shift:{key}"
    shift = sticky.get(shift_key)
    if shift is None:
        shift = 0.0
        valid = lookup(f"valid:{key[len('num:'):]}") if key.startswith("num:") else None
        if valid is not None:
            valid = np.asarray(valid, dtype=bool)
            first = np.flatnonzero(valid)[:1]
            if first.size:
                candidate = float(arr[int(first[0])])
                if np.isfinite(candidate):
                    shift = candidate
        else:
            finite = arr[np.isfinite(arr)]
            if finite.size:
                shift = float(finite[0])
        sticky[shift_key] = shift
    return shift


def wire_shifts(sticky) -> Dict[str, float]:
    """The f32 wire's per-column pre-centering shifts recorded by
    pack_batch_inputs, keyed by input key (empty on the f64 wire)."""
    return {
        key[len("shift:"):]: value
        for key, value in sticky.items()
        if key.startswith("shift:") and value != 0.0
    }


def pack_batch_inputs(
    built_items, padded: int, dtype, sticky=None, num_rows=None, prepacked=None
):
    """Build the minimal wire format for one batch, as a `pack` span
    (category `dispatch`) with one `h2d` child per device put.

    Fewer bytes over the host link, whatever its bandwidth:
      * bool masks  -> bitpacked, 1 bit/row
      * all-true masks (no filter, null-free column) -> NOT transferred;
        synthesized on device from the row count
      * integers    -> range-downcast to int8/int16 where exact
      * floats      -> the compute dtype
    Same-format arrays are concatenated into ONE flat 1-D buffer per group
    so each put streams at bandwidth instead of paying per-array latency.

    `prepacked` maps input keys to runtime.WireRows the decode-to-wire
    workers already emitted in final wire form (the batch Table's
    ``wire_rows``): their padded buffers splice into the group buffers
    verbatim — no packbits, no narrowing, no shift math here. A
    prepacked key's built array may be None (the Column was never
    materialized). Sticky pinning follows the same rules as the packed
    route, so fused and fallback batches of one pass converge on the
    same layout.

    Returns (packed_inputs, layout); `layout` is hashable and keys the
    compiled program (groups, const_keys, padded). `sticky` (a dict the
    caller keeps for the life of one pass) pins each key's wire format
    across batches — a key only ever moves toward the wider/general form
    (const->bits, narrow int->wider int), bounding recompiles at 2 per key
    instead of one per distinct batch data range.
    """
    with observe.span("pack", cat="dispatch") as sp:
        if sp and num_rows is not None:
            sp.set(rows=int(num_rows))
        return _pack_batch_inputs(
            built_items, padded, dtype, sticky, num_rows, prepacked
        )


def _pack_batch_inputs(built_items, padded, dtype, sticky, num_rows, prepacked):
    if sticky is None:
        sticky = {}
    if prepacked is None:
        prepacked = {}
    _built_map = {k: a for k, a in built_items}

    def _built_lookup(key: str):
        return _built_map.get(key)

    entries_by_group: Dict[tuple, List[tuple]] = {}
    const_keys: List[str] = []
    for key, arr in built_items:
        wire_row = prepacked.get(key)
        if wire_row is not None:
            if wire_row.kind == "bits":
                # same elision/pinning ladder as the bool branch below:
                # all-valid rows elide to const until any batch has an
                # invalid row, then the key is bits for the pass
                if wire_row.all_valid and sticky.get(key, "const") == "const":
                    sticky[key] = "const"
                    const_keys.append(key)
                    continue
                sticky[key] = "bits"
                entries_by_group.setdefault(("uint8", "bits"), []).append(
                    (key, "bits", wire_row.arr)
                )
            elif wire_row.kind == "ival":
                entries_by_group.setdefault(
                    (wire_row.arr.dtype.name, "ival"), []
                ).append((key, "ival", wire_row.arr))
            else:  # "val": compute-dtype row, shift already applied
                sticky.setdefault(f"shift:{key}", wire_row.shift)
                entries_by_group.setdefault(
                    (np.dtype(dtype).name, "val"), []
                ).append((key, "val", wire_row.arr))
            continue
        if num_rows is None:
            num_rows = len(arr)
        if arr.dtype == np.bool_:
            if arr.all() and sticky.get(key, "const") == "const":
                sticky[key] = "const"
                const_keys.append(key)
                continue
            sticky[key] = "bits"
            bits = np.zeros(padded // 8, dtype=np.uint8)
            packed_bits = np.packbits(arr)
            bits[: len(packed_bits)] = packed_bits
            entries_by_group.setdefault(("uint8", "bits"), []).append(
                (key, "bits", bits)
            )
        elif np.issubdtype(arr.dtype, np.integer):
            arr = runtime.narrow_int_wire(arr, key, sticky)
            entries_by_group.setdefault((arr.dtype.name, "int"), []).append(
                (key, "int", arr)
            )
        else:
            if np.dtype(dtype) == np.float32 and key.startswith("num:"):
                # pre-center before the f32 cast: clustered data (mean
                # ~1e7, variance ~1e-2) would otherwise lose its entire
                # variance signal to f32 quantization ON THE WIRE. The
                # shift is scan-constant (sticky) so cross-batch merges
                # stay valid; analyzers undo it via unshift_agg/_batch.
                shift = resolve_shift(key, arr, sticky, _built_lookup)
                if shift != 0.0:
                    arr = np.asarray(arr, dtype=np.float64) - shift
            arr = arr.astype(dtype, copy=False)
            entries_by_group.setdefault((np.dtype(dtype).name, "val"), []).append(
                (key, "val", arr)
            )

    packed_inputs: Dict[str, Any] = {}
    groups = []
    for (dtype_name, kind), entries in sorted(entries_by_group.items()):
        group_name = f"{dtype_name}:{kind}"
        row_len = padded // 8 if kind == "bits" else padded
        buf = np.zeros(len(entries) * row_len, dtype=dtype_name)
        for i, (_key, _kind, arr) in enumerate(entries):
            buf[i * row_len : i * row_len + len(arr)] = arr
        packed_inputs[group_name] = _h2d_put(buf)
        groups.append((group_name, tuple((e[0], e[1]) for e in entries)))
    if const_keys:
        packed_inputs["__nrows"] = _h2d_put(np.array([num_rows or 0], dtype=np.int32))
    layout = (tuple(groups), tuple(sorted(const_keys)), padded)
    return packed_inputs, layout


def _h2d_put(buf: np.ndarray):
    """One host-to-device put of a packed wire buffer, as an `h2d` span."""
    with observe.span("h2d", cat="dispatch") as sp:
        if sp:
            sp.set(bytes=int(buf.nbytes))
        return jnp.asarray(buf)


# -- pure plan construction ---------------------------------------------------
#
# Everything the pass decides BEFORE it sees a row — member placement,
# the deduplicated input-spec set, family-kernel job identity and
# grouping — lives in the pure functions below. `FusedScanPass.run`,
# `DistributedScanPass._run`, and `_precompute_family_kernels` consume
# them at runtime; the static cost analyzer (deequ_tpu/lint/cost.py)
# calls the SAME functions so its predictions cannot drift from the
# planner (the trace-differential suite pins this).


@dataclass
class ScanMemberPlan:
    """Data-free partition of one scan pass's members by placement.

    Index lists refer to positions in the analyzer sequence handed to
    `plan_scan_members`; an index appears in exactly one of the four
    lists or in `spec_errors` (spec construction failed — that analyzer
    fails alone, not the pass)."""

    mode: str
    merge_idx: List[int] = field(default_factory=list)
    assisted_idx: List[int] = field(default_factory=list)
    host_idx: List[int] = field(default_factory=list)
    host_assisted_idx: List[int] = field(default_factory=list)
    specs: Dict[str, Any] = field(default_factory=dict)
    device_keys: set = field(default_factory=set)
    # device keys consumed by device-ASSISTED members: their host
    # finishers may re-read the built host arrays (fold.submit's
    # host_ctx), so these keys are not packed-only and the decode-to-wire
    # planner must keep their columns on the Column path
    assisted_keys: set = field(default_factory=set)
    host_keys: Dict[int, List[str]] = field(default_factory=dict)
    spec_errors: Dict[int, BaseException] = field(default_factory=dict)

    @property
    def packed_only_keys(self) -> set:
        """Device keys whose ONLY consumers are merge members' compiled
        reduces — the keys that live purely on the packed wire. The
        decode-to-wire planner may fuse a column exactly when every one
        of its consumer keys is in this set."""
        host = set()
        for keys in self.host_keys.values():
            host.update(keys)
        return self.device_keys - self.assisted_keys - host

    @property
    def device_member_count(self) -> int:
        return len(self.merge_idx) + len(self.assisted_idx)

    @property
    def host_member_count(self) -> int:
        return len(self.host_idx) + len(self.host_assisted_idx)

    @property
    def any_members(self) -> bool:
        return bool(
            self.merge_idx
            or self.assisted_idx
            or self.host_idx
            or self.host_assisted_idx
        )


def plan_scan_members(analyzers: Sequence[Any], mode: Optional[str] = None) -> ScanMemberPlan:
    """Partition a scan's members by placement — pure and data-free.

    Placement (runtime.placement_mode): on a slow device link, discrete
    analyzers (mask/code-only inputs) — or, below the bandwidth floor,
    EVERY analyzer — fold on the host inside the SAME logical scan
    instead of shipping rows; `host_only` device-assisted members
    (strings, dict codes) never ship regardless of placement, and
    `value_exact` members (min/max) fold on the host whenever the wire
    is float32."""
    if mode is None:
        mode = runtime.placement_mode()
    plan = ScanMemberPlan(mode=mode)
    host_all = mode == "host-all"
    host_discrete = host_all or mode == "host-discrete"
    # an f32 device wire cannot carry a float64 column's values exactly,
    # so members whose metric IS one of those values fold on the host
    f32_wire = runtime.compute_dtype() == jnp.float32
    for i, analyzer in enumerate(analyzers):
        try:
            analyzer_specs = analyzer.input_specs()
        except Exception as e:  # noqa: BLE001
            plan.spec_errors[i] = e
            continue
        if getattr(analyzer, "device_assisted", False):
            if host_all or getattr(analyzer, "host_only", False):
                plan.host_assisted_idx.append(i)
                plan.host_keys[i] = [s.key for s in analyzer_specs]
            else:
                plan.assisted_idx.append(i)
                plan.device_keys.update(s.key for s in analyzer_specs)
                plan.assisted_keys.update(s.key for s in analyzer_specs)
        elif (
            host_all
            or (host_discrete and getattr(analyzer, "discrete_inputs", False))
            or (f32_wire and getattr(analyzer, "value_exact", False))
        ):
            plan.host_idx.append(i)
            plan.host_keys[i] = [s.key for s in analyzer_specs]
        else:
            plan.merge_idx.append(i)
            plan.device_keys.update(s.key for s in analyzer_specs)
        for spec in analyzer_specs:
            plan.specs.setdefault(spec.key, spec)
    return plan


def build_union_plan(
    plans: Sequence[Sequence[Any]],
) -> Tuple[List[Any], List[List[int]]]:
    """Union-plan builder for fleet-level scan sharing: merge several
    suites' analyzer lists into ONE superset fused scan — pure and
    data-free.

    Analyzers deduplicate by engine identity ((type, repr), the same
    equality the runner and the state-cache signature use), preserving
    first-appearance order, so the union's pass order is deterministic
    in submission order. Returns ``(union, memberships)``:
    ``union`` is the superset analyzer list, ``memberships[i]`` indexes
    plan i's (deduplicated, order-preserved) analyzers into ``union``.
    Each member plan's states fan back out by selecting its rows of the
    union's results — bit-identical to a solo run, because per-analyzer
    fold states are independent of which other members ride the pass
    (the multi-family kernels are proven batched-vs-solo identical and
    partition states merge over the semigroup).

    Equivalent-but-differently-spelled where clauses deliberately stay
    separate members: each suite's states then fold under its own
    spelling, keeping the fan-out trivially exact (the prover records
    such pairs as CONTAINED_WITH_RESIDUAL when asked directly)."""
    union: List[Any] = []
    index: Dict[Any, int] = {}
    memberships: List[List[int]] = []
    for plan in plans:
        rows: List[int] = []
        seen: set = set()
        for analyzer in plan:
            if analyzer in seen:
                continue
            seen.add(analyzer)
            pos = index.get(analyzer)
            if pos is None:
                pos = len(union)
                index[analyzer] = pos
                union.append(analyzer)
            rows.append(pos)
        memberships.append(rows)
    return union, memberships


@dataclass(frozen=True)
class FamilyJobPlan:
    """One planned family-kernel job: the (column, where) family whose
    fused moments + decimated quantile sample (+ HLL registers when an
    ApproxCountDistinct on the same family consumes them) come out of a
    single C traversal. Identity is the memo key `qkey`."""

    column: str
    where: Optional[str]
    wkey: str
    cap: int
    want_regs: bool

    @property
    def qkey(self) -> str:
        return f"__qsample:{self.column}:{self.wkey}:{self.cap}"

    @property
    def mkey(self) -> str:
        return f"__moments:{self.column}:{self.wkey}"

    @property
    def rkey(self) -> str:
        return f"__hllregs:{self.column}:{self.wkey}"


def family_group_key(wkey: str, cap: int) -> Tuple[str, int]:
    """Grouping key for batching family jobs into ONE multi-column
    native traversal: same where mask, same sample cap. (All jobs of one
    batch share the row count, so this is the full runtime key too.)"""
    return (wkey, cap)


def plan_family_jobs(
    host_assisted_members: Sequence[Any],
    host_members: Sequence[Any] = (),
) -> List[FamilyJobPlan]:
    """Plan the family-kernel jobs a host fold would run — pure and
    data-free. One job per distinct (column, where, cap) family across
    the host-assisted members (quantile sketches); `want_regs` marks
    families whose HLL registers a host-folded ApproxCountDistinct on
    the same (column, where) will consume."""
    from deequ_tpu.analyzers.base import where_key

    acd_families = {
        (getattr(member, "column", None), where_key(getattr(member, "where", None)))
        for member in host_members
        if getattr(member, "name", "") == "ApproxCountDistinct"
    }
    jobs: List[FamilyJobPlan] = []
    seen: set = set()
    for member in host_assisted_members:
        sample_size = getattr(member, "_sample_size", None)
        column = getattr(member, "column", None)
        if sample_size is None or column is None:
            continue
        where = getattr(member, "where", None)
        wkey = where_key(where)
        job = FamilyJobPlan(
            column=column,
            where=where,
            wkey=wkey,
            cap=int(sample_size()),
            want_regs=(column, wkey) in acd_families,
        )
        if job.qkey in seen:
            continue
        seen.add(job.qkey)
        jobs.append(job)
    return jobs


def group_family_jobs(
    jobs: Sequence[FamilyJobPlan],
) -> List[Tuple[Tuple[str, int], List[FamilyJobPlan]]]:
    """Group planned family jobs by `family_group_key` — each group is
    one (possibly multi-column batched) native kernel dispatch per
    batch. Order: first appearance, matching the runtime dispatch."""
    groups: Dict[Tuple[str, int], List[FamilyJobPlan]] = {}
    for job in jobs:
        groups.setdefault(family_group_key(job.wkey, job.cap), []).append(job)
    return list(groups.items())


class AnalyzerRunResult:
    """Outcome of one analyzer in a pass: a state (possibly None = empty)
    or an error."""

    def __init__(
        self,
        analyzer: ScanShareableAnalyzer,
        state: Optional[State] = None,
        error: Optional[BaseException] = None,
    ):
        self.analyzer = analyzer
        self.state = state
        self.error = error

    def state_or_raise(self) -> Optional[State]:
        if self.error is not None:
            raise self.error
        return self.state


def _merge_partition_results(
    a: AnalyzerRunResult, b: AnalyzerRunResult
) -> AnalyzerRunResult:
    """Semigroup merge of one analyzer's outcome across two partitions:
    errors win (a failing analyzer fails for the dataset, matching the
    single-pass contract), a None state is the identity (an empty
    partition contributes nothing), and a failing merge becomes that
    analyzer's error, never the pass's."""
    if a.error is not None:
        return a
    if b.error is not None:
        return b
    if a.state is None:
        return AnalyzerRunResult(a.analyzer, state=b.state)
    if b.state is None:
        return a
    try:
        return AnalyzerRunResult(a.analyzer, state=a.state.merge(b.state))
    except Exception as e:  # noqa: BLE001
        return AnalyzerRunResult(a.analyzer, error=e)


def scan_partition(
    analyzers,
    partition,
    *,
    batch_size=None,
    forensics=None,
    controller=None,
):
    """Fold ONE partition to per-analyzer results through the normal
    single-source fused path (native reader read-ahead, decode->wire
    fusion, backpressured pipeline — everything a whole-dataset scan
    uses). This is the one sub-scan both `_run_partitioned` and the
    sharded scan (parallel/multihost.py) call, which is what makes a
    shard's per-partition states byte-identical to a solo run's: same
    analyzer list, same batch sizing, same fold — same bits."""
    sub = FusedScanPass(
        analyzers, batch_size, forensics=forensics, controller=controller
    )
    return sub.run(partition.source())


def _to_f64(tree: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda x: np.asarray(x, dtype=np.float64), tree
    )


def prune_table_columns(table, specs: Dict[str, Any]):
    """Column pruning for streaming sources: when every live input spec
    declares the columns it reads, restrict the scan to their union so
    the source only decodes what the pass consumes (the reference gets
    this from Spark's column pruning; here it's the difference between
    decoding 6 Parquet columns per pass and 3). In-memory Tables slice
    lazily and don't implement with_columns; unknown-read specs
    (columns=None) disable pruning for safety."""
    with_columns = getattr(table, "with_columns", None)
    if with_columns is None:
        return table
    needed: set = set()
    for spec in specs.values():
        if spec.columns is None:
            return table
        needed.update(spec.columns)
    if not needed:
        # e.g. a Size()-only pass: row counts need only the cheapest column
        names = getattr(table, "column_names", None)
        if not names:
            return table
        needed = {names[0]}
    return with_columns(sorted(needed))


def plan_row_group_prune(table, members):
    """Static row-group pruning for a parquet-backed scan: build a
    PrunePlan (lint/pushdown.py's three-valued interpreter) from the
    file's row-group statistics and the live members' where filters.
    None when the source has no statistics surface, the knob is off, or
    anything at all goes wrong — pruning is an optimization, never a
    failure mode. The decision itself is pure: the source is the only
    statistics reader."""
    if not runtime.pushdown_enabled():
        return None
    stats_fn = getattr(table, "row_group_stats", None)
    if stats_fn is None or getattr(table, "with_prune", None) is None:
        return None
    from deequ_tpu.lint.pushdown import build_prune_plan

    try:
        groups = stats_fn()
        if not groups:
            return None
        return build_prune_plan(
            [getattr(m, "where", None) for m in members],
            groups,
            dict(table.schema),
        )
    except Exception:  # noqa: BLE001
        return None


#: spec-key prefixes whose builds consume only the packed representation
#: of a dictionary-string column (codes + mask + uniques digest) — the
#: lazy per-row string gather never fires, so such columns are safe for
#: the native decode's lazy-values Column. The predicate prefixes
#: (`where`, `pred`, `prednn`) hold this for a predicate over that one
#: column, which `data/expr.py:Predicate` evaluates per dictionary entry;
#: one over several columns, or over a dictionary about as large as the
#: batch, takes the row path and materializes the strings on demand
#: (slower, never wrong). An unknown prefix routes the column to the host
#: chain instead (conservative, never wrong). Numeric/bool columns skip
#: this check: their Columns are fully materialized by both paths.
PACKED_SAFE_PREFIXES = frozenset(
    {
        "num", "valid", "where", "pred", "prednn", "match", "dtclass",
        "hll", "lcc_codes", "lcc_uniq", "optnum", "optnumv",
    }
)

#: per-row bytes of intermediate host materialization the fast path
#: avoids for one column: the fill_null'd arrow array copy (element
#: width) plus the bitmap→bool mask expansion (1 byte). Prediction-only
#: accounting for EXPLAIN/cost — never used for correctness.
_DECODE_TOKEN_BYTES = {
    "double": 8, "float": 4, "int8": 1, "int16": 2, "int32": 4,
    "int64": 8, "uint8": 1, "uint16": 2, "uint32": 4, "uint64": 8,
    "bool": 1, "dictionary<string,int32>": 4,
}


@dataclass(frozen=True)
class DecodePlan:
    """Static per-column decode routing for one parquet-backed scan:
    which columns take the buffer-level native fast path, which fall
    back to the host chain (with the reason, for EXPLAIN's DQ312), and
    the worker count the scan decodes with. Purely a perf/accounting
    decision — both routes emit bit-identical Columns.

    The wire_* fields carry the decode-to-wire verdict layered on top:
    columns (a subset of `fast`) whose every live consumer is
    packed-only decode STRAIGHT to wire buffers, and the rest of the
    wire candidates record why they stayed on the Column path (column,
    reason, offending consumer key — EXPLAIN's DQ313 caret)."""

    fast: Tuple[str, ...]
    fallbacks: Tuple[Tuple[str, str], ...]  # (column, reason)
    workers: int
    wire_fused: Tuple[str, ...] = ()
    wire_falloffs: Tuple[Tuple[str, str, str], ...] = ()  # (col, reason, key)
    wire_batch: int = 0
    wire_specs: Any = field(default=None, compare=False)  # col -> ColumnWireSpec
    # native-parquet-reader verdict layered on the fast set: columns
    # whose EVERY live column chunk the page decoder proves from footer
    # metadata (classify_reader_columns), the per-column fall-off
    # reasons (EXPLAIN's DQ315), and the non-pruned group count the
    # chunk counters scale by. reader_planned distinguishes "reader
    # planning ran and fused nothing" from "never planned" so the
    # drift pin sees 0 == 0 rather than a missing series.
    reader_cols: Tuple[str, ...] = ()
    reader_falloffs: Tuple[Tuple[str, str], ...] = ()  # (column, reason)
    reader_groups: int = 0
    reader_planned: bool = False
    # encoded-fold verdict layered on the reader set: columns whose
    # every live chunk is provably all-dictionary-coded AND whose every
    # consumer the run-fold memos can serve (classify_encfold_columns),
    # the per-column fall-off reasons (EXPLAIN's DQ325), and the
    # col -> EncFoldColSpec map the source ships to decode_unit.
    # enc_planned follows reader_planned's record-the-zeros contract.
    enc_cols: Tuple[str, ...] = ()
    enc_falloffs: Tuple[Tuple[str, str], ...] = ()  # (column, reason)
    enc_specs: Any = field(default=None, compare=False)
    enc_planned: bool = False

    @property
    def total(self) -> int:
        return len(self.fast) + len(self.fallbacks)


def classify_decode_columns(
    col_types: Dict[str, str], specs: Dict[str, Any]
) -> Tuple[List[str], List[Tuple[str, str]]]:
    """Pure eligibility split over a scan's columns. `col_types` is the
    source's decode_column_types() token map; `specs` the live input
    specs (their key prefixes prove which dictionary-string columns are
    consumed packed-only). Shared verbatim by the planner and the cost
    model so prediction and execution can never disagree."""
    from deequ_tpu.ops import native

    consumers: Dict[str, set] = {}
    for spec in specs.values():
        prefix = spec.key.split(":", 1)[0]
        for col in spec.columns or ():
            consumers.setdefault(col, set()).add(prefix)
    fast: List[str] = []
    fallbacks: List[Tuple[str, str]] = []
    for name in sorted(col_types):
        token = col_types[name]
        if token in native.DECODE_PRIMITIVES or token == "bool":
            fast.append(name)
        elif token == "dictionary<string,int32>":
            unsafe = sorted(consumers.get(name, ()) - PACKED_SAFE_PREFIXES)
            if unsafe:
                fallbacks.append(
                    (
                        name,
                        "host string values may be required by "
                        + ", ".join(unsafe),
                    )
                )
            else:
                fast.append(name)
        elif token in ("string", "large_string"):
            fallbacks.append((name, "plain string values are host objects"))
        elif token.startswith("timestamp"):
            fallbacks.append((name, "timestamp decode needs an arrow cast"))
        elif token.startswith("decimal"):
            fallbacks.append((name, "decimal values decode host-side"))
        else:
            fallbacks.append((name, f"no native kernel for {token}"))
    return fast, fallbacks


def decode_saved_bytes_per_row(plan: DecodePlan, col_types: Dict[str, str]) -> int:
    """Predicted bytes/row of intermediate materialization the fast
    columns skip (value copy + mask byte-expansion)."""
    return sum(
        _DECODE_TOKEN_BYTES.get(col_types.get(c, ""), 0) + 1 for c in plan.fast
    )


def classify_reader_columns(
    col_types: Dict[str, str],
    groups,
    codec_mask: int,
    skip_groups=frozenset(),
) -> Tuple[List[str], List[Tuple[str, str]], int]:
    """Pure native-parquet-reader eligibility split over a scan's
    fast-decode columns, proved statically from footer metadata alone.

    `col_types` maps the CANDIDATE columns (the decode plan's fast set —
    reader ⊆ fastpath by construction) to their decode tokens; `groups`
    are the source's row_group_stats() with chunk-layout fields
    (physical type, codec, page encodings, byte ranges, nesting);
    `codec_mask` is native.reader_codecs()'s loadable-decompressor
    bitmask; `skip_groups` replays the prune verdict so only chunks the
    scan will actually read are judged. A column qualifies only when
    EVERY live chunk does — one odd chunk falls the whole column back,
    with a reason naming the disqualifying encoding/codec (EXPLAIN's
    DQ315). Returns (reader_cols, falloffs, live_group_count). Shared
    verbatim by the planner and the cost model so prediction and
    execution can never disagree."""
    from deequ_tpu.ops import native

    live = [rg for rg in groups if rg.index not in skip_groups]
    reader: List[str] = []
    falloffs: List[Tuple[str, str]] = []
    if not live:
        return (
            reader,
            [(n, "every row group is pruned") for n in sorted(col_types)],
            0,
        )
    for name in sorted(col_types):
        token = col_types[name]
        spec = native.READER_TOKENS.get(token)
        if spec is None:
            falloffs.append((name, f"no native page decoder for {token}"))
            continue
        allowed_phys, _ = spec
        reason = None
        for rg in live:
            st = rg.columns.get(name)
            if (
                st is None
                or st.physical_type is None
                or st.codec is None
                or st.encodings is None
                or st.chunk_offset is None
                or st.chunk_bytes is None
                or st.num_values is None
                or st.max_def_level is None
                or st.max_rep_level is None
            ):
                reason = (
                    f"row group {rg.index} carries no chunk layout metadata"
                )
                break
            if st.physical_type not in allowed_phys:
                reason = (
                    f"physical type {st.physical_type} cannot back {token}"
                )
                break
            bit = native.READER_CODEC_MASK.get(st.codec)
            if bit is None:
                reason = f"codec {st.codec} has no native decompressor"
                break
            if not (codec_mask & bit):
                reason = f"codec {st.codec} library is not loadable here"
                break
            extra = sorted(set(st.encodings) - native.READER_ENCODINGS)
            if extra:
                reason = f"page encoding {extra[0]} has no native decoder"
                break
            if token == "bool" and (
                set(st.encodings) & {"PLAIN_DICTIONARY", "RLE_DICTIONARY"}
            ):
                reason = "dictionary-encoded boolean pages decode via arrow"
                break
            if st.max_rep_level != 0 or st.max_def_level > 1:
                reason = "nested or repeated values need the arrow reader"
                break
            if int(st.num_values) != int(rg.num_rows):
                reason = "chunk value count disagrees with the row group"
                break
        if reason is not None:
            falloffs.append((name, reason))
        else:
            reader.append(name)
    return reader, falloffs, len(live)


def reader_saved_alloc_bytes_per_row(
    reader_cols, col_types: Dict[str, str]
) -> int:
    """Predicted bytes/row of arrow materialization the native reader
    skips per fused column: the decoded arrow array (element width) plus
    its validity bitmap byte — the buffers pyarrow would have built just
    for the decode kernels to re-read. Prediction-only accounting for
    EXPLAIN/cost — never used for correctness."""
    return sum(
        _DECODE_TOKEN_BYTES.get(col_types.get(c, ""), 0) + 1
        for c in reader_cols
    )


#: analyzer families the encoded-fold planner may serve from run-fold
#: memos (ops/analyzers answering from the family/moments memo keys):
#: anything else on the column needs row-width values and falls it off.
_ENCFOLD_ANALYZERS = frozenset(
    {
        "Mean", "Sum", "Minimum", "Maximum", "StandardDeviation",
        "Completeness", "ApproxQuantile", "ApproxQuantiles",
        "ApproxCountDistinct",
    }
)

#: members whose family job publishes the full sketch memos
_ENCFOLD_SKETCH = frozenset(
    {"ApproxQuantile", "ApproxQuantiles", "ApproxCountDistinct"}
)

#: input-spec key prefixes the memo publication can stand in for
_ENCFOLD_KEY_PREFIXES = frozenset({"num", "valid", "hll"})


def classify_encfold_columns(
    col_types: Dict[str, str],
    analyzers,
    specs: Dict[str, Any],
    device_keys,
    groups,
    skip_groups=frozenset(),
    int_bounds=None,
):
    """Pure encoded-fold eligibility split over a scan's native-reader
    columns, proved statically — exactly like classify_reader_columns.

    `col_types` maps the CANDIDATE columns (the reader set — encoded
    fold ⊆ reader by construction) to their decode tokens; `analyzers`
    are the pass's live members; `specs` the deduplicated input specs
    (their key prefixes prove which consumers the memo publication can
    serve); `device_keys` the member plan's device-consumed key set (a
    device-packed column would expand its stub every batch — excluded);
    `groups` the row_group_stats with page-placement fields;
    `int_bounds` the statically pinned footer min/max per column. A
    column qualifies only when EVERY live chunk is provably
    all-dictionary-coded AND every consumer is memo-servable — one odd
    chunk or consumer falls the whole column back, with a reason naming
    the disqualifier (EXPLAIN's DQ325). Returns
    (col -> EncFoldColSpec, falloffs). Shared verbatim by the planner
    and the cost model so prediction and execution can never
    disagree."""
    from deequ_tpu.data import native_reader as nr
    from deequ_tpu.data.encfold import EncFoldColSpec

    live = [rg for rg in groups if rg.index not in skip_groups]
    if not live:
        return {}, [
            (n, "codec: every row group is pruned")
            for n in sorted(col_types)
        ]
    int_bounds = int_bounds or {}
    # per-column consumer views: spec key prefixes + exact keys (for the
    # device-placement exclusion), analyzer names
    prefixes: Dict[str, set] = {}
    keys_by_col: Dict[str, set] = {}
    for spec in specs.values():
        prefix = spec.key.split(":", 1)[0]
        for col in spec.columns or ():
            prefixes.setdefault(col, set()).add(prefix)
            keys_by_col.setdefault(col, set()).add(spec.key)
    names: Dict[str, set] = {}
    wheres: Dict[str, set] = {}
    for a in analyzers:
        try:
            a_cols = set()
            for s in a.input_specs():
                a_cols.update(s.columns or ())
                if s.columns is None:
                    # unknowable reads: the analyzer may touch anything
                    a_cols.update(col_types)
        except Exception:  # noqa: BLE001 - unknowable reads: consume all
            a_cols = set(col_types)
        for col in a_cols:
            names.setdefault(col, set()).add(a.name)
            if getattr(a, "where", None) is not None:
                wheres.setdefault(col, set()).add(a.name)
    enc: Dict[str, Any] = {}
    falloffs: List[Tuple[str, str]] = []
    for name in sorted(col_types):
        token = col_types[name]
        if token not in nr.ENCFOLD_TOKENS:
            falloffs.append(
                (name, f"dtype: no run-fold kernel for {token}")
            )
            continue
        consumers = names.get(name, set())
        bad = sorted(consumers - _ENCFOLD_ANALYZERS)
        if bad:
            falloffs.append(
                (name, f"analyzer: {bad[0]} needs row-width values")
            )
            continue
        filtered = sorted(wheres.get(name, ()))
        if filtered:
            falloffs.append(
                (
                    name,
                    f"analyzer: {filtered[0]} carries a where filter "
                    "(family memos publish unfiltered only)",
                )
            )
            continue
        extra = sorted(prefixes.get(name, set()) - _ENCFOLD_KEY_PREFIXES)
        if extra:
            falloffs.append(
                (name, f"analyzer: consumer {extra[0]}: needs row values")
            )
            continue
        if keys_by_col.get(name, set()) & set(device_keys):
            falloffs.append(
                (name, "analyzer: consumed by a device-placed member")
            )
            continue
        has_sketch = bool(consumers & _ENCFOLD_SKETCH)
        kind = "f64" if token in ("double", "float") else "i64"
        bounds = int_bounds.get(name)
        publish_moments = (
            kind == "i64"
            and "StandardDeviation" not in consumers
            and bounds is not None
            and -(1 << 31) < int(bounds[0])
            and int(bounds[1]) < (1 << 31)
        )
        if "StandardDeviation" in consumers and not has_sketch:
            falloffs.append(
                (
                    name,
                    "analyzer: StandardDeviation without a sketch "
                    "family needs the kernel's m2 stream",
                )
            )
            continue
        if not (has_sketch or publish_moments or
                prefixes.get(name, set()) <= {"valid"}):
            falloffs.append(
                (
                    name,
                    "dict-size: no memo-servable consumer (moments "
                    "bounds unproven and no sketch family)",
                )
            )
            continue
        reason = None
        for rg in live:
            st = rg.columns.get(name)
            if st is None:
                reason = (
                    f"codec: row group {rg.index} carries no chunk "
                    "layout metadata"
                )
                break
            if (
                getattr(st, "dictionary_page_offset", None) is None
                or getattr(st, "data_page_offset", None) is None
                or st.dictionary_page_offset >= st.data_page_offset
            ):
                reason = (
                    f"codec: chunk in row group {rg.index} has no "
                    "leading dictionary page"
                )
                break
            encs = set(st.encodings or ())
            if "RLE_DICTIONARY" in encs:
                # v2 footers list PLAIN unconditionally (the dictionary
                # page's own encoding): genuinely plain data pages fail
                # closed per chunk at decode (PQE_UNSUPPORTED)
                continue
            if "PLAIN_DICTIONARY" not in encs:
                reason = (
                    f"codec: chunk in row group {rg.index} is not "
                    "dictionary-coded"
                )
                break
            if "PLAIN" in encs:
                # v1 footers list PLAIN only when the writer actually
                # fell back to plain data pages mid-chunk
                reason = (
                    f"codec: chunk in row group {rg.index} fell back "
                    "to PLAIN data pages (dict-size overflow at write)"
                )
                break
        if reason is not None:
            falloffs.append((name, reason))
            continue
        enc[name] = EncFoldColSpec(
            column=name,
            token=token,
            kind=kind,
            publish_moments=publish_moments,
        )
    return enc, falloffs


#: integer arrow tokens the wire kernels take (uint64 deliberately
#: absent: the OFF path ships it through int64-wrap semantics the wire
#: kernels don't reproduce) and their type value bounds
_WIRE_INT_TOKEN_BOUNDS = {
    "int8": (-(1 << 7), (1 << 7) - 1),
    "int16": (-(1 << 15), (1 << 15) - 1),
    "int32": (-(1 << 31), (1 << 31) - 1),
    "int64": (-(1 << 63), (1 << 63) - 1),
    "uint8": (0, (1 << 8) - 1),
    "uint16": (0, (1 << 16) - 1),
    "uint32": (0, (1 << 32) - 1),
}

#: narrow wire dtypes an int column may pin to, narrowest first
_WIRE_NARROW_LADDER = (
    ("int8", -(1 << 7), (1 << 7) - 1),
    ("int16", -(1 << 15), (1 << 15) - 1),
    ("int32", -(1 << 31), (1 << 31) - 1),
)


def _pin_int_wire_width(token: str, bounds) -> Optional[str]:
    """The narrowest exact wire dtype for an int column, pinned
    STATICALLY for the whole pass: from the file's min/max statistics
    when every row group has them, else from the arrow type's value
    bounds. The range always widens to include 0 (the null fill the
    kernels write). None when nothing ≤ int32 holds the range — the
    column then ships as a float64 value row, which is what the Column
    path produces for every integer anyway."""
    lo, hi = _WIRE_INT_TOKEN_BOUNDS[token]
    if bounds is not None:
        lo, hi = bounds
    lo = min(int(lo), 0)
    hi = max(int(hi), 0)
    for name, dlo, dhi in _WIRE_NARROW_LADDER:
        if dlo <= lo and hi <= dhi:
            return name
    return None


def classify_wire_columns(
    col_types: Dict[str, str],
    specs: Dict[str, Any],
    packed_only_keys: set,
    dtype_name: str,
    int_bounds: Optional[Dict[str, Any]] = None,
):
    """Pure decode-to-wire eligibility split over a scan's columns.

    A column fuses iff its every live consumer key is `num:{col}` /
    `valid:{col}` AND in `packed_only_keys` (merge members' compiled
    reduces only — see ScanMemberPlan.packed_only_keys), its token has a
    wire kernel, and its wire value layout is statically known. Anything
    else stays on the Column path with a (column, reason, offending key)
    record for EXPLAIN's DQ313. `dtype_name` is the compute dtype
    ('float64'/'float32'); `int_bounds` maps columns to (min, max) file
    statistics (None/absent = no usable stats). Shared verbatim by the
    planner and the cost model so prediction and execution can never
    disagree."""
    from deequ_tpu.ops import native

    wire_specs: Dict[str, runtime.ColumnWireSpec] = {}
    falloffs: List[Tuple[str, str, str]] = []
    int_bounds = int_bounds or {}
    candidates = [
        name
        for name in sorted(col_types)
        if col_types[name] in ("double", "float", "bool")
        or col_types[name] in _WIRE_INT_TOKEN_BOUNDS
        or col_types[name] == "uint64"
    ]
    if not candidates:
        return wire_specs, falloffs
    unknown_reads = any(spec.columns is None for spec in specs.values())
    consumers: Dict[str, set] = {}
    for spec in specs.values():
        for col in spec.columns or ():
            consumers.setdefault(col, set()).add(spec.key)
    for name in candidates:
        token = col_types[name]
        if unknown_reads:
            falloffs.append(
                (name, "an input spec reads unknown columns", "")
            )
            continue
        if token == "uint64":
            falloffs.append(
                (name, "uint64 int64-wrap semantics stay on the Column path", "")
            )
            continue
        keys = consumers.get(name, set())
        if not keys:
            falloffs.append((name, "no live consumer reads this column", ""))
            continue
        allowed = {f"num:{name}", f"valid:{name}"}
        bad = sorted(keys - allowed)
        if bad:
            falloffs.append(
                (name, f"consumer {bad[0]} needs the host Column", bad[0])
            )
            continue
        off_wire = sorted(keys - packed_only_keys)
        if off_wire:
            falloffs.append(
                (
                    name,
                    f"{off_wire[0]} is re-read off-wire by a host/assisted member",
                    off_wire[0],
                )
            )
            continue
        want_value = f"num:{name}" in keys
        want_valid = f"valid:{name}" in keys
        value_kind = ""
        value_dtype = ""
        needs_shift = False
        desc = "bits"
        if want_value:
            if token == "bool":
                falloffs.append(
                    (
                        name,
                        "bool numeric values build host-side (astype)",
                        f"num:{name}",
                    )
                )
                continue
            if token in ("double", "float"):
                value_kind = "val"
                value_dtype = dtype_name
                needs_shift = dtype_name == "float32"
                desc = "f32+shift" if needs_shift else "f64"
            elif dtype_name == "float32":
                # f32 wire ships ints as shifted f32 value rows, exactly
                # like the Column path's pack
                value_kind = "val"
                value_dtype = "float32"
                needs_shift = True
                desc = "f32+shift"
            else:
                narrow = _pin_int_wire_width(token, int_bounds.get(name))
                if narrow is None:
                    value_kind = "val"
                    value_dtype = "float64"
                    desc = "f64"
                else:
                    value_kind = "ival"
                    value_dtype = narrow
                    desc = narrow.replace("int", "i")
            if not native.wire_supported(token, value_dtype):
                falloffs.append(
                    (name, f"no wire kernel for {token}->{value_dtype}", "")
                )
                continue
        wire_specs[name] = runtime.ColumnWireSpec(
            column=name,
            token=token,
            want_value=want_value,
            want_valid=want_valid,
            value_kind=value_kind,
            value_dtype=value_dtype,
            needs_shift=needs_shift,
            desc=desc,
        )
    return wire_specs, falloffs


def wire_saved_pack_bytes_per_row(wire_specs: Dict[str, Any]) -> int:
    """Predicted bytes/row of host pack work the fused columns skip: the
    full-width value array pack re-reads plus the uint8 mask packbits
    re-reads, per column. Prediction-only accounting for EXPLAIN/cost."""
    saved = 0
    for spec in wire_specs.values():
        if spec.want_value:
            saved += 8  # the f64 numeric_values array pack re-reads
        if spec.want_valid:
            saved += 1  # the uint8 mask packbits re-reads
    return saved


def wire_int_bounds(table, columns) -> Dict[str, Any]:
    """Per-column (min, max) from the file's row-group statistics, for
    the wire planner's static narrow-int pinning. A column appears only
    when EVERY row group has usable min/max — a single missing stat
    falls the column back to its type bounds (wider, never wrong).
    Empty on any error: bounds are an optimization input."""
    stats_fn = getattr(table, "row_group_stats", None)
    if stats_fn is None or not columns:
        return {}
    try:
        groups = stats_fn()
    except Exception:  # noqa: BLE001
        return {}
    return wire_int_bounds_from_groups(groups, columns)


def wire_int_bounds_from_groups(groups, columns) -> Dict[str, Any]:
    """Same pinning input computed from already-loaded row-group stats —
    the cost model replays the wire verdict from its `row_groups`
    argument without a live source handle."""
    if not groups:
        return {}
    bounds: Dict[str, Any] = {}
    for name in columns:
        lo = hi = None
        for rg in groups:
            st = rg.columns.get(name)
            if st is None or st.min_value is None or st.max_value is None:
                lo = None
                break
            try:
                g_lo, g_hi = int(st.min_value), int(st.max_value)
            except (TypeError, ValueError):
                lo = None
                break
            lo = g_lo if lo is None else min(lo, g_lo)
            hi = g_hi if hi is None else max(hi, g_hi)
        if lo is not None and hi is not None:
            bounds[name] = (lo, hi)
    return bounds


def plan_decode_fastpath(
    table,
    specs: Dict[str, Any],
    member_plan=None,
    batch_size: int = 0,
    analyzers=None,
):
    """Build a DecodePlan for a parquet-backed scan, or None when the
    knob is off, the source has no decode-planning surface, the native
    library is unavailable, or anything at all goes wrong — the fast
    path is an optimization, never a failure mode. Call AFTER column
    pruning so only surviving columns are classified.

    With `member_plan` (the pass's ScanMemberPlan) and `batch_size`, the
    plan layers the decode-to-wire verdict on top: fast columns whose
    every consumer is packed-only get a ColumnWireSpec and skip the
    Column intermediate entirely (DEEQU_TPU_WIRE_FUSED gates this
    independently of the fast path)."""
    if not runtime.decode_fastpath_enabled():
        return None
    types_fn = getattr(table, "decode_column_types", None)
    if types_fn is None or getattr(table, "with_decode_fastpath", None) is None:
        return None
    from deequ_tpu.ops import native

    if not native.available():
        return None
    try:
        col_types = types_fn()
        if not col_types:
            return None
        fast, fallbacks = classify_decode_columns(col_types, specs)
        wire_specs: Dict[str, Any] = {}
        wire_falloffs: List[Tuple[str, str, str]] = []
        if (
            member_plan is not None
            and batch_size > 0
            and runtime.wire_fused_enabled()
            and getattr(table, "with_wire_fusion", None) is not None
        ):
            fast_types = {c: col_types[c] for c in fast}
            dtype_name = np.dtype(runtime.compute_dtype()).name
            wire_specs, wire_falloffs = classify_wire_columns(
                fast_types,
                specs,
                member_plan.packed_only_keys,
                dtype_name,
                int_bounds=wire_int_bounds(table, sorted(fast_types)),
            )
        reader_cols: Tuple[str, ...] = ()
        reader_falloffs: Tuple[Tuple[str, str], ...] = ()
        reader_groups = 0
        reader_planned = False
        enc_cols: Tuple[str, ...] = ()
        enc_falloffs: Tuple[Tuple[str, str], ...] = ()
        enc_specs = None
        enc_planned = False
        if (
            runtime.native_reader_enabled()
            and getattr(table, "with_native_reader", None) is not None
            and getattr(table, "row_group_stats", None) is not None
        ):
            # reader planning is best-effort on top of the fast-path
            # verdict: a stats failure here must not cost the fast set
            try:
                codec_mask = native.reader_codecs()
                groups = table.row_group_stats()
                if groups and codec_mask:
                    skip = (
                        getattr(table, "prune_groups", None) or frozenset()
                    )
                    r_cols, r_falloffs, reader_groups = (
                        classify_reader_columns(
                            {c: col_types[c] for c in fast},
                            groups,
                            codec_mask,
                            skip,
                        )
                    )
                    reader_cols = tuple(r_cols)
                    reader_falloffs = tuple(r_falloffs)
                    reader_planned = True
                    # encoded-fold verdict layered on the reader set:
                    # needs the live analyzers (consumer proofs) and
                    # the encoded-fold source surface. Best-effort like
                    # reader planning — a failure here must not cost
                    # the reader set.
                    if (
                        reader_cols
                        and analyzers is not None
                        and member_plan is not None
                        and runtime.encoded_fold_enabled()
                        and getattr(table, "with_encoded_fold", None)
                        is not None
                    ):
                        e_specs, e_falloffs = classify_encfold_columns(
                            {c: col_types[c] for c in reader_cols},
                            analyzers,
                            specs,
                            member_plan.device_keys,
                            groups,
                            skip,
                            int_bounds=wire_int_bounds_from_groups(
                                groups, sorted(reader_cols)
                            ),
                        )
                        enc_cols = tuple(sorted(e_specs))
                        enc_falloffs = tuple(e_falloffs)
                        enc_specs = e_specs or None
                        enc_planned = True
            except Exception:  # noqa: BLE001
                reader_cols = ()
                reader_falloffs = ()
                reader_groups = 0
                reader_planned = False
                enc_cols = ()
                enc_falloffs = ()
                enc_specs = None
                enc_planned = False
        return DecodePlan(
            fast=tuple(fast),
            fallbacks=tuple(fallbacks),
            workers=runtime.decode_workers(),
            wire_fused=tuple(sorted(wire_specs)),
            wire_falloffs=tuple(wire_falloffs),
            wire_batch=int(batch_size),
            wire_specs=wire_specs or None,
            reader_cols=reader_cols,
            reader_falloffs=reader_falloffs,
            reader_groups=reader_groups,
            reader_planned=reader_planned,
            enc_cols=enc_cols,
            enc_falloffs=enc_falloffs,
            enc_specs=enc_specs,
            enc_planned=enc_planned,
        )
    except Exception:  # noqa: BLE001
        return None


def apply_decode_plan(table, plan: DecodePlan):
    """Act on a DecodePlan: record the `decode_fastpath` span + counters
    (the trace side of cost_drift's zero-drift pins and the
    engine.decode_fastpath_ratio / engine.wire_fused_ratio telemetry
    series), then view the source with the fast set — and, when the
    wire verdict fused columns, the WireFusionPlan — attached."""
    with observe.span(
        "decode_fastpath",
        cat="plan",
        cols_total=plan.total,
        cols_fast=len(plan.fast),
        cols_fallback=len(plan.fallbacks),
        cols_wire_fused=len(plan.wire_fused),
        cols_reader=len(plan.reader_cols),
        reader_groups=plan.reader_groups,
        cols_encfold=len(plan.enc_cols),
        workers=plan.workers,
    ):
        pass
    runtime.record_decode_fastpath(len(plan.fast), plan.total, plan.workers)
    if plan.wire_batch > 0:
        # wire planning ran (single-engine pass with a member plan):
        # record the verdict even when it fused nothing, so the drift
        # pin sees 0 predicted == 0 observed rather than a missing series
        runtime.record_wire_fused(len(plan.wire_fused), plan.total)
    if plan.reader_planned:
        # same record-the-zeros contract for the reader chunk counters:
        # chunk counts are STATIC (columns × non-pruned groups), the
        # trace side of cost_drift's reader_chunks_native pin
        native_chunks = len(plan.reader_cols) * plan.reader_groups
        total_chunks = plan.total * plan.reader_groups
        runtime.record_reader_chunks(
            native_chunks, total_chunks - native_chunks, total_chunks
        )
    if plan.enc_planned:
        # record-the-zeros contract for the encoded-fold column verdict
        # (the STATIC half — per-unit run/fallback counters come from
        # decode_unit): the trace side of cost_drift's encfold_columns
        # pin sees 0 predicted == 0 observed rather than a missing series
        runtime.record_encfold_plan(len(plan.enc_cols), plan.total)
    if plan.fast:
        table = table.with_decode_fastpath(plan.fast)
    if plan.wire_specs:
        with_wire = getattr(table, "with_wire_fusion", None)
        if with_wire is not None:
            table = with_wire(
                runtime.WireFusionPlan(plan.wire_specs, plan.wire_batch)
            )
    if plan.reader_cols:
        with_reader = getattr(table, "with_native_reader", None)
        if with_reader is not None:
            table = with_reader(plan.reader_cols)
    if plan.enc_specs:
        with_enc = getattr(table, "with_encoded_fold", None)
        if with_enc is not None:
            table = with_enc(plan.enc_specs)
    return table


def apply_prune_plan(table, prune, specs: Dict[str, Any]):
    """Act on a PrunePlan: swap every proven-all-true where's mask spec
    for a constant (the filter's columns then fall out of column
    pruning and the all-true mask elides on the wire), then view the
    source without its proven-all-false groups. The `prune` span and
    rg_* counters record what happened for the trace differential
    against EXPLAIN's prediction."""
    from deequ_tpu.analyzers.base import InputSpec, _all_true, where_key

    elided = 0
    for text in prune.elided_wheres():
        key = where_key(text)
        if key in specs:
            specs[key] = InputSpec(
                key=key,
                build=lambda t: _all_true(t.num_rows),
                columns=(),
            )
            elided += 1
    with observe.span(
        "prune",
        cat="plan",
        groups_total=prune.total_groups,
        groups_skipped=prune.skipped_groups,
        rows_skipped=prune.skipped_rows,
        wheres_elided=elided,
    ):
        pass
    runtime.record_pruned_groups(prune.skipped_groups, prune.total_groups)
    if prune.skip:
        table = table.with_prune(prune.skip)
    return table


class HostInputs(dict):
    """Per-batch input map for host-folded members. Host-only keys build
    LAZILY on first access: a member that answers from a pre-pass memo
    (e.g. ApproxCountDistinct reading fused-family HLL registers) never
    pays for the inputs it skipped. Build failures are remembered and
    re-raised on every access, so they fail exactly the members that
    consume the key — the same isolation contract as the eager path."""

    def __init__(self, specs: Dict[str, Any], batch):
        super().__init__()
        self._specs = specs
        self.batch = batch
        self.build_errors: Dict[str, BaseException] = {}

    def materialize(self, key: str) -> None:
        try:
            self[key]
        except Exception:  # noqa: BLE001 - recorded in build_errors
            pass

    def __missing__(self, key):
        err = self.build_errors.get(key)
        if err is not None:
            raise err
        spec = self._specs.get(key)
        if spec is None:
            raise KeyError(key)
        with observe.span("build", cat="build") as sp:
            if sp:
                sp.set(key=key.partition(":")[0], rows=int(self.batch.num_rows))
            try:
                value = np.asarray(spec.build(self.batch))
            except Exception as e:  # noqa: BLE001
                self.build_errors[key] = e
                raise
        self[key] = value
        return value

    def get(self, key, default=None):
        try:
            return self[key]
        except KeyError:
            if key in self.build_errors:
                raise
            return default


def fold_host_batch(
    built: Dict[str, np.ndarray],
    build_errors: Dict[str, BaseException],
    host_members,
    host_assisted,
    host_member_keys,
    host_aggs: Dict[int, Any],
    host_assisted_states: Dict[int, Any],
    host_errors: Dict[int, BaseException],
    batch=None,
    streaming: bool = False,
    family_memo: Optional[Dict] = None,
    precomputed: bool = False,
) -> None:
    """One batch's host-placed fold, shared by FusedScanPass and
    DistributedScanPass: merge members run their xp-generic reduce with
    numpy; assisted members (sketches) run the SAME per-batch computation
    the device would (sort+decimate) and fold via host_consume. A failed
    input fails only the members that need it. `family_memo` is a dict
    the caller keeps alive for the whole scan: cross-batch facts (e.g.
    which columns miss the counts shortcut) persist across batches.
    `precomputed=True` skips the family-kernel precompute — the stream
    pipeline's prep stage (ops/pipeline.py) already ran it off the fold
    stage's critical path and its memos sit in `built`."""
    if not precomputed:
        _precompute_family_kernels(
            built,
            host_assisted,
            batch,
            host_members=host_members,
            host_errors=host_errors,
            streaming=streaming,
            family_memo=family_memo,
        )
    # assisted members fold FIRST: some publish per-batch memos that
    # merge members answer from (e.g. _LowCardCounts' dictionary
    # presence serving ApproxCountDistinct)
    for i, member in host_assisted:
        if i in host_errors:
            continue
        try:
            for key in host_member_keys[i]:
                if key in build_errors:
                    raise build_errors[key]
            out = member.device_batch(built, np)
            host_assisted_states[i] = member.host_consume(
                host_assisted_states.get(i), out
            )
        except Exception as e:  # noqa: BLE001
            host_errors[i] = e
    for i, member in host_members:
        if i in host_errors:
            continue
        try:
            for key in host_member_keys[i]:
                if key in build_errors:
                    raise build_errors[key]
            agg = _to_f64(member.device_reduce(built, np))
            prev = host_aggs.get(i)
            host_aggs[i] = agg if prev is None else member.merge_agg(prev, agg, np)
        except Exception as e:  # noqa: BLE001
            host_errors[i] = e


_FAMILY_POOL = None


def _family_pool():
    """Process-wide worker pool for family kernels (created once: the C
    kernels' thread-local arenas stay warm and bounded per thread)."""
    global _FAMILY_POOL
    if _FAMILY_POOL is None:
        from concurrent.futures import ThreadPoolExecutor

        _FAMILY_POOL = ThreadPoolExecutor(
            max_workers=min(8, os.cpu_count() or 1),
            thread_name_prefix="deequ-family",
        )
    return _FAMILY_POOL


def _family_hll_mode(batch, column: str):
    """(hll_mode, hashvals) for folding the column's HLL++ register
    update into the family kernel — matching canonical_int64's identity
    rules exactly (ops/sketches/hll.py): floats hash their f64 bit
    pattern (mode 1); ints and bools hash the canonical int64 VALUE
    (mode 2, via the original backing array — no float roundtrip).
    (0, None) when the identity can't be reproduced in-kernel.

    Only STREAMING scans fold HLL this way — the CALLER gates on its
    streaming flag (`_precompute_family_kernels`: `want_regs and
    streaming`): in-memory tables amortize the hash+pack across runs
    through the per-column encode cache, which is cheaper than
    re-hashing inside every family-kernel call — a stream's batches are
    fresh columns with nothing to amortize."""
    from deequ_tpu.data.table import ColumnType

    if batch is None:
        return 0, None
    try:
        col = batch.column(column)
    except Exception:  # noqa: BLE001
        return 0, None
    if col.ctype == ColumnType.DOUBLE and col.values.dtype == np.float64:
        return 1, None
    if col.ctype == ColumnType.LONG and col.values.dtype == np.int64:
        return 2, col.values
    if col.ctype == ColumnType.BOOLEAN and col.values.dtype == np.bool_:
        return 2, col.values.astype(np.int64)
    return 0, None


def _counts_family_shortcut(
    built, batch, column, where, wkey, cap, want_regs, qkey, mkey, rkey
) -> bool:
    """Try the counts-based family path (ops/counts_family) for a
    low-range integer column: ONE windowed count pass replaces the
    select kernel's two, and the family memos (moments, decimated
    sample, HLL registers) derive from the counts table in O(#bins).
    Returns True when the memos were published (the select job is then
    skipped); False falls through to the regular kernel. Never touches
    `num:{column}` — on success the f64 view is never built at all."""
    from deequ_tpu.data.table import ColumnType
    from deequ_tpu.ops import counts_family

    if batch is None:
        return False
    try:
        col = batch.column(column)
    except Exception:  # noqa: BLE001 - missing column: let the member fail
        return False
    if col.ctype not in (ColumnType.LONG, ColumnType.DOUBLE):
        return False
    values = np.asarray(col.values)
    is_long = col.ctype == ColumnType.LONG
    if values.dtype != (np.int64 if is_long else np.float64):
        return False
    try:
        valid = np.asarray(built[f"valid:{column}"])
        warr = None if where is None else np.asarray(built[wkey])
    except Exception:  # noqa: BLE001 - input build failure: regular path
        return False
    if valid.dtype != np.bool_ or len(valid) != len(values):
        return False
    if warr is not None and (
        warr.dtype != np.bool_ or len(warr) != len(values)
    ):
        return False
    derived = None
    if is_long:
        # dense window first (cheapest); sparse wide-range ints fall
        # through to the hash counter
        res = counts_family.counts_for_column(values, valid, warr)
        if res is not None:
            counts, lo, _n_valid, n_where = res
            derived = counts_family.family_from_counts(
                counts, lo, cap, n_where, want_regs
            )
    if derived is None:
        n_v = len(values)
        if n_v > 262144:
            # sample pre-check before the ~262k-slot hash probe: a
            # strided 4096-row sample that is nearly all-distinct
            # (>4000; a 65536-distinct population — the counter's
            # bound — expects ~3969) implies the full column is far
            # beyond the bound and the probe is guaranteed to abort.
            # A wrong skip only costs the shortcut, never correctness.
            sample = values[:: n_v // 4096][:4096]
            if np.unique(sample).size > 4000:
                return False
        hres = counts_family.hash_counts_for_column(values, valid, warr)
        if hres is None:
            return False
        keys, counts, _n_valid, n_where = hres
        derived = counts_family.family_from_hash_counts(
            keys, counts, "i64" if is_long else "f64", cap, n_where,
            want_regs,
        )
    mom, sample, n_valid, level, regs = derived
    built[qkey] = {
        "sample": sample,
        "n": np.asarray([n_valid], dtype=np.float64),
        "level": np.asarray([level], dtype=np.int32),
    }
    if regs is not None:
        built[rkey] = regs
    if mkey not in built:
        built[mkey] = {
            "count": float(mom[0]),
            "sum": float(mom[1]),
            "min": float(mom[2]),
            "max": float(mom[3]),
            "m2": float(mom[4]),
            "n_where": float(mom[5]),
            "n_rows": float(len(values)),
        }
    return True


def _precompute_family_kernels(
    built: Dict[str, np.ndarray],
    host_assisted,
    batch=None,
    host_members=(),
    host_errors=(),
    streaming: bool = False,
    family_memo: Optional[Dict] = None,
) -> None:
    """Host-fold scan sharing ACROSS analyzer kinds: when a quantile
    sketch rides the pass, one combined C traversal produces the
    (column, where) family's fused moments (consumed by
    Mean/Min/Max/Sum/StdDev via their `_moments` memo), the sketch's
    decimated sample, AND the column's HLL++ registers (consumed by
    ApproxCountDistinct, whose hash inputs then never get built at all
    under the lazy HostInputs map) — two passes over the column instead
    of the seven that separate kernels would pay. Low-range INTEGER
    columns skip even those two passes: one windowed count pass derives
    the whole family from the value distribution (ops/counts_family).
    Results land in the per-batch memo keys the members already read;
    any failure simply leaves the memos unset and each member computes
    on its own.

    `family_memo` (optional, scoped to ONE scan/stream by the caller)
    carries cross-batch facts: a column that failed the counts shortcut
    once (high-cardinality, wrong dtype) fails it for every batch of the
    stream, so the probe is skipped after the first miss.

    Same-(where, cap) families are batched into ONE multi-column native
    traversal (masked_moments_select_multi) — the across-column leg of
    scan sharing. `DEEQU_TPU_NO_MULTI_FAMILY=1` forces the per-column
    kernel (the batched path is bit-identical; the toggle exists for
    parity testing and triage).

    Job identity and grouping come from the PURE planner
    (`plan_family_jobs`/`group_family_jobs`) — the static cost analyzer
    calls the same functions; this body only adds the data-dependent
    parts (counts shortcut, array builds, kernel dispatch)."""
    from deequ_tpu.ops import counts_family, native

    # dead members don't pay their family kernel; HLL piggybacking is
    # only worth the per-row hash when a live host-folded
    # ApproxCountDistinct on the same (column, where) will consume it
    planned = plan_family_jobs(
        [member for i, member in host_assisted if i not in host_errors],
        host_members=[
            member for i, member in host_members if i not in host_errors
        ],
    )
    counts_ok = counts_family.enabled()
    # encoded-fold publication: batches decoded through the run-fold
    # path carry per-column value multisets (table.encfold payloads) —
    # publishing their family memos HERE pre-empts both the counts
    # shortcut and the select kernel below (a published qkey skips the
    # job), deriving through the same counts_family code the row path's
    # shortcut uses. Declining is always safe: the memo stays unset and
    # the job runs against the stub's expanded rows, bit-identical.
    enc = getattr(batch, "encfold", None) if batch is not None else None
    if enc and counts_ok:
        try:
            from deequ_tpu.data import encfold as _encfold

            _encfold.publish_memos(built, enc, planned)
        except Exception:  # noqa: BLE001 - memos stay unset, jobs run
            pass
    jobs = []
    for pj in planned:
        column, where, wkey = pj.column, pj.where, pj.wkey
        cap, want_regs = pj.cap, pj.want_regs
        qkey, mkey, rkey = pj.qkey, pj.mkey, pj.rkey
        if qkey in built:
            continue
        miss_key = ("counts_miss", column, wkey)
        if family_memo is not None and miss_key in family_memo:
            shortcut = False  # known miss: same column, same stream
        else:
            try:
                shortcut = counts_ok and _counts_family_shortcut(
                    built, batch, column, where, wkey, cap, want_regs,
                    qkey, mkey, rkey,
                )
            except Exception:  # noqa: BLE001 - memo stays unset, select runs
                shortcut = False
            if (
                not shortcut
                and counts_ok
                and batch is not None
                and family_memo is not None
            ):
                # the miss reasons (dtype, cardinality beyond the hash
                # counter) are column properties, stable across a
                # stream's batches — don't re-probe ~262k rows per batch
                family_memo[miss_key] = True
        if shortcut:
            continue
        try:
            x = np.asarray(built[f"num:{column}"])
            valid = np.asarray(built[f"valid:{column}"])
            warr = None if where is None else np.asarray(built[wkey])
            if valid.dtype != np.bool_ or (
                warr is not None and warr.dtype != np.bool_
            ):
                continue
        except Exception:  # noqa: BLE001 - memo stays unset, members recompute
            continue
        if valid.all():
            # all-valid elision: identical results, and it unlocks the
            # kernels' unmasked fast paths (branchless key transform,
            # quad-interleaved accumulation in the batched kernel)
            valid = None
        if want_regs and streaming:
            hll_mode, hashvals = _family_hll_mode(batch, column)
        else:
            hll_mode, hashvals = 0, None
        jobs.append(
            (qkey, mkey, rkey, x, valid, warr, cap, hll_mode, hashvals, wkey, column)
        )

    if not jobs:
        return

    def run_one(job):
        qkey, mkey, rkey, x, valid, warr, cap, hll_mode, hashvals, _w, _col = job
        try:
            return (
                native.masked_moments_select(
                    x, valid, warr, cap, hll_mode=hll_mode, hashvals=hashvals
                ),
                len(x),
            )
        except Exception:  # noqa: BLE001
            return None, len(x)

    # batch same-(where, cap) families into one traversal (all jobs of
    # one batch share the row count — `family_group_key` is the full
    # grouping decision); singleton groups keep the solo kernel (same
    # machinery, no batching overhead to amortize)
    no_multi = os.environ.get("DEEQU_TPU_NO_MULTI_FAMILY", "") not in ("", "0")
    group_map: Dict[Any, list] = {}
    for idx, job in enumerate(jobs):
        group_map.setdefault(family_group_key(job[9], job[6]), []).append(idx)
    groups = list(group_map.values())

    # worker-pool threads adopt the dispatching thread's trace context so
    # family spans stay under this scan's subtree (no-op when untraced)
    trace_tracer = observe.current_tracer()
    trace_parent = observe.current_span()

    def run_group(idxs):
        job0 = jobs[idxs[0]]
        with observe.attached(trace_tracer, trace_parent), observe.span(
            "family_kernel",
            cat="dispatch",
            where=str(job0[9]),
            cap=int(job0[6]),
            rows=len(job0[3]),
            dtype=str(job0[3].dtype),
            columns=len(idxs),
            cols=",".join(jobs[i][10] for i in idxs),
            batched=len(idxs) > 1 and not no_multi,
        ):
            if len(idxs) > 1 and not no_multi:
                g = [jobs[i] for i in idxs]
                try:
                    outs = native.masked_moments_select_multi(
                        [(j[3], j[4], j[7], j[8]) for j in g], g[0][5], g[0][6]
                    )
                except Exception:  # noqa: BLE001
                    outs = None
                if outs is not None:
                    return [(res, len(j[3])) for j, res in zip(g, outs)]
                # batched kernel unavailable/failed: per-column fallback
            return [run_one(jobs[i]) for i in idxs]

    if len(groups) > 1 and (os.cpu_count() or 1) > 1:
        # the C kernel releases the GIL: independent family groups run
        # concurrently on multicore hosts (a no-op gain on 1-core boxes).
        # ONE long-lived pool: the kernel keeps grow-only thread-local
        # arenas, so short-lived per-batch threads would leak them.
        group_outs = list(_family_pool().map(run_group, groups))
    else:
        group_outs = [run_group(g) for g in groups]
    outcomes: list = [None] * len(jobs)
    for idxs, outs in zip(groups, group_outs):
        for idx, out in zip(idxs, outs):
            outcomes[idx] = out

    for (qkey, mkey, rkey, *_rest), (res, n_rows) in zip(jobs, outcomes):
        if res is None:
            continue
        mom, sample, n_valid, level, regs = res
        built[qkey] = {
            "sample": sample,
            "n": np.asarray([n_valid], dtype=np.float64),
            "level": np.asarray([level], dtype=np.int32),
        }
        if regs is not None:
            built[rkey] = regs
        if mkey not in built:
            built[mkey] = {
                "count": float(mom[0]),
                "sum": float(mom[1]),
                "min": float(mom[2]),
                "max": float(mom[3]),
                "m2": float(mom[4]),
                "n_where": float(mom[5]),
                "n_rows": float(n_rows),
            }


def materialize_host_results(
    host_members,
    host_assisted,
    host_aggs: Dict[int, Any],
    host_assisted_states: Dict[int, Any],
    host_errors: Dict[int, BaseException],
) -> Dict[int, "AnalyzerRunResult"]:
    results: Dict[int, AnalyzerRunResult] = {}
    for i, member in host_members:
        if i in host_errors:
            results[i] = AnalyzerRunResult(member, error=host_errors[i])
        else:
            try:
                results[i] = AnalyzerRunResult(
                    member, state=member.state_from_aggregates(host_aggs.get(i))
                )
            except Exception as e:  # noqa: BLE001
                results[i] = AnalyzerRunResult(member, error=e)
    for i, member in host_assisted:
        if i in host_errors:
            results[i] = AnalyzerRunResult(member, error=host_errors[i])
        else:
            results[i] = AnalyzerRunResult(member, state=host_assisted_states.get(i))
    return results


class _RowSlice:
    """Rows [lo, hi) of a batch's host inputs: one mesh shard's view."""

    def __init__(self, inputs, lo: int, hi: int):
        self.inputs, self.lo, self.hi = inputs, lo, hi

    def __getitem__(self, key):
        return np.asarray(self.inputs[key])[self.lo : self.hi]


class PipelinedAggFold:
    """Cross-batch host fold that overlaps device compute with host work:
    each submitted batch output starts an async D2H copy, and the
    PREVIOUS batch (whose copy has had a full batch of device time to
    land) is fetched and folded. Avoids paying the device round-trip
    latency per batch, which would otherwise dominate small folds.

    Two kinds of outputs per batch: merge-analyzers' aggregates fold in
    float64 via merge_agg; assisted-analyzers' per-batch artifacts are
    handed to host_consume, once per device shard (`n_dev` shards are
    gathered along leaf axis 0 by the mesh pass)."""

    def __init__(
        self,
        analyzers: Sequence[ScanShareableAnalyzer],
        assisted: Sequence[ScanShareableAnalyzer] = (),
        n_dev: int = 1,
        sticky=None,
    ):
        self.analyzers = list(analyzers)
        self.assisted = list(assisted)
        self.n_dev = n_dev
        self.sticky = sticky if sticky is not None else {}
        self._total: Optional[List[Any]] = None
        self._assisted_states: List[Any] = [None] * len(self.assisted)
        self._pending = None

    def submit(
        self, device_out, meta_box=None, host_ctx=None, shard_rows=None
    ) -> None:
        jax.tree_util.tree_map(lambda x: x.copy_to_host_async(), device_out)
        if self._pending is not None:
            self._fold(self._pending)
        # host_ctx (the batch's built inputs) stays alive until this batch
        # folds: device-assisted members finish their outputs against the
        # host-resident columns (quantile samples read off the float64
        # values); on a mesh, shard d holds rows [d, d + 1) * shard_rows
        self._pending = (device_out, meta_box, host_ctx, shard_rows)

    def _fold(self, pending) -> None:
        device_out, meta_box, host_ctx, shard_rows = pending
        with observe.span("transfer", cat="transfer") as transfer_sp:
            fetched = jax.device_get(device_out)
            if transfer_sp:
                transfer_sp.set(
                    bytes=int(
                        sum(
                            int(getattr(leaf, "nbytes", 0))
                            for leaf in jax.tree_util.tree_leaves(fetched)
                        )
                    )
                )
        with observe.span("merge", cat="merge"):
            if meta_box is not None:
                merge_out, assisted_out = unpack_outputs(
                    fetched, meta_box["meta"]
                )
            else:
                merge_out, assisted_out = fetched
            batch_aggs = [_to_f64(t) for t in merge_out]
            if self._total is None:
                self._total = batch_aggs
            elif batch_aggs:
                self._total = [
                    a.merge_agg(t, b, np)
                    for a, t, b in zip(self.analyzers, self._total, batch_aggs)
                ]
            shifts = wire_shifts(self.sticky)
            for i, (analyzer, out) in enumerate(
                zip(self.assisted, assisted_out)
            ):
                for d in range(self.n_dev):
                    shard = jax.tree_util.tree_map(
                        lambda x, d=d: np.asarray(x).reshape(self.n_dev, -1)[d],
                        out,
                    )
                    if host_ctx is not None:
                        rows = host_ctx
                        if self.n_dev > 1:
                            rows = _RowSlice(
                                host_ctx, d * shard_rows, (d + 1) * shard_rows
                            )
                        shard = analyzer.host_finish_batch(shard, rows, shifts)
                    if shifts:
                        shard = analyzer.unshift_batch(shard, shifts)
                    self._assisted_states[i] = analyzer.host_consume(
                        self._assisted_states[i], shard
                    )

    def finish(self):
        if self._pending is not None:
            self._fold(self._pending)
            self._pending = None
        return (
            self._total if self._total is not None else []
        ), self._assisted_states


class FusedScanPass:
    """Runs a set of scan-shareable analyzers in one device pass."""

    def __init__(
        self,
        analyzers: Sequence[ScanShareableAnalyzer],
        batch_size: Optional[int] = None,
        state_cache=None,
        forensics=None,
        controller=None,
    ):
        self.analyzers = list(analyzers)
        # None = unset: the pass may widen the default for pure-host
        # in-memory folds; an EXPLICIT size (even one equal to the
        # default) is always honored as a memory bound
        self._batch_size_explicit = batch_size is not None
        self.batch_size = (
            batch_size if batch_size is not None else DEFAULT_BATCH_SIZE
        )
        # repository/states.StateCacheContext (or None): lets a
        # partitioned run swap a partition's scan for a state load
        self._state_cache = state_cache
        # observe/forensics.ForensicsCapture (or None, the default):
        # row-level violation capture + provenance notes. The off path
        # is one falsy check per batch — provably inert
        self._forensics = forensics
        # core/controller.RunController (or None, the default): the
        # cooperative cancel/deadline token honored at batch granularity
        # — the off path is one `is not None` check per batch
        self._controller = controller

    def run(self, table: Table) -> List[AnalyzerRunResult]:
        if getattr(table, "partitions", None) is not None:
            # partitioned dataset: fold per partition, merge states in
            # deterministic partition order — the shape that makes the
            # state cache a pure scan-for-load swap (bit-identical)
            return self._run_partitioned(table)
        return self._run_single(table)

    def _run_single(self, table: Table) -> List[AnalyzerRunResult]:
        # 1. plan: member placement + deduplicated input specs via the
        #    pure planner (an analyzer whose spec construction fails —
        #    e.g. unparseable predicate — fails alone, not the pass)
        results: Dict[int, AnalyzerRunResult] = {}
        with observe.span(
            "plan_fuse", cat="plan", analyzers=len(self.analyzers)
        ) as plan_sp:
            plan = plan_scan_members(self.analyzers)
            for i, err in plan.spec_errors.items():
                results[i] = AnalyzerRunResult(self.analyzers[i], error=err)
            plan_sp.set(
                placement=plan.mode,
                input_keys=len(plan.specs),
                device_members=plan.device_member_count,
                host_members=plan.host_member_count,
            )
        merge_idx = plan.merge_idx
        assisted_idx = plan.assisted_idx
        host_idx = plan.host_idx
        host_assisted_idx = plan.host_assisted_idx
        specs = plan.specs
        device_keys = plan.device_keys
        host_keys = plan.host_keys

        if plan.any_members:
            live_idx = merge_idx + assisted_idx + host_idx + host_assisted_idx
            # the source's plan: row-group and column pruning, decode
            # routing, and the source views they attach (a Parquet
            # source reads its footer for each)
            with observe.span("plan_source", cat="plan"):
                prune = plan_row_group_prune(
                    table, [self.analyzers[i] for i in live_idx]
                )
                if prune is not None:
                    # spec elision must precede column pruning so a
                    # constant-mask where's filter columns drop out of decode
                    table = apply_prune_plan(table, prune, specs)
                table = prune_table_columns(table, specs)
                if self._forensics is not None:
                    # coordinate map + prune provenance come from the PRUNED
                    # source: scan offsets then map to surviving row groups
                    self._forensics.note_table(table)
                # decode routing comes last: it classifies exactly the
                # columns that survived pruning (with_columns returns a new
                # source, so the fast set must attach to the final view)
                decode_plan = plan_decode_fastpath(
                    table,
                    specs,
                    member_plan=plan,
                    batch_size=self.batch_size,
                    analyzers=[self.analyzers[i] for i in live_idx],
                )
                if decode_plan is not None:
                    table = apply_decode_plan(table, decode_plan)
                    if self._forensics is not None:
                        self._forensics.note_decode_plan(decode_plan)
            merge_analyzers = [self.analyzers[i] for i in merge_idx]
            assisted = [self.analyzers[i] for i in assisted_idx]
            host_members = [(i, self.analyzers[i]) for i in host_idx]
            host_assisted = [(i, self.analyzers[i]) for i in host_assisted_idx]
            try:
                with observe.span(
                    "fused_scan", cat="scan", analyzers=len(self.analyzers)
                ):
                    aggs, assisted_states, host_results, device_error = (
                        self._run_pass(
                            table, merge_analyzers, specs, assisted,
                            device_keys, host_members, host_keys, host_assisted,
                        )
                    )
                results.update(host_results)  # host outcomes stand on their own
                if device_error is not None:
                    # a runtime failure of the shared device program fails
                    # every analyzer IN that program; host-folded members
                    # keep their own outcomes
                    # (reference: AnalysisRunner.scala:310-313)
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
                    for i, analyzer, state in zip(
                        assisted_idx, assisted, assisted_states
                    ):
                        results[i] = AnalyzerRunResult(analyzer, state=state)
            except RunCancelled:
                # deliberate early exit, not an analyzer failure: the
                # caller resumes from committed partition states
                raise
            except Exception as e:  # noqa: BLE001
                for i in merge_idx + assisted_idx + host_idx + host_assisted_idx:
                    results.setdefault(i, AnalyzerRunResult(self.analyzers[i], error=e))

        return [results[i] for i in range(len(self.analyzers))]

    def _run_partitioned(self, source) -> List[AnalyzerRunResult]:
        """Cached-vs-scan split over a partitioned source: for every
        partition in deterministic order, either load its analyzer
        states from the attached state cache (fingerprint + plan
        signature hit) or scan just that partition through the normal
        single-source path and publish its states; then merge partition
        states through the `State.merge` semigroup IN PARTITION ORDER.
        Cache on, off, or absent all fold and merge identically — only
        where a partition's states come from differs — so results are
        bit-identical to a full rescan by construction."""
        parts = list(source.partitions())
        cache = (
            self._state_cache
            if self._state_cache is not None and runtime.state_cache_enabled()
            else None
        )
        signature = None
        cap = self._forensics
        if cache is not None or cap is not None:
            from deequ_tpu.repository.states import plan_signature

            batch_rows = getattr(source, "batch_rows", None)
            signature = plan_signature(
                self.analyzers,
                placement=runtime.placement_mode(),
                compute_dtype=np.dtype(runtime.compute_dtype()).name,
                batch_size=(
                    self.batch_size if self._batch_size_explicit else None
                ),
                batch_rows=int(batch_rows) if batch_rows else None,
                variant=runtime.fold_signature_variant(),
            )
        if cap is not None:
            cap.note_plan_signature(signature)
        merged: Optional[List[AnalyzerRunResult]] = None
        cached_n = 0
        scanned_n = 0
        ctl = self._controller
        for part in parts:
            if ctl is not None:
                # partition boundaries are the resume points: every
                # partition finished before this check committed its
                # states above, so a cancel here loses no work
                ctl.check(
                    where=f"partition {part.name}",
                    progress={
                        "partitions_done": cached_n + scanned_n,
                        "partitions_total": len(parts),
                        "partitions_cached": cached_n,
                    },
                    boundary=True,
                )
            results: Optional[List[AnalyzerRunResult]] = None
            if cache is not None:
                sp = observe.span(
                    "state_cache", cat="cache", op="load", partition=part.name
                )
                with sp:
                    states = cache.repository.load_states(
                        cache.dataset, part.fingerprint, signature,
                        self.analyzers,
                    )
                    if sp:
                        sp.set(hit=states is not None)
                if states is not None:
                    results = [
                        AnalyzerRunResult(a, state=s)
                        for a, s in zip(self.analyzers, states)
                    ]
                    cached_n += 1
                    if cap is not None:
                        cap.note_partition(part.name, part.fingerprint, "cache")
            if results is None:
                results = scan_partition(
                    self.analyzers,
                    part,
                    batch_size=(
                        self.batch_size if self._batch_size_explicit else None
                    ),
                    forensics=(
                        cap.enter_partition(part.name, part.fingerprint)
                        if cap is not None
                        else None
                    ),
                    controller=ctl,
                )
                scanned_n += 1
                if cap is not None:
                    cap.note_partition(part.name, part.fingerprint, "scan")
                if cache is not None and all(r.error is None for r in results):
                    with observe.span(
                        "state_cache", cat="cache", op="save",
                        partition=part.name,
                    ):
                        cache.repository.save_states(
                            cache.dataset, part.fingerprint, signature,
                            [(r.analyzer, r.state) for r in results],
                        )
            merged = (
                results
                if merged is None
                else [
                    _merge_partition_results(m, r)
                    for m, r in zip(merged, results)
                ]
            )
        runtime.record_state_cache(cached_n, scanned_n, len(parts))
        assert merged is not None  # constructor guarantees >= 1 partition
        return merged

    def _run_pass(
        self,
        table: Table,
        analyzers,
        specs,
        assisted=(),
        device_keys=None,
        host_members=(),
        host_member_keys=None,
        host_assisted=(),
    ):
        dtype = runtime.compute_dtype()
        use_device = bool(analyzers or assisted)
        if (
            use_device
            and np.dtype(dtype) == np.float32
            and self.batch_size > runtime.MAX_F32_EXACT_COUNT_BATCH
        ):
            # only the packed f32 device transfer loses exactness; pure
            # host placement folds in float64 and takes any batch size
            raise ValueError(
                f"batch_size={self.batch_size} exceeds "
                f"{runtime.MAX_F32_EXACT_COUNT_BATCH} (2^24): per-batch "
                "counts would lose exactness in the float32 packed "
                "transfer. Use a smaller batch_size."
            )
        if device_keys is None:
            device_keys = set(specs)
        runtime.record_pass(
            "scan:"
            + ",".join(
                a.name
                for a in list(analyzers)
                + list(assisted)
                + [m for _, m in host_members]
                + [m for _, m in host_assisted]
            )
        )

        sticky: Dict[str, Any] = {}
        fold = PipelinedAggFold(analyzers, assisted, sticky=sticky)
        device_spec_keys = sorted(device_keys)
        streaming = bool(getattr(table, "is_streaming", False))
        # decode-to-wire handshake: the source's attached WireFusionPlan
        # (None when not planned). After every pack the resolved sticky
        # shifts publish through it so decode workers can start fusing
        # shift-needing columns; a device death abandons the handshake.
        wire_plan = getattr(table, "wire_plan", None)

        # host fold state: per host member, (f64 aggregate, error)
        host_aggs: Dict[int, Any] = {}
        host_errors: Dict[int, BaseException] = {}
        device_error: Optional[BaseException] = None

        all_host = list(host_members) + list(host_assisted)
        if host_member_keys is None:
            host_member_keys = {
                i: [s.key for s in member.input_specs()] for i, member in all_host
            }
        host_assisted_states: Dict[int, Any] = {}
        family_memo: Dict[Any, Any] = {}  # cross-batch, one scan's scope
        scanned_rows = 0
        scanned_batches = 0
        batch_size = self.batch_size
        if (
            not use_device
            and not streaming
            and not self._batch_size_explicit
        ):
            # pure host fold over an in-memory table with no explicit
            # batch size (explicit sizes are memory bounds and always
            # honored): the 4M default exists for the f32 DEVICE wire
            # (2^24 count exactness) and for stream memory bounds —
            # neither applies, and one batch saves the per-batch
            # machinery and sketch folds. Capped at ~16M rows so
            # worst-case kernel scratch stays bounded.
            batch_size = max(batch_size, min(table.num_rows, 1 << 24))
        hb_total_rows: Optional[int] = None
        try:
            raw_rows = getattr(table, "num_rows", None)
            if raw_rows is not None:
                hb_total_rows = int(raw_rows)
        except (TypeError, ValueError):
            hb_total_rows = None
        # a streaming source caps its own batches at `batch_rows`
        # (data/source.py uses min(batch_size, batch_rows)), so the
        # batch-count prediction must apply the same cap
        hb_batch = batch_size
        try:
            raw_cap = getattr(table, "batch_rows", None)
            if streaming and raw_cap:
                hb_batch = min(hb_batch, int(raw_cap))
        except (TypeError, ValueError):
            pass
        progress = observe.heartbeat.start(
            runtime.heartbeat_s(),
            total_rows=hb_total_rows,
            predicted_batches=(
                None
                if hb_total_rows is None
                else max(1, -(-hb_total_rows // hb_batch))
            ),
            name="fused_scan",
        )
        ctl = self._controller
        watchdog = None
        if ctl is not None:
            wd_s = runtime.stall_watchdog_s()
            if wd_s > 0:
                # per-stage forensics on stall: the live heartbeat
                # snapshot (bottleneck/occupancy/readahead) when the
                # heartbeat runs, else deequ-* thread stacks
                watchdog = StallWatchdog(
                    ctl, wd_s, snapshot_fn=progress.snapshot
                ).start()
        try:
            if streaming and runtime.pipeline_enabled():
                scanned_rows, scanned_batches, device_error = self._scan_pipelined(
                    table, batch_size, analyzers, assisted, specs,
                    device_spec_keys, use_device, dtype, sticky, fold,
                    host_members, host_assisted, host_member_keys,
                    host_aggs, host_assisted_states, host_errors, family_memo,
                    progress=progress,
                )
            else:
                for batch in table.batches(batch_size):
                    if ctl is not None:
                        ctl.check(
                            where="fused_scan batch",
                            progress={
                                "batches": scanned_batches,
                                "rows": scanned_rows,
                            },
                        )
                    # per-key builds with error capture: a failing input (e.g.
                    # a predicate over a missing column) fails only the
                    # analyzers that need it — host members individually, the
                    # device group as a whole (reference:
                    # AnalysisRunner.scala:310-313). Only keys with a
                    # still-live consumer are built at all.
                    live_keys: set = set()
                    if use_device and device_error is None:
                        live_keys.update(device_spec_keys)
                    for i, _member in all_host:
                        if i not in host_errors:
                            live_keys.update(host_member_keys[i])
                    device_live = use_device and device_error is None
                    host_live = any(i not in host_errors for i, _m in all_host)
                    if not device_live and not host_live:
                        break  # everything already failed; stop scanning
                    # device keys build eagerly (the shared program needs them
                    # packed); host-only keys build lazily on member access.
                    # Keys the decode workers already emitted in wire form
                    # (batch.wire_rows) skip the build entirely.
                    built = HostInputs(specs, batch)
                    build_errors = built.build_errors
                    wire_rows = getattr(batch, "wire_rows", None) or {}
                    if device_live:
                        for key in device_spec_keys:
                            if key not in wire_rows:
                                built.materialize(key)
                    if use_device and device_error is None:
                        try:
                            with observe.span(
                                "dispatch", cat="dispatch", rows=batch.num_rows
                            ) as dispatch_sp:
                                for key in device_spec_keys:
                                    if key in build_errors:
                                        raise build_errors[key]
                                padded = _pad_size(batch.num_rows, self.batch_size)
                                packed_inputs, layout = pack_batch_inputs(
                                    [
                                        (k, None if k in wire_rows else built[k])
                                        for k in device_spec_keys
                                    ],
                                    padded, dtype, sticky, num_rows=batch.num_rows,
                                    prepacked=wire_rows,
                                )
                                if wire_plan is not None:
                                    wire_plan.publish_shifts(
                                        {
                                            k: float(
                                                sticky.get(f"shift:{k}", 0.0)
                                            )
                                            for k in wire_plan.shift_keys
                                        }
                                    )
                                if dispatch_sp:
                                    dispatch_sp.set(
                                        wire_bytes=int(
                                            sum(
                                                int(getattr(v, "nbytes", 0))
                                                for v in packed_inputs.values()
                                            )
                                        )
                                    )
                                fused, meta_box = get_fused_fn(
                                    analyzers, assisted, layout
                                )
                                runtime.record_launch()
                                # async dispatch: the device crunches this
                                # batch while the host folds the previous
                                # batch (and the host members below)
                                fold.submit(
                                    fused(packed_inputs), meta_box, host_ctx=built
                                )
                        except Exception as e:  # noqa: BLE001
                            device_error = e
                            if wire_plan is not None:
                                wire_plan.abandon_shifts()
                    with observe.span("host_fold", cat="host", rows=batch.num_rows):
                        fold_host_batch(
                            built, build_errors, host_members, host_assisted,
                            host_member_keys, host_aggs, host_assisted_states,
                            host_errors, batch=batch, streaming=streaming,
                            family_memo=family_memo,
                        )
                    if self._forensics is not None:
                        with observe.span(
                            "forensics_capture", cat="forensics",
                            rows=batch.num_rows,
                        ):
                            self._forensics.capture_batch(batch, scanned_rows)
                    scanned_rows += batch.num_rows
                    scanned_batches += 1
                    if ctl is not None:
                        ctl.beat()
                    progress.advance(batch.num_rows)
        finally:
            if watchdog is not None:
                watchdog.stop()
            progress.finish()

        observe.annotate(rows=scanned_rows, batches=scanned_batches)
        aggs, assisted_states = [], []
        if device_error is None:
            try:
                # the final device_get lives here: an execution/transfer
                # failure surfaces now and must not erase host outcomes
                aggs, assisted_states = fold.finish()
                shifts = wire_shifts(sticky)
                if shifts:
                    aggs = [
                        a.unshift_agg(agg, shifts)
                        for a, agg in zip(analyzers, aggs)
                    ]
            except Exception as e:  # noqa: BLE001
                device_error = e
        host_results = materialize_host_results(
            host_members, host_assisted, host_aggs, host_assisted_states, host_errors
        )
        return aggs, assisted_states, host_results, device_error

    def _scan_pipelined(
        self,
        table,
        batch_size,
        analyzers,
        assisted,
        specs,
        device_spec_keys,
        use_device,
        dtype,
        sticky,
        fold,
        host_members,
        host_assisted,
        host_member_keys,
        host_aggs,
        host_assisted_states,
        host_errors,
        family_memo,
        progress=observe.heartbeat.NOOP_PROGRESS,
    ):
        """The pipelined streaming consumer loop (`DEEQU_TPU_PIPELINE`):
        per-batch prep — eager device-key builds, wire packing with its
        H2D put, family kernels — runs on a dedicated stage thread
        (ops/pipeline.py) ahead of this, the fold stage, which keeps
        every state mutation (`fold.submit` merges, `fold_host_batch`)
        in batch order on one thread. Fold order, fold inputs, and the
        single-threaded sticky-dict mutation are exactly the serial
        path's, so metrics are bit-identical; only WHERE the prep work
        runs changes. Liveness feedback to the prep stage (a failed
        device program, dead host members) lags by the queue depth —
        in-flight batches may prep work the fold stage then ignores."""
        all_host = list(host_members) + list(host_assisted)
        # prep-visible mirror of device_error: set either by a pack
        # failure on the prep thread or a dispatch/runtime failure here,
        # so in-flight batches stop paying for device packing
        device_down = threading.Event()
        wire_plan = getattr(table, "wire_plan", None)

        def _prep(batch):
            built = HostInputs(specs, batch)
            packed_inputs = layout = device_exc = None
            wire_rows = getattr(batch, "wire_rows", None) or {}
            if use_device and not device_down.is_set():
                if wire_plan is not None:
                    # opens the decode workers' shift_for wait window:
                    # from here a publish is imminent, so overlapped
                    # batches briefly wait instead of falling back
                    wire_plan.mark_pack_started()
                for key in device_spec_keys:
                    if key not in wire_rows:
                        built.materialize(key)
                try:
                    with observe.span(
                        "dispatch",
                        cat="dispatch",
                        rows=batch.num_rows,
                        wire_fuse=len(wire_rows),
                    ) as dispatch_sp:
                        for key in device_spec_keys:
                            if key in built.build_errors:
                                raise built.build_errors[key]
                        padded = _pad_size(batch.num_rows, self.batch_size)
                        # the H2D put happens HERE (jnp.asarray inside):
                        # batch N+1's wire lands device-side while the
                        # fold stage still runs batch N. Keys in
                        # batch.wire_rows splice in the decode workers'
                        # pre-packed buffers instead of packing here.
                        packed_inputs, layout = pack_batch_inputs(
                            [
                                (k, None if k in wire_rows else built[k])
                                for k in device_spec_keys
                            ],
                            padded, dtype, sticky, num_rows=batch.num_rows,
                            prepacked=wire_rows,
                        )
                        if wire_plan is not None:
                            # single prep thread: sticky shifts are final
                            # after this batch's pack — open the decode
                            # workers' shift gate
                            wire_plan.publish_shifts(
                                {
                                    k: float(sticky.get(f"shift:{k}", 0.0))
                                    for k in wire_plan.shift_keys
                                }
                            )
                        if dispatch_sp:
                            dispatch_sp.set(
                                wire_bytes=int(
                                    sum(
                                        int(getattr(v, "nbytes", 0))
                                        for v in packed_inputs.values()
                                    )
                                )
                            )
                except Exception as e:  # noqa: BLE001
                    device_exc = e
                    packed_inputs = layout = None
                    device_down.set()
                    if wire_plan is not None:
                        wire_plan.abandon_shifts()
            if any(i not in host_errors for i, _m in all_host):
                with observe.span(
                    "host_prep", cat="host", rows=batch.num_rows
                ):
                    _precompute_family_kernels(
                        built, host_assisted, batch,
                        host_members=host_members, host_errors=host_errors,
                        streaming=True, family_memo=family_memo,
                    )
            return batch, built, packed_inputs, layout, device_exc

        scanned_rows = 0
        scanned_batches = 0
        device_error: Optional[BaseException] = None
        ctl = self._controller
        items = pipeline.staged(
            table.batches(batch_size), _prep, name="prep", progress=progress
        )
        with contextlib.closing(items):
            with observe.span(
                "pipe_stage", cat="pipeline", stage="fold"
            ) as stage_sp:
                for item in items:
                    if ctl is not None:
                        # raising here unwinds through closing(items):
                        # the same shutdown contract an exhausted scan
                        # uses joins every stage thread and fd
                        ctl.check(
                            where="pipelined fold batch",
                            progress={
                                "batches": scanned_batches,
                                "rows": scanned_rows,
                            },
                        )
                    batch, built, packed_inputs, layout, device_exc = item
                    device_live = use_device and device_error is None
                    host_live = any(i not in host_errors for i, _m in all_host)
                    if not device_live and not host_live:
                        break  # everything already failed; stop scanning
                    with progress.timed("fold"), observe.span(
                        "pipe_item", cat="pipeline", stage="fold",
                        rows=batch.num_rows,
                    ):
                        if device_live:
                            if device_exc is not None:
                                device_error = device_exc
                            elif packed_inputs is not None:
                                try:
                                    # the launch counts as dispatch, as on
                                    # the serial path; the pack and puts
                                    # ran in the prep stage's `dispatch`
                                    with observe.span("launch", cat="dispatch"):
                                        fused, meta_box = get_fused_fn(
                                            analyzers, assisted, layout
                                        )
                                        runtime.record_launch()
                                        out = fused(packed_inputs)
                                    # async dispatch; submit folds the
                                    # PREVIOUS batch (async D2H landed)
                                    # while the device crunches this one
                                    fold.submit(out, meta_box, host_ctx=built)
                                except Exception as e:  # noqa: BLE001
                                    device_error = e
                            if device_error is not None:
                                device_down.set()
                                if wire_plan is not None:
                                    wire_plan.abandon_shifts()
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
                        if self._forensics is not None:
                            with observe.span(
                                "forensics_capture", cat="forensics",
                                rows=batch.num_rows,
                            ):
                                self._forensics.capture_batch(
                                    batch, scanned_rows
                                )
                    scanned_rows += batch.num_rows
                    scanned_batches += 1
                    if ctl is not None:
                        ctl.beat()
                    progress.advance(batch.num_rows)
                if stage_sp:
                    stage_sp.set(items=scanned_batches)
        return scanned_rows, scanned_batches, device_error

"""Static scan-cost analyzer: predict the execution shape of an analysis
plan — passes, fused family groups, batches, wire bytes, transfers —
WITHOUT touching a row of data.

The predictions are not estimates of a separate model: placement
partitioning, input-spec dedup, and family grouping come from the SAME
pure planner the runtime consumes (`ops/fused.plan_scan_members` /
`plan_family_jobs` / `group_family_jobs`), and the batching/wire math
replays `FusedScanPass._run_pass` / `pack_batch_inputs` arithmetic. The
trace-differential suite (tests/test_trace_differential.py) pins the
predicted dispatch signature against the observed `RunTrace` span tree,
so the model cannot silently drift from execution.

Stated model assumptions (where runtime behavior is data-dependent):

  * bool where/predicate masks are transferred (the runtime elides a
    mask that happens to be all-true on a given batch);
  * the counts-family shortcut is off (DEEQU_TPU_NO_COUNTS_FASTPATH=1
    in the differential suite);
  * the shared freq aggregation stays on host (group count below the
    device threshold) unless a cardinality hint says otherwise.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from deequ_tpu.lint.effects import (
    AnalyzerEffect,
    _MASK_PREFIXES,
    analyzer_read_columns,
    pass_read_bytes_per_row,
    pass_wire_bytes_per_row,
    prednn_elided,
    scan_effects,
)
from deequ_tpu.lint.schema import SchemaInfo

#: every span name the execution layer can emit for one analysis run;
#: `span_counts` carries an entry for each (0 = predicted absent) so the
#: differential suite compares complete vocabularies, not subsets.
EXECUTION_SPANS = (
    "plan_fuse",
    "fused_scan",
    "dist_scan",
    "dispatch",
    "host_fold",
    "transfer",
    "merge",
    "family_kernel",
    "grouping",
    "group_pass",
    "freq_agg",
    "state_allgather",
)

#: counter names `runtime` records that the model predicts
COUNTERS = ("device_passes", "device_launches", "group_passes")


@dataclass(frozen=True)
class FamilyGroupCost:
    """One predicted family-kernel dispatch group: the (where, cap)
    batch of quantile-family columns a single native traversal serves
    per scan batch. Mirrors the `family_kernel` span attrs."""

    where: str  # where_key of the family ("where:<all>" for no filter)
    cap: int
    dtype: str  # compute dtype of the value arrays
    columns: Tuple[str, ...]
    batched: bool
    want_regs: bool = False


@dataclass
class PassCost:
    """Predicted cost of ONE pass over the data (a fused scan, one
    grouping-column-set frequency pass, or a solo analyzer's own scan)."""

    kind: str  # 'scan' | 'grouping' | 'aux'
    label: str
    analyzers: Tuple[str, ...] = ()
    columns: Tuple[str, ...] = ()
    device_members: int = 0
    host_members: int = 0
    input_keys: Tuple[str, ...] = ()
    read_bytes_per_row: float = 0.0
    wire_bytes_per_row: float = 0.0
    n_batches: int = 1
    #: exact packed wire bytes of the FIRST batch (replays the
    #: `pack_batch_inputs` layout math); None when the key set contains
    #: a data-dependent format (e.g. range-narrowed int codes)
    wire_bytes_per_batch: Optional[int] = None
    #: row-group pushdown prediction (scan passes over parquet sources
    #: with statistics only): groups in the file / groups the runtime
    #: will skip / decode bytes those skipped groups would have cost.
    #: None = no statistics were available to the planner.
    rg_total: Optional[int] = None
    rg_skipped: Optional[int] = None
    saved_read_bytes: Optional[float] = None
    #: decode fast-path prediction (scan passes over parquet sources
    #: whose decode vocabulary was provided): columns the native
    #: buffer-level decode will take / columns scanned / per-column
    #: fallback reasons / bytes of intermediate host materialization the
    #: fast columns avoid over the decoded rows. None = no decode
    #: vocabulary (in-memory table) or the fast path is unavailable.
    decode_cols_total: Optional[int] = None
    decode_cols_fast: Optional[int] = None
    decode_fallbacks: Tuple[Tuple[str, str], ...] = ()
    saved_decode_bytes: Optional[float] = None
    decode_workers: Optional[int] = None
    #: decode-to-wire prediction (layered on the fast-path verdict,
    #: single-engine scans only): columns decoding straight to packed
    #: wire slices / per-column fall-off reasons with the offending
    #: consumer key / bytes of host pack re-reads the fused columns skip
    #: over the decoded rows. None = wire planning will not run (knob
    #: off, distributed pass, no member plan).
    wire_fused_cols: Optional[int] = None
    wire_falloffs: Tuple[Tuple[str, str, str], ...] = ()
    saved_pack_bytes: Optional[float] = None
    #: native-parquet-reader prediction (layered on the fast-path
    #: verdict, needs footer chunk metadata in `row_groups`): column
    #: chunks the page-level native reader will decode / chunks the scan
    #: touches (scanned columns × non-pruned groups) / per-column
    #: fall-off reasons naming the disqualifying encoding or codec /
    #: bytes of arrow materialization the native chunks avoid over the
    #: decoded rows. None = reader planning will not run (knob off, no
    #: chunk metadata, no loadable codec).
    reader_chunks_total: Optional[int] = None
    reader_chunks_native: Optional[int] = None
    reader_fallbacks: Tuple[Tuple[str, str], ...] = ()
    saved_alloc_bytes: Optional[float] = None
    #: encoded-fold prediction (layered on the native-reader verdict,
    #: single-engine scans only — the consumer proofs need the live
    #: analyzer set): columns whose chunks will fold over (run, code)
    #: streams without row-width materialization / columns scanned /
    #: per-column fall-off reasons naming the disqualifying codec,
    #: analyzer family, dtype, or dict-size condition. None =
    #: encoded-fold planning will not run (knob off, distributed pass,
    #: no reader verdict).
    encfold_cols: Optional[int] = None
    encfold_cols_total: Optional[int] = None
    #: of encfold_cols: columns whose moments fold as Σ(run_len × value)
    #: directly over RLE runs (the rest roll dictionary codes up into
    #: their sketch families)
    encfold_moment_cols: Optional[int] = None
    encfold_falloffs: Tuple[Tuple[str, str], ...] = ()
    #: partition-state-cache prediction (partitioned parquet sources
    #: only): partitions in the dataset / partitions whose states will
    #: load from the attached StateRepository instead of scanning / file
    #: bytes those cached partitions would have read+decoded. None = the
    #: source is not partitioned.
    partitions_total: Optional[int] = None
    partitions_cached: Optional[int] = None
    saved_partition_bytes: Optional[float] = None
    family_groups: Tuple[FamilyGroupCost, ...] = ()
    #: grouping passes: estimated distinct-group count (product of
    #: `approx_distinct` hints); None when any hint is missing
    estimated_groups: Optional[int] = None
    spill_risk: bool = False
    notes: Tuple[str, ...] = ()


#: stated host-side throughput for the decode+prep stages of the stream
#: pipeline (Arrow decode + wire pack are memcpy-shaped): used to turn
#: read bytes/batch into a host seconds/batch for the overlap model.
PIPELINE_HOST_BYTES_PER_S = 2e9


@dataclass
class PipelineCost:
    """Predicted shape of the backpressured stream pipeline
    (ops/pipeline.py) for the scan pass: per-batch stage costs under the
    stated overlap model, and whether the configured queue depth can
    hide the measured H2D transfer latency.

    Model: decode+prep host work per batch is `read_bytes / batch` at
    `PIPELINE_HOST_BYTES_PER_S` (stated constant); the H2D wire time is
    the exact packed first-batch bytes over the measured link bandwidth
    (the same disk-cached probe the placement policy uses, or an
    injected `link_bandwidth`). Serially those costs add; pipelined, the
    critical path is the slowest stage — the overlap-adjusted cost. With
    queue depth d the prep stage can run at most d batches ahead, so a
    single transfer outlasting d batches of host work starves the fold
    stage no matter how the stages interleave (the DQ305 condition)."""

    enabled: bool  # the source is streamed, so the staged driver runs
    queue_depth: int
    stages: Tuple[str, ...] = ("decode", "prep", "fold")
    n_batches: int = 1
    wire_bytes_per_batch: Optional[int] = None
    link_bandwidth: Optional[float] = None  # bytes/s; None = unmeasured
    host_s_per_batch: Optional[float] = None
    wire_s_per_batch: Optional[float] = None
    serial_s_per_batch: Optional[float] = None
    overlapped_s_per_batch: Optional[float] = None
    bottleneck: Optional[str] = None  # 'host' | 'transfer'

    @property
    def depth_hides_transfer(self) -> Optional[bool]:
        """False when one batch's H2D transfer outlasts `queue_depth`
        batches of host work — the queue drains and the fold stage
        starves. None when either side is unmeasured."""
        if self.wire_s_per_batch is None or self.host_s_per_batch is None:
            return None
        return self.wire_s_per_batch <= self.queue_depth * self.host_s_per_batch


@dataclass
class PlanCost:
    """Machine-readable prediction of a plan's execution shape."""

    placement: str
    compute_dtype: str
    engine: str
    num_rows: Optional[int]
    batch_size: Optional[int]
    analyzers: Tuple[str, ...] = ()  # post-dedupe, pre-precondition
    precondition_failures: Tuple[Tuple[str, str], ...] = ()
    effects: Tuple[AnalyzerEffect, ...] = ()
    passes: List[PassCost] = field(default_factory=list)
    counters: Dict[str, int] = field(default_factory=dict)
    span_counts: Dict[str, int] = field(default_factory=dict)
    num_hosts: int = 1
    allgather_rounds: int = 0
    #: sharded streaming scan (parallel/multihost.run_sharded_analysis):
    #: processes in the mesh and each one's partition-slice size in
    #: shard order (from parallel/shard.plan_shards) — rendered in
    #: EXPLAIN's `shards:` line and pinned against the observed
    #: `shard.count` / `shard.partitions_max` trace counters
    num_shards: int = 1
    shard_partitions: Tuple[int, ...] = ()
    #: stream-pipeline prediction for the scan pass; None for
    #: non-streaming plans (in-memory tables never engage the pipeline)
    pipeline: Optional[PipelineCost] = None
    #: the full lint/pushdown.PrunePlan behind the scan pass's rg_*
    #: fields (per-predicate verdicts + eligibility for DQ310/DQ311);
    #: None when no row-group statistics reached the planner
    prune: Optional[Any] = None
    #: resilience knobs the run will execute under: the transient-IO
    #: retry budget (DEEQU_TPU_RETRIES) and the caller's deadline in
    #: seconds (None = unbounded) — rendered in EXPLAIN's resilience
    #: line and checked by DQ318 (a deadline over an unpartitioned
    #: source leaves nothing committed for a resume)
    retry_budget: Optional[int] = None
    deadline_s: Optional[float] = None
    #: admission classification (DQService admission control): the cost
    #: tier this plan lands in — 'interactive' | 'batch' | 'heavy' —
    #: from the predicted post-prune, post-cache scan bytes against the
    #: ADMISSION_*_BYTES thresholds. Unknown row counts classify as
    #: 'batch' (admit, but never preempt others). Set by analyze_plan.
    admission_tier: Optional[str] = None
    #: scan-bytes headroom left in the tenant's quota window after this
    #: plan runs once — set by explain_plan when the caller supplies
    #: `quota_scan_bytes`; negative means the plan overdraws the window
    #: and DQ319 fires when it can NEVER fit
    quota_headroom_bytes: Optional[float] = None
    #: windowed query (windows/query.py): the window spec text, how many
    #: segment envelopes the merge tree touches, how many member
    #: partitions must rescan (no usable cached state), and the member
    #: bytes the segment algebra avoids reading — rendered in EXPLAIN's
    #: `windows:` line and pinned against the observed `window.*` trace
    #: counters. window_spec None = not a window query.
    window_spec: Optional[str] = None
    window_segments_merged: int = 0
    window_partitions_rescanned: int = 0
    saved_window_bytes: float = 0.0

    @property
    def shard_partitions_max(self) -> int:
        """The largest shard's partition count (the straggler bound)."""
        return max(self.shard_partitions) if self.shard_partitions else 0

    @property
    def shard_skew(self) -> float:
        """Largest shard over the even split; 1.0 = perfectly balanced."""
        total = sum(self.shard_partitions)
        if not total or self.num_shards < 1:
            return 1.0
        return self.shard_partitions_max / (total / self.num_shards)

    @property
    def total_read_bytes_per_row(self) -> float:
        return sum(p.read_bytes_per_row for p in self.passes)

    @property
    def total_wire_bytes_per_row(self) -> float:
        return sum(p.wire_bytes_per_row for p in self.passes)

    @property
    def scan_pass(self) -> Optional[PassCost]:
        for p in self.passes:
            if p.kind == "scan":
                return p
        return None

    @property
    def predicted_scan_bytes(self) -> Optional[float]:
        """Predicted bytes this plan reads end to end: per-pass read
        bytes/row × rows, minus what pushdown skips and what cached
        partition states avoid. None when the row count is unknown —
        admission then classifies conservatively ('batch')."""
        if self.num_rows is None:
            return None
        total = 0.0
        for p in self.passes:
            total += p.read_bytes_per_row * float(self.num_rows)
        scan = self.scan_pass
        if scan is not None:
            total -= float(scan.saved_read_bytes or 0.0)
            total -= float(scan.saved_partition_bytes or 0.0)
        return max(0.0, total)

    def dispatch_signature(self) -> Dict[str, Any]:
        """The comparable execution shape: counters, span histogram, and
        the deduplicated family-group set — exactly what
        `observe.compare.dispatch_signature(trace)` extracts from a real
        run's trace."""
        families = sorted(
            (g.where, g.cap, g.dtype, g.columns, g.batched)
            for p in self.passes
            for g in p.family_groups
        )
        return {
            "counters": dict(self.counters),
            "spans": {k: v for k, v in self.span_counts.items() if v},
            "family_groups": families,
        }


# -- admission tiers (DQService admission control) ---------------------------

#: plans predicted to read fewer bytes than this are 'interactive':
#: they may preempt a running heavy profile (~64 MiB ≈ well under a
#: second of scan on any placement)
ADMISSION_INTERACTIVE_BYTES = 64 << 20
#: plans predicted to read at least this many bytes are 'heavy': they
#: are preemptible at partition boundaries and never preempt others
ADMISSION_HEAVY_BYTES = 1 << 30

ADMISSION_TIERS = ("interactive", "batch", "heavy")


def _tier_threshold(env: str, default: float) -> float:
    """Operator override for a tier boundary (fleet tuning: a deploy
    whose 'interactive' latency budget maps to a different scan size
    than the defaults)."""
    import os

    raw = os.environ.get(env, "")
    if not raw:
        return float(default)
    try:
        return float(raw)
    except ValueError:
        return float(default)


def cost_tier(cost: "PlanCost") -> str:
    """Classify a PlanCost into an admission tier from its predicted
    scan bytes. Unknown row counts land in 'batch': admitted, queued
    behind interactive work, but never trusted to preempt. Boundaries
    are overridable via DEEQU_TPU_TIER_INTERACTIVE_BYTES and
    DEEQU_TPU_TIER_HEAVY_BYTES."""
    scan_bytes = cost.predicted_scan_bytes
    if scan_bytes is None:
        return "batch"
    if scan_bytes < _tier_threshold(
        "DEEQU_TPU_TIER_INTERACTIVE_BYTES", ADMISSION_INTERACTIVE_BYTES
    ):
        return "interactive"
    if scan_bytes >= _tier_threshold(
        "DEEQU_TPU_TIER_HEAVY_BYTES", ADMISSION_HEAVY_BYTES
    ):
        return "heavy"
    return "batch"


def cost_drift(cost: "PlanCost", trace: Any) -> Dict[str, float]:
    """Predicted-vs-observed drift per PlanCost field, from a RunTrace.

    Positive values mean the run did *more* than the planner predicted
    (extra passes/launches, more batches, wider wire rows). Keys:
    `drift.counter.<name>`, `drift.span.<name>`, `drift.family_groups`,
    and — when both sides are known — `drift.batches` and
    `drift.wire_bytes_first_batch`. Feeds `engine.drift.*` in the
    telemetry record so the sentinel can watch prediction quality as a
    time series alongside throughput.
    """
    from deequ_tpu.observe import compare  # lazy: keep lint importable without observe

    predicted = cost.dispatch_signature()
    observed = compare.dispatch_signature(trace)
    out: Dict[str, float] = {}
    for key in set(predicted["counters"]) | set(observed["counters"]):
        out[f"drift.counter.{key}"] = float(
            observed["counters"].get(key, 0) - predicted["counters"].get(key, 0)
        )
    for key in set(predicted["spans"]) | set(observed["spans"]):
        out[f"drift.span.{key}"] = float(
            observed["spans"].get(key, 0) - predicted["spans"].get(key, 0)
        )
    out["drift.family_groups"] = float(
        len(observed["family_groups"]) - len(predicted["family_groups"])
    )

    scan = cost.scan_pass
    if scan is not None:
        observed_batches = 0
        saw_batches = False
        first_wire: Optional[int] = None
        for sp in trace.spans():
            if sp.name in ("fused_scan", "dist_scan") and "batches" in sp.attrs:
                observed_batches += int(sp.attrs["batches"])
                saw_batches = True
            elif (
                first_wire is None
                and sp.name == "dispatch"
                and "wire_bytes" in sp.attrs
            ):
                first_wire = int(sp.attrs["wire_bytes"])
        if saw_batches:
            out["drift.batches"] = float(observed_batches - scan.n_batches)
        if first_wire is not None and scan.wire_bytes_per_batch is not None:
            out["drift.wire_bytes_first_batch"] = float(
                first_wire - scan.wire_bytes_per_batch
            )
        if scan.rg_skipped is not None and "rg_total" in trace.counters:
            out["drift.rg_skipped"] = float(
                int(trace.counters.get("rg_skipped", 0)) - scan.rg_skipped
            )
        if (
            scan.decode_cols_fast is not None
            and "decode_cols_total" in trace.counters
        ):
            out["drift.decode_cols_fast"] = float(
                int(trace.counters.get("decode_cols_fast", 0))
                - scan.decode_cols_fast
            )
        if (
            scan.wire_fused_cols is not None
            and "wire_cols_total" in trace.counters
        ):
            out["drift.wire_fused_cols"] = float(
                int(trace.counters.get("wire_fused_cols", 0))
                - scan.wire_fused_cols
            )
        if (
            scan.reader_chunks_native is not None
            and "reader_chunks_total" in trace.counters
        ):
            out["drift.reader_chunks_native"] = float(
                int(trace.counters.get("reader_chunks_native", 0))
                - scan.reader_chunks_native
            )
        if (
            scan.encfold_cols is not None
            and "encfold_cols" in trace.counters
        ):
            out["drift.encfold_columns"] = float(
                int(trace.counters.get("encfold_cols", 0))
                - scan.encfold_cols
            )
        if (
            scan.partitions_cached is not None
            and scan.partitions_total is not None
            and "partitions_total" in trace.counters
        ):
            out["drift.partitions_cached"] = float(
                int(trace.counters.get("partitions_cached", 0))
                - scan.partitions_cached
            )
            out["drift.partitions_scanned"] = float(
                int(trace.counters.get("partitions_scanned", 0))
                - (scan.partitions_total - scan.partitions_cached)
            )

    # sharded-scan pins: the shard planner is deterministic, so the
    # observed shard split must equal the predicted one exactly
    if cost.num_shards > 1 and "shard.count" in trace.counters:
        out["drift.shard_count"] = float(
            int(trace.counters.get("shard.count", 0)) - cost.num_shards
        )
        if cost.shard_partitions:
            out["drift.shard_partitions_max"] = float(
                int(trace.counters.get("shard.partitions_max", 0))
                - cost.shard_partitions_max
            )

    # window pins: the cover decomposition is deterministic, so a warm
    # window query must merge exactly the predicted number of segment
    # envelopes and rescan exactly the predicted partitions
    if cost.window_spec is not None and "window.segments_merged" in trace.counters:
        out["drift.window_segments_merged"] = float(
            int(trace.counters.get("window.segments_merged", 0))
            - cost.window_segments_merged
        )
        out["drift.window_partitions_rescanned"] = float(
            int(trace.counters.get("window.partitions_rescanned", 0))
            - cost.window_partitions_rescanned
        )
    return out


# -- wire-format replay -------------------------------------------------------


def _predict_packed_bytes(
    device_keys: Sequence[str],
    schema: SchemaInfo,
    rows: int,
    batch_size: int,
    compute_itemsize: int,
    elided: frozenset = frozenset(),
) -> Optional[int]:
    """Replay `pack_batch_inputs` byte accounting for one batch of
    `rows` rows. Returns None when a key's wire format is data-dependent
    (runtime range-narrowing) and therefore not statically exact.
    `elided` holds where-keys the pushdown analyzer proved all-true on
    every decoded group: the runtime swaps them for constant masks, so
    they cost scalar bookkeeping, not mask bytes."""
    from deequ_tpu.ops.fused import _pad_size

    padded = _pad_size(rows, batch_size)
    total = 0
    any_const = False
    for key in device_keys:
        if key == "where:<all>" or key in elided:
            any_const = True
        elif key.startswith("valid:"):
            fld = schema.field(key[len("valid:") :])
            if fld is not None and not fld.nullable:
                any_const = True  # all-true mask: synthesized on device
            else:
                total += padded // 8
        elif key.startswith("prednn:") and prednn_elided(
            key[len("prednn:") :], schema
        ):
            any_const = True
        elif key.startswith(_MASK_PREFIXES):
            total += padded // 8
        elif key.startswith("num:"):
            total += padded * compute_itemsize
        elif key.startswith("dtclass:"):
            total += padded  # int8 codes; narrow_int_wire keeps int8
        else:
            return None  # e.g. hll: hash codes — narrowing is data-dependent
    if any_const:
        total += 4  # the int32[1] `__nrows` scalar
    return total


def _n_batches(num_rows: Optional[int], batch_size: int) -> int:
    if num_rows is None:
        return 1
    return max(1, math.ceil(num_rows / batch_size))


def _quantile_cap(analyzer: Any) -> Optional[int]:
    sample_size = getattr(analyzer, "_sample_size", None)
    if callable(sample_size):
        try:
            return int(sample_size())
        except Exception:  # noqa: BLE001
            return None
    return None


# -- the analyzer -------------------------------------------------------------


def analyze_plan(
    analyzers: Sequence[Any],
    schema: SchemaInfo,
    *,
    num_rows: Optional[int] = None,
    batch_size: Optional[int] = None,
    placement: Optional[str] = None,
    engine: str = "single",
    num_hosts: int = 1,
    num_shards: int = 1,
    shard_partitions: Optional[Sequence[int]] = None,
    num_devices: int = 1,
    streaming: bool = False,
    stream_batch_rows: Optional[int] = None,
    link_bandwidth: Optional[float] = None,
    pipeline_depth: Optional[int] = None,
    row_groups: Optional[Sequence[Any]] = None,
    decode_types: Optional[Dict[str, str]] = None,
    partitions: Optional[Sequence[Any]] = None,
    deadline_s: Optional[float] = None,
) -> PlanCost:
    """Abstract interpretation of `AnalysisRunner._do_analysis_run`:
    dedupe -> static precondition filtering (zero-row table) ->
    grouping/scanning split -> the pure scan planner -> batching and
    wire math. Pure: no kernel is compiled, no row is read.

    `streaming=True` additionally predicts the stream pipeline's shape
    (`PlanCost.pipeline`): per-batch host vs wire seconds under the
    stated overlap model, with the link bandwidth taken from
    `link_bandwidth` or the disk-cached placement probe.
    `stream_batch_rows` is the source's own per-batch row cap
    (`ParquetSource.batch_rows`): a streamed source yields batches of
    `min(batch_size, batch_rows)` rows, so the batch count and per-batch
    wire bytes must honor it to stay trace-exact.

    `row_groups` (a `lint/pushdown.RowGroupStats` sequence, from
    `ParquetSource.row_group_stats()`) switches the scan pass onto the
    pushdown model: batch count and first-batch rows come from an exact
    replay of the source's row-group iteration over the groups the
    runtime will actually decode, and the pass reports predicted
    skipped/decoded groups + saved read bytes.

    `decode_types` (`ParquetSource.decode_column_types()`) switches on
    the decode fast-path prediction: the scan pass reports which columns
    the buffer-level native decode will take, the per-column fallback
    reasons, and the intermediate materialization bytes avoided — via
    the SAME classifier the runtime planner runs, so
    `drift.decode_cols_fast` pins to zero.

    `num_shards` / `shard_partitions` (per-shard partition counts in
    shard order, from `parallel/shard.plan_shards`) describe a sharded
    streaming scan: rendered in EXPLAIN's `shards:` line and pinned
    against the observed `shard.*` trace counters.

    `partitions` (per-partition `{"cached": bool, "bytes": int}` records
    from the runner's state-repository probe, partition order) switches
    on the partition-state-cache prediction: the scan pass reports how
    many partitions will load as cached states vs scan, and the file
    bytes the cached ones avoid reading — pinned against the observed
    `partitions_cached` / `partitions_scanned` trace counters."""
    from deequ_tpu.analyzers.base import Preconditions, ScanShareableAnalyzer
    from deequ_tpu.analyzers.frequency import (
        FrequencyBasedAnalyzer,
        ScanShareableFrequencyBasedAnalyzer,
    )
    from deequ_tpu.analyzers.freq_spill import default_max_groups_in_memory
    from deequ_tpu.analyzers.grouping import GroupingAnalyzer
    from deequ_tpu.ops import runtime
    from deequ_tpu.ops.fused import (
        DEFAULT_BATCH_SIZE,
        group_family_jobs,
        plan_family_jobs,
    )
    from deequ_tpu.ops.freq_agg import _DEVICE_THRESHOLD

    compute_dtype = np.dtype(runtime.compute_dtype())
    itemsize = int(compute_dtype.itemsize)

    # dedupe preserving order — same identity the runner uses
    seen: set = set()
    unique: List[Any] = []
    for a in analyzers:
        if a not in seen:
            seen.add(a)
            unique.append(a)

    # static precondition replay on the zero-row schema table
    empty = schema.empty_table()
    passed: List[Any] = []
    failures: List[Tuple[str, str]] = []
    for a in unique:
        try:
            err = Preconditions.find_first_failing(empty, a.preconditions())
        except Exception as e:  # noqa: BLE001
            err = e
        if err is None:
            passed.append(a)
        else:
            failures.append((repr(a), f"{type(err).__name__}: {err}"))

    grouping = [a for a in passed if isinstance(a, GroupingAnalyzer)]
    scanning = [a for a in passed if not isinstance(a, GroupingAnalyzer)]
    shareable = [a for a in scanning if isinstance(a, ScanShareableAnalyzer)]
    solo = [a for a in scanning if not isinstance(a, ScanShareableAnalyzer)]

    cost = PlanCost(
        placement=placement or runtime.placement_mode(),
        compute_dtype=compute_dtype.name,
        engine=engine,
        num_rows=num_rows,
        batch_size=batch_size,
        analyzers=tuple(repr(a) for a in unique),
        precondition_failures=tuple(failures),
        num_hosts=max(1, int(num_hosts)),
        num_shards=max(1, int(num_shards)),
        shard_partitions=tuple(int(c) for c in (shard_partitions or ())),
        counters={k: 0 for k in COUNTERS},
        span_counts={k: 0 for k in EXECUTION_SPANS},
        retry_budget=runtime.retry_budget(),
        deadline_s=float(deadline_s) if deadline_s is not None else None,
    )
    spans = cost.span_counts
    counters = cost.counters
    distributed = engine == "distributed"

    # ---- the fused scan pass ------------------------------------------------
    if shareable:
        plan, effects = scan_effects(shareable, mode=cost.placement)
        cost.effects = tuple(effects)
        use_device = bool(plan.merge_idx or plan.assisted_idx)

        if distributed:
            eff_batch = (batch_size or (1 << 21)) * max(1, int(num_devices))
        else:
            eff_batch = batch_size or DEFAULT_BATCH_SIZE
            if (
                not use_device
                and not streaming
                and batch_size is None
                and num_rows is not None
            ):
                # pure host fold over an in-memory table widens to one
                # batch (FusedScanPass._run_pass host-widening rule;
                # streamed sources never widen)
                eff_batch = max(eff_batch, min(num_rows, 1 << 24))
        # a streaming source caps each batch at its own batch_rows
        # (data/source.py: min(batch_size, batch_rows)); padding still
        # follows the engine batch size, so `eff_batch` keeps feeding
        # the _pad_size replay while `per_batch` drives the batch count
        per_batch = eff_batch
        if streaming and stream_batch_rows:
            per_batch = min(per_batch, int(stream_batch_rows))
        batches = _n_batches(num_rows, per_batch)

        # ---- row-group pushdown (parquet statistics available) ----------
        # Mirrors the runtime decision point exactly: FusedScanPass.run
        # prunes with the wheres of the LIVE members (spec errors are
        # already out), gated on the same knob this prediction reads.
        prune_plan = None
        pushdown_on = runtime.pushdown_enabled()
        batch_rows_list: Optional[Tuple[int, ...]] = None
        if row_groups and streaming and plan.any_members:
            from deequ_tpu.lint.pushdown import build_prune_plan, types_from_schema

            live_idx = (
                plan.merge_idx + plan.assisted_idx
                + plan.host_idx + plan.host_assisted_idx
            )
            try:
                prune_plan = build_prune_plan(
                    [getattr(shareable[i], "where", None) for i in live_idx],
                    row_groups,
                    types_from_schema(schema),
                )
            except Exception:  # noqa: BLE001 — prediction only, never fatal
                prune_plan = None
        if prune_plan is not None:
            cost.prune = prune_plan
            batch_rows_list = prune_plan.predicted_batch_rows(
                per_batch, pruned=pushdown_on
            )
            # the decode replay is exact even without any skip: it
            # models the source's tiny-group coalescing, which plain
            # ceil(num_rows / per_batch) cannot
            batches = max(1, len(batch_rows_list))

        device_keys = sorted(plan.device_keys)
        scan_columns: List[str] = []
        for eff in effects:
            for c in eff.columns:
                if c not in scan_columns:
                    scan_columns.append(c)

        host_assisted_members = [shareable[i] for i in plan.host_assisted_idx]
        host_only_members = [shareable[i] for i in plan.host_idx]
        jobs = plan_family_jobs(host_assisted_members, host_only_members)
        groups = group_family_jobs(jobs)
        family_groups = tuple(
            FamilyGroupCost(
                where=key[0],
                cap=key[1],
                # family kernels consume `numeric_values()` host arrays,
                # which are float64 regardless of the device dtype
                dtype="float64",
                columns=tuple(j.column for j in grp),
                batched=len(grp) > 1,
                want_regs=any(j.want_regs for j in grp),
            )
            for key, grp in groups
        )

        first_rows = (
            min(num_rows, per_batch) if num_rows is not None else per_batch
        )
        elided_keys: frozenset = frozenset()
        if batch_rows_list is not None:
            first_rows = batch_rows_list[0] if batch_rows_list else 0
        if prune_plan is not None and pushdown_on:
            elided_keys = frozenset(
                f"where:{text}" for text in prune_plan.elided_wheres()
            )
        wire_exact = (
            _predict_packed_bytes(
                device_keys, schema, first_rows, eff_batch, itemsize,
                elided=elided_keys,
            )
            if use_device
            else 0
        )

        notes: List[str] = []
        if plan.spec_errors:
            notes.append(f"{len(plan.spec_errors)} member(s) fail at spec build")
        scan_pass = PassCost(
            kind="scan",
            label="fused scan",
            analyzers=tuple(repr(a) for a in shareable),
            columns=tuple(scan_columns),
            device_members=plan.device_member_count,
            host_members=plan.host_member_count,
            input_keys=tuple(device_keys),
            read_bytes_per_row=pass_read_bytes_per_row(scan_columns, schema),
            wire_bytes_per_row=(
                pass_wire_bytes_per_row(device_keys, schema, itemsize)
                if use_device
                else 0.0
            ),
            n_batches=batches,
            wire_bytes_per_batch=wire_exact,
            family_groups=family_groups,
            notes=tuple(notes),
        )
        if prune_plan is not None:
            scan_pass.rg_total = prune_plan.total_groups
            scan_pass.rg_skipped = (
                prune_plan.skipped_groups if pushdown_on else 0
            )
            scan_pass.saved_read_bytes = (
                scan_pass.read_bytes_per_row * prune_plan.skipped_rows
                if pushdown_on
                else 0.0
            )

        # ---- decode fast-path (parquet decode vocabulary available) -----
        # Mirrors FusedScanPass.run's plan_decode_fastpath exactly: same
        # knob, same native-library gate, same classifier over the same
        # post-pruning, post-elision column set — so the prediction pins
        # to the observed decode_cols_fast counter with zero drift.
        if decode_types and plan.any_members:
            from deequ_tpu.ops import native
            from deequ_tpu.ops.fused import (
                DecodePlan,
                classify_decode_columns,
                classify_wire_columns,
                decode_saved_bytes_per_row,
                wire_int_bounds_from_groups,
                wire_saved_pack_bytes_per_row,
            )

            if runtime.decode_fastpath_enabled() and native.available():
                specs_eff = {
                    k: s for k, s in plan.specs.items() if k not in elided_keys
                }
                needed: set = set()
                prunable = True
                for spec in specs_eff.values():
                    if spec.columns is None:
                        prunable = False
                        break
                    needed.update(spec.columns)
                if not prunable:
                    kept = list(decode_types)
                elif needed:
                    kept = [n for n in decode_types if n in needed]
                else:
                    # Size()-only pass: the source keeps its first column
                    kept = list(decode_types)[:1]
                col_types = {n: decode_types[n] for n in kept}
                if col_types:
                    fast, fallbacks = classify_decode_columns(
                        col_types, specs_eff
                    )
                    dplan = DecodePlan(
                        fast=tuple(fast),
                        fallbacks=tuple(fallbacks),
                        workers=runtime.decode_workers(),
                    )
                    scan_pass.decode_cols_total = dplan.total
                    scan_pass.decode_cols_fast = len(dplan.fast)
                    scan_pass.decode_fallbacks = dplan.fallbacks
                    scan_pass.decode_workers = dplan.workers
                    decoded_rows = num_rows
                    if (
                        decoded_rows is not None
                        and prune_plan is not None
                        and pushdown_on
                    ):
                        decoded_rows = max(
                            0, decoded_rows - prune_plan.skipped_rows
                        )
                    scan_pass.saved_decode_bytes = (
                        float(
                            decode_saved_bytes_per_row(dplan, col_types)
                            * decoded_rows
                        )
                        if decoded_rows is not None
                        else None
                    )
                    # ---- decode-to-wire verdict (layered on the fast
                    # set, single-engine scans only — the distributed
                    # pass plans without a member plan). Mirrors
                    # plan_decode_fastpath's wire branch: same knob,
                    # same classifier, same packed-only key set, same
                    # statically pinned int bounds — so the prediction
                    # pins to the observed wire_fused_cols counter with
                    # zero drift.
                    if not distributed and runtime.wire_fused_enabled():
                        fast_types = {c: col_types[c] for c in fast}
                        wire_specs, wire_falloffs = classify_wire_columns(
                            fast_types,
                            specs_eff,
                            plan.packed_only_keys,
                            compute_dtype.name,
                            int_bounds=wire_int_bounds_from_groups(
                                row_groups or (), sorted(fast_types)
                            ),
                        )
                        scan_pass.wire_fused_cols = len(wire_specs)
                        scan_pass.wire_falloffs = tuple(wire_falloffs)
                        scan_pass.saved_pack_bytes = (
                            float(
                                wire_saved_pack_bytes_per_row(wire_specs)
                                * decoded_rows
                            )
                            if decoded_rows is not None
                            else None
                        )
                    # ---- native-reader verdict (layered on the fast
                    # set; needs the footer chunk metadata carried by
                    # row_groups). Mirrors plan_decode_fastpath's
                    # reader branch: same knob, same classifier, same
                    # codec mask, same prune replay — so the prediction
                    # pins to the observed reader_chunks_native counter
                    # with zero drift.
                    if runtime.native_reader_enabled() and row_groups:
                        from deequ_tpu.ops.fused import (
                            classify_reader_columns,
                            reader_saved_alloc_bytes_per_row,
                        )

                        codec_mask = native.reader_codecs()
                        if codec_mask:
                            skip = (
                                prune_plan.skip
                                if prune_plan is not None and pushdown_on
                                else frozenset()
                            )
                            r_cols, r_falloffs, r_groups = (
                                classify_reader_columns(
                                    {c: col_types[c] for c in fast},
                                    row_groups,
                                    codec_mask,
                                    skip,
                                )
                            )
                            scan_pass.reader_chunks_native = (
                                len(r_cols) * r_groups
                            )
                            scan_pass.reader_chunks_total = (
                                dplan.total * r_groups
                            )
                            scan_pass.reader_fallbacks = tuple(r_falloffs)
                            scan_pass.saved_alloc_bytes = (
                                float(
                                    reader_saved_alloc_bytes_per_row(
                                        r_cols, col_types
                                    )
                                    * decoded_rows
                                )
                                if decoded_rows is not None
                                else None
                            )
                            # ---- encoded-fold verdict (layered on the
                            # reader set, single-engine scans only — the
                            # consumer proofs need the live analyzers).
                            # Mirrors plan_decode_fastpath's
                            # encoded-fold branch: same knob, same
                            # classifier over the same reader columns,
                            # same footer replay — so the prediction
                            # pins to the observed encfold_cols counter
                            # with zero drift.
                            if (
                                not distributed
                                and r_cols
                                and runtime.encoded_fold_enabled()
                            ):
                                from deequ_tpu.ops.fused import (
                                    classify_encfold_columns,
                                )

                                e_specs, e_falloffs = (
                                    classify_encfold_columns(
                                        {c: col_types[c] for c in r_cols},
                                        shareable,
                                        specs_eff,
                                        device_keys,
                                        row_groups,
                                        skip,
                                        int_bounds=(
                                            wire_int_bounds_from_groups(
                                                row_groups, sorted(r_cols)
                                            )
                                        ),
                                    )
                                )
                                scan_pass.encfold_cols = len(e_specs)
                                scan_pass.encfold_cols_total = dplan.total
                                scan_pass.encfold_moment_cols = sum(
                                    1
                                    for s in e_specs.values()
                                    if s.publish_moments
                                )
                                scan_pass.encfold_falloffs = tuple(
                                    e_falloffs
                                )
        cost.passes.append(scan_pass)

        if streaming:
            depth = (
                pipeline_depth
                if pipeline_depth is not None
                else runtime.pipeline_depth()
            )
            bw = link_bandwidth
            if bw is None and use_device:
                bw = runtime._load_bandwidth_from_disk()
            read_per_batch = scan_pass.read_bytes_per_row * first_rows
            host_s = (
                read_per_batch / PIPELINE_HOST_BYTES_PER_S
                if read_per_batch > 0
                else None
            )
            if not use_device:
                wire_s: Optional[float] = 0.0
            elif wire_exact is not None and bw:
                wire_s = wire_exact / float(bw)
            else:
                wire_s = None  # data-dependent wire or unmeasured link
            serial = overlapped = bottleneck = None
            if host_s is not None and wire_s is not None:
                serial = host_s + wire_s
                overlapped = max(host_s, wire_s)
                bottleneck = "transfer" if wire_s > host_s else "host"
            cost.pipeline = PipelineCost(
                enabled=streaming,
                queue_depth=depth,
                n_batches=batches,
                wire_bytes_per_batch=wire_exact if use_device else 0,
                link_bandwidth=bw,
                host_s_per_batch=host_s,
                wire_s_per_batch=wire_s,
                serial_s_per_batch=serial,
                overlapped_s_per_batch=overlapped,
                bottleneck=bottleneck,
            )

        if plan.any_members:
            counters["device_passes"] += 1
            spans["host_fold"] += batches
            if distributed:
                spans["dist_scan"] += 1
            else:
                spans["fused_scan"] += 1
            if use_device:
                counters["device_launches"] += batches
                spans["dispatch"] += batches
                spans["transfer"] += batches
                spans["merge"] += batches
            spans["family_kernel"] += len(groups) * batches
        if not distributed:
            spans["plan_fuse"] += 1
        if cost.num_hosts > 1 and plan.any_members:
            cost.allgather_rounds = 1
            spans["state_allgather"] += 1

    # ---- solo scanning analyzers (their own pass each) ----------------------
    for a in solo:
        cols = analyzer_read_columns(a)
        cost.passes.append(
            PassCost(
                kind="aux",
                label=f"solo scan: {getattr(a, 'name', type(a).__name__)}",
                analyzers=(repr(a),),
                columns=cols,
                read_bytes_per_row=pass_read_bytes_per_row(cols, schema),
                n_batches=1,
                notes=("runs outside the shared pass",),
            )
        )
        # Histogram's vectorized group pass records a group_pass counter
        if getattr(a, "name", "") == "Histogram":
            counters["group_passes"] += 1

    # ---- grouping passes (one frequency pass per column set) ----------------
    freq_based = [a for a in grouping if isinstance(a, FrequencyBasedAnalyzer)]
    other_grouping = [
        a for a in grouping if not isinstance(a, FrequencyBasedAnalyzer)
    ]
    sets: Dict[Tuple[str, ...], List[Any]] = {}
    for a in freq_based:
        sets.setdefault(tuple(sorted(a.grouping_columns())), []).append(a)

    max_groups = default_max_groups_in_memory()
    for cols, group in sets.items():
        est: Optional[int] = 1
        for c in cols:
            fld = schema.field(c)
            if fld is None or fld.approx_distinct is None:
                est = None
                break
            est *= max(1, int(fld.approx_distinct))
        freq_shareable = [
            a for a in group if isinstance(a, ScanShareableFrequencyBasedAnalyzer)
        ]
        freq_solo = [
            a
            for a in group
            if not isinstance(a, ScanShareableFrequencyBasedAnalyzer)
        ]
        notes = []
        spill = est is not None and est > max_groups
        if spill:
            notes.append(
                f"~{est} groups exceeds the in-memory budget ({max_groups}): "
                "the frequency state will spill to disk"
            )
        cost.passes.append(
            PassCost(
                kind="grouping",
                label=f"grouping pass over ({', '.join(cols)})",
                analyzers=tuple(repr(a) for a in group),
                columns=cols,
                read_bytes_per_row=pass_read_bytes_per_row(cols, schema),
                n_batches=1,
                estimated_groups=est,
                spill_risk=spill,
                notes=tuple(notes),
            )
        )
        spans["grouping"] += 1
        spans["group_pass"] += 1
        counters["group_passes"] += 1
        if freq_shareable:
            spans["freq_agg"] += 1
            counters["device_passes"] += 1
            # spilled states stream on host; only an in-memory counts
            # array at/above the device threshold launches a kernel
            if est is not None and est >= _DEVICE_THRESHOLD and not spill:
                counters["device_launches"] += 1
        # non-shareable frequency analyzers (e.g. MutualInformation)
        # each take an extra aggregation pass over the counts
        counters["device_passes"] += len(freq_solo)

    for a in other_grouping:
        cols = analyzer_read_columns(a)
        cost.passes.append(
            PassCost(
                kind="aux",
                label=f"grouping (own pass): {getattr(a, 'name', type(a).__name__)}",
                analyzers=(repr(a),),
                columns=cols,
                read_bytes_per_row=pass_read_bytes_per_row(cols, schema),
            )
        )

    # ---- partition-state cache (partitioned parquet sources) ---------------
    # `partitions` records ({"cached": bool, "bytes": int}, partition
    # order) come from the runner's pre-scan repository probe with the
    # exact fingerprint + plan signature the fused pass will use, so
    # `drift.partitions_cached` / `drift.partitions_scanned` pin to zero
    if partitions is not None:
        scan = cost.scan_pass
        if scan is not None:
            cached = [p for p in partitions if p.get("cached")]
            scan.partitions_total = len(partitions)
            scan.partitions_cached = len(cached)
            scan.saved_partition_bytes = float(
                sum(int(p.get("bytes", 0)) for p in cached)
            )

    cost.admission_tier = cost_tier(cost)
    return cost


__all__ = [
    "ADMISSION_HEAVY_BYTES",
    "ADMISSION_INTERACTIVE_BYTES",
    "ADMISSION_TIERS",
    "COUNTERS",
    "EXECUTION_SPANS",
    "PIPELINE_HOST_BYTES_PER_S",
    "FamilyGroupCost",
    "PassCost",
    "PipelineCost",
    "PlanCost",
    "analyze_plan",
    "cost_tier",
]

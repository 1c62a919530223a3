"""Execution-engine runtime: dtypes, the pass monitor, jit cache keys.

The monitor is the production analogue of the reference's test-only
SparkMonitor job/stage listener (reference:
src/test/scala/com/amazon/deequ/SparkMonitor.scala:25-75): it counts fused
device passes and program launches so scan-sharing is an *asserted*
property (SURVEY.md §6 efficiency invariants).
"""

from __future__ import annotations

import contextlib
import os
import time
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deequ_tpu.observe import counters as _counters
from deequ_tpu.observe.spans import timed_call as _timed


def compute_dtype() -> jnp.dtype:
    """float64 when x64 is live (CPU tests / parity), float32 on TPU.

    Per-batch reductions are XLA tree-reductions (error ~ eps·log n); the
    cross-batch fold happens host-side in float64 either way, so f32 device
    partials stay accurate as long as batches are < 2^24 rows.
    """
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


MAX_F32_EXACT_COUNT_BATCH = 1 << 24  # f32 integers exact below 2^24


def check_int_wire_width(dtype, key: str) -> None:
    """With jax_enable_x64 off, jnp.asarray/device_put silently
    canonicalizes 64-bit integers to 32 bits (verified: values > 2^31
    arrive corrupted). Every engine that ships an int column to the
    device must make that limitation a loud error instead."""
    if np.dtype(dtype).itemsize >= 8 and not jax.config.jax_enable_x64:
        raise ValueError(
            f"input '{key}' needs a 64-bit integer wire format "
            "(values exceed 32-bit range) but jax_enable_x64 is "
            "off; enable x64 or pre-cast the column to float."
        )


def narrow_int_wire(arr: np.ndarray, key: str, sticky: dict) -> np.ndarray:
    """Range-downcast an integer array to the narrowest exact wire dtype.

    Shared by both engines (fused packing and distributed device_put).
    `sticky` pins each key's dtype monotonically wider across batches so
    compiled layouts stay stable instead of flapping per batch range.
    Raises when the range genuinely needs 64-bit ints the engine can't
    ship exactly (x64 off)."""
    unsigned = np.issubdtype(arr.dtype, np.unsignedinteger)
    candidates = (
        (np.uint8, np.uint16, np.uint32, np.uint64)
        if unsigned
        else (np.int8, np.int16, np.int32, np.int64)
    )
    chosen = np.dtype(sticky.get(key, candidates[0]))
    if arr.size:
        mn, mx = int(arr.min()), int(arr.max())
        # the widest candidate of arr's own signedness family always
        # covers [mn, mx], so this loop always picks one
        for cand in candidates:
            info = np.iinfo(cand)
            if (
                np.dtype(cand).itemsize >= chosen.itemsize
                and info.min <= mn
                and mx <= info.max
            ):
                chosen = np.dtype(cand)
                break
    chosen = np.dtype(min(chosen, arr.dtype, key=lambda d: np.dtype(d).itemsize))
    check_int_wire_width(chosen, key)
    sticky[key] = chosen
    return arr.astype(chosen, copy=False)


# ---------------------------------------------------------------------------
# Persistent compilation cache
# ---------------------------------------------------------------------------

# <checkout>/.jax_cache: fixed, because the directory is part of what a
# later process looks entries up by
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory. chip_smoke.py calls this before its first
    compile; the library never does on import. Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads
    it and nothing is changed; otherwise the cache lives at
    `<checkout>/.jax_cache`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE_DIR)
    return _CHECKOUT_CACHE_DIR


# ---------------------------------------------------------------------------
# Placement: where a reduction earns its bytes
# ---------------------------------------------------------------------------

_PLACEMENT_CACHE: Optional[str] = None
# Cost model, bytes vs FLOPs: a value reduction ships ~4 B/row and costs
# ~2 ns/row on the host, so the device only wins above ~2 GB/s links
# (PCIe/ICI-attached accelerators). Discrete (mask/code-only) reductions
# ship ~0.1-2 B/row against ~1 ns/row of host popcount, breaking even
# around 100 MB/s.
PLACEMENT_DEVICE_ALL_BANDWIDTH = 2e9  # bytes/s: everything on device
PLACEMENT_BANDWIDTH_FLOOR = 100e6  # bytes/s: below, nothing earns the wire


def measure_device_bandwidth(nbytes: int = 4 << 20, iters: int = 3) -> float:
    """Effective H2D+D2H bandwidth probe (synchronized via a value fetch —
    async dispatch makes un-fetched timings meaningless). Best-of-`iters`
    with a measured empty-dispatch baseline subtracted, so per-dispatch
    latency doesn't misclassify a fast (PCIe-class) link as slow on a
    one-shot noisy sample."""
    data = np.zeros(nbytes // 4, dtype=np.float32)
    tiny = np.zeros(1, dtype=np.float32)
    total = jax.jit(jnp.sum)
    float(total(data))  # compile + warm
    float(total(tiny))
    best = _timed(lambda: float(total(data)))
    if nbytes / best < PLACEMENT_BANDWIDTH_FLOOR / 10:
        # hopelessly slow link: extra samples can only raise the estimate
        # by the dispatch baseline, never flip the 'host-all' call, and
        # each costs ~nbytes/bandwidth seconds of startup
        return nbytes / best
    dispatch = min(
        _timed(lambda: float(total(tiny))) for _ in range(iters)
    )
    for _ in range(iters - 1):
        best = min(best, _timed(lambda: float(total(data))))
    return nbytes / max(best - dispatch, 1e-9)


def placement_for_bandwidth(bandwidth: float) -> str:
    """The placement a measured host-to-device bandwidth (bytes/s) earns."""
    if bandwidth >= PLACEMENT_DEVICE_ALL_BANDWIDTH:
        return "device"
    if bandwidth >= PLACEMENT_BANDWIDTH_FLOOR:
        return "host-discrete"
    return "host-all"


def placement_mode() -> str:
    """Where reductions run, by measured link economics:

      'device'        — everything in the fused XLA pass (fast links:
                        PCIe/ICI-attached chips, or CPU-backend jax where
                        "transfer" is a memcpy)
      'host-discrete' — mask/code-only reductions fold on the host;
                        value-dense work (moments, sorts) still earns its
                        4 B/row on a mid-speed link
      'host-all'      — the host link is slower than the host can simply
                        REDUCE: every analyzer folds on the host through
                        the same xp-generic reduction code; the device
                        program is skipped

    The scheduler analogue of Spark's map-side combine decision, decided
    by a synchronized bandwidth probe whose measurement is cached on disk
    per (host, platform, device kind) with a TTL (PLACEMENT_CACHE_TTL_S),
    so only the first process in a week pays the probe's compiles. A
    probe that fails raises: a failure is never read as a slow link that
    sends the work to the host. Override with
    DEEQU_TPU_PLACEMENT=device|host-discrete|host|auto ('host' =
    host-all); delete <cache dir>/placement.json to force a re-probe.
    """
    global _PLACEMENT_CACHE
    env = os.environ.get("DEEQU_TPU_PLACEMENT", "auto")
    if env == "device":
        return "device"
    if env in ("host", "host-all"):
        return "host-all"
    if env == "host-discrete":
        return "host-discrete"
    if _PLACEMENT_CACHE is None:
        bandwidth = _load_bandwidth_from_disk()
        if bandwidth is None:
            bandwidth = measure_device_bandwidth()
            _save_bandwidth_to_disk(bandwidth)
        # classify at use time, so cached probes survive threshold tuning
        _PLACEMENT_CACHE = placement_for_bandwidth(bandwidth)
    return _PLACEMENT_CACHE


# a cached probe is trusted this long; after that, re-measure (links can
# change between sessions even for the same device kind)
PLACEMENT_CACHE_TTL_S = 7 * 24 * 3600


# ---------------------------------------------------------------------------
# Stream pipeline knobs (ops/pipeline.py — the staged streaming executor)
# ---------------------------------------------------------------------------

DEFAULT_PIPELINE_DEPTH = 2


def pushdown_enabled() -> bool:
    """Whether parquet scans may skip row groups the static pruning
    interpreter (lint/pushdown.py) proves carry no qualifying row for
    ANY fused member's where filter, and may swap proven-all-true
    filters for constant masks.

    `DEEQU_TPU_PUSHDOWN=0` (or `off`) disables both: every group decodes
    and every filter evaluates, exactly as before the analyzer existed —
    the baseline the pushdown differential suite compares against.
    Pruning is a pure decode-skip: folds are where-masked, so results
    are bit-identical either way."""
    import os

    return os.environ.get("DEEQU_TPU_PUSHDOWN", "") not in ("0", "off")


def decode_fastpath_enabled() -> bool:
    """Whether parquet decode may route planner-approved columns through
    the buffer-level native kernels (ops/native/decode.c) instead of the
    host from_arrow chain.

    `DEEQU_TPU_DECODE_FASTPATH=0` (or `off`) forces every column through
    the host chain — the baseline the decode differential suite compares
    against. Both paths emit bit-identical Columns, so this knob only
    moves decode time, never results."""
    import os

    return os.environ.get("DEEQU_TPU_DECODE_FASTPATH", "") not in ("0", "off")


def wire_fused_enabled() -> bool:
    """Whether planner-approved packed-only columns may decode STRAIGHT
    to the device wire format (ops/native/decode.c wire kernels):
    bitpacked mask rows, narrowed int rows, shifted float rows emitted
    by the decode workers, skipping both the Column intermediate and
    pack_batch_inputs' serial numpy pack for those columns.

    `DEEQU_TPU_WIRE_FUSED=0` (or `off`) is the kill switch: every column
    materializes a Column and packs in prep, exactly as before — the
    baseline the wire differential suite compares against. The device
    sees identical input values either way, so metrics are
    bit-identical; only where the wire bytes are produced changes."""
    import os

    return os.environ.get("DEEQU_TPU_WIRE_FUSED", "") not in ("0", "off")


def state_cache_enabled() -> bool:
    """Whether partitioned scans may consult an attached StateRepository
    (repository/states.py): partitions whose fingerprint + plan
    signature already have a stored state envelope load as states
    instead of decoding and folding their rows.

    `DEEQU_TPU_STATE_CACHE=0` (or `off`) is the kill switch: every
    partition scans, exactly as with no repository attached — the
    baseline the state-cache differential suite compares against.
    Partitioned sources fold per partition and merge in deterministic
    partition order either way, so results are bit-identical; only
    whether a partition's states come from a scan or from disk
    changes."""
    import os

    return os.environ.get("DEEQU_TPU_STATE_CACHE", "") not in ("0", "off")


def scan_sharing_enabled() -> bool:
    """Whether the DQService may merge co-tenant submissions over the
    same dataset fingerprint into ONE superset fused scan (fleet-level
    scan sharing, service/sharing.py) when the plan-subsumption prover
    (lint/subsume.py) proves every participant contained.

    `DEEQU_TPU_SCAN_SHARING=0` (or `off`) is the kill switch: every
    submission scans solo, exactly as before sharing existed — the
    baseline the sharing differential suite compares against. Metrics
    are bit-identical either way (the fan-out rides the state
    semigroup); only how many times the table is read changes."""
    import os

    return os.environ.get("DEEQU_TPU_SCAN_SHARING", "") not in ("0", "off")


def share_group_max() -> int:
    """Cap on participants in one shared scan
    (`DEEQU_TPU_SHARE_GROUP_MAX`, default 8): bounds the fan-out a
    single worker performs and the blast radius of one preemption."""
    import os

    raw = os.environ.get("DEEQU_TPU_SHARE_GROUP_MAX", "")
    try:
        n = int(raw) if raw else 8
    except ValueError:
        return 8
    return max(1, n)


def pallas_folds_enabled() -> bool:
    """Whether the numeric moments state folds may run as
    Pallas kernels (ops/pallas_kernels.py) on platforms that compile
    them. `DEEQU_TPU_PALLAS_FOLDS=0` (or `off`) is the kill switch.
    Call sites additionally require `pallas_kernels.usable()` (a TPU
    probe — always False on CPU, where the XLA fold runs unchanged) and
    a block-aligned batch shape. UNLIKE the pipeline/pushdown/decode
    knobs, the blocked Pallas sum is NOT bit-identical to the XLA
    reduction, so this knob enters the plan signature as a fold
    variant (`fold_variant`) — cached states never cross the two
    arithmetics."""
    import os

    return os.environ.get("DEEQU_TPU_PALLAS_FOLDS", "") not in ("0", "off")


def fold_variant() -> str:
    """The fold-arithmetic variant tag the plan signature hashes, "+"-
    joined from:

      * "f32-exact" on the float32 wire: Minimum/Maximum fold on the host
        (`value_exact`) and quantile samples are read off the column's
        float64 values — states from before carried float32-rounded
        values;
      * "pallas-kahan" when the Pallas moments folds are enabled AND the
        platform actually compiles them (compensated blocked sums).

    "" on the float64 wire without Pallas (the default arithmetic —
    signatures unchanged): the CPU tests, where interpret-mode kernel
    runs live only in tests, never in the product fold."""
    tags = []
    if compute_dtype() == jnp.float32:
        tags.append("f32-exact")
    if pallas_folds_enabled():
        from deequ_tpu.ops import pallas_kernels

        if pallas_kernels.usable():
            tags.append("pallas-kahan")
    return "+".join(tags)


def fold_signature_variant() -> str:
    """The variant tag plan signatures actually hash: `fold_variant`
    plus an "encfold" mode tag whenever the encoded-fold path could
    engage (kill switch on, the native reader stack it rides on
    enabled, and the native library loadable). Encoded-fold results are
    bit-identical to the row fold by construction, but cached states
    must still never mix across the two fold modes — same conservatism
    as the pallas tag, applied to a mode that changes where states come
    from rather than their arithmetic."""
    base = fold_variant()
    if (
        encoded_fold_enabled()
        and native_reader_enabled()
        and decode_fastpath_enabled()
    ):
        from deequ_tpu.ops import native

        if native.available():
            return base + "+encfold" if base else "encfold"
    return base


def shard_tag() -> str:
    """This process's shard tag in a sharded scan (`DEEQU_TPU_SHARD`,
    set by the mesh launcher for each worker): a short string like "2"
    that worker-thread names and heartbeat lines carry, so watchdog
    dumps and merged cross-process traces attribute work to the right
    shard. Empty outside sharded runs — names are unchanged."""
    import os

    return os.environ.get("DEEQU_TPU_SHARD", "")


def native_reader_enabled() -> bool:
    """Whether planner-approved column chunks may be read by the native
    parquet reader (ops/native/parquet_read.c): page headers parsed,
    page bodies decompressed (snappy/zstd via dlopen) and PLAIN /
    RLE-dictionary / RLE-boolean values decoded straight into the same
    Arrow-layout buffers the decode fast path consumes — pyarrow never
    touches those chunks, and the read thread preads ahead of decode.

    `DEEQU_TPU_NATIVE_READER=0` (or `off`) is the kill switch: every
    chunk arrives through pyarrow exactly as before — the baseline the
    reader differential suite compares against. The decode and wire
    kernels see bit-identical buffers either way, so metrics are
    bit-identical; only who produced the bytes changes."""
    import os

    return os.environ.get("DEEQU_TPU_NATIVE_READER", "") not in ("0", "off")


def encoded_fold_enabled() -> bool:
    """Whether planner-approved dictionary-coded columns may fold
    analyzer family state over (run_length, dict_code) streams straight
    off the page decoder (ops/native/parquet_read.c runs mode +
    ops/native/encfold.c) instead of expanding to row width first.

    `DEEQU_TPU_ENCODED_FOLD=0` (or `off`) is the kill switch: every
    chunk expands to rows exactly as before — the baseline the
    encoded-fold differential suite compares against. The run-fold
    derivations share the row path's counts-family code and decline
    whenever bit-identity is not proven for a batch, so metrics are
    bit-identical either way; only how many bytes get materialized
    changes. The mode still enters the plan signature
    (`fold_signature_variant`) so cached states never mix across the
    two fold paths."""
    import os

    return os.environ.get("DEEQU_TPU_ENCODED_FOLD", "") not in ("0", "off")


def forensics_enabled() -> bool:
    """Whether verification runs capture failure forensics by default
    (observe/forensics.py): a bounded deterministic sample of violating
    rows per row-level-capable constraint, plus the run's provenance
    record, persisted as an audit trail.

    Unlike every other knob this one defaults OFF — capture does real
    per-batch work, so it must be asked for: `DEEQU_TPU_FORENSICS=1`
    (or `on`/`true`), or `with_forensics()` on the run builder. When
    off the fused pass carries a None capture and the per-batch hook is
    one falsy check — the forensics differential suite proves the off
    path bit-identical and the overhead suite bounds it under the same
    budget as tracing."""
    import os

    return os.environ.get("DEEQU_TPU_FORENSICS", "") in ("1", "on", "true")


def wire_pad_size(n: int, batch_size: int) -> int:
    """The fused pass's padded row length for an n-row batch (mirror of
    ops/fused.py:_pad_size, which delegates here): power of two, min 8,
    capped at batch_size rounded up to a multiple of 8. Lives in runtime
    so data/source.py's decode-to-wire path can size wire rows without
    importing the fused engine."""
    size = 8
    while size < n:
        size *= 2
    return min(size, max(-(-batch_size // 8) * 8, 8))


@dataclass(frozen=True)
class ColumnWireSpec:
    """Statically pinned wire layout for one decode-to-wire column:
    which wire rows its packed consumers need and the exact dtypes, so
    every batch of the pass ships the same layout (the sticky contract)
    and decode can emit final wire bytes without seeing any data."""

    column: str
    token: str  # arrow type token the chunk must match at decode time
    want_value: bool  # a num:{column} spec is live
    want_valid: bool  # a valid:{column} spec is live
    value_kind: str = ""  # "val" (compute dtype) | "ival" (narrow int)
    value_dtype: str = ""  # numpy dtype name of the wire value row
    needs_shift: bool = False  # f32 wire: wait for the sticky shift
    desc: str = ""  # short render token for EXPLAIN ("f64", "i8", ...)


@dataclass
class WireRow:
    """One pre-packed wire row decode attaches to a batch Table
    (`table.wire_rows[key]`): the padded buffer pack_batch_inputs splices
    into the batch's group buffer verbatim."""

    kind: str  # "bits" | "val" | "ival"
    arr: "np.ndarray"
    shift: float = 0.0
    all_valid: bool = False  # bits row with zero invalid rows (may elide)


class WireFusionPlan:
    """The decode↔pack handshake for one fused pass.

    Carries the per-column ColumnWireSpecs plus the pass batch size (for
    padded-row sizing), and coordinates the f32 wire's scan-constant
    pre-centering shifts: decode cannot know them statically, so
    shift-needing columns stay on the Column path until the FIRST
    batch's pack resolves the shifts (resolve_shift, single prep thread)
    and publishes them here; later batches then fuse with the exact
    sticky shift. On the f64 wire no key shifts and the gate is open
    from the start."""

    def __init__(self, columns, batch_size: int):
        import threading

        self.columns = dict(columns)  # column -> ColumnWireSpec
        self.batch_size = int(batch_size)
        self.shifts: dict = {}
        self._abandoned = False
        self._pack_started = False
        self._shift_ready = threading.Event()
        if not any(s.needs_shift for s in self.columns.values()):
            self._shift_ready.set()

    @property
    def shift_keys(self) -> List[str]:
        return [
            f"num:{c}" for c, s in self.columns.items() if s.needs_shift
        ]

    def mark_pack_started(self) -> None:
        """The prep thread is about to pack a batch. Until this point a
        shift_for wait is pure stall — nothing can possibly publish —
        so decode workers return None immediately instead (the
        first-batch fallback is by design). GIL-atomic bool write."""
        self._pack_started = True

    def publish_shifts(self, shifts: dict) -> None:
        self.shifts.update(shifts)
        self._shift_ready.set()

    def abandon_shifts(self) -> None:
        """The pack path died before resolving shifts (device failure):
        shift-needing columns decode through the Column path forever."""
        self._abandoned = True
        self._shift_ready.set()

    def shift_for(self, key: str, timeout: float = 0.25):
        """The published sticky shift for a num: key, or None when not
        (yet) available — the caller falls back to the Column path for
        this batch and retries on the next. Non-blocking until the
        first pack is underway (mark_pack_started): before that the
        publish cannot happen, and waiting would serialize a full
        timeout per shift-needing column into the first batch's decode.
        Once a pack is in flight the short wait lets the overlapped
        next batch catch the publish instead of falling back."""
        if not self._shift_ready.is_set():
            if not self._pack_started:
                return None
            if not self._shift_ready.wait(timeout):
                return None
        if self._abandoned:
            return None
        return float(self.shifts.get(key, 0.0))


def decode_workers() -> int:
    """Number of parallel row-group decode workers
    (`DEEQU_TPU_DECODE_WORKERS`, default `min(cores, 4)`; 1 = the
    single decode thread the pipeline always had). pyarrow and the
    native decode kernels release the GIL, so workers scale decode on
    multi-core boxes; the merge back into the pipeline is in submission
    order, so results are bit-identical at any worker count."""
    import os

    raw = os.environ.get("DEEQU_TPU_DECODE_WORKERS", "")
    try:
        workers = int(raw)
    except ValueError:
        workers = 0
    if workers < 1:
        workers = min(os.cpu_count() or 1, 4)
    return workers


def pipeline_depth() -> int:
    """Bounded inter-stage queue depth (`DEEQU_TPU_PIPELINE_DEPTH`,
    default 2): at most this many prepped batches — packed wire buffers
    already put to the device — wait between the prep and fold stages.
    Depth 1 is classic double-buffering; deeper queues smooth decode
    jitter at the cost of one resident batch each. Host memory stays
    O(depth + constant) batches."""
    import os

    raw = os.environ.get("DEEQU_TPU_PIPELINE_DEPTH", "")
    try:
        depth = int(raw)
    except ValueError:
        return DEFAULT_PIPELINE_DEPTH
    return max(1, min(depth, 64))


def source_stall_s() -> float:
    """Per-row-group source stall in seconds (`DEEQU_TPU_SOURCE_STALL_MS`,
    default 0 = off): a latency-injection knob for benchmarking the
    pipeline against sources with real per-read wait — object-store GETs,
    network filesystems — on boxes whose local disk is too fast (and
    whose kernel readahead too good) for decode/IO overlap to matter.
    The stall is paid by the decode stage thread, where a remote read
    would block. Never set it for real-throughput numbers."""
    import os

    raw = os.environ.get("DEEQU_TPU_SOURCE_STALL_MS", "")
    if not raw:
        return 0.0
    try:
        return max(0.0, float(raw)) / 1000.0
    except ValueError:
        return 0.0


def retry_budget() -> int:
    """Bounded retries per transient-IO operation in the readahead
    fetch path (`DEEQU_TPU_RETRIES`, default 3, 0 = no retry): a failed
    or short pread/ranged GET re-issues with exponential backoff up to
    this many times before the unit degrades to the pyarrow fallback —
    a retried transient fault costs milliseconds, an exhausted budget
    costs one unit's fallback decode, and neither ever changes a metric
    (the chaos differential in tests/test_suite_differential_fuzz.py
    pins bit-identity under injected faults). Outcomes are counted as
    `engine.retry.*` telemetry watched by the sentinel."""
    import os

    raw = os.environ.get("DEEQU_TPU_RETRIES", "")
    if not raw:
        return 3
    try:
        return max(0, int(raw))
    except ValueError:
        return 3


def retry_base_s() -> float:
    """First-retry backoff in seconds (`DEEQU_TPU_RETRY_BASE_MS`,
    default 10ms): attempt k sleeps `base * 2^k` with deterministic
    jitter (core/controller.backoff_s). Tests shrink it to keep chaos
    runs fast; production leaves the default so a flapping object store
    is not hammered."""
    import os

    raw = os.environ.get("DEEQU_TPU_RETRY_BASE_MS", "")
    if not raw:
        return 0.010
    try:
        return max(0.0, float(raw)) / 1000.0
    except ValueError:
        return 0.010


def stall_watchdog_s() -> float:
    """Stall-watchdog window in seconds (`DEEQU_TPU_STALL_WATCHDOG_S`,
    default 0 = off): when positive AND a RunController is attached to
    the run, a watchdog thread checks the controller's per-batch beat
    counter every window; one silent window dumps per-stage state to
    stderr (heartbeat snapshot when live, else engine thread stacks),
    two consecutive silent windows cancel the run with DQ404 — a wedged
    scan fails with forensics instead of hanging forever."""
    import os

    raw = os.environ.get("DEEQU_TPU_STALL_WATCHDOG_S", "")
    if not raw:
        return 0.0
    try:
        return max(0.0, float(raw))
    except ValueError:
        return 0.0


def service_workers() -> int:
    """Worker-pool size for the long-lived DQService
    (`DEEQU_TPU_SERVICE_WORKERS`, default 2): how many suites execute
    concurrently over the shared pool. Admission control bounds what
    reaches the pool; this bounds what runs at once."""
    import os

    raw = os.environ.get("DEEQU_TPU_SERVICE_WORKERS", "")
    if not raw:
        return 2
    try:
        return max(1, int(raw))
    except ValueError:
        return 2


def service_drain_s() -> float:
    """Graceful-drain window in seconds for the DQService
    (`DEEQU_TPU_SERVICE_DRAIN_S`, default 30): on SIGTERM / close(),
    running suites get this long to commit their in-flight partition
    and unwind through the soft-cancel (DQ407) before the drain
    escalates to a hard cancel. Queued work is returned immediately
    with DQ414 either way."""
    import os

    raw = os.environ.get("DEEQU_TPU_SERVICE_DRAIN_S", "")
    if not raw:
        return 30.0
    try:
        return max(0.0, float(raw))
    except ValueError:
        return 30.0


def heartbeat_s() -> float:
    """Live scan heartbeat interval in seconds (`DEEQU_TPU_HEARTBEAT_S`,
    default 0 = off): when positive, streaming scans emit periodic
    progress snapshots — completed/predicted batches, instantaneous
    rows/s, pipeline-stage bottleneck, ETA — through
    `observe.heartbeat` (registered callbacks, or JSONL lines at
    `DEEQU_TPU_HEARTBEAT_OUT`, falling back to stderr). Disabled, the
    scan loop touches only a falsy no-op handle and no timer thread is
    ever spawned."""
    from deequ_tpu.observe import heartbeat

    return heartbeat.env_interval_s()


def _platform_key() -> Optional[str]:
    """Identity of the attached LINK — the cache key. Bandwidth is a
    property of how THIS HOST reaches the device, not of the device kind
    alone: one device kind can sit behind host links of very different
    bandwidth, so the host name is part of the key."""
    import socket

    try:
        device = jax.devices()[0]
        host = socket.gethostname() or "?"
        return f"{host}:{device.platform}:{getattr(device, 'device_kind', '?')}"
    except Exception:  # noqa: BLE001
        return None


def _placement_cache_path() -> Optional[str]:
    import os

    from deequ_tpu.ops.native import per_user_cache_dir

    directory = per_user_cache_dir()
    if directory is None:
        return None
    return os.path.join(directory, "placement.json")


def _load_bandwidth_from_disk() -> Optional[float]:
    """The probe costs two device compiles plus synchronized fetches at
    every process start, so the MEASURED BANDWIDTH is
    cached per (host, platform, device kind) with a TTL. Delete the file
    (or set DEEQU_TPU_PLACEMENT) to force a re-probe."""
    import json
    import os

    path = _placement_cache_path()
    key = _platform_key()
    if path is None or key is None or not os.path.exists(path):
        return None
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
        if not isinstance(data, dict):
            return None
        entry = data.get(key)
        if not isinstance(entry, dict):
            return None
        bandwidth = entry.get("bandwidth")
        ts = entry.get("ts", 0)
        if not isinstance(bandwidth, (int, float)) or bandwidth <= 0:
            return None
        if time.time() - float(ts) > PLACEMENT_CACHE_TTL_S:
            return None
        return float(bandwidth)
    except (OSError, ValueError, TypeError):
        return None


def _save_bandwidth_to_disk(bandwidth: float) -> None:
    import json
    import os

    from deequ_tpu.core.fileio import write_text_output

    path = _placement_cache_path()
    key = _platform_key()
    if path is None or key is None:
        return
    data = {}
    try:
        if os.path.exists(path):
            with open(path, encoding="utf-8") as f:
                loaded = json.load(f)
            if isinstance(loaded, dict):
                data = loaded
    except (OSError, ValueError):
        data = {}
    data[key] = {"bandwidth": float(bandwidth), "ts": time.time()}
    # drop expired/garbage entries on save (old key formats and renamed
    # hosts would otherwise sit in placement.json forever)
    now = time.time()
    data = {
        k: v
        for k, v in data.items()
        if isinstance(v, dict)
        and isinstance(v.get("ts"), (int, float))
        and now - float(v["ts"]) <= PLACEMENT_CACHE_TTL_S
    }
    try:
        write_text_output(path, json.dumps(data), overwrite=True)
    except OSError:
        pass


@dataclass
class ExecutionStats:
    """Counts of engine work during a monitored block."""

    device_passes: int = 0  # one per fused scan over a dataset (≈ Spark job)
    device_launches: int = 0  # one per compiled-program invocation (per batch)
    group_passes: int = 0  # one per group-by frequency computation
    pass_labels: List[str] = field(default_factory=list)
    # Pallas kernel name -> programs traced with that kernel in them
    kernel_traces: Dict[str, int] = field(default_factory=dict)
    # mesh-sharded inputs: rows placed (global) and rows each device holds
    placed_rows: int = 0
    device_rows: Dict[str, int] = field(default_factory=dict)
    # state-provider I/O: states read back and written, bytes each way
    states_loaded: int = 0
    states_saved: int = 0
    state_bytes_loaded: int = 0
    state_bytes_saved: int = 0
    # predicate input builds by route (dictionary entries vs rows), and
    # the dictionary entries the dictionary route evaluated
    pred_builds_dictionary: int = 0
    pred_builds_rows: int = 0
    pred_dict_entries: int = 0
    # quantile host finishes by route (per batch; per shard on a mesh)
    quantile_finish_hist16: int = 0
    quantile_finish_sorted: int = 0
    # column profiles: scans, histograms, straggler and cast columns
    profile_passes: int = 0
    profile_histograms: int = 0
    profile_stragglers: int = 0
    profile_cast_columns: int = 0

    @property
    def jobs(self) -> int:
        return self.device_passes + self.group_passes


@contextlib.contextmanager
def monitored() -> Iterator[ExecutionStats]:
    """Collect engine-execution counts for everything run inside the block.

    Counting itself lives in `deequ_tpu.observe.counters` (thread-local
    sink stack, shared with the tracing subsystem so span pass-count
    attributes stay bit-identical to these stats); this wrapper keeps
    the historical `runtime.monitored()` surface."""
    stats = ExecutionStats()
    with _counters.collect(stats):
        yield stats


def record_pass(label: str) -> None:
    _counters.record_pass(label)


def record_launch() -> None:
    _counters.record_launch()


def record_placement(arrays) -> None:
    """Count the rows of mesh-sharded input arrays just placed, and the
    rows each device holds of them (from each array's shard index, so
    nothing is fetched): a sharded input spreads n rows as n/d per
    device, a replicated or single-device one does not."""
    total = 0
    per_device: Dict[str, int] = {}
    for arr in arrays:
        n = int(arr.shape[0])
        total += n
        for shard in arr.addressable_shards:
            rows = len(range(*shard.index[0].indices(n)))
            key = str(shard.device.id)
            per_device[key] = per_device.get(key, 0) + rows
    _counters.record_placement(total, per_device)


def record_group_pass(label: str) -> None:
    _counters.record_group_pass(label)


def record_pruned_groups(skipped: int, total: int) -> None:
    _counters.record_pruned_groups(skipped, total)


def record_decode_fastpath(fast: int, total: int, workers: int) -> None:
    _counters.record_decode_fastpath(fast, total, workers)


def record_wire_fused(fused: int, total: int) -> None:
    _counters.record_wire_fused(fused, total)


def record_plan_cache(hit: bool) -> None:
    _counters.record_plan_cache(hit)


def record_state_cache(cached: int, scanned: int, total: int) -> None:
    _counters.record_state_cache(cached, scanned, total)


def record_window(
    segments: int, hits: int, built: int, rescanned: int, partitions: int
) -> None:
    _counters.record_window(segments, hits, built, rescanned, partitions)


def record_reader_chunks(native: int, fallback: int, total: int) -> None:
    _counters.record_reader_chunks(native, fallback, total)


def record_encfold_plan(cols: int, total: int) -> None:
    _counters.record_encfold_plan(cols, total)


def record_encfold(
    chunks: int,
    fallback: int,
    runs: int,
    values: int,
    codes: int,
    bytes_saved: int,
) -> None:
    _counters.record_encfold(
        chunks, fallback, runs, values, codes, bytes_saved
    )


def record_retry(attempts: int, recovered: int, exhausted: int) -> None:
    _counters.record_retry(attempts, recovered, exhausted)


def record_fault(injected: int = 0, fallback_units: int = 0) -> None:
    _counters.record_fault(injected, fallback_units)


def record_shard_scan(
    shard: int,
    num_shards: int,
    partitions_local: int,
    partitions_max: int,
    partitions_total: int,
    merge_bytes: int,
    rows_local: int,
) -> None:
    _counters.record_shard_scan(
        shard,
        num_shards,
        partitions_local,
        partitions_max,
        partitions_total,
        merge_bytes,
        rows_local,
    )


def pad_to(arr: np.ndarray, size: int) -> np.ndarray:
    """Pad a 1-D host array to `size` rows (content irrelevant: padded rows
    carry where/valid = False so they never contribute to reductions).
    Keeps one compiled shape per batch size instead of one per tail."""
    n = len(arr)
    if n == size:
        return arr
    pad = np.zeros(size - n, dtype=arr.dtype)
    return np.concatenate([arr, pad])

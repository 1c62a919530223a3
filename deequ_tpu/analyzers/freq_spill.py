"""Disk-spilled group frequencies: bounded-memory high-cardinality group-by.

The reference keeps its frequencies table as a Spark DataFrame cached at
MEMORY_AND_DISK (reference: runners/AnalysisRunner.scala:75,479-483), so
Uniqueness/Entropy/CountDistinct over a near-unique key at a billion rows
spills instead of OOMing. This module is the engine-level equivalent:

  * `GroupCountAccumulator` folds per-batch `FrequenciesAndNumRows`
    partials in RAM until the accumulated group count crosses a cap
    (DEEQU_TPU_MAX_GROUPS_IN_MEMORY, default 2M groups), then switches
    to hash-partitioned disk spill: each partial's groups are routed by
    a stable 64-bit key hash into one of N partition files. Typed key
    columns (int, bool, float) route, spill and compact as typed arrays;
    string or object columns take the text hash and the pandas merge.
  * `finalize()` compacts each partition once (all chunks of a
    partition merge together; a partition holds ~#groups/N distinct
    keys, so peak memory is O(cap + batch + groups/N), never O(groups))
    and returns a `SpilledFrequencies` state.
  * `SpilledFrequencies` satisfies the same consumer contracts as the
    in-memory state — additive `freq_reduce` aggregation (streamed
    per partition by ops/freq_agg), exact Histogram top-N (per-partition
    top-N then global), MutualInformation marginals, semigroup `merge` —
    without ever materializing the full key set.

Every `freq_reduce` in the frequency family is a sum over groups of
f(count_g, num_rows), which is what makes streaming per-partition
evaluation exact, not approximate.
"""

from __future__ import annotations

import os
import pickle
import shutil
import tempfile
import weakref
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

from deequ_tpu.analyzers.states import State

def default_max_groups_in_memory() -> int:
    """Group cap before the fold spills to disk; env-tunable so memory-
    constrained deployments (and tests) can lower it."""
    return int(os.environ.get("DEEQU_TPU_MAX_GROUPS_IN_MEMORY", 2_000_000))


N_SPILL_PARTITIONS = 64
# routing works in row chunks so the stringify/hash temporaries stay
# O(chunk), not O(partial)
_ROUTE_CHUNK = 1 << 18


def _hash_key_rows(key_columns: Sequence[np.ndarray]) -> np.ndarray:
    """Stable uint64 hash per group row (combines all key columns).
    Stability across batches/processes matters: the same key must land
    in the same partition everywhere, so merges stay partition-local."""
    acc = np.full(len(key_columns[0]), np.uint64(0x9E3779B97F4A7C15))
    for kc in key_columns:
        acc = (acc * np.uint64(0xC2B2AE3D27D4EB4F)) ^ _key_hashes(kc)
    return acc


def _key_hashes(kc: np.ndarray) -> np.ndarray:
    """uint64 per key VALUE, whatever array carries it: a typed column
    and an object column holding the same number hash alike (so one
    writer never splits a key across partitions), numbers by a 64-bit
    mix of their value, strings by their text."""
    from deequ_tpu.analyzers.frequency import typed_from_values, typed_key_column
    from deequ_tpu.ops.strings import hash_strings

    if kc.dtype != object:  # a state's key columns are typed or objects
        return _mix64(_value_bits(typed_key_column(kc)))
    values = kc.tolist()
    types = set(map(type, values))
    typed = typed_from_values(values, types)
    if typed is not None:
        return _mix64(_value_bits(typed))
    h = hash_strings(kc.astype(str).astype(object))
    if not all(issubclass(t, str) for t in types):
        # mixed families (slow path): each number hashes as a typed
        # column would carry it; ints beyond int64 keep their text hash
        for family, dtype in (
            ((bool, np.bool_, int, np.integer), np.int64),
            ((float, np.floating), np.float64),
        ):
            idx = [
                i
                for i, v in enumerate(values)
                if isinstance(v, family)
                and (dtype is np.float64 or -(1 << 63) <= v < (1 << 63))
            ]
            if idx:
                picked = np.array([values[i] for i in idx], dtype=dtype)
                h[idx] = _mix64(_value_bits(picked))
    return h


def _value_bits(typed: np.ndarray) -> np.ndarray:
    """uint64 per value, equal for values Python holds equal: bools and
    integral floats as the int they equal (so -0.0 is 0), every NaN as
    one NaN."""
    if typed.dtype.kind != "f":
        return typed.astype(np.int64, copy=False).view(np.uint64)
    integral = (np.floor(typed) == typed) & (typed >= -(2.0**63)) & (typed < 2.0**63)
    canon = typed.copy()
    canon[np.isnan(canon)] = np.nan
    bits = canon.view(np.uint64)
    bits[integral] = typed[integral].astype(np.int64).view(np.uint64)
    return bits


def _mix64(z: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer: every input bit reaches the low bits the
    partition index is taken from."""
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


class _SpillWriter:
    """Appends (key_columns, counts) chunks hash-partitioned on disk."""

    def __init__(self, columns: List[str], n_partitions: int = N_SPILL_PARTITIONS):
        self.columns = list(columns)
        self.n_partitions = n_partitions
        self.directory = tempfile.mkdtemp(prefix="deequ_tpu_spill_")
        self._seq = 0
        self.num_rows = 0
        # a fold that dies mid-stream must not leak GBs of spill chunks:
        # the writer owns the directory until finalize() hands it over
        self._cleanup = weakref.finalize(
            self, shutil.rmtree, self.directory, ignore_errors=True
        )

    def append(self, partial, include_rows: bool = True) -> None:
        """Route a FrequenciesAndNumRows partial's groups to partitions,
        in row chunks so the hash/sort temporaries stay O(chunk).
        `include_rows=False` spills the groups without adding the
        partial's num_rows (used when the caller accounts rows itself);
        the partial is never mutated."""
        if include_rows:
            self.num_rows += partial.num_rows
        if partial.num_groups == 0:
            return
        key_columns = partial.key_columns
        if partial.columns != self.columns:
            key_columns = [
                partial.key_columns[partial.columns.index(c)] for c in self.columns
            ]
        # hash/sort in row chunks (temporaries stay O(chunk)); buffer each
        # partition's selections across chunks and write ONE file per
        # partition per append — 64 files instead of 64 x n_chunks
        per_part_keys: List[List[List[np.ndarray]]] = [
            [] for _ in range(self.n_partitions)
        ]
        per_part_counts: List[List[np.ndarray]] = [
            [] for _ in range(self.n_partitions)
        ]
        for start in range(0, len(partial.counts), _ROUTE_CHUNK):
            stop = min(start + _ROUTE_CHUNK, len(partial.counts))
            kcs = [kc[start:stop] for kc in key_columns]
            counts = partial.counts[start:stop]
            # an 8- or 16-bit partition index stable-sorts by radix, O(chunk)
            parts = (_hash_key_rows(kcs) % np.uint64(self.n_partitions)).astype(
                np.min_scalar_type(self.n_partitions - 1)
            )
            order = np.argsort(parts, kind="stable")
            sorted_parts = parts[order]
            boundaries = np.searchsorted(
                sorted_parts, np.arange(self.n_partitions + 1)
            )
            for p in range(self.n_partitions):
                lo, hi = boundaries[p], boundaries[p + 1]
                if lo == hi:
                    continue
                sel = order[lo:hi]
                per_part_keys[p].append([kc[sel] for kc in kcs])
                per_part_counts[p].append(counts[sel])
        self._seq += 1
        for p in range(self.n_partitions):
            if not per_part_counts[p]:
                continue
            chunk = (
                [
                    np.concatenate([kcs[j] for kcs in per_part_keys[p]])
                    for j in range(len(key_columns))
                ],
                np.concatenate(per_part_counts[p]),
            )
            path = os.path.join(self.directory, f"p{p:03d}_{self._seq:06d}.pkl")
            with open(path, "wb") as f:
                pickle.dump(chunk, f, protocol=pickle.HIGHEST_PROTOCOL)

    def finalize(self) -> "SpilledFrequencies":
        """Compact each partition to one chunk; record exact group count."""
        from deequ_tpu.analyzers.frequency import (
            FrequenciesAndNumRows,
            concat_key_chunks,
        )

        num_groups = 0
        typed = True
        # one directory scan, bucketed by partition prefix
        by_partition: dict = {}
        for fn in os.listdir(self.directory):
            if fn.startswith("p") and fn.endswith(".pkl") and "_" in fn:
                by_partition.setdefault(fn[: fn.index("_")], []).append(fn)
        for p in range(self.n_partitions):
            chunk_files = sorted(by_partition.get(f"p{p:03d}", []))
            if not chunk_files:
                continue
            key_chunks: List[List[np.ndarray]] = []
            count_chunks: List[np.ndarray] = []
            for fn in chunk_files:
                with open(os.path.join(self.directory, fn), "rb") as f:
                    kcs, counts = pickle.load(f)
                key_chunks.append(kcs)
                count_chunks.append(counts)
            merged = FrequenciesAndNumRows(
                self.columns,
                [
                    concat_key_chunks([kc[j] for kc in key_chunks])
                    for j in range(len(self.columns))
                ],
                np.concatenate(count_chunks),
                0,
            )
            if len(chunk_files) > 1:
                merged = merged.compacted()
            num_groups += merged.num_groups
            typed = typed and merged.typed
            with open(
                os.path.join(self.directory, f"part{p:03d}.pkl"), "wb"
            ) as f:
                pickle.dump(
                    (merged.key_columns, merged.counts),
                    f,
                    protocol=pickle.HIGHEST_PROTOCOL,
                )
            for fn in chunk_files:
                os.unlink(os.path.join(self.directory, fn))
        # ownership of the directory passes to the state object
        self._cleanup.detach()
        return SpilledFrequencies(
            self.columns,
            self.directory,
            self.n_partitions,
            self.num_rows,
            num_groups,
            typed,
        )


class SpilledFrequencies(State):
    """Disk-backed group frequencies (hash-partitioned, compacted).

    Quacks like FrequenciesAndNumRows for every consumer that can stream
    (freq aggregation, Histogram top-N, MutualInformation, merge); it
    deliberately does NOT expose a whole-table ``counts`` array."""

    is_spilled = True

    def __init__(
        self,
        columns: List[str],
        directory: str,
        n_partitions: int,
        num_rows: int,
        num_groups: int,
        typed: bool,
    ):
        self.columns = list(columns)
        self.directory = directory
        self.n_partitions = n_partitions
        self.num_rows = int(num_rows)
        self.num_groups = int(num_groups)
        # every partition compacted on typed key columns
        self.typed = bool(typed)
        self._cleanup = weakref.finalize(
            self, shutil.rmtree, directory, ignore_errors=True
        )

    def partitions(self) -> Iterator["object"]:
        """Yield each partition as an in-memory FrequenciesAndNumRows
        (groups are disjoint across partitions)."""
        from deequ_tpu.analyzers.frequency import FrequenciesAndNumRows

        for p in range(self.n_partitions):
            path = os.path.join(self.directory, f"part{p:03d}.pkl")
            if not os.path.exists(path):
                continue
            with open(path, "rb") as f:
                key_columns, counts = pickle.load(f)
            yield FrequenciesAndNumRows(self.columns, key_columns, counts, 0)

    def top_n(self, n: int) -> Tuple[List[np.ndarray], np.ndarray]:
        """Exact global top-n groups by (count desc, key asc):
        per-partition top-n, then top-n of the union (each partition
        holds its keys' FULL counts; the deterministic tie-break matches
        the in-memory path, analyzers/frequency.py:top_n_order).

        SINGLE-COLUMN states only (the key-ascending tie-break is over
        the first key column; Histogram — the one consumer — always
        groups one column)."""
        from deequ_tpu.analyzers.frequency import top_n_order

        if len(self.columns) != 1:
            raise ValueError(
                "top_n's deterministic tie-break is defined for "
                f"single-column states, got {self.columns}"
            )

        best_keys: List[List[np.ndarray]] = []
        best_counts: List[np.ndarray] = []
        for part in self.partitions():
            order = top_n_order(part.key_columns[0], part.counts, n)
            best_keys.append([kc[order] for kc in part.key_columns])
            best_counts.append(part.counts[order])
        if not best_counts:
            return (
                [np.array([], dtype=object) for _ in self.columns],
                np.array([], dtype=np.int64),
            )
        counts = np.concatenate(best_counts)
        keys = [
            np.concatenate([bk[j] for bk in best_keys])
            for j in range(len(self.columns))
        ]
        order = top_n_order(keys[0], counts, n)
        return [kc[order] for kc in keys], counts[order]

    def merge(self, other) -> "SpilledFrequencies":
        """Semigroup merge with either state flavor: re-partition both
        sides into a fresh spill (partition-local compaction keeps the
        memory bound). Neither operand is mutated. Sides may carry a
        key column differently (typed here, objects from a loaded state):
        routing hashes values, so a key meets itself in one partition, and
        compaction goes typed where the object side converts exactly."""
        writer = _SpillWriter(self.columns, self.n_partitions)
        for part in self.partitions():
            writer.append(part, include_rows=False)
        if getattr(other, "is_spilled", False):
            for part in other.partitions():
                writer.append(part, include_rows=False)
        else:
            writer.append(_reorder(other, self.columns), include_rows=False)
        writer.num_rows = self.num_rows + other.num_rows
        return writer.finalize()

    def __repr__(self) -> str:
        return (
            f"SpilledFrequencies({self.columns}, groups={self.num_groups}, "
            f"num_rows={self.num_rows}, partitions={self.n_partitions})"
        )


def _reorder(state, columns: List[str]):
    from deequ_tpu.analyzers.frequency import FrequenciesAndNumRows

    if state.columns == list(columns):
        return state
    if sorted(state.columns) != sorted(columns):
        raise ValueError(
            f"cannot merge frequencies over {state.columns} with {columns}"
        )
    return FrequenciesAndNumRows(
        list(columns),
        [state.key_columns[state.columns.index(c)] for c in columns],
        state.counts,
        state.num_rows,
    )


class GroupCountAccumulator:
    """Cross-batch fold of frequency partials with a group-count cap.

    Below the cap this is the plain in-memory merge chain; above it,
    partials spill to hash partitions and merging is deferred to the
    per-partition compaction in finalize()."""

    def __init__(
        self,
        columns: Sequence[str],
        max_groups_in_memory: Optional[int] = None,
        n_partitions: int = N_SPILL_PARTITIONS,
    ):
        self.columns = list(columns)
        self.max_groups = (
            default_max_groups_in_memory()
            if max_groups_in_memory is None
            else max_groups_in_memory
        )
        self.n_partitions = n_partitions
        self._buffer = None
        self._writer: Optional[_SpillWriter] = None

    @property
    def spilled(self) -> bool:
        return self._writer is not None

    def add(self, partial) -> None:
        if self._writer is not None:
            self._writer.append(partial)  # num_rows accumulates in append
            return
        combined = (
            partial.num_groups
            if self._buffer is None
            else self._buffer.num_groups + partial.num_groups
        )
        if combined > self.max_groups:
            # spill both sides UNMERGED: running the O(groups) hash merge
            # on a buffer that's about to spill anyway would make peak
            # memory ~3x the cap for near-unique keys (low reduction
            # factor — the same reason Spark skips map-side combine there);
            # partition-local compaction in finalize() dedups instead
            self._writer = _SpillWriter(self.columns, self.n_partitions)
            if self._buffer is not None:
                self._writer.append(self._buffer)
                self._buffer = None
            self._writer.append(partial)
            return
        self._buffer = (
            partial if self._buffer is None else self._buffer.merge(partial)
        )

    def finalize(self):
        from deequ_tpu.analyzers.frequency import FrequenciesAndNumRows

        if self._writer is not None:
            return self._writer.finalize()
        if self._buffer is None:
            return FrequenciesAndNumRows(
                self.columns, [], np.array([], dtype=np.int64), 0
            )
        return self._buffer

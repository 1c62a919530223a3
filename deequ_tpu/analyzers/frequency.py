"""Frequency-based (grouping) analyzers.

The frequency computation is the engine's group-by:
  SELECT cols, COUNT(*) FROM data WHERE all cols NOT NULL GROUP BY cols
(reference: analyzers/GroupingAnalyzers.scala:44-81). Host-side, columns
are dictionary-encoded and combined with ravel_multi_index, so the group-by
is one vectorized np.unique over dense codes; the aggregations over the
resulting counts array (uniqueness/distinctness/entropy/...) fuse into one
device reduction shared by every analyzer on the same grouping columns
(reference: AnalysisRunner.scala:466-534).

State merge is a key-aligned counts sum — the dict analogue of the
reference's null-safe outer join (GroupingAnalyzers.scala:128-148).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from deequ_tpu import observe
from deequ_tpu.analyzers.base import Preconditions, entity_from
from deequ_tpu.analyzers.grouping import GroupingAnalyzer
from deequ_tpu.analyzers.states import State
from deequ_tpu.core.maybe import Success
from deequ_tpu.core.metrics import DoubleMetric, Entity, Metric
from deequ_tpu.data.table import ColumnType, Table


# ---------------------------------------------------------------------------
# State
# ---------------------------------------------------------------------------


class FrequenciesAndNumRows(State):
    """Group keys + counts + overall #rows
    (reference: GroupingAnalyzers.scala:124-157).

    Keys are stored columnar (one object array per grouping column,
    aligned with ``counts``) so merges stay vectorized; ``keys`` exposes
    the row-tuple view lazily for consumers that want it.
    """

    __slots__ = ("columns", "key_columns", "counts", "num_rows", "_keys")

    def __init__(self, columns, keys, counts, num_rows: int):
        """`keys` is either a list of per-group tuples or a list of
        per-COLUMN arrays (len == len(columns)); both are accepted so
        construction sites build whichever is natural."""
        self.columns: List[str] = list(columns)
        counts = np.asarray(counts, dtype=np.int64)
        if len(keys) == len(self.columns) and all(
            isinstance(k, np.ndarray) for k in keys
        ):
            self.key_columns = [np.asarray(k, dtype=object) for k in keys]
        else:
            n = len(keys)
            self.key_columns = [
                np.array([k[j] for k in keys], dtype=object)
                for j in range(len(self.columns))
            ]
            assert all(len(kc) == n for kc in self.key_columns)
        self.counts = counts
        self.num_rows = int(num_rows)
        self._keys: Optional[List[Tuple]] = None

    @property
    def keys(self) -> List[Tuple]:
        if self._keys is None:
            self._keys = (
                list(zip(*[kc.tolist() for kc in self.key_columns]))
                if len(self.counts)
                else []
            )
        return self._keys

    @property
    def num_groups(self) -> int:
        return len(self.counts)

    def merge(self, other) -> "FrequenciesAndNumRows":
        if getattr(other, "is_spilled", False):
            # spilled ⊕ in-memory commutes; the spilled side knows how
            return other.merge(self)
        other_cols = other.key_columns
        if self.columns != other.columns:
            # align by column name (the columnar analogue of the
            # reference's name-based outer join); declared order may
            # differ from the runner's sorted sharing order
            if sorted(self.columns) != sorted(other.columns):
                raise ValueError(
                    f"cannot merge frequencies over {self.columns} with {other.columns}"
                )
            other_cols = [
                other.key_columns[other.columns.index(c)] for c in self.columns
            ]
        key_columns, counts = _group_sum(
            [
                np.concatenate([self.key_columns[j], other_cols[j]])
                for j in range(len(self.columns))
            ],
            np.concatenate([self.counts, other.counts]),
        )
        return FrequenciesAndNumRows(
            list(self.columns),
            key_columns,
            counts,
            self.num_rows + other.num_rows,
        )

    def compacted(self) -> "FrequenciesAndNumRows":
        """Re-group duplicate key rows (spill-partition compaction)."""
        key_columns, counts = _group_sum(self.key_columns, self.counts)
        return FrequenciesAndNumRows(
            list(self.columns), key_columns, counts, self.num_rows
        )

    def __eq__(self, other) -> bool:
        if not isinstance(other, FrequenciesAndNumRows):
            return False
        return (
            self.columns == other.columns
            and self.num_rows == other.num_rows
            and dict(zip(self.keys, self.counts.tolist()))
            == dict(zip(other.keys, other.counts.tolist()))
        )

    def __repr__(self) -> str:
        return (
            f"FrequenciesAndNumRows({self.columns}, groups={self.num_groups}, "
            f"num_rows={self.num_rows})"
        )


def _group_sum(
    key_columns: List[np.ndarray], counts: np.ndarray
) -> Tuple[List[np.ndarray], np.ndarray]:
    """C-hash group-by summing counts over identical key rows — the
    vectorized form of the reference's null-safe outer join + count sum
    (GroupingAnalyzers.scala:128-148); no Python loop over groups."""
    import pandas as pd

    n_cols = len(key_columns)
    frame = {f"k{j}": key_columns[j] for j in range(n_cols)}
    frame["__count"] = counts
    grouped = (
        pd.DataFrame(frame)
        .groupby(
            [f"k{j}" for j in range(n_cols)],
            sort=False,
            dropna=False,  # NaN/None group keys are real groups
        )["__count"]
        .sum()
    )
    index = grouped.index
    if n_cols == 1:
        out_keys = [index.to_numpy(dtype=object)]
    else:
        out_keys = [
            index.get_level_values(j).to_numpy(dtype=object)
            for j in range(n_cols)
        ]
    return out_keys, grouped.to_numpy(dtype=np.int64)


def top_n_order(keys: np.ndarray, counts: np.ndarray, n: int) -> np.ndarray:
    """Indices of the top-n groups by (count desc, key asc) — the
    deterministic tie-break shared by Histogram's in-memory selection
    and SpilledFrequencies.top_n (the reference's rdd.top leaves tie
    order partition-dependent; a total order keeps the detail-bin set
    identical across execution paths).

    Groups strictly above the n-th count sort fully; the boundary tie
    group only pays an O(|ties|) key partition for its n-fill smallest
    keys, so an all-tied high-cardinality column never string-sorts
    every group."""
    counts = np.asarray(counts)
    m = len(counts)
    if m == 0 or n <= 0:
        return np.array([], dtype=np.int64)
    if m <= n:
        keys_u = np.asarray(keys).astype(str)  # U-dtype: vectorized sort
        return np.lexsort((keys_u, -counts))
    kth = np.partition(counts, m - n)[m - n]
    above = np.nonzero(counts > kth)[0]
    above_keys = np.asarray(keys)[above].astype(str)
    above_order = np.lexsort((above_keys, -counts[above]))
    n_fill = n - len(above)
    if n_fill <= 0:
        return above[above_order][:n]
    tie = np.nonzero(counts == kth)[0]
    tie_keys = np.asarray(keys)[tie].astype(str)
    if len(tie) > n_fill:
        part = np.argpartition(tie_keys, n_fill - 1)[:n_fill]
        tie, tie_keys = tie[part], tie_keys[part]
    fill = tie[np.argsort(tie_keys)]
    return np.concatenate([above[above_order], fill])


def _column_key_values(col) -> Tuple[np.ndarray, np.ndarray]:
    """(codes, uniques) with uniques as python-friendly scalars."""
    codes, uniques = col.dict_encode()
    if col.ctype == ColumnType.LONG:
        uniques = np.array([int(u) for u in uniques], dtype=object)
    elif col.ctype in (ColumnType.DOUBLE, ColumnType.DECIMAL):
        uniques = np.array([float(u) for u in uniques], dtype=object)
    elif col.ctype == ColumnType.BOOLEAN:
        uniques = np.array([bool(u) for u in uniques], dtype=object)
    else:
        uniques = np.asarray(uniques, dtype=object)
    return codes, uniques


def compute_frequencies(
    data: Table,
    grouping_columns: Sequence[str],
    num_rows: Optional[int] = None,
    mesh=None,
) -> FrequenciesAndNumRows:
    """reference: GroupingAnalyzers.scala:53-80. Rows where ANY grouping
    column is NULL are excluded from groups; num_rows counts all rows.

    Streaming sources are folded batch-by-batch with the vectorized
    state merge — bounded host memory at O(#groups), never O(#rows).
    With a mesh, the count aggregation runs row-sharded on the devices
    (psum merge); the host keeps dict-encode and key bookkeeping."""
    from deequ_tpu.ops import runtime

    with observe.span(
        "group_pass", cat="group", columns=",".join(grouping_columns)
    ):
        runtime.record_group_pass(",".join(grouping_columns))
        return _compute_frequencies(data, grouping_columns, num_rows, mesh)


def _compute_frequencies(
    data: Table,
    grouping_columns: Sequence[str],
    num_rows: Optional[int] = None,
    mesh=None,
) -> FrequenciesAndNumRows:
    if hasattr(data, "with_columns"):
        data = data.with_columns(list(grouping_columns))
    if getattr(data, "is_streaming", False):
        # bounded-memory fold: in-RAM merges below the group cap, hash-
        # partitioned disk spill above it (the MEMORY_AND_DISK escape
        # hatch, reference: AnalysisRunner.scala:75,479-483)
        from deequ_tpu.analyzers.freq_spill import GroupCountAccumulator

        acc = GroupCountAccumulator(grouping_columns)
        for batch in data.batches(getattr(data, "batch_rows", 1 << 22)):
            partial = _frequencies_of_batch(batch, grouping_columns, mesh)
            with observe.span("group_merge", cat="group") as sp:
                acc.add(partial)
                if sp:
                    sp.set(groups=int(partial.num_groups), spilled=acc.spilled)
        with observe.span("group_merge", cat="group") as sp:
            state = acc.finalize()
            if sp:
                sp.set(groups=int(state.num_groups), spilled=acc.spilled)
        if num_rows is not None:
            state.num_rows = num_rows
        return state

    state = _frequencies_of_batch(data, grouping_columns, mesh)
    if num_rows is not None:
        state.num_rows = num_rows
    return state


# raveled group-code spaces larger than this spill to the host np.unique
# path (the analogue of the reference's cache-grouped-data escape hatch)
_MAX_DEVICE_BINS = 1 << 20


def _frequencies_of_batch(
    data: Table, grouping_columns: Sequence[str], mesh=None
) -> FrequenciesAndNumRows:
    """One batch's frequencies, as a `group_encode` span (each column's
    dictionary encode and validity) and a `group_count` span (the
    combined codes counted, the group keys gathered)."""
    with observe.span("group_encode", cat="group") as sp:
        if sp:
            sp.set(rows=int(data.num_rows), columns=len(grouping_columns))
        cols = [data.column(name) for name in grouping_columns]
        valid = np.ones(data.num_rows, dtype=np.bool_)
        for col in cols:
            valid &= col.valid
        encoded = [_column_key_values(col) for col in cols]
    with observe.span("group_count", cat="group") as sp:
        state = _count_groups(data, grouping_columns, valid, encoded, mesh)
        if sp:
            sp.set(rows=int(data.num_rows), groups=int(state.num_groups))
    return state


def _count_groups(
    data: Table, grouping_columns: Sequence[str], valid, encoded, mesh
) -> FrequenciesAndNumRows:
    dims = [max(len(u), 1) for _, u in encoded]

    if not valid.any():
        return FrequenciesAndNumRows(
            list(grouping_columns),
            [np.array([], dtype=object) for _ in encoded],
            np.array([], dtype=np.int64),
            data.num_rows,
        )

    code_arrays = [np.where(valid, c, 0) for c, _ in encoded]
    combined_all = np.ravel_multi_index(code_arrays, dims)
    total_bins = int(np.prod(dims))

    if mesh is not None and total_bins <= _MAX_DEVICE_BINS:
        from deequ_tpu.parallel.distributed import sharded_bincount

        combined_signed = np.where(valid, combined_all, -1)
        bin_counts = sharded_bincount(combined_signed, total_bins, mesh)
        unique_codes = np.nonzero(bin_counts)[0]
        counts = bin_counts[unique_codes]
    else:
        combined = combined_all[valid]
        unique_codes, counts = np.unique(combined, return_counts=True)
        counts = counts.astype(np.int64)

    unraveled = np.unravel_index(unique_codes, dims)
    # per-column gather of group-key values: one fancy-index per column,
    # no Python loop over groups
    key_columns = [encoded[j][1][unraveled[j]] for j in range(len(encoded))]

    return FrequenciesAndNumRows(
        list(grouping_columns), key_columns, counts, data.num_rows
    )


# ---------------------------------------------------------------------------
# Analyzer bases
# ---------------------------------------------------------------------------


class FrequencyBasedAnalyzer(GroupingAnalyzer):
    """reference: GroupingAnalyzers.scala:28-41."""

    def grouping_columns(self) -> List[str]:
        return list(self.columns)

    @property
    def instance(self) -> str:
        return ",".join(self.columns)

    @property
    def entity(self) -> Entity:
        return entity_from(self.columns)

    def preconditions(self) -> List[Callable[[Table], None]]:
        return [Preconditions.at_least_one(self.columns)] + [
            Preconditions.has_column(c) for c in self.columns
        ]

    def compute_state_from(self, table: Table) -> Optional[FrequenciesAndNumRows]:
        return compute_frequencies(table, self.grouping_columns())


class ScanShareableFrequencyBasedAnalyzer(FrequencyBasedAnalyzer):
    """Aggregations over the shared frequencies table
    (reference: GroupingAnalyzers.scala:84-121). `freq_reduce` is generic
    over the array namespace so it fuses into one device program per
    grouping set and also serves host evaluation."""

    def freq_reduce(self, counts, num_rows: int, xp) -> Any:
        raise NotImplementedError

    def metric_from_freq_agg(self, agg: Any, state: FrequenciesAndNumRows) -> Metric:
        raise NotImplementedError

    def compute_metric_from(self, state: Optional[FrequenciesAndNumRows]) -> Metric:
        if state is None:
            return self.empty_state_failure()
        from deequ_tpu.ops.freq_agg import run_shared_freq_agg

        return run_shared_freq_agg(state, [self])[0]

    def to_success_metric(self, value: float) -> DoubleMetric:
        return DoubleMetric(self.entity, self.name, self.instance, Success(value))


# ---------------------------------------------------------------------------
# Concrete frequency analyzers
# ---------------------------------------------------------------------------


def _single_or_seq(columns) -> List[str]:
    if isinstance(columns, str):
        return [columns]
    return list(columns)


def _scala_list_repr(columns: Sequence[str]) -> str:
    return f"List({', '.join(columns)})"


class Uniqueness(ScanShareableFrequencyBasedAnalyzer):
    """Fraction of values occurring exactly once
    (reference: analyzers/Uniqueness.scala:26)."""

    def __init__(self, columns):
        self.columns = _single_or_seq(columns)

    @property
    def name(self) -> str:
        return "Uniqueness"

    def freq_reduce(self, counts, num_rows: int, xp) -> Any:
        return {"unique": xp.sum(xp.asarray(counts == 1, dtype=counts.dtype))}

    def metric_from_freq_agg(self, agg: Any, state: FrequenciesAndNumRows) -> Metric:
        if state.num_groups == 0:
            return self.empty_state_failure()  # SQL sum over empty -> NULL
        return self.to_success_metric(float(agg["unique"]) / state.num_rows)

    def __repr__(self) -> str:
        return f"Uniqueness({_scala_list_repr(self.columns)})"


class Distinctness(ScanShareableFrequencyBasedAnalyzer):
    """Fraction of distinct values (reference: analyzers/Distinctness.scala:29)."""

    def __init__(self, columns):
        self.columns = _single_or_seq(columns)

    @property
    def name(self) -> str:
        return "Distinctness"

    def freq_reduce(self, counts, num_rows: int, xp) -> Any:
        return {"distinct": xp.sum(xp.asarray(counts >= 1, dtype=counts.dtype))}

    def metric_from_freq_agg(self, agg: Any, state: FrequenciesAndNumRows) -> Metric:
        if state.num_groups == 0:
            return self.empty_state_failure()
        return self.to_success_metric(float(agg["distinct"]) / state.num_rows)

    def __repr__(self) -> str:
        return f"Distinctness({_scala_list_repr(self.columns)})"


class UniqueValueRatio(ScanShareableFrequencyBasedAnalyzer):
    """#unique / #distinct groups (reference: analyzers/UniqueValueRatio.scala:25)."""

    def __init__(self, columns):
        self.columns = _single_or_seq(columns)

    @property
    def name(self) -> str:
        return "UniqueValueRatio"

    def freq_reduce(self, counts, num_rows: int, xp) -> Any:
        return {
            "unique": xp.sum(xp.asarray(counts == 1, dtype=counts.dtype)),
            "groups": xp.sum(xp.asarray(counts >= 1, dtype=counts.dtype)),
        }

    def metric_from_freq_agg(self, agg: Any, state: FrequenciesAndNumRows) -> Metric:
        if state.num_groups == 0:
            return self.empty_state_failure()
        return self.to_success_metric(float(agg["unique"]) / float(agg["groups"]))

    def __repr__(self) -> str:
        return f"UniqueValueRatio({_scala_list_repr(self.columns)})"


class CountDistinct(ScanShareableFrequencyBasedAnalyzer):
    """#groups; count(*) never nulls, so empty -> 0.0
    (reference: analyzers/CountDistinct.scala:24)."""

    def __init__(self, columns):
        self.columns = _single_or_seq(columns)

    @property
    def name(self) -> str:
        return "CountDistinct"

    def freq_reduce(self, counts, num_rows: int, xp) -> Any:
        return {"groups": xp.sum(xp.asarray(counts >= 1, dtype=counts.dtype))}

    def metric_from_freq_agg(self, agg: Any, state: FrequenciesAndNumRows) -> Metric:
        return self.to_success_metric(float(agg["groups"]))

    def __repr__(self) -> str:
        return f"CountDistinct({_scala_list_repr(self.columns)})"


class Entropy(ScanShareableFrequencyBasedAnalyzer):
    """-Σ (c/N)·ln(c/N) with N = total rows incl. nulls, exactly like the
    reference's UDF over group counts (reference: analyzers/Entropy.scala:28-41)."""

    def __init__(self, column: str):
        self.columns = [column]

    @property
    def name(self) -> str:
        return "Entropy"

    def freq_reduce(self, counts, num_rows, xp) -> Any:
        n = xp.maximum(xp.asarray(num_rows, dtype=counts.dtype), 1)
        p = counts / n
        safe_p = xp.where(p > 0, p, 1.0)
        return {"entropy": xp.sum(xp.where(p > 0, -safe_p * xp.log(safe_p), 0.0))}

    def metric_from_freq_agg(self, agg: Any, state: FrequenciesAndNumRows) -> Metric:
        if state.num_groups == 0:
            return self.empty_state_failure()
        return self.to_success_metric(float(agg["entropy"]))

    def __repr__(self) -> str:
        # Scala: case class Entropy(column: String)
        return f"Entropy({self.columns[0]})"


class MutualInformation(FrequencyBasedAnalyzer):
    """Σ pxy·ln(pxy/(px·py)) over the joint frequencies; NOT shareable
    (joins marginals — reference: analyzers/MutualInformation.scala:35-90)."""

    def __init__(self, column_a, column_b=None):
        if column_b is None:
            self.columns = _single_or_seq(column_a)
        else:
            self.columns = [column_a, column_b]

    @property
    def name(self) -> str:
        return "MutualInformation"

    @property
    def entity(self) -> Entity:
        return Entity.MULTICOLUMN

    def preconditions(self) -> List[Callable[[Table], None]]:
        return [Preconditions.exactly_n_columns(self.columns, 2)] + super().preconditions()

    def compute_metric_from(self, state: Optional[FrequenciesAndNumRows]) -> Metric:
        if state is None or state.num_groups == 0:
            return self.empty_state_failure()
        from deequ_tpu.ops import runtime

        runtime.record_pass("freq-agg:MutualInformation")
        total = state.num_rows
        # state columns may be sorted differently than self.columns
        ia = state.columns.index(self.columns[0])
        ib = state.columns.index(self.columns[1])

        if getattr(state, "is_spilled", False):
            # two streamed passes over the partitions: marginal counts
            # (memory O(|A| + |B|), typically << O(|A×B|) joint groups),
            # then the joint sum
            marg_a: Dict[str, float] = {}
            marg_b: Dict[str, float] = {}
            for part in state.partitions():
                counts = part.counts.astype(np.float64)
                for keys, marg in (
                    (part.key_columns[ia], marg_a),
                    (part.key_columns[ib], marg_b),
                ):
                    uniq, inv = np.unique(keys.astype(str), return_inverse=True)
                    sums = np.bincount(inv, weights=counts)
                    for u, s in zip(uniq, sums):
                        marg[u] = marg.get(u, 0.0) + s
            value = 0.0
            for part in state.partitions():
                counts = part.counts.astype(np.float64)
                pxy = counts / total
                # dict lookups per UNIQUE key, gathers per row (inverse
                # codes) — same vectorization as the in-memory branch
                ua, inv_a = np.unique(
                    part.key_columns[ia].astype(str), return_inverse=True
                )
                ub, inv_b = np.unique(
                    part.key_columns[ib].astype(str), return_inverse=True
                )
                px = np.array([marg_a[u] for u in ua])[inv_a] / total
                py = np.array([marg_b[u] for u in ub])[inv_b] / total
                value += float(np.sum(pxy * np.log(pxy / (px * py))))
            return DoubleMetric(
                self.entity, self.name, self.instance, Success(value)
            )

        keys_a = state.key_columns[ia]
        keys_b = state.key_columns[ib]
        counts = state.counts.astype(np.float64)

        _, codes_a = np.unique(keys_a.astype(str), return_inverse=True)
        _, codes_b = np.unique(keys_b.astype(str), return_inverse=True)
        marg_a = np.bincount(codes_a, weights=counts)
        marg_b = np.bincount(codes_b, weights=counts)

        pxy = counts / total
        px = marg_a[codes_a] / total
        py = marg_b[codes_b] / total
        value = float(np.sum(pxy * np.log(pxy / (px * py))))
        return DoubleMetric(self.entity, self.name, self.instance, Success(value))

    def __repr__(self) -> str:
        return f"MutualInformation({_scala_list_repr(self.columns)})"

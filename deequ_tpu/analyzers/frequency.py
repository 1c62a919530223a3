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

import math
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

    Keys are stored columnar (one array per grouping column, aligned
    with ``counts``) so merges stay vectorized: int, bool and float key
    columns stay typed numpy arrays, anything else is an object array.
    ``keys`` exposes the row-tuple view (Python scalars) lazily for
    consumers that want it.
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
            self.key_columns = [
                k if k.dtype.kind in _TYPED_KINDS else k.astype(object, copy=False)
                for k in keys
            ]
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

    @property
    def typed(self) -> bool:
        """Every key column is typed, so merges skip pandas and objects."""
        return all(kc.dtype.kind in _TYPED_KINDS for kc in self.key_columns)

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
                concat_key_chunks([self.key_columns[j], other_cols[j]])
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


# a key column of these dtype kinds stays a typed array, as this dtype
_TYPED_DTYPES = {"b": np.bool_, "i": np.int64, "f": np.float64}
_TYPED_KINDS = "".join(_TYPED_DTYPES)


def typed_key_column(kc: np.ndarray) -> Optional[np.ndarray]:
    """`kc` as an int64, bool or float64 array where that is exact, else
    None. An object array converts only when every element is of one
    family: all bools, all ints within int64, or all floats."""
    kind = kc.dtype.kind
    if kind in _TYPED_DTYPES:
        return kc.astype(_TYPED_DTYPES[kind], copy=False)
    if kind != "O" or not len(kc):
        return None
    values = kc.tolist()
    return typed_from_values(values, set(map(type, values)))


def typed_from_values(values: list, types: set) -> Optional[np.ndarray]:
    """The typed array of `values` (whose element types are `types`)
    where one family holds them all exactly, else None."""
    if all(issubclass(t, (bool, np.bool_)) for t in types):
        return np.array(values, dtype=np.bool_)
    if all(
        issubclass(t, (int, np.integer)) and not issubclass(t, bool) for t in types
    ):
        try:
            return np.array(values, dtype=np.int64)
        except OverflowError:
            return None
    if all(issubclass(t, (float, np.floating)) for t in types):
        return np.array(values, dtype=np.float64)
    return None


def concat_key_chunks(chunks: Sequence[np.ndarray]) -> np.ndarray:
    """One key column from chunks of it: typed when every non-empty chunk
    is typed or converts exactly to the same typed dtype, else object
    (the pandas merge then groups by Python equality)."""
    live = [c for c in chunks if len(c)] or list(chunks[:1])
    if all(c.dtype == object for c in live):
        return np.concatenate(live)
    typed = [typed_key_column(c) for c in live]
    if all(t is not None for t in typed) and len({t.dtype for t in typed}) == 1:
        return np.concatenate(typed)
    return np.concatenate([c.astype(object) for c in live])


def _canonical_floats(x: np.ndarray) -> np.ndarray:
    """-0.0 as 0.0 and every NaN as one NaN, so equal keys have equal
    bits (pandas `dropna=False` groups them the same way)."""
    out = np.where(x == 0.0, 0.0, x).astype(np.float64, copy=False)
    out[np.isnan(out)] = np.nan
    return out


def _group_sum(
    key_columns: List[np.ndarray], counts: np.ndarray
) -> Tuple[List[np.ndarray], np.ndarray]:
    """Group-by summing counts over identical key rows — the vectorized
    form of the reference's null-safe outer join + count sum
    (GroupingAnalyzers.scala:128-148); no Python loop over groups.
    Typed key columns sort; any object column takes pandas' C hash."""
    if key_columns and all(kc.dtype.kind in _TYPED_KINDS for kc in key_columns):
        return _typed_group_sum(key_columns, counts)
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


def _typed_group_sum(
    key_columns: List[np.ndarray], counts: np.ndarray
) -> Tuple[List[np.ndarray], np.ndarray]:
    """Sort the key rows, mark where a key changes, sum each run with
    `np.add.reduceat`. Group order is the sort's, which no consumer
    depends on."""
    cols = [
        _canonical_floats(kc) if kc.dtype.kind == "f" else kc for kc in key_columns
    ]
    counts = np.asarray(counts, dtype=np.int64)
    if not len(counts):
        return [c[:0] for c in cols], counts
    packed = _packed_key(cols)
    # floats sort and compare by their (canonical) bits: equal keys meet
    sort_keys = (
        [packed]
        if packed is not None
        else [c.view(np.int64) if c.dtype.kind == "f" else c for c in cols]
    )
    if len(sort_keys) == 1:
        order = np.argsort(sort_keys[0])
    else:
        order = np.lexsort(sort_keys[::-1])
    change = np.zeros(len(order) - 1, dtype=np.bool_)
    for k in sort_keys:
        ks = k[order]
        change |= ks[1:] != ks[:-1]
    starts = np.flatnonzero(np.concatenate(([True], change)))
    firsts = order[starts]
    return [c[firsts] for c in cols], np.add.reduceat(counts[order], starts)


def _packed_key(cols: List[np.ndarray]) -> Optional[np.ndarray]:
    """Two or more int/bool key columns as one int64 (mixed radix over
    each column's range), where the ranges' product fits; else None.
    One argsort of it beats a lexsort of the columns several times."""
    if len(cols) < 2 or any(c.dtype.kind not in "bi" for c in cols):
        return None
    los = [int(c.min()) for c in cols]
    spans = [int(c.max()) - lo + 1 for c, lo in zip(cols, los)]
    if math.prod(spans) > np.iinfo(np.int64).max:
        return None
    key = np.zeros(len(cols[0]), dtype=np.int64)
    for c, lo, span in zip(cols, los, spans):
        key = key * span + (c.astype(np.int64) - lo)
    return key


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
    """(codes, uniques): LONG, BOOLEAN and DOUBLE/DECIMAL uniques as
    typed int64 / bool / float64 arrays, anything else as objects."""
    codes, uniques = col.dict_encode()
    dtype = _KEY_DTYPES.get(col.ctype, object)
    return codes, np.asarray(uniques, dtype=dtype)


_KEY_DTYPES = {
    ColumnType.LONG: np.int64,
    ColumnType.BOOLEAN: np.bool_,
    ColumnType.DOUBLE: np.float64,
    ColumnType.DECIMAL: np.float64,
}


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
                    sp.set(
                        groups=int(partial.num_groups),
                        spilled=acc.spilled,
                        typed=partial.typed,
                    )
        with observe.span("group_merge", cat="group") as sp:
            state = acc.finalize()
            if sp:
                sp.set(
                    groups=int(state.num_groups),
                    spilled=acc.spilled,
                    typed=state.typed,
                )
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
            [uniques[:0] for _, uniques in encoded],
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
    # no Python loop over groups (typed uniques give typed key columns)
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

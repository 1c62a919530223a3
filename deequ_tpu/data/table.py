"""Columnar in-memory table: the engine's DataFrame equivalent.

Design (SURVEY.md §7): numeric/bool columns are dense numpy arrays plus a
validity bitmask; strings stay host-side (object arrays + dictionary
encoding) because TPUs can't regex; batches stream to device for fused
reductions. Replaces the role Spark's DataFrame plays for the reference
(reference: pom.xml:70-91, L0 in SURVEY layer map).
"""

from __future__ import annotations

import enum
import threading
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from deequ_tpu.observe import spans as _spans


class ColumnType(enum.Enum):
    STRING = "StringType"
    LONG = "LongType"
    DOUBLE = "DoubleType"
    BOOLEAN = "BooleanType"
    TIMESTAMP = "TimestampType"
    DECIMAL = "DecimalType"

    @property
    def is_numeric(self) -> bool:
        return self in (ColumnType.LONG, ColumnType.DOUBLE, ColumnType.DECIMAL)


NUMPY_BACKING = {
    ColumnType.STRING: object,
    ColumnType.LONG: np.int64,
    ColumnType.DOUBLE: np.float64,
    ColumnType.BOOLEAN: np.bool_,
    ColumnType.TIMESTAMP: "datetime64[us]",
    ColumnType.DECIMAL: np.float64,
}


class Column:
    """One column: dense values + validity mask (True = present).

    CONTRACT: null slots in ``values`` hold the neutral fill (0 / "" /
    epoch) — never NaN — so masked reductions can consume the backing
    array directly (0 * mask == 0; NaN would poison every sum). All
    constructors enforce this; build Columns through them.

    `values` may be passed as a zero-arg callable for LAZY
    materialization: streamed string columns keep their Arrow backing
    (dictionary codes serve the analyzers) and only pay the
    object-array conversion if something truly needs per-row Python
    strings.
    """

    # content digest of the backing arrow dictionary (set by from_arrow
    # for parquet dictionary columns): lets dictionary-LEVEL derived
    # values (classify/parse/hash of the dict itself) be shared across
    # STREAM batches, whose equal dictionaries are rebuilt per row group
    _dict_content_key = None

    def __init__(self, name: str, ctype: ColumnType, values, valid: np.ndarray):
        self.name = name
        self.ctype = ctype
        self.valid = valid
        if callable(values):
            self._values = None
            self._values_fn = values
        else:
            assert len(values) == len(valid)
            self._values = values
            self._values_fn = None
        # per-instance memo for derived encodings (dict codes, parsed
        # numerics) shared by every analyzer reading this batch's column
        self._cache: Dict[str, object] = {}

    @property
    def values(self) -> np.ndarray:
        if self._values is None:
            materialized = self._values_fn()
            assert len(materialized) == len(self.valid)
            self._values = materialized
        return self._values

    def __repr__(self) -> str:
        return f"Column({self.name!r}, {self.ctype})"

    def __len__(self) -> int:
        return len(self.valid)

    @property
    def null_count(self) -> int:
        return int(len(self.valid) - self.valid.sum())

    def non_null_values(self) -> np.ndarray:
        return self.values[self.valid]

    def slice(self, start: int, stop: int) -> "Column":
        child = Column(
            self.name,
            self.ctype,
            # lazy through the slice: only materialize the parent if the
            # child's python-object values are actually consumed
            lambda: self.values[start:stop],
            self.valid[start:stop],
        )
        # derived encodings (dict codes, parsed numerics) are row-wise, so
        # a slice can reuse the parent's arrays — string columns are then
        # encoded ONCE per table, not once per batch per pass
        child._parent = (self, start, stop)
        return child

    def take(self, indices: np.ndarray) -> "Column":
        return Column(self.name, self.ctype, self.values[indices], self.valid[indices])

    def numeric_values(self) -> Tuple[np.ndarray, np.ndarray]:
        """(float64 values, valid) — strings that don't parse as numbers
        become invalid (null), matching the expr-engine coercion.

        Returned arrays are shared (cached / possibly the column's own
        backing store): callers must treat them as immutable."""
        if self.ctype == ColumnType.DOUBLE or self.ctype == ColumnType.DECIMAL:
            # constructors fill null slots with 0.0, so the backing array
            # is directly usable under mask algebra (0 * mask == 0, no NaN
            # poisoning) — no per-batch materialization
            return self.values, self.valid

        def compute(col: "Column"):
            if col.ctype == ColumnType.BOOLEAN:
                return col.values.astype(np.float64), col.valid
            if col.ctype == ColumnType.TIMESTAMP:
                return (
                    col.values.astype("datetime64[us]")
                    .astype(np.int64)
                    .astype(np.float64),
                    col.valid,
                )
            if col.ctype == ColumnType.STRING:
                codes, _uniques = col.dict_encode()
                u_vals, u_ok = parsed_dictionary(col)
                return (
                    gather_with_null(u_vals, codes, 0.0),
                    gather_with_null(u_ok, codes, False),
                )
            # LONG
            return (
                np.where(col.valid, col.values.astype(np.float64), 0.0),
                col.valid,
            )

        return cached_column_encode(
            self,
            "numeric_values",
            compute,
            slicer=lambda v, s, e: (v[0][s:e], v[1][s:e]),
        )

    def as_float(self) -> np.ndarray:
        """Values as float64; null/unparseable slots = 0.0 (mask separately
        via ``numeric_values`` when the parse-failure mask matters)."""
        return self.numeric_values()[0]

    def dict_encode(self) -> Tuple[np.ndarray, np.ndarray]:
        """Dictionary-encode: (codes, uniques). Null rows get code -1.
        Codes are an integer array — int64 from the numpy/arrow encode
        paths, int32 when a parquet dictionary column's indices map
        zero-copy; consumers must not assume an 8-byte stride.

        The group-by building block: arbitrary keys become dense integer
        codes the device can bincount/segment-reduce over. Memoized per
        Column instance — every string analyzer on a batch shares one
        encode.
        """
        return cached_column_encode(
            self,
            "dict_encode",
            _compute_dict_encode,
            # codes slice row-wise; the dictionary is shared whole
            slicer=lambda v, s, e: (v[0][s:e], v[1]),
        )


def _compute_dict_encode(col: "Column") -> Tuple[np.ndarray, np.ndarray]:
    if not col.valid.any():
        return (
            np.full(len(col.values), -1, dtype=np.int64),
            np.array([], dtype=object),
        )
    arrow_arr = col._cache.get("arrow")
    if arrow_arr is not None:
        # arrow-backed string column: hash-based C dictionary encode
        return _arrow_dict_encode(arrow_arr)
    if col.ctype == ColumnType.STRING:
        # arrow's hash-based dictionary encode is ~8x numpy's sort-based
        # unique on object arrays (measured: 0.6s vs 5.2s per 4M rows);
        # fall back to np.unique only without pyarrow
        try:
            import pyarrow as pa

            return _arrow_dict_encode(
                pa.array(
                    col.values,
                    type=pa.string(),
                    mask=None if col.valid.all() else ~col.valid,
                )
            )
        except ImportError:
            pass
        except pa.lib.ArrowException:
            # backing values that aren't str (mixed object arrays,
            # numeric values under a STRING ctype): the numpy path
            # below stringifies them
            pass
    vals = col.values[col.valid]
    if col.ctype == ColumnType.STRING:
        vals = vals.astype(str)
    uniques, inv = np.unique(vals, return_inverse=True)
    codes = np.full(len(col.values), -1, dtype=np.int64)
    codes[col.valid] = inv
    return codes, uniques


def _arrow_dict_encode(arrow_arr) -> Tuple[np.ndarray, np.ndarray]:
    encoded = arrow_arr.dictionary_encode()
    codes = (
        encoded.indices.fill_null(-1)
        .to_numpy(zero_copy_only=False)
        .astype(np.int64)
    )
    uniques = encoded.dictionary.to_numpy(zero_copy_only=False)
    if uniques.dtype != object:
        uniques = uniques.astype(object)
    return codes, uniques


def cached_column_encode(col: "Column", key: str, compute, slicer=None):
    """Column-deterministic derived encoding, memoized on the Column with
    parent-slice delegation: one materialization per TABLE, batches slice
    it. `compute(column)` builds the full-column value on the root
    column; `slicer(value, start, stop)` produces a batch view of it
    (default: plain array slicing — pass one when the cached value is a
    tuple with non-row-wise parts, e.g. dict_encode's uniques)."""
    cached = col._cache.get(key)
    if cached is None:
        parent = getattr(col, "_parent", None)
        if parent is not None:
            p, start, stop = parent
            whole = cached_column_encode(p, key, compute, slicer)
            cached = (
                slicer(whole, start, stop)
                if slicer is not None
                else whole[start:stop]
            )
        else:
            cached = compute(col)
        col._cache[key] = cached
    return cached


_DICT_DERIVED_CACHE: "OrderedDict" = None  # type: ignore[assignment]
_DICT_DERIVED_MAX = 256
# byte budget: a stream whose every row group carries a DISTINCT
# near-64k-entry dictionary must not pin hundreds of MB of derived
# arrays for the process lifetime (the bounded-RSS stream contract)
_DICT_DERIVED_MAX_BYTES = 32 << 20
_DICT_DERIVED_BYTES = 0
# family kernels run dictionary encodes from a thread pool (fused.py):
# the OrderedDict reorder/evict and the byte counter are not atomic
_DICT_DERIVED_LOCK = threading.Lock()


def _derived_nbytes(value) -> int:
    if isinstance(value, np.ndarray):
        return int(value.nbytes)
    if isinstance(value, (tuple, list)):
        return sum(_derived_nbytes(v) for v in value)
    return 64  # scalars / small objects: nominal


def root_column(col: "Column") -> "Column":
    """The column a chain of `Column.slice`s was cut from: the holder of
    the memos its slices share."""
    while getattr(col, "_parent", None) is not None:
        col = col._parent[0]
    return col


def cached_dictionary_encode(col: "Column", key: str, compute):
    """DICTIONARY-level derived value (classify / numeric parse / hash of
    the dictionary itself — NOT row data): memoized on the root Column
    like `cached_column_encode`, and additionally across BATCHES via the
    arrow dictionary's content digest when available. A streamed parquet
    source rebuilds an equal dictionary for every row group; without
    this memo every batch re-classifies/re-parses/re-hashes the same few
    thousand strings. The cross-batch tier is bounded by entry count AND
    bytes (LRU eviction)."""
    global _DICT_DERIVED_CACHE, _DICT_DERIVED_BYTES
    root = root_column(col)
    cached = root._cache.get(key)
    if cached is not None:
        return cached
    content_key = root._dict_content_key
    if content_key is not None:
        with _DICT_DERIVED_LOCK:
            if _DICT_DERIVED_CACHE is None:
                from collections import OrderedDict

                _DICT_DERIVED_CACHE = OrderedDict()
            hit = _DICT_DERIVED_CACHE.get((content_key, key))
            if hit is not None:
                _DICT_DERIVED_CACHE.move_to_end((content_key, key))
                root._cache[key] = hit[0]
                return hit[0]
    value = compute(root)
    root._cache[key] = value
    if content_key is not None:
        nbytes = _derived_nbytes(value)
        with _DICT_DERIVED_LOCK:
            _DICT_DERIVED_CACHE[(content_key, key)] = (value, nbytes)
            _DICT_DERIVED_BYTES += nbytes
            while _DICT_DERIVED_CACHE and (
                len(_DICT_DERIVED_CACHE) > _DICT_DERIVED_MAX
                or _DICT_DERIVED_BYTES > _DICT_DERIVED_MAX_BYTES
            ):
                _key, (_value, evicted_bytes) = _DICT_DERIVED_CACHE.popitem(
                    last=False
                )
                _DICT_DERIVED_BYTES -= evicted_bytes
    return value


def _arrow_dictionary_digest(dictionary):
    """Content digest of an arrow string dictionary (the cross-batch
    memo key): sha1 over its raw buffers, ~µs for the few-thousand-entry
    dictionaries parquet produces. None (no sharing) for offset/sliced
    or oversized dictionaries, where buffer bytes would not equal
    content."""
    try:
        if dictionary.offset != 0 or len(dictionary) > (1 << 16):
            return None
        import hashlib

        h = hashlib.sha1()
        for buf in dictionary.buffers():
            if buf is not None:
                h.update(buf)
        return (len(dictionary), h.digest())
    except Exception:  # noqa: BLE001 - memo is an optimization only
        return None


def parsed_dictionary(col: "Column"):
    """(parsed float64 values, parse-ok bool) per dictionary entry of a
    STRING column, through the cross-batch dictionary memo — shared by
    numeric_values' per-row gather and the profiler's counts-based
    numeric-stats path."""
    from deequ_tpu.ops.strings import parse_floats

    return cached_dictionary_encode(
        col,
        "dictparse",
        lambda c: parse_floats(np.asarray(c.dict_encode()[1], dtype=object)),
    )


def hashed_dictionary(col: "Column") -> np.ndarray:
    """uint64 xxhash per dictionary entry of a STRING column, through
    the cross-batch dictionary memo — shared by the packed-HLL input
    spec and the _LowCardCounts presence path of ApproxCountDistinct."""
    from deequ_tpu.ops.strings import hash_strings

    return cached_dictionary_encode(
        col,
        "dicthash",
        lambda c: hash_strings(np.asarray(c.dict_encode()[1], dtype=object)),
    )


def gather_with_null(lut: np.ndarray, codes: np.ndarray, null_value) -> np.ndarray:
    """Per-row gather of a per-unique LUT through dict_encode codes in ONE
    pass: dict_encode's null sentinel (-1) indexes a slot holding
    `null_value` appended at the end (numpy negative indexing), so no
    mask/scatter temporaries are needed. Relies on codes ∈ [-1, len(lut))."""
    lut = np.asarray(lut)
    ext = np.append(lut, np.asarray([null_value], dtype=lut.dtype))
    return ext[codes]


def _infer_type(values: Sequence) -> ColumnType:
    non_null = [v for v in values if v is not None]
    if not non_null:
        return ColumnType.STRING
    if all(isinstance(v, bool) for v in non_null):
        return ColumnType.BOOLEAN
    if all(isinstance(v, (int, np.integer)) and not isinstance(v, bool) for v in non_null):
        return ColumnType.LONG
    if all(
        isinstance(v, (int, float, np.integer, np.floating)) and not isinstance(v, bool)
        for v in non_null
    ):
        return ColumnType.DOUBLE
    return ColumnType.STRING


def _column_from_list(name: str, values: Sequence, ctype: Optional[ColumnType]) -> Column:
    if ctype is None:
        ctype = _infer_type(values)
    n = len(values)
    valid = np.array([v is not None and v == v for v in values], dtype=np.bool_) \
        if ctype in (ColumnType.DOUBLE, ColumnType.DECIMAL) \
        else np.array([v is not None for v in values], dtype=np.bool_)
    backing = NUMPY_BACKING[ctype]
    if ctype == ColumnType.STRING:
        arr = np.empty(n, dtype=object)
        for i, v in enumerate(values):
            arr[i] = str(v) if v is not None else ""
    else:
        fill = {
            ColumnType.LONG: 0,
            ColumnType.DOUBLE: 0.0,
            ColumnType.DECIMAL: 0.0,
            ColumnType.BOOLEAN: False,
            ColumnType.TIMESTAMP: np.datetime64(0, "us"),
        }[ctype]
        arr = np.array(
            [v if (v is not None and v == v) else fill for v in values], dtype=backing
        ) if ctype in (ColumnType.DOUBLE, ColumnType.DECIMAL) else np.array(
            [v if v is not None else fill for v in values], dtype=backing
        )
    return Column(name, ctype, arr, valid)


def shared_all_true(shared: Dict[str, np.ndarray], n: int) -> np.ndarray:
    """One read-only all-true mask shared by every null-free column of a
    batch (lets pack elide mask work via `valid.all()` without a scan
    per column). `shared` is the per-from_arrow scratch dict."""
    mask = shared.get("all_true")
    if mask is None or len(mask) != n:
        mask = np.ones(n, dtype=bool)
        mask.setflags(write=False)
        shared["all_true"] = mask
    return mask


def pool_empty(n: int, dtype) -> np.ndarray:
    """Uninitialized Column backing allocated from the arrow memory pool.

    Streaming decode allocates and keeps dozens of outputs per batch;
    fresh `np.empty` arrays at that size come from new mmaps, so the
    decode kernels pay a page fault per 4KB on first touch. The arrow
    pool recycles the previous batch's pages (it already backs the
    fallback's `fill_null` outputs), which measures ~1.8x faster per
    column on the wide-stream shape. Degrades to `np.empty` when
    pyarrow is unavailable."""
    try:
        import pyarrow as pa
    except ImportError:
        return np.empty(n, dtype=dtype)
    dt = np.dtype(dtype)
    buf = pa.allocate_buffer(int(n) * dt.itemsize)
    out = np.frombuffer(buf, dtype=dt)
    # np.frombuffer honors the source's mutability; assert rather than
    # silently hand the kernels a read-only backing
    assert out.flags.writeable
    return out


def _column_from_arrow_fallback(name, arr, arrow_table, shared) -> Column:
    """Host-side decode of one (already combined) arrow chunk.

    This is the designated fallback behind the native fast path
    (data/arrow_decode.py): columns whose values must exist host-side
    (plain strings, decimals) or whose layout the native kernels don't
    take land here. tools/lint.py's DECODE rule keeps `.to_numpy(` copy
    idioms confined to this chain."""
    import pyarrow as pa

    if pa.types.is_dictionary(arr.type) and not (
        pa.types.is_string(arr.type.value_type)
        or pa.types.is_large_string(arr.type.value_type)
    ):
        # only string dictionaries have a first-class code path;
        # others decode to their value type so the column's ctype
        # matches what _arrow_ctype reports for the schema
        arr = arr.dictionary_decode()
    # null-free columns skip the fill_null/where copies and get
    # zero-copy numpy views of the arrow buffers where possible
    # (views are read-only; Column treats values as immutable,
    # which also lets all null-free columns share one mask)
    no_nulls = arr.null_count == 0
    if no_nulls:
        valid = shared_all_true(shared, len(arr))
    else:
        valid = np.asarray(arr.is_valid())
    t = arr.type
    if pa.types.is_boolean(t):
        vals = np.asarray(arr if no_nulls else arr.fill_null(False))
        return Column(name, ColumnType.BOOLEAN, vals, valid)
    elif pa.types.is_integer(t):
        vals = np.asarray(arr if no_nulls else arr.fill_null(0))
        if vals.dtype != np.int64:
            vals = vals.astype(np.int64)
        return Column(name, ColumnType.LONG, vals, valid)
    elif pa.types.is_floating(t):
        vals = np.asarray(arr if no_nulls else arr.fill_null(0.0))
        if vals.dtype != np.float64:
            vals = vals.astype(np.float64)
        nan = np.isnan(vals)
        if nan.any():
            valid = valid & ~nan
            vals = np.where(valid, vals, 0.0)
        # a float64 field annotated by to_arrow keeps its
        # DECIMAL ctype across the arrow/parquet round trip
        # (values were float64 already; only the logical type
        # needs restoring)
        ctype = (
            ColumnType.DECIMAL
            if _arrow_logical_decimal(arrow_table, name)
            else ColumnType.DOUBLE
        )
        return Column(name, ctype, vals, valid)
    elif pa.types.is_decimal(t):
        vals = np.array(
            [float(v) if v is not None else 0.0 for v in arr.to_pylist()],
            dtype=np.float64,
        )
        return Column(name, ColumnType.DECIMAL, vals, valid)
    elif pa.types.is_timestamp(t):
        vals = np.asarray(arr.cast(pa.timestamp("us")).fill_null(0))
        return Column(
            name, ColumnType.TIMESTAMP, vals.astype("datetime64[us]"), valid
        )
    elif pa.types.is_dictionary(t) and (
        pa.types.is_string(t.value_type)
        or pa.types.is_large_string(t.value_type)
    ):
        # dictionary-decoded string column (ParquetSource reads
        # string columns this way): the codes ARE the dict_encode
        # result — no per-row string materialization, no re-encode.
        # `values` stays lazy; only consumers that truly need
        # per-row python strings pay the gather.
        # int32 stays int32: arrow dictionary indices feed
        # bincount/gathers directly (the int64 upcast cost a
        # copy plus double the bincount traffic); null-free
        # indices map zero-copy
        idx = arr.indices
        if idx.null_count == 0:
            codes = idx.to_numpy(zero_copy_only=True)
        else:
            codes = idx.fill_null(-1).to_numpy(zero_copy_only=False)
        uniques = dictionary_uniques_fallback(arr.dictionary)
        col = Column(
            name,
            ColumnType.STRING,
            lambda codes=codes, uniques=uniques: gather_with_null(
                uniques, codes, ""
            ),
            valid,
        )
        col._cache["dict_encode"] = (codes, uniques)
        col._dict_content_key = _arrow_dictionary_digest(
            arr.dictionary
        )
        return col
    elif pa.types.is_string(t) or pa.types.is_large_string(t):
        vals = arr.to_numpy(zero_copy_only=False)
        if vals.dtype != object:
            vals = vals.astype(object)
        if not valid.all():
            vals[~valid] = ""
        col = Column(name, ColumnType.STRING, vals, valid)
        # keep the arrow array: dict_encode uses its C hash-based
        # dictionary_encode instead of a sort-based np.unique
        col._cache["arrow"] = arr
        return col
    else:
        py = arr.to_pylist()
        vals = np.empty(len(py), dtype=object)
        for i, v in enumerate(py):
            vals[i] = str(v) if v is not None else ""
        return Column(name, ColumnType.STRING, vals, valid)


def _arrow_logical_decimal(arrow_table, name: str) -> bool:
    """True when the float64 field carries the deequ_tpu DECIMAL
    logical-type annotation written by Table.to_arrow."""
    try:
        md = arrow_table.schema.field(name).metadata or {}
    except Exception:  # noqa: BLE001 - schemaless inputs
        md = {}
    return md.get(b"deequ_tpu.logical_type") == ColumnType.DECIMAL.value.encode()


def dictionary_uniques_fallback(dictionary) -> np.ndarray:
    """Designated fallback: materialize a dictionary's uniques as a host
    object array. This is the only host-side string materialization the
    dictionary decode paths (native and fallback) perform — per-row
    strings stay lazy."""
    uniques = dictionary.to_numpy(zero_copy_only=False)
    if uniques.dtype != object:
        uniques = uniques.astype(object)
    return uniques


class Table:
    """Immutable columnar table."""

    def __init__(self, columns: Sequence[Column]):
        self._columns: Dict[str, Column] = {c.name: c for c in columns}
        lengths = {len(c) for c in columns}
        if len(lengths) > 1:
            raise ValueError(f"ragged columns: {lengths}")
        self._num_rows = lengths.pop() if lengths else 0

    # -- construction -------------------------------------------------------

    @staticmethod
    def from_pydict(
        data: Dict[str, Sequence], types: Optional[Dict[str, ColumnType]] = None
    ) -> "Table":
        types = types or {}
        return Table(
            [_column_from_list(k, v, types.get(k)) for k, v in data.items()]
        )

    @staticmethod
    def from_numpy(
        data: Dict[str, np.ndarray],
        valid: Optional[Dict[str, np.ndarray]] = None,
        types: Optional[Dict[str, ColumnType]] = None,
    ) -> "Table":
        valid = valid or {}
        types = types or {}
        cols = []
        for name, arr in data.items():
            arr = np.asarray(arr)
            if name in types:
                ctype = types[name]
            elif arr.dtype == np.bool_:
                ctype = ColumnType.BOOLEAN
            elif np.issubdtype(arr.dtype, np.integer):
                ctype = ColumnType.LONG
            elif np.issubdtype(arr.dtype, np.floating):
                ctype = ColumnType.DOUBLE
            elif np.issubdtype(arr.dtype, np.datetime64):
                ctype = ColumnType.TIMESTAMP
            else:
                # object arrays go through the same inference as
                # from_pydict: {bool, None} is a BOOLEAN column (its
                # histogram keys must be 'true'/'false', not Python's
                # str(True)), object ints are LONG, etc. A caller-supplied
                # mask ANDs with the non-null mask the values imply.
                inferred = _column_from_list(name, list(arr), None)
                extra_mask = valid.get(name)
                if extra_mask is not None:
                    inferred = Column(
                        name,
                        inferred.ctype,
                        inferred.values,
                        inferred.valid & np.asarray(extra_mask, dtype=np.bool_),
                    )
                cols.append(inferred)
                continue
            v = valid.get(name)
            if v is None:
                if ctype in (ColumnType.DOUBLE, ColumnType.DECIMAL):
                    v = ~np.isnan(np.asarray(arr, dtype=np.float64))
                    arr = np.where(v, arr, 0.0)
                elif ctype == ColumnType.STRING:
                    v = np.array([x is not None for x in arr], dtype=np.bool_)
                    if not v.all():
                        arr = arr.copy()
                        arr[~v] = ""
                else:
                    v = np.ones(len(arr), dtype=np.bool_)
            elif ctype in (ColumnType.DOUBLE, ColumnType.DECIMAL):
                # NaN == NULL under this engine; enforce the neutral-fill
                # contract even when the caller supplies the mask
                v = np.asarray(v, dtype=np.bool_) & ~np.isnan(
                    np.asarray(arr, dtype=np.float64)
                )
                arr = np.where(v, arr, 0.0)
            cols.append(Column(name, ctype, arr, np.asarray(v, dtype=np.bool_)))
        return Table(cols)

    @staticmethod
    def from_pandas(df) -> "Table":
        import pandas as pd  # noqa: F401

        cols = []
        for name in df.columns:
            s = df[name]
            valid = (~s.isna()).to_numpy(dtype=np.bool_)
            if s.dtype == object or str(s.dtype) in ("string", "str"):
                arr = np.empty(len(s), dtype=object)
                raw = s.tolist()
                all_bool = True
                for i, v in enumerate(raw):
                    arr[i] = "" if not valid[i] else str(v)
                    if valid[i] and not isinstance(v, bool):
                        all_bool = False
                if all_bool and valid.any():
                    barr = np.array(
                        [bool(v) if valid[i] else False for i, v in enumerate(raw)],
                        dtype=np.bool_,
                    )
                    cols.append(Column(str(name), ColumnType.BOOLEAN, barr, valid))
                    continue
                cols.append(Column(str(name), ColumnType.STRING, arr, valid))
            elif str(s.dtype).startswith("datetime"):
                arr = s.to_numpy(dtype="datetime64[us]")
                arr = np.where(valid, arr, np.datetime64(0, "us"))
                cols.append(Column(str(name), ColumnType.TIMESTAMP, arr, valid))
            elif s.dtype == np.bool_ or str(s.dtype) == "boolean":
                arr = s.fillna(False).to_numpy(dtype=np.bool_)
                cols.append(Column(str(name), ColumnType.BOOLEAN, arr, valid))
            elif str(s.dtype).startswith(("Int", "UInt")) or (
                isinstance(s.dtype, np.dtype) and np.issubdtype(s.dtype, np.integer)
            ):
                arr = s.fillna(0).to_numpy(dtype=np.int64)
                cols.append(Column(str(name), ColumnType.LONG, arr, valid))
            elif str(s.dtype).startswith("Float"):
                # pandas nullable Float32/Float64 extension dtypes
                arr = s.to_numpy(dtype=np.float64, na_value=np.nan)
                valid = valid & ~np.isnan(np.where(valid, arr, 0.0))
                arr = np.where(valid, arr, 0.0)
                cols.append(Column(str(name), ColumnType.DOUBLE, arr, valid))
            else:
                arr = s.to_numpy(dtype=np.float64)
                valid = valid & ~np.isnan(np.where(valid, arr, 0.0))
                arr = np.where(valid, arr, 0.0)
                cols.append(Column(str(name), ColumnType.DOUBLE, arr, valid))
        return Table(cols)

    @staticmethod
    def from_arrow(arrow_table, fastpath_columns=None, wire=None) -> "Table":
        """`decode_arrow` under a `table_from_arrow` span (category
        `ingest`; attrs `rows`, `columns`): a table a caller hands over.
        A streamed source decodes its batches under its own `arrow_decode`
        span instead."""
        with _spans.span(
            "table_from_arrow",
            cat="ingest",
            rows=int(arrow_table.num_rows),
            columns=int(arrow_table.num_columns),
        ):
            return Table.decode_arrow(arrow_table, fastpath_columns, wire)

    @staticmethod
    def decode_arrow(arrow_table, fastpath_columns=None, wire=None) -> "Table":
        """Decode an arrow table into engine Columns.

        `fastpath_columns` (a set of names, normally threaded through
        `ParquetSource.with_decode_fastpath` by the planner's
        `plan_decode_fastpath`) routes those columns through the
        buffer-level native decode (data/arrow_decode.py + ops/native/
        decode.c): one C pass from arrow buffers to the Column backing,
        no intermediate numpy materialization. Any column the native
        path cannot take (missing library, unexpected layout) falls back
        to the host chain automatically — the two produce bit-identical
        Columns, so the fast path is a pure perf decision.

        `wire` (a `runtime.WireFusionPlan`) goes one step further for
        its columns: decode straight to the packed device wire format,
        skipping the Column intermediate entirely. Fused columns get a
        lazy stub Column plus wire rows collected on the returned
        table's ``wire_rows`` attribute; any per-batch failure (layout
        surprise, narrow-int overflow, unresolved shift) falls back to
        the ordinary decode for that column, that batch."""
        import pyarrow as pa

        cols = []
        wire_rows: Dict[str, object] = {}
        shared: Dict[str, np.ndarray] = {}  # one mask for null-free columns
        fast = None
        wire_fast = None
        if fastpath_columns or (wire is not None and wire.columns):
            from deequ_tpu.data import arrow_decode

            fast = arrow_decode.decode_fast_column
            wire_fast = arrow_decode.decode_wire_column
        for name in arrow_table.column_names:
            chunked = arrow_table.column(name)
            if isinstance(chunked, pa.ChunkedArray):
                chunks = list(chunked.chunks)
            else:
                chunks = [chunked]
            if wire_fast is not None and wire is not None and name in wire.columns:
                fused = wire_fast(
                    name, chunks, arrow_table, wire.columns[name], wire
                )
                if fused is not None:
                    stub, rows = fused
                    cols.append(stub)
                    wire_rows.update(rows)
                    continue
            if fast is not None and fastpath_columns and name in fastpath_columns:
                col = fast(name, chunks, arrow_table, shared)
                if col is not None:
                    cols.append(col)
                    continue
            # single-chunk columns (every row-group/slice read) skip
            # the combine_chunks memcpy; the chunk may carry a slice
            # offset, which every consumer below handles
            if len(chunks) == 1:
                arr = chunks[0]
            elif not chunks:
                arr = pa.array([], chunked.type)
            else:
                arr = chunked.combine_chunks()
                if isinstance(arr, pa.ChunkedArray):
                    arr = arr.chunk(0)
            cols.append(
                _column_from_arrow_fallback(name, arr, arrow_table, shared)
            )
        table = Table(cols)
        if wire_rows:
            table.wire_rows = wire_rows
        return table

    def to_arrow(self, dictionary_encode_strings: bool = False):
        """Arrow table with faithful nulls: the Column neutral-fill
        contract is inverted (null slots become arrow nulls, not the
        0.0/""/False fillers). The single conversion used by every
        write-to-parquet path (tests, dryruns, bench).

        DECIMAL columns are float64-backed in memory (the precision was
        already capped at ingest — see `from_arrow`), so they emit as
        float64 with the logical type recorded in field metadata
        (``deequ_tpu.logical_type = DecimalType``). A round trip through
        arrow/parquet keeps the DecimalType ctype but NOT decimal
        precision beyond float64's 53 bits."""
        import pyarrow as pa

        arrays, fields = [], []
        for name, ctype in self.schema:
            col = self.column(name)
            values = col.values
            valid = np.asarray(col.valid)
            if values.dtype == object:
                # explicit string type: an ALL-NULL column would
                # otherwise infer arrow's null type, whose
                # dictionary_encode produces a DictionaryArray parquet
                # cannot write ("null encoded in dictionary")
                arr = pa.array(
                    [v if ok else None for v, ok in zip(values, valid)],
                    type=pa.string() if ctype == ColumnType.STRING else None,
                )
                if dictionary_encode_strings and pa.types.is_string(arr.type):
                    arr = arr.dictionary_encode()
            else:
                arr = pa.array(values, mask=~valid)
            metadata = (
                {b"deequ_tpu.logical_type": ctype.value.encode()}
                if ctype == ColumnType.DECIMAL
                else None
            )
            fields.append(pa.field(name, arr.type, metadata=metadata))
            arrays.append(arr)
        return pa.table(arrays, schema=pa.schema(fields))

    def to_parquet(self, path: str, row_group_size: Optional[int] = None,
                   dictionary_encode_strings: bool = False) -> None:
        import pyarrow.parquet as pq

        pq.write_table(
            self.to_arrow(dictionary_encode_strings),
            path,
            row_group_size=row_group_size,
        )

    @staticmethod
    def from_parquet(path: str, columns: Optional[List[str]] = None) -> "Table":
        import pyarrow.parquet as pq

        return Table.from_arrow(pq.read_table(path, columns=columns))

    @staticmethod
    def scan_parquet(
        path: str,
        columns: Optional[List[str]] = None,
        batch_rows: int = 1 << 22,
    ):
        """Out-of-core scan: a streaming source every pass can consume
        (bounded host memory; prefetch thread overlaps decode with device
        compute). Use instead of `from_parquet` when the table exceeds
        host RAM."""
        from deequ_tpu.data.source import ParquetSource

        return ParquetSource(path, columns=columns, batch_rows=batch_rows)

    @staticmethod
    def scan_parquet_dataset(
        paths,
        columns: Optional[List[str]] = None,
        batch_rows: int = 1 << 22,
    ):
        """Out-of-core scan over a directory (or explicit list) of
        parquet partition files, folded one partition at a time and
        merged through the analyzer state semigroup in deterministic
        name order. The shape incremental runs require: attach a
        `StateRepository` (`AnalysisRunBuilder.with_state_repository`)
        and re-runs scan only new or modified partitions."""
        from deequ_tpu.data.source import PartitionedParquetSource

        return PartitionedParquetSource(
            paths, columns=columns, batch_rows=batch_rows
        )

    # -- schema / access ----------------------------------------------------

    @property
    def num_rows(self) -> int:
        return self._num_rows

    def __len__(self) -> int:
        return self._num_rows

    @property
    def column_names(self) -> List[str]:
        return list(self._columns.keys())

    def has_column(self, name: str) -> bool:
        return name in self._columns

    def column(self, name: str) -> Column:
        if name not in self._columns:
            from deequ_tpu.core.exceptions import NoSuchColumnException

            raise NoSuchColumnException(f"Input data does not include column {name}!")
        return self._columns[name]

    def __getitem__(self, name: str) -> Column:
        return self.column(name)

    @property
    def schema(self) -> List[Tuple[str, ColumnType]]:
        return [(c.name, c.ctype) for c in self._columns.values()]

    # -- transforms ---------------------------------------------------------

    def slice(self, start: int, stop: int) -> "Table":
        return Table([c.slice(start, stop) for c in self._columns.values()])

    def filter(self, row_mask: np.ndarray) -> "Table":
        idx = np.nonzero(np.asarray(row_mask, dtype=bool))[0]
        return Table([c.take(idx) for c in self._columns.values()])

    def select(self, names: Sequence[str]) -> "Table":
        return Table([self.column(n) for n in names])

    def with_column(self, col: Column) -> "Table":
        cols = [c for c in self._columns.values() if c.name != col.name]
        return Table(cols + [col])

    def batches(self, batch_size: int) -> Iterator["Table"]:
        """Stream fixed-size row slices (the unit shipped to device)."""
        if self._num_rows <= batch_size:
            # single batch: yield self so per-Column caches (dict codes,
            # parsed numerics) are shared across every pass over this table
            yield self
            return
        for start in range(0, self._num_rows, batch_size):
            yield self.slice(start, min(start + batch_size, self._num_rows))

    def random_split(
        self, weights: Sequence[float], seed: Optional[int] = None
    ) -> List["Table"]:
        """reference: suggestions/ConstraintSuggestionRunner.scala:127-148
        (df.randomSplit for train/test)."""
        rng = np.random.default_rng(seed)
        total = float(sum(weights))
        u = rng.random(self._num_rows)
        bounds = np.cumsum([w / total for w in weights])
        out = []
        lo = 0.0
        for hi in bounds:
            out.append(self.filter((u >= lo) & (u < hi)))
            lo = hi
        return out

    def to_pydict(self) -> Dict[str, List]:
        out: Dict[str, List] = {}
        for c in self._columns.values():
            vals: List = []
            for i in range(len(c)):
                if not c.valid[i]:
                    vals.append(None)
                elif c.ctype == ColumnType.STRING:
                    vals.append(c.values[i])
                elif c.ctype == ColumnType.BOOLEAN:
                    vals.append(bool(c.values[i]))
                elif c.ctype == ColumnType.LONG:
                    vals.append(int(c.values[i]))
                elif c.ctype == ColumnType.TIMESTAMP:
                    vals.append(c.values[i])
                else:
                    vals.append(float(c.values[i]))
            out[c.name] = vals
        return out

    def to_pandas(self):
        import pandas as pd

        return pd.DataFrame(self.to_pydict())

    def __repr__(self):
        cols = ", ".join(f"{n}:{t.value}" for n, t in self.schema)
        return f"Table({self._num_rows} rows; {cols})"

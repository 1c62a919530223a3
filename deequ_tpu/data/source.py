"""Streaming data sources: out-of-core input for every pass.

The reference scans "billions of rows" through Spark's partitioned
readers (reference: README.md:43); the TPU-native equivalent streams
Arrow record batches from Parquet through the fused/distributed passes
with a prefetch thread overlapping host decode with device compute —
host memory stays bounded at O(batch + #groups), never O(rows).

A source duck-types the slice of the Table interface the engine reads:
``num_rows``, ``column_names``, ``schema``, ``has_column``,
``column(name)`` (schema-only: a zero-row column for precondition
checks), ``batches(n)`` (the row stream), and ``is_streaming = True``
which switches group-by/histogram folds to batch-merge mode.
"""

from __future__ import annotations

import hashlib
import os
import queue
import struct
import threading
import time
from collections import OrderedDict
from typing import Callable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from deequ_tpu.data.table import Column, ColumnType, NUMPY_BACKING, Table
from deequ_tpu.observe import spans as _spans

_SENTINEL = object()

#: how long `batches()` waits for its decode thread at shutdown before
#: abandoning it (the thread is a daemon; it can only still be alive if
#: a single row-group decode takes longer than this)
JOIN_TIMEOUT_S = 10.0

#: ranged GETs a native-reader fetch slot keeps in flight against the
#: DEEQU_TPU_SOURCE_STALL_MS latency model — the conventional range
#: -request concurrency of object-store clients. Only the stall model
#: consults this; local preads are issued back to back either way.
READER_INFLIGHT_GETS = 8


def _resolve_quietly_fallback(fut) -> None:
    """Resolve a readahead future to None, tolerating the race where a
    fetch slot resolves it concurrently. Fallback-designated (the FAULTS
    lint in tools/lint.py permits the swallowed exception here): losing
    the race IS the success case."""
    try:
        fut.set_result(None)
    except Exception:  # noqa: BLE001 - racing fetch slot already resolved it
        pass


def _close_all_fallback(handles) -> None:
    """Best-effort teardown of reader handles. Fallback-designated: a
    close failure during unwind must never mask the primary error, and
    the fd itself is bounded by the open_files registry."""
    for handle in handles:
        try:
            handle.close()
        except Exception:  # noqa: BLE001 - teardown best-effort
            pass


def _arrow_ctype(t) -> ColumnType:
    import pyarrow as pa

    if pa.types.is_dictionary(t):
        t = t.value_type
    if pa.types.is_boolean(t):
        return ColumnType.BOOLEAN
    if pa.types.is_integer(t):
        return ColumnType.LONG
    if pa.types.is_floating(t):
        return ColumnType.DOUBLE
    if pa.types.is_decimal(t):
        return ColumnType.DECIMAL
    if pa.types.is_timestamp(t):
        return ColumnType.TIMESTAMP
    return ColumnType.STRING


def _decode_table(arrow_table, fastpath, wire=None) -> Table:
    """Arrow batch -> engine Table under an `arrow_decode` span.

    The span isolates the buffer->wire conversion self-time from the
    parquet read/decompression that surrounds it in the decode stage,
    so traces (and BENCH_DECODE.json) report the exact seconds the
    decode fast path targets. `wire_fuse` counts the columns this batch
    decoded straight to wire buffers (decode-to-wire fusion)."""
    sp = _spans.span("arrow_decode", cat="decode")
    with sp:
        table = Table.from_arrow(arrow_table, fastpath, wire=wire)
        if sp:
            wire_rows = getattr(table, "wire_rows", None) or {}
            fused_cols = {k.split(":", 1)[1] for k in wire_rows}
            sp.set(
                rows=int(table.num_rows),
                fast=bool(fastpath),
                wire_fuse=len(fused_cols),
            )
    return table


def _empty_column(name: str, ctype: ColumnType) -> Column:
    backing = NUMPY_BACKING[ctype]
    return Column(
        name,
        ctype,
        np.empty(0, dtype=backing),
        np.empty(0, dtype=np.bool_),
    )


class DataSource:
    """Base for streaming sources. Subclasses implement `_schema()` and
    `_iter_tables(batch_size)`."""

    is_streaming = True
    batch_rows = 1 << 22

    # -- schema ------------------------------------------------------------

    def _schema(self) -> List[Tuple[str, ColumnType]]:
        raise NotImplementedError

    @property
    def schema(self) -> List[Tuple[str, ColumnType]]:
        return self._schema()

    @property
    def column_names(self) -> List[str]:
        return [name for name, _ in self._schema()]

    def has_column(self, name: str) -> bool:
        return any(n == name for n, _ in self._schema())

    def column(self, name: str) -> Column:
        """Zero-row column carrying the schema type — enough for the
        precondition system (has_column / is_numeric / is_string)."""
        for n, ctype in self._schema():
            if n == name:
                return _empty_column(n, ctype)
        from deequ_tpu.core.exceptions import NoSuchColumnException

        raise NoSuchColumnException(f"Input data does not include column {name}!")

    def __getitem__(self, name: str) -> Column:
        return self.column(name)

    # -- rows --------------------------------------------------------------

    @property
    def num_rows(self) -> int:
        raise NotImplementedError

    def __len__(self) -> int:
        return self.num_rows

    def _iter_tables(self, batch_size: int) -> Iterator[Table]:
        raise NotImplementedError

    def batches(self, batch_size: int) -> Iterator[Table]:
        """Stream decoded Tables with a bounded prefetch thread: the next
        batch's host decode overlaps the consumer's device compute. The
        producer is the DECODE STAGE of the stream pipeline
        (ops/pipeline.py): it adopts the consumer's trace context and
        reports per-batch `pipe_item` spans under a `pipe_stage` span,
        which the run report's pipeline-occupancy section aggregates; the
        consumer's take from its queue is a `wait` span (`on="decode"`).

        Abandonment-safe (pinned by tests/test_pipeline_shutdown.py): if
        the consumer drops the generator early (an error mid-pass, a
        downstream stage shutting down), the finally block signals the
        producer, drains the queue so its blocked put() wakes, and joins
        the thread within JOIN_TIMEOUT_S. The producer closes its
        `_iter_tables` iterator ON the producer thread before exiting,
        so file handles (e.g. the open ParquetFile) release
        deterministically rather than at garbage collection.

        `DEEQU_TPU_PIPELINE=0` (runtime.pipeline_enabled) decodes
        synchronously on the caller's thread instead — no prefetch
        thread, no queue: the fully SERIAL fallback the stream
        pipeline's differential tests compare against. Batch content
        and order are identical either way."""
        from deequ_tpu.ops import runtime

        if not runtime.pipeline_enabled():
            yield from self._batches_serial(batch_size)
            return
        q: "queue.Queue" = queue.Queue(maxsize=2)
        stop = threading.Event()
        error: List[BaseException] = []
        tracer = _spans.current_tracer()
        parent = _spans.current_span()

        def _put(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def producer() -> None:
            it = self._iter_tables(batch_size)

            def _next():
                try:
                    return next(it)
                except StopIteration:
                    return _SENTINEL

            try:
                with _spans.attached(tracer, parent):
                    with _spans.span(
                        "pipe_stage", cat="pipeline", stage="decode"
                    ) as stage_sp:
                        items = 0
                        while not stop.is_set():
                            sp = _spans.span(
                                "pipe_item", cat="pipeline", stage="decode"
                            )
                            with sp:
                                table = _next()
                                if sp:
                                    # the exhausted fetch still runs the
                                    # iterator's tail (flush + close) —
                                    # real decode time, but not an item
                                    if table is _SENTINEL:
                                        sp.set(eos=True)
                                    else:
                                        sp.set(rows=int(table.num_rows))
                            if table is _SENTINEL:
                                break
                            if not _put(table):
                                return
                            items += 1
                        if stage_sp:
                            stage_sp.set(items=items)
            except BaseException as e:  # noqa: BLE001
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

        thread = threading.Thread(
            target=producer, daemon=True, name="deequ-decode"
        )
        thread.start()
        produced_any = False
        try:
            while True:
                with _spans.span("wait", cat="wait", on="decode"):
                    item = q.get()
                if item is _SENTINEL:
                    break
                produced_any = True
                yield item
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
        if not produced_any:
            # zero-row source: one empty batch so aggregations see the
            # schema and produce their empty-state verdicts, matching the
            # in-memory Table contract
            yield Table([_empty_column(n, t) for n, t in self._schema()])

    def _batches_serial(self, batch_size: int) -> Iterator[Table]:
        """The DEEQU_TPU_PIPELINE=0 decode: same iterator, same batch
        sequence, same empty-batch fallback — on the calling thread."""
        produced_any = False
        it = self._iter_tables(batch_size)
        try:
            for table in it:
                produced_any = True
                yield table
        finally:
            close = getattr(it, "close", None)
            if close is not None:
                close()
        if not produced_any:
            yield Table([_empty_column(n, t) for n, t in self._schema()])


class ParquetSource(DataSource):
    """Out-of-core Parquet scan (reference scale claim: README.md:43;
    SURVEY §7 step 10 — streamed Arrow batches through the fused pass)."""

    def __init__(
        self,
        path: str,
        columns: Optional[List[str]] = None,
        batch_rows: int = 1 << 22,
        prune_groups: Optional[Sequence[int]] = None,
        decode_fastpath: Optional[Sequence[str]] = None,
        wire_fusion=None,
        native_reader: Optional[Sequence[str]] = None,
        encoded_fold=None,
    ):
        import pyarrow.parquet as pq

        self.path = path
        self.columns = columns
        self.batch_rows = batch_rows
        # row groups statically proven skippable (lint/pushdown.py): the
        # scan never reads them, so num_rows reports decoded rows only
        self.prune_groups = (
            frozenset(int(g) for g in prune_groups) if prune_groups else None
        )
        # columns the planner approved for the buffer-level native decode
        # (ops/fused.py:plan_decode_fastpath → with_decode_fastpath);
        # None/empty = every column takes the host from_arrow chain
        self.decode_fastpath = (
            frozenset(decode_fastpath) if decode_fastpath else None
        )
        # decode-to-wire plan (runtime.WireFusionPlan) for the subset of
        # fast-decode columns whose every consumer is packed-only: those
        # skip the Column intermediate entirely. Shared by reference —
        # the plan carries the pass's sticky-shift handshake.
        self.wire_fusion = wire_fusion
        # columns the planner proved native-reader-eligible from footer
        # metadata (ops/fused.py:classify_reader_columns): their chunks
        # pread + page-decode through ops/native/parquet_read.c instead
        # of pyarrow. None/empty = the pyarrow read path everywhere.
        self.native_reader = (
            frozenset(native_reader) if native_reader else None
        )
        # per-column encoded-fold specs (data/encfold.EncFoldColSpec)
        # the planner proved run-foldable (classify_encfold_columns):
        # those chunks decode to (run, code) streams and fold family
        # state over runs instead of rows. None/empty = row-width path.
        self.encoded_fold = dict(encoded_fold) if encoded_fold else None
        pf = pq.ParquetFile(path)
        meta = pf.metadata
        if self.prune_groups:
            self._num_rows = sum(
                meta.row_group(g).num_rows
                for g in range(meta.num_row_groups)
                if g not in self.prune_groups
            )
        else:
            self._num_rows = meta.num_rows
        arrow_schema = pf.schema_arrow
        names = columns if columns is not None else arrow_schema.names
        self._schema_cache = [
            (name, _arrow_ctype(arrow_schema.field(name).type)) for name in names
        ]
        pf.close()

    def _schema(self) -> List[Tuple[str, ColumnType]]:
        return self._schema_cache

    @property
    def num_rows(self) -> int:
        return self._num_rows

    def with_columns(self, names) -> "ParquetSource":
        """Column-pruned view: the fused pass calls this with the union
        of its input specs' columns so only consumed columns are decoded
        (Spark's column pruning, the dominant stream-mode cost). A prune
        set survives projection — the two compose in either order."""
        keep = [n for n, _ in self._schema_cache if n in set(names)]
        if keep == [n for n, _ in self._schema_cache] or not keep:
            return self
        return ParquetSource(
            self.path,
            columns=keep,
            batch_rows=self.batch_rows,
            prune_groups=self.prune_groups,
            decode_fastpath=self.decode_fastpath,
            wire_fusion=self.wire_fusion,
            native_reader=self.native_reader,
            encoded_fold=self.encoded_fold,
        )

    def with_prune(self, skip) -> "ParquetSource":
        """Row-group-pruned view: `skip` holds indices the pushdown
        interpreter proved all-false for every fused member's where.
        Composes with an existing prune set (union) and with
        with_columns (the projection carries the set forward)."""
        skip = frozenset(int(g) for g in skip)
        if not skip:
            return self
        if self.prune_groups:
            skip = skip | self.prune_groups
        return ParquetSource(
            self.path,
            columns=self.columns,
            batch_rows=self.batch_rows,
            prune_groups=skip,
            decode_fastpath=self.decode_fastpath,
            wire_fusion=self.wire_fusion,
            native_reader=self.native_reader,
            encoded_fold=self.encoded_fold,
        )

    def with_decode_fastpath(self, names) -> "ParquetSource":
        """Fast-decode view: `names` are the columns the planner proved
        eligible for the buffer-level native decode. Pure routing — the
        fast and fallback decode emit bit-identical Columns — so this
        composes freely with with_columns/with_prune."""
        names = frozenset(names)
        if not names or names == (self.decode_fastpath or frozenset()):
            return self
        return ParquetSource(
            self.path,
            columns=self.columns,
            batch_rows=self.batch_rows,
            prune_groups=self.prune_groups,
            decode_fastpath=names,
            wire_fusion=self.wire_fusion,
            native_reader=self.native_reader,
            encoded_fold=self.encoded_fold,
        )

    def with_wire_fusion(self, plan) -> "ParquetSource":
        """Decode-to-wire view: `plan` is the runtime.WireFusionPlan the
        planner built for this pass's packed-only columns. Carried by
        reference (it holds the sticky-shift handshake); composes freely
        with the other with_* views."""
        if plan is None or not plan.columns:
            return self
        return ParquetSource(
            self.path,
            columns=self.columns,
            batch_rows=self.batch_rows,
            prune_groups=self.prune_groups,
            decode_fastpath=self.decode_fastpath,
            wire_fusion=plan,
            native_reader=self.native_reader,
            encoded_fold=self.encoded_fold,
        )

    def with_native_reader(self, names) -> "ParquetSource":
        """Native-reader view: `names` are the columns the planner proved
        eligible for the page-level native decode (every chunk's codec,
        encodings and nesting checked against the footer). Pure routing
        — the native and pyarrow reads emit bit-identical buffers — so
        this composes freely with the other with_* views."""
        names = frozenset(names)
        if not names or names == (self.native_reader or frozenset()):
            return self
        return ParquetSource(
            self.path,
            columns=self.columns,
            batch_rows=self.batch_rows,
            prune_groups=self.prune_groups,
            decode_fastpath=self.decode_fastpath,
            wire_fusion=self.wire_fusion,
            native_reader=names,
            encoded_fold=self.encoded_fold,
        )

    def with_encoded_fold(self, specs) -> "ParquetSource":
        """Encoded-fold view: `specs` maps columns the planner proved
        run-foldable (ops/fused.py:classify_encfold_columns) to their
        EncFoldColSpec. Encoded fold rides on the native reader
        (enc ⊆ reader by planner contract) and fails closed per chunk to
        the row-width decode, so this composes freely with the other
        with_* views."""
        specs = dict(specs) if specs else None
        if not specs or specs == self.encoded_fold:
            return self
        return ParquetSource(
            self.path,
            columns=self.columns,
            batch_rows=self.batch_rows,
            prune_groups=self.prune_groups,
            decode_fastpath=self.decode_fastpath,
            wire_fusion=self.wire_fusion,
            native_reader=self.native_reader,
            encoded_fold=specs,
        )

    @property
    def wire_plan(self):
        """The attached WireFusionPlan (None when not planned) — the
        handle the fused pass uses for the shift publish handshake."""
        return self.wire_fusion

    def decode_column_types(self):
        """Arrow type tokens per scanned column AS THE SCAN DECODES THEM
        (string columns arrive dictionary-encoded via read_dictionary,
        with int32 indices) — the pure vocabulary the decode planner
        (ops/fused.py:classify_decode_columns) and the cost model key
        against ops/native.DECODE_PRIMITIVES, keeping both pyarrow-free.
        This is the only reader of the arrow schema for decode planning,
        like row_group_stats is for pushdown."""
        import pyarrow as pa
        import pyarrow.parquet as pq

        out = {}
        pf = pq.ParquetFile(self.path)
        try:
            arrow_schema = pf.schema_arrow
            for name, _ in self._schema_cache:
                t = arrow_schema.field(name).type
                if pa.types.is_string(t) or pa.types.is_large_string(t):
                    # read_dictionary rewrites these on the way in
                    out[name] = "dictionary<string,int32>"
                elif pa.types.is_dictionary(t) and (
                    pa.types.is_string(t.value_type)
                    or pa.types.is_large_string(t.value_type)
                ) and t.index_type == pa.int32():
                    out[name] = "dictionary<string,int32>"
                else:
                    out[name] = str(t)
        finally:
            pf.close()
        return out

    def row_group_stats(self):
        """Per-row-group parquet statistics as pure records for the
        pushdown interpreter — the ONLY statistics reader, so
        lint/pushdown.py itself never touches pyarrow (tools/lint.py
        PUSHDOWN rule). Unusable stats become None fields; verdicts then
        degrade to unknown, never to wrong."""
        import pyarrow.parquet as pq

        from deequ_tpu.lint.pushdown import ColumnStats, RowGroupStats

        names = {name for name, _ in self._schema_cache}
        out: List[RowGroupStats] = []
        pf = pq.ParquetFile(self.path)
        try:
            meta = pf.metadata
            schema = meta.schema
            for g in range(meta.num_row_groups):
                rg = meta.row_group(g)
                cols = {}
                for j in range(rg.num_columns):
                    chunk = rg.column(j)
                    name = chunk.path_in_schema
                    if name not in names:
                        continue
                    # chunk-layout fields for the native-reader planner
                    # (classify_reader_columns): physical type, codec,
                    # page encodings, byte range, nesting levels. Any
                    # read failure leaves them None — the column then
                    # falls off the native reader, never mis-qualifies.
                    try:
                        se = schema.column(j)
                        dpo = int(chunk.data_page_offset)
                        dictpo = (
                            int(chunk.dictionary_page_offset)
                            if chunk.has_dictionary_page
                            and chunk.dictionary_page_offset is not None
                            else None
                        )
                        offset = (
                            dpo if dictpo is None else min(dpo, dictpo)
                        )
                        layout = dict(
                            physical_type=str(chunk.physical_type),
                            codec=str(chunk.compression),
                            encodings=tuple(
                                str(e) for e in chunk.encodings
                            ),
                            chunk_offset=offset,
                            chunk_bytes=int(chunk.total_compressed_size),
                            num_values=int(chunk.num_values),
                            max_def_level=int(se.max_definition_level),
                            max_rep_level=int(se.max_repetition_level),
                            data_page_offset=dpo,
                            dictionary_page_offset=dictpo,
                        )
                    except Exception:  # noqa: BLE001 - degrade to unknown
                        layout = {}
                    st = chunk.statistics
                    if st is None:
                        cols[name] = ColumnStats(**layout)
                        continue
                    has_mm = bool(getattr(st, "has_min_max", False))
                    nc = (
                        st.null_count
                        if bool(getattr(st, "has_null_count", True))
                        else None
                    )
                    cols[name] = ColumnStats(
                        min_value=st.min if has_mm else None,
                        max_value=st.max if has_mm else None,
                        null_count=int(nc) if nc is not None else None,
                        **layout,
                    )
                out.append(
                    RowGroupStats(
                        index=g, num_rows=int(rg.num_rows), columns=cols
                    )
                )
        finally:
            pf.close()
        return out

    def _decode_fastpath_set(self) -> Optional[frozenset]:
        """The planner-approved fast-decode set, or None when the knob
        forces the host chain (the decode differential's baseline)."""
        from deequ_tpu.ops import runtime

        if self.decode_fastpath and runtime.decode_fastpath_enabled():
            return self.decode_fastpath
        return None

    def _wire_fusion_active(self):
        """The attached WireFusionPlan when the kill switch allows it.
        Wire fusion rides on the native fast path, so both knobs gate
        it — DEEQU_TPU_WIRE_FUSED=0 (or fastpath off) restores the
        exact pre-fusion decode for the differential baseline."""
        from deequ_tpu.ops import runtime

        if (
            self.wire_fusion is not None
            and self.wire_fusion.columns
            and runtime.wire_fused_enabled()
            and runtime.decode_fastpath_enabled()
        ):
            return self.wire_fusion
        return None

    def _native_reader_active(self) -> Optional[frozenset]:
        """The planner-approved native-reader column set when every gate
        allows it: the DEEQU_TPU_NATIVE_READER kill switch, the decode
        fast path it assembles through (reader ⊆ fastpath by planner
        contract), and the native library itself."""
        from deequ_tpu.ops import native, runtime

        if (
            self.native_reader
            and runtime.native_reader_enabled()
            and runtime.decode_fastpath_enabled()
            and native.available()
        ):
            return self.native_reader
        return None

    def _encoded_fold_active(self, native_cols):
        """The planner-approved encoded-fold spec map restricted to the
        active native-reader columns, or None when the
        DEEQU_TPU_ENCODED_FOLD kill switch (or any native-reader gate)
        turns the run-fold path off — the differential's baseline."""
        from deequ_tpu.ops import runtime

        if (
            self.encoded_fold
            and native_cols
            and runtime.encoded_fold_enabled()
        ):
            specs = {
                n: s
                for n, s in self.encoded_fold.items()
                if n in native_cols
            }
            return specs or None
        return None

    def _reader_chunk_meta(self, native_cols):
        """Per-(row-group, column) native decode recipes from the footer,
        re-proving each chunk's eligibility against what is actually on
        disk (physical type, codec loadability, page encodings, nesting,
        value counts). A chunk the planner approved but the footer now
        disqualifies simply gets no recipe — it reads through pyarrow,
        bit-identical. Never returns a recipe it cannot honor."""
        import pyarrow.parquet as pq

        from deequ_tpu.data import native_reader as nr
        from deequ_tpu.ops import native

        codec_mask = native.reader_codecs()
        metas = {}
        pf = pq.ParquetFile(self.path)
        try:
            meta = pf.metadata
            schema = meta.schema
            arrow_schema = pf.schema_arrow
            tokens = {}
            for name in native_cols:
                try:
                    tok = str(arrow_schema.field(name).type)
                except KeyError:
                    continue
                if tok in native.READER_TOKENS:
                    tokens[name] = tok
            for g in range(meta.num_row_groups):
                if self.prune_groups is not None and g in self.prune_groups:
                    continue
                rg = meta.row_group(g)
                for j in range(rg.num_columns):
                    chunk = rg.column(j)
                    name = chunk.path_in_schema
                    tok = tokens.get(name)
                    if tok is None:
                        continue
                    se = schema.column(j)
                    allowed_phys, dtype = native.READER_TOKENS[tok]
                    phys = str(chunk.physical_type)
                    codec = str(chunk.compression)
                    encodings = {str(e) for e in chunk.encodings}
                    if (
                        phys not in allowed_phys
                        or codec not in native.READER_CODEC_ENUM
                        or not (
                            codec_mask & native.READER_CODEC_MASK[codec]
                        )
                        or not encodings <= native.READER_ENCODINGS
                        or se.max_repetition_level != 0
                        or se.max_definition_level > 1
                        or int(chunk.num_values) != int(rg.num_rows)
                    ):
                        continue
                    offset = int(chunk.data_page_offset)
                    if (
                        chunk.has_dictionary_page
                        and chunk.dictionary_page_offset is not None
                    ):
                        offset = min(
                            offset, int(chunk.dictionary_page_offset)
                        )
                    metas[(g, name)] = nr.ChunkMeta(
                        column=name,
                        token=tok,
                        dtype=dtype,
                        phys=native.READER_PHYS_ENUM[phys],
                        codec=native.READER_CODEC_ENUM[codec],
                        offset=offset,
                        nbytes=int(chunk.total_compressed_size),
                        num_values=int(chunk.num_values),
                        max_def=int(se.max_definition_level),
                    )
        finally:
            pf.close()
        return metas

    def _iter_tables(self, batch_size: int) -> Iterator[Table]:
        from deequ_tpu.ops import runtime

        workers = runtime.decode_workers()
        if self._native_reader_active():
            yield from self._iter_tables_native(batch_size, workers)
        elif workers > 1:
            yield from self._iter_tables_parallel(batch_size, workers)
        else:
            yield from self._iter_tables_serial(batch_size)

    def _iter_tables_native(
        self, batch_size: int, workers: int
    ) -> Iterator[Table]:
        """The native parquet read path: a dedicated read-ahead thread
        preads each unit's planner-approved column-chunk byte ranges
        (posix_fadvise(WILLNEED) hints the NEXT unit before this one's
        preads, so the object-store stall model overlaps IO with
        decompression), and the decode pool page-decodes them through
        ops/native/parquet_read.c + data/native_reader.py — pyarrow
        reads only the columns without a native recipe. Units, batch
        slicing and the ordered merge are IDENTICAL to
        _iter_tables_parallel, so the batch sequence is bit-identical
        to the pyarrow path at any worker count."""
        import collections
        from concurrent.futures import Future, ThreadPoolExecutor

        import pyarrow as pa
        import pyarrow.parquet as pq

        from deequ_tpu.core.controller import retry_call
        from deequ_tpu.data import encfold as _encfold
        from deequ_tpu.data import native_reader as nr
        from deequ_tpu.observe import heartbeat
        from deequ_tpu.ops import runtime
        from deequ_tpu.testing import faults

        fastpath = self._decode_fastpath_set()
        wire = self._wire_fusion_active()
        size = min(batch_size, self.batch_rows)
        units = self._plan_decode_units(size)
        if not units:
            return
        native_cols = self._native_reader_active()
        enc_specs = self._encoded_fold_active(native_cols)
        ctypes = dict(self._schema_cache)
        metas = self._reader_chunk_meta(native_cols)
        if not metas:
            # nothing on disk qualified (footer changed since planning):
            # take the ordinary path wholesale rather than paying the
            # fetch-thread machinery for zero native chunks
            if workers > 1:
                yield from self._iter_tables_parallel(batch_size, workers)
            else:
                yield from self._iter_tables_serial(batch_size)
            return
        tokens = {m.column: m.token for m in metas.values()}
        scanned = [n for n, _ in self._schema_cache]
        stall_s = runtime.source_stall_s()
        retry_attempts = runtime.retry_budget()
        retry_base = runtime.retry_base_s()
        str_cols = [
            n for n, t in self._schema_cache if t == ColumnType.STRING
        ]
        tracer = _spans.current_tracer()
        parent = _spans.current_span()
        # per-unit fetch plan: the (group, recipe) pairs the read-ahead
        # thread preads, in deterministic (group, schema) order
        unit_chunks = [
            [
                (g, metas[(g, n)])
                for g in unit
                for n in scanned
                if (g, n) in metas
            ]
            for unit in units
        ]
        futures: List[Future] = [Future() for _ in units]
        stop = threading.Event()
        # Read-ahead window: fetch slot i may start once fewer than
        # workers + 2 units separate it from the decode cursor. This is
        # admission by UNIT INDEX, not a counting semaphore, because a
        # semaphore can be barged: a slot starting unit i+3 can steal
        # the permit a sleeping slot i was woken for, and once the
        # window fills with units AHEAD of the decode cursor the scan
        # deadlocks (decode waits for unit i, unit i waits for decode).
        # The index test cannot starve: decode waiting on unit i means
        # every unit before i is consumed, so i always clears the gate.
        window = threading.Condition()
        consumed = [0]

        def window_wait(i: int) -> None:
            with window:
                while (
                    i >= consumed[0] + workers + 2
                    and not stop.is_set()
                ):
                    window.wait(1.0)

        def window_advance() -> None:
            with window:
                consumed[0] += 1
                window.notify_all()
        # Readahead depth: real object stores serve overlapping range
        # requests, so the latency model is paid per in-flight GET, not
        # summed serially across the scan. Depth stays small — enough
        # to hide one unit's GET behind another's decode without
        # flooding the page cache; the window gate still bounds
        # fetched-but-undecoded units at workers + 2.
        fetch_depth = min(len(units), max(2, workers))
        try:
            read_fd = os.open(self.path, os.O_RDONLY)
        except OSError:
            if workers > 1:
                yield from self._iter_tables_parallel(batch_size, workers)
            else:
                yield from self._iter_tables_serial(batch_size)
            return

        def fetch_unit(i: int) -> None:
            try:
                if stop.is_set():
                    return
                window_wait(i)
                if stop.is_set():
                    return
                chunks = unit_chunks[i]
                with _spans.attached(tracer, parent):
                    # hint this unit's ranges up front: the kernel
                    # fills the page cache during the very stall the
                    # latency model charges below
                    for _, m in chunks:
                        nr.fadvise_chunk(read_fd, m)
                    raw = {}
                    bytes_read = 0
                    retries = recovered = exhausted = observed = 0
                    sp = _spans.span("page_read", cat="read")
                    with sp, heartbeat.current().timed("read"):
                        faults.fault_point("read.latency")
                        # the object-store latency model: one ranged
                        # GET per row group. Owning the byte schedule
                        # means the GETs fly concurrently (capped like
                        # any real range-request client), so the slot
                        # pays one round of latency per in-flight
                        # window — the serial per-group stall is
                        # exactly what the blocking pyarrow read pays
                        if stall_s > 0.0:
                            rounds = -(-len(units[i]) // READER_INFLIGHT_GETS)
                            time.sleep(stall_s * rounds)
                        for g, m in chunks:
                            # bounded retry + exponential backoff around
                            # the pread/ranged GET: transient errors and
                            # short reads re-issue up to the budget; an
                            # exhausted chunk degrades to the pyarrow
                            # fallback on the decode side — never a
                            # failed scan, never a wrong answer
                            def _fetch(m=m):
                                faults.fault_point("read.pread")
                                data = nr.fetch_chunk(read_fd, m)
                                if (
                                    data is not None
                                    and faults.fault_point("read.short")
                                    == "short"
                                ):
                                    return None  # truncated: retryable
                                return data

                            data, r, rec_ok = retry_call(
                                _fetch,
                                attempts=retry_attempts,
                                base_s=retry_base,
                                key=f"{self.path}:{i}:{m.column}",
                            )
                            retries += r
                            observed += r
                            if rec_ok:
                                recovered += 1
                            elif data is None:
                                exhausted += 1
                                observed += 1
                            if data is not None:
                                if (
                                    faults.fault_point("read.corrupt")
                                    == "corrupt"
                                ):
                                    # truncation, not a bit flip: the
                                    # decoder detects short buffers and
                                    # returns None (column falls back
                                    # whole); a flipped payload byte
                                    # could decode to wrong VALUES
                                    observed += 1
                                    data = data[: max(1, len(data) // 2)]
                                bytes_read += len(data)
                            raw[(g, m.column)] = data
                        if sp:
                            sp.set(
                                groups=len(units[i]),
                                chunks=len(chunks),
                                bytes_read=bytes_read,
                            )
                    if retries or exhausted:
                        runtime.record_retry(retries, recovered, exhausted)
                    if observed:
                        runtime.record_fault(injected=observed)
                futures[i].set_result(raw)
            except BaseException:  # noqa: BLE001 - degrade to pyarrow
                # a failed fetch slot is contained, never silent: the
                # unit decodes through the pyarrow fallback and the
                # degrade is counted in the fault telemetry
                with _spans.attached(tracer, parent):
                    runtime.record_fault(injected=1, fallback_units=1)
            finally:
                if not futures[i].done():
                    _resolve_quietly_fallback(futures[i])

        local = threading.local()
        open_files: List = []
        files_lock = threading.Lock()

        def _pf():
            pf = getattr(local, "pf", None)
            if pf is None:
                pf = pq.ParquetFile(
                    self.path, read_dictionary=str_cols or None
                )
                local.pf = pf
                with files_lock:
                    open_files.append(pf)
            return pf

        wire_cols = set(wire.columns) if wire is not None else set()

        def decode_unit(i: int) -> List[Table]:
            faults.fault_point("decode.worker")
            unit = units[i]
            readahead_hit = futures[i].done()
            heartbeat.current().note_readahead(bool(readahead_hit))
            raw = futures[i].result()
            window_advance()
            with _spans.attached(tracer, parent):
                with _spans.span(
                    "page_decode", cat="decode", groups=len(unit)
                ) as sp:
                    segments: dict = {}
                    failed: set = set()
                    enc_off: set = set()
                    enc_fallback = 0
                    if raw is not None:
                        for g, m in unit_chunks[i]:
                            data = raw.get((g, m.column))
                            dec = None
                            if data is not None:
                                if (
                                    enc_specs
                                    and m.column in enc_specs
                                    and m.column not in enc_off
                                ):
                                    dec = nr.decode_chunk_runs(data, m)
                                    if dec is None:
                                        # fail closed: a chunk the run
                                        # decoder refuses (corrupt run,
                                        # plain data page, fault) takes
                                        # the row-width path — never
                                        # wrong values
                                        enc_off.add(m.column)
                                        enc_fallback += 1
                                if dec is None:
                                    dec = nr.decode_chunk(data, m)
                            if dec is None:
                                failed.add(m.column)
                            else:
                                segments.setdefault(m.column, []).append(
                                    dec
                                )
                    # a column is native for this unit only when EVERY
                    # group chunk decoded: partial columns cannot
                    # assemble, so they fall back whole
                    covered = {
                        n
                        for n, segs in segments.items()
                        if n not in failed and len(segs) == len(unit)
                    }
                    # a column folds over runs only when EVERY chunk
                    # run-decoded; a mixed column expands its run chunks
                    # back to row width so the ordinary assemble path
                    # applies unchanged
                    run_cols: set = set()
                    for name in list(covered):
                        segs = segments[name]
                        is_run = [
                            isinstance(s, nr.RunChunk) for s in segs
                        ]
                        if all(is_run):
                            run_cols.add(name)
                        elif any(is_run):
                            expanded = []
                            for s in segs:
                                if isinstance(s, nr.RunChunk):
                                    s = nr.expand_runs(s)
                                if s is None:
                                    break
                                expanded.append(s)
                            if len(expanded) == len(segs):
                                segments[name] = expanded
                            else:
                                covered.discard(name)
                                failed.add(name)
                    enc_runs = enc_values = enc_saved = 0
                    for name in run_cols:
                        for rc in segments[name]:
                            enc_runs += len(rc.run_len)
                            enc_values += rc.num_values
                            # row-width materialization avoided: the
                            # row path builds an 8-byte value plus a
                            # 1-byte mask per row; the runs path keeps
                            # 12 bytes per run plus the dictionary
                            enc_saved += max(
                                0,
                                9 * rc.num_values
                                - 12 * len(rc.run_len)
                                - rc.dict_values.nbytes,
                            )
                    enc_codes = 0
                    fb_cols = [n for n in scanned if n not in covered]
                    fb_merged = None
                    if fb_cols:
                        pf = _pf()
                        parts = [
                            pf.read_row_group(g, columns=fb_cols)
                            for g in unit
                        ]
                        fb_merged = (
                            parts[0]
                            if len(parts) == 1
                            else pa.concat_tables(parts)
                        )
                        del parts
                        total = int(fb_merged.num_rows)
                    else:
                        first = next(iter(covered))
                        total = sum(
                            seg.num_values for seg in segments[first]
                        )
                    tables = []
                    for start in range(0, total, size):
                        stop_row = min(start + size, total)
                        fb_table = (
                            _decode_table(
                                fb_merged.slice(start, size),
                                fastpath,
                                wire,
                            )
                            if fb_merged is not None
                            else None
                        )
                        shared: dict = {}
                        wire_rows = dict(
                            getattr(fb_table, "wire_rows", None) or {}
                        )
                        enc_payloads: dict = {}
                        cols = []
                        for name in scanned:
                            if name not in covered:
                                cols.append(fb_table.column(name))
                                continue
                            if name in run_cols:
                                cols.append(
                                    _encfold.EncFoldStub(
                                        name,
                                        ctypes[name],
                                        tokens[name],
                                        segments[name],
                                        start,
                                        stop_row,
                                    )
                                )
                                payload = _encfold.build_payload(
                                    enc_specs[name],
                                    segments[name],
                                    start,
                                    stop_row,
                                )
                                if payload is not None:
                                    enc_payloads[name] = payload
                                    enc_codes += payload.codes_folded
                                continue
                            col = None
                            if name in wire_cols:
                                res = nr.assemble_wire_column(
                                    name,
                                    tokens[name],
                                    segments[name],
                                    start,
                                    stop_row,
                                    wire.columns[name],
                                    wire,
                                )
                                if res is not None:
                                    col, rows = res
                                    wire_rows.update(rows)
                            if col is None:
                                col = nr.assemble_column(
                                    name,
                                    tokens[name],
                                    segments[name],
                                    start,
                                    stop_row,
                                    shared,
                                )
                            cols.append(col)
                        table = Table(cols)
                        if wire_rows:
                            table.wire_rows = wire_rows
                        if enc_payloads:
                            table.encfold = enc_payloads
                        tables.append(table)
                    if sp:
                        chunks_native = len(unit) * len(covered)
                        sp.set(
                            rows=int(total),
                            chunks_native=chunks_native,
                            chunks_fallback=len(unit) * len(scanned)
                            - chunks_native,
                            readahead_hit=bool(readahead_hit),
                            runs_native=int(enc_runs),
                            chunks_runs=len(unit) * len(run_cols),
                        )
                    if enc_specs and (run_cols or enc_fallback):
                        runtime.record_encfold(
                            chunks=len(unit) * len(run_cols),
                            fallback=enc_fallback,
                            runs=enc_runs,
                            values=enc_values,
                            codes=enc_codes,
                            bytes_saved=enc_saved,
                        )
                    return tables

        fetch_pool = ThreadPoolExecutor(
            max_workers=fetch_depth, thread_name_prefix="deequ-read-ahead"
        )
        for i in range(len(units)):
            fetch_pool.submit(fetch_unit, i)
        pool = ThreadPoolExecutor(
            max_workers=workers, thread_name_prefix="deequ-decode-worker"
        )
        pending = collections.deque()
        next_unit = 0
        try:
            while next_unit < len(units) or pending:
                while next_unit < len(units) and len(pending) < workers + 1:
                    pending.append(
                        (next_unit, pool.submit(decode_unit, next_unit))
                    )
                    next_unit += 1
                unit_i, fut = pending.popleft()
                try:
                    tables = fut.result()
                except Exception:  # noqa: BLE001 - contained: one inline redo
                    # a decode worker died mid-unit. The fetched bytes
                    # are still resolved in futures[unit_i], so the unit
                    # re-decodes inline on the consumer thread — bit
                    # -identical output, one unit of lost parallelism.
                    # A second failure is persistent and propagates.
                    runtime.record_fault(injected=1)
                    tables = decode_unit(unit_i)
                    runtime.record_retry(1, 1, 0)
                for table in tables:
                    yield table
        finally:
            stop.set()
            with window:
                window.notify_all()
            fetch_pool.shutdown(wait=False, cancel_futures=True)
            for fut in futures:
                if not fut.done():
                    _resolve_quietly_fallback(fut)
            for _, fut in pending:
                fut.cancel()
            pool.shutdown(wait=True)
            # no fetch slot may outlive the fd it preads from
            fetch_pool.shutdown(wait=True)
            try:
                os.close(read_fd)
            except OSError:  # fault-ok: teardown double-close guard
                pass
            with files_lock:
                _close_all_fallback(open_files)

    def _iter_tables_serial(self, batch_size: int) -> Iterator[Table]:
        import pyarrow.parquet as pq

        from deequ_tpu.ops import runtime

        fastpath = self._decode_fastpath_set()
        wire = self._wire_fusion_active()
        size = min(batch_size, self.batch_rows)
        # Read row group by row group: this pyarrow's iter_batches /
        # dataset scanner retain every decoded batch in the pool for the
        # reader's lifetime (measured: RSS grows linearly with batches
        # consumed), while read_row_group frees cleanly. Memory bound is
        # O(row group + batch), so files written with sane group sizes
        # stream at constant memory.
        # String columns decode as DictionaryArray (read_dictionary):
        # parquet pages are dictionary-encoded on disk, so this skips
        # materializing per-row strings AND hands dict_encode its codes
        # for free (Table.from_arrow stores them directly).
        str_cols = [
            n for n, t in self._schema_cache if t == ColumnType.STRING
        ]
        with pq.ParquetFile(
            self.path, read_dictionary=str_cols or None
        ) as pf:
            # NOTE: memory_map=True was tried and REVERTED: it saves a
            # buffer copy (~3%) but maps the whole file into RSS, turning
            # the bounded-memory contract's headline number (peak RSS)
            # into file size.
            # One batch per row group (sliced down when a group exceeds
            # the cap). TINY groups (< size/4 — incremental writers often
            # produce 10k-row groups) still coalesce, or per-batch fold
            # machinery would multiply 100x; near-batch-size groups pass
            # through directly because pa.concat_tables forces a
            # dictionary unification on string columns that costs more
            # (~0.9s/100M measured) than the machinery it saves.
            import pyarrow as pa

            tiny = max(1, size // 4)
            pending: list = []
            pending_rows = 0
            # benchmark-only latency injection (object-store model):
            # sleeps on the decoding thread before each row-group read,
            # i.e. exactly where a remote range-GET would block
            stall_s = runtime.source_stall_s()

            def flush():
                if not pending:
                    return None
                merged = (
                    pending[0]
                    if len(pending) == 1
                    else pa.concat_tables(pending)
                )
                pending.clear()
                return merged

            skip = self.prune_groups
            for g in range(pf.metadata.num_row_groups):
                if skip is not None and g in skip:
                    continue  # statically proven all-false: never decode
                if stall_s > 0.0:
                    time.sleep(stall_s)
                group = pf.read_row_group(g, columns=self.columns)
                if group.num_rows < tiny:
                    pending.append(group)
                    pending_rows += group.num_rows
                    if pending_rows < size:
                        continue
                    group = flush()
                    pending_rows = 0
                elif pending:
                    head = flush()
                    pending_rows = 0
                    for start in range(0, head.num_rows, size):
                        yield _decode_table(head.slice(start, size), fastpath, wire)
                for start in range(0, group.num_rows, size):
                    yield _decode_table(group.slice(start, size), fastpath, wire)
                del group
            tail = flush()
            if tail is not None:
                for start in range(0, tail.num_rows, size):
                    yield _decode_table(tail.slice(start, size), fastpath, wire)

    def _plan_decode_units(self, size: int) -> List[Tuple[int, ...]]:
        """Replay the serial loop's coalescing decisions from metadata
        alone: every branch there depends only on each group's row count,
        so the unit list — each unit a tuple of row-group indices whose
        concat is sliced into batches — reproduces the serial batch
        sequence EXACTLY. This is what keeps the parallel decode
        bit-identical at any worker count."""
        import pyarrow.parquet as pq

        pf = pq.ParquetFile(self.path)
        try:
            meta = pf.metadata
            rows = [
                meta.row_group(g).num_rows for g in range(meta.num_row_groups)
            ]
        finally:
            pf.close()
        tiny = max(1, size // 4)
        units: List[Tuple[int, ...]] = []
        pending: List[int] = []
        pending_rows = 0
        skip = self.prune_groups
        for g, num in enumerate(rows):
            if skip is not None and g in skip:
                continue
            if num < tiny:
                pending.append(g)
                pending_rows += num
                if pending_rows < size:
                    continue
                units.append(tuple(pending))
                pending = []
                pending_rows = 0
            else:
                if pending:
                    units.append(tuple(pending))
                    pending = []
                    pending_rows = 0
                units.append((g,))
        if pending:
            units.append(tuple(pending))
        return units

    def _iter_tables_parallel(
        self, batch_size: int, workers: int
    ) -> Iterator[Table]:
        """Row-group decode fanned across `workers` threads with an
        ordered merge: units (see _plan_decode_units) are submitted in
        serial order and results yielded in submission order, so the
        batch sequence is bit-identical to the serial loop. pyarrow's
        parquet decode and the native kernels release the GIL, so the
        units genuinely overlap. Each worker thread opens its OWN
        ParquetFile (the handle is not thread-safe); in-flight units are
        bounded at workers + 1, so host memory stays
        O(workers × row group)."""
        import collections
        from concurrent.futures import ThreadPoolExecutor

        import pyarrow as pa
        import pyarrow.parquet as pq

        from deequ_tpu.ops import runtime
        from deequ_tpu.testing import faults

        fastpath = self._decode_fastpath_set()
        wire = self._wire_fusion_active()
        size = min(batch_size, self.batch_rows)
        units = self._plan_decode_units(size)
        if not units:
            return
        stall_s = runtime.source_stall_s()
        str_cols = [
            n for n, t in self._schema_cache if t == ColumnType.STRING
        ]
        tracer = _spans.current_tracer()
        parent = _spans.current_span()
        local = threading.local()
        open_files: List = []
        files_lock = threading.Lock()

        def _pf():
            pf = getattr(local, "pf", None)
            if pf is None:
                pf = pq.ParquetFile(
                    self.path, read_dictionary=str_cols or None
                )
                local.pf = pf
                with files_lock:
                    open_files.append(pf)
            return pf

        def decode_unit(unit: Tuple[int, ...]) -> List[Table]:
            faults.fault_point("decode.worker")
            pf = _pf()
            with _spans.attached(tracer, parent):
                with _spans.span(
                    "decode_unit", cat="decode", groups=len(unit)
                ) as sp:
                    parts = []
                    for g in unit:
                        if stall_s > 0.0:
                            time.sleep(stall_s)
                        parts.append(
                            pf.read_row_group(g, columns=self.columns)
                        )
                    merged = (
                        parts[0] if len(parts) == 1 else pa.concat_tables(parts)
                    )
                    del parts
                    tables = [
                        _decode_table(merged.slice(start, size), fastpath, wire)
                        for start in range(0, merged.num_rows, size)
                    ]
                    if sp:
                        sp.set(rows=int(merged.num_rows))
                    return tables

        pool = ThreadPoolExecutor(
            max_workers=workers, thread_name_prefix="deequ-decode-worker"
        )
        pending = collections.deque()
        next_unit = 0
        try:
            while next_unit < len(units) or pending:
                while next_unit < len(units) and len(pending) < workers + 1:
                    pending.append(
                        (
                            units[next_unit],
                            pool.submit(decode_unit, units[next_unit]),
                        )
                    )
                    next_unit += 1
                unit, fut = pending.popleft()
                try:
                    tables = fut.result()
                except Exception:  # noqa: BLE001 - contained: one inline redo
                    # a decode worker died mid-unit: re-decode inline on
                    # the consumer thread (bit-identical — units are
                    # pure functions of the file). A second failure is
                    # persistent and propagates.
                    runtime.record_fault(injected=1)
                    tables = decode_unit(unit)
                    runtime.record_retry(1, 1, 0)
                for table in tables:
                    yield table
        finally:
            for _, fut in pending:
                fut.cancel()
            pool.shutdown(wait=True)
            with files_lock:
                _close_all_fallback(open_files)

    def __repr__(self) -> str:
        return f"ParquetSource({self.path!r}, rows={self._num_rows})"


class MappedSource(DataSource):
    """Lazy per-batch transform over another source — e.g. the profiler's
    pass-2 cast of inferred-numeric string columns
    (reference: profiles/ColumnProfiler.scala:329-339,399-417)."""

    def __init__(
        self,
        base,
        fn: Callable[[Table], Table],
        schema_overrides: Optional[List[Tuple[str, ColumnType]]] = None,
        fn_columns: Optional[Sequence[str]] = None,
    ):
        self.base = base
        self.fn = fn
        # fn's read set. Column pruning can only be forwarded past fn when
        # the caller declares which columns fn consumes — an undeclared fn
        # may derive one column from another, and a pruned batch would
        # silently starve it (or raise mid-scan).
        self.fn_columns = None if fn_columns is None else tuple(fn_columns)
        self._overrides = list(schema_overrides or [])
        overrides = dict(self._overrides)
        self._schema_cache = [
            (name, overrides.get(name, ctype)) for name, ctype in base.schema
        ]
        self.batch_rows = getattr(base, "batch_rows", DataSource.batch_rows)

    def with_columns(self, names) -> "MappedSource":
        base_wc = getattr(self.base, "with_columns", None)
        if base_wc is None:
            return self
        if self.fn_columns is None:
            # fn's read set is unknown: pruning the base could starve it
            return self
        # the pruned source's schema is names ∪ fn_columns (fn's inputs
        # stay decoded and visible — a superset of the request, like an
        # unprunable source would be); overrides are kept for EVERY
        # surviving column so the schema matches what fn actually emits
        base_needs = sorted(set(names) | set(self.fn_columns))
        return MappedSource(
            base_wc(base_needs),
            self.fn,
            [(n, t) for n, t in self._overrides if n in set(base_needs)],
            fn_columns=self.fn_columns,
        )

    def _schema(self) -> List[Tuple[str, ColumnType]]:
        return self._schema_cache

    @property
    def num_rows(self) -> int:
        return self.base.num_rows

    def batches(self, batch_size: int) -> Iterator[Table]:
        # the base source already prefetches; apply fn inline
        produced_any = False
        for batch in self.base.batches(batch_size):
            produced_any = True
            yield self.fn(batch)
        if not produced_any:
            yield Table([_empty_column(n, t) for n, t in self._schema()])

# -- partitioned datasets (incremental scans) ---------------------------------

# footer fingerprints memoized by (device, inode, size, mtime_ns): any
# rewrite of the file changes size or mtime (and usually inode), so a
# stat hit can only ever return the digest of the bytes currently on
# disk. Bounded FIFO so a long-lived service scanning many datasets
# can't grow it without limit.
_FP_CACHE: "OrderedDict[str, Tuple[Tuple[int, int, int, int], str]]" = (
    OrderedDict()
)
_FP_CACHE_LOCK = threading.Lock()
_FP_CACHE_MAX = 8192


def partition_fingerprint(path: str) -> str:
    """Content fingerprint of one parquet partition file: sha256 over
    the file's NAME within the dataset, its byte size, and the parquet
    footer's row-group metadata (per-group row counts and byte sizes,
    per-chunk column paths, compressed sizes and min/max/null-count
    statistics). Any rewrite of the file — appended rows, mutated
    values, recompression — changes the footer and therefore the
    fingerprint, so a cached state for the old content can never be
    reused (the state-cache invalidation contract,
    repository/states.py). The directory part of the path is
    deliberately excluded: relocating a dataset wholesale keeps its
    cache warm, since entries are already namespaced by dataset.

    Fingerprints are memoized per stat signature: a preempted run that
    resumes over an N-partition dataset re-fingerprints nothing that
    hasn't changed on disk, so time-to-first-resume-boundary stays flat
    in N instead of costing one footer read per partition per attempt."""
    import pyarrow.parquet as pq

    fstat = os.stat(path)
    stat_sig = (fstat.st_dev, fstat.st_ino, fstat.st_size, fstat.st_mtime_ns)
    with _FP_CACHE_LOCK:
        hit = _FP_CACHE.get(path)
        if hit is not None and hit[0] == stat_sig:
            _FP_CACHE.move_to_end(path)
            return hit[1]

    h = hashlib.sha256()
    h.update(os.path.basename(path).encode("utf-8") + b"\x00")
    h.update(struct.pack(">q", fstat.st_size))
    pf = pq.ParquetFile(path)
    try:
        meta = pf.metadata
        h.update(struct.pack(">qq", meta.num_rows, meta.num_row_groups))
        for g in range(meta.num_row_groups):
            rg = meta.row_group(g)
            h.update(struct.pack(">qq", rg.num_rows, rg.total_byte_size))
            for j in range(rg.num_columns):
                chunk = rg.column(j)
                h.update(chunk.path_in_schema.encode("utf-8") + b"\x00")
                h.update(struct.pack(">q", chunk.total_compressed_size))
                st = chunk.statistics
                if st is not None and bool(getattr(st, "has_min_max", False)):
                    h.update(repr(st.min).encode("utf-8") + b"\x00")
                    h.update(repr(st.max).encode("utf-8") + b"\x00")
                if st is not None and bool(getattr(st, "has_null_count", False)):
                    h.update(struct.pack(">q", int(st.null_count)))
    finally:
        pf.close()
    digest = h.hexdigest()
    with _FP_CACHE_LOCK:
        _FP_CACHE[path] = (stat_sig, digest)
        _FP_CACHE.move_to_end(path)
        while len(_FP_CACHE) > _FP_CACHE_MAX:
            _FP_CACHE.popitem(last=False)
    return digest


class Partition:
    """One partition of a `PartitionedParquetSource`: a parquet file,
    its dataset-stable name, and its content fingerprint (computed
    lazily — a fingerprint reads footer metadata, never a row)."""

    def __init__(self, path: str, columns: Optional[List[str]], batch_rows: int):
        self.path = path
        self.name = os.path.basename(path)
        self._columns = columns
        self._batch_rows = batch_rows
        self._fingerprint: Optional[str] = None

    @property
    def fingerprint(self) -> str:
        if self._fingerprint is None:
            self._fingerprint = partition_fingerprint(self.path)
        return self._fingerprint

    def source(self) -> ParquetSource:
        """A fresh single-file source for scanning just this partition —
        it rides the full existing scan stack (pushdown, decode fast
        path, wire fusion) unchanged."""
        return ParquetSource(
            self.path, columns=self._columns, batch_rows=self._batch_rows
        )

    def __repr__(self) -> str:
        return f"Partition({self.name!r})"


class PartitionedParquetSource(DataSource):
    """A dataset of parquet files scanned one partition at a time, in
    deterministic name order. The fused pass folds EACH partition to
    analyzer states and merges them through the `State.merge` semigroup
    in that same order whether or not a state cache is attached — which
    is what makes cached and uncached runs trivially bit-identical
    (float addition is non-associative, so the merge ORDER is the
    contract, not an implementation detail). With a `StateRepository`
    attached, partitions whose fingerprint + plan signature already
    have a stored envelope load as states instead of scanning."""

    def __init__(
        self,
        paths,
        columns: Optional[List[str]] = None,
        batch_rows: int = 1 << 22,
    ):
        if isinstance(paths, str):
            if os.path.isdir(paths):
                resolved = [
                    os.path.join(paths, n)
                    for n in os.listdir(paths)
                    if n.endswith(".parquet") and not n.startswith(".")
                ]
            else:
                resolved = [paths]
        else:
            resolved = [str(p) for p in paths]
        if not resolved:
            raise ValueError(
                "PartitionedParquetSource needs at least one parquet file"
            )
        # name order, not listing order: the merge order (and therefore
        # the exact float result) must not depend on directory traversal
        self.paths = sorted(resolved, key=os.path.basename)
        self.columns = columns
        self.batch_rows = batch_rows
        first = ParquetSource(
            self.paths[0], columns=columns, batch_rows=batch_rows
        )
        self._schema_cache = first.schema
        import pyarrow.parquet as pq

        total = 0
        for p in self.paths:
            pf = pq.ParquetFile(p)
            try:
                total += pf.metadata.num_rows
            finally:
                pf.close()
        self._num_rows = total

    def _schema(self) -> List[Tuple[str, ColumnType]]:
        return self._schema_cache

    @property
    def num_rows(self) -> int:
        return self._num_rows

    def partitions(self) -> List[Partition]:
        """The per-file partitions in deterministic (name) order — the
        duck-typed hook `FusedScanPass.run` splits on."""
        return [
            Partition(p, self.columns, self.batch_rows) for p in self.paths
        ]

    def with_columns(self, names) -> "PartitionedParquetSource":
        keep = [n for n, _ in self._schema_cache if n in set(names)]
        if keep == [n for n, _ in self._schema_cache] or not keep:
            return self
        return PartitionedParquetSource(
            self.paths, columns=keep, batch_rows=self.batch_rows
        )

    def subset(self, paths) -> "PartitionedParquetSource":
        """Shard-filtered view of the dataset: the same source restricted
        to `paths` (a shard's slice from `parallel/shard.py`), preserving
        column projection, batch sizing and — critically — the global
        name order, so a per-shard fold merges its partitions in exactly
        the order the solo fold visits them. Unknown paths are a plan
        bug, not data: raise instead of silently scanning less."""
        keep = set(str(p) for p in paths)
        unknown = keep - set(self.paths)
        if unknown:
            raise ValueError(
                f"subset paths not in this dataset: {sorted(unknown)}"
            )
        picked = [p for p in self.paths if p in keep]
        if not picked:
            raise ValueError("subset would leave no partitions")
        return PartitionedParquetSource(
            picked, columns=self.columns, batch_rows=self.batch_rows
        )

    def decode_column_types(self):
        """Decode vocabulary of the dataset (all partitions share one
        schema): delegate to the first partition."""
        return ParquetSource(
            self.paths[0], columns=self.columns, batch_rows=self.batch_rows
        ).decode_column_types()

    def _iter_tables(self, batch_size: int) -> Iterator[Table]:
        # whole-dataset stream for consumers outside the partitioned
        # fold (grouping passes, profiler): partitions chain in the same
        # deterministic order the per-partition merge uses
        for part in self.partitions():
            yield from part.source()._iter_tables(batch_size)

    def __repr__(self) -> str:
        return (
            f"PartitionedParquetSource({len(self.paths)} files, "
            f"rows={self._num_rows})"
        )

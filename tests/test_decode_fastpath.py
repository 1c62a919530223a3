"""Decode fast path (ISSUE 8): buffer-level native decode + parallel
row-group decode workers.

Three layers are pinned here:
  - bit-identity of `Table.from_arrow(..., fastpath_columns=...)`
    against the host chain on every Arrow edge case the kernels must
    honor — sliced arrays with nonzero offsets, multi-chunk columns,
    all-null groups, validity-bitmap tail bits, NaN folds, integer
    widening, bool bitmaps, dictionary codes (including dictionaries
    crossing row groups);
  - the planner: decode_column_types tokens, classify_decode_columns
    eligibility/reasons, the decode-unit replay of the serial
    coalescer, and the runtime/prediction zero-drift pin;
  - observability: decode counters, the telemetry derivations, and
    the sentinel's watch list.

The end-to-end fastpath/workers differential fuzz lives in
tests/test_suite_differential_fuzz.py.
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from deequ_tpu.data.source import ParquetSource
from deequ_tpu.data.table import Table
from deequ_tpu.ops import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="no C compiler for the native kernels"
)


def _materialize(col):
    return np.asarray(col.values)


def assert_tables_bit_identical(fast: Table, slow: Table, context=""):
    assert fast.column_names == slow.column_names
    for name in fast.column_names:
        cf, cs = fast.column(name), slow.column(name)
        assert cf.ctype == cs.ctype, (context, name)
        vf, vs = _materialize(cf), _materialize(cs)
        assert vf.dtype == vs.dtype, (context, name, vf.dtype, vs.dtype)
        assert np.array_equal(vf, vs), (context, name)
        assert np.array_equal(np.asarray(cf.valid), np.asarray(cs.valid)), (
            context,
            name,
        )
        if "dict_encode" in cs._cache:
            codes_f, uniq_f = cf._cache["dict_encode"]
            codes_s, uniq_s = cs._cache["dict_encode"]
            assert codes_f.dtype == codes_s.dtype
            assert np.array_equal(codes_f, codes_s), (context, name)
            assert list(uniq_f) == list(uniq_s), (context, name)
            assert cf._dict_content_key == cs._dict_content_key


def both_paths(arrow_table, columns):
    fast = Table.from_arrow(arrow_table, fastpath_columns=set(columns))
    slow = Table.from_arrow(arrow_table)
    return fast, slow


class TestFromArrowBitIdentity:
    def test_sliced_float_with_nulls_and_nan(self):
        arr = pa.array(
            [1.5, None, float("nan"), 4.0, 5.5, None, 7.0], type=pa.float64()
        )
        t = pa.table({"x": arr.slice(1, 5)})
        fast, slow = both_paths(t, ["x"])
        assert_tables_bit_identical(fast, slow, "sliced f64")
        # null AND NaN slots both fold to invalid + 0.0
        assert _materialize(fast.column("x"))[0] == 0.0
        assert not fast.column("x").valid[0]

    def test_float32_widens_to_float64(self):
        arr = pa.array([1.25, None, float("nan"), 9.0], type=pa.float32())
        t = pa.table({"g": arr})
        fast, slow = both_paths(t, ["g"])
        assert_tables_bit_identical(fast, slow, "f32")
        assert _materialize(fast.column("g")).dtype == np.float64

    @pytest.mark.parametrize(
        "dtype",
        [pa.int8(), pa.int16(), pa.int32(), pa.int64(),
         pa.uint8(), pa.uint16(), pa.uint32(), pa.uint64()],
    )
    def test_integer_widths_widen_with_nulls(self, dtype):
        vals = [1, None, 3, None, 5, 100]
        t = pa.table({"i": pa.array(vals, type=dtype)})
        fast, slow = both_paths(t, ["i"])
        assert_tables_bit_identical(fast, slow, str(dtype))

    def test_uint64_wraps_like_numpy_astype(self):
        big = (1 << 63) + 7  # > INT64_MAX: must wrap, not raise
        t = pa.table({"u": pa.array([big, 1, None], type=pa.uint64())})
        fast, slow = both_paths(t, ["u"])
        assert_tables_bit_identical(fast, slow, "uint64 wrap")

    def test_bool_bitmap_with_nonzero_offset(self):
        arr = pa.array([True, None, False, True, None, True, False, True, True])
        t = pa.table({"b": arr.slice(3, 5)})
        fast, slow = both_paths(t, ["b"])
        assert_tables_bit_identical(fast, slow, "sliced bool")

    def test_validity_bitmap_tail_bits(self):
        # n not a multiple of 8: bits past the last row exist in the
        # bitmap byte but must never be read
        for n in (1, 3, 7, 9, 15, 17):
            vals = [None if i % 3 == 0 else float(i) for i in range(n)]
            t = pa.table({"x": pa.array(vals, type=pa.float64())})
            fast, slow = both_paths(t, ["x"])
            assert_tables_bit_identical(fast, slow, f"tail n={n}")

    def test_all_null_column(self):
        t = pa.table({"u": pa.array([None] * 11, type=pa.int32())})
        fast, slow = both_paths(t, ["u"])
        assert_tables_bit_identical(fast, slow, "all-null")
        assert not fast.column("u").valid.any()

    def test_multi_chunk_primitive(self):
        chunked = pa.chunked_array(
            [
                pa.array([1.0, None], type=pa.float64()),
                pa.array([float("nan"), 4.0, 5.0], type=pa.float64()),
                pa.array([], type=pa.float64()),
                pa.array([None, 7.0], type=pa.float64()),
            ]
        )
        t = pa.table({"x": chunked})
        fast, slow = both_paths(t, ["x"])
        assert_tables_bit_identical(fast, slow, "multi-chunk")

    def test_dictionary_column_single_chunk(self):
        arr = pa.array(["a", "b", None, "a", "c", None]).dictionary_encode()
        t = pa.table({"s": arr})
        fast, slow = both_paths(t, ["s"])
        assert_tables_bit_identical(fast, slow, "dict")
        codes, _ = fast.column("s")._cache["dict_encode"]
        assert codes.dtype == np.int32
        assert codes[2] == -1  # null sentinel

    def test_multi_chunk_dictionary_falls_back_identically(self):
        # dictionary unification is the fallback's job; the fast path
        # must route multi-chunk dict columns back without divergence
        chunked = pa.chunked_array(
            [
                pa.array(["a", "b", "a"]).dictionary_encode(),
                pa.array(["c", "b", None]).dictionary_encode(),
            ]
        )
        t = pa.table({"s": chunked})
        fast, slow = both_paths(t, ["s"])
        assert_tables_bit_identical(fast, slow, "multi-chunk dict")

    def test_fastpath_off_by_default_for_unlisted_columns(self):
        t = pa.table({"x": pa.array([1.0, 2.0]), "y": pa.array([3.0, 4.0])})
        fast, slow = both_paths(t, ["x"])  # y not approved
        assert_tables_bit_identical(fast, slow, "partial set")


class TestSourceDecode:
    def _write(self, tmp_path, n=3000, row_group_size=256):
        rng = np.random.default_rng(5)
        t = pa.table(
            {
                "x": pa.array(np.where(rng.random(n) < 0.1, np.nan, rng.random(n))),
                "i": pa.array(rng.integers(0, 50, n), type=pa.int16()),
                "s": pa.array(rng.choice(["a", "b", "c", None], n).tolist()),
                "b": pa.array((rng.random(n) < 0.5).tolist()),
            }
        )
        path = str(tmp_path / "d.parquet")
        pq.write_table(t, path, row_group_size=row_group_size)
        return path

    def test_decode_column_types_tokens(self, tmp_path):
        path = self._write(tmp_path)
        tokens = ParquetSource(path).decode_column_types()
        assert tokens == {
            "x": "double",
            "i": "int16",
            # strings arrive dictionary-encoded via read_dictionary
            "s": "dictionary<string,int32>",
            "b": "bool",
        }

    def test_dictionary_crossing_row_groups(self, tmp_path, monkeypatch):
        # each row group carries its own dictionary; codes must stay
        # per-batch consistent on both routes, at any worker count
        path = self._write(tmp_path, n=2000, row_group_size=100)

        def strings(env_workers, fastpath):
            monkeypatch.setenv("DEEQU_TPU_DECODE_WORKERS", env_workers)
            src = ParquetSource(path, batch_rows=512)
            if fastpath:
                src = src.with_decode_fastpath(["s", "x", "i", "b"])
            out = []
            for batch in src.batches(512):
                col = batch.column("s")
                vals = _materialize(col)
                valid = np.asarray(col.valid)
                out.extend(
                    v if ok else None for v, ok in zip(vals.tolist(), valid)
                )
            return out

        base = strings("1", False)
        assert strings("1", True) == base
        assert strings("3", True) == base
        assert strings("3", False) == base

    def test_decode_units_replay_serial_coalescing(self, tmp_path):
        # mixed tiny/large groups: write two files and concat-read one
        # with groups of very different sizes via multiple writes
        rng = np.random.default_rng(9)
        parts = [17, 13, 900, 11, 7, 600, 23]  # tiny runs around big groups
        tables = [
            pa.table({"v": pa.array(rng.random(k))}) for k in parts
        ]
        path = str(tmp_path / "mixed.parquet")
        with pq.ParquetWriter(path, tables[0].schema) as w:
            for t in tables:
                w.write_table(t, row_group_size=max(parts))
        src = ParquetSource(path, batch_rows=512)
        units = src._plan_decode_units(512)
        # units must cover every group exactly once, in order
        flat = [g for unit in units for g in unit]
        assert flat == list(range(len(parts)))
        # the serial iterator and the parallel one agree batch-for-batch
        serial = [b.num_rows for b in src._iter_tables_serial(512)]
        parallel = [b.num_rows for b in src._iter_tables_parallel(512, 3)]
        assert serial == parallel

    def test_workers_env_knob(self, monkeypatch):
        from deequ_tpu.ops import runtime

        monkeypatch.setenv("DEEQU_TPU_DECODE_WORKERS", "3")
        assert runtime.decode_workers() == 3
        monkeypatch.setenv("DEEQU_TPU_DECODE_WORKERS", "not-a-number")
        assert runtime.decode_workers() >= 1  # falls to the default
        monkeypatch.delenv("DEEQU_TPU_DECODE_WORKERS")
        import os

        assert runtime.decode_workers() == min(os.cpu_count() or 1, 4)

    def test_fastpath_env_knob(self, monkeypatch):
        from deequ_tpu.ops import runtime

        monkeypatch.delenv("DEEQU_TPU_DECODE_FASTPATH", raising=False)
        assert runtime.decode_fastpath_enabled()
        monkeypatch.setenv("DEEQU_TPU_DECODE_FASTPATH", "0")
        assert not runtime.decode_fastpath_enabled()


class TestPlannerAndDrift:
    def test_classifier_eligibility_and_reasons(self):
        from deequ_tpu.analyzers.base import InputSpec
        from deequ_tpu.ops.fused import classify_decode_columns

        col_types = {
            "f": "double",
            "i": "int32",
            "b": "bool",
            "d": "dictionary<string,int32>",
            "p": "string",
            "ts": "timestamp[us]",
            "dec": "decimal128(10, 2)",
        }
        specs = {
            "num:f": InputSpec(key="num:f", build=None, columns=("f",)),
            "valid:d": InputSpec(key="valid:d", build=None, columns=("d",)),
        }
        fast, fallbacks = classify_decode_columns(col_types, specs)
        assert set(fast) == {"f", "i", "b", "d"}
        reasons = dict(fallbacks)
        assert "host objects" in reasons["p"]
        assert "timestamp" in reasons["ts"]
        assert "decimal" in reasons["dec"]

    def test_classifier_conservative_on_unknown_prefix(self):
        from deequ_tpu.analyzers.base import InputSpec
        from deequ_tpu.ops.fused import classify_decode_columns

        specs = {
            "rawstr:d": InputSpec(key="rawstr:d", build=None, columns=("d",)),
        }
        fast, fallbacks = classify_decode_columns(
            {"d": "dictionary<string,int32>"}, specs
        )
        assert fast == []
        assert fallbacks and "rawstr" in fallbacks[0][1]

    def test_prediction_pins_to_trace_with_zero_drift(self, tmp_path, monkeypatch):
        from deequ_tpu.analyzers import Completeness, Mean
        from deequ_tpu.lint.cost import cost_drift
        from deequ_tpu.lint.explain import explain_plan
        from deequ_tpu.observe.runtrace import traced_run
        from deequ_tpu.runners import AnalysisRunner

        monkeypatch.setenv("DEEQU_TPU_PLACEMENT", "host")
        n = 4000
        t = pa.table(
            {
                "i": pa.array(np.arange(n), type=pa.int64()),
                "ts": pa.array([np.datetime64("2024-01-01", "us")] * n),
            }
        )
        path = str(tmp_path / "p.parquet")
        pq.write_table(t, path, row_group_size=1024)
        analyzers = [Mean("i"), Completeness("ts")]
        res = explain_plan(ParquetSource(path, batch_rows=2048), analyzers)
        scan = res.cost.scan_pass
        assert scan.decode_cols_total == 2
        assert scan.decode_cols_fast == 1
        assert dict(scan.decode_fallbacks).keys() == {"ts"}
        assert scan.saved_decode_bytes and scan.saved_decode_bytes > 0
        assert any(d.code == "DQ312" for d in res.diagnostics)

        with traced_run("t", enable=True) as handle:
            AnalysisRunner().on_data(
                ParquetSource(path, batch_rows=2048)
            ).add_analyzers(analyzers).run()
        drift = cost_drift(res.cost, handle.trace)
        assert drift["drift.decode_cols_fast"] == 0.0
        assert handle.trace.counters["decode_cols_fast"] == 1
        assert handle.trace.counters["decode_cols_total"] == 2

    def test_knob_off_disables_plan_and_prediction(self, tmp_path, monkeypatch):
        from deequ_tpu.analyzers import Mean
        from deequ_tpu.lint.explain import explain_plan
        from deequ_tpu.observe.runtrace import traced_run
        from deequ_tpu.runners import AnalysisRunner

        monkeypatch.setenv("DEEQU_TPU_DECODE_FASTPATH", "0")
        t = pa.table({"i": pa.array(np.arange(100), type=pa.int64())})
        path = str(tmp_path / "off.parquet")
        pq.write_table(t, path)
        analyzers = [Mean("i")]
        res = explain_plan(ParquetSource(path), analyzers)
        assert res.cost.scan_pass.decode_cols_total is None
        with traced_run("t", enable=True) as handle:
            AnalysisRunner().on_data(ParquetSource(path)).add_analyzers(
                analyzers
            ).run()
        assert "decode_cols_total" not in handle.trace.counters


class TestObservability:
    def test_telemetry_derivations_and_sentinel_watch(self, tmp_path, monkeypatch):
        from deequ_tpu.analyzers import Completeness, Mean
        from deequ_tpu.observe.runtrace import traced_run
        from deequ_tpu.observe.telemetry import engine_metric_record
        from deequ_tpu.runners import AnalysisRunner

        monkeypatch.setenv("DEEQU_TPU_PLACEMENT", "host")
        t = pa.table(
            {
                "i": pa.array(np.arange(500), type=pa.int64()),
                "ts": pa.array([np.datetime64("2024-01-01", "us")] * 500),
            }
        )
        path = str(tmp_path / "m.parquet")
        pq.write_table(t, path)
        with traced_run("t", enable=True) as handle:
            AnalysisRunner().on_data(ParquetSource(path)).add_analyzers(
                [Mean("i"), Completeness("ts")]
            ).run()
        rec = engine_metric_record(handle.trace)
        assert rec["engine.decode_fastpath_ratio"] == 0.5
        from deequ_tpu.ops import runtime

        assert rec["engine.decode_workers"] == float(runtime.decode_workers())

        import importlib.util
        import os

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "sentinel", os.path.join(repo, "tools", "sentinel.py")
        )
        sentinel = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sentinel)
        watched = dict(sentinel.WATCHED_SERIES)
        assert watched.get("engine.decode_fastpath_ratio") == "down"
        assert watched.get("engine.decode_workers") == "down"

    def test_decode_fastpath_span_attrs(self, tmp_path, monkeypatch):
        from deequ_tpu import observe
        from deequ_tpu.analyzers import Mean
        from deequ_tpu.runners import AnalysisRunner

        monkeypatch.setenv("DEEQU_TPU_PLACEMENT", "host")
        t = pa.table({"i": pa.array(np.arange(300), type=pa.int64())})
        path = str(tmp_path / "sp.parquet")
        pq.write_table(t, path)
        with observe.tracing() as tracer:
            AnalysisRunner().on_data(ParquetSource(path)).add_analyzers(
                [Mean("i")]
            ).run()

        def spans(root):
            stack = [root]
            while stack:
                sp = stack.pop()
                yield sp
                stack.extend(sp.children)

        plan_spans = [
            sp
            for root in tracer.roots
            for sp in spans(root)
            if sp.name == "decode_fastpath"
        ]
        assert plan_spans
        attrs = plan_spans[0].attrs
        assert attrs["cols_total"] == 1
        assert attrs["cols_fast"] == 1
        assert attrs["cols_fallback"] == 0
        assert attrs["workers"] >= 1

    def test_distributed_scan_uses_fastpath(self, tmp_path, monkeypatch):
        """DistributedScanPass plans decode routing like FusedScanPass:
        the mesh shards packed wire arrays, so the fast path must engage
        (and stay bit-identical) on the multi-device route too."""
        from deequ_tpu import observe
        from deequ_tpu.analyzers import Completeness, Mean
        from deequ_tpu.parallel import DistributedScanPass, data_mesh

        t = pa.table(
            {
                "x": pa.array(
                    [float(i) / 3 if i % 5 else None for i in range(4096)]
                ),
                "b": pa.array([bool(i % 2) for i in range(4096)]),
            }
        )
        path = str(tmp_path / "d.parquet")
        pq.write_table(t, path)
        analyzers = [Mean("x"), Completeness("b")]

        def run():
            with observe.tracing() as tracer:
                res = DistributedScanPass(analyzers, mesh=data_mesh()).run(
                    ParquetSource(path)
                )
            snap = [
                (
                    repr(r.analyzer),
                    r.analyzer.compute_metric_from(r.state_or_raise()).value.get(),
                )
                for r in res
            ]
            return snap, tracer

        on, tracer = run()
        monkeypatch.setenv("DEEQU_TPU_DECODE_FASTPATH", "0")
        off, _ = run()
        assert on == off

        def spans(root):
            stack = [root]
            while stack:
                sp = stack.pop()
                yield sp
                stack.extend(sp.children)

        plan_spans = [
            sp
            for root in tracer.roots
            for sp in spans(root)
            if sp.name == "decode_fastpath"
        ]
        assert plan_spans
        assert plan_spans[0].attrs["cols_fast"] == 2

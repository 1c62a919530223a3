"""Sketch-based analyzers: bounded-memory approximations.

ApproxCountDistinct: host hashes values (vectorized xxhash64), the device
scatter-maxes HLL registers inside the fused pass, merges are register-wise
max (reference: analyzers/ApproxCountDistinct.scala:47 + catalyst kernel).

ApproxQuantile(s): per-batch KLL partial sketches folded on the host —
the host-reduce stage of the fused pass (same single logical scan;
reference: analyzers/ApproxQuantile.scala:49, ApproxQuantiles.scala:39).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from deequ_tpu.analyzers.base import (
    InputSpec,
    Preconditions,
    ScanShareableAnalyzer,
    col_valid_spec,
    col_values_spec,
    render_where,
    where_key,
    where_spec,
)
from deequ_tpu.analyzers.states import DoubleValuedState, State
from deequ_tpu.core.exceptions import IllegalAnalyzerParameterException
from deequ_tpu.core.maybe import Success
from deequ_tpu.core.metrics import DoubleMetric, KeyedDoubleMetric, Metric
from deequ_tpu.data.table import Table
from deequ_tpu.ops.sketches import hll
from deequ_tpu.ops.sketches.kll import KLLSketch, k_for_error


# ---------------------------------------------------------------------------
# ApproxCountDistinct
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ApproxCountDistinctState(DoubleValuedState):
    """HLL registers (reference: ApproxCountDistinct.scala:26 — merge is
    register-wise max)."""

    registers: np.ndarray

    def merge(self, other: "ApproxCountDistinctState") -> "ApproxCountDistinctState":
        return ApproxCountDistinctState(hll.merge_registers(self.registers, other.registers))

    def metric_value(self) -> float:
        return hll.estimate(self.registers)

    def words(self) -> np.ndarray:
        return hll.pack_words(self.registers)

    def __eq__(self, other) -> bool:
        return isinstance(other, ApproxCountDistinctState) and np.array_equal(
            self.registers, other.registers
        )

    def __hash__(self) -> int:
        return hash(self.registers.tobytes())


def _hist16_available(n: int) -> bool:
    """Pallas hist16 usable for this batch shape (TPU platform + block
    multiple); interpret-mode tests monkeypatch this.

    The n <= 2^24 cap keeps the kernel exact: hist16 accumulates bin
    counts in float32 (MXU tiles), which counts exactly only up to
    2^24 per bin. A low-cardinality column in an oversized explicit
    FusedScanPass(batch_size=...) batch could push one bin past that
    and silently corrupt counts/ranks, so such batches fall back to
    the sort path instead."""
    from deequ_tpu.ops import pallas_kernels

    return (
        n <= (1 << 24)
        and pallas_kernels.shape_supported(n)
        and pallas_kernels.usable()
    )


_BOOL_HLL = None


def _bin16(keys32: np.ndarray) -> np.ndarray:
    """Top 16 bits of float32 values' order-preserving sortable keys: the
    host twin of `pallas_kernels.f32_sortable_bin16`."""
    u = keys32.view(np.int32)
    key = np.where(u < 0, ~u, u | np.int32(-(1 << 31)))
    return (key >> 16) & 0xFFFF


def _bool_hll_identities():
    """(idx, rank, packed) for the two canonical boolean identities
    (int64 0/1) — ONE definition shared by the per-row gather spec and
    the _LowCardCounts presence shortcut, computed once."""
    global _BOOL_HLL
    if _BOOL_HLL is None:
        from deequ_tpu.ops.sketches.hll import xxhash64_u64

        idx, rank = hll.registers_from_hashes(
            xxhash64_u64(np.array([0, 1], dtype=np.int64))
        )
        packed = ((idx << 6) | rank).astype(np.int32)
        _BOOL_HLL = (idx, rank, packed)
    return _BOOL_HLL


def _hll_spec(column: str) -> InputSpec:
    """One int32 per row packing (register idx << 6 | rank) so the column
    is hashed exactly once per batch; invalid rows pack to 0 (idx 0,
    rank 0 — a no-op for the scatter-max)."""

    def compute(col) -> np.ndarray:
        from deequ_tpu.data.table import ColumnType

        if col.ctype == ColumnType.STRING:
            # share the batch's dict-encode; hash unique strings only
            # (cross-batch dictionary memo); null rows map to packed
            # code 0 (idx 0, rank 0 — a no-op for the scatter-max)
            from deequ_tpu.data.table import (
                gather_with_null,
                hashed_dictionary,
            )

            codes, _uniques = col.dict_encode()
            idx_u, rank_u = hll.registers_from_hashes(hashed_dictionary(col))
            return gather_with_null(
                ((idx_u << 6) | rank_u).astype(np.int32), codes, 0
            )
        if col.ctype == ColumnType.BOOLEAN:
            # two possible identities (canonical int64 0/1): hash them
            # once and gather — no per-row hashing
            _idx, _rank, packed_u = _bool_hll_identities()
            return np.where(
                col.valid, packed_u[col.values.view(np.uint8)], np.int32(0)
            )
        # one-pass C kernel when available, identical numpy codes otherwise
        return hll.pack_codes(col.values, col.valid)

    def build(t: Table) -> np.ndarray:
        from deequ_tpu.data.table import cached_column_encode

        # column-deterministic: memoized per table, sliced per batch
        return cached_column_encode(t.column(column), "hll_packed", compute)

    return InputSpec(key=f"hll:{column}", build=build, columns=(column,))


@dataclass(frozen=True)
class ApproxCountDistinct(ScanShareableAnalyzer):
    """HLL++ distinct estimate (reference: analyzers/ApproxCountDistinct.scala:47)."""

    discrete_inputs = True  # packed idx|rank codes: host-foldable
    column: str
    where: Optional[str] = None

    @property
    def name(self) -> str:
        return "ApproxCountDistinct"

    @property
    def instance(self) -> str:
        return self.column

    def preconditions(self) -> List[Callable[[Table], None]]:
        return [Preconditions.has_column(self.column)]

    def input_specs(self) -> List[InputSpec]:
        return [_hll_spec(self.column), where_spec(self.where)]

    def device_reduce(self, inputs: Dict[str, Any], xp) -> Any:
        if xp is np:
            # fused-family kernel already produced this column's
            # registers this batch? (checked BEFORE touching the packed
            # hash input, which then never gets built under HostInputs)
            regs = inputs.get(f"__hllregs:{self.column}:{where_key(self.where)}")
            if regs is not None:
                return {"registers": np.asarray(regs)}
            if self.where is None:
                # a bool column counted this batch (_LowCardCounts):
                # registers from the ≤2 present canonical identities
                pres_bool = inputs.get(f"__lccbool:{self.column}")
                if pres_bool is not None:
                    idx, rank, _packed = _bool_hll_identities()
                    registers = np.zeros(hll.M, dtype=np.int32)
                    for value, present in enumerate(pres_bool):
                        if present:
                            registers[idx[value]] = max(
                                registers[idx[value]], int(rank[value])
                            )
                    return {"registers": registers}
                # a string column whose dictionary presence was counted
                # this batch (_LowCardCounts): hash only the PRESENT
                # uniques — identical registers, no full-row scatter
                pres = inputs.get(f"__lccpresence:{self.column}")
                if pres is not None:
                    from deequ_tpu.ops.strings import hash_strings

                    present, uniques = pres
                    present = np.asarray(present)
                    # hash the FULL dictionary through the cross-batch
                    # memo when reachable (stream batches rebuild equal
                    # dictionaries), then select the present entries
                    hashes = None
                    batch = getattr(inputs, "batch", None)
                    if batch is not None:
                        try:
                            from deequ_tpu.data.table import (
                                hashed_dictionary,
                            )

                            full = hashed_dictionary(
                                batch.column(self.column)
                            )
                            if len(full) == len(present):
                                hashes = full[present]
                        except Exception:  # noqa: BLE001 - direct hash
                            hashes = None
                    if hashes is None:
                        hashes = hash_strings(
                            np.asarray(uniques, dtype=object)[present]
                        )
                    idx, rank = hll.registers_from_hashes(hashes)
                    registers = np.zeros(hll.M, dtype=np.int32)
                    np.maximum.at(registers, idx, rank.astype(np.int32))
                    return {"registers": registers}
        packed = xp.asarray(inputs[f"hll:{self.column}"])
        w = inputs[where_key(self.where)]
        if xp is np:
            from deequ_tpu.ops import native

            registers = np.zeros(hll.M, dtype=np.int32)
            where = np.asarray(w)
            if native.hll_update_registers(
                np.asarray(packed), None if where.all() else where, registers
            ):
                return {"registers": registers}
            masked_rank = np.where(where, packed & 0x3F, 0)
            np.maximum.at(registers, np.asarray(packed >> 6), masked_rank)
            return {"registers": registers}
        from deequ_tpu.ops import pallas_kernels

        if pallas_kernels.shape_supported(
            int(packed.shape[0])
        ) and pallas_kernels.usable():
            # pallas path: XLA serializes the 512-register scatter-max on
            # TPU; the blockwise one-hot kernel keeps it on the VPU
            masked_codes = xp.where(xp.asarray(w), packed, 0)
            return {
                "registers": pallas_kernels.hll_register_max(masked_codes)
            }
        idx = packed >> 6
        rank = packed & 0x3F
        masked_rank = xp.where(xp.asarray(w), rank, 0)
        registers = xp.zeros(hll.M, dtype=masked_rank.dtype).at[idx].max(masked_rank)
        return {"registers": registers}

    def merge_agg(self, a: Any, b: Any, xp) -> Any:
        return {"registers": xp.maximum(a["registers"], b["registers"])}

    def state_from_aggregates(self, agg: Any) -> Optional[State]:
        return ApproxCountDistinctState(
            np.asarray(agg["registers"]).astype(np.int32)
        )

    def compute_metric_from(self, state: Optional[State]) -> Metric:
        if state is None:
            return self.empty_state_failure()
        return DoubleMetric(
            self.entity, self.name, self.instance, Success(state.metric_value())
        )

    def __repr__(self) -> str:
        return f"ApproxCountDistinct({self.column},{render_where(self.where)})"


# ---------------------------------------------------------------------------
# ApproxQuantile(s) — host-reduced members of the fused pass
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ApproxQuantileState(State):
    """Mergeable quantile digest (reference: ApproxQuantile.scala:28-35)."""

    digest: KLLSketch

    def merge(self, other: "ApproxQuantileState") -> "ApproxQuantileState":
        return ApproxQuantileState(self.digest.merge(other.digest))

    def __eq__(self, other) -> bool:
        if not isinstance(other, ApproxQuantileState):
            return False
        k1, n1, l1 = self.digest.to_arrays()
        k2, n2, l2 = other.digest.to_arrays()
        return (
            k1 == k2
            and n1 == n2
            and len(l1) == len(l2)
            and all(np.array_equal(a, b) for a, b in zip(l1, l2))
        )

    def __hash__(self) -> int:
        return hash((self.digest.k, self.digest.n))


def _quantile_param_check(quantile: float) -> Callable[[Table], None]:
    def check(table: Table) -> None:
        if not (0.0 <= quantile <= 1.0):
            raise IllegalAnalyzerParameterException(
                "Quantile parameter must be in the closed interval [0, 1]. "
                f"Currently, the value is: {quantile}!"
            )

    return check


def _relative_error_param_check(relative_error: float) -> Callable[[Table], None]:
    def check(table: Table) -> None:
        if not (0.0 <= relative_error <= 1.0):
            raise IllegalAnalyzerParameterException(
                "Relative error parameter must be in the closed interval [0, 1]. "
                f"Currently, the value is: {relative_error}!"
            )

    return check


import zlib


def _batch_seed(sample: np.ndarray, n: int, level: int) -> int:
    """Deterministic per-batch sketch seed: KLL's error bound wants
    compaction offsets that decorrelate across merged partials, and the
    engine's differential contracts (pipeline on/off, engine parity,
    repeated runs in one process) need bit-identical results. Hashing
    the batch's own decimated sample gives both — distinct batches get
    distinct offsets, while a scan's outcome depends only on its inputs
    and fold order, never on which scans ran earlier in the process
    (the old global counter made every run order-sensitive). Pure
    function of the arguments: safe from concurrent shard reducers."""
    h = zlib.crc32(np.ascontiguousarray(sample, dtype=np.float64).tobytes())
    return (h ^ (int(n) * 0x9E3779B1) ^ (int(level) << 17)) & 0x7FFFFFFF


class _QuantileAnalyzerBase(ScanShareableAnalyzer):
    """Device-assisted member of the fused scan: the DEVICE does the
    heavy per-batch work — sort the masked column and stride-decimate to
    a fixed-size sample at a power-of-two level — inside the same XLA
    program as every other analyzer (sharing the column transfer); the
    HOST only merges each shard's decimated sample into the KLL at that
    level (exactly the `_bulk_insert` law whose rank-error bound is
    tested). This lowers the sketch's compactor work to an XLA sort, the
    north-star requirement, and makes quantiles scale with mesh devices
    via shard_map like every device-reduced analyzer.

    Precision note: on a float32 device engine (TPU with x64 off) the
    column is sorted in float32, so quantile RESULTS are quantized to one
    float32 ulp of the value's magnitude (e.g. ~2.7e8 for
    microsecond-epoch timestamps ~1.7e15). The rank error bound is
    unaffected. The CPU/x64 engine sketches exact float64.
    (reference: catalyst/StatefulApproxQuantile.scala:28 — the mergeable
    digest role; the sort+decimate replaces its per-row GK updates.)"""

    device_assisted = True

    def _sample_size(self) -> int:
        # one level's worth: n/stride lands in (k, 2k]
        return 2 * k_for_error(self.relative_error)

    def input_specs(self) -> List[InputSpec]:
        return [
            col_values_spec(self.column),
            col_valid_spec(self.column),
            where_spec(getattr(self, "where", None)),
        ]

    def device_batch(self, inputs: Dict[str, Any], xp) -> Any:
        if xp is np:
            # fused family kernel already ran for this batch? (fold_host_batch
            # precomputes moments+sample in one C traversal)
            memo = inputs.get(
                f"__qsample:{self.column}:"
                f"{where_key(getattr(self, 'where', None))}:{self._sample_size()}"
            )
            if memo is not None:
                return memo
        x = xp.asarray(inputs[f"num:{self.column}"])
        if xp is np:
            valid = np.asarray(inputs[f"valid:{self.column}"])
            where = inputs.get(where_key(getattr(self, "where", None)))
            if getattr(self, "where", None) is None:
                where = None
            from deequ_tpu.ops import native

            # host fold fastest path: C histogram-assisted selection
            # extracts the decimated sample (identical values) without
            # sorting the whole batch — ~10x less work than sort
            res = native.masked_select_decimate(
                x, valid, where, self._sample_size()
            )
            if res is not None:
                sample, n_valid, level = res
                return {
                    "sample": sample,
                    "n": np.asarray([n_valid], dtype=np.float64),
                    "level": np.asarray([level], dtype=np.int32),
                }
            # no native library: compact the masked rows ONCE and sort
            # only them (the generic path pays two float-mask temps plus a
            # full-length sort with +inf fillers — ~2x the work); the
            # decimated sample is identical because masked rows sort to
            # the tail either way
            mask = np.asarray(valid, dtype=bool)
            if where is not None:
                mask = mask & np.asarray(where, dtype=bool)
            xm = np.asarray(x, dtype=np.float64)[mask]
            n = xm.size
            if n == 0:
                return {
                    "sample": np.zeros(0, dtype=np.float64),
                    "n": np.zeros(1, dtype=np.float64),
                    "level": np.zeros(1, dtype=np.int32),
                }
            cap = self._sample_size()
            level = max(0, int(np.ceil(np.log2(max(n, 1) / cap))))
            stride = 1 << level
            offset = stride // 2
            kept = max(0, -(-(n - offset) // stride))
            # full sort of the compacted rows: numpy's vectorized introsort
            # beats a scalar C multiselect by ~5x here (measured), so the
            # "only k order statistics" trick does NOT pay on this host
            xm.sort()
            sample = xm[offset::stride][:kept]
            return {
                "sample": sample,
                "n": np.asarray([n], dtype=np.float64),
                "level": np.asarray([level], dtype=np.int32),
            }
        live = xp.asarray(inputs[f"valid:{self.column}"]).astype(bool) & xp.asarray(
            inputs[where_key(getattr(self, "where", None))]
        ).astype(bool)
        if (
            inputs.get("__single_device")
            and x.dtype == xp.float32
            and _hist16_available(int(x.shape[0]))
        ):
            # TPU radix-select: the MXU builds the full 16-bit histogram
            # of the sortable-key space (one-hot matmuls, ~1ns/row) and
            # the HOST walks the 65536 counts, gathering only the bins
            # that own a decimation rank (host_finish_batch) — replaces
            # the O(n log^2 n) bitonic device sort entirely.
            from deequ_tpu.ops import pallas_kernels

            bins = pallas_kernels.f32_sortable_bin16(x, live)
            return {
                "hist16": pallas_kernels.hist16(bins),
                "n": xp.sum(live.astype(x.dtype))[None],
            }
        m = live.astype(x.dtype)
        big = xp.asarray(xp.inf, dtype=x.dtype)
        vals = xp.where(m > 0, x, big)
        sorted_vals = xp.sort(vals)
        n = xp.sum(m)
        cap = self._sample_size()
        # stride = 2^ceil(log2(n/cap)) so the kept sample has <= cap items;
        # all index math in int32 (native on TPU; batches are < 2^31 rows)
        level = xp.maximum(
            0.0, xp.ceil(xp.log2(xp.maximum(n, 1.0) / cap))
        ).astype(xp.int32)
        stride = xp.asarray(1, dtype=xp.int32) << level
        offset = stride // 2  # midpoint decimation (deterministic)
        idx = xp.minimum(
            offset + stride * xp.arange(cap, dtype=xp.int32), len(vals) - 1
        )
        sample = sorted_vals[idx]
        out = {
            "sample": sample,
            "n": n[None] if hasattr(n, "shape") else xp.asarray([n]),
            "level": level[None].astype(xp.int32),
        }
        if x.dtype == xp.float32:
            # where each sample's run of equal float32 keys starts: the
            # host swaps the sample for the column's own float64 value at
            # the same rank (host_finish_batch)
            out["lo"] = xp.searchsorted(sorted_vals, sample, side="left").astype(
                xp.int32
            )
        return out

    def unshift_batch(self, out: Any, shifts) -> Any:
        s = shifts.get(f"num:{self.column}", 0.0)
        if s == 0.0 or "sample" not in out or out.get("exact"):
            return out
        return {**out, "sample": np.asarray(out["sample"], dtype=np.float64) + s}

    def host_finish_batch(self, out: Any, host_inputs, shifts) -> Any:
        """Read a float32-wire batch's decimated sample off the column's
        own float64 values, so every engine and batch size returns the
        same, exact samples. Both device forms fix the ranks (the sort
        path's offset + stride * i); float32 rounding is monotone, so the
        float64 sort of the gathered rows keeps every rank. The result is
        marked `exact` so no shift is undone on it.

          * hist16 (TPU radix-select): walk the 65536 counts to the bins
            that own a rank, gather ONLY those bins' rows, sort them;
          * sorted sample + `lo` (the sort path): gather the rows whose
            float32 key equals a sample's, sort them, and step `rank - lo`
            into each key's run."""
        if "hist16" in out:
            return self._finish_hist16(out, host_inputs, shifts)
        if "lo" in out:
            return self._finish_sorted(out, host_inputs, shifts)
        return out

    def _wire_rows(self, host_inputs, shifts):
        """(float64 values, live mask, float32 wire keys) of the batch's
        host-resident column: the wire's value space, reproduced."""
        x = np.asarray(host_inputs[f"num:{self.column}"], dtype=np.float64)
        live = np.asarray(host_inputs[f"valid:{self.column}"], dtype=bool)
        where = getattr(self, "where", None)
        if where is not None:
            live = live & np.asarray(host_inputs[where_key(where)], dtype=bool)
        shift = shifts.get(f"num:{self.column}", 0.0)
        xs32 = (x - shift).astype(np.float32) if shift != 0.0 else x.astype(
            np.float32
        )
        return x, live, xs32

    @staticmethod
    def _ranks(n: int, level: int) -> np.ndarray:
        """The decimation ranks at `level`: offset + stride * i."""
        stride = 1 << level
        offset = stride // 2
        kept = max(0, -(-(n - offset) // stride))
        return offset + stride * np.arange(kept, dtype=np.int64)

    @staticmethod
    def _exact(sample, n: int, level: int) -> dict:
        return {
            "sample": sample,
            "n": np.asarray([n], dtype=np.float64),
            "level": np.asarray([level], dtype=np.int32),
            "exact": True,
        }

    def _finish_hist16(self, out: Any, host_inputs, shifts) -> Any:
        counts = np.asarray(out["hist16"], dtype=np.float64).reshape(65536)
        # bins 65409..65535: positive-NaN key region (impossible for
        # valid rows under the NaN==NULL contract) + the mask sentinel —
        # never ranked. Bin 65408 is exactly +inf: kept.
        counts[65409:] = 0.0
        counts = counts.astype(np.int64)
        n = int(counts.sum())
        if n <= 0:
            return self._exact(np.zeros(0, dtype=np.float64), 0, 0)
        level = max(0, int(np.ceil(np.log2(n / self._sample_size()))))
        ranks = self._ranks(n, level)

        cum = np.cumsum(counts)
        bins_of_rank = np.searchsorted(cum, ranks, side="right")
        wanted = np.zeros(65536, dtype=bool)
        wanted[bins_of_rank] = True

        x, live, xs32 = self._wire_rows(host_inputs, shifts)
        sel = live & wanted[_bin16(xs32)]
        gathered = np.sort(x[sel])

        # rank within the gathered (wanted-bins-only) ordering: subtract
        # the mass of NON-wanted bins below each rank's bin
        unwanted_cum = np.cumsum(counts * ~wanted)
        below = np.where(
            bins_of_rank > 0, unwanted_cum[bins_of_rank - 1], 0
        )
        return self._exact(gathered[ranks - below], n, level)

    def _finish_sorted(self, out: Any, host_inputs, shifts) -> Any:
        n = int(round(float(np.asarray(out["n"]).reshape(-1)[0])))
        if n <= 0:
            return self._exact(np.zeros(0, dtype=np.float64), 0, 0)
        # the device's own level: the ranks its sample and `lo` refer to
        level = int(np.asarray(out["level"]).reshape(-1)[0])
        ranks = self._ranks(n, level)
        kept = len(ranks)
        # + 0.0 folds -0.0 into +0.0: the device sort ties them too
        keys = np.asarray(out["sample"], dtype=np.float32)[:kept] + np.float32(0)
        lo = np.asarray(out["lo"], dtype=np.int64)[:kept]

        x, live, xs32 = self._wire_rows(host_inputs, shifts)
        xs32 = xs32 + np.float32(0)
        wanted = np.zeros(65536, dtype=bool)
        wanted[_bin16(keys)] = True
        sel = live & wanted[_bin16(xs32)]
        sel[sel] = np.isin(xs32[sel], keys)
        xv, kv = x[sel], xs32[sel]
        order = np.argsort(xv, kind="stable")
        gathered = xv[order]
        start = np.searchsorted(kv[order], keys, side="left")
        return self._exact(gathered[start + ranks - lo], n, level)

    def host_consume(self, state: Optional[State], out: Any) -> Optional[State]:
        n = int(round(float(np.asarray(out["n"]).reshape(-1)[0])))
        if n <= 0:
            return state
        level = int(np.asarray(out["level"]).reshape(-1)[0])
        stride = 1 << level
        offset = stride // 2
        kept = max(0, -(-(n - offset) // stride))  # ceil((n-offset)/stride)
        sample = np.asarray(out["sample"], dtype=np.float64).reshape(-1)[:kept]
        k = k_for_error(self.relative_error)
        sketch = KLLSketch(k=k, seed=_batch_seed(sample, n, level))
        sketch.insert_level(sample, level, true_count=n)
        partial = ApproxQuantileState(sketch)
        return partial if state is None else state.merge(partial)


@dataclass(frozen=True)
class ApproxQuantile(_QuantileAnalyzerBase):
    """Single quantile (reference: analyzers/ApproxQuantile.scala:49)."""

    column: str
    quantile: float
    relative_error: float = 0.01
    where: Optional[str] = None

    @property
    def name(self) -> str:
        return "ApproxQuantile"

    @property
    def instance(self) -> str:
        return self.column

    def preconditions(self) -> List[Callable[[Table], None]]:
        return [
            _quantile_param_check(self.quantile),
            _relative_error_param_check(self.relative_error),
            Preconditions.has_column(self.column),
            Preconditions.is_numeric(self.column),
        ]

    def compute_metric_from(self, state: Optional[State]) -> Metric:
        if state is None:
            return self.empty_state_failure()
        return DoubleMetric(
            self.entity,
            self.name,
            self.instance,
            Success(state.digest.quantile(self.quantile)),
        )

    def __repr__(self) -> str:
        # `where` is our extension over the reference signature
        # (reference: ApproxQuantile.scala:49 has no filter); render it only
        # when set so the default matches the reference toString
        base = f"ApproxQuantile({self.column},{self.quantile},{self.relative_error}"
        if self.where is not None:
            return base + f",{render_where(self.where)})"
        return base + ")"


@dataclass(frozen=True)
class ApproxQuantiles(_QuantileAnalyzerBase):
    """Many quantiles from one digest -> KeyedDoubleMetric
    (reference: analyzers/ApproxQuantiles.scala:39)."""

    column: str
    quantiles: Tuple[float, ...]
    relative_error: float = 0.01

    def __init__(self, column: str, quantiles, relative_error: float = 0.01):
        object.__setattr__(self, "column", column)
        object.__setattr__(self, "quantiles", tuple(quantiles))
        object.__setattr__(self, "relative_error", relative_error)

    @property
    def name(self) -> str:
        return "ApproxQuantiles"

    @property
    def instance(self) -> str:
        return self.column

    def preconditions(self) -> List[Callable[[Table], None]]:
        return (
            [_quantile_param_check(q) for q in self.quantiles]
            + [
                _relative_error_param_check(self.relative_error),
                Preconditions.has_column(self.column),
                Preconditions.is_numeric(self.column),
            ]
        )

    def compute_metric_from(self, state: Optional[State]) -> Metric:
        if state is None:
            from deequ_tpu.core.exceptions import EmptyStateException
            from deequ_tpu.core.maybe import Failure

            return KeyedDoubleMetric(
                self.entity,
                self.name,
                self.instance,
                Failure(
                    EmptyStateException(
                        f"Empty state for analyzer {self!r}, all input values were NULL."
                    )
                ),
            )
        values = state.digest.quantiles(list(self.quantiles))
        keyed = {_format_quantile(q): v for q, v in zip(self.quantiles, values)}
        return KeyedDoubleMetric(self.entity, self.name, self.instance, Success(keyed))

    def to_failure_metric(self, exception: BaseException) -> Metric:
        from deequ_tpu.core.exceptions import wrap_if_necessary
        from deequ_tpu.core.maybe import Failure

        return KeyedDoubleMetric(
            self.entity, self.name, self.instance, Failure(wrap_if_necessary(exception))
        )

    def __repr__(self) -> str:
        qs = ", ".join(_format_quantile(q) for q in self.quantiles)
        return f"ApproxQuantiles({self.column},List({qs}),{self.relative_error})"


def _format_quantile(q: float) -> str:
    return repr(float(q))

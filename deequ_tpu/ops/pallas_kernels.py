"""Pallas TPU kernels for device ops XLA lowers poorly.

The fused scan leaves almost everything to XLA (reductions fuse well on
the MXU/VPU), with ONE exception: the HLL register update is a
scatter-max into 512 registers, which XLA serializes on TPU. This
kernel reformulates it as a blockwise one-hot compare + max reduction —
pure VPU work, sequential-grid accumulation into the 512-register
output (reference hot loop: catalyst/StatefulHyperloglogPlus.scala:86-115;
kernel playbook: the repo's pallas guide).

Used on the TPU platform whenever shapes allow (row count a multiple of
the 1024-row block); on other platforms, or for other shapes, callers
run the `.at[idx].max(rank)` XLA path, and interpret mode backs the CPU
tests — results are identical by construction.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from deequ_tpu.observe import counters as _counters
from deequ_tpu.ops.sketches.hll import M as N_REGISTERS

# the (8, N_REGISTERS) output tile assumes the register count is a lane
# multiple; a precision change that breaks this must fail loudly, not
# drop registers
assert N_REGISTERS % 128 == 0, N_REGISTERS
_BLOCK_ROWS = 8  # (8, 128) int32 tile -> 1024 codes per grid step
_BLOCK = _BLOCK_ROWS * 128

_USABLE: Optional[bool] = None


def _kernel(codes_ref, out_ref):
    from jax.experimental import pallas as pl

    codes = codes_ref[:]  # (BLOCK_ROWS, 128) int32, masked rows carry 0
    idx = codes >> 6
    rank = codes & 0x3F
    # one-hot compare against all 512 registers: (BR, 128, 512) VPU work.
    # The per-sublane partial max keeps the output a clean (8, 512) tile
    # (an in-kernel (512,) -> (4,128) reshape fails to lower on some
    # mosaic builds); the final 8-way max is one tiny XLA op outside.
    regs = jax.lax.broadcasted_iota(jnp.int32, (_BLOCK_ROWS, 128, N_REGISTERS), 2)
    contrib = jnp.where(idx[:, :, None] == regs, rank[:, :, None], 0)
    block_max = jnp.max(contrib, axis=1)  # (BLOCK_ROWS, 512)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[:] = jnp.zeros((_BLOCK_ROWS, N_REGISTERS), dtype=jnp.int32)

    out_ref[:] = jnp.maximum(out_ref[:], block_max)


def hll_register_max(codes, interpret: bool = False):
    """Register-wise max over packed (idx << 6 | rank) codes.

    `codes` length must be a multiple of 1024 (callers check
    `shape_supported`); masked/invalid rows must carry code 0 (idx 0,
    rank 0 — a no-op for the max)."""
    from jax.experimental import pallas as pl

    _counters.record_kernel("hll_register_max")
    n = codes.shape[0]
    grid = n // _BLOCK
    codes2d = codes.reshape(grid * _BLOCK_ROWS, 128).astype(jnp.int32)
    out = pl.pallas_call(
        _kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, 128), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_BLOCK_ROWS, N_REGISTERS), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((_BLOCK_ROWS, N_REGISTERS), jnp.int32),
        interpret=interpret,
    )(codes2d)
    return jnp.max(out, axis=0)


def shape_supported(n: int) -> bool:
    return n >= _BLOCK and n % _BLOCK == 0


def usable() -> bool:
    """True on the TPU platform, False on any other, where callers run
    the XLA path. On a TPU a kernel the chip refuses fails the program
    that holds it, like any other device failure; it is never swapped
    for the XLA path. Called while programs are traced, so it must not
    dispatch anything itself."""
    global _USABLE
    if _USABLE is None:
        _USABLE = jax.devices()[0].platform == "tpu"
    return _USABLE


# ---------------------------------------------------------------------------
# hist16: full 16-bit histogram via MXU one-hot matmuls
# ---------------------------------------------------------------------------
#
# The quantile sketch's device-side heavy step used to be a full XLA sort
# (bitonic, ~25-100ns/elem on the VPU). The radix-select view only needs
# COUNTS at 16-bit key granularity: hist[h, l] = #rows whose sortable-key
# top byte is h and next byte is l. Per block that is
#
#     onehot_high^T @ onehot_low        -- a (256, B) x (B, 256) matmul
#
# i.e. pure MXU work (~65k MACs/row ≈ 1ns/row), accumulated across the
# grid into one (256, 256) float32 tile. The host walks the 65536 counts
# (256KB) to locate the wanted decimation ranks, then gathers and sorts
# ONLY the few bins that own a rank — the same histogram-assisted
# selection the host C kernel runs, with the counting on the TPU.
# (Reference role: catalyst/StatefulApproxQuantile.scala:28 — the
# per-partition digest update this feeds.)

_HIST_BINS = 256  # per axis; 256 x 256 = full 16-bit space


def _hist16_kernel(bins_ref, out_ref):
    from jax.experimental import pallas as pl

    bins = bins_ref[:]  # (BLOCK_ROWS, 128) int32 in [0, 65536)
    high = (bins >> 8) & 0xFF
    low = bins & 0xFF
    iota = jax.lax.broadcasted_iota(
        jnp.int32, (_BLOCK_ROWS, 128, _HIST_BINS), 2
    )
    oh_high = (high[:, :, None] == iota).astype(jnp.float32)
    oh_low = (low[:, :, None] == iota).astype(jnp.float32)
    # per-sublane (256,128)x(128,256) matmuls batched over the sublane
    # dim, summed on the VPU: mosaic's tpu.matmul wants standard 2-D
    # contractions (a fused multi-dim contraction fails verification)
    per_sublane = jax.lax.dot_general(
        oh_high,
        oh_low,
        dimension_numbers=(((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # (BLOCK_ROWS, 256, 256)
    block_hist = jnp.sum(per_sublane, axis=0)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[:] = jnp.zeros((_HIST_BINS, _HIST_BINS), dtype=jnp.float32)

    out_ref[:] = out_ref[:] + block_hist


def hist16(bins, interpret: bool = False):
    """(256, 256) float32 histogram over 16-bit bin ids.

    `bins` length must be a multiple of 1024 (`shape_supported`); rows
    to exclude must carry the sentinel 65535 (the NaN region of the
    float32 sortable-key space — real masked-in values never reach it),
    which the host walk drops. Counts are exact in f32 up to 2^24 rows.
    """
    from jax.experimental import pallas as pl

    _counters.record_kernel("hist16")
    n = bins.shape[0]
    grid = n // _BLOCK
    bins2d = bins.reshape(grid * _BLOCK_ROWS, 128).astype(jnp.int32)
    return pl.pallas_call(
        _hist16_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, 128), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_HIST_BINS, _HIST_BINS), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((_HIST_BINS, _HIST_BINS), jnp.float32),
        interpret=interpret,
    )(bins2d)


# ---------------------------------------------------------------------------
# masked moment folds: count/sum (+ centered sum-of-squares)
# ---------------------------------------------------------------------------
#
# The numeric analyzers' per-batch folds (Mean/Sum/StandardDeviation) are
# masked reductions XLA handles as separate reduce ops, each re-reading
# the (x, m) operands from HBM. The pallas form reads every (8, 128)
# block ONCE and accumulates both partials in VMEM over the sequential
# grid — one HBM pass for the moment set, sums Kahan-compensated — with
# a tiny XLA lane-reduce epilog outside the kernel. Minimum/Maximum are
# not folded here: on the float32 wire they fold on the host
# (`value_exact`), and a float64 wire keeps its own XLA min/max.
#
# BIT-IDENTITY CAVEAT: blocked accumulation is a different float
# summation ORDER than XLA's flat reduce, so sums/means need not match
# an XLA fold bitwise (count is exact in any order). That is
# why `runtime.fold_variant()` hashes "pallas-kahan" into the plan
# signature: committed states from the two arithmetics never mix in the
# state cache. tests/test_pallas_kernels.py pins the kernels bitwise
# against an identically-blocked XLA reference (and exactly against the
# naive fold for the order-insensitive stats).


def _masked_moments_kernel(x_ref, m_ref, cnt_ref, sum_ref, comp_ref):
    from jax.experimental import pallas as pl

    x = x_ref[:]  # (BLOCK_ROWS, 128) f32
    m = m_ref[:]  # (BLOCK_ROWS, 128) f32 in {0, 1}

    @pl.when(pl.program_id(0) == 0)
    def _init():
        cnt_ref[:] = jnp.zeros((_BLOCK_ROWS, 128), dtype=jnp.float32)
        sum_ref[:] = jnp.zeros((_BLOCK_ROWS, 128), dtype=jnp.float32)
        comp_ref[:] = jnp.zeros((_BLOCK_ROWS, 128), dtype=jnp.float32)

    cnt_ref[:] = cnt_ref[:] + m
    _kahan_add(sum_ref, comp_ref, x * m)


def _kahan_add(sum_ref, comp_ref, v):
    """Compensated accumulation of `v` into the lane sums. Each lane adds
    one value per grid step, so a plain f32 sum over a multi-million-row
    batch drifts by ~1e-6 relative (measured on TPC-H l_orderkey at
    SF1); the compensation term keeps it near one ulp."""
    y = v - comp_ref[:]
    t = sum_ref[:] + y
    comp_ref[:] = (t - sum_ref[:]) - y
    sum_ref[:] = t


def masked_moments(x, m, interpret: bool = False):
    """(count, sum) scalars of `x` under mask `m` in one pass.

    `x` length must be a multiple of 1024 (`shape_supported`); masked
    rows (m == 0) contribute 0 to both — exactly the analyzers' XLA fold
    semantics."""
    from jax.experimental import pallas as pl

    _counters.record_kernel("masked_moments")
    n = x.shape[0]
    grid = n // _BLOCK
    x2d = x.reshape(grid * _BLOCK_ROWS, 128).astype(jnp.float32)
    m2d = m.reshape(grid * _BLOCK_ROWS, 128).astype(jnp.float32)
    tile = pl.BlockSpec((_BLOCK_ROWS, 128), lambda i: (i, 0))
    acc = pl.BlockSpec((_BLOCK_ROWS, 128), lambda i: (0, 0))
    out = jax.ShapeDtypeStruct((_BLOCK_ROWS, 128), jnp.float32)
    cnt, total, comp = pl.pallas_call(
        _masked_moments_kernel,
        grid=(grid,),
        in_specs=[tile, tile],
        out_specs=[acc] * 3,
        out_shape=[out] * 3,
        interpret=interpret,
    )(x2d, m2d)
    return jnp.sum(cnt), jnp.sum(total - comp)


def _sumsq_kernel(d_ref, out_ref, comp_ref):
    from jax.experimental import pallas as pl

    d = d_ref[:]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[:] = jnp.zeros((_BLOCK_ROWS, 128), dtype=jnp.float32)
        comp_ref[:] = jnp.zeros((_BLOCK_ROWS, 128), dtype=jnp.float32)

    _kahan_add(out_ref, comp_ref, d * d)


def masked_centered_sumsq(x, m, avg, interpret: bool = False):
    """sum(((x - avg) * m)^2) — StandardDeviation's m2 fold. The
    centering is a cheap XLA prolog; the square-accumulate runs blocked
    in VMEM like `masked_moments`. Same shape contract."""
    from jax.experimental import pallas as pl

    _counters.record_kernel("masked_centered_sumsq")
    n = x.shape[0]
    grid = n // _BLOCK
    d = ((x.astype(jnp.float32) - avg) * m.astype(jnp.float32)).reshape(
        grid * _BLOCK_ROWS, 128
    )
    acc = pl.BlockSpec((_BLOCK_ROWS, 128), lambda i: (0, 0))
    out = jax.ShapeDtypeStruct((_BLOCK_ROWS, 128), jnp.float32)
    total, comp = pl.pallas_call(
        _sumsq_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((_BLOCK_ROWS, 128), lambda i: (i, 0))],
        out_specs=[acc, acc],
        out_shape=[out, out],
        interpret=interpret,
    )(d)
    return jnp.sum(total - comp)


def fold_moments_or_none(x, m):
    """The analyzers' gate: (count, sum) via the pallas fold
    when the knob, platform, and shape all allow — else None and the
    caller runs its XLA fold. Mirrors `runtime.fold_variant()`: whenever
    this returns non-None, the plan signature carries "pallas-kahan"."""
    from deequ_tpu.ops import runtime

    if not runtime.pallas_folds_enabled():
        return None
    if getattr(x, "ndim", 0) != 1 or not shape_supported(int(x.shape[0])):
        return None
    if not usable():
        return None
    return masked_moments(x, m)


def f32_sortable_bin16(values_f32, live_mask):
    """Top-16 sortable-key bins for float32 values (order-preserving:
    bin ascending == value ascending); excluded rows get sentinel 65535.
    Pure XLA VPU ops — runs inside the fused program before hist16."""
    u = jax.lax.bitcast_convert_type(values_f32, jnp.int32)
    key = jnp.where(u < 0, ~u, u | jnp.int32(-2147483648))
    bins = jax.lax.shift_right_logical(key, jnp.int32(16))
    return jnp.where(live_mask, bins, jnp.int32(65535))

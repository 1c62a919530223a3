"""Pallas HLL register-max kernel: interpret-mode equivalence with the
XLA scatter-max path (the CPU-side proof for the TPU kernel; on real
TPU hardware `usable()` turns it on inside the fused scan)."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from deequ_tpu.ops import pallas_kernels
from deequ_tpu.ops.sketches import hll


def reference_registers(codes: np.ndarray) -> np.ndarray:
    regs = np.zeros(pallas_kernels.N_REGISTERS, dtype=np.int32)
    np.maximum.at(regs, codes >> 6, codes & 0x3F)
    return regs


def random_codes(rng, n):
    idx = rng.integers(0, pallas_kernels.N_REGISTERS, n, dtype=np.int32)
    rank = rng.integers(0, 57, n, dtype=np.int32)
    return (idx << 6) | rank


class TestShapeGate:
    def test_supported_shapes(self):
        assert pallas_kernels.shape_supported(1024)
        assert pallas_kernels.shape_supported(1 << 22)
        assert not pallas_kernels.shape_supported(8)
        assert not pallas_kernels.shape_supported(1025)
        assert not pallas_kernels.shape_supported(0)

    def test_usable_is_false_on_cpu(self):
        # the test platform is CPU: the pallas path must gate itself off
        assert pallas_kernels.usable() is False


class TestInterpretModeEquivalence:
    @pytest.mark.parametrize("n", [1024, 4096, 1 << 15])
    def test_random_codes(self, n):
        rng = np.random.default_rng(n)
        codes = random_codes(rng, n)
        got = np.asarray(
            pallas_kernels.hll_register_max(codes, interpret=True)
        )
        np.testing.assert_array_equal(got, reference_registers(codes))

    def test_masked_rows_are_noops(self):
        rng = np.random.default_rng(7)
        codes = random_codes(rng, 2048)
        codes[::3] = 0  # masked/invalid rows carry code 0
        got = np.asarray(
            pallas_kernels.hll_register_max(codes, interpret=True)
        )
        # masked rows must contribute nothing: equal to the registers of
        # the UNMASKED rows alone
        unmasked_only = codes[codes != 0]
        pad = np.zeros(2048 - len(unmasked_only), dtype=np.int32)
        np.testing.assert_array_equal(
            got, reference_registers(np.concatenate([unmasked_only, pad]))
        )

    def test_all_zero(self):
        got = np.asarray(
            pallas_kernels.hll_register_max(
                np.zeros(1024, dtype=np.int32), interpret=True
            )
        )
        np.testing.assert_array_equal(got, np.zeros(512, dtype=np.int32))

    def test_single_register_saturation(self):
        codes = np.full(1024, (511 << 6) | 56, dtype=np.int32)
        got = np.asarray(
            pallas_kernels.hll_register_max(codes, interpret=True)
        )
        assert got[511] == 56
        assert got[:511].sum() == 0

    def test_matches_hll_pack_pipeline(self):
        """End-to-end against the production packer: registers from the
        pallas kernel == registers from the host fold for real values."""
        rng = np.random.default_rng(0)
        values = rng.integers(0, 5000, 4096)
        valid = rng.random(4096) < 0.9
        packed = hll.pack_codes(values, valid)
        got = np.asarray(
            pallas_kernels.hll_register_max(packed, interpret=True)
        )
        expected = np.zeros(hll.M, dtype=np.int32)
        np.maximum.at(expected, packed >> 6, packed & 0x3F)
        np.testing.assert_array_equal(got, expected)
        # and the estimate built from them is the production estimate
        assert hll.estimate(got) == hll.estimate(expected)


class TestHist16RadixSelect:
    """The MXU histogram kernel (one-hot matmuls -> full 16-bit count
    table) + host walk must reproduce the device sort path's decimated
    sample EXACTLY (same ranks in the same float32 value space)."""

    def test_hist16_counts_match_bincount(self):
        rng = np.random.default_rng(0)
        n = 8192
        x = (rng.lognormal(0, 2, n) * np.where(rng.random(n) < 0.4, -1, 1)).astype(
            np.float32
        )
        live = rng.random(n) > 0.1
        bins = np.asarray(
            pallas_kernels.f32_sortable_bin16(jnp.asarray(x), jnp.asarray(live))
        )
        hist = np.asarray(
            pallas_kernels.hist16(jnp.asarray(bins), interpret=True)
        ).reshape(65536)
        u = x.view(np.int32)
        key = np.where(u < 0, ~u, u | np.int32(-(1 << 31)))
        ref_bins = np.where(live, (key.astype(np.int64) >> 16) & 0xFFFF, 65535)
        ref = np.bincount(ref_bins, minlength=65536)
        assert np.array_equal(hist.astype(np.int64), ref)
        # bin order must follow value order (sortable-key property)
        order = np.argsort(x[live], kind="stable")
        assert (np.diff(ref_bins[live][order]) >= 0).all()

    def test_quantile_path_equals_sort_path(self, monkeypatch):
        """End-to-end through the f32 device engine: both paths pick the
        same decimation ranks and read the samples off the column's own
        float64 values, so the resulting quantiles match exactly, equal
        the float64 engine's and are values of the column. Engagement is
        asserted, not assumed."""
        import deequ_tpu.analyzers.sketch as sketch_mod
        from deequ_tpu.analyzers import ApproxQuantile
        from deequ_tpu.data.table import Table
        from deequ_tpu.ops import runtime
        from deequ_tpu.ops.fused import FusedScanPass

        monkeypatch.setenv("DEEQU_TPU_PLACEMENT", "device")

        rng = np.random.default_rng(8)
        n = 50_000
        x = rng.lognormal(3, 1, n)
        x[rng.random(n) < 0.05] = np.nan
        x = x * np.where(rng.random(n) < 0.3, -1, 1)

        calls = {"hist16": 0}
        real_hist16 = pallas_kernels.hist16

        def interpreted_hist16(bins, interpret=False):
            calls["hist16"] += 1
            return real_hist16(bins, interpret=True)

        def run(use_hist, dtype=jnp.float32):
            # KLL seeds are content-derived (sketch._batch_seed): equal
            # samples give equal sketches with no counter pinning
            monkeypatch.setattr(runtime, "compute_dtype", lambda: dtype)
            if use_hist:
                monkeypatch.setattr(
                    sketch_mod, "_hist16_available", lambda n: True
                )
                monkeypatch.setattr(pallas_kernels, "hist16", interpreted_hist16)
            else:
                monkeypatch.setattr(
                    sketch_mod, "_hist16_available", lambda n: False
                )
            t = Table.from_numpy({"x": x})
            res = FusedScanPass([ApproxQuantile("x", 0.5)]).run(t)
            state = res[0].state_or_raise()
            return res[0].analyzer.compute_metric_from(state).value.get()

        via_hist = run(True)
        assert calls["hist16"] >= 1  # the kernel actually ran
        via_sort = run(False)
        assert via_hist == via_sort, (via_hist, via_sort)
        via_sort_f64 = run(False, jnp.float64)
        assert via_hist == via_sort_f64, (via_hist, via_sort_f64)
        assert via_hist in set(x[~np.isnan(x)])


class TestMaskedMomentFolds:
    """ISSUE 15 satellite: the numeric analyzers' count/sum (+ stddev
    m2) folds as single-HBM-pass pallas kernels, pinned in interpret
    mode against an identically-blocked XLA reference — BITWISE for
    every stat (blocked summation is its own arithmetic; that is exactly
    what the "pallas-kahan" plan-signature variant isolates), and
    exactly for the count vs the naive fold."""

    @staticmethod
    def _data(n, seed, all_masked=False):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=n).astype(np.float32) * 100.0)
        if all_masked:
            m = jnp.zeros(n, dtype=jnp.float32)
        else:
            m = jnp.asarray((rng.random(n) < 0.8).astype(np.float32))
        return x, m

    @staticmethod
    def _blocked_reference(x, m):
        """The kernel's exact accumulation order in plain jnp ops:
        (8, 128) Kahan-compensated lane accumulators over the sequential
        grid, then the same tiny lane-reduce epilog."""
        x3 = x.reshape(-1, 8, 128)
        m3 = m.reshape(-1, 8, 128)
        cnt = jnp.zeros((8, 128), jnp.float32)
        tot = jnp.zeros((8, 128), jnp.float32)
        comp = jnp.zeros((8, 128), jnp.float32)
        for blk in range(x3.shape[0]):
            xb, mb = x3[blk], m3[blk]
            cnt = cnt + mb
            y = xb * mb - comp
            t = tot + y
            comp = (t - tot) - y
            tot = t
        return jnp.sum(cnt), jnp.sum(tot - comp)

    @pytest.mark.parametrize("n", [1024, 4096, 1 << 14])
    def test_bitwise_vs_blocked_xla_reference(self, n):
        x, m = self._data(n, seed=n)
        got = [np.asarray(v) for v in
               pallas_kernels.masked_moments(x, m, interpret=True)]
        ref = [np.asarray(v) for v in self._blocked_reference(x, m)]
        for g, r in zip(got, ref):
            assert g.tobytes() == r.tobytes(), (g, r)

    def test_order_insensitive_stats_match_naive_fold_exactly(self):
        x, m = self._data(4096, seed=3)
        cnt, total = [
            np.asarray(v)
            for v in pallas_kernels.masked_moments(x, m, interpret=True)
        ]
        xn, mn_np = np.asarray(x), np.asarray(m)
        assert cnt == mn_np.sum()
        # sums reassociate: allclose, not bitwise, vs the naive fold
        np.testing.assert_allclose(
            total, (xn * mn_np).sum(dtype=np.float32), rtol=1e-5
        )

    def test_compensated_sums_hold_1e_6(self):
        """TPC-H l_orderkey over 1024 blocks: a plain f32 lane sum of
        this column drifts to ~4e-6 relative; the compensated one must
        hold the 1e-6 parity target."""
        from deequ_tpu.testing.tpch import lineitem_columns

        key = np.asarray(lineitem_columns(1 << 20)["l_orderkey"], np.float64)
        x = jnp.asarray(key, dtype=jnp.float32)
        m = jnp.ones(x.shape[0], dtype=jnp.float32)
        _cnt, total = pallas_kernels.masked_moments(
            x, m, interpret=True
        )
        assert abs(float(total) - key.sum()) / key.sum() < 1e-6
        avg = key.mean()
        m2 = pallas_kernels.masked_centered_sumsq(
            x, m, jnp.float32(avg), interpret=True
        )
        want = ((key - np.float32(avg)) ** 2).sum()
        assert abs(float(m2) - want) / want < 1e-6

    def test_all_masked_yields_identities(self):
        x, m = self._data(1024, seed=5, all_masked=True)
        cnt, total = [
            np.asarray(v)
            for v in pallas_kernels.masked_moments(x, m, interpret=True)
        ]
        assert cnt == 0.0 and total == 0.0

    def test_centered_sumsq_matches_stddev_fold(self):
        x, m = self._data(2048, seed=11)
        xn, mm = np.asarray(x), np.asarray(m)
        avg = np.float32((xn * mm).sum() / mm.sum())
        got = np.asarray(
            pallas_kernels.masked_centered_sumsq(x, m, avg, interpret=True)
        )
        naive = (((xn - avg) * mm) ** 2).sum(dtype=np.float32)
        np.testing.assert_allclose(got, naive, rtol=1e-5)

    def test_gate_is_off_on_cpu(self, monkeypatch):
        # even with the knob on, usable() is False on CPU: the fold
        # returns None and fold_variant stays "" — cached states on CPU
        # never carry the pallas variant
        from deequ_tpu.ops import runtime

        monkeypatch.setenv("DEEQU_TPU_PALLAS_FOLDS", "1")
        x, m = self._data(1024, seed=1)
        assert pallas_kernels.fold_moments_or_none(x, m) is None
        assert runtime.fold_variant() == ""

    def test_gate_rejects_unsupported_shapes(self, monkeypatch):
        monkeypatch.setenv("DEEQU_TPU_PALLAS_FOLDS", "1")
        x, m = self._data(1024, seed=1)
        assert pallas_kernels.fold_moments_or_none(x[:100], m[:100]) is None

    def test_knob_off_disables_fold(self, monkeypatch):
        monkeypatch.setenv("DEEQU_TPU_PALLAS_FOLDS", "0")
        from deequ_tpu.ops import runtime

        assert not runtime.pallas_folds_enabled()
        x, m = self._data(1024, seed=1)
        assert pallas_kernels.fold_moments_or_none(x, m) is None

    def test_fold_variant_enters_plan_signature(self):
        from deequ_tpu.analyzers.scan import Mean
        from deequ_tpu.repository.states import plan_signature

        base = plan_signature([Mean("x")], placement="device",
                              compute_dtype="float32", batch_size=None,
                              batch_rows=None)
        default = plan_signature([Mean("x")], placement="device",
                                 compute_dtype="float32", batch_size=None,
                                 batch_rows=None, variant="")
        pallas = plan_signature([Mean("x")], placement="device",
                                compute_dtype="float32", batch_size=None,
                                batch_rows=None, variant="pallas-kahan")
        # empty variant leaves existing signatures unchanged; the pallas
        # arithmetic gets its own cache namespace
        assert base == default
        assert pallas != base

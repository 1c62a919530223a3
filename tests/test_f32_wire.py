"""The float32 wire format (what a real TPU runs with x64 off), exercised
on CPU by forcing runtime.compute_dtype to float32: multi-batch scans must
keep counts EXACT (bitpacked masks, packed-output casts, 2^24 guard) and
float statistics within f32 tolerance of the f64 engine."""

from __future__ import annotations

import numpy as np
import pytest

from deequ_tpu.analyzers import (
    ApproxCountDistinct,
    Completeness,
    Compliance,
    DataType,
    Maximum,
    Mean,
    Minimum,
    PatternMatch,
    Size,
    StandardDeviation,
    Sum,
)
from deequ_tpu.analyzers.sketch import ApproxQuantile
from deequ_tpu.data.table import Table
from deequ_tpu.ops import runtime
from deequ_tpu.ops.fused import FusedScanPass


@pytest.fixture
def f32_engine(monkeypatch):
    import jax.numpy as jnp

    monkeypatch.setattr(runtime, "compute_dtype", lambda: jnp.float32)
    # exercise the DEVICE wire format, not the host fold
    monkeypatch.setenv("DEEQU_TPU_PLACEMENT", "device")


def make_table(n=10_000):
    rng = np.random.default_rng(3)
    x = rng.normal(100.0, 10.0, n)
    x[::17] = np.nan
    return Table.from_numpy(
        {
            "x": x,
            "q": rng.integers(-3, 1000, n),
            "s": np.array(
                [["9", "word", "1.5", None][i % 4] for i in range(n)], dtype=object
            ),
        }
    )


ANALYZERS = [
    Size(),
    Size(where="q > 500"),
    Completeness("x"),
    Completeness("s"),
    Compliance("pos", "q >= 0"),
    PatternMatch("s", r"^\d+$"),
    DataType("s"),
    ApproxCountDistinct("q"),
    Mean("x"),
    Minimum("x"),
    Maximum("x"),
    Sum("x"),
    StandardDeviation("x"),
    ApproxQuantile("x", 0.5),
]


def metrics_with(batch_size, table):
    out = {}
    for r in FusedScanPass(ANALYZERS, batch_size=batch_size).run(table):
        state = r.state_or_raise()
        metric = r.analyzer.compute_metric_from(state)
        out[repr(r.analyzer)] = metric.value.get()
    return out


def test_f32_multibatch_counts_exact_and_floats_bounded(f32_engine):
    table = make_table()
    f32_multi = metrics_with(512, table)  # 20 batches through the wire

    # recompute ground truth in f64 (fresh pass w/o the monkeypatched dtype
    # is not possible inside the fixture, so compute expected values directly)
    x = table.column("x")
    xs = x.values[x.valid]
    n = table.num_rows
    q = table.column("q").values

    # counting analyzers: EXACT across batches
    assert f32_multi["Size(None)"] == n
    assert f32_multi["Size(Some(q > 500))"] == int((q > 500).sum())
    assert f32_multi["Completeness(x,None)"] == pytest.approx(
        x.valid.sum() / n, abs=0
    )
    assert f32_multi["Compliance(pos,q >= 0,None)"] == pytest.approx(
        (q >= 0).sum() / n, abs=0
    )
    # 1 in 4 rows is a digit string; 1 in 4 is NULL
    assert f32_multi[f"PatternMatch(s,^\\d+$,None)"] == pytest.approx(0.25, abs=1e-12)

    # float statistics: within f32 relative tolerance
    assert f32_multi["Minimum(x,None)"] == pytest.approx(xs.min(), rel=1e-6)
    assert f32_multi["Maximum(x,None)"] == pytest.approx(xs.max(), rel=1e-6)
    assert f32_multi["Mean(x,None)"] == pytest.approx(xs.mean(), rel=1e-4)
    assert f32_multi["Sum(x,None)"] == pytest.approx(xs.sum(), rel=1e-4)
    assert f32_multi["StandardDeviation(x,None)"] == pytest.approx(
        xs.std(), rel=1e-3
    )
    assert f32_multi["ApproxQuantile(x,0.5,0.01)"] == pytest.approx(
        float(np.quantile(xs, 0.5)), rel=0.01
    )
    # HLL over int values: within the declared rsd
    exact_distinct = len(np.unique(q))
    assert f32_multi["ApproxCountDistinct(q,None)"] == pytest.approx(
        exact_distinct, rel=0.15
    )


def test_f32_batch_size_guard(f32_engine):
    table = make_table(100)
    results = FusedScanPass([Size()], batch_size=(1 << 24) + 8).run(table)
    with pytest.raises(ValueError, match="2\\^24"):
        results[0].state_or_raise()


def test_f32_multibatch_equals_singlebatch(f32_engine):
    """Same engine, different batch boundaries: counts identical, floats
    within fold roundoff."""
    table = make_table()
    multi = metrics_with(512, table)
    single = metrics_with(1 << 16, table)
    for key in multi:
        if key.startswith(("Size", "Completeness", "Compliance", "PatternMatch")):
            assert multi[key] == single[key], key
        elif key.startswith("ApproxQuantile"):
            assert multi[key] == pytest.approx(single[key], rel=0.02), key
        else:
            assert multi[key] == pytest.approx(single[key], rel=1e-4), key


class TestIllConditionedF32:
    """VERDICT r3 #6: the 1e-6 parity contract under a float32 wire must
    survive ill-conditioned data. The engine pre-centers each numeric
    column (scan-constant shift, undone via unshift_agg/unshift_batch)
    BEFORE the f32 cast; without it the variance signal is destroyed by
    wire quantization and no kernel can recover it."""

    def _table(self, n=40_000, mean=1.0e7, sd=1.0e-1):
        rng = np.random.default_rng(42)
        x = mean + rng.normal(0.0, sd, n)
        # y correlated with x through the SMALL signal only
        y = 2.0e7 + 3.0 * (x - mean) + rng.normal(0.0, sd / 10, n)
        run = np.full(n, mean)  # long near-constant run
        run[n // 2 :] = mean + 1.0e-1
        return Table.from_numpy({"x": x, "y": y, "run": run})

    def test_naive_f32_cast_destroys_the_signal(self):
        """The premise: casting x (mean 1e7, sd 0.1) straight to f32
        quantizes at 1 ulp = 1.0 — stddev inflates by ~the quantization
        noise. This is what a shift-less engine would compute at best."""
        t = self._table()
        x = t.column("x").values
        naive = np.asarray(x, dtype=np.float32).astype(np.float64)
        naive_sd = naive.std()
        # every value rounds to the same float32: the signal is GONE
        assert naive_sd == 0.0

    def test_stddev_and_mean_survive_f32_wire(self, f32_engine):
        from deequ_tpu.analyzers import StandardDeviation

        t = self._table()
        x = t.column("x").values
        res = FusedScanPass(
            [Mean("x"), StandardDeviation("x"), Minimum("x"), Maximum("x"), Sum("x")]
        ).run(t)
        got = {type(r.analyzer).__name__: r for r in res}
        exact_sd = float(np.std(np.asarray(x, dtype=np.float64)))
        sd = got["StandardDeviation"].state_or_raise().metric_value()
        assert sd == pytest.approx(exact_sd, rel=1e-3), (sd, exact_sd)
        mean = got["Mean"].state_or_raise().metric_value()
        assert mean == pytest.approx(float(np.mean(x)), rel=1e-9)
        assert got["Minimum"].state_or_raise().metric_value() == pytest.approx(
            float(np.min(x)), abs=1e-5
        )
        assert got["Maximum"].state_or_raise().metric_value() == pytest.approx(
            float(np.max(x)), abs=1e-5
        )
        assert got["Sum"].state_or_raise().metric_value() == pytest.approx(
            float(np.sum(np.asarray(x, dtype=np.float64))), rel=1e-7
        )

    def test_correlation_survives_f32_wire(self, f32_engine):
        from deequ_tpu.analyzers import Correlation

        t = self._table()
        x = np.asarray(t.column("x").values, dtype=np.float64)
        y = np.asarray(t.column("y").values, dtype=np.float64)
        exact_r = float(np.corrcoef(x, y)[0, 1])
        assert exact_r > 0.9  # the correlation lives in the small signal
        res = FusedScanPass([Correlation("x", "y")]).run(t)
        r = res[0].state_or_raise().metric_value()
        assert r == pytest.approx(exact_r, abs=2e-3), (r, exact_r)

    def test_near_constant_run_stddev(self, f32_engine):
        from deequ_tpu.analyzers import StandardDeviation

        t = self._table()
        res = FusedScanPass([StandardDeviation("run")]).run(t)
        sd = res[0].state_or_raise().metric_value()
        assert sd == pytest.approx(0.05, rel=1e-3)  # half at +0.1 -> sd 0.05

    def test_quantile_sample_unshifted(self, f32_engine):
        t = self._table()
        res = FusedScanPass([ApproxQuantile("x", 0.5)]).run(t)
        q = res[0].analyzer.compute_metric_from(res[0].state_or_raise())
        median = q.value.get()
        x = np.sort(np.asarray(t.column("x").values, dtype=np.float64))
        rank = (x <= median).mean()
        assert abs(rank - 0.5) <= 0.03, (median, rank)
        assert abs(median - 1.0e7) < 1.0  # absolute scale restored

    def test_leading_null_does_not_disable_centering(self, f32_engine):
        """The shift is picked from the first VALID row: a null in row 0
        (whose 0.0 fill is 'finite') must not silently disable the
        pre-centering (reviewer finding, round 4)."""
        from deequ_tpu.analyzers import StandardDeviation

        rng = np.random.default_rng(42)
        x = 1.0e7 + rng.normal(0.0, 0.1, 40_000)
        x[0] = np.nan
        t = Table.from_numpy({"x": x})
        res = FusedScanPass([StandardDeviation("x")]).run(t)
        sd = res[0].state_or_raise().metric_value()
        assert sd == pytest.approx(float(np.nanstd(x)), rel=1e-3)


class TestExactValuesOnF32Wire:
    """Metrics that ARE one of the column's values (min, max, the
    quantile samples) come back as the column's own float64 values on
    the float32 wire, as on the float64 one: Minimum/Maximum fold on the
    host (`value_exact`), and quantile samples are read off the host
    column, on one device and per shard across a mesh."""

    @staticmethod
    def _x(n=20_000):
        rng = np.random.default_rng(5)
        x = 1.0e7 + rng.lognormal(0.0, 1.0, n) * np.where(
            rng.random(n) < 0.3, -1.0, 1.0
        )
        x[rng.random(n) < 0.05] = np.nan
        return x

    def test_min_max_plan_on_host(self, f32_engine):
        from deequ_tpu.ops.fused import plan_scan_members

        plan = plan_scan_members(
            [Mean("x"), Minimum("x"), Maximum("x")], mode="device"
        )
        assert plan.merge_idx == [0]
        assert plan.host_idx == [1, 2]
        # states folded this way never mix with older float32-wire ones
        assert "f32-exact" in runtime.fold_variant().split("+")

    def test_min_max_exact(self, f32_engine):
        x = self._x()
        res = FusedScanPass([Minimum("x"), Maximum("x")], batch_size=4096).run(
            Table.from_numpy({"x": x})
        )
        assert res[0].state_or_raise().metric_value() == np.nanmin(x)
        assert res[1].state_or_raise().metric_value() == np.nanmax(x)

    @pytest.mark.parametrize("engine", ["single", "mesh"])
    def test_quantiles_equal_the_float64_engine(self, monkeypatch, engine):
        """Same ranks, same float64 samples: the float32 wire's quantiles
        equal the float64 engine's bit for bit, over several batches (and
        shards, whose host rows must line up with the device's)."""
        import jax.numpy as jnp

        from deequ_tpu.parallel import DistributedScanPass, data_mesh

        monkeypatch.setenv("DEEQU_TPU_PLACEMENT", "device")
        x = self._x()
        table = Table.from_numpy({"x": x})
        analyzers = [ApproxQuantile("x", q) for q in (0.1, 0.5, 0.97)]

        def run():
            if engine == "single":
                results = FusedScanPass(analyzers, batch_size=4096).run(table)
            else:
                results = DistributedScanPass(
                    analyzers, mesh=data_mesh(), batch_size_per_device=512
                ).run(table)
            return [
                r.analyzer.compute_metric_from(r.state_or_raise()).value.get()
                for r in results
            ]

        want = run()
        monkeypatch.setattr(runtime, "compute_dtype", lambda: jnp.float32)
        with runtime.monitored() as stats:
            got = run()
        assert got == want
        assert set(got) <= set(x[~np.isnan(x)])
        if engine == "mesh":
            # every device held its even share of the sharded inputs
            shares = set(stats.device_rows.values())
            assert len(stats.device_rows) == 8 and len(shares) == 1
            assert shares.pop() * 8 == stats.placed_rows > 0

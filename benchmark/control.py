#!/usr/bin/env python3
"""The control of the comparison that decides `correct`: the plain
reference put in the program's place and computed one precision below
what the configuration states (float32 on the device, where the
guarantees are stated against float64), held to the same limits. It has
to come out as not correct; its worst errors are the upper readings the
limits in PERF.md were set from.

  python3 benchmark/control.py --workload <cell> --seeds <n> [<n> ...] [--verdicts N]

For a partition cell the control answers for each of the first `--verdicts`
days of the chain alone, as a run's verdicts do (a run makes about 240).
Not run by the benchmark's own runs.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.data import tpch  # noqa: E402
from benchmark.reference.reference import Reference, Scorecard, expand  # noqa: E402


def _answers(config: dict, traffic: dict, seed: int, verdicts: int):
    """The columns of each answer a run checks: the whole table, or each
    day of the chain alone."""
    scale = float(config["scale"])
    if traffic["kind"] != "partition_loop":
        yield tpch.lineitem(int(config["rows"]), seed, scale)
        return
    days = int(config["days"])
    sizes = tpch.day_sizes(int(config["rows"]), days)
    for k in range(min(verdicts, days)):
        yield tpch.lineitem_day(int(sizes[k]), seed, k, scale)


def _padded(col, n: int):
    """`col` in float32 on the device, padded with zeros to a power of
    two: every day of a chain then runs one compiled program per formula."""
    import jax.numpy as jnp

    x = np.zeros(1 << max(n - 1, 1).bit_length(), np.float32)
    x[:n] = np.asarray(col, np.float32)
    return jnp.asarray(x)


@functools.lru_cache(maxsize=None)
def _formula(fam: str):
    """The reference's formula for `fam` in float32 over the first `n`
    lanes of padded inputs, compiled once a padded size."""
    import jax
    import jax.numpy as jnp

    def f(x, y, n, at):
        live = jnp.arange(x.shape[0]) < n
        zero = jnp.zeros((), x.dtype)
        total = jnp.sum(jnp.where(live, x, zero))
        mean = total / n
        if fam == "Sum":
            return total
        if fam == "Mean":
            return mean
        if fam == "StandardDeviation":
            return jnp.sqrt(jnp.sum(jnp.where(live, (x - mean) ** 2, zero)) / n)
        if fam == "Minimum":
            return jnp.min(jnp.where(live, x, jnp.inf))
        if fam == "Maximum":
            return jnp.max(jnp.where(live, x, -jnp.inf))
        if fam == "ApproxQuantile":
            return jnp.sort(jnp.where(live, x, jnp.inf))[at]
        if fam == "Correlation":
            dx = jnp.where(live, x - mean, zero)
            dy = jnp.where(live, y - jnp.sum(jnp.where(live, y, zero)) / n, zero)
            return jnp.sum(dx * dy) / jnp.sqrt(jnp.sum(dx * dx) * jnp.sum(dy * dy))
        raise ValueError(fam)

    return jax.jit(f)


def control_values(metrics, ref: Reference) -> list:
    """Each metric from the reference's own formulas in float32 with
    jax.numpy (on the device where there is one); counts stay exact, as
    no lower precision applies to them."""
    out = []
    for m in metrics:
        fam = m.family
        if fam in ("Size", "Completeness", "Uniqueness", "Compliance",
                   "ApproxCountDistinct"):
            out.append(ref.value(m))
            continue
        x = _padded(ref.cols[m.columns[0]], ref.n)
        y = _padded(ref.cols[m.columns[1]], ref.n) if fam == "Correlation" else x
        at = max(int(np.ceil(m.param * ref.n)) - 1, 0) if fam == "ApproxQuantile" else 0
        v = _formula(fam)(x, y, np.int32(ref.n), np.int32(at))
        out.append(float(np.float64(np.asarray(v))))
    return out


def score(config: dict, traffic: dict, seed: int, verdicts: int) -> Scorecard:
    domains = config["columns"]
    metrics = expand(traffic["check"], list(domains))
    card = Scorecard(config["guarantees"])
    for k, cols in enumerate(_answers(config, traffic, seed, verdicts)):
        ref = Reference(cols, domains)
        for m, got in zip(metrics, control_values(metrics, ref)):
            card.metric(m, got, ref, ref.value(m), f"control {k} {m.family}{m.columns}")
    return card


def main(argv=None) -> int:
    import jax

    from benchmark.harness import core

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--verdicts", type=int, default=240)
    args = ap.parse_args(argv)
    cell = argparse.Namespace(workload=args.workload, seed=0, seconds=0, trace=0)
    ctx = core.context(cell, jax.devices())
    print(f"control on {jax.devices()[0].device_kind}", flush=True)
    for seed in args.seeds:
        card = score(ctx.config, ctx.traffic, seed, args.verdicts)
        print(json.dumps({"seed": seed, "correct": card.ok,
                          "compared": card.as_json(), "where": card.where}), flush=True)
    return 0

if __name__ == "__main__":
    sys.exit(main())

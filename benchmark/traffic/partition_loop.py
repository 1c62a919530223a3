"""Closed loop over landing partitions, one client: each step opens the
next day's Parquet file and verifies it alone, as a pipeline gates each
landing partition. The latency of a verdict runs from opening the
partition to holding its VerificationResult.

The configuration's `days` are generated once per checkout and seed; the
chain walks them in order and wraps around.

Parameters (the traffic mix's file, benchmark/traffic/<traffic>.json):
  warm_verdicts steps of the chain made in set-up (they warm every
                program the window runs)
  check         the check spec (reference.expand)
"""

from __future__ import annotations

import os
import time

from benchmark.data import cache, tpch
from benchmark.harness import program
from benchmark.harness.core import Call
from benchmark.reference.reference import Reference, expand


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.p = ctx.traffic
        cfg = ctx.config
        self.domains = cfg["columns"]
        self.scale = float(cfg["scale"])
        self.metrics = expand(self.p["check"], list(self.domains))
        self.pool = int(cfg["days"])
        self.sizes = tpch.day_sizes(int(cfg["rows"]), self.pool)
        self.chain = 0  # partitions verified so far, warm-up included
        self.outputs = []  # (chain index, verdict)

    def prepare(self) -> None:
        ctx = self.ctx
        folder = cache.directory(ctx.config["name"], int(ctx.config["rows"]), ctx.seed)
        t0 = time.perf_counter()
        written = 0
        self.paths = []
        for day in range(self.pool):
            path = os.path.join(folder, f"day-{day:04d}-{int(self.sizes[day])}.parquet")
            written += cache.parquet(
                path,
                lambda d=day: tpch.to_arrow(
                    tpch.lineitem_day(int(self.sizes[d]), ctx.seed, d, self.scale)
                ),
            )
            self.paths.append(path)
        ctx.log(f"data: {self.pool} daily partitions of {int(self.sizes.min())}-"
                f"{int(self.sizes.max())} rows, {written} written, "
                f"{self.pool - written} found in the cache, in "
                f"{time.perf_counter() - t0:.3f}s")
        self.check = program.check(ctx.cell["name"], self.metrics, self.domains)

    def _verify_next(self):
        from deequ_tpu import Table, VerificationSuite

        k = self.chain
        t0 = time.perf_counter()
        source = Table.scan_parquet(self.paths[k % self.pool])
        result = VerificationSuite().on_data(source).add_check(self.check).run()
        t1 = time.perf_counter()
        self.chain += 1
        return k, result, t0, t1

    def warm(self) -> None:
        for _ in range(int(self.p["warm_verdicts"])):
            k, result, _t0, _t1 = self._verify_next()
            self.outputs.append((k, program.verdict(result)))

    def step(self) -> Call:
        k, result, t0, t1 = self._verify_next()
        self.outputs.append((k, program.verdict(result)))
        return Call(rows=int(self.sizes[k % self.pool]), t0=t0, t1=t1)

    def release(self) -> None:
        pass

    def verify(self, card) -> None:
        days = [tpch.lineitem_day(int(self.sizes[d]), self.ctx.seed, d, self.scale)
                for d in range(min(self.chain, self.pool))]
        for k, (status, rows) in self.outputs:
            ref = Reference(days[k % self.pool], self.domains)
            if len(rows) != len(self.metrics):
                card.failures.append(f"verdict {k}: {len(rows)} results for "
                                     f"{len(self.metrics)} metrics")
                continue
            wrong = 0
            want_all = True
            for (got, st), m in zip(rows, self.metrics):
                want = ref.value(m)
                card.metric(m, got, ref, want, f"verdict {k} {m.family}{m.columns}")
                ws = "SUCCESS" if ref.expected(m, self.domains) else "FAILURE"
                want_all &= ws == "SUCCESS"
                wrong += st != ws
            wrong += status != ("SUCCESS" if want_all else "ERROR")
            card.count("verdicts_wrong", wrong, f"verdict {k}")

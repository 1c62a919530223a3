"""Closed loop over one whole table, one client: each call verifies the
table again as soon as the last call returned.

Parameters (the traffic mix's file, benchmark/traffic/<traffic>.json):
  source   "memory" (a Table built in set-up) or "parquet" (a Parquet file
           of the table, scanned by `Table.scan_parquet` in every call)
  run      "suite" (VerificationSuite with one Check) or "analysis"
           (AnalysisRunner with the analyzers)
  check    the check spec (reference.expand)
  row_group_rows  the Parquet file's row group size
"""

from __future__ import annotations

import os
import time

from benchmark.data import cache, tpch
from benchmark.harness import program
from benchmark.harness.core import Call
from benchmark.reference.reference import Reference, assertion, expand


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.p = ctx.traffic
        cfg = ctx.config
        self.rows = int(cfg["rows"])
        self.domains = cfg["columns"]
        self.metrics = expand(self.p["check"], list(self.domains))
        self.table = self.path = None
        self.outputs = []

    def prepare(self) -> None:
        ctx, p = self.ctx, self.p
        t0 = time.perf_counter()
        self.cols = tpch.lineitem(self.rows, ctx.seed, float(ctx.config["scale"]))
        ctx.log(f"data: {self.rows} rows generated in {time.perf_counter() - t0:.3f}s")
        if p["source"] == "parquet":
            self.path = os.path.join(cache.directory(ctx.config["name"], self.rows, ctx.seed),
                                     "lineitem.parquet")
            t0 = time.perf_counter()
            wrote = cache.parquet(self.path, lambda: tpch.to_arrow(self.cols),
                                  int(p["row_group_rows"]))
            ctx.log(f"data: parquet {'written' if wrote else 'found in the cache'}"
                    f" ({os.path.getsize(self.path)} bytes) in "
                    f"{time.perf_counter() - t0:.3f}s")
        else:
            from deequ_tpu import Table

            t0 = time.perf_counter()
            self.table = Table.from_arrow(tpch.to_arrow(self.cols, dictionary=False))
            ctx.log(f"data: table built in {time.perf_counter() - t0:.3f}s")
        if p["run"] == "suite":
            self.check = program.check(ctx.cell["name"], self.metrics, self.domains)
        else:
            self.analyzers = program.analyzers(self.metrics)

    def _data(self):
        if self.path is None:
            return self.table
        from deequ_tpu import Table

        return Table.scan_parquet(self.path)

    def _run(self):
        from deequ_tpu import VerificationSuite
        from deequ_tpu.runners.analysis_runner import AnalysisRunner

        data = self._data()
        if self.p["run"] == "suite":
            return VerificationSuite().on_data(data).add_check(self.check).run()
        return AnalysisRunner.on_data(data).add_analyzers(self.analyzers).run()

    def warm(self) -> None:
        self._run()

    def step(self) -> Call:
        t0 = time.perf_counter()
        out = self._run()
        t1 = time.perf_counter()
        if self.p["run"] == "suite":
            self.outputs.append(program.verdict(out))
        else:
            self.outputs.append(("", [(v, None) for v in
                                      program.metric_values(out, self.analyzers)]))
        return Call(rows=self.rows, t0=t0, t1=t1)

    def release(self) -> None:
        self.table = None

    def verify(self, card) -> None:
        ref = Reference(self.cols, self.domains)
        suite = self.p["run"] == "suite"
        expected = [(ref.value(m), m) for m in self.metrics]
        want_status = [
            "SUCCESS" if assertion(m, self.domains)(v) else "FAILURE"
            for v, m in expected
        ]
        for call, (status, rows) in enumerate(self.outputs):
            if len(rows) != len(self.metrics):
                card.failures.append(f"call {call}: {len(rows)} results for "
                                     f"{len(self.metrics)} metrics")
                continue
            wrong = 0
            for (got, st), (want, m), ws in zip(rows, expected, want_status):
                card.metric(m, got, ref, want, f"call {call} {m.family}{m.columns}")
                if suite and st != ws:
                    wrong += 1
            if suite:
                overall = "SUCCESS" if all(s == "SUCCESS" for s in want_status) else "ERROR"
                wrong += status != overall
                card.count("verdicts_wrong", wrong, f"call {call}")


"""Closed loop of column profiles, one client: each call builds a fresh
`Table` from the Arrow table held in host memory and profiles it with
`ColumnProfilerRunner`'s defaults, as a profiling job does with each
table it takes in.

Set-up generates the configuration's table and holds it as Arrow with
plain string columns (`tpch.to_arrow(..., dictionary=False)`), then
makes one warm call. A call is `Table.from_arrow` and the profile, so
the per-column memos of a table (string codes, type classes) are paid
in every call. `verify` holds every call's profile to
`benchmark/reference/profile.py`.

The traffic mix's file has no parameters beyond its `kind`: the
profile's options are upstream's defaults.
"""

from __future__ import annotations

import time

from benchmark.data import tpch
from benchmark.harness.core import Call
from benchmark.reference.profile import ProfileReference, fields

# what each call's `runtime.monitored()` counted, logged summed over the
# window; a field the program lacks is left out
COUNTS = (
    "device_passes", "device_launches", "group_passes",
    "quantile_finish_hist16", "quantile_finish_sorted",
    "profile_passes", "profile_histograms", "profile_stragglers",
    "profile_cast_columns",
)


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.rows = int(ctx.config["rows"])
        self.outputs = []
        self.counts = {}

    def prepare(self) -> None:
        ctx = self.ctx
        t0 = time.perf_counter()
        self.cols = tpch.lineitem(self.rows, ctx.seed, float(ctx.config["scale"]))
        ctx.log(f"data: {self.rows} rows generated in {time.perf_counter() - t0:.3f}s")
        t0 = time.perf_counter()
        self.arrow = tpch.to_arrow(self.cols, dictionary=False)
        ctx.log(f"data: arrow table ({self.arrow.nbytes} bytes) in "
                f"{time.perf_counter() - t0:.3f}s")

    def _run(self):
        from deequ_tpu import Table
        from deequ_tpu.profiles.runner import ColumnProfilerRunner

        return ColumnProfilerRunner.on_data(Table.from_arrow(self.arrow)).run()

    def warm(self) -> None:
        self._run()

    def step(self) -> Call:
        from deequ_tpu.ops import runtime

        with runtime.monitored() as stats:
            t0 = time.perf_counter()
            out = self._run()
            t1 = time.perf_counter()
        self.outputs.append(fields(out))
        for name in COUNTS:
            if hasattr(stats, name):
                self.counts[name] = self.counts.get(name, 0) + getattr(stats, name)
        return Call(rows=self.rows, t0=t0, t1=t1)

    def release(self) -> None:
        self.arrow = None
        self.ctx.log(f"profile counters over {len(self.outputs)} calls: {self.counts}")

    def verify(self, card) -> None:
        ref = ProfileReference(self.cols)
        for call, got in enumerate(self.outputs):
            ref.compare(got, card, f"call {call}")

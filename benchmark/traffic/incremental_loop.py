"""Closed loop over landing partitions whose metrics are kept current, one
client: step k opens chain day k's Parquet file, folds it, merges its
states into the stored states of chain days 0..k-1, persists the merged
states and checks them, as upstream deequ's IncrementalMetricsExample
does each day:

  VerificationSuite().on_data(Table.scan_parquet(<day k>)).add_check(check)
      .aggregate_with(FileSystemStateProvider(<prefix k-1>))
      .save_states_with(FileSystemStateProvider(<prefix k>)).run()

There is no loader at k = 0. Each step makes fresh provider objects, so
the states come back from the files, as a new daily process would read
them. Prefixes are by chain index, so a wrap around the pool of days
overwrites nothing; the states of day k-2 are deleted after step k,
outside its clock. The states live under the checkout (ignored by git),
as a deployment keeps them on its disk; on the benchmark's chip host the
checkout, like `TMPDIR` there, is a 9p mount. The latency of a verdict
runs from opening the partition to holding the VerificationResult, with
the new states written.
Every run starts with no state.

The days and the check are the gate's (partition_loop): the
configuration's `days`, generated once per checkout and seed, walked in
order and wrapped around. Parameters as partition_loop's.
"""

from __future__ import annotations

import glob
import os
import shutil
import tempfile
import time

from benchmark.harness.core import HERE
from benchmark.reference.cumulative import CumulativeReference, chain_days
from benchmark.traffic.partition_loop import Driver as PartitionLoop

# the states of a run, in the checkout (ignored by git)
STATE_ROOT = os.path.join(HERE, ".cache", "state")


class Driver(PartitionLoop):
    def prepare(self) -> None:
        super().prepare()
        os.makedirs(STATE_ROOT, exist_ok=True)
        self.state_dir = tempfile.mkdtemp(prefix="run-", dir=STATE_ROOT)

    def _prefix(self, k: int) -> str:
        return os.path.join(self.state_dir, f"chain-{k:06d}")

    def _verify_next(self):
        from deequ_tpu import Table, VerificationSuite
        from deequ_tpu.analyzers.state_provider import FileSystemStateProvider

        k = self.chain
        t0 = time.perf_counter()
        source = Table.scan_parquet(self.paths[k % self.pool])
        suite = VerificationSuite().on_data(source).add_check(self.check)
        if k:
            suite = suite.aggregate_with(FileSystemStateProvider(self._prefix(k - 1)))
        result = suite.save_states_with(FileSystemStateProvider(self._prefix(k))).run()
        t1 = time.perf_counter()
        self.chain += 1
        for path in glob.glob(self._prefix(k - 2) + "-*"):
            os.remove(path)
        return k, result, t0, t1

    def release(self) -> None:
        shutil.rmtree(self.state_dir, ignore_errors=True)

    def verify(self, card) -> None:
        """Every verdict, warm-up included, against one pass over chain
        days 0..k."""
        ref = CumulativeReference(self.domains, self.metrics)
        outputs = dict(self.outputs)
        for k, (d, cols) in enumerate(chain_days(self.ctx.config, self.ctx.seed, self.chain)):
            ref.add(d, cols)
            if k not in outputs:
                continue
            status, rows = outputs[k]
            if len(rows) != len(self.metrics):
                card.failures.append(f"verdict {k}: {len(rows)} results for "
                                     f"{len(self.metrics)} metrics")
                continue
            wrong = 0
            want_all = True
            for (got, st), m in zip(rows, self.metrics):
                card.metric(m, got, ref, ref.value(m), f"verdict {k} {m.family}{m.columns}")
                ws = "SUCCESS" if ref.expected(m, self.domains) else "FAILURE"
                want_all &= ws == "SUCCESS"
                wrong += st != ws
            wrong += status != ("SUCCESS" if want_all else "ERROR")
            card.count("verdicts_wrong", wrong, f"verdict {k}")

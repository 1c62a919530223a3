"""Self time of the program's `wait` spans on the calling thread (the
thread of the root spans): the caller blocked on a pipeline stage's queue
(ops/pipeline.py, data/source.py), per verdict, ms.
"""

from benchmark.harness.spans import ms_per_call, self_seconds


def read(run):
    caller = {r.tid for r in run.spans}
    return ms_per_call(run, self_seconds(
        run.spans, lambda s: s.name == "wait" and s.tid in caller))

"""Self time of the program's `assisted_fold` spans (ops/fused.py
`PipelinedAggFold._fold_assisted`: each device-assisted member's per-shard
artifacts, such as a quantile's sorted sample or its hist16 counts,
finished against the batch's host columns and consumed, shard by shard),
ms per million rows. None where no such span opened.
"""

from benchmark.harness.spans import ms_per_mrow, self_seconds


def read(run):
    return ms_per_mrow(run, self_seconds(run.spans, lambda s: s.name == "assisted_fold"))

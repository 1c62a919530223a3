"""Self time of the program's `h2d` spans (ops/fused.py pack_batch_inputs:
the host-to-device puts of the wire buffers, for as long as they hold
the host), ms per million rows.
"""

from benchmark.harness.spans import ms_per_mrow, self_seconds


def read(run):
    return ms_per_mrow(run, self_seconds(run.spans, lambda s: s.name == "h2d"))

"""Self time of the program's `pack` spans (ops/fused.py pack_batch_inputs:
a batch's inputs packed into wire buffers, less the puts), ms per million rows.
"""

from benchmark.harness.spans import ms_per_mrow, self_seconds


def read(run):
    return ms_per_mrow(run, self_seconds(run.spans, lambda s: s.name == "pack"))

"""Self time of the program's `dispatch` spans (input packing in
ops/fused.py:pack_batch_inputs, device_put, program launch), ms per
million rows.
"""

from benchmark.harness.spans import ms_per_mrow, of_category, self_seconds


def read(run):
    return ms_per_mrow(run, self_seconds(run.spans, of_category("dispatch")))

"""Busy time of the read and decode stages per verdict, ms (as
decode_ms_per_Mrow).
"""

from benchmark.harness.spans import decoding, ms_per_call, self_seconds


def read(run):
    return ms_per_call(run, self_seconds(run.spans, decoding))

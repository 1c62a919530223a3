"""Busy time of the read and decode stages (data/source.py,
data/native_reader.py, data/arrow_decode.py, data/encfold.py): self time
of `pipe_item` spans of the read and decode stages and of `read` and
`decode` spans, ms per million rows.
"""

from benchmark.harness.spans import decoding, ms_per_mrow, self_seconds


def read(run):
    return ms_per_mrow(run, self_seconds(run.spans, decoding))

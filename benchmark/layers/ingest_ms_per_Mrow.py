"""Self time of the program's `table_from_arrow` spans (data/table.py
`Table.from_arrow`: an Arrow table a caller hands over, decoded into the
engine's columns), ms per million rows. None where no such span opened.
"""

from benchmark.harness.spans import ms_per_mrow, self_seconds


def read(run):
    return ms_per_mrow(run, self_seconds(run.spans, lambda s: s.name == "table_from_arrow"))

"""Self time of the program's `profile_finish` spans
(profiles/column_profiler.py: a profile's work after its first scan,
such as statistics extraction, type inference, the histograms and the
profiles themselves; any later scan is a child and is left out), ms per
million rows. None where no such span opened.
"""

from benchmark.harness.spans import ms_per_mrow, self_seconds


def read(run):
    return ms_per_mrow(run, self_seconds(run.spans, lambda s: s.name == "profile_finish"))

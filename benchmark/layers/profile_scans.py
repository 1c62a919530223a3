"""Scans a column profile makes: the program's `profile_pass` spans
(profiles/column_profiler.py, one for each scan of the data) over the
window's calls. None where no such span opened.
"""

from benchmark.harness.spans import walk


def read(run):
    scans = sum(1 for s, _depth in walk(run.spans) if s.name == "profile_pass")
    return scans / len(run.calls) if scans and run.calls else None

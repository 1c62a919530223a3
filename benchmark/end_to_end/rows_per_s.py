"""Rows of every call completed in the window over the time from the
window's start to the end of its last call."""


def read(run):
    if not run.calls:
        return None
    return run.rows / (run.window[1] - run.window[0])

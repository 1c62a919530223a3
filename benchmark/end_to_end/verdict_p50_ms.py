"""Median over every verdict of the window: opening the partition to
holding its VerificationResult, in milliseconds."""

import numpy as np


def read(run):
    if not run.calls:
        return None
    return float(np.percentile([(c.t1 - c.t0) * 1e3 for c in run.calls], 50))

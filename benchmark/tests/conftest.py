"""CPU tests of the harness, at tiny sizes with x64 off (the chip's
dtype path). Not part of the repo's tier-1 suite: run them with
`JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`."""

import argparse
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)


@pytest.fixture
def run_cell(tmp_path, monkeypatch):
    """Drive one run of a cell on the CPU, past the harness's look for a
    chip, at a tiny size; returns (result line, scorecard)."""
    from benchmark.data import cache
    from benchmark.harness import core

    monkeypatch.setattr(cache, "CACHE", str(tmp_path / "data"))

    def go(cell, rows, days=4, seconds=1.0, trace=0, seed=2**31 + 11, log=None):
        args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds, trace=trace)
        ctx = core.context(args, jax.devices())
        ctx.config["rows"] = rows
        ctx.config["days"] = days
        ctx.log = log or (lambda msg: None)
        return core.run(ctx, time.perf_counter())

    return go

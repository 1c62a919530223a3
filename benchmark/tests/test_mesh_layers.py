"""The four-chip cell's readers against a hand-built run: the collective
time of the device that spent the most in collectives, and the self time
of the `assisted_fold` spans; each finds nothing where its source is
missing, and the merge category still holds the assisted fold."""

import os

import pytest

from benchmark.harness.core import HERE, Call, Run, load_module
from benchmark.trace.xplane import Window


class S:
    def __init__(self, name, cat, t0, t1, children=(), **attrs):
        self.name, self.cat, self.t0, self.t1 = name, cat, t0, t1
        self.children, self.attrs = list(children), attrs


def reader(name):
    return load_module(os.path.join(HERE, "layers", f"{name}.py"), f"t_{name}")


CALLS = [Call(2_000_000, 0.0, 5.0), Call(2_000_000, 5.0, 10.0)]  # 4 Mrow


def _run(spans, device):
    return Run(CALLS, 0, (0.0, 10.0), 30.0, 4, "TPU v5 lite", spans, device, 0)


FOREST = [
    S("dist_scan", "scan", 0.0, 10.0, [
        S("merge", "merge", 4.0, 7.0, [
            # 2.0 s of its own, less a child of 0.5 s
            S("assisted_fold", "merge", 4.5, 6.5, [S("sort", "host", 5.0, 5.5)],
              shards=4, members=2),
        ]),
        S("assisted_fold", "merge", 8.0, 8.25, shards=4, members=2),
    ])
]


@pytest.fixture
def run():
    device = {
        0: Window(3e9, 0.02e9, {}, [(0, 3e9)]),
        1: Window(2e9, 0.06e9, {}, [(0, 2e9)]),  # the most collective time
        2: Window(2e9, 0.0, {}, [(0, 2e9)]),
        3: Window(1e9, 0.01e9, {}, [(0, 1e9)]),
    }
    return _run(FOREST, device)


def test_collective_reads_the_device_with_the_most(run):
    assert reader("collective_ms_per_Mrow").read(run) == pytest.approx(60.0 / 4)


def test_assisted_fold_is_self_time(run):
    # (2.0 - 0.5) + 0.25 s over 4 Mrow
    assert reader("assisted_fold_ms_per_Mrow").read(run) == pytest.approx(1.75e3 / 4)


def test_merge_category_keeps_the_assisted_fold(run):
    # merge's own 3.0 - 2.0, plus the assisted folds' 1.75
    assert reader("merge_ms_per_Mrow").read(run) == pytest.approx(2.75e3 / 4)


@pytest.mark.parametrize("device", [
    None,  # no trace
    {0: Window(1e9, 0.0, {}, [(0, 1e9)])},  # one chip: no collective
])
def test_collective_finds_nothing(device):
    assert reader("collective_ms_per_Mrow").read(_run(FOREST, device)) is None


def test_assisted_fold_finds_nothing_without_the_span():
    spans = [S("fused_scan", "scan", 0.0, 1.0, [S("merge", "merge", 0.0, 0.5)])]
    assert reader("assisted_fold_ms_per_Mrow").read(_run(spans, None)) is None

"""The readers of the input build, stage waits, group-pass phases and the
pack and put phases of dispatch, against a hand-built span forest on two
threads: self time is a span's duration less its children's, and
`stage_wait_ms.partition` counts only the calling thread's waits."""

import os

import pytest

from benchmark.harness.core import HERE, Call, Run, load_module

CALLER, PREP = 1, 2


class S:
    def __init__(self, name, cat, t0, t1, children=(), tid=CALLER, **attrs):
        self.name, self.cat, self.t0, self.t1 = name, cat, t0, t1
        self.children, self.attrs, self.tid = list(children), attrs, tid


def reader(name):
    return load_module(os.path.join(HERE, "layers", f"{name}.py"), f"t_{name}")


@pytest.fixture
def run():
    prep = S("pipe_stage", "pipeline", 0.0, 6.0, [
        S("wait", "wait", 0.0, 0.5, tid=PREP, on="decode"),
        S("pipe_item", "pipeline", 0.5, 4.0, [
            S("build", "build", 0.5, 3.0, [S("native:x", "native", 1.0, 1.5, tid=PREP)],
              tid=PREP, key="pred"),
            S("dispatch", "dispatch", 3.0, 4.0, [
                S("pack", "dispatch", 3.0, 4.0, [
                    S("h2d", "dispatch", 3.5, 3.75, tid=PREP, bytes=8),
                ], tid=PREP),
            ], tid=PREP),
        ], tid=PREP),
    ], tid=PREP)
    forest = [
        S("verification_suite", "run", 0.0, 10.0, [
            S("pipe_stage", "pipeline", 0.0, 6.0, [
                prep,
                S("wait", "wait", 0.0, 4.0, on="prep"),
                S("pipe_item", "pipeline", 4.0, 6.0, [S("build", "build", 4.0, 4.5)]),
            ]),
            S("group_pass", "group", 6.0, 10.0, [
                S("wait", "wait", 6.0, 6.25, on="decode"),
                S("group_encode", "group", 6.25, 7.25, rows=10, columns=2),
                S("group_count", "group", 7.25, 8.75, rows=10, groups=3),
                S("group_merge", "group", 8.75, 9.75, groups=3, spilled=False),
            ]),
        ])
    ]
    calls = [Call(1_000_000, 0.0, 5.0), Call(1_000_000, 5.0, 10.0)]
    return Run(calls, 0, (0.0, 10.0), 30.0, 1, "TPU v5 lite", forest, None, 0)


@pytest.mark.parametrize("name, want", [
    # the prep thread's build less its native child, and the caller's
    ("build_ms.partition", (2.5 - 0.5 + 0.5) * 1e3 / 2),
    ("build_ms_per_Mrow", (2.5 - 0.5 + 0.5) * 1e3 / 2),
    # the caller's two waits; the prep thread's wait on decode is not its
    ("stage_wait_ms.partition", (4.0 + 0.25) * 1e3 / 2),
    ("group_encode_ms_per_Mrow", 1.0e3 / 2),
    ("group_count_ms_per_Mrow", 1.5e3 / 2),
    ("group_merge_ms_per_Mrow", 1.0e3 / 2),
    ("pack_ms_per_Mrow", 0.75e3 / 2),  # less its put
    ("h2d_ms_per_Mrow", 0.25e3 / 2),
])
def test_reader(run, name, want):
    assert reader(name).read(run) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "build_ms.partition", "stage_wait_ms.partition", "build_ms_per_Mrow",
    "group_encode_ms_per_Mrow", "group_count_ms_per_Mrow",
    "group_merge_ms_per_Mrow", "pack_ms_per_Mrow", "h2d_ms_per_Mrow",
])
def test_reader_finds_nothing(name):
    """A program without these spans (the parent of the change that
    added them) gives no value, and no error."""
    empty = Run([Call(1000, 0.0, 1.0)], 0, (0.0, 1.0), 1.0, 1, "TPU v5 lite",
                [S("x", "scan", 0.0, 1.0, [S("group_pass", "group", 0.0, 1.0)])],
                None, 0)
    assert reader(name).read(empty) is None


def test_stage_wait_skips_other_threads_waits():
    only_prep = Run([Call(1000, 0.0, 1.0)], 0, (0.0, 1.0), 1.0, 1, "TPU v5 lite",
                    [S("run", "run", 0.0, 1.0, [
                        S("wait", "wait", 0.0, 0.5, tid=PREP, on="decode")])],
                    None, 0)
    assert reader("stage_wait_ms.partition").read(only_prep) is None

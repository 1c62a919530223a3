"""The per-layer readers against a hand-built span forest and device
reduction: self time is a span's duration less its children's."""

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


@pytest.fixture
def run():
    forest = [
        S("fused_scan", "scan", 0.0, 10.0, [
            S("plan_fuse", "plan", 0.0, 1.0),
            S("dispatch", "dispatch", 1.0, 3.0, [S("pack", "host", 1.5, 2.0)],
              wire_bytes=819e6),
            S("transfer", "transfer", 3.0, 4.0),
            S("host_fold", "host", 4.0, 6.0, [S("kernel", "native", 4.0, 5.0)]),
            S("merge", "merge", 6.0, 6.5),
            S("group_pass", "group", 6.5, 9.0, [S("freq_agg", "group", 7.0, 8.0)]),
            S("pipe_item", "pipeline", 9.0, 9.5, stage="decode"),
            S("page_read", "read", 9.5, 9.75),
            S("constraint_eval", "constraint", 9.75, 10.0),
        ])
    ]
    device = {0: Window(2e9, 0.5e9, {"fusion": 2e9}, [(0, 2e9)]),
              1: Window(1e9, 0.0, {}, [(0, 1e9)])}
    calls = [Call(1_000_000, 0.0, 5.0), Call(1_000_000, 5.0, 10.0)]
    return Run(calls, 0, (0.0, 10.0), 30.0, 2, "TPU v5 lite", forest, device, 0)


@pytest.mark.parametrize("name, want", [
    ("group_ms_per_Mrow", 2.5e3 / 2),  # 1.5 s + 1.0 s over 2 Mrow
    ("host_fold_ms_per_Mrow", (0.5 + 1.0 + 1.0) * 1e3 / 2),
    ("dispatch_ms_per_Mrow", 1.5e3 / 2),
    ("device_wait_ms_per_Mrow", 1e3 / 2),
    ("merge_ms_per_Mrow", 0.5e3 / 2),
    ("decode_ms_per_Mrow", 0.75e3 / 2),
    ("fold_device_ms_per_Mrow", 2e3 / 2),  # the busiest device
    # 819 MB over 2 chips at 819 GB/s is 0.5 ms, over 2 s busy
    ("fold_roofline", 100 * 0.5e-3 / 2.0),
    ("plan_ms.partition", 1e3 / 2),
    ("decode_ms.partition", 0.75e3 / 2),
    ("fold_ms.partition", (1.5 + 1.0 + 0.5 + 1.0 + 1.0 + 0.5) * 1e3 / 2),
    ("evaluate_ms.partition", 0.25e3 / 2),
    ("compiles.partition", 0.0),
])
def test_reader(run, name, want):
    assert reader(name).read(run) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "group_ms_per_Mrow", "decode_ms_per_Mrow", "fold_device_ms_per_Mrow",
    "fold_roofline", "plan_ms.partition",
])
def test_reader_finds_nothing(name):
    empty = Run([Call(1000, 0.0, 1.0)], 0, (0.0, 1.0), 1.0, 1, "TPU v5 lite",
                [S("x", "scan", 0.0, 1.0)], None, 0)
    assert reader(name).read(empty) is None


def test_every_benchmark_metric_has_a_reader():
    import json

    from benchmark.harness.core import ROOT

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(HERE, "layers", f"{m['name']}.py"))
    for m in bench["end_to_end"]:
        assert os.path.exists(os.path.join(HERE, "end_to_end", f"{m['name']}.py"))
    for c in bench["workloads"]:
        assert os.path.exists(os.path.join(HERE, "traffic", f"{c['traffic']}.json"))

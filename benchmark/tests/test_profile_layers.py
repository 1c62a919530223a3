"""The profile cell's readers against hand-built runs: the self time of
`table_from_arrow` and of `profile_finish` (less any scan nested in it),
and the `profile_pass` spans a call; each finds nothing where its spans
are missing, as in a run of a program that has none."""

import os

import pytest

from benchmark.harness.core import HERE, Call, Run, load_module


class S:
    def __init__(self, name, cat, t0, t1, children=(), **attrs):
        self.name, self.cat, self.t0, self.t1 = name, cat, t0, t1
        self.children, self.attrs = list(children), attrs


def reader(name):
    return load_module(os.path.join(HERE, "layers", f"{name}.py"), f"t_{name}")


CALLS = [Call(2_000_000, 0.0, 5.0), Call(2_000_000, 5.0, 10.0)]  # 4 Mrow


def _run(spans):
    return Run(CALLS, 0, (0.0, 10.0), 30.0, 1, "TPU v5 lite", spans, None, 0)


def _call(t, passes=1):
    """One call: the table built, then the profile; each scan after the
    first nested in the finish, as passes 2 and 3 are."""
    later = [S("profile_pass", "profile", t + 3.0, t + 3.5, [
        S("analysis_run", "run", t + 3.0, t + 3.5)], **{"pass": p})
        for p in range(2, passes + 1)]
    return [
        S("table_from_arrow", "ingest", t, t + 1.0, rows=2_000_000, columns=16),
        S("profile", "profile", t + 1.0, t + 5.0, [
            S("profile_pass", "profile", t + 1.0, t + 2.5, [
                S("analysis_run", "run", t + 1.0, t + 2.5)], **{"pass": 1}),
            S("profile_finish", "profile", t + 2.5, t + 4.0, later),
        ], columns=16, passes=passes),
    ]


def test_ingest_is_the_self_time_of_table_from_arrow():
    # 1.0 s a call over 4 Mrow
    assert reader("ingest_ms_per_Mrow").read(_run(_call(0.0) + _call(5.0))) == pytest.approx(
        2e3 / 4)


def test_profile_finish_leaves_out_a_nested_scan():
    # 1.5 s, then 1.5 less a 0.5 s scan
    spans = _call(0.0) + _call(5.0, passes=2)
    assert reader("profile_finish_ms_per_Mrow").read(_run(spans)) == pytest.approx(2.5e3 / 4)


@pytest.mark.parametrize("passes, want", [((1, 1), 1.0), ((1, 2), 1.5), ((3, 3), 3.0)])
def test_profile_scans_a_call(passes, want):
    spans = _call(0.0, passes[0]) + _call(5.0, passes[1])
    assert reader("profile_scans").read(_run(spans)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["ingest_ms_per_Mrow", "profile_finish_ms_per_Mrow",
                                  "profile_scans"])
def test_nothing_without_the_spans(name):
    # the suite's forest, as a program without the profile's spans leaves it
    spans = [S("analysis_run", "run", 0.0, 4.0, [S("fused_scan", "scan", 0.0, 4.0)])]
    assert reader(name).read(_run(spans)) is None
    assert reader(name).read(_run([])) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_is_correct(run_cell, trace):
    line, card = run_cell("lineitem-sf1-profile", rows=5_000, trace=trace)
    assert line["correct"] and line["failed"] == 0, card.failures
    metrics = line["metrics"]
    if trace:
        assert metrics["profile_scans"]["value"] == 1.0
        assert {"ingest_ms_per_Mrow", "profile_finish_ms_per_Mrow", "build_ms_per_Mrow",
                "assisted_fold_ms_per_Mrow", "pack_ms_per_Mrow"} <= set(metrics)
    else:
        assert set(metrics) == {"rows_per_s", "setup_s"}

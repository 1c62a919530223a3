"""The trace reduction on a hand-built trace, in the text form of the
profiler's XSpace (times in picoseconds there, nanoseconds after
reading)."""

import pytest

from benchmark.trace import xplane

TRACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 1000000 }
  }
  lines {
    id: 2
    name: "XLA Modules"
    timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 11000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%all-gather.3 = f32[32]{0} all-gather(f32[8]{0} %y), dimensions={0}" } }
  event_metadata { key: 3 value { id: 3 name: "jit_fused" } }
}
planes {
  id: 2
  name: "/device:TPU:1"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
planes {
  id: 3
  name: "/host:CPU"
  lines {
    id: 1
    name: "python3"
    timestamp_ns: 500
    events { metadata_id: 1 offset_ps: 100000 duration_ps: 1000 }
  }
  event_metadata { key: 1 value { id: 1 name: "deequ_bench_anchor" } }
}
"""


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData

    return xplane.from_profile(ProfileData.from_text_proto(TRACE))


def test_reads_device_ops_and_anchor(trace):
    assert trace.anchor_ns == 600
    assert [o.name for o in trace.ops[0]] == [
        "%fusion.1 fusion", "%all-gather.3 all-gather", "%fusion.1 fusion"]
    assert trace.ops[0][1] == xplane.Op("%all-gather.3 all-gather", 2000, 5000)
    assert trace.ops[1][0].name == "fusion.1"  # a name that is no HLO text stays
    assert set(trace.ops) == {0, 1}  # the module line is not an op


def test_busy_is_the_union_of_ops_in_the_window(trace):
    w = xplane.reduce(trace, 0, 1e9)
    # [1000, 5000) and [11000, 12000): overlapping ops count once
    assert w[0].busy_ns == 5000
    assert w[0].collective_ns == 3000
    assert w[0].op_ns == {"%fusion.1 fusion": 3000, "%all-gather.3 all-gather": 3000}
    assert w[1].busy_ns == 500
    clipped = xplane.reduce(trace, 4000, 11500)
    assert clipped[0].busy_ns == 1500
    assert clipped[0].op_ns["%all-gather.3 all-gather"] == 1000


def test_gaps_and_their_attribution(trace):
    w = xplane.reduce(trace, 0, 1e9)[0]
    gaps = xplane.gaps(w.busy, 0, 13000)
    assert gaps == [(0, 1000), (5000, 11000), (12000, 13000)]
    host = [("scan", 0, 20000, 0), ("group_pass", 6000, 9000, 1),
            ("transfer", 7000, 8500, 2)]
    named = xplane.attribute(gaps, host)
    # midpoint 8000 lies in all three; the deepest wins
    assert named == [("scan", 1000), ("transfer", 6000), ("scan", 1000)]
    assert xplane.attribute([(30000, 31000)], host) == [("(no program span)", 1000)]


def test_peaks_are_a_table_with_no_default():
    assert xplane.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        xplane.peaks("TPU v9 imaginary")


def test_recorded_v5e_trace():
    """A trace recorded on one TPU v5e (my chip run, PR 22): three rounds
    of a reduce and a sort, each awaited, after the anchor. The device's
    first op starts about 1 ms before the host's anchor although it was
    launched after it: the device clock reads that much behind the host's
    in this trace, an error the window's edges and the gaps carry."""
    import os

    from jax.profiler import ProfileData

    path = os.path.join(os.path.dirname(__file__), "data", "v5e_probe.xplane.pb")
    t = xplane.from_profile(ProfileData.from_file(path))
    assert t.anchor_ns == 45146306.0
    assert list(t.ops) == [0] and len(t.ops[0]) == 15
    assert t.ops[0][0].start == 44152415.0
    assert xplane.reduce(t, 0, 1e18)[0].busy_ns == 6825546.0
    w = xplane.reduce(t, t.anchor_ns, 1e18)[0]
    assert w.busy_ns == 6722507.0
    assert w.collective_ns == 0
    assert max(w.op_ns, key=w.op_ns.get) == "%sort.6 sort"

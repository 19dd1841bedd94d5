import json
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import trace


def _ev(meta, start_ns, dur_ns, scope=None):
    stats = f'stats {{ metadata_id: 1 str_value: "jit(_run)/parentt.{scope}/op" }}' if scope else ""
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000} "
            f"duration_ps: {dur_ns * 1000} {stats} }}")


# Ops over 10 us: overlapping cascade ops, the CRT stages, two ops with
# no scope, and host annotations over two of the three gaps.
SYNTHETIC = f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    {_ev(9, 0, 5000)} {_ev(9, 8000, 1000)} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    {_ev(1, 0, 1000, "cascade")} {_ev(1, 500, 1000, "cascade")}
    {_ev(2, 3000, 1000, "compose")} {_ev(3, 4000, 1000, "decompose")}
    {_ev(4, 8000, 1000)} {_ev(4, 9500, 500)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fusion.1" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "fusion.2" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "fusion.3" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "copy.4" }} }}
  event_metadata {{ key: 9 value {{ id: 9 name: "jit__run" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    {_ev(1, 1400, 1100)} {_ev(2, 6000, 1000)} {_ev(3, 9600, 100)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "engine.dispatch" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "client.submit" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "something.else" }} }}
}}
planes {{ id: 3 name: "Task Environment"
  stats {{ metadata_id: 1 uint64_value: 1000000 }}
  stats {{ metadata_id: 2 uint64_value: 1010000 }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "profile_start_time" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "profile_stop_time" }} }}
}}
'''


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_profile(ProfileData.from_text_proto(SYNTHETIC))


def test_busy_is_the_union_of_op_intervals(reduced):
    assert reduced.window_s == pytest.approx(10e-6)
    assert reduced.busy_s == pytest.approx(5e-6)  # [0,1.5) [3,5) [8,9) [9.5,10) us


def test_scope_time_by_innermost_parentt_name(reduced):
    assert reduced.scope_s == pytest.approx(
        {"cascade": 2e-6, "compose": 1e-6, "decompose": 1e-6, "unattributed": 1.5e-6})
    assert reduced.unattributed_share() == pytest.approx(1.5 / 5.5)
    assert reduced.modules == 2


def test_idle_gaps_named_by_host_annotation(reduced):
    gaps = {label: (s, n) for label, s, n in reduced.gaps}
    assert gaps["engine.dispatch"] == (pytest.approx(1.5e-6), 1)
    assert gaps["client.submit"] == (pytest.approx(3e-6), 1)
    assert gaps["host.other"] == (pytest.approx(0.5e-6), 1)
    br = reduced.breakdown()
    assert br["idle_gaps"][0] == ["client.submit x1", pytest.approx(3e-6)]
    assert br["device_ops"][0] == ["cascade:fusion.1", pytest.approx(2e-6)]


HLO = """
%fused_computation.7 (param_0: u32[8]) -> u32[8] {
  %param_0 = u32[8]{0} parameter(0)
  ROOT %multiply.3 = u32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(_run)/parentt.polymul/parentt.cascade/mul"}
}

ENTRY %main.9 (Arg_0: u32[8]) -> u32[8] {
  %Arg_0 = u32[8]{0} parameter(0)
  %copy.2 = u32[8]{0} copy(%Arg_0), metadata={op_name="jit(_run)/parentt.polymul/parentt.decompose/ntt"}
  %fusion.7 = u32[8]{0} fusion(%copy.2), kind=kLoop, calls=%fused_computation.7
  ROOT %add.1 = u32[8]{0} add(%fusion.7, %fusion.7), metadata={op_name="jit(_run)/parentt.polymul/x"}
}
"""


def test_scopes_from_hlo_text():
    scopes = trace.scopes_from_hlo(HLO)
    assert scopes["copy.2"] == "decompose"  # the stage, not an inner name
    assert scopes["fusion.7"] == "cascade"  # from its fused computation
    assert scopes["add.1"] == "polymul"  # no stage: the innermost scope
    assert "Arg_0" not in scopes


def test_trace_names_ops_by_hlo_instruction():
    txt = SYNTHETIC.replace('name: "copy.4"', 'name: "%copy.2 = u32[8]{0} copy(%Arg_0)"')
    red = trace.reduce_profile(ProfileData.from_text_proto(txt),
                               trace.scopes_from_hlo(HLO))
    assert red.scope_s["decompose"] == pytest.approx(2.5e-6)
    assert "unattributed" not in red.scope_s


def test_no_device_plane_is_an_error():
    host_only = SYNTHETIC.split('planes { id: 2')[0].replace("/device:TPU:0", "/host:CPU")
    with pytest.raises(ValueError, match="no TPU device plane"):
        trace.reduce_profile(ProfileData.from_text_proto(host_only))


DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def chip_trace():
    """90 ms of `paper-n4096.closed32` traced on one TPU v5e: the TPU
    plane's XLA Ops and XLA Modules lines and the benchmark's host
    annotations, cut from a 0.5 s trace, with the scopes of the ops it
    names from the executable's HLO."""
    raw = (DATA / "chip_trace.xplane.pb").read_bytes()
    scopes = json.loads((DATA / "chip_trace.scopes.json").read_text())
    return ProfileData.from_serialized_xspace(raw), scopes


def test_recorded_chip_trace(chip_trace):
    pd, scopes = chip_trace
    red = trace.reduce_profile(pd, scopes)
    # the numbers the reduction gave on the chip host when the trace was cut
    assert red.window_s == pytest.approx(0.089510572, rel=1e-6)
    assert red.busy_s == pytest.approx(0.012292418, rel=1e-6)
    assert red.modules == 7
    assert red.scope_s == pytest.approx({"cascade": 0.006984894, "decompose": 0.003532053,
                                         "compose": 0.001534409, "unattributed": 0.000241062},
                                        rel=1e-6)
    # the idle gaps partition the window's idle time, named by annotation
    assert {label for label, _, _ in red.gaps} <= {"engine.dispatch", "host.other"}
    assert sum(s for _, s, _ in red.gaps) == pytest.approx(red.window_s - red.busy_s)


def test_recorded_chip_trace_busy_is_an_interval_union(chip_trace):
    pd, scopes = chip_trace
    plane = next(p for p in pd.planes if p.name.startswith("/device:TPU:"))
    ops = next(line for line in plane.lines if line.name == "XLA Ops")
    ivs = sorted((ev.start_ns, ev.end_ns) for ev in ops.events)
    busy, end = 0.0, float("-inf")
    for s, e in ivs:  # sweep: count only what extends past the covered end
        if e > end:
            busy += e - max(s, end)
            end = e
    red = trace.reduce_profile(pd, scopes)
    assert red.busy_s == pytest.approx(busy / 1e9, rel=1e-9)
    assert red.window_s == pytest.approx((max(e for _, e in ivs) - ivs[0][0]) / 1e9)

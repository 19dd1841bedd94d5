import pytest
from jax.profiler import ProfileData

from bench import phases, trace
from bench.tests.test_trace import SYNTHETIC, _ev

# test_trace's synthetic trace, busy over [0,1.5) [3,5) [8,9) [9.5,10) us,
# with a dispatcher thread's step and phase annotations added: the first
# gap spans readback, resolve, the step's own time and pack; the second
# upload, launch, device_wait and the step's own time; the third ends
# after the step does.
DISPATCHER = f'''
  lines {{ id: 2 name: "dispatcher" timestamp_ns: 0
    {_ev(10, 1000, 8200)} {_ev(11, 1200, 800)} {_ev(12, 2000, 400)} {_ev(13, 2600, 900)}
    {_ev(14, 4500, 1000)} {_ev(15, 5500, 500)} {_ev(16, 6000, 1000)} }}
  event_metadata {{ key: 10 value {{ id: 10 name: "engine.step" }} }}
  event_metadata {{ key: 11 value {{ id: 11 name: "engine.readback" }} }}
  event_metadata {{ key: 12 value {{ id: 12 name: "engine.resolve" }} }}
  event_metadata {{ key: 13 value {{ id: 13 name: "engine.pack" }} }}
  event_metadata {{ key: 14 value {{ id: 14 name: "engine.upload" }} }}
  event_metadata {{ key: 15 value {{ id: 15 name: "engine.launch" }} }}
  event_metadata {{ key: 16 value {{ id: 16 name: "engine.device_wait" }} }}
'''
ANCHOR = 'event_metadata { key: 3 value { id: 3 name: "something.else" } }'
WITH_PHASES = SYNTHETIC.replace(ANCHOR, ANCHOR + DISPATCHER)


def test_a_gap_is_split_between_the_phases_it_spans():
    idle = phases.idle_by_phase(ProfileData.from_text_proto(WITH_PHASES))
    assert idle == pytest.approx({
        "engine.readback": 0.5e-6, "engine.resolve": 0.4e-6, "engine.pack": 0.4e-6,
        "engine.upload": 0.5e-6, "engine.launch": 0.5e-6, "engine.device_wait": 1.0e-6,
        "engine.step.self": 1.4e-6, "host.other": 0.3e-6})


def test_idle_by_phase_partitions_the_reduction_idle_time():
    pd = ProfileData.from_text_proto(WITH_PHASES)
    red = trace.reduce_profile(pd)
    assert sum(phases.idle_by_phase(pd).values()) == pytest.approx(red.window_s - red.busy_s)


def test_phase_annotations_leave_the_reduction_unchanged():
    before = trace.reduce_profile(ProfileData.from_text_proto(SYNTHETIC))
    after = trace.reduce_profile(ProfileData.from_text_proto(WITH_PHASES))
    assert after.gaps == before.gaps
    assert after.breakdown() == before.breakdown()
    assert (after.busy_s, after.window_s, after.scope_s) == (
        before.busy_s, before.window_s, before.scope_s)


@pytest.mark.parametrize("intervals, want", [
    # a phase inside its step wins over the step
    ([(0, 10, "engine.step"), (2, 4, "engine.pack")],
     [None, "engine.step", "engine.pack", "engine.step", None]),
    # a step that starts where its first phase does still loses to it
    ([(2, 4, "engine.pack"), (2, 10, "engine.step")],
     [None, "engine.pack", "engine.step", None]),
    # three deep: the one that started last is innermost
    ([(0, 10, "a"), (1, 9, "b"), (2, 3, "c")],
     [None, "a", "b", "c", "b", "a", None]),
])
def test_innermost_interval_names_each_piece(intervals, want):
    cuts, names = phases._innermost(intervals)
    assert names == want
    assert len(cuts) == len(names) + 1


def test_a_trace_without_steps_has_no_phase_idle():
    assert phases.idle_by_phase(ProfileData.from_text_proto(SYNTHETIC)) == {}


def test_median_ms_reads_finished_spans_of_one_name():
    spans = [{"name": "engine.pack", "status": "ok", "t_start": 1.0, "t_end": 1.002},
             {"name": "engine.pack", "status": "ok", "t_start": 2.0, "t_end": 2.004},
             {"name": "engine.pack", "status": "failed", "t_start": 3.0, "t_end": 3.5},
             {"name": "request", "status": "resolved", "t_start": 0.0, "t_end": 9.0}]
    assert phases.median_ms({"spans": spans}, "engine.pack") == pytest.approx(3.0)
    assert phases.median_ms({"spans": spans}, "engine.upload") is None

import pytest

from bench import run


def _step(overlapped=None, status="ok", name="engine.step"):
    attrs = {"dispatch_index": 0}
    if overlapped is not None:
        attrs["overlapped"] = overlapped
    return {"name": name, "status": status, "t_start": 0.0, "t_end": 1.0, "attrs": attrs}


READ = run.load_reader(run.ROOT, "engine.overlap_pct")


def test_share_of_ok_steps_that_overlapped():
    spans = [_step(True), _step(True), _step(True), _step(False),
             _step(True, status="failed"),  # failed steps do not count
             _step(name="engine.pack"), {**_step(True), "name": "request"}]
    assert READ({"spans": spans}) == pytest.approx(75.0)


def test_no_attribute_reads_nothing():
    assert READ({"spans": [_step(), _step(), _step(name="engine.pack")]}) is None
    assert READ({"spans": []}) is None

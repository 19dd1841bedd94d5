import threading

import numpy as np
import pytest

from bench import loadgen


def test_arrivals_same_set_any_seed():
    a = loadgen.arrivals(500.0, 1, 10.0, np.random.default_rng(1))
    b = loadgen.arrivals(500.0, 1, 10.0, np.random.default_rng(2**33 + 5))
    assert len(a) == len(b) == 5000
    assert a[0] == b[0] == 0.0 and a[-1] < 10.0 and b[-1] < 10.0
    gaps_a = np.append(np.diff(a), 10.0 - a[-1])  # the gaps sum to the window
    gaps_b = np.append(np.diff(b), 10.0 - b[-1])
    assert np.allclose(np.sort(gaps_a), np.sort(gaps_b))
    assert not np.allclose(np.diff(a), np.diff(b))


def test_arrivals_bursts_count_queries():
    assert len(loadgen.arrivals(400.0, 20, 10.0, np.random.default_rng(0))) == 200


@pytest.mark.parametrize("traffic", [
    {"kind": "poisson"}, {"kind": "closed", "callers": 0},
    {"kind": "open", "rate_per_s": 0}, {"kind": "open", "rate_per_s": 5, "burst": 0},
])
def test_validate_refuses(traffic):
    with pytest.raises((ValueError, KeyError)):
        loadgen.validate(traffic)


class _Fut:
    """Resolves after ``delay`` seconds on a timer thread."""

    def __init__(self, delay, fail=False):
        self._ev = threading.Event()
        self.fail = fail
        threading.Timer(delay, self._ev.set).start()

    def result(self, timeout=None):
        if not self._ev.wait(timeout):
            raise TimeoutError("late")
        if self.fail:
            raise RuntimeError("broken")
        return "v"


def test_closed_loop_keeps_callers_outstanding():
    live = []
    peak = []

    def submit(k):
        live.append(k)
        peak.append(len(live) - len(done))
        return _Fut(0.01)

    done = []
    w = loadgen.drive({"kind": "closed", "callers": 4}, submit, 0.3,
                      np.random.default_rng(0), lambda r, v: done.append(r.k))
    assert max(peak) == 4
    assert len(done) == len(w.requests) > 20
    assert all(r.t_done >= r.t_sub for r in w.requests)


def test_open_loop_counts_failures_and_lateness():
    def submit(k):
        return _Fut(0.001, fail=(k % 10 == 0))

    w = loadgen.drive({"kind": "open", "rate_per_s": 200, "burst": 2}, submit, 0.5,
                      np.random.default_rng(0), lambda r, v: None)
    assert len(w.requests) == 100
    failed = [r for r in w.requests if r.t_done is None]
    assert len(failed) == 10 and all("broken" in r.error for r in failed)
    assert (w.lateness_ms() >= 0).all()

"""The traffic the benchmark offers: deterministic in the seed, the rates
and bursts it declares, and latency taken from the scheduled time."""

import json
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

import benchkit
import grids
from loads import open_loop

TRAFFIC = json.loads((benchkit.ROOT / "bench" / "traffic" /
                      "zipf80.json").read_text())


def test_schedule_is_deterministic_in_the_seed():
    a = open_loop.schedule(TRAFFIC, 2**33 + 1, 30.0, 12_960)
    b = open_loop.schedule(TRAFFIC, 2**33 + 1, 30.0, 12_960)
    c = open_loop.schedule(TRAFFIC, 2**33 + 2, 30.0, 12_960)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    assert not np.array_equal(a[1], c[1])
    # every seed offers the same number of queries
    assert a[0].shape == c[0].shape


def test_sweep_seeds_are_deterministic_and_fresh():
    s = grids.sweep_seeds(2**33 + 7, 1, 3)
    assert s == grids.sweep_seeds(2**33 + 7, 1, 3)
    assert len(set(s)) == 3
    assert s != grids.sweep_seeds(2**33 + 7, 2, 3)
    assert s != grids.sweep_seeds(2**33 + 8, 1, 3)


@pytest.mark.parametrize("seconds", [30.0, 51.0])
def test_mean_rate_and_bursts_are_as_declared(seconds):
    tr = dict(TRAFFIC, rate_qps=12.0)
    t, _ = open_loop.schedule(tr, 5, seconds, 12_960)
    assert np.all(np.diff(t) >= 0) and t[0] >= 0 and t[-1] < seconds
    period = tr["burst_every_s"]
    full = int(seconds // period) * period
    assert abs(np.sum(t < full) - tr["rate_qps"] * full) <= 1
    phase = t % period
    in_burst = (phase >= tr["burst_start_s"]) & \
        (phase < tr["burst_start_s"] + tr["burst_s"])
    burst_rate = in_burst.sum() / (tr["burst_s"] * seconds / period)
    calm_rate = (~in_burst).sum() / ((period - tr["burst_s"])
                                     * seconds / period)
    assert burst_rate / calm_rate == pytest.approx(tr["burst_factor"],
                                                   rel=0.05)


def test_popularity_is_skewed_and_moves():
    t, pos = open_loop.schedule(dict(TRAFFIC, rate_qps=200.0), 9, 20.0,
                                12_960)
    first, second = pos[t < 10.0], pos[t >= 10.0]
    top = np.bincount(first).argmax()
    # the hottest cell of the first period takes a Zipf(0.99) share ...
    assert np.mean(first == top) > 0.05
    # ... and the rotation moves it out of the hot set afterwards
    assert np.mean(second == top) < 0.01


class _SlowServer:
    """Answers every query ``delay`` s after it is submitted; the first
    submit stalls the caller ``stall`` s, as a busy lock would."""

    def __init__(self, delay: float, stall: float):
        self.delay, self.stall = delay, stall
        self.calls = 0

    def submit(self, spec):
        self.calls += 1
        if self.calls == 1:
            time.sleep(self.stall)
        fut = Future()
        threading.Timer(self.delay, fut.set_result, args=(_Answer(),)).start()
        return fut


class _Answer:
    meta = {"cache": "hit"}


def test_latency_runs_from_the_scheduled_time():
    cfg = json.loads((benchkit.ROOT / "bench" / "configs" /
                      "daemon.json").read_text())
    load = open_loop.Load(cfg, dict(TRAFFIC, rate_qps=40.0, drain_s=5.0),
                          3, None)
    load.srv = _SlowServer(delay=0.02, stall=0.3)
    load.measure(1.0)
    load.drain()
    lat = load.latencies_ms()
    late = (load.t_sub - (load.t0 + load.times)) * 1e3
    assert np.all(np.isfinite(lat))
    # queries due during the stall were sent late, and their latency
    # counts the wait: latency >= lateness + service time
    assert late.max() > 200.0
    assert np.all(lat >= late + 20.0 - 1.0)
    out = load.outcome()
    assert out["metrics"]["query_p90_ms"] >= np.sort(lat)[
        int(np.ceil(0.9 * lat.size)) - 1] - 1e-9

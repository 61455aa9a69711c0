"""The reduction from a profiler trace to device busy time, program time
and breakdown, on a small trace recorded on a TPU v5e: a jitted
``run`` called three times and one eager ``concatenate``, inside the
``bench/window`` span."""

import pytest

import benchkit
import trace_reduce as tr

TRACE = benchkit.ROOT / "bench" / "tests" / "data" / "tpu_tiny.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return tr.Trace.from_file(str(TRACE))


def test_device_and_window_are_found(trace):
    assert len(trace.devices) == 1
    assert 0 < trace.window_s < 5


def test_busy_and_idle(trace):
    busy = trace.busy_s()
    assert 0 < busy < trace.window_s
    assert trace.idle_share() == pytest.approx(1 - busy / trace.window_s)


def test_program_time_is_within_busy_time(trace):
    t = trace.module_s("jit_run")
    assert 0 < t <= trace.busy_s() + 1e-9
    assert trace.module_s("no_such_program") == 0


def test_breakdown(trace):
    b = tr.breakdown(trace)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    ops = [s for _, s in b["device_ops"]]
    assert ops == sorted(ops, reverse=True)
    idle = sum(s for _, s in b["idle_gaps"])
    assert idle <= trace.window_s - trace.busy_s() + 1e-9


def test_union_length_merges_overlaps():
    ivs = [(0, 10, "a"), (5, 20, "b"), (30, 40, "c"), (-5, 1, "d")]
    total, spans = tr.union_length(ivs, 0, 35)
    assert total == 25 and spans == [(0, 20), (30, 35)]


def test_peaks_name_their_device():
    assert tr.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        tr.peak("TPU v9 imaginary", "hbm_bytes_per_s")

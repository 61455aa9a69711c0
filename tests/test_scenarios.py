"""Property tests for the scenario engine (core/scenarios.py).

Under arbitrary fail-stop schedules the ReCXL design guarantees that
recovery replay is deterministic and idempotent, that the repaired
directory never references a failed node, and that the recovered memory
equals the live truth. The batched sweep side must keep the paper's
headline geomeans inside the PAPER_CLAIMS acceptance bands.
"""

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.recxl_paper import PAPER_CLAIMS, WORKLOADS
from repro.core.failures import FailureEvent
from repro.core.scenarios import (
    FaultScenario,
    directory_references,
    enumerate_fault_scenarios,
    fig10_grid,
    fig16_grid,
    fig17_grid,
    fig18_grid,
    run_fault_scenario,
    run_sweep,
    sweep_grid,
)
from repro.core.simulator import (CONFIGS, geomean_slowdowns,
                                  slowdowns_from_results)

needs_devices = pytest.mark.skipif(jax.device_count() < 4,
                                   reason="needs >= 4 devices")


# ---------------------------------------------------------------------------
# Sweep grids
# ---------------------------------------------------------------------------

def test_grid_builders_shapes():
    assert len(fig10_grid()) == len(WORKLOADS) * len(CONFIGS)
    assert len(fig16_grid()) == 3 * 2 * 4
    assert len(fig17_grid()) == len(WORKLOADS) * 4
    assert len(fig18_grid()) == 3 * 2 * 3
    assert all(s.config == "proactive" for s in fig17_grid())
    grid = sweep_grid(workloads=("ycsb",), configs=("wb",), seeds=(0, 1),
                      sb_sizes=(36, 72))
    assert len(grid) == 4


@pytest.fixture(scope="module")
def fig10_results():
    return run_sweep(fig10_grid(), n_stores=20_000, n_shards=1)


def test_fig10_geomeans_inside_paper_bands(fig10_results):
    """The batched sweep must reproduce the paper's headline geomeans
    (same acceptance bands as the serial tests in test_simulator.py)."""
    table = slowdowns_from_results(fig10_results)
    gm = geomean_slowdowns(table)
    assert 6.0 <= gm["wt"] <= 9.5, gm
    assert 2.3 <= gm["baseline"] <= 3.5, gm
    assert 1.1 <= gm["proactive"] <= 1.55, gm
    gain = 1.0 - gm["parallel"] / gm["baseline"]
    assert 0.0 <= gain <= 0.10, gm


def test_fig17_nr_overhead_band():
    """N_r=4 stays within a few percent of N_r=3 (paper Fig. 17)."""
    grid = fig17_grid(replicas=(3, 4), workloads=("bodytrack", "canneal",
                                                  "ycsb"))
    res = run_sweep(grid, n_stores=20_000, n_shards=1)
    t = {(r.workload, s.n_replicas): r.exec_time_ns
         for r, s in zip(res, grid)}
    ratios = [t[(w, 4)] / t[(w, 3)] for w in ("bodytrack", "canneal",
                                              "ycsb")]
    assert 0.99 <= float(np.mean(ratios)) <= 1.15


# ---------------------------------------------------------------------------
# Fault scenarios
# ---------------------------------------------------------------------------

def test_enumerate_fault_scenarios_cover_all_nodes_and_variants():
    scns = enumerate_fault_scenarios(n_nodes=4, n_steps=6)
    assert len(scns) == 3 * (4 * 4 + 1)
    for v in ("baseline", "parallel", "proactive"):
        nodes = {e.node for s in scns if s.variant == v for e in s.events}
        assert nodes == {0, 1, 2, 3}


def test_fault_scenario_validation():
    with pytest.raises(ValueError):
        FaultScenario(name="bad", events=(), variant="nosuch").validate()
    with pytest.raises(ValueError):
        FaultScenario(name="bad", events=(FailureEvent(step=1, node=9),)
                      ).validate()
    with pytest.raises(ValueError):
        FaultScenario(name="bad", events=(), n_replicas=4,
                      n_nodes=4).validate()


@st.composite
def fail_stop_schedules(draw):
    """1-2 fail-stop events at arbitrary steps on distinct nodes."""
    n = draw(st.integers(1, 2))
    steps = draw(st.lists(st.integers(1, 4), min_size=n, max_size=n))
    nodes = draw(st.lists(st.integers(0, 3), min_size=n, max_size=n,
                          unique=True))
    return tuple(FailureEvent(step=s, node=node)
                 for s, node in zip(sorted(steps), nodes))


@needs_devices
@given(fail_stop_schedules(),
       st.sampled_from(["baseline", "parallel", "proactive"]))
@settings(max_examples=4, deadline=None)
def test_recovery_invariants_under_arbitrary_schedules(events, variant):
    scn = FaultScenario(name="prop", events=events, variant=variant,
                        n_steps=6)
    out = run_fault_scenario(scn)
    assert out.failed_nodes == tuple(sorted({e.node for e in events}))
    assert len(out.checks) == len(out.failed_nodes)
    for c in out.checks:
        assert c.unrecoverable == 0, c
        assert c.replay_idempotent, c
        assert c.directory_consistent, c
        assert c.exact, c
        assert c.newest_ts == c.step       # newest validated version wins
        assert c.downtime_ns > 0, c        # SS VII-E estimate attached
    assert not directory_references(out.directory, set(out.failed_nodes))
    assert out.resumed
    assert out.total_downtime_ns > 0


@needs_devices
def test_coalescing_and_capacity_wrap_recovery():
    """Ring wrap (n_steps > log_capacity) + coalesced REPLs still recover
    the newest version."""
    scn = FaultScenario(name="wrap", events=(FailureEvent(step=5, node=2),),
                        n_steps=7, coalescing=True, log_capacity=2)
    out = run_fault_scenario(scn)
    assert out.all_invariants_hold
    assert out.checks[0].newest_ts == 5


@needs_devices
def test_fault_scenario_contention_scales_downtime():
    """The same fail-stop schedule yields contention-dependent downtime:
    conflicted ownership churn inflates the crash-exposed volumes, an
    eager persist schedule shrinks them (docs/contention.md)."""
    ev = (FailureEvent(step=2, node=1),)
    base = run_fault_scenario(FaultScenario(name="base", events=ev))
    hot = run_fault_scenario(FaultScenario(name="hot", events=ev,
                                           conflict_rate=0.6))
    eager = run_fault_scenario(FaultScenario(
        name="eager", events=ev, consistency_schedule="eager"))
    assert base.all_invariants_hold and hot.all_invariants_hold
    assert hot.total_downtime_ns > base.total_downtime_ns
    assert eager.total_downtime_ns < base.total_downtime_ns
    with pytest.raises(ValueError):
        FaultScenario(name="bad", events=ev, conflict_rate=3.0).validate()


@needs_devices
def test_straggler_events_recorded_not_failed():
    scn = FaultScenario(
        name="straggler",
        events=(FailureEvent(step=1, node=3, kind="straggler", delay_s=0.5),
                FailureEvent(step=3, node=1)),
        n_steps=5)
    out = run_fault_scenario(scn)
    assert out.failed_nodes == (1,)
    assert out.stragglers == {3: 0.5}
    assert out.all_invariants_hold

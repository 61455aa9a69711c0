"""Differential + concurrency harness for the scenario-serving daemon.

The serving contract (``repro.core.serving``): every answer the daemon
produces -- lane-cache hit or diff-upload miss, any batching, any
interleaving with ``clear_sim_caches()`` -- is bit-identical (``==``)
to the serial oracle for the same spec, and steady-state serving
compiles nothing. These tests pin all
of it, plus the ``_plane_keys`` bank-geometry invariants PRs 4-6
relied on implicitly.
"""

import threading

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax

from repro.core import engine as E
from repro.core import simulator as S
from repro.core.scenarios import (
    contention_mega_grid,
    directory_mega_grid,
    downtime_query,
    grid_delta,
    mega_grid,
    recovery_sweep,
    run_sweep,
    sweep_grid,
)
from repro.core.serving import ScenarioServer, _row_capacity
from repro.core.simulator import (
    CONFIGS,
    PAPER_CLUSTER,
    ScenarioSpec,
    bank_row_maps,
    clear_sim_caches,
)

N = 700
WORKLOAD_POOL = ("ycsb", "canneal", "barnes", "raytrace", "ocean_ncp")
FLOAT_FIELDS = ("exec_time_ns", "repl_at_head_frac", "sb_full_frac",
                "max_log_bytes", "cxl_mem_bw_gbps", "log_dump_bw_gbps")

#: The warm grid every deterministic test heats the daemon with: mixed
#: SB depths, two configs on each side of the replicate/local split.
WARM_GRID = sweep_grid(workloads=("ycsb", "canneal"),
                       configs=("wb", "proactive"),
                       sb_sizes=(None, 48), n_replicas=(None, 3))


def _spec_pool(draw):
    """One random spec over the pooled serve axes (a superset of
    WARM_GRID's axes, so draws mix hits and misses)."""
    return ScenarioSpec(
        draw(st.sampled_from(WORKLOAD_POOL)),
        draw(st.sampled_from(CONFIGS)),
        seed=draw(st.integers(min_value=0, max_value=2)),
        n_replicas=draw(st.sampled_from((None, 2, 3))),
        link_bw_gbps=draw(st.sampled_from((None, 40.0))),
        n_cns=draw(st.sampled_from((None, 8))),
        sb_size=draw(st.sampled_from((None, 16, 48))),
        coalescing=draw(st.booleans()))


@st.composite
def query_streams(draw):
    """A ragged mixed-SB query stream: WARM_GRID cells (lane-cache
    hits) interleaved with novel pool cells (diff-upload misses),
    duplicates and all."""
    n = draw(st.integers(min_value=1, max_value=20))
    stream = []
    for _ in range(n):
        if draw(st.booleans()):
            stream.append(WARM_GRID[draw(st.integers(
                min_value=0, max_value=len(WARM_GRID) - 1))])
        else:
            stream.append(_spec_pool(draw))
    return stream


def _oracle(specs):
    """The serial oracle's answers for ``specs``, cell by cell."""
    return run_sweep(specs, n_stores=N, engine="serial")


def lane_count(specs, cluster=PAPER_CLUSTER):
    """Unique scan lanes of a grid: the (SB, trace, wv) dedup the
    engine and the daemon both key on."""
    lanes = set()
    for s in specs:
        sb = s.sb_size if s.sb_size is not None else cluster.store_buffer
        lanes.add((sb,) + S._plane_keys(s, cluster))
    return len(lanes)


# ---------------------------------------------------------------------------
# Differential: daemon answers == cold oracle, hit and miss paths
# ---------------------------------------------------------------------------


@settings(max_examples=8, deadline=None)
@given(query_streams())
def test_daemon_bitident_to_cold_oracle_on_random_streams(stream):
    with ScenarioServer(n_stores=N, batch_cells=8) as srv:
        srv.warm(WARM_GRID)
        warm_again = srv.query_batch(WARM_GRID)     # pure hit path
        served = srv.query_batch(stream)            # mixed hit/miss
        served_again = srv.query_batch(stream)      # now pure hits
        st_ = srv.stats()
    # the daemon's flush tiles gather from the capacity-padded device
    # bank; the serial oracle derives every cell from scratch
    clear_sim_caches()
    oracle = _oracle(WARM_GRID + stream)
    for got, want in zip(warm_again + served, oracle):
        for f in FLOAT_FIELDS:
            assert getattr(got, f) == getattr(want, f), (got.meta, f)
    # the re-served stream is answered from the lane cache, identically
    for x, y in zip(served, served_again):
        assert x == y
        assert y.meta["cache"] == "hit"
    assert st_["lane_hits"] >= len(WARM_GRID) + len(stream)
    assert st_["bank_builds"] == 1


def test_hit_and_miss_paths_and_meta_provenance():
    with ScenarioServer(n_stores=N, batch_cells=8) as srv:
        srv.warm(WARM_GRID)
        srv.reset_stats()

        hit = srv.query(WARM_GRID[0])
        assert hit.meta["cache"] == "hit"
        assert hit.meta["h2d_bytes"] == 0           # nothing crossed
        assert srv.stats()["appended_trace_rows"] == 0

        novel = ScenarioSpec("bodytrack", "proactive", n_replicas=4)
        miss = srv.query(novel)
        assert miss.meta["cache"] == "miss"
        assert miss.meta["h2d_bytes"] > 0           # rows + index diff
        st_ = srv.stats()
        assert st_["appended_trace_rows"] == 1
        assert st_["appended_wv_rows"] == 1
        # marginal bytes of one novel cell are row-scale, not bank-scale
        assert st_["h2d_bytes"] < st_["bank_bytes"]

        again = srv.query(novel)
        assert again.meta["cache"] == "hit"
        assert again.meta["h2d_bytes"] == 0
        assert again == miss
    oracle = _oracle([WARM_GRID[0], novel])
    assert hit == oracle[0]
    assert miss == oracle[1]


def test_sharded_serving_matches_oracle():
    n_shards = min(2, len(jax.devices()))
    with ScenarioServer(n_stores=N, batch_cells=8,
                        n_shards=n_shards) as srv:
        served = srv.query_batch(WARM_GRID)
        novel = [ScenarioSpec("barnes", "proactive", seed=2)]
        served += srv.query_batch(novel)
    oracle = _oracle(WARM_GRID + novel)
    for a, b in zip(served, oracle):
        assert a == b, (a.meta, b.meta)


def test_capacity_growth_reuploads_and_stays_bitident():
    """Appends past the device capacity trigger a (rare) full re-upload
    at the grown shape -- answers must stay bit-identical across the
    capacity step and resident rows must survive it."""
    base = [ScenarioSpec("ycsb", "proactive", n_replicas=r)
            for r in (1, 2)]
    with ScenarioServer(n_stores=N, batch_cells=8, row_pad=4) as srv:
        first = srv.query_batch(base)
        assert srv.stats()["bank_uploads"] == 1
        cap0 = srv.stats()["bank_capacity"]
        # 6 novel wv rows blow through the 4-row quantum
        grow = [ScenarioSpec(w, "proactive", n_replicas=4)
                for w in WORKLOAD_POOL] + \
               [ScenarioSpec("ycsb", "baseline", link_bw_gbps=40.0)]
        grown = srv.query_batch(grow)
        st_ = srv.stats()
        assert st_["bank_uploads"] == 2
        assert st_["bank_capacity"][1] > cap0[1]
        assert _row_capacity(st_["bank_rows"], 4) >= st_["bank_capacity"][1] \
            or st_["bank_capacity"][1] > st_["dev_rows"][1]
        recheck = srv.query_batch(base)             # old lanes still hit
        assert all(r.meta["cache"] == "hit" for r in recheck)
    oracle = _oracle(base + grow)
    for a, b in zip(first + grown, oracle):
        assert a == b


# ---------------------------------------------------------------------------
# Compile-count regression: steady-state serving compiles nothing
# ---------------------------------------------------------------------------


def test_steady_state_serving_compiles_zero_programs():
    """After warmup, 100 mixed queries (hits, novel in-capacity misses,
    batches, singles) trace zero new tile programs."""
    with ScenarioServer(n_stores=N, batch_cells=8) as srv:
        srv.warm(WARM_GRID)
        tc0 = E.trace_count()
        rng = np.random.default_rng(7)
        novel = sweep_grid(workloads=WORKLOAD_POOL,
                           configs=("proactive", "baseline"),
                           seeds=(0, 1, 2), n_replicas=(2,),
                           sb_sizes=(None, 48))
        queries = [WARM_GRID[rng.integers(len(WARM_GRID))]
                   if rng.random() < 0.5
                   else novel[rng.integers(len(novel))]
                   for _ in range(100)]
        for q in queries[:50]:
            srv.query(q)                            # single-cell flushes
        srv.query_batch(queries[50:])               # one batched flush
        st_ = srv.stats()
        assert E.trace_count() == tc0, \
            f"steady-state serving traced {E.trace_count() - tc0} programs"
        assert st_["compiled_programs"] == 0
        assert st_["lane_misses"] > 0               # misses really ran
        assert st_["lane_hits"] > 0


# ---------------------------------------------------------------------------
# Bank-key stability pins (the _plane_keys contract of PRs 4-6)
# ---------------------------------------------------------------------------


def test_bank_key_stability_pins():
    """The serving refactor must not move a single bank row or lane:
    mega_grid keeps its 27 + 1298 rows (and 2 700 scan lanes), and the
    coupled mega-grids keep their lane counts."""
    mega = mega_grid()
    trace_map, wv_map = bank_row_maps(mega)
    assert len(trace_map) == 27
    assert len(wv_map) == 1298
    assert lane_count(mega) == 2700
    assert lane_count(contention_mega_grid()) == 990
    assert lane_count(directory_mega_grid()) == 2160


def test_bank_bytes_stable_across_serving_refactor():
    """Byte-level pin on a materialized sub-grid: the extend-capable
    bank builds the same columns (same bytes, same row order) as the
    pre-refactor from-scratch path, and serving a grid does not perturb
    the memoized bank another engine would resolve."""
    sub = mega_grid(seeds=(0,), replicas=(1, 3), bandwidths=(160.0, 40.0),
                    cn_counts=(16,), sb_sizes=(72, 48))
    scratch = S._make_trace_bank(tuple(sub), N, PAPER_CLUSTER)
    with ScenarioServer(n_stores=N, batch_cells=8) as srv:
        srv.warm(sub, populate=False)
        srv.query_batch(sub[: len(sub) // 2])
        bank = srv._bank
        assert bank.trace_row == scratch.trace_row
        assert bank.wv_row == scratch.wv_row
        assert bank.arrivals.tobytes() == scratch.arrivals.tobytes()
        assert bank.w.tobytes() == scratch.w.tobytes()
        assert bank.v.tobytes() == scratch.v.tobytes()
        assert bank.pr_nc.tobytes() == scratch.pr_nc.tobytes()


# ---------------------------------------------------------------------------
# LRU bounds: lane eviction + bank compaction (PR-8 satellite)
# ---------------------------------------------------------------------------


def test_lru_lane_eviction_reask_bitident():
    """With max_lanes set, the least-recently-asked lanes are evicted
    past the bound; an evicted-then-reasked query takes the miss path
    again and stays bit-identical to its first answer and the oracle."""
    grid = sweep_grid(workloads=WORKLOAD_POOL, configs=("proactive", "wb"),
                      n_replicas=(None, 2))
    assert lane_count(grid) > 6
    with ScenarioServer(n_stores=N, batch_cells=8, max_lanes=6) as srv:
        first = srv.query_batch(grid)
        st_ = srv.stats()
        assert st_["lanes_cached"] == 6
        assert st_["lane_evictions"] == lane_count(grid) - 6
        # grid[0]'s lane was served earliest -> evicted -> a miss again
        re0 = srv.query(grid[0])
        assert re0.meta["cache"] == "miss"
        assert re0 == first[0]
        # ...and the most recent lanes are still resident hits
        re_last = srv.query(grid[-1])
        assert re_last.meta["cache"] == "hit"
        assert re_last == first[-1]
        # hammering one hot lane never evicts it (move_to_end on hit)
        for _ in range(4):
            srv.query_batch([grid[-1], grid[0]])
        assert srv.query(grid[0]).meta["cache"] == "hit"
    oracle = _oracle(grid)
    for a, b in zip(first, oracle):
        assert a == b


def test_bank_compaction_bounds_rows_and_stays_bitident():
    """max_bank_rows compacts the append-only bank down to the live
    cached lanes' rows; answers before, across, and after compactions
    all == the oracle, and the compaction counter advances."""
    grid = sweep_grid(workloads=WORKLOAD_POOL,
                      configs=("proactive", "wb", "baseline"),
                      n_replicas=(None, 2, 3))
    with ScenarioServer(n_stores=N, batch_cells=8, row_pad=4,
                        max_lanes=4, max_bank_rows=12) as srv:
        served = [srv.query(s) for s in grid]
        st_ = srv.stats()
        assert st_["bank_compactions"] >= 1
        assert st_["lane_evictions"] > 0
        # the live bank tracks the bounded lane set, not query history
        assert st_["bank_rows"] < lane_count(grid) * 2
        again = [srv.query(s) for s in grid]
    oracle = _oracle(grid)
    for a, b, c in zip(served, again, oracle):
        assert a == c and b == c
    with pytest.raises(ValueError):
        ScenarioServer(n_stores=N, max_lanes=0)
    with pytest.raises(ValueError):
        ScenarioServer(n_stores=N, max_bank_rows=1)


# ---------------------------------------------------------------------------
# Query translation: grid deltas and downtime requests
# ---------------------------------------------------------------------------


def test_grid_delta_translation():
    axes = dict(workloads=("ycsb", "canneal"), configs=("wb", "proactive"),
                sb_sizes=(None, 48), n_replicas=(None, 3, 4))
    delta = grid_delta(WARM_GRID, **axes)
    full = sweep_grid(**axes)
    assert delta == [s for s in full if s not in set(WARM_GRID)]
    assert all(s.n_replicas == 4 for s in delta)    # only the new axis val
    assert grid_delta(full, **axes) == []
    with ScenarioServer(n_stores=N, batch_cells=8) as srv:
        srv.warm(WARM_GRID)
        srv.reset_stats()
        served = srv.query_grid(**axes)
        st_ = srv.stats()
    assert st_["lane_hits"] >= len(full) - len(delta)
    oracle = _oracle(full)
    for a, b in zip(served, oracle):
        assert a == b


def test_downtime_queries_match_recovery_model():
    est = downtime_query("ycsb", 50.0, n_cns=8)
    sweep = recovery_sweep(workloads=("ycsb",), fail_times_ms=(50.0,),
                           cn_counts=(8,))
    assert np.isclose(est.total_ns, float(sweep.total_ns[0, 0, 0]),
                      rtol=1e-9)
    # coupling axes move the estimate the same direction as the sweep's
    loaded = downtime_query("ycsb", 50.0, n_cns=8, directory_load=0.5)
    assert loaded.directory_ns > est.directory_ns
    with ScenarioServer(n_stores=N) as srv:
        got = srv.query_downtime("ycsb", 50.0, n_cns=8)
        assert got == est
        assert srv.stats()["downtime_queries"] == 1


# ---------------------------------------------------------------------------
# Async batching + threaded stress vs clear_sim_caches()
# ---------------------------------------------------------------------------


def test_submit_futures_batch_and_resolve():
    with ScenarioServer(n_stores=N, batch_cells=64,
                        batch_window_ms=100.0) as srv:
        srv.warm(WARM_GRID)
        srv.reset_stats()
        futs = [srv.submit(s) for s in WARM_GRID + WARM_GRID]
        got = [f.result(timeout=120) for f in futs]
        st_ = srv.stats()
        assert st_["queries"] == 2 * len(WARM_GRID)
        # the window coalesced concurrent submissions into few flushes
        assert 1 <= st_["batches"] <= 8
    oracle = _oracle(WARM_GRID)
    for a, b in zip(got, oracle + oracle):
        assert a == b
    with pytest.raises(RuntimeError):
        srv.submit(WARM_GRID[0])                    # closed


def test_concurrent_queries_race_cache_clears_bitident():
    """N threads hammer the daemon (sync + async paths) while another
    thread repeatedly drops every host/compile cache: no deadlock, no
    bank double-build, every answer still == the oracle."""
    oracle = _oracle(WARM_GRID)
    novel = [ScenarioSpec(w, "proactive", seed=2, n_replicas=2)
             for w in WORKLOAD_POOL]
    novel_oracle = _oracle(novel)
    want = {s: r for s, r in zip(WARM_GRID + novel,
                                 list(oracle) + list(novel_oracle))}

    with ScenarioServer(n_stores=N, batch_cells=8,
                        batch_window_ms=1.0) as srv:
        srv.warm(WARM_GRID)
        stop = threading.Event()
        errors = []

        def clearer():
            while not stop.is_set():
                clear_sim_caches()

        def worker(seed):
            rng = np.random.default_rng(seed)
            pool = WARM_GRID + novel
            try:
                for _ in range(6):
                    picks = [pool[rng.integers(len(pool))]
                             for _ in range(4)]
                    if rng.random() < 0.5:
                        got = srv.query_batch(picks)
                    else:
                        got = [f.result(timeout=120)
                               for f in map(srv.submit, picks)]
                    for s, r in zip(picks, got):
                        if r != want[s]:
                            errors.append((s, r, want[s]))
            except Exception as e:                  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(6)]
        clr = threading.Thread(target=clearer)
        clr.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        stop.set()
        clr.join(timeout=60)
        alive = [t for t in threads + [clr] if t.is_alive()]
        assert not alive, f"deadlocked threads: {alive}"
        assert not errors, errors[:3]
        st_ = srv.stats()
        assert st_["bank_builds"] == 1, "bank was rebuilt under the race"


def test_submit_timeout_fails_future_with_diagnostic():
    """A submit() deadline expiring -- queued OR mid-flush -- fails
    that future with a TimeoutError diagnostic instead of blocking the
    caller forever (the watchdog satellite of PR 9)."""
    with ScenarioServer(n_stores=N, batch_cells=8) as srv:
        srv._lock.acquire()                 # wedge the flush path
        try:
            fut = srv.submit(WARM_GRID[0], timeout_ms=50)
            with pytest.raises(TimeoutError, match="timed out"):
                fut.result(timeout=30)
        finally:
            srv._lock.release()
        assert srv.stats()["submit_timeouts"] >= 1
        # the daemon is still healthy afterwards
        ok = srv.submit(WARM_GRID[0], timeout_ms=60_000).result(timeout=120)
        assert ok == _oracle([WARM_GRID[0]])[0]
    with pytest.raises(ValueError):
        ScenarioServer(n_stores=N, submit_timeout_ms=0)


def test_watchdog_fails_wedged_flush():
    """watchdog_ms bounds a wedged daemon flush: every future of the
    stuck batch fails with a diagnostic naming the watchdog."""
    with ScenarioServer(n_stores=N, batch_cells=8,
                        watchdog_ms=100) as srv:
        srv._lock.acquire()
        try:
            futs = [srv.submit(s) for s in WARM_GRID[:2]]
            for f in futs:
                with pytest.raises(TimeoutError, match="watchdog"):
                    f.result(timeout=30)
        finally:
            srv._lock.release()
        assert srv.stats()["watchdog_flush_failures"] >= 1


def test_close_drains_or_fails_pending_deterministically():
    """close() under concurrent submitters: every outstanding future is
    either resolved (flushed during the drain) or failed with a
    RuntimeError -- never left pending."""
    srv = ScenarioServer(n_stores=N, batch_cells=8)
    srv._lock.acquire()                     # hold the daemon mid-flush
    fut = srv.submit(WARM_GRID[0])
    closer = threading.Thread(target=srv.close)
    closer.start()
    srv._lock.release()
    closer.join(timeout=120)
    assert not closer.is_alive(), "close() hung on a pending queue"
    try:
        res = fut.result(timeout=30)        # drained during close
        assert res == _oracle([WARM_GRID[0]])[0]
    except RuntimeError:
        pass                                # or failed deterministically
    with pytest.raises(RuntimeError):
        srv.submit(WARM_GRID[0])


def test_stats_snapshot_is_deep_copied_and_consistent():
    """stats() is a deep-copied snapshot taken under the server lock
    (the PR-10 race regression): a reader hammering it while another
    thread serves never observes a half-updated counter set -- in every
    snapshot lane_hits + lane_misses == queries exactly -- and a
    captured snapshot is frozen, i.e. later queries (and caller-side
    mutation) never alter it or the live counters."""
    with ScenarioServer(n_stores=N, batch_cells=8) as srv:
        srv.warm(WARM_GRID)
        srv.reset_stats()
        snaps = []
        errors = []
        stop = threading.Event()

        def reader():
            while not stop.is_set():
                st_ = srv.stats()
                if st_["lane_hits"] + st_["lane_misses"] != st_["queries"]:
                    errors.append(st_)
                snaps.append(st_)

        rd = threading.Thread(target=reader)
        rd.start()
        rng = np.random.default_rng(7)
        for _ in range(40):
            picks = [WARM_GRID[rng.integers(len(WARM_GRID))]
                     for _ in range(3)]
            srv.query_batch(picks)
        stop.set()
        rd.join(timeout=60)
        assert not rd.is_alive(), "stats() reader deadlocked"
        assert not errors, f"torn snapshot(s): {errors[:2]}"
        assert snaps and snaps[-1]["queries"] <= 120

        # frozen: later traffic + caller mutation leave the capture and
        # the live counters untouched
        frozen = srv.stats()
        before = frozen["queries"]
        srv.query_batch([WARM_GRID[0]])
        assert frozen["queries"] == before
        frozen["lane_hits"] = -1
        frozen["bank_capacity"] = None
        live = srv.stats()
        assert live["queries"] == before + 1
        assert live["lane_hits"] >= 0 and live["bank_capacity"] is not None

"""Flight-recorder contract tests (``repro.core.telemetry``,
docs/observability.md).

Two families:

* **Recorder semantics** -- nested spans, counters, gauges,
  distribution percentiles, ring-buffer wrap (aggregates survive event
  drops), the disabled-path noop singleton, and Chrome trace-event
  export round-tripping through :func:`validate_chrome_trace`.

* **Zero-churn pins** -- recording must never change a number: traced
  ``run_grid`` results ``==`` untraced, the host memo keys
  (``_plane_keys`` / ``_specs_key``) and the resident bank bytes are
  byte-identical, and a traced re-run of a warm grid compiles 0 extra
  programs.  Plus the span taxonomy the docs promise: prefetch /
  compile-warm / daemon threads each carry balanced B/E spans, and a
  chaos-injected shard loss emits the
  detection -> rollback -> rebuild -> re-place -> re-dispatch timeline
  in exactly that order.

Plus the recorder's place on the profiler's clock: its spans reach a
``jax.profiler`` trace, and the gc / compile hooks it installs while
live are gone when it is not.
"""

import dataclasses
import gc
import glob
import json
import threading

import pytest

import jax
import jax.numpy as jnp

from repro.core import chaos
from repro.core import engine as E
from repro.core import telemetry as tm
from repro.core.scenarios import chaos_grid, sweep_grid
from repro.core.serving import ScenarioServer
from repro.core.simulator import (
    _CELL_ARRAY_CACHE,
    PAPER_CLUSTER,
    _plane_keys,
    _specs_key,
    clear_sim_caches,
    get_trace_bank,
)

N = 600
GRID = sweep_grid(workloads=("ycsb", "canneal"),
                  configs=("wb", "proactive"),
                  sb_sizes=(None, 48), n_replicas=(None, 3))


@pytest.fixture(autouse=True)
def _no_recorder_leaks():
    """Every test starts and ends with the recorder disabled."""
    tm.disable()
    yield
    tm.disable()


# ---------------------------------------------------------------- recorder

def test_nested_spans_counters_gauges_and_summary():
    with tm.recording() as rec:
        with tm.span("outer", tag=1):
            with tm.span("outer/inner"):
                tm.count("hits")
                tm.count("hits", 4)
            tm.gauge("depth", 3)
            tm.gauge("depth", 7)          # latest wins
        for v in (1.0, 2.0, 3.0, 4.0):
            tm.observe("lat_ms", v)
        summ = rec.summary()
    assert summ["counters"]["hits"] == 5
    assert summ["gauges"]["depth"] == 7
    assert summ["spans"]["outer"]["count"] == 1
    assert summ["spans"]["outer/inner"]["count"] == 1
    # the inner span is contained in the outer one
    assert summ["spans"]["outer"]["total"] >= \
        summ["spans"]["outer/inner"]["total"]
    d = summ["dists"]["lat_ms"]
    assert d["count"] == 4 and d["max"] == 4.0
    assert summ["threads"] == 1 and summ["events_dropped"] == 0


def test_distribution_percentiles_nearest_rank():
    with tm.recording() as rec:
        for v in range(1, 101):
            tm.observe("x", float(v))
        d = rec.summary()["dists"]["x"]
    assert d["p50"] in (50.0, 51.0)
    assert d["p99"] in (99.0, 100.0)
    assert d["max"] == 100.0 and d["count"] == 100


def test_ring_wrap_drops_events_but_keeps_aggregates():
    with tm.recording(ring_events=64) as rec:
        for i in range(500):
            with tm.span("tick"):
                tm.count("n")
        summ = rec.summary()
    assert summ["counters"]["n"] == 500
    assert summ["spans"]["tick"]["count"] == 500
    assert summ["events_dropped"] > 0
    assert summ["events"] <= 64


def test_disabled_path_is_a_shared_noop():
    assert not tm.enabled() and tm.active() is None
    s1, s2 = tm.span("a", big=1), tm.span("b")
    assert s1 is s2 is tm._NOOP_SPAN          # no per-call allocation
    with s1:
        tm.count("never")
        tm.gauge("never", 1)
        tm.observe("never", 1.0)
    assert tm.summary() == {}


def test_recording_scope_restores_previous_recorder():
    tm.enable()
    outer = tm.active()
    with tm.recording() as rec:
        assert tm.active() is rec and rec is not outer
    assert tm.active() is outer
    tm.disable()
    assert tm.active() is None


def test_export_chrome_roundtrips_validation(tmp_path):
    import threading

    def other():
        with tm.span("worker/job"):
            tm.count("jobs")

    path = tmp_path / "trace.jsonl"
    with tm.recording() as rec:
        with tm.span("main/outer"):
            t = threading.Thread(target=other)
            t.start()
            t.join()
        tm.gauge("g", 2)
        tm.observe("o", 1.5)
        n = rec.export_chrome(str(path))
    stats = tm.validate_chrome_trace(str(path))
    assert stats["events"] == n > 0
    assert stats["threads"] >= 2           # main + worker
    assert stats["spans"] >= 2
    lines = path.read_text().splitlines()
    assert all(json.loads(ln) for ln in lines)
    names = {json.loads(ln).get("name") for ln in lines}
    assert {"main/outer", "worker/job"} <= names


def test_validate_rejects_unbalanced_trace(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text(
        '{"ph":"M","pid":1,"tid":1,"name":"thread_name",'
        '"args":{"name":"t"}}\n'
        '{"ph":"B","pid":1,"tid":1,"ts":0,"name":"open"}\n')
    with pytest.raises(ValueError):
        tm.validate_chrome_trace(str(bad))


# ------------------------------------------------------------ zero churn

def test_traced_run_grid_bitident_keys_bank_and_compiles():
    clear_sim_caches()
    res_off = E.run_grid(GRID, n_stores=N)
    keys_off = [_plane_keys(s, PAPER_CLUSTER) for s in GRID]
    skey_off = _specs_key(tuple(GRID), N, PAPER_CLUSTER)
    bank_off = get_trace_bank(GRID, N, PAPER_CLUSTER).nbytes
    tc = E.trace_count()

    with tm.recording() as rec:
        res_on = E.run_grid(GRID, n_stores=N)
        summ = rec.summary()

    assert E.trace_count() == tc, "tracing a warm grid must compile 0"
    assert all(a == b for a, b in zip(res_off, res_on))
    assert [_plane_keys(s, PAPER_CLUSTER) for s in GRID] == keys_off
    assert _specs_key(tuple(GRID), N, PAPER_CLUSTER) == skey_off
    assert get_trace_bank(GRID, N, PAPER_CLUSTER).nbytes == bank_off
    # and the traced run actually observed the pipeline
    assert summ["spans"]["tile/dispatch"]["count"] >= 1
    assert summ["spans"]["engine/plan"]["count"] == 1
    assert summ["spans"]["engine/run"]["count"] == 1
    assert res_on[0].meta["telemetry"] is not None
    # tracing may annotate meta, but == ignores it by contract
    assert "telemetry" not in (res_off[0].meta or {})


def test_pipeline_spans_nest_and_balance_per_thread(tmp_path):
    clear_sim_caches()
    path = tmp_path / "grid.jsonl"
    with tm.recording() as rec:
        E.run_grid(GRID, n_stores=N)
        rec.export_chrome(str(path))
        summ = rec.summary()
    for name in ("tile/prep", "tile/h2d", "tile/dispatch", "tile/drain",
                 "bank/place", "compile/warm"):
        assert summ["spans"][name]["count"] >= 1, name
    assert summ["gauges"]["engine/in_flight_tiles"] >= 0
    assert "engine/prefetch_queue_depth" in summ["gauges"]
    # prefetch + warm threads record off the main thread
    assert summ["threads"] >= 2
    stats = tm.validate_chrome_trace(str(path))   # raises on bad nesting
    assert stats["threads"] == summ["threads"]
    # per-thread B/E balance, explicitly
    depth = {}
    for ln in path.read_text().splitlines():
        ev = json.loads(ln)
        if ev["ph"] == "B":
            depth[ev["tid"]] = depth.get(ev["tid"], 0) + 1
        elif ev["ph"] == "E":
            depth[ev["tid"]] = depth[ev["tid"]] - 1
            assert depth[ev["tid"]] >= 0
    assert all(v == 0 for v in depth.values())


def test_daemon_spans_and_latency_histograms():
    clear_sim_caches()
    with ScenarioServer(n_stores=N, batch_cells=8,
                        batch_window_ms=1.0) as srv:
        srv.warm(GRID[:8])
        with tm.recording() as rec:
            srv.query_batch(GRID)                     # hits + misses
            for f in [srv.submit(s) for s in GRID[:4]]:
                f.result(timeout=120)
            st = srv.stats()
            summ = rec.summary()
    assert summ["spans"]["serve/flush"]["count"] >= 2
    assert summ["spans"]["serve/bank_sync"]["count"] >= 1
    q = summ["dists"]["serve/query_ms"]
    assert q["count"] == len(GRID) + 4
    assert summ["dists"]["serve/queue_wait_ms"]["count"] >= 4
    assert summ["dists"]["serve/window_wait_ms"]["count"] >= 1
    hits = summ["counters"]["serve/lane_hits"]
    misses = summ["counters"]["serve/lane_misses"]
    assert hits + misses == len(GRID) + 4
    assert st["telemetry"]["spans"].keys() == summ["spans"].keys()


def test_chaos_recovery_timeline_span_order():
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 host devices for a shard loss")
    # 24 cells / 8-cell tiles => several dispatches, so the fault armed
    # at dispatch 2 fires mid-grid with work in flight
    grid = chaos_grid()[:24]
    clear_sim_caches()
    base = E.run_grid(grid, n_stores=N, tile_cells=8, n_shards=2)
    with chaos.inject(chaos.ChaosConfig(lose_shard=1,
                                        lose_at_dispatch=2)):
        with tm.recording() as rec:
            res = E.run_grid(grid, n_stores=N, tile_cells=8, n_shards=2)
            evs = rec.span_events("recover")
            summ = rec.summary()
    assert all(a == b for a, b in zip(res, base))
    begins = [nm for ph, _t, nm, _tid in evs if ph == "B"]
    assert begins == ["recover", "recover/detect", "recover/rollback",
                      "recover/rebuild", "recover/replace",
                      "recover/redispatch"]
    # nested spans: children are contained in the parent duration
    parent = summ["spans"]["recover"]["total"]
    for child in ("recover/detect", "recover/rollback",
                  "recover/rebuild", "recover/replace"):
        assert summ["spans"][child]["total"] <= parent + 1e-6
    assert summ["counters"]["chaos/faults_detected"] == 1
    assert summ["counters"]["chaos/shard_loss"] == 1
    assert summ["spans"]["chaos/replica_rebuild"]["count"] + \
        summ["spans"].get("chaos/journal_rebuild",
                          {"count": 0})["count"] >= 1


# ------------------------------------------- bank build, plan and finish

def test_bank_build_spans_and_row_counters():
    clear_sim_caches()
    with tm.recording() as rec:
        bank = get_trace_bank(GRID, N, PAPER_CLUSTER)
        get_trace_bank(GRID, N, PAPER_CLUSTER)          # memo hit
        first = rec.summary()
    spans, counters = first["spans"], first["counters"]
    assert spans["bank/get"]["count"] == 2
    for name in ("bank/build", "bank/synth", "bank/rows", "bank/stack"):
        assert spans[name]["count"] == 1, name
    parts = sum(spans[n]["total"] for n in
                ("bank/synth", "bank/rows", "bank/stack"))
    assert parts <= spans["bank/build"]["total"] + 1e-6
    assert spans["bank/build"]["total"] <= spans["bank/get"]["total"] + 1e-6
    assert counters["bank/trace_rows"] == bank.trace_rows
    assert counters["bank/wv_rows"] == bank.wv_rows
    # the build collapses no row on the host: placement does, on the
    # device; the host planes are collapsed on their first read
    assert counters["bank/wv_rows_built"] == 0
    with tm.recording() as rec:
        assert bank.w.shape[0] == bank.wv_rows
        assert rec.summary()["counters"]["bank/wv_rows_built"] \
            == bank.wv_rows
    # a rebuilt bank over warm row memos collapses no row again
    from repro.core.simulator import _BANK_CACHE
    _BANK_CACHE.clear()
    with tm.recording() as rec:
        rebuilt = get_trace_bank(GRID, N, PAPER_CLUSTER)
        assert rebuilt.pr_nc.shape[0] == bank.wv_rows
        again = rec.summary()["counters"]
    assert again["bank/wv_rows"] == bank.wv_rows
    assert again["bank/wv_rows_built"] == 0


def test_contended_bank_build_times_and_counts_its_delay_rows():
    """A contended grid's bank build spans each delay row it builds
    (``contention/rows``, inside ``bank/rows``) and counts the conflict
    draws and delay rows it built; the legacy mega-grid records none of
    them and keeps its 27 + 1 298 rows."""
    from repro.core.contention import contention_cache_sizes
    from repro.core.scenarios import contention_mega_grid, mega_grid

    clear_sim_caches()
    specs = contention_mega_grid(workloads=("ycsb", "canneal"), seeds=(0,))
    with tm.recording() as rec:
        get_trace_bank(specs, 64, PAPER_CLUSTER)
        summ = rec.summary()
    spans, counters = summ["spans"], summ["counters"]
    draws, delays = contention_cache_sizes()
    assert draws > 0 and delays > 0
    assert counters["contention/draws_built"] == draws
    assert counters["contention/delay_rows_built"] == delays
    assert spans["contention/rows"]["count"] == delays
    assert spans["contention/rows"]["total"] <= \
        spans["bank/rows"]["total"] + 1e-6
    clear_sim_caches()
    with tm.recording() as rec:
        bank = get_trace_bank(mega_grid(), 32, PAPER_CLUSTER)
        summ = rec.summary()
    assert "contention/rows" not in summ["spans"]
    assert not {"contention/draws_built", "contention/delay_rows_built"} \
        & set(summ["counters"])
    assert (bank.trace_rows, bank.wv_rows) == (27, 1298)
    clear_sim_caches()


def test_directory_bank_build_times_and_counts_its_delay_rows():
    """A directory-coupled grid's bank build spans each delay row it
    builds (``directory/rows``, inside ``bank/rows``), one per distinct
    (workload, seed, loaded directory params, N_r) -- at 20 GB/s each
    replica count has a congestion of its own -- and counts the rows
    that add one; an axis-off grid records none of them."""
    from repro.core.directory import resolve_directory_load
    from repro.core.scenarios import directory_mega_grid

    clear_sim_caches()
    specs = directory_mega_grid(workloads=("ycsb", "canneal"), seeds=(0,),
                                cn_counts=(16, 4), sb_sizes=(72,))
    specs = [dataclasses.replace(s, link_bw_gbps=20.0) for s in specs]
    with tm.recording() as rec:
        bank = get_trace_bank(specs, 300, PAPER_CLUSTER)
        summ = rec.summary()
    spans, counters = summ["spans"], summ["counters"]
    loaded = [s for s in specs if s.directory_load > 0.0]
    rows = {(s.workload, s.seed, s.n_replicas,
             resolve_directory_load(s.directory_load, s.n_cns,
                                    s.n_replicas)) for s in loaded}
    assert counters["directory/delay_rows_built"] == len(rows) == 24
    assert spans["directory/rows"]["count"] == len(rows)
    assert spans["directory/rows"]["total"] <= \
        spans["bank/rows"]["total"] + 1e-6
    assert counters["directory/coupled_rows"] == \
        len({_plane_keys(s, PAPER_CLUSTER)[1] for s in loaded}) == 72
    assert bank.wv_rows == 84           # 12 more at load 0
    clear_sim_caches()
    with tm.recording() as rec:
        get_trace_bank(GRID, 64, PAPER_CLUSTER)
        summ = rec.summary()
    assert "directory/rows" not in summ["spans"]
    assert not {"directory/delay_rows_built", "directory/coupled_rows"} \
        & set(summ["counters"])
    clear_sim_caches()


def test_run_grid_counts_cells_lanes_and_tiles_once():
    clear_sim_caches()
    with tm.recording() as rec:
        E.run_grid(GRID, n_stores=N)
        summ = rec.summary()
    st = E.bank_stats()
    assert summ["counters"]["engine/cells"] == len(GRID)
    assert summ["counters"]["engine/lanes"] == st["scan_lanes"]
    assert summ["counters"]["engine/tiles"] == \
        summ["spans"]["tile/dispatch"]["count"]
    assert summ["spans"]["tile/finish"]["count"] == \
        summ["counters"]["engine/tiles"]
    run = summ["spans"]["engine/run"]["total"]
    for child in ("engine/plan", "bank/get", "tile/finish"):
        assert summ["spans"][child]["total"] <= run + 1e-6, child


#: WB, WT (coalescing off, as WT runs) and replicating cells over two
#: seeds, replica and link knobs, coalescing on and off.
SCALAR_GRID = sweep_grid(workloads=("ycsb", "barnes"),
                         configs=("wb", "wt", "parallel", "proactive"),
                         seeds=(0, 1), n_replicas=(None, 2),
                         link_bw_gbps=(None, 40.0), sb_sizes=(None, 48),
                         coalescing=(True, False))


def test_bank_plane_run_builds_no_cell_arrays():
    """Banked tile prep derives each cell's result scalars from its
    trace: no per-store cell array is built past the bank build, and the
    per-trace memo misses once per (workload, seed, coalescing class)."""
    clear_sim_caches()
    get_trace_bank(SCALAR_GRID, N, PAPER_CLUSTER)
    misses0 = _CELL_ARRAY_CACHE.misses
    with tm.recording() as rec:
        E.run_grid(SCALAR_GRID, n_stores=N, tile_cells=16)
        counters = rec.summary()["counters"]
    assert _CELL_ARRAY_CACHE.misses == misses0
    assert counters["engine/result_scalars_built"] == len(
        {(s.workload, s.seed, s.coalescing and s.config != "wt")
         for s in SCALAR_GRID})
    # a second sweep of the grid finds every per-trace scalar memoized
    with tm.recording() as rec:
        E.run_grid(SCALAR_GRID, n_stores=N, tile_cells=16)
        counters = rec.summary()["counters"]
    assert _CELL_ARRAY_CACHE.misses == misses0
    assert counters["engine/result_scalars_built"] == 0


# ------------------------------------------------ profiler clock + hooks

def _host_events(log_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    return out


def test_spans_reach_the_profiler_trace(tmp_path):
    clear_sim_caches()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("test/outer"):
            with tm.recording() as rec:
                E.run_grid(GRID, n_stores=N)
                gc.collect()
                summ = rec.summary()
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    (lo, hi), = events["test/outer"]
    for name in ("engine/run", "engine/plan", "tile/finish", "bank/synth",
                 "bank/rows", "host/gc"):
        assert events.get(name), name
        assert all(lo <= a <= b <= hi for a, b in events[name]), name
    # the recorder keeps its own view of the same spans
    assert summ["spans"]["host/gc"]["count"] >= 1
    assert summ["counters"]["host/gc_collections"] >= 1


def test_hooks_leave_with_the_recorder():
    from jax._src.monitoring import get_event_duration_listeners

    def hooked():
        return (gc.callbacks.count(tm._gc_hook),
                get_event_duration_listeners().count(tm._compile_hook))

    before = list(gc.callbacks)
    tm.enable()
    assert hooked() == (1, 1)
    tm.disable()
    assert gc.callbacks == before and hooked() == (0, 0)
    with tm.recording():
        with tm.recording():
            assert hooked() == (1, 1)
        assert hooked() == (1, 1)
    assert gc.callbacks == before and hooked() == (0, 0)


def test_gc_during_thread_registration_does_not_deadlock(tmp_path):
    class CollectingLock:
        """The registration lock, collecting garbage once held."""

        def __init__(self):
            self._lock = threading.Lock()

        def __enter__(self):
            self._lock.acquire()
            gc.collect()
            return self

        def __exit__(self, *exc):
            self._lock.release()

    def worker():
        with tm.span("worker/job"):
            gc.collect()

    path = tmp_path / "gc.jsonl"
    with tm.recording() as rec:
        rec._lock = CollectingLock()
        t = threading.Thread(target=worker, daemon=True)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive(), "gc inside thread registration hung"
        rec.export_chrome(str(path))
        summ = rec.summary()
    assert summ["spans"]["worker/job"]["count"] == 1
    assert summ["spans"]["host/gc"]["count"] >= 1
    tm.validate_chrome_trace(str(path))     # host/gc B/E balanced


def test_compile_counter_counts_a_fresh_jit():
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    with tm.recording() as rec:
        f(jnp.arange(7.0)).block_until_ready()
        summ = rec.summary()
    assert summ["counters"]["jax/compiles"] >= 1
    assert summ["dists"]["jax/compile_s"]["count"] == \
        summ["counters"]["jax/compiles"]


def test_daemon_bank_growth_compiles_and_cached_flush_does_not():
    clear_sim_caches()
    grown = sweep_grid(workloads=("barnes",), configs=("proactive",),
                       seeds=(7,), sb_sizes=(None,), n_replicas=(None,))
    with ScenarioServer(n_stores=N, batch_cells=8,
                        batch_window_ms=1.0) as srv:
        srv.warm(GRID)
        with tm.recording():
            srv.query_batch(grown)              # new trace + wv rows
            grow = srv.stats()["telemetry"]
        with tm.recording():
            srv.query_batch(grown)              # every lane cached
            cached = srv.stats()["telemetry"]
    assert grow["spans"]["serve/bank_extend"]["count"] == 1
    assert grow["counters"]["jax/compiles"] > 0
    assert cached["counters"].get("jax/compiles", 0) == 0

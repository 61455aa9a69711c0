"""``serve/telemetry/*`` bench rows: the flight recorder measured on
the tiers it instruments (``repro.core.telemetry``, docs/observability.md).

Four claims, each a row family:

* **Per-stage breakdown of the streaming mega-grid.** One traced
  ``run_grid`` over ``scenarios.mega_grid`` (12 960 cells full mode)
  attributes wall time to the pipeline stages -- ``prep_frac`` (host
  tile prep, prefetch thread), ``h2d_frac`` (tile payload + bank
  placement), ``compute_frac`` (async program dispatch) and
  ``d2h_frac`` (the drain wait: device compute completion + outputs
  back to host -- with async dispatch the compute wall lands here).
  Fractions are of the summed stage time, so they sum to exactly 1.

* **Telemetry overhead.** The same warmed grid is re-run ``_REPS``
  interleaved off/on timing pairs (best-of each leg):
  ``telemetry_overhead_ratio`` = traced / untraced wall and must stay
  <= 1.05 (the near-zero-cost contract the CI ``telemetry`` job greps).
  ``oracle_bitident`` asserts the traced results ``==`` the untraced
  run AND the serial oracle on sampled cells -- recording never
  changes a number.

* **Serving p50/p99 from telemetry histograms.** A warmed
  :class:`ScenarioServer` serves a 70/30 hit/miss stream; the
  ``serve/query_ms`` histogram's p50/p99 must land within 20% of the
  bench-harness percentiles measured around the same calls
  (``p50_agree`` / ``p99_agree``), so latency SLOs no longer need an
  external harness. A submit() burst also exercises the queue-wait /
  batching-window histograms.

* **Chaos recovery timeline.** A mid-grid shard loss under
  ``chaos.inject`` yields the named nested spans
  detection -> rollback -> rebuild -> re-place -> re-dispatch;
  their durations are recorded as rows and ``recover_span_order``
  asserts the order.

``trace_events`` / ``trace_valid`` round-trip the traced mega-grid
through ``export_chrome`` + ``validate_chrome_trace`` (the same schema
check CI runs on the launcher's ``--trace-out`` file).
"""

from __future__ import annotations

import os
import tempfile
import time
from typing import Dict, List

import numpy as np

QUICK = os.environ.get("RECXL_BENCH_QUICK", "") not in ("", "0")
#: Same knob as the fig10 megagrid rows: paper-scale traces by default,
#: shrunken smoke under --quick.
MEGA_STORES = int(os.environ.get("RECXL_BENCH_MEGA_STORES",
                                 "2000" if QUICK else "30000"))
SERVE_STORES = int(os.environ.get("RECXL_BENCH_SERVE_STORES",
                                  "2000" if QUICK else "10000"))
N_QUERIES = 60 if QUICK else 300
#: Timed repetitions per (off, on) overhead leg, interleaved
#: off/on/off/on and taken best-of: host scheduler noise on a warm
#: full-grid run is several times the recorder's actual cost, so the
#: ratio must be a min-vs-min of alternating samples, not two
#: back-to-back walls.
_REPS = 5


def _row(name: str, derived, us: float = 0.0) -> Dict:
    return {"name": f"serve/telemetry/{name}", "us_per_call": us,
            "derived": derived}


def bench_telemetry() -> List[Dict]:
    from repro.core import chaos
    from repro.core import engine as E
    from repro.core import telemetry
    from repro.core.scenarios import (
        chaos_grid,
        grid_delta,
        mega_grid,
        sweep_grid,
    )
    from repro.core.serving import ScenarioServer
    from repro.core.simulator import clear_sim_caches, simulate_spec

    rows: List[Dict] = []

    # ---- traced mega-grid: per-stage breakdown + overhead ratio -------
    if QUICK:
        specs = mega_grid(seeds=(0,), replicas=(1, 3),
                          bandwidths=(160.0, 40.0), cn_counts=(16,),
                          sb_sizes=(72, 48))
    else:
        specs = mega_grid()
    n = len(specs)

    clear_sim_caches()
    E.run_grid(specs, n_stores=MEGA_STORES)       # warm compiles + memos

    # one traced run feeds the per-stage breakdown and the Chrome-trace
    # round-trip
    with telemetry.recording() as rec:
        res_on = E.run_grid(specs, n_stores=MEGA_STORES)
        summ = rec.summary()
        trace_path = os.path.join(
            tempfile.gettempdir(), f"recxl_bench_trace_{os.getpid()}.jsonl")
        n_events = rec.export_chrome(trace_path)

    res_off = E.run_grid(specs, n_stores=MEGA_STORES)
    t_off = t_on = float("inf")
    for _ in range(_REPS):
        t_off = min(t_off, _timed(
            lambda: E.run_grid(specs, n_stores=MEGA_STORES))[0])
        with telemetry.recording():
            t_on = min(t_on, _timed(
                lambda: E.run_grid(specs, n_stores=MEGA_STORES))[0])
    try:
        telemetry.validate_chrome_trace(trace_path)
        trace_valid = 1
    except ValueError:
        trace_valid = 0
    finally:
        try:
            os.unlink(trace_path)
        except OSError:
            pass

    spans = summ["spans"]

    def _total(*names: str) -> float:
        return sum(spans[s]["total"] for s in names if s in spans) / 1e3

    prep_s = _total("tile/prep")
    h2d_s = _total("tile/h2d", "bank/place")
    compute_s = _total("tile/dispatch")
    d2h_s = _total("tile/drain")
    stage_s = max(prep_s + h2d_s + compute_s + d2h_s, 1e-12)

    sample = list(range(0, n, max(1, n // 6)))[:6]
    ident = all(res_off[i] == res_on[i] for i in range(n))
    ident = ident and all(
        res_on[i] == simulate_spec(specs[i], n_stores=MEGA_STORES)
        for i in sample)

    rows += [
        _row("grid_cells", n),
        _row("stores_per_cell", MEGA_STORES),
        _row("prep_frac", round(prep_s / stage_s, 4)),
        _row("h2d_frac", round(h2d_s / stage_s, 4)),
        _row("compute_frac", round(compute_s / stage_s, 4)),
        _row("d2h_frac", round(d2h_s / stage_s, 4)),
        _row("frac_sum", round((prep_s + h2d_s + compute_s + d2h_s)
                               / stage_s, 4)),
        _row("stage_total_s", round(stage_s, 3),
             us=stage_s * 1e6 / max(n, 1)),
        _row("telemetry_overhead_ratio", round(t_on / t_off, 3),
             us=t_on * 1e6 / max(n, 1)),
        _row("trace_events", n_events),
        _row("trace_valid", trace_valid),
    ]

    # ---- serving: telemetry histogram p50/p99 vs the bench harness ----
    warm_grid = sweep_grid(seeds=(0, 1), n_replicas=(None, 2, 4),
                           sb_sizes=(None, 48))
    novel = grid_delta(warm_grid,
                       workloads=("ycsb", "canneal", "barnes"),
                       configs=("proactive", "baseline"),
                       n_replicas=(3,), sb_sizes=(None, 48), seeds=(0, 2))
    rng = np.random.default_rng(0)
    stream = [warm_grid[rng.integers(len(warm_grid))]
              if rng.random() < 0.7
              else novel[rng.integers(len(novel))]
              for _ in range(N_QUERIES)]

    clear_sim_caches()
    with ScenarioServer(n_stores=SERVE_STORES, batch_cells=32) as srv:
        srv.warm(warm_grid)
        with telemetry.recording() as rec:
            lat = np.empty(len(stream))
            for i, spec in enumerate(stream):
                t1 = time.perf_counter()
                srv.query(spec)
                lat[i] = time.perf_counter() - t1
            # snapshot the query histogram BEFORE the submit burst so
            # the telemetry percentiles cover exactly the same samples
            # the harness timed; the burst only feeds the queue-wait /
            # batching-window histograms
            ssumm = rec.summary()
            for f in [srv.submit(s) for s in stream[:16]]:
                f.result()
            wsumm = rec.summary()
    lat_ms = np.sort(lat) * 1e3
    p50_h = float(lat_ms[len(lat_ms) // 2])
    p99_h = float(lat_ms[int(len(lat_ms) * 0.99)])
    q = ssumm["dists"]["serve/query_ms"]
    p50_t, p99_t = q["p50"], q["p99"]
    waits = wsumm["dists"].get("serve/queue_wait_ms", {})
    rows += [
        _row("p50_ms_telemetry", round(p50_t, 3)),
        _row("p50_ms_harness", round(p50_h, 3)),
        _row("p50_agree", int(abs(p50_t - p50_h) <= 0.2 * p50_h)),
        _row("p99_ms_telemetry", round(p99_t, 3)),
        _row("p99_ms_harness", round(p99_h, 3)),
        _row("p99_agree", int(abs(p99_t - p99_h) <= 0.2 * p99_h)),
        _row("queue_wait_p50_ms", round(waits.get("p50", 0.0), 3)),
    ]

    # ---- chaos: recovery timeline with named span durations -----------
    import jax
    n_sh = min(2, len(jax.devices()))
    cg = chaos_grid()[:24]
    c_stores = 500 if QUICK else 5000
    base = E.run_grid(cg, n_stores=c_stores, tile_cells=8, n_shards=n_sh)
    with chaos.inject(chaos.ChaosConfig(lose_shard=n_sh - 1,
                                        lose_at_dispatch=2)):
        with telemetry.recording() as rec:
            res_c = E.run_grid(cg, n_stores=c_stores, tile_cells=8,
                               n_shards=n_sh)
            evs = rec.span_events("recover")
            csumm = rec.summary()
    order = [nm for ph, _t, nm, _tid in evs if ph == "B"]
    want = ["recover", "recover/detect", "recover/rollback",
            "recover/rebuild", "recover/replace", "recover/redispatch"]
    order_ok = int(order == want and all(a == b
                                         for a, b in zip(res_c, base)))
    cs = csumm["spans"]

    def _ms(name: str) -> float:
        return round(cs.get(name, {}).get("total", 0.0), 3)

    rows += [
        _row("recover_detect_ms", _ms("recover/detect")),
        _row("recover_rollback_ms", _ms("recover/rollback")),
        _row("recover_rebuild_ms", _ms("recover/rebuild")),
        _row("recover_replace_ms", _ms("recover/replace")),
        _row("recover_redispatch_ms", _ms("recover/redispatch")),
        _row("recover_total_ms", _ms("recover")),
        _row("recover_span_order", order_ok),
        _row("oracle_bitident", int(ident and order_ok)),
    ]
    return rows


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return time.perf_counter() - t0, out

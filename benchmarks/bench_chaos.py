"""``serve/chaos/*`` bench rows: the fault-injection + recovery tier
(``repro.core.chaos``, docs/resilience.md).

Each row measures one leg of the PR-9 resilience contract on the
streaming engine and the serving daemon:

* ``recovered_bitident`` -- EVERY recovered run in this bench (engine
  spare-replacement, engine degraded-mesh, server mid-stream loss,
  server journal rebuild, corrupt-row re-place, upload retries) is
  re-checked ``==`` against the fault-free oracle; must be 1;
* ``steady_compiles`` -- tile programs traced by steady-state re-runs
  AFTER spare-path recovery (engine and server summed; must be 0: the
  rebuilt rows are re-placed into the same shapes/shardings);
* ``detection_ms`` -- gather-path CRC sampling latency from fault
  injection to :class:`IntegrityError` on a corrupted bank row;
* ``recovery_ms`` / ``server_recovery_ms`` / ``journal_recovery_ms`` --
  wall-clock of one spare-replacement recovery: engine replica rebuild,
  server replica rebuild mid-query-stream, and the 1-shard server's
  Logging-Unit journal path;
* ``degraded_qps_ratio`` -- throughput of the degraded-mesh
  configuration (one fewer shard, bank replicated -- what a recovered
  run keeps serving on when no spare exists) over the healthy mesh;
* ``replica_byte_overhead`` -- measured resident device bytes of the
  ``k_replicas=2`` placement over the plain ``k=1`` sub-bank (~2x the
  stacks; arrivals stay replicated either way);
* ``upload_retries`` -- injected h2d failures absorbed by the bounded
  retry policy without surfacing.

Registered by benchmarks/run.py; the ``chaos`` CI job runs this in
``--quick`` mode and asserts ``recovered_bitident==1`` and
``steady_compiles==0``.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List

QUICK = os.environ.get("RECXL_BENCH_QUICK", "") not in ("", "0")
STORES = int(os.environ.get("RECXL_BENCH_CHAOS_STORES",
                            "2000" if QUICK else "10000"))


def bench_chaos() -> List[Dict]:
    import jax

    from repro.core import chaos
    from repro.core import engine as E
    from repro.core.chaos import ChaosConfig
    from repro.core.scenarios import chaos_grid, run_sweep, sweep_grid
    from repro.core.serving import ScenarioServer
    from repro.core.simulator import clear_sim_caches

    n_shards = min(8, len(jax.devices()))
    grid = (chaos_grid(replicas=(None, 2), bandwidths=(None,)) if QUICK
            else chaos_grid())

    def bitident(got, want):
        return len(got) == len(want) and all(a == b
                                             for a, b in zip(got, want))

    clear_sim_caches()
    oracle = run_sweep(grid, n_stores=STORES, engine="serial")

    # healthy baseline (k=1): timing + resident bytes for the ratios
    clear_sim_caches()
    t0 = time.perf_counter()
    base = E.run_grid(grid, n_stores=STORES, tile_cells=16,
                      n_shards=n_shards)
    base_s = time.perf_counter() - t0
    ident = bitident(base, oracle)
    k1_bytes = E.bank_stats()["bank_dev_bytes"]

    # spare replacement: shard lost mid-grid, rebuilt from the replica
    # block, re-placed into the same shapes -- then a steady-state
    # re-run that must trace nothing new
    steady_compiles = 0
    with chaos.inject(ChaosConfig(lose_shard=n_shards - 1,
                                  lose_at_dispatch=2)) as cs:
        clear_sim_caches()
        rec = E.run_grid(grid, n_stores=STORES, tile_cells=16,
                         n_shards=n_shards)
        ident = ident and bitident(rec, oracle)
        k2_bytes = E.bank_stats()["bank_dev_bytes"]
        tc0 = E.trace_count()
        again = E.run_grid(grid, n_stores=STORES, tile_cells=16,
                           n_shards=n_shards)
        steady_compiles += E.trace_count() - tc0
        ident = ident and bitident(again, oracle)
        rep = cs.report()
    recovery_ms = rep["recoveries"][0]["ms"] if rep["recoveries"] else -1.0
    recovery_source = (rep["recoveries"][0]["source"]
                       if rep["recoveries"] else "none")

    # detection latency: corrupted resident row caught by gather-path
    # CRC sampling, recovered by a full re-place from the host truth
    with chaos.inject(ChaosConfig(corrupt_wv_row=0)) as cs:
        clear_sim_caches()
        det = E.run_grid(grid, n_stores=STORES, tile_cells=16,
                         n_shards=n_shards)
        ident = ident and bitident(det, oracle)
        detection_ms = cs.report()["detection_ms"]

    # failed h2d uploads absorbed by the bounded retry policy
    with chaos.inject(ChaosConfig(upload_failures=2)) as cs:
        clear_sim_caches()
        up = E.run_grid(grid, n_stores=STORES, tile_cells=16,
                        n_shards=n_shards)
        ident = ident and bitident(up, oracle)
        upload_retries = cs.report()["upload_retries"]

    # degraded mesh: the configuration a spare-less recovery keeps
    # serving on (one fewer shard, bank replicated) -- measure its
    # throughput against the healthy mesh, and run one actual
    # degraded-recovery pass for bit-identity
    degraded_ratio = 1.0
    if n_shards > 1:
        clear_sim_caches()
        t0 = time.perf_counter()
        deg = E.run_grid(grid, n_stores=STORES, tile_cells=16,
                         n_shards=n_shards - 1,
                         bank_partition="replicated")
        deg_s = time.perf_counter() - t0
        ident = ident and bitident(deg, oracle)
        degraded_ratio = (len(grid) / deg_s) / (len(grid) / base_s)
        with chaos.inject(ChaosConfig(lose_shard=0, lose_at_dispatch=1,
                                      recovery="degraded")):
            clear_sim_caches()
            drec = E.run_grid(grid, n_stores=STORES, tile_cells=16,
                              n_shards=n_shards)
            ident = ident and bitident(drec, oracle)
            ident = ident and E.bank_stats()["degraded"] is True

    # serving daemon: shard loss mid-query-stream (replica rebuild,
    # capacity kept, zero recompiles), then the 1-shard journal path
    warm_grid = sweep_grid(workloads=("ycsb", "raytrace"))
    novel = sweep_grid(workloads=("barnes",),
                       configs=("baseline", "proactive"),
                       n_replicas=(2, 3))
    clear_sim_caches()
    novel_oracle = run_sweep(novel, n_stores=STORES, engine="serial")

    with chaos.inject(ChaosConfig(lose_shard=max(n_shards - 1, 0),
                                  lose_at_dispatch=2)) as cs:
        clear_sim_caches()
        with ScenarioServer(n_stores=STORES, n_shards=n_shards,
                            batch_cells=16) as srv:
            srv.warm(warm_grid)
            srv.reset_stats()
            got = srv.query_batch(novel)
            ident = ident and bitident(got, novel_oracle)
            steady_compiles += srv.stats()["compiled_programs"]
            again = srv.query_batch(novel)
            ident = ident and bitident(again, novel_oracle)
            steady_compiles += srv.stats()["compiled_programs"]
        rep = cs.report()
    server_recovery_ms = (rep["recoveries"][0]["ms"]
                          if rep["recoveries"] else -1.0)

    with chaos.inject(ChaosConfig(lose_shard=0, lose_at_dispatch=2)) as cs:
        clear_sim_caches()
        with ScenarioServer(n_stores=STORES, batch_cells=16) as srv:
            srv.warm(warm_grid)
            got = srv.query_batch(novel)
            ident = ident and bitident(got, novel_oracle)
        rep = cs.report()
    journal_recovery_ms = (rep["recoveries"][0]["ms"]
                           if rep["recoveries"] else -1.0)
    journal_source = (rep["recoveries"][0]["source"]
                      if rep["recoveries"] else "none")

    return [
        {"name": "serve/chaos/cells", "us_per_call": 0.0,
         "derived": len(grid)},
        {"name": "serve/chaos/n_shards", "us_per_call": 0.0,
         "derived": n_shards},
        {"name": "serve/chaos/recovered_bitident", "us_per_call": 0.0,
         "derived": int(ident)},
        {"name": "serve/chaos/steady_compiles", "us_per_call": 0.0,
         "derived": steady_compiles},
        {"name": "serve/chaos/detection_ms",
         "us_per_call": detection_ms * 1e3,
         "derived": round(detection_ms, 2)},
        {"name": "serve/chaos/recovery_ms",
         "us_per_call": recovery_ms * 1e3,
         "derived": round(recovery_ms, 2)},
        {"name": "serve/chaos/recovery_source", "us_per_call": 0.0,
         "derived": recovery_source},
        {"name": "serve/chaos/server_recovery_ms",
         "us_per_call": server_recovery_ms * 1e3,
         "derived": round(server_recovery_ms, 2)},
        {"name": "serve/chaos/journal_recovery_ms",
         "us_per_call": journal_recovery_ms * 1e3,
         "derived": round(journal_recovery_ms, 2)},
        {"name": "serve/chaos/journal_source", "us_per_call": 0.0,
         "derived": journal_source},
        {"name": "serve/chaos/degraded_qps_ratio", "us_per_call": 0.0,
         "derived": round(degraded_ratio, 3)},
        {"name": "serve/chaos/replica_byte_overhead", "us_per_call": 0.0,
         "derived": round(k2_bytes / max(k1_bytes, 1), 3)},
        {"name": "serve/chaos/upload_retries", "us_per_call": 0.0,
         "derived": upload_retries},
    ]

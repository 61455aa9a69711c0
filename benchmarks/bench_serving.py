"""``serve/latency/*`` bench rows: the scenario-serving daemon
(``repro.core.serving`` -- persistent engine service, incremental bank
diffs, canonical query batching).

One :class:`ScenarioServer` is warmed on a mixed-SB sweep grid, then a
seeded query stream (70% lane-cache hits against the warm grid, 30%
novel diff-upload cells) is served one query at a time -- the
latency-SLO shape of the ROADMAP's "engine as a service" goal. Rows
record:

* ``p50_ms`` / ``p99_ms`` per-query latency and ``qps`` throughput of
  the steady-state stream (p99 is dominated by the miss flushes --
  one serve-tile scan each; p50 is the pure host-math hit path);
* ``cache_hit_ratio`` -- lane-cache hits over queries (the scan-lane
  dedup working as an answer cache);
* ``steady_compiles`` -- tile programs traced DURING the stream
  (must be 0: serving reuses the warmed canonical signatures);
* ``h2d_per_query_b`` -- marginal host->device bytes per query, and
  ``single_miss_h2d_frac`` -- the marginal bytes of ONE warm novel
  single-cell query over a cold full-bank upload (the incremental-diff
  headline: row-scale, not bank-scale; asserted <= 1%);
* ``bank_partition`` / ``sharded_steady_compiles`` /
  ``bank_dev_mb_per_shard`` -- a second daemon at > 1 shard holds the
  capacity bank PARTITIONED (per-shard sub-banks, PR 8): steady-state
  serving must still trace 0 programs there, and the measured
  per-shard resident bytes are recorded;
* ``oracle_bitident`` -- every streamed answer re-checked ``==``
  against the serial oracle (sharded answers included).

Registered by benchmarks/run.py; the ``serving`` CI job asserts the
``oracle_bitident`` and ``cache_hit_ratio`` rows in ``--quick`` mode.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List

import numpy as np

QUICK = os.environ.get("RECXL_BENCH_QUICK", "") not in ("", "0")
#: Stores per timeline for the serving rows (the daemon's sweet spot is
#: many small queries, so this stays below the mega-grid store counts).
STORES = int(os.environ.get("RECXL_BENCH_SERVE_STORES",
                            "2000" if QUICK else "10000"))
#: Live queries in the steady-state stream.
N_QUERIES = 60 if QUICK else 400


def bench_serving() -> List[Dict]:
    from repro.core import engine as E
    from repro.core.scenarios import grid_delta, run_sweep, sweep_grid
    from repro.core.serving import ScenarioServer
    from repro.core.simulator import clear_sim_caches

    # a rich warm bank (hundreds of wv rows) so the single-miss probe's
    # one-row diff is measured against a realistically sized platform
    warm_grid = sweep_grid(seeds=(0, 1), n_replicas=(None, 2, 4),
                           sb_sizes=(None, 48),
                           link_bw_gbps=(None, 40.0))
    novel = grid_delta(warm_grid,
                       workloads=("ycsb", "canneal", "barnes", "raytrace"),
                       configs=("proactive", "baseline", "parallel"),
                       n_replicas=(3,), sb_sizes=(None, 48),
                       seeds=(0, 2))
    rng = np.random.default_rng(0)
    stream = [warm_grid[rng.integers(len(warm_grid))]
              if rng.random() < 0.7
              else novel[rng.integers(len(novel))]
              for _ in range(N_QUERIES)]

    clear_sim_caches()
    rows: List[Dict] = []
    with ScenarioServer(n_stores=STORES, batch_cells=32) as srv:
        t0 = time.perf_counter()
        srv.warm(warm_grid)
        warm_s = time.perf_counter() - t0
        warm_stats = srv.stats()

        srv.reset_stats()
        tc0 = E.trace_count()
        lat = np.empty(len(stream))
        t0 = time.perf_counter()
        served = []
        for i, spec in enumerate(stream):
            t1 = time.perf_counter()
            served.append(srv.query(spec))
            lat[i] = time.perf_counter() - t1
        wall = time.perf_counter() - t0
        steady_compiles = E.trace_count() - tc0
        st = srv.stats()
        lat_ms = np.sort(lat) * 1e3

        # marginal diff upload of ONE warm novel single-cell query,
        # against what a cold engine would ship for its bank; a fresh
        # seed forces both a new trace row and a new (w, v) row
        probe = grid_delta(warm_grid + stream,
                           workloads=("bodytrack",),
                           configs=("proactive",), seeds=(2,))
        srv.reset_stats()
        served_probe = srv.query_batch(probe)
        probe_h2d = srv.stats()["h2d_bytes"]
        full_upload = srv.stats()["bank_bytes"]

    # partitioned capacity bank (PR 8): a sharded daemon holds the
    # capacity sub-bank partitioned over the cells mesh -- steady-state
    # compiles must STILL be 0 with owner-scheduled serve tiles, and
    # stats() reports the measured per-shard resident bytes
    import jax
    n_sh = min(2, len(jax.devices()))
    with ScenarioServer(n_stores=STORES, batch_cells=32,
                        n_shards=n_sh) as ssrv:
        ssrv.warm(warm_grid)
        tc0 = E.trace_count()
        sh_served = [ssrv.query(s) for s in stream[:24]]
        sharded_compiles = E.trace_count() - tc0
        sh_stats = ssrv.stats()

    # serial oracle for every answer the daemon produced (fresh caches:
    # the oracle must not ride the daemon's memos)
    clear_sim_caches()
    oracle = run_sweep(stream + probe, n_stores=STORES, engine="serial")
    ident = all(a == b for a, b in zip(served + served_probe, oracle))
    ident = ident and all(a == b for a, b in zip(sh_served, oracle))

    rows += [
        {"name": "serve/latency/queries", "us_per_call": 0.0,
         "derived": len(stream)},
        {"name": "serve/latency/stores_per_cell", "us_per_call": 0.0,
         "derived": STORES},
        {"name": "serve/latency/warm_s",
         "us_per_call": warm_s * 1e6 / max(len(warm_grid), 1),
         "derived": round(warm_s, 2)},
        {"name": "serve/latency/warm_bank_rows", "us_per_call": 0.0,
         "derived": warm_stats["bank_rows"]},
        {"name": "serve/latency/p50_ms",
         "us_per_call": float(lat_ms[len(lat_ms) // 2]) * 1e3,
         "derived": round(float(lat_ms[len(lat_ms) // 2]), 3)},
        {"name": "serve/latency/p99_ms",
         "us_per_call": float(lat_ms[int(len(lat_ms) * 0.99)]) * 1e3,
         "derived": round(float(lat_ms[int(len(lat_ms) * 0.99)]), 3)},
        {"name": "serve/latency/qps", "us_per_call": wall * 1e6 / len(stream),
         "derived": round(len(stream) / wall, 1)},
        {"name": "serve/latency/cache_hit_ratio", "us_per_call": 0.0,
         "derived": round(st["hit_ratio"], 3)},
        {"name": "serve/latency/steady_compiles", "us_per_call": 0.0,
         "derived": steady_compiles},
        {"name": "serve/latency/h2d_per_query_b", "us_per_call": 0.0,
         "derived": round(st["h2d_bytes"] / len(stream), 1)},
        {"name": "serve/latency/single_miss_h2d_frac", "us_per_call": 0.0,
         "derived": round(probe_h2d / max(full_upload, 1), 5)},
        {"name": "serve/latency/bank_partition", "us_per_call": 0.0,
         "derived": str(sh_stats["bank_partition"])},
        {"name": "serve/latency/sharded_steady_compiles", "us_per_call": 0.0,
         "derived": sharded_compiles},
        {"name": "serve/latency/bank_dev_mb_per_shard", "us_per_call": 0.0,
         "derived": round(sh_stats["bank_dev_bytes_per_shard"] / (1 << 20),
                          3)},
        {"name": "serve/latency/oracle_bitident", "us_per_call": 0.0,
         "derived": int(ident)},
    ]
    return rows

"""``fig17/directory/*`` bench rows: the queueing-coupled directory
model (two-level max-plus recurrence, docs/simulator.md) on the
streaming banked engine tier.

One cold end-to-end run of ``scenarios.directory_mega_grid`` (2 592
cells full mode, a shrunken smoke under ``--quick`` /
``RECXL_BENCH_QUICK=1``) through ``run_sweep(engine="stream")``, plus a
directory-loaded ``recovery_sweep``. Rows record:

* the per-load geomean slowdowns of the **baseline** configuration over
  the in-grid ``directory_load=0.0`` cells (bit-identical to the
  axis-off semantics -- the normalization baseline) and
  ``slowdown_monotone`` asserting they are non-decreasing in offered
  load. Baseline pays the shard's M/D/1 wait serially per store;
  ``proactive_hides_load`` reports the same corner under proactive,
  whose decoupled drain chain absorbs the w-side delay -- the
  capacity-vs-resilience headline of the coupling;
* that the coupled mega-grid still runs on the streaming banked data
  plane with a handful of compiled programs (``engine_compiles``) and
  scan-lane dedup active (``scan_lanes`` < ``cells``: load-0 cells
  dedup across CN counts, coupled cells sharing a resolved
  ``DirectoryParams`` + max-plus row are one lane);
* ``sharer_pool`` -- the directory-derived census (16-CN, N_r=3) that
  replaces the fixed ``contention.SHARER_POOL`` binomial;
* ``oracle_bitident`` -- sampled cells re-run through BOTH serial
  references (the jitted ``simulate_spec`` oracle and the pure-Python
  ``contention.serial_oracle`` pre-collapse loop, which routes through
  ``_prepare_cell`` and therefore folds the identical level-2 epoch
  delays) and checked ``==``;
* ``downtime_load_over_base`` -- the recovery coupling: the directory
  walk of Algorithm 1 dilated by the shard's background utilization.

Registered by benchmarks/run.py (kept out of protocol_benches.py's
import graph); the ``low-memory`` CI job asserts the
``oracle_bitident`` row in ``--quick`` mode.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List

import numpy as np

QUICK = os.environ.get("RECXL_BENCH_QUICK", "") not in ("", "0")
#: Store count for the directory mega-grid rows (paper-scale traces by
#: default; the quick smoke shrinks them so CI still exercises the
#: tier). Shares the megagrid override knob.
STORES = int(os.environ.get("RECXL_BENCH_MEGA_STORES",
                            "2000" if QUICK else "30000"))

#: Offered-load axis of the slowdown rows; 0.0 is the in-grid
#: normalization baseline (bit-identical to ``directory_load=None``).
LOADS = (0.0, 0.2, 0.4, 0.7)


def bench_directory() -> List[Dict]:
    from repro.core import engine as E
    from repro.core.contention import serial_oracle
    from repro.core.directory import sharer_pool
    from repro.core.scenarios import (
        directory_mega_grid,
        recovery_sweep,
        run_sweep,
    )
    from repro.core.simulator import (
        ScenarioSpec,
        clear_sim_caches,
        simulate_spec,
    )

    if QUICK:
        workloads = ("ycsb", "canneal", "streamcluster")
        specs = directory_mega_grid(
            workloads=workloads, configs=("baseline", "proactive"),
            seeds=(0,), replicas=(3,), cn_counts=(16, 4),
            loads=LOADS, sb_sizes=(72,))
    else:
        specs = directory_mega_grid(loads=LOADS)
        workloads = tuple(dict.fromkeys(s.workload for s in specs))
    n = len(specs)

    clear_sim_caches()
    traces0 = E.trace_count()
    t0 = time.perf_counter()
    res = run_sweep(specs, n_stores=STORES, engine="stream")
    engine_s = time.perf_counter() - t0
    compiles = E.trace_count() - traces0
    stats = E.bank_stats()
    by = {s: r for s, r in zip(specs, res)}

    rows: List[Dict] = [
        {"name": "fig17/directory/cells", "us_per_call": 0.0, "derived": n},
        {"name": "fig17/directory/stores_per_cell", "us_per_call": 0.0,
         "derived": STORES},
        {"name": "fig17/directory/engine_s",
         "us_per_call": engine_s * 1e6 / n, "derived": round(engine_s, 2)},
        {"name": "fig17/directory/engine_compiles", "us_per_call": 0.0,
         "derived": compiles},
        {"name": "fig17/directory/scan_lanes", "us_per_call": 0.0,
         "derived": stats["scan_lanes"]},
        {"name": "fig17/directory/lane_dedup_ratio", "us_per_call": 0.0,
         "derived": round(n / max(stats["scan_lanes"], 1), 2)},
        {"name": "fig17/directory/bank_rows", "us_per_call": 0.0,
         "derived": f"{stats['trace_rows']}trace+{stats['wv_rows']}wv"},
        {"name": "fig17/directory/h2d_mb", "us_per_call": 0.0,
         "derived": round(stats["h2d_bytes"] / (1 << 20), 1)},
        {"name": "fig17/directory/sharer_pool", "us_per_call": 0.0,
         "derived": sharer_pool(16, 3)},
    ]

    # --- per-load geomean slowdown over the in-grid load-0 baseline ---
    # Baseline config: the shard wait lands on the serial commit chain,
    # so slowdown must grow with offered load. (Proactive's drain chain
    # absorbs it -- reported separately, never asserted monotone.)
    def cell(w: str, config: str, load: float) -> ScenarioSpec:
        return ScenarioSpec(w, config, seed=0, n_replicas=3, n_cns=16,
                            sb_size=72, directory_load=load)

    geomeans = []
    for load in LOADS[1:]:
        sds = [by[cell(w, "baseline", load)].exec_time_ns
               / by[cell(w, "baseline", 0.0)].exec_time_ns
               for w in workloads]
        gm = float(np.exp(np.mean(np.log(sds))))
        geomeans.append(gm)
        rows.append({"name": f"fig17/directory/load{load}_geomean_slowdown",
                     "us_per_call": 0.0, "derived": round(gm, 3)})
    monotone = all(b >= a for a, b in zip([1.0] + geomeans, geomeans))
    rows.append({"name": "fig17/directory/slowdown_monotone",
                 "us_per_call": 0.0, "derived": int(monotone)})
    w0 = workloads[0]
    rows.append({"name": f"fig17/directory/{w0}/proactive_hides_load",
                 "us_per_call": 0.0,
                 "derived": round(
                     by[cell(w0, "proactive", LOADS[-1])].exec_time_ns
                     / by[cell(w0, "proactive", 0.0)].exec_time_ns, 3)})

    # --- oracle bit-identity on sampled cells (both serial references) -
    ident = True
    for i in list(range(0, n, max(1, n // 4)))[:5]:
        s = specs[i]
        rs = simulate_spec(s, n_stores=STORES)
        ro = serial_oracle(s, n_stores=STORES)
        ident = ident and all(
            getattr(res[i], f) == getattr(rs, f) == getattr(ro, f)
            for f in ("exec_time_ns", "repl_at_head_frac", "sb_full_frac"))
    rows.append({"name": "fig17/directory/oracle_bitident",
                 "us_per_call": 0.0, "derived": int(ident)})

    # --- recovery coupling: directory walk dilated by background load -
    base_sweep = recovery_sweep(workloads=("ycsb",), cn_counts=(16,))
    load_sweep = recovery_sweep(workloads=("ycsb",), cn_counts=(16,),
                                directory_load=0.6)
    t_mid = base_sweep.fail_times_ms[1]
    rows.append({"name": "fig17/directory/downtime_load_over_base",
                 "us_per_call": 0.0,
                 "derived": round(load_sweep.total_ms("ycsb", t_mid, 16)
                                  / base_sweep.total_ms("ycsb", t_mid, 16),
                                  3)})
    return rows

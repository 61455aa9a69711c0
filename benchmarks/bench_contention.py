"""``fig17/contention/*`` bench rows: the contention & crash-consistency
scenario subsystem (repro.core.contention, docs/contention.md) on the
streaming banked engine tier.

One cold end-to-end run of ``scenarios.contention_mega_grid`` (2 592
cells full mode, a shrunken smoke under ``--quick`` /
``RECXL_BENCH_QUICK=1``) through ``run_sweep(engine="stream")``, plus a
contended ``recovery_sweep``. Rows record:

* the contended-regime slowdowns the new axes model (per-workload and
  geomean: heavy contention -- conflict_rate=0.5, read_share=0.6,
  eager persist ordering -- over the in-grid neutral cells, which are
  bit-identical to the uncontended semantics);
* that the contended mega-grid still runs on the streaming banked data
  plane with a handful of compiled programs (``engine_compiles`` -- the
  acceptance bound is <= 3) and scan-lane dedup active (``scan_lanes``
  < ``cells``: the CN axis shares lanes because contention keys
  deliberately exclude ``n_cns``);
* ``oracle_bitident`` -- sampled cells re-run through BOTH serial
  references (the jitted ``simulate_spec`` oracle and the pure-Python
  ``contention.serial_oracle`` pre-collapse loop) and checked ``==``,
  so the subsystem's rows can never quietly come from drifting
  arithmetic;
* ``downtime_conflict_over_base`` -- the SS VII-E recovery coupling:
  estimated downtime under heavy conflict vs the uncontended model.

Registered by benchmarks/run.py (kept out of protocol_benches.py's
import graph); the ``docs`` and ``low-memory`` CI jobs assert the
``oracle_bitident`` row in ``--quick`` mode.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List

import numpy as np

QUICK = os.environ.get("RECXL_BENCH_QUICK", "") not in ("", "0")
#: Store count for the contention mega-grid rows (paper-scale traces by
#: default; the quick smoke shrinks them so CI still exercises the
#: tier). Shares the megagrid override knob.
STORES = int(os.environ.get("RECXL_BENCH_MEGA_STORES",
                            "2000" if QUICK else "30000"))

#: The heavy-contention corner reported by the slowdown rows (must be
#: present in both the quick and full grids).
HOT = dict(conflict_rate=0.5, read_share=0.6, consistency_schedule="eager")
#: The in-grid neutral corner (bit-identical to the uncontended
#: semantics -- the normalization baseline).
BASE = dict(conflict_rate=0.0, read_share=0.0, consistency_schedule="lazy")


def bench_contention() -> List[Dict]:
    from repro.core import engine as E
    from repro.core.contention import serial_oracle
    from repro.core.scenarios import (
        contention_mega_grid,
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
        specs = contention_mega_grid(
            workloads=workloads, seeds=(0,), replicas=(1,),
            cn_counts=(16, 8), conflict_rates=(0.0, 0.5),
            read_shares=(0.0, 0.6), schedules=("lazy", "eager"))
    else:
        specs = contention_mega_grid()
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

    # --- contended-regime slowdowns (hot corner over in-grid neutral) --
    def cell(w: str, **axes) -> ScenarioSpec:
        return ScenarioSpec(w, "proactive", seed=0, n_replicas=1,
                            n_cns=16, **axes)

    rows: List[Dict] = [
        {"name": "fig17/contention/cells", "us_per_call": 0.0, "derived": n},
        {"name": "fig17/contention/stores_per_cell", "us_per_call": 0.0,
         "derived": STORES},
        {"name": "fig17/contention/engine_s",
         "us_per_call": engine_s * 1e6 / n, "derived": round(engine_s, 2)},
        {"name": "fig17/contention/engine_compiles", "us_per_call": 0.0,
         "derived": compiles},
        {"name": "fig17/contention/scan_lanes", "us_per_call": 0.0,
         "derived": stats["scan_lanes"]},
        {"name": "fig17/contention/lane_dedup_ratio", "us_per_call": 0.0,
         "derived": round(n / max(stats["scan_lanes"], 1), 2)},
        {"name": "fig17/contention/bank_rows", "us_per_call": 0.0,
         "derived": f"{stats['trace_rows']}trace+{stats['wv_rows']}wv"},
        {"name": "fig17/contention/h2d_mb", "us_per_call": 0.0,
         "derived": round(stats["h2d_bytes"] / (1 << 20), 1)},
    ]
    slowdowns = []
    for w in workloads:
        hot = by[cell(w, **HOT)].exec_time_ns
        base = by[cell(w, **BASE)].exec_time_ns
        slowdowns.append(hot / base)
    for w, sd in list(zip(workloads, slowdowns))[:3]:
        rows.append({"name": f"fig17/contention/{w}/hot_over_base",
                     "us_per_call": 0.0, "derived": round(sd, 3)})
    rows.append({"name": "fig17/contention/geomean_hot_over_base",
                 "us_per_call": 0.0,
                 "derived": round(float(np.exp(np.mean(np.log(slowdowns)))),
                                  3)})

    # --- conflict-only and schedule-only regimes (full grid has both) --
    mid = by.get(cell(workloads[0], conflict_rate=0.5, read_share=0.0,
                      consistency_schedule="lazy"))
    if mid is not None:
        base = by[cell(workloads[0], **BASE)].exec_time_ns
        rows.append({
            "name": f"fig17/contention/{workloads[0]}/conflict_only",
            "us_per_call": 0.0,
            "derived": round(mid.exec_time_ns / base, 3)})

    # --- oracle bit-identity on sampled cells (both serial references) -
    ident = True
    for i in list(range(0, n, max(1, n // 4)))[:5]:
        s = specs[i]
        rs = simulate_spec(s, n_stores=STORES)
        ro = serial_oracle(s, n_stores=STORES)
        ident = ident and all(
            getattr(res[i], f) == getattr(rs, f) == getattr(ro, f)
            for f in ("exec_time_ns", "repl_at_head_frac", "sb_full_frac"))
    rows.append({"name": "fig17/contention/oracle_bitident",
                 "us_per_call": 0.0, "derived": int(ident)})

    # --- recovery coupling: downtime varies with the contention regime -
    base_sweep = recovery_sweep(workloads=("ycsb",), cn_counts=(16,))
    hot_sweep = recovery_sweep(workloads=("ycsb",), cn_counts=(16,),
                               conflict_rate=0.5)
    eager_sweep = recovery_sweep(workloads=("ycsb",), cn_counts=(16,),
                                 consistency_schedule="eager")
    t_mid = base_sweep.fail_times_ms[1]
    base_ms = base_sweep.total_ms("ycsb", t_mid, 16)
    rows.append({"name": "fig17/contention/downtime_conflict_over_base",
                 "us_per_call": 0.0,
                 "derived": round(hot_sweep.total_ms("ycsb", t_mid, 16)
                                  / base_ms, 3)})
    rows.append({"name": "fig17/contention/downtime_eager_over_base",
                 "us_per_call": 0.0,
                 "derived": round(eager_sweep.total_ms("ycsb", t_mid, 16)
                                  / base_ms, 3)})
    return rows

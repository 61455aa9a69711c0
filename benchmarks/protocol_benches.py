"""Benchmarks backed by the protocol simulator -- one per paper figure.

Every function returns a list of row dicts with at least
(name, us_per_call, derived); run.py renders them as CSV.

All grids run through ``scenarios.run_sweep`` (the streaming engine,
``repro.core.engine.run_grid``): one call per figure instead of a
serial Python loop per cell. ``bench_batch_speedup`` times the engine
against the serial oracle on the full Fig. 10 grid; ``bench_megagrid``
times the engine on the >=10^4-cell sensitivity cross-product and
checks its cells against the serial oracle. All rows land in the ``BENCH_protocol.json``
trajectory.
``bench_recovery`` adds the SS VII-E downtime model rows
(``fig9/recovery/*``) from one batched failure-time x node sweep.

See README.md (in this directory) for the bench-row schema.

Quick smoke mode for CI: set ``RECXL_BENCH_QUICK=1`` (shrinks the store
count) -- or override the store count directly with
``RECXL_BENCH_STORES=<n>``.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List, Sequence

from repro.configs.recxl_paper import PAPER_CLAIMS, WORKLOADS
from repro.core.scenarios import fig16_grid, fig17_grid, fig18_grid, run_sweep
from repro.core.simulator import (
    CONFIGS,
    ScenarioSpec,
    SimResult,
    clear_sim_caches,
    geomean_slowdowns,
    simulate,
    slowdowns_from_results,
)

QUICK = os.environ.get("RECXL_BENCH_QUICK", "") not in ("", "0")
N_STORES = int(os.environ.get("RECXL_BENCH_STORES",
                              "5000" if QUICK else "30000"))
#: Store count for the mega-grid rows (paper-scale traces by default;
#: the quick smoke shrinks them so CI still exercises the tier).
MEGA_STORES = int(os.environ.get("RECXL_BENCH_MEGA_STORES",
                                 "2000" if QUICK else "30000"))


def _run(specs: Sequence[ScenarioSpec]) -> Dict[tuple, SimResult]:
    """One sweep through ``run_sweep``; results keyed by the spec
    itself."""
    res = run_sweep(specs, n_stores=N_STORES)
    return {s: r for s, r in zip(specs, res)}


def bench_wb_wt() -> List[Dict]:
    """Fig. 2: WB vs WT execution time (normalized to WB)."""
    specs = [ScenarioSpec(w, c) for w in WORKLOADS for c in ("wb", "wt")]
    by = _run(specs)
    rows = []
    for w in WORKLOADS:
        wb = by[ScenarioSpec(w, "wb")]
        wt = by[ScenarioSpec(w, "wt")]
        rows.append({
            "name": f"fig2/{w}/wt_over_wb",
            "us_per_call": wt.exec_time_ns / 1e3,
            "derived": round(wt.exec_time_ns / wb.exec_time_ns, 3),
        })
    return rows


def bench_protocols() -> List[Dict]:
    """Fig. 10: the five configurations; headline validation vs. paper."""
    specs = [ScenarioSpec(w, c) for w in WORKLOADS for c in CONFIGS]
    by = _run(specs)
    table = slowdowns_from_results(by.values())
    gm = geomean_slowdowns(table)
    rows = []
    for w, row in table.items():
        for c in CONFIGS:
            rows.append({"name": f"fig10/{w}/{c}",
                         "us_per_call": by[ScenarioSpec(w, c)].exec_time_ns / 1e3,
                         "derived": round(row[c], 3)})
    for c, target_key in [("wt", "wt_slowdown_geomean"),
                          ("baseline", "baseline_slowdown_geomean"),
                          ("proactive", "proactive_slowdown_geomean")]:
        rows.append({
            "name": f"fig10/geomean/{c}",
            "us_per_call": 0.0,
            "derived": round(gm[c], 3),
            "paper_claim": PAPER_CLAIMS[target_key],
        })
    return rows


def bench_batch_speedup() -> List[Dict]:
    """Wall clock on the full Fig. 10 grid (45 cells): the serial
    per-cell oracle loop against ``run_sweep`` (the engine). The cold
    sweep runs after ``clear_sim_caches()`` and pays trace synthesis and
    compiles; the steady-state rows are warmed so they track sweep
    throughput, not XLA compile time."""
    specs = [ScenarioSpec(w, c) for w in WORKLOADS for c in CONFIGS]

    clear_sim_caches()
    t0 = time.perf_counter()
    run_sweep(specs, n_stores=N_STORES)
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    run_sweep(specs, n_stores=N_STORES)
    batched_s = time.perf_counter() - t0

    for s in specs[:5]:                     # warm the per-config serial jits
        simulate(s.workload, s.config, n_stores=N_STORES)
    t0 = time.perf_counter()
    for s in specs:
        simulate(s.workload, s.config, n_stores=N_STORES)
    serial_s = time.perf_counter() - t0

    n = len(specs)
    return [
        {"name": "fig10/sweep/serial_ms", "us_per_call": serial_s * 1e6 / n,
         "derived": round(serial_s * 1e3, 2)},
        {"name": "fig10/sweep/batched_ms", "us_per_call": batched_s * 1e6 / n,
         "derived": round(batched_s * 1e3, 2)},
        {"name": "fig10/sweep/batched_cold_ms", "us_per_call": cold_s * 1e6 / n,
         "derived": round(cold_s * 1e3, 2)},
        {"name": "fig10/sweep/speedup_serial_over_batched",
         "us_per_call": 0.0,
         "derived": round(serial_s / max(batched_s, 1e-9), 2)},
    ]


def bench_megagrid() -> List[Dict]:
    """``fig10/megagrid/*``: the streaming sharded engine on the full
    sensitivity cross-product (``scenarios.mega_grid``: 12 960 cells
    full mode, a shrunken smoke under ``--quick``).

    One cold end-to-end run after ``clear_sim_caches()``: ``engine_s``
    times :func:`repro.core.engine.run_grid` (tiled, cell-sharded over
    the local devices, double-buffered host prep, one device-resident
    dedup'd bank, tiles ship int32 row indices, the tile program
    gathers). Bank rows (from ``engine.bank_stats()``) record the run's
    H2D bytes, bank rows, the engine-accounted device-memory high-water
    mark, and the MEASURED resident bank device bytes of the per-shard
    sub-bank partition -- per-shard, fleet total, the replicated
    layout's cost, and their cut ratio.

    ``oracle_bitident`` re-runs cells through the serial oracle and
    checks ``==`` -- every cell under ``--quick``, six sampled cells of
    the full grid -- so the timing rows can never quietly come from
    drifting arithmetic.
    """
    import jax

    from repro.core import engine as E
    from repro.core.scenarios import mega_grid

    if QUICK:
        specs = mega_grid(seeds=(0,), replicas=(1, 3),
                          bandwidths=(160.0, 40.0), cn_counts=(16,),
                          sb_sizes=(72, 48))
    else:
        specs = mega_grid()
    n = len(specs)

    clear_sim_caches()
    traces0 = E.trace_count()
    t0 = time.perf_counter()
    res_e = E.run_grid(specs, n_stores=MEGA_STORES)
    engine_s = time.perf_counter() - t0
    compiles = E.trace_count() - traces0
    shards = res_e[0].meta["n_shards"]
    bank = E.bank_stats()

    # every cell of the quick smoke, a sample of the full grid
    pick = range(n) if QUICK else list(range(0, n, max(1, n // 5)))[:6]
    oracle = run_sweep([specs[i] for i in pick], n_stores=MEGA_STORES,
                       engine="serial")
    ident = all(res_e[i].exec_time_ns == rs.exec_time_ns
                and res_e[i].sb_full_frac == rs.sb_full_frac
                and res_e[i].repl_at_head_frac == rs.repl_at_head_frac
                for i, rs in zip(pick, oracle))

    mb = 1.0 / (1 << 20)
    rows = [
        {"name": "fig10/megagrid/cells", "us_per_call": 0.0, "derived": n},
        {"name": "fig10/megagrid/stores_per_cell", "us_per_call": 0.0,
         "derived": MEGA_STORES},
        {"name": "fig10/megagrid/engine_s",
         "us_per_call": engine_s * 1e6 / n, "derived": round(engine_s, 2)},
        {"name": "fig10/megagrid/engine_cells_per_s", "us_per_call": 0.0,
         "derived": round(n / engine_s, 1)},
        {"name": "fig10/megagrid/engine_compiles", "us_per_call": 0.0,
         "derived": compiles},
        {"name": "fig10/megagrid/engine_shards", "us_per_call": 0.0,
         "derived": f"{shards}/{len(jax.devices())}dev"},
        {"name": "fig10/megagrid/bank_rows", "us_per_call": 0.0,
         "derived": f"{bank['trace_rows']}trace+{bank['wv_rows']}wv"},
        {"name": "fig10/megagrid/h2d_bank_mb", "us_per_call": 0.0,
         "derived": round(bank["h2d_bytes"] * mb, 1)},
        # replication of staged arrays to the other shards is
        # device-to-device traffic, not host bandwidth: the whole bank
        # under "replicated", only the arrivals column under "sub"
        {"name": "fig10/megagrid/bank_fabric_mb", "us_per_call": 0.0,
         "derived": round(bank["bank_fabric_bytes"] * mb, 1)},
        # resident-bank device bytes, MEASURED from the live buffers
        # (engine._measured_device_bytes). The run uses the per-shard
        # sub-bank partition (PR 8 default): one copy of each max-plus
        # row fleet-wide, arrivals replicated, so the per-shard bytes
        # drop to ~1/n_shards of the replicated layout -- whose cost is
        # exactly bank_mb x n_shards (pinned == measured by
        # tests/test_engine.py), the cut_ratio baseline below.
        {"name": "fig10/megagrid/bank_partition", "us_per_call": 0.0,
         "derived": str(bank["bank_partition"])},
        {"name": "fig10/megagrid/bank_mb", "us_per_call": 0.0,
         "derived": round(bank["bank_bytes"] * mb, 1)},
        {"name": "fig10/megagrid/bank_dev_mb_per_shard", "us_per_call": 0.0,
         "derived": round(bank["bank_dev_bytes_per_shard"] * mb, 1)},
        {"name": "fig10/megagrid/bank_dev_total_mb", "us_per_call": 0.0,
         "derived": round(bank["bank_dev_bytes"] * mb, 1)},
        {"name": "fig10/megagrid/bank_dev_replicated_mb", "us_per_call": 0.0,
         "derived": round(bank["bank_bytes"] * shards * mb, 1)},
        {"name": "fig10/megagrid/bank_dev_cut_ratio", "us_per_call": 0.0,
         "derived": round(bank["bank_bytes"] * shards
                          / max(bank["bank_dev_bytes"], 1), 2)},
        {"name": "fig10/megagrid/bank_dev_shard_ratio", "us_per_call": 0.0,
         "derived": round(bank["bank_dev_bytes_per_shard"]
                          / max(bank["bank_bytes"] / max(shards, 1), 1),
                          3)},
        {"name": "fig10/megagrid/dev_mem_hwm_mb", "us_per_call": 0.0,
         "derived": round(bank["dev_mem_hwm_bytes"] * mb, 1)},
        {"name": "fig10/megagrid/oracle_bitident", "us_per_call": 0.0,
         "derived": int(ident)},
    ]
    return rows


def bench_repl_timing() -> List[Dict]:
    """Fig. 11: fraction of REPLs sent at the SB head under proactive."""
    specs = [ScenarioSpec(w, "proactive") for w in WORKLOADS]
    by = _run(specs)
    return [{"name": f"fig11/{s.workload}/repl_at_head",
             "us_per_call": by[s].exec_time_ns / 1e3,
             "derived": round(by[s].repl_at_head_frac, 4)}
            for s in specs]


def bench_coalescing() -> List[Dict]:
    """Fig. 12: proactive speedup from supporting coalescing."""
    specs = [ScenarioSpec(w, "proactive", coalescing=co)
             for w in WORKLOADS for co in (True, False)]
    by = _run(specs)
    rows = []
    for w in WORKLOADS:
        on = by[ScenarioSpec(w, "proactive", coalescing=True)]
        off = by[ScenarioSpec(w, "proactive", coalescing=False)]
        rows.append({"name": f"fig12/{w}/coalescing_speedup",
                     "us_per_call": on.exec_time_ns / 1e3,
                     "derived": round(off.exec_time_ns / on.exec_time_ns, 4)})
    return rows


def bench_log_size() -> List[Dict]:
    """Fig. 13: max DRAM log bytes per CN per dump period."""
    specs = [ScenarioSpec(w, "proactive") for w in WORKLOADS]
    by = _run(specs)
    return [{"name": f"fig13/{s.workload}/log_mb",
             "us_per_call": by[s].exec_time_ns / 1e3,
             "derived": round(by[s].max_log_bytes / 1e6, 3)}
            for s in specs]


def bench_bandwidth() -> List[Dict]:
    """Fig. 14: CXL bandwidth split (memory traffic vs log dumps)."""
    specs = [ScenarioSpec(w, "proactive") for w in WORKLOADS]
    by = _run(specs)
    rows = []
    for s in specs:
        r = by[s]
        rows.append({"name": f"fig14/{s.workload}/mem_bw_gbps",
                     "us_per_call": r.exec_time_ns / 1e3,
                     "derived": round(r.cxl_mem_bw_gbps, 2)})
        rows.append({"name": f"fig14/{s.workload}/dump_bw_gbps",
                     "us_per_call": 0.0,
                     "derived": round(r.log_dump_bw_gbps, 3)})
    return rows


def bench_owned_lines() -> List[Dict]:
    """Fig. 15: owned (dirty/exclusive) lines of a crashed CN. The
    simulator's working-set profile supplies the line census; the
    framework's ShardDirectory supplies the shard census."""
    from repro.core.directory import ShardDirectory
    rows = []
    for w, prof in WORKLOADS.items():
        owned = min(prof.working_lines, 163_000)
        rows.append({"name": f"fig15/{w}/owned_lines",
                     "us_per_call": 0.0,
                     "derived": owned})
    d = ShardDirectory(n_nodes=16, n_buckets=8, n_replicas=3)
    s = d.stats(0)
    rows.append({"name": "fig15/framework/owned_shards",
                 "us_per_call": 0.0, "derived": s["owned"]})
    rows.append({"name": "fig15/framework/replica_entries",
                 "us_per_call": 0.0, "derived": s["shared"]})
    return rows


def bench_link_bw() -> List[Dict]:
    """Fig. 16: sensitivity to CXL link bandwidth (160 -> 20 GB/s)."""
    grid = fig16_grid()
    by = _run(grid)
    rows = []
    for w in ("ycsb", "canneal", "streamcluster"):
        base = by[ScenarioSpec(w, "wb", link_bw_gbps=160.0)].exec_time_ns
        for bw in (160.0, 80.0, 40.0, 20.0):
            for cfg in ("wb", "proactive"):
                t = by[ScenarioSpec(w, cfg, link_bw_gbps=bw)]
                rows.append({
                    "name": f"fig16/{w}/{cfg}/bw{int(bw)}",
                    "us_per_call": t.exec_time_ns / 1e3,
                    "derived": round(t.exec_time_ns / base, 3)})
    return rows


def bench_replication_factor() -> List[Dict]:
    """Fig. 17: execution time vs N_r (normalized to N_r=3)."""
    grid = fig17_grid()
    by = _run(grid)
    rows = []
    for w in WORKLOADS:
        t3 = by[ScenarioSpec(w, "proactive", n_replicas=3)].exec_time_ns
        for nr in (1, 2, 3, 4):
            t = by[ScenarioSpec(w, "proactive", n_replicas=nr)]
            rows.append({"name": f"fig17/{w}/nr{nr}",
                         "us_per_call": t.exec_time_ns / 1e3,
                         "derived": round(t.exec_time_ns / t3, 4)})
    return rows


def bench_num_nodes() -> List[Dict]:
    """Fig. 18: execution time vs CN count (normalized to 16)."""
    grid = fig18_grid()
    by = _run(grid)
    rows = []
    for w in ("barnes", "ycsb", "bodytrack"):
        t16 = {c: by[ScenarioSpec(w, c, n_cns=16)].exec_time_ns
               for c in ("wb", "proactive")}
        for ncn in (4, 8, 16):
            for c in ("wb", "proactive"):
                t = by[ScenarioSpec(w, c, n_cns=ncn)]
                rows.append({"name": f"fig18/{w}/{c}/cn{ncn}",
                             "us_per_call": t.exec_time_ns / 1e3,
                             "derived": round(t.exec_time_ns / t16[c], 3)})
    return rows


def bench_recovery() -> List[Dict]:
    """SS VII-E / Fig. 9: estimated downtime after a CN fail-stop.

    One jitted ``recovery_sweep`` call covers the whole (workload x
    failure-time x node-count) grid; rows report per-workload downtime
    at mid-interval on 16 CNs, the worst-case/best-case ratio across
    the failure-time axis (the undumped log grows until the next dump),
    the 4-CN over 16-CN ratio (fewer nodes -> bigger shards to replay),
    and the batched sweep's wall-clock.
    """
    from repro.core.scenarios import recovery_sweep

    sweep = recovery_sweep()                       # warm the jit
    t0 = time.perf_counter()
    sweep = recovery_sweep()
    wall_s = time.perf_counter() - t0

    t_lo, t_mid, t_hi = sweep.fail_times_ms
    rows = []
    for w in sweep.workloads:
        rows.append({"name": f"fig9/recovery/{w}/downtime_ms",
                     "us_per_call": sweep.total_ms(w, t_mid, 16) * 1e3,
                     "derived": round(sweep.total_ms(w, t_mid, 16), 4)})
    iw = sweep.workloads.index("ycsb")
    late = sweep.total_ns[iw, sweep.fail_times_ms.index(t_hi),
                          sweep.cn_counts.index(16)]
    early = sweep.total_ns[iw, sweep.fail_times_ms.index(t_lo),
                           sweep.cn_counts.index(16)]
    rows.append({"name": "fig9/recovery/ycsb/late_over_early_fail",
                 "us_per_call": 0.0, "derived": round(float(late / early), 3)})
    cn4 = sweep.total_ns[iw, sweep.fail_times_ms.index(t_mid),
                         sweep.cn_counts.index(4)]
    cn16 = sweep.total_ns[iw, sweep.fail_times_ms.index(t_mid),
                          sweep.cn_counts.index(16)]
    rows.append({"name": "fig9/recovery/ycsb/cn4_over_cn16",
                 "us_per_call": 0.0, "derived": round(float(cn4 / cn16), 3)})
    n_cells = sweep.total_ns.size
    rows.append({"name": "fig9/recovery/sweep_ms",
                 "us_per_call": wall_s * 1e6 / n_cells,
                 "derived": round(wall_s * 1e3, 3)})
    return rows


ALL_PROTOCOL_BENCHES = [
    bench_wb_wt, bench_protocols, bench_batch_speedup, bench_megagrid,
    bench_repl_timing, bench_coalescing, bench_log_size, bench_bandwidth,
    bench_owned_lines, bench_link_bw, bench_replication_factor,
    bench_num_nodes, bench_recovery,
]

"""Reproduce the paper's headline evaluation (Figs. 2 and 10) with the
trace-driven protocol simulator, compare against the published claims,
and estimate post-failure downtime (SS VII-E).

The whole 9-workload x 5-configuration grid runs as ONE ``run_sweep``
call through the streaming engine (see the ScenarioSpec API in
repro/core/simulator.py), and a batched ``recovery_sweep`` reports
estimated downtime per workload across the dump interval.

    PYTHONPATH=src python examples/protocol_sim.py
"""

import time

from repro.configs.recxl_paper import PAPER_CLAIMS, WORKLOADS
from repro.core.scenarios import recovery_sweep, run_sweep
from repro.core.simulator import (
    CONFIGS,
    ScenarioSpec,
    geomean_slowdowns,
    slowdowns_from_results,
)

N_STORES = 30_000


def main() -> None:
    print("simulating 9 workloads x 5 configurations "
          "(16 CN / 16 MN cluster, Table II parameters)...")
    specs = [ScenarioSpec(w, c) for w in WORKLOADS for c in CONFIGS]
    t0 = time.perf_counter()
    results = run_sweep(specs, n_stores=N_STORES)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    results = run_sweep(specs, n_stores=N_STORES)
    warm = time.perf_counter() - t0
    table = slowdowns_from_results(results)
    gm = geomean_slowdowns(table)
    print(f"...{len(specs)} cells: {cold:.2f}s cold, {warm*1e3:.0f} ms warm")

    print(f"\n{'workload':14s}" + "".join(
        f"{c:>11s}" for c in CONFIGS))
    for w, row in table.items():
        print(f"{w:14s}" + "".join(f"{row[c]:11.2f}" for c in row))

    print("\nheadline comparison (slowdown vs WB, geomean):")
    rows = [
        ("write-through (WT)", gm["wt"], PAPER_CLAIMS["wt_slowdown_geomean"]),
        ("ReCXL-baseline", gm["baseline"],
         PAPER_CLAIMS["baseline_slowdown_geomean"]),
        ("ReCXL-parallel", gm["parallel"],
         PAPER_CLAIMS["baseline_slowdown_geomean"] * 0.97),
        ("ReCXL-proactive", gm["proactive"],
         PAPER_CLAIMS["proactive_slowdown_geomean"]),
    ]
    print(f"  {'configuration':22s}{'reproduced':>12s}{'paper':>8s}")
    for name, got, paper in rows:
        print(f"  {name:22s}{got:12.2f}{paper:8.2f}")

    print("\nestimated downtime after a CN fail-stop (SS VII-E model,")
    print("failure at 10% / 50% / 90% of the Logging-Unit dump interval):")
    sweep = recovery_sweep(cn_counts=(16,))
    print(f"  {'workload':14s}{'early':>9s}{'mid':>9s}{'late':>9s}   (ms)")
    for w in sweep.workloads:
        cells = [sweep.total_ms(w, t, 16) for t in sweep.fail_times_ms]
        print(f"  {w:14s}" + "".join(f"{ms:9.3f}" for ms in cells))


if __name__ == "__main__":
    main()

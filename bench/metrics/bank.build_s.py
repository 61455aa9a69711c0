"""Trace-bank build per sweep: the mean wall time of the benchmark's
own ``scenarios.grid_bank(specs)`` call made before each sweep."""

import statistics


def read(run):
    xs = run.records.get("bank_build_s")
    return statistics.mean(xs) if xs else None

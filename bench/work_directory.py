"""The work a directory-coupled sweep asks of the device, counted from
its cells.

A replicating cell's max-plus row adds one per-store row to megagrid's
(``bench/work.py``): the directory wait, which depends on the trace, the
sharer pool of the cell's directory shard and the load each sharer
offers (``bench/reference_directory.py``). At load 0 the row is zero
whatever the pool, so such cells share one lane per megagrid row. A
sweep needs one scan lane per distinct key. Shards whose pool and load
give equal background utilization (16 and 8 CNs at N_r = 3: pools 15
and 7 over 15 and 7 peers) hold equal rows; they are counted apart, as
distinct shards.
"""

from __future__ import annotations

from typing import Iterable, Mapping

import reference
import reference_contention
import work


def lane_key(cell, k: Mapping) -> tuple:
    """``(SB depth, trace, max-plus row)`` of a resolved directory cell;
    ``k`` is the configuration's ``directory`` group."""
    sb, trace, row = work.lane_key(cell)
    if cell.config in reference.REPLICATING:
        load = cell.directory_load
        pool = reference_contention.sharer_pool(
            cell.n_cns, cell.n_replicas, int(k["dir_buckets"])) \
            if load > 0.0 else 0
        row = row + (load, pool)
    return (sb, trace, row)


def scan_lanes(cells: Iterable, k: Mapping) -> int:
    """Distinct scan lanes of a directory sweep."""
    return len({lane_key(c, k) for c in cells})

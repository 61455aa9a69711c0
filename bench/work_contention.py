"""The work a contended sweep asks of the device, counted from its cells.

A replicating cell's max-plus row adds two per-store rows to megagrid's
(``bench/work.py``): the conflict delay, which depends on the trace
seed, the conflict rate, the read share and the sharer pool, and the
persist stall, which depends on the schedule alone. With conflict rate 0
no store is in a hot episode, so the delay row is zero whatever the read
share and pool: such cells share the lane of read share 0. A sweep needs
one scan lane per distinct key; coincidences that depend on the drawn
data (an eager barrier that hides a conflict delay) are not counted.
"""

from __future__ import annotations

from typing import Iterable, Mapping

import reference
import reference_contention
import work


def lane_key(cell, k: Mapping) -> tuple:
    """``(SB depth, trace, max-plus row)`` of a resolved contended cell;
    ``k`` is the configuration's ``contention`` group."""
    sb, trace, row = work.lane_key(cell)
    if cell.config in reference.REPLICATING:
        hot = cell.conflict_rate > 0.0
        row = row + (cell.schedule, cell.conflict_rate,
                     cell.read_share if hot else 0.0,
                     reference_contention.cell_pool(cell, k) if hot else 0)
    return (sb, trace, row)


def scan_lanes(cells: Iterable, k: Mapping) -> int:
    """Distinct scan lanes of a contended sweep."""
    return len({lane_key(c, k) for c in cells})

"""The work a sweep asks of the device, counted from its cells alone.

A cell's store-buffer timeline reads three per-store inputs: the
arrival times of its trace, its precollapsed max-plus row, and its SB
depth. Cells that agree on all three have the same timeline, so a sweep
needs one scan lane per distinct triple. The counts here follow from
the simulator's semantics, not from the program's own key functions,
so the work counted stays the same whatever implements the scan.
"""

from __future__ import annotations

from typing import Iterable

#: Bytes one scan lane must read per store: f32 arrival, f32 w, f32 v,
#: and the bool proactive-not-coalesced mask.
BYTES_PER_LANE_STORE = 4 + 4 + 4 + 1


def lane_key(cell) -> tuple:
    """``(SB depth, trace, max-plus row)`` of a resolved cell. WB and WT
    commit locally at a constant cost, so their row depends on the rule
    alone; the replicating rules' row depends on the trace and on the
    replica count, link bandwidth and coalescing, never on the CN
    count or the SB depth."""
    trace = (cell.workload, cell.seed)
    if cell.config in ("wb", "wt"):
        row = (cell.config,)
    else:
        row = (cell.config, cell.workload, cell.seed, cell.n_replicas,
               float(cell.link_bw_gbps), bool(cell.coalescing))
    return (cell.sb_size, trace, row)


def scan_lanes(cells: Iterable) -> int:
    """Distinct scan lanes of a sweep (cells with every knob resolved)."""
    return len({lane_key(c) for c in cells})


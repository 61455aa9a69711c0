"""Plain reference of the directory-coupled store-buffer semantics.

The directory counterpart of ``bench/reference.py``, whose trace
synthesis, per-store commit costs and serial recurrence it reuses. It
imports nothing from ``repro`` and takes every parameter from the
configuration file: the Table II cluster, the workload profiles and the
``directory`` constants. The coherence directory lives in MN memory
(Sec. V.C); the shard that serves a CN's lines is shared by the CN's
replica group. A replicating cell at directory load ``L`` > 0 sees:

    pool   = the union of node 0's replica peers over ``dir_buckets``
             buckets under the rotation of Sec. III.A (at most
             n_cns - 1: ``reference_contention.sharer_pool``)
    rho_bg = L * pool / (n_cns - 1)     the sharers' background load

and, per epoch ``e`` of ``epoch_len`` stores on the arrival clock
(``span_e`` from the epoch's first arrival to the next epoch's, or one
cycle past the last arrival; at least one cycle):

    own_e  = the epoch's directory transactions (non-coalesced stores)
             x service / dir_buckets
    bg_e   = rho_bg * span_e
    rho_e  = min((own_e + bg_e) / span_e, rho_cap)
    wait_e = (q_e + rho_e * service / (2 (1 - rho_e))) * congestion
    q_{e+1} = max(q_e + own_e + bg_e - span_e, 0),  q_0 = 0

the backlog carried into the epoch (Lindley) plus the M/D/1 wait inside
it. Every non-coalesced store of the epoch adds ``wait_e`` to its
exposed coherence latency; coalesced stores and WB/WT commits make no
directory transaction. Load 0 adds exact zeros. ``service`` names the
cluster key of the directory's service time (its DRAM access).

``dtype`` is the precision of the timeline. The configuration states
float32; ``ml_dtypes.bfloat16`` gives the lower-precision control.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Sequence

import numpy as np

import reference
from reference_contention import congestion, sharer_pool


@dataclasses.dataclass(frozen=True)
class Cell(reference.Cell):
    """One directory-coupled scenario cell, every knob resolved."""
    directory_load: float = 0.0


def rho_bg(cell: Cell, k: Mapping) -> float:
    """Background utilization of the cell's directory shard."""
    if cell.directory_load <= 0.0:
        return 0.0
    pool = sharer_pool(cell.n_cns, cell.n_replicas, int(k["dir_buckets"]))
    return cell.directory_load * pool / max(cell.n_cns - 1, 1)


def epoch_terms(arrivals: np.ndarray, tx: np.ndarray, rho: float,
                cl: reference.Cluster, k: Mapping) -> List[tuple]:
    """``(first store, end store, backlog in, in-epoch wait)`` of every
    epoch, the Lindley recurrence one epoch at a time, in float64."""
    n = arrivals.shape[0]
    e = int(k["epoch_len"])
    s = float(cl.p[k["service"]])
    cap = float(k["rho_cap"])
    a = arrivals.astype(np.float64)
    out = []
    q = 0.0
    for lo in range(0, n, e):
        hi = min(lo + e, n)
        end = a[hi] if hi < n else a[-1] + cl.cycle_ns
        span = max(end - a[lo], cl.cycle_ns)
        own = float(np.count_nonzero(tx[lo:hi])) * s / int(k["dir_buckets"])
        bg = rho * span
        r = min((own + bg) / span, cap)
        out.append((lo, hi, q, r * s / (2.0 * (1.0 - r))))
        q = max(q + (own + bg - span), 0.0)
    return out


def delay_row(arrivals: np.ndarray, tx: np.ndarray, rho: float,
              cong: float, cl: reference.Cluster, k: Mapping
              ) -> np.ndarray:
    """Per-store directory wait (float32 ns): ``wait_e`` on the
    transacting stores of each epoch, 0 elsewhere and at load 0."""
    out = np.zeros(arrivals.shape[0], np.float32)
    if rho <= 0.0:
        return out
    for lo, hi, q, wq in epoch_terms(arrivals, tx, rho, cl, k):
        out[lo:hi] = np.where(tx[lo:hi], (q + wq) * cong, 0.0)
    return out


def answers(cells: Sequence[Cell], cfg: Mapping, dtype=np.float32
            ) -> List[Dict[str, float]]:
    """The reference answer (``FIELDS``) of every cell, in order, at
    the configuration's ``n_stores``, with the timeline in ``dtype``."""
    if not cells:
        return []
    cl = reference.Cluster(cfg["cluster"])
    k = cfg["directory"]
    n_stores = int(cfg["n_stores"])
    traces: Dict[tuple, Dict[str, np.ndarray]] = {}
    rows: Dict[tuple, np.ndarray] = {}
    inputs = []
    for c in cells:
        wl = cfg["workloads"][c.workload]
        if (c.workload, c.seed) not in traces:
            traces[c.workload, c.seed] = reference.synthesize_trace(
                wl, n_stores, c.seed, cl)
        trace = traces[c.workload, c.seed]
        x = reference._cell_inputs(c, trace, wl, n_stores, cl)
        rho = rho_bg(c, k)
        if c.config in reference.REPLICATING and rho > 0.0:
            cong = congestion(c, trace, wl, cl)
            key = (c.workload, c.seed, c.coalescing, rho, cong)
            if key not in rows:
                rows[key] = delay_row(trace["arrivals"], ~x.coalesce, rho,
                                      cong, cl, k)
            x = dataclasses.replace(x, exposed=x.exposed + rows[key])
        inputs.append(x)
    last, at_head, sb_full = reference._timelines(inputs, cl.costs(), dtype)
    out = []
    for i, x in enumerate(inputs):
        ans = dict(x.fields)
        ans["exec_time_ns"] = float(np.float32(last[i])) * x.work_scale
        ans["repl_at_head_frac"] = float(at_head[i]) / max(n_stores, 1)
        ans["sb_full_frac"] = float(sb_full[i]) / max(n_stores, 1)
        out.append(ans)
    return out

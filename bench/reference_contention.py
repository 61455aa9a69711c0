"""Plain reference of the contended store-buffer semantics.

The contention counterpart of ``bench/reference.py``, whose trace
synthesis, per-store commit costs and serial recurrence it reuses. It
imports nothing from ``repro`` and takes every parameter from the
configuration file: the Table II cluster, the workload profiles and the
``contention`` constants. A replicating cell under contention pays, per
store:

    delay_i = (retries_i * (rtt + dram) + sharers_i * inval * rtt)
              * congestion                 (added to the exposed latency)
    flush_i = the schedule's persist stall (added to t_repl_i and svc_i)

``retries`` and ``sharers`` come from hot-spot episodes of conflicted
stores: alternating geometric runs (mean hot run ``conflict_run_len``)
from ``default_rng([rng_salt, seed])``, then geometric retry counts,
then a binomial sharer census over the CN's sharer pool, drawn last.
The pool is the union of a CN's replica peers over the directory's
buckets under the rotation rule of Sec. III.A (sha256-seeded offsets),
and 0 when the cell reads nothing shared. WB commits locally and pays
neither row.

``dtype`` is the precision of the timeline. The configuration states
float32; ``ml_dtypes.bfloat16`` gives the lower-precision control.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import Dict, List, Mapping, Sequence, Tuple

import numpy as np

import reference


@dataclasses.dataclass(frozen=True)
class Cell(reference.Cell):
    """One contended scenario cell, every knob resolved."""
    read_share: float = 0.0
    conflict_rate: float = 0.0
    schedule: str = "lazy"


def _hash_int(*xs: int) -> int:
    h = hashlib.sha256(",".join(map(str, xs)).encode()).digest()
    return int.from_bytes(h[:8], "little")


@functools.lru_cache(maxsize=None)
def sharer_pool(n_cns: int, n_replicas: int, n_buckets: int) -> int:
    """Peers that can hold a Shared copy of a CN's line: the union of
    node 0's replica targets over ``n_buckets`` buckets. Node ``s``
    replicates bucket ``b`` onto ``(s + o) % n_cns`` for ``nr``
    distinct offsets ``o`` drawn from 1..n_cns-1, each pick seeded by
    sha256 over (bucket, nr, n_cns) and the rank; node 0's targets are
    its offsets, never itself."""
    if n_cns <= 1:
        return 0
    nr = max(1, min(int(n_replicas), n_cns - 1))
    peers = set()
    for bucket in range(n_buckets):
        avail = list(range(1, n_cns))
        seed = _hash_int(bucket, nr, n_cns)
        for r in range(nr):
            seed = _hash_int(seed, r)
            peers.add(avail.pop(seed % len(avail)))
    return len(peers)


def cell_pool(cell: Cell, k: Mapping) -> int:
    """The sharer pool a cell's census draws from: 0 unless it reads
    shared lines."""
    if cell.read_share <= 0.0:
        return 0
    return sharer_pool(cell.n_cns, cell.n_replicas, int(k["dir_buckets"]))


def conflict_draws(n_stores: int, seed: int, conflict_rate: float,
                   read_share: float, pool: int, k: Mapping
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-store ``(retries, sharers)`` of one trace: nonzero only in
    the hot episodes of a two-state chain over stores."""
    rng = np.random.default_rng([int(k["rng_salt"]), seed])
    m = max(n_stores, 1)
    frac = float(np.clip(conflict_rate, 0.0, 0.98))
    hot = np.zeros(m, bool)
    if frac > 0.0:
        run_len = float(k["conflict_run_len"])
        cold_len = run_len * (1.0 - frac) / max(frac, 1e-3)
        state0 = bool(rng.random() < frac)
        run_hot = rng.geometric(1.0 / run_len, m)
        run_cold = rng.geometric(min(1.0 / max(cold_len, 1.0), 1.0), m)
        runs = np.empty(2 * m, dtype=np.int64)
        states = np.empty(2 * m, dtype=bool)
        first, second = (run_hot, run_cold) if state0 \
            else (run_cold, run_hot)
        runs[0::2], runs[1::2] = first, second
        states[0::2], states[1::2] = state0, not state0
        n = int(np.searchsorted(np.cumsum(runs), m)) + 1
        hot = np.repeat(states[:n], runs[:n])[:m]
    retries = np.where(hot, rng.geometric(max(1.0 - frac, 0.02), m) - 1, 0)
    sharers = np.where(hot, rng.binomial(pool, read_share, m), 0)
    return retries[:n_stores].astype(np.int32), \
        sharers[:n_stores].astype(np.int32)


def flush_row(schedule: str, n_stores: int, cl: reference.Cluster,
              k: Mapping) -> np.ndarray:
    """Persist stall per store: none (lazy), every store (eager), or
    the last store of every epoch."""
    if schedule == "lazy":
        return np.zeros(n_stores, np.float32)
    if schedule == "eager":
        return np.full(n_stores, cl.pmem_lat_ns, np.float32)
    if schedule == "epoch":
        e = int(k["epoch_len"])
        last = np.arange(n_stores) % e == e - 1
        return np.where(last, cl.pmem_lat_ns, 0.0).astype(np.float32)
    raise ValueError(f"unknown schedule {schedule!r}")


def congestion(cell: Cell, trace: Dict[str, np.ndarray],
               wl: Mapping[str, float], cl: reference.Cluster) -> float:
    """The link-congestion factor that scales a cell's latencies."""
    store_rate = 1e9 / max(float(np.mean(trace["gaps"])), 1e-3)
    cores = cl.cores_per_cn
    repl = store_rate * cores * cell.n_replicas * (8 + 64) / 1e9
    read_rate = (wl["remote_read_rate"] / wl["remote_store_rate"]) \
        * store_rate
    mem = (store_rate + read_rate) * cores * (64 + 16) / 1e9
    return max(1.0, (mem + repl) / cell.link_bw_gbps)


def answers(cells: Sequence[Cell], cfg: Mapping, dtype=np.float32
            ) -> List[Dict[str, float]]:
    """The reference answer (``FIELDS``) of every cell, in order, at
    the configuration's ``n_stores``, with the timeline in ``dtype``."""
    if not cells:
        return []
    cl = reference.Cluster(cfg["cluster"])
    k = cfg["contention"]
    n_stores = int(cfg["n_stores"])
    rtt = cl.cxl_rtt_ns
    t_retry = rtt + cl.dram_lat_ns
    t_inval = float(k["inval_rtt_share"]) * rtt
    traces: Dict[tuple, Dict[str, np.ndarray]] = {}
    draws: Dict[tuple, Tuple[np.ndarray, np.ndarray]] = {}
    inputs = []
    for c in cells:
        wl = cfg["workloads"][c.workload]
        if (c.workload, c.seed) not in traces:
            traces[c.workload, c.seed] = reference.synthesize_trace(
                wl, n_stores, c.seed, cl)
        trace = traces[c.workload, c.seed]
        x = reference._cell_inputs(c, trace, wl, n_stores, cl)
        if c.config in reference.REPLICATING:
            key = (c.seed, c.conflict_rate, c.read_share, cell_pool(c, k))
            if key not in draws:
                draws[key] = conflict_draws(n_stores, *key, k)
            retries, sharers = draws[key]
            delay = ((retries * t_retry + sharers * t_inval)
                     * congestion(c, trace, wl, cl)).astype(np.float32)
            flush = flush_row(c.schedule, n_stores, cl, k)
            x = dataclasses.replace(
                x, exposed=x.exposed + delay, t_repl_i=x.t_repl_i + flush,
                svc_i=(x.svc_i + flush).astype(np.float32))
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

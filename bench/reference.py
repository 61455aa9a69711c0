"""Plain reference of the ReCXL store-buffer simulator's semantics.

Independent of the code under test: it imports nothing from ``repro``
and takes every parameter from the configuration file (Table II
cluster, workload profiles). For each scenario cell it synthesizes the
remote-store trace from the cell's seed, derives the per-store commit
costs, runs the store-buffer timeline one store at a time, and returns
the cell's physics fields.

The timeline is the serial recurrence, store by store (vectorized only
across cells, never across stores):

    oldest_i = c_{i - sb}  (0 for i < sb)
    r_i      = max(a_i, oldest_i)
    wb/wt/baseline/parallel:   c_i = max(r_i, c_{i-1}) + extra_i
    proactive, coalesced:      c_i = max(r_i, c_{i-1}) + t_l1
    proactive, not coalesced:  c_i = max(r_i + t_repl_i, r_i + coh_i,
                                         c_{i-1} + svc_i)

``dtype`` is the precision of the timeline. The configuration states
float32; ``ml_dtypes.bfloat16`` gives the lower-precision control.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Sequence, Tuple

import numpy as np

REPLICATING = ("baseline", "parallel", "proactive")
RULES = ("wb", "wt", "baseline", "parallel", "proactive")

#: The physics fields of one answer, compared with ``==``.
FIELDS = ("exec_time_ns", "n_repl_msgs", "repl_at_head_frac",
          "max_log_bytes", "cxl_mem_bw_gbps", "log_dump_bw_gbps",
          "sb_full_frac")


@dataclasses.dataclass(frozen=True)
class Cell:
    """One scenario cell, every knob resolved."""
    workload: str
    config: str
    seed: int
    n_replicas: int
    link_bw_gbps: float
    n_cns: int
    sb_size: int
    coalescing: bool = True


class Cluster:
    """Table II parameters from the configuration's ``cluster`` group."""

    def __init__(self, params: Mapping[str, float]):
        self.p = dict(params)

    def __getattr__(self, name: str):
        try:
            return self.p[name]
        except KeyError:
            raise AttributeError(name) from None

    @property
    def cycle_ns(self) -> float:
        return 1.0 / self.p["cpu_freq_ghz"]

    def costs(self) -> Dict[str, float]:
        rtt = self.cxl_rtt_ns
        return {"t_l1": self.cycle_ns * 2.0,
                "t_wt": rtt + self.pmem_lat_ns,
                "t_repl": rtt + self.sram_log_lat_ns,
                "t_drain": self.cycle_ns}


def synthesize_trace(wl: Mapping[str, float], n_stores: int, seed: int,
                     cl: Cluster) -> Dict[str, np.ndarray]:
    """One remote-store trace: a two-state (burst / calm) Markov chain
    drawn as alternating geometric runs, exponential calm gaps, a
    coalescing draw and a tail of exposed coherence latency."""
    rng = np.random.default_rng(seed)
    ns_per_instr = 1.0 / (2.0 * cl.cpu_freq_ghz)
    mean_gap = 1000.0 / wl["remote_store_rate"] * ns_per_instr

    burst_len = max(wl["burst_len"], 1.0)
    frac = np.clip(wl["burstiness"], 0.0, 0.98)
    calm_len = burst_len * (1.0 - frac) / max(frac, 1e-3)
    p_leave_calm = min(1.0 / max(calm_len, 1.0), 1.0)
    state0 = bool(rng.random() < frac)
    m = max(n_stores, 1)
    run_burst = rng.geometric(1.0 / burst_len, m)
    run_calm = rng.geometric(p_leave_calm, m)
    runs = np.empty(2 * m, dtype=np.int64)
    states = np.empty(2 * m, dtype=bool)
    first, second = (run_burst, run_calm) if state0 else (run_calm, run_burst)
    runs[0::2], runs[1::2] = first, second
    states[0::2], states[1::2] = state0, not state0
    k = int(np.searchsorted(np.cumsum(runs), n_stores)) + 1
    in_burst = np.repeat(states[:k], runs[:k])[:n_stores]

    burst_gap = cl.cycle_ns
    n_burst = int(in_burst.sum())
    n_calm = n_stores - n_burst
    calm_gap = max((mean_gap * n_stores - burst_gap * n_burst)
                   / max(n_calm, 1), burst_gap)
    gaps = np.where(in_burst, burst_gap, rng.exponential(calm_gap, n_stores))

    idx = np.arange(n_stores, dtype=np.int64)
    last_calm = np.maximum.accumulate(np.where(~in_burst, idx, -1))
    pos = np.where(in_burst, idx - last_calm, 0).astype(np.float32)
    coalesce = rng.random(n_stores) < wl["coalesce_rate"]
    base_rtt = cl.cxl_rtt_ns + cl.dram_lat_ns
    tail = rng.random(n_stores) < 0.12
    exposed = np.where(tail, rng.exponential(0.15 * base_rtt, n_stores), 0.0)

    gaps32 = gaps.astype(np.float32)
    return {"gaps": gaps32,
            "arrivals": np.cumsum(gaps32, dtype=np.float32),
            "coalesce": coalesce, "in_burst": in_burst, "burst_pos": pos,
            "exposed_coh": exposed.astype(np.float32)}


@dataclasses.dataclass
class _Inputs:
    cell: Cell
    arrivals: np.ndarray
    coalesce: np.ndarray
    exposed: np.ndarray
    t_repl_i: np.ndarray
    svc_i: np.ndarray
    fields: Dict[str, float]          # every field but the timeline's
    work_scale: float


def _cell_inputs(cell: Cell, trace: Dict[str, np.ndarray],
                 wl: Mapping[str, float], n_stores: int,
                 cl: Cluster) -> _Inputs:
    """Per-store costs of one cell (congestion, replica fan-out, burst
    backlog, drain floor) and its bandwidth / log-size fields."""
    replicating = cell.config in REPLICATING
    nr, bw = cell.n_replicas, cell.link_bw_gbps
    costs = cl.costs()
    cores = cl.cores_per_cn

    mean_gap = float(np.mean(trace["gaps"]))
    store_rate = 1e9 / max(mean_gap, 1e-3)
    repl_demand = store_rate * cores * nr * (8 + 64) / 1e9
    read_rate = (wl["remote_read_rate"] / wl["remote_store_rate"]) \
        * store_rate
    mem_demand = (store_rate + read_rate) * cores * (64 + 16) / 1e9
    total = mem_demand + (repl_demand if replicating else 0.0)
    congestion = max(1.0, total / bw)
    port_serial = 1.0 + 0.08 * (nr - 1)

    coalesce_on = cell.coalescing and cell.config != "wt"
    coalesce = trace["coalesce"] if coalesce_on \
        else np.zeros_like(trace["coalesce"])
    exposed = trace["exposed_coh"] * congestion
    svc_entry = 2.0 * (1e3 / cl.logging_unit_freq_mhz)
    qslope = (svc_entry * cores * nr * (1.0 - wl["coalesce_rate"])
              - cl.cycle_ns)
    queue = np.minimum(trace["burst_pos"] * max(qslope, 0.0), 195.0) \
        * trace["in_burst"] * congestion
    t_repl_i = costs["t_repl"] * congestion * port_serial + queue
    svc_floor = 4.0 * cl.dram_lat_ns * (1.0 - wl["coalesce_rate"]) \
        * congestion * (1.0 + 0.1 * (nr - cl.n_replicas))
    svc_i = np.where(trace["in_burst"], svc_floor,
                     costs["t_drain"]).astype(np.float32)

    log_bytes = store_rate * cores * nr * (cl.dump_period_ms * 1e-3) * 12
    dump_bw = (log_bytes / cl.gzip_factor) / (cl.dump_period_ms * 1e-3) / 1e9
    n_coalesced = int(np.asarray(coalesce, bool).sum())
    fields = {
        "n_repl_msgs": int(n_stores - n_coalesced) if replicating else 0,
        "max_log_bytes": log_bytes,
        "cxl_mem_bw_gbps": mem_demand * cell.n_cns,
        "log_dump_bw_gbps": dump_bw * cell.n_cns if replicating else 0.0,
    }
    return _Inputs(cell=cell, arrivals=trace["arrivals"],
                   coalesce=np.asarray(coalesce, bool),
                   exposed=np.asarray(exposed, np.float32),
                   t_repl_i=np.asarray(t_repl_i, np.float32), svc_i=svc_i,
                   fields=fields, work_scale=cl.n_cns / cell.n_cns)


def _timelines(cells: Sequence[_Inputs], costs: Dict[str, float], dtype
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The serial store-buffer recurrence, store by store, for every
    cell at once. Returns ``(last commit time, REPLs issued at the SB
    head, stores that found the SB full)`` per cell."""
    n = cells[0].arrivals.shape[0]
    b = len(cells)

    def col(name):
        return np.stack([getattr(c, name) for c in cells], axis=1)

    a = col("arrivals").astype(dtype)
    co = col("coalesce")
    coh = col("exposed").astype(dtype)
    tr = col("t_repl_i").astype(dtype)
    sv = col("svc_i").astype(dtype)
    t_l1, t_wt = dtype(costs["t_l1"]), dtype(costs["t_wt"])
    rule = np.array([c.cell.config for c in cells])
    extra = np.full((n, b), t_l1, dtype)
    extra[:, rule == "wt"] = t_wt
    bl, pl = rule == "baseline", rule == "parallel"
    extra[:, bl] = np.where(co[:, bl], t_l1, coh[:, bl] + tr[:, bl])
    extra[:, pl] = np.where(co[:, pl], t_l1,
                            np.maximum(coh[:, pl], tr[:, pl]))
    pr_nc = (rule == "proactive")[None, :] & ~co

    sb = np.array([c.cell.sb_size for c in cells])
    cols = np.arange(b)
    commits = np.zeros((n, b), dtype)
    last = np.zeros(b, dtype)
    zero = np.zeros(b, dtype)
    at_head = np.zeros(b, np.int64)
    sb_full = np.zeros(b, np.int64)
    for i in range(n):
        back = i - sb
        oldest = np.where(back >= 0, commits[np.maximum(back, 0), cols],
                          zero)
        r = np.maximum(a[i], oldest)
        sb_full += oldest > a[i]
        p = pr_nc[i]
        at_head += p & (r >= last)
        c = np.where(p, np.maximum(np.maximum(r + tr[i], r + coh[i]),
                                   last + sv[i]),
                     np.maximum(r, last) + extra[i])
        commits[i] = c
        last = c
    return last, at_head, sb_full


def answers(cells: Sequence[Cell], cfg: Mapping, dtype=np.float32
            ) -> List[Dict[str, float]]:
    """The reference answer (``FIELDS``) of every cell, in order, at
    the configuration's ``n_stores``, with the timeline in ``dtype``."""
    if not cells:
        return []
    cl = Cluster(cfg["cluster"])
    n_stores = int(cfg["n_stores"])
    traces: Dict[Tuple[str, int], Dict[str, np.ndarray]] = {}
    inputs = []
    for c in cells:
        key = (c.workload, c.seed)
        if key not in traces:
            traces[key] = synthesize_trace(cfg["workloads"][c.workload],
                                           n_stores, c.seed, cl)
        inputs.append(_cell_inputs(c, traces[key],
                                   cfg["workloads"][c.workload],
                                   n_stores, cl))
    last, at_head, sb_full = _timelines(inputs, cl.costs(), dtype)
    out = []
    for k, x in enumerate(inputs):
        ans = dict(x.fields)
        ans["exec_time_ns"] = float(np.float32(last[k])) * x.work_scale
        ans["repl_at_head_frac"] = float(at_head[k]) / max(n_stores, 1)
        ans["sb_full_frac"] = float(sb_full[k]) / max(n_stores, 1)
        out.append(ans)
    return out


def mismatches(got: Sequence[Mapping[str, float]],
               want: Sequence[Mapping[str, float]]) -> int:
    """Answers of ``got`` that differ from ``want`` in any field."""
    if len(got) != len(want):
        raise ValueError(f"{len(got)} answers against {len(want)}")
    return sum(any(g[f] != w[f] for f in FIELDS) for g, w in zip(got, want))

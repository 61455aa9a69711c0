"""Trace-driven ReCXL protocol simulator (paper SS VI-VII).

The paper evaluates ReCXL with SST + Pin traces of PARSEC / SPLASH-2 /
YCSB on a 16-CN / 16-MN cluster (Table II). We reproduce that evaluation
with a vectorized store-timeline simulator: per application class, a
synthetic remote-store trace (arrival times, coalescability) is pushed
through a store-buffer model that implements the exact commit rules of
the five configurations (Fig. 6):

* WB            c_i = max(r_i, c_{i-1}) + t_l1
* WT            c_i = max(r_i, c_{i-1}) + t_rtt + t_pmem     (TSO serial)
* baseline      c_i = max(r_i, c_{i-1}) + t_coh_exposed + t_repl
* parallel      c_i = max(r_i, c_{i-1}) + max(t_coh_exposed, t_repl)
* proactive     c_i = max(c_{i-1} + t_drain, ack_i, coh_i)
                with ack_i = r_i + t_repl issued at *retire* time, so
                REPL->ACK cycles of queued stores overlap (Fig. 8)

where r_i (retire into SB) stalls when the SB is full:
r_i = max(a_i, c_{i-SB}) -- the SB-occupancy recurrence is carried through
one ``lax.scan`` with a ring of the last SB commit times.

Exclusive prefetch (Fig. 7) is modeled by drawing the *exposed* coherence
latency: the RFO is issued at address resolution (lead time ~ SB queueing
delay), so at the SB head the transaction has usually completed --
matching the paper's finding that ReCXL-parallel barely beats
ReCXL-baseline.

Everything is deterministic given (workload, seed). Calibration targets
are the paper's headline numbers (PAPER_CLAIMS in configs/recxl_paper.py);
tests assert the reproduced geomeans land inside acceptance bands.

Grid sweeps -- the ScenarioSpec API
-----------------------------------

A whole evaluation grid (Figs. 10-18: workload x config x sensitivity
knob) runs through one entry, ``repro.core.scenarios.run_sweep``:

    specs = [ScenarioSpec(w, c) for w in WORKLOADS for c in CONFIGS]
    results = run_sweep(specs)               # List[SimResult], same order

:class:`ScenarioSpec` names one grid cell: ``(workload, config, seed,
n_replicas, link_bw_gbps, n_cns, sb_size, coalescing)``; ``None`` knobs
default to the :class:`ClusterConfig`. The sweep runs on the streaming
engine (``repro.core.engine.run_grid``), which scans the grid's unique
timelines over the columnar trace bank below with the blocked scan;
this module holds the pieces it is built from and the serial oracle
(:func:`simulate`) it is pinned against.

The blocked scan
----------------

A per-step scan is CPU-bound on ``lax.scan`` step overhead: every store
would be one scan step of a handful of tiny ``(B,)`` ops. The blocked
formulation (:func:`_scan_wv`; ``chunk`` stores per block -- the
:func:`auto_chunk` heuristic by default, always clamped to the SB
depth, which bounds how far back the retire recurrence can look, so
within a block every ``c_{i-sb}`` read refers to a *previous* block)
removes it:

* everything that does not feed back into the commit recurrence is
  precomputed before the scan: arrival times (one host-side
  ``np.cumsum`` per trace, shared verbatim with the serial oracle), and
  the coalesce-mask selects / exposed-latency terms of all five commit
  rules collapsed -- exactly, because IEEE-754 addition is monotone, so
  ``max(r, c) + e == max(r + e, c + e)`` and ``max(r + a, r + b) == r +
  max(a, b)`` hold bit-for-bit -- into one shared max-plus recurrence
  ``c_i = max(r_i + w_i, c_{i-1} + v_i)`` (:func:`_make_wv_row`, the
  host form of :func:`_blocked_precompute`);
* ``lax.scan`` runs only over **chunk boundaries** (``n_stores /
  chunk`` steps); the store-buffer ring is carried as a tuple of the
  last ``sb`` commit times, so its reads are static indexing, both
  censuses (SB-full, Fig. 11 REPL-at-head) accumulate in-scan, and only
  the irreducible 2-op max-plus core runs per store (an unrolled, fully
  fusible chain of ``(B,)`` ops);
* a ragged tail (``n_stores % chunk``) is processed once after the scan
  with the same step function, so every chunk size is exact.

The result is **bit-identical** to the serial oracle for every chunk
size (tests/test_batch_sim.py enforces ``==``).

The columnar trace-bank data plane
----------------------------------

Per-store inputs would scale host prep, H2D transfer and device memory
with ``cells x n_stores``, even though arrivals are identical across
every cell of one trace and most cells share their derivations. The
**bank** collapses that to ``unique_rows x n_stores``:

* one ``arrivals`` column per unique ``(workload, seed)`` trace;
* one ``(w, v, pr_nc)`` column per unique *max-plus row key* --
  ``(config-rule, workload, seed, N_r, bw, coalescing)``, with the
  constant WB/WT rules collapsing to a single constant column each.
  The max-plus collapse runs **once per unique row, on the device at
  placement** (:func:`collapse_planes`, from the bank's
  :class:`RowTable`: per-trace columns, per-row scalars, host-built
  delay / flush rows), bit for bit the host collapse
  :func:`_make_wv_row` (IEEE add/max/select are exactly defined, and
  the two collapse programs keep every multiply out of the program
  with the adds), so the device never re-derives ``w``/``v`` per
  cell and the planes never cross host->device;
* cells carry only two ``int32`` row indices; the tile program gathers
  its columns on device (:func:`_bank_gather`), and the engine keeps
  one device-resident bank per grid;
* on the host a cell is only its result scalars (:func:`_cell_scalars`,
  memoized per trace): its per-store arrays are built once per unique
  row, by the bank build, and never per cell.

:func:`get_trace_bank` builds (and memoizes) the bank;
``tests/test_trace_bank.py`` property-tests that bank rows reconstruct
the per-cell inputs bit-exactly.

Engine-vs-serial contract: ``simulate()`` (the differential-testing
oracle) and the engine share trace synthesis and the per-cell cost
derivation, and their timelines apply identical arithmetic -- every
``SimResult`` field from the engine must match the serial path
bit-for-bit (tests/test_batch_sim.py enforces this across chunk sizes,
including ragged tails). The serial path stays the readable reference;
new commit rules must be added to ``_timeline``, ``_make_wv_row`` and
``_blocked_precompute``.

Contention & crash-consistency axes
-----------------------------------

``ScenarioSpec`` carries three ``None``-defaulted axes -- ``read_share``,
``conflict_rate``, ``consistency_schedule`` -- modeled by
``repro.core.contention`` (docs/contention.md): conflict retry backoff
and sharer invalidations are added to the exposed coherence latency
(the ``w`` side of the max-plus recurrence absorbs them through the
store's ready time), persist barriers to the REPL-ack / drain terms
(the ``v`` side), all inside :func:`_make_cell_arrays` BEFORE the
collapse -- so the scan works unchanged and stays bit-identical to
the serial oracle. Active axes append the resolved params to the bank's
max-plus row key; all-``None`` axes change neither outputs nor dedup
keys, bit-for-bit.

Two-level recurrence (queueing-coupled directory)
-------------------------------------------------

The ``directory_load`` axis nests the per-store max-plus recurrence
inside a **per-epoch service-rate recurrence** over the shared
``ShardDirectory`` shard (docs/simulator.md): stores are grouped into
``DirectoryParams.epoch``-long directory epochs; per epoch the shard's
backlog follows the Lindley recurrence

    q_e = max(q_{e-1} + own_e + bg_e - span_e, 0)

where ``own_e`` is this cell's offered directory work in the epoch,
``bg_e`` the background utilization from the cell's *real* sharer pool
(``directory.sharer_pool`` -- the union of the shard's replica peers,
never the fixed 15-peer census), and ``span_e`` the epoch's wall-clock
span on the arrival clock. Each epoch's waiting time (carried backlog
+ an M/D/1 in-epoch wait) is folded into every directory-transacting
store's ``w`` side (:func:`_directory_delay_row`, host-side inside
:func:`_make_cell_arrays` BEFORE the collapse) -- so the level-1
collapse and the scan again work unchanged. ``directory_load=None``
keeps outputs AND dedup keys bit-identical; active coupling appends the
resolved :class:`~repro.core.directory.DirectoryParams` to the wv key,
so cells sharing a (shard, epoch-profile) still dedup to one bank row /
scan lane. :func:`_resolve_coupling` is the single resolution point shared
by :func:`_prepare_cell` and :func:`_plane_keys`, so data and keys
cannot drift.

Failure/recovery scenario sweeps and the recovery-time (downtime) model
build on this API in ``repro.core.scenarios`` / ``repro.core.recovery``.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.recxl_paper import (
    ClusterConfig,
    PAPER_CLUSTER,
    WORKLOADS,
    WorkloadProfile,
)
from repro.core.contention import (
    ContentionParams,
    clear_contention_caches,
    contention_arrays,
    contention_memo_misses,
    resolve_contention,
    schedule_flush_ns,
)
from repro.core.directory import (
    DirectoryParams,
    resolve_directory_load,
    sharer_pool,
)
from repro.core.hostcache import BoundedCache
from repro.core import telemetry as _tm

CONFIGS = ("wb", "wt", "baseline", "parallel", "proactive")
_CONFIG_IDX = {c: i for i, c in enumerate(CONFIGS)}
_REPLICATING = ("baseline", "parallel", "proactive")


@dataclasses.dataclass(frozen=True)
class SimResult:
    """Per-cell simulation outputs (one store-buffer timeline).

    Field units: ``exec_time_ns`` ns (commit time of the last store,
    work-scaled for CN-count sweeps); ``max_log_bytes`` bytes (per CN
    per dump period, Fig. 13); ``*_bw_gbps`` GB/s cluster-wide (Fig.
    14); ``repl_at_head_frac`` / ``sb_full_frac`` are fractions of
    ``n_stores`` in [0, 1].
    """
    workload: str
    config: str
    exec_time_ns: float              # ns
    n_stores: int
    n_repl_msgs: int                 # REPL messages after coalescing
    repl_at_head_frac: float         # Fig. 11: REPLs issued at SB head
    max_log_bytes: float             # Fig. 13: bytes/CN/dump period
    cxl_mem_bw_gbps: float           # Fig. 14: memory traffic (GB/s)
    log_dump_bw_gbps: float          # Fig. 14: log dump traffic (GB/s)
    sb_full_frac: float              # stores that stalled on a full SB
    #: Engine metadata (not part of the simulated physics): which engine
    #: produced the cell, the blocked-scan ``chunk`` actually used (the
    #: auto heuristic's pick when ``chunk_size=None``), tile/shard info
    #: from the streaming engine. Excluded from equality comparisons.
    meta: Optional[Dict[str, object]] = dataclasses.field(
        default=None, compare=False)


@dataclasses.dataclass(frozen=True)
class ScenarioSpec:
    """One cell of an evaluation grid (Figs. 10-18 sensitivity space).

    ``None`` knobs resolve to the ClusterConfig defaults at simulation
    time, so a spec is portable across cluster configs. Knob units:
    ``n_replicas`` peer replicas (Fig. 17), ``link_bw_gbps`` CXL link
    bandwidth in GB/s (Fig. 16), ``n_cns`` compute nodes (Fig. 18),
    ``sb_size`` store-buffer entries, ``coalescing`` enables same-line
    SB coalescing (Fig. 12).

    Contention / crash-consistency axes (``repro.core.contention``;
    docs/contention.md): ``read_share`` fraction of the remote mix that
    is reads (sharer census, [0, 1)), ``conflict_rate`` fraction of
    stores hitting a directory conflict ([0, 1)),
    ``consistency_schedule`` persist-ordering discipline (``"lazy"`` /
    ``"epoch"`` / ``"eager"``). All three default to ``None`` --
    contention modeling off, outputs and bank dedup keys unchanged; if
    any is set, the others resolve to their neutral values.

    ``directory_load`` ([0, 1) or ``None``) is the queueing-coupled
    directory axis (``repro.core.directory``): the offered utilization
    each sharer contributes to the cell's shared ``ShardDirectory``
    shard, folded into the max-plus ``w`` side per directory epoch by
    the level-2 recurrence. ``None`` = coupling off (bit-identical
    outputs and keys); ``0.0`` = the in-grid normalization cell (zero
    delays, own bank row).
    """
    workload: str
    config: str
    seed: int = 0
    n_replicas: Optional[int] = None
    link_bw_gbps: Optional[float] = None
    n_cns: Optional[int] = None
    sb_size: Optional[int] = None
    coalescing: bool = True
    read_share: Optional[float] = None
    conflict_rate: Optional[float] = None
    consistency_schedule: Optional[str] = None
    directory_load: Optional[float] = None

    def contention(self) -> Optional[ContentionParams]:
        """The cell's resolved contention params (``None`` = axes off;
        raises ``ValueError`` on out-of-range axes)."""
        return resolve_contention(self.read_share, self.conflict_rate,
                                  self.consistency_schedule)

    def validate(self, cluster: ClusterConfig) -> None:
        if self.config not in CONFIGS:
            raise ValueError(f"unknown config {self.config!r}")
        if self.workload not in WORKLOADS:
            raise ValueError(f"unknown workload {self.workload!r}")
        sb = self.sb_size if self.sb_size is not None else cluster.store_buffer
        if sb < 1:
            raise ValueError(f"sb_size must be >= 1, got {sb}")
        nr = self.n_replicas if self.n_replicas is not None else cluster.n_replicas
        if nr < 1:
            raise ValueError(f"n_replicas must be >= 1, got {nr}")
        ncn = self.n_cns if self.n_cns is not None else cluster.n_cns
        if ncn < 1:
            raise ValueError(f"n_cns must be >= 1, got {ncn}")
        bw = self.link_bw_gbps if self.link_bw_gbps is not None \
            else cluster.cxl_link_bw_gbps
        if bw <= 0.0:
            raise ValueError(f"link_bw_gbps must be > 0, got {bw}")
        self.contention()        # raises on out-of-range contention axes
        resolve_directory_load(self.directory_load, ncn, nr)


# ---------------------------------------------------------------------------
# Trace synthesis (fully vectorized -- no per-store Python loops)
# ---------------------------------------------------------------------------

def synthesize_trace(wl: WorkloadProfile, n_stores: int, seed: int,
                     cluster: ClusterConfig) -> Dict[str, np.ndarray]:
    """Synthesize one deterministic remote-store trace.

    Returns per-store arrays, each of shape ``(n_stores,)``:

    * ``gaps``        -- inter-arrival gap to the previous store (ns, f32)
    * ``arrivals``    -- absolute arrival time ``cumsum(gaps)`` (ns, f32;
      a single host-side ``np.cumsum`` shared by the serial oracle and
      the engine's bank, so both consume bit-identical inputs)
    * ``coalesce``    -- store coalesces with the previous SB entry (bool)
    * ``in_burst``    -- store is inside a flush burst (bool)
    * ``burst_pos``   -- index distance into the current burst (f32)
    * ``exposed_coh`` -- coherence latency still exposed at the SB head
      after the exclusive prefetch (ns, f32)

    Arrivals follow a two-state Markov burst process: inside a store
    burst (flush phases of the SPMD apps) gaps are ~1 cycle and runs are
    ``burst_len`` stores long on average; between bursts, exponential
    compute gaps keep the trace-wide mean store rate at the profile's
    value. Burst runs longer than the SB depth are what separate
    ReCXL-proactive from ReCXL-parallel (Fig. 8): only there does commit
    latency back-pressure the core.

    The chain is materialized by its run-length representation: burst /
    calm run lengths are geometric (exactly the two-state chain's
    sojourn distribution), drawn for the whole trace at once and
    expanded with ``np.repeat`` -- there is no per-store Python loop, so
    a batch of traces costs a handful of array ops per cell.
    """
    rng = np.random.default_rng(seed)
    ipc = 2.0
    ns_per_instr = 1.0 / (ipc * cluster.cpu_freq_ghz)
    instr_per_store = 1000.0 / wl.remote_store_rate
    mean_gap = instr_per_store * ns_per_instr

    # two-state Markov chain over stores, as alternating geometric runs
    burst_len = max(wl.burst_len, 1.0)
    p_leave_burst = 1.0 / burst_len
    frac = np.clip(wl.burstiness, 0.0, 0.98)     # fraction of stores in bursts
    calm_len = burst_len * (1.0 - frac) / max(frac, 1e-3)
    p_leave_calm = min(1.0 / max(calm_len, 1.0), 1.0)
    state0 = bool(rng.random() < frac)
    # each run is >= 1 store, so n_stores runs of each state always cover
    # the trace; trim to the first run crossing n_stores before expanding.
    m = max(n_stores, 1)
    run_burst = rng.geometric(p_leave_burst, m)
    run_calm = rng.geometric(p_leave_calm, m)
    runs = np.empty(2 * m, dtype=np.int64)
    states = np.empty(2 * m, dtype=bool)
    first, second = (run_burst, run_calm) if state0 else (run_calm, run_burst)
    runs[0::2], runs[1::2] = first, second
    states[0::2], states[1::2] = state0, not state0
    k = int(np.searchsorted(np.cumsum(runs), n_stores)) + 1
    in_burst = np.repeat(states[:k], runs[:k])[:n_stores]

    burst_gap = cluster.cycle_ns
    n_burst = int(in_burst.sum())
    n_calm = n_stores - n_burst
    calm_gap = ((mean_gap * n_stores - burst_gap * n_burst)
                / max(n_calm, 1))
    calm_gap = max(calm_gap, burst_gap)
    gaps = np.where(in_burst, burst_gap,
                    rng.exponential(calm_gap, n_stores))

    # position within the current burst (Logging-Unit backlog ramps with
    # it): index distance to the latest calm store at or before i.
    idx = np.arange(n_stores, dtype=np.int64)
    last_calm = np.maximum.accumulate(np.where(~in_burst, idx, -1))
    pos = np.where(in_burst, idx - last_calm, 0).astype(np.float32)

    coalesce = rng.random(n_stores) < wl.coalesce_rate

    # Exposed coherence at the SB head: the exclusive prefetch is issued
    # at address resolution, so by SB-head time the RFO has almost always
    # completed (the paper's explanation for parallel ~= baseline). A
    # small tail of stores (conflicted / Shared-elsewhere lines) exposes
    # part of the round trip.
    base_rtt = cluster.cxl_rtt_ns + cluster.dram_lat_ns
    tail = rng.random(n_stores) < 0.12
    exposed = np.where(tail, rng.exponential(0.15 * base_rtt, n_stores), 0.0)

    gaps32 = gaps.astype(np.float32)
    return {"gaps": gaps32,
            "arrivals": np.cumsum(gaps32, dtype=np.float32),
            "coalesce": coalesce,
            "in_burst": in_burst,
            "burst_pos": pos,
            "exposed_coh": exposed.astype(np.float32)}


@functools.lru_cache(maxsize=64)
def _trace_cached(workload: str, n_stores: int, seed: int,
                  cluster: ClusterConfig) -> Dict[str, np.ndarray]:
    """Memoized :func:`synthesize_trace` (traces are deterministic in
    the key, and sweeps re-scan the same trace for many cells and many
    calls). Callers must treat the arrays as read-only."""
    return synthesize_trace(WORKLOADS[workload], n_stores, seed, cluster)


# ---------------------------------------------------------------------------
# Host-side memoization (bounded, hash-keyed, centrally clearable)
# ---------------------------------------------------------------------------

#: The shared cache primitive (repro.core.hostcache -- contention.py
#: uses the same class for its memos without an import cycle).
_BoundedCache = BoundedCache


#: Reduced-key per-store array derivations (see :func:`_cell_arrays`).
_CELL_ARRAY_CACHE = _BoundedCache(maxsize=512)
#: Per-trace result scalars (see :func:`_trace_scalars`): three numbers
#: per ``(workload, seed, coalescing)``, 54 entries a mega-grid sweep.
_TRACE_SCALAR_CACHE = _BoundedCache(maxsize=256)
#: Precollapsed max-plus rows (see :func:`_wv_row`): one ``(w, v,
#: pr_nc)`` triple per unique row key, ~9 bytes x n_stores each.
_WV_ROW_CACHE = _BoundedCache(maxsize=1024)
#: Whole-grid columnar banks (see :func:`get_trace_bank`). One mega-grid
#: bank is a few hundred MB of host columns plus its device placements,
#: so at most two stay alive.
_BANK_CACHE = _BoundedCache(maxsize=2)

_CACHE_CLEARERS: List[Callable[[], None]] = []


def register_cache_clearer(fn: Callable[[], None]) -> Callable[[], None]:
    """Register a cache-dropping callback with :func:`clear_sim_caches`
    (the streaming engine registers its compiled-tile cache here, so one
    call resets every layer without import cycles)."""
    _CACHE_CLEARERS.append(fn)
    return fn


def clear_sim_caches() -> None:
    """Drop every host-side simulator memo: synthesized traces, reduced-
    key cell arrays, per-trace scalars, max-plus rows, banks, and any
    registered engine caches (compiled tile programs). Benchmarks call
    this between runs so no timing rides on caches an earlier run
    warmed; long-lived processes can call it to release pinned memory
    after a mega-grid sweep."""
    _trace_cached.cache_clear()
    _CELL_ARRAY_CACHE.clear()
    _TRACE_SCALAR_CACHE.clear()
    _WV_ROW_CACHE.clear()
    _BANK_CACHE.clear()       # drops host columns AND device placements
    clear_contention_caches()   # conflict draws + delay rows
    for fn in list(_CACHE_CLEARERS):
        fn()


# ---------------------------------------------------------------------------
# Per-cell cost derivation (shared by the serial oracle and the bank)
# ---------------------------------------------------------------------------

def _commit_cost_ns(config: str, cluster: ClusterConfig) -> Dict[str, float]:
    rtt = cluster.cxl_rtt_ns
    return {
        "t_l1": cluster.cycle_ns * 2.0,
        "t_wt": rtt + cluster.pmem_lat_ns,
        # REPL->ACK round trip to peer CNs + SRAM log write at the replica.
        # N_r REPLs go out in parallel; ack time = slowest ~ one RTT + log.
        "t_repl": rtt + cluster.sram_log_lat_ns,
        # VAL is one-way, off the commit path
        "t_drain": cluster.cycle_ns,
    }


@dataclasses.dataclass
class _CellScalars:
    """What result assembly (:func:`_finish_result`) and lane dedup need
    of one cell: no per-store array (see :func:`_cell_scalars`)."""
    spec: ScenarioSpec
    n_stores: int
    sb_size: int
    work_scale: float
    # derived bandwidth / log metrics (timeline-independent)
    n_repl_msgs: int
    max_log_bytes: float
    cxl_mem_bw_gbps: float
    log_dump_bw_gbps: float


@dataclasses.dataclass
class _CellInputs(_CellScalars):
    """Everything the serial ``_timeline`` and result assembly need for
    one cell."""
    config_idx: int
    # per-store timeline inputs, each (n_stores,)
    arrivals: np.ndarray
    coalesce: np.ndarray
    exposed: np.ndarray
    t_repl_i: np.ndarray
    svc_i: np.ndarray


@dataclasses.dataclass(frozen=True)
class _TraceScalars:
    """The scalars of one trace that results read (read-only)."""
    n_coalesced: int
    store_rate_per_core: float       # stores/s/core
    mem_demand: float                # GB/s per CN


@dataclasses.dataclass(frozen=True)
class _CellArrays:
    """Heavy per-store derivations shared across grid cells (read-only)."""
    coalesce: np.ndarray             # (n_stores,) bool
    exposed: np.ndarray              # (n_stores,) f32 ns
    t_repl_i: np.ndarray             # (n_stores,) f32 ns
    svc_i: np.ndarray                # (n_stores,) f32 ns


def _directory_delay_row(arrivals: np.ndarray, tx_mask: np.ndarray,
                         dirp: DirectoryParams, cluster: ClusterConfig,
                         congestion: float) -> np.ndarray:
    """Level-2 recurrence: per-store directory-queue delay (f32 ns).

    Stores are grouped into ``dirp.epoch``-long directory epochs on the
    arrival clock. Per epoch ``e`` the shared shard sees

    * ``own_e``  -- this cell's offered service: its directory
      transactions (the non-coalesced stores) times the directory's
      DRAM state-access service time, spread over the node's
      ``dirp.buckets`` shards (each shard serves 1/buckets of the
      node's lines);
    * ``bg_e``   -- the sharer pool's background utilization
      ``rho_bg * span_e``;

    and carries the Lindley backlog ``q_e = max(q_{e-1} + own_e + bg_e
    - span_e, 0)`` -- the service-rate recurrence the per-store
    max-plus recurrence nests inside. Every directory-transacting
    store of epoch ``e`` then waits the backlog carried INTO the epoch
    plus the M/D/1 in-epoch queueing wait ``rho * s / (2 (1 - rho))``,
    scaled by the cell's link-congestion factor like every other
    latency. Host numpy (f64 recurrence, f32 result): the delays are
    folded into the ``w`` side before the collapse, so no scan kernel
    changes. Exactly all-zero when ``rho_bg == 0`` (the load-0
    normalization cell); monotone in ``rho_bg``. Span
    ``directory/rows``: each nonzero row built, on either path.
    """
    n = int(arrivals.shape[0])
    if dirp.rho_bg <= 0.0 or n == 0:
        return np.zeros(n, np.float32)
    with _tm.span("directory/rows"):
        e_len = int(dirp.epoch)
        a = np.asarray(arrivals, np.float64)
        starts = a[::e_len]
        ends = np.concatenate([starts[1:], a[-1:] + cluster.cycle_ns])
        span = np.maximum(ends - starts, cluster.cycle_ns)
        tx = np.add.reduceat(np.asarray(tx_mask, np.float64),
                             np.arange(0, n, e_len))
        s_dir = float(cluster.dram_lat_ns)
        own = tx * s_dir / dirp.buckets
        bg = float(dirp.rho_bg) * span
        x = own + bg - span
        cs = np.cumsum(x)
        backlog = cs - np.minimum(np.minimum.accumulate(cs), 0.0)
        b_prev = np.concatenate([[0.0], backlog[:-1]])
        rho = np.minimum((own + bg) / span, 0.95)
        wq = rho * s_dir / (2.0 * (1.0 - rho))
        d_e = (b_prev + wq) * congestion
        delay = np.repeat(d_e, e_len)[:n]
        return np.where(tx_mask, delay, 0.0).astype(np.float32)


@dataclasses.dataclass(frozen=True)
class _RowScalars:
    """The per-row scalars of one replicating max-plus row: every
    per-store term of :func:`_make_cell_arrays` is an elementwise
    function of the trace's arrays and these. Python floats, the exact
    values the host expressions use; the device collapse rounds each
    to f32 once, as numpy does when it meets them."""
    congestion: float
    qslope: float                    # queue ramp per burst position (>= 0)
    qcap: float                      # SRAM backpressure bound (ns)
    t_repl_base: float               # congestion- and port-scaled REPL
    svc_floor: float                 # drain floor inside bursts
    t_drain: float                   # drain service outside bursts


def _row_scalars(ts: _TraceScalars, workload: str, cluster: ClusterConfig,
                 nr: int, bw: float, replicating: bool) -> _RowScalars:
    """The one derivation of a row's congestion and REPL / drain
    scalars from its trace's scalars ``ts``, for the host arrays
    (:func:`_make_cell_arrays`) and the row table of the device
    collapse (:func:`_row_recipes`)."""
    wl = WORKLOADS[workload]
    costs = _commit_cost_ns("proactive", cluster)   # config-independent

    # --- replication fan-out cost scaling -------------------------------
    # N_r REPLs leave in parallel but share the CN's CXL port: serialization
    # grows mildly with N_r; congestion scales latencies when offered load
    # nears the link bandwidth (Fig. 16/17 behaviour).
    repl_bytes = 8 + 64  # header + payload (coalesced line worst case)
    cores = cluster.cores_per_cn
    repl_demand = (ts.store_rate_per_core * cores * nr * repl_bytes
                   / 1e9)                                     # GB/s
    total_demand = ts.mem_demand + (repl_demand if replicating else 0.0)
    congestion = max(1.0, total_demand / bw)
    port_serial = 1.0 + 0.08 * (nr - 1)

    # Per-store REPL latency: inflated inside cluster-wide bursts (the
    # SPMD apps' flush phases align across CNs, so every Logging Unit is
    # absorbing its peers' REPL streams at once). The ACK backlog ramps
    # with position in the burst, capped when the SRAM Log Buffer
    # backpressures into DRAM-speed handling; the *sustained* drain floor
    # is the DRAM-log write path (~2 DRAM accesses per entry), which is
    # what bounds ReCXL-proactive during long flushes.
    svc_entry_ns = 2.0 * (1e3 / cluster.logging_unit_freq_mhz)  # SRAM path
    # saturated drain: log-entry write + log-metadata RMW at DRAM speed
    dram_svc_ns = 4.0 * cluster.dram_lat_ns
    qslope = (svc_entry_ns * cores * nr * (1.0 - wl.coalesce_rate)
              - cluster.cycle_ns)
    return _RowScalars(
        congestion=congestion,
        qslope=max(qslope, 0.0),
        qcap=195.0,
        t_repl_base=costs["t_repl"] * congestion * port_serial,
        # commit-drain service floor inside bursts (proactive path)
        svc_floor=dram_svc_ns * (1.0 - wl.coalesce_rate) * congestion
        * (1.0 + 0.1 * (nr - cluster.n_replicas)),
        t_drain=costs["t_drain"])


def _make_cell_arrays(workload: str, n_stores: int, seed: int,
                      cluster: ClusterConfig, nr: int, bw: float,
                      replicating: bool, coalesce_on: bool,
                      contention: Optional[ContentionParams] = None,
                      directory: Optional[DirectoryParams] = None
                      ) -> _CellArrays:
    trace = _trace_cached(workload, n_stores, seed, cluster)
    ts = _make_trace_scalars(workload, n_stores, seed, cluster, coalesce_on)
    sc = _row_scalars(ts, workload, cluster, nr, bw, replicating)
    congestion = sc.congestion

    coalesce = trace["coalesce"] if coalesce_on else \
        np.zeros_like(trace["coalesce"])
    exposed = trace["exposed_coh"] * congestion
    queue_i = np.minimum(trace["burst_pos"] * sc.qslope, sc.qcap) \
        * trace["in_burst"] * congestion
    t_repl_i = sc.t_repl_base + queue_i
    svc_i = np.where(trace["in_burst"], sc.svc_floor,
                     sc.t_drain).astype(np.float32)

    if contention is not None:
        # conflict backoff + sharer invalidations delay the coherence
        # transaction (the store's ready time absorbs them through the
        # exposed latency -> the w side of the max-plus recurrence);
        # persist barriers ride the REPL-ack and drain-service terms
        # (the v side). Neutral params yield all-zero rows, so x + 0.0
        # keeps every output bit-identical to the uncontended cell.
        delay, flush = contention_arrays(contention, n_stores, seed,
                                         cluster, congestion)
        exposed = exposed + delay
        t_repl_i = t_repl_i + flush
        svc_i = (svc_i + flush).astype(np.float32)

    if directory is not None:
        # the level-2 (per-epoch service-rate) recurrence: the shared
        # directory shard's queueing delay rides the w side exactly
        # like the contention backoff -- zero rows at load 0, so the
        # normalization cell stays bit-identical to the axis-off cell.
        dir_delay = _directory_delay_row(
            np.asarray(trace["arrivals"], np.float32),
            ~np.asarray(coalesce, bool), directory, cluster, congestion)
        exposed = exposed + dir_delay

    return _CellArrays(
        coalesce=np.asarray(coalesce, bool),
        exposed=np.asarray(exposed, np.float32),
        t_repl_i=np.asarray(t_repl_i, np.float32),
        svc_i=svc_i,
    )


def _make_trace_scalars(workload: str, n_stores: int, seed: int,
                        cluster: ClusterConfig, coalesce_on: bool
                        ) -> _TraceScalars:
    """The one derivation of a trace's store rate, coalesced-store count
    and memory demand, for both the per-store arrays
    (:func:`_make_cell_arrays`) and the result scalars
    (:func:`_cell_scalars`)."""
    wl = WORKLOADS[workload]
    trace = _trace_cached(workload, n_stores, seed, cluster)
    mean_gap = float(np.mean(trace["gaps"]))
    store_rate_per_core = 1e9 / max(mean_gap, 1e-3)          # stores/s/core
    cores = cluster.cores_per_cn
    mem_bytes = 64 + 16
    read_rate = (wl.remote_read_rate / wl.remote_store_rate) * store_rate_per_core
    mem_demand = (store_rate_per_core + read_rate) * cores * mem_bytes / 1e9
    return _TraceScalars(
        n_coalesced=int(trace["coalesce"].sum()) if coalesce_on else 0,
        store_rate_per_core=store_rate_per_core,
        mem_demand=mem_demand)


def _trace_scalars(workload: str, n_stores: int, seed: int,
                   cluster: ClusterConfig, coalesce_on: bool
                   ) -> _TraceScalars:
    """Memoized :func:`_make_trace_scalars` (:data:`_TRACE_SCALAR_CACHE`):
    every cell of one trace and coalescing class shares the entry."""
    key = (workload, n_stores, seed, cluster, coalesce_on)
    return _TRACE_SCALAR_CACHE.get_or_put(
        key, lambda: _make_trace_scalars(*key))


def _cell_arrays(workload: str, n_stores: int, seed: int,
                 cluster: ClusterConfig, nr: int, bw: float,
                 replicating: bool, coalesce_on: bool,
                 contention: Optional[ContentionParams] = None,
                 directory: Optional[DirectoryParams] = None
                 ) -> _CellArrays:
    """Memoized :func:`_make_cell_arrays` on the *reduced* key.

    The per-store arrays depend on the spec only through ``(workload,
    seed, n_replicas, link_bw, replicating-config?, coalescing
    effective?, contention, directory)`` -- NOT on ``config`` itself
    (beyond the replicating / wt-coalescing classes), ``sb_size`` or
    ``n_cns`` (the directory coupling sees the CN count only through
    the already-resolved :class:`DirectoryParams`). On a mega-grid
    whose axes include config/SB/CN sweeps, one derivation therefore
    serves many cells; the bound (:data:`_CELL_ARRAY_CACHE`) keeps
    pinned host memory at ~16 bytes x n_stores per entry."""
    key = (workload, n_stores, seed, cluster, nr, bw, replicating,
           coalesce_on, contention, directory)
    return _CELL_ARRAY_CACHE.get_or_put(
        key, lambda: _make_cell_arrays(*key))


def _resolve_coupling(spec: ScenarioSpec, cluster: ClusterConfig
                      ) -> Tuple[Optional[ContentionParams],
                                 Optional[DirectoryParams]]:
    """Resolve one cell's shared-resource coupling, canonically.

    The SINGLE resolution point for both the per-store data
    (:func:`_prepare_cell`) and the dedup keys (:func:`_plane_keys`),
    so the two cannot drift. Returns ``(contention, directory)``:

    * WB/WT commit locally without a directory transaction, so both
      components are ``None`` (their constant bank rows survive any
      coupling axis);
    * active contention gets the **directory-derived** sharer census:
      ``sharer_pool(n_cns, n_replicas)`` when ``read_share > 0`` (the
      small-cluster overcount bugfix -- never more than ``n_cns - 1``
      peers), canonical 0 when ``read_share == 0`` (the census is
      identically zero either way, so the CN weak-scaling axis keeps
      sharing lanes);
    * ``directory_load`` resolves through
      :func:`~repro.core.directory.resolve_directory_load`.
    """
    if spec.config not in _REPLICATING:
        return None, None
    nr = cluster.n_replicas if spec.n_replicas is None else spec.n_replicas
    ncn = cluster.n_cns if spec.n_cns is None else spec.n_cns
    con = spec.contention()
    if con is not None:
        pool = sharer_pool(ncn, nr) if con.read_share > 0.0 else 0
        if pool != con.sharer_pool:
            con = dataclasses.replace(con, sharer_pool=pool)
    dirp = resolve_directory_load(spec.directory_load, ncn, nr)
    return con, dirp


# ---------------------------------------------------------------------------
# Columnar trace bank (deduplicated data plane)
# ---------------------------------------------------------------------------

def _plane_keys(spec: ScenarioSpec, cluster: ClusterConfig
                ) -> Tuple[tuple, tuple]:
    """The two dedup keys of one cell's per-store inputs.

    ``trace_key`` selects the arrivals column (identical across every
    cell that scans the same trace); ``wv_key`` selects the
    precollapsed max-plus ``(w, v, pr_nc)`` column. WB/WT rows are
    constants (``t_l1`` / ``t_wt`` everywhere -- they commit locally
    without a directory transaction, so contention never touches them),
    so their key is just the rule name; the replicating rules depend on
    the reduced derivation knobs but NOT on ``sb_size`` / ``n_cns`` --
    the same reduction :func:`_cell_arrays` exploits, now visible to
    the device data plane. Active coupling axes append their resolved
    params (via :func:`_resolve_coupling`) in fixed order --
    :class:`ContentionParams` first, then
    :class:`~repro.core.directory.DirectoryParams` -- so coupled cells
    sharing a (shard, epoch-profile) still dedup to one row / lane;
    all-``None`` axes append NOTHING, so legacy grids keep
    byte-identical keys (and therefore identical bank rows -- no dedup
    churn)."""
    trace_key = (spec.workload, spec.seed)
    if spec.config in ("wb", "wt"):
        return trace_key, (spec.config,)
    nr = cluster.n_replicas if spec.n_replicas is None else spec.n_replicas
    bw = cluster.cxl_link_bw_gbps if spec.link_bw_gbps is None \
        else spec.link_bw_gbps
    wv_key = (spec.config, spec.workload, spec.seed, nr, bw,
              spec.coalescing)
    con, dirp = _resolve_coupling(spec, cluster)
    if con is not None:
        wv_key = wv_key + (con,)
    if dirp is not None:
        wv_key = wv_key + (dirp,)
    return trace_key, wv_key


def sub_bank_rows(rows: int, n_shards: int) -> int:
    """Local (per-shard) row count of a ``rows``-row wv plane
    partitioned round-robin over ``n_shards`` sub-banks: global row
    ``r`` is owned by shard ``r % n_shards`` at local row
    ``r // n_shards``, so the widest shard holds ``ceil(rows /
    n_shards)`` rows (floored at 1 so an empty or tiny plane still
    yields a valid gather target at local row 0). The ownership rule is
    a pure function of the global row index, so the append-only
    :meth:`TraceBank.extend` contract carries over: appending global
    rows only ever APPENDS to each shard's local sub-bank, never
    reshuffles it."""
    return max(1, -(-rows // n_shards))


def sub_bank_layout(rows: int, n_shards: int, k_replicas: int = 1,
                    cap: Optional[int] = None) -> np.ndarray:
    """The sub-bank layout as a row-index table: ``(n_shards,
    k_replicas * cap)`` global wv rows, ``-1`` where the slot is a zero
    padding row. ``cap`` (local rows per replica block) defaults to
    :func:`sub_bank_rows`. Block ``j`` of shard ``s`` holds, at local
    row ``q``, global row ``q * n_shards + (s - j) % n_shards`` -- the
    rows owned by shard ``(s - j) % n_shards`` -- so block 0 is the
    shard's own sub-bank and, with ``k_replicas >= 2``, row ``r`` is
    also resident on shard ``(r % n + 1) % n``. The one statement of
    the layout: the host stacks (:meth:`TraceBank.sub_bank_host`, the
    serving daemon's capacity stacks) and the device collapse
    (:meth:`TraceBank.collapse_inputs`) all read it."""
    cap = sub_bank_rows(rows, n_shards) if cap is None else cap
    s = np.arange(n_shards)[:, None, None]
    j = np.arange(k_replicas)[None, :, None]
    q = np.arange(cap)[None, None, :]
    r = q * n_shards + (s - j) % n_shards
    return np.where(r < rows, r, -1).reshape(n_shards, k_replicas * cap)


def take_rows(col: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """``col[idx]`` with zero rows where ``idx`` is ``-1`` (a
    :func:`sub_bank_layout` padding slot); always a fresh array."""
    out = np.zeros(idx.shape + col.shape[1:], col.dtype)
    have = idx >= 0
    out[have] = col[idx[have]]
    return out


def _coupling_of(wv_key: tuple) -> Tuple[Optional[ContentionParams],
                                         Optional[DirectoryParams]]:
    """The coupling params a replicating row key carries. Trailing
    components are typed, not positional: a key may carry contention,
    directory params, both (contention first), or neither -- see
    :func:`_plane_keys`."""
    con = dirp = None
    for extra in wv_key[6:]:
        if isinstance(extra, ContentionParams):
            con = extra
        elif isinstance(extra, DirectoryParams):
            dirp = extra
    return con, dirp


def _make_wv_row(wv_key: tuple, n_stores: int, cluster: ClusterConfig
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One precollapsed max-plus column, on the host: the host truth of
    a bank row (the lazily materialized :attr:`TraceBank.w` / ``v`` /
    ``pr_nc`` planes, the chaos digests) and the reference the device
    collapse (:func:`collapse_products` / :func:`collapse_sums`) is
    tested ``==`` against.

    Applies the exact arithmetic of :func:`_blocked_precompute` -- f32
    add / maximum / select are exactly-defined IEEE ops, so numpy and
    XLA produce identical bits -- once per unique row instead of once
    per cell. Returns ``(w, v, pr_nc)``, each ``(n_stores,)`` (f32,
    f32, bool)."""
    costs = _commit_cost_ns("proactive", cluster)
    t_l1 = np.float32(costs["t_l1"])
    t_wt = np.float32(costs["t_wt"])
    config = wv_key[0]
    if config in ("wb", "wt"):
        w = np.full(n_stores, t_l1 if config == "wb" else t_wt, np.float32)
        return w, w, np.zeros(n_stores, bool)
    _, workload, seed, nr, bw, coalescing = wv_key[:6]
    con, dirp = _coupling_of(wv_key)
    arr = _cell_arrays(workload, n_stores, seed, cluster, nr, bw, True,
                       coalescing, contention=con, directory=dirp)
    if config == "baseline":
        w = np.where(arr.coalesce, t_l1, arr.exposed + arr.t_repl_i)
        return w, w, np.zeros(n_stores, bool)
    if config == "parallel":
        w = np.where(arr.coalesce, t_l1,
                     np.maximum(arr.exposed, arr.t_repl_i))
        return w, w, np.zeros(n_stores, bool)
    if config == "proactive":
        pr_nc = ~arr.coalesce
        w = np.where(pr_nc, np.maximum(arr.t_repl_i, arr.exposed), t_l1)
        v = np.where(pr_nc, arr.svc_i, t_l1)
        return w, v, pr_nc
    raise ValueError(config)


def _wv_row(wv_key: tuple, n_stores: int, cluster: ClusterConfig):
    """Memoized :func:`_make_wv_row` (rows recur across banks and across
    sweeps of the same grid). Counter ``bank/wv_rows_built``: rows
    collapsed on the host, one per memo miss."""
    def build():
        _tm.count("bank/wv_rows_built")
        return _make_wv_row(wv_key, n_stores, cluster)

    return _WV_ROW_CACHE.get_or_put((wv_key, n_stores, cluster), build)


# ---------------------------------------------------------------------------
# Row table and the device collapse
# ---------------------------------------------------------------------------

#: Rule codes of the row table. A ``_RULE_CONST`` row is its constant
#: everywhere: WB (``t_l1``), WT (``t_wt``) and, at 0, the zero rows
#: that pad a sub-bank -- so a zero table row collapses to a zero row.
_RULE_CONST, _RULE_BASELINE, _RULE_PARALLEL, _RULE_PROACTIVE = range(4)
_RULE_OF = {"baseline": _RULE_BASELINE, "parallel": _RULE_PARALLEL,
            "proactive": _RULE_PROACTIVE}
#: int32 columns of the row table: rule, trace row, coalescing on, and
#: three rows of the extra plane (contention delay, directory delay,
#: persist flush; row 0 of the plane is zeros).
_I_RULE, _I_TRACE, _I_COAL, _I_DELAY, _I_DIR, _I_FLUSH = range(6)
#: f32 columns: the :class:`_RowScalars` fields in order, then the
#: row's constant (``t_l1`` of a replicating row's coalesced stores).
_S_CONG, _S_QSLOPE, _S_QCAP, _S_TREPL, _S_SVC, _S_DRAIN, _S_CONST = range(7)
#: Rows per upload chunk of the extra plane (see :func:`collapse_planes`).
EXTRA_CHUNK_ROWS = 8


@dataclasses.dataclass(frozen=True)
class RowTable:
    """What the device collapse builds a bank's max-plus rows from:
    each wv row is an elementwise function of its trace's per-store
    arrays, a few per-row scalars and at most three host-built rows
    with f64 intermediates (contention delay, directory delay, persist
    flush), so only those cross host->device -- about 2% of the planes'
    bytes on the mega-grid.

    The extra plane's rows are deduplicated by value (rows with equal
    congestion share one), so their number depends on the traces; its
    device capacity ``extra_cap`` counts them by the row keys alone (a
    multiple of :data:`EXTRA_CHUNK_ROWS`), so every sweep of one grid
    structure places the same shapes and compiles nothing new."""
    coalesce: np.ndarray             # (T, n_stores) bool, per trace row
    exposed_coh: np.ndarray          # (T, n_stores) f32 ns
    burst_pos: np.ndarray            # (T, n_stores) f32
    in_burst: np.ndarray             # (T, n_stores) bool
    extra: np.ndarray                # (E, n_stores) f32 ns, row 0 zeros
    extra_cap: int                   # device rows of the extra plane, >= E
    ints: np.ndarray                 # (P, 6) int32, the _I_* columns
    scal: np.ndarray                 # (P, 7) f32, the _S_* columns

    def extra_chunks(self) -> Tuple[np.ndarray, ...]:
        """The extra plane as :data:`EXTRA_CHUNK_ROWS`-row chunks, the
        last one zero-padded: fixed upload shapes whatever ``E`` is."""
        c = EXTRA_CHUNK_ROWS
        e = self.extra.shape[0]
        chunks = [self.extra[i:i + c] for i in range(0, e - e % c, c)]
        if e % c:
            last = np.zeros((c,) + self.extra.shape[1:], self.extra.dtype)
            last[:e % c] = self.extra[e - e % c:]
            chunks.append(last)
        return tuple(chunks)

    @property
    def shared_nbytes(self) -> int:
        """Bytes of the per-trace columns and the extra plane's chunks:
        what every shard of a mesh needs whole."""
        return sum(int(x.nbytes) for x in (
            self.coalesce, self.exposed_coh, self.burst_pos,
            self.in_burst) + self.extra_chunks())


class _ExtraRows:
    """The extra plane of a row table under construction: host-built
    rows deduplicated by ``key`` (what determines the row's values),
    row 0 the zero row. ``slot`` names the row by row-key components
    alone; each slot determines its key, so the slots bound the rows
    whatever values the traces give."""

    def __init__(self, n_stores: int) -> None:
        self.index: Dict[tuple, int] = {}
        self.slots: set = set()
        self.rows: List[np.ndarray] = [np.zeros(n_stores, np.float32)]

    def add(self, key: tuple, slot: tuple,
            build: Callable[[], np.ndarray]) -> int:
        self.slots.add(slot)
        i = self.index.get(key)
        if i is None:
            i = self.index[key] = len(self.rows)
            self.rows.append(build())
        return i

    @property
    def capacity(self) -> int:
        c = EXTRA_CHUNK_ROWS
        return -(-(1 + len(self.slots)) // c) * c


def _row_recipes(trace_row: Dict[tuple, int], wv_row: Dict[tuple, int],
                 n_stores: int, cluster: ClusterConfig
                 ) -> Tuple[List[tuple], List[tuple], "_ExtraRows"]:
    """One ``(ints, scalars)`` recipe per wv row, in row order, and the
    extra plane. Each scalar is the Python float the host
    expression of :func:`_make_cell_arrays` uses (numpy rounds it to
    f32 as it meets the f32 arrays; the table rounds it once the same
    way), and the delay / flush rows are the very rows
    :func:`_make_cell_arrays` adds."""
    costs = _commit_cost_ns("proactive", cluster)
    t_l1, t_wt = costs["t_l1"], costs["t_wt"]
    extra = _ExtraRows(n_stores)
    trace_scalars: Dict[tuple, _TraceScalars] = {}
    ints: List[tuple] = []
    scal: List[tuple] = []
    for key in wv_row:
        config = key[0]
        if config in ("wb", "wt"):
            ints.append((_RULE_CONST, 0, 0, 0, 0, 0))
            scal.append((0.0,) * 6 + (t_l1 if config == "wb" else t_wt,))
            continue
        _, workload, seed, nr, bw, coalescing = key[:6]
        con, dirp = _coupling_of(key)
        ts = trace_scalars.get((workload, seed))
        if ts is None:
            ts = trace_scalars[(workload, seed)] = _make_trace_scalars(
                workload, n_stores, seed, cluster, False)
        sc = _row_scalars(ts, workload, cluster, nr, bw, True)
        delay = dir_delay = flush = 0
        if con is not None:
            delay = extra.add(
                ("delay", con, seed, sc.congestion),
                ("delay", con, workload, seed, nr, bw),
                lambda: contention_arrays(con, n_stores, seed, cluster,
                                          sc.congestion)[0])
            flush = extra.add(
                ("flush", con.schedule), ("flush", con.schedule),
                lambda: schedule_flush_ns(con.schedule, n_stores, cluster))
        if dirp is not None and dirp.rho_bg > 0.0:
            # a zero-load shard adds the plane's zero row (row 0)
            trace = _trace_cached(workload, n_stores, seed, cluster)
            coalesce = trace["coalesce"] if coalescing else \
                np.zeros_like(trace["coalesce"])
            dir_delay = extra.add(
                ("directory", workload, seed, coalescing, dirp,
                 sc.congestion),
                ("directory", workload, seed, coalescing, dirp, nr, bw),
                lambda: _directory_delay_row(
                    np.asarray(trace["arrivals"], np.float32),
                    ~np.asarray(coalesce, bool), dirp, cluster,
                    sc.congestion))
        ints.append((_RULE_OF[config], trace_row[(workload, seed)],
                     int(bool(coalescing)), delay, dir_delay, flush))
        scal.append((sc.congestion, sc.qslope, sc.qcap, sc.t_repl_base,
                     sc.svc_floor, sc.t_drain, t_l1))
    return ints, scal, extra


def _stack_row_table(trace_row: Dict[tuple, int], recipes: tuple,
                     n_stores: int, cluster: ClusterConfig) -> RowTable:
    """Stack the per-trace columns, the recipes and the extra plane."""
    ints, scal, extra = recipes
    traces = [_trace_cached(w, n_stores, seed, cluster)
              for (w, seed) in trace_row]

    def col(name: str) -> np.ndarray:
        return np.stack([t[name] for t in traces], axis=0)

    return RowTable(
        coalesce=col("coalesce"), exposed_coh=col("exposed_coh"),
        burst_pos=col("burst_pos"), in_burst=col("in_burst"),
        extra=np.stack(extra.rows, axis=0), extra_cap=extra.capacity,
        ints=np.asarray(ints, np.int32).reshape(-1, 6),
        scal=np.asarray(scal, np.float32).reshape(-1, 7))


def collapse_products(exposed_coh: jax.Array, burst_pos: jax.Array,
                      in_burst: jax.Array, ints: jax.Array,
                      scal: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """First program of the device collapse: the products of
    :func:`_make_cell_arrays`, ``exposed_coh * congestion`` and
    ``min(burst_pos * qslope, qcap) * in_burst * congestion``, in its
    order, for a laid-out table ``ints`` / ``scal`` of shape ``(S, L,
    ·)``; returns two ``(S, L, n_stores)`` f32 planes. No add runs
    here, and no multiply in :func:`collapse_sums`: XLA:CPU contracts a
    multiply feeding an add inside one program into a fused
    multiply-add, which rounds once where numpy rounds twice (1 ulp on
    about a sixth of the mega-grid's rows), and nothing inside one
    program stops it. Two programs keep every intermediate rounded to
    f32, as on the host."""
    tr = ints[..., _I_TRACE]
    cong = scal[..., _S_CONG, None]
    exposed = exposed_coh[tr] * cong
    queue = (jnp.minimum(burst_pos[tr] * scal[..., _S_QSLOPE, None],
                         scal[..., _S_QCAP, None])
             * in_burst[tr].astype(jnp.float32) * cong)
    return exposed, queue


def collapse_sums(exposed: jax.Array, queue: jax.Array,
                  coalesce: jax.Array, in_burst: jax.Array,
                  extra: jax.Array, ints: jax.Array, scal: jax.Array
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Second program of the device collapse: the adds, maxima and
    selects of :func:`_make_cell_arrays` and :func:`_make_wv_row`, op
    for op, over :func:`collapse_products`' planes. An absent delay or
    flush adds the extra plane's zero row: ``x + 0.0 == x`` for the
    non-negative latencies here. Returns the ``(S, L, n_stores)``
    planes ``(w, v, pr_nc)``."""
    tr = ints[..., _I_TRACE]
    flush = extra[ints[..., _I_FLUSH]]
    ex = exposed + extra[ints[..., _I_DELAY]]
    ex = ex + extra[ints[..., _I_DIR]]
    t_repl = scal[..., _S_TREPL, None] + queue
    t_repl = t_repl + flush
    svc = jnp.where(in_burst[tr], scal[..., _S_SVC, None],
                    scal[..., _S_DRAIN, None]) + flush
    coal = coalesce[tr] & (ints[..., _I_COAL, None] != 0)
    const = scal[..., _S_CONST, None]
    rule = ints[..., _I_RULE, None]
    pr_nc = (rule == _RULE_PROACTIVE) & ~coal
    w = jnp.where(
        rule == _RULE_BASELINE, jnp.where(coal, const, ex + t_repl),
        jnp.where(rule == _RULE_PARALLEL,
                  jnp.where(coal, const, jnp.maximum(ex, t_repl)),
                  jnp.where(pr_nc, jnp.maximum(t_repl, ex), const)))
    v = jnp.where(rule == _RULE_PROACTIVE, jnp.where(pr_nc, svc, const), w)
    return w, v, pr_nc


#: The two collapse programs on one device (the engine shard_maps the
#: same bodies over its mesh). The product planes are donated to
#: ``w`` / ``v``.
COLLAPSE_PROGRAMS = (jax.jit(collapse_products),
                     jax.jit(collapse_sums, donate_argnums=(0, 1)))


@functools.partial(jax.jit, static_argnums=(1,))
def _extra_open(chunk: jax.Array, rows: int) -> jax.Array:
    """A zero ``(rows, n_stores)`` extra plane holding ``chunk`` at row
    0 (placed like ``chunk``)."""
    return jnp.zeros((rows,) + chunk.shape[1:], chunk.dtype) \
        .at[:chunk.shape[0]].set(chunk)


@functools.partial(jax.jit, donate_argnums=(0,))
def _extra_write(plane: jax.Array, chunk: jax.Array,
                 row: jax.Array) -> jax.Array:
    """``chunk`` written into ``plane`` at ``row`` (a traced offset)."""
    return jax.lax.dynamic_update_slice(plane, chunk, (row, 0))


def collapse_planes(inputs: tuple, extra_cap: int,
                    products: Callable = COLLAPSE_PROGRAMS[0],
                    sums: Callable = COLLAPSE_PROGRAMS[1]) -> tuple:
    """Build the placed max-plus planes from placed
    :meth:`TraceBank.collapse_inputs`: returns ``(arrivals, w, v,
    pr_nc)``, the planes shaped as the table. The extra plane's chunks
    are written into a zero plane of ``extra_cap`` rows
    (``RowTable.extra_cap``) on the device, so no program's shape
    depends on how many rows the traces' values gave. Span
    ``bank/collapse`` covers the writes, both programs and the wait
    for them."""
    a, coalesce, exposed_coh, burst_pos, in_burst, chunks, ints, scal = inputs
    with _tm.span("bank/collapse", rows=int(ints.shape[0] * ints.shape[1])):
        extra = _extra_open(chunks[0], extra_cap)
        for i, chunk in enumerate(chunks[1:], 1):
            extra = _extra_write(extra, chunk, i * EXTRA_CHUNK_ROWS)
        exposed, queue = products(exposed_coh, burst_pos, in_burst,
                                  ints, scal)
        planes = sums(exposed, queue, coalesce, in_burst, extra, ints, scal)
        jax.block_until_ready(planes)
    return (a,) + tuple(planes)


@dataclasses.dataclass
class TraceBank:
    """Columnar, deduplicated per-store inputs for one grid.

    Rows are **store-contiguous** (``(rows, n_stores)``, C-contiguous):
    a device gather along axis 0 is then one row memcpy per cell (XLA
    lowers whole-row gathers to copies -- measured ~3x faster on CPU
    than a column gather out of a time-major bank), and the transpose
    into the scan's time-major layout is a cheap local device op.
    ``arrivals[trace_row[k]]`` is the arrivals row
    of trace key ``k``; ``w / v / pr_nc[wv_row[k]]`` the precollapsed
    max-plus row of row key ``k``.

    The max-plus rows have two forms. The sweep path places them
    through :meth:`sub_device_args`, which uploads the
    :class:`RowTable` (per-trace columns, per-row scalars, the
    host-built delay / flush rows) and collapses the rows on the device
    (:func:`collapse_planes`): the planes never exist on the host. The
    host planes ``w`` / ``v`` / ``pr_nc`` are materialized on first
    access, row by row through :func:`_make_wv_row`, for
    the readers that need a host truth (the serving daemon's splices,
    :meth:`device_args`, :meth:`extend`, the chaos digests; one row:
    :meth:`host_row`). Both are memoized with the bank by
    :func:`get_trace_bank` and placed at most once per placement key;
    :func:`clear_sim_caches` drops them.

    Banks are **append-only**: :meth:`extend` adds the rows of new
    specs in first-seen order -- exactly the order a from-scratch build
    of the merged grid would assign -- so an extended bank is
    byte-identical to :func:`get_trace_bank` of the concatenated spec
    list (tests/test_trace_bank.py pins this), existing row indices
    stay valid forever, and :meth:`device_args` uploads only the
    **diff** (the appended rows) for placements that already hold the
    old rows. The scenario-serving daemon (``repro.core.serving``)
    lives on this: the marginal H2D cost of a novel query is its new
    rows, not the bank."""
    n_stores: int
    cluster: ClusterConfig
    arrivals: np.ndarray             # (T, n_stores) f32 ns
    trace_row: Dict[tuple, int]
    wv_row: Dict[tuple, int]
    _device: Dict[object, tuple] = dataclasses.field(
        default_factory=dict, repr=False)
    # Logging-Unit journal: un-acknowledged extend() diffs (None = off;
    # see enable_journal / ack_journal / replay_journal below)
    _journal: Optional[List[Dict[str, np.ndarray]]] = dataclasses.field(
        default=None, repr=False)
    # derived from the row keys, built on first use (None = not yet)
    _planes: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]] = \
        dataclasses.field(default=None, init=False, repr=False)
    _table: Optional[RowTable] = dataclasses.field(
        default=None, init=False, repr=False)

    @property
    def trace_rows(self) -> int:
        return self.arrivals.shape[0]

    @property
    def wv_rows(self) -> int:
        return len(self.wv_row)

    @property
    def n_rows(self) -> int:
        return self.trace_rows + self.wv_rows

    @property
    def nbytes(self) -> int:
        """Bytes of all four columns (= H2D bytes of one
        :meth:`device_args` upload): arrivals plus 9 bytes a store of
        each wv row, whether or not the host planes exist yet."""
        return self.arrivals.nbytes + self.wv_rows * self.n_stores * 9

    def _host_planes(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        if self._planes is None:
            rows = [_wv_row(k, self.n_stores, self.cluster)
                    for k in self.wv_row]
            n = self.n_stores
            self._planes = tuple(
                np.stack([r[i] for r in rows], axis=0) if rows
                else np.zeros((0, n), dt)
                for i, dt in enumerate((np.float32, np.float32, bool)))
        return self._planes

    @property
    def w(self) -> np.ndarray:
        """(P, n_stores) f32 ns, host truth (materialized on access)."""
        return self._host_planes()[0]

    @property
    def v(self) -> np.ndarray:
        """(P, n_stores) f32 ns, host truth (materialized on access)."""
        return self._host_planes()[1]

    @property
    def pr_nc(self) -> np.ndarray:
        """(P, n_stores) bool, host truth (materialized on access)."""
        return self._host_planes()[2]

    def host_row(self, r: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Global wv row ``r``'s host truth ``(w, v, pr_nc)``, without
        materializing the planes."""
        if self._planes is not None:
            return tuple(p[r] for p in self._planes)
        # row indices are assigned in insertion order (bank_row_maps,
        # extend), so the r-th key is row r's
        return _wv_row(list(self.wv_row)[r], self.n_stores, self.cluster)

    @property
    def row_table(self) -> RowTable:
        """The :class:`RowTable` of the bank's rows (built with the
        bank, rebuilt on first use after :meth:`extend`)."""
        if self._table is None:
            self._table = _stack_row_table(
                self.trace_row,
                _row_recipes(self.trace_row, self.wv_row, self.n_stores,
                             self.cluster),
                self.n_stores, self.cluster)
        return self._table

    @property
    def shared_nbytes(self) -> int:
        """Bytes a sub-bank placement needs whole on every shard: the
        arrivals plus the row table's per-trace columns and extra
        plane."""
        return int(self.arrivals.nbytes) + self.row_table.shared_nbytes

    def rows_for(self, spec: ScenarioSpec) -> Tuple[int, int]:
        """(trace_row, wv_row) indices of one cell of the build grid."""
        tk, wk = _plane_keys(spec, self.cluster)
        return self.trace_row[tk], self.wv_row[wk]

    def device_args(self, key: object = 1,
                    place: Optional[Callable[[tuple], tuple]] = None
                    ) -> Tuple[int, tuple]:
        """Device-resident ``(arrivals, w, v, pr_nc)`` for one placement,
        from the host planes.

        ``place`` maps the host tuple onto devices (the streaming engine
        passes a replicating ``device_put`` over its ``cells`` mesh);
        the default commits to the default device. Placements are
        memoized by ``key``, so a grid swept repeatedly uploads once.
        Returns ``(bytes_uploaded_now, arrays)`` --
        ``bytes_uploaded_now`` is 0 on a placement-cache hit, which is
        what the engine's ``h2d_bytes`` accounting reports.

        After :meth:`extend` grew the bank, a resident placement is
        refreshed **incrementally**: only the appended row slices cross
        host->device (``place`` sees just the diff) and are concatenated
        onto the resident buffers device-side, so
        ``bytes_uploaded_now`` is the diff's bytes, not the bank's."""
        dev = self._device.get(key)
        if dev is not None:
            t_res, p_res = int(dev[0].shape[0]), int(dev[1].shape[0])
            if t_res == self.trace_rows and p_res == self.wv_rows:
                return 0, dev
            # diff upload: ship only the rows appended since placement
            host = (self.arrivals[t_res:], self.w[p_res:],
                    self.v[p_res:], self.pr_nc[p_res:])
            fresh = place(host) if place is not None else \
                tuple(jnp.asarray(x) for x in host)
            dev = tuple(jnp.concatenate([d, f], axis=0)
                        for d, f in zip(dev, fresh))
            self._device[key] = dev
            return sum(int(x.nbytes) for x in host), dev
        host = (self.arrivals, self.w, self.v, self.pr_nc)
        dev = place(host) if place is not None else \
            tuple(jnp.asarray(x) for x in host)
        self._device[key] = dev
        return self.nbytes, dev

    def sub_bank_host(self, n_shards: int, k_replicas: int = 1) -> tuple:
        """Host arrays of the per-shard sub-bank layout: ``(arrivals,
        w_sub, v_sub, pr_nc_sub)`` with the three max-plus planes
        stacked ``(n_shards, k_replicas * local_rows, n_stores)`` by
        :func:`sub_bank_layout` -- shard ``s``'s PRIMARY sub-bank
        (local rows ``[0, local)``) is rows ``s::n_shards`` of the
        global plane, zero-padded to the widest shard's
        :func:`sub_bank_rows` count.  Arrivals stay the global 2-D
        plane (they are replicated on device; see
        ``distributed.sharding.SUB_BANK_SPEC``).

        ``k_replicas > 1`` appends the paper's **Replica set** along
        the local-row axis: replica block ``j`` (local rows ``[j *
        local, (j + 1) * local)``) of shard ``s`` holds the rows owned
        by shard ``(s - j) % n_shards`` -- so global row ``r`` is
        resident on shards ``r % n`` (primary) and ``(r % n + 1) % n``
        (first replica), and losing ONE shard never loses a row
        (``repro.core.chaos.replica_rebuild`` reads the survivor's
        block back).  Gathers always target the primary block, so the
        scan arithmetic -- and at ``k_replicas=1`` the bytes -- are
        unchanged from the PR-8 layout; the replica blocks cost
        ``(k - 1)/n_shards`` extra resident bytes per max-plus plane.

        The host form of what :meth:`sub_device_args` builds on the
        device. At one shard (which forces ``k_replicas=1``) with at
        least one wv row the layout is the identity: the planes are
        ``(1, P, n_stores)`` VIEWS of ``w``, ``v`` and ``pr_nc``, not a
        copy. Every other layout (several shards, replica blocks, the
        one padding row of an empty plane) is a fresh zero-padded
        copy."""
        if not 1 <= k_replicas <= n_shards:
            raise ValueError(f"k_replicas must be in [1, {n_shards}], "
                             f"got {k_replicas}")
        if n_shards == 1 and self.wv_rows:
            return (self.arrivals, self.w[None], self.v[None],
                    self.pr_nc[None])
        idx = sub_bank_layout(self.wv_rows, n_shards, k_replicas)
        return (self.arrivals,) + tuple(
            take_rows(col, idx) for col in (self.w, self.v, self.pr_nc))

    def collapse_inputs(self, n_shards: int, k_replicas: int = 1) -> tuple:
        """Host inputs of the device collapse into the
        :meth:`sub_bank_host` layout: ``(arrivals, coalesce,
        exposed_coh, burst_pos, in_burst, extra_chunks, ints, scal)`` --
        the arrivals and the :class:`RowTable`'s columns whole, its
        extra plane as a tuple of fixed-shape chunks, its ``ints`` /
        ``scal`` laid out ``(n_shards, k_replicas * local_rows, ·)`` by
        :func:`sub_bank_layout`, a padding slot a zero row (a
        ``_RULE_CONST`` row of constant 0, which collapses to the zero
        padding row). At one shard the table is a view."""
        if not 1 <= k_replicas <= n_shards:
            raise ValueError(f"k_replicas must be in [1, {n_shards}], "
                             f"got {k_replicas}")
        t = self.row_table
        if n_shards == 1 and self.wv_rows:
            ints, scal = t.ints[None], t.scal[None]
        else:
            idx = sub_bank_layout(self.wv_rows, n_shards, k_replicas)
            ints, scal = take_rows(t.ints, idx), take_rows(t.scal, idx)
        return (self.arrivals, t.coalesce, t.exposed_coh, t.burst_pos,
                t.in_burst, t.extra_chunks(), ints, scal)

    def sub_device_args(self, n_shards: int,
                        place: Optional[Callable[[tuple], tuple]] = None,
                        k_replicas: int = 1,
                        programs: Tuple[Callable, Callable] =
                        COLLAPSE_PROGRAMS) -> Tuple[int, tuple]:
        """Device-resident sub-bank placement (:meth:`sub_bank_host`
        layout), collapsed on the device: ``place`` puts the
        :meth:`collapse_inputs` on devices (default: the default
        device) and :func:`collapse_planes` runs the two collapse
        ``programs`` over them (the engine passes them shard_mapped
        over its mesh), returning ``(arrivals, w, v, pr_nc)``. Memoized
        like :meth:`device_args` under the key ``("sub", n_shards)``
        (``("sub", n_shards, k_replicas)`` for a replicated layout, so
        resilient and plain placements of one bank coexist). Returns
        ``(bytes_uploaded_now, arrays)``: the collapse inputs' bytes,
        about 2% of the planes'. Growth re-places the whole sub-bank
        (no diff path: the streaming engine never extends a bank
        mid-run, and the serving daemon keeps its own capacity-padded
        device state with per-shard splices). Counter
        ``bank/rows_device``: the wv rows collapsed on the device,
        replica copies included."""
        key = ("sub", n_shards) if k_replicas == 1 \
            else ("sub", n_shards, k_replicas)
        entry = self._device.get(key)
        rows_now = (self.trace_rows, self.wv_rows)
        if entry is not None:
            rows_placed, dev = entry
            if rows_placed == rows_now:
                return 0, dev
        host = self.collapse_inputs(n_shards, k_replicas)
        _tm.count("bank/rows_device", k_replicas * self.wv_rows)
        placed = place(host) if place is not None else \
            jax.tree.map(jnp.asarray, host)
        dev = collapse_planes(placed, self.row_table.extra_cap, *programs)
        self._device[key] = (rows_now, dev)
        return sum(int(x.nbytes) for x in jax.tree.leaves(host)), dev

    def drop_placement(self, key: object) -> None:
        """Forget one memoized device placement (recovery re-admission:
        after a shard loss the stale arrays must not be served from the
        memo -- the next ``device_args``/``sub_device_args`` call
        re-places from the host truth)."""
        self._device.pop(key, None)

    # -- Logging-Unit journal (resilience; see repro.core.chaos) ----------

    @property
    def journal_enabled(self) -> bool:
        return self._journal is not None

    @property
    def journal_entries(self) -> int:
        """Un-acknowledged ``extend()`` diffs currently retained."""
        return len(self._journal) if self._journal is not None else 0

    def enable_journal(self) -> None:
        """Start journaling ``extend()`` diffs (the paper's Logging
        Unit, host-side): every append records a COPY of its new rows,
        retained until :meth:`ack_journal` confirms the device dump.
        Idempotent; off by default (the copies cost memory), enabled by
        the serving daemon when chaos/recovery is requested."""
        if self._journal is None:
            self._journal = []

    def ack_journal(self) -> None:
        """Acknowledge the device dump: every journaled diff is now
        resident device-side, so the retained copies are dropped (the
        host columns remain the durable truth)."""
        if self._journal is not None:
            self._journal.clear()

    def replay_journal(self) -> Dict[str, np.ndarray]:
        """Concatenate the un-acknowledged diffs in append order --
        what a recovering node would replay on top of the last
        acknowledged dump.  ``chaos.journal_rebuild`` digest-checks
        this against the bank's tail rows before using it."""
        if self._journal is None:
            raise RuntimeError("journal not enabled")
        empty = {"arrivals": np.zeros((0,), np.float32),
                 "w": np.zeros((0,), np.float32),
                 "v": np.zeros((0,), np.float32),
                 "pr_nc": np.zeros((0,), bool)}
        if not self._journal:
            return empty
        return {name: (np.concatenate([e[name] for e in self._journal
                                       if e[name].shape[0]], axis=0)
                       if any(e[name].shape[0] for e in self._journal)
                       else empty[name])
                for name in ("arrivals", "w", "v", "pr_nc")}

    def extend(self, specs: Sequence[ScenarioSpec]) -> Tuple[int, int]:
        """Append the rows of ``specs`` not yet in the bank, in place.

        New ``(trace, wv)`` keys get rows in **first-seen order over
        ``specs``** -- the same order :func:`_make_trace_bank` assigns
        when building the merged grid from scratch, so after
        ``bank.extend(delta)`` the bank's columns and row maps are
        byte-identical to ``get_trace_bank(base + delta)``
        (tests/test_trace_bank.py pins ``==`` on the bytes). Existing
        rows and indices are never reordered, so handles, cached index
        vectors and resident device placements of the old grid all stay
        valid; stale placements are refreshed by the next
        :meth:`device_args` call via a diff upload of just these rows.
        Host planes already materialized grow by the new rows; the row
        table is rebuilt on its next use.

        Returns ``(new_trace_rows, new_wv_rows)`` -- ``(0, 0)`` when
        every spec's rows were already present. Not thread-safe on its
        own; the serving daemon serializes extends under its lock.

        With the Logging-Unit journal enabled (:meth:`enable_journal`),
        every append additionally retains a COPY of its new rows until
        :meth:`ack_journal` confirms the device dump -- the host-side
        replay source ``repro.core.chaos.journal_rebuild`` recovers a
        lost shard from."""
        t0, p0 = self.trace_rows, self.wv_rows
        new_trace: List[tuple] = []
        new_wv: List[tuple] = []
        for s in specs:
            tk, wk = _plane_keys(s, self.cluster)
            if tk not in self.trace_row:
                self.trace_row[tk] = len(self.trace_row)
                new_trace.append(tk)
            if wk not in self.wv_row:
                self.wv_row[wk] = len(self.wv_row)
                new_wv.append(wk)
        if new_trace or new_wv:
            self._table = None
        if new_trace:
            rows = [_trace_cached(w, self.n_stores, seed, self.cluster)
                    ["arrivals"] for (w, seed) in new_trace]
            self.arrivals = np.concatenate(
                [self.arrivals, np.stack(rows, axis=0)], axis=0)
        if new_wv and self._planes is not None:
            cols = [_wv_row(k, self.n_stores, self.cluster) for k in new_wv]
            self._planes = tuple(
                np.concatenate([plane, np.stack([c[i] for c in cols],
                                                axis=0)], axis=0)
                for i, plane in enumerate(self._planes))
        if self._journal is not None and (new_trace or new_wv):
            self._journal.append({
                "arrivals": self.arrivals[t0:].copy(),
                "w": self.w[p0:].copy(),
                "v": self.v[p0:].copy(),
                "pr_nc": self.pr_nc[p0:].copy()})
        return len(new_trace), len(new_wv)


def bank_row_maps(specs: Sequence[ScenarioSpec],
                  cluster: ClusterConfig = PAPER_CLUSTER
                  ) -> Tuple[Dict[tuple, int], Dict[tuple, int]]:
    """The (trace, wv) row maps of a grid WITHOUT materializing columns
    -- one cheap dict pass over the specs. The streaming engine uses
    this to know the bank's shape (and so its tile signatures) before
    the heavy row materialization starts, so compile warming overlaps
    the bank build."""
    trace_row: Dict[tuple, int] = {}
    wv_row: Dict[tuple, int] = {}
    for s in specs:
        tk, wk = _plane_keys(s, cluster)
        trace_row.setdefault(tk, len(trace_row))
        wv_row.setdefault(wk, len(wv_row))
    return trace_row, wv_row


def _make_trace_bank(specs: Tuple[ScenarioSpec, ...], n_stores: int,
                     cluster: ClusterConfig) -> TraceBank:
    """Build a bank: its traces (``bank/synth``), the row table's
    recipes and host-built delay / flush rows (``bank/rows``), and the
    stacked arrivals, per-trace columns, table and extra plane
    (``bank/stack``). No max-plus row is collapsed here: placement
    collapses them on the device, and the host planes wait for a
    reader."""
    with _tm.span("bank/build", cells=len(specs)):
        trace_row, wv_row = bank_row_maps(specs, cluster)
        with _tm.span("bank/synth", rows=len(trace_row)):
            a_rows = [_trace_cached(w, n_stores, seed, cluster)["arrivals"]
                      for (w, seed) in trace_row]
        draws0, delays0 = contention_memo_misses()
        with _tm.span("bank/rows", rows=len(wv_row)):
            recipes = _row_recipes(trace_row, wv_row, n_stores, cluster)
        _tm.count("bank/trace_rows", len(trace_row))
        _tm.count("bank/wv_rows", len(wv_row))
        # host-collapsed rows are counted where _wv_row builds them; a
        # bank build collapses none, so the counter reads 0 unless a
        # host reader materializes rows
        _tm.count("bank/wv_rows_built", 0)
        if any(isinstance(x, ContentionParams) for k in wv_row
               for x in k[6:]):
            draws1, delays1 = contention_memo_misses()
            _tm.count("contention/draws_built", draws1 - draws0)
            _tm.count("contention/delay_rows_built", delays1 - delays0)
        if any(isinstance(x, DirectoryParams) for k in wv_row
               for x in k[6:]):
            _tm.count("directory/delay_rows_built", sum(
                k[0] == "directory" for k in recipes[2].index))
            _tm.count("directory/coupled_rows", sum(
                r[_I_DIR] != 0 for r in recipes[0]))
        with _tm.span("bank/stack"):
            bank = TraceBank(
                n_stores=n_stores, cluster=cluster,
                arrivals=np.stack(a_rows, axis=0),
                trace_row=trace_row, wv_row=wv_row)
            bank._table = _stack_row_table(trace_row, recipes, n_stores,
                                           cluster)
            return bank


def get_trace_bank(specs: Sequence[ScenarioSpec], n_stores: int,
                   cluster: ClusterConfig = PAPER_CLUSTER) -> TraceBank:
    """Build (or fetch) the memoized columnar bank of a grid.

    Digest-keyed (:func:`_specs_key`), so repeated sweeps of the same
    grid -- and ``scenarios.grid_bank`` ahead of a sweep -- share ONE
    bank handle, and therefore one device upload per placement.
    :func:`clear_sim_caches` drops it."""
    with _tm.span("bank/get"):
        key = ("bank",) + _specs_key(tuple(specs), n_stores, cluster)
        return _BANK_CACHE.get_or_put(
            key, lambda: _make_trace_bank(tuple(specs), n_stores, cluster))


def _cell_scalars(spec: ScenarioSpec, n_stores: int,
                  cluster: ClusterConfig) -> _CellScalars:
    """Resolve a ScenarioSpec into the scalars of its result, without
    the per-store arrays: the bank's consumers (the streaming engine's
    tiles, the serving daemon) scan precollapsed bank rows and read
    only these. The per-trace part is memoized (:func:`_trace_scalars`);
    the rest is a few float operations per cell. :func:`_prepare_cell`
    builds its scalars here too, so the engine's and the serial
    oracle's results agree bit for bit.
    Contention and directory coupling change only the per-store
    arrays, never these scalars."""
    config = spec.config
    nr = cluster.n_replicas if spec.n_replicas is None else spec.n_replicas
    ncn = cluster.n_cns if spec.n_cns is None else spec.n_cns
    sb = cluster.store_buffer if spec.sb_size is None else spec.sb_size
    replicating = config in _REPLICATING
    ts = _trace_scalars(spec.workload, n_stores, spec.seed, cluster,
                        spec.coalescing and config != "wt")

    # --- scaling with CN count: fewer CNs -> each runs more of the fixed
    # total work (weak scaling of the cluster as in Fig. 18).
    work_scale = cluster.n_cns / ncn

    n_repl = int(n_stores - ts.n_coalesced) if replicating else 0

    # --- log sizing (Fig. 13): entries accumulated per dump period ------
    entry_bytes = 12                       # Fig. 5: ~97 bits
    stores_per_s = ts.store_rate_per_core * cluster.cores_per_cn * nr
    log_bytes = stores_per_s * (cluster.dump_period_ms * 1e-3) * entry_bytes
    dump_bw = (log_bytes / cluster.gzip_factor) / (cluster.dump_period_ms * 1e-3) / 1e9

    return _CellScalars(
        spec=spec, n_stores=n_stores, sb_size=sb, work_scale=work_scale,
        n_repl_msgs=n_repl,
        max_log_bytes=log_bytes,
        cxl_mem_bw_gbps=ts.mem_demand * ncn,
        log_dump_bw_gbps=(dump_bw * ncn if replicating else 0.0),
    )


def _prepare_cell(spec: ScenarioSpec, trace: Dict[str, np.ndarray],
                  n_stores: int, cluster: ClusterConfig) -> _CellInputs:
    """Resolve a ScenarioSpec against a synthesized trace into the exact
    per-store arrays the timeline consumes, plus its result scalars
    (:func:`_cell_scalars`). Pure host-side numpy; used verbatim by
    ``simulate`` and the contention oracle (which validate the specs up
    front) so the references cannot drift. The heavy array work
    lives in :func:`_cell_arrays` and is shared across every cell with
    the same reduced key."""
    config = spec.config
    nr = cluster.n_replicas if spec.n_replicas is None else spec.n_replicas
    bw = cluster.cxl_link_bw_gbps if spec.link_bw_gbps is None else spec.link_bw_gbps

    # contention and directory coupling only touch the directory/
    # replication transactions of the replicating configs (WB/WT commit
    # locally on the modeled path), keeping the WB normalization
    # baseline -- and the constant WB/WT bank rows -- unchanged;
    # _resolve_coupling is shared with _plane_keys so the per-store
    # data and the dedup keys cannot drift.
    con, dirp = _resolve_coupling(spec, cluster)
    arr = _cell_arrays(spec.workload, n_stores, spec.seed, cluster, nr, bw,
                       config in _REPLICATING,
                       spec.coalescing and config != "wt",
                       contention=con, directory=dirp)
    return _CellInputs(
        **vars(_cell_scalars(spec, n_stores, cluster)),
        config_idx=_CONFIG_IDX[config],
        arrivals=trace["arrivals"],
        coalesce=arr.coalesce,
        exposed=arr.exposed,
        t_repl_i=arr.t_repl_i,
        svc_i=arr.svc_i,
    )


def _finish_result(cell: _CellScalars, exec_ns: float, at_head: int,
                   sb_full: int,
                   meta: Optional[Dict[str, object]] = None) -> SimResult:
    n = cell.n_stores
    return SimResult(
        workload=cell.spec.workload,
        config=cell.spec.config,
        exec_time_ns=float(exec_ns) * cell.work_scale,
        n_stores=n,
        n_repl_msgs=cell.n_repl_msgs,
        repl_at_head_frac=float(at_head) / max(n, 1),
        max_log_bytes=cell.max_log_bytes,
        cxl_mem_bw_gbps=cell.cxl_mem_bw_gbps,
        log_dump_bw_gbps=cell.log_dump_bw_gbps,
        sb_full_frac=float(sb_full) / max(n, 1),
        meta=meta,
    )


# ---------------------------------------------------------------------------
# Store-buffer timeline -- serial oracle (one lax.scan per cell)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("config", "sb_size"))
def _timeline(arrivals: jax.Array, coalesce: jax.Array, exposed: jax.Array,
              t_repl_i: jax.Array, svc_i: jax.Array,
              config: str, sb_size: int, t_l1: float, t_wt: float,
              t_drain: float) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (exec_time_ns, repl_at_head_count, sb_full_count).

    ``arrivals``: absolute store arrival times (ns), precomputed on the
    host so the oracle and the engine share one bit-identical input.
    ``t_repl_i``: per-store REPL->ACK latency (congestion/N_r adjusted).
    ``svc_i``: per-store replica Logging-Unit service time -- the
    throughput floor of commit draining during cluster-wide bursts (every
    CN's unit is absorbing the other CNs' REPL streams at the same time).
    """
    def body(carry, inp):
        ring, last_c, at_head, sb_full = carry
        a_i, co_i, coh_i, tr_i, sv_i = inp
        # retire: wait for a free SB slot (commit of store i - sb_size)
        oldest = ring[0]
        r_i = jnp.maximum(a_i, oldest)
        sb_full = sb_full + (oldest > a_i)

        if config == "wb":
            c_i = jnp.maximum(r_i, last_c) + t_l1
        elif config == "wt":
            c_i = jnp.maximum(r_i, last_c) + t_wt
        elif config == "baseline":
            extra = jnp.where(co_i, t_l1, coh_i + tr_i)
            c_i = jnp.maximum(r_i, last_c) + extra
        elif config == "parallel":
            extra = jnp.where(co_i, t_l1, jnp.maximum(coh_i, tr_i))
            c_i = jnp.maximum(r_i, last_c) + extra
        elif config == "proactive":
            # REPL issued at retire; ack returns tr_i later; REPL->ACK
            # cycles of queued stores overlap (Fig. 8). Commits drain no
            # faster than the replica units can log (sv_i floor).
            ack_i = r_i + tr_i
            coh_done = r_i + coh_i
            c_raw = jnp.maximum(jnp.maximum(ack_i, coh_done),
                                last_c + sv_i)
            c_i = jnp.where(co_i, jnp.maximum(r_i, last_c) + t_l1, c_raw)
            # Fig. 11: the REPL went out "at the SB head" if nothing was
            # queued ahead of the store when it retired.
            at_head = at_head + jnp.where(~co_i & (r_i >= last_c), 1, 0)
        else:
            raise ValueError(config)

        ring = jnp.roll(ring, -1).at[-1].set(c_i)
        return (ring, c_i, at_head, sb_full), None

    ring0 = jnp.zeros((sb_size,), jnp.float32)
    (ring, last_c, at_head, sb_full), _ = jax.lax.scan(
        body, (ring0, jnp.float32(0.0), jnp.int32(0), jnp.int32(0)),
        (arrivals, coalesce, exposed, t_repl_i, svc_i))
    return last_c, at_head, sb_full


# ---------------------------------------------------------------------------
# Store-buffer timeline -- blocked scan (chunk the store stream, scan over
# chunk boundaries, max-plus inputs collapsed before the scan)
# ---------------------------------------------------------------------------

#: Deepest block the auto heuristic picks for narrow tiles (see
#: :func:`auto_chunk`; wide tiles cap at :data:`AUTO_CHUNK_CAP`).
DEFAULT_CHUNK_SIZE = 128


def _blocked_precompute(coalesce: jax.Array, exposed: jax.Array,
                        t_repl_i: jax.Array, svc_i: jax.Array,
                        config_idx: jax.Array, t_l1: float, t_wt: float
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Collapse all five commit rules into one max-plus recurrence.

    The device-side reference of the host collapse :func:`_make_wv_row`
    (tests/test_trace_bank.py pins the two ``==``). Every rule is
    exactly (bit-for-bit) of the form

        c_i = max(r_i + w_i,  c_{i-1} + v_i)

    because IEEE-754 addition is monotone, so ``max(r, c) + e ==
    max(r + e, c + e)`` and ``max(r + a, r + b) == r + max(a, b)``
    hold exactly:

    * WB / WT / baseline / parallel / coalesced-proactive
      (``c_i = max(r_i, c_{i-1}) + extra_i``):  w_i = v_i = extra_i,
      where ``extra_i`` is t_l1, t_wt, or the coalesce-mask select over
      ``exposed``/``t_repl_i`` of the replicating rules;
    * non-coalesced proactive
      (``c_i = max(r_i + max(t_repl_i, coh_i), c_{i-1} + svc_i)``):
      w_i = max(t_repl_i, exposed_i), v_i = svc_i.

    Returns ``(w, v, pr_nc)``, each time-major ``(n_stores, B)``
    (``w``/``v`` f32 ns, ``pr_nc`` bool = proactive-and-not-coalesced,
    the Fig. 11 REPL-at-SB-head candidate mask), computed in one
    vectorized pass.
    """
    is_wt = config_idx == _CONFIG_IDX["wt"]
    is_bl = config_idx == _CONFIG_IDX["baseline"]
    is_pl = config_idx == _CONFIG_IDX["parallel"]
    is_pr = config_idx == _CONFIG_IDX["proactive"]

    ex_bl = jnp.where(coalesce, t_l1, exposed + t_repl_i)
    ex_pl = jnp.where(coalesce, t_l1, jnp.maximum(exposed, t_repl_i))
    # wb and coalesced-proactive both add t_l1
    ex_other = jnp.where(is_wt[None, :], jnp.float32(t_wt),
                         jnp.float32(t_l1))
    extra = jnp.where(is_bl[None, :], ex_bl,
                      jnp.where(is_pl[None, :], ex_pl, ex_other))
    pr_nc = is_pr[None, :] & ~coalesce
    w = jnp.where(pr_nc, jnp.maximum(t_repl_i, exposed), extra)
    v = jnp.where(pr_nc, svc_i, extra)
    return w, v, pr_nc


def _blocked_steps_uniform(carry, a_b, w_b, v_b, p_b):
    """Advance the blocked timeline by one block of ``K`` stores.

    Every lane shares one store-buffer depth ``sb`` (the tile planner
    groups lanes by SB), so the commit history is carried as a *tuple*
    of ``sb`` ``(B,)`` arrays (oldest first): the SB-ring read for
    store ``k`` is the plain Python indexing ``hist[k]`` (``c_{i-sb}``
    exactly, K <= sb) and the history shift is static tuple slicing --
    no gather, no stacked commit matrix, no materialized per-store
    timeline. Both censuses accumulate in-scan (integer adds,
    order-exact). The per-store work is ~7 tiny fusible ``(B,)`` ops,
    the serial oracle's arithmetic on the collapsed rows, so results
    stay bit-identical to it.

    ``carry`` = (hist tuple, last (B,), at_head (B,) i32, sb_full (B,)
    i32); block inputs are time-major ``(K, B)`` slices.
    """
    hist, last, at_head, sb_full = carry
    k_len = a_b.shape[0]
    cs = []
    for k in range(k_len):
        old = hist[k]
        r_k = jnp.maximum(a_b[k], old)
        sb_full = sb_full + (old > a_b[k])
        at_head = at_head + (p_b[k] & (r_k >= last))
        last = jnp.maximum(r_k + w_b[k], last + v_b[k])
        cs.append(last)
    return (hist[k_len:] + tuple(cs), last, at_head, sb_full)


def _scan_wv(arrivals: jax.Array, w: jax.Array, v: jax.Array,
             pr_nc: jax.Array, chunk: int, sb_uniform: int
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The blocked scan over collapsed max-plus inputs.

    Inputs are time-major ``(n_stores, B)``: ``arrivals`` plus the
    ``(w, v, pr_nc)`` max-plus rows, gathered from the bank
    (:func:`_bank_gather`). Every lane shares the store-buffer depth
    ``sb_uniform`` (the tile planner groups lanes by SB), and ``chunk``
    (stores per block) is at most that depth. ``n_stores // chunk``
    full blocks run inside one ``lax.scan`` -- in the time-major layout
    the blocking reshape is free -- and the ragged tail (``n_stores %
    chunk`` stores) is processed once after the scan with the same step
    function (:func:`_blocked_steps_uniform`), so results are exact for
    every chunk size. Must be called inside jit.

    Returns per-lane (exec_time_ns, repl_at_head_count, sb_full_count).
    """
    n, n_b = arrivals.shape
    n_main = (n // chunk) * chunk
    rem = n - n_main

    def to_blocks(x):
        # time-major blocking is a free reshape: (n_main, B) ->
        # (n_blocks, chunk, B)
        return x[:n_main].reshape(-1, chunk, n_b)

    carry = (tuple(jnp.zeros((n_b,), jnp.float32)
                   for _ in range(sb_uniform)),
             jnp.zeros((n_b,), jnp.float32),
             jnp.zeros((n_b,), jnp.int32),
             jnp.zeros((n_b,), jnp.int32))
    if n_main:
        xs = tuple(to_blocks(x) for x in (arrivals, w, v, pr_nc))

        def body(c, blk):
            return _blocked_steps_uniform(c, *blk), None

        carry, _ = jax.lax.scan(body, carry, xs)
    if rem:
        tail = tuple(x[n_main:] for x in (arrivals, w, v, pr_nc))
        carry = _blocked_steps_uniform(carry, *tail)
    _, last_c, at_head, sb_full = carry
    return last_c, at_head, sb_full


def _bank_gather(a_bank: jax.Array, w_bank: jax.Array, v_bank: jax.Array,
                 p_bank: jax.Array, trace_idx: jax.Array, wv_idx: jax.Array
                 ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """In-jit bank gather into the scan's time-major layout.

    One row memcpy per lane (whole-row gathers lower to copies) plus a
    cheap device transpose to time-major. The engine's tile program
    (``engine._build_bank_tile_fn``) calls it ahead of
    :func:`_scan_wv`. Must be called inside jit."""
    return (jnp.take(a_bank, trace_idx, axis=0).T,
            jnp.take(w_bank, wv_idx, axis=0).T,
            jnp.take(v_bank, wv_idx, axis=0).T,
            jnp.take(p_bank, wv_idx, axis=0).T)


# ---------------------------------------------------------------------------
# Public entries
# ---------------------------------------------------------------------------

def simulate(workload: str, config: str,
             cluster: ClusterConfig = PAPER_CLUSTER,
             n_stores: int = 50_000, seed: int = 0,
             n_replicas: Optional[int] = None,
             link_bw_gbps: Optional[float] = None,
             n_cns: Optional[int] = None,
             sb_size: Optional[int] = None,
             coalescing: bool = True,
             read_share: Optional[float] = None,
             conflict_rate: Optional[float] = None,
             consistency_schedule: Optional[str] = None,
             directory_load: Optional[float] = None) -> SimResult:
    """Simulate one (workload, config) pair on one compute node.

    All sensitivity knobs of Figs. 16-18 are exposed as overrides
    (``n_replicas`` replica count, ``link_bw_gbps`` CXL link bandwidth in
    GB/s, ``n_cns`` compute-node count, ``sb_size`` store-buffer
    entries), as are the contention axes (``read_share`` /
    ``conflict_rate`` / ``consistency_schedule`` -- see
    ``repro.core.contention``) and the directory-coupling axis
    (``directory_load`` -- see ``repro.core.directory``). This is the
    serial oracle the engine is differentially tested against; returns
    a :class:`SimResult` (times in ns, log sizes in bytes, bandwidths in
    GB/s).
    """
    spec = ScenarioSpec(workload, config, seed=seed, n_replicas=n_replicas,
                        link_bw_gbps=link_bw_gbps, n_cns=n_cns,
                        sb_size=sb_size, coalescing=coalescing,
                        read_share=read_share, conflict_rate=conflict_rate,
                        consistency_schedule=consistency_schedule,
                        directory_load=directory_load)
    spec.validate(cluster)
    trace = _trace_cached(workload, n_stores, seed, cluster)
    cell = _prepare_cell(spec, trace, n_stores, cluster)
    costs = _commit_cost_ns(config, cluster)
    exec_ns, at_head, sb_full = _timeline(
        jnp.asarray(cell.arrivals), jnp.asarray(cell.coalesce),
        jnp.asarray(cell.exposed), jnp.asarray(cell.t_repl_i),
        jnp.asarray(cell.svc_i), config, cell.sb_size,
        costs["t_l1"], costs["t_wt"], costs["t_drain"])
    return _finish_result(cell, exec_ns, int(at_head), int(sb_full),
                          meta={"engine": "serial",
                                "data_plane": "stacked",
                                "bank_partition": None})


def simulate_spec(spec: ScenarioSpec,
                  cluster: ClusterConfig = PAPER_CLUSTER,
                  n_stores: int = 50_000) -> SimResult:
    """Run the serial oracle for one :class:`ScenarioSpec` cell.

    The single place that maps EVERY spec knob -- including the
    contention axes -- onto :func:`simulate`'s keyword surface, so
    differential callers (``run_sweep(engine="serial")``, benchmark
    oracle checks) cannot silently drop a new axis."""
    return simulate(spec.workload, spec.config, cluster=cluster,
                    n_stores=n_stores, seed=spec.seed,
                    n_replicas=spec.n_replicas,
                    link_bw_gbps=spec.link_bw_gbps, n_cns=spec.n_cns,
                    sb_size=spec.sb_size, coalescing=spec.coalescing,
                    read_share=spec.read_share,
                    conflict_rate=spec.conflict_rate,
                    consistency_schedule=spec.consistency_schedule,
                    directory_load=spec.directory_load)


def _specs_key(specs: Sequence[ScenarioSpec], n_stores: int,
               cluster: ClusterConfig) -> Tuple[int, int, str]:
    """Constant-size digest key for a (specs, n_stores, cluster) grid."""
    h = hashlib.blake2b(digest_size=16)
    h.update(repr((n_stores, cluster)).encode())
    for s in specs:
        h.update(repr(s).encode())
    return (len(specs), n_stores, h.hexdigest())


#: Cap for the auto-chunk heuristic on *wide* batches. The per-block
#: unroll is ``chunk`` steps of ~7 row ops and a ``chunk``-long carried
#: history, so past a few dozen stores per block wide batches (rows of
#: hundreds+ cells) lose throughput to carry traffic and compile time;
#: measured fastest around 32-48 at tile widths, vs the full SB depth
#: for narrow batches. Explicit ``chunk_size`` callers can pick
#: anything.
AUTO_CHUNK_CAP = 48

#: Batch width (padded cell count) at which the auto heuristic switches
#: from the deep narrow-batch chunk to the capped wide-batch chunk.
AUTO_CHUNK_WIDE_CELLS = 256


def auto_chunk(n_stores: int, sb_min: int,
               n_cells: Optional[int] = None) -> int:
    """Blocked-scan chunk heuristic (used when ``chunk_size=None``).

    The SB depth bounds how far back the retire recurrence can look
    (``c_{i-sb}``), so a block may never exceed the tile's SB depth.
    Beyond that, two measured regimes (CPU):

    * **narrow** tiles (``n_cells`` < :data:`AUTO_CHUNK_WIDE_CELLS`):
      ``lax.scan`` step overhead dominates the tiny per-store row ops,
      so the deepest legal block wins -- ``min(sb, n_stores,
      DEFAULT_CHUNK_SIZE)``;
    * **wide** tiles (``n_cells`` at or past the threshold, or
      ``n_cells=None``): the unrolled block body and its carried
      history dominate, so the cap is :data:`AUTO_CHUNK_CAP` -- and a
      chunk that divides ``n_stores`` exactly is preferred, because a
      ragged tail duplicates the whole unrolled block body in the
      compiled program.

    The pick lands in ``SimResult.meta['chunk']``.
    """
    hi = min(sb_min, n_stores)
    if n_cells is not None and n_cells < AUTO_CHUNK_WIDE_CELLS:
        return max(1, min(hi, DEFAULT_CHUNK_SIZE))
    cap = min(hi, AUTO_CHUNK_CAP)
    for c in range(cap, 15, -1):         # largest exact divisor, if any
        if n_stores % c == 0:
            return c
    return max(1, cap)


def slowdowns_from_results(results: Sequence[SimResult],
                           baseline: str = "wb"
                           ) -> Dict[str, Dict[str, float]]:
    """Group a sweep's SimResults into a per-workload slowdown table
    normalized to ``baseline`` (one ``baseline`` cell per workload must
    be present; cells are keyed by (workload, config), so pass results
    from a grid that does not repeat a cell with different knobs)."""
    times: Dict[str, Dict[str, float]] = {}
    for r in results:
        times.setdefault(r.workload, {})[r.config] = r.exec_time_ns
    out: Dict[str, Dict[str, float]] = {}
    for w, row in times.items():
        if baseline not in row:
            raise ValueError(f"no {baseline!r} cell for workload {w!r}")
        out[w] = {c: t / row[baseline] for c, t in row.items()}
    return out


def slowdown_table(configs: Tuple[str, ...] = CONFIGS,
                   workloads: Optional[Tuple[str, ...]] = None,
                   n_stores: int = 50_000, batched: bool = True,
                   cluster: ClusterConfig = PAPER_CLUSTER,
                   **kw) -> Dict[str, Dict[str, float]]:
    """Fig. 2 / Fig. 10: per-workload slowdowns normalized to WB.

    ``batched=True`` (default) runs the whole grid as ONE
    ``scenarios.run_sweep`` call; ``batched=False`` keeps the serial
    per-cell oracle loop for differential testing. ``kw`` takes any
    ScenarioSpec knob (seed, n_replicas, link_bw_gbps, n_cns, sb_size,
    coalescing).
    """
    workloads = workloads or tuple(WORKLOADS)
    cfgs = tuple(dict.fromkeys(("wb",) + tuple(configs)))
    if batched:
        from repro.core.scenarios import run_sweep
        specs = [ScenarioSpec(w, c, **kw) for w in workloads for c in cfgs]
        results = run_sweep(specs, cluster=cluster, n_stores=n_stores)
        table = slowdowns_from_results(results)
        return {w: {c: table[w][c] for c in configs} for w in workloads}
    out: Dict[str, Dict[str, float]] = {}
    for w in workloads:
        base = simulate(w, "wb", cluster=cluster, n_stores=n_stores,
                        **kw).exec_time_ns
        out[w] = {}
        for c in configs:
            t = simulate(w, c, cluster=cluster, n_stores=n_stores,
                         **kw).exec_time_ns
            out[w][c] = t / base
    return out


def geomean_slowdowns(table: Dict[str, Dict[str, float]]) -> Dict[str, float]:
    """Per-config geometric mean over the workloads of a slowdown table
    (the paper's headline aggregation; dimensionless ratios)."""
    out: Dict[str, float] = {}
    for c in next(iter(table.values())):
        vals = [table[w][c] for w in table]
        out[c] = float(np.exp(np.mean(np.log(vals))))
    return out

"""Sharded streaming grid engine: the simulator's one fast path.

:func:`run_grid` runs any scenario grid -- one cell, a figure grid, or
the 12 960-cell (workload x config x seed x N_r x bw x CN x SB)
mega-grid of Figs. 10/16-18 -- and returns results ``==`` the serial
``simulate()`` oracle (tests/test_engine.py asserts it). Four stages:

1. **Scan lanes and the columnar trace bank.** A timeline consumes
   nothing but its (arrivals row, max-plus row, SB depth), so cells
   sharing that triple are ONE scan lane: each lane is scanned once and
   its outputs are scattered to the member cells (the 12 960-cell
   mega-grid scans ~2 700 lanes). The grid's
   :class:`~repro.core.simulator.TraceBank` holds each unique arrivals
   row and each host-collapsed ``(w, v, pr_nc)`` max-plus row once; it
   is uploaded once per grid and stays device-resident, and tiles ship
   only two ``int32`` row-index vectors. The tile program gathers its
   rows in-jit (``_bank_gather``) and runs the blocked scan
   (``_scan_wv``).

2. **Tile scheduler** (:func:`plan_tiles`). Lanes are grouped by
   store-buffer depth, so every tile is SB-uniform and runs the
   tuple-history blocked scan, and each group is cut into tiles padded
   to the full tile size, so a grid runs one compiled program per SB
   group (the :class:`TileSignature` -> :func:`_tile_fn` cache),
   however ragged it is.

3. **Per-shard sub-banks under ``shard_map``.** With more than one
   local device the cell axis is sharded over a ``cells`` mesh
   (``repro.distributed.context.cells_mesh``). The three max-plus
   planes are partitioned into per-shard sub-banks (row ``r`` on shard
   ``r % n_shards``) and every lane is scheduled onto the shard that
   owns its row, so the gather and the scan run with ZERO cross-device
   communication; cells are independent timelines, so sharding cannot
   change a lane's arithmetic. ``bank_partition="replicated"`` (one
   full bank per shard) is kept for the degraded-mesh recovery.

4. **Double-buffered streaming.** A worker thread prepares tile k+1's
   index vectors and result scalars while the devices compute tile k;
   dispatch runs ahead of the devices by at most
   :data:`MAX_IN_FLIGHT_TILES` tiles before the oldest is drained,
   bounding live memory at the bank plus a few tile payloads. A second
   thread compiles every signature against the resident bank while the
   first tiles run.

``SimResult.meta`` records the chunk, tile and shard geometry of each
cell; :func:`bank_stats` reports the last run's bank accounting (H2D
bytes, bank rows, scan lanes, measured device bytes). Everything this
module caches is dropped by ``simulator.clear_sim_caches()`` (its
compiled-tile cache is registered via ``register_cache_clearer``).

Two notes on axes and threads:

* **Coupled axes ride the plane keys.** Lane and bank-row dedup both
  key on ``simulator._plane_keys``, which already appends the resolved
  ``ContentionParams`` / ``DirectoryParams`` tails for coupled cells
  (the two-level directory recurrence is folded into the wv row on the
  host, before the bank ever sees it). The engine therefore needs no
  knowledge of either axis: coupled cells that share a (shard,
  epoch-profile) still collapse to one scan lane, and axis-off grids
  produce byte-identical keys -- and rows -- to grids without the axes.
* **Memo caches are shared with worker threads.** The prefetch and
  compile-warm executors mutate the same :class:`BoundedCache` memos
  (per-trace scalars, trace synthesis, compiled tiles) as the caller;
  ``hostcache.BoundedCache`` serializes per-cache, so each key is
  built exactly once even when a warm thread and the dispatch loop
  race on it.
"""

from __future__ import annotations

import dataclasses
import math
import time
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import wait as _futures_wait
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.recxl_paper import ClusterConfig, PAPER_CLUSTER
from repro.core import chaos as _chaos
from repro.core import telemetry as _tm
from repro.core.chaos import (
    ChaosError,
    IntegrityError,
    ShardLossError,
    ThreadDeathError,
    UploadError,
)
from repro.core.retry import PLACEMENT_RETRY, retry_call
from repro.core.simulator import (
    ScenarioSpec,
    SimResult,
    TraceBank,
    _TRACE_SCALAR_CACHE,
    _bank_gather,
    _cell_scalars,
    _finish_result,
    _plane_keys,
    _scan_wv,
    auto_chunk,
    bank_row_maps,
    get_trace_bank,
    register_cache_clearer,
    sub_bank_rows,
)
from repro.distributed.context import cells_mesh, shard_map
from repro.distributed.sharding import (
    bank_shardings,
    bank_tile_specs,
    index_shardings,
    sub_bank_shardings,
    sub_bank_tile_specs,
)

#: Lanes per tile at the default byte budget. Large enough that one
#: scan amortizes dispatch overhead, small enough that a tile's
#: gathered (B_tile, n_stores) inputs stream through cache instead of
#: RAM.
DEFAULT_TILE_CELLS = 1024

#: Byte budget for one tile's per-store inputs, counted at 17 bytes
#: per lane-store. Long traces shrink the tile lane count so the
#: double-buffered ring (tile k on device, tile k+1 on the prep thread)
#: stays at ~2x this footprint regardless of ``n_stores``. 128 MB
#: measured fastest end-to-end at paper-scale store counts (the sweet
#: spot between per-tile dispatch overhead and cache-resident scans).
DEFAULT_TILE_BYTES = 128 << 20


def _default_tile_cells(n_stores: int) -> int:
    per_cell = max(1, 17 * n_stores)
    return int(min(DEFAULT_TILE_CELLS,
                   max(64, DEFAULT_TILE_BYTES // per_cell)))


#: Dispatched-but-undrained tile bound. Dispatch runs ahead of device
#: compute, so this -- together with the prep thread's one-tile
#: lookahead -- is what actually caps the engine's live memory at a few
#: tile footprints regardless of grid size.
MAX_IN_FLIGHT_TILES = 3

#: Spare-replacement recovery attempts per :func:`run_grid` call before
#: the fault propagates (a second independent failure mid-recovery is
#: out of the modeled scope -- bounded like every retry here).
MAX_RECOVERIES = 3

#: Gather-path integrity sampling cap: at most this many of a tile's
#: wv rows are CRC-checked against the host bank before dispatch (only
#: under an active chaos scope that wants verification -- see
#: ``chaos.ChaosConfig.verify_rows``; the production path never reads
#: rows back).
VERIFY_ROWS_PER_TILE = 16


class EngineWorkerError(RuntimeError):
    """A streaming-engine worker thread (prefetch / compile-warm)
    failed or stalled.  Carries the tile / signature context so the
    caller sees *which* unit of work died instead of a bare exception
    surfacing tiles later (or, for a stalled worker, never)."""

    def __init__(self, stage: str, tile_no: Optional[int],
                 sig: Optional[TileSignature] = None, note: str = ""):
        msg = f"{stage} worker failed"
        if tile_no is not None:
            msg += f" on tile {tile_no}"
        if sig is not None:
            msg += (f" (sig: b_pad={sig.b_pad} sb={sig.sb_uniform}"
                    f" chunk={sig.chunk})")
        if note:
            msg += f": {note}"
        super().__init__(msg)
        self.stage = stage
        self.tile_no = tile_no
        self.sig = sig


_HEARTBEATS: Dict[str, float] = {}


def worker_heartbeats() -> Dict[str, float]:
    """``time.monotonic()`` of each engine worker thread's last unit of
    work (``"prefetch"`` / ``"compile-warm"``) -- the liveness signal
    ``run_grid(worker_timeout_s=...)`` and external watchdogs check a
    stalled worker against."""
    return dict(_HEARTBEATS)


def _h2d_hook(nbytes: int = 0) -> None:
    """Chaos injection point for one host->device placement (no-op
    without an active scope)."""
    st = _chaos.active()
    if st is not None:
        st.on_upload(nbytes)


def _retried(fn: Callable[[], object], describe: str):
    """Bounded jittered retry around a placement/dispatch callable:
    only transient :class:`~repro.core.chaos.UploadError` is retried --
    shard loss and integrity faults must reach the recovery path."""
    st = _chaos.active()
    return retry_call(fn, policy=PLACEMENT_RETRY, retryable=(UploadError,),
                      describe=describe,
                      on_retry=st.note_retry if st is not None else None)


@dataclasses.dataclass(frozen=True)
class TileSignature:
    """Everything that selects a compiled tile program.

    Two tiles with equal signatures reuse one XLA executable: ``b_pad``
    is the padded lane count, ``chunk`` the blocked-scan block length,
    ``sb_uniform`` the tile's (uniform, by scheduling) SB depth,
    ``n_shards`` the ``cells`` mesh size, and ``bank_shape`` the
    ``(trace_rows, wv_rows)`` of the grid's bank -- jit specializes on
    the bank's shape, so it is part of the program key.
    ``bank_sub=True`` selects the per-shard sub-bank layout (the
    default): the three max-plus columns arrive as a ``(n_shards,
    local_rows, n_stores)`` shard-partitioned stack, wv indices are
    shard-LOCAL, and ``bank_shape[1]`` is the local (per-shard) row
    count; ``False`` is the replicated bank of the degraded-mesh
    recovery. A whole mega-grid runs with a handful of distinct
    signatures.
    """
    b_pad: int
    n_stores: int
    chunk: int
    sb_uniform: int
    n_shards: int
    bank_shape: Tuple[int, int] = (0, 0)
    bank_sub: bool = False


@dataclasses.dataclass(frozen=True)
class Tile:
    """One scheduled slice of a grid: original positions + specs + sig.

    ``slots`` (sub-bank scheduling only) maps entry ``j`` of
    ``indices``/``specs`` to its padded position in the tile's index
    vectors and outputs: the vector is laid out as ``n_shards``
    contiguous blocks of ``b_pad // n_shards`` slots, and lane ``j``
    sits inside the block of the shard that OWNS its wv row, so the
    in-jit gather under ``shard_map`` stays shard-local. ``None`` means
    the identity layout (entry ``j`` at position ``j``), as at one shard
    and on the replicated bank."""
    indices: Tuple[int, ...]
    specs: Tuple[ScenarioSpec, ...]
    sig: TileSignature
    slots: Optional[Tuple[int, ...]] = None


def _align(n_shards: int) -> int:
    """Tile-size alignment: a multiple of 8 and of the shard count
    (shard_map divisibility)."""
    return 8 * n_shards // math.gcd(8, n_shards)


def plan_tiles(specs: Sequence[ScenarioSpec],
               cluster: ClusterConfig = PAPER_CLUSTER,
               n_stores: int = 50_000,
               chunk_size: Optional[int] = None,
               tile_cells: int = DEFAULT_TILE_CELLS,
               n_shards: int = 1,
               owners: Optional[Sequence[int]] = None) -> List[Tile]:
    """Schedule a grid into full-size, SB-uniform tiles.

    Cells are grouped by resolved store-buffer depth (preserving order
    within a group -- results are scattered back to original positions
    by :func:`run_grid`), so every tile runs the tuple-history fast
    path with its chunk clamped only by its OWN depth, not the
    narrowest cell of the whole grid. Each group is cut into
    ``tile_cells``-sized tiles, and every tile -- a ragged last one too
    -- pads to the full tile: one compiled program per SB group, since
    deduplicated scan lanes leave few tiles per group and a ragged
    tail's own program costs ~50x the padding lanes it would avoid.

    ``owners`` (sub-bank scheduling) gives each cell's owning shard
    (``wv_row % n_shards``, aligned with ``specs``): each tile's index
    vector is then laid out as ``n_shards`` blocks of ``b_pad //
    n_shards`` slots (``_align`` guarantees divisibility) and every
    lane lands in its owner's block, recorded in :attr:`Tile.slots` --
    the layout under which a ``shard_map`` over the ``cells`` axis
    hands each shard exactly the lanes whose wv rows it holds. Tiles
    per group become ``ceil(max_per_shard_lanes / block)`` instead of
    ``ceil(lanes / tile_cells)``; round-robin row ownership keeps the
    shard blocks balanced to within one lane on real grids.
    """
    align = _align(n_shards)
    tile_cells = max(align, -(-tile_cells // align) * align)

    groups: Dict[int, List[Tuple[int, ScenarioSpec]]] = {}
    for i, s in enumerate(specs):
        sb = s.sb_size if s.sb_size is not None else cluster.store_buffer
        groups.setdefault(sb, []).append((i, s))

    tiles: List[Tile] = []
    for sb, members in groups.items():
        chunk = auto_chunk(n_stores, sb, tile_cells) if chunk_size is None \
            else max(1, min(chunk_size, n_stores, sb))

        sig = TileSignature(b_pad=tile_cells, n_stores=n_stores,
                            chunk=chunk, sb_uniform=sb, n_shards=n_shards)

        if owners is not None and n_shards > 1:
            by_shard: List[List[Tuple[int, ScenarioSpec]]] = \
                [[] for _ in range(n_shards)]
            for i, s in members:
                by_shard[owners[i]].append((i, s))
            block = tile_cells // n_shards
            n_tiles = max(1, -(-max(len(b) for b in by_shard) // block))
            for t in range(n_tiles):
                part: List[Tuple[int, ScenarioSpec]] = []
                slots: List[int] = []
                for sh, b in enumerate(by_shard):
                    for q, (i, s) in enumerate(b[t * block:(t + 1) * block]):
                        part.append((i, s))
                        slots.append(sh * block + q)
                tiles.append(Tile(indices=tuple(i for i, _ in part),
                                  specs=tuple(s for _, s in part),
                                  sig=sig, slots=tuple(slots)))
            continue
        for off in range(0, len(members), tile_cells):
            part = members[off:off + tile_cells]
            tiles.append(Tile(indices=tuple(i for i, _ in part),
                              specs=tuple(s for _, s in part), sig=sig))
    return tiles


# ---------------------------------------------------------------------------
# Signature-keyed compile cache
# ---------------------------------------------------------------------------

_TILE_FNS: Dict[TileSignature, Callable] = {}
_TRACE_COUNT = 0


def trace_count() -> int:
    """Tile-program traces since import (monotone; compile-cache
    diagnostics -- tests assert it does NOT grow across same-signature
    tiles, benchmarks report the per-run delta)."""
    return _TRACE_COUNT


_BANK_STATS: Dict[str, object] = {}


def bank_stats() -> Dict[str, object]:
    """Bank accounting of the most recent :func:`run_grid` call
    (``trace_count()``-style observability; benchmarks turn it into the
    ``fig10/megagrid/*`` bank rows). Keys:

    * ``cells`` / ``n_shards`` -- run geometry; ``scan_lanes`` -- unique
      timelines actually scanned;
    * ``bank_partition`` -- ``"sub"`` (per-shard sub-banks, the
      default) or ``"replicated"``;
    * ``trace_rows`` / ``wv_rows`` / ``bank_rows`` -- deduplicated bank
      columns; ``bank_bytes`` -- host bytes of one bank copy;
      ``bank_dev_bytes_per_shard`` / ``bank_dev_bytes`` -- **measured**
      resident device bytes of the placed bank (summed
      from the live buffers' addressable shards: max per device, and
      fleet total). Replicated placement measures ~``bank x n_shards``
      total; the sub-bank placement holds one copy of each max-plus
      row fleet-wide (arrivals stay replicated -- they are ~1% of the
      bytes and a lane's trace/wv rows may have different owners), so
      the total stays ~``bank_bytes`` and per-shard drops to
      ~``1/n_shards``;
    * ``h2d_bytes`` -- bytes that actually crossed host->device this
      run (one bank upload iff it was not already device-resident,
      plus every tile's payload); ``bank_fabric_bytes`` -- the
      device-to-device bytes of replicating staged arrays to the other
      shards (NOT host bandwidth; the whole bank under the replicated
      placement, only the arrivals column under sub-banks);
    * ``dev_mem_hwm_bytes`` -- engine-accounted device-memory
      high-water mark: the measured resident bank bytes plus the
      in-flight tiles' input payloads at their peak.

    Empty until the first ``run_grid`` of the process."""
    return dict(_BANK_STATS)


def _build_bank_tile_fn(sig: TileSignature) -> Callable:
    """The tile program: in-jit XLA gather from the device-resident
    bank columns, then the blocked scan ``_scan_wv`` -- the same
    program on every backend. Tiles ship only the two ``int32``
    row-index vectors.

    ``sig.bank_sub`` selects the per-shard sub-bank layout: the three
    max-plus planes arrive stacked ``(n_shards, local_rows, n_stores)``
    with the shard axis partitioned over the ``cells`` mesh, so under
    ``shard_map`` each shard's view is ``(1, local_rows, n_stores)``
    and ``[0]`` IS its local sub-bank -- the gather (wv indices are
    pre-remapped to local rows, and the scheduler put every lane in its
    owner's slot block) runs against shard-resident rows with zero
    cross-shard communication, through the SAME program as the
    replicated layout. Gathering a local row moves the identical bits
    the global gather would, so both layouts stay ``==``."""

    def run(a_bank, w_bank, v_bank, p_bank, trace_idx, wv_idx):
        global _TRACE_COUNT
        _TRACE_COUNT += 1          # runs once per trace, not per call
        if sig.bank_sub:
            # per-shard view of the shard-partitioned stacks (a no-op
            # reshape on device: axis 0 is size 1 inside shard_map, and
            # the full local plane at n_shards=1)
            w_bank, v_bank, p_bank = w_bank[0], v_bank[0], p_bank[0]
        # the gather (one row memcpy per lane + a cheap device
        # transpose) -- and NO per-tile precompute: w/v were collapsed
        # on the host, once per unique row
        a, w, v, p = _bank_gather(a_bank, w_bank, v_bank, p_bank,
                                  trace_idx, wv_idx)
        return _scan_wv(a, w, v, p, sig.chunk, sig.sb_uniform)

    if sig.n_shards > 1:
        # replicated: banks replicated (gathers stay local), indices
        # cell-sharded. sub: max-plus stacks shard-partitioned, every
        # lane scheduled onto its owner shard -- either way zero
        # cross-device communication
        run = shard_map(run, cells_mesh(sig.n_shards),
                        in_specs=(sub_bank_tile_specs() if sig.bank_sub
                                  else bank_tile_specs()),
                        out_specs=(P("cells"),) * 3)
    return jax.jit(run)


def _tile_fn(sig: TileSignature) -> Callable:
    fn = _TILE_FNS.get(sig)
    if fn is None:
        fn = _TILE_FNS.setdefault(sig, _build_bank_tile_fn(sig))
    return fn


#: Public alias of the signature-keyed tile-program cache lookup. The
#: scenario-serving daemon (``repro.core.serving``) batches queries
#: into the SAME full-size tile shapes as the streaming engine and
#: calls the programs through this entry, so steady-state serving adds
#: zero compiles beyond the signatures :func:`warm_signatures` warmed
#: (``trace_count()`` counts serve-path traces too -- tests pin it).
tile_fn = _tile_fn


@register_cache_clearer
def _clear_engine_caches() -> None:
    _TILE_FNS.clear()


# ---------------------------------------------------------------------------
# Double-buffered streaming executor
# ---------------------------------------------------------------------------

def _tile_payload_bytes(sig: TileSignature) -> int:
    """Host->device bytes of one tile: its two int32 index vectors."""
    return 8 * sig.b_pad


def _place_tile(np_args: tuple, sig: TileSignature) -> tuple:
    """Put one tile's two index vectors on the mesh, cell axis sharded.

    All callers (the streaming loop AND the compile-warming thread) go
    through here so every call of a tile program sees identically
    committed/sharded inputs -- jit specializes on input shardings, so
    a mismatch would silently compile each program twice."""
    if sig.n_shards == 1:
        return np_args
    return jax.device_put(np_args, index_shardings(cells_mesh(sig.n_shards)))


def _place_bank(bank: TraceBank, n_shards: int) -> Tuple[int, tuple]:
    """Device-resident bank columns for one mesh size: replicated over
    the ``cells`` mesh (gathers stay shard-local), plain committed
    arrays on a single device. Memoized on the bank -- one upload per
    (bank, mesh), shared by every tile and sweep of the grid.

    Replication is staged: the host arrays cross to device 0 ONCE (the
    only host->device transfer -- what ``h2d_bytes`` counts), and the
    other shards' copies are made from that committed buffer, i.e.
    device-fabric traffic (``bank_stats()['bank_fabric_bytes']``), not
    host bandwidth. Returns ``(bytes_uploaded_now, device_arrays)``."""
    if n_shards == 1:
        def place1(host: tuple) -> tuple:
            # same commitment as the memo's default path -- the hook is
            # the only addition, so shardings (and jit keys) match PR-8
            _h2d_hook(sum(int(x.nbytes) for x in host))
            return tuple(jax.numpy.asarray(x) for x in host)
        return bank.device_args(1, place1)
    mesh = cells_mesh(n_shards)

    def place(host: tuple) -> tuple:
        _h2d_hook(sum(int(x.nbytes) for x in host))
        staged = jax.device_put(host, jax.devices()[0])   # host -> dev0
        return jax.device_put(staged, bank_shardings(mesh))  # dev -> dev

    return bank.device_args(("cells", n_shards), place)


def _place_sub_bank(bank: TraceBank, n_shards: int,
                    k_replicas: int = 1) -> Tuple[int, tuple]:
    """Device-resident PER-SHARD sub-bank (``bank_partition="sub"``,
    the default): arrivals replicated as in :func:`_place_bank` (tiny
    -- ~1% of the bank's bytes -- and a lane's trace row may be owned
    by a different shard than its wv row), the three max-plus planes
    shard-partitioned via ``TraceBank.sub_bank_host`` -- ONE copy of
    each wv row fleet-wide, so resident device bytes drop to
    ~``1/n_shards`` of the replicated layout. At one shard that layout
    is the identity, so the bank's own host columns are uploaded as
    they are, with no host re-layout copy. The sub stacks
    ``device_put`` straight to their sharded layout (each device
    receives only its slice: host->device bytes stay at bank scale,
    no fabric replication); only the arrivals staging replicates.
    Memoized on the bank like :func:`_place_bank`.

    ``k_replicas > 1`` (chaos/recovery runs only) places the
    :meth:`TraceBank.sub_bank_host` Replica-set layout: each shard's
    stack carries ``k`` local-row blocks, block ``j`` holding the rows
    owned by shard ``(s - j) % n_shards`` -- single-shard loss then
    never loses a row (``chaos.replica_rebuild``). Gathers still target
    block 0, so the compiled programs only see the wider local axis."""
    if n_shards == 1:
        def place1(host: tuple) -> tuple:
            # same commitment as the memo's default path -- the hook is
            # the only addition, so shardings (and jit keys) match PR-8
            _h2d_hook(sum(int(x.nbytes) for x in host))
            return tuple(jax.numpy.asarray(x) for x in host)
        return bank.sub_device_args(1, place1, k_replicas)
    mesh = cells_mesh(n_shards)

    def place(host: tuple) -> tuple:
        _h2d_hook(sum(int(x.nbytes) for x in host))
        a = jax.device_put(host[0], jax.devices()[0])     # host -> dev0
        a = jax.device_put(a, bank_shardings(mesh)[0])    # dev -> dev
        subs = jax.device_put(tuple(host[1:]), sub_bank_shardings(mesh))
        return (a,) + tuple(subs)

    return bank.sub_device_args(n_shards, place, k_replicas)


def _measured_device_bytes(arrays: Sequence[jax.Array]) -> Tuple[int, int]:
    """Resident device bytes of ``arrays``, MEASURED from the live
    buffers: ``(total_bytes, max_bytes_on_one_device)`` summed over
    every array's addressable shards. A replicated array contributes
    one full copy per device, a shard-partitioned one only its slices
    -- so this reports what the placement actually holds, not an
    analytic ``bank x n_shards`` model (``bank_stats()`` satellite of
    the sub-bank PR; the old product over-reported sub placements
    n_shards-fold)."""
    per_dev: Dict[object, int] = {}
    for arr in arrays:
        for sh in arr.addressable_shards:
            dev = sh.device
            per_dev[dev] = per_dev.get(dev, 0) + int(sh.data.nbytes)
    if not per_dev:
        return 0, 0
    return sum(per_dev.values()), max(per_dev.values())


def warm_signatures(sigs: List[TileSignature], bank_dev: tuple) -> None:
    """Compile every distinct tile program with zero inputs (runs on the
    compile thread, so XLA compilation -- which releases the GIL --
    overlaps the first tiles' host prep and device compute; jax's
    per-program lock keeps a racing main-thread call from compiling the
    same program twice). Public: the scenario-serving daemon's warm
    pool calls it at startup against its own device-resident bank, so
    the first live query never pays a compile.

    Warming goes through a real call, so the warmed entry is the one
    the streaming loop's calls hit. Programs warm against the REAL
    device-resident bank (placed on the main thread before this runs --
    a zero bank of the right shape would hit the same program but
    duplicating the replicated placement measured slower than the
    compile it hides) with zero index vectors: row 0 is a valid gather
    everywhere, and the warm call sees exactly the shardings of the
    streaming loop's calls."""
    for sig in sigs:
        idx = (np.zeros((sig.b_pad,), np.int32),
               np.zeros((sig.b_pad,), np.int32))
        _tile_fn(sig)(*bank_dev, *_place_tile(idx, sig))


def run_grid(specs: Sequence[ScenarioSpec],
             cluster: ClusterConfig = PAPER_CLUSTER,
             n_stores: int = 50_000,
             chunk_size: Optional[int] = None,
             tile_cells: Optional[int] = None,
             n_shards: Optional[int] = None,
             bank_partition: Optional[str] = None,
             k_replicas: Optional[int] = None,
             worker_timeout_s: Optional[float] = None) -> List[SimResult]:
    """Stream a grid through the sharded tile engine.

    Results come back in ``specs`` order, bit-identical to the serial
    oracle. One device-resident columnar bank serves the grid, tiles
    ship index vectors, the tile program gathers, and only unique *scan
    lanes* (cells with distinct ``(SB, trace, max-plus row)`` triples
    -- the only inputs a timeline consumes) are scanned, with lane
    outputs scattered to member cells. ``chunk_size=None`` uses the
    :func:`auto_chunk` heuristic per SB group; ``tile_cells`` defaults
    to the :data:`DEFAULT_TILE_BYTES` budget (capped at
    :data:`DEFAULT_TILE_CELLS`), narrowed to the widest SB group's lane
    count when that fits one tile; ``n_shards`` defaults to every local
    device (1 falls back to single-device streaming -- still tiled,
    cached and double-buffered).

    ``bank_partition`` picks the bank's device layout: ``"sub"`` (the
    default) partitions the three max-plus columns into per-shard
    sub-banks -- one copy of each row fleet-wide, scan lanes scheduled
    onto their owning shard with shard-local wv indices, so resident
    bank device bytes are ~``1/n_shards`` of the replicated layout with
    the gather still shard-local -- while ``"replicated"`` holds one
    full bank per shard (the degraded-mesh recovery uses it). Both
    partitions are bit-identical: they gather the same rows.

    The loop overlaps three stages: the prefetch thread derives tile
    k+1's host payload while tile k's is placed cell-sharded on the
    mesh and its (asynchronously dispatched) scan runs. Dispatch runs
    ahead of the devices by at most :data:`MAX_IN_FLIGHT_TILES` tiles:
    past that the loop drains the oldest tile (blocking until its
    compute finishes and releasing its input buffers), which -- with
    the bank resident -- caps live memory at the bank plus a few tile
    payloads however large the grid is. :func:`bank_stats` reports the
    run's H2D / memory accounting (measured from the live buffers).

    **Resilience** (docs/resilience.md). ``k_replicas`` widens the
    sub-bank placement with the paper's Replica set (default: 2 under
    an active ``chaos.inject`` scope, else 1 -- the exact PR-8
    layout); ``worker_timeout_s`` bounds how long the dispatch loop
    waits on a silent prefetch worker before raising
    :class:`EngineWorkerError`. Under an active chaos scope the loop
    detects injected shard loss / corrupt rows / upload faults and
    recovers in place: in-flight tiles are cancelled, the lost shard's
    rows are rebuilt from the surviving replica block (or the bank's
    Logging-Unit journal), digest-verified against the host truth, and
    the bank is re-placed -- same shapes and shardings, so the
    spare-replacement path adds ZERO compiles and the recovered run's
    results stay bit-identical (tests/test_chaos.py pins ``==``).
    ``ChaosConfig(recovery="degraded")`` instead finishes the
    unfinished cells on a mesh shrunk by one shard with the bank
    replicated (one recompile, kept serving).
    """
    with _tm.span("engine/run", cells=len(specs)):
        return _run_grid(specs, cluster, n_stores, chunk_size, tile_cells,
                         n_shards, bank_partition, k_replicas,
                         worker_timeout_s)


def _run_grid(specs: Sequence[ScenarioSpec], cluster: ClusterConfig,
              n_stores: int, chunk_size: Optional[int],
              tile_cells: Optional[int], n_shards: Optional[int],
              bank_partition: Optional[str], k_replicas: Optional[int],
              worker_timeout_s: Optional[float]) -> List[SimResult]:
    """:func:`run_grid`'s body, inside its ``engine/run`` span."""
    if not specs:
        return []
    if chunk_size is not None and chunk_size < 1:
        raise ValueError(
            f"chunk_size must be >= 1 (or None for auto), got {chunk_size}")
    partition = bank_partition or "sub"
    if partition not in ("sub", "replicated"):
        raise ValueError(f"unknown bank_partition {bank_partition!r}")
    if k_replicas is not None and k_replicas != 1 and partition != "sub":
        raise ValueError("k_replicas > 1 applies to the sub-partitioned "
                         f"bank only (got partition={partition!r})")
    n_dev = len(jax.devices())
    if n_shards is None:
        # all local devices: even oversubscribed virtual CPU devices
        # measured faster than matching the physical core count (each
        # shard's scan body is single-threaded in XLA; more shards =
        # more concurrent executions for the host threadpool to fill)
        n_shards = n_dev
    if not 1 <= n_shards <= n_dev:
        raise ValueError(f"n_shards must be in [1, {n_dev}], got {n_shards}")

    bank = bank_dev = None
    bank_fresh = 0
    k_eff = 1
    local_rows = 0
    lane_members: List[List[int]] = []
    results: List[Optional[SimResult]] = [None] * len(specs)

    with _tm.span("engine/plan"):
        for s in specs:
            s.validate(cluster)
        # --- scan-lane dedup -------------------------------------------
        # A cell's timeline consumes exactly (arrivals row, max-plus row,
        # SB depth) -- nothing else. Cells sharing that triple (e.g. the
        # whole CN axis of a sweep, or WB/WT cells across replication
        # knobs) therefore have bit-identical timelines: the engine scans
        # each unique LANE once and scatters the lane outputs to every
        # member cell (work_scale and the bandwidth / log metrics are
        # per-cell host math in ``_finish_result``). The mega-grid's
        # 12 960 cells collapse to ~2 700 scanned lanes.
        lane_of: Dict[tuple, int] = {}
        lane_specs: List[ScenarioSpec] = []
        lane_wv_keys: List[tuple] = []
        for i, s in enumerate(specs):
            sb = s.sb_size if s.sb_size is not None else cluster.store_buffer
            key = (sb,) + _plane_keys(s, cluster)
            j = lane_of.setdefault(key, len(lane_specs))
            if j == len(lane_specs):
                lane_specs.append(s)
                lane_wv_keys.append(key[2])
                lane_members.append([i])
            else:
                lane_members[j].append(i)
        # the bank's SHAPE comes from a cheap key pass, so the tile
        # signatures -- and therefore compile warming -- do not wait for
        # the heavy row materialization below
        trace_map, wv_map = bank_row_maps(specs, cluster)
        sub = partition == "sub"
        if sub:
            # per-shard sub-banks: the signature carries the LOCAL
            # (per-shard) wv row count, and the scheduler places each
            # lane in the slot block of the shard owning its wv row.
            # k_eff > 1 (chaos/recovery runs only) appends the Replica
            # set blocks along the local axis -- the signature sees the
            # widened stack (jit specializes on the bank shape), while
            # indices keep targeting the primary block
            k_eff = _chaos.resolve_k_replicas(k_replicas, n_shards)
            local_rows = sub_bank_rows(len(wv_map), n_shards)
            shape = (len(trace_map), k_eff * local_rows)
            owners = [wv_map[wk] % n_shards for wk in lane_wv_keys]
        else:
            shape = (len(trace_map), len(wv_map))
            owners = None
        width = tile_cells
        if width is None:
            # a grid whose widest SB group (per owning shard) fits one
            # default tile pads only to that width: a small grid scans,
            # and picks its chunk for, its own lanes, not a full tile
            per_group: Dict[tuple, int] = {}
            for j, key in enumerate(lane_of):
                g = (key[0], owners[j] if owners else 0)
                per_group[g] = per_group.get(g, 0) + 1
            width = min(_default_tile_cells(n_stores),
                        max(per_group.values()) * (n_shards if owners
                                                   else 1))
        tiles = [dataclasses.replace(
            t, sig=dataclasses.replace(t.sig, bank_shape=shape,
                                       bank_sub=sub))
            for t in plan_tiles(
                lane_specs, cluster=cluster, n_stores=n_stores,
                chunk_size=chunk_size, tile_cells=width,
                n_shards=n_shards, owners=owners)]
    _tm.count("engine/cells", len(specs))
    _tm.count("engine/lanes", len(lane_members))
    _tm.count("engine/tiles", len(tiles))
    h2d_bytes = sum(_tile_payload_bytes(t.sig) for t in tiles)
    live_bytes = 0
    hwm_bytes = 0
    fabric_bytes = 0
    bank_dev_total = bank_dev_per = 0

    def prep(tile: Tile):
        """Tile prep (prefetch thread): the two padded int32
        row-index vectors, plus per-MEMBER-cell result scalars grouped
        by lane (the scatter targets -- ``_cell_scalars`` builds no
        per-store array: the bank's rows already hold them).
        Sub-banked tiles remap wv rows to their SHARD-LOCAL index
        (``row // n_shards``) and scatter each lane into its
        :attr:`Tile.slots` position; unfilled slots stay 0 -- trace
        row 0 and local row 0 are valid gather targets on every shard
        (sub-banks are padded to at least one row), and padding
        outputs are discarded."""
        trace_idx = np.zeros(tile.sig.b_pad, np.int32)
        wv_idx = np.zeros(tile.sig.b_pad, np.int32)
        slots = tile.slots if tile.slots is not None \
            else range(len(tile.specs))
        wv_div = n_shards if tile.sig.bank_sub else 1
        for s, pos in zip(tile.specs, slots):
            tr, wr = bank.rows_for(s)
            trace_idx[pos] = tr
            wv_idx[pos] = wr // wv_div
        groups = [[(i, _cell_scalars(specs[i], n_stores, cluster))
                   for i in lane_members[lane]]
                  for lane in tile.indices]
        return groups, (trace_idx, wv_idx)

    def finish(entry) -> None:
        """Drain one dispatched tile: blocks until its device compute is
        done, releasing its input buffers, and scatters each lane's
        outputs back to its member cells' original grid positions
        (through :attr:`Tile.slots` when the sub-bank scheduler placed
        lanes in shard-owner blocks). Marks the tile done -- the
        recovery loop re-dispatches exactly the tiles that never
        drained."""
        nonlocal live_bytes
        kt, tile, groups, (exec_ns, at_head, sb_full) = entry
        with _tm.span("tile/drain", tile=kt):
            # blocks on the device compute + ships the outputs back
            exec_ns = np.asarray(exec_ns)
            at_head = np.asarray(at_head)
            sb_full = np.asarray(sb_full)
        live_bytes -= _tile_payload_bytes(tile.sig)
        slots = tile.slots if tile.slots is not None \
            else range(len(tile.indices))
        with _tm.span("tile/finish", tile=kt):
            for group, pos in zip(groups, slots):
                for i, cell in group:
                    meta = {"engine": ("sharded" if tile.sig.n_shards > 1
                                       else "streamed"),
                            "chunk": tile.sig.chunk,
                            "auto_chunk": chunk_size is None,
                            "tile_cells": tile.sig.b_pad,
                            "n_shards": tile.sig.n_shards,
                            "data_plane": "bank",
                            "bank_partition": partition,
                            "bank_rows": bank.n_rows,
                            "h2d_bytes": h2d_bytes,
                            "bank_fabric_bytes": fabric_bytes}
                    results[i] = _finish_result(cell, exec_ns[pos],
                                                int(at_head[pos]),
                                                int(sb_full[pos]), meta=meta)
        done[kt] = True

    # --- resilience plumbing (inert without an active chaos scope) -----
    st = _chaos.active()

    def prep_guarded(tile: Tile, no: int):
        """Prefetch-thread unit of work: heartbeat + chaos kill point +
        context-wrapping -- a poisoned tile surfaces as an
        :class:`EngineWorkerError` naming the tile, not as an opaque
        error tiles later."""
        _HEARTBEATS["prefetch"] = time.monotonic()
        if st is not None:
            st.on_thread("prefetch")
        try:
            with _tm.span("tile/prep", tile=no):
                return prep(tile)
        except ChaosError:
            raise
        except Exception as e:
            raise EngineWorkerError("prefetch", no, tile.sig,
                                    repr(e)) from e

    def warm_guarded():
        _HEARTBEATS["compile-warm"] = time.monotonic()
        if st is not None:
            st.on_thread("warm")
        try:
            with _tm.span("compile/warm", signatures=len(sigs)):
                warm_signatures(sigs, bank_dev)
        except ChaosError:
            raise
        except Exception as e:
            raise EngineWorkerError("compile-warm", None,
                                    sigs[0] if sigs else None,
                                    repr(e)) from e

    def wait_prep(fut, no: int, sig: TileSignature):
        """Prefetch result with a stall bound: ``worker_timeout_s``
        turns a silently wedged worker into a prompt, attributed
        :class:`EngineWorkerError` instead of a hang."""
        if worker_timeout_s is None:
            return fut.result()
        deadline = time.monotonic() + worker_timeout_s
        while True:
            _futures_wait([fut], timeout=min(0.05, worker_timeout_s))
            if fut.done():
                return fut.result()
            if time.monotonic() > deadline:
                raise EngineWorkerError(
                    "prefetch", no, sig,
                    f"no result within worker_timeout_s={worker_timeout_s}")

    def check_warm() -> None:
        """Surface compile-thread failures promptly (each dispatch
        iteration), respawning the warm worker if chaos killed it --
        compiles then happen lazily on first call, which is slower but
        correct."""
        nonlocal warm
        if warm.done() and warm.exception() is not None:
            if isinstance(warm.exception(), ThreadDeathError):
                warm = compile_pool.submit(warm_guarded)
            else:
                raise warm.exception()

    def verify_tile(tile: Tile) -> None:
        """Gather-path integrity sampling: CRC-check (a sample of) the
        tile's wv rows against the host truth before dispatch. Chaos
        verification runs only -- the production path never reads
        device rows back."""
        if st is None or not st.wants_verify():
            return
        rows = sorted({bank.rows_for(sp)[1] for sp in tile.specs})
        _chaos.verify_rows(bank, bank_dev, rows[:VERIFY_ROWS_PER_TILE],
                           n_shards=n_shards if sub else 1,
                           local_cap=local_rows if sub else 0,
                           where="tile gather sample")

    def bank_place_key():
        if sub:
            return ("sub", n_shards) if k_eff == 1 \
                else ("sub", n_shards, k_eff)
        return 1 if n_shards == 1 else ("cells", n_shards)

    def place_bank_now() -> None:
        nonlocal bank_fresh, bank_dev, fabric_bytes, h2d_bytes
        nonlocal bank_dev_total, bank_dev_per
        with _tm.span("bank/place", rows=bank.n_rows):
            _place_bank_body()

    def _place_bank_body() -> None:
        nonlocal bank_fresh, bank_dev, fabric_bytes, h2d_bytes
        nonlocal bank_dev_total, bank_dev_per
        if sub:
            bank_fresh, bank_dev = _retried(
                lambda: _place_sub_bank(bank, n_shards, k_eff),
                "bank placement")
            # only the replicated arrivals staging crosses the
            # device fabric; the partitioned max-plus stacks ship
            # each shard's slice straight from the host
            fabric_bytes += (bank.arrivals.nbytes * (n_shards - 1)
                             if bank_fresh else 0)
        else:
            bank_fresh, bank_dev = _retried(
                lambda: _place_bank(bank, n_shards), "bank placement")
            fabric_bytes += (bank.nbytes * (n_shards - 1)
                             if bank_fresh else 0)
        h2d_bytes += bank_fresh
        bank_dev_total, bank_dev_per = _measured_device_bytes(bank_dev)

    def recover(err: Exception) -> None:
        """Spare-replacement recovery: rebuild the lost rows from the
        surviving replica block (or the Logging-Unit journal),
        digest-verify the rebuild against the host truth, drop the
        stale placement and re-place -- same shapes and shardings, so
        every compiled program still hits (the 0-recompile invariant
        tests/test_chaos.py pins)."""
        nonlocal bank_dev
        t0 = time.monotonic()
        lost = err.shard if isinstance(err, ShardLossError) else None
        if lost is not None:
            # spare replacement: the mesh shape is unchanged (a spare
            # takes the lost shard's coordinates) -- validate via the
            # elastic-scaling policy it shares with the trainer tier
            from repro.distributed.elastic import cells_spare_replacement
            cells_spare_replacement(n_shards, lost)
        source = "host"
        if sub and lost is not None:
            with _tm.span("recover/rebuild", shard=lost):
                if k_eff >= 2:
                    rebuilt = _chaos.replica_rebuild(
                        bank_dev, lost, n_shards=n_shards,
                        k_replicas=k_eff, local_cap=local_rows,
                        wv_rows=bank.wv_rows)
                    source = "replica"
                elif bank.journal_enabled:
                    rebuilt = _chaos.journal_rebuild(bank, lost, n_shards)
                    source = "journal"
                else:
                    rebuilt = None
                    source = "host"
                if rebuilt is not None:
                    _chaos.verify_rebuild(bank, rebuilt, lost, n_shards)
        with _tm.span("recover/replace", source=source):
            bank.drop_placement(bank_place_key())
            place_bank_now()
        if st is not None:
            st.note_recovery(source, (time.monotonic() - t0) * 1e3,
                             lost, "spare")

    in_flight: List[tuple] = []
    done = [False] * len(tiles)
    recover_attempts = 0
    redispatch_pending = False
    degraded_from: Optional[int] = None
    prep_pool = ThreadPoolExecutor(max_workers=1)
    compile_pool = ThreadPoolExecutor(max_workers=1)
    try:
        # materialize + upload the bank before warming: the warm calls
        # (and every tile call) gather from the one resident placement,
        # and compilation overlaps the first tiles' loop
        bank = get_trace_bank(specs, n_stores, cluster)
        place_bank_now()
        if st is not None:
            # chaos row corruption lands on the DEVICE copy only (the
            # host columns stay the truth the CRC digests and rebuilds
            # verify against)
            bank_dev = st.tamper_bank(
                bank_dev, n_shards=n_shards,
                k_replicas=k_eff if sub else 1,
                local_cap=local_rows if sub else 0,
                wv_rows=bank.wv_rows)
        live_bytes = hwm_bytes = bank_dev_total
        # per-trace result scalars the tiles derive, past the bank
        # build (which counts its own rows)
        scalars0 = _TRACE_SCALAR_CACHE.misses
        sigs = list(dict.fromkeys(t.sig for t in tiles))
        warm = compile_pool.submit(warm_guarded)
        while not all(done):
            pending = [k for k, d in enumerate(done) if not d]
            try:
                fut = prep_pool.submit(prep_guarded, tiles[pending[0]],
                                       pending[0])
                for pi, kt in enumerate(pending):
                    tile = tiles[kt]
                    try:
                        groups, np_args = wait_prep(fut, kt, tile.sig)
                    except ThreadDeathError:
                        # prefetch worker killed mid-grid: rebuild this
                        # tile inline on the caller thread and keep
                        # streaming (the injected death was confined to
                        # the future; later submits run normally)
                        groups, np_args = prep(tile)
                    if pi + 1 < len(pending):
                        nxt = pending[pi + 1]
                        fut = prep_pool.submit(prep_guarded, tiles[nxt],
                                               nxt)
                    check_warm()
                    verify_tile(tile)

                    def place_dispatch(args=np_args, sig=tile.sig):
                        _h2d_hook(_tile_payload_bytes(sig))
                        return _place_tile(args, sig)

                    with _tm.span("tile/h2d", tile=kt):
                        placed = _retried(place_dispatch,
                                          f"tile {kt} placement")
                    if st is not None:
                        st.on_dispatch(f"tile {kt}")
                    # first dispatch after a recovery is the timeline's
                    # re-dispatch leg; name its span accordingly
                    dispatch_span = ("recover/redispatch"
                                     if redispatch_pending
                                     else "tile/dispatch")
                    redispatch_pending = False
                    with _tm.span(dispatch_span, tile=kt):
                        out = _tile_fn(tile.sig)(*bank_dev, *placed)
                    in_flight.append((kt, tile, groups, out))
                    _tm.gauge("engine/in_flight_tiles",
                              len(in_flight))
                    _tm.gauge("engine/prefetch_queue_depth",
                              len(pending) - pi - 1)
                    live_bytes += _tile_payload_bytes(tile.sig)
                    hwm_bytes = max(hwm_bytes, live_bytes)
                    # backpressure: dispatch runs ahead of the devices,
                    # so without a bound every dispatched tile's input
                    # buffers stay alive at once; draining the oldest
                    # keeps at most MAX_IN_FLIGHT_TILES tiles of device
                    # memory pinned (plus the resident bank) while
                    # still overlapping prep/compute/drain
                    if len(in_flight) >= MAX_IN_FLIGHT_TILES:
                        finish(in_flight.pop(0))
                while in_flight:
                    finish(in_flight.pop(0))
            except (ShardLossError, IntegrityError) as e:
                with _tm.span("recover", error=type(e).__name__):
                    with _tm.span("recover/detect",
                                  error=type(e).__name__):
                        _tm.count("chaos/faults_detected")
                    # cancel in-flight tiles: their outputs may involve
                    # the lost/corrupt placement, and their tiles
                    # re-dispatch (done[] is only set by finish)
                    with _tm.span("recover/rollback",
                                  tiles=len(in_flight)):
                        for (_kt, t_, _g, _o) in in_flight:
                            live_bytes -= _tile_payload_bytes(t_.sig)
                        in_flight.clear()
                    recover_attempts += 1
                    if st is None or recover_attempts > MAX_RECOVERIES:
                        raise
                    if (isinstance(e, ShardLossError) and n_shards > 1
                            and st.cfg.recovery == "degraded"):
                        degraded_from = e.shard
                        break
                    recover(e)
                redispatch_pending = True
        if degraded_from is None:
            try:
                warm.result()  # surface compile-thread exceptions
            except ThreadDeathError:
                pass           # injected kill, already respawned/absorbed
    finally:
        prep_pool.shutdown(wait=True)
        compile_pool.shutdown(wait=True)
    _tm.count("engine/result_scalars_built",
              _TRACE_SCALAR_CACHE.misses - scalars0)

    if degraded_from is not None:
        # degraded-mesh fallback: finish the unfinished cells on a mesh
        # shrunk by the lost shard with the bank replicated -- ONE
        # recompile set, but no spare needed (elastic.py's shrink
        # semantics; the spare path above is the default)
        from repro.distributed.elastic import cells_degraded_shards
        t0 = time.monotonic()
        left = [i for i, r in enumerate(results) if r is None]
        sub_res = run_grid([specs[i] for i in left], cluster=cluster,
                           n_stores=n_stores, chunk_size=chunk_size,
                           tile_cells=tile_cells,
                           n_shards=cells_degraded_shards(n_shards),
                           bank_partition="replicated")
        for i, r in zip(left, sub_res):
            results[i] = r
        if st is not None:
            st.note_recovery("degraded-mesh",
                             (time.monotonic() - t0) * 1e3,
                             degraded_from, "degraded")

    _BANK_STATS.clear()
    _BANK_STATS.update({
        "cells": len(specs), "n_shards": n_shards,
        "bank_partition": partition,
        "scan_lanes": len(lane_members),
        "trace_rows": bank.trace_rows,
        "wv_rows": bank.wv_rows,
        "bank_rows": bank.n_rows,
        "bank_bytes": bank.nbytes,
        "bank_dev_bytes_per_shard": bank_dev_per,
        "bank_dev_bytes": bank_dev_total,
        "h2d_bytes": h2d_bytes,
        "bank_fabric_bytes": fabric_bytes,
        "dev_mem_hwm_bytes": hwm_bytes,
        "k_replicas": k_eff,
        "degraded": degraded_from is not None,
        "chaos": st.report() if st is not None else None,
    })
    rec = _tm.active()
    if rec is not None:
        # one merged per-run summary, shared (by reference) between
        # bank_stats() and every cell's meta -- the summarized dict the
        # flight recorder exports alongside the Chrome trace
        summ = rec.summary()
        _BANK_STATS["telemetry"] = summ
        for r in results:
            if r is not None and r.meta is not None:
                r.meta.setdefault("telemetry", summ)
    return results

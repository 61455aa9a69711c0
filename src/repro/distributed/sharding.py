"""Logical-axis sharding rules.

Parameters: FSDP over (``pod``, ``data``) x tensor-parallel over ``model``
(2-D sharded weights). The rules are keyed on parameter path + shape and
handle the awkward cases explicitly:

* GQA KV projections whose head count does not divide the model axis
  (deepseek kv=8, starcoder2 kv=4, ...) fall back to FSDP-only storage --
  still fully sharded in HBM, all-gathered just-in-time by GSPMD.
* hymba's 25 attention heads do not divide 16; its attention weights are
  FSDP-only while its SSD branch (d_inner % 16 == 0) stays
  tensor-parallel.
* MoE expert stacks match the ``shard_map`` specs in models/moe.py
  (EP when n_experts % model == 0, ff-sliced TP otherwise).

Activations: batch over (``pod``, ``data``); the TP-sharded dim (heads /
ff / vocab) over ``model``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import MeshConfig, ModelConfig
from repro.distributed.context import MeshContext, get_mesh_context


# ---------------------------------------------------------------------------
# Activation constraints (no-ops without a mesh context)
# ---------------------------------------------------------------------------

# "batch": residual stream sharded over (pod, data) only -- the baseline,
#   which makes GSPMD emit Megatron-style per-layer all-reduces of the
#   full residual for TP partial sums.
# "seq_model": additionally shard the sequence dim over `model` between
#   blocks (Megatron sequence parallelism): the TP partial-sum all-reduce
#   becomes reduce-scatter(+ all-gather before the next block's matmuls),
#   halving collective bytes and sharding the norm compute. A beyond-paper
#   perf knob recorded in EXPERIMENTS.md SSPerf.
_ACTIVATION_POLICY = "batch"


def set_activation_policy(policy: str) -> None:
    global _ACTIVATION_POLICY
    if policy not in ("batch", "seq_model"):
        raise ValueError(policy)
    _ACTIVATION_POLICY = policy


def get_activation_policy() -> str:
    return _ACTIVATION_POLICY


def constrain_batch(x: jax.Array) -> jax.Array:
    """(B, S, d) or (B, S): shard batch over (pod, data); under the
    seq_model policy 3-D activations also shard S over `model`."""
    ctx = get_mesh_context()
    if ctx is None:
        return x
    if (_ACTIVATION_POLICY == "seq_model" and x.ndim == 3
            and ctx.model_axis is not None
            and x.shape[1] % ctx.model_size == 0):
        spec = P(ctx.batch_axes, ctx.model_axis, None)
    else:
        spec = P(ctx.batch_axes, *([None] * (x.ndim - 1)))
    spec = sanitize_spec(spec, x.shape, ctx.mesh)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


def constrain_gathered(x: jax.Array) -> jax.Array:
    """(B, S, d): force the sequence dim UNSHARDED (batch-only sharding).

    Under sequence parallelism the residual stream lives seq-sharded
    between blocks; calling this once on the post-norm activation makes
    GSPMD emit a single all-gather per block instead of one per
    projection matmul (the Megatron-SP gather point)."""
    ctx = get_mesh_context()
    if ctx is None or _ACTIVATION_POLICY != "seq_model":
        return x
    spec = sanitize_spec(P(ctx.batch_axes, *([None] * (x.ndim - 1))),
                         x.shape, ctx.mesh)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


def constrain_logits(x: jax.Array) -> jax.Array:
    """(B, S, V): batch over (pod, data), vocab over model."""
    ctx = get_mesh_context()
    if ctx is None or ctx.model_axis is None:
        return x
    spec = P(ctx.batch_axes, None, ctx.model_axis)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


def constrain_heads(x: jax.Array) -> jax.Array:
    """(B, S, H, hd): heads over model when divisible."""
    ctx = get_mesh_context()
    if ctx is None or ctx.model_axis is None:
        return x
    if x.shape[2] % ctx.model_size != 0:
        return constrain_batch(x)
    spec = P(ctx.batch_axes, None, ctx.model_axis, None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


# ---------------------------------------------------------------------------
# Parameter sharding rules
# ---------------------------------------------------------------------------

def _path_str(path: Tuple[Any, ...]) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _leaf_spec(path: str, leaf: jax.Array, cfg: ModelConfig,
               n_model: int, fsdp: Tuple[str, ...],
               model_ax: Optional[str], stacked: bool) -> P:
    """PartitionSpec for one parameter leaf (without the layer-stack dim)."""
    name = path.split("/")[-1]
    ndim = leaf.ndim - (1 if stacked else 0)
    m = model_ax

    def spec(*dims):
        return P(*( [None] + list(dims) if stacked else list(dims) ))

    if ndim <= 1:
        return spec(*([None] * ndim))             # scales/biases replicated

    # --- embeddings -------------------------------------------------------
    if name == "tok":
        return spec(m, fsdp)                      # vocab TP, d FSDP
    if name == "out":
        return spec(fsdp, m)

    # --- MoE expert stacks (E, d, ff) / (E, ff, d) -------------------------
    if path.endswith("moe/w_gate") or path.endswith("moe/w_up"):
        if m and cfg.n_experts % n_model == 0 and cfg.n_experts >= n_model:
            return spec(m, None, fsdp)
        return spec(None, None, ((m,) if m else ()) + fsdp)
    if path.endswith("moe/w_down"):
        if m and cfg.n_experts % n_model == 0 and cfg.n_experts >= n_model:
            return spec(m, fsdp, None)
        return spec(None, ((m,) if m else ()) + fsdp, None)
    if path.endswith("moe/router"):
        return spec(None, None)
    if "moe/shared" in path:
        if name == "w_down":
            return spec(((m,) if m else ()) + fsdp, None)
        return spec(None, ((m,) if m else ()) + fsdp)

    # --- attention ---------------------------------------------------------
    heads_tp = m is not None and cfg.n_heads % n_model == 0
    kv_tp = m is not None and cfg.n_kv_heads % n_model == 0
    if name == "wq":
        return spec(fsdp, m if heads_tp else None)
    if name in ("wk", "wv"):
        return spec(fsdp, m if kv_tp else None)
    if name == "wo":
        return spec(m, fsdp) if heads_tp else spec(fsdp, None)

    # --- SSD mixer ----------------------------------------------------------
    ssm_tp = m is not None and cfg.ssm_state > 0 and cfg.d_inner % n_model == 0
    if name in ("w_z", "w_x"):
        return spec(fsdp, m if ssm_tp else None)
    if name in ("w_B", "w_C", "w_dt"):
        return spec(fsdp, None)
    if name == "out_proj":
        return spec(m, fsdp) if ssm_tp else spec(fsdp, None)
    if name.startswith("conv_w"):
        return spec(None, m if (ssm_tp and name == "conv_wx") else None)

    # --- dense MLP -----------------------------------------------------------
    ff_tp = m is not None and (cfg.d_ff % n_model == 0) and cfg.d_ff > 0
    if name in ("w_gate", "w_up"):
        return spec(fsdp, m if ff_tp else None)
    if name == "w_down":
        return spec(m, fsdp) if ff_tp else spec(fsdp, None)

    # default: FSDP the largest dim
    dims = [None] * ndim
    dims[0] = fsdp
    return spec(*dims)


def sanitize_spec(spec: P, shape: Tuple[int, ...],
                  mesh: jax.sharding.Mesh) -> P:
    """Reduce sharding on dims the mesh axes do not divide evenly.

    jit input/output shardings require even divisibility (uneven sharding
    only works for in-jit constraints). For tuple entries the longest
    dividing *prefix* is kept (axes are ordered most-important-first by
    the rules), e.g. moonshot's shared-expert ff of 2816 cannot go over
    (model, pod, data) = 512 ways but keeps (model, pod) = 32.
    """
    import numpy as _np
    dims = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for d, ax in enumerate(dims):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        chosen = None
        for k in range(len(axes), 0, -1):
            n = int(_np.prod([mesh.shape[a] for a in axes[:k]]))
            if shape[d] % n == 0:
                chosen = axes[:k] if k > 1 else axes[0]
                break
        out.append(chosen)
    return P(*out)


def param_specs(params: Any, cfg: ModelConfig,
                ctx: Optional[MeshContext] = None) -> Any:
    """PartitionSpec pytree matching ``params``.

    Leaves under a ``layers`` subtree are treated as layer-stacked (leading
    L dim replicated).
    """
    ctx = ctx or get_mesh_context()
    if ctx is None:
        raise ValueError("param_specs requires a mesh context")
    fsdp = ctx.fsdp_axes
    n_model = ctx.model_size
    model_ax = ctx.model_axis

    def rule(path, leaf):
        ps = _path_str(path)
        stacked = "layers" in ps.split("/") or "enc_layers" in ps.split("/")
        spec = _leaf_spec(ps, leaf, cfg, n_model, fsdp, model_ax, stacked)
        return sanitize_spec(spec, leaf.shape, ctx.mesh)

    return jax.tree_util.tree_map_with_path(rule, params)


def named_shardings(params: Any, cfg: ModelConfig,
                    ctx: Optional[MeshContext] = None) -> Any:
    ctx = ctx or get_mesh_context()
    specs = param_specs(params, cfg, ctx)
    return jax.tree.map(lambda s: NamedSharding(ctx.mesh, s), specs)


# ---------------------------------------------------------------------------
# Serving-cache sharding rules
# ---------------------------------------------------------------------------

def cache_specs(cache: Any, cfg: ModelConfig,
                ctx: Optional[MeshContext] = None) -> Any:
    """PartitionSpecs for KV / SSM caches.

    * k/v/cross_k/cross_v: (L, B, S, K_heads, hd) -- batch over
      (pod, data); KV heads over ``model`` when divisible.
    * conv: (L, B, K-1, ch); ssd: (L, B, H, P, N) -- batch sharded, the
      channel/head dim over ``model`` when divisible.
    * length: replicated scalar.
    """
    ctx = ctx or get_mesh_context()
    if ctx is None:
        raise ValueError("cache_specs requires a mesh context")
    m, nm, batch = ctx.model_axis, ctx.model_size, ctx.batch_axes

    import numpy as _np
    nb = int(_np.prod([ctx.mesh.shape[a] for a in batch]))

    def rule(path, leaf):
        name = _path_str(path).split("/")[-1]
        if leaf.ndim == 0:
            return P()
        if name in ("k", "v", "cross_k", "cross_v"):
            heads = leaf.shape[-2]
            htp = m if (m and heads % nm == 0) else None
            if leaf.shape[1] % nb == 0:
                spec = P(None, batch, None, htp, None)
            else:
                # batch too small (long_500k, B=1): shard the sequence dim
                spec = P(None, None, batch, htp, None)
        elif name == "conv":
            ch = leaf.shape[-1]
            ctp = m if (m and ch % nm == 0) else None
            spec = P(None, batch, None, ctp)
        elif name == "ssd":
            h = leaf.shape[2]
            htp = m if (m and h % nm == 0) else None
            spec = P(None, batch, htp, None, None)
        else:
            # tokens / misc: batch-sharded on dim 0
            spec = P(batch, *([None] * (leaf.ndim - 1)))
        return sanitize_spec(spec, leaf.shape, ctx.mesh)

    return jax.tree_util.tree_map_with_path(rule, cache)


# ---------------------------------------------------------------------------
# Protocol-simulator tile sharding (cells axis of the streaming engine)
# ---------------------------------------------------------------------------

#: Replicated bank (``bank_partition="replicated"``, the degraded-mesh
#: recovery's layout): the four store-contiguous ``(rows, n_stores)``
#: trace-bank arrays are REPLICATED across the ``cells`` mesh -- any
#: shard's cells may gather any row, and a replicated bank keeps the
#: in-kernel gather local (sharding the row axis would force
#: collectives and break the engine's zero-communication contract).
#: The per-lane ``int32`` row-index vectors are the only cell-sharded
#: tile inputs, placed explicitly (``index_shardings``) so the
#: streaming loop overlaps tile k+1's upload with tile k's compute.
BANK_COLUMN_SPEC = P(None, None)
TILE_INDEX_SPEC = P("cells")


def bank_tile_specs() -> Tuple[P, ...]:
    """In PartitionSpecs for a banked tile program: 4 replicated bank
    columns, then the 2 cell-sharded row-index vectors."""
    return (BANK_COLUMN_SPEC,) * 4 + (TILE_INDEX_SPEC,) * 2


def bank_shardings(mesh: jax.sharding.Mesh) -> Tuple[NamedSharding, ...]:
    """NamedShardings replicating the 4 bank columns over ``mesh`` (one
    explicit ``device_put`` per mega-grid -- the bank is device-resident
    across every tile that gathers from it)."""
    return (NamedSharding(mesh, BANK_COLUMN_SPEC),) * 4


#: Per-shard sub-bank plane (``bank_partition="sub"``, the default): the
#: three max-plus columns are stacked ``(n_shards, local_rows,
#: n_stores)`` with the SHARD axis cell-sharded -- one copy of each wv
#: row fleet-wide instead of one per shard. Global wv row ``r`` lives in
#: stack entry ``r % n_shards`` at local row ``r // n_shards``, and the
#: tile scheduler places every scan lane in its owning shard's slot
#: block, so the in-jit gather (with LOCAL indices) never leaves the
#: shard: still zero cross-device communication on the scan path. The
#: tiny arrivals plane stays replicated (``BANK_COLUMN_SPEC``): a lane's
#: trace row and wv row can be owned by different shards, and arrivals
#: are ~1% of the bank's bytes -- partitioning them would buy nothing
#: and force a second ownership constraint on the scheduler.
#:
#: **Replicated sub-banks** (``k_replicas > 1``, resolved by
#: :func:`repro.core.chaos.resolve_k_replicas`): the local axis grows to
#: ``k * local_rows`` and block ``j`` of shard ``s`` holds the rows OWNED
#: by shard ``(s - j) % n_shards`` -- ReCXL-style Logging Units, so wv
#: row ``r`` is resident on its owner ``r % n`` (block 0) and on the
#: next shard over (block 1), and losing any single shard leaves a full
#: replica of its rows one hop away for
#: :func:`repro.core.chaos.replica_rebuild`. The gather path always
#: indexes block 0, so the tile programs, their signatures, and the
#: scan-lane scheduler are IDENTICAL at every ``k`` -- replication costs
#: bytes (reported by ``bank_stats()["sub_bank_bytes"]``), never
#: compiles; this same spec shards the wider stack unchanged.
SUB_BANK_SPEC = P("cells", None, None)


def sub_bank_tile_specs() -> Tuple[P, ...]:
    """In PartitionSpecs for a sub-banked tile program: the replicated
    arrivals column, 3 shard-partitioned sub-bank stacks, then the 2
    cell-sharded row-index vectors (trace indices global, wv indices
    shard-local)."""
    return (BANK_COLUMN_SPEC,) + (SUB_BANK_SPEC,) * 3 + (TILE_INDEX_SPEC,) * 2


def sub_bank_shardings(mesh: jax.sharding.Mesh) -> Tuple[NamedSharding, ...]:
    """NamedShardings partitioning the 3 sub-bank stacks over ``mesh``
    (shard axis 0 over ``cells``: ``device_put`` slices the host stack
    per device, so upload bytes are the bank's, not bank x shards --
    times ``k_replicas`` when the chaos tier stacks replica blocks on
    the local axis; the sharding itself is k-agnostic)."""
    return (NamedSharding(mesh, SUB_BANK_SPEC),) * 3


def index_shardings(mesh: jax.sharding.Mesh) -> Tuple[NamedSharding, ...]:
    """NamedShardings for one banked tile's (trace_idx, wv_idx)."""
    return (NamedSharding(mesh, TILE_INDEX_SPEC),) * 2


def batch_specs(batch: Any, ctx: Optional[MeshContext] = None) -> Any:
    ctx = ctx or get_mesh_context()
    return jax.tree.map(
        lambda x: sanitize_spec(
            P(ctx.batch_axes, *([None] * (x.ndim - 1))), x.shape, ctx.mesh),
        batch)

"""The banked tile programs compile for a TPU v5e at their real sizes.

Each test lowers a tile program built by ``engine._build_bank_tile_fn``
for a v5e topology that is described, not attached, and compiles it
with the TPU compiler: the mega-grid's two store-buffer signatures at
50 000 stores, the serving daemon's capacity-padded tile, and the
4-shard sub-bank program. Nothing runs; the compiler's refusals (block
tiling, memory) are what these tests catch without a chip. Each
program must fit one chip's HBM by ``memory_analysis()``, and the
sharded one must contain no collective.
"""

import dataclasses
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding

from repro.configs.recxl_paper import PAPER_CLUSTER
from repro.core import engine
from repro.core.scenarios import mega_grid
from repro.core.serving import SERVE_BATCH_CELLS, SERVE_ROW_PAD, _row_capacity
from repro.core.simulator import bank_row_maps, sub_bank_rows
from repro.distributed.sharding import sub_bank_tile_specs
from repro.launch.serve_scenarios import query_stream

N_STORES = 50_000
#: One v5e chip's HBM (Google Cloud, "TPU v5e": 16 GB per chip).
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip could be written to a persistent
    # cache but never read back here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _bank_sig(sig, bank_shape):
    return dataclasses.replace(sig, bank_shape=bank_shape,
                               bank_sub=True)


def _args(sig, shardings):
    """Shape-only arguments of a sub-banked tile program."""
    t_rows, local = sig.bank_shape
    shapes = [((t_rows, sig.n_stores), jnp.float32),
              ((sig.n_shards, local, sig.n_stores), jnp.float32),
              ((sig.n_shards, local, sig.n_stores), jnp.float32),
              ((sig.n_shards, local, sig.n_stores), jnp.bool_),
              ((sig.b_pad,), jnp.int32), ((sig.b_pad,), jnp.int32)]
    return [jax.ShapeDtypeStruct(s, d, sharding=sh)
            for (s, d), sh in zip(shapes, shardings)]


def _fits_hbm(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes
            - mem.alias_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, mem
    return used


def _mega_sigs(n_shards):
    specs = mega_grid()
    trace_map, wv_map = bank_row_maps(specs, PAPER_CLUSTER)
    shape = (len(trace_map), sub_bank_rows(len(wv_map), n_shards))
    tiles = engine.plan_tiles(
        specs, n_stores=N_STORES, n_shards=n_shards,
        tile_cells=engine._default_tile_cells(N_STORES))
    return {t.sig.sb_uniform: _bank_sig(t.sig, shape) for t in tiles}


@pytest.mark.parametrize("sb", [72, 48])
def test_mega_grid_tile_compiles_for_v5e(topo, sb):
    sig = _mega_sigs(1)[sb]
    assert sig.n_stores == N_STORES and sig.b_pad % 8 == 0
    one_chip = SingleDeviceSharding(topo.devices[0])
    compiled = engine._build_bank_tile_fn(sig).lower(
        *_args(sig, [one_chip] * 6)).compile()
    _fits_hbm(compiled)


def test_serving_capacity_tile_compiles_for_v5e(topo):
    warm_grid, _ = query_stream(0)
    trace_map, wv_map = bank_row_maps(warm_grid, PAPER_CLUSTER)
    shape = (_row_capacity(len(trace_map), SERVE_ROW_PAD),
             _row_capacity(len(wv_map), SERVE_ROW_PAD))
    tiles = engine.plan_tiles(warm_grid, n_stores=N_STORES,
                              tile_cells=SERVE_BATCH_CELLS)
    sigs = {_bank_sig(t.sig, shape) for t in tiles}
    assert {s.sb_uniform for s in sigs} == {72, 48}
    one_chip = SingleDeviceSharding(topo.devices[0])
    for sig in sigs:
        assert sig.b_pad == SERVE_BATCH_CELLS
        compiled = engine._build_bank_tile_fn(sig).lower(
            *_args(sig, [one_chip] * 6)).compile()
        _fits_hbm(compiled)


def test_four_shard_sub_bank_tile_compiles_for_v5e(topo, monkeypatch):
    mesh = Mesh(np.asarray(topo.devices[:4]), ("cells",),
                axis_types=(AxisType.Auto,))
    monkeypatch.setattr(engine, "cells_mesh", lambda n: mesh)
    sig = _mega_sigs(4)[72]
    assert sig.n_shards == 4
    shardings = [NamedSharding(mesh, spec) for spec in sub_bank_tile_specs()]
    compiled = engine._build_bank_tile_fn(sig).lower(
        *_args(sig, shardings)).compile()
    _fits_hbm(compiled)
    hlo = compiled.as_text()
    for op in ("all-gather", "all-reduce", "collective-permute", "all-to-all"):
        assert op not in hlo, op

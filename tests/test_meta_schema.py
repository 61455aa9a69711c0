"""``SimResult.meta`` provenance schema, pinned across every path.

The observability contract (docs/observability.md): any result, from
the serial oracle, the engine (one shard, sharded, under chaos, after a
degraded-mesh recovery) or the serving daemon, must say where it came
from -- ``meta["engine"]``, ``meta["data_plane"]`` and
``meta["bank_partition"]`` are always present, with the path's
documented values. This test is the single place that keeps the schema
from drifting.
"""

import pytest

from repro.core import chaos
from repro.core import engine as E
from repro.core.scenarios import sweep_grid
from repro.core.simulator import simulate

N = 500
GRID = sweep_grid(workloads=("ycsb",), configs=("wb", "proactive"),
                  sb_sizes=(None, 48))


def _serial():
    return [simulate("ycsb", "wb", n_stores=N).meta]


def _streamed():
    return [r.meta for r in E.run_grid(GRID, n_stores=N, n_shards=1)]


def _sharded():
    # n_shards=1 would report engine="streamed", so this tier needs a
    # real second device (the CI tier-1 matrix also runs host_devices=1)
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("sharded tier needs >= 2 devices")
    return [r.meta for r in E.run_grid(GRID, n_stores=N, n_shards=2)]


def _two_devices():
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")


def _chaos_replicas():
    # the replica-set layout of a chaos run: same engine, wider sub-bank
    _two_devices()
    with chaos.inject(chaos.ChaosConfig()):
        return [r.meta for r in E.run_grid(GRID, n_stores=N, n_shards=2,
                                           k_replicas=2)]


def _degraded():
    # a shard lost at the first dispatch with no spare: every cell is
    # re-run on the shrunken (one-shard) mesh over a replicated bank
    _two_devices()
    with chaos.inject(chaos.ChaosConfig(lose_shard=0, lose_at_dispatch=1,
                                        recovery="degraded")):
        out = E.run_grid(GRID, n_stores=N, n_shards=2)
    assert E.bank_stats()["degraded"] is True
    return [r.meta for r in out]


def _serving():
    from repro.core.serving import ScenarioServer
    with ScenarioServer(n_stores=N, batch_cells=8) as srv:
        return [r.meta for r in srv.query_batch(GRID)]


TIERS = {
    "serial": (_serial, "serial", "stacked", None),
    "streamed": (_streamed, "streamed", "bank", "sub"),
    "sharded": (_sharded, "sharded", "bank", "sub"),
    "chaos-replicas": (_chaos_replicas, "sharded", "bank", "sub"),
    "degraded": (_degraded, "streamed", "bank", "replicated"),
    "serving": (_serving, "serving", "bank", "sub"),
}


@pytest.mark.parametrize("tier", sorted(TIERS))
def test_meta_provenance_schema(tier):
    run, engine, plane, partition = TIERS[tier]
    metas = run()
    assert metas, tier
    for m in metas:
        assert m is not None, tier
        # the three provenance keys are unconditionally present
        for key in ("engine", "data_plane", "bank_partition"):
            assert key in m, (tier, key, sorted(m))
        assert m["engine"] == engine, (tier, m)
        assert m["data_plane"] == plane, (tier, m)
        assert m["bank_partition"] == partition, (tier, m)


@pytest.mark.parametrize("tier", sorted(TIERS))
def test_meta_is_per_result_not_aliased(tier):
    """Annotating one result's meta must not leak into its batch
    siblings (frozen dataclass, mutable dict -- aliasing would)."""
    metas = TIERS[tier][0]()
    if len(metas) < 2:
        pytest.skip("single-result tier")
    metas[0]["__scratch__"] = 1
    assert "__scratch__" not in metas[1]

"""Fault-injection differential suite (``repro.core.chaos``).

The resilience contract (PR 9, mirroring the paper's §VI-VII recovery
argument): any single injected fault -- shard loss, corrupted bank row,
failed h2d upload, worker-thread death -- detected mid-grid or
mid-query-stream is recovered IN PLACE, and the recovered results are
bit-identical (``==``) to the fault-free oracle.  The spare-replacement
path re-places the rebuilt rows into the same shapes/shardings, so it
adds ZERO compiles; the two rebuild sources (surviving replica block,
Logging-Unit journal replay) produce byte-identical rows.  With chaos
off, ``k_replicas`` resolves to 1 and every placement key, byte count
and compile count is untouched (the PR-8 zero-churn pin).
"""

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import chaos
from repro.core import engine as E
from repro.core.chaos import ChaosConfig, IntegrityError
from repro.core.retry import (
    PLACEMENT_RETRY,
    RetryExhausted,
    RetryPolicy,
    backoff_delays,
    retry_call,
)
from repro.core.scenarios import chaos_grid, run_sweep, sweep_grid
from repro.core.serving import ScenarioServer
from repro.core.simulator import (
    CONFIGS,
    PAPER_CLUSTER,
    ScenarioSpec,
    clear_sim_caches,
    get_trace_bank,
    sub_bank_rows,
)

N = 700
WORKLOAD_POOL = ("ycsb", "canneal", "barnes", "raytrace", "ocean_ncp")
FLOAT_FIELDS = ("exec_time_ns", "repl_at_head_frac", "sb_full_frac",
                "max_log_bytes", "cxl_mem_bw_gbps", "log_dump_bw_gbps")
SHARD_COUNTS = sorted({1, min(8, jax.device_count())})
FAULT_KINDS = ("shard-loss", "corrupt-row", "upload-failure",
               "kill-prefetch", "kill-warm")


def _oracle(specs):
    """The fault-free answers: the serial oracle, cell by cell."""
    return run_sweep(specs, n_stores=N, engine="serial")


def _assert_bit_identical(got, want, ctx):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        for f in FLOAT_FIELDS:
            assert getattr(a, f) == getattr(b, f), (ctx, a.meta, f)


def _fault_cfg(kind, n_shards, **kw):
    """One-fault ChaosConfig per differential axis value."""
    if kind == "shard-loss":
        return ChaosConfig(lose_shard=n_shards - 1, lose_at_dispatch=1, **kw)
    if kind == "corrupt-row":
        return ChaosConfig(corrupt_wv_row=0, **kw)
    if kind == "upload-failure":
        return ChaosConfig(upload_failures=2, **kw)
    if kind == "kill-prefetch":
        return ChaosConfig(kill_thread="prefetch", **kw)
    if kind == "kill-warm":
        return ChaosConfig(kill_thread="warm", **kw)
    raise AssertionError(kind)


@st.composite
def ragged_grids(draw):
    """Small ragged mixed-SB grids (multiple tile signatures, so a
    mid-grid fault lands between differently-shaped tiles)."""
    n = draw(st.integers(min_value=2, max_value=8))
    specs = []
    for _ in range(n):
        specs.append(ScenarioSpec(
            draw(st.sampled_from(WORKLOAD_POOL)),
            draw(st.sampled_from(CONFIGS)),
            seed=draw(st.integers(min_value=0, max_value=1)),
            n_replicas=draw(st.sampled_from((None, 2, 3))),
            link_bw_gbps=draw(st.sampled_from((None, 40.0))),
            sb_size=draw(st.sampled_from((None, 48)))))
    return specs


# ---------------------------------------------------------------------------
# Engine: every fault x 1 and 8 shards
# ---------------------------------------------------------------------------


@settings(max_examples=4, deadline=None)
@given(ragged_grids(),
       st.sampled_from(FAULT_KINDS),
       st.sampled_from(SHARD_COUNTS))
def test_engine_faults_recover_bit_identical(grid, kind, n_shards):
    """The headline differential: a fault injected mid-grid recovers to
    results ``==`` the fault-free oracle on every shard count."""
    oracle = _oracle(grid)
    with chaos.inject(_fault_cfg(kind, n_shards)) as cs:
        got = E.run_grid(grid, n_stores=N, tile_cells=16,
                         n_shards=n_shards)
    _assert_bit_identical(got, oracle, (kind, n_shards))
    rep = cs.report()
    if kind == "shard-loss":
        assert rep["recoveries"], (kind, n_shards)
        assert rep["recoveries"][0]["shard"] == n_shards - 1
    if kind == "upload-failure":
        assert rep["upload_retries"] == 2
    if kind.startswith("kill"):
        assert rep["threads_killed"]


def test_engine_shard_loss_zero_recompiles_on_spare_path():
    """Spare replacement re-places the SAME shapes: the recovery itself
    must not trace a single new tile program, and a steady-state re-run
    after recovery stays at 0 compiles too."""
    n_shards = min(8, jax.device_count())
    if n_shards < 2:
        pytest.skip("needs >= 2 shards for a surviving replica")
    grid = chaos_grid()
    clear_sim_caches()
    oracle = _oracle(grid)
    with chaos.inject(ChaosConfig(lose_shard=2, lose_at_dispatch=2)) as cs:
        warm = E.run_grid(grid, n_stores=N, tile_cells=16,
                          n_shards=n_shards)
        _assert_bit_identical(warm, oracle, "warmup-with-loss")
        assert cs.report()["recoveries"][0]["source"] == "replica"
        stats = E.bank_stats()
        assert stats["k_replicas"] == 2
        tc0 = E.trace_count()
        again = E.run_grid(grid, n_stores=N, tile_cells=16,
                           n_shards=n_shards)
        _assert_bit_identical(again, oracle, "steady-after-recovery")
        assert E.trace_count() == tc0          # zero new compiles
    rec = cs.report()["recoveries"]
    assert len(rec) == 1 and rec[0]["mode"] == "spare"


def test_engine_degraded_mesh_recovery():
    """No spare: the unfinished cells are re-run on a mesh shrunk by
    one shard with the bank replicated -- one recompile, results still
    bit-identical, and ``bank_stats()`` reports the degraded run."""
    n_shards = min(8, jax.device_count())
    if n_shards < 2:
        pytest.skip("cannot shrink a single-shard mesh")
    grid = sweep_grid(workloads=("ycsb", "barnes"),
                      configs=("wb", "proactive"), n_replicas=(None, 2))
    oracle = _oracle(grid)
    with chaos.inject(ChaosConfig(lose_shard=0, lose_at_dispatch=1,
                                  recovery="degraded")) as cs:
        got = E.run_grid(grid, n_stores=N, tile_cells=16,
                         n_shards=n_shards)
    _assert_bit_identical(got, oracle, "degraded")
    assert E.bank_stats()["degraded"] is True
    rec = cs.report()["recoveries"]
    assert rec and rec[0]["mode"] == "degraded" \
        and rec[0]["source"] == "degraded-mesh"


def test_poisoned_tile_surfaces_with_context(monkeypatch):
    """Satellite bugfix pin: a genuine (non-injected) prefetch failure
    surfaces promptly as :class:`EngineWorkerError` naming the stage
    and tile -- not as a hang or an opaque error tiles later."""
    grid = [ScenarioSpec(w, c) for w in ("ycsb", "barnes")
            for c in ("wb", "proactive")]
    clear_sim_caches()
    real = E._cell_scalars

    def poisoned(spec, *a, **kw):
        if spec.workload == "barnes":
            raise ValueError("poisoned tile input")
        return real(spec, *a, **kw)

    monkeypatch.setattr(E, "_cell_scalars", poisoned)
    with pytest.raises(E.EngineWorkerError) as ei:
        E.run_grid(grid, n_stores=N, tile_cells=16, n_shards=1)
    assert ei.value.stage == "prefetch"
    assert ei.value.tile_no is not None
    assert "poisoned tile input" in str(ei.value)
    # the run fails promptly AND cleanly: the engine serves the same
    # grid fine immediately afterwards
    monkeypatch.setattr(E, "_cell_scalars", real)
    clear_sim_caches()
    _assert_bit_identical(E.run_grid(grid, n_stores=N, tile_cells=16,
                                     n_shards=1),
                          _oracle(grid), "after-poison")


# ---------------------------------------------------------------------------
# Rebuild sources: replica block vs Logging-Unit journal
# ---------------------------------------------------------------------------


def test_journal_replay_equals_replica_rebuild():
    """The two rebuild sources are interchangeable: for every shard,
    the rows read back from the surviving replica block are
    byte-identical to the journal/host rebuild, and both pass
    ``verify_rebuild``'s digests."""
    n_shards = min(8, jax.device_count())
    if n_shards < 2:
        pytest.skip("replica rebuild needs >= 2 shards")
    base = sweep_grid(workloads=("ycsb", "canneal"), configs=CONFIGS)
    delta = sweep_grid(workloads=("barnes",), configs=("wb", "proactive"),
                       n_replicas=(2, 3))
    clear_sim_caches()
    bank = get_trace_bank(base, N, PAPER_CLUSTER)
    bank.enable_journal()
    bank.extend(delta)                    # journaled, un-acked diffs
    assert bank.journal_entries > 0
    _, dev = bank.sub_device_args(n_shards, k_replicas=2)
    local_cap = sub_bank_rows(bank.wv_rows, n_shards)
    for lost in range(n_shards):
        via_replica = chaos.replica_rebuild(
            dev, lost, n_shards=n_shards, k_replicas=2,
            local_cap=local_cap, wv_rows=bank.wv_rows)
        via_journal = chaos.journal_rebuild(bank, lost, n_shards)
        for name in ("w", "v", "pr_nc"):
            assert np.array_equal(via_replica[name], via_journal[name]), \
                (lost, name)
        chaos.verify_rebuild(bank, via_replica, lost, n_shards)
        chaos.verify_rebuild(bank, via_journal, lost, n_shards)
    # a corrupted rebuild must NOT pass the digests
    bad = {k: v.copy() for k, v in via_journal.items()}
    bad["w"][0, 0] += 1.0
    with pytest.raises(IntegrityError):
        chaos.verify_rebuild(bank, bad, n_shards - 1, n_shards)


def test_replica_layout_and_integrity_detection():
    """Replica-block geometry: block ``j`` of shard ``s`` holds the
    rows owned by ``(s - j) % n``; ``fetch_wv_row`` reads identical
    bytes off either block; ``verify_rows`` catches a tampered row."""
    n_shards = min(8, jax.device_count())
    if n_shards < 2:
        pytest.skip("needs >= 2 shards")
    grid = sweep_grid(workloads=("ycsb", "raytrace"), configs=CONFIGS)
    clear_sim_caches()
    bank = get_trace_bank(grid, N, PAPER_CLUSTER)
    k = 2
    a, w, v, p = bank.sub_bank_host(n_shards, k)
    p_loc = sub_bank_rows(bank.wv_rows, n_shards)
    assert w.shape == (n_shards, k * p_loc, N)
    for r in range(bank.wv_rows):
        owner, loc = r % n_shards, r // n_shards
        for j in range(k):
            s = (owner + j) % n_shards
            assert np.array_equal(w[s, j * p_loc + loc], bank.w[r]), (r, j)
    # byte cost: the replicated layout is exactly k stacked copies
    a1, w1, v1, p1 = bank.sub_bank_host(n_shards, 1)
    assert w.nbytes == k * w1.nbytes
    # device path: both resident copies digest-match the host truth
    _, dev = bank.sub_device_args(n_shards, k_replicas=k)
    for r in (0, bank.wv_rows - 1):
        for j in range(k):
            got = chaos.fetch_wv_row(dev, r, n_shards=n_shards,
                                     local_cap=p_loc, block=j)
            assert chaos.row_digest(got[0]) == chaos.row_digest(bank.w[r])
    chaos.verify_rows(bank, dev, range(bank.wv_rows),
                      n_shards=n_shards, local_cap=p_loc)
    with chaos.inject(ChaosConfig(corrupt_wv_row=1)) as cs:
        tampered = cs.tamper_bank(dev, n_shards=n_shards, k_replicas=k,
                                  local_cap=p_loc, wv_rows=bank.wv_rows)
        with pytest.raises(IntegrityError) as ei:
            chaos.verify_rows(bank, tampered, range(bank.wv_rows),
                              n_shards=n_shards, local_cap=p_loc)
        assert ei.value.rows == (1,)


# ---------------------------------------------------------------------------
# Chaos off: the PR-8 zero-churn pin
# ---------------------------------------------------------------------------


def test_chaos_off_zero_churn():
    """With no chaos scope, ``k_replicas`` resolves to 1 and the
    placement keys, resident bytes and compile counts are the PR-8
    ones bit-for-bit -- resilience costs nothing until requested."""
    assert chaos.active() is None
    n_shards = min(8, jax.device_count())
    assert chaos.resolve_k_replicas(None, n_shards) == 1
    assert chaos.resolve_k_replicas(3, n_shards) == min(3, n_shards)
    with chaos.inject(ChaosConfig()):
        assert chaos.resolve_k_replicas(None, n_shards) == \
            min(2, n_shards)
        assert chaos.resolve_k_replicas(None, 1) == 1     # clamped
    grid = sweep_grid(workloads=("ycsb", "canneal"), configs=CONFIGS)
    clear_sim_caches()
    E.run_grid(grid, n_stores=N, tile_cells=16, n_shards=n_shards)
    stats = E.bank_stats()
    assert stats["k_replicas"] == 1
    assert stats["chaos"] is None
    assert stats["degraded"] is False
    bank = get_trace_bank(grid, N)
    # the k=1 placement memo key is EXACTLY the PR-8 key (pinned by
    # test_trace_bank.py too): resilient placements use a distinct key
    assert ("sub", n_shards) in bank._device
    assert ("sub", n_shards, 2) not in bank._device
    # measured bytes match the k=1 host stacks exactly
    a, w, v, p = bank.sub_bank_host(n_shards, 1)
    assert stats["bank_dev_bytes"] == \
        n_shards * a.nbytes + w.nbytes + v.nbytes + p.nbytes
    # journal off by default: no diff copies retained
    assert bank.journal_entries == 0


# ---------------------------------------------------------------------------
# Bounded retry (core.retry)
# ---------------------------------------------------------------------------


def test_retry_backoff_deterministic_and_capped():
    pol = RetryPolicy(max_attempts=5, base_delay_s=0.010,
                      max_delay_s=0.025, jitter=0.5, seed=0)
    d1 = list(backoff_delays(pol, "x"))
    d2 = list(backoff_delays(pol, "x"))
    assert d1 == d2                              # seeded by describe
    assert d1 != list(backoff_delays(pol, "y"))
    assert len(d1) == pol.max_attempts - 1
    assert all(0 < d <= pol.max_delay_s * (1 + pol.jitter) for d in d1)


def test_retry_call_recovers_and_exhausts():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise chaos.UploadError("transient")
        return "ok"

    retries = []
    assert retry_call(flaky, policy=PLACEMENT_RETRY,
                      retryable=(chaos.UploadError,), describe="flaky",
                      on_retry=lambda n, e, d: retries.append(e)) == "ok"
    assert calls["n"] == 3 and len(retries) == 2

    def dead():
        raise chaos.UploadError("always")

    with pytest.raises(RetryExhausted) as ei:
        retry_call(dead, policy=PLACEMENT_RETRY,
                   retryable=(chaos.UploadError,), describe="dead-path")
    assert ei.value.attempts == PLACEMENT_RETRY.max_attempts
    assert "dead-path" in str(ei.value)
    assert isinstance(ei.value.last, chaos.UploadError)

    # non-retryable errors pass straight through on attempt 1
    def bug():
        raise ValueError("logic bug")

    with pytest.raises(ValueError):
        retry_call(bug, policy=PLACEMENT_RETRY,
                   retryable=(chaos.UploadError,), describe="bug")


# ---------------------------------------------------------------------------
# Serving daemon: faults mid-query-stream
# ---------------------------------------------------------------------------


SERVE_WARM = sweep_grid(workloads=("ycsb", "raytrace"), configs=CONFIGS)
SERVE_NOVEL = sweep_grid(workloads=("barnes",),
                         configs=("baseline", "proactive"),
                         n_replicas=(2, 3))


@pytest.mark.parametrize("kind", ("shard-loss", "corrupt-row",
                                  "upload-failure", "kill-daemon"))
def test_server_faults_recover_bit_identical(kind):
    """Mid-query-stream faults: the server detects, recovers in place
    (keeping its padded capacity, so ZERO recompiles), and every answer
    stays ``==`` the cold oracle."""
    n_shards = min(8, jax.device_count())
    clear_sim_caches()
    oracle = _oracle(SERVE_NOVEL)
    cfg = (ChaosConfig(kill_thread="daemon") if kind == "kill-daemon"
           else ChaosConfig(lose_shard=n_shards - 1, lose_at_dispatch=2)
           if kind == "shard-loss" else _fault_cfg(kind, n_shards))
    with chaos.inject(cfg) as cs:
        with ScenarioServer(n_stores=N, n_shards=n_shards,
                            batch_cells=16,
                            submit_timeout_ms=60_000) as srv:
            assert srv.k_replicas == min(2, n_shards)
            srv.warm(SERVE_WARM)
            srv.reset_stats()
            if kind == "kill-daemon":
                futs = [srv.submit(s) for s in SERVE_NOVEL]
                got = [f.result(timeout=120) for f in futs]
            else:
                got = srv.query_batch(SERVE_NOVEL)
            _assert_bit_identical(got, oracle, kind)
            stats = srv.stats()
            assert stats["compiled_programs"] == 0, kind
            if kind == "shard-loss":
                assert stats["recoveries"] == 1
                assert cs.report()["recoveries"][0]["source"] == \
                    ("replica" if n_shards > 1 else "journal")
                # post-recovery steady state: all hits, still 0 compiles
                again = srv.query_batch(SERVE_NOVEL)
                _assert_bit_identical(again, oracle, "steady")
                assert srv.stats()["compiled_programs"] == 0
            if kind == "kill-daemon":
                assert stats["worker_restarts"] >= 1


def test_server_journal_acked_after_flush():
    """The Logging Unit retains un-dumped diffs only until the device
    dump is acknowledged at the end of a successful flush."""
    with chaos.inject(ChaosConfig()):
        clear_sim_caches()
        n_shards = min(2, jax.device_count())
        with ScenarioServer(n_stores=N, n_shards=n_shards,
                            batch_cells=16) as srv:
            srv.warm(SERVE_WARM)
            assert srv.stats()["journal_entries"] == 0   # acked by warm
            srv.query_batch(SERVE_NOVEL)
            assert srv.stats()["journal_entries"] == 0

"""End-to-end engine behaviour: epochs, replicas, hybrid bytes, faults."""
import numpy as np
import pytest

from repro.core.engine import StarEngine
from repro.core.fault import ClusterConfig, RecoveryCase, classify_failure
from repro.db import tpcc, ycsb


@pytest.fixture(scope="module")
def ycsb_engine():
    cfg = ycsb.YCSBConfig(n_partitions=4, records_per_partition=500)
    eng = StarEngine(cfg.n_partitions, cfg.records_per_partition)
    for ep in range(3):
        eng.run_epoch(ycsb.make_batch(cfg, 192, seed=ep))
    return eng


def test_replica_consistent_after_epochs(ycsb_engine):
    assert ycsb_engine.replica_consistent()


def test_epoch_advances(ycsb_engine):
    assert ycsb_engine.epoch == 4
    assert ycsb_engine.stats.fences == 6


def test_controller_solves_eq12(ycsb_engine):
    tau_p, tau_s = ycsb_engine.controller.plan()
    e = ycsb_engine.controller.e_ms
    assert abs(tau_p + tau_s - e) < 1e-9                     # Eq (1)
    t_p, t_s = ycsb_engine.controller.t_p, ycsb_engine.controller.t_s
    P = ycsb_engine.controller.frac_cross
    if P > 0 and t_s > 0:
        lhs = tau_s * t_s / (tau_p * t_p + tau_s * t_s)      # Eq (2)
        assert abs(lhs - P) < 1e-6


def test_tpcc_hybrid_replication_saves_bytes():
    cfg = tpcc.TPCCConfig(n_partitions=2, n_items=500, cust_per_district=50,
                          order_ring=64)
    state = tpcc.TPCCState(cfg)
    rng = np.random.default_rng(0)
    eng = StarEngine(cfg.n_partitions, cfg.rows_per_partition,
                     init_val=tpcc.init_values(cfg, rng))
    for ep in range(2):
        eng.run_epoch(tpcc.make_batch(cfg, state, 128, seed=ep))
    assert eng.replica_consistent()
    assert eng.stats.value_bytes_if_not_hybrid > 3 * eng.stats.op_bytes_hybrid


def test_ycsb_no_hybrid_savings(ycsb_engine):
    """Paper §7.5: YCSB writes update the whole record — no savings."""
    s = ycsb_engine.stats
    assert s.op_bytes_hybrid >= 0.9 * s.value_bytes_if_not_hybrid


def test_failure_revert_and_continue():
    cfg = ycsb.YCSBConfig(n_partitions=4, records_per_partition=300)
    eng = StarEngine(cfg.n_partitions, cfg.records_per_partition,
                     cluster=ClusterConfig(f=1, k=4, n_partitions=4))
    eng.run_epoch(ycsb.make_batch(cfg, 128, seed=0))
    snap = np.array(eng.snapshot["val"])
    plan = eng.inject_failure({2})
    assert plan.case == RecoveryCase.PHASE_SWITCHING
    assert np.array_equal(np.array(eng.master["val"]), snap)
    eng.run_epoch(ycsb.make_batch(cfg, 128, seed=1))
    assert eng.replica_consistent()


def test_failure_case_enumeration_f2_k6():
    """Paper §4.5.3: all 2^8-1 = 255 failure patterns of f=2, k=6 classify
    into the four cases; spot-check the boundaries."""
    cfg = ClusterConfig(f=2, k=6, n_partitions=6, replicas_per_partition=2)
    counts = {c: 0 for c in RecoveryCase}
    for mask in range(1, 256):
        failed = {i for i in range(8) if mask & (1 << i)}
        counts[classify_failure(cfg, failed)] += 1
    assert sum(counts.values()) == 255
    assert all(v > 0 for v in counts.values())
    # no full replica nodes alive and no complete partial set -> case 4
    assert classify_failure(cfg, set(range(8))) == RecoveryCase.UNAVAILABLE
    # only full replicas fail -> case 2 (fall back to distributed CC)
    assert classify_failure(cfg, {0, 1}) == RecoveryCase.FALLBACK_DIST_CC
    # all partial nodes fail -> case 3 (full replica only)
    assert classify_failure(cfg, set(range(2, 8))) == RecoveryCase.FULL_ONLY
    # one partial fails, its partition still has a live secondary -> case 1
    assert classify_failure(cfg, {3}) == RecoveryCase.PHASE_SWITCHING


def test_fence_models_network_lag(ycsb_engine):
    """The replication fence ships the epoch's stream bytes through the
    cost-model Network envelope: t_fence_net_s > 2 barrier RTTs whenever
    bytes moved, and it accumulates in the engine stats."""
    from repro.baselines.cost_model import Network
    net = Network()
    cfg = ycsb.YCSBConfig(n_partitions=2, records_per_partition=200)
    eng = StarEngine(2, 200, net=net)
    m = eng.run_epoch(ycsb.make_batch(cfg, 128, seed=3))
    floor = 2 * 2 * net.rtt_s                  # two fences, 2 RTTs each
    assert m["t_fence_net_s"] >= floor
    assert m["t_fence_net_s"] > floor, "stream bytes must add transfer time"
    assert eng.stats.fence_net_s >= m["t_fence_net_s"]


def test_engine_adaptive_epoch_flag():
    eng = StarEngine(2, 64, adaptive_epoch=True, iteration_ms=10.0)
    assert eng.controller.adaptive
    for _ in range(40):
        eng.controller.observe_latency(30.0, 35.0)
    assert eng.controller.e_ms > 15.0


def _star(kernel):
    return StarEngine(2, 8, kernel=kernel)


def _cluster(kernel):
    import jax
    from repro.core.cluster import ClusterStarEngine
    return ClusterStarEngine(jax.make_mesh((1,), ("part",)), 2, 8,
                             kernel=kernel)


@pytest.mark.parametrize("make", [_star, _cluster], ids=["star", "cluster"])
def test_pallas_kernel_refused_on_tpu_backend(monkeypatch, make):
    """The fused kernels do not lower for a TPU: on that backend the engine
    refuses them at construction (naming the failing lowerings) instead
    of failing inside a compile or falling back to the jnp path."""
    import jax
    assert make("pallas").kernel == "pallas"      # interpret mode off-TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="scatter-min"):
        make("pallas")
    assert make("jnp").kernel == "jnp"

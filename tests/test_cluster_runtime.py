"""Distributed cluster runtime (8 forced host devices, subprocess):
sharded TxnService over the mesh, live failure injection, §4.5 recovery.

Each test boots a 4-node mesh (ppn=2: 8 partitions on 4 devices) in a
subprocess with forced host devices, exactly like tests/test_cluster_router,
and drives the ClusterRuntime — revert at the fence, RecoveryCase
classification, donor copy / full-replica rebuild / disk reload — asserting
``replica_consistent()`` at every fence after recovery.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run(code: str) -> str:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=480)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_runtime_parity_and_case1_failover():
    """ClusterRuntime (ppn=2) matches StarEngine commit counts; killing one
    partial node mid-run classifies PHASE_SWITCHING, restores the node's
    block from the full replica (a real donor copy — the block was
    scribbled), and the replicas are bit-identical at the next fence."""
    out = _run("""
        import jax, numpy as np
        from repro.cluster import ClusterRuntime
        from repro.core.engine import StarEngine
        from repro.core.fault import FaultInjector, RecoveryCase
        from repro.db import ycsb
        cfg = ycsb.YCSBConfig(n_partitions=8, records_per_partition=128)
        mesh = jax.make_mesh((4,), ("part",), devices=jax.devices()[:4])
        inj = FaultInjector(); inj.schedule_kill(2, epoch=3)
        rt = ClusterRuntime(mesh, 8, 128, injector=inj)
        eng = StarEngine(8, 128)
        events = []
        for ep in range(5):
            batch = ycsb.make_batch(cfg, 128, seed=ep)
            mc = rt.run_epoch(batch)
            ms = eng.run_epoch(batch)
            assert mc["committed_single"] == ms["committed_single"], (ep, mc, ms)
            assert mc["committed_cross"] == ms["committed_cross"], (ep, mc, ms)
            assert rt.replica_consistent(), ep
            if "recovery" in mc: events.append(mc["recovery"])
        assert np.array_equal(np.asarray(rt.eng.full_val),
                              np.asarray(eng.master["val"]))
        [ev] = events
        assert ev.case is RecoveryCase.PHASE_SWITCHING, ev
        assert ev.run_mode == "star" and ev.failed == (2,)
        assert ev.t_recovery_s > 0 and ev.reverted_to == 2
        assert rt.coordinator.view >= 3      # failure + rejoin reconfigs
        assert inj.killed == set()           # node rejoined
        print("OK case1", round(ev.t_recovery_s * 1e3, 1), "ms")
    """)
    assert "OK case1" in out


def test_named_cluster_programs_stay_bit_identical_to_star_engine():
    """With every mesh program a named function, four nodes (ppn=2) end
    each epoch in StarEngine's state bit for bit: every copy's values and
    TIDs (master blocks, full replica, secondaries) and every commit
    decision."""
    out = _run("""
        import jax, numpy as np
        from repro.cluster import ClusterRuntime
        from repro.core.engine import StarEngine
        from repro.db import ycsb
        cfg = ycsb.YCSBConfig(n_partitions=8, records_per_partition=128,
                              cross_ratio=0.2)
        mesh = jax.make_mesh((4,), ("part",), devices=jax.devices()[:4])
        rt = ClusterRuntime(mesh, 8, 128)
        eng = StarEngine(8, 128)
        ppn = rt.eng.ppn
        for ep in range(4):
            batch = ycsb.make_batch(cfg, 160, seed=10 + ep)
            mc = rt.run_epoch(batch)
            ms = eng.run_epoch(batch)
            for k in ("p_committed", "c_committed"):
                assert np.array_equal(np.asarray(mc[k]),
                                      np.asarray(ms[k])), (ep, k)
            want_v = np.asarray(eng.master["val"])
            want_t = np.asarray(eng.master["tid"])
            for v, t in ((rt.eng.part_val, rt.eng.part_tid),
                         (rt.eng.full_val, rt.eng.full_tid),
                         (np.roll(np.asarray(rt.eng.sec_val), -ppn, 0),
                          np.roll(np.asarray(rt.eng.sec_tid), -ppn, 0))):
                assert np.array_equal(np.asarray(v), want_v), ep
                assert np.array_equal(np.asarray(t), want_t), ep
        assert int(np.asarray(mc["c_committed"]).sum()) > 0
        print("OK parity")
    """)
    assert "OK parity" in out


def test_runtime_unavailable_reloads_from_disk():
    """Killing the full-replica node plus both homes of a partition block
    leaves neither a full replica nor a complete partial set: UNAVAILABLE.
    The runtime reloads checkpoint + per-node logs from disk (the blocks
    and the full copy were scribbled — only the disk bytes can be the
    source) and resumes bit-identical."""
    out = _run("""
        import jax, numpy as np, tempfile
        from repro.cluster import ClusterRuntime
        from repro.core.fault import FaultInjector, RecoveryCase
        from repro.db import ycsb
        from repro.db.wal import Durability
        cfg = ycsb.YCSBConfig(n_partitions=8, records_per_partition=128)
        mesh = jax.make_mesh((4,), ("part",), devices=jax.devices()[:4])
        inj = FaultInjector()
        for n in (0, 1, 2): inj.schedule_kill(n, epoch=4)
        with tempfile.TemporaryDirectory() as d:
            dur = Durability(d, n_workers=4, checkpoint_every=2)
            rt = ClusterRuntime(mesh, 8, 128, injector=inj, durability=dur)
            events = []
            for ep in range(6):
                m = rt.run_epoch(ycsb.make_batch(cfg, 128, seed=ep))
                assert rt.replica_consistent(), ep
                if "recovery" in m: events.append(m["recovery"])
            [ev] = events
            assert ev.case is RecoveryCase.UNAVAILABLE, ev
            assert ev.reloaded_from_disk and ev.run_mode == "halt"
            assert set(ev.lost_blocks) == {0, 1}
            assert dur.checkpoints >= 1 and dur.entries_logged > 0
            print("OK unavailable", round(ev.t_recovery_s * 1e3), "ms")
    """)
    assert "OK unavailable" in out


def test_cluster_service_node_sharded_with_failure():
    """The online service over the mesh: node-sharded admission (per-node
    queue caps), double-buffered batching into shard_map, a mid-run node
    kill recovered live, and per-node telemetry in the summary."""
    out = _run("""
        import jax, numpy as np
        from repro.cluster import ClusterRuntime, ClusterTxnService
        from repro.core.fault import FaultInjector
        from repro.db import ycsb
        from repro.service import AdmissionConfig, OpenLoopClient, YCSBSource
        cfg = ycsb.YCSBConfig(n_partitions=8, records_per_partition=128)
        mesh = jax.make_mesh((4,), ("part",), devices=jax.devices()[:4])
        inj = FaultInjector(); inj.schedule_kill(3, epoch=6)
        rt = ClusterRuntime(mesh, 8, 128, injector=inj)
        client = OpenLoopClient(YCSBSource(cfg, seed=1), rate_txn_s=800.0,
                                seed=7)
        svc = ClusterTxnService(rt, [client],
                                AdmissionConfig(64, 64, node_queue_cap=96),
                                slots_per_partition=16, master_lanes=16)
        out = svc.run(duration_s=1.0)
        assert rt.replica_consistent()
        assert out["committed"] > 0
        assert out["recoveries"] == 1 and out["recovery_latency_s"][0] > 0
        assert len(out["node_committed"]) == 4
        assert sum(out["node_committed"]) == rt.stats.committed_single
        assert len(out["node_queue_depth_max"]) == 4
        assert len(out["node_fence_wait_s"]) == 4
        print("OK service", out["committed"], out["recovery_latency_s"])
    """)
    assert "OK service" in out

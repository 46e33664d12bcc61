"""Serve a live transaction stream through the DISTRIBUTED cluster runtime.

Run with forced host devices (one device == one paper node):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        PYTHONPATH=src python examples/serve_cluster.py --mix full [--quick]

``--mix full`` (the default) serves the five-transaction TPC-C mix
(45/43/4/4/4) — ordered secondary indexes sharded with the mesh, Delivery
consuming through index range scans, consume feedback re-queueing skipped
districts.  ``--mix ycsb`` serves the original YCSB stream.

Open-loop Poisson clients feed node-sharded admission (per-node bounded
queues on top of the per-partition caps); the epoch batcher double-buffers
host batch formation against the mesh execution (shard_map partitioned
phase with zero collectives, the §5 op-stream slabs shipping to the full
replica and the physical secondary homes DURING the phase, psum fence
waiting only on the unshipped tail, single-master phase on the full
replica).  Mid-run, a FaultInjector kills node 2: the coordinator detects
the missed fence, reverts the in-flight epoch (discarding the consumed
stream slabs), classifies the failure (§4.5), restores the node's
partitions from a surviving copy, and the service keeps serving —
recovery latency, per-node skew, and the overlapped-vs-fence stream bytes
appear in the summary.

``--read-tier`` additionally serves declared-read-only transactions
(OrderStatus/StockLevel) from the bounded-staleness replica tier: a read
lane in admission, snapshot reads off the full + secondary copies between
fences, ``--max-staleness K`` bounding how many fences a serving snapshot
may trail (0 = fence-fresh; reads that can't meet the bound fall back to
the OCC path, never go stale):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        PYTHONPATH=src python examples/serve_cluster.py \\
        --mix full --read-tier --max-staleness 2 [--quick]

``--analytics`` (full mix only) attaches the HTAP lane: columnar
materialized views maintained incrementally from the engine's ChangeLog
(the same ordered op stream the replicas replay), promoted and stamped
at every commit fence, serving a CH-benCHmark-style query mix between
fences — top revenue districts, stock-below-threshold, undelivered
backlog, and fence-granular revenue time-travel — without touching the
OCC phases:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        PYTHONPATH=src python examples/serve_cluster.py \\
        --mix full --analytics [--quick]
"""
import argparse

import numpy as np

import jax

from repro import compile_cache
from repro.cluster import ClusterRuntime, ClusterTxnService
from repro.core.fault import FaultInjector
from repro.db import tpcc, ycsb
from repro.obs import Tracer, set_tracer
from repro.service import (AdmissionConfig, OpenLoopClient, TPCCSource,
                           YCSBSource)

_ap = argparse.ArgumentParser(description=__doc__)
_ap.add_argument("--quick", action="store_true")
_ap.add_argument("--mix", default="full", choices=("full", "ycsb"))
_ap.add_argument("--read-tier", action="store_true",
                 help="serve declared-read-only txns from replica "
                 "snapshots between fences (bounded staleness)")
_ap.add_argument("--max-staleness", type=int, default=2, metavar="K",
                 help="freshness bound in fence epochs for snapshot reads "
                 "(0 = fence-fresh from the full copy)")
_ap.add_argument("--analytics", action="store_true",
                 help="attach the HTAP lane: ChangeLog-maintained "
                 "materialized views + CH-style query mix (full mix only)")
_ap.add_argument("--trace", metavar="OUT.json", default=None,
                 help="export a Chrome/Perfetto trace of the run (epoch/"
                 "phase/slab/fence/SM-round/recovery spans) to this path")
_ap.add_argument("--metrics", metavar="OUT.jsonl", default=None,
                 help="export the per-epoch MetricsRegistry snapshots "
                 "as JSON lines to this path")
_ap.add_argument("--kill-epoch", type=int, default=8, metavar="E",
                 help="epoch at which the FaultInjector kills a node "
                 "(lower it so --quick runs still exercise recovery)")
_ARGS = _ap.parse_args()
QUICK, MIX = _ARGS.quick, _ARGS.mix
READ_TIER, MAX_STALENESS = _ARGS.read_tier, _ARGS.max_staleness
ANALYTICS = _ARGS.analytics
TRACE, METRICS = _ARGS.trace, _ARGS.metrics
if ANALYTICS and MIX != "full":
    _ap.error("--analytics requires --mix full (TPC-C views)")


def main():
    compile_cache.enable()
    tracer = None
    if TRACE:
        tracer = Tracer(capacity=1 << 18, enabled=True)
        set_tracer(tracer)
    n = jax.device_count()
    if n < 2:
        print("NOTE: run with XLA_FLAGS=--xla_force_host_platform_device_"
              "count=4 to simulate a multi-node cluster; continuing with "
              f"{n} device(s).")
    mesh = jax.make_mesh((n,), ("part",))
    inj = FaultInjector()
    inj.schedule_kill(node=min(2, n - 1), epoch=_ARGS.kill_epoch)

    feedback = None
    if MIX == "full":
        P = n                                   # one warehouse per node
        cfg = tpcc.TPCCConfig(n_partitions=P, n_items=400,
                              cust_per_district=40, order_ring=64,
                              mix="full", delivery_gen_lag=256)
        state = tpcc.TPCCState(cfg)
        init = tpcc.init_values(cfg, np.random.default_rng(7), state=state)
        rt = ClusterRuntime(mesh, P, cfg.rows_per_partition, init_val=init,
                            indexes=tpcc.index_specs(cfg), injector=inj)
        client = OpenLoopClient(TPCCSource(cfg, state=state, seed=1),
                                rate_txn_s=600.0, seed=7)
        feedback = lambda b, m: tpcc.apply_consume_feedback(state, b, m)  # noqa: E731
    else:
        P = 2 * n                               # two partitions per node
        cfg = ycsb.YCSBConfig(n_partitions=P, records_per_partition=256)
        rt = ClusterRuntime(mesh, P, 256, injector=inj)
        client = OpenLoopClient(YCSBSource(cfg, seed=1), rate_txn_s=800.0,
                                seed=7)
    tier = None
    if READ_TIER:
        from repro.reads import ReadTier
        tier = ReadTier(max_staleness_epochs=MAX_STALENESS,
                        sec_refresh_every=2)
    lane = None
    if ANALYTICS:
        from repro.changelog import AnalyticsLane
        lane = AnalyticsLane(cfg, stock_threshold=40, retain=8)
    svc = ClusterTxnService(rt, [client],
                            AdmissionConfig(64, 64, node_queue_cap=96),
                            slots_per_partition=16, master_lanes=16,
                            feedback=feedback, read_tier=tier,
                            analytics=lane)
    out = svc.run(duration_s=0.8 if QUICK else 2.5)
    assert rt.replica_consistent(), "replicas diverged!"

    print(f"\n=== cluster service over {n} node(s), {P} partitions, "
          f"mix={MIX} ===")
    print(f"  sustained      : {out['throughput_txn_s']:8.0f} txn/s "
          f"({out['committed']} committed / {out['epochs']} epochs)")
    print(f"  latency        : p50 {out['p50_ms']:6.1f} ms   "
          f"p99 {out['p99_ms']:6.1f} ms")
    print(f"  per-node commit: {out['node_committed']}")
    print(f"  per-node shed  : {out['node_shed']}  "
          f"(queue depth max {out['node_queue_depth_max']})")
    print(f"  fence-wait EMA : {out['fence_wait_ema_ms']} ms")
    total = out["op_bytes_overlapped"] + out["op_bytes_fence"]
    if total:
        print(f"  op stream      : {out['op_bytes_overlapped']} B overlapped"
              f" / {out['op_bytes_fence']} B at the fence "
              f"({100 * out['op_bytes_overlapped'] / total:.0f}% hidden, "
              f"{out['slabs_shipped']} slabs)")
    if out["recoveries"]:
        ev = svc.recovery_events[0]
        src = ("disk" if ev.reloaded_from_disk
               else "secondary copy" if ev.restored_from_secondary
               else "full replica")
        print(f"  RECOVERY       : epoch {ev.epoch} lost node(s) "
              f"{list(ev.failed)} -> {ev.case.name} "
              f"({ev.run_mode}, restored from {src}), recovered in "
              f"{ev.t_recovery_s * 1e3:.1f} ms, view {ev.view}")
    if READ_TIER and MIX == "full":
        combined = out["combined_txn_s"]
        print(f"  read tier      : {out['read_served']} snapshot reads at "
              f"{out['read_txn_s']:.0f} txn/s "
              f"(p50 {out['read_p50_ms']:.1f} ms, "
              f"p99 {out['read_p99_ms']:.1f} ms)")
        print(f"  read freshness : max {out['read_max_freshness']} epoch(s) "
              f"(bound {MAX_STALENESS}), by replica {out['read_by_replica']},"
              f" {out['read_fallbacks']} OCC fallbacks, "
              f"{out['read_shed']} shed, "
              f"{out['read_replicas_removed']} replica(s) purged on failure")
        print(f"  combined       : {combined:8.0f} txn/s "
              f"(write {out['write_txn_s']:.0f} + read {out['read_txn_s']:.0f})")
        # CI gate: the tier must actually serve, never past the bound, and
        # combined throughput must clear a collapse floor
        assert out["read_served"] > 0, "read tier served nothing"
        assert out["read_stale_violations"] == 0, \
            f"stale-bound violations: {out['read_stale_violations']}"
        assert out["read_max_freshness"] <= MAX_STALENESS, out
        # loose floor (the injected kill + recovery dominates --quick runs):
        # catches collapse-to-zero, not host speed
        assert combined > 10, f"combined throughput collapsed: {combined}"
        print("  read tier: OK (served > 0, zero stale-bound violations)")
    if ANALYTICS:
        print(f"  analytics      : {out['analytics_serves']} serves / "
              f"{out['analytics_queries']} queries "
              f"(q p50 {out['analytics_q_p50_ms']:.3f} ms, "
              f"p99 {out['analytics_q_p99_ms']:.3f} ms)")
        print(f"  mv maintenance : {out['analytics_mv_slabs']} slabs, "
              f"{out['analytics_mv_writes']} writes, "
              f"{out['analytics_mv_commits']} commits, "
              f"{out['analytics_mv_reverts']} reverts, "
              f"{out['analytics_retained_epochs']} fences retained")
        # CI gate: the lane must actually serve, every serve fence-fresh,
        # and the freshest stamp must bit-equal a from-scratch recompute
        # of the committed full-replica state (end-to-end, post-recovery)
        assert out["analytics_serves"] > 0, "analytics lane served nothing"
        assert out["analytics_max_epoch_lag"] == 0, out
        epoch, aggs = lane.views.latest()
        want = lane.views.recompute(rt.committed_state()[0])
        for k in ("revenue", "stock_low", "undelivered", "order_latency"):
            assert np.array_equal(aggs[k], want[k]), k
        assert epoch == rt.committed_epoch
        print("  analytics: OK (served > 0, fence-fresh, final stamp "
              "bit-equal to recompute)")
    print("  replicas bit-identical at the final fence: OK "
          "(records + indexes + secondaries)")
    if TRACE:
        n_ev = tracer.export_chrome(TRACE)
        print(f"  trace          : {n_ev} events -> {TRACE} "
              f"({tracer.dropped} dropped)")
    if METRICS:
        n_snap = svc.metrics.export_jsonl(METRICS)
        print(f"  metrics        : {n_snap} epoch snapshots -> {METRICS}")


if __name__ == "__main__":
    main()

"""Figure 12: latency percentiles.

Async + epoch group commit: MEASURED through the online service layer — an
open-loop Poisson YCSB stream is admitted, batched, executed, and stamped
enqueue→commit-fence; the percentiles below are real end-to-end times on
this host (paper: p50 ~ e/2, p99 ~ e at e=10 ms on theirs).
Sync: per-protocol round-trip counts from the cost model (model-derived).
"""
import numpy as np

from benchmarks.common import get_calibration
from repro.baselines.cost_model import Network


def _measure_async_service(duration_s=1.5, rate=1500.0):
    from repro.core.engine import StarEngine
    from repro.db import ycsb
    from repro.service import (AdmissionConfig, OpenLoopClient, TxnService,
                               YCSBSource)
    cfg = ycsb.YCSBConfig(n_partitions=4, records_per_partition=1024,
                          cross_ratio=0.10)
    eng = StarEngine(4, 1024)
    client = OpenLoopClient(YCSBSource(cfg, seed=1), rate_txn_s=rate, seed=7)
    svc = TxnService(eng, [client], AdmissionConfig(256, 512),
                     slots_per_partition=32, master_lanes=32)
    out = svc.run(duration_s=duration_s)
    out["queue_delay_ms"] = eng.controller.queue_delay_ms
    # phase attribution off the registry time series (first → last epoch
    # snapshot: excludes warmup/compile), not hand-merged stats fields
    snaps = svc.metrics.snapshots
    s0, s1 = (snaps[0], snaps[-1]) if len(snaps) > 1 else ({}, svc.metrics.latest())
    phases = {ph: s1[f"engine.{ph}_time_s"] - s0.get(f"engine.{ph}_time_s", 0.0)
              for ph in ("part", "sm", "fence")}
    tot = max(sum(phases.values()), 1e-9)
    out["phase_pct"] = {ph: round(100.0 * t / tot, 1)
                        for ph, t in phases.items()}
    return out


def _measure_read_tier_split(duration_s=2.0, rate=250.0, max_staleness=2):
    """TPC-C full mix through TxnService WITH the read tier: the write path
    (NewOrder/Payment/Delivery, enqueue -> commit fence) and the read path
    (OrderStatus/StockLevel, enqueue -> snapshot serve) each get their own
    measured percentiles — the latency half of the read/write split whose
    throughput half fig11 reports.  The offered rate is set WELL below this
    host's full-mix capacity: at overload the percentiles measure queue
    buildup, not the serving paths."""
    import numpy as np

    from repro.core.engine import StarEngine
    from repro.db import tpcc
    from repro.reads import ReadTier
    from repro.service import (AdmissionConfig, OpenLoopClient, TPCCSource,
                               TxnService)
    cfg = tpcc.TPCCConfig(n_partitions=4, n_items=400, cust_per_district=40,
                          order_ring=64, mix="full", delivery_gen_lag=256)
    state = tpcc.TPCCState(cfg)
    init = tpcc.init_values(cfg, np.random.default_rng(7), state=state)
    eng = StarEngine(4, cfg.rows_per_partition, init_val=init,
                     indexes=tpcc.index_specs(cfg))
    client = OpenLoopClient(TPCCSource(cfg, state=state, seed=1),
                            rate_txn_s=rate, seed=7)
    tier = ReadTier(max_staleness_epochs=max_staleness, sec_refresh_every=2)
    svc = TxnService(eng, [client], AdmissionConfig(64, 64),
                     slots_per_partition=16, master_lanes=16,
                     feedback=lambda b, m: tpcc.apply_consume_feedback(
                         state, b, m),
                     read_tier=tier)
    return svc.run(duration_s=duration_s)


def run():
    rows = []
    net = Network()
    # epoch-commit: measured percentiles through the service layer
    m = _measure_async_service()
    epoch_us = 1e6 * m["epoch_time_s"] / max(m["epochs"], 1)
    rows.append(("fig12/async_all_p50_ms", epoch_us, round(m["p50_ms"], 2)))
    rows.append(("fig12/async_all_p99_ms", epoch_us, round(m["p99_ms"], 2)))
    rows.append(("fig12/async_all_p999_ms", epoch_us, round(m["p999_ms"], 2)))
    rows.append(("fig12/async_throughput_txn_s", epoch_us,
                 round(m["throughput_txn_s"], 1)))
    rows.append(("fig12/async_queue_delay_ms", epoch_us,
                 round(m["queue_delay_ms"], 2)))
    for ph, pct in m["phase_pct"].items():
        rows.append((f"fig12/async_phase_{ph}_pct", 0.0, pct))
    # read-tier split: write path vs bounded-staleness snapshot-read path
    rt = _measure_read_tier_split()
    rows += [
        ("fig12/read_tier_write_p50_ms", 0.0, round(rt["p50_ms"], 2)),
        ("fig12/read_tier_write_p99_ms", 0.0, round(rt["p99_ms"], 2)),
        ("fig12/read_tier_read_p50_ms", 0.0, round(rt["read_p50_ms"], 2)),
        ("fig12/read_tier_read_p99_ms", 0.0, round(rt["read_p99_ms"], 2)),
        ("fig12/read_tier_write_txn_s", 0.0, round(rt["write_txn_s"], 1)),
        ("fig12/read_tier_read_txn_s", 0.0, round(rt["read_txn_s"], 1)),
        ("fig12/read_tier_read_served", 0.0, rt["read_served"]),
        ("fig12/read_tier_max_freshness", 0.0, rt["read_max_freshness"]),
    ]
    for wl in ("ycsb", "tpcc"):
        cal = get_calibration(wl)
        for P in (0.1, 0.5, 0.9):
            # sync PB.OCC: one replication RTT
            pb = (cal.t_cross_cpu + net.rtt_s) * 1e3
            # sync Dist.OCC: remote reads + 2PC
            occ = (cal.t_cross_cpu * (1 + cal.retry_factor)
                   + P * (cal.remote_reads_per_cross + 2) * net.rtt_s) * 1e3
            # sync Dist.S2PL: locks held across reads + 2PC, queueing at p99
            s2pl = (cal.t_cross_cpu * (1 + 2 * cal.retry_factor)
                    + P * (cal.remote_reads_per_cross + 2) * net.rtt_s) * 1e3
            rows += [
                (f"fig12/{wl}_sync_P{P:g}_pb_occ_p50_ms", 0.0, round(pb, 3)),
                (f"fig12/{wl}_sync_P{P:g}_dist_occ_p50_ms", 0.0, round(occ, 3)),
                (f"fig12/{wl}_sync_P{P:g}_dist_s2pl_p50_ms", 0.0, round(s2pl, 3)),
                (f"fig12/{wl}_sync_P{P:g}_dist_s2pl_p99_ms", 0.0,
                 round(s2pl * 8, 3)),
            ]
    return rows


def main():
    """``--smoke``: tiny measured service run only — a CI gate that the
    online path still serves traffic with sane latency (interpret-friendly:
    no workload calibration, one small open-loop run)."""
    import argparse
    import sys

    from benchmarks.common import emit
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    if not args.smoke:
        emit(run())
        return
    m = _measure_async_service(duration_s=0.5, rate=400.0)
    emit([("fig12/smoke_p50_ms", 0.0, round(m["p50_ms"], 2)),
          ("fig12/smoke_p99_ms", 0.0, round(m["p99_ms"], 2)),
          ("fig12/smoke_throughput_txn_s", 0.0,
           round(m["throughput_txn_s"], 1))])
    if not (m["committed"] > 0 and m["p50_ms"] > 0):
        sys.exit(f"service smoke failed: {m}")
    print(f"SMOKE OK committed={m['committed']} p50={m['p50_ms']:.1f}ms")


if __name__ == "__main__":
    main()

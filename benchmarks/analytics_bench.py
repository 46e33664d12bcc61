"""Tracked perf harness for the HTAP analytics lane (ChangeLog MVs).

The analytics lane answers CH-benCHmark-style queries from columnar
materialized views maintained incrementally off the SAME ordered op
stream the replicas replay (``repro.changelog``).  This harness runs the
full five-transaction TPC-C mix on a ``StarEngine`` with the views
subscribed and emits:

* MV maintenance cost per stream event — ``mv_apply_slab`` (the scan
  scatter over one partitioned slab) and ``mv_apply_master`` (the Thomas
  merge of the single-master stream) — measured wall time per call plus
  the headline **apply throughput in writes/s** (the tracked regression
  floor: CI fails if it collapses below ``FLOOR_WRITES_S``);
* the fence stamp cost (aggregates off the committed projection) and the
  per-serve latency of the query mix (``lane.serve``);
* the correctness gates the numbers are only meaningful under: at EVERY
  fence the stamped aggregates bit-equal a from-scratch recompute of the
  engine's committed state, and time-travel returns exactly the recorded
  stamps for every retained fence.

``--bench-json BENCH_analytics.json`` writes the schema-versioned
snapshot (the committed tracking artifact, like BENCH_kernels.json).
``--smoke`` runs a small shape + all gates for CI; ``--validate`` runs
the bit-equality gates only.

    PYTHONPATH=src python -m benchmarks.analytics_bench --smoke
    PYTHONPATH=src python -m benchmarks.analytics_bench --bench-json BENCH_analytics.json
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import timed

SCHEMA = 1
#: tracked floor on MV apply throughput (writes applied per second of
#: maintenance time) — a collapse gate, far below any healthy host
FLOOR_WRITES_S = 5_000.0


class _Capture:
    """ChangeLog subscriber that keeps the published stream events so the
    timing loop can re-apply them against fresh views."""

    def __init__(self):
        self.slabs = []        # (log, info)
        self.masters = []      # stream dicts

    def on_slab(self, log, info):
        self.slabs.append((log, dict(info)))

    def on_master(self, stream):
        self.masters.append(stream)


def _mk(P, epochs, B, seed=7):
    from repro.core.engine import StarEngine
    from repro.db import tpcc
    cfg = tpcc.TPCCConfig(n_partitions=P, n_items=400, cust_per_district=40,
                          order_ring=64, mix="full", delivery_gen_lag=256)
    state = tpcc.TPCCState(cfg)
    init = tpcc.init_values(cfg, np.random.default_rng(seed), state=state)
    eng = StarEngine(P, cfg.rows_per_partition, init_val=init,
                     indexes=tpcc.index_specs(cfg), n_slabs=4)
    return cfg, state, eng


def _drive(cfg, state, eng, lane, epochs, B, check=True):
    """Run the mix with the lane attached; gate bit-equality per fence."""
    from repro.db import tpcc
    views = lane.views
    oracle = {eng.committed_epoch: views.recompute(eng.committed_state()[0])}
    for ep in range(epochs):
        batch = tpcc.make_batch(cfg, state, B, seed=ep)
        m = eng.run_epoch(batch)
        tpcc.apply_consume_feedback(state, batch, m)
        lane.serve(eng.committed_epoch)
        if not check:
            continue
        epoch, aggs = views.latest()
        assert epoch == eng.committed_epoch, (epoch, eng.committed_epoch)
        want = views.recompute(eng.committed_state()[0])
        for k in ("revenue", "stock_low", "undelivered", "order_latency"):
            assert np.array_equal(aggs[k], want[k]), \
                f"MV {k} diverged from recompute at fence {epoch}"
        oracle[epoch] = want
    if check:
        retained = views.retained_epochs()
        assert retained, "no fence stamps retained"
        for e in retained:
            tt = views.time_travel(e)
            for k, v in oracle[e].items():
                assert np.array_equal(tt[k], v), (e, k)
        assert views.time_travel(-1) is None
    return eng.replica_consistent()


def run(smoke: bool = False):
    from repro.changelog import AnalyticsLane, MaterializedViews
    P, epochs, B, reps = (2, 3, 96, 2) if smoke else (4, 8, 192, 5)
    cfg, state, eng = _mk(P, epochs, B)
    lane = AnalyticsLane(cfg, stock_threshold=40, retain=4)
    assert lane.ensure_attached(eng)
    cap = eng.changelog.subscribe(_Capture())
    assert _drive(cfg, state, eng, lane, epochs, B), "replicas diverged"
    lbl = f"analytics/p{P}_b{B}"
    rows = []

    # -- MV maintenance cost: re-apply captured stream events ------------
    views = MaterializedViews(cfg, stock_threshold=40, retain=4)
    val, tid = eng.committed_state()
    views.on_reset(val, tid, 0)
    slab_log, slab_info = max(
        cap.slabs, key=lambda e: int(np.asarray(e[0]["write"]).sum()))
    w_slab = int(np.asarray(slab_log["write"]).sum())
    us_slab, _ = timed(lambda: (views.on_slab(slab_log, slab_info),
                                views.proj)[1], reps=reps)
    us_slab *= 1e6
    rows.append((f"{lbl}/mv_apply_slab", us_slab, f"{w_slab}w"))

    w_sm = us_sm = 0
    if cap.masters:
        sm = max(cap.masters,
                 key=lambda s: int(np.asarray(s["log"]["write"]).sum()))
        w_sm = int(np.asarray(sm["log"]["write"]).sum())
        us_sm, _ = timed(lambda: (views.on_master(sm), views.proj)[1],
                         reps=reps)
        us_sm *= 1e6
        rows.append((f"{lbl}/mv_apply_master", us_sm, f"{w_sm}w"))

    # headline: writes applied per second of maintenance wall time
    writes_s = (w_slab + w_sm) / ((us_slab + us_sm) * 1e-6)
    rows.append((f"{lbl}/mv_apply_writes_per_s", 0.0, round(writes_s, 1)))

    # -- fence stamp + query serve ---------------------------------------
    proj = np.asarray(views.proj)
    us_stamp, _ = timed(lambda: views._aggregates(proj), reps=reps)
    rows.append((f"{lbl}/fence_stamp", us_stamp * 1e6, "4 aggregates"))
    us_serve, _ = timed(
        lambda: lane.serve(eng.committed_epoch) or {"epoch": 0}, reps=reps)
    rows.append((f"{lbl}/query_serve", us_serve * 1e6,
                 f"{len(lane.QUERIES)}q mix"))
    s = lane.summary()
    rows.append((f"{lbl}/q_p50_ms", 0.0, round(s["analytics_q_p50_ms"], 4)))
    rows.append((f"{lbl}/q_p99_ms", 0.0, round(s["analytics_q_p99_ms"], 4)))
    rows.append((f"{lbl}/mv_slabs", 0.0, s["analytics_mv_slabs"]))
    rows.append((f"{lbl}/mv_writes", 0.0, s["analytics_mv_writes"]))
    return rows, writes_s


def validate():
    """Bit-equality gates only: every fence stamp == recompute, and
    time-travel returns exactly the recorded stamps."""
    from repro.changelog import AnalyticsLane
    cfg, state, eng = _mk(2, 2, 96)
    lane = AnalyticsLane(cfg, stock_threshold=40, retain=4)
    assert lane.ensure_attached(eng)
    assert _drive(cfg, state, eng, lane, 2, 96), "replicas diverged"
    print("BIT-EQUAL OK mv == recompute at every fence, time-travel exact")


def main():
    import argparse
    import json

    from benchmarks.common import emit
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small shape + bit-equality + throughput floor (CI)")
    ap.add_argument("--validate", action="store_true",
                    help="bit-equality gates only")
    ap.add_argument("--bench-json", metavar="PATH", default=None,
                    help="write the snapshot, e.g. BENCH_analytics.json")
    args = ap.parse_args()
    if args.validate:
        validate()
        return
    rows, writes_s = run(smoke=args.smoke)
    print("name,us_per_call,derived")
    emit(rows)
    # the tracked claim: MV maintenance keeps up — apply throughput must
    # clear the collapse floor (measured on the heaviest captured events)
    assert writes_s >= FLOOR_WRITES_S, \
        f"MV apply throughput collapsed: {writes_s:.0f} < {FLOOR_WRITES_S}"
    if args.bench_json:
        bench = {
            "schema": SCHEMA,
            "smoke": bool(args.smoke),
            "floor_writes_per_s": FLOOR_WRITES_S,
            "mv_apply_writes_per_s": round(writes_s, 1),
            "rows": {r[0]: r[2] for r in rows},
            "us_per_call": {r[0]: round(r[1], 3) for r in rows if r[1]},
        }
        with open(args.bench_json, "w") as f:
            json.dump(bench, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.bench_json}")
    if args.smoke:
        print(f"SMOKE OK mv_apply_writes_per_s={writes_s:.0f} "
              f"(floor {FLOOR_WRITES_S:.0f})")


if __name__ == "__main__":
    main()

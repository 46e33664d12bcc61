"""Figure 11: throughput of STAR vs PB.OCC / Dist.OCC / Dist.S2PL on YCSB and
TPC-C, async (epoch group commit) and sync replication, varying the
cross-partition fraction.

Measured: per-txn CPU cost + OCC retry factor from the real executors on this
host.  Modeled: 4-node cluster wall clock through the calibrated network
envelope (cost_model.py).  Paper claims checked: STAR ~= Dist.* at P=0;
STAR > both at P>=10%; up to ~10x at high P; PB.OCC flat in P.

``--mix full`` additionally MEASURES the full five-transaction TPC-C mix
(45/43/4/4/4 over the ordered-index storage engine) end to end through
``StarEngine.run_epoch`` and reports its throughput alongside the paper's
NewOrder+Payment mix — the workload the paper could not run:

    PYTHONPATH=src python -m benchmarks.fig11_throughput --mix full [--smoke]
"""
import time

from benchmarks.common import get_calibration, get_envelope_calibration
from repro.baselines.cost_model import (dist_throughput, pb_occ_throughput,
                                        star_throughput)


def measure_tpcc_mix(mix: str, n_txns: int = 512, epochs: int = 4,
                     smoke: bool = False, kernel: str = "jnp"):
    """Run the REAL engine over `mix` and return measured throughput rows.

    Wall clock covers the two device phases + fences (jit warm); throughput
    is committed transactions per second of engine time on this host.
    ``kernel`` selects the executor dispatch: "jnp" (reference) or "pallas"
    (fused OCC kernels — interpreted off-TPU, bit-identical results).
    """
    import numpy as np
    from repro.core.engine import StarEngine
    from repro.db import tpcc
    from repro.obs import MetricsRegistry

    if smoke:
        n_txns, epochs = 128, 2
    cfg = tpcc.TPCCConfig(n_partitions=4, n_items=1000 if smoke else 4000,
                          cust_per_district=100, order_ring=128, mix=mix,
                          delivery_gen_lag=n_txns)
    state = tpcc.TPCCState(cfg)
    rng = np.random.default_rng(0)
    init = tpcc.init_values(cfg, rng, state=state)
    eng = StarEngine(cfg.n_partitions, cfg.rows_per_partition, init_val=init,
                     indexes=tpcc.index_specs(cfg) if mix == "full" else None,
                     kernel=kernel)
    reg = MetricsRegistry()
    reg.register_object("engine", eng.stats)
    wb = tpcc.make_batch(cfg, state, n_txns, seed=1000)
    wm = eng.run_epoch(wb)                               # warm jit
    if mix == "full":      # resolve the warm batch's Delivery claims too
        tpcc.apply_consume_feedback(state, wb, wm)
    reg.snapshot(0)                  # post-warm baseline time-series point
    warm = eng.stats.part_time_s + eng.stats.sm_time_s   # exclude jit compile
    warm_sm, warm_rounds = eng.stats.sm_time_s, eng.stats.sm_rounds
    t0 = time.perf_counter()
    committed = 0
    for ep in range(epochs):
        batch = tpcc.make_batch(cfg, state, n_txns, seed=ep)
        m = eng.run_epoch(batch)
        committed += m["committed_single"] + m["committed_cross"]
        if mix == "full":        # consume feedback: re-queue skipped districts
            tpcc.apply_consume_feedback(state, batch, m)
        reg.snapshot(ep + 1)
    elapsed = eng.stats.part_time_s + eng.stats.sm_time_s - warm
    wall = time.perf_counter() - t0
    assert eng.replica_consistent(), "replica diverged under measurement"
    thr = committed / max(elapsed, 1e-9)
    tag = f"{mix}_{kernel}"
    rows = [
        (f"fig11/tpcc_measured_mix_{tag}_txn_s", 1e6 * wall / max(committed, 1),
         round(thr)),
        (f"fig11/tpcc_measured_mix_{tag}_committed", 0.0, committed),
        (f"fig11/tpcc_measured_mix_{tag}_consume_skips", 0.0,
         eng.stats.consume_skips),
    ]
    if eng.stats.sm_rounds > warm_rounds:     # per-round OCC kernel time
        sm_us = (1e6 * (eng.stats.sm_time_s - warm_sm)
                 / (eng.stats.sm_rounds - warm_rounds))
        # stamp the measured value into the derived column too: the BENCH
        # snapshot's "rows" dict keeps derived values only, and a literal 0
        # here once shipped `sm_round_us: 0` per mix while the headline
        # showed the real number
        assert sm_us > 0.0, (mix, kernel, eng.stats)
        rows.append((f"fig11/tpcc_measured_mix_{tag}_sm_round_us",
                     sm_us, round(sm_us, 3)))
    # §5 op-stream shipping split: fence-exposed bytes (for BENCH snapshot)
    rows.append((f"fig11/tpcc_measured_mix_{tag}_op_bytes_fence", 0.0,
                 int(eng.stats.op_bytes_fence)))
    rows.append((f"fig11/tpcc_measured_mix_{tag}_op_bytes_overlapped", 0.0,
                 int(eng.stats.op_bytes_overlapped)))
    # phase breakdown off the registry time series (post-warm baseline vs
    # final snapshot), not hand-merged stats fields
    s0, s1 = reg.snapshots[0], reg.snapshots[-1]
    t_part = s1["engine.part_time_s"] - s0["engine.part_time_s"]
    t_sm = s1["engine.sm_time_s"] - s0["engine.sm_time_s"]
    t_fence = s1["engine.fence_time_s"] - s0["engine.fence_time_s"]
    tot = max(t_part + t_sm + t_fence, 1e-9)
    for ph, t in (("part", t_part), ("sm", t_sm), ("fence", t_fence)):
        rows.append((f"fig11/tpcc_measured_mix_{tag}_phase_{ph}_pct", 0.0,
                     round(100.0 * t / tot, 1)))
    return rows


def measure_read_tier(n_txns: int = 2048, epochs: int = 2, smoke: bool = False,
                      max_staleness: int = 2):
    """Full-mix TPC-C at equal offered load, read tier OFF vs ON.

    OFF: every transaction (including the read-only OrderStatus/StockLevel
    ~8%) burns a partitioned/OCC slot through ``run_epoch``.  ON: the same
    per-epoch request stream (identical seeds; read-only txns write nothing
    so the committed DB state evolves bit-identically) is split — writes run
    through the engine, declared-read-only txns are served lock-free from
    the replica snapshot catalog between fences.  Reports the read/write
    split and the combined-throughput comparison the read tier exists for.

    The full-scale default of 2048-txn epochs puts the engine in the
    work-dominated regime where a read-only txn's marginal slot cost is
    real (~ms); at smoke scale the epoch is fixed-overhead-bound and the
    on-vs-off difference sits inside host noise, so smoke only gates the
    scale-independent invariants (see main()).
    """
    import numpy as np
    from repro.core.engine import StarEngine
    from repro.db import tpcc
    from repro.reads import SnapshotCatalog, SnapshotReadExecutor

    if smoke:
        n_txns, epochs = 128, 2

    def build():
        cfg = tpcc.TPCCConfig(n_partitions=4,
                              n_items=1000 if smoke else 4000,
                              cust_per_district=100, order_ring=128,
                              mix="full", delivery_gen_lag=n_txns)
        state = tpcc.TPCCState(cfg)
        rng = np.random.default_rng(0)
        init = tpcc.init_values(cfg, rng, state=state)
        eng = StarEngine(cfg.n_partitions, cfg.rows_per_partition,
                         init_val=init, indexes=tpcc.index_specs(cfg))
        return cfg, state, eng

    def run_pass(serve_reads: bool):
        """One full pass over the offered stream.  Both passes replay the
        same seeds over a fresh engine+state, so the committed DB evolves
        bit-identically (read-only txns write nothing) and every per-epoch
        batch shape is deterministic — running each pass TWICE and timing
        only the second run keeps jit compiles and other one-time costs
        out of the measured region for both sides equally."""
        cfg, state, eng = build()
        execu = SnapshotReadExecutor() if serve_reads else None
        catalog = (SnapshotCatalog(cfg.n_partitions, retain=max_staleness + 2)
                   if serve_reads else None)
        wb = tpcc.make_batch(cfg, state, n_txns, seed=1000)
        tpcc.apply_consume_feedback(state, wb, eng.run_epoch(wb))
        if serve_reads:
            for v in eng.read_views():
                catalog.stamp(v)
        warm = eng.stats.part_time_s + eng.stats.sm_time_s
        committed = reads = 0
        read_s = 0.0
        for ep in range(epochs):
            raw = tpcc.make_raw(cfg, state, n_txns, np.random.default_rng(ep))
            ro = raw["read_only"]
            if serve_reads:     # writes only reach the engine (thinner T)
                batch = tpcc.make_batch(
                    cfg, state, 0, raw={k: v[~ro] for k, v in raw.items()})
            else:
                batch = tpcc.make_batch(cfg, state, 0, raw=raw)
            m = eng.run_epoch(batch)
            committed += m["committed_single"] + m["committed_cross"]
            tpcc.apply_consume_feedback(state, batch, m)
            if not serve_reads:
                continue
            for v in eng.read_views():   # fence passed: refresh catalog
                catalog.stamp(v)
            # serve the read lane: group by home partition onto the
            # least-loaded fresh-enough replica, one batched gather each
            sel = np.nonzero(ro)[0]
            homes = raw["home"][sel]
            t0 = time.perf_counter()
            for p in np.unique(homes):
                grp = sel[homes == p]
                _ent, _ep, snap, arow = catalog.choose(
                    int(p), max_staleness, weight=len(grp))
                out = execu.run(snap, np.full(len(grp), arow, np.int32),
                                raw["rows"][grp], raw["kinds"][grp],
                                raw["deltas"][grp])
                np.asarray(out["val"])        # block until served
            read_s += time.perf_counter() - t0
            reads += len(sel)
        assert eng.replica_consistent()
        return (committed, reads,
                eng.stats.part_time_s + eng.stats.sm_time_s - warm, read_s)

    # One untimed shape-warm run per pass (absorbs jit compiles), then
    # best-of-N timed runs, INTERLEAVED so slow host stretches (frequency
    # drift, scheduler contention) land on both sides: min-time filters
    # the additive noise that otherwise swamps the ~read-share-sized
    # structural difference.
    reps = 3
    run_pass(False)
    run_pass(True)
    offs, ons = [], []
    for _ in range(reps):
        offs.append(run_pass(False))
        ons.append(run_pass(True))
    off_committed = offs[0][0]
    off_s = min(r[2] for r in offs)
    on_write, on_read = ons[0][0], ons[0][1]
    write_s = min(r[2] for r in ons)
    read_s = min(r[3] for r in ons)

    thr_off = off_committed / max(off_s, 1e-9)
    thr_on = (on_write + on_read) / max(write_s + read_s, 1e-9)
    return [
        ("fig11/tpcc_read_tier_off_txn_s", 1e6 * off_s / max(off_committed, 1),
         round(thr_off)),
        ("fig11/tpcc_read_tier_on_txn_s", 1e6 * (write_s + read_s)
         / max(on_write + on_read, 1), round(thr_on)),
        ("fig11/tpcc_read_tier_write_txn_s", 0.0,
         round(on_write / max(write_s, 1e-9))),
        ("fig11/tpcc_read_tier_read_txn_s", 0.0,
         round(on_read / max(read_s, 1e-9))),
        ("fig11/tpcc_read_tier_off_committed", 0.0, int(off_committed)),
        ("fig11/tpcc_read_tier_on_committed", 0.0, int(on_write + on_read)),
        ("fig11/tpcc_read_tier_read_served", 0.0, int(on_read)),
        ("fig11/tpcc_read_tier_speedup_pct", 0.0,
         round(100.0 * (thr_on / max(thr_off, 1e-9) - 1.0), 1)),
    ]


def run(mix: str | None = None, smoke: bool = False, kernel: str = "jnp"):
    rows = []
    if mix is not None:
        # measure the requested mix; "full" also measures the paper's
        # NewOrder+Payment mix alongside for direct comparison
        rows += measure_tpcc_mix(mix, smoke=smoke, kernel=kernel)
        if mix == "full":
            rows += measure_tpcc_mix("standard2", smoke=smoke, kernel=kernel)
            rows += measure_read_tier(smoke=smoke)
    if smoke:
        return rows
    n = 4
    for wl in ("ycsb", "tpcc"):
        cal = get_calibration(wl)
        us = cal.t_cross_cpu * 1e6
        for sync in (False, True):
            tag = "sync" if sync else "async"
            for P in (0.0, 0.1, 0.3, 0.5, 0.7, 0.9):
                star = star_throughput(n, P, cal, sync_replication=sync)
                pb = pb_occ_throughput(P, cal, sync_replication=sync)
                occ = dist_throughput(n, P, cal, "occ", sync_replication=sync)
                s2pl = dist_throughput(n, P, cal, "s2pl", sync_replication=sync)
                rows += [
                    (f"fig11/{wl}_{tag}_P{P:g}_star", us, round(star)),
                    (f"fig11/{wl}_{tag}_P{P:g}_pb_occ", us, round(pb)),
                    (f"fig11/{wl}_{tag}_P{P:g}_dist_occ", us, round(occ)),
                    (f"fig11/{wl}_{tag}_P{P:g}_dist_s2pl", us, round(s2pl)),
                ]
        # claim checks at P = 10% (async) — host calibration
        star10 = star_throughput(n, 0.1, cal)
        rows.append((f"fig11/{wl}_claim_star_over_dist_occ_P10", 0.0,
                     round(star10 / dist_throughput(n, 0.1, cal, "occ"), 2)))
        rows.append((f"fig11/{wl}_claim_star_over_pb_P90", 0.0,
                     round(star_throughput(n, 0.9, cal)
                           / pb_occ_throughput(0.9, cal), 2)))
        # paper-envelope calibration (Silo-scale per-txn CPU)
        env = get_envelope_calibration(wl)
        for P in (0.0, 0.1, 0.5, 0.9):
            rows += [
                (f"fig11/{wl}_env_P{P:g}_star", 0.0,
                 round(star_throughput(n, P, env))),
                (f"fig11/{wl}_env_P{P:g}_pb_occ", 0.0,
                 round(pb_occ_throughput(P, env))),
                (f"fig11/{wl}_env_P{P:g}_dist_occ", 0.0,
                 round(dist_throughput(n, P, env, "occ"))),
                (f"fig11/{wl}_env_P{P:g}_dist_s2pl", 0.0,
                 round(dist_throughput(n, P, env, "s2pl"))),
            ]
        rows.append((f"fig11/{wl}_env_claim_star_over_dist_occ_P10", 0.0,
                     round(star_throughput(n, 0.1, env)
                           / dist_throughput(n, 0.1, env, "occ"), 2)))
        rows.append((f"fig11/{wl}_env_claim_star_over_dist_sync_P10", 0.0,
                     round(star_throughput(n, 0.1, env)
                           / dist_throughput(n, 0.1, env, "occ",
                                             sync_replication=True), 2)))
        rows.append((f"fig11/{wl}_env_claim_star_over_pb2node", 0.0,
                     round(star_throughput(n, 0.1, env)
                           / pb_occ_throughput(0.1, env), 2)))
    return rows


def main():
    import argparse

    from benchmarks.common import emit
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mix", choices=["full", "standard2"], default=None,
                    help="also MEASURE this TPC-C mix through the engine")
    ap.add_argument("--kernel", choices=["jnp", "pallas"], default="jnp",
                    help="executor dispatch for the measured mixes: jnp "
                    "reference or the fused Pallas OCC kernels "
                    "(interpret mode off-TPU; bit-identical)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny scale, measured rows only; fails the build "
                    "when throughput collapses (CI regression gate)")
    ap.add_argument("--bench-json", metavar="PATH", default=None,
                    help="write the measured-row snapshot (full-mix txn/s, "
                    "read-tier split, SM round us, fence-exposed bytes) as "
                    "JSON, e.g. BENCH_fig11.json")
    args = ap.parse_args()
    rows = run(mix=args.mix or ("full" if args.smoke else None),
               smoke=args.smoke, kernel=args.kernel)
    print("name,us_per_call,derived")
    emit(rows)
    if args.bench_json:
        import json
        d = {r[0]: r[2] for r in rows if r[0].startswith("fig11/tpcc_")}
        us = {r[0]: round(r[1], 3) for r in rows
              if r[0].startswith("fig11/tpcc_") and r[1]}
        k = args.kernel
        bench = {
            "schema": 1,
            "full_mix_txn_s": d.get(f"fig11/tpcc_measured_mix_full_{k}_txn_s"),
            "read_tier_on_txn_s": d.get("fig11/tpcc_read_tier_on_txn_s"),
            "read_tier_off_txn_s": d.get("fig11/tpcc_read_tier_off_txn_s"),
            "read_txn_s": d.get("fig11/tpcc_read_tier_read_txn_s"),
            "write_txn_s": d.get("fig11/tpcc_read_tier_write_txn_s"),
            "sm_round_us": us.get(
                f"fig11/tpcc_measured_mix_full_{k}_sm_round_us"),
            "fence_exposed_bytes": d.get(
                f"fig11/tpcc_measured_mix_full_{k}_op_bytes_fence"),
            "rows": d, "us_per_call": us,
        }
        with open(args.bench_json, "w") as f:
            json.dump(bench, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.bench_json}")
    if args.smoke:
        thr = {r[0]: r[2] for r in rows
               if r[0].endswith("_txn_s") or r[0].endswith("_committed")}
        rates = {k: v for k, v in thr.items() if k.endswith("_txn_s")}
        commits = {k: v for k, v in thr.items() if k.endswith("_committed")}
        # loose floors: catch collapse/regression-to-zero, not host speed
        assert rates and all(v > 5 for v in rates.values()), \
            f"throughput collapsed: {thr}"
        assert all(v > 100 for v in commits.values()), thr
        # per-mix SM-round attribution must survive into the derived column
        # (regression gate for the sm_round_us: 0 snapshot bug)
        sm_rows = {r[0]: r[2] for r in rows
                   if r[0].endswith("_sm_round_us")}
        assert sm_rows and all(v > 0 for v in sm_rows.values()), \
            f"per-mix sm_round attribution lost: {sm_rows}"
        if "fig11/tpcc_read_tier_read_txn_s" in rates:
            # Scale-independent invariants only: serving a read from a
            # snapshot must be much cheaper than committing a write through
            # the engine, and on-vs-off must not collapse.  The strict
            # on > off comparison is a FULL-scale result (2048-txn epochs,
            # work-dominated regime) — at smoke scale both passes are
            # fixed-overhead-bound and the ~0% difference is host noise.
            assert (rates["fig11/tpcc_read_tier_read_txn_s"]
                    > rates["fig11/tpcc_read_tier_write_txn_s"]), \
                f"snapshot reads slower than engine writes: {thr}"
            spd = next(r[2] for r in rows
                       if r[0] == "fig11/tpcc_read_tier_speedup_pct")
            assert spd > -15, \
                f"read tier collapsed vs baseline: {spd}% {thr}"
        print("SMOKE OK "
              + " ".join(k.split("tpcc_")[1] for k in sorted(rates)))


if __name__ == "__main__":
    main()

"""Bring-up smoke of the STAR served path on TPU (a smoke run, not a benchmark).

    python chip_smoke.py              # one chip: TxnService over StarEngine
    python chip_smoke.py --chips 4    # four chips: ClusterTxnService + kill

Both paths run TPC-C's full 45/43/4/4/4 mix at spec per-warehouse widths
(``TPCCConfig`` defaults: 100,000 items and stock rows, 10 districts x
3,000 customers, 10%/15% remote NewOrder/Payment) with 16 warehouses, one
per partition, and the three ordered indexes.  The one cut is
``order_ring=256``, forced by the ``orders_by_cust`` key bits.

One chip: an open-loop client serves through ``TxnService`` for a few
seconds (consume feedback wired), then checks committed > 0, replica
consistency (records + indexes) and zero index overflow; then the same
seeded ``make_batch`` epochs run through ``StarEngine.run_epoch`` on the TPU
and on the host CPU, and records, TIDs, every index segment and the
per-epoch commit counts must be bit-equal.

``--chips 4``: one STAR node per chip over a ``("part",)`` mesh (full
replica on the master chip, physical secondaries), served through
``ClusterTxnService`` while a ``FaultInjector`` kills node 2 mid-run; §4.5
recovery must run and leave the replicas consistent.  Then the same seeded
epochs on ``ClusterRuntime`` must be bit-equal to ``StarEngine`` on one chip.

Without a TPU the script exits non-zero before any work.  The last line of
standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

N_WAREHOUSES = 16
ORDER_RING = 256           # orders_by_cust key bits cap the full mix here
EPOCH_TXNS = 1024          # seeded parity epochs: make_batch(1024 txns)
PARITY_T = 128             # pinned queue slots: one program for every epoch
PARITY_EPOCHS = 3
SERVE_S = 5.0
RATE_TXN_S = 2000.0
SLOTS_PER_PARTITION = 64   # served batch: 16 x 64 queue slots ...
MASTER_LANES = 128         # ... plus 128 cross-partition lanes
KILL_NODE, KILL_EPOCH = 2, 5


def log(msg: str) -> None:
    print(msg, flush=True)


def tpcc_config(n_partitions: int = N_WAREHOUSES):
    from repro.db import tpcc
    return tpcc.TPCCConfig(n_partitions=n_partitions, mix="full",
                           order_ring=ORDER_RING)


def seeded_state(cfg, seed: int):
    """A fresh host mirror and the initial (P, R, C) records for it."""
    import numpy as np
    from repro.db import tpcc
    state = tpcc.TPCCState(cfg)
    init = tpcc.init_values(cfg, np.random.default_rng(seed), state=state)
    return state, init


def seeded_batches(cfg, seed: int, n_epochs: int = PARITY_EPOCHS,
                   n_txns: int = EPOCH_TXNS, T: int = PARITY_T):
    """The initial records and ``n_epochs`` fixed make_batch epochs, drawn
    once so every engine under comparison executes identical input."""
    from repro.db import tpcc
    state, init = seeded_state(cfg, seed)
    return init, [tpcc.make_batch(cfg, state, n_txns, seed=seed + 1 + e, T=T)
                  for e in range(n_epochs)]


def star_engine(cfg, init):
    from repro.core.engine import StarEngine
    from repro.db import tpcc
    return StarEngine(cfg.n_partitions, cfg.rows_per_partition,
                      init_val=init, indexes=tpcc.index_specs(cfg),
                      kernel="jnp")


def host_state(val, tid, indexes) -> dict:
    """Records, TIDs and every index segment as host arrays."""
    import numpy as np
    out = {"val": np.asarray(val), "tid": np.asarray(tid)}
    for i, ix in enumerate(indexes):
        for k in ("key", "prow", "tid"):
            out[f"idx{i}.{k}"] = np.asarray(ix[k])
    return out


def run_star(cfg, init, batches, device) -> dict:
    """Run the seeded epochs through StarEngine.run_epoch on ``device``."""
    import jax
    with jax.default_device(device):
        eng = star_engine(cfg, init)
        commits = [(m["committed_single"], m["committed_cross"])
                   for m in (eng.run_epoch(b) for b in batches)]
        out = host_state(eng.store.val, eng.store.tid, eng.store.indexes)
        consistent = eng.replica_consistent()
        overflow = eng.stats.index_overflow
    return {"commits": commits, "state": out, "consistent": consistent,
            "overflow": overflow}


def diff_states(a: dict, b: dict) -> list[str]:
    """Names of the arrays that are not bit-equal."""
    import numpy as np
    return [k for k in a if not np.array_equal(a[k], b[k])]


def feedback_for(state):
    from repro.db import tpcc
    return lambda b, m: tpcc.apply_consume_feedback(state, b, m)


def open_loop_client(cfg, state, seed: int):
    from repro.service import OpenLoopClient, TPCCSource
    return OpenLoopClient(TPCCSource(cfg, state=state, seed=seed + 1),
                          rate_txn_s=RATE_TXN_S, seed=seed + 2)


def serve(svc, serve_s: float) -> dict:
    """Warm up (compiles every program the fixed batch shapes use), then
    serve ``serve_s`` seconds of arrivals and drain; report the service
    summary and the engine's phase times after warm-up (host clock)."""
    stats = svc.engine.stats
    t0 = time.perf_counter()
    svc.warmup()
    t1 = time.perf_counter()
    e0, p0, s0, f0 = (stats.epochs, stats.part_time_s, stats.sm_time_s,
                      stats.fence_time_s)
    out = svc.run(duration_s=serve_s, warmup_epochs=0)
    t2 = time.perf_counter()
    log(f"smoke served: committed={out['committed']} epochs={out['epochs']} "
        f"txn/s={out['throughput_txn_s']} p50_ms={out['p50_ms']} "
        f"p99_ms={out['p99_ms']} shed={out['shed']} warmup_s={t1 - t0} "
        f"serve_wall_s={t2 - t1}  (smoke run, not a benchmark)")
    log(f"smoke engine phases after warm-up, host clock, "
        f"{stats.epochs - e0} committed epochs: partitioned_s="
        f"{stats.part_time_s - p0} single_master_s={stats.sm_time_s - s0} "
        f"fence_s={stats.fence_time_s - f0} epoch_s={out['epoch_time_s']} "
        f"ingest_overlap_s={out['ingest_overlap_s']}")
    return out


# ---------------------------------------------------------------------------
def serve_one_chip(cfg, seed: int, serve_s: float = SERVE_S) -> bool:
    """TxnService over StarEngine: serve, then check the replicas."""
    from repro.service import TxnService
    state, init = seeded_state(cfg, seed)
    eng = star_engine(cfg, init)
    svc = TxnService(eng, [open_loop_client(cfg, state, seed)],
                     slots_per_partition=SLOTS_PER_PARTITION,
                     master_lanes=MASTER_LANES, feedback=feedback_for(state))
    out = serve(svc, serve_s)
    consistent = eng.replica_consistent()
    log(f"smoke replicas consistent (records + {len(eng.store.indexes)} "
        f"indexes): {consistent}; index_overflow="
        f"{eng.stats.index_overflow}; consume_skips="
        f"{eng.stats.consume_skips}")
    return out["committed"] > 0 and consistent \
        and eng.stats.index_overflow == 0


def parity_cpu(cfg, device, seed: int) -> bool:
    """The same seeded epochs on the TPU and on the host CPU, bit for bit."""
    import jax
    init, batches = seeded_batches(cfg, seed)
    t0 = time.perf_counter()
    tpu = run_star(cfg, init, batches, device)
    t1 = time.perf_counter()
    cpu = run_star(cfg, init, batches, jax.devices("cpu")[0])
    t2 = time.perf_counter()
    diff = diff_states(tpu["state"], cpu["state"])
    same = not diff and tpu["commits"] == cpu["commits"]
    log(f"parity tpu-vs-cpu: epochs={len(batches)} commits tpu="
        f"{tpu['commits']} cpu={cpu['commits']} arrays="
        f"{len(tpu['state'])} differing={diff} bit_equal={same} "
        f"consistent tpu={tpu['consistent']} cpu={cpu['consistent']} "
        f"wall_s tpu={t1 - t0} cpu={t2 - t1}")
    return same and tpu["consistent"] and cpu["consistent"] \
        and tpu["overflow"] == 0


def serve_cluster(cfg, mesh, seed: int, serve_s: float = SERVE_S) -> bool:
    """ClusterTxnService over ClusterRuntime with node KILL_NODE killed at
    epoch KILL_EPOCH: §4.5 recovery runs and the replicas agree after."""
    from repro.cluster import ClusterRuntime, ClusterTxnService
    from repro.core.fault import FaultInjector
    from repro.db import tpcc
    state, init = seeded_state(cfg, seed)
    inj = FaultInjector()
    inj.schedule_kill(node=KILL_NODE, epoch=KILL_EPOCH)
    rt = ClusterRuntime(mesh, cfg.n_partitions, cfg.rows_per_partition,
                        init_val=init, indexes=tpcc.index_specs(cfg),
                        injector=inj)
    svc = ClusterTxnService(
        rt, [open_loop_client(cfg, state, seed)],
        slots_per_partition=SLOTS_PER_PARTITION, master_lanes=MASTER_LANES,
        feedback=feedback_for(state))
    out = serve(svc, serve_s)
    consistent = rt.replica_consistent()
    log(f"smoke cluster: nodes={rt.n_nodes} ppn={rt.topology.ppn} "
        f"node_committed={out['node_committed']} "
        f"secondaries={rt.eng.secondary}")
    for ev in svc.recovery_events:
        src = ("disk" if ev.reloaded_from_disk
               else "secondary copy" if ev.restored_from_secondary
               else "full replica")
        log(f"smoke recovery: epoch={ev.epoch} failed={list(ev.failed)} "
            f"case={ev.case.name} run_mode={ev.run_mode} restored_from="
            f"{src} t_recovery_s={ev.t_recovery_s}")
    log(f"smoke cluster replicas consistent after recovery (records + "
        f"indexes + secondaries): {consistent}; index_overflow="
        f"{rt.stats.index_overflow}")
    return (out["committed"] > 0 and out["recoveries"] == 1 and consistent
            and rt.stats.index_overflow == 0)


def parity_cluster(cfg, mesh, device, seed: int) -> bool:
    """The same seeded epochs on ClusterRuntime over the mesh and on
    StarEngine on one chip: commit counts per epoch, and the full replica's
    records, TIDs and index segments, bit for bit."""
    from repro.cluster import ClusterRuntime
    from repro.db import tpcc
    init, batches = seeded_batches(cfg, seed)
    star = run_star(cfg, init, batches, device)
    rt = ClusterRuntime(mesh, cfg.n_partitions, cfg.rows_per_partition,
                        init_val=init, indexes=tpcc.index_specs(cfg))
    commits = [(m["committed_single"], m["committed_cross"])
               for m in (rt.run_epoch(b) for b in batches)]
    consistent = rt.replica_consistent()
    full = host_state(rt.eng.full_val, rt.eng.full_tid, rt.eng.full_idx)
    diff = diff_states(full, star["state"])
    same = not diff and commits == star["commits"]
    log(f"parity cluster-vs-StarEngine: epochs={len(batches)} commits "
        f"cluster={commits} star={star['commits']} differing={diff} "
        f"bit_equal={same} cluster_consistent={consistent}")
    return same and consistent and star["consistent"]


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip cluster path")
    args = ap.parse_args(argv)

    # the CPU backend stays available beside the TPU for the parity phase
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    import jax
    from repro import compile_cache

    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; refusing to run on "
              f"{dev.platform}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    log(f"compile cache: {compile_cache.enable()}")

    seed = 0
    cfg = tpcc_config()
    log(f"config: TPC-C full mix, {cfg.n_partitions} warehouses, "
        f"{cfg.n_items} items, {cfg.cust_per_district} customers/district, "
        f"order_ring={cfg.order_ring}, {cfg.rows_per_partition} rows x "
        f"10 int32 words per warehouse")
    checks = {}
    if args.chips == 1:
        checks["served"] = serve_one_chip(cfg, seed)
        log(f"smoke peak_bytes_in_use after serving: {peak_bytes(dev)}")
        gc.collect()        # engines hold reference cycles (subscribers)
        checks["parity_cpu"] = parity_cpu(cfg, dev, seed + 10)
    else:
        mesh = jax.make_mesh((args.chips,), ("part",),
                             devices=devices[:args.chips])
        checks["served_with_kill"] = serve_cluster(cfg, mesh, seed)
        log("smoke peak_bytes_in_use per chip after serving: "
            f"{[peak_bytes(d) for d in devices[:args.chips]]}")
        gc.collect()        # engines hold reference cycles (subscribers)
        checks["parity_star"] = parity_cluster(cfg, mesh, dev, seed + 10)
    log(f"checks: {checks}")
    if not all(checks.values()):
        print(f"chip_smoke: failed {[k for k, v in checks.items() if not v]}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Serve a live YCSB stream through the online transaction service.

Open-loop Poisson clients feed admission-controlled bounded queues; the
epoch batcher double-buffers host batch formation against device execution;
every transaction is stamped enqueue→commit-fence.  Three scenarios:

  1. steady state  — sustained txn/s + measured p50/p99 latency;
  2. burst + skew  — bursty arrivals over a Zipfian hot-key workload;
  3. overload      — 20x capacity: admission sheds, queues stay bounded.

    PYTHONPATH=src python examples/serve_txn.py [--quick]
"""
import sys

import numpy as np

from repro import compile_cache
from repro.core.engine import StarEngine
from repro.db import ycsb
from repro.service import (AdmissionConfig, OpenLoopClient, TxnService,
                           YCSBSource)

compile_cache.enable()
QUICK = "--quick" in sys.argv
DUR = 0.5 if QUICK else 2.0


def serve(name, cfg, rate, process="poisson", policy="shed", duration=DUR,
          part_cap=256, master_cap=512):
    eng = StarEngine(cfg.n_partitions, cfg.records_per_partition)
    client = OpenLoopClient(YCSBSource(cfg, seed=1), rate_txn_s=rate,
                            process=process, seed=7)
    svc = TxnService(eng, [client],
                     AdmissionConfig(part_cap, master_cap, policy),
                     slots_per_partition=32, master_lanes=32)
    out = svc.run(duration_s=duration)
    assert eng.replica_consistent(), "replica diverged!"
    print(f"\n=== {name} (offered {rate:.0f} txn/s, {process}) ===")
    print(f"  sustained    : {out['throughput_txn_s']:8.0f} txn/s "
          f"({out['committed']} committed / {out['epochs']} epochs)")
    print(f"  latency      : p50 {out['p50_ms']:6.1f} ms   "
          f"p99 {out['p99_ms']:6.1f} ms   p99.9 {out['p999_ms']:6.1f} ms")
    print(f"  admission    : {out['admitted']} admitted, {out['shed']} shed, "
          f"{out['backpressured']} backpressured, "
          f"{out['rerouted']} rerouted")
    print(f"  queue depth  : part≤{out['max_part_depth']} "
          f"master≤{out['max_master_depth']}   "
          f"ingest overlapped {1e3 * out['ingest_overlap_s']:.0f} ms "
          f"under device exec")
    return out


base = ycsb.YCSBConfig(n_partitions=4, records_per_partition=1024,
                       cross_ratio=0.10)

# 1. steady state: the headline numbers
steady = serve("steady state", base, rate=1500.0)

# 2. bursty arrivals on a hot-key Zipfian mix
skew = ycsb.YCSBConfig(n_partitions=4, records_per_partition=1024,
                       cross_ratio=0.10, zipf_theta=0.9)
serve("burst + zipf(0.9) skew", skew, rate=1000.0, process="bursty")

# 3. overload: 20x the sustainable rate — shed, never unbounded
over = serve("overload 20x", base, rate=30_000.0, part_cap=64, master_cap=128,
             duration=DUR / 2)
assert over["shed"] > 0, "overload must shed"
assert over["max_part_depth"] <= 64 and over["max_master_depth"] <= 128

print("\nall scenarios served; replicas bit-identical at every fence ✓")

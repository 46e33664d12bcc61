"""The paper end-to-end: STAR on TPC-C — phase switching, hybrid replication
savings, epoch fences, failure + recovery across all four §4.5.3 cases.

    PYTHONPATH=src python examples/star_tpcc_demo.py
"""
import numpy as np

from repro import compile_cache
from repro.core.engine import StarEngine
from repro.core.fault import ClusterConfig, classify_failure
from repro.db import tpcc

compile_cache.enable()
cfg = tpcc.TPCCConfig(n_partitions=4, n_items=2000, cust_per_district=200,
                      order_ring=128)
state = tpcc.TPCCState(cfg)
rng = np.random.default_rng(0)
eng = StarEngine(cfg.n_partitions, cfg.rows_per_partition,
                 init_val=tpcc.init_values(cfg, rng),
                 cluster=ClusterConfig(f=2, k=6, n_partitions=6))

for epoch in range(4):
    m = eng.run_epoch(tpcc.make_batch(cfg, state, 256, seed=epoch))
    print(f"epoch {epoch}: NewOrder+Payment singles={m['committed_single']} "
          f"cross={m['committed_cross']} tau_p={m['tau_p_ms']:.1f}ms "
          f"tau_s={m['tau_s_ms']:.1f}ms")

s = eng.stats
print(f"\nhybrid replication: {s.op_bytes_hybrid/1e3:.1f} KB shipped vs "
      f"{s.value_bytes_if_not_hybrid/1e3:.1f} KB value-replicated "
      f"({s.value_bytes_if_not_hybrid/max(s.op_bytes_hybrid,1):.1f}x saving)")
assert eng.replica_consistent()
print("replica consistent ✓")

print("\nfailure-case classification (f=2, k=6, paper §4.5.3):")
for failed, label in [({2}, "one partial node"), ({0, 1}, "both full nodes"),
                      (set(range(2, 8)), "all partial nodes"),
                      (set(range(8)), "everything")]:
    c = classify_failure(eng.cluster, failed)
    print(f"  fail {sorted(failed)} -> case {c.value} ({c.name})")

plan = eng.inject_failure({3})
print(f"\ninjected failure -> {plan.case.name}, run_mode={plan.run_mode}, "
      f"remastered {len(plan.remaster)} partitions")
eng.run_epoch(tpcc.make_batch(cfg, state, 128, seed=999))
assert eng.replica_consistent()
print("post-recovery epoch committed ✓")

# --------------------------------------------------------------------------
# the FULL five-transaction mix (45/43/4/4/4) — what the paper could not run:
# OrderStatus/Delivery/StockLevel ride the ordered secondary indexes
# --------------------------------------------------------------------------
print("\nfull TPC-C mix over the storage engine (ordered indexes):")
fcfg = tpcc.TPCCConfig(n_partitions=4, n_items=2000, cust_per_district=200,
                       order_ring=128, mix="full", delivery_gen_lag=256)
fstate = tpcc.TPCCState(fcfg)
frng = np.random.default_rng(1)
feng = StarEngine(fcfg.n_partitions, fcfg.rows_per_partition,
                  init_val=tpcc.init_values(fcfg, frng, state=fstate),
                  indexes=tpcc.index_specs(fcfg))
for epoch in range(4):
    m = feng.run_epoch(tpcc.make_batch(fcfg, fstate, 256, seed=epoch))
    print(f"epoch {epoch}: singles={m['committed_single']} "
          f"cross={m['committed_cross']} "
          f"net-fence={m['t_fence_net_s']*1e6:.0f}us")
assert feng.replica_consistent(), "records AND indexes replicate bit-equal"
undeliv = sum(len(q) for wq in fstate.undelivered for q in wq)
print(f"Delivery consumed oldest NEW-ORDERs via index scans "
      f"({undeliv} still undelivered, {feng.stats.consume_skips} skips)")
print("replica consistent (records + ordered indexes) ✓")

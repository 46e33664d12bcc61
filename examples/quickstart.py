"""Quickstart: the two faces of this framework in ~60 seconds on CPU.

1. The paper's engine — STAR phase-switched transactions on YCSB, with
   replica consistency verified through the replication streams.
2. The training runtime — a reduced LM trained a few steps under STAR-DP
   epoch-commit semantics, with a mid-run failure + revert.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax

from repro import compile_cache
from repro.configs import get_arch
from repro.core.engine import StarEngine
from repro.db import ycsb
from repro.launch.mesh import make_host_mesh
from repro.train.trainer import Trainer, TrainerConfig

compile_cache.enable()

# --- 1. STAR transaction engine ------------------------------------------
print("== STAR engine (YCSB, 4 partitions) ==")
cfg = ycsb.YCSBConfig(n_partitions=4, records_per_partition=1000)
eng = StarEngine(cfg.n_partitions, cfg.records_per_partition)
for epoch in range(3):
    m = eng.run_epoch(ycsb.make_batch(cfg, 256, seed=epoch))
    print(f" epoch {epoch}: singles={m['committed_single']} "
          f"cross={m['committed_cross']} tau_p={m['tau_p_ms']:.2f}ms "
          f"tau_s={m['tau_s_ms']:.2f}ms")
assert eng.replica_consistent()
print(" replica bit-consistent with master after fences ✓")

plan = eng.inject_failure({2})
print(f" injected failure of node 2 -> case {plan.case.name}, "
      f"mode {plan.run_mode}; reverted to last committed epoch")
eng.run_epoch(ycsb.make_batch(cfg, 256, seed=99))
assert eng.replica_consistent()
print(" recovered and committed a fresh epoch ✓")

# --- 2. STAR-DP trainer ---------------------------------------------------
print("== STAR-DP trainer (reduced glm4-9b) ==")
arch = get_arch("glm4-9b", smoke=True)
tr = Trainer(arch, make_host_mesh(), TrainerConfig(seq_len=64, batch=4,
                                                   steps_per_epoch=4))
m = tr.run(8)
print(f" step {m['step']}: loss {m['loss']:.3f}")
tr.run(2)                      # uncommitted progress...
back = tr.inject_failure()     # ...lost on failure; revert to the fence
print(f" failure -> reverted to committed step {back}")
m = tr.run(4)
print(f" resumed: step {m['step']} loss {m['loss']:.3f} ✓")

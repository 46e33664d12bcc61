"""The served path's programs compile for a described TPU v5e (no chip).

At the shapes ``chip_smoke.py`` runs — TPC-C full mix at spec widths, 16
warehouses, 1,024-transaction epochs (128 queue slots per partition, 128
cross-partition lanes) — each program must pass the TPU compiler and fit
one chip's 16 GB.  The one-chip programs are StarEngine's partitioned
phase, single-master phase and op-stream replay; the four-chip programs are
the cluster engine's ``shard_map`` partitioned phase and its secondary
replay, whose op-stream ship is a collective permute over ``part``.

The topology is described inside a fixture (never at import), and the
persistent compilation cache is off around the compiles: entries written
for a described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.core import replication as repl
from repro.core.cluster import MeshPrograms
from repro.core.partitioned import run_partitioned
from repro.core.single_master import run_single_master
from repro.db import tpcc
from repro.storage.index import make_index

HBM_BYTES = 16 * 10**9          # one v5e chip
N_WAREHOUSES, T_SLOTS, LANES, N_SLABS, ROUNDS = 16, 128, 128, 4, 16
# the ycsb-16p deployment: 16 partitions of 200,000 rows of 25 int32 words,
# 64 queue slots per partition, 10 ops per transaction
YCSB_P, YCSB_R, YCSB_C, YCSB_T, YCSB_M = 16, 200_000, 25, 64, 10


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    saved_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    saved_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler / topology support here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", saved_cache)
        if saved_log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = saved_log_dir


@pytest.fixture(scope="module")
def cfg():
    return tpcc.TPCCConfig(n_partitions=N_WAREHOUSES, mix="full",
                           order_ring=256)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _place(tree, sharding):
    return jax.tree.map(lambda a: _sds(a.shape, a.dtype, sharding), tree)


def _shapes(cfg, T=T_SLOTS):
    """Abstract (val, tid, ptxn, index, cross) at the smoke's shapes."""
    P, R, M, C = cfg.n_partitions, cfg.rows_per_partition, tpcc.M, tpcc.C
    S = jax.ShapeDtypeStruct
    ptxn = {"valid": S((P, T), jnp.bool_), "row": S((P, T, M), jnp.int32),
            "kind": S((P, T, M), jnp.int32),
            "delta": S((P, T, M, C), jnp.int32),
            "user_abort": S((P, T), jnp.bool_)}
    cross = {"valid": S((LANES,), jnp.bool_),
             "row": S((LANES, M), jnp.int32),
             "kind": S((LANES, M), jnp.int32),
             "delta": S((LANES, M, C), jnp.int32),
             "user_abort": S((LANES,), jnp.bool_)}
    index = jax.eval_shape(
        lambda: [make_index(s, P) for s in tpcc.index_specs(cfg)])
    return (S((P, R, C), jnp.int32), S((P, R), jnp.uint32), ptxn, index,
            cross)


def _check(compiled):
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, used
    return compiled.as_text()


def test_star_engine_programs_compile_for_v5e(topo, cfg):
    chip = SingleDeviceSharding(topo.devices[0])
    val, tid, ptxn, index, cross = _place(_shapes(cfg), chip)
    epoch = _sds((), jnp.uint32, chip)
    seq = _sds((cfg.n_partitions,), jnp.uint32, chip)
    part = jax.jit(run_partitioned, static_argnames=("kernel",))
    _check(part.lower(val, tid, ptxn, epoch, seq, index,
                      kernel="jnp").compile())

    N = cfg.n_partitions * cfg.rows_per_partition
    sm = jax.jit(run_single_master,
                 static_argnames=("max_rounds", "deterministic", "kernel"))
    _check(sm.lower(_sds((N, tpcc.C), jnp.int32, chip),
                    _sds((N,), jnp.uint32, chip), cross, epoch,
                    max_rounds=ROUNDS, index=index,
                    kernel="jnp").compile())

    _, _, out, _ = jax.eval_shape(
        lambda v, t, p, e, s, i: run_partitioned(v, t, p, e, s, i),
        val, tid, ptxn, epoch, seq, index)
    replay = jax.jit(repl.replay_partitioned, static_argnames=("kernel",))
    _check(replay.lower(val, tid, _place(out["log"], chip), index,
                        kernel="jnp").compile())


def test_cluster_programs_compile_for_v5e_2x2(topo, cfg):
    mesh = Mesh(np.asarray(topo.devices[:4]), ("part",))
    prog = MeshPrograms(mesh, cfg.n_partitions, cfg.rows_per_partition,
                        tpcc.C, tpcc.index_specs(cfg), ROUNDS, "jnp")
    shard = NamedSharding(mesh, PartitionSpec("part"))
    T = T_SLOTS // N_SLABS                     # one op-stream slab
    val, tid, ptxn, index, _ = _place(_shapes(cfg, T), shard)
    seq = _sds((cfg.n_partitions,), jnp.uint32, shard)
    epoch = _sds((), jnp.uint32, NamedSharding(mesh, PartitionSpec()))
    part = prog.part.lower(val, tid, index, seq, ptxn, epoch).compile()
    txt = _check(part)
    assert not any(op in txt for op in ("all-reduce(", "all-gather(",
                                        "collective-permute(",
                                        "all-to-all(", "reduce-scatter("))

    log = jax.eval_shape(prog.part, val, tid, index, seq, ptxn, epoch)[4]
    sec = prog.replay_sec.lower(val, tid, _place(log, shard),
                                index).compile()
    assert "collective-permute" in _check(sec)


def test_partitioned_commit_in_place_for_v5e_ycsb16(topo):
    """At ycsb-16p's shapes each queue slot scatters into the table in place.

    No whole-table copy or pad runs inside the compiled slot loop, and the
    program's temporaries stay under 1.5 tables: one relayout of the table,
    not a padded copy of it per slot.
    """
    from _hlo import opcode, ops_of_shape, while_body_instructions
    P, R, C, T, M = YCSB_P, YCSB_R, YCSB_C, YCSB_T, YCSB_M
    chip = SingleDeviceSharding(topo.devices[0])
    ptxn = {"valid": _sds((P, T), jnp.bool_, chip),
            "row": _sds((P, T, M), jnp.int32, chip),
            "kind": _sds((P, T, M), jnp.int32, chip),
            "delta": _sds((P, T, M, C), jnp.int32, chip),
            "user_abort": _sds((P, T), jnp.bool_, chip)}
    compiled = jax.jit(run_partitioned).lower(
        _sds((P, R, C), jnp.int32, chip), _sds((P, R), jnp.uint32, chip),
        ptxn, _sds((), jnp.uint32, chip),
        _sds((P,), jnp.uint32, chip)).compile()
    body = while_body_instructions(_check(compiled))
    assert any(opcode(line)[1] == "scatter" for line in body)
    whole = ops_of_shape(body, ("pad", "copy", "copy-start"),
                         ((P, R, C), (P, R), (P, R + 1, C), (P, R + 1)))
    assert not whole, whole
    table_bytes = P * R * C * 4
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1.5 * table_bytes, (temp, table_bytes)

"""The served path's programs compile for a described TPU v5e (no chip).

At the shapes ``chip_smoke.py`` runs — TPC-C full mix at spec widths, 16
warehouses, 1,024-transaction epochs (128 queue slots per partition, 128
cross-partition lanes) — each program must pass the TPU compiler and fit
one chip's 16 GB.  The one-chip programs are StarEngine's partitioned
phase, single-master phase and op-stream replay; the four-chip programs are
the cluster engine's ``shard_map`` partitioned phase and its secondary
replay, whose op-stream ship is a collective permute over ``part``.  At
the ``ycsb-64p-4n`` deployment's shapes every cluster program compiles
under a name of its own, and the master chip's full-replica programs fit.

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
# the ycsb-64p-4n deployment: the same rows on four nodes of 16 partitions,
# 512 cross-partition lanes on the master
YCSB4_P, YCSB4_LANES = 64, 512
# every cluster program and the name it runs under in a device trace
CLUSTER_NAMES = {"ship_home": "jit_cluster_ship_home",
                 "part": "jit_cluster_partitioned",
                 "fence_barrier": "jit_cluster_fence",
                 "sm": "jit_cluster_single_master",
                 "scatter": "jit_cluster_scatter_back",
                 "scatter_sec": "jit_cluster_scatter_back_sec",
                 "replay_full": "jit_cluster_replay_full",
                 "replay_sec": "jit_cluster_replay_sec",
                 "sm_idx_replay": "jit_cluster_index_replay",
                 "sm_idx_replay_sec": "jit_cluster_index_replay_sec"}


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


def _module_name(hlo: str) -> str:
    return hlo.split("HloModule ", 1)[1].split(",", 1)[0].strip()


def _cluster_args(prog, mesh, P, R, C, T, M, B, index):
    """Abstract arguments of every program of ``prog``: one op-stream slab
    of ``T`` queue slots, ``B`` cross-partition lanes, ``index`` the
    partition-sharded index segments (a list, empty without indexes)."""
    shard = NamedSharding(mesh, PartitionSpec("part"))
    bcast = NamedSharding(mesh, PartitionSpec())
    master = SingleDeviceSharding(mesh.devices.flat[0])
    N = mesh.devices.size

    def txns(lead, sharding):
        return {"valid": _sds(lead, jnp.bool_, sharding),
                "row": _sds(lead + (M,), jnp.int32, sharding),
                "kind": _sds(lead + (M,), jnp.int32, sharding),
                "delta": _sds(lead + (M, C), jnp.int32, sharding),
                "user_abort": _sds(lead, jnp.bool_, sharding)}
    val, tid = _sds((P, R, C), jnp.int32, shard), \
        _sds((P, R), jnp.uint32, shard)
    epoch = _sds((), jnp.uint32, bcast)
    part = (val, tid, index, _sds((P,), jnp.uint32, shard),
            txns((P, T), shard), epoch)
    log = _place(jax.eval_shape(prog.part, *part)[4], shard)
    fval = _sds((P * R, C), jnp.int32, master)
    ftid = _sds((P * R,), jnp.uint32, master)
    findex = _place(index, master)
    cross = txns((B,), master)
    m_epoch = _sds((), jnp.uint32, master)
    sm = (fval, ftid, findex, cross, m_epoch)
    slog = jax.eval_shape(prog.sm, *sm)[2]["log"]
    n = int(np.prod(slog["write"].shape))
    stream = (_sds((n,), jnp.int32, bcast), _sds((n, C), jnp.int32, bcast),
              _sds((n,), jnp.uint32, bcast))
    args = {"ship_home": (val,), "part": part,
            "fence_barrier": (_sds((N,), jnp.int32, shard),),
            "sm": sm,
            "scatter": (val, tid) + stream,
            "scatter_sec": (val, tid) + stream,
            "replay_full": (_sds((P, R, C), jnp.int32, master),
                            _sds((P, R), jnp.uint32, master),
                            _place(log, master), findex),
            "replay_sec": (val, tid, log, index)}
    if index:
        args["sm_idx_replay"] = args["sm_idx_replay_sec"] = (
            index, _sds((B, M), jnp.int32, bcast),
            _sds((B, M, C), jnp.int32, bcast),
            _place(slog["iwrite"], bcast), _place(slog["tid"], bcast))
    return args


def test_cluster_programs_run_under_fixed_names_for_v5e_2x2(topo, cfg):
    """Every cluster program, index replays included (TPC-C's indexes),
    lowers for a described v5e:2x2 as ``jit_cluster_<what>``, one name per
    program, so a device trace tells each apart."""
    mesh = Mesh(np.asarray(topo.devices[:4]), ("part",))
    P, R, C = cfg.n_partitions, cfg.rows_per_partition, tpcc.C
    prog = MeshPrograms(mesh, P, R, C, tpcc.index_specs(cfg), ROUNDS, "jnp")
    index = _place(_shapes(cfg)[3], NamedSharding(mesh,
                                                  PartitionSpec("part")))
    args = _cluster_args(prog, mesh, P, R, C, T_SLOTS // N_SLABS, tpcc.M,
                         LANES, index)
    programs = {k for k, v in vars(prog).items() if hasattr(v, "lower")}
    assert programs == set(CLUSTER_NAMES) == set(args)
    names = {k: getattr(prog, k).lower(*args[k]).as_text().split(
        "module @", 1)[1].split(" ", 1)[0] for k in programs}
    assert names == CLUSTER_NAMES


def test_cluster_programs_compile_for_v5e_2x2_at_ycsb64_4n(topo):
    """At ycsb-64p-4n's shapes (64 partitions of 200,000 rows of 25 words,
    16-slot op-stream slabs, 512 lanes) every cluster program compiles under
    its own name, and the master chip's full-replica replay and
    single-master phase each fit one chip's 16 GB."""
    mesh = Mesh(np.asarray(topo.devices[:4]), ("part",))
    P, R, C, M = YCSB4_P, YCSB_R, YCSB_C, YCSB_M
    prog = MeshPrograms(mesh, P, R, C, [], ROUNDS, "jnp")
    args = _cluster_args(prog, mesh, P, R, C, YCSB_T // N_SLABS, M,
                         YCSB4_LANES, [])
    for k, a in args.items():
        compiled = getattr(prog, k).lower(*a).compile()
        txt = _check(compiled) if k in ("replay_full", "sm") \
            else compiled.as_text()
        assert _module_name(txt) == CLUSTER_NAMES[k], k

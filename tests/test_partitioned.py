"""Partitioned-phase executor: serial per-partition semantics (§4.1)."""
import jax
import jax.numpy as jnp
import numpy as np
from _hyp import given, settings, st

from repro.core.ops import READ, apply_op
from repro.core.partitioned import run_partitioned
from repro.core.tid import tid_epoch

C, M = 6, 4


def _ptxns(rng, P, T, n_rows):
    return {
        "valid": rng.random((P, T)) < 0.9,
        "row": np.stack([[rng.choice(n_rows, M, replace=False)
                          for _ in range(T)] for _ in range(P)]).astype(np.int32),
        "kind": rng.integers(0, 4, (P, T, M)).astype(np.int32),
        "delta": rng.integers(-9, 9, (P, T, M, C)).astype(np.int32),
        "user_abort": rng.random((P, T)) < 0.05,
    }


def _serial_ref(val, ptxn):
    """Pure-python per-partition serial execution."""
    val = np.array(val)
    P, T, _ = ptxn["row"].shape
    for p in range(P):
        for t in range(T):
            if not ptxn["valid"][p, t] or ptxn["user_abort"][p, t]:
                continue
            rows = ptxn["row"][p, t]
            old = jnp.asarray(val[p, rows])
            new = np.array(apply_op(jnp.asarray(ptxn["kind"][p, t]), old,
                                    jnp.asarray(ptxn["delta"][p, t])))
            w = ptxn["kind"][p, t] > READ
            val[p, rows[w]] = new[w]
    return val


@given(st.integers(0, 10_000), st.integers(1, 4), st.integers(1, 8))
@settings(max_examples=20, deadline=None)
def test_matches_serial_reference(seed, P, T):
    rng = np.random.default_rng(seed)
    n_rows = 16
    ptxn = _ptxns(rng, P, T, n_rows)
    val0 = jnp.asarray(rng.integers(0, 50, (P, n_rows, C)), jnp.int32)
    tid0 = jnp.zeros((P, n_rows), jnp.uint32)
    val, tidw, out, stats = run_partitioned(
        val0, tid0, jax.tree.map(jnp.asarray, ptxn), jnp.uint32(3))
    assert np.array_equal(np.array(val), _serial_ref(val0, ptxn))
    # every written record is tagged with a TID in the current epoch
    written = np.array(tidw) != 0
    assert np.all(np.array(tid_epoch(jnp.asarray(tidw)))[written] == 3)


def test_op_replication_replay_matches():
    """Ordered replay of the partitioned log reproduces the primary (§5)."""
    from repro.core.replication import replay_operations
    rng = np.random.default_rng(1)
    P, T, n_rows = 2, 6, 12
    ptxn = _ptxns(rng, P, T, n_rows)
    val0 = jnp.asarray(rng.integers(0, 50, (P, n_rows, C)), jnp.int32)
    tid0 = jnp.zeros((P, n_rows), jnp.uint32)
    val, tidw, out, _ = run_partitioned(
        val0, tid0, jax.tree.map(jnp.asarray, ptxn), jnp.uint32(1))
    rval, rtid = jax.vmap(replay_operations)(val0, tid0, out["log"])
    assert jnp.array_equal(val, rval)
    assert jnp.array_equal(tidw, rtid)


def test_edge_slots_match_serial_reference():
    """The last row, a read aliasing another partition's write, a dead slot.

    Slot 0: partition 0 writes row R-1 and row 5; partition 1 reads its own
    row 5 (the same local row) and writes nothing.  Slot 1 is invalid in every
    partition.  Slot 2: partition 1 writes row R-1.
    """
    from repro.core.ops import ADD, SET
    P, T, R = 2, 3, 16
    rng = np.random.default_rng(7)
    row = np.array([[[R - 1, 5, 0, 1], [2, 3, 4, 6], [R - 1, 2, 3, 4]],
                    [[5, 7, 8, 9], [R - 1, 5, 0, 1], [R - 1, 0, 1, 2]]],
                   np.int32)
    kind = np.array([[[SET, ADD, READ, READ], [SET, SET, ADD, ADD],
                      [READ, READ, READ, READ]],
                     [[READ, READ, READ, READ], [SET, SET, SET, SET],
                      [ADD, READ, READ, READ]]], np.int32)
    ptxn = {"valid": np.array([[True, False, True], [True, False, True]]),
            "row": row, "kind": kind,
            "delta": rng.integers(1, 9, (P, T, M, C)).astype(np.int32),
            "user_abort": np.zeros((P, T), bool)}
    val0 = jnp.asarray(rng.integers(0, 50, (P, R, C)), jnp.int32)
    tid0 = jnp.zeros((P, R), jnp.uint32)
    val, tidw, out, stats = run_partitioned(
        val0, tid0, jax.tree.map(jnp.asarray, ptxn), jnp.uint32(3))
    assert np.array_equal(np.array(val), _serial_ref(val0, ptxn))
    written = np.zeros((P, R), bool)
    written[0, [R - 1, 5]] = True
    written[1, R - 1] = True
    tidw = np.array(tidw)
    assert np.array_equal(tidw != 0, written)
    assert np.all(np.array(tid_epoch(jnp.asarray(tidw)))[written] == 3)
    log_tid = np.array(out["log"]["tid"])
    assert tidw[0, R - 1] == tidw[0, 5] == log_tid[0, 0, 0]
    assert tidw[1, R - 1] == log_tid[1, 2, 0]
    assert int(stats["writes"]) == 3 and int(stats["committed"]) == 4
    assert np.array_equal(np.array(out["committed"]),
                          [[True, False, True], [True, False, True]])


def test_commit_scatters_in_place():
    """Each queue slot updates the loop-carried table in place.

    The compiled loop body may hold no pad, slice or copy of the whole
    (P, R, C) value table or (P, R) TID table, and no instruction anywhere
    has R + 1 rows (a sentinel row concatenated onto the table).
    """
    from _hlo import opcode, ops_of_shape, while_body_instructions
    P, R, T = 4, 4096, 8
    S = jax.ShapeDtypeStruct
    ptxn = {"valid": S((P, T), jnp.bool_), "row": S((P, T, M), jnp.int32),
            "kind": S((P, T, M), jnp.int32),
            "delta": S((P, T, M, C), jnp.int32),
            "user_abort": S((P, T), jnp.bool_)}
    hlo = jax.jit(run_partitioned).lower(
        S((P, R, C), jnp.int32), S((P, R), jnp.uint32), ptxn,
        S((), jnp.uint32), S((P,), jnp.uint32)).compile().as_text()
    assert f"[{P},{R + 1}" not in hlo, "a table with a sentinel row"
    body = while_body_instructions(hlo)
    assert any(opcode(line)[1] == "scatter" for line in body)
    whole = ops_of_shape(body, ("pad", "slice", "copy", "copy-start"),
                         ((P, R, C), (P, R)))
    assert not whole, whole

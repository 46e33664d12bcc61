"""Fused Pallas OCC kernels (interpret mode) vs the jnp ref.py oracle.

Bit-for-bit parity on random op batches — including lock-conflict
interleavings (many lanes claiming the same rows) and phantom-abort
interleavings (inserts landing inside concurrently scanned ranges) — for:

* the full single-master executor (``kernel="pallas"`` vs ``"jnp"``),
* ``locate_index_ops`` (searchsorted + SCAN_L window probe),
* the partitioned executor / ``step_index_ops``,
* ``segment_scan(use_pallas=True)`` and ``StorageEngine.range_scan``.

Property-driven via tests/_hyp.py (real hypothesis when installed, seeded
fallback otherwise), so tier-1 runs the sweep either way.
"""
import jax
import jax.numpy as jnp
import numpy as np
from _hyp import given, settings, st

from repro.core.ops import (DELETE_IDX, IDX_OPS, INSERT_IDX, IX_EXPECT,
                            IX_HI, IX_ID, IX_KEY, IX_PROW, SCAN_CONSUME,
                            SCAN_READ)
from repro.core.partitioned import run_partitioned
from repro.core.single_master import run_single_master
from repro.kernels.occ.ops import locate_index_ops, step_index_ops
from repro.storage import IndexSpec, SENTINEL, make_index, segment_scan
from repro.storage.index import full_key

C = 10
M = 24


def _tree_equal(a, b):
    eq = jax.tree.map(lambda x, y: bool(jnp.array_equal(x, y)), a, b)
    return all(jax.tree.leaves(eq))


def _random_index_workload(rng, B, P, n_rows, caps, conflict_rows):
    """Random txn batch mixing primary ops with scan/insert/delete/consume
    index ops over overlapping key ranges — lock conflicts on the primary
    rows (drawn from a small pool) AND phantom conflicts on the scans
    (inserts inside scanned ranges) arise by construction."""
    # per-txn rows are drawn WITHOUT replacement (the generators' documented
    # invariant: at most one op per row per txn — duplicate-row scatters
    # would be order-unspecified); the small shared pool still produces
    # dense cross-lane lock conflicts
    pool = max(conflict_rows, M)
    rows = np.stack([rng.choice(pool, M, replace=False)
                     for _ in range(B)]).astype(np.int32)
    kinds = rng.integers(0, 4, (B, M)).astype(np.int32)
    deltas = rng.integers(-50, 50, (B, M, C)).astype(np.int32)
    deltas[..., -1] = 0                        # guard column: unguarded
    index = [make_index(IndexSpec(f"ix{i}", c), P)
             for i, c in enumerate(caps)]
    # seed some live entries so scans/consumes/deletes have targets
    for i, c in enumerate(caps):
        n_seed = int(rng.integers(0, min(c, 6)))
        for _ in range(n_seed):
            p = int(rng.integers(0, P))
            k = int(full_key(p, int(rng.integers(0, 60))))
            pos = int(jnp.searchsorted(index[i]["key"][p], k))
            if pos < c and int(index[i]["key"][p, pos]) != k:
                key = index[i]["key"].at[p].set(
                    jnp.sort(index[i]["key"][p].at[c - 1].set(k)))
                index[i] = {"key": key, "prow": index[i]["prow"],
                            "tid": index[i]["tid"]}
    for b in range(B):
        for k in range(int(rng.integers(0, IDX_OPS // 2))):
            iid = int(rng.integers(0, len(caps)))
            p = int(rng.integers(0, P))
            base = int(full_key(p, 0))
            r = rng.random()
            deltas[b, k] = 0
            if r < 0.35:
                kinds[b, k] = INSERT_IDX
                deltas[b, k, IX_KEY] = base + int(rng.integers(0, 60))
                deltas[b, k, IX_PROW] = int(rng.integers(0, n_rows))
            elif r < 0.6:
                kinds[b, k] = SCAN_READ
                lo = base + int(rng.integers(0, 40))
                deltas[b, k, IX_KEY] = lo
                deltas[b, k, IX_HI] = lo + int(rng.integers(1, 40))
            elif r < 0.8:
                kinds[b, k] = SCAN_CONSUME
                deltas[b, k, IX_KEY] = base
                deltas[b, k, IX_HI] = base + 60
                deltas[b, k, IX_EXPECT] = base + int(rng.integers(0, 60))
                rows[b, k] = pool + k      # tombstone row, txn-unique
            else:
                kinds[b, k] = DELETE_IDX
                deltas[b, k, IX_KEY] = base + int(rng.integers(0, 60))
            deltas[b, k, IX_ID] = iid
    txns = {"valid": rng.random(B) < 0.95, "row": rows, "kind": kinds,
            "delta": deltas, "user_abort": rng.random(B) < 0.1}
    return jax.tree.map(jnp.asarray, txns), index


@given(st.integers(0, 100_000))
@settings(max_examples=12, deadline=None)
def test_single_master_pallas_parity_random(seed):
    """Full executor parity: state, logs, stats, index — conflicts and
    phantom interleavings included."""
    rng = np.random.default_rng(seed)
    B = int(rng.integers(2, 12))
    P = int(rng.integers(1, 3))
    caps = [int(rng.integers(4, 20)) for _ in range(int(rng.integers(1, 3)))]
    n_rows = 64 * P
    txns, index = _random_index_workload(rng, B, P, n_rows, caps,
                                         conflict_rows=n_rows // 4)
    val0 = jnp.asarray(rng.integers(0, 50, (n_rows, C)), jnp.int32)
    tid0 = jnp.asarray(rng.integers(0, 5, n_rows).astype(np.uint32) * 2)
    outs = {}
    for kern in ("jnp", "pallas"):
        outs[kern] = run_single_master(
            val0, tid0, txns, jnp.uint32(2), max_rounds=4,
            index=[dict(i) for i in index], kernel=kern)
    (v1, t1, o1, s1), (v2, t2, o2, s2) = outs["jnp"], outs["pallas"]
    assert jnp.array_equal(v1, v2) and jnp.array_equal(t1, t2)
    assert _tree_equal(o1, o2)
    assert _tree_equal(s1, s2)


@given(st.integers(0, 100_000))
@settings(max_examples=10, deadline=None)
def test_locate_index_ops_parity(seed):
    rng = np.random.default_rng(seed)
    B, P = int(rng.integers(1, 8)), int(rng.integers(1, 4))
    caps = [int(rng.integers(4, 24)) for _ in range(int(rng.integers(1, 4)))]
    n_rows = 32 * P
    txns, index = _random_index_workload(rng, B, P, n_rows, caps,
                                         conflict_rows=8)
    K = min(IDX_OPS, M)
    a = locate_index_ops(index, txns["kind"][:, :K], txns["delta"][:, :K],
                         n_rows, kernel="jnp")
    b = locate_index_ops(index, txns["kind"][:, :K], txns["delta"][:, :K],
                         n_rows, kernel="pallas")
    assert a["no_addr"] == b["no_addr"]
    assert _tree_equal({k: v for k, v in a.items() if k != "no_addr"},
                       {k: v for k, v in b.items() if k != "no_addr"})


def test_phantom_abort_parity():
    """The canonical phantom interleaving (insert into a concurrently
    scanned range) produces identical abort/commit rounds on both paths."""
    index = [make_index(IndexSpec("ix", 16), 1)]
    rows = np.zeros((2, M), np.int32)
    kinds = np.full((2, M), 0, np.int32)
    deltas = np.zeros((2, M, C), np.int32)
    kinds[0, 0] = INSERT_IDX
    deltas[0, 0, IX_KEY] = 50
    deltas[0, 0, IX_PROW] = 3
    kinds[1, 0] = SCAN_READ
    deltas[1, 0, IX_KEY] = 0
    deltas[1, 0, IX_HI] = 100
    txns = jax.tree.map(jnp.asarray, {
        "valid": np.ones(2, bool), "row": rows, "kind": kinds,
        "delta": deltas, "user_abort": np.zeros(2, bool)})
    val0 = jnp.zeros((64, C), jnp.int32)
    tid0 = jnp.zeros((64,), jnp.uint32)
    res = {}
    for kern in ("jnp", "pallas"):
        res[kern] = run_single_master(val0, tid0, txns, jnp.uint32(1),
                                      max_rounds=3,
                                      index=[dict(index[0])], kernel=kern)
    o1, o2 = res["jnp"][2], res["pallas"][2]
    assert _tree_equal(o1, o2)
    # and the phantom really aborted the scanner in round 0 on both
    assert int(np.asarray(o1["committed_round"])[1]) > 0


@given(st.integers(0, 100_000))
@settings(max_examples=8, deadline=None)
def test_partitioned_pallas_parity_random(seed):
    rng = np.random.default_rng(seed)
    P, T = int(rng.integers(1, 4)), int(rng.integers(1, 5))
    caps = [int(rng.integers(4, 16))]
    R = 64
    txns, index = _random_index_workload(rng, P * T, P, R, caps,
                                         conflict_rows=R // 2)
    ptxn = {k: jnp.asarray(np.asarray(v).reshape((P, T) + v.shape[1:]))
            for k, v in txns.items()}
    # rows are partition-local in the partitioned executor
    val0 = jnp.asarray(rng.integers(0, 50, (P, R, C)), jnp.int32)
    tid0 = jnp.zeros((P, R), jnp.uint32)
    outs = {}
    for kern in ("jnp", "pallas"):
        outs[kern] = run_partitioned(val0, tid0, ptxn, jnp.uint32(1),
                                     index=[dict(i) for i in index],
                                     kernel=kern)
    (v1, t1, o1, s1), (v2, t2, o2, s2) = outs["jnp"], outs["pallas"]
    assert jnp.array_equal(v1, v2) and jnp.array_equal(t1, t2)
    assert _tree_equal(o1, o2)
    assert _tree_equal(s1, s2)


@given(st.integers(0, 100_000))
@settings(max_examples=10, deadline=None)
def test_step_index_ops_parity(seed):
    rng = np.random.default_rng(seed)
    P = int(rng.integers(1, 5))
    caps = [int(rng.integers(4, 24)) for _ in range(int(rng.integers(1, 3)))]
    txns, index = _random_index_workload(rng, P, P, 32, caps,
                                         conflict_rows=8)
    K = min(IDX_OPS, M)
    a = step_index_ops(index, txns["kind"][:, :K], txns["delta"][:, :K],
                       kernel="jnp")
    b = step_index_ops(index, txns["kind"][:, :K], txns["delta"][:, :K],
                       kernel="pallas")
    assert _tree_equal(a, b)


@given(st.integers(0, 100_000), st.integers(0, 80), st.integers(1, 60))
@settings(max_examples=15, deadline=None)
def test_segment_scan_pallas_parity(seed, lo, width):
    rng = np.random.default_rng(seed)
    cap = int(rng.integers(4, 64))
    n_live = int(rng.integers(0, cap))
    keys = np.full(cap, SENTINEL, np.int32)
    keys[:n_live] = np.sort(rng.choice(100, n_live, replace=False))
    a = segment_scan(jnp.asarray(keys), jnp.int32(lo), jnp.int32(lo + width))
    b = segment_scan(jnp.asarray(keys), jnp.int32(lo), jnp.int32(lo + width),
                     use_pallas=True)
    assert _tree_equal(tuple(a), tuple(b))


def test_storage_engine_range_scan_pallas():
    from repro.storage import StorageEngine
    eng = StorageEngine(2, 8, n_cols=4, index_specs=[IndexSpec("ix", 16)])
    idx = eng.indexes[0]
    idx["key"] = idx["key"].at[1, 0].set((1 << 24) | 7)
    idx["prow"] = idx["prow"].at[1, 0].set(5)
    a = eng.range_scan("ix", 1, (1 << 24) | 0, (1 << 24) | 100)
    b = eng.range_scan("ix", 1, (1 << 24) | 0, (1 << 24) | 100,
                       use_pallas=True)
    assert _tree_equal(tuple(a), tuple(b))
    assert bool(b[3][0]) and int(b[0][0]) == ((1 << 24) | 7)


# ---------------------------------------------------------------------------
# fused index-merge kernel vs the gather-form oracle
# ---------------------------------------------------------------------------
def _random_merge_batch(rng, P, cap, Kd, Ki, key_space=10_000):
    """Random sorted segments (SENTINEL-padded, canonical free slots) plus a
    random delete/insert batch — deletes mix live hits with misses, inserts
    mix fresh keys with masked (SENTINEL) slots."""
    key = np.full((P, cap), SENTINEL, np.int32)
    for p in range(P):
        n_live = int(rng.integers(0, cap + 1))
        key[p, :n_live] = np.sort(
            rng.choice(key_space, n_live, replace=False)).astype(np.int32)
    live = key != SENTINEL
    prow = np.where(live, rng.integers(0, 1000, (P, cap)), 0).astype(np.int32)
    tid = np.where(live, rng.integers(1, 99, (P, cap)), 0).astype(np.uint32)

    del_pq = np.full((P, Kd), SENTINEL, np.int32)
    for p in range(P):
        for j in range(Kd):
            r = rng.random()
            if r < 0.4 and live[p].any():
                del_pq[p, j] = rng.choice(key[p][live[p]])   # live hit
            elif r < 0.7:
                del_pq[p, j] = int(rng.integers(0, key_space))  # maybe miss
    ins_pq = np.full((P, Ki), SENTINEL, np.int32)
    mask = rng.random((P, Ki)) < 0.8
    ins_pq[mask] = rng.integers(0, key_space, int(mask.sum()))
    prow_pq = np.where(ins_pq != SENTINEL,
                       rng.integers(0, 1000, (P, Ki)), 0).astype(np.int32)
    tid_pq = np.where(ins_pq != SENTINEL,
                      rng.integers(1, 99, (P, Ki)), 0).astype(np.uint32)
    return tuple(jnp.asarray(a) for a in
                 (key, prow, tid, del_pq, ins_pq.astype(np.int32),
                  prow_pq, tid_pq))


@given(st.integers(0, 100_000))
@settings(max_examples=15, deadline=None)
def test_index_merge_pallas_parity_random(seed):
    """Fused kernel == gather-form oracle bit-exact: keys, prows, TIDs and
    overflow counts — overflow (dropped live keys) and empty segments
    arise by construction from small caps + dense inserts."""
    from repro.kernels.index_merge.ops import index_merge
    from repro.kernels.index_merge.ref import segment_merge_ref
    rng = np.random.default_rng(seed)
    P = int(rng.integers(1, 4))
    cap = int(rng.integers(2, 32))
    Kd = int(rng.integers(1, 8))
    Ki = int(rng.integers(1, 8))
    batch = _random_merge_batch(rng, P, cap, Kd, Ki, key_space=60)
    ref = jax.vmap(segment_merge_ref)(*batch)
    for kwargs in ({"use_pallas": False},
                   {"use_pallas": True},
                   {"use_pallas": True, "block_slots": 8}):  # multi-tile
        out = index_merge(*batch, **kwargs)
        assert _tree_equal(tuple(ref), tuple(out)), kwargs


def test_index_merge_edge_cases():
    """Deterministic corners: overflow dropping live keys, the all-SENTINEL
    segment, delete-only and insert-only batches."""
    from repro.kernels.index_merge.ops import index_merge
    from repro.kernels.index_merge.ref import segment_merge_ref
    cap = 6
    # full segment + 4 inserts -> overflow 4, tail live keys dropped
    key = jnp.asarray([[10, 20, 30, 40, 50, 60]], jnp.int32)
    prow = jnp.arange(6, dtype=jnp.int32)[None]
    tid = jnp.arange(1, 7, dtype=jnp.uint32)[None]
    dels = jnp.full((1, 2), SENTINEL, jnp.int32)
    ins = jnp.asarray([[5, 15, 25, 35]], jnp.int32)
    ipr = jnp.asarray([[9, 9, 9, 9]], jnp.int32)
    itd = jnp.asarray([[7, 7, 7, 7]], jnp.uint32)
    ref = jax.vmap(segment_merge_ref)(key, prow, tid, dels, ins, ipr, itd)
    out = index_merge(key, prow, tid, dels, ins, ipr, itd, use_pallas=True)
    assert _tree_equal(tuple(ref), tuple(out))
    assert int(out[3][0]) == 4

    # all-SENTINEL segment: inserts land from slot 0
    empty = jnp.full((1, cap), SENTINEL, jnp.int32)
    z = jnp.zeros((1, cap), jnp.int32)
    zt = jnp.zeros((1, cap), jnp.uint32)
    ref = jax.vmap(segment_merge_ref)(empty, z, zt, dels, ins, ipr, itd)
    out = index_merge(empty, z, zt, dels, ins, ipr, itd, use_pallas=True)
    assert _tree_equal(tuple(ref), tuple(out))
    assert int(out[0][0, 0]) == 5 and int(out[3][0]) == 0

    # delete-only (Ki == 0 pad path) and insert-only (Kd == 0 pad path)
    d2 = jnp.asarray([[20, 40]], jnp.int32)
    e_i = jnp.zeros((1, 0), jnp.int32)
    out = index_merge(key, prow, tid, d2, e_i, e_i, e_i.astype(jnp.uint32),
                      use_pallas=True)
    ref = jax.vmap(segment_merge_ref)(
        key, prow, tid, d2, jnp.full((1, 1), SENTINEL, jnp.int32),
        jnp.zeros((1, 1), jnp.int32), jnp.zeros((1, 1), jnp.uint32))
    assert _tree_equal(tuple(ref), tuple(out))
    e_d = jnp.zeros((1, 0), jnp.int32)
    out = index_merge(key, prow, tid, e_d, ins, ipr, itd, use_pallas=True)
    ref = jax.vmap(segment_merge_ref)(
        key, prow, tid, jnp.full((1, 1), SENTINEL, jnp.int32), ins, ipr, itd)
    assert _tree_equal(tuple(ref), tuple(out))


def test_index_merge_vmapped_tpcc_scale():
    """A TPC-C-sized segment batch (cap=11520) under jax.vmap over the
    pallas dispatch — the shape the ORDER-LINE index replays at."""
    from repro.kernels.index_merge.ops import index_merge
    from repro.kernels.index_merge.ref import segment_merge_ref
    rng = np.random.default_rng(7)
    P, cap, Kd, Ki = 4, 11520, 16, 16
    batches = [_random_merge_batch(rng, P, cap, Kd, Ki, key_space=50_000)
               for _ in range(2)]
    stacked = tuple(jnp.stack([b[i] for b in batches]) for i in range(7))
    ref = jax.vmap(lambda *a: jax.vmap(segment_merge_ref)(*a))(*stacked)
    out = jax.vmap(lambda *a: index_merge(*a, use_pallas=True))(*stacked)
    assert _tree_equal(tuple(ref), tuple(out))


# ---------------------------------------------------------------------------
# tiled OCC grids: forced multi-tile blocks == auto single-tile == oracle
# ---------------------------------------------------------------------------
@given(st.integers(0, 100_000))
@settings(max_examples=8, deadline=None)
def test_occ_round_tiled_grid_parity(seed):
    """The 3-launch pipeline with forced small blocks (multi-tile lock,
    lane and row grids, with padding remainders) matches the auto
    single-tile blocks bit-for-bit, Silo and deterministic modes both."""
    from repro.kernels.occ.kernel import occ_round_pallas
    rng = np.random.default_rng(seed)
    N, B, Mm = 37, 11, 4
    val = jnp.asarray(rng.integers(0, 100, (N, C)), jnp.int32)
    tidw = jnp.asarray(rng.integers(0, 50, (N,)), jnp.uint32)
    rows = jnp.asarray(
        np.stack([rng.choice(N, Mm, replace=False) for _ in range(B)]),
        jnp.int32)
    kind = jnp.asarray(rng.integers(0, 4, (B, Mm)), jnp.int32)
    delta = jnp.asarray(rng.integers(-3, 3, (B, Mm, C)), jnp.int32)
    wmask = jnp.asarray(rng.random((B, Mm)) < 0.5)
    amask = wmask | jnp.asarray(rng.random((B, Mm)) < 0.5)
    active = jnp.asarray(rng.random((B,)) < 0.8)
    epoch_arr = jnp.asarray([3], jnp.uint32)
    last_tid = jnp.asarray(rng.integers(0, 50, (B,)), jnp.uint32)
    K, L1, S = 3, 4, 20
    NT = N + S
    ix = (jnp.asarray(rng.integers(N, NT, (B, K)), jnp.int32),
          jnp.asarray(rng.integers(0, 50, (B, K)), jnp.uint32),
          jnp.asarray(rng.integers(N, NT + 1, (B, K, L1)), jnp.int32),
          jnp.asarray(rng.integers(0, 50, (B, K, L1)), jnp.uint32),
          jnp.asarray(rng.random((B, K, L1)) < 0.5),
          jnp.asarray(rng.random((B, K)) < 0.5))
    args = (val, tidw, rows, kind, delta, wmask, amask, active, epoch_arr,
            last_tid)
    for det in (False, True):
        for ixa, nt in ((None, N), (ix, NT)):
            base = occ_round_pallas(*args, ixa, NT=nt, deterministic=det,
                                    interpret=True)
            tiled = occ_round_pallas(*args, ixa, NT=nt, deterministic=det,
                                     interpret=True, block_nt=8, block_b=4,
                                     block_rows=16)
            assert all(bool(jnp.array_equal(a, b))
                       for a, b in zip(base, tiled)), (det, nt)


def test_scan_window_block_q_parity():
    """Query-block grid (scalar-prefetched probe streams) with a padded
    remainder block matches the single-tile launch."""
    from repro.kernels.occ.kernel import scan_window_pallas
    rng = np.random.default_rng(3)
    S, Q = 64, 13
    fk = jnp.sort(jnp.asarray(rng.integers(0, 1000, (S,)), jnp.int32))
    ft = jnp.asarray(rng.integers(0, 50, (S,)), jnp.uint32)
    q = jnp.asarray(rng.integers(0, 1000, (Q,)), jnp.int32)
    sb = jnp.zeros((Q,), jnp.int32)
    sc = jnp.full((Q,), S, jnp.int32)
    a = scan_window_pallas(fk, ft, q, sb, sc, n_slots=3, n_iters=7,
                           interpret=True)
    b = scan_window_pallas(fk, ft, q, sb, sc, n_slots=3, n_iters=7,
                           interpret=True, block_q=4)
    assert _tree_equal(tuple(a), tuple(b))

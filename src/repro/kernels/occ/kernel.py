"""Pallas TPU kernels: fused OCC round + fused index scan window.

Two kernels cover the single-master hot path (ROADMAP "Pallas OCC kernels"):

* ``scan_window_pallas`` — the ordered-index probe.  The jnp reference
  resolves each op's range scan by materializing a ``(B, K, cap)`` gather of
  the whole segment per index before ``searchsorted`` — at TPC-C scale that
  is hundreds of MB of HBM traffic per OCC round.  The kernel keeps the
  concatenated segments resident (one ``(S,)`` key array + ``(S,)`` TID
  array), scalar-prefetches the per-query ``q``/``seg_base``/``seg_cap``
  streams to SMEM (``pltpu.PrefetchScalarGridSpec``) so each grid step can
  address its probe window before the DMA lands, runs a vectorized
  lower-bound binary search per op (``n_iters`` rounds of one gathered
  compare each) and gathers only the bounded ``n_slots`` window —
  O(B·K·(log cap + L)) elements touched instead of O(B·K·cap).  The grid
  tiles the query stream (``block_q``); on CPU the auto block is the whole
  stream (one grid step — interpret-mode cost unchanged).

* ``occ_round_pallas`` — one OCC round over the flat row+index-slot lock
  space, lowered as a three-launch pipeline so every launch tiles a
  hardware-sized grid instead of holding the whole round in one VMEM
  footprint:

    1. lock build   — grid over tiles of the flat ``NT+1`` lock space; each
                      tile scatter-mins the claim stream (lane ids of write
                      rows + index-slot claims) into a tile-local running
                      array with a dump slot, O(claims) work per tile.
    2. validate     — grid over lane blocks; ``val``/``tidw``/the built lock
                      array stay resident while each block gathers its
                      reads, applies ops, checks lock ownership + Silo read
                      validation (or Calvin deterministic locking) and
                      generates TIDs.
    3. install      — grid over row tiles; winner post-images scatter into
                      each tile through clipped tile-local addresses.

  ``min`` is commutative and winner rows are unique, so the tiling is
  bit-identical to the former monolithic launch for every block size.

Both kernels run under ``interpret=True`` only (the tier-1/CI path) with
auto single-tile blocks, and are bit-identical to ``ref.py`` by
construction; ``tests/test_occ_kernels.py`` enforces this on random op
batches including lock-conflict and phantom-abort interleavings, with
forced multi-tile grids.  Neither lowers for a TPU yet: the lock build and
the install scatter (scatter-min / scatter have no Pallas TPU lowering),
the scan window reads its scalar-prefetch refs as vectors (SMEM holds
scalars), and the validate launch keeps the whole ``(N, C)`` table
resident in VMEM.  Callers pass ``interpret`` explicitly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import tid as tidlib
from repro.core.ops import apply_op


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# ---------------------------------------------------------------------------
# fused index scan window: binary search + bounded window gather
# ---------------------------------------------------------------------------
def _scan_window_kernel(q_ref, base_ref, cap_ref, key_ref, tid_ref,
                        pos_ref, keys_ref, tids_ref, *, n_slots, n_iters,
                        block_q):
    t = pl.program_id(0)
    fk = key_ref[...]                                  # (S,) int32, resident
    ft = tid_ref[...]                                  # (S,) uint32, resident
    # per-query streams live in SMEM (scalar prefetch): slice this grid
    # step's block
    sl = pl.ds(t * block_q, block_q)
    q = q_ref[sl]                                      # (block_q,) query keys
    base = base_ref[sl]                                # (block_q,) seg starts
    cap = cap_ref[sl]                                  # (block_q,) seg lens

    # vectorized lower bound: pos = first slot with seg[pos] >= q
    lo = jnp.zeros(q.shape, jnp.int32)
    hi = cap

    def body(_, lh):
        lo, hi = lh
        live = lo < hi
        mid = (lo + hi) // 2                           # in [lo, hi) ⊂ [0,cap)
        kmid = fk[base + jnp.minimum(mid, cap - 1)]
        right = live & (kmid < q)
        return (jnp.where(right, mid + 1, lo),
                jnp.where(live & ~right, mid, hi))

    lo, hi = jax.lax.fori_loop(0, n_iters, body, (lo, hi))
    pos_ref[...] = lo
    window = lo[:, None] + jnp.arange(n_slots, dtype=jnp.int32)[None, :]
    slots = jnp.clip(window, 0, cap[:, None] - 1)
    gidx = base[:, None] + slots                       # (block_q, n_slots)
    keys_ref[...] = fk[gidx]
    tids_ref[...] = ft[gidx]


@functools.partial(jax.jit,
                   static_argnames=("n_slots", "n_iters", "interpret",
                                    "block_q"))
def scan_window_pallas(flat_key, flat_tid, q, seg_base, seg_cap, *,
                       n_slots: int, n_iters: int, interpret: bool,
                       block_q: int | None = None):
    """flat_key/flat_tid: (S,) concatenated sorted segments; q/seg_base/
    seg_cap: (Q,) per-query key, segment start offset and segment length.
    Returns (pos0 (Q,) == searchsorted-left, keys_at (Q, n_slots),
    tids_at (Q, n_slots)) with window slots clipped to the segment.

    ``block_q`` tiles the query stream over a grid (the per-query streams
    ride SMEM scalar prefetch); ``None`` = one tile covering all queries —
    the CPU/interpret default.
    """
    Q = q.shape[0]
    if block_q is None:
        block_q = Q
    Qp = _round_up(Q, block_q)
    if Qp != Q:
        # padded probes scan a 1-slot window at segment offset 0 — discarded
        q = jnp.concatenate([q, jnp.zeros((Qp - Q,), q.dtype)])
        seg_base = jnp.concatenate(
            [seg_base, jnp.zeros((Qp - Q,), seg_base.dtype)])
        seg_cap = jnp.concatenate(
            [seg_cap, jnp.ones((Qp - Q,), seg_cap.dtype)])
    kernel = functools.partial(_scan_window_kernel, n_slots=n_slots,
                               n_iters=n_iters, block_q=block_q)
    S = flat_key.shape[0]
    pos, keys, tids = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(Qp // block_q,),
            in_specs=[pl.BlockSpec((S,), lambda i, q, b, c: (0,)),
                      pl.BlockSpec((S,), lambda i, q, b, c: (0,))],
            out_specs=[
                pl.BlockSpec((block_q,), lambda i, q, b, c: (i,)),
                pl.BlockSpec((block_q, n_slots), lambda i, q, b, c: (i, 0)),
                pl.BlockSpec((block_q, n_slots), lambda i, q, b, c: (i, 0)),
            ]),
        out_shape=[jax.ShapeDtypeStruct((Qp,), jnp.int32),
                   jax.ShapeDtypeStruct((Qp, n_slots), flat_key.dtype),
                   jax.ShapeDtypeStruct((Qp, n_slots), flat_tid.dtype)],
        interpret=interpret,
    )(q, seg_base, seg_cap, flat_key, flat_tid)
    return pos[:Q], keys[:Q], tids[:Q]


# ---------------------------------------------------------------------------
# OCC round, launch 1/3: lock build over tiles of the flat lock space
# ---------------------------------------------------------------------------
def _lock_build_kernel(addr_ref, lane_ref, lock_ref, *, block_nt,
                       sentinel_lane):
    t = pl.program_id(0)
    base = t * block_nt
    local = addr_ref[...] - base                       # (Kc,)
    inside = (local >= 0) & (local < block_nt)
    tgt = jnp.where(inside, local, block_nt)           # dump slot block_nt
    run = jnp.full((block_nt + 1,), sentinel_lane, jnp.int32)
    run = run.at[tgt].min(lane_ref[...])
    lock_ref[...] = run[:block_nt]


def _lock_build(addr, lane, *, NT, B, block_nt, interpret):
    """Scatter-min lane ids over the flat (NT+1,) lock space, tiled.

    addr/lane: (Kc,) claim streams — masked claims carry addr == NT (the
    dump slot) and lane == B (the sentinel lane), so ``min`` ignores them.
    ``min`` is commutative: any tiling is bit-identical to one global
    scatter-min.
    """
    NTp = _round_up(NT + 1, block_nt)
    Kc = addr.shape[0]
    lock = pl.pallas_call(
        functools.partial(_lock_build_kernel, block_nt=block_nt,
                          sentinel_lane=B),
        grid=(NTp // block_nt,),
        in_specs=[pl.BlockSpec((Kc,), lambda i: (0,)),
                  pl.BlockSpec((Kc,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_nt,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((NTp,), jnp.int32),
        interpret=interpret,
    )(addr, lane)
    return lock[:NT + 1]


# ---------------------------------------------------------------------------
# OCC round, launch 2/3: per-lane validate + TID generation over lane blocks
# ---------------------------------------------------------------------------
def _validate_kernel(val_ref, tidw_ref, lock_ref, rows_ref, kind_ref,
                     delta_ref, wmask_ref, amask_ref, active_ref, epoch_ref,
                     last_tid_ref, *rest, NT, block_b, deterministic,
                     has_ix):
    it = iter(rest)
    rlock_ref = next(it) if deterministic else None
    if has_ix:
        claim_addr_ref, claim_tid_ref = next(it), next(it)
        scan_addr_ref, scan_tid_ref = next(it), next(it)
        scan_valid_ref, has_claim_ref = next(it), next(it)
    commit_out, ntid_out, new_out, w_out = it

    val = val_ref[...]                                              # (N,C)
    tidw = tidw_ref[...]                                            # (N,)
    lock = lock_ref[...]                                            # (NT+1,)
    rows = rows_ref[...]                                            # (b,M)
    kind = kind_ref[...]
    delta_v = delta_ref[...]
    wmask = wmask_ref[...]
    amask = amask_ref[...]
    active = active_ref[...]                                        # (b,)
    epoch = epoch_ref[0]
    last_tid = last_tid_ref[...]

    t = pl.program_id(0)
    # global lane ids of this block — lock holders are global lane ids
    lanes = t * block_b + jnp.arange(block_b, dtype=jnp.int32)      # (b,)

    old = val[rows]                                                 # (b,M,C)
    rtids = tidw[rows]                                              # (b,M)
    new = apply_op(kind, old, delta_v)

    holder = lock[rows]                                             # (b,M)
    wins_all = jnp.all(jnp.where(wmask, holder == lanes[:, None], True),
                       axis=1)
    if has_ix:
        claim_addr = claim_addr_ref[...]                            # (b,K)
        claim_tid = claim_tid_ref[...]
        scan_addr = scan_addr_ref[...]                              # (b,K,L+1)
        scan_tid = scan_tid_ref[...]
        scan_valid = scan_valid_ref[...]
        has_claim = has_claim_ref[...]
        hold_ic = lock[claim_addr]                                  # (b,K)
        wins_all &= jnp.all(
            jnp.where(has_claim, hold_ic == lanes[:, None], True), axis=1)
    if deterministic:
        rlock = rlock_ref[...]                                      # (NT+1,)
        holder_any = rlock[rows]
        commit_now = active & jnp.all(
            jnp.where(amask, holder_any == lanes[:, None], True), axis=1)
        if has_ix:
            commit_now &= jnp.all(jnp.where(
                scan_valid & active[:, None, None],
                rlock[scan_addr] == lanes[:, None, None], True), axis=(1, 2))
            commit_now &= jnp.all(jnp.where(
                has_claim, rlock[claim_addr] == lanes[:, None], True), axis=1)
    else:
        dirty = holder < lanes[:, None]                             # (b,M)
        read_ok = jnp.all(~(amask & dirty), axis=1)
        if has_ix:
            sdirty = scan_valid & active[:, None, None] \
                & (lock[scan_addr] < lanes[:, None, None])
            read_ok &= ~jnp.any(sdirty, axis=(1, 2))
        commit_now = active & wins_all & read_ok

    # TID generation (criteria a, b, c)
    obs = jnp.max(jnp.where(amask, rtids, jnp.uint32(0)), axis=1)
    if has_ix:
        obs = jnp.maximum(obs, jnp.max(
            jnp.where(scan_valid, scan_tid, jnp.uint32(0)), axis=(1, 2)))
        obs = jnp.maximum(obs, jnp.max(
            jnp.where(has_claim, claim_tid, jnp.uint32(0)), axis=1))
    new_tid = tidlib.next_tid(epoch, obs, last_tid)                 # (b,)

    commit_out[...] = commit_now
    ntid_out[...] = new_tid
    new_out[...] = new
    w_out[...] = wmask & commit_now[:, None]


# ---------------------------------------------------------------------------
# OCC round, launch 3/3: winner install over row tiles
# ---------------------------------------------------------------------------
def _install_kernel(val_ref, tidw_ref, wrows_ref, newf_ref, wtid_ref,
                    val_out, tid_out, *, block_rows):
    t = pl.program_id(0)
    base = t * block_rows
    local = wrows_ref[...] - base                      # (B*M,)
    inside = (local >= 0) & (local < block_rows)
    tgt = jnp.where(inside, local, block_rows)         # dump row block_rows
    C = val_ref.shape[1]
    v = jnp.concatenate([val_ref[...],
                         jnp.zeros((1, C), val_ref.dtype)], 0)
    val_out[...] = v.at[tgt].set(newf_ref[...])[:block_rows]
    td = jnp.concatenate([tidw_ref[...],
                          jnp.zeros((1,), tidw_ref.dtype)], 0)
    tid_out[...] = td.at[tgt].set(wtid_ref[...])[:block_rows]


def _install(val, tidw, wrows, newf, wtids, *, block_rows, interpret):
    """Scatter winner post-images + TIDs into row tiles.  Winner rows are
    unique (one lock holder per row), so tile-local ``.set`` scatters are
    conflict-free; masked lanes address the per-tile dump row."""
    N, C = val.shape
    Np = _round_up(N, block_rows)
    if Np != N:
        val = jnp.concatenate(
            [val, jnp.zeros((Np - N, C), val.dtype)], 0)
        tidw = jnp.concatenate(
            [tidw, jnp.zeros((Np - N,), tidw.dtype)], 0)
    Kw = wrows.shape[0]
    val2, tid2 = pl.pallas_call(
        functools.partial(_install_kernel, block_rows=block_rows),
        grid=(Np // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows,), lambda i: (i,)),
                  pl.BlockSpec((Kw,), lambda i: (0,)),
                  pl.BlockSpec((Kw, C), lambda i: (0, 0)),
                  pl.BlockSpec((Kw,), lambda i: (0,))],
        out_specs=[pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((Np, C), val.dtype),
                   jax.ShapeDtypeStruct((Np,), tidw.dtype)],
        interpret=interpret,
    )(val, tidw, wrows, newf, wtids)
    return val2[:N], tid2[:N]


@functools.partial(jax.jit,
                   static_argnames=("NT", "deterministic", "interpret",
                                    "block_nt", "block_b", "block_rows"))
def occ_round_pallas(val, tidw, rows, kind, delta_v, wmask, amask, active,
                     epoch_arr, last_tid, ix_args=None, *, NT: int,
                     interpret: bool, deterministic: bool = False,
                     block_nt: int | None = None, block_b: int | None = None,
                     block_rows: int | None = None):
    """One OCC round as the lock-build → validate → install pipeline.

    ``ix_args`` (optional) is the tuple (claim_addr, claim_tid, scan_addr,
    scan_tid, scan_valid, has_claim); ``NT`` the flat lock-space size.
    ``block_nt``/``block_b``/``block_rows`` tile the lock space, the lane
    batch and the row space respectively; ``None`` = one tile (the
    CPU/interpret default, which degenerates to the former monolithic
    cost).  Returns (val', tidw', commit_now, new_tid, new, w) —
    bit-identical to ``ref.occ_round_ref`` for every block size.
    """
    N, C = val.shape
    B, M = rows.shape
    has_ix = ix_args is not None
    if block_nt is None:
        block_nt = NT + 1
    if block_b is None:
        block_b = B
    if block_rows is None:
        block_rows = N

    lanes = jnp.arange(B, dtype=jnp.int32)
    SB = jnp.int32(B)                                  # sentinel lane

    # --- launch 1: write/claim lock over the flat row+index-slot space ---
    addr = jnp.where(wmask, rows, NT).reshape(-1)
    lane = jnp.where(wmask, lanes[:, None], SB).reshape(-1)
    if has_ix:
        (claim_addr, claim_tid, scan_addr, scan_tid, scan_valid,
         has_claim) = ix_args
        addr = jnp.concatenate(
            [addr, jnp.where(has_claim, claim_addr, NT).reshape(-1)])
        lane = jnp.concatenate(
            [lane, jnp.where(has_claim, lanes[:, None], SB).reshape(-1)])
    lock = _lock_build(addr, lane, NT=NT, B=B, block_nt=block_nt,
                       interpret=interpret)

    rlock = None
    if deterministic:
        # Calvin-style: every access (reads included) claims its address
        raddr = jnp.where(amask, rows, NT).reshape(-1)
        rlane = jnp.where(amask, lanes[:, None], SB).reshape(-1)
        if has_ix:
            sa = jnp.where(scan_valid & active[:, None, None], scan_addr, NT)
            raddr = jnp.concatenate([
                raddr, sa.reshape(-1),
                jnp.where(has_claim, claim_addr, NT).reshape(-1)])
            rlane = jnp.concatenate([
                rlane,
                jnp.where(sa < NT, lanes[:, None, None], SB).reshape(-1),
                jnp.where(has_claim, lanes[:, None], SB).reshape(-1)])
        rlock = _lock_build(raddr, rlane, NT=NT, B=B, block_nt=block_nt,
                            interpret=interpret)

    # --- launch 2: validate + TID over lane blocks -----------------------
    Bp = _round_up(B, block_b)
    def pad_b(a):
        if Bp == B:
            return a
        pad = jnp.zeros((Bp - B,) + a.shape[1:], a.dtype)
        return jnp.concatenate([a, pad], 0)

    lane_args = [rows, kind, delta_v, wmask, amask, active, last_tid]
    if has_ix:
        lane_args += [claim_addr, claim_tid, scan_addr, scan_tid,
                      scan_valid, has_claim]
    lane_args = [pad_b(a) for a in lane_args]
    (rows_p, kind_p, delta_p, wmask_p, amask_p, active_p, last_p,
     *ix_p) = lane_args

    def lane_spec(a):
        bs = (block_b,) + a.shape[1:]
        nd = a.ndim
        return pl.BlockSpec(bs, lambda i, nd=nd: (i,) + (0,) * (nd - 1))

    in_specs = [pl.BlockSpec((N, C), lambda i: (0, 0)),      # val resident
                pl.BlockSpec((N,), lambda i: (0,)),          # tidw resident
                pl.BlockSpec((NT + 1,), lambda i: (0,))]     # lock resident
    args = [val, tidw, lock]
    if deterministic:
        pass  # rlock inserted after the per-lane refs in kernel arg order
    in_specs += [lane_spec(rows_p), lane_spec(kind_p), lane_spec(delta_p),
                 lane_spec(wmask_p), lane_spec(amask_p), lane_spec(active_p),
                 pl.BlockSpec((1,), lambda i: (0,)),         # epoch
                 lane_spec(last_p)]
    args += [rows_p, kind_p, delta_p, wmask_p, amask_p, active_p,
             epoch_arr, last_p]
    if deterministic:
        in_specs.append(pl.BlockSpec((NT + 1,), lambda i: (0,)))
        args.append(rlock)
    for a in ix_p:
        in_specs.append(lane_spec(a))
        args.append(a)

    kernel = functools.partial(_validate_kernel, NT=NT, block_b=block_b,
                               deterministic=deterministic, has_ix=has_ix)
    commit_now, new_tid, new, w = pl.pallas_call(
        kernel,
        grid=(Bp // block_b,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((block_b,), lambda i: (i,)),
                   pl.BlockSpec((block_b,), lambda i: (i,)),
                   pl.BlockSpec((block_b, M, C), lambda i: (i, 0, 0)),
                   pl.BlockSpec((block_b, M), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((Bp,), jnp.bool_),
                   jax.ShapeDtypeStruct((Bp,), jnp.uint32),
                   jax.ShapeDtypeStruct((Bp, M, C), val.dtype),
                   jax.ShapeDtypeStruct((Bp, M), jnp.bool_)],
        interpret=interpret,
    )(*args)
    commit_now, new_tid = commit_now[:B], new_tid[:B]
    new, w = new[:B], w[:B]

    # --- launch 3: winner install over row tiles -------------------------
    wrows = jnp.where(w, rows, N).reshape(-1)
    newf = new.reshape(-1, C)
    wtids = jnp.broadcast_to(new_tid[:, None], (B, M)).reshape(-1)
    val2, tid2 = _install(val, tidw, wrows, newf, wtids,
                          block_rows=block_rows, interpret=interpret)
    return val2, tid2, commit_now, new_tid, new, w

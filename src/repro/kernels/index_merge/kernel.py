"""Pallas TPU kernel: fused sorted-segment index merge.

One launch fuses everything ``segment_merge_ref`` does per segment — the
delete-compact (searchsorted position + hit test + hole dedup), BOTH rank
passes (the deletes' hole-prefix counts and the inserts' side="right"
merge positions) and the merged gather/scatter that materializes the new
canonical segment — with the overflow count produced in-kernel.

Tiling (the thomas_merge discipline, applied to destination SLOTS instead
of destination rows): grid = (P, capP // block_slots).  Grid dim 0 walks
segments, so the batched (vmapped at the call sites) merge is ONE launch;
grid dim 1 walks destination-slot tiles.  The segment key/payload runs and
the per-op batches use a constant index map along dim 1, so they stay
VMEM-resident while every tile of the same segment executes; only the
(1, block_slots) output tiles move.  Each tile recomputes the cheap
O(K log cap) per-op rank pass from the resident runs and then resolves its
own slots — no cross-tile state, no (Q, Q) dead-below compare and no
(cap+1,) step-function scatters over the whole output domain per batch
element (the jnp reference's traffic; see ops.index_merge_bytes).

Per destination slot ``o`` the kernel answers "which element of
merge(live existing, live incoming) ranks o-th" with two binary searches
over resident arrays: ``j_excl`` = #live incoming below o (search the
strictly-increasing live insert positions) and the hole-rank inverse
D(r) = #holes at live rank ≤ r (search the monotone p - holes_below(p)).
Free slots are canonical (SENTINEL, 0, 0) and the dropped-live-key
overflow is ``max(n_live + n_ins - cap, 0)`` exactly as the oracle counts
it — bit-identical by tests/test_occ_kernels.py's hypothesis sweep.

Runs under ``interpret=True`` only (the tier-1/CI path); the in-kernel
hole scatter is the same ``.at[].max`` primitive the OCC lock kernel uses.
It does not lower for a TPU yet: the ``(1, capP)`` / ``(1, Kd)`` blocks
break the (8, 128) tiling rule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.storage.index import SENTINEL


def _first_true(pred, shape, size, n_iters):
    """Vectorized lower bound: smallest idx in [0, size] with pred(idx)
    True, assuming pred is monotone (False..False True..True); ``size`` if
    pred never holds.  pred maps an (shape,) int32 idx array to bool."""
    lo = jnp.zeros(shape, jnp.int32)
    hi = jnp.full(shape, size, jnp.int32)

    def body(_, lh):
        lo, hi = lh
        live = lo < hi
        mid = (lo + hi) // 2                       # in [lo, hi) ⊂ [0, size)
        p = pred(mid)
        return (jnp.where(live & ~p, mid + 1, lo),
                jnp.where(live & p, mid, hi))

    lo, _ = jax.lax.fori_loop(0, n_iters, body, (lo, hi))
    return lo


def _index_merge_kernel(key_ref, prow_ref, tid_ref, dk_ref, ik_ref, ip_ref,
                        it_ref, k2_ref, p2_ref, t2_ref, ov_ref, *,
                        cap, block_slots, n_iters, ki_iters):
    capP = key_ref.shape[1]
    Kd = dk_ref.shape[1]
    Ki = ik_ref.shape[1]
    o32 = jnp.int32
    tile = pl.program_id(1)

    seg_k = key_ref[0, :]                          # (capP,) resident run
    seg_p = prow_ref[0, :]
    seg_t = tid_ref[0, :]
    dk = dk_ref[0, :]                              # (Kd,) SENTINEL = masked
    ik = ik_ref[0, :]                              # (Ki,) pre-sorted asc
    ip = ip_ref[0, :]
    it = it_ref[0, :]

    # -- delete rank pass: position, hit test, dedup'd hole-prefix counts.
    # The oracle's sort(tgt)+uniq dedup becomes a scatter-max of hole flags
    # (same dedup: two dels hitting one slot still make ONE hole) + cumsum.
    pos = _first_true(lambda m: seg_k[jnp.minimum(m, capP - 1)] >= dk,
                      (Kd,), capP, n_iters)
    posc = jnp.minimum(pos, capP - 1)
    hit = (seg_k[posc] == dk) & (dk != SENTINEL)
    hole = jnp.zeros((capP + 1,), o32).at[
        jnp.where(hit, posc, capP)].max(1)
    # hb[p] = holes strictly below slot p (== oracle's holes_before at p)
    hb = jnp.concatenate([jnp.zeros((1,), o32),
                          jnp.cumsum(hole[:capP], dtype=o32)])
    n_dead = hb[capP]
    n_live = jnp.sum(seg_k != SENTINEL, dtype=o32) - n_dead

    # -- insert rank pass: side="right" keeps existing-first tie order;
    # subtracting hb[ss] removes the dead slots still sitting below the
    # searchsorted point (the oracle's Ki×Kd dead_below compare, O(log)).
    n_ilive = jnp.sum(ik != SENTINEL, dtype=o32)
    ss = _first_true(lambda m: seg_k[jnp.minimum(m, capP - 1)] > ik,
                     (Ki,), capP, n_iters)
    j_iota = jnp.arange(Ki, dtype=o32)
    pos_i = j_iota + ss - hb[ss]
    # live prefix strictly increasing; dead tail pushed past every slot
    ipos = jnp.where(j_iota < n_ilive, jnp.minimum(pos_i, capP), capP + 1)
    n_merged = n_live + n_ilive

    # -- destination slots owned by this tile
    o = tile * block_slots + jnp.arange(block_slots, dtype=o32)
    j_excl = _first_true(lambda m: ipos[jnp.minimum(m, Ki - 1)] >= o,
                         (block_slots,), Ki, ki_iters)   # #incoming < o
    jidx = jnp.clip(j_excl, 0, Ki - 1)
    is_inc = (ipos[jidx] == o) & (j_excl < Ki)
    r = o - j_excl                                 # live-existing rank
    # D(r) = #holes at live rank ≤ r: live rank of slot p is p - hb[p]
    # (monotone), so search the first p whose rank exceeds r and count the
    # holes below it — the oracle's d_at cumsum, evaluated point-wise.
    pstar = _first_true(lambda m: (m - hb[m]) > r,
                        (block_slots,), capP, n_iters)
    i_src = jnp.clip(r + hb[pstar], 0, capP - 1)
    valid = o < n_merged
    k2 = jnp.where(valid,
                   jnp.where(is_inc, ik[jidx], seg_k[i_src]), SENTINEL)
    live = k2 != SENTINEL                          # canonical free slots
    k2_ref[0, :] = k2
    p2_ref[0, :] = jnp.where(live,
                             jnp.where(is_inc, ip[jidx], seg_p[i_src]), 0)
    t2_ref[0, :] = jnp.where(live,
                             jnp.where(is_inc, it[jidx], seg_t[i_src]),
                             jnp.uint32(0))
    # every tile of segment p derives the same scalar; last write wins
    ov_ref[0, 0] = jnp.maximum(n_merged - cap, 0).astype(o32)


def _round_up(x, m):
    return -(-x // m) * m


@functools.partial(jax.jit, static_argnames=("block_slots", "interpret"))
def index_merge_pallas(key, prow, tid, del_key, ins_key, ins_prow, ins_tid,
                       *, interpret: bool, block_slots=None):
    """Batched fused merge: one launch over all P segments.

    key/prow/tid: (P, cap) sorted canonical segments.  del_key: (P, Kd)
    with SENTINEL = masked out.  ins_key/ins_prow/ins_tid: (P, Ki ≥ 1)
    with each row PRE-SORTED ascending by key (ops.py sorts — the oracle's
    per-segment stable argsort, hoisted out of the kernel).  Returns
    (key', prow', tid' (P, cap), overflow (P,)) bit-identical to
    vmap(segment_merge_ref) over the unsorted batches.
    """
    P, cap = key.shape
    Kd = del_key.shape[1]
    Ki = ins_key.shape[1]
    assert Ki >= 1 and Kd >= 1, "dispatch pads empty op batches"
    if block_slots is None:
        # one tile per segment up to 4096 slots: interpret mode then runs
        # the per-op rank pass once per segment (the monolith cost), while
        # forced smaller blocks exercise the real multi-tile grid in tests
        block_slots = min(_round_up(cap, 128), 4096)
    capP = _round_up(cap, block_slots)
    if capP != cap:
        pad = ((0, 0), (0, capP - cap))
        key = jnp.pad(key, pad, constant_values=SENTINEL)
        prow = jnp.pad(prow, pad)
        tid = jnp.pad(tid, pad)
    kernel = functools.partial(
        _index_merge_kernel, cap=cap, block_slots=block_slots,
        n_iters=int(capP).bit_length() + 1,
        ki_iters=int(Ki).bit_length() + 1)
    seg_spec = pl.BlockSpec((1, capP), lambda p, i: (p, 0))
    k2, p2, t2, ov = pl.pallas_call(
        kernel,
        grid=(P, capP // block_slots),
        in_specs=[
            seg_spec, seg_spec, seg_spec,                  # resident runs
            pl.BlockSpec((1, Kd), lambda p, i: (p, 0)),    # del batch
            pl.BlockSpec((1, Ki), lambda p, i: (p, 0)),    # ins batch
            pl.BlockSpec((1, Ki), lambda p, i: (p, 0)),
            pl.BlockSpec((1, Ki), lambda p, i: (p, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_slots), lambda p, i: (p, i)),
            pl.BlockSpec((1, block_slots), lambda p, i: (p, i)),
            pl.BlockSpec((1, block_slots), lambda p, i: (p, i)),
            pl.BlockSpec((1, 1), lambda p, i: (p, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((P, capP), key.dtype),
            jax.ShapeDtypeStruct((P, capP), prow.dtype),
            jax.ShapeDtypeStruct((P, capP), tid.dtype),
            jax.ShapeDtypeStruct((P, 1), jnp.int32),
        ],
        interpret=interpret,
    )(key, prow, tid, del_key, ins_key, ins_prow, ins_tid)
    return k2[:, :cap], p2[:, :cap], t2[:, :cap], ov[:, 0]

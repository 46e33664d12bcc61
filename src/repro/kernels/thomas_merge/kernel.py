"""Pallas TPU kernel: Thomas-write-rule merge of a replication stream.

TPU adaptation of the replica-side apply loop (paper §3/§5): the destination
table is tiled over rows (grid dim 0); each program instance holds one
(block_rows, C) value tile + (block_rows,) TID tile in VMEM and streams the
ENTIRE write batch through VMEM in (block_k,) chunks, keeping a running
arg-max-by-TID per destination row with masked vector compares — no atomics,
no sorting, deterministic.  Writes whose row falls outside the tile are
masked out; duplicate rows resolve to the max TID (strictly-greater rule).

Grid: (N // block_rows,).  For each k-chunk the kernel materializes a
(block_k, block_rows) one-hot-ish comparison, so block sizes are chosen to
keep block_k * block_rows * 4B within a VMEM budget (see ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _merge_kernel(rows_ref, vals_ref, tids_ref, val_ref, tid_ref,
                  out_val_ref, out_tid_ref, *, block_k: int):
    block_rows, C = val_ref.shape
    K = rows_ref.shape[0]
    row0 = pl.program_id(0) * block_rows

    cur_tid = tid_ref[...]                       # (R,) uint32
    cur_val = val_ref[...]                       # (R, C) int32

    # best incoming write per local row: running (tid, index-into-batch)
    best_tid = jnp.zeros((block_rows,), jnp.uint32)
    best_idx = jnp.zeros((block_rows,), jnp.int32)

    n_chunks = K // block_k

    def body(c, carry):
        best_tid, best_idx = carry
        off = c * block_k
        rows = rows_ref[pl.ds(off, block_k)]                       # (Bk,)
        tids = tids_ref[pl.ds(off, block_k)]                       # (Bk,)
        local = rows - row0                                        # (Bk,)
        in_tile = (local >= 0) & (local < block_rows)
        # (Bk, R) match matrix: does write j target local row r?
        r_iota = jax.lax.broadcasted_iota(jnp.int32, (block_k, block_rows), 1)
        match = in_tile[:, None] & (local[:, None] == r_iota)
        cand = jnp.where(match, tids[:, None], jnp.uint32(0))      # (Bk, R)
        chunk_best = jnp.max(cand, axis=0)                         # (R,)
        chunk_idx = jnp.argmax(cand, axis=0).astype(jnp.int32) + off
        take = chunk_best > best_tid
        best_tid = jnp.where(take, chunk_best, best_tid)
        best_idx = jnp.where(take, chunk_idx, best_idx)
        return best_tid, best_idx

    best_tid, best_idx = jax.lax.fori_loop(0, n_chunks, body,
                                           (best_tid, best_idx))

    apply = best_tid > cur_tid                                     # (R,)
    new_val = vals_ref[best_idx, :]                                # (R, C)
    out_val_ref[...] = jnp.where(apply[:, None], new_val, cur_val)
    out_tid_ref[...] = jnp.where(apply, best_tid, cur_tid)


@functools.partial(jax.jit, static_argnames=("block_rows", "block_k",
                                             "interpret"))
def thomas_merge_pallas(val, tidw, wrows, wvals, wtids, *, interpret: bool,
                        block_rows=256, block_k=256):
    """val: (N, C) int32; tidw: (N,) uint32; wrows/(K,), wvals/(K,C),
    wtids/(K,).  N % block_rows == 0 and K % block_k == 0 (ops.py pads)."""
    N, C = val.shape
    K = wrows.shape[0]
    assert N % block_rows == 0 and K % block_k == 0
    grid = (N // block_rows,)
    kernel = functools.partial(_merge_kernel, block_k=block_k)
    out_val, out_tid = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((K,), lambda i: (0,)),            # rows (streamed)
            pl.BlockSpec((K, C), lambda i: (0, 0)),        # vals
            pl.BlockSpec((K,), lambda i: (0,)),            # tids
            pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
            pl.BlockSpec((block_rows,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
            pl.BlockSpec((block_rows,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, C), val.dtype),
            jax.ShapeDtypeStruct((N,), tidw.dtype),
        ],
        interpret=interpret,
    )(wrows, wvals, wtids, val, tidw)
    return out_val, out_tid

"""Replication: value vs operation streams + the Thomas write rule (§3, §5).

* ``thomas_apply`` — out-of-order-safe value replication: apply a write iff
  its TID exceeds the record's current TID.  Duplicates for the same row are
  resolved with a scatter-max on TID first (ties carry identical values, so
  double-apply is idempotent).  This is the replica-side hot loop and has a
  Pallas kernel (repro.kernels.thomas_merge); this jnp version is the
  reference path and oracle.

* ``replay_operations`` — ordered operation replication for the partitioned
  phase (§5): a single writer per partition makes the stream order-correct, so
  replicas re-execute (kind, delta) instead of shipping post-images.

* index replication — ordered-index maintenance (INSERT_IDX/DELETE_IDX/
  SCAN_CONSUME) replays through the SAME ``storage.index.apply_index_ops``
  batches the executors installed: per queue slot for the partitioned
  phase's ordered stream (``replay_partitioned``), per OCC round for the
  single-master stream (``replay_index_rounds``) — so master and replica
  index arrays stay bit-equal and ``replica_consistent()`` covers indexes.

* byte accounting — value bytes use real row sizes, operation bytes the
  operand sizes, reproducing the paper's ~10x TPC-C saving (Fig. 15).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ops import IDX_OPS, apply_op
from repro.obs import trace as obs
from repro.storage.index import apply_index_ops

KEY_BYTES = 8
TID_BYTES = 8
# an index-maintenance op ships (key, kind, operand words) on the op stream
INDEX_OP_BYTES = KEY_BYTES + 4 + 8


def thomas_apply(val, tidw, wrows, wvals, wtids):
    """val: (N, C); tidw: (N,); wrows: (K,) int32 (-1 = skip);
    wvals: (K, C); wtids: (K,) uint32.  Returns (val', tidw', applied mask)."""
    N, C = val.shape
    rows = jnp.where(wrows >= 0, wrows, N)
    tid_pad = jnp.concatenate([tidw, jnp.zeros((1,), tidw.dtype)])
    # per-row max incoming TID
    merged = tid_pad.at[rows].max(wtids)
    win = (wtids == merged[rows]) & (wtids > tid_pad[rows]) & (wrows >= 0)
    prows = jnp.where(win, rows, N)
    val_pad = jnp.concatenate([val, jnp.zeros((1, C), val.dtype)])
    val_new = val_pad.at[prows].set(wvals)[:N]
    tid_new = tid_pad.at[prows].set(wtids)[:N]
    return val_new, tid_new, win


def thomas_apply_batch(val, tidw, log):
    """Flatten a phase log {'row','val','tid','write'} into one merge."""
    C = val.shape[1]
    rows = jnp.where(log["write"], log["row"], -1).reshape(-1)
    vals = log["val"].reshape(-1, C)
    tids = log["tid"].reshape(-1)
    return thomas_apply(val, tidw, rows, vals, tids)


def replay_operations(val, tidw, log):
    """Ordered replay for one partition's stream (operation replication).

    log: {'row': (T, M), 'kind': (T, M), 'delta': (T, M, C), 'tid': (T, M),
          'write': (T, M)} — already in commit order (single writer).
    """
    def step(carry, slot):
        val, tidw = carry
        old = val[slot["row"]]                                  # (M, C)
        new = apply_op(slot["kind"], old, slot["delta"])
        w = slot["write"]
        # scatter only write ops (read/padding rows may alias a written row)
        R = val.shape[0]
        rows_w = jnp.where(w, slot["row"], R)
        val = jnp.concatenate([val, jnp.zeros((1, val.shape[1]), val.dtype)]
                              ).at[rows_w].set(new)[:R]
        tidw = jnp.concatenate([tidw, jnp.zeros((1,), tidw.dtype)]
                               ).at[rows_w].set(slot["tid"])[:R]
        return (val, tidw), None

    (val, tidw), _ = jax.lax.scan(step, (val, tidw), log)
    return val, tidw


def replay_partitioned(val, tidw, log, index=None, part_ids=None,
                       kernel: str = "jnp", interpret=None):
    """Ordered replay of the whole partitioned-phase stream, all partitions
    at once (the vectorized form of ``replay_operations``), with optional
    index maintenance.

    val: (P, R, C); tidw: (P, R); log: {'row','kind','delta','tid','write'}
    each (P, T, M, ...) plus 'iwrite' (P, T, K) when index ops were logged.
    index: list of {"key","prow","tid"} (P, cap_i) pytrees.
    part_ids: optional (P,) global partition id per array row (rolled
    secondary-replica layouts pass their home-major permutation).
    kernel: "pallas" replays index maintenance through the fused
    index-merge kernel — the same path the master ran, bit-equal arrays.
    """
    P, T, M = log["row"].shape
    K = min(IDX_OPS, M)

    def step(carry, slot):
        val, tidw, index = carry
        old = jnp.take_along_axis(val, slot["row"][..., None], axis=1)
        new = apply_op(slot["kind"], old, slot["delta"])
        R = val.shape[1]
        rows_w = jnp.where(slot["write"], slot["row"], R)

        def commit(v, t, r, n, nt):
            v = jnp.concatenate([v, jnp.zeros((1, v.shape[1]), v.dtype)])
            t = jnp.concatenate([t, jnp.zeros((1,), t.dtype)])
            return v.at[r].set(n)[:R], t.at[r].set(nt)[:R]

        val, tidw = jax.vmap(commit)(val, tidw, rows_w, new, slot["tid"])
        if index is not None:
            # overflow is identical to the master's (same batches) — the
            # executors already counted it
            index, _ = apply_index_ops(
                index, slot["kind"][:, :K], slot["delta"][:, :K],
                slot["iwrite"], slot["tid"][:, :K], part_ids=part_ids,
                use_pallas=(kernel == "pallas"), interpret=interpret)
        return (val, tidw, index), None

    slots = jax.tree.map(lambda a: jnp.moveaxis(a, 1, 0), log)   # (T, P, …)
    (val, tidw, index), _ = jax.lax.scan(step, (val, tidw, index), slots)
    return val, tidw, index


def replay_index_rounds(index, kinds, delta, iwrite, tids, part_ids=None,
                        kernel: str = "jnp", interpret=None):
    """Replay the single-master phase's index-maintenance stream.

    Within one OCC round committed index ops hold disjoint position locks,
    so each round's batch commutes internally and rounds are ordered — the
    replica applies the identical per-round ``apply_index_ops`` batches the
    master installed, producing bit-equal index arrays.

    kinds/delta: (B, K≥) static op arrays (same every round);
    iwrite: (rounds, B, K) committed-index-op masks; tids: (rounds, B, M).
    part_ids: optional (P,) global partition id per segment row (partial /
    rolled-secondary replica layouts).
    kernel: "pallas" replays through the fused index-merge kernel.
    """
    K = iwrite.shape[-1]

    def step(index, per_round):
        iw, tid_r = per_round
        return apply_index_ops(index, kinds[:, :K], delta[:, :K], iw,
                               tid_r[:, :K], part_ids=part_ids,
                               use_pallas=(kernel == "pallas"),
                               interpret=interpret)[0], None

    index, _ = jax.lax.scan(step, index, (iwrite, tids))
    return index


# ---------------------------------------------------------------------------
# per-worker WAL streams (durability, §4.5.1/§5)
# ---------------------------------------------------------------------------
def wal_partition_streams(log, R: int, n_workers: int, worker_of_partition):
    """Split one epoch's partitioned-phase log into per-worker WAL streams.

    The op stream is logged in its §5 TRANSFORMED form — the op was applied
    on the primary, the WHOLE post-image ``val`` is logged with its commit
    TID — so recovery can replay any (file, chunk) order under the Thomas
    write rule.  Rows globalize to the flat P*R space (what checkpoints
    store).  Yields ``(worker, rows, vals, tids, mask)`` with non-empty
    masks only.

    log: {'row' (P,T,M), 'val' (P,T,M,C), 'tid' (P,T,M), 'write' (P,T,M)};
    worker_of_partition: (P,) int — e.g. ``p % n_workers`` (single host)
    or ``p // ppn`` (cluster node blocks).
    """
    rows = np.asarray(log["row"])
    P = rows.shape[0]
    grows = rows + np.arange(P, dtype=np.int64)[:, None, None] * R
    vals = np.asarray(log["val"])
    tids = np.asarray(log["tid"])
    wm = np.asarray(log["write"])
    worker_of_partition = np.asarray(worker_of_partition)
    for w in range(n_workers):
        sel = worker_of_partition == w
        if sel.any() and wm[sel].any():
            yield w, grows[sel], vals[sel], tids[sel], wm[sel]


def wal_master_streams(log, R: int, C: int, n_workers: int,
                       worker_of_partition):
    """Split the single-master phase's value stream (already whole-record
    post-images on global rows) to each owner's WAL.  Yields
    ``(worker, rows, vals, tids, mask)`` with non-empty masks only."""
    rows = np.asarray(log["row"]).reshape(-1)
    vals = np.asarray(log["val"]).reshape(-1, C)
    tids = np.asarray(log["tid"]).reshape(-1)
    wm = np.asarray(log["write"]).reshape(-1)
    owner = np.asarray(worker_of_partition)[rows // R]
    for w in range(n_workers):
        m = wm & (owner == w)
        if m.any():
            yield w, rows, vals, tids, m


def wal_index_streams(plog, n_workers: int, worker_of_partition,
                      cross_kinds=None, cross_delta=None, slog=None):
    """Split one epoch's index-maintenance op streams into per-worker WAL
    chunks.  Unlike record post-images (Thomas-merged, order-free), index
    ops replay ORDERED — each op carries a ``step`` id (partitioned queue
    slot t, then single-master round T+r) and recovery re-applies each
    file's chunks step-group by step-group in file order.  A partition's
    ops all land in its owner's file (partitioned ops by construction;
    single-master ops split by the op key's partition), so cross-file
    chunks touch disjoint segments and commute.

    plog: partitioned log with 'kind' (P,T,M), 'delta' (P,T,M,C),
    'iwrite' (P,T,K), 'tid' (P,T,M).  cross_kinds/cross_delta: the
    single-master batch's (B, M)/(B, M, C) op arrays with slog the SM log
    ('iwrite' (rounds,B,K), 'tid' (rounds,B,M)).

    Yields ``(worker, step, kinds, delta, tids)`` flat committed-op arrays
    in step-ascending order, non-empty only.
    """
    from repro.storage.index import PART_SHIFT
    from repro.core.ops import IX_KEY
    worker_of_partition = np.asarray(worker_of_partition)
    T = 0
    per_worker = {w: [] for w in range(n_workers)}
    if plog is not None and "iwrite" in plog:
        iw = np.asarray(plog["iwrite"])                         # (P, T, K)
        P, T, K = iw.shape
        kinds = np.asarray(plog["kind"])[:, :, :K]
        delta = np.asarray(plog["delta"])[:, :, :K]
        tids = np.asarray(plog["tid"])[:, :, :K]
        steps = np.broadcast_to(np.arange(T, dtype=np.int32)[None, :, None],
                                iw.shape)
        for w in range(n_workers):
            sel = worker_of_partition == w
            m = iw[sel]
            if not m.any():
                continue
            # (n_p, T, K) -> (T, n_p, K) so the flat stream is step-major
            order = (1, 0, 2)
            m_t = m.transpose(order).reshape(-1)
            per_worker[w].append((
                steps[sel].transpose(order).reshape(-1)[m_t],
                kinds[sel].transpose(order).reshape(-1)[m_t],
                delta[sel].transpose(1, 0, 2, 3).reshape(
                    -1, delta.shape[-1])[m_t],
                tids[sel].transpose(order).reshape(-1)[m_t]))
    if slog is not None and "iwrite" in slog:
        iw = np.asarray(slog["iwrite"])                         # (r, B, K)
        rounds, B, K = iw.shape
        kinds = np.broadcast_to(np.asarray(cross_kinds)[None, :, :K],
                                iw.shape)
        cross_delta = np.asarray(cross_delta)
        delta = np.broadcast_to(cross_delta[None, :, :K],
                                iw.shape + (cross_delta.shape[-1],))
        tids = np.asarray(slog["tid"])[:, :, :K]
        steps = np.broadcast_to(
            T + np.arange(rounds, dtype=np.int32)[:, None, None], iw.shape)
        part = (delta[..., IX_KEY].astype(np.int64) >> PART_SHIFT)
        owner = worker_of_partition[np.clip(part, 0,
                                            len(worker_of_partition) - 1)]
        flat = iw.reshape(-1)
        for w in range(n_workers):
            m = flat & (owner.reshape(-1) == w)
            if not m.any():
                continue
            per_worker[w].append((
                steps.reshape(-1)[m], kinds.reshape(-1)[m],
                delta.reshape(-1, delta.shape[-1])[m],
                tids.reshape(-1)[m]))
    for w, chunks in per_worker.items():
        if chunks:
            yield (w,
                   np.concatenate([c[0] for c in chunks]),
                   np.concatenate([c[1] for c in chunks]),
                   np.concatenate([c[2] for c in chunks]),
                   np.concatenate([c[3] for c in chunks]))


# ---------------------------------------------------------------------------
# bandwidth accounting (Fig. 15)
# ---------------------------------------------------------------------------
def value_bytes(log_write_mask, row_bytes_per_op) -> jnp.ndarray:
    """Value replication ships the full row (+key+tid) per committed write."""
    return jnp.sum(jnp.where(log_write_mask,
                             row_bytes_per_op + KEY_BYTES + TID_BYTES, 0))


def operation_bytes(log_write_mask, op_bytes_per_op) -> jnp.ndarray:
    """Operation replication ships only (key, kind, operand)."""
    return jnp.sum(jnp.where(log_write_mask,
                             op_bytes_per_op + KEY_BYTES + 4, 0))


def index_op_bytes(iwrite_mask) -> int:
    """Index-maintenance ops ride the SAME op stream as record ops — their
    bytes are fence-relevant too (they were silently uncounted before)."""
    return int(np.sum(np.asarray(iwrite_mask), dtype=np.int64)) \
        * INDEX_OP_BYTES


def slab_op_bytes(wmask, op_tbl, iwrite, n_slabs: int) -> list[int]:
    """Per-slab op-stream bytes: the epoch's T queue slots split into
    ``n_slabs`` contiguous chunks (record ops + index ops per chunk),
    using the same ``T * s // S`` bounds the cluster engine executes its
    stream slabs with.  The sum over slabs is exactly the epoch's total
    op-stream bytes — the invariant the byte-attribution regression test
    pins.  Shared by both engines so the byte model cannot desynchronize
    between fig13 (cluster) and fig15 (single-host)."""
    T = wmask.shape[1]
    S = max(1, min(n_slabs, T))
    bounds = [T * s // S for s in range(S + 1)]
    out = []
    for s in range(S):
        sl = slice(bounds[s], bounds[s + 1])
        b = int(operation_bytes(wmask[:, sl], op_tbl[:, sl]))
        if iwrite is not None:
            b += index_op_bytes(iwrite[:, sl])
        out.append(b)
    return out


def fence_net_seconds(net, fence_bytes: int, overlapped_bytes: int = 0,
                      t_exec_s: float = 0.0) -> float:
    """The modeled inter-node fence cost, shared by both engines:
    ``fence_bytes`` (the unshipped tail) drain entirely inside the fence
    plus two barrier round trips; ``overlapped_bytes`` shipped DURING the
    preceding ``t_exec_s`` of execution and surface only as the residue
    their transfer did not hide."""
    return net.transfer_s(fence_bytes) + 2 * net.rtt_s \
        + max(0.0, net.transfer_s(overlapped_bytes) - t_exec_s)


def wait_int(x, epoch=None) -> int:
    """``int(x)`` of a byte reduction dispatched to the device, in an
    ``engine.accounting.wait`` span: the reduction queues behind the
    replica's apply of the stream just published, so reading it back is
    where the host waits for that apply."""
    with obs.span("engine.accounting.wait", "wait", epoch=epoch):
        return int(x)


def epoch_stream_bytes(batch, log, has_index: bool, n_slabs: int,
                       pad_fn, epoch=None) -> tuple[int, list[int], int]:
    """One epoch's partitioned-stream byte accounting, shared by both
    engines so their fence models cannot desynchronize.

    batch carries either per-op tables (``p_row_bytes``/``p_op_bytes``,
    padded to the log's T via ``pad_fn``) or uniform per-op-slot tables
    (``row_bytes``/``op_bytes``); log is the phase's (P, T, M) write log
    (with ``iwrite`` when indexes are attached); ``epoch`` labels the
    wait span.  Returns
    ``(value_bytes_alt, per_slab_op_bytes, index_op_bytes)`` — all zeros /
    empty when the batch carries no byte tables."""
    has_tables = "p_row_bytes" in batch \
        or batch.get("row_bytes") is not None
    if not has_tables:
        return 0, [], 0
    wmask = np.asarray(log["write"])
    iw = np.asarray(log["iwrite"]) if has_index else None
    if "p_row_bytes" in batch:
        prb = np.asarray(pad_fn(batch["p_row_bytes"]))
        pob = np.asarray(pad_fn(batch["p_op_bytes"]))
    else:
        prb = np.broadcast_to(
            np.asarray(batch["row_bytes"])[None, None, :], wmask.shape)
        pob = np.broadcast_to(
            np.asarray(batch["op_bytes"])[None, None, :], wmask.shape)
    vb_alt = wait_int(value_bytes(wmask, prb), epoch)
    slabs = slab_op_bytes(wmask, pob, iw, n_slabs)
    ib = index_op_bytes(iw) if iw is not None else 0
    return vb_alt, slabs, ib


def split_overlapped(slab_bytes: list[int]) -> tuple[int, int]:
    """Split a per-slab byte list into (overlapped, fence_exposed).

    The fence-exposed tail is the LAST slab that carried committed bytes —
    it ships closest to the fence, so charging it there is the
    conservative attribution (trailing queue slots are often padding, and
    crediting an empty final slab would claim a 100% hide)."""
    if not slab_bytes:
        return 0, 0
    tail_i = max((i for i, b in enumerate(slab_bytes) if b > 0),
                 default=len(slab_bytes) - 1)
    tail = slab_bytes[tail_i]
    return sum(slab_bytes) - tail, tail


def snapshot_watermark(committed_epoch: int, slab_ledger) -> tuple[int, int]:
    """Per-replica applied watermark for the read tier's snapshot catalog:
    (last-applied fence epoch, stream slabs of that epoch the replicas had
    consumed when it committed).  A committed snapshot's watermark always
    covers its whole epoch — the fence waited on the unshipped tail — so
    the slab count is telemetry (how much of the commit the in-phase
    stream hid), while the epoch is the freshness authority."""
    slabs = sum(1 for (e, _s) in slab_ledger if e == committed_epoch)
    return int(committed_epoch), slabs

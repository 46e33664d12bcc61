"""Partitioned-phase executor (§4.1): H-Store-style serial execution.

Transactions are pre-routed to their home partition — arrays shaped (P, T, …).
A ``lax.scan`` walks the T queue slots; at slot t every partition executes its
t-th transaction simultaneously (vmap across partitions = the paper's
one-worker-thread-per-partition).  No locks, no read validation — there are no
concurrent accesses within a partition (§4.1) — but TIDs are still generated
and written records tagged, so replication and the Thomas write rule work.

Ordered-index ops execute serially too: scans resolve by ``searchsorted``
against the partition's own index segments (``kernel="pallas"`` dispatches
the probe to the fused scan-window kernel of ``repro.kernels.occ``); a
SCAN_CONSUME whose first live key differs from the host-declared EXPECT key
skips its op group (its own delete/tombstone plus every op guarded by it —
TPC-C Delivery's "skip the district" semantics, counted in
``consume_skips`` and logged per-op in ``log["cskip"]`` so the host mirror
can re-queue the district) while the rest of the transaction commits — the
optimistic host-side sequencing validated on-device.

The executor returns the per-partition ordered write log: the operation-
replication stream (§5) replays it in order on replicas — index maintenance
included (the ``iwrite`` mask per queue slot).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import tid as tidlib
from repro.core.ops import (IDX_OPS, SCAN_CONSUME, apply_op,
                            resolve_op_guards, writes_index, writes_primary)
from repro.storage.index import apply_index_ops


def run_partitioned(val, tidw, ptxn, epoch, seq0=None, index=None,
                    kernel: str = "jnp", interpret=None, part_ids=None):
    """val: (P, R, C) int32; tidw: (P, R) uint32.

    ptxn: {'valid': (P,T) bool, 'row': (P,T,M) int32 (partition-local flat
    row), 'kind': (P,T,M) int32, 'delta': (P,T,M,C) int32,
    'user_abort': (P,T) bool}.

    index: optional list of ordered-index pytrees {"key","prow","tid"}
    (P, cap_i) — enables the SCAN_*/INSERT_IDX/DELETE_IDX op kinds (which
    occupy op slots [0, IDX_OPS)).

    kernel: "jnp" (reference) or "pallas" (fused index probe).

    part_ids: optional (P,) int32 — the global partition id each local row
    holds (a shard_map block passes its slice of the global ids so index
    maintenance aligns op keys with the right local segments).

    Returns (val', tid', log, stats).  log holds every op slot's post-image
    (P,T,M,...) with a write mask — the replication stream (plus the
    per-slot "iwrite" index-maintenance mask when an index is attached);
    ``out["seq"]`` carries the final per-partition TID sequence so callers
    chaining the slabs of one epoch thread it into the next call.
    """
    # deferred: importing repro.kernels.occ.ops runs repro.core.ops, whose
    # PACKAGE init (repro/core/__init__.py) imports engine -> this module —
    # a module-level import here breaks `import repro.kernels.occ.ops`
    from repro.kernels.occ.ops import step_index_ops

    P, T, M = ptxn["row"].shape
    K = min(IDX_OPS, M)
    if index is not None:
        assert ptxn["delta"].shape[-1] > 4, \
            "index ops need IX_* param columns + a free guard col"
    seq = seq0 if seq0 is not None else jnp.zeros((P,), jnp.uint32)

    def step(carry, slot):
        val, tidw, seq, index, overflow = carry
        rows, kind, delta = slot["row"], slot["kind"], slot["delta"]   # (P,M)…
        valid = slot["valid"] & ~slot["user_abort"]                    # (P,)

        old = jnp.take_along_axis(val, rows[..., None], axis=1)        # (P,M,C)
        # the last delta column is op-guard metadata when an index is
        # attached — mask it out of the applied (and logged) value stream
        delta_v = delta.at[..., -1].set(0) if index is not None else delta
        new = apply_op(kind, old, delta_v)
        wmask = writes_primary(kind) & valid[:, None]                  # (P,M)
        if index is not None:
            consume_ok, slot_tid = step_index_ops(
                index, kind[:, :K], delta[:, :K], kernel=kernel,
                interpret=interpret)
            # op groups: a failed consume skips its district's guarded
            # updates and its own delete/tombstone; the txn still commits
            wmask, iwrite_ok = resolve_op_guards(kind, delta, consume_ok,
                                                 wmask)

        rtids = jnp.take_along_axis(tidw, rows, axis=1)                # (P,M)
        obs = jnp.max(rtids, axis=1)
        if index is not None:
            obs = jnp.maximum(obs, jnp.max(slot_tid, axis=1))
        new_tid = tidlib.next_tid(epoch, obs, tidlib.make_tid(epoch, seq))
        seq = jnp.where(valid, tidlib.tid_seq(new_tid), seq)

        # scatter ONLY write ops (read/padding ops may share a row with a
        # write in the same txn — a duplicate-index scatter would race);
        # the others aim past the end at row R and are dropped, so the
        # table is updated in place (a -1 would wrap even under "drop")
        R = val.shape[1]
        wrows = jnp.where(wmask, rows, R)                               # (P,M)

        def commit(v, t, r, n, nt):
            return v.at[r].set(n, mode="drop"), t.at[r].set(nt, mode="drop")

        val, tidw = jax.vmap(commit)(
            val, tidw, wrows, new,
            jnp.broadcast_to(new_tid[:, None], wrows.shape))

        log = {"row": rows, "val": new, "tid": jnp.broadcast_to(new_tid[:, None], (P, M)),
               "write": wmask, "kind": kind, "delta": delta_v}
        skips = jnp.int32(0)
        if index is not None:
            iw = writes_index(kind[:, :K]) & valid[:, None] & iwrite_ok  # (P,K)
            index, ov = apply_index_ops(
                index, kind[:, :K], delta[:, :K], iw,
                jnp.broadcast_to(new_tid[:, None], (P, K)),
                part_ids=part_ids,
                use_pallas=(kernel == "pallas"), interpret=interpret)
            overflow = overflow + ov
            log["iwrite"] = iw
            # per-op skipped-consume mask — the consume-feedback stream the
            # host mirror uses to re-queue skipped Delivery districts
            log["cskip"] = (kind[:, :K] == SCAN_CONSUME) & ~consume_ok \
                & valid[:, None]
            skips = jnp.sum(log["cskip"])
        return (val, tidw, seq, index, overflow), (log, valid, skips)

    slots = jax.tree.map(lambda a: jnp.moveaxis(a, 1, 0), ptxn)        # (T,P,…)
    (val, tidw, seq, index, overflow), (log, committed, skips) = jax.lax.scan(
        step, (val, tidw, seq, index, jnp.int32(0)), slots)
    log = jax.tree.map(lambda a: jnp.moveaxis(a, 0, 1), log)           # (P,T,…)
    committed = jnp.moveaxis(committed, 0, 1)                          # (P,T)
    stats = {
        "committed": jnp.sum(committed),
        "user_aborts": jnp.sum(ptxn["valid"] & ptxn["user_abort"]),
        "consume_skips": jnp.sum(skips),
        "writes": jnp.sum(log["write"]),
        "index_overflow": overflow,
    }
    out = {"log": log, "committed": committed, "seq": seq}
    if index is not None:
        out["index"] = index
    return val, tidw, out, stats

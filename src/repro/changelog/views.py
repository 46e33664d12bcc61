"""Incrementally-maintained columnar materialized views over the ChangeLog.

The analytics subscriber the ChangeLog refactor pays for: a columnar
projection of the TPC-C store (the value columns the decision-support
aggregates here read — ``VIEW_COLS``) maintained
incrementally from the SAME ordered op stream the replicas replay,
slab by slab, on whatever device holds the subscriber's arrays.

Correctness rests on the stream's existing guarantees, not new ones:

* partitioned slabs scatter the log's POST-IMAGE values with exactly the
  scatter ``replay_partitioned`` uses (pad-row ``.at[rows_w].set`` per
  queue slot) — the WAL recovery test already pins post-image == replay,
  so the projection is the replayed state's column subset, bit-equal;
* the single-master stream merges under the Thomas write rule
  (``thomas_apply`` on the projected columns) — identical TID
  comparisons pick identical winners, so the projected columns equal
  the replica's.

At every commit fence the working projection is promoted to the
committed one and the CH-benCHmark-style aggregates are computed from it
and STAMPED ``(epoch, aggregates)`` into a bounded history — queryable
between fences (``latest``), with fence-granular time-travel to any
retained epoch (``time_travel``).  ``recompute`` is the from-scratch
oracle over a full committed (P, R, C) value array; the property tests
assert bit-equality at every fence, including across a mid-stream kill +
recovery.  A §4.5 revert snaps the working projection back to committed;
a disk reload rebuilds it via ``on_reset``.

Aggregates (per partition == per warehouse):

* ``revenue``   (P, N_DIST) int64 — Σ order-line amounts per district
  over the retained order ring;
* ``stock_low`` (P,)        int32 — stock rows with quantity below the
  threshold (StockLevel's decision-support cousin);
* ``undelivered`` (P, N_DIST) int32 — NEW-ORDER ring slots not yet
  tombstoned by Delivery (o_id column != 0);
* ``order_latency`` (P, N_DIST, len(LATENCY_BUCKETS)+1) int32 — per-
  district histogram of NewOrder→Delivery latency (in order-ids) over the
  delivered orders retained in the ring: cumulative counts per bucket
  edge plus a trailing total column.

All four read the retained ring state — reused ring slots overwrite in
place, so "revenue" is revenue over the ring window, exactly what the
oracle recomputes.
"""
from __future__ import annotations

from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.replication import thomas_apply
from repro.db import tpcc

#: value columns the views project: col 0 (next_o_id / s_qty / o_id ...),
#: col 2 (order-line amount / d_ytd ...) and col 5 (order latency in
#: order-ids, stamped by Delivery on the orders row)
VIEW_COLS = (0, 2, 5)

#: order-latency histogram bucket edges (latency in order-ids, i.e. how
#: far next_o_id advanced past an order before Delivery consumed it);
#: cumulative counts per edge + a trailing total ("+inf") column
LATENCY_BUCKETS = (1, 2, 4, 8, 16, 32)


class MaterializedViews:
    """ChangeLog subscriber maintaining columnar TPC-C aggregates."""

    def __init__(self, cfg, stock_threshold: int = 15, retain: int = 8):
        self.cfg = cfg
        self.stock_threshold = int(stock_threshold)
        self.retain = int(retain)
        self.proj = None               # (P, R, len(VIEW_COLS)) projection
        self.ptid = None               # (P, R) working TIDs
        self._c_proj = None            # committed projection
        self._c_ptid = None
        self._stamps: deque = deque()  # (epoch, {name: np.ndarray})
        # maintenance counters (analytics bench / summary surface)
        self.slabs_applied = 0
        self.writes_applied = 0
        self.master_merges = 0
        self.commits = 0
        self.reverts = 0
        self._jit_slab = jax.jit(self._apply_slab)
        self._jit_master = jax.jit(self._apply_master)

    # -- stream application ---------------------------------------------
    @staticmethod
    def _apply_slab(proj, ptid, row, vals, tid, write):
        """Scatter one slab's post-image column projection, queue-slot by
        queue-slot — the same pad-row scatter ``replay_partitioned``
        commits with, on the (P, R, len(VIEW_COLS)) projection."""
        R = proj.shape[1]

        def step(carry, slot):
            proj, ptid = carry
            rows_w = jnp.where(slot["write"], slot["row"], R)

            def commit(v, t, r, n, nt):
                v = jnp.concatenate([v, jnp.zeros((1, v.shape[1]),
                                                  v.dtype)])
                t = jnp.concatenate([t, jnp.zeros((1,), t.dtype)])
                return v.at[r].set(n)[:R], t.at[r].set(nt)[:R]

            proj, ptid = jax.vmap(commit)(proj, ptid, rows_w,
                                          slot["val"], slot["tid"])
            return (proj, ptid), None

        slots = jax.tree.map(
            lambda a: jnp.moveaxis(a, 1, 0),
            {"row": row, "val": vals, "tid": tid, "write": write})
        (proj, ptid), _ = jax.lax.scan(step, (proj, ptid), slots)
        return proj, ptid

    @staticmethod
    def _apply_master(proj, ptid, rows, vals, tids):
        """Thomas-merge the single-master stream's projected post-images
        on the flat row space (identical TID comparisons to the replica's
        ``thomas_apply_batch`` — identical winners)."""
        P, R, Cp = proj.shape
        v, t, _ = thomas_apply(proj.reshape(P * R, Cp),
                               ptid.reshape(P * R), rows, vals, tids)
        return v.reshape(P, R, Cp), t.reshape(P, R)

    def on_slab(self, log, info):
        if self.proj is None:
            return
        # cluster slab logs arrive mesh-sharded; the projection lives on
        # one device — gather the slab there (same hop _ReplicaShip pays)
        dev = next(iter(self.proj.devices()))
        log = jax.device_put(
            {k: log[k] for k in ("row", "val", "tid", "write")}, dev)
        vals = jnp.stack([log["val"][..., c] for c in VIEW_COLS], axis=-1)
        self.proj, self.ptid = self._jit_slab(
            self.proj, self.ptid, log["row"], vals, log["tid"],
            log["write"])
        self.slabs_applied += 1
        self.writes_applied += int(np.asarray(log["write"]).sum())

    def on_master(self, stream):
        if self.proj is None or stream["log"] is None:
            return
        dev = next(iter(self.proj.devices()))
        log = jax.device_put(
            {k: stream["log"][k] for k in ("row", "val", "tid", "write")},
            dev)
        C = log["val"].shape[-1]
        rows = jnp.where(log["write"], log["row"], -1).reshape(-1)
        vals = jnp.stack(
            [log["val"].reshape(-1, C)[:, c] for c in VIEW_COLS], axis=-1)
        tids = log["tid"].reshape(-1)
        self.proj, self.ptid = self._jit_master(self.proj, self.ptid,
                                                rows, vals, tids)
        self.master_merges += 1
        self.writes_applied += int(np.asarray(log["write"]).sum())

    # -- fences ----------------------------------------------------------
    def on_commit(self, epoch, record):
        if self.proj is None:
            return
        self._c_proj, self._c_ptid = self.proj, self.ptid
        self.commits += 1
        self._stamp(epoch)

    def on_revert(self, epoch, n_slabs):
        if self._c_proj is None:
            return
        self.proj, self.ptid = self._c_proj, self._c_ptid
        self.reverts += 1

    def on_reset(self, val, tid, epoch):
        """Disk reload (§4.5.1): rebuild the projection from the recovered
        committed arrays and stamp the recovered fence."""
        val = jnp.asarray(val)
        self.proj = jnp.stack([val[..., c] for c in VIEW_COLS], axis=-1)
        self.ptid = jnp.asarray(tid)
        self._c_proj, self._c_ptid = self.proj, self.ptid
        self._stamp(epoch)

    def _stamp(self, epoch):
        epoch = int(epoch)
        if self._stamps and self._stamps[-1][0] == epoch:
            return                                   # idempotent per fence
        self._stamps.append(
            (epoch, self._aggregates(np.asarray(self._c_proj))))
        while len(self._stamps) > self.retain:
            self._stamps.popleft()

    # -- aggregates ------------------------------------------------------
    def _aggregates(self, proj) -> dict:
        """Aggregates off an np (P, R, len(VIEW_COLS)) column projection.
        Host-side numpy on purpose: int64 sums are exact without the x64
        flag, and the fence stamp is the only consumer (once per epoch)."""
        cfg = self.cfg
        P = proj.shape[0]
        ring, nd = cfg.order_ring, tpcc.N_DIST
        ol = proj[:, cfg.off_order_line:
                  cfg.off_order_line + nd * ring * 15, 1]
        st = proj[:, cfg.off_stock:cfg.off_stock + cfg.n_items, 0]
        no = proj[:, cfg.off_new_order:cfg.off_new_order + nd * ring, 0]
        # Delivery stamps the order's age (in order-ids, always >= 1) in
        # orders col 5; NewOrder's whole-row SET zeroes it on ring reuse,
        # so lat > 0 selects exactly the ring's delivered-and-retained
        # orders.  Counts are exact integers — bit-equal to the oracle.
        lat = proj[:, cfg.off_orders:cfg.off_orders + nd * ring,
                   2].reshape(P, nd, ring)
        live = lat > 0
        return {
            "revenue": ol.astype(np.int64).reshape(
                P, nd, ring * 15).sum(axis=-1),
            "stock_low": (st < self.stock_threshold).sum(
                axis=-1).astype(np.int32),
            "undelivered": (no.reshape(P, nd, ring) != 0).sum(
                axis=-1).astype(np.int32),
            "order_latency": np.stack(
                [(live & (lat <= b)).sum(axis=-1)
                 for b in LATENCY_BUCKETS] + [live.sum(axis=-1)],
                axis=-1).astype(np.int32),
        }

    def recompute(self, val) -> dict:
        """From-scratch oracle: the same aggregates off a full committed
        (P, R, C) value array — what every stamped fence must bit-equal
        (integer sums are exact and order-free)."""
        v = np.asarray(val)
        return self._aggregates(np.stack([v[..., c] for c in VIEW_COLS],
                                         axis=-1))

    # -- queries ---------------------------------------------------------
    @property
    def attached(self) -> bool:
        return self.proj is not None

    def latest(self):
        """(epoch, aggregates) of the freshest committed fence stamp."""
        return self._stamps[-1] if self._stamps else None

    def retained_epochs(self) -> list[int]:
        return [e for e, _ in self._stamps]

    def time_travel(self, epoch: int):
        """The aggregates exactly as stamped at fence ``epoch`` (None if
        no longer retained)."""
        for e, aggs in self._stamps:
            if e == int(epoch):
                return aggs
        return None

"""Plain serial reference of the STAR epoch, in numpy.

It executes the batches the engine was handed, one transaction at a time,
and yields the state a correct engine must end in: record values, the
entries of every ordered index, and the commit decision of each
transaction.  It imports nothing of the program under test.

Semantics (the stored-procedure model the configurations state):

* A transaction is M ops ``(row, kind, delta)`` over int32 rows of C words.
  Kinds: READ 0, SET 1, ADD 2, APPEND 3 (rolling hash + capped length),
  STOCK_DECR 4 (TPC-C stock update), PAY_CUST 5 (TPC-C Payment customer),
  SCAN_READ 6, SCAN_CONSUME 7, INSERT_IDX 8, DELETE_IDX 9.  Index ops sit
  in the first ``IDX_OPS`` op slots; their delta columns hold
  (key or lo, hi or prow, expect, index id).
* With indexes, the last delta column is a guard: an op with guard g > 0
  applies only if the consume at op slot g - 1 validated.  A consume
  validates when the first live key at or after ``lo`` equals ``expect``
  and lies below ``hi``; it then deletes that key and zeroes its row.
* Partitioned phase: each partition runs its queue slots in order, with no
  concurrency control; every valid transaction that does not abort by
  itself commits.
* Single-master phase: deterministic Silo OCC in rounds over a snapshot.
  Within a round, lanes claim their write rows and the index positions
  they change, lowest lane first; a lane commits when it holds every claim
  and no earlier lane claimed anything it read (rows, and the scanned
  index window with its next-key slot).  The outcome is the serial
  execution of the committed lanes in (round, lane) order.  Lanes still
  uncommitted after ``occ_rounds`` rounds commit in a later epoch.
* Replication: every replica must hold exactly the master's committed
  state at each fence.
"""
from __future__ import annotations

from bisect import bisect_left, bisect_right

import numpy as np

READ, SET, ADD, APPEND, STOCK_DECR, PAY_CUST = 0, 1, 2, 3, 4, 5
SCAN_READ, SCAN_CONSUME, INSERT_IDX, DELETE_IDX = 6, 7, 8, 9
IX_KEY = IX_LO = 0
IX_HI = IX_PROW = 1
IX_EXPECT, IX_ID = 2, 3
IDX_OPS = 12
SCAN_L = 8
SENTINEL = 0x7FFFFFFF
PART_SHIFT = 24
APPEND_CAP = 500


def _hash(h, x):
    h = h.astype(np.int64) * 1000003 + x.astype(np.int64)
    return (h & 0x7FFFFFFF).astype(np.int32)


def apply_ops(kind, old, delta):
    """New row values of ops ``kind (n,)`` on rows ``old (n, C)``."""
    old = old.astype(np.int32)
    delta = delta.astype(np.int32)
    new = old.copy()
    k = kind
    with np.errstate(over="ignore"):
        s = k == SET
        new[s] = delta[s]
        a = k == ADD
        new[a] = old[a] + delta[a]
        for sel in (k == APPEND, k == PAY_CUST):
            if sel.any():
                new[sel, 0] = _hash(old[sel, 0], delta[sel, 0])
                new[sel, 1] = np.minimum(old[sel, 1] + delta[sel, 1],
                                         APPEND_CAP)
        pc = k == PAY_CUST
        new[pc, 2:] = old[pc, 2:] + delta[pc, 2:]
        st = k == STOCK_DECR
        if st.any():
            q = old[st, 0] - delta[st, 0]
            new[st, 0] = np.where(q >= 10, q, q + 91)
            new[st, 1] = old[st, 1] + delta[st, 0]
            new[st, 2] = old[st, 2] + 1
            new[st, 3] = old[st, 3] + delta[st, 3]
        new[k == SCAN_CONSUME] = 0
    return new


def writes_primary(kind):
    return ((kind > READ) & (kind <= PAY_CUST)) | (kind == SCAN_CONSUME)


def writes_index(kind):
    return kind >= SCAN_CONSUME


def is_index(kind):
    return kind >= SCAN_READ


class Index:
    """One ordered index of one partition: a sorted multiset of keys with a
    row payload per entry."""

    def __init__(self):
        self.keys: list[int] = []
        self.prows: list[int] = []

    def first_at(self, lo: int):
        """(position, first key at or after ``lo`` or SENTINEL)."""
        pos = bisect_left(self.keys, lo)
        return pos, (self.keys[pos] if pos < len(self.keys) else SENTINEL)

    def key_at(self, pos: int) -> int:
        return self.keys[pos] if pos < len(self.keys) else SENTINEL

    def apply(self, deletes, inserts):
        """Deletes resolve against the entries before the batch (one hole
        per distinct key), then inserts merge after equal keys."""
        for key in sorted(set(deletes)):
            pos = bisect_left(self.keys, key)
            if pos < len(self.keys) and self.keys[pos] == key:
                del self.keys[pos], self.prows[pos]
        for key, prow in sorted(inserts):
            pos = bisect_right(self.keys, key)
            self.keys.insert(pos, key)
            self.prows.insert(pos, prow)

    def entries(self):
        return sorted(zip(self.keys, self.prows))


class Reference:
    """The reference database: records ``(P, R, C)`` and ``n_indexes``
    ordered indexes per partition."""

    def __init__(self, init_val, n_indexes: int = 0, occ_rounds: int = 16):
        self.val = np.array(init_val, np.int32, copy=True)
        self.P, self.R, self.C = self.val.shape
        self.n_idx = n_indexes
        self.idx = [[Index() for _ in range(self.P)]
                    for _ in range(n_indexes)]
        self.rounds = occ_rounds

    # ------------------------------------------------------------------
    def _guards(self, kind, delta, consume_ok):
        """Apply op guards: (write mask, index-write mask)."""
        K = consume_ok.shape[0]
        wmask = writes_primary(kind)
        guard = delta[:, -1] * (writes_primary(kind) | writes_index(kind))
        gok = consume_ok[np.clip(guard - 1, 0, K - 1)]
        guard_ok = np.where(guard > 0, gok, True)
        live = np.where(kind[:K] == SCAN_CONSUME, consume_ok, True)
        wmask = wmask & guard_ok
        wmask[:K] &= live
        return wmask, writes_index(kind[:K]) & live & guard_ok[:K]

    def _index_batch(self, kind, delta, iwrite):
        """Apply one transaction's committed index ops."""
        per = {}
        for k in np.nonzero(iwrite)[0]:
            i, key = int(delta[k, IX_ID]), int(delta[k, IX_KEY])
            p = key >> PART_SHIFT
            dels, ins = per.setdefault((i, p), ([], []))
            if kind[k] == INSERT_IDX:
                ins.append((key, int(delta[k, IX_PROW])))
            elif kind[k] == SCAN_CONSUME:
                dels.append(int(delta[k, IX_EXPECT]))
            else:
                dels.append(key)
        for (i, p), (dels, ins) in per.items():
            self.idx[i][p].apply(dels, ins)

    def _consume_ok(self, kind, delta, p_of, only_consume: bool):
        K = min(IDX_OPS, kind.shape[0])
        ok = np.full(K, only_consume)
        for k in range(K):
            if not is_index(kind[k]) or (only_consume
                                         and kind[k] != SCAN_CONSUME):
                continue
            ix = self.idx[int(delta[k, IX_ID])][p_of(int(delta[k, IX_LO]))]
            _, first = ix.first_at(int(delta[k, IX_LO]))
            ok[k] = (first == delta[k, IX_EXPECT]) and first < delta[k, IX_HI] \
                and first != SENTINEL
        return ok

    # ------------------------------------------------------------------
    def partitioned(self, ptxn) -> np.ndarray:
        """Run the partitioned phase; returns the commit mask ``(P, T)``."""
        valid = ptxn["valid"] & ~ptxn["user_abort"]
        P, T = valid.shape
        if not self.n_idx:
            # without indexes a slot's transactions, one per partition,
            # touch disjoint rows: run them side by side
            for t in range(T):
                ps = np.nonzero(valid[:, t])[0]
                if not ps.size:
                    continue
                rows, kind = ptxn["row"][ps, t], ptxn["kind"][ps, t]
                new = apply_ops(kind.reshape(-1),
                                self.val[ps[:, None], rows].reshape(-1, self.C),
                                ptxn["delta"][ps, t].reshape(-1, self.C))
                w = writes_primary(kind)
                pw = np.broadcast_to(ps[:, None], rows.shape)[w]
                self.val[pw, rows[w]] = new.reshape(rows.shape + (self.C,))[w]
            return valid
        for t in range(T):
            for p in np.nonzero(valid[:, t])[0]:
                rows, kind = ptxn["row"][p, t], ptxn["kind"][p, t]
                delta = ptxn["delta"][p, t]
                dv = delta.copy()
                if self.n_idx:
                    dv[:, -1] = 0
                new = apply_ops(kind, self.val[p, rows], dv)
                if self.n_idx:
                    ok = self._consume_ok(kind, delta, lambda lo, p=p: p,
                                          only_consume=True)
                    wmask, iw = self._guards(kind, delta, ok)
                else:
                    wmask, iw = writes_primary(kind), None
                self.val[p, rows[wmask]] = new[wmask]
                if iw is not None and iw.any():
                    self._index_batch(kind, delta, iw)
        return valid

    def single_master(self, cross) -> np.ndarray:
        """Run the single-master phase; returns the commit mask ``(B,)``."""
        flat = self.val.reshape(self.P * self.R, self.C)
        runnable = cross["valid"] & ~cross["user_abort"]
        committed = np.zeros(runnable.shape[0], bool)
        P = self.P

        def p_of(lo):
            return min(max(lo >> PART_SHIFT, 0), P - 1)

        for _ in range(self.rounds):
            active = np.nonzero(runnable & ~committed)[0]
            if not active.size:
                break
            plans = []
            for b in active:
                rows, kind = cross["row"][b], cross["kind"][b]
                delta = cross["delta"][b]
                reads = {("r", int(r)) for r in
                         rows[~is_index(kind) | (kind == SCAN_CONSUME)]}
                claims, iw = set(), None
                if self.n_idx:
                    ok = self._consume_ok(kind, delta, p_of,
                                          only_consume=False)
                    wmask, iw = self._guards(kind, delta, ok)
                    for k in np.nonzero(is_index(kind[:IDX_OPS]))[0]:
                        i, lo = int(delta[k, IX_ID]), int(delta[k, IX_LO])
                        ix = self.idx[i][p_of(lo)]
                        pos, _ = ix.first_at(lo)
                        if writes_index(kind[k]):
                            claims.add(("i", i, p_of(lo), pos))
                        if kind[k] in (SCAN_READ, SCAN_CONSUME):
                            for j in range(SCAN_L + 1):
                                if j and not ix.key_at(pos + j - 1) \
                                        < delta[k, IX_HI]:
                                    break
                                reads.add(("i", i, p_of(lo), pos + j))
                else:
                    wmask = writes_primary(kind)
                claims |= {("r", int(r)) for r in rows[wmask]}
                plans.append((b, claims, reads, wmask, iw))
            lock = {}
            for b, claims, _, _, _ in plans:           # lowest lane first
                for a in claims:
                    lock.setdefault(a, b)
            big = runnable.shape[0]
            for b, claims, reads, wmask, iw in plans:
                if any(lock[a] != b for a in claims):
                    continue
                if any(lock.get(a, big) < b for a in reads):
                    continue
                committed[b] = True
                rows, kind = cross["row"][b], cross["kind"][b]
                dv = cross["delta"][b].copy()
                if self.n_idx:
                    dv[:, -1] = 0
                new = apply_ops(kind, flat[rows], dv)
                flat[rows[wmask]] = new[wmask]
                if iw is not None and iw.any():
                    self._index_batch(kind, cross["delta"][b], iw)
        return committed

    def epoch(self, batch):
        """One epoch: (partitioned commit mask, single-master commit mask)."""
        return self.partitioned(batch["ptxn"]), \
            self.single_master(batch["cross"])

    def index_entries(self, i: int, p: int):
        return self.idx[i][p].entries()

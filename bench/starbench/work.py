"""Bytes the work of a batch needs: the least a phase has to move.

A transaction has to read each distinct record it touches once, value and
TID, and write back each distinct record it changes.  Its index ops read
and write (key, row, TID) entries: an insert or a delete reads its
position and writes one entry, a scan reads its window (``SCAN_L`` result
slots and the next-key slot), a consume reads the window and deletes one
entry.  Padding op slots, which repeat a row the transaction already
names, add nothing.  Only live transactions count (valid, not aborting
by themselves), each once, however many OCC rounds it took.
"""
from __future__ import annotations

import numpy as np

from starbench.reference import (DELETE_IDX, INSERT_IDX, SCAN_CONSUME,
                                 SCAN_L, SCAN_READ, is_index, writes_primary)

WORD = 4
ENTRY = 3 * WORD                # index entry: key, row, TID


def _distinct(rows, mask) -> int:
    """Distinct rows per transaction under ``mask``, summed."""
    r = np.where(mask, rows.astype(np.int64), -1)
    r = np.sort(r, axis=1)
    new = np.concatenate([r[:, :1] >= 0,
                          (r[:, 1:] != r[:, :-1]) & (r[:, 1:] >= 0)], axis=1)
    return int(new.sum())


def txn_bytes(rows, kind, n_cols: int) -> int:
    """rows, kind: (n, M) of the live transactions."""
    record = n_cols * WORD + WORD                       # value + TID
    primary = ~is_index(kind) | (kind == SCAN_CONSUME)
    read = _distinct(rows, primary)
    written = _distinct(rows, writes_primary(kind))
    scans = int(np.isin(kind, (SCAN_READ, SCAN_CONSUME)).sum())
    point = int(np.isin(kind, (INSERT_IDX, DELETE_IDX)).sum())
    consumes = int((kind == SCAN_CONSUME).sum())
    return ((read + written) * record
            + scans * (SCAN_L + 1) * ENTRY
            + (2 * point + consumes) * ENTRY)


def partitioned_bytes(batch: dict, n_cols: int) -> int:
    p = batch["ptxn"]
    live = p["valid"] & ~p["user_abort"]
    return txn_bytes(p["row"][live], p["kind"][live], n_cols)


def single_master_bytes(batch: dict, n_cols: int) -> int:
    c = batch["cross"]
    live = c["valid"] & ~c["user_abort"]
    return txn_bytes(c["row"][live], c["kind"][live], n_cols)

"""YCSB transactions as the STAR paper runs them (section 7.1.1), made by
the benchmark itself so that the rows keep the source's width.

One table of ``records_per_partition`` rows in each of ``n_partitions``
partitions, each row ``row_words`` int32 words (the paper's 10 columns of
10 bytes are 25 words); ``ops_per_txn`` ops per transaction, ``write_ops``
of them a whole-row SET and the rest READs, on uniform keys; a share
``cross_ratio`` of transactions spans partitions (the first op stays on
the home partition, the others go to any).  The arithmetic follows the
program's own generator (``repro.db.ycsb.make_raw``), with the width a
parameter there fixed at 10 words.

Requests come in the served path's format: ``parts`` and partition-local
``rows`` (n, M), ``kinds`` (n, M), ``deltas`` (n, M, C), ``user_abort``,
``home`` (-1 for a cross-partition transaction: it goes to the master
queue undeclared) and ``read_only``.
"""
from __future__ import annotations

import numpy as np

from starbench.reference import READ, SET

WORD = 4


class YCSBSource:
    def __init__(self, params: dict, seed: int):
        self.P = int(params["n_partitions"])
        self.R = int(params["records_per_partition"])
        self.cross_ratio = float(params["cross_ratio"])
        self.M = int(params["ops_per_txn"])
        self.C = int(params["row_words"])
        self.write_ops = int(params["write_ops"])
        self.rng = np.random.default_rng(seed)
        # the replication stream's byte accounting: a write ships its row
        self.row_bytes = np.full(self.M, self.C * WORD, np.int32)
        self.op_bytes = self.row_bytes.copy()

    def init_values(self, rng: np.random.Generator) -> np.ndarray:
        """The table's records, ``(P, R, C)`` random int32 words."""
        return rng.integers(0, 2**31 - 1, (self.P, self.R, self.C),
                            dtype=np.int32)

    def generate(self, n: int) -> dict:
        rng, P, M = self.rng, self.P, self.M
        is_cross = rng.random(n) < self.cross_ratio
        home = rng.integers(0, P, n).astype(np.int32)
        parts = np.where(is_cross[:, None],
                         rng.integers(0, P, (n, M)).astype(np.int32),
                         home[:, None])
        parts[:, 0] = home
        rows = rng.integers(0, self.R, (n, M)).astype(np.int32)
        kinds = np.full((n, M), READ, np.int32)
        wpos = rng.integers(0, M, (n, self.write_ops))
        for j in range(self.write_ops):
            kinds[np.arange(n), wpos[:, j]] = SET
        deltas = np.zeros((n, M, self.C), np.int32)
        w = kinds == SET
        deltas[w] = rng.integers(0, 2**31 - 1, (int(w.sum()), self.C),
                                 dtype=np.int32)
        return {"parts": parts, "rows": rows, "kinds": kinds,
                "deltas": deltas, "user_abort": np.zeros(n, bool),
                "home": np.where(is_cross, -1, home).astype(np.int32),
                "read_only": (kinds == READ).all(axis=1)}

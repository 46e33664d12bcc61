"""Decide ``correct``: the engine's end state and commit decisions against
the plain reference run over the requests the clients issued.

The program chooses which request goes into which slot of which epoch's
batch, and that order is its serialization; the content of each slot is
not its to choose.  So the reference replays each epoch's batch rebuilt
from the clients' own record of every request, in the slots the program
put them, and the batch the program formed is held to that record.

Every number compared counts disagreements, and each has the limit 0:

* ``request_ops``: batch slots whose ops (rows, kinds, deltas, abort
  flag), or whose partition, differ from those of the request the slot
  answers, and live slots that answer no request;
* ``commit_decisions``: transactions whose commit or abort differs;
* ``<copy>_rows``: rows of a copy whose words differ from the
  reference's, for every copy the configuration names: on one chip the
  master and the replica store; on a mesh the master blocks, the full
  replica and the physical secondaries;
* ``<copy>_index_entries``: (key, row) entries of the ordered indexes
  present on one side only.
"""
from __future__ import annotations

from collections import Counter

import numpy as np

from starbench.reference import SENTINEL, Reference


def _entries(index):
    key, prow = np.asarray(index["key"]), np.asarray(index["prow"])
    out = []
    for p in range(key.shape[0]):
        live = key[p] != SENTINEL
        out.append(sorted(zip(key[p][live].tolist(),
                              prow[p][live].tolist())))
    return out


def stores(engine) -> dict:
    """Every copy of the database the configuration names, as
    ``{name: (values (P, R, C), indexes)}`` in partition order."""
    if hasattr(engine, "store"):                      # one chip
        return {"master": (engine.store.val, engine.store.indexes),
                "replica": (engine.replica_store.val,
                            engine.replica_store.indexes)}
    eng = engine.eng                                  # a mesh of nodes
    out = {"master": (eng.part_val, eng.part_idx),
           "full_replica": (eng.full_val, eng.full_idx)}
    if eng.secondary:
        # row p of the secondary copies holds partition (p - ppn) mod P
        def unroll(a):
            return np.roll(np.asarray(a), -eng.ppn, axis=0)
        out["secondary"] = (unroll(eng.sec_val),
                            [{k: unroll(ix[k]) for k in ("key", "prow")}
                             for ix in eng.sec_idx])
    return out


def engine_state(engine) -> dict:
    """Host copies of what the check compares, read from the engine."""
    return {name: (np.asarray(val), [_entries(ix) for ix in idx])
            for name, (val, idx) in stores(engine).items()}


def _rows_of(tenant, txn, offset):
    """Index into the issued record of each slot's request, -1 if none."""
    idx = np.full(np.shape(txn), -1, np.int64)
    for t, o in offset.items():
        sel = (tenant == t) & (txn >= 0)
        idx[sel] = o + txn[sel]
    return idx


def _slots(formed, idx, req, parts_ok, globalize):
    """One phase's slots rebuilt from the issued requests ``idx``, and the
    number of slots that differ from the ``formed`` ones."""
    valid = idx >= 0
    safe = np.where(valid, idx, 0)
    rows = req["rows"][safe]
    if globalize is not None:
        rows = (req["parts"][safe].astype(np.int64) * globalize
                + rows).astype(np.int32)
    out = {"valid": valid,
           "row": np.where(valid[..., None], rows, 0).astype(np.int32),
           "kind": np.where(valid[..., None], req["kinds"][safe], 0)
           .astype(np.int32),
           "delta": np.where(valid[..., None, None], req["deltas"][safe], 0)
           .astype(np.int32),
           "user_abort": req["user_abort"][safe] & valid}
    fv = np.asarray(formed["valid"], bool)
    same = ((out["row"] == formed["row"]).all(-1)
            & (out["kind"] == formed["kind"]).all(-1)
            & (out["delta"] == formed["delta"]).all((-2, -1))
            & (out["user_abort"] == formed["user_abort"]))
    bad = (valid != fv) | (valid & ~same) | (valid & ~parts_ok(safe))
    return out, int(bad.sum())


def issued_batches(epochs, ledger, R: int):
    """Every recorded batch rebuilt from the issued requests in the slots
    the program gave them, and the count of slots that differ."""
    req, offset = ledger.requests()
    out, differ = [], 0
    for k, e in enumerate(epochs):
        formed = e["batch"]
        P, T = formed["ptxn"]["valid"].shape
        B = formed["cross"]["valid"].shape[0]
        keys = ledger.formed.get(k)
        if keys is None or req is None:
            # an epoch no request reached: every slot must be empty
            empty = int(np.asarray(formed["ptxn"]["valid"]).sum()
                        + np.asarray(formed["cross"]["valid"]).sum())
            out.append(formed)
            differ += empty
            continue
        pt, pi, ct, ci = keys
        p_idx = _rows_of(pt, pi, offset)
        c_idx = np.full(B, -1, np.int64)
        c_idx[:len(ci)] = _rows_of(ct, ci, offset)
        home = np.arange(P)[:, None, None]
        ptxn, dp = _slots(formed["ptxn"], p_idx, req,
                          lambda safe: (req["parts"][safe] == home).all(-1),
                          None)
        cross, dc = _slots(formed["cross"], c_idx, req,
                           lambda safe: np.ones(safe.shape, bool), R)
        out.append(dict(formed, ptxn=ptxn, cross=cross))
        differ += dp + dc
    return out, differ


def reference_run(epochs, batches, world, occ_rounds: int):
    """Replay ``batches`` through the reference; returns it and the number
    of commit decisions that differ from the engine's in ``epochs``."""
    ref = Reference(world.init_val, n_indexes=len(world.index_specs or []),
                    occ_rounds=occ_rounds)
    differ = 0
    for e, batch in zip(epochs, batches):
        p_ok, c_ok = ref.epoch(batch)
        T, B = p_ok.shape[1], c_ok.shape[0]
        differ += int((p_ok != e["p_committed"][:, :T]).sum())
        differ += int((c_ok != e["c_committed"][:B]).sum())
    return ref, differ


def _index_differ(a, b) -> int:
    n = 0
    for seg_a, seg_b in zip(a, b):
        ca, cb = Counter(seg_a), Counter(seg_b)
        n += sum(((ca - cb) + (cb - ca)).values())
    return n


def compare(state: dict, ref: Reference, request_differ: int,
            commit_differ: int) -> dict:
    """Each compared number beside its limit, 0 for all."""
    out = {"request_ops": request_differ, "commit_decisions": commit_differ}
    for name, (val, _) in state.items():
        out[f"{name}_rows"] = int(np.any(val != ref.val, axis=-1).sum())
    if ref.n_idx:
        want = [[ref.index_entries(i, p) for p in range(ref.P)]
                for i in range(ref.n_idx)]
        for name, (_, idx) in state.items():
            out[f"{name}_index_entries"] = sum(
                _index_differ(g, w) for g, w in zip(idx, want))
    return {k: {"value": v, "limit": 0} for k, v in out.items()}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())

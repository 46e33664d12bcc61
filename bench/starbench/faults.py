"""Broken variants of the timed path, for the control and the fault tests.

Each fault sits in the engine proxy: ``before(run, batch)`` may change the
batch the engine executes, ``after(run, batch, m)`` may change the engine's
state or the answers it returns.  The run records the batch the service
formed, which a fault changes by changing it in place; the reference
replays what the clients issued.

* ``replica_lag`` is the control: the replica store skips the
  single-master phase's value stream, as an acknowledgement sent before
  that stream is applied would leave it; it breaks the configurations'
  replication guarantee.
* ``state_unchanged``: each epoch returns its state as it found it, while
  its commits are acknowledged.
* ``half_batch``: the second half of the partitions and of the master
  lanes are left out of execution and acknowledged as committed.
* ``altered_answer``: each epoch's first live transaction is answered with
  the opposite of its commit decision.
* ``altered_op``: each epoch's first live partition slot has its ops moved
  one row on where the batch is formed, as a fault in admission or batch
  formation would: the request runs on rows it never named.
"""
from __future__ import annotations

import numpy as np


class Fault:
    def before(self, run, batch):
        return batch

    def after(self, run, batch, m):
        pass


class ReplicaLag(Fault):
    def before(self, run, batch):
        if not getattr(self, "_done", False):
            for sub in run.engine.changelog._subs:
                if type(sub).__name__ == "_ReplicaReplay":
                    sub.on_master = lambda stream: None
            self._done = True
        return batch


class StateUnchanged(Fault):
    def before(self, run, batch):
        e = run.engine
        self._saved = (e.store.state(), e.replica_store.state())
        return batch

    def after(self, run, batch, m):
        e = run.engine
        e.store.load_state(self._saved[0])
        e.replica_store.load_state(self._saved[1])
        e.store.snapshot_commit()
        e.replica_store.snapshot_commit()


class HalfBatch(Fault):
    def before(self, run, batch):
        out = dict(batch)
        p, c = dict(batch["ptxn"]), dict(batch["cross"])
        P, B = p["valid"].shape[0], c["valid"].shape[0]
        p["valid"] = p["valid"].copy()
        p["valid"][P // 2:] = False
        c["valid"] = c["valid"].copy()
        c["valid"][B // 2:] = False
        out["ptxn"], out["cross"] = p, c
        return out

    def after(self, run, batch, m):
        P, T = batch["ptxn"]["valid"].shape
        B = batch["cross"]["valid"].shape[0]
        pc = np.array(m["p_committed"])
        pc[P // 2:, :T] = batch["ptxn"]["valid"][P // 2:] \
            & ~batch["ptxn"]["user_abort"][P // 2:]
        cc = np.array(m["c_committed"])
        cc[B // 2:B] = batch["cross"]["valid"][B // 2:] \
            & ~batch["cross"]["user_abort"][B // 2:]
        m["p_committed"], m["c_committed"] = pc, cc


class AlteredAnswer(Fault):
    def after(self, run, batch, m):
        live = batch["ptxn"]["valid"] & ~batch["ptxn"]["user_abort"]
        if live.any():
            p, t = np.argwhere(live)[0]
            pc = np.array(m["p_committed"])
            pc[p, t] = ~pc[p, t]
            m["p_committed"] = pc


class AlteredOp(Fault):
    def before(self, run, batch):
        p = batch["ptxn"]
        live = p["valid"] & ~p["user_abort"]
        if live.any():
            i, t = np.argwhere(live)[0]
            p["row"] = p["row"].copy()
            p["row"][i, t] = (p["row"][i, t] + 1) % run.world.init_val.shape[1]
        return batch


FAULTS = {"replica_lag": ReplicaLag, "state_unchanged": StateUnchanged,
          "half_batch": HalfBatch, "altered_answer": AlteredAnswer,
          "altered_op": AlteredOp}

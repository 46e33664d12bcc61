"""Reading a run of the cluster path, one STAR node per chip.

The cluster engine's programs run as ``jit_cluster_<what>``.  The full
replica lives on the designated master's chip alone, so the chip that ran
the full-replica replay is the master; the others are partition nodes.

* ``master_plane``: the device plane of the master, or None where no
  program of that name ran (a program that predates the names).
* ``seconds_on``: device seconds of the named programs on one plane.
* ``idle_ns_in_calls``: how long one chip ran nothing inside the traced
  engine calls (the benchmark's ``engine.run_epoch`` annotations).
* ``replay_bytes``: the least a copy must move to replay an epoch's
  committed partitioned stream.  The stream carries writes alone: for
  each distinct record a committed transaction wrote, the copy reads the
  TID it holds (the write rule compares it) and writes the value and the
  new TID.  Each committed transaction counts once.
"""
from __future__ import annotations

import numpy as np

from starbench import devtrace, spanclock
from starbench.reference import writes_primary
from starbench.work import WORD, _distinct

FULL_REPLAY = "jit_cluster_replay_full"


def master_plane(events) -> str | None:
    planes = sorted({e["plane"] for e in events
                     if e["line"] == devtrace.MODULES
                     and devtrace.module_name(e["name"]) == FULL_REPLAY})
    return planes[0] if len(planes) == 1 else None


def seconds_on(events, plane: str, names) -> float:
    return sum(e["dur_ns"] for e in events
               if e["plane"] == plane and e["line"] == devtrace.MODULES
               and devtrace.module_name(e["name"]) in names) / 1e9


def idle_ns_in_calls(events, plane: str) -> float:
    busy = devtrace.busy_intervals(events, plane)
    return sum(b - a for call in spanclock.annotations(events)
               for a, b in spanclock.idle(busy, call["start_ns"],
                                          call["start_ns"] + call["dur_ns"]))


def replay_bytes(batch: dict, p_committed, n_cols: int) -> int:
    p = batch["ptxn"]
    valid = np.asarray(p["valid"], bool)
    live = valid & np.asarray(p_committed, bool)[:, :valid.shape[1]]
    kind = np.asarray(p["kind"])[live]
    written = _distinct(np.asarray(p["row"])[live], writes_primary(kind))
    return written * (WORD + n_cols * WORD + WORD)

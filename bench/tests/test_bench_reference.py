"""The check that decides ``correct``: sound runs pass it, broken ones fail.

The runs here skip the look for a chip and drive the rest of a run at a
size the CPU holds: the served path, the window, the reference replay and
the comparison.
"""
import numpy as np
import pytest

import bench_tiny
from starbench import cells, faults, harness
from starbench.reference import (ADD, INSERT_IDX, IX_EXPECT, IX_HI, IX_ID,
                                 IX_KEY, READ, SCAN_CONSUME, SET, Reference)

SECONDS = 0.5
CELLS = ["tpcc.tiny", "ycsb.tiny"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.tiny_root(tmp_path_factory.mktemp("bench"))


def run(root, cell, fault=None, seed=2**31 + 11, trace=False):
    spec = cells.resolve(cell, root)
    return harness.execute(spec, seed, SECONDS, trace, fault=fault)


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct(root, cell):
    res = run(root, cell)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert all(c["value"] == 0 for c in res["checks"].values())
    m = res["metrics"]
    assert m["txn_s"]["value"] > 0 and res["attempted"] > 0
    assert m["commit_p50_ms"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["replica_lag", "state_unchanged",
                                   "half_batch", "altered_answer",
                                   "altered_op"])
def test_a_broken_timed_path_is_not_correct(root, cell, fault):
    res = run(root, cell, fault=faults.FAULTS[fault]())
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_a_slot_that_runs_other_ops_than_its_request_is_counted(root, cell):
    """The formed batch is held to the clients' record of each request:
    ops moved to other rows where the batch is formed show up as such,
    whether or not they change a row the reference ends with."""
    res = run(root, cell, fault=faults.AlteredOp())
    assert res["checks"]["request_ops"]["value"] > 0


def test_a_traced_run_reports_the_host_layers(root):
    from repro.obs import trace as obs
    before = obs.get_tracer()
    res = run(root, "tpcc.tiny", trace=True)
    assert obs.get_tracer() is before        # the program's tracer is back
    assert res["correct"]
    assert {"service.ingest_ms", "engine.epoch_ms",
            "latency.p99_ms"} <= set(res["metrics"])
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_four_nodes_recover_a_kill_and_stay_correct(root):
    """The mesh path on four host devices: every copy (master blocks, full
    replica, secondaries) matches the reference after the node kill."""
    import json
    import os
    import subprocess
    import sys
    code = (
        "import json, sys; sys.path.insert(0, %r); import bench_tiny; "
        "from starbench import cells, harness; "
        "spec = cells.resolve('tpcc.tiny4', %r); "
        "print(json.dumps(harness.execute(spec, 7, 1.0, False)))"
        % (str(bench_tiny.BENCH / "tests"), str(root)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert {"secondary_rows", "full_replica_index_entries"} <= set(
        res["checks"])
    assert res["metrics"]["recovery_s"]["value"] > 0


def cross(rows, kinds, deltas=None, C=10):
    B, M = np.shape(rows)
    return {"row": np.array(rows, np.int32), "kind": np.array(kinds, np.int32),
            "delta": (np.zeros((B, M, C), np.int32) if deltas is None
                      else np.array(deltas, np.int32)),
            "valid": np.ones(B, bool), "user_abort": np.zeros(B, bool)}


def test_occ_lowest_lane_wins_and_readers_of_its_writes_retry():
    ref = Reference(np.zeros((1, 8, 10), np.int32))
    d = np.zeros((3, 2, 10), np.int32)
    d[:, :, 0] = 1
    # lane 0 adds to row 1; lane 1 adds to row 1 too (lock lost);
    # lane 2 reads row 1 (written by an earlier lane) and sets row 2
    b = cross([[1, 0], [1, 0], [1, 2]], [[ADD, READ], [ADD, READ],
                                         [READ, SET]], d)
    ref.rounds = 1
    assert ref.single_master(b).tolist() == [True, False, False]
    assert ref.val[0, 1, 0] == 1 and ref.val[0, 2, 0] == 0
    ref.rounds = 16
    assert ref.single_master(b).tolist() == [True, True, True]
    assert ref.val[0, 1, 0] == 3 and ref.val[0, 2, 0] == 1


def test_consume_deletes_the_oldest_key_and_guards_its_district():
    ref = Reference(np.zeros((1, 8, 10), np.int32), n_indexes=1)
    M, C = 12, 10
    ins = np.zeros((1, M, C), np.int32)
    kinds = np.full((1, M), READ, np.int32)
    for j, key in enumerate((30, 10, 20)):
        kinds[0, j] = INSERT_IDX
        ins[0, j, IX_KEY], ins[0, j, IX_HI], ins[0, j, IX_ID] = key, j, 0
    ptxn = {"row": np.zeros((1, 1, M), np.int32), "kind": kinds[None],
            "delta": ins[None], "valid": np.ones((1, 1), bool),
            "user_abort": np.zeros((1, 1), bool)}
    ref.partitioned(ptxn)
    assert [k for k, _ in ref.index_entries(0, 0)] == [10, 20, 30]

    def consume(expect):
        d = np.zeros((1, 1, M, C), np.int32)
        k = np.full((1, 1, M), READ, np.int32)
        k[0, 0, 0] = SCAN_CONSUME
        d[0, 0, 0, IX_KEY], d[0, 0, 0, IX_HI] = 0, 100
        d[0, 0, 0, IX_EXPECT] = expect
        k[0, 0, 1] = ADD                    # guarded by the consume
        d[0, 0, 1, 0], d[0, 0, 1, -1] = 5, 1
        r = np.zeros((1, 1, M), np.int32)
        r[0, 0, 0], r[0, 0, 1] = 3, 4
        return {"row": r, "kind": k, "delta": d,
                "valid": np.ones((1, 1), bool),
                "user_abort": np.zeros((1, 1), bool)}

    ref.val[0, 3] = 7
    ref.partitioned(consume(20))            # stale: the oldest is 10
    assert [k for k, _ in ref.index_entries(0, 0)] == [10, 20, 30]
    assert ref.val[0, 4, 0] == 0 and ref.val[0, 3, 0] == 7
    ref.partitioned(consume(10))
    assert [k for k, _ in ref.index_entries(0, 0)] == [20, 30]
    assert ref.val[0, 4, 0] == 5 and ref.val[0, 3, 0] == 0

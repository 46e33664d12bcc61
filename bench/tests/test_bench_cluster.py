"""The four-node YCSB cell (``ycsb-64p-4n``, ``ycsb64.c4.kill``) and the
readers of the cluster's layers.

* A tiny form of the cell, its shapes and traffic read from the real files
  with only the scale cut: four nodes on four host devices through
  ``harness.execute`` with node 2 killed in the window.  Every copy (master
  blocks, full replica, secondaries) matches the reference, and the
  traced run reads the host-side readers.
* The readers on hand-made device events and spans.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import bench_tiny
from starbench import cells, devtrace, mesh
from starbench.reference import READ, SET

CELL = "ycsb64.c4.kill"
MS = 1e6                                    # ns per ms


def tiny_cluster_root(tmp: Path) -> Path:
    """``bench_tiny``'s checkout plus ``ycsb.tiny4``: the cell's
    configuration and traffic at 8 partitions of 256 rows on four nodes."""
    root = bench_tiny.tiny_root(tmp)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    real = cells.resolve(CELL)
    cfg = dict(real["config"], name="ycsb-tiny4")
    cfg["params"] = dict(cfg["params"], n_partitions=8,
                         records_per_partition=256)
    (root / "bench" / "configs" / "ycsb-tiny4.json").write_text(
        json.dumps(cfg))
    traffic = dict(real["traffic"], outstanding=288, slots_per_partition=16,
                   master_lanes=16, trace_epochs=2,
                   kill=dict(real["traffic"]["kill"], window_epoch=3))
    (root / "bench" / "workloads" / "tiny4.kill.json").write_text(
        json.dumps(traffic))
    bench["configs"].append({"name": "ycsb-tiny4", "source": "test",
                             "reduced": [], "why": "test",
                             "file": "bench/configs/ycsb-tiny4.json"})
    bench["workloads"].append({"name": "ycsb.tiny4", "config": "ycsb-tiny4",
                               "traffic": "tiny4.kill", "chips": 4,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "recovery_s":
            m["workloads"].append("ycsb.tiny4")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def four_node_runs(tmp_path_factory):
    """An untraced and a traced run of ``ycsb.tiny4``, in one process with
    four host devices."""
    root = tiny_cluster_root(tmp_path_factory.mktemp("bench"))
    code = (
        "import json, sys; sys.path.insert(0, %r); import bench_tiny; "
        "from starbench import cells, harness; "
        "spec = cells.resolve('ycsb.tiny4', %r); "
        "print(json.dumps([harness.execute(spec, 2**33 + 5, 1.0, t) "
        "for t in (False, True)]))"
        % (str(bench_tiny.BENCH / "tests"), str(root)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_four_ycsb_nodes_recover_a_kill_and_stay_correct(four_node_runs):
    for res in four_node_runs:
        assert res["correct"], res["checks"]
        assert {"master_rows", "full_replica_rows",
                "secondary_rows"} <= set(res["checks"])
        assert all(c["value"] == 0 for c in res["checks"].values())
    plain = four_node_runs[0]["metrics"]
    assert plain["recovery_s"]["value"] > 0
    assert plain["txn_s"]["value"] > 0


def test_a_traced_cpu_run_reads_the_host_side_cluster_readers(
        four_node_runs):
    """On the CPU the profiler records no device plane: the device readers
    give nothing, the host clock and the spans give the rest."""
    m = four_node_runs[1]["metrics"]
    assert {"cluster.epoch_ms", "fence.host_ms", "recovery.ms"} <= set(m)
    assert all(m[k]["value"] > 0 for k in ("cluster.epoch_ms",
                                           "fence.host_ms", "recovery.ms"))
    assert not {"cluster.partitioned.device_ms",
                "cluster.master_replay.device_ms",
                "cluster.master_replay_roofline",
                "cluster.partition_idle_ms"} & set(m)


# -- the readers on hand-made events ---------------------------------------
def module(plane, name, start_ms, end_ms):
    return {"plane": f"/device:TPU:{plane}", "line": devtrace.MODULES,
            "name": f"{name}(7)", "start_ns": start_ms * MS,
            "dur_ns": (end_ms - start_ms) * MS}


def call(start_ms, end_ms):
    return {"plane": "/host:CPU", "line": "python",
            "name": "engine.run_epoch", "start_ns": start_ms * MS,
            "dur_ns": (end_ms - start_ms) * MS}


def events():
    """Two engine calls on two chips; chip 0 is the master."""
    out = [call(0, 100), call(120, 220)]
    for base in (0, 120):
        out += [module(0, "jit_cluster_partitioned", base + 5, base + 10),
                module(1, "jit_cluster_partitioned", base + 5, base + 15),
                module(0, "jit_cluster_replay_full", base + 10, base + 70),
                module(1, "jit_cluster_replay_sec", base + 15, base + 35),
                module(0, "jit_cluster_single_master", base + 70, base + 90),
                module(1, "jit_cluster_scatter_back", base + 92, base + 95)]
    return out


def ycsb_batch(writes):
    """A batch of one partition and two slots, one SET each at ``writes``."""
    kind = [[[SET, READ], [SET, SET]]]
    return {"ptxn": {"valid": [[True, True]], "row": [writes],
                     "kind": kind, "user_abort": [[False, False]]}}


def ctx_of(events, traced):
    return {"device_events": events, "traced": traced, "n_cols": 25,
            "peaks": {"hbm_bytes_per_s": 1e9}, "spans": [], "epochs": []}


def read(name, ctx):
    return cells.load_reader(name).read(ctx)


def test_device_readers_on_two_chips():
    batch = ycsb_batch([[4, 4], [5, 6]])
    traced = [{"batch": batch, "p_committed": [[True, True, False, False]]},
              {"batch": batch, "p_committed": [[False, True, False, False]]}]
    ctx = ctx_of(events(), traced)
    assert mesh.master_plane(ctx["device_events"]) == "/device:TPU:0"
    # (5 + 10) ms a call, over two chips
    assert read("cluster.partitioned.device_ms", ctx) == pytest.approx(7.5)
    assert read("cluster.master_replay.device_ms", ctx) == pytest.approx(60)
    # chip 1 runs 5-35 and 92-95 of each 100-ms call
    assert read("cluster.partition_idle_ms", ctx) == pytest.approx(67)
    # committed: slot 0 writes row 4 once, slot 1 rows 5 and 6; then slot 1
    need = (1 + 2 + 2) * (4 + 25 * 4 + 4)
    assert read("cluster.master_replay_roofline", ctx) == pytest.approx(
        need / 1e9 / 0.120 * 100)


def test_device_readers_give_nothing_without_the_named_replay():
    ev = [e for e in events() if "replay_full" not in e["name"]]
    ctx = ctx_of(ev, [{"batch": ycsb_batch([[1, 1], [2, 2]]),
                       "p_committed": [[True, True]]}])
    for name in ("cluster.master_replay.device_ms",
                 "cluster.master_replay_roofline",
                 "cluster.partition_idle_ms"):
        assert read(name, ctx) is None, name


def span(sid, parent, name, epoch, start_ms, end_ms, **args):
    return {"id": sid, "parent": parent, "name": name, "cat": "x",
            "args": dict(args, epoch=epoch), "t0_s": start_ms / 1e3,
            "dur_s": (end_ms - start_ms) / 1e3}


def kill_spans():
    """Epoch 3 served; epoch 4 doomed, recovered and re-executed."""
    return [span(1, None, "engine.epoch", 3, 0, 100),
            span(2, 1, "engine.fence", 3, 50, 55, which=1),
            span(3, 1, "engine.fence", 3, 90, 98, which=2),
            span(4, None, "recovery.doomed", 4, 100, 200),
            span(5, 4, "engine.epoch", 4, 100, 200, doomed=True),
            span(6, 5, "engine.fence", 4, 150, 160, which=1),
            span(7, 5, "engine.fence", 4, 190, 199, which=2),
            span(8, None, "recovery", 4, 200, 420),
            span(9, 8, "recovery.restore", 4, 210, 300, bytes=8),
            span(10, 8, "recovery.reexecute", 4, 320, 420),
            span(11, 10, "engine.epoch", 4, 320, 420),
            span(12, 11, "engine.fence", 4, 370, 374, which=1),
            span(13, 11, "engine.fence", 4, 410, 416, which=2)]


def test_span_readers_leave_the_doomed_epoch_out_of_the_fences():
    ctx = {"spans": kill_spans(), "traced": [],
           "epochs": [{"engine_epoch": 3}, {"engine_epoch": 4}]}
    assert read("fence.host_ms", ctx) == pytest.approx((13 + 10) / 2)
    assert read("recovery.ms", ctx) == pytest.approx(100 + 220)


def test_a_kill_before_the_window_is_not_read():
    ctx = {"spans": kill_spans(), "traced": [],
           "epochs": [{"engine_epoch": 5}]}
    assert read("recovery.ms", ctx) is None
    unmarked = [dict(s, args={k: v for k, v in s["args"].items()
                              if k != "doomed"}) for s in kill_spans()]
    ctx = {"spans": unmarked, "traced": [], "epochs": [{"engine_epoch": 4}]}
    assert read("recovery.ms", ctx) is None


def test_the_median_epoch_leaves_the_kill_out():
    ctx = {"fences": (1.0, None, None),
           "epochs": [{"t_fence": t} for t in (1.5, 2.0, 4.0, 4.5, 5.0)]}
    assert read("cluster.epoch_ms", ctx) == pytest.approx(500)

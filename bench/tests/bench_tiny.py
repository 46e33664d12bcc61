"""A checkout of the benchmark at a size the CPU runs in seconds.

``tiny_root(tmp)`` copies ``bench/`` and writes a ``BENCHMARK.json`` whose
cells keep the real cells' traffic shapes (closed and open loop, TPC-C full
mix with its indexes, YCSB) at a few partitions and rows.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TPCC = {"name": "tpcc-tiny", "kind": "tpcc", "occ_rounds": 16,
        "params": {"n_partitions": 4, "mix": "full", "n_items": 400,
                   "cust_per_district": 40, "order_ring": 64}}
YCSB = {"name": "ycsb-tiny", "kind": "ycsb", "occ_rounds": 16,
        "params": {"n_partitions": 4, "records_per_partition": 512,
                   "row_words": 25, "ops_per_txn": 10, "write_ops": 1,
                   "cross_ratio": 0.25}}
ADMISSION = {"part_queue_cap": 256, "master_queue_cap": 1024,
             "policy": "shed"}
CLOSED = {"loop": "closed", "outstanding": 96, "warmup_epochs": 1, "setup_epochs": 1, "trace_epochs": 2,
          "slots_per_partition": 16, "master_lanes": 16,
          "admission": ADMISSION}
KILL = dict(CLOSED, outstanding=128, setup_epochs=2,
            kill={"node": 2, "window_epoch": 3})
OPEN = {"loop": "open", "rate_txn_s": 400.0,
        "warmup_epochs": 1, "setup_epochs": 2, "trace_epochs": 2,
        "slots_per_partition": 16, "master_lanes": 16,
        "admission": ADMISSION}


def tiny_root(tmp: Path) -> Path:
    """A checkout holding the tiny cells ``tpcc.tiny``, ``ycsb.tiny`` and
    ``tpcc.tiny4`` (four nodes, one killed in the window)."""
    root = Path(tmp) / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    for cfg in (TPCC, YCSB):
        (root / "bench" / "configs" / f"{cfg['name']}.json").write_text(
            json.dumps(cfg))
    (root / "bench" / "workloads" / "tiny.closed.json").write_text(
        json.dumps(CLOSED))
    (root / "bench" / "workloads" / "tiny.open.json").write_text(
        json.dumps(OPEN))
    (root / "bench" / "workloads" / "tiny.kill.json").write_text(
        json.dumps(KILL))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [
        {"name": c["name"], "source": "test", "reduced": [], "why": "test",
         "file": f"bench/configs/{c['name']}.json"} for c in (TPCC, YCSB)]
    bench["workloads"] = [
        {"name": "tpcc.tiny", "config": "tpcc-tiny", "traffic": "tiny.closed",
         "chips": 1, "why": "test"},
        {"name": "ycsb.tiny", "config": "ycsb-tiny", "traffic": "tiny.open",
         "chips": 1, "why": "test"},
        {"name": "tpcc.tiny4", "config": "tpcc-tiny", "traffic": "tiny.kill",
         "chips": 4, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    bench["end_to_end"].append(
        {"name": "recovery_s", "unit": "s", "better": "lower", "bound": 0.25,
         "source": "host_clock", "workloads": ["tpcc.tiny4"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root

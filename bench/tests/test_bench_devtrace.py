"""The reduction from a device trace to layer times, on a recorded excerpt."""
import json
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401
from starbench import devtrace

EXCERPT = Path(__file__).parent / "data" / "ycsb16.open80.trace.json"


@pytest.fixture(scope="module")
def events():
    return json.loads(EXCERPT.read_text())["events"]


def modules(events):
    return sorted((e for e in events if e["line"] == devtrace.MODULES),
                  key=lambda e: e["start_ns"])


def test_program_time_sums_its_executions(events):
    want = sum(e["dur_ns"] for e in events if e["line"] == devtrace.MODULES
               and e["name"].startswith("jit_run_partitioned(")) / 1e9
    assert want > 0
    assert devtrace.module_seconds(events, {"jit_run_partitioned"}) == want
    both = devtrace.module_seconds(
        events, {"jit_run_partitioned", "jit_replay_partitioned"})
    assert both > want


def test_busy_counts_nested_operations_once(events):
    mods = modules(events)
    for a, b in zip(mods, mods[1:]):          # programs run one at a time
        assert a["start_ns"] + a["dur_ns"] <= b["start_ns"]
    want = sum(e["dur_ns"] for e in mods) / 1e9
    assert devtrace.busy_s(events) == pytest.approx(want, rel=1e-12)


def test_operations_are_named_by_program(events):
    top = devtrace.top_ops(events, n=3)
    assert len(top) == 3
    assert all(name.startswith("jit_run_partitioned/") for name, _ in top)
    assert all(" " not in name and "%" not in name for name, _ in top)
    assert [t for _, t in top] == sorted((t for _, t in top), reverse=True)


def test_idle_gaps_are_named_by_the_covering_annotation(events):
    mods = modules(events)
    gaps = [(b["start_ns"] - a["start_ns"] - a["dur_ns"], a, b)
            for a, b in zip(mods, mods[1:])]
    gap, a, b = max(gaps, key=lambda g: g[0])
    end_a = a["start_ns"] + a["dur_ns"]
    host = [{"plane": "/host:CPU", "line": "python3",
             "name": "engine.run_epoch", "start_ns": 0,
             "dur_ns": b["start_ns"] + 10**9},
            {"plane": "/host:CPU", "line": "python3",
             "name": "service.ingest", "start_ns": end_a,
             "dur_ns": b["start_ns"] - end_a}]
    name, seconds = devtrace.idle_gaps(events + host, n=1)[0]
    assert seconds == pytest.approx(gap / 1e9)
    assert name == "service.ingest"


def test_no_device_plane_reads_nothing():
    host_only = [{"plane": "/host:CPU", "line": "python3", "name": "x",
                  "start_ns": 0, "dur_ns": 5}]
    assert devtrace.busy_s(host_only) == 0.0
    assert devtrace.module_seconds(host_only, {"jit_run_partitioned"}) == 0.0
    assert devtrace.top_ops(host_only) == []
    assert devtrace.idle_gaps(host_only) == []

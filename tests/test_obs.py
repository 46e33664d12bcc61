"""Unified observability layer: tracer, registry, and their wiring.

* trace-export schema: the Chrome/Perfetto ``trace_event`` JSON a real
  engine run exports is loadable — every event has ph X/i, microsecond
  ts, non-negative dur, and complete spans NEST per (pid, tid): any two
  either disjoint or contained, with the whole-epoch span containing the
  phase spans;
* registry bit-match: per-epoch snapshots taken by the service layer
  read the SAME live stats dataclasses — the final snapshot equals every
  legacy ``EngineStats``/``ServiceStats`` field exactly, on a full-mix
  TPC-C run;
* overhead budget: with tracing DISABLED (the default), the per-call
  cost of the instrumentation points times a generous spans-per-epoch
  count stays under 2% of a measured epoch;
* recovery span tree (subprocess, forced host devices): a mid-run node
  kill exports classify → revert → restore → re-master → re-execute
  spans, all nested inside one ``recovery`` span;
* epoch span tree: a served epoch records every host segment of
  ``run_epoch`` (upload, partitioned dispatch and wait, byte accounting,
  fences, flatten, single-master dispatch and wait, readback) and the
  service's retirement of the batch, each with its epoch and its parent,
  each inside its parent — on ``StarEngine`` and on ``ClusterRuntime``.
"""
import json
import os
import subprocess
import sys
import textwrap
import time
from dataclasses import fields
from pathlib import Path

import numpy as np
import pytest

from repro.core.engine import StarEngine
from repro.db import tpcc
from repro.obs import MetricsRegistry, Tracer, set_tracer
from repro.obs.trace import get_tracer

REPO = Path(__file__).resolve().parents[1]
SRC = str(REPO / "src")
# the benchmark's reader of the span tree walks it for the tree checks
sys.path.insert(0, str(REPO / "bench"))
from starbench import spanclock  # noqa: E402


def _run(code: str, devices: int = 4) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=480)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def _small_engine():
    cfg = tpcc.TPCCConfig(n_partitions=2, n_items=400, cust_per_district=40,
                          order_ring=64, mix="full", delivery_gen_lag=256)
    state = tpcc.TPCCState(cfg)
    init = tpcc.init_values(cfg, np.random.default_rng(5), state=state)
    eng = StarEngine(cfg.n_partitions, cfg.rows_per_partition, init_val=init,
                     indexes=tpcc.index_specs(cfg))
    return cfg, state, eng


# ---------------------------------------------------------------------------
# trace export schema + nesting
# ---------------------------------------------------------------------------
EPS = 0.05        # us; absorbs the 3-decimal export rounding at boundaries


def _contained(a, b):
    """Complete event a inside complete event b (closed interval)."""
    return (a["ts"] >= b["ts"] - EPS
            and a["ts"] + a["dur"] <= b["ts"] + b["dur"] + EPS)


def test_trace_export_schema_and_nesting(tmp_path):
    tracer = Tracer(enabled=True)
    old = set_tracer(tracer)
    try:
        cfg, state, eng = _small_engine()
        for ep in range(3):
            batch = tpcc.make_batch(cfg, state, 96, seed=ep)
            m = eng.run_epoch(batch)
            tpcc.apply_consume_feedback(state, batch, m)
    finally:
        set_tracer(old)

    path = tmp_path / "trace.json"
    n = tracer.export_chrome(str(path))
    assert n > 0 and tracer.dropped == 0
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and len(evs) == n
    names = {e["name"] for e in evs}
    # the stack's load-bearing spans are all present
    for want in ("engine.epoch", "engine.partitioned", "engine.fence",
                 "engine.single_master", "changelog.slab_ship",
                 "changelog.commit"):
        assert want in names, (want, sorted(names))
    for e in evs:
        assert e["ph"] in ("X", "i"), e
        assert isinstance(e["ts"], (int, float))
        assert {"pid", "tid", "name", "cat"} <= e.keys()
        if e["ph"] == "X":
            assert e["dur"] >= 0, e           # no negative durations
    # sorted by ts (stable Perfetto ingestion)
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)
    # the engine span hierarchy nests per (pid, tid): pairwise disjoint
    # or contained (other categories may straddle measured-window edges)
    by_tid = {}
    for e in evs:
        if e["ph"] == "X" and e["name"].startswith("engine."):
            by_tid.setdefault((e["pid"], e["tid"]), []).append(e)
    assert by_tid
    for group in by_tid.values():
        for i, a in enumerate(group):
            for b in group[i + 1:]:
                disjoint = (a["ts"] + a["dur"] <= b["ts"] + EPS
                            or b["ts"] + b["dur"] <= a["ts"] + EPS)
                assert disjoint or _contained(a, b) or _contained(b, a), \
                    (a, b)
    # every phase span sits inside a whole-epoch span
    epochs = [e for e in evs if e["name"] == "engine.epoch"]
    for e in evs:
        if e["name"] in ("engine.partitioned", "engine.single_master"):
            assert any(_contained(e, ep) for ep in epochs), e


def test_trace_instants_and_kernel_counts():
    from repro.obs.trace import kernel_launch, kernel_launch_counts
    before = kernel_launch_counts().get("test.k", 0)
    kernel_launch("test.k", lanes=8)
    kernel_launch("test.k", lanes=8)
    assert kernel_launch_counts()["test.k"] == before + 2


def test_events_record_their_parent_and_absolute_start():
    tr = Tracer(enabled=True)
    t_before = time.perf_counter()
    with tr.span("outer", epoch=1):
        with tr.span("inner"):
            tr.instant("mark")
        t0 = time.perf_counter()
        tr.complete("done", "", t0, t0 + 0.5)
    tr.complete("alone", "", t0, t0)
    ev = {e["name"]: e for e in tr.events()}
    assert ev["outer"]["parent"] is None and ev["alone"]["parent"] is None
    assert ev["inner"]["parent"] == ev["outer"]["id"]
    assert ev["mark"]["parent"] == ev["inner"]["id"]
    assert ev["done"]["parent"] == ev["outer"]["id"]
    assert len({e["id"] for e in ev.values()}) == len(ev)
    # t0_s is the perf_counter clock itself; ts_s keeps its origin
    assert ev["done"]["t0_s"] == t0 and ev["done"]["dur_s"] == 0.5
    assert t_before <= ev["outer"]["t0_s"] <= ev["inner"]["t0_s"]
    origin = ev["outer"]["t0_s"] - ev["outer"]["ts_s"]
    for e in ev.values():
        assert e["ts_s"] == pytest.approx(e["t0_s"] - origin, abs=1e-9)


def test_ring_buffer_bounded_drop_oldest():
    tr = Tracer(capacity=16, enabled=True)
    for i in range(64):
        tr.instant(f"e{i}")
    assert len(tr.events()) == 16
    assert tr.dropped == 48
    assert tr.events()[0]["name"] == "e48"     # oldest dropped
    assert tr.to_chrome()["otherData"]["dropped_events"] == 48


# ---------------------------------------------------------------------------
# registry: bit-match with the legacy stats dataclasses
# ---------------------------------------------------------------------------
def test_registry_snapshot_bit_matches_legacy_stats():
    from repro.service import (AdmissionConfig, OpenLoopClient, TPCCSource,
                               TxnService)
    cfg = tpcc.TPCCConfig(n_partitions=2, n_items=400, cust_per_district=40,
                          order_ring=64, mix="full", delivery_gen_lag=256)
    state = tpcc.TPCCState(cfg)
    init = tpcc.init_values(cfg, np.random.default_rng(7), state=state)
    eng = StarEngine(2, cfg.rows_per_partition, init_val=init,
                     indexes=tpcc.index_specs(cfg))
    client = OpenLoopClient(TPCCSource(cfg, state=state, seed=1),
                            rate_txn_s=400.0, seed=7)
    svc = TxnService(eng, [client], AdmissionConfig(64, 64),
                     slots_per_partition=16, master_lanes=16,
                     feedback=lambda b, m: tpcc.apply_consume_feedback(
                         state, b, m))
    out = svc.run(duration_s=0.4)
    assert out["committed"] > 0
    snaps = svc.metrics.snapshots
    assert len(snaps) == svc.stats.epochs          # one point per epoch
    last = snaps[-1]
    # live-object registration: the final snapshot equals every numeric
    # legacy field EXACTLY (same objects read at snapshot time)
    for f in fields(eng.stats):
        v = getattr(eng.stats, f.name)
        if isinstance(v, (int, float)):
            assert last[f"engine.{f.name}"] == v, f.name
    for f in fields(svc.stats):
        v = getattr(svc.stats, f.name)
        if isinstance(v, (int, float)):
            assert last[f"service.{f.name}"] == v, f.name
    for f in fields(svc.admission.stats):
        v = getattr(svc.admission.stats, f.name)
        if isinstance(v, (int, float)):
            assert last[f"admission.{f.name}"] == v, f.name
    # kernel-launch counters surface under kernels.*
    assert any(k.startswith("kernels.occ.") for k in last), sorted(last)[:20]
    # the time series is per-epoch monotonic where the stats are counters
    ep = [s["engine.epochs"] for s in snaps]
    assert ep == sorted(ep)


def test_registry_exporters(tmp_path):
    reg = MetricsRegistry()
    reg.counter_add("a.count", 3)
    reg.gauge_set("a.gauge", 1.5)
    reg.snapshot(0)
    reg.counter_add("a.count", 1)
    reg.snapshot(1)
    p = tmp_path / "m.jsonl"
    n = reg.export_jsonl(str(p))
    lines = [json.loads(ln) for ln in p.read_text().splitlines()]
    assert n == len(lines) == 2
    assert lines[0]["a.count"] == 3 and lines[1]["a.count"] == 4
    assert lines[0]["a.gauge"] == 1.5
    assert lines[1]["epoch"] == 1


# ---------------------------------------------------------------------------
# disabled-path overhead budget
# ---------------------------------------------------------------------------
def test_disabled_tracer_overhead_under_budget():
    """The default (disabled) tracer must cost <= 2% of epoch time for a
    generous per-epoch span count.  Measured as per-call cost of the real
    disabled entry points times a 4x-headroom span budget."""
    from repro.obs import trace as obs
    assert not get_tracer().enabled          # the default is off

    cfg, state, eng = _small_engine()
    eng.run_epoch(tpcc.make_batch(cfg, state, 96, seed=99))   # warm jit
    t0 = time.perf_counter()
    eng.run_epoch(tpcc.make_batch(cfg, state, 96, seed=100))
    epoch_s = time.perf_counter() - t0

    reps = 20000
    t0 = time.perf_counter()
    for _ in range(reps):
        with obs.span("x", cat="y", epoch=1):
            pass
        obs.complete("x", "y", 0.0, 1.0, epoch=1)
        obs.instant("x", "y")
    per_call_s = (time.perf_counter() - t0) / (3 * reps)

    # spans per epoch, with ~4x headroom over what the engine actually
    # emits (epoch + 2 phases + 2 fences + per-slab ship/commit + rounds
    # + service/read/analytics spans)
    spans_per_epoch = 256
    overhead = per_call_s * spans_per_epoch
    assert overhead <= 0.02 * epoch_s, \
        (f"disabled tracing {overhead * 1e6:.1f}us/epoch vs "
         f"epoch {epoch_s * 1e3:.2f}ms")


# ---------------------------------------------------------------------------
# recovery span tree across a mid-run kill (subprocess cluster)
# ---------------------------------------------------------------------------
def test_recovery_span_tree_exported():
    out = _run("""
        import json
        import numpy as np
        import jax
        from repro.cluster import ClusterRuntime
        from repro.core.fault import FaultInjector
        from repro.db import ycsb
        from repro.obs import Tracer, set_tracer

        tracer = Tracer(enabled=True)
        set_tracer(tracer)
        n = jax.device_count()
        mesh = jax.make_mesh((n,), ("part",))
        inj = FaultInjector(); inj.schedule_kill(node=1, epoch=1)
        P = 2 * n
        cfg = ycsb.YCSBConfig(n_partitions=P, records_per_partition=64)
        rt = ClusterRuntime(mesh, P, 64, injector=inj)
        for ep in range(3):
            rt.run_epoch(ycsb.make_batch(cfg, 64, seed=ep))
        assert rt.replica_consistent()
        doc = tracer.to_chrome()
        print("TRACE " + json.dumps(doc["traceEvents"]))
    """, devices=2)
    line = [ln for ln in out.splitlines() if ln.startswith("TRACE ")][-1]
    evs = json.loads(line[len("TRACE "):])
    spans = {e["name"]: e for e in evs if e["ph"] == "X"}
    # the full §4.5 recovery tree made it into the export
    for want in ("recovery", "recovery.classify", "recovery.revert",
                 "recovery.restore", "recovery.remaster",
                 "recovery.reexecute"):
        assert want in spans, (want, sorted(spans))
    root = spans["recovery"]
    for child in ("recovery.classify", "recovery.revert",
                  "recovery.restore", "recovery.remaster",
                  "recovery.reexecute"):
        c = spans[child]
        assert c["tid"] == root["tid"]
        assert _contained(c, root), (child, c, root)
    assert root["args"]["case"] == "PHASE_SWITCHING"


# ---------------------------------------------------------------------------
# the epoch's span tree: every host segment, with epoch and parent
# ---------------------------------------------------------------------------
EPOCH_SPANS = {"engine.epoch", "engine.upload", "engine.partitioned",
               "service.ingest_overlap", "engine.partitioned.wait",
               "engine.accounting", "engine.accounting.wait",
               "engine.fence", "engine.sm_flatten",
               "engine.single_master", "engine.single_master.wait",
               "engine.readback", "changelog.slab_ship",
               "changelog.master_ship", "changelog.commit",
               "service.complete"}


def _epoch_tree(events, want):
    """The spans of the first served epoch that records every name of
    ``want``: its ``engine.epoch`` root, the spans below it and
    the service's ``service.complete`` of the same epoch."""
    for root in (e for e in events if e["name"] == "engine.epoch"):
        tree = [root] + spanclock.descendants(events, root)
        tree += [e for e in events if e["name"] == "service.complete"
                 and e["args"]["epoch"] == root["args"]["epoch"]]
        if want <= {e["name"] for e in tree}:
            return root, tree
    raise AssertionError(sorted({e["name"] for e in events}))


def _check_epoch_tree(events, want=EPOCH_SPANS):
    root, tree = _epoch_tree(events, want)
    by_id = {e["id"]: e for e in events}
    ep = root["args"]["epoch"]
    assert root["parent"] is None
    for e in tree:
        if e["name"].startswith(("engine.", "service.", "changelog.")):
            assert e["args"]["epoch"] == ep, e
        if e is root or e["name"] == "service.complete":
            continue
        parent = by_id[e["parent"]]
        if e["dur_s"] is None:                  # an instant inside its span
            assert parent["t0_s"] <= e["t0_s"] \
                <= parent["t0_s"] + parent["dur_s"], (e, parent)
            continue
        assert parent["t0_s"] <= e["t0_s"], (e, parent)
        assert e["t0_s"] + e["dur_s"] \
            <= parent["t0_s"] + parent["dur_s"] + 1e-9, (e, parent)
    # service.complete follows the engine call it retires
    done = [e for e in tree if e["name"] == "service.complete"]
    assert done[0]["t0_s"] >= root["t0_s"] + root["dur_s"]
    assert {e["args"].get("stream") for e in tree
            if e["name"] == "engine.accounting"} == {"part", "sm"}
    readback = next(e for e in tree if e["name"] == "engine.readback")
    assert readback["args"]["arrays"] > 0 and readback["args"]["bytes"] > 0
    upload = next(e for e in tree if e["name"] == "engine.upload")
    assert upload["args"]["bytes"] > 0
    # each device wait sits in its phase, the accounting's wait for its
    # reductions in the accounting, and every wait is of category "wait"
    for wait, phase in (("engine.partitioned.wait", "engine.partitioned"),
                        ("engine.single_master.wait",
                         "engine.single_master"),
                        ("engine.accounting.wait", "engine.accounting")):
        for w in (e for e in tree if e["name"] == wait):
            assert by_id[w["parent"]]["name"] == phase
            assert w["cat"] == "wait"
    # fence 1 holds the cluster's psum barrier as it holds StarEngine's
    for e in tree:
        if e["name"] == "fence.psum":
            assert by_id[e["parent"]]["args"].get("which") == 1
    return root, tree


def test_served_epoch_records_every_host_segment():
    from repro.db import ycsb
    from repro.service import (AdmissionConfig, ClosedLoopClient,
                               TxnService, YCSBSource)
    cfg = ycsb.YCSBConfig(n_partitions=4, records_per_partition=256,
                          cross_ratio=0.25)
    eng = StarEngine(4, 256)
    svc = TxnService(eng, [ClosedLoopClient(YCSBSource(cfg, seed=1), 96)],
                     AdmissionConfig(256, 256), slots_per_partition=16,
                     master_lanes=16)
    tracer = Tracer(enabled=True)
    old = set_tracer(tracer)
    try:
        svc.run(duration_s=30.0, max_epochs=3)
    finally:
        set_tracer(old)
    events = tracer.events()
    root, tree = _check_epoch_tree(events)
    fences = sorted(e["args"]["which"] for e in tree
                    if e["name"] == "engine.fence")
    assert fences == [1, 2]
    # the service's ingest spans nest under the overlapped ingest
    ingest = next(e for e in tree if e["name"] == "service.ingest_overlap")
    assert {e["name"] for e in tree if e["parent"] == ingest["id"]} \
        >= {"service.admission", "service.batch_form"}
    # the single-master time is no longer split into synthetic rounds
    assert not any(e["name"] == "engine.sm_round" for e in events)


def test_cluster_epoch_records_the_same_host_segments():
    out = _run("""
        import json
        import jax
        from repro.cluster import ClusterRuntime, ClusterTxnService
        from repro.db import ycsb
        from repro.obs import Tracer, set_tracer
        from repro.service import (AdmissionConfig, ClosedLoopClient,
                                   YCSBSource)

        cfg = ycsb.YCSBConfig(n_partitions=8, records_per_partition=128,
                              cross_ratio=0.25)
        mesh = jax.make_mesh((4,), ("part",), devices=jax.devices()[:4])
        rt = ClusterRuntime(mesh, 8, 128)
        svc = ClusterTxnService(
            rt, [ClosedLoopClient(YCSBSource(cfg, seed=1), 96)],
            AdmissionConfig(256, 256), slots_per_partition=16,
            master_lanes=16)
        tracer = Tracer(enabled=True)
        set_tracer(tracer)
        svc.run(duration_s=60.0, max_epochs=3)
        print("EVENTS " + json.dumps(tracer.events()))
    """, devices=4)
    line = [ln for ln in out.splitlines() if ln.startswith("EVENTS ")][-1]
    events = json.loads(line[len("EVENTS "):])
    root, tree = _check_epoch_tree(events)
    names = {e["name"] for e in tree}
    assert {"cluster.slab_execute", "fence.tail_ship", "fence.psum",
            "fence.replay_drain"} <= names
    drain = next(e for e in tree if e["name"] == "fence.replay_drain")
    assert drain["cat"] == "wait"
    assert not any(e["name"] == "engine.sm_round" for e in events)

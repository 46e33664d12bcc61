"""The program's spans on the device trace's clock, and the readers of the
host segments of the epoch.

* On hand-made intervals: idle stretches split among the innermost spans
  covering them, and which of those are the engine's own host code.
* A real profiler session on the CPU around a tiny served run: every
  placed ``engine.epoch`` span sits inside the ``engine.run_epoch``
  annotation of its call.
* A recorded excerpt of one chip run (two traced epochs of
  ``ycsb16.closed``): the three readers against sums taken by hand.
"""
import json
from pathlib import Path

import pytest

import bench_tiny
from starbench import cells, devtrace, harness, spanclock

EXCERPT = Path(__file__).parent / "data" / "ycsb16.closed.spans.json"
MS = 1e6                                    # ns per ms


def span(sid, parent, name, start_ms, end_ms, cat="host"):
    return {"id": sid, "parent": parent, "name": name, "cat": cat,
            "args": {"epoch": 7}, "t0_s": start_ms / 1e3,
            "dur_s": (end_ms - start_ms) / 1e3}


def device(start_ms, end_ms):
    return {"plane": "/device:TPU:0", "line": devtrace.MODULES,
            "name": "jit_x(1)", "start_ns": start_ms * MS,
            "dur_ns": (end_ms - start_ms) * MS}


def test_idle_goes_to_the_innermost_span_covering_it():
    spans = [span(0, None, "engine.epoch", 0, 40),
             span(1, 0, "engine.partitioned", 5, 25),
             span(2, 1, "engine.partitioned.wait", 12, 18, cat="wait"),
             span(3, 0, "engine.readback", 32, 36)]
    busy = devtrace.busy_intervals([device(0, 10), device(20, 30)],
                                   "/device:TPU:0")
    gaps = spanclock.idle(busy, 0, 45 * MS)
    assert gaps == [(10 * MS, 20 * MS), (30 * MS, 45 * MS)]
    stretches = spanclock.innermost(spanclock.placed(spans, 0.0))
    got = [(s and s["name"], round(n / MS, 6))
           for s, n in spanclock.attribute(gaps, stretches)]
    assert got == [("engine.partitioned", 2), ("engine.partitioned.wait", 6),
                   ("engine.partitioned", 2), ("engine.epoch", 2),
                   ("engine.readback", 4), ("engine.epoch", 4), (None, 5)]
    by_id = {s["id"]: s for s in spans}
    host = {s["name"]: spanclock.engine_host_segment(s, by_id) for s in spans}
    assert host == {"engine.epoch": False, "engine.partitioned": True,
                    "engine.partitioned.wait": False,
                    "engine.readback": True}


def test_spans_of_a_program_without_parents_are_not_placed():
    spans = [{"name": "engine.epoch", "cat": "epoch", "ts_s": 0.0,
              "dur_s": 1.0, "tid": 0, "args": {"epoch": 1}}]
    ctx = {"spans": spans, "device_events": [device(0, 10)],
           "traced": [{"t_call": 0.0, "engine_epoch": 1}],
           "epochs": [{"engine_epoch": 1}]}
    for name in ("engine.host_ms", "service.complete_ms", "engine.idle_ms"):
        assert cells.load_reader(name).read(ctx) is None, name


def test_a_profiled_cpu_run_places_each_epoch_inside_its_annotation(
        tmp_path, monkeypatch):
    """A real ``jax.profiler`` session on the CPU: the offset fitted from
    the calls' host clocks puts every ``engine.epoch`` span inside the
    ``engine.run_epoch`` annotation around its call, to 50 us."""
    root = bench_tiny.tiny_root(tmp_path)
    seen = {}
    per_layer = harness.per_layer

    def keep(spec, ctx):
        seen["ctx"] = ctx
        return per_layer(spec, ctx)
    monkeypatch.setattr(harness, "per_layer", keep)
    res = harness.execute(cells.resolve("ycsb.tiny", root), 2**31 + 5, 0.5,
                          True)
    assert res["correct"], res["checks"]
    ctx = seen["ctx"]
    ann = spanclock.annotations(ctx["device_events"])
    assert len(ann) == len(ctx["traced"]) >= 1
    off = spanclock.offset_ns(ctx["device_events"], ctx["traced"])
    placed = {s["args"]["epoch"]: s
              for s in spanclock.placed(ctx["spans"], off)
              if s["name"] == "engine.epoch"}
    for a, e in zip(ann, ctx["traced"]):
        s = placed[e["engine_epoch"]]
        assert a["start_ns"] - 50e3 <= s["start_ns"] <= s["end_ns"] \
            <= a["start_ns"] + a["dur_ns"] + 50e3, (a, s)
    # the host readers read; the CPU is no device, so no idle is placed
    assert {"engine.host_ms", "service.complete_ms"} <= set(res["metrics"])
    assert "engine.idle_ms" not in res["metrics"]


@pytest.fixture(scope="module")
def excerpt():
    return json.loads(EXCERPT.read_text())


def test_the_excerpt_places_each_epoch_inside_its_annotation(excerpt):
    ann = spanclock.annotations(excerpt["device_events"])
    off = spanclock.offset_ns(excerpt["device_events"], excerpt["traced"])
    roots = {s["args"]["epoch"]: s
             for s in spanclock.placed(excerpt["spans"], off)
             if s["name"] == "engine.epoch"}
    for a, e in zip(ann, excerpt["traced"]):
        s = roots[e["engine_epoch"]]
        assert a["start_ns"] - 50e3 <= s["start_ns"] <= s["end_ns"] \
            <= a["start_ns"] + a["dur_ns"] + 50e3


def test_engine_host_ms_is_the_epoch_less_its_waits_and_ingest(excerpt):
    got = cells.load_reader("engine.host_ms").read(excerpt)
    by_id = {s["id"]: s for s in excerpt["spans"]}
    want = {e["engine_epoch"] for e in excerpt["epochs"]}
    total = {k: 0.0 for k in want}
    epoch = {}
    for s in excerpt["spans"]:
        top = s
        while top["parent"] in by_id:
            top = by_id[top["parent"]]
        k = top["args"].get("epoch")
        if top["name"] != "engine.epoch" or k not in want:
            continue
        if s is top:
            total[k] += s["dur_s"]
            epoch[k] = s["dur_s"]
            continue
        # less each wait (category "wait") and the overlapped ingest that
        # no other such span holds
        chain, up = [s], s
        while up["parent"] in by_id:
            up = by_id[up["parent"]]
            chain.append(up)
        off = [c["cat"] == "wait" or c["name"] == "service.ingest_overlap"
               for c in chain]
        if off[0] and not any(off[1:]):
            total[k] -= s["dur_s"]
    assert got == pytest.approx(sum(total.values()) / len(want) * 1e3)
    assert 0 < got < sum(epoch.values()) / len(want) * 1e3
    # the accounting's wait for its reductions is a wait, not host time
    acc = [s for s in excerpt["spans"] if s["name"] == "engine.accounting"
           and s["args"]["epoch"] in want]
    held = [s for s in excerpt["spans"] if s["name"] ==
            "engine.accounting.wait" and s["args"]["epoch"] in want]
    assert held and all(s["cat"] == "wait" for s in held)
    assert sum(s["dur_s"] for s in held) <= sum(s["dur_s"] for s in acc)


def test_service_complete_ms_reads_the_retirement_span(excerpt):
    got = cells.load_reader("service.complete_ms").read(excerpt)
    want = {e["engine_epoch"] for e in excerpt["epochs"]}
    durs = [s["dur_s"] for s in excerpt["spans"]
            if s["name"] == "service.complete"
            and s["args"]["epoch"] in want]
    assert len(durs) == len(want)
    assert got == pytest.approx(sum(durs) / len(want) * 1e3)


def test_engine_idle_ms_is_the_idle_under_host_segments(excerpt):
    got = cells.load_reader("engine.idle_ms").read(excerpt)
    pieces = spanclock.traced_idle(excerpt)
    total = sum(n for _, n in pieces)
    named = sum(n for s, n in pieces
                if s is not None and s["name"] != "engine.epoch")
    waits = sum(n for s, n in pieces
                if s is not None and spanclock.off_host(s))
    # idle inside the traced calls, less the unnamed and the waits
    assert 0 < got * 1e6 * len(excerpt["traced"]) <= named - waits + 1
    assert named >= 0.8 * total

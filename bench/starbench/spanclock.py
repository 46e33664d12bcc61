"""Place the program's spans on the device trace's clock.

The program's tracer (``repro.obs.trace``) stamps each span with
``time.perf_counter()`` (its ``t0_s``); the profiler stamps device and host
events in nanoseconds from the start of its trace.  The two clocks differ
by an offset that holds over a run.  Each traced epoch gives one pair of
stamps of the same instant: the host clock just before the engine call
(``t_call`` of the run's epoch record) and the start of the benchmark's
``engine.run_epoch`` annotation around that call.  The median of their
differences is the offset.

On that clock every stretch in which the first device ran nothing is
attributed to the innermost program span covering it: the host work the
chip waited for.  Spans without ``t0_s`` and ``parent`` (from a program
that predates them) are not placed, and the readers built on this module
return ``None`` for them.

Spans are classed by what they record, not by name: a span of category
``wait`` holds the host blocked on the device or the runtime (a
``block_until_ready``, a copy queued behind device work), and the
service's ``service.ingest_overlap`` is the next batch formed while the
device runs.  Neither is the engine's own host code.
"""
from __future__ import annotations

import statistics

from starbench import devtrace

ANNOTATION = "engine.run_epoch"
ROOT = "engine.epoch"
INGEST = "service.ingest_overlap"


def off_host(span) -> bool:
    """Whether a span is time the engine's host code does not spend: a
    wait on the device or the runtime, or the overlapped ingest."""
    return span.get("cat") == "wait" or span["name"] == INGEST


def annotations(events, name: str = ANNOTATION) -> list[dict]:
    """The benchmark's host annotations called ``name``, in time order."""
    return sorted((e for e in events if e["name"] == name),
                  key=lambda e: e["start_ns"])


def offset_ns(events, traced) -> float | None:
    """Profiler time minus ``perf_counter`` time, in ns: the median over
    the traced epochs, each paired with its ``engine.run_epoch``
    annotation.  None when the two do not pair one to one."""
    ann = annotations(events)
    if not ann or len(ann) != len(traced):
        return None
    return statistics.median(a["start_ns"] - e["t_call"] * 1e9
                             for a, e in zip(ann, traced))


def placed(spans, offset: float) -> list[dict]:
    """The complete spans (not instants), each with ``start_ns`` and
    ``end_ns`` on the device trace's clock."""
    return [dict(s, start_ns=s["t0_s"] * 1e9 + offset,
                 end_ns=(s["t0_s"] + s["dur_s"]) * 1e9 + offset)
            for s in spans if s["dur_s"] is not None]


def has_tree(spans) -> bool:
    """Whether the spans carry the absolute start and the parent id."""
    return bool(spans) and "t0_s" in spans[0] and "parent" in spans[0]


def depths(spans) -> dict:
    """Span id -> nesting depth (0 for a span with no recorded parent)."""
    by_id = {s["id"]: s for s in spans}
    out = {}

    def depth(sid):
        if sid not in out:
            p = by_id[sid]["parent"]
            out[sid] = 0 if p not in by_id else depth(p) + 1
        return out[sid]
    for sid in by_id:
        depth(sid)
    return out


def innermost(spans) -> list[tuple]:
    """The placed spans flattened to disjoint ``(start, end, span)``
    stretches, each held by the deepest span open in it, in time order."""
    depth = depths(spans)
    cuts = sorted({t for s in spans for t in (s["start_ns"], s["end_ns"])})
    order = sorted(spans, key=lambda s: s["start_ns"])
    out, i, open_ = [], 0, []
    for a, b in zip(cuts, cuts[1:]):
        while i < len(order) and order[i]["start_ns"] <= a:
            open_.append(order[i])
            i += 1
        open_ = [s for s in open_ if s["end_ns"] > a]
        if open_:
            top = max(open_, key=lambda s: (depth[s["id"]],
                                            -(s["end_ns"] - s["start_ns"])))
            out.append((a, b, top))
    return out


def idle(busy, lo: float, hi: float) -> list[tuple]:
    """Stretches of ``[lo, hi)`` outside the merged ``busy`` intervals."""
    out, t = [], lo
    for s, e in busy:
        if e <= t:
            continue
        if s >= hi:
            break
        if s > t:
            out.append((t, s))
        t = e
    if t < hi:
        out.append((t, hi))
    return out


def attribute(gaps, stretches) -> list[tuple]:
    """``(span or None, ns)`` for each piece of each idle gap: the span
    holding that piece, None where no span covers it."""
    out, j = [], 0
    for a, b in gaps:
        t = a
        while j < len(stretches) and stretches[j][1] <= a:
            j += 1
        k = j
        while t < b and k < len(stretches) and stretches[k][0] < b:
            s, e, span = stretches[k]
            if s > t:
                out.append((None, s - t))
                t = s
            end = min(e, b)
            if end > t:
                out.append((span, end - t))
                t = end
            k += 1
        if t < b:
            out.append((None, b - t))
    return out


def traced_idle(ctx) -> list[tuple] | None:
    """``(span or None, ns)`` for the device idle inside each traced
    ``engine.run_epoch`` annotation, with the program's spans placed on
    the trace's clock; None where they cannot be placed."""
    spans, events = ctx["spans"], ctx["device_events"]
    planes = devtrace.devices(events)
    if not has_tree(spans) or not planes:
        return None
    off = offset_ns(events, ctx["traced"])
    if off is None:
        return None
    ann = annotations(events)
    lo, hi = ann[0]["start_ns"], ann[-1]["start_ns"] + ann[-1]["dur_ns"]
    stretches = innermost([s for s in placed(spans, off)
                           if s["end_ns"] > lo and s["start_ns"] < hi])
    busy = devtrace.busy_intervals(events, planes[0])
    out = []
    for a in ann:
        gaps = idle(busy, a["start_ns"], a["start_ns"] + a["dur_ns"])
        out += attribute(gaps, stretches)
    return out


def engine_host_segment(span, by_id) -> bool:
    """Whether a span is the engine's own host code: below an
    ``engine.epoch`` span and not off the host's code (``off_host``), nor
    inside such a span."""
    while span is not None and span["name"] != ROOT and not off_host(span):
        parent = by_id.get(span["parent"])
        if parent is not None and parent["name"] == ROOT:
            return True
        span = parent
    return False


def descendants(spans, root, stop=None) -> list[dict]:
    """Every span recorded below ``root``; the walk does not go below a
    span for which ``stop`` is true."""
    kids = {}
    for s in spans:
        kids.setdefault(s["parent"], []).append(s)
    out, todo = [], [root["id"]]
    while todo:
        for s in kids.get(todo.pop(), ()):
            out.append(s)
            if stop is None or not stop(s):
                todo.append(s["id"])
    return out

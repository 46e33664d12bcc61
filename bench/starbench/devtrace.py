"""Reduce a JAX profiler trace to device times.

``load`` flattens the ``.xplane.pb`` a traced run writes into plain event
records; everything after works on those records, so a small recorded
excerpt checks the arithmetic without a chip.

* Device planes are ``/device:<KIND>:<n>`` (any kind but the host CPU).
  Their ``XLA Modules`` line holds one event per program execution, named
  after the jitted function (``jit_run_partitioned(…)``); their
  ``XLA Ops`` line holds the operations inside.
* Busy time is the union of a chip's program and operation intervals;
  idle is the rest of the traced window.
* Host events are the benchmark's own annotations; a gap in the device's
  work is named after the innermost one that covers its middle.
"""
from __future__ import annotations

import glob
import re
from bisect import bisect_right
from pathlib import Path

MODULES, OPS = "XLA Modules", "XLA Ops"


def load(trace_dir, host_names) -> list[dict]:
    """Event records of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(str(Path(trace_dir) / "plugins" / "profile"
                                 / "*" / "*.xplane.pb")))
    if not files:
        return []
    data = ProfileData.from_file(files[-1])
    out = []
    for plane in data.planes:
        device = plane.name.startswith("/device:") \
            and not plane.name.startswith("/device:CPU")
        for line in plane.lines:
            if device and line.name not in (MODULES, OPS):
                continue
            for ev in line.events:
                if not device and ev.name not in host_names:
                    continue
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "start_ns": ev.start_ns,
                            "dur_ns": ev.duration_ns})
    return out


def _merge(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def devices(events) -> list[str]:
    return sorted({e["plane"] for e in events if e["line"] in (MODULES, OPS)})


def busy_intervals(events, plane):
    """Merged intervals in which a program or an operation ran."""
    return _merge((e["start_ns"], e["start_ns"] + e["dur_ns"])
                  for e in events
                  if e["plane"] == plane and e["line"] in (MODULES, OPS))


def busy_s(events) -> float:
    """Seconds some operation ran, averaged over the chips traced."""
    planes = devices(events)
    if not planes:
        return 0.0
    total = sum(e - s for p in planes for s, e in busy_intervals(events, p))
    return total / len(planes) / 1e9


def module_name(name: str) -> str:
    """``jit_run_partitioned(42)`` -> ``jit_run_partitioned``."""
    return re.sub(r"\(.*$", "", name).strip()


def module_seconds(events, names) -> float:
    """Device seconds of the programs named ``names``, averaged over
    chips."""
    planes = devices(events)
    if not planes:
        return 0.0
    t = sum(e["dur_ns"] for e in events if e["line"] == MODULES
            and module_name(e["name"]) in names)
    return t / len(planes) / 1e9


def op_name(name: str) -> str:
    """``%while.342 = (s32[]…) while(…)`` -> ``while.342``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def _owner(modules):
    """A function from a time to the name of the module running then."""
    spans = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"],
                    module_name(e["name"])) for e in modules)
    starts = [s for s, _, _ in spans]

    def find(t):
        i = bisect_right(starts, t) - 1
        return spans[i][2] if i >= 0 and t < spans[i][1] else "?"
    return find


def top_ops(events, n: int = 10):
    """The operations that took most device time, named
    ``<program>/<op>``: [[name, seconds]], averaged over chips."""
    planes = devices(events)
    tot = {}
    for plane in planes:
        owner = _owner([e for e in events if e["plane"] == plane
                        and e["line"] == MODULES])
        for e in events:
            if e["plane"] == plane and e["line"] == OPS:
                k = f"{owner(e['start_ns'])}/{op_name(e['name'])}"
                tot[k] = tot.get(k, 0) + e["dur_ns"]
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / len(planes) / 1e9] for k, v in ranked]


def idle_gaps(events, n: int = 10):
    """The longest gaps between device work on the first chip, named by
    the host annotation covering each: [[name, seconds]]."""
    planes = devices(events)
    if not planes:
        return []
    busy = busy_intervals(events, planes[0])
    host = [e for e in events if e["line"] not in (MODULES, OPS)]
    gaps = []
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        mid = (e0 + s1) / 2
        cover = [h for h in host
                 if h["start_ns"] <= mid <= h["start_ns"] + h["dur_ns"]]
        name = min(cover, key=lambda h: h["dur_ns"])["name"] if cover \
            else "outside the benchmark's annotations"
        gaps.append([name, (s1 - e0) / 1e9])
    return sorted(gaps, key=lambda g: -g[1])[:n]

"""One metrics registry: counters and gauges under a dotted namespace.

The existing stats dataclasses (``EngineStats``, ``ServiceStats``,
``ReadTierStats``, the per-node arrays in the cluster service) REGISTER
into a :class:`MetricsRegistry` instead of being hand-merged by every
benchmark:

* ``register_object("engine", eng.stats)`` — every numeric dataclass
  field becomes a gauge ``engine.<field>`` read live at snapshot time;
* ``register_provider("cluster", fn)`` — ``fn()`` returns a flat
  ``{name: value}`` dict merged under the prefix (how per-node arrays
  become ``cluster.node3.fence_wait_s``).

``snapshot(epoch)`` materializes one point of the per-epoch time series
(registered objects + providers + explicit counters/gauges);
``export_jsonl`` writes one JSON object per snapshot line.
"""
from __future__ import annotations

import dataclasses
import json
import math
import threading


def _numeric(v):
    if isinstance(v, bool):
        return int(v)
    if isinstance(v, (int, float)) and not (isinstance(v, float)
                                            and math.isnan(v)):
        return v
    return None


class MetricsRegistry:
    """Namespaced counters/gauges + per-epoch snapshots."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict = {}
        self._gauges: dict = {}
        self._objects: list = []      # (prefix, obj)
        self._providers: list = []    # (prefix, fn)
        self.snapshots: list = []

    # -- primitive instruments --------------------------------------------
    def counter_add(self, name: str, value=1):
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + value

    def gauge_set(self, name: str, value):
        with self._lock:
            self._gauges[name] = value

    # -- registration: the stats dataclasses plug in here ------------------
    def register_object(self, prefix: str, obj) -> None:
        """Expose every numeric dataclass/attribute field as
        ``<prefix>.<field>`` gauges, read live at snapshot time."""
        self._objects.append((prefix, obj))

    def register_provider(self, prefix: str, fn) -> None:
        """``fn() -> {name: value}`` merged under ``<prefix>.`` at
        snapshot time (per-node arrays, lane summaries, launch counts)."""
        self._providers.append((prefix, fn))

    # -- reading -----------------------------------------------------------
    def _object_values(self, prefix, obj):
        if dataclasses.is_dataclass(obj):
            items = ((f.name, getattr(obj, f.name))
                     for f in dataclasses.fields(obj))
        else:
            items = ((k, v) for k, v in vars(obj).items()
                     if not k.startswith("_"))
        out = {}
        for k, v in items:
            n = _numeric(v)
            if n is not None:
                out[f"{prefix}.{k}"] = n
        return out

    def values(self) -> dict:
        """Flat ``{metric: value}`` of everything, read live."""
        out = {}
        for prefix, obj in self._objects:
            out.update(self._object_values(prefix, obj))
        for prefix, fn in self._providers:
            for k, v in (fn() or {}).items():
                n = _numeric(v)
                if n is not None:
                    out[f"{prefix}.{k}" if prefix else k] = n
        with self._lock:
            out.update(self._counters)
            out.update({k: v for k, v in self._gauges.items()
                        if _numeric(v) is not None})
        return out

    def snapshot(self, epoch=None) -> dict:
        """Record one time-series point; returns it."""
        snap = {"epoch": epoch}
        snap.update(sorted(self.values().items()))
        self.snapshots.append(snap)
        return snap

    def latest(self) -> dict:
        return self.snapshots[-1] if self.snapshots else self.snapshot()

    # -- exporters ---------------------------------------------------------
    def export_jsonl(self, path: str) -> int:
        """One JSON object per snapshot line; returns the line count."""
        snaps = self.snapshots or [self.snapshot()]
        with open(path, "w") as f:
            for s in snaps:
                f.write(json.dumps(s) + "\n")
        return len(snaps)

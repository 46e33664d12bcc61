"""Find a cell's pieces by name.

``BENCHMARK.json`` at the checkout's root lists the configurations, the
cells and the metrics.  Each piece is a file of its own under ``bench/``:

* a configuration: the file its ``configs`` entry names;
* a traffic mix: ``bench/workloads/<traffic>.json``;
* a per-layer metric: ``bench/metrics/<metric>.py``, a module with a
  ``read(ctx)`` function that returns the value or ``None``.

A later change adds a cell, a configuration or a metric by adding files
and entries, never by editing these functions.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class CellError(Exception):
    """The checkout does not describe the asked-for cell."""


def load_benchmark(root: Path = ROOT) -> dict:
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise CellError(f"no BENCHMARK.json at {path}")
    return json.loads(path.read_text())


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise CellError(f"unknown {what} {name!r}")


def resolve(name: str, root: Path = ROOT) -> dict:
    """Everything one cell needs: its entry, configuration, traffic mix and
    the metrics it reports."""
    root = Path(root)
    bench = load_benchmark(root)
    cell = _by_name(bench["workloads"], name, "workload")
    cfg_entry = _by_name(bench["configs"], cell["config"], "config")
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic_path = root / "bench" / "workloads" / f"{cell['traffic']}.json"
    if not traffic_path.is_file():
        raise CellError(f"no traffic file {traffic_path}")
    traffic = json.loads(traffic_path.read_text())
    e2e = [m for m in bench["end_to_end"] if applies(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if applies(m, name) and m["moves"] in reported]
    return {"name": name, "cell": cell, "config": config,
            "config_name": cfg_entry["name"], "traffic": traffic,
            "end_to_end": e2e, "per_layer": per_layer, "root": root,
            "run_seconds": bench["run_seconds"]}


def applies(metric: dict, cell: str) -> bool:
    """A metric without a ``workloads`` key applies to every cell."""
    return "workloads" not in metric or cell in metric["workloads"]


def load_reader(name: str, root: Path = ROOT):
    """The reader module of per-layer metric ``name``."""
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise CellError(f"no reader {path}")
    spec = importlib.util.spec_from_file_location(
        "starbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

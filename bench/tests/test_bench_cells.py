"""Cells, configurations and metrics are found by name, from files alone."""
import json
import shutil

import pytest

import bench_tiny
from starbench import cells, harness

NEW_METRIC = '''"""A metric dropped in as a file."""


def read(ctx):
    return 42.0
'''


def test_every_cell_of_the_benchmark_resolves():
    bench = cells.load_benchmark(bench_tiny.REPO)
    assert bench["workloads"]
    for cell in bench["workloads"]:
        spec = cells.resolve(cell["name"], bench_tiny.REPO)
        assert spec["config"]["name"] == cell["config"]
        assert {m["name"] for m in spec["end_to_end"]} >= {"setup_s"}
        assert spec["per_layer"], "every cell reports a per-layer metric"
        for m in spec["per_layer"]:
            assert hasattr(cells.load_reader(m["name"], bench_tiny.REPO),
                           "read")


def test_a_cell_and_a_metric_added_as_files_are_found(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(bench_tiny.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    bench = json.loads((bench_tiny.REPO / "BENCHMARK.json").read_text())
    (root / "bench" / "workloads" / "ycsb16.closed9.json").write_text(
        json.dumps({"loop": "closed", "outstanding": 9}))
    (root / "bench" / "metrics" / "answer.count.py").write_text(NEW_METRIC)
    bench["workloads"].append(
        {"name": "ycsb16.closed9", "config": "ycsb-16p",
         "traffic": "ycsb16.closed9", "chips": 1, "why": "test"})
    bench["per_layer"].append(
        {"name": "answer.count", "unit": "1", "better": "higher",
         "source": "program_counter", "layer": "engine epoch",
         "moves": "txn_s", "workloads": ["ycsb16.closed9"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    spec = cells.resolve("ycsb16.closed9", root)
    assert spec["traffic"] == {"loop": "closed", "outstanding": 9}
    assert spec["config"]["kind"] == "ycsb"
    assert [m["name"] for m in spec["per_layer"]] == ["answer.count"]
    assert harness.per_layer(spec, {}) == {
        "answer.count": {"value": 42.0, "unit": "1"}}
    # the new metric does not leak into cells it does not list
    assert "answer.count" not in {
        m["name"] for m in cells.resolve("ycsb16.closed", root)["per_layer"]}


def test_unknown_cell_and_missing_files_are_errors(tmp_path):
    with pytest.raises(cells.CellError):
        cells.resolve("no.such.cell", bench_tiny.REPO)
    with pytest.raises(cells.CellError):
        cells.load_benchmark(tmp_path)
    with pytest.raises(cells.CellError):
        cells.load_reader("no.such.metric", bench_tiny.REPO)


def test_peaks_are_keyed_by_device_kind():
    assert harness.peak_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", ""])
def test_an_unknown_device_kind_is_an_error(kind):
    with pytest.raises(KeyError, match="no peaks"):
        harness.peak_of(kind)


def test_the_command_refuses_to_run_without_a_chip():
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(bench_tiny.BENCH / "run.py"), "--workload",
         "ycsb16.closed", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=bench_tiny.REPO, env=env, capture_output=True, text=True,
        timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no accelerator" in out.stderr

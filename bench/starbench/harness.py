"""One run of one cell: serve the window, check it, print the result.

The result is the last line of standard output, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, with
``--trace 1``, ``breakdown``; its last key, ``checks``, holds each number
the correctness check compared beside its limit.  The same numbers end
standard error.
"""
from __future__ import annotations

import gc
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from starbench import cells, check, devtrace, stats
from starbench.serve import Run

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"
HOST_ANNOTATIONS = ("engine.run_epoch", "service.ingest")


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def peak_of(device_kind: str, path: Path = PEAKS) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def find_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoChip(f"JAX found no accelerator (platform {devs[0].platform})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def enable_cache() -> str:
    """Keep JAX's persistent compilation cache at a fixed path inside the
    checkout, whatever the environment names, so that only a cell's first
    run in a checkout compiles."""
    import jax
    path = str(cells.BENCH_DIR / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def end_to_end(run: Run, t_process: float) -> tuple[dict, int, int]:
    """(metrics, attempted, failed) of the window."""
    start, end = run.window
    fences = [e["t_fence"] for e in run.epochs]
    t0, t1 = fences[start], fences[end]
    lat, ok = [], []
    for k, l, o in run.ledger.answers:
        if start < k <= end:
            lat.append(l)
            ok.append(o)
    lat = np.concatenate(lat) if lat else np.zeros(0)
    ok = np.concatenate(ok) if ok else np.zeros(0, bool)
    shed = sum(1 for t in run.ledger.shed_at if t0 < t <= t1)
    failed = shed + run.ledger.outstanding
    metrics = {
        "txn_s": stats.rate(int(ok.sum()), t0, t1),
        "commit_p50_ms": stats.percentile_ms(lat, 50),
        "commit_p99_ms": stats.percentile_ms(lat, 99),
        "setup_s": run.t_window_open - t_process,
    }
    if run.recovery is not None and start < run.recovery[0] <= end:
        # the fence-to-fence gap a client waits through: the doomed epoch,
        # recovery and the re-executed epoch
        metrics["recovery_s"] = run.recovery[1]
    gaps = sorted(range(start + 1, end + 1),
                  key=lambda k: fences[k] - fences[k - 1], reverse=True)[:3]
    for k in gaps:
        log(f"long epoch: window epoch {k - start}, "
            f"{epoch_split(run.epochs[k], fences[k - 1])}")
    k = sorted(range(start + 1, end + 1),
               key=lambda k: fences[k] - fences[k - 1])[(end - start) // 2]
    log(f"median epoch: window epoch {k - start}, "
        f"{epoch_split(run.epochs[k], fences[k - 1])}")
    log(f"window: {end - start} epochs, {t1 - t0} s fence to fence, "
        f"{int(ok.sum())} committed, {lat.size} answered, {shed} shed, "
        f"{run.ledger.outstanding} unanswered at the end")
    return metrics, int(lat.size) + failed, failed


def epoch_split(e: dict, prev_fence: float) -> str:
    """Where the host clock went in one epoch, from the fence before it:
    until the engine call, the overlapped ingest, the partitioned phase
    (to its block), the rest up to fence 1 (stream publish, byte
    accounting, fence), the single-master phase, and the rest up to
    fence 2."""
    s = e["split"]
    if None in s.values():
        return f"{e['t_fence'] - prev_fence} s"
    call, f1, f2 = e["t_call"], s["t_fence1_s"], s["t_fence2_s"]
    out = (f"{e['t_fence'] - prev_fence} s: before the call "
           f"{call - prev_fence}, ingest {s['t_ingest_s']}, partitioned "
           f"{s['t_part_s']}, to fence 1 {f1 - call - s['t_part_s']}, "
           f"single-master {s['t_sm_s']}, to fence 2 "
           f"{f2 - f1 - s['t_sm_s']}, after {e['t_fence'] - f2}")
    return out + f"; process CPU over the call {e['cpu_s']} s"


def layer_context(run: Run, window: dict, events, spans, peaks) -> dict:
    """What the per-layer readers read."""
    start, end = run.window
    t_first, t_last = run.trace_span if run.trace_span else (end, end)
    return {
        # host-clock metrics read the window's epochs before the profiler
        "epochs": run.epochs[start + 1:t_first + 1],
        "fences": (run.epochs[start]["t_fence"],
                   run.epochs[t_first]["t_fence"], t_first - start),
        "spans": spans,
        "device_events": events,
        "traced": run.epochs[t_first + 1:t_last + 1],
        "peaks": peaks,
        "n_cols": run.world.init_val.shape[-1],
        # the end-to-end numbers of the whole window, host clock
        "window": window,
    }


def per_layer(spec, ctx) -> dict:
    out = {}
    for m in spec["per_layer"]:
        value = cells.load_reader(m["name"], spec["root"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def execute(spec: dict, seed: int, seconds: float, trace: bool,
            devices=None, fault=None, t_process: float | None = None,
            peaks=None) -> dict:
    """Serve, check and measure one run; returns the result object."""
    t_process = time.perf_counter() if t_process is None else t_process
    trace_dir = spec["root"] / "bench" / "out" / f"trace-{spec['name']}"
    run = Run(spec, seed, seconds, trace, trace_dir=trace_dir, fault=fault,
              devices=devices)
    spans = []
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        from repro.obs import trace as obs
        tracer = obs.Tracer(enabled=True, capacity=1 << 20)
        previous = obs.set_tracer(tracer)
        try:
            run.serve()
        finally:
            obs.set_tracer(previous)
        spans = tracer.events()
    else:
        run.serve()
    n_compiles = run.compiles_at["close"] - run.compiles_at["open"]
    log(f"compiles in the window: {n_compiles}")
    if n_compiles:
        raise RuntimeError(f"{n_compiles} compiles inside the window")
    metrics, attempted, failed = end_to_end(run, t_process)
    device = {}
    if devices is not None:
        d = devices[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": len(devices),
                  "memory_peak_bytes": max(
                      (x.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for x in devices)}
    result = {"attempted": attempted, "failed": failed}
    if trace:
        events = devtrace.load(trace_dir, HOST_ANNOTATIONS)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = layer_context(run, metrics, events, spans, peaks)
        result["metrics"] = per_layer(spec, ctx)
        a, b = run.trace_span
        device["busy_s"] = devtrace.busy_s(events)
        device["window_s"] = run.epochs[b]["t_fence"] \
            - run.epochs[a]["t_fence"]
        result["breakdown"] = {"device_ops": devtrace.top_ops(events),
                               "idle_gaps": devtrace.idle_gaps(events)}
    else:
        result["metrics"] = {m["name"]: {"value": metrics[m["name"]],
                                         "unit": m["unit"]}
                             for m in spec["end_to_end"]}
    result["device"] = device

    t_ref = time.perf_counter()
    state = check.engine_state(run.engine)
    epochs, world = run.epochs, run.world
    run.release()
    gc.collect()
    batches, request_differ = check.issued_batches(
        epochs, run.ledger, world.init_val.shape[1])
    ref, differ = check.reference_run(epochs, batches, world,
                                      spec["config"]["occ_rounds"])
    checks = check.compare(state, ref, request_differ, differ)
    log(f"reference: {len(epochs)} epochs in {time.perf_counter() - t_ref} s")
    for k, c in checks.items():
        log(f"check {k}: {c['value']} limit {c['limit']}")
    return {"correct": check.passed(checks), **result, "checks": checks}


def main(argv=None, t_process: float | None = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = cells.resolve(args.workload)
        devices = find_devices(spec["cell"]["chips"])
        peaks = peak_of(devices[0].device_kind)
    except (cells.CellError, NoChip, KeyError) as e:
        log(f"bench: {e}")
        return 1
    d = devices[0]
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devices)}")
    log(f"compile cache: {enable_cache()}")
    result = execute(spec, args.seed, args.seconds, bool(args.trace),
                     devices=devices, t_process=t_process, peaks=peaks)
    print(json.dumps(result), flush=True)
    return 0

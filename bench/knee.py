"""Sweep an open-loop cell's offered rate on the chip, to find its knee.

    python3 bench/knee.py --workload <cell> --seconds <s> --seed <n> \
        --rates 600,800,1000

For each rate, in one process: the committed txn/s of the window, the
requests shed in it, and the admission queue depth when it closed.  The
knee is the highest rate with nothing shed and no more than one batch
queued at the close; the cell's traffic file then states four fifths of it.
"""
import sys
import time

from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

import argparse  # noqa: E402
import copy  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402

from starbench import cells, harness  # noqa: E402
from starbench.serve import Run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    spec = cells.resolve(args.workload)
    try:
        harness.find_devices(spec["cell"]["chips"])
    except harness.NoChip as e:
        print(f"knee: {e}", file=sys.stderr)
        return 1
    harness.enable_cache()
    for rate in (float(r) for r in args.rates.split(",")):
        s = copy.deepcopy(spec)
        s["traffic"]["rate_txn_s"] = rate
        run = Run(s, args.seed, args.seconds, False)
        run.serve()
        metrics, attempted, failed = harness.end_to_end(run, time.perf_counter())
        a, b = run.window
        batch = s["traffic"]["slots_per_partition"] * \
            s["config"]["params"]["n_partitions"] + s["traffic"]["master_lanes"]
        print(json.dumps({
            "rate": rate, "txn_s": metrics["txn_s"],
            "p50_ms": metrics["commit_p50_ms"],
            "p99_ms": metrics["commit_p99_ms"],
            "shed": failed, "depth_at_close": run.depth_at_close,
            "batch": batch,
            "epoch_ms": (run.epochs[b]["t_fence"] - run.epochs[a]["t_fence"])
            / (b - a) * 1e3}), flush=True)
        run.release()
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())

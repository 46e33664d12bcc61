"""Run a cell on several seeds in one process, sound or with a fault, and
print what the correctness check compared for each.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3 \
        [--fault replica_lag|state_unchanged|half_batch|altered_answer|altered_op]

The sound runs give the lower readings of the check's numbers, the
control (``replica_lag``) and the faults their upper readings.  One JSON
line per seed; the benchmark's own runs never run this.
"""
import sys
import time

T_PROCESS = time.perf_counter()

from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402

from starbench import cells, faults, harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = ap.parse_args(argv)
    spec = cells.resolve(args.workload)
    try:
        devices = harness.find_devices(spec["cell"]["chips"])
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 1
    harness.enable_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        fault = faults.FAULTS[args.fault]() if args.fault else None
        res = harness.execute(spec, seed, args.seconds, False,
                              devices=devices, fault=fault)
        print(json.dumps({
            "workload": args.workload, "seed": seed, "fault": args.fault,
            "correct": res["correct"],
            "checks": {k: c["value"] for k, c in res["checks"].items()},
            "metrics": {k: m["value"] for k, m in res["metrics"].items()},
            "attempted": res["attempted"], "failed": res["failed"]}),
            flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())

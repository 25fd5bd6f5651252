#!/usr/bin/env python3
"""Readings that the correctness limits of a cell are set from.

    python3 bench/calibrate.py --workload stablelm_3b.chat \
        --seeds 101,102,103,104 --control-seeds 3

In one process, for each seed: one run of the cell as ``bench/run.py``
makes it (its own warm-up, load, window and check sample), the check's
numbers for what the program served, and, on the first
``--control-seeds`` seeds, the control's numbers on the same sample: the
float32 reference with every 16-bit value rounded to int8
(``bench/models/<model>.py``), judged by the cell's own limits, where it
has to come out not correct.  One JSON line per seed, then a summary:
the largest program reading and the smallest control reading of each
number, and whether every control failed.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: BENCHMARK.json's)")
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="compute the control on the first N seeds")
    args = ap.parse_args()
    from harness import cell, spec
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    cell.use_compile_cache()
    c = spec.cell(args.workload)
    seconds = args.seconds or spec.load_benchmark()["run_seconds"]
    c.per_layer, c.end_to_end = [], []
    prog, ctl, ctl_failed = {}, {}, True
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        out = cell.run_cell(c, seed, seconds, False, time.perf_counter(),
                            control="int8" if i < args.control_seeds
                            else None)
        row = {"seed": seed, "correct": out["correct"],
               "program": {k: v["value"] for k, v in out["check"].items()},
               "control": out.get("control")}
        print(json.dumps(row), flush=True)
        for k, v in row["program"].items():
            if v is not None:
                prog[k] = max(prog.get(k, v), v)
        if row["control"] is not None:
            ctl_failed &= not row["control"]["correct"]
            for k, v in row["control"].items():
                if k != "correct":
                    ctl[k] = min(ctl.get(k, v["value"]), v["value"])
    print(json.dumps({"workload": args.workload, "program_max": prog,
                      "control_min": ctl, "limits": c.check["limits"],
                      "every_control_not_correct": ctl_failed}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the machine it is started on.

    python3 bench/run.py --workload stablelm_3b.chat --seed 7 \
        --seconds 51 --trace 0

The cell, its configuration, its traffic mix, its correctness limits and
its metrics are found by name from ``BENCHMARK.json`` (see
``bench/harness/spec.py``).  ``--trace 0`` prints the cell's end-to-end
metrics; ``--trace 1`` runs the same window under the JAX profiler and
prints its per-layer metrics, the device's busy time and a breakdown.

Set-up and the correctness check are logged on standard error, the check's
numbers with their limits last.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (platform, kind, count, peak memory; traced runs add
``busy_s`` and ``window_s``), traced runs ``breakdown``, and ``check``.
Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits with code 2.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    from harness import spec
    c = spec.cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < c.chips:
        print(f"bench: {args.workload} needs {c.chips} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from harness import cell
    cell.use_compile_cache()
    out = cell.run_cell(c, args.seed, args.seconds, bool(args.trace),
                        T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Find the knee of a cell: the highest offered rate at which the backlog
does not grow over the window.

    python3 bench/sweep.py --workload stablelm_3b.chat \
        --rates 0.8,1.0,1.2,1.4 --seconds 30 --seed 5

One process, one engine: the cell's own warm-up at the first rate, then
the rates in ascending order, each for a settling period and a window,
each starting from the state the previous rate left.  For each rate it
prints one JSON line: the requests offered in the window, the backlog
(requests accepted but not yet prefilling: the gateway's queue and the
scheduler's waiting queue) when the window opened and when it closed,
the time-to-first-token median and 90th percentile of the window's
requests that got one, how many did not, the output tokens per second,
the most pages live requests held and the fused-step compiles inside the
window.  The cell itself then runs at a fixed rate written in its
traffic file.
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--settle", type=float, default=15.0,
                    help="seconds at each rate before its window")
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args()
    import jax
    from harness import cell, driver, model, spec, stats
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    cell.use_compile_cache()
    c = spec.cell(args.workload)
    mod = model.load(c.config)
    d = mod.dims_of(c.config)
    dev = jax.devices()[0]
    weights = mod.make_weights(d, args.seed, dev)
    eng, gw = cell.build_engine(c.config, mod, d, weights, dev)
    door = gw.door(driver.TENANT)
    t_origin, next_id = time.perf_counter(), 0
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(c.mix, rate_per_s=rate)
        rlog = driver.RunLog(window_s=args.seconds)
        drv = driver.Driver(gw, eng, mix, d.vocab, args.seed + i, rlog)
        drv.t_origin, drv.next_id = t_origin, next_id
        drv.sched_t = drv.now()
        if i == 0:
            drv.warm_up()
        else:
            drv.burst = 0
        settle = drv.now() + args.settle
        drv.run(until=settle, offer_until=settle)
        drv.open_window()
        b0 = len(door.queue) + len(eng.queue)
        end = rlog.w0 + args.seconds
        drv.run(until=end, offer_until=end)
        b1 = len(door.queue) + len(eng.queue)
        next_id = drv.next_id
        reqs = driver.window_reqs(rlog)
        ttft = [r.req.prefill_done - r.req.arrival for r in reqs
                if r.req.prefill_done >= 0]
        toks = sum(1 for r in rlog.reqs for t in driver.emissions(r.req)
                   if driver.in_window(rlog, t))
        steps = driver.window_steps(rlog)
        print(json.dumps({
            "rate_per_s": rate, "offered": len(reqs),
            "backlog_open": b0, "backlog_close": b1,
            "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50) if ttft else None,
            "ttft_p90_ms": 1e3 * stats.percentile(ttft, 90) if ttft else None,
            "no_first_token": len(reqs) - len(ttft),
            "output_tokens_per_s": toks / args.seconds,
            "peak_pages": max((s.pages for s in rlog.steps), default=0),
            "window_compiles": sum(s.compiles for s in steps),
            "compiled": [(round(t, 1), k) for t, k in rlog.compiled],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

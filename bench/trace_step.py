#!/usr/bin/env python3
"""One traced run of a cell with the program's own tracing attached.

    python3 bench/trace_step.py --workload stablelm_3b.chat --seed 7

The run ``bench/run.py --trace 1`` makes (set-up, warm-up, the window
under the JAX profiler, the drain), with one ``FlightRecorder`` on the
run's clock attached to the gateway and the engine: the paged runtime's
``step.*`` spans, its counters and every request's segments land on it,
and the step spans also land on the profiler's host plane.  The device
trace is reduced as ``bench/run.py`` reduces it, and also by the fused
step's named scopes (``harness/spans.py``).  No correctness check: that
is ``bench/run.py``'s.

The last line of standard output is one JSON object: every per-layer
metric of the cell and the readers in ``bench/metrics/`` that read the
program's spans (``kv_pool_busy_pct``, ``door_wait_p90_ms``,
``sched_wait_p90_ms``, ``step_host_span_ms``), the end-to-end
``itl_mean_ms`` and ``ttft_p90_ms`` of this traced run, device self
time per scope (``scope_s``, and its largest operations) with busy and
kernel time, the idle gaps named by the innermost span, the counters'
growth over the window, and checks of the recorder against the driver's
own step log.  The window's spans go to
``bench/.out/spans-<cell>-<seed>.json`` (Chrome trace JSON).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

# readers of this cell's metrics that read the program's spans
PROGRAM_READERS = ("kv_pool_busy_pct", "door_wait_p90_ms",
                   "sched_wait_p90_ms", "step_host_span_ms")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: BENCHMARK.json's)")
    args = ap.parse_args()
    import jax
    from harness import cell, spec
    if jax.devices()[0].platform != "tpu":
        print("trace_step: no TPU", file=sys.stderr)
        return 2
    cell.use_compile_cache()
    c = spec.cell(args.workload)
    seconds = args.seconds or spec.load_benchmark()["run_seconds"]
    out = traced_run(c, args.seed, seconds, T_START)
    print(json.dumps(out), flush=True)
    return 0


def traced_run(c, seed: int, seconds: float, t_start: float,
               device=None) -> dict:
    """One traced run of cell ``c`` with a recorder attached; ``t_start``
    is the process's start on the ``perf_counter`` clock."""
    import jax
    from harness import cell, driver, model, profile, spans, spec
    from repro.core.obs import dump_chrome_trace
    from repro.serving.trace import FlightRecorder
    dev = device or jax.devices()[0]
    mod = model.load(c.config)
    d = mod.dims_of(c.config)
    weights = jax.block_until_ready(mod.make_weights(d, seed, dev))
    eng, gw = cell.build_engine(c.config, mod, d, weights, dev)
    rt = eng.runtime
    rlog = spans.ProgramLog(window_s=float(seconds))
    drv = driver.Driver(gw, eng, c.mix, d.vocab, seed, rlog, annotate=True)
    rec = FlightRecorder(clock=drv.now)
    gw.tracer = rec
    eng.tracer = rec
    drv.t_origin = time.perf_counter()
    drv.warm_up()
    trace_dir = cell.OUT_DIR / f"trace-step-{c.name}-{seed}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    lead = drv.now() + cell.LEAD_S
    drv.run(until=lead, offer_until=lead)
    drv.open_window()
    setup_s = time.perf_counter() - t_start
    end = rlog.w0 + float(seconds)
    counters_open = dict(rec.counters)
    n_events = len(rec.events)
    with jax.profiler.TraceAnnotation(profile.WINDOW_SPAN):
        t0 = drv.now()
        drv.run(until=end, offer_until=end)
    rlog.traced = (t0, drv.now())
    counters_close = dict(rec.counters)
    jax.profiler.stop_trace()
    drain = float(c.mix["drain_s"])
    drv.run(until=end + drain, offer_until=end + drain,
            stop=lambda: cell.first_tokens_in(rlog))
    gw.check()
    rec.check()
    rlog.program = spans.program_log(rec, rlog, counters_open,
                                     counters_close)
    cell.OUT_DIR.mkdir(parents=True, exist_ok=True)
    dump_chrome_trace(rec.events[n_events:],
                      str(cell.OUT_DIR / f"spans-{c.name}-{seed}.json"))
    hlo = [exe.as_text() for exe in rt._mixed_exec.values()]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    eng.tracer = gw.tracer = None
    del eng, gw, drv, rt, rec

    events = spans.extract(str(trace_dir), hlo)
    shutil.rmtree(trace_dir, ignore_errors=True)
    rlog.trace = profile.reduce(events, cell.KERNELS)
    if rlog.trace is not None:
        rlog.trace["scope_s"] = spans.scope_times(events)
    by_op = spans.scope_times(events, by_op=True) or {}
    run = cell.Run(cell=c.name, model=mod, dims=d, log=rlog,
                   peaks=spec.peaks(dev.device_kind)
                   if dev.platform == "tpu" else {},
                   setup={"setup_s": setup_s},
                   kv_bytes=1 if c.config["serve"]["kv_dtype"] == "int8"
                   else 2)
    names = [m["name"] for m in c.per_layer] + list(PROGRAM_READERS) \
        + ["itl_mean_ms", "ttft_p90_ms"]
    metrics = {n: spec.reader(n)(run) for n in names}

    # the recorder against the driver's own step log
    steps = driver.traced_steps(rlog)
    device_spans = [[x for x in spans.step_spans(rlog, s)
                     if x[0] == "step.device"] for s in steps]
    tr = rlog.trace or {}
    return {
        "workload": c.name, "seed": seed, "metrics": metrics,
        "scope_s": tr.get("scope_s"),
        "busy_s": tr.get("busy_s"), "window_s": tr.get("window_s"),
        "kernel_s": tr.get("kernel_s"), "idle_gaps": tr.get("idle_gaps"),
        "device_ops": tr.get("device_ops"),
        "scope_ops": sorted(by_op.items(), key=lambda kv: -kv[1])[:20],
        "window_counters": rlog.program["counters"],
        "window_steps": len(driver.window_steps(rlog)),
        "traced_steps": len(steps),
        "device_span_is_compute_s": sum(
            len(ds) == 1 and ds[0][2] == s.compute_s
            for ds, s in zip(device_spans, steps)),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": peak},
    }


if __name__ == "__main__":
    sys.exit(main())

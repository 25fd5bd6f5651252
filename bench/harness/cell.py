"""One run of one cell: set-up, warm-up, the measured window, the drain,
the correctness check and the metrics, in one process."""
from __future__ import annotations

import gc
import math
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, Optional

import jax
import numpy as np

from harness import check, driver, model, profile, spec, stats, traffic

CACHE_DIR = spec.BENCH / ".cache" / "jax"
OUT_DIR = spec.BENCH / ".out"
KERNELS = {"paged_attention": "paged_attention_mixed"}
LEAD_S = 3.0          # warm traffic between the warm-up and the window


@dataclass
class Run:
    """What the metric readers read."""
    cell: str
    model: object           # the configuration's bench/models module
    dims: object
    log: driver.RunLog
    peaks: Dict[str, float]
    setup: Dict[str, float] = field(default_factory=dict)
    kv_bytes: int = 2


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def use_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout; every
    executable goes in it, so a cell's second run compiles nothing.  No
    size limit: a limit smaller than one run's executables evicts each
    entry before the next run asks for it, and every run compiles anew."""
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CacheCounter:
    """Counts persistent-cache hits and misses through jax.monitoring."""

    def __init__(self):
        self.n = {"hits": 0, "misses": 0}
        from jax import monitoring
        monitoring.register_event_listener(self._on)

    def _on(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.n["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.n["misses"] += 1


def build_engine(conf: dict, mod, d, weights, device, attn_impl="auto",
                 kv_dtype: Optional[str] = None):
    """The program under test: one paged engine behind a gateway and a
    cache-aware router, as the serving launcher wires them."""
    from repro.serving.directory import CacheAwareRouter, PrefixDirectory
    from repro.serving.engine import ServingEngine
    from repro.serving.gateway import Gateway
    s = conf["serve"]
    eng = ServingEngine(
        mod.program_config(d), weights, backend="paged", max_slots=s["slots"],
        seq_cap=s["seq_cap"], page_size=s["page_size"],
        pool_pages=s["pool_pages"], kv_dtype=kv_dtype or s["kv_dtype"],
        prefix_cache=s["prefix_cache"], attn_impl=attn_impl, device=device)
    directory = PrefixDirectory(page_size=s["page_size"])
    directory.attach(driver.TENANT, 0, eng.kv)
    router = CacheAwareRouter(directory, driver.TENANT)
    gw = Gateway({driver.TENANT: [eng]}, {driver.TENANT: router})
    return eng, gw


def first_tokens_in(log_: driver.RunLog) -> bool:
    return all(r.req.prefill_done >= 0 or r.req.done
               for r in driver.window_reqs(log_))


def refused(gw, rec) -> bool:
    from repro.serving.gateway import Verdict
    v = gw.door(driver.TENANT).verdict_of(rec.req.req_id)
    return v in (Verdict.REJECTED, Verdict.SHED, Verdict.EXPIRED)


def run_cell(c: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, device=None, attn_impl: str = "auto",
             fault=None, control: Optional[str] = None) -> dict:
    """One run of cell ``c``.  ``t_start`` is the process's start on the
    ``perf_counter`` clock.  ``fault`` (tests only) is called with the
    engine before the window opens, to break the timed path.  With
    ``control`` (calibration only), the result also holds the control's
    numbers on the same sample and its verdict under the cell's limits,
    under ``"control"``."""
    setup: Dict[str, float] = {}
    dev = device or jax.devices()[0]
    setup["jax_init_s"] = time.perf_counter() - t_start
    counter = CacheCounter()
    mod = model.load(c.config)
    d = mod.dims_of(c.config)
    mix = c.mix
    t = time.perf_counter()
    weights = jax.block_until_ready(mod.make_weights(d, seed, dev))
    setup["weights_s"] = time.perf_counter() - t
    t = time.perf_counter()
    eng, gw = build_engine(c.config, mod, d, weights, dev, attn_impl)
    jax.block_until_ready(eng.runtime.pools)
    setup["pools_s"] = time.perf_counter() - t
    rt = eng.runtime
    if fault is not None:
        fault(eng)

    rlog = driver.RunLog(window_s=float(seconds))
    drv = driver.Driver(gw, eng, mix, d.vocab, seed, rlog, annotate=trace)
    trace_dir = OUT_DIR / f"trace-{c.name}-{seed}"
    drv.t_origin = time.perf_counter()
    drv.warm_up()
    setup["warm_compiles"] = len(rt.compile_s)
    setup["warm_compile_s"] = sum(rt.compile_s.values())
    if trace:
        # the profiler starts a little before the window, so the stall of
        # starting it falls in warm-up, which catches up before the window
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    lead = drv.now() + LEAD_S
    drv.run(until=lead, offer_until=lead)
    drv.open_window()
    setup["warm_s"] = rlog.w0
    setup["cache_hits"] = counter.n["hits"]
    setup["cache_misses"] = counter.n["misses"]
    setup_s = time.perf_counter() - t_start
    end = rlog.w0 + float(seconds)
    if trace:
        with jax.profiler.TraceAnnotation(profile.WINDOW_SPAN):
            t0 = drv.now()
            drv.run(until=end, offer_until=end)
        rlog.traced = (t0, drv.now())
        jax.profiler.stop_trace()
    else:
        drv.run(until=end, offer_until=end)
    in_window = driver.window_steps(rlog)
    drain = float(mix["drain_s"])
    drv.run(until=end + drain, offer_until=end + drain,
            stop=lambda: first_tokens_in(rlog))
    mem = dev.memory_stats() or {}
    peak = mem.get("peak_bytes_in_use")
    device_info = {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": peak}

    wreqs = driver.window_reqs(rlog)
    failed = sum(1 for r in wreqs
                 if refused(gw, r) or r.req.prefill_done < 0)
    gw.check()
    log(f"setup: {setup_s:.3f} s = " + ", ".join(
        f"{k} {v:.3f}" if isinstance(v, float) else f"{k} {v}"
        for k, v in setup.items()))
    log("compiled (s, bucket): " + ", ".join(
        f"{t:.1f} {k}" for t, k in rlog.compiled))
    log(f"window: {len(wreqs)} requests, {len(in_window)} steps, "
        f"{sum(s.compiles for s in in_window)} fused-step compiles inside "
        f"the window, peak pages held {max((s.pages for s in rlog.steps), default=0)} "
        f"of {rt.kv.num_pages}, preempted "
        f"{sum(s.preempted for s in in_window)}, offer lateness max "
        f"{max(rlog.late_s, default=0.0) * 1e3:.3f} ms")
    ttft = driver.window_ttft(rlog)
    if ttft:
        log("window ttft ms: " + ", ".join(
            f"p{q} {1e3 * stats.percentile(ttft, q):.1f}"
            for q in (50, 75, 90)))

    # free the program's state before the reference runs
    finished = [r.req for r in rlog.reqs if r.req.done
                and r.req.finished >= 0]
    seq_cap = rt.seq_cap
    del eng, gw, drv, rt
    gc.collect()

    picked = check.sample(finished, seed, c.check["min_served_tokens"],
                          c.check["max_requests"])
    t = time.perf_counter()
    g = ctl = None
    if picked:
        g, ctl = check.gaps(mod, d, weights, picked, seq_cap, control)
    check_s = time.perf_counter() - t
    correct, compared = check.judge(g, c.check["limits"])
    log(f"check: {len(picked)} requests, "
        f"{0 if g is None else g.size} served tokens, reference "
        f"{check_s:.3f} s")

    run = Run(cell=c.name, model=mod, dims=d, log=rlog,
              peaks=spec.peaks(dev.device_kind) if dev.platform == "tpu"
              else {}, setup={"setup_s": setup_s, **setup},
              kv_bytes=1 if c.config["serve"]["kv_dtype"] == "int8" else 2)
    breakdown = None
    if trace:
        events = profile.extract(str(trace_dir))
        rlog.trace = profile.reduce(events, KERNELS)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if rlog.trace is not None:
            device_info["busy_s"] = rlog.trace["busy_s"]
            device_info["window_s"] = rlog.trace["window_s"]
            breakdown = {"device_ops": rlog.trace["device_ops"],
                         "idle_gaps": rlog.trace["idle_gaps"]}
    metrics = {}
    for m in (c.per_layer if trace else c.end_to_end):
        v = spec.reader(m["name"])(run)
        if v is None:
            continue
        if not math.isfinite(v):
            raise ValueError(f"metric {m['name']} is {v}")
        metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    for name, v in compared.items():
        log(f"compared: {name} {v['value']} limit {v['limit']}")
    out = {"correct": bool(correct), "attempted": len(wreqs),
           "failed": failed, "metrics": metrics, "device": device_info}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if control is not None and ctl is not None:
        ctl_ok, ctl_nums = check.judge(ctl, c.check["limits"])
        out["control"] = {"correct": ctl_ok, **ctl_nums}
    out["check"] = compared
    return out

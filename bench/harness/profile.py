"""Device traces: capture one with the JAX profiler, cut it down to the
events the metrics need, and reduce those to busy time, kernel time and
the breakdown.

``extract`` keeps, from the ``.xplane.pb`` the profiler writes, the
operations of each device plane's op line and the driver's own host
spans; ``reduce`` works on that list alone, so a small recorded one can
check the arithmetic without a chip.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional

from harness.stats import union_length

WINDOW_SPAN = "bench.traced_window"
HOST_SPANS = ("driver.wait_arrival", "gateway.dispatch", "engine.step",
              "gateway.finalize", WINDOW_SPAN)
# the line of a TPU plane that holds one event per executed operation;
# "XLA Modules" and "Steps" hold whole programs and would double count
OP_LINE = "XLA Ops"


def short_name(name: str) -> str:
    """``%copy.107 = bf16[...] copy(...)`` -> ``%copy.107``: TPU op events
    carry the whole HLO instruction as their name."""
    return name.split(" = ", 1)[0]


def extract(trace_dir: str) -> List[dict]:
    """Events of the newest trace under ``trace_dir``: device operations
    (``kind: "op"``, with their plane) and the driver's host spans
    (``kind: "span"``).  Times in nanoseconds on the profiler's clock."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    out: List[dict] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = list(plane.lines)
            names = [ln.name for ln in lines]
            keep = [ln for ln in lines if ln.name == OP_LINE] \
                if OP_LINE in names else lines
            for ln in keep:
                for ev in ln.events:
                    out.append({"kind": "op", "plane": plane.name,
                                "name": short_name(ev.name),
                                "start": ev.start_ns,
                                "dur": ev.duration_ns})
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name in HOST_SPANS:
                        out.append({"kind": "span", "name": ev.name,
                                    "start": ev.start_ns,
                                    "dur": ev.duration_ns})
    return out


def reduce(events: Iterable[dict], kernels: Dict[str, str],
           top: int = 10) -> Optional[dict]:
    """Busy time, per-kernel time and the breakdown inside the traced
    window (the ``bench.traced_window`` span).

    ``kernels`` maps a metric's kernel key to a substring of its event
    names.  Returns None where the trace holds no window or no device
    operation.  Busy time is the union of the operations' intervals on
    each device plane, averaged over the planes."""
    events = list(events)
    win = [e for e in events if e["kind"] == "span"
           and e["name"] == WINDOW_SPAN]
    if not win:
        return None
    w0 = win[0]["start"]
    w1 = w0 + win[0]["dur"]
    ops = []
    for e in events:
        if e["kind"] != "op":
            continue
        s, t = max(e["start"], w0), min(e["start"] + e["dur"], w1)
        if t > s:
            ops.append((e["plane"], e["name"], s, t))
    if not ops:
        return None
    planes = sorted({p for p, _, _, _ in ops})
    busy = {p: union_length([(s, t) for q, _, s, t in ops if q == p])
            for p in planes}
    by_name: Dict[str, float] = defaultdict(float)
    for p in planes:
        for name, ns in _self_times([o for o in ops if o[0] == p]):
            by_name[name] += ns
    kernel_ns = {k: 0.0 for k in kernels}
    for _, name, s, t in ops:
        for k, sub in kernels.items():
            if sub in name:
                kernel_ns[k] += t - s
    n = len(planes)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy.values()) / n * 1e-9,
        "kernel_s": {k: v / n * 1e-9 for k, v in kernel_ns.items()},
        "planes": planes,
        "device_ops": [[name, ns / n * 1e-9] for name, ns in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": _idle_gaps(ops, planes[0], events, w0, w1, top),
    }


def _self_times(ops) -> List[tuple]:
    """(name, self time) of each operation of one plane: its duration
    less the operations nested in it (a loop's body ops inside the loop
    op), so the breakdown counts every nanosecond once."""
    out, stack = [], []     # stack: [name, end, child ns, start]
    for _, name, s, t in sorted(ops, key=lambda o: (o[2], -o[3])):
        while stack and stack[-1][1] <= s:
            n, e, child, st = stack.pop()
            out.append((n, e - st - child))
        if stack:
            stack[-1][2] += t - s
        stack.append([name, t, 0.0, s])
    while stack:
        n, e, child, st = stack.pop()
        out.append((n, e - st - child))
    return out


def _idle_gaps(ops, plane, events, w0, w1, top) -> List[list]:
    """The longest idle stretches of one device plane, each named by the
    host span it lies in (by its midpoint)."""
    spans = sorted((e["start"], e["start"] + e["dur"], e["name"])
                   for e in events if e["kind"] == "span"
                   and e["name"] != WINDOW_SPAN)
    ivs = sorted((s, t) for p, _, s, t in ops if p == plane)
    gaps, cur = [], w0
    for s, t in ivs:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, t)
    if w1 > cur:
        gaps.append((cur, w1))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, t in gaps[:top]:
        mid = (s + t) / 2
        label = "host.other"
        for a, b, name in spans:
            if a <= mid <= b:
                label = name
        out.append([label, (t - s) * 1e-9])
    return out

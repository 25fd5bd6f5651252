"""The program's own spans, device scopes and counters in a traced run.

With a ``FlightRecorder`` built on the run's clock attached to the
gateway and the engine, the paged runtime records the phases of each step
(``step.plan``, ``step.pack``, ``step.put``, ``step.compile``,
``step.device``, ``step.fetch``, ``step.commit``) as spans on that clock
and, through ``jax.profiler.TraceAnnotation``, on the profiler's host
plane; its counters count steps, rows and backend compiles; and every
operation of the fused step carries one of the named scopes in ``SCOPES``
in its ``op_name``.

``extract`` reads a profiler trace as ``profile.extract`` does, and also
keeps the ``step.*`` spans and each device operation's scope (looked up
in the compiled modules' HLO text), so ``profile.reduce`` names each
idle gap by the innermost span it falls in;
``scope_times`` gives the device's self time per scope.  ``program_log``
copies what the metric readers need out of the recorder before the
engine is freed.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional

from harness import driver, profile

# the fused step's named scopes (serving/paged_runtime.py)
SCOPES = ("embed", "attn_in", "kv_scatter", "kv_pool", "attn_kernel",
          "attn_out", "ffn", "logits", "layer_weights")
UNSCOPED = "unscoped"
STEP_SPANS = ("step.plan", "step.pack", "step.put", "step.compile",
              "step.device", "step.fetch", "step.commit")
# the host part of a step: everything but the compile and the device call
HOST_STEP_SPANS = ("step.plan", "step.pack", "step.put", "step.fetch",
                   "step.commit")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


@dataclass
class ProgramLog(driver.RunLog):
    """A ``RunLog`` that also holds what the program recorded (see
    ``program_log``); None where nothing was attached."""
    program: Optional[dict] = None


def scope_of(op_name: str) -> str:
    """The innermost named scope in an ``op_name`` path
    (``jit(_mixed_impl)/layer_weights/while/body/closed_call/kv_pool/
    dynamic_slice`` -> ``kv_pool``), else ``unscoped``."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def instr_key(text: str) -> Optional[tuple]:
    """``(name, result type, opcode)`` of one HLO instruction, as the
    compiled module's text prints it (``%copy.9 = bf16[8]{0} copy(%p),
    metadata=...``) and as a TPU op event's name does (the same with the
    operands' types, no metadata, cut short when long).  None where
    ``text`` is no instruction."""
    m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (.*)", text)
    if m is None:
        return None
    name, rest = m.groups()
    if rest.startswith("("):                  # a tuple type
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        typ, rest = rest[:i + 1], rest[i + 1:]
    else:
        typ, _, rest = rest.partition(" ")
    return name, typ, rest.strip().split("(", 1)[0]


def hlo_scopes(hlo_texts: Iterable[str]) -> Dict[tuple, str]:
    """``instr_key`` -> scope over the given compiled HLO modules (one
    per bucket of the fused step); a key whose scope differs between
    modules maps to ``ambiguous``."""
    out: Dict[tuple, str] = {}
    for text in hlo_texts:
        for line in text.splitlines():
            m = _OP_NAME.search(line)
            key = instr_key(line) if m else None
            if key is None:
                continue
            scope = scope_of(m.group(1))
            out[key] = scope if out.get(key, scope) == scope \
                else "ambiguous"
    return out


def extract(trace_dir: str, hlo_texts: Iterable[str]) -> List[dict]:
    """Events of the newest trace under ``trace_dir``, as
    ``profile.extract`` gives them, with the program's ``step.*`` spans
    added and each operation's ``scope``.  A TPU op event carries its
    HLO instruction but not its ``op_name``, so the scope is looked up
    by ``instr_key`` in the compiled modules ``hlo_texts``; an operation
    of no such module (the argmax's, for one) is ``unscoped``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    by_key = hlo_scopes(hlo_texts)
    spans = set(profile.HOST_SPANS) | set(STEP_SPANS)
    scopes: Dict[str, str] = {}
    out: List[dict] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = list(plane.lines)
            names = [ln.name for ln in lines]
            keep = [ln for ln in lines if ln.name == profile.OP_LINE] \
                if profile.OP_LINE in names else lines
            for ln in keep:
                for ev in ln.events:
                    if ev.name not in scopes:
                        scopes[ev.name] = by_key.get(instr_key(ev.name),
                                                     UNSCOPED)
                    out.append({"kind": "op", "plane": plane.name,
                                "name": profile.short_name(ev.name),
                                "scope": scopes[ev.name],
                                "start": ev.start_ns, "dur": ev.duration_ns})
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name in spans:
                        out.append({"kind": "span", "name": ev.name,
                                    "start": ev.start_ns,
                                    "dur": ev.duration_ns})
    return out


def scope_times(events: Iterable[dict], by_op: bool = False
                ) -> Optional[Dict[str, float]]:
    """Self time (seconds) per scope inside the traced window, averaged
    over the device planes as ``profile.reduce`` averages busy time: each
    operation's duration less the operations nested in it, so the scopes
    sum to the busy time.  ``by_op`` keys it by ``scope:op`` instead.
    None without a window or an operation."""
    events = list(events)
    win = [e for e in events if e["kind"] == "span"
           and e["name"] == profile.WINDOW_SPAN]
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
            scope = e.get("scope", UNSCOPED)
            ops.append((e["plane"], f"{scope}:{e['name']}" if by_op
                        else scope, s, t))
    if not ops:
        return None
    planes = sorted({p for p, _, _, _ in ops})
    ns: Dict[str, float] = defaultdict(float)
    for p in planes:
        for scope, self_ns in profile._self_times(
                [o for o in ops if o[0] == p]):
            ns[scope] += self_ns
    return {k: v / len(planes) * 1e-9 for k, v in sorted(ns.items())}


def program_log(rec, log: driver.RunLog, counters_open: Dict[str, int],
                counters_close: Dict[str, int]) -> dict:
    """What the readers need from the recorder ``rec``: the ``step.*``
    spans as ``(name, start, dur)`` on the run's clock, the counters'
    growth over the window, and each window request's segment sums up to
    its first token (the segments so far where it has none)."""
    segs: Dict[int, Dict[str, float]] = {}
    done = {s.req_id: s for dq in rec.summaries.values() for s in dq}
    for r in driver.window_reqs(log):
        req = r.req
        if req.req_id in done:
            segs[req.req_id] = done[req.req_id].ttft_segs
            continue
        tl = rec.timeline_of(req)
        if tl is not None:
            segs[req.req_id] = tl.seg_sums(until=tl.first_token_t)
    names = sorted(set(counters_open) | set(counters_close))
    return {
        "spans": [(ev.name, ev.ts, ev.dur) for ev in rec.spans("step.")],
        "counters": {k: counters_close.get(k, 0) - counters_open.get(k, 0)
                     for k in names},
        "ttft_segs": segs,
    }


def step_spans(log: driver.RunLog, step: driver.StepRec) -> List[tuple]:
    """The ``(name, start, dur)`` spans the program recorded inside one
    of the driver's steps."""
    return [s for s in log.program["spans"]
            if step.t0 <= s[1] and s[1] + s[2] <= step.t1]

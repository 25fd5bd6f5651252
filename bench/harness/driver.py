"""The wall-clock driver: one Python loop over the served request path.

Each turn of the loop offers every request whose scheduled time has
passed (``Gateway.offer``), dispatches the door queue through the
``CacheAwareRouter`` into the paged engine (``Gateway.dispatch``), runs
one engine step if there is work (``ServingEngine.step``: scheduler plan,
fused step, Pallas kernel) and stamps its tokens with the step's end
time (``Gateway.finalize``); with no work it sleeps until the next
arrival.  Time is ``time.perf_counter`` relative to the start of the
warm-up; the window is ``[w0, w0 + window_s)`` on that clock, and opens
once the warm-up has reached a steady state (``warm_up``).

Arrivals are open loop: every request's ``arrival`` is its scheduled
time, so time to first token counts any time the loop was busy when the
request fell due.
"""
from __future__ import annotations

import contextlib
import math
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

from harness import traffic

TENANT = "bench"


@dataclass
class StepRec:
    """One engine step as the driver saw it."""
    t0: float                   # on the run's clock
    t1: float
    compute_s: float            # the program's own device-step time
    compile_s: float            # fused-step compiles inside this step
    compiles: int
    prefill_tokens: int
    preempted: int
    pages: int                  # pages held by live requests after it
    dec_ctx: List[int] = field(default_factory=list)   # ctx per decode row
    pre: List[tuple] = field(default_factory=list)     # (start, len) chunks


@dataclass
class ReqRec:
    """One request: the program's ``Request`` and where it came from."""
    req: object
    phase: int                  # traffic.WARM / WINDOW / AFTER


@dataclass
class RunLog:
    window_s: float
    w0: Optional[float] = None         # the window opens; None in warm-up
    steps: List[StepRec] = field(default_factory=list)
    reqs: List[ReqRec] = field(default_factory=list)
    late_s: List[float] = field(default_factory=list)   # offer lateness
    compiled: List[tuple] = field(default_factory=list)  # (time, bucket)
    end_s: float = 0.0                 # when the run stopped looking
    trace: Optional[dict] = None       # reduced device trace, traced runs
    traced: Optional[tuple] = None     # (t0, t1) of the traced window


class Driver:
    """Drives one engine behind one gateway with a mix's traffic."""

    def __init__(self, gateway, engine, mix: dict, vocab: int, seed: int,
                 log: RunLog, annotate: bool = False):
        self.gw, self.eng, self.mix, self.log = gateway, engine, mix, log
        self.t_origin = 0.0
        self.next_id = 0
        self.streams = {p: traffic.stream(mix, vocab, seed, p)
                        for p in (traffic.WARM, traffic.WINDOW,
                                  traffic.AFTER)}
        self.pending = None              # (time, phase, spec)
        self.sched_t = 0.0
        self.burst = mix["warm"]["burst"]
        self.annotate = annotate
        self.runtime = engine.runtime

    # ------------------------------------------------------------- helpers
    def now(self) -> float:
        return time.perf_counter() - self.t_origin

    def span(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def phase_at(self, t: float) -> int:
        if self.log.w0 is None or t < self.log.w0:
            return traffic.WARM
        return (traffic.WINDOW if t < self.log.w0 + self.log.window_s
                else traffic.AFTER)

    def _new_request(self, spec, arrival: float, phase: int):
        from repro.serving.request import Request
        req = Request(req_id=self.next_id, tenant=TENANT,
                      prompt_len=len(spec.prompt),
                      max_new_tokens=spec.max_new, arrival=arrival,
                      prompt_tokens=spec.prompt)
        self.next_id += 1
        self.log.reqs.append(ReqRec(req, phase))
        return req

    def _offer_due(self, now: float, offer_until: float) -> None:
        while True:
            if self.pending is None:
                phase = self.phase_at(self.sched_t)
                spec = next(self.streams[phase])
                self.pending = (self.sched_t, phase, spec)
                # the warm-up opens with a burst of requests at once, so
                # the engine holds about as many live requests as in a
                # steady state from its first steps on
                self.burst = max(0, self.burst - 1)
                if self.burst == 0:
                    self.sched_t += spec.gap_s
            t, phase, spec = self.pending
            if t > now or t >= offer_until:
                return
            self.pending = None
            self.log.late_s.append(now - t)
            self.gw.offer(self._new_request(spec, t, phase), now)

    def _record(self, rep, t0: float, t1: float, n_exec: int,
                compile_s: float) -> None:
        rt = self.runtime
        for key in list(rt.compile_s)[n_exec:]:
            self.log.compiled.append((t1, key))
        dec_ctx = [r.prompt_len + r.generated - 1 for r in rep.decoded]
        self.log.steps.append(StepRec(
            t0=t0, t1=t1, compute_s=rep.compute_s, compile_s=compile_s,
            compiles=len(rt.compile_s) - n_exec,
            prefill_tokens=rep.prefill_tokens, preempted=len(rep.preempted), pages=self.eng.kv.reserved_pages,
            dec_ctx=dec_ctx,
            pre=[(start, n) for _, start, n, _ in rep.chunks]))

    # ---------------------------------------------------------------- loop
    def run(self, until: float, offer_until: float,
            stop: Optional[Callable[[], bool]] = None) -> None:
        """Turn the loop until ``until`` (seconds on the run's clock), or
        until ``stop()`` says so; offer arrivals only before
        ``offer_until``."""
        rt = self.runtime
        while True:
            now = self.now()
            if now >= until or (stop is not None and stop()):
                self.log.end_s = now
                return
            with self.span("gateway.dispatch"):
                self._offer_due(now, offer_until)
                self.gw.dispatch(now)
            if self.eng.has_work():
                n_exec, c_before = len(rt.compile_s), sum(rt.compile_s.values())
                t0 = self.now()
                with self.span("engine.step"):
                    rep = self.eng.step()
                t1 = self.now()
                with self.span("gateway.finalize"):
                    self.gw.finalize(TENANT, self.eng, rep, t1, t0)
                if rep.kind != "idle":
                    self._record(rep, t0, t1, n_exec,
                                 sum(rt.compile_s.values()) - c_before)
                continue
            nxt = self.pending[0] if self.pending is not None else until
            wake = min(until, nxt)
            if stop is not None:
                wake = min(wake, now + 0.01)
            with self.span("driver.wait_arrival"):
                time.sleep(max(0.0, wake - self.now()))

    def warm_up(self) -> None:
        """Run the warm-up traffic for at least ``min_s`` seconds, then on
        until ``quiet_s`` seconds pass in which no new fused-step
        executable was compiled or loaded (at most ``max_s`` in all):
        every shape the traffic reaches in a steady state is then
        compiled before the window opens."""
        w = self.mix["warm"]

        def quiet() -> bool:
            t = self.now()
            last = self.log.compiled[-1][0] if self.log.compiled else 0.0
            return t >= w["min_s"] and t - last >= w["quiet_s"]

        self.run(until=w["max_s"], offer_until=w["max_s"], stop=quiet)

    def open_window(self) -> None:
        """The window opens now; its arrivals start now, from the seed's
        window stream, whatever the warm-up had scheduled next."""
        self.log.w0 = self.sched_t = self.now()
        self.pending = None


def window_reqs(log: RunLog) -> List[ReqRec]:
    return [r for r in log.reqs if r.phase == traffic.WINDOW]


def emissions(req) -> List[float]:
    """Times the request's tokens were emitted, first token included."""
    if req.prefill_done < 0:
        return []
    return [req.prefill_done] + list(req.decode_times)


def window_ttft(log: RunLog) -> List[float]:
    """Time to first token of each of the window's requests, from its
    scheduled arrival; ``inf`` for one that never got its first token."""
    return [r.req.prefill_done - r.req.arrival if r.req.prefill_done >= 0
            else math.inf for r in window_reqs(log)]


def window_gaps(log: RunLog) -> List[float]:
    """Gaps between consecutive tokens of a request, for every gap whose
    later token was emitted inside the window, over all requests."""
    out = []
    for r in log.reqs:
        ts = emissions(r.req)
        out += [b - a for a, b in zip(ts, ts[1:]) if in_window(log, b)]
    return out


def in_window(log: RunLog, t: float) -> bool:
    return log.w0 is not None and log.w0 <= t < log.w0 + log.window_s


def window_steps(log: RunLog) -> List[StepRec]:
    return [s for s in log.steps
            if in_window(log, s.t0) and s.t1 <= log.w0 + log.window_s]


def traced_steps(log: RunLog) -> List[StepRec]:
    if log.traced is None:
        return []
    t0, t1 = log.traced
    return [s for s in log.steps if t0 <= s.t0 and s.t1 <= t1]


"""Spans, counters and device scopes inside the served step: the tracer's
own clock and nesting, the paged runtime's ``step.*`` spans on a wall
clock, the compile counter, the named scopes in the compiled fused step,
and the zero-cost path with no tracer attached."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.core import obs
from repro.core.obs import Tracer
from repro.serving.engine import ServingEngine
from repro.serving.request import Request
from repro.serving.trace import FlightRecorder

CFG = reduced(get_config("stablelm_3b")).replace(dtype="float32")
STEP_SPANS = ["step.plan", "step.pack", "step.put", "step.compile",
              "step.device", "step.fetch", "step.commit"]
SCOPES = ["embed", "attn_in", "kv_scatter", "kv_pool", "attn_kernel",
          "attn_out", "ffn", "logits", "layer_weights"]


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def _engine():
    return ServingEngine(CFG, max_slots=4, seq_cap=64, page_size=4,
                         seed=0, backend="paged", chunk_tokens=8,
                         attn_impl="ref")


def _requests(n=3, prompt_len=12, max_new=4):
    rng = np.random.default_rng(5)
    return [Request(req_id=j, tenant="T1", prompt_len=prompt_len,
                    max_new_tokens=max_new, arrival=0.0,
                    prompt_tokens=rng.integers(0, CFG.vocab_size, prompt_len))
            for j in range(n)]


def _drive(eng, reqs, clock):
    """Wall-clock harness: each step is stamped with ``clock`` before and
    after, as a real driver stamps it."""
    for r in reqs:
        r.arrival = clock()
        assert eng.submit(r)
    steps = []
    while eng.has_work():
        t0 = clock()
        rep = eng.step()
        t1 = clock()
        eng.finalize_step(rep, t1, t0)
        steps.append((t0, t1, rep))
        assert len(steps) < 200
    return steps


def test_scope_nests_on_the_injected_clock():
    clock = FakeClock()
    tr = Tracer(clock=clock)
    with tr.scope("outer", k=1) as outer:
        with tr.scope("inner") as inner:
            tr.count("rows", 3)
        with tr.scope("inner2") as inner2:
            pass
    tr.count("rows")
    tr.count("steps")
    assert [e.name for e in tr.spans()] == ["outer", "inner", "inner2"]
    assert outer.parent is None
    assert inner.parent == outer.id and inner2.parent == outer.id
    assert len({outer.id, inner.id, inner2.id}) == 3
    # stamps come from the clock alone: outer 1..6, inner 2..3, inner2 4..5
    assert (outer.ts, outer.dur) == (1.0, 5.0)
    assert (inner.ts, inner.dur) == (2.0, 1.0)
    assert (inner2.ts, inner2.dur) == (4.0, 1.0)
    assert outer.args == {"k": 1} and outer.ph == "X"
    assert tr.counters == {"rows": 4, "steps": 1}
    assert [e.name for e in tr.spans("inner")] == ["inner", "inner2"]


def test_scope_closes_on_error_and_needs_a_clock():
    tr = Tracer(clock=FakeClock())
    with pytest.raises(RuntimeError):
        with tr.scope("boom"):
            raise RuntimeError("x")
    with tr.scope("after") as ev:
        pass
    assert tr.events[0].dur == 1.0 and ev.parent is None
    with pytest.raises(ValueError):
        with Tracer().scope("no clock"):
            pass


def test_scope_enters_a_profiler_annotation(monkeypatch):
    entered = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    tr = Tracer(clock=FakeClock())
    with tr.scope("step.device"):
        pass
    assert entered == ["step.device"]


def test_compile_counter_counts_only_while_attached():
    tr = Tracer()
    tr.watch_compiles()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7))           # a fresh compile
    n = tr.counters.get("compiles", 0)
    assert n >= 1
    tr.watch_compiles()                                    # nested attach
    tr.unwatch_compiles()
    jax.jit(lambda x: x * 5 - 2)(jnp.arange(9))
    assert tr.counters["compiles"] > n
    tr.unwatch_compiles()
    n = tr.counters["compiles"]
    jax.jit(lambda x: x * 7 + 4)(jnp.arange(11))
    assert tr.counters["compiles"] == n
    assert tr not in obs._compile_watchers


def test_step_spans_tile_each_step_on_a_wall_clock():
    t_origin = time.perf_counter()
    clock = lambda: time.perf_counter() - t_origin       # noqa: E731
    rec = FlightRecorder(clock=clock)
    eng = _engine()
    eng.tracer = rec
    assert eng.runtime.tracer is rec
    steps = _drive(eng, _requests(), clock)
    eng.tracer = None
    spans = rec.spans("step.")
    assert all(s.parent is None for s in spans)
    # group the spans by the driver step they fall in
    i = 0
    compiled = list(eng.runtime.compile_s.values())
    n_compiles = 0
    for t0, t1, rep in steps:
        mine = []
        while i < len(spans) and spans[i].ts < t1:
            mine.append(spans[i])
            i += 1
        names = [s.name for s in mine]
        if rep.kind == "idle":
            assert names == ["step.plan"]
            continue
        want = [n for n in STEP_SPANS
                if n != "step.compile" or "step.compile" in names]
        assert names == want
        # ordered, inside the step, and none overlaps the next
        assert mine[0].ts >= t0 and mine[-1].ts + mine[-1].dur <= t1
        for a, b in zip(mine, mine[1:]):
            assert a.ts + a.dur <= b.ts
        dev = mine[names.index("step.device")]
        assert rep.compute_s == dev.dur
        if "step.compile" in names:
            comp = mine[names.index("step.compile")]
            assert comp.dur == compiled[n_compiles]
            assert set(comp.args) == {"rows", "width", "logits"}
            n_compiles += 1
    assert i == len(spans)
    assert n_compiles == len(compiled) >= 1
    busy = [rep for _, _, rep in steps if rep.kind != "idle"]
    assert rec.counters["steps"] == len(busy)
    assert rec.counters["rows"] == sum(r.tokens for r in busy)
    assert rec.counters["rows_padded"] >= rec.counters["rows"]
    # the fused step's buckets, and the argmax's, compiled while attached
    assert rec.counters["compiles"] >= n_compiles
    # request segments share the clock with the spans and still conserve
    rec.check()
    assert rec.finished == 3
    for s in rec.summaries["T1"]:
        assert s.ttft_segs["prefill_chunk"] > 0


def test_no_tracer_records_nothing(monkeypatch):
    """With no tracer attached the runtime opens no scope, counts nothing
    and enters no profiler annotation; with a tracer that has no clock
    (a virtual-clock harness) it opens no scope either."""
    def forbidden(*a, **k):
        raise AssertionError("recorded with no tracer attached")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", forbidden)
    monkeypatch.setattr(Tracer, "scope", forbidden)
    monkeypatch.setattr(Tracer, "count", forbidden)
    eng = _engine()
    reqs = _requests()
    steps = _drive(eng, reqs, FakeClock())
    assert all(r.done for r in reqs)
    assert all(rep.compute_s > 0 for _, _, rep in steps
               if rep.kind != "idle")
    assert eng.runtime.compile_s
    assert not obs._compile_watchers

    monkeypatch.undo()
    rec = FlightRecorder()                       # virtual clock: no spans
    eng = _engine()
    eng.tracer = rec
    _drive(eng, _requests(), FakeClock())
    eng.tracer = None
    assert rec.spans() == []
    assert rec.counters["steps"] >= 1


def test_fused_step_hlo_carries_every_scope_name():
    rt = _engine().runtime
    t, w, n = 16, 4, 4
    compiled = rt._mixed_fn.lower(
        rt.params, rt.pools, jnp.zeros(t, jnp.int32),
        jnp.zeros(t, jnp.int32), jnp.int32(3), jnp.zeros((t, w), jnp.int32),
        jnp.zeros(n, jnp.int32)).compile()
    text = compiled.as_text()
    for name in SCOPES:
        assert f"/{name}/" in text, name

"""The program's spans and scopes in a traced run: the scope of an
``op_name``, self time per scope and idle gaps named by the innermost
span on a synthetic trace, the readers of the program's spans on
synthetic logs, and one traced run of the chat cell at a small size on
the CPU with a recorder attached."""
import sys
import time
from pathlib import Path

import jax
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "tests")]

from harness import driver, profile, spans, spec  # noqa: E402
from test_bench_check import small_cell  # noqa: E402

KERNELS = {"paged_attention": "paged_attention_mixed"}
MS = 1_000_000


@pytest.mark.parametrize("op_name,scope", [
    ("jit(_mixed_impl)/layer_weights/while/body/closed_call/kv_pool/"
     "dynamic_slice", "kv_pool"),
    ("jit(_mixed_impl)/layer_weights/while/body/closed_call/attn_kernel/"
     "jit(paged_attention_mixed)/broadcast_in_dim", "attn_kernel"),
    ("jit(_mixed_impl)/layer_weights/while/body/dynamic_slice",
     "layer_weights"),
    ("jit(_mixed_impl)/logits/dot_general", "logits"),
    ("jit(argmax)/argmax", "unscoped"),
    ("", "unscoped"),
])
def test_scope_of_is_the_innermost_named_scope(op_name, scope):
    assert spans.scope_of(op_name) == scope


# a TPU op event's name: the instruction with its operands' types, no
# metadata, cut short when long
EVENT_COPY = ("%copy.9 = bf16[1,641,32,16,80]{4,2,3,1,0:T(8,128)(2,1)} "
              "copy(bf16[1,641,32,16,80]{4,3,2,1,0:T(8,128)(2,1)} %param.4)")
EVENT_TUPLE = ("%convert_bitcast_fusion.2 = (f32[1,96,1]{1,2,0:T(1,128)S(1)}"
               ", s32[96]{0:T(128)}) fusion(s32[96]{0:T(128)} %positions.1,"
               " s32[]{:T(128)S(6)} %cop")
# the compiled modules' text of two buckets
HLO_A = """  %copy.9 = bf16[1,641,32,16,80]{4,2,3,1,0:T(8,128)(2,1)} copy(%param.4), metadata={op_name="jit(_mixed_impl)/layer_weights/while/body/closed_call/kv_pool/dynamic_slice" stack_frame_id=4}
  ROOT %convert_bitcast_fusion.2 = (f32[1,96,1]{1,2,0:T(1,128)S(1)}, s32[96]{0:T(128)}) fusion(%positions.1, %copy.1), kind=kLoop, calls=%fc.2, metadata={op_name="jit(_mixed_impl)/attn_in/convert_element_type"}
  %copy.10 = s32[96]{0} copy(%p.1)
  %fusion.3 = bf16[96,2560]{1,0} fusion(%a, %b), metadata={op_name="jit(_mixed_impl)/ffn/add"}"""
HLO_B = """  %copy.9 = bf16[32,80]{1,0} copy(%param.4), metadata={op_name="jit(_mixed_impl)/layer_weights/while/body/closed_call/attn_in/dot_general"}
  %fusion.3 = bf16[96,2560]{1,0} fusion(%a, %b), metadata={op_name="jit(_mixed_impl)/attn_out/add"}"""


def test_instr_key_reads_the_text_and_the_event_alike():
    pool = "bf16[1,641,32,16,80]{4,2,3,1,0:T(8,128)(2,1)}"
    assert spans.instr_key(EVENT_COPY) == ("%copy.9", pool, "copy")
    assert spans.instr_key(HLO_A.splitlines()[0]) == ("%copy.9", pool,
                                                      "copy")
    tup = "(f32[1,96,1]{1,2,0:T(1,128)S(1)}, s32[96]{0:T(128)})"
    assert spans.instr_key(EVENT_TUPLE) == ("%convert_bitcast_fusion.2",
                                            tup, "fusion")
    assert spans.instr_key(HLO_A.splitlines()[1])[1] == tup
    assert spans.instr_key("HloModule jit_f") is None


def test_hlo_scopes_by_instruction_key_across_buckets():
    by_key = spans.hlo_scopes([HLO_A, HLO_B])
    # one name, two buckets, two shapes: each keeps its own scope
    assert by_key[spans.instr_key(EVENT_COPY)] == "kv_pool"
    assert by_key[("%copy.9", "bf16[32,80]{1,0}", "copy")] == "attn_in"
    assert by_key[spans.instr_key(EVENT_TUPLE)] == "attn_in"
    # one name and shape with two scopes; an op with no op_name is left out
    assert by_key[("%fusion.3", "bf16[96,2560]{1,0}", "fusion")] == \
        "ambiguous"
    assert ("%copy.10", "s32[96]{0}", "copy") not in by_key


def _scoped_trace():
    """Two 10 ms steps in a 24 ms window: a loop op holding scoped ops,
    and the program's step spans inside the driver's ``engine.step``."""
    ops = [("while.1", "layer_weights", 0, 8), ("copy.2", "kv_pool", 1, 3),
           ("paged_attention_mixed.3", "attn_kernel", 3, 6),
           ("fusion.4", "ffn", 6, 7), ("fusion.5", "logits", 8, 9),
           ("fusion.6", "unscoped", 12, 13), ("copy.2", "kv_pool", 13, 16)]
    ev = [{"kind": "op", "plane": "/device:TPU:0", "name": n, "scope": sc,
           "start": s * MS, "dur": (t - s) * MS} for n, sc, s, t in ops]
    sp = [(profile.WINDOW_SPAN, 0, 24), ("engine.step", 0, 11),
          ("step.plan", 0, 0.5), ("step.device", 0.5, 9.5),
          ("step.fetch", 9.5, 10.75), ("engine.step", 11, 19.5),
          ("step.device", 11, 16), ("step.commit", 17, 19),
          ("driver.wait_arrival", 19.5, 24)]
    ev += [{"kind": "span", "name": n, "start": s * MS, "dur": (t - s) * MS}
           for n, s, t in sp]
    return ev


def test_scope_times_sum_to_busy_and_gaps_take_the_innermost_span():
    ev = _scoped_trace()
    s = spans.scope_times(ev)
    assert s == pytest.approx({"layer_weights": 0.002, "kv_pool": 0.005,
                               "attn_kernel": 0.003, "ffn": 0.001,
                               "logits": 0.001, "unscoped": 0.001})
    r = profile.reduce(ev, KERNELS)
    assert sum(s.values()) == pytest.approx(r["busy_s"])
    by_op = spans.scope_times(ev, by_op=True)
    assert by_op["kv_pool:copy.2"] == pytest.approx(0.005)
    assert by_op["layer_weights:while.1"] == pytest.approx(0.002)
    # idle: [16,24] (midpoint 20, in wait_arrival) and [9,12] (midpoint
    # 10.5, in step.fetch inside engine.step: the innermost names it)
    assert [(n, round(d * 1e3, 6)) for n, d in r["idle_gaps"]] == [
        ("driver.wait_arrival", 8.0), ("step.fetch", 3.0)]
    ev = [e for e in ev if e["name"] != profile.WINDOW_SPAN]
    assert spans.scope_times(ev) is None


def _run(program=None, trace=None, steps=()):
    log = spans.ProgramLog(window_s=10.0, w0=0.0, program=program,
                           trace=trace)
    log.steps = list(steps)
    return type("Run", (), {"log": log})()


def _step(t0, t1):
    return driver.StepRec(t0=t0, t1=t1, compute_s=0.0, compile_s=0.0,
                          compiles=0, prefill_tokens=0, preempted=0,
                          pages=0)


def test_readers_of_the_program_spans():
    r = spec.reader
    segs = {i: {"door_queued": 0.001 * i, "sched_queued": 0.002 * i,
                "prefill_chunk": 0.1} for i in range(11)}
    st = [("step.plan", 1.0, 0.001), ("step.pack", 1.001, 0.002),
          ("step.device", 1.003, 0.1), ("step.fetch", 1.103, 0.001),
          ("step.plan", 2.0, 0.003), ("step.compile", 2.003, 5.0),
          ("step.device", 7.003, 0.1), ("step.commit", 7.103, 0.001)]
    run = _run(program={"spans": st, "ttft_segs": segs, "counters": {}},
               trace={"busy_s": 2.0, "scope_s": {"kv_pool": 0.3,
                                                 "kv_scatter": 0.1,
                                                 "ffn": 1.6}},
               steps=[_step(1.0, 1.2), _step(2.0, 7.2)])
    assert r("door_wait_p90_ms")(run) == pytest.approx(9.0)
    assert r("sched_wait_p90_ms")(run) == pytest.approx(18.0)
    assert r("kv_pool_busy_pct")(run) == pytest.approx(20.0)
    # (1 + 2 + 1) ms and (3 + 1) ms of host spans; compile and device not
    assert r("step_host_span_ms")(run) == pytest.approx(4.0)


def test_readers_return_nothing_without_a_recorder():
    log = driver.RunLog(window_s=10.0, w0=0.0,
                        trace={"busy_s": 1.0, "window_s": 10.0})
    run = type("Run", (), {"log": log})()
    for name in ("door_wait_p90_ms", "sched_wait_p90_ms",
                 "kv_pool_busy_pct", "step_host_span_ms"):
        assert spec.reader(name)(run) is None
    assert spec.reader("kv_pool_busy_pct")(_run(trace=None)) is None


def test_traced_run_on_the_cpu():
    """The chat cell at a small size, traced with a recorder attached:
    every step's device span is its ``compute_s``, the program's readers
    read, and no device plane means no device reading."""
    ts = spec.load_file(BENCH / "trace_step.py", "bench_trace_step")
    c = small_cell()
    out = ts.traced_run(c, 2**33 + 7, 2.0, time.perf_counter(),
                        device=jax.devices("cpu")[0])
    assert out["traced_steps"] > 0
    assert out["device_span_is_compute_s"] == out["traced_steps"]
    m = out["metrics"]
    for name in ("door_wait_p90_ms", "sched_wait_p90_ms",
                 "step_host_span_ms", "itl_mean_ms"):
        assert m[name] is not None and m[name] >= 0, name
    assert m["kv_pool_busy_pct"] is None and out["busy_s"] is None
    assert out["window_counters"]["steps"] >= out["window_steps"]
    assert out["window_counters"]["rows_padded"] >= \
        out["window_counters"]["rows"]

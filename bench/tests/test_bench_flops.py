"""FLOP and byte counts of the roofline and MFU metrics against
hand-worked shapes, and the two readers that divide by them."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import cell, driver, model, spec  # noqa: E402

MOD = model.load({"model": "dense_prenorm"})
# d 8, 2 heads of 4 (MHA), ff 16, 3 layers, vocab 10
D = MOD.Dims("tiny", d_model=8, layers=3, heads=2, kv_heads=2, head_dim=4,
             d_ff=16, vocab=10, rope_theta=1e4, norm_eps=1e-5,
             dtype="bfloat16")
PEAKS = {"bf16_flops_per_s": 1000.0, "hbm_bytes_per_s": 100.0}


def test_counts_by_hand():
    # q, o: 8*2*4 each; k, v: 8*2*4 each; ffn: 3*8*16
    assert D.layer_matmul_params == 64 + 64 + 64 + 64 + 384
    # 3 layers x (2 x 640 + QK^T and PV: 4 x 2 heads x 4 x ctx 5)
    assert MOD.row_flops(D, 5) == 3 * (1280 + 160)
    assert MOD.logit_flops(D) == 2 * 8 * 10
    assert MOD.attn_flops(D, 5) == 3 * 160
    # K and V of 5 tokens (2 x 5 x 2 heads x 4 x 2 bytes) + q and out
    # of 2 rows (2 x 2 x 2 heads x 4 x 2 bytes), 3 layers
    assert MOD.attn_bytes(D, 5, 2) == 3 * (160 + 64)
    assert MOD.attn_bytes(D, 5, 2, kv_bytes=1) == 3 * (80 + 64)


def _run(trace):
    log = driver.RunLog(window_s=10.0, w0=0.0)
    # one decode step (rows at ctx 5 and 9) and one with a 3-row chunk
    # starting at 4 plus a decode row at ctx 2
    log.steps = [
        driver.StepRec(1.0, 1.1, 0.08, 0.0, 0, prefill_tokens=0,
                       preempted=0, pages=2, dec_ctx=[5, 9]),
        driver.StepRec(2.0, 2.2, 0.15, 0.0, 0, prefill_tokens=3,
                       preempted=0, pages=2, dec_ctx=[2], pre=[(4, 3)]),
    ]
    log.traced = (0.5, 2.5)
    log.trace = trace
    return cell.Run(cell="t", model=MOD, dims=D, log=log, peaks=PEAKS)


def test_roofline_reader_by_hand():
    run = _run({"window_s": 2.0, "busy_s": 1.0,
                "kernel_s": {"paged_attention": 10.0}})
    # step 1: flops 480 * (5 + 9) / 5 ... per row attn_flops(c) = 96 c
    f1 = 96 * (5 + 9)
    b1 = MOD.attn_bytes(D, 5, 1) + MOD.attn_bytes(D, 9, 1)
    f2 = 96 * 2 + 96 * (5 + 6 + 7)
    b2 = MOD.attn_bytes(D, 2, 1) + MOD.attn_bytes(D, 7, 3)
    least = max(f1 / 1000, b1 / 100) + max(f2 / 1000, b2 / 100)
    got = spec.reader("paged_attn_roofline")(run)
    assert got == pytest.approx(100 * least / 10.0)


def test_mfu_reader_by_hand():
    run = _run({"window_s": 2.0, "busy_s": 1.0,
                "kernel_s": {"paged_attention": 1.0}})
    rows = [5, 9, 2, 5, 6, 7]
    flops = sum(MOD.row_flops(D, c) for c in rows) + 4 * 160
    got = spec.reader("step_mfu_pct")(run)
    assert got == pytest.approx(100 * flops / (2.0 * 1000))


def test_trace_readers_say_nothing_without_a_trace():
    run = _run(None)
    for m in ("paged_attn_roofline", "paged_attn_busy_pct",
              "step_mfu_pct", "device_idle_pct"):
        assert spec.reader(m)(run) is None
    run = _run({"window_s": 2.0, "busy_s": 1.0,
                "kernel_s": {"paged_attention": 0.0}})
    assert spec.reader("paged_attn_roofline")(run) is None
    assert spec.reader("paged_attn_busy_pct")(run) is None
    assert spec.reader("device_idle_pct")(run) == pytest.approx(50.0)

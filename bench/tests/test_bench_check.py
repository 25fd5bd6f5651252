"""The comparison that decides ``correct``, driven through a whole run
on the CPU at a small size: the chip check is skipped, everything else
(gateway, router, paged engine, fused step, the window, the drain, the
float32 reference) runs as on the chip.  A sound run is correct; a run
with the timed path broken underneath is not; nor is the int8 control,
judged by the cell's own limits."""
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import cell, check, spec  # noqa: E402

CELL = "stablelm_3b.chat"


def small_cell():
    """The chat cell at a size the CPU runs in seconds; its limits are
    the cell's own."""
    c = spec.cell(CELL)
    c.config = dict(c.config, hidden_size=320, intermediate_size=864,
                    num_hidden_layers=4, num_attention_heads=4,
                    num_key_value_heads=4, vocab_size=2048)
    c.config["serve"] = dict(c.config["serve"], slots=4, seq_cap=128,
                             pool_pages=48)
    c.mix = dict(c.mix, rate_per_s=3.0, drain_s=10.0,
                 warm=dict(burst=2, min_s=1.0, quiet_s=0.5, max_s=20.0),
                 prompt=dict(c.mix["prompt"], median=24, max=48),
                 output=dict(c.mix["output"], median=12, max=24))
    c.end_to_end, c.per_layer = [], []
    return c


def _run(fault=None):
    return cell.run_cell(small_cell(), 2**33 + 5, 3.0, False,
                         time.perf_counter(), device=jax.devices("cpu")[0],
                         fault=fault)


def _alter_tokens(eng):
    """A token altered where it is produced: every decode step's newest
    token of each lane is replaced by its neighbour in the vocabulary."""
    step = eng.runtime.step
    vocab = eng.cfg.vocab_size

    def broken():
        rep = step()
        for r in {id(r): r for r in rep.decoded}.values():
            r.output_tokens[-1] = (r.output_tokens[-1] + 1) % vocab
        return rep

    eng.runtime.step = broken


def _state_unchanged(eng):
    """A step that returns its state unchanged: the K/V rows are never
    written into the page pools, so decode attends to empty pages."""
    eng.runtime._scatter = lambda pool, k, v, page_ids, offs: pool


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["check"]
    assert out["attempted"] > 0
    assert list(out)[-1] == "check"


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged])
def test_broken_timed_path_is_not_correct(fault):
    out = _run(fault)
    assert not out["correct"], out["check"]


def test_int8_control_is_not_correct():
    """The control at the cell's depth (32 layers) and a width the CPU
    holds: the reference with every 16-bit value rounded to int8, judged
    by the cell's own limits on the same served tokens, is not correct,
    while the bfloat16 program on those tokens is.  At the cell's own
    size the readings are in PERF.md."""
    c = small_cell()
    c.config = dict(c.config, num_hidden_layers=32)
    out = cell.run_cell(c, 12, 3.0, False, time.perf_counter(),
                        device=jax.devices("cpu")[0], control="int8")
    assert out["correct"], out["check"]
    assert out["control"]["correct"] is False, out["control"]


def test_sample_takes_the_longest_and_enough_tokens():
    class R:
        def __init__(self, i, n):
            self.req_id, self.prompt_len = i, 10
            self.output_tokens = [0] * n
            self.done = True
    reqs = [R(i, n) for i, n in enumerate([5, 50, 7, 9, 11, 13])]
    picked = check.sample(reqs, 3, 20, 10)
    assert picked[0].req_id == 1
    assert sum(len(r.output_tokens) for r in picked) >= 20
    assert picked == check.sample(reqs, 3, 20, 10)
    assert len(check.sample(reqs, 3, 10**6, 3)) == 3


def test_judge_needs_a_sample_and_every_limit():
    lim = {"widest_gap": 0.5, "mean_gap": 0.01}
    assert check.judge(None, lim)[0] is False
    assert check.judge(np.array([0.0, 0.4]), lim)[0] is False
    ok, nums = check.judge(np.array([0.0, 0.01]), lim)
    assert ok and nums["widest_gap"] == {"value": 0.01, "limit": 0.5}

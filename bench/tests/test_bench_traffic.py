"""The traffic generator: deterministic for a seed, lengths inside the
stated clips, and the same work for every seed."""
import itertools
import json
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import traffic  # noqa: E402

MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))
SEEDS = [0, 7, 2**40 + 17]


def _mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def _take(mix, seed, phase, n, vocab=1000):
    return list(itertools.islice(traffic.stream(mix, vocab, seed, phase), n))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = _mix(name)
    a, b = _take(mix, 123, traffic.WINDOW, 120), \
        _take(mix, 123, traffic.WINDOW, 120)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.gap_s) == (y.max_new, y.gap_s)
    c = _take(mix, 124, traffic.WINDOW, 120)
    assert any(not np.array_equal(x.prompt, z.prompt) for x, z in zip(a, c))


@pytest.mark.parametrize("name", MIXES)
@pytest.mark.parametrize("seed", SEEDS)
def test_lengths_inside_the_clips(name, seed):
    mix = _mix(name)
    for r in _take(mix, seed, traffic.WARM, 2 * mix["block"]):
        assert mix["prompt"]["min"] <= len(r.prompt) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= r.max_new <= mix["output"]["max"]
        assert r.prompt.dtype == np.int32 and r.gap_s > 0


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_block_of_work(name):
    """Each block is one multiset of sizes and gaps, shuffled per seed."""
    mix = _mix(name)
    b = mix["block"]
    blocks = []
    for seed in SEEDS:
        reqs = _take(mix, seed, traffic.WINDOW, b)
        blocks.append((Counter(len(r.prompt) for r in reqs),
                       Counter(r.max_new for r in reqs),
                       Counter(round(r.gap_s, 12) for r in reqs)))
    assert all(blk == blocks[0] for blk in blocks)


def test_open_loop_gaps_have_the_offered_rate():
    mix = _mix("chat")
    reqs = _take(mix, 9, traffic.WINDOW, 10 * mix["block"])
    rate = len(reqs) / sum(r.gap_s for r in reqs)
    assert rate == pytest.approx(mix["rate_per_s"], rel=0.03)


def test_chat_prompts_are_unique():
    reqs = _take(_mix("chat"), 3, traffic.WINDOW, 100, vocab=50304)
    assert len({r.prompt.tobytes() for r in reqs}) == 100


def test_lognormal_quantiles_follow_median_and_clips():
    q = traffic.lognormal_quantiles(
        {"median": 64, "sigma": 0.5, "min": 8, "max": 100}, 101)
    assert q[50] == 64 and q.min() >= 8 and q.max() == 100
    assert (np.diff(q) >= 0).all()


@pytest.mark.parametrize("seed", SEEDS[1:])
def test_order_seed_fixes_the_schedule_not_the_tokens(seed):
    """With ``order_seed`` every run's seed offers the same sizes at the
    same times; only the tokens follow the run's seed."""
    mix = dict(_mix("chat"), order_seed=11)
    a = _take(mix, SEEDS[0], traffic.WINDOW, 2 * mix["block"])
    b = _take(mix, seed, traffic.WINDOW, 2 * mix["block"])
    assert [(len(x.prompt), x.max_new, x.gap_s) for x in a] == \
        [(len(y.prompt), y.max_new, y.gap_s) for y in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("seed", SEEDS)
def test_warm_up_opens_with_the_longest_output(seed):
    """``longest_first`` puts the block's longest prompts and outputs
    first in the warm-up's stream; the block's work is unchanged."""
    mix = _mix("chat")
    n, b = mix["warm"].get("longest_first", 0), mix["block"]
    prompts = traffic.lognormal_quantiles(mix["prompt"], b)
    outs = traffic.lognormal_quantiles(mix["output"], b)
    warm = _take(mix, seed, traffic.WARM, 2 * b)
    assert [r.max_new for r in warm[:n]] == sorted(outs)[::-1][:n]
    assert [len(r.prompt) for r in warm[:n]] == sorted(prompts)[::-1][:n]
    assert sorted(r.max_new for r in warm[:b]) == sorted(outs)
    assert sorted(len(r.prompt) for r in warm[:b]) == sorted(prompts)
    assert n >= 1

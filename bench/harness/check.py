"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests the run finished is
drawn from the seed, the longest among them always in it.  The float32
reference runs once over each prompt with its served tokens (teacher
forced), and each served token is judged by its gap: how far the
reference's logit for it lies below the reference's best logit at that
position.  Greedy decoding at full precision gives gaps of 0; bfloat16
rounding lets near-ties flip and gives small ones; a wrong token, a
cache that was never written, or arithmetic a precision step lower
gives large ones.

Two numbers are compared, each with its own limit from the cell's check
file: the widest gap over the sample, and the mean gap.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

CHECK_STREAM = 5
BAD_TOKEN_GAP = 1e30


def sample(reqs: List[object], seed: int, min_tokens: int,
           max_reqs: int) -> List[object]:
    """Finished requests drawn from the seed until they hold
    ``min_tokens`` served tokens (or ``max_reqs`` requests); the one with
    the most tokens is always first."""
    done = [r for r in reqs if r.done]
    if not done:
        return []
    done.sort(key=lambda r: (-(r.prompt_len + len(r.output_tokens)),
                             r.req_id))
    picked, rest = [done[0]], done[1:]
    order = np.random.default_rng(np.random.SeedSequence(
        [int(seed), CHECK_STREAM])).permutation(len(rest))
    for i in order:
        if sum(len(r.output_tokens) for r in picked) >= min_tokens \
                or len(picked) >= max_reqs:
            break
        picked.append(rest[i])
    return picked


def make_gap_fn(model, d, quant: Optional[str] = None):
    """Jitted ``(weights, tokens [T]) -> (served_gap [T], control_gap [T])``.

    ``served_gap[p]`` is how far the float32 reference's logit of
    ``tokens[p + 1]`` lies below its best logit at position ``p``.  With
    ``quant`` set, ``control_gap[p]`` is the same gap for the token that
    the control's own logits put first at ``p``; otherwise zeros."""

    def fn(weights, tokens):
        ref = model.reference_logits(d, weights, tokens)
        best = jnp.max(ref, axis=-1)
        nxt = jnp.concatenate([tokens[1:], tokens[:1]])
        served = best - jnp.take_along_axis(ref, nxt[:, None], -1)[:, 0]
        if quant is None:
            return served, jnp.zeros_like(served)
        ctl = jnp.argmax(model.reference_logits(d, weights, tokens, quant), -1)
        return served, best - jnp.take_along_axis(ref, ctl[:, None], -1)[:, 0]

    return jax.jit(fn)


def gaps(model, d, weights, reqs: List[object], seq_cap: int,
         quant: Optional[str] = None):
    """Gaps of every served token of ``reqs`` (and, with ``quant``, of
    the tokens the control would have chosen at the same positions)."""
    fn = make_gap_fn(model, d, quant)
    served, control = [], []
    for r in reqs:
        out = np.asarray(r.output_tokens, np.int64)
        bad = (out < 0) | (out >= d.vocab)
        seq = np.concatenate([np.asarray(r.prompt_tokens, np.int64),
                              np.where(bad, 0, out)])
        toks = np.zeros(seq_cap, np.int32)
        toks[:len(seq)] = seq
        g, c = fn(weights, jnp.asarray(toks))
        at = slice(r.prompt_len - 1, r.prompt_len - 1 + len(out))
        # a token outside the vocabulary has no logit: an absurd gap
        served.append(np.where(bad, BAD_TOKEN_GAP, np.asarray(g)[at]))
        control.append(np.asarray(c)[at])
    return np.concatenate(served), np.concatenate(control)


def numbers(g: np.ndarray) -> Dict[str, float]:
    return {"widest_gap": float(g.max()), "mean_gap": float(g.mean())}


def judge(g: Optional[np.ndarray], limits: Dict[str, float]):
    """(correct, {name: {"value", "limit"}}) for the served gaps ``g``;
    no sample at all is not correct."""
    if g is None or g.size == 0:
        return False, {k: {"value": None, "limit": v}
                       for k, v in limits.items()}
    vals = numbers(g)
    out = {k: {"value": vals[k], "limit": limits[k]} for k in limits}
    return all(vals[k] <= limits[k] for k in limits), out

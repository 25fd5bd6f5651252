"""Pallas kernel microbenchmarks.

On this CPU container the kernels execute in interpret mode, so absolute
microseconds are NOT TPU numbers — the benchmark's role here is (a) a
regression harness for kernel call overheads and (b) the oracle-vs-kernel
speed sanity check.  On a real TPU the same harness times the Mosaic
binaries.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.rwkv6_scan.ops import rwkv6_scan
from repro.kernels.selective_scan.ops import selective_scan


def timeit(fn, *args, iters=3, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args, **kw)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def _paged_decode_bench() -> float:
    """End-to-end paged serving decode path (the runtime the paged backend
    drives each step: scatter new KV into the page pool + block-table
    attention + FFN), measured as warm us per decoded token on a reduced
    model.  Tracks the serving hot spot, not just the bare kernel."""
    from repro.configs.base import get_config, reduced
    from repro.serving.engine import ServingEngine
    from repro.serving.request import Request
    cfg = reduced(get_config("stablelm_3b"))
    eng = ServingEngine(cfg, max_slots=4, seq_cap=128, page_size=16, seed=0,
                        backend="paged", attn_impl="auto")
    for i in range(4):
        eng.submit(Request(req_id=i, tenant="T1", prompt_len=32,
                           max_new_tokens=18, arrival=0.0))
    decode_s, counted, seen = 0.0, 0, 0
    while eng.has_work():
        rep = eng.step()
        if rep.kind == "decode" and rep.decode_tokens:
            # pure-decode steps only (mixed steps are benched separately);
            # skip the first decodes so bucket compile time stays out
            if seen >= 8:
                decode_s += rep.compute_s
                counted += rep.decode_tokens
            seen += rep.decode_tokens
        eng.finalize_step(rep, 0.0)
    return decode_s / max(counted, 1) * 1e6


def _mixed_step_bench() -> float:
    """Fused mixed prefill+decode step (the continuous-batching hot path):
    a long prompt's chunks ride in the same jitted call as the running
    decode lanes.  Reported as warm us per token (prefill + decode tokens)
    over the mixed steps only; the same admission pattern runs twice so
    the second pass hits a warm jit cache."""
    from repro.configs.base import get_config, reduced
    from repro.serving.engine import ServingEngine
    from repro.serving.request import Request
    cfg = reduced(get_config("stablelm_3b"))
    eng = ServingEngine(cfg, max_slots=4, seq_cap=128, page_size=16, seed=0,
                        backend="paged", attn_impl="auto",
                        prefix_cache=False)

    def one_pass(base_id):
        mixed_s, mixed_tokens = 0.0, 0
        eng.submit(Request(req_id=base_id, tenant="T1", prompt_len=16,
                           max_new_tokens=24, arrival=0.0))
        # admit a long prompt once the first request is decoding, so its
        # chunks fuse with live decode lanes
        admitted = False
        steps = 0
        while eng.has_work():
            if not admitted and eng.active():
                eng.submit(Request(req_id=base_id + 1, tenant="T1",
                                   prompt_len=96, max_new_tokens=8,
                                   arrival=0.0))
                admitted = True
            rep = eng.step()
            if rep.kind == "mixed":
                mixed_s += rep.compute_s
                mixed_tokens += rep.tokens
            eng.finalize_step(rep, float(steps))
            steps += 1
        return mixed_s, mixed_tokens

    one_pass(0)                       # warm the mixed-step jit shapes
    mixed_s, mixed_tokens = one_pass(10)
    return mixed_s / max(mixed_tokens, 1) * 1e6


def _spec_step_bench() -> float:
    """Speculative verify step (the multi-token decode-lane hot path):
    a request is served cold to record its completion, then replayed
    with exact draft hints so every fused step verifies a k-token draft
    through the ragged kernel and commits the burst.  Reported as warm
    us per ACCEPTED+committed token over the verify steps — directly
    comparable to ``paged_decode_us_per_token`` (the same path at
    q_len=1): the gap between the two is the per-step fixed cost the
    speculation amortises."""
    from repro.configs.base import get_config, reduced
    from repro.serving.engine import ServingEngine
    from repro.serving.request import Request
    cfg = reduced(get_config("stablelm_3b"))

    def serve(hints, spec_k, measure):
        eng = ServingEngine(cfg, max_slots=4, seq_cap=128, page_size=16,
                            seed=0, backend="paged", attn_impl="auto",
                            spec_k=spec_k)
        req = Request(req_id=0, tenant="T1", prompt_len=32,
                      max_new_tokens=26, arrival=0.0,
                      prompt_tokens=np.arange(32) % cfg.vocab_size,
                      draft_hints=hints)
        eng.submit(req)
        spec_s, committed, seen = 0.0, 0, 0
        while eng.has_work():
            rep = eng.step()
            if measure and rep.kind == "decode" and rep.decode_tokens:
                if seen >= 2:       # skip warmup steps (bucket compiles
                    spec_s += rep.compute_s       # happen AOT anyway)
                    committed += rep.decode_tokens
                seen += 1
            eng.finalize_step(rep, 0.0)
        return req, spec_s, committed

    cold, _, _ = serve(None, 0, False)
    # replay pass 1 warms the verify-row jit buckets; pass 2 is measured
    serve(np.asarray(cold.output_tokens), 4, False)
    _, spec_s, committed = serve(np.asarray(cold.output_tokens), 4, True)
    return spec_s / max(committed, 1) * 1e6


def run(verbose=True):
    rng = np.random.default_rng(0)
    rows = []
    q = jnp.asarray(rng.standard_normal((1, 256, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 256, 2, 64)), jnp.float32)
    rows.append(("flash_attention_interp",
                 timeit(flash_attention, q, k, v)))
    rows.append(("flash_attention_ref",
                 timeit(jax.jit(flash_attention_ref), q, k, v)))

    qd = jnp.asarray(rng.standard_normal((4, 4, 64)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((16, 2, 128, 64)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((16, 2, 128, 64)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, 16, (4, 4)), jnp.int32)
    ln = jnp.asarray([300, 400, 128, 512], jnp.int32)
    rows.append(("paged_attention_interp",
                 timeit(paged_attention, qd, kp, vp, bt, ln, impl="kernel")))
    rows.append(("paged_attention_ref",
                 timeit(jax.jit(paged_attention_ref), qd, kp, vp, bt, ln)))
    rows.append(("paged_decode_us_per_token", _paged_decode_bench()))
    rows.append(("mixed_step_us_per_token", _mixed_step_bench()))
    rows.append(("spec_step_us_per_accepted_token", _spec_step_bench()))

    x = jnp.asarray(rng.standard_normal((1, 128, 128)) * 0.3, jnp.float32)
    dt = jnp.asarray(np.abs(rng.standard_normal((1, 128, 128))) * 0.1,
                     jnp.float32)
    a = jnp.asarray(-np.abs(rng.standard_normal((128, 16))) - 0.1, jnp.float32)
    b = jnp.asarray(rng.standard_normal((1, 128, 16)) * 0.3, jnp.float32)
    c = jnp.asarray(rng.standard_normal((1, 128, 16)) * 0.3, jnp.float32)
    d = jnp.asarray(rng.standard_normal((128,)), jnp.float32)
    rows.append(("selective_scan_interp",
                 timeit(selective_scan, x, dt, a, b, c, d)))

    r = jnp.asarray(rng.standard_normal((1, 128, 4, 32)) * 0.3, jnp.float32)
    kk = jnp.asarray(rng.standard_normal((1, 128, 4, 32)) * 0.3, jnp.float32)
    vv = jnp.asarray(rng.standard_normal((1, 128, 4, 32)) * 0.3, jnp.float32)
    w = jnp.asarray(np.full((1, 128, 4, 32), 0.9), jnp.float32)
    u = jnp.asarray(rng.standard_normal((4, 32)) * 0.3, jnp.float32)
    rows.append(("rwkv6_scan_interp", timeit(rwkv6_scan, r, kk, vv, w, u)))

    if verbose:
        print("== kernel microbench (interpret mode on CPU) ==")
        for name, us in rows:
            print(f"{name},{us:.0f},us_per_call")
    return dict(rows)


if __name__ == "__main__":
    run()

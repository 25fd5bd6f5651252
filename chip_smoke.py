#!/usr/bin/env python3
"""Smoke run on a TPU: serve StableLM-3B at its published widths through
the paged path, and check what comes out.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four replicas, one per chip

One chip: ``repro.launch.serve.serve(backend="paged")`` serves 8 requests
(512-token prompts, 32 new tokens) through gateway -> cache-aware router
-> PagedScheduler -> fused PagedRuntime step -> Pallas paged-attention
kernel, at 32 layers, d_model 2560 and 32x80 heads, with seeded random
weights.  It checks that every request completed with no shed, rejected
or expired verdict, that every token is in the vocabulary, that every
compiled fused-step executable holds the Pallas kernel
(``tpu_custom_call``), and that one fused step's logits are finite and
agree between the kernel and the pure-jnp oracle.  It then times a
second pass of the same traffic on the warm engine.

``--four-chips`` runs only the path across chips: one tenant with four
replicas behind ``CacheAwareRouter``, each replica's weights and pools
on its own chip, and greedy-token parity of every replica with
replica 0 on one fixed prompt.

The readings it prints are smoke readings on the host clock, not
benchmark results.  The last line of standard output is one JSON object
naming the device.  Any failed check raises, and the exit code is then
non-zero.  Without a TPU it exits non-zero before serving anything.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax                                          # noqa: E402
import jax.numpy as jnp                             # noqa: E402
import numpy as np                                  # noqa: E402

from repro.launch.serve import serve, use_checkout_compile_cache  # noqa: E402
from repro.serving.paged_runtime import PagedRuntime  # noqa: E402
from repro.serving.request import Request           # noqa: E402

# what is served: the model at its published widths and depth, one
# tenant, every request arriving at once
SERVE = dict(arch="stablelm_3b", reduced=False, backend="paged",
             num_tenants=1, slots=8, seq_cap=576, qps=1e4, seed=0,
             verbose=False)
ONE_CHIP = dict(replicas=1, requests=8, prompt_len=512, max_new=32)
FOUR_CHIPS = dict(replicas=4, requests=8, prompt_len=64, max_new=8)
# kernel vs oracle on one fused step: relative L2 error of the logits,
# by depth.  Both read the same bf16 pages; the kernel rounds the softmax
# probabilities to bf16 (8-bit mantissa) before P.V and sums pages in
# another order, so one layer's logits differ by ~6e-3.  Every later
# bf16 layer carries and adds to that difference: with random weights it
# grows about linearly with depth, to 0.2-0.3 at 32 layers for a correct
# kernel.  An off-by-one causal mask gives 0.31 at one layer and 1.2 at
# 32 (both measured on the CPU in interpret mode at reduced widths).
RTOL_ONE_LAYER, RTOL_ALL_LAYERS = 2e-2, 0.6


def log(msg: str) -> None:
    print(msg, flush=True)


def served_engines(kw: dict):
    """Serve ``kw``'s traffic through ``serve()``; check every verdict and
    token; return the tenant's engines."""
    t0 = time.perf_counter()
    out = serve(**SERVE, **kw)
    stats = out["T1"]
    log(f"served {kw['requests']} requests x {kw['replicas']} replica(s) "
        f"in {time.perf_counter() - t0:.1f} s wall (compiles included): "
        f"completed {stats['completed']}/{stats['offered']}, "
        f"shed {stats['shed']}, rejected {stats['rejected']}, "
        f"expired {stats['expired']}")
    if not (stats["completed"] == stats["offered"] == kw["requests"]):
        raise AssertionError(f"not every request completed: {stats}")
    if stats["shed"] or stats["rejected"] or stats["expired"]:
        raise AssertionError(f"requests lost at the door: {stats}")
    engines = out["engines"]["T1"]
    vocab = engines[0].cfg.vocab_size
    for rid, toks in stats["outputs"].items():
        if len(toks) != kw["max_new"]:
            raise AssertionError(f"request {rid}: {len(toks)} tokens")
        if not all(0 <= t < vocab for t in toks):
            raise AssertionError(f"request {rid}: token outside the "
                                 f"vocabulary of {vocab}")
    return engines


def check_kernel_in_executables(rt: PagedRuntime) -> None:
    """Every compiled fused step must hold the Pallas kernel, not the jnp
    oracle or the interpreter."""
    if not rt._mixed_exec:
        raise AssertionError("no fused step was compiled")
    for key, exe in sorted(rt._mixed_exec.items()):
        if "tpu_custom_call" not in exe.as_text():
            raise AssertionError(f"bucket {key}: no tpu_custom_call")
    for key in sorted(rt.compile_s):
        rows, width, logits = key
        log(f"smoke reading: compile {rt.compile_s[key]:.2f} s for bucket "
            f"rows={rows} width={width} logit_rows={logits}")
    log(f"tpu_custom_call in all {len(rt._mixed_exec)} fused-step "
        f"executables")


def timed_pass(eng, kw: dict, seed: int) -> None:
    """Run the same traffic again on the warm engine through its own
    submit/step API, timing each step on the host clock."""
    rng = np.random.default_rng(seed)
    reqs = [Request(req_id=100_000 + i, tenant="T1",
                    prompt_len=kw["prompt_len"], max_new_tokens=kw["max_new"],
                    arrival=0.0,
                    prompt_tokens=rng.integers(0, eng.cfg.vocab_size,
                                               kw["prompt_len"]))
            for i in range(kw["requests"])]
    for r in reqs:
        if not eng.submit(r):
            raise AssertionError(f"request {r.req_id} refused")
    rt = eng.runtime
    steady, tokens, clock = [], 0, 0.0
    while eng.has_work():
        n_exec = len(rt._mixed_exec)
        t0 = time.perf_counter()
        rep = eng.step()
        dt = time.perf_counter() - t0
        clock += dt
        eng.finalize_step(rep, clock)
        if len(rt._mixed_exec) == n_exec and rep.kind != "idle":
            steady.append(dt)
            tokens += rep.tokens
    if not all(r.done for r in reqs):
        raise AssertionError("timed pass left requests unfinished")
    log(f"smoke reading: steady step time median "
        f"{np.median(steady) * 1e3:.1f} ms over {len(steady)} steps "
        f"(host clock, planning included)")
    log(f"smoke reading: {tokens / sum(steady):.0f} tokens/s "
        f"(prefill + decode tokens over the steady steps)")


def kernel_vs_ref(eng, prompt_len: int = 192) -> None:
    """One fused step, the same batch, under the Pallas kernel and the jnp
    oracle, on the served weights cut to one layer and at their full
    depth: the logits must be finite and agree within the tolerances."""
    cfg = eng.cfg
    page = eng.runtime.page
    rng = np.random.default_rng(7)
    tokens = rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
    positions = np.arange(prompt_len, dtype=np.int32)
    n_pages = -(-prompt_len // page)
    width = 1 << (n_pages - 1).bit_length()
    table = np.zeros(width, np.int32)
    table[:n_pages] = np.arange(n_pages)
    batch = jax.device_put((tokens, positions, np.int32(prompt_len),
                            np.tile(table, (prompt_len, 1)),
                            np.arange(page - 1, prompt_len, page,
                                      dtype=np.int32)), eng.device)
    for depth, tol in ((1, RTOL_ONE_LAYER),
                       (cfg.repeats, RTOL_ALL_LAYERS)):
        cut = dataclasses.replace(cfg, repeats=depth)
        params = {**eng.params, "period": jax.tree.map(
            lambda x: x[:depth], eng.params["period"])}
        logits = {}
        for impl in ("kernel", "ref"):
            rt = PagedRuntime(cut, params, max_slots=1,
                              seq_cap=width * page, page_size=page,
                              pool_pages=width, attn_impl=impl,
                              device=eng.device)
            out, _ = rt._mixed_fn(rt.params, rt.pools, *batch)
            logits[impl] = np.asarray(out.astype(jnp.float32))
            if not np.isfinite(logits[impl]).all():
                raise AssertionError(f"{impl}, {depth} layer(s): "
                                     f"non-finite logits")
        k, r = logits["kernel"], logits["ref"]
        rel = float(np.linalg.norm(k - r) / np.linalg.norm(r))
        log(f"kernel vs ref, {depth} layer(s): {k.shape[0]} logit rows x "
            f"{k.shape[1]}, relative L2 error {rel:.3e} (tolerance "
            f"{tol:g}), max |diff| {np.abs(k - r).max():.3e} of max |ref| "
            f"{np.abs(r).max():.3e}")
        if not rel <= tol:
            raise AssertionError(f"kernel and ref logits differ at {depth} "
                                 f"layer(s): {rel:.3e} > {tol:g}")


def greedy_tokens(eng, prompt: np.ndarray, max_new: int, req_id: int):
    req = Request(req_id=req_id, tenant="T1", prompt_len=len(prompt),
                  max_new_tokens=max_new, arrival=0.0, prompt_tokens=prompt)
    if not eng.submit(req):
        raise AssertionError(f"replica refused request {req_id}")
    while eng.has_work():
        eng.finalize_step(eng.step(), 0.0)
    return list(req.output_tokens)


def one_chip() -> None:
    engines = served_engines(ONE_CHIP)
    eng = engines[0]
    log(f"model {eng.cfg.name}: {eng.cfg.repeats} layers, d_model "
        f"{eng.cfg.d_model}, {eng.cfg.attn.num_heads}x"
        f"{eng.cfg.attn.head_dim} heads, vocab {eng.cfg.vocab_size}")
    check_kernel_in_executables(eng.runtime)
    timed_pass(eng, ONE_CHIP, seed=1)
    kernel_vs_ref(eng)
    stats = jax.devices()[0].memory_stats() or {}
    log(f"smoke reading: peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'not reported')}")


def four_chips() -> None:
    if len(jax.devices()) < 4:
        raise AssertionError(f"--four-chips needs 4 devices, JAX has "
                             f"{len(jax.devices())}")
    engines = served_engines(FOUR_CHIPS)
    homes = []
    for j, eng in enumerate(engines):
        devs = {d for leaf in jax.tree.leaves((eng.params, eng.runtime.pools))
                for d in leaf.devices()}
        if len(devs) != 1:
            raise AssertionError(f"replica {j} spans devices {devs}")
        homes.append(devs.pop())
        check_kernel_in_executables(eng.runtime)
    if len(set(homes)) != len(engines):
        raise AssertionError(f"replicas share devices: {homes}")
    log(f"replicas on devices {[d.id for d in homes]}")
    prompt = np.random.default_rng(3).integers(
        0, engines[0].cfg.vocab_size, FOUR_CHIPS["prompt_len"])
    ref = greedy_tokens(engines[0], prompt, FOUR_CHIPS["max_new"], 200_000)
    for j, eng in enumerate(engines[1:], start=1):
        toks = greedy_tokens(eng, prompt, FOUR_CHIPS["max_new"], 200_000 + j)
        if toks != ref:
            raise AssertionError(f"replica {j} tokens differ from "
                                 f"replica 0: {toks} vs {ref}")
    log(f"token parity: replicas 1-{len(engines) - 1} match replica 0 on "
        f"{len(ref)} greedy tokens")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica, four-chip path")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found {dev.platform})")
    use_checkout_compile_cache()
    if args.four_chips:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()

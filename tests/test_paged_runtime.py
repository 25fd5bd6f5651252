"""Paged serving runtime: dense-vs-paged token parity through the fused
mixed prefill+decode step, per-step token budgets, prefix-cache sharing,
SLO-aware preemption, and refcount/page-accounting invariants — all on
CPU, with the Pallas paged-attention kernel exercised in interpret mode."""
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.serving.engine import ServingEngine
from repro.serving.kvcache import PagedKVCache
from repro.serving.request import Request

# float32 keeps the two backends bit-identical (the bf16 KV cache is
# value-identical too, but fp32 removes any tie-breaking ambiguity from
# the token-parity assertions)
CFG = reduced(get_config("stablelm_3b")).replace(dtype="float32")

# mixed long/short trace: (prompt_len, max_new_tokens)
TRACE = [(40, 4), (7, 8), (21, 2), (3, 6), (60, 3)]


def make_trace(seed=0, trace=TRACE, **kw):
    rng = np.random.default_rng(seed)
    return [Request(req_id=i, tenant="T1", prompt_len=pl, max_new_tokens=mn,
                    arrival=0.0,
                    prompt_tokens=rng.integers(0, CFG.vocab_size, pl), **kw)
            for i, (pl, mn) in enumerate(trace)]


def drain(eng, max_steps=800):
    reports = []
    while eng.has_work():
        rep = eng.step()
        eng.finalize_step(rep, float(len(reports)))
        reports.append(rep)
        assert len(reports) < max_steps, "engine did not converge"
    return reports


def assert_no_leaks(eng):
    """After a drain no sequence holds pages; only refcount-zero prefix
    pages may remain parked on the cached LRU (reclaimable capacity)."""
    kv = eng.kv
    assert kv.used_pages == 0
    assert kv.reserved_pages == 0
    assert not kv.tables
    assert len(kv.free) + kv.cached_pages == kv.num_pages
    assert all(kv.ref.get(p, 0) == 0 for p in kv.cached)


def assert_refcount_invariants(kv: PagedKVCache):
    """Every page is exactly one of {free, cached, owned}; refcounts equal
    the number of tables referencing the page; no page is freed while it
    has live sharers."""
    owned = {}
    for e in kv.tables.values():
        seen = set()
        for p in e.pages:
            assert p not in seen, "page mapped twice in one sequence"
            seen.add(p)
            owned[p] = owned.get(p, 0) + 1
    for p, n in owned.items():
        assert kv.ref.get(p) == n, f"page {p}: ref {kv.ref.get(p)} != {n}"
        assert p not in kv.free and p not in kv.cached, \
            f"owned page {p} also free/cached"
    for p in kv.cached:
        assert p not in kv.free and p not in owned
        assert kv.ref.get(p, 0) == 0
    assert len(owned) + len(set(kv.free)) + len(kv.cached) == kv.num_pages
    assert len(kv.free) == len(set(kv.free)), "free list duplicate"
    assert 0 <= kv.used_pages <= kv.reserved_pages <= kv.num_pages


# ----------------------------------------------------------------- parity
@pytest.mark.parametrize("impl", ["ref", "kernel"])
def test_paged_dense_token_parity(impl):
    """Same mixed long/short trace through both backends -> identical
    output tokens; 'kernel' runs the ragged Pallas kernel in interpret
    mode.  The paged side now serves everything through the fused mixed
    step (decode lanes + prefill chunks in one jitted call)."""
    dense = ServingEngine(CFG, max_slots=4, seq_cap=96, page_size=8, seed=0)
    paged = ServingEngine(CFG, max_slots=4, seq_cap=96, page_size=8, seed=0,
                          backend="paged", chunk_tokens=16, attn_impl=impl)
    reqs_d, reqs_p = make_trace(), make_trace()
    for r in reqs_d:
        assert dense.submit(r)
    for r in reqs_p:
        assert paged.submit(r)
    drain(dense)
    reports = drain(paged)
    for rd, rp in zip(reqs_d, reqs_p):
        assert rd.done and rp.done
        assert len(rd.output_tokens) == rd.max_new_tokens
        assert rd.output_tokens == rp.output_tokens, \
            f"req {rd.req_id}: {rd.output_tokens} != {rp.output_tokens}"
    # the fused step actually fused: some steps carried prefill AND decode
    assert any(r.kind == "mixed" for r in reports)
    assert_no_leaks(paged)
    assert_no_leaks(dense)


def test_paged_accounting_during_run():
    """Refcount/occupancy invariants hold at every step (shared pages
    counted once, refcounts consistent, free/cached/owned partition the
    pool)."""
    eng = ServingEngine(CFG, max_slots=4, seq_cap=96, page_size=8, seed=0,
                        backend="paged", chunk_tokens=16, attn_impl="ref")
    for r in make_trace(seed=3):
        assert eng.submit(r)
    while eng.has_work():
        rep = eng.step()
        assert_refcount_invariants(eng.kv)
        eng.finalize_step(rep, 0.0)
    assert_no_leaks(eng)


def test_logits_bucket_follows_row_bucket():
    """Without drafts the logit rows pad to one per slot within the row
    bucket, so however many lanes a step carries, its executable is
    keyed by (rows, width) alone."""
    eng = ServingEngine(CFG, max_slots=4, seq_cap=96, page_size=8, seed=0,
                        backend="paged", chunk_tokens=16, attn_impl="ref")
    reqs = make_trace(seed=4)
    for r in reqs:
        assert eng.submit(r)
    drain(eng)
    assert all(r.done for r in reqs)
    keys = list(eng.runtime.compile_s)
    assert any(t > 4 for t, _, _ in keys) and any(t < 4 for t, _, _ in keys)
    assert all(n == min(t, 4) for t, _, n in keys), keys


# --------------------------------------------------- fused mixed stepping
def test_step_token_budget_bounds_every_step():
    """Per-step work never exceeds the fused token budget, and a single
    prompt's chunks are bounded by chunk_tokens."""
    chunk = 16
    eng = ServingEngine(CFG, max_slots=4, seq_cap=96, page_size=8, seed=0,
                        backend="paged", chunk_tokens=chunk, attn_impl="ref")
    budget = eng.runtime.sched.step_token_budget()
    rng = np.random.default_rng(5)
    req = Request(req_id=0, tenant="T1", prompt_len=60, max_new_tokens=2,
                  arrival=0.0,
                  prompt_tokens=rng.integers(0, CFG.vocab_size, 60))
    assert eng.submit(req)
    reports = drain(eng)
    prefills = [r for r in reports if r.prefill_tokens]
    assert all(r.tokens <= budget for r in reports)
    assert all(r.prefill_tokens <= chunk for r in prefills)
    assert sum(r.prefill_tokens for r in prefills) == 60
    assert len(prefills) == 4          # ceil(60/16)
    assert req.done and len(req.output_tokens) == 2


def test_mixed_step_decode_never_stalls_on_admission():
    """The head-of-line fix: while a long prompt chunk-prefills, every one
    of its chunk steps ALSO decodes the already-running sequence in the
    same fused call — admissions consume prefill budget, never decode
    steps (under PR 3's interleave each chunk stalled all decode lanes)."""
    eng = ServingEngine(CFG, max_slots=4, seq_cap=96, page_size=8, seed=0,
                        backend="paged", chunk_tokens=16, attn_impl="ref")
    rng = np.random.default_rng(7)
    short = Request(req_id=0, tenant="T1", prompt_len=8, max_new_tokens=12,
                    arrival=0.0,
                    prompt_tokens=rng.integers(0, CFG.vocab_size, 8))
    assert eng.submit(short)
    # get the short request decoding before the long prompt arrives
    while not short.generated:
        eng.finalize_step(eng.step(), 0.0)
    long_ = Request(req_id=1, tenant="T1", prompt_len=64, max_new_tokens=2,
                    arrival=0.0,
                    prompt_tokens=rng.integers(0, CFG.vocab_size, 64))
    assert eng.submit(long_)
    stalled = []
    while eng.has_work():
        rep = eng.step()
        if rep.prefill_tokens and not short.done:
            # the long prompt's chunk rode WITH the short seq's decode
            stalled.append(rep.decode_tokens == 0)
            assert rep.kind == "mixed"
        eng.finalize_step(rep, 0.0)
    assert stalled and not any(stalled), \
        f"decode stalled during {sum(stalled)}/{len(stalled)} chunk steps"
    assert short.done and long_.done
    assert_no_leaks(eng)


# ---------------------------------------------------- prefix-cache sharing
def _shared_engine(**kw):
    return ServingEngine(CFG, max_slots=4, seq_cap=96, page_size=8, seed=0,
                         backend="paged", chunk_tokens=16, attn_impl="ref",
                         **kw)


def test_prefix_hit_parity_and_compute_skip():
    """A request sharing a warm prompt prefix produces IDENTICAL tokens to
    a cold run while prefilling only the tail (page-aligned prefix served
    from shared pages)."""
    rng = np.random.default_rng(21)
    toks = rng.integers(0, CFG.vocab_size, 40)     # 5 pages, 4 shareable

    cold = _shared_engine(prefix_cache=False)
    r_cold = Request(req_id=0, tenant="T1", prompt_len=40, max_new_tokens=6,
                     arrival=0.0, prompt_tokens=toks.copy())
    assert cold.submit(r_cold)
    drain(cold)

    eng = _shared_engine()
    r1 = Request(req_id=1, tenant="T1", prompt_len=40, max_new_tokens=6,
                 arrival=0.0, prompt_tokens=toks.copy())
    assert eng.submit(r1)
    drain(eng)
    assert eng.metrics.prefill_tokens_total == 40      # cold: full prompt
    assert r1.output_tokens == r_cold.output_tokens

    r2 = Request(req_id=2, tenant="T1", prompt_len=40, max_new_tokens=6,
                 arrival=1.0, prompt_tokens=toks.copy())
    assert eng.submit(r2)
    drain(eng)
    # (40-1)//8 = 4 full pages = 32 tokens came from the cache; only the
    # 8-token tail was prefilled
    assert eng.metrics.prefix_hit_tokens_total == 32
    assert eng.metrics.prefill_tokens_total == 48
    assert eng.metrics.prefix_hit_rate() == pytest.approx(32 / 80)
    assert r2.output_tokens == r_cold.output_tokens
    assert_no_leaks(eng)


def test_prefix_pages_shared_live_with_refcounts():
    """Two live requests with the same prompt share physical pages
    (refcount 2) and the pages are never freed while shared."""
    rng = np.random.default_rng(23)
    toks = rng.integers(0, CFG.vocab_size, 40)
    eng = _shared_engine()
    r1 = Request(req_id=0, tenant="T1", prompt_len=40, max_new_tokens=20,
                 arrival=0.0, prompt_tokens=toks.copy())
    assert eng.submit(r1)
    while not r1.generated:                 # r1 decoding, pages committed
        eng.finalize_step(eng.step(), 0.0)
    r2 = Request(req_id=1, tenant="T1", prompt_len=40, max_new_tokens=4,
                 arrival=0.0, prompt_tokens=toks.copy())
    assert eng.submit(r2)
    saw_shared = False
    while eng.has_work():
        assert_refcount_invariants(eng.kv)
        if any(n == 2 for n in eng.kv.ref.values()):
            saw_shared = True
        eng.finalize_step(eng.step(), 0.0)
    assert saw_shared, "prompts never shared a physical page"
    assert r1.output_tokens[:4] == r2.output_tokens[:4]
    assert_no_leaks(eng)


def test_prefix_cache_eviction_reclaims_capacity():
    """Cached refcount-zero prefix pages are transparently reclaimed when
    fresh allocations need them (no MemoryError, no stale index)."""
    rng = np.random.default_rng(25)
    eng = ServingEngine(CFG, max_slots=2, seq_cap=64, page_size=8, seed=0,
                        backend="paged", pool_pages=8, chunk_tokens=16,
                        attn_impl="ref")
    for i in range(4):                    # distinct prompts, 4 pages each
        r = Request(req_id=i, tenant="T1", prompt_len=32, max_new_tokens=2,
                    arrival=float(i),
                    prompt_tokens=rng.integers(0, CFG.vocab_size, 32))
        assert eng.submit(r)
        drain(eng)
        assert r.done
        assert_refcount_invariants(eng.kv)
    assert_no_leaks(eng)


# ------------------------------------------------------------- preemption
def _overcommitted_engine(**kw):
    # pool of 6 pages x 4 tokens; two 16-token sequences need 8 pages
    return ServingEngine(CFG, max_slots=4, seq_cap=32, page_size=4, seed=0,
                         backend="paged", pool_pages=6, chunk_tokens=8,
                         attn_impl="ref", **kw)


def test_preemption_evicts_by_slo_priority_and_requeues():
    eng = _overcommitted_engine()
    rng = np.random.default_rng(11)
    hi = Request(req_id=0, tenant="T1", prompt_len=8, max_new_tokens=8,
                 arrival=0.0, slo_ms=50.0, priority=2.0,
                 prompt_tokens=rng.integers(0, CFG.vocab_size, 8))
    lo = Request(req_id=1, tenant="T1", prompt_len=8, max_new_tokens=8,
                 arrival=0.0, priority=0.5,
                 prompt_tokens=rng.integers(0, CFG.vocab_size, 8))
    assert eng.submit(hi) and eng.submit(lo)
    reports = drain(eng)
    preempted_ids = [r.req_id for rep in reports for r in rep.preempted]
    log = eng.runtime.sched.preempt_log
    assert preempted_ids or log, "overcommitted pool never preempted"
    # only the low-priority request is ever evicted
    assert set(r for r, _ in log) == {lo.req_id}
    # both (including the requeued victim) run to completion
    assert hi.done and len(hi.output_tokens) == hi.max_new_tokens
    assert lo.done and len(lo.output_tokens) == lo.max_new_tokens
    assert_no_leaks(eng)


def test_preempted_sequence_regenerates_identical_tokens():
    """Recompute-style preemption + greedy decode: the victim's restart
    must reproduce the tokens an uncontended run produces (the restart
    may legally ride a prefix hit on its own surviving cached pages)."""
    rng = np.random.default_rng(13)
    toks = rng.integers(0, CFG.vocab_size, 8)

    solo = ServingEngine(CFG, max_slots=4, seq_cap=32, page_size=4, seed=0,
                         backend="paged", chunk_tokens=8, attn_impl="ref")
    ref_req = Request(req_id=9, tenant="T1", prompt_len=8, max_new_tokens=8,
                      arrival=0.0, prompt_tokens=toks.copy())
    assert solo.submit(ref_req)
    drain(solo)

    eng = _overcommitted_engine()
    hi = Request(req_id=0, tenant="T1", prompt_len=8, max_new_tokens=8,
                 arrival=0.0, priority=2.0,
                 prompt_tokens=rng.integers(0, CFG.vocab_size, 8))
    lo = Request(req_id=1, tenant="T1", prompt_len=8, max_new_tokens=8,
                 arrival=0.0, priority=0.5, prompt_tokens=toks.copy())
    assert eng.submit(hi) and eng.submit(lo)
    drain(eng)
    assert any(r == lo.req_id for r, _ in eng.runtime.sched.preempt_log)
    assert lo.output_tokens == ref_req.output_tokens
    assert_no_leaks(eng)


def test_refcount_invariants_under_churn_and_preemption():
    """Shared-prefix traffic on an overcommitted pool: preemption,
    prefix reuse, and cached-page eviction interleave, and the refcount
    invariants must hold at every step (no page freed while shared, zero
    leaks after the churn)."""
    rng = np.random.default_rng(31)
    common = rng.integers(0, CFG.vocab_size, 8)     # 2 shared pages
    eng = ServingEngine(CFG, max_slots=3, seq_cap=32, page_size=4, seed=0,
                        backend="paged", pool_pages=10, chunk_tokens=8,
                        attn_impl="ref")
    reqs = []
    for i in range(6):
        tail = rng.integers(0, CFG.vocab_size, 4)
        reqs.append(Request(
            req_id=i, tenant="T1", prompt_len=12, max_new_tokens=6,
            arrival=float(i), priority=float(rng.integers(0, 3)),
            prompt_tokens=np.concatenate([common, tail])))
    for r in reqs[:3]:
        assert eng.submit(r)
    steps = 0
    while eng.has_work():
        if steps == 4:
            for r in reqs[3:]:
                assert eng.submit(r)
        rep = eng.step()
        assert_refcount_invariants(eng.kv)
        eng.finalize_step(rep, float(steps))
        steps += 1
        assert steps < 800
    assert all(r.done for r in reqs)
    assert eng.metrics.prefix_hit_tokens_total > 0, "churn never hit prefix"
    assert_no_leaks(eng)


def test_paged_submit_rejects_only_never_fitting():
    eng = _overcommitted_engine()
    # 6 pages x 4 tokens = 24-token pool; 32-token footprint can never fit
    assert not eng.submit(Request(req_id=0, tenant="T1", prompt_len=16,
                                  max_new_tokens=16, arrival=0.0))
    # an overcommitting-but-feasible request is accepted (dense would
    # reject the second one at submit)
    assert eng.submit(Request(req_id=1, tenant="T1", prompt_len=12,
                              max_new_tokens=8, arrival=0.0))
    assert eng.submit(Request(req_id=2, tenant="T1", prompt_len=12,
                              max_new_tokens=8, arrival=0.0))
    drain(eng)
    assert_no_leaks(eng)


# ------------------------------------------------- kv-cache satellite fixes
def test_release_unknown_or_double_raises():
    """Regression: a silent release of an unknown/already-released seq_id
    would push its pages onto the free list twice and hand the same page
    to two sequences."""
    kv = PagedKVCache(num_pages=8, page_size=4)
    with pytest.raises(KeyError):
        kv.release(7)
    kv.allocate(1, prompt_len=8)
    kv.release(1)
    with pytest.raises(KeyError):
        kv.release(1)
    assert len(kv.free) == 8            # no double-free corruption


def test_preemption_path_guards_double_release():
    """The scheduler's preempt/complete paths must tolerate a sequence
    whose pages were already released (e.g. evicted while planned) without
    tripping the strict release() or corrupting the free list."""
    from repro.serving.sched import PagedScheduler, SchedConfig, SeqState
    kv = PagedKVCache(num_pages=8, page_size=4, enable_prefix_cache=False)
    sched = PagedScheduler(kv, SchedConfig(chunk_tokens=8, max_active=2))
    req = Request(req_id=0, tenant="T1", prompt_len=8, max_new_tokens=2,
                  arrival=0.0,
                  prompt_tokens=np.zeros(8, np.int64))
    assert sched.submit(req)
    plan = sched.plan()
    assert plan.prefills
    seq = plan.prefills[0][0]
    sched.preempt(seq)                  # releases pages, requeues
    sched.preempt(seq)                  # double-preempt: must be safe
    sched.complete(seq)                 # and complete-after-release too
    assert len(kv.free) == 8
    assert not kv.tables


def test_block_table_overflow_raises():
    kv = PagedKVCache(num_pages=8, page_size=4)
    kv.allocate(1, prompt_len=12)           # 3 pages
    with pytest.raises(ValueError):
        kv.block_table(1, pages_per_seq=2)  # too narrow: must not truncate
    bt = kv.block_table(1, pages_per_seq=4)
    assert list(bt[:3]) == kv.tables[1].pages


def test_reserved_vs_used_pages_diverge_under_dense_reservation():
    kv = PagedKVCache(num_pages=16, page_size=4)
    kv.allocate(1, prompt_len=4, reserve_total=16)   # 4 pages reserved
    assert kv.reserved_pages == 4
    assert kv.used_pages == 1                        # only the prompt live
    for _ in range(4):
        kv.append_token(1)
    assert kv.used_pages == 2 and kv.reserved_pages == 4
    kv.release(1)
    assert kv.reserved_pages == 0 and kv.used_pages == 0


def test_engine_metrics_report_both_kv_gauges():
    eng = ServingEngine(CFG, max_slots=2, seq_cap=32, page_size=8, seed=0)
    assert eng.submit(Request(req_id=0, tenant="T1", prompt_len=8,
                              max_new_tokens=16, arrival=0.0))
    eng.finalize_step(eng.step(), 0.0)      # prefill
    m = eng.metrics
    assert m.kv_total_pages == eng.kv.num_pages
    # dense reservation: prompt+max_new reserved, only prompt-ish live
    assert m.kv_reserved_pages == 3 and m.kv_used_pages == 1
    assert m.kv_utilisation() > m.kv_live_utilisation() > 0
    drain(eng)


# ---------------------------------------------------------- int8 page pools
def _first_step_logits(eng, req):
    """Capture the fused step's logits for the lane serving ``req``."""
    rt = eng.runtime
    captured = {}
    orig = rt._run_mixed

    def wrap(*args):
        logits, dt = orig(*args)
        captured["logits"] = logits
        return logits, dt

    rt._run_mixed = wrap
    try:
        assert eng.submit(req)
        eng.finalize_step(eng.step(), 0.0)
    finally:
        rt._run_mixed = orig
    return np.asarray(captured["logits"], np.float32)


def test_int8_pages_logits_close_and_pool_halved():
    """kv_dtype='int8' quantizes the page pools (int8 K/V + per-page-row
    scales) and the first-step logits stay within the same tolerance the
    dense REPRO_KV_INT8 harness (tests/test_kv_quant.py) enforces."""
    import jax
    import jax.numpy as jnp
    from repro.models.model import Model
    params = Model(CFG).init(jax.random.key(1))
    rng = np.random.default_rng(2)
    toks = rng.integers(0, CFG.vocab_size, 12)

    def make(kv_dtype):
        return ServingEngine(CFG, params=params, max_slots=2, seq_cap=32,
                             page_size=8, seed=0, backend="paged",
                             chunk_tokens=16, attn_impl="ref",
                             kv_dtype=kv_dtype)

    def req():
        return Request(req_id=0, tenant="T1", prompt_len=12,
                       max_new_tokens=2, arrival=0.0,
                       prompt_tokens=toks.copy())

    eng_f = make("auto")
    eng_q = make("int8")
    pool = eng_q.runtime.pools["period"]["sub0"]
    assert pool["k"].dtype == jnp.int8 and "k_scale" in pool
    # int8 halves the page bytes (+ small f32 scale overhead)
    kv_bytes = pool["k"].nbytes + pool["k_scale"].nbytes
    assert kv_bytes < 0.55 * (2 * pool["k"].size *
                              jnp.dtype(CFG.dtype).itemsize)
    lg_f = _first_step_logits(eng_f, req())[0]
    lg_q = _first_step_logits(eng_q, req())[0]
    err = np.max(np.abs(lg_q - lg_f))
    ref = np.max(np.abs(lg_f)) + 1e-6
    assert err / ref < 0.08, f"relative logits error {err/ref:.3f}"


def test_int8_pages_full_run_no_leaks():
    eng = ServingEngine(CFG, max_slots=4, seq_cap=96, page_size=8, seed=0,
                        backend="paged", chunk_tokens=16, attn_impl="ref",
                        kv_dtype="int8")
    reqs = make_trace(seed=9)
    for r in reqs:
        assert eng.submit(r)
    drain(eng)
    assert all(r.done and len(r.output_tokens) == r.max_new_tokens
               for r in reqs)
    assert_no_leaks(eng)


def test_int8_on_dense_backend_rejected():
    with pytest.raises(ValueError):
        ServingEngine(CFG, backend="dense", kv_dtype="int8")

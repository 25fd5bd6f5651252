"""Verified KV-page shipping: export→import round-trips bit-exactly
(tokens, refcounts, chain hashes, int8 scales), a corrupted transfer is
ALWAYS detected and degrades to the recompute redrive (never a wrong
token), shared prompt prefixes attach through the destination's
chain-hash index instead of copying, migration cost is priced against
the ledger's per-root fabric demand, and the gateway's OpenMetrics
exemplars parse back to the slowest retained request per bucket.
"""
import copy
import dataclasses
import re

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.base import get_config, reduced
from repro.serving.engine import ServingEngine
from repro.serving.gateway import Gateway
from repro.serving.metrics import LatencyWindow
from repro.serving.migrate import (LaneManifest, MigrationPlanner,
                                   PageImporter, _page_digest)
from repro.serving.request import Request

CFG = reduced(get_config("stablelm_3b")).replace(dtype="float32")


def mk_engine():
    # float32 + ref attention: greedy output is a pure function of the
    # prompt (batch- and restart-invariant), so cross-engine token
    # parity is assertable bit-exactly.  int8 pages so the property
    # covers the quantized pool's scale leaves too.
    return ServingEngine(CFG, max_slots=4, seq_cap=64, page_size=4,
                         seed=0, backend="paged", pool_pages=48,
                         chunk_tokens=8, attn_impl="ref", kv_dtype="int8")


def mk_req(rid, prompt_tokens, max_new=6):
    return Request(req_id=rid, tenant="T1", prompt_len=len(prompt_tokens),
                   max_new_tokens=max_new, arrival=0.0,
                   prompt_tokens=np.asarray(prompt_tokens, np.int64))


def run_out(eng, t=0.0):
    while eng.has_work():
        t += 0.01
        eng.finalize_step(eng.step(), t, t - 0.01)
    return t


def reference_tokens(prompts, max_new=6):
    """Fault-free greedy outputs, one fresh engine."""
    eng = mk_engine()
    reqs = [mk_req(i, p, max_new) for i, p in enumerate(prompts)]
    for r in reqs:
        assert eng.submit(r)
    run_out(eng)
    return {r.req_id: list(r.output_tokens) for r in reqs}


def dst_pool_payload(rt, pages):
    """Read back one page's leaves from a runtime's pools (each a stack
    of layers), in the same leaf-key layout the exporter serializes,
    with every lane: the shipped ones and the zero pad lanes past them."""
    from repro.serving.migrate import _pool_leaves
    out = []
    for page in pages:
        payload = {}
        for key, group, name, fld in _pool_leaves(rt.pools):
            payload[key] = np.asarray(rt.pools[group][name][fld][:, page])
        out.append(payload)
    return out


def assert_landed(rec, landed):
    """A shipped page landed byte for byte in its lanes, with zeros in
    the pool's pad lanes, and its chain link recomputes on the landed
    bytes.  Returns the landed payload as shipped."""
    shipped = {}
    for key, arr in rec.payload.items():
        width = np.asarray(arr).shape[-1]
        assert np.array_equal(np.asarray(arr), landed[key][..., :width]), key
        assert not landed[key][..., width:].any(), key
        shipped[key] = landed[key][..., :width]
    return shipped


# ------------------------------------------------- round-trip property
@settings(max_examples=6, deadline=None)
@given(st.data())
def test_export_import_round_trip(data):
    """Randomized lanes (mid-prefill, decoding, queued) drained with
    ``ship_state=True`` and imported into a fresh replica: every warm
    lane lands with its tokens, page bytes (int8 payloads AND scales),
    chain hashes and refcounts intact, every cold lane degrades to a
    plain resubmit, and the merged cluster finishes token-identical to
    a fault-free run."""
    rng = np.random.default_rng(data.draw(st.integers(0, 2**16)))
    n_req = data.draw(st.integers(min_value=2, max_value=4))
    prompts = [rng.integers(0, CFG.vocab_size,
                            int(data.draw(st.integers(5, 20))))
               for _ in range(n_req)]
    steps = data.draw(st.integers(min_value=1, max_value=10))
    base = reference_tokens(prompts)

    src = mk_engine()
    reqs = [mk_req(i, p) for i, p in enumerate(prompts)]
    for r in reqs:
        assert src.submit(r)
    t = 0.0
    for _ in range(steps):
        if not src.has_work():
            break
        t += 0.01
        src.finalize_step(src.step(), t, t - 0.01)

    mans = src.drain_requests(ship_state=True)
    assert src.kv.reserved_pages == 0            # drain leaks nothing
    dst = mk_engine()
    imp = PageImporter(dst.runtime)
    for man in mans:
        ok = imp.import_lane(man)
        assert ok == man.warm                    # fresh dst: warm lands
        if not ok:
            assert dst.submit(man.req)
        if not man.warm:
            continue
        rid = man.req.req_id
        entry = dst.kv.tables[rid]
        assert entry.length == man.cache_tokens
        # page-for-page byte equality, chain recomputed on the DST pool
        landed = dst_pool_payload(dst.runtime, entry.pages)
        prev = b""
        for rec, payload in zip(man.pages, landed):
            assert any("scale" in k for k in payload), \
                "int8 pool must ship its scale leaves"
            prev = _page_digest(prev, rec.tokens, assert_landed(rec, payload))
            assert prev == rec.digest
        for page in entry.pages:
            assert dst.kv.ref.get(page, 0) >= 1
        # cursor stamps restored: the lane resumes, it does not restart
        assert man.req.generated == man.generated
        assert list(man.req.output_tokens) == list(man.output_tokens)
    assert imp.verify_failures == 0

    run_out(dst, t)
    assert dst.kv.reserved_pages == 0
    done = {r.req_id: list(r.output_tokens) for r in reqs}
    assert done == base                          # token-identical merge


def test_round_trip_ships_head_dim_lanes_of_padded_pool():
    """head_dim 80 pools pad to 128 lanes on the device: a lane ships
    only its 80 K/V lanes per head, lands in them with zero pad lanes
    beside, and the destination finishes token-identical."""
    cfg = CFG.replace(attn=dataclasses.replace(CFG.attn, head_dim=80))
    mk = lambda: ServingEngine(cfg, max_slots=4, seq_cap=64, page_size=4,
                               seed=0, backend="paged", pool_pages=48,
                               chunk_tokens=8, attn_impl="ref")
    prompt = np.random.default_rng(5).integers(0, cfg.vocab_size, 11)
    ref = mk()
    base = mk_req(0, prompt)
    assert ref.submit(base)
    run_out(ref)
    src = mk()
    req = mk_req(0, prompt)
    assert src.submit(req)
    t = 0.0
    for _ in range(4):                           # prefill + some decode
        t += 0.01
        src.finalize_step(src.step(), t, t - 0.01)
    (man,) = src.drain_requests(ship_state=True)
    assert man.warm
    dst = mk()
    assert PageImporter(dst.runtime).import_lane(man)
    entry = dst.kv.tables[0]
    for rec, landed in zip(man.pages,
                           dst_pool_payload(dst.runtime, entry.pages)):
        assert {a.shape[-1] for k, a in rec.payload.items()} == {80}
        assert {a.shape[-1] for a in landed.values()} == {128}
        assert_landed(rec, landed)
    run_out(dst, t)
    assert list(req.output_tokens) == list(base.output_tokens)


# -------------------------------------------- corruption -> recompute
def _warm_manifest():
    """One decoding lane's manifest plus its (reset) request and the
    fault-free reference output."""
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, CFG.vocab_size, 13)
    base = reference_tokens([prompt])
    src = mk_engine()
    req = mk_req(0, prompt)
    assert src.submit(req)
    t = 0.0
    for _ in range(4):                           # prefill + some decode
        t += 0.01
        src.finalize_step(src.step(), t, t - 0.01)
    (man,) = src.drain_requests(ship_state=True)
    assert man.warm and len(man.pages) >= 2
    return man, req, base[0]


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_corrupted_byte_is_always_detected(data):
    """Flip ANY single byte of ANY shipped page leaf (or a token, or
    the digest itself): the importer must reject the whole lane before
    a byte lands — destination bit-identical to before the call."""
    man, _, _ = _warm_manifest()
    man = copy.deepcopy(man)
    p = data.draw(st.integers(0, len(man.pages) - 1))
    rec = man.pages[p]
    kind = data.draw(st.sampled_from(["payload", "token", "digest"]))
    if kind == "payload":
        key = data.draw(st.sampled_from(sorted(rec.payload)))
        arr = np.array(rec.payload[key], copy=True)
        flat = arr.view(np.uint8).reshape(-1)
        flat[data.draw(st.integers(0, flat.size - 1))] ^= 0xFF
        rec.payload[key] = arr
    elif kind == "token" and rec.tokens:
        i = data.draw(st.integers(0, len(rec.tokens) - 1))
        toks = list(rec.tokens)
        toks[i] ^= 1
        rec.tokens = tuple(toks)
    else:
        rec.digest = bytes(b ^ 0xFF for b in rec.digest)
    dst = mk_engine()
    imp = PageImporter(dst.runtime)
    assert imp.import_lane(man) is False
    assert imp.verify_failures == 1
    assert not dst.kv.tables                     # nothing landed
    assert dst.kv.reserved_pages == 0
    assert not dst.runtime.sched.active and not dst.runtime.sched.prefilling


def test_rejected_lane_recomputes_token_identically():
    """The fallback path end-to-end: a corrupted transfer degrades to
    the cold resubmit and regenerates EXACTLY the fault-free tokens —
    the worst a bad transfer can cost is latency, never a wrong
    token."""
    man, req, base_out = _warm_manifest()
    bad = copy.deepcopy(man)
    key = sorted(bad.pages[0].payload)[0]
    arr = np.array(bad.pages[0].payload[key], copy=True)
    arr.view(np.uint8).reshape(-1)[0] ^= 0xFF
    bad.pages[0].payload[key] = arr
    dst = mk_engine()
    imp = PageImporter(dst.runtime)
    assert imp.import_lane(bad) is False
    assert dst.submit(req)                       # recompute redrive
    run_out(dst)
    assert list(req.output_tokens) == base_out
    assert dst.kv.reserved_pages == 0


# ------------------------------------------------ prefix-page attach
def test_import_attaches_shared_prefix_pages():
    """A destination that already holds the lane's prompt prefix (its
    chain-hash index) adopts those pages by ref bump — zero copies for
    the shared run — and the resumed lane still finishes
    token-identical."""
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, CFG.vocab_size, 12)  # 3 full pages of 4
    dst = mk_engine()
    primer = mk_req(100, prompt)
    assert dst.submit(primer)
    run_out(dst)                                  # publishes the prefix
    assert dst.kv.prefix_index

    src = mk_engine()
    req = mk_req(0, prompt)
    assert src.submit(req)
    t = 0.0
    for _ in range(4):
        t += 0.01
        src.finalize_step(src.step(), t, t - 0.01)
    (man,) = src.drain_requests(ship_state=True)
    assert man.warm
    imp = PageImporter(dst.runtime)
    assert imp.import_lane(man)
    assert imp.attached_pages >= 2                # shared run adopted
    assert imp.copied_pages == len(man.pages) - imp.attached_pages
    entry = dst.kv.tables[0]
    assert entry.shared_tokens == imp.attached_pages * dst.kv.page_size
    run_out(dst)
    assert list(req.output_tokens) == list(primer.output_tokens)
    assert dst.kv.reserved_pages == 0


# -------------------------------------------------- fabric-aware price
def test_planner_prices_against_root_demand():
    """Transfer bandwidth is what the more contended root complex has
    left (per the ledger's demand bookkeeping), floored at ``min_frac``
    of capacity; without ledger/topology it falls back to raw
    capacity."""
    class _Fabric:
        pcie_capacity = 10e9

    class _Topo:
        def root_of(self, device):
            return device.split(":")[0]

    class _Ledger:
        def __init__(self, demand):
            self.demand = demand

        def root_demand(self, root):
            return self.demand.get(root, 0.0)

    man = LaneManifest(req=mk_req(0, np.arange(8)),
                       prompt_tokens=np.arange(8))
    man.cache_tokens = 8
    from repro.serving.migrate import PageRecord
    man.pages.append(PageRecord(src_page=0, tokens=(1, 2, 3, 4),
                                payload={"k": np.zeros(250_000, np.int8)},
                                digest=b"x"))
    ledger = _Ledger({"h0": 8e9, "h1": 0.0})
    pl = MigrationPlanner(fabric=_Fabric(), topo=_Topo(), ledger=ledger,
                          min_frac=0.1, setup_s=0.005)
    plan = pl.price([man], src_device="h0:g0", dst_device="h1:g0")
    assert plan.bandwidth == pytest.approx(2e9)   # h0 is the bottleneck
    assert plan.transfer_s == pytest.approx(0.005 + plan.bytes / 2e9)
    # saturated root: floored at min_frac, never starved
    pl2 = MigrationPlanner(fabric=_Fabric(), topo=_Topo(),
                           ledger=_Ledger({"h0": 50e9}), min_frac=0.1)
    assert pl2.price([man], "h0:g0", "h1:g0").bandwidth \
        == pytest.approx(1e9)
    # no ledger: raw capacity (single-host tests)
    assert MigrationPlanner(fabric=_Fabric()).price([man]).bandwidth \
        == pytest.approx(10e9)


# ------------------------------------------- exemplar parse-back
EX_RE = re.compile(
    r'gateway_door_ttft_seconds_bucket\{tenant="T1",le="([^"]+)"\}'
    r' (\S+)(?: # \{req_id="(\d+)"\} (\S+) (\S+))?')


def test_prometheus_exemplars_parse_back():
    """Histogram bucket lines carry the slowest retained req_id per
    bucket in OpenMetrics exemplar syntax — parse the exposition back
    and recover exactly the requests we observed."""
    eng = mk_engine()
    w = eng.metrics.latency
    assert isinstance(w, LatencyWindow)
    # three samples in one bucket (slowest must win), one far tail, and
    # one sample WITHOUT a req_id (must never become an exemplar)
    w.observe(1.0, 0.011, req_id=1)
    w.observe(2.0, 0.013, req_id=2)
    w.observe(3.0, 0.012, req_id=3)
    w.observe(4.0, 0.900, req_id=9)
    w.observe(5.0, 3.000)
    gw = Gateway({"T1": [eng]})
    text = gw.prometheus()
    seen = {}
    for le, count, rid, val, ts in EX_RE.findall(text):
        if rid:
            seen[le] = (int(rid), float(val), float(ts))
    # the 0.011/0.012/0.013 bucket: slowest (req 2) is the exemplar,
    # and it sits inside its own bucket's bounds
    le2 = next(le for le, (rid, _, _) in seen.items() if rid == 2)
    assert seen[le2] == (2, 0.013, 2.0)
    assert 0.013 <= float(le2)
    le9 = next(le for le, (rid, _, _) in seen.items() if rid == 9)
    assert seen[le9] == (9, 0.9, 4.0)
    # the req-id-less 3.0s sample landed in SOME bucket but no bucket
    # claims it as an exemplar
    assert not any(v == 3.0 for _, v, _ in seen.values())
    assert all(float(v) <= float(le) for le, (_, v, _) in seen.items()
               if le != "+Inf")

"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis
property tests, executed in interpret mode on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.paged_attention.ops import (paged_attention,
                                               paged_attention_mixed)
from repro.kernels.paged_attention.ref import (paged_attention_mixed_ref,
                                               paged_attention_ref)
from repro.kernels.rwkv6_scan.ops import rwkv6_scan
from repro.kernels.rwkv6_scan.ref import rwkv6_scan_ref
from repro.kernels.selective_scan.ops import selective_scan
from repro.kernels.selective_scan.ref import selective_scan_ref

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------- flash
@pytest.mark.parametrize("b,s,t,h,kv,hd,causal,window,cap,dtype", [
    (2, 128, 128, 4, 2, 64, True, 0, None, jnp.float32),
    (1, 256, 256, 8, 8, 128, True, 128, 50.0, jnp.float32),
    (2, 64, 192, 4, 1, 64, True, 0, None, jnp.float32),
    (1, 128, 128, 4, 4, 64, False, 0, None, jnp.float32),
    (1, 128, 128, 2, 2, 128, True, 0, None, jnp.bfloat16),
    (1, 384, 384, 4, 2, 64, True, 256, None, jnp.float32),
])
def test_flash_attention_allclose(b, s, t, h, kv, hd, causal, window, cap,
                                  dtype):
    q = jnp.asarray(RNG.standard_normal((b, s, h, hd)), dtype)
    k = jnp.asarray(RNG.standard_normal((b, t, kv, hd)), dtype)
    v = jnp.asarray(RNG.standard_normal((b, t, kv, hd)), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, softcap=cap,
                          block_q=64, block_k=64)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window,
                              softcap=cap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


@settings(max_examples=10, deadline=None)
@given(bq=st.sampled_from([32, 64, 128]), bk=st.sampled_from([32, 64, 128]),
       s=st.sampled_from([64, 128, 192]))
def test_flash_attention_block_shape_invariance(bq, bk, s):
    """Property: output is independent of the BlockSpec tiling."""
    q = jnp.asarray(RNG.standard_normal((1, s, 2, 64)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, s, 2, 64)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, s, 2, 64)), jnp.float32)
    a = flash_attention(q, k, v, block_q=bq, block_k=bk)
    b = flash_attention(q, k, v, block_q=64, block_k=64)
    # fp32 online-softmax reassociation differs across tilings: ~1e-4
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4,
                               atol=5e-4)


# ---------------------------------------------------------------- paged
@pytest.mark.parametrize("b,h,kv,hd,page,pps,npages", [
    (2, 4, 2, 64, 128, 4, 16),
    (4, 8, 8, 128, 128, 2, 8),
    (1, 4, 1, 64, 128, 8, 32),
    (3, 6, 2, 64, 256, 2, 6),
])
def test_paged_attention_allclose(b, h, kv, hd, page, pps, npages):
    q = jnp.asarray(RNG.standard_normal((b, h, hd)), jnp.float32)
    kp = jnp.asarray(RNG.standard_normal((npages, kv, page, hd)), jnp.float32)
    vp = jnp.asarray(RNG.standard_normal((npages, kv, page, hd)), jnp.float32)
    bt = jnp.asarray(RNG.integers(0, npages, (b, pps)), jnp.int32)
    lens = jnp.asarray(RNG.integers(1, pps * page, (b,)), jnp.int32)
    # impl="kernel" pins the Pallas kernel (interpret mode on CPU); the
    # default impl="auto" routes to the oracle off-TPU, which would make
    # this comparison vacuous
    out = paged_attention(q, kp, vp, bt, lens, impl="kernel")
    ref = paged_attention_ref(q, kp, vp, bt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3,
                               atol=2e-3)


@pytest.mark.parametrize("impl", ["kernel", "ref"])
def test_paged_attention_ignores_pages_beyond_length(impl):
    """Property: garbage in pages past `lengths` must not leak into output."""
    b, h, kv, hd, page, pps, npages = 1, 2, 2, 64, 128, 4, 8
    q = jnp.asarray(RNG.standard_normal((b, h, hd)), jnp.float32)
    kp = jnp.asarray(RNG.standard_normal((npages, kv, page, hd)), jnp.float32)
    vp = jnp.asarray(RNG.standard_normal((npages, kv, page, hd)), jnp.float32)
    bt = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
    lens = jnp.asarray([130], jnp.int32)
    out1 = paged_attention(q, kp, vp, bt, lens, impl=impl)
    kp2 = kp.at[2:].set(1e4)     # poison pages beyond length
    vp2 = vp.at[2:].set(-1e4)
    out2 = paged_attention(q, kp2, vp2, bt, lens, impl=impl)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-5)


@pytest.mark.parametrize("b,qn,h,kv,hd,page,pps,npages", [
    (2, 8, 4, 2, 64, 128, 4, 16),
    (3, 16, 8, 4, 128, 128, 2, 8),
    (1, 4, 4, 1, 64, 128, 8, 32),
])
def test_paged_attention_mixed_allclose(b, qn, h, kv, hd, page, pps, npages):
    """Ragged mixed rows (per-row causal positions, including pad rows at
    position 0): Pallas kernel (interpret) vs oracle."""
    q = jnp.asarray(RNG.standard_normal((b, qn, h, hd)), jnp.float32)
    kp = jnp.asarray(RNG.standard_normal((npages, kv, page, hd)), jnp.float32)
    vp = jnp.asarray(RNG.standard_normal((npages, kv, page, hd)), jnp.float32)
    bt = jnp.asarray(RNG.integers(0, npages, (b, pps)), jnp.int32)
    # lane 0: a prefill-style run of consecutive positions; other lanes:
    # random valid positions with trailing pad rows at 0
    qpos = RNG.integers(0, pps * page, (b, qn)).astype(np.int32)
    qpos[0] = np.arange(qn) + RNG.integers(0, pps * page - qn)
    qpos[:, qn - qn // 2:] = 0                      # pad-row tail
    qpos = jnp.asarray(qpos)
    out = paged_attention_mixed(q, kp, vp, bt, qpos, impl="kernel")
    ref = paged_attention_mixed_ref(q, kp, vp, bt, qpos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3,
                               atol=2e-3)


def test_paged_attention_mixed_q1_matches_decode():
    """Property: the ragged path with q_len=1 IS the decode path."""
    b, h, kv, hd, page, pps, npages = 2, 4, 2, 64, 128, 4, 16
    q = jnp.asarray(RNG.standard_normal((b, h, hd)), jnp.float32)
    kp = jnp.asarray(RNG.standard_normal((npages, kv, page, hd)), jnp.float32)
    vp = jnp.asarray(RNG.standard_normal((npages, kv, page, hd)), jnp.float32)
    bt = jnp.asarray(RNG.integers(0, npages, (b, pps)), jnp.int32)
    lens = jnp.asarray([200, 400], jnp.int32)
    dec = paged_attention(q, kp, vp, bt, lens, impl="ref")
    mix = paged_attention_mixed(q[:, None], kp, vp, bt,
                                (lens - 1)[:, None], impl="ref")
    np.testing.assert_allclose(np.asarray(mix[:, 0]), np.asarray(dec),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("impl", ["kernel", "ref"])
def test_paged_attention_mixed_causal_within_chunk(impl):
    """Garbage at key slots PAST a row's position must not leak into that
    row — the in-page-walk causal mask (poisoning slots past position p
    leaves rows <= p bit-identical)."""
    b, qn, h, kv, hd, page, pps, npages = 1, 4, 2, 2, 64, 128, 2, 4
    q = jnp.asarray(RNG.standard_normal((b, qn, h, hd)), jnp.float32)
    kp = jnp.asarray(RNG.standard_normal((npages, kv, page, hd)), jnp.float32)
    vp = jnp.asarray(RNG.standard_normal((npages, kv, page, hd)), jnp.float32)
    bt = jnp.asarray([[0, 1]], jnp.int32)
    qpos = jnp.asarray([[60, 61, 62, 63]], jnp.int32)
    out1 = paged_attention_mixed(q, kp, vp, bt, qpos, impl=impl)
    kp2 = kp.at[0, :, 64:].set(1e4).at[1].set(1e4)  # poison past pos 63
    vp2 = vp.at[0, :, 64:].set(-1e4).at[1].set(-1e4)
    out2 = paged_attention_mixed(q, kp2, vp2, bt, qpos, impl=impl)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-5)


@pytest.mark.parametrize("impl", ["kernel", "ref"])
def test_paged_attention_int8_pages_close(impl):
    """int8 pages + per-page-row scales stay close to the fp path."""
    b, qn, h, kv, hd, page, pps, npages = 2, 4, 4, 2, 64, 128, 2, 8
    q = jnp.asarray(RNG.standard_normal((b, qn, h, hd)), jnp.float32)
    kp = jnp.asarray(RNG.standard_normal((npages, kv, page, hd)), jnp.float32)
    vp = jnp.asarray(RNG.standard_normal((npages, kv, page, hd)), jnp.float32)
    bt = jnp.asarray(RNG.integers(0, npages, (b, pps)), jnp.int32)
    qpos = jnp.asarray(RNG.integers(0, pps * page, (b, qn)), jnp.int32)

    def quant(p):
        s = np.abs(np.asarray(p)).max(-1) / 127.0 + 1e-8
        iv = np.clip(np.round(np.asarray(p) / s[..., None]), -127, 127)
        # scales ride [P, page, KV]: one row of every head's per token
        return (jnp.asarray(iv.astype(np.int8)),
                jnp.asarray(s.swapaxes(1, 2), jnp.float32))

    kq, ks = quant(kp)
    vq, vs = quant(vp)
    fp = paged_attention_mixed(q, kp, vp, bt, qpos, impl=impl)
    i8 = paged_attention_mixed(q, kq, vq, bt, qpos, impl=impl,
                               k_scales=ks, v_scales=vs)
    np.testing.assert_allclose(np.asarray(i8), np.asarray(fp), rtol=0.05,
                               atol=0.05)


def test_paged_attention_bucketed_width_invariance():
    """Property: narrowing the block table to the live pages (the
    runtime's width bucketing) must not change the output."""
    b, h, kv, hd, page, npages = 2, 4, 2, 64, 128, 8
    q = jnp.asarray(RNG.standard_normal((b, h, hd)), jnp.float32)
    kp = jnp.asarray(RNG.standard_normal((npages, kv, page, hd)), jnp.float32)
    vp = jnp.asarray(RNG.standard_normal((npages, kv, page, hd)), jnp.float32)
    bt = jnp.asarray(RNG.integers(0, npages, (b, 4)), jnp.int32)
    lens = jnp.asarray([100, 200], jnp.int32)    # <= 2 pages live
    wide = paged_attention(q, kp, vp, bt, lens, impl="ref")
    narrow = paged_attention(q, kp, vp, bt[:, :2], lens, impl="ref")
    np.testing.assert_allclose(np.asarray(wide), np.asarray(narrow),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("case", ["lanes", "pad_lane", "q_block", "int8",
                                  "short_walk"])
def test_paged_attention_mixed_walks_only_live_pages(case):
    """Each lane walks its table only up to its last live page
    (``max(position) // page``), and the grid's page axis only up to the
    longest lane's: pages named past it, in a table wider than any lane
    needs, are never read, so NaN and +inf there leave the output finite
    and bit for bit that of the unpoisoned run."""
    h, kv, hd, page, pps = 4, 2, 64, 16, 8
    if case == "lanes":        # Q=1 lanes ending on pages 0, 2, 5
        qpos = [[3], [40], [95]]
    elif case == "pad_lane":   # a pad lane at position 0 beside a live one
        qpos = [[0], [70]]
    elif case == "q_block":    # one lane's rows end on pages 1, 2 and 3
        qpos = [[30, 31, 32, 33, 47, 48, 63, 0]]
    elif case == "short_walk":  # every lane within 2 of the table's 8 pages
        qpos = [[5], [20], [0]]
    else:                      # int8 pools: poisoned pages and scales
        qpos = [[17, 18], [50, 0], [0, 0]]
    qpos = np.asarray(qpos, np.int32)
    b, qn = qpos.shape
    last = qpos.max(axis=1) // page
    # lane i's live slots name clean pages; every slot past its last live
    # page names a page of the poisoned half of the pool
    npages = 2 * b * pps
    bt = np.arange(b * pps, dtype=np.int32).reshape(b, pps)
    dead = np.arange(pps)[None] > last[:, None]
    bt = np.where(dead, bt + b * pps, bt)
    q = jnp.asarray(RNG.standard_normal((b, qn, h, hd)), jnp.float32)
    kp = RNG.standard_normal((npages, kv, page, hd)).astype(np.float32)
    vp = RNG.standard_normal((npages, kv, page, hd)).astype(np.float32)
    bad = slice(b * pps, None)
    kwargs, poisoned = {}, {}
    if case == "int8":
        ks = np.abs(kp).max(-1) / 127.0
        vs = np.abs(vp).max(-1) / 127.0
        kp = np.round(kp / ks[..., None]).astype(np.int8)
        vp = np.round(vp / vs[..., None]).astype(np.int8)
        ks, vs = ks.swapaxes(1, 2), vs.swapaxes(1, 2)   # [P, page, KV]
        kwargs = dict(k_scales=jnp.asarray(ks), v_scales=jnp.asarray(vs))
        ks2, vs2 = ks.copy(), vs.copy()
        ks2[bad], vs2[bad] = np.nan, np.inf
        poisoned = dict(k_scales=jnp.asarray(ks2), v_scales=jnp.asarray(vs2))
        kp2, vp2 = kp.copy(), vp.copy()
        kp2[bad], vp2[bad] = 127, -127
    else:
        kp2, vp2 = kp.copy(), vp.copy()
        kp2[bad], vp2[bad] = np.nan, np.inf
        vp2[bad, :, ::2] = np.nan
    kp, vp, kp2, vp2, bt, qpos = map(jnp.asarray, (kp, vp, kp2, vp2, bt,
                                                   qpos))
    clean = paged_attention_mixed(q, kp, vp, bt, qpos, impl="kernel",
                                  **kwargs)
    dirty = paged_attention_mixed(q, kp2, vp2, bt, qpos, impl="kernel",
                                  **(poisoned or kwargs))
    assert np.isfinite(np.asarray(dirty)).all()
    np.testing.assert_array_equal(np.asarray(dirty), np.asarray(clean))
    ref = paged_attention_mixed_ref(q, kp, vp, bt, qpos, **kwargs)
    np.testing.assert_allclose(np.asarray(clean), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("hd", [80, 128])
@pytest.mark.parametrize("pool_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("layer", [0, 2])
def test_paged_attention_stacked_pool_matches_unstacked(hd, pool_dtype,
                                                        layer):
    """A stack of three layers' lane-dense pools ``[L, P, KV, page,
    hd_pool]`` (``hd_pool`` = ``hd`` rounded up to 128 lanes, zero past
    ``hd``; int8 scales ``[L, P, page, 128]``), read at a traced layer
    index, gives the kernel's outputs for that layer's unstacked,
    unpadded pages ``[P, KV, page, hd]``, and the oracle's."""
    nl, b, qn, h, kv, page, pps, npages = 3, 3, 2, 4, 2, 16, 4, 10
    hd_pool = -(-hd // 128) * 128
    q = jnp.asarray(RNG.standard_normal((b, qn, h, hd)), jnp.bfloat16)
    kp = RNG.standard_normal((nl, npages, kv, page, hd)).astype(np.float32)
    vp = RNG.standard_normal((nl, npages, kv, page, hd)).astype(np.float32)
    bt = jnp.asarray(RNG.integers(0, npages, (b, pps)), jnp.int32)
    qpos = jnp.asarray(RNG.integers(0, pps * page, (b, qn)), jnp.int32)
    one, stack = {}, {}
    if pool_dtype == "int8":
        ks = np.abs(kp).max(-1) / 127.0 + 1e-8         # [L, P, KV, page]
        vs = np.abs(vp).max(-1) / 127.0 + 1e-8
        kp = np.round(kp / ks[..., None]).astype(np.int8)
        vp = np.round(vp / vs[..., None]).astype(np.int8)
        ks, vs = ks.swapaxes(2, 3), vs.swapaxes(2, 3)  # [L, P, page, KV]
        one = dict(k_scales=jnp.asarray(ks[layer]),
                   v_scales=jnp.asarray(vs[layer]))
        lanes = ((0, 0),) * 3 + ((0, 128 - kv),)
        stack = dict(k_scales=jnp.asarray(np.pad(ks, lanes)),
                     v_scales=jnp.asarray(np.pad(vs, lanes)))
    else:
        kp = kp.astype(jnp.bfloat16)
        vp = vp.astype(jnp.bfloat16)
    lanes = ((0, 0),) * 4 + ((0, hd_pool - hd),)
    kst, vst = jnp.asarray(np.pad(kp, lanes)), jnp.asarray(np.pad(vp, lanes))
    unstacked = paged_attention_mixed(q, jnp.asarray(kp[layer]),
                                      jnp.asarray(vp[layer]), bt, qpos,
                                      impl="kernel", **one)
    stacked = paged_attention_mixed(q, kst, vst, bt, qpos,
                                    layer=jnp.int32(layer), impl="kernel",
                                    **stack)
    ref = paged_attention_mixed(q, kst, vst, bt, qpos,
                                layer=jnp.int32(layer), impl="ref", **stack)
    assert stacked.shape == (b, qn, h, hd)
    # the pad lanes add exact zeros: the same outputs bit for bit
    np.testing.assert_array_equal(np.asarray(stacked), np.asarray(unstacked))
    np.testing.assert_allclose(np.asarray(stacked, np.float32),
                               np.asarray(ref, np.float32), rtol=2e-2,
                               atol=2e-2)


# ------------------------------------------------------------- sel. scan
@pytest.mark.parametrize("b,s,d,n,block_d,chunk", [
    (2, 64, 128, 16, 64, 32),
    (1, 256, 256, 8, 128, 64),
    (1, 96, 64, 4, 64, 96),
])
def test_selective_scan_allclose(b, s, d, n, block_d, chunk):
    x = jnp.asarray(RNG.standard_normal((b, s, d)) * 0.5, jnp.float32)
    dt = jnp.asarray(np.abs(RNG.standard_normal((b, s, d))) * 0.1, jnp.float32)
    a = jnp.asarray(-np.abs(RNG.standard_normal((d, n))) - 0.1, jnp.float32)
    bb = jnp.asarray(RNG.standard_normal((b, s, n)) * 0.5, jnp.float32)
    c = jnp.asarray(RNG.standard_normal((b, s, n)) * 0.5, jnp.float32)
    dd = jnp.asarray(RNG.standard_normal((d,)), jnp.float32)
    h0 = jnp.asarray(RNG.standard_normal((b, d, n)) * 0.1, jnp.float32)
    y, hf = selective_scan(x, dt, a, bb, c, dd, h0, block_d=block_d,
                           chunk=chunk)
    yr, hr = selective_scan_ref(x, dt, a, bb, c, dd, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hr), rtol=1e-4,
                               atol=1e-4)


def test_selective_scan_chunk_boundary_state_continuity():
    """Property: chunked scan == two half-scans chained via state."""
    b, s, d, n = 1, 64, 32, 8
    x = jnp.asarray(RNG.standard_normal((b, s, d)) * 0.5, jnp.float32)
    dt = jnp.asarray(np.abs(RNG.standard_normal((b, s, d))) * 0.1, jnp.float32)
    a = jnp.asarray(-np.abs(RNG.standard_normal((d, n))) - 0.1, jnp.float32)
    bb = jnp.asarray(RNG.standard_normal((b, s, n)) * 0.5, jnp.float32)
    c = jnp.asarray(RNG.standard_normal((b, s, n)) * 0.5, jnp.float32)
    dd = jnp.asarray(RNG.standard_normal((d,)), jnp.float32)
    y, hf = selective_scan(x, dt, a, bb, c, dd, chunk=16)
    y1, h1 = selective_scan(x[:, :32], dt[:, :32], a, bb[:, :32], c[:, :32],
                            dd, chunk=16)
    y2, h2 = selective_scan(x[:, 32:], dt[:, 32:], a, bb[:, 32:], c[:, 32:],
                            dd, h1, chunk=16)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(hf), rtol=1e-4,
                               atol=1e-4)


# ------------------------------------------------------------------ rwkv
@pytest.mark.parametrize("b,s,h,hd,chunk", [
    (2, 64, 4, 32, 32),
    (1, 96, 2, 64, 48),
    (1, 33, 1, 32, 16),   # ragged chunk boundary
])
def test_rwkv6_scan_allclose(b, s, h, hd, chunk):
    if s % chunk:
        pytest.skip("kernel requires chunk | seq (padding handled by caller)")
    r = jnp.asarray(RNG.standard_normal((b, s, h, hd)) * 0.5, jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, h, hd)) * 0.5, jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, h, hd)) * 0.5, jnp.float32)
    w = jnp.asarray(0.45 + 0.5 / (1 + np.exp(-RNG.standard_normal((b, s, h, hd)))),
                    jnp.float32)
    u = jnp.asarray(RNG.standard_normal((h, hd)) * 0.5, jnp.float32)
    s0 = jnp.asarray(RNG.standard_normal((b, h, hd, hd)) * 0.1, jnp.float32)
    y, sf = rwkv6_scan(r, k, v, w, u, s0, chunk=chunk)
    yr, sr = rwkv6_scan_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sr), rtol=1e-4,
                               atol=1e-4)


def test_rwkv6_matches_model_lax_scan():
    """The Pallas kernel and the model's lax.scan implement one recurrence."""
    from repro.configs.base import get_config, reduced
    from repro.models import rwkv as rwkv_mod
    cfg = reduced(get_config("rwkv6_1_6b"))
    heads, hd = cfg.d_model // cfg.rwkv.head_dim, cfg.rwkv.head_dim
    b, s = 1, 32
    r = jnp.asarray(RNG.standard_normal((b, s, heads, hd)) * 0.3, jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, heads, hd)) * 0.3, jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, heads, hd)) * 0.3, jnp.float32)
    w = jnp.asarray(0.5 + 0.4 / (1 + np.exp(-RNG.standard_normal((b, s, heads, hd)))),
                    jnp.float32)
    u = jnp.asarray(RNG.standard_normal((heads, hd)) * 0.3, jnp.float32)
    y_kernel, _ = rwkv6_scan(r, k, v, w, u, chunk=16)
    y_ref, _ = rwkv6_scan_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)

"""Pure-jnp oracle for paged attention (ragged mixed prefill+decode).

The general entry point is :func:`paged_attention_mixed_ref`: every batch
lane carries ``q_len >= 1`` query rows (a decode lane is ``q_len=1``, a
prefill chunk is ``q_len=chunk``) and a per-row *sequence position*;
causality is enforced inside the page walk by masking every key slot past
the row's position.  The classic single-token decode oracle
(:func:`paged_attention_ref`) is the ``q_len=1`` special case.

Pools are ``[P, KV, page, hd_pool]`` (the KV-head axis ahead of the
page axis, the layout the TPU kernel tiles), or a stack of layers
``[L, P, KV, page, hd_pool]`` read at ``layer``; lanes past the head dim
of q are zero pad and are dropped.  Pages may optionally be
int8-quantized with per-page-row scales (``[(L,) P, page, KV]``, the KV
axis possibly padded): gathered pages are dequantized before the score
matmul, so only the pages a lane actually touches pay the dequant.
"""
from __future__ import annotations

import jax.numpy as jnp

_NEG_INF = -2.0e38


def _gather_pages(pages, block_tables, scales, out_dtype):
    """pages[block_tables] -> [B, PPS*page, KV, hd], dequantized."""
    b, pps = block_tables.shape
    kv, page, hd = pages.shape[1], pages.shape[2], pages.shape[3]
    g = pages[block_tables].astype(jnp.float32)  # [B, PPS, KV, page, hd]
    if scales is not None:                       # [P, page, KV(+pad)]
        sc = scales[block_tables][..., :kv].swapaxes(-1, -2)
        g = g * sc.astype(jnp.float32)[..., None]
    g = g.transpose(0, 1, 3, 2, 4).reshape(b, pps * page, kv, hd)
    return g.astype(out_dtype)


def paged_attention_mixed_ref(q, k_pages, v_pages, block_tables, q_positions,
                              *, layer=0, scale=None, k_scales=None,
                              v_scales=None):
    """Ragged multi-row attention over a paged KV cache.

    q            [B, Q, H, hd]      (Q query rows per lane; pad rows are
                                     harmless — give them position 0)
    k_pages      [P, KV, page, hd_pool]  (global page pool; int8 if
                                     *_scales), or [L, P, ...] read at
                                     ``layer``; hd_pool >= hd
    v_pages      [P, KV, page, hd_pool]
    block_tables [B, PPS] int32     (page ids per sequence)
    q_positions  [B, Q] int32       (sequence position of each query row;
                                     row i attends key slots t <= pos[i])
    k_scales     [P, page, KV] f32  (optional int8 per-page-row scales;
                                     [L, P, page, KV] with a stack; KV
                                     may carry pad lanes)
    v_scales     [P, page, KV] f32
    Returns      [B, Q, H, hd]
    """
    if k_pages.ndim == 5:
        k_pages, v_pages = k_pages[layer], v_pages[layer]
        if k_scales is not None:
            k_scales, v_scales = k_scales[layer], v_scales[layer]
    b, qn, h, hd = q.shape
    k_pages, v_pages = k_pages[..., :hd], v_pages[..., :hd]
    kv = k_pages.shape[1]
    g = h // kv
    if scale is None:
        scale = 1.0 / float(hd) ** 0.5
    k = _gather_pages(k_pages, block_tables, k_scales, jnp.float32)
    v = _gather_pages(v_pages, block_tables, v_scales, jnp.float32)
    t = k.shape[1]
    qg = q.reshape(b, qn, kv, g, hd)
    s = jnp.einsum("bqkgh,btkh->bkgqt", qg.astype(jnp.float32), k) * scale
    pos_k = jnp.arange(t, dtype=jnp.int32)
    mask = pos_k[None, None] <= q_positions[:, :, None]      # [B, Q, T]
    mask = mask[:, None, None]                               # [B,1,1,Q,T]
    s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(mask, p, 0.0)
    p = p / (jnp.sum(p, axis=-1, keepdims=True) + 1e-30)
    out = jnp.einsum("bkgqt,btkh->bqkgh", p, v)
    return out.reshape(b, qn, h, hd).astype(q.dtype)


def paged_attention_ref(q, k_pages, v_pages, block_tables, lengths, *,
                        scale=None, k_scales=None, v_scales=None):
    """Single-token decode attention over a paged KV cache (q_len=1 case).

    q            [B, H, hd]
    lengths      [B] int32  (tokens in each sequence; >= 1)
    Returns      [B, H, hd]
    """
    out = paged_attention_mixed_ref(
        q[:, None], k_pages, v_pages, block_tables,
        (lengths - 1)[:, None].astype(jnp.int32), scale=scale,
        k_scales=k_scales, v_scales=v_scales)
    return out[:, 0]

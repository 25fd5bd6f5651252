"""Pallas TPU paged attention (the vLLM-style serving hot spot), ragged.

One kernel serves the whole fused mixed prefill+decode step: every batch
lane carries a block of ``Q`` query rows (a decode lane uses one live row,
a prefill chunk uses ``chunk`` rows; pad rows are masked by position) and
causality is enforced *inside the page walk* — key slot ``t`` of the
gathered pages contributes to query row ``i`` only when
``t <= q_positions[lane, i]``.

Layouts (chosen for the TPU's (8, 128) tiling rule, which a block's last
two dims must meet or span whole):

  * page pools are stacks of layers, ``[L, P, KV, page, hd_pool]`` — the
    KV-head axis ahead of the page axis, so one block takes every head of
    a page and each head's ``[page, hd_pool]`` tile is a whole trailing
    pair.  The layer to read is a scalar-prefetched operand, so a caller
    holding every layer's pool in one array reads it in place, with no
    per-layer slice.  ``hd_pool`` may exceed the head dim of q: the lanes
    past it hold zeros, q is zero-padded to match (a zero lane adds
    nothing to ``q . k``) and the output's pad lanes are dropped.  A
    single layer's ``[P, KV, page, hd_pool]`` pool is read as a stack of
    one;
  * int8 scales are ``[L, P, page, KV]`` (KV may carry pad lanes), one
    row of every head's scale per token slot, so a step writes a token's
    scales as one row; a head's ``[page, 1]`` column dequantizes its
    gathered page rows in f32, with no in-kernel relayout;
  * q is regrouped to ``[B, KV, Q*G, hd_pool]`` outside the kernel (row
    ``r = qi*G + gi``), and the per-row causal bound rides as an int32
    ``[B, Q*G, 1]`` column.

Page gathering is done through the BlockSpec index map driven by a
scalar-prefetched block table (PrefetchScalarGridSpec): the DMA engine
resolves the gather ahead of compute.  Pages stream over the innermost
grid dimension with a per-head online-softmax accumulator in VMEM
scratch; each head is a 2-D ``[Q*G, hd] x [hd, page]`` product with f32
accumulation.

Grid: (batch, walk), pages innermost, where ``walk`` is a dynamic grid
bound: the longest lane's live pages, ``max(last) + 1``.  A table wider
than every live sequence therefore costs no grid steps, and a caller may
hand its tables over at full width, one compiled shape for every context
length.  Each lane walks only its live pages: a second scalar-prefetched
operand holds the lane's last live page slot,
``max(q_positions[lane]) // page``.  The page index map
is clamped at that slot, so past it the block index repeats and the
pipeline issues no further DMA for the lane, and the accumulation is
skipped there under ``pl.when``.  A page past every row's position is
fully masked and would leave the accumulator bit for bit unchanged, so
the skip changes no output bit; table entries past a lane's last page
are never read.  The positional mask still covers the partial last page
and the rows of a Q>1 lane that end on earlier pages.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -2.0e38


def _mixed_kernel(tables_ref, last_ref, layer_ref, qpos_ref, q_ref, k_ref,
                  v_ref, *rest, scale: float, page: int, quant: bool):
    if quant:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    bi, pi = pl.program_id(0), pl.program_id(1)
    kv_heads, rows = q_ref.shape[1], q_ref.shape[2]

    @pl.when(pi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(pi <= last_ref[bi])        # past it the page is all masked
    def _accumulate():
        pos_k = pi * page + jax.lax.broadcasted_iota(jnp.int32,
                                                     (rows, page), 1)
        mask = pos_k <= qpos_ref[0]                   # [rows, page]
        for h in range(kv_heads):
            q = q_ref[0, h]                           # [rows, hd]
            k = k_ref[0, 0, h]                        # [page, hd]
            v = v_ref[0, 0, h]
            if quant:
                # dequantize by the page rows' scales, a [page, 1] column
                q = q.astype(jnp.float32)
                k = k.astype(jnp.float32) * ks_ref[0, 0, :, h:h + 1]
                v = v.astype(jnp.float32) * vs_ref[0, 0, :, h:h + 1]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(mask, s, _NEG_INF)

            m_prev = m_ref[h]                         # [rows, 1]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            m_ref[h] = m_cur
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(pi == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / (l_ref[...] + 1e-30)).astype(o_ref.dtype)


def paged_attention_mixed(q, k_pages, v_pages, block_tables, q_positions, *,
                          layer=0, scale=None, interpret: bool = False,
                          k_scales=None, v_scales=None):
    """q: [B,Q,H,hd]; pages: [L,P,KV,page,hd_pool] read at ``layer`` (a
    traced int32 scalar), or one layer's [P,KV,page,hd_pool];
    hd_pool >= hd, its lanes past hd zero; tables: [B,PPS];
    q_positions: [B,Q] (per-row sequence position, causal bound);
    k_scales/v_scales: [L,P,page,KV] (or [P,page,KV]) when the pages are
    int8.  ``scale`` defaults to 1/sqrt(hd), the head dim of q."""
    if k_pages.ndim == 4:
        k_pages, v_pages = k_pages[None], v_pages[None]
        if k_scales is not None:
            k_scales, v_scales = k_scales[None], v_scales[None]
    b, qn, h, hd = q.shape
    kv, page, hd_pool = k_pages.shape[2:]
    g = h // kv
    rows = qn * g
    pps = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / float(hd) ** 0.5
    quant = k_scales is not None
    qr = q.reshape(b, qn, kv, g, hd).transpose(0, 2, 1, 3, 4)
    qr = qr.reshape(b, kv, rows, hd)
    if hd_pool > hd:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, 0), (0, hd_pool - hd)))
    qpos = jnp.repeat(q_positions.astype(jnp.int32), g, axis=1)[..., None]
    # each lane's last live page slot: a pad lane (position 0) keeps slot 0
    last = jnp.clip(jnp.max(q_positions, axis=1) // page, 0, pps - 1)
    last = last.astype(jnp.int32)
    walk = jnp.max(last) + 1            # page steps: the longest lane's
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def at_lane(bi, pi, tables, last, layer):
        return (bi, 0, 0, 0)

    def page_of(bi, pi, tables, last):
        # clamped: past the last live slot the block repeats, so no DMA
        return tables[bi, jnp.minimum(pi, last[bi])]

    def at_page(bi, pi, tables, last, layer):
        return (layer[0], page_of(bi, pi, tables, last), 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, rows, 1),
                     lambda bi, pi, tables, last, layer: (bi, 0, 0)),
        pl.BlockSpec((1, kv, rows, hd_pool), at_lane),
        pl.BlockSpec((1, 1, kv, page, hd_pool), at_page),
        pl.BlockSpec((1, 1, kv, page, hd_pool), at_page),
    ]
    inputs = [block_tables, last, layer, qpos, qr, k_pages, v_pages]
    if quant:
        # scales stream next to their pages through the same gather
        spec = pl.BlockSpec((1, 1, page, k_scales.shape[-1]),
                            lambda bi, pi, tables, last, layer: (
                                layer[0], page_of(bi, pi, tables, last),
                                0, 0))
        in_specs += [spec, spec]
        inputs += [k_scales.astype(jnp.float32),
                   v_scales.astype(jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, walk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kv, rows, hd_pool), at_lane),
        scratch_shapes=[
            pltpu.VMEM((kv, rows, hd_pool), jnp.float32),
            pltpu.VMEM((kv, rows, 1), jnp.float32),
            pltpu.VMEM((kv, rows, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_mixed_kernel, scale=scale, page=page,
                               quant=quant)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, rows, hd_pool), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_attention_mixed",
    )(*inputs)
    out = out[..., :hd].reshape(b, kv, qn, g, hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, qn, h, hd)


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    scale=None, interpret: bool = False,
                    k_scales=None, v_scales=None):
    """Single-token decode: q [B,H,hd], lengths [B] — the q_len=1 case."""
    qpos = (lengths - 1)[:, None].astype(jnp.int32)
    out = paged_attention_mixed(q[:, None], k_pages, v_pages, block_tables,
                                qpos, scale=scale, interpret=interpret,
                                k_scales=k_scales, v_scales=v_scales)
    return out[:, 0]

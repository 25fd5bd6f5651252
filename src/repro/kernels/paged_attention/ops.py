"""Jit'd public wrappers for paged attention (decode and ragged mixed).

These are the entry points the paged serving runtime calls each step with
*real* per-sequence block tables built from the ``PagedKVCache`` page
tables.  ``paged_attention_mixed`` is the fused-step form: every lane
carries ``Q`` query rows with per-row sequence positions (decode lanes use
one live row, prefill chunks use ``chunk`` rows) and causality is enforced
inside the page walk.  ``paged_attention`` keeps the classic q_len=1
decode contract on top of it.

``impl`` selects the execution path:

  * ``"auto"``   — Pallas kernel on TPU, pure-jnp oracle elsewhere (the
                   oracle is the fast CPU fallback; the interpreted kernel
                   is ~100x slower than the oracle on CPU);
  * ``"kernel"`` — always the Pallas kernel (interpret mode off-TPU), used
                   by the parity tests and kernel benchmarks;
  * ``"ref"``    — always the pure-jnp oracle.

Contract expected by both paths: ``block_tables`` may be narrower than the
maximum pages-per-sequence (the runtime buckets the width to the longest
live sequence so attention cost tracks live tokens, not the seq cap),
every table entry up to a lane's last live page (``max(q_positions of
the lane) // page``) must be a valid page index, and every query row's
position must map to a key slot whose page holds real data (pad rows are
given position 0, which reads the lane's first slot — written for any
live lane — and their output is discarded by the caller).  The kernel
walks each lane only up to that last page and never reads the entries
past it; the oracle gathers the whole table and masks it, so there
they must still name pages in range that hold finite values.  When the
page pools are int8, ``k_scales``/``v_scales`` carry the per-page-row
dequantization scales ``[P, KV, page]``.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.paged_attention.kernel import (
    paged_attention as _kernel,
    paged_attention_mixed as _kernel_mixed,
)
from repro.kernels.paged_attention.ref import (
    paged_attention_mixed_ref,
    paged_attention_ref,
)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("scale", "impl", "interpret"))
def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    scale=None, impl: str = "auto", interpret: bool = False,
                    k_scales=None, v_scales=None):
    """q: [B,H,hd]; pages: [P,KV,page,hd]; tables: [B,PPS]; lengths: [B]."""
    if impl not in ("auto", "kernel", "ref"):
        raise ValueError(f"unknown paged_attention impl {impl!r}")
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return paged_attention_ref(q, k_pages, v_pages, block_tables, lengths,
                                   scale=scale, k_scales=k_scales,
                                   v_scales=v_scales)
    return _kernel(q, k_pages, v_pages, block_tables, lengths, scale=scale,
                   interpret=interpret or not _on_tpu(),
                   k_scales=k_scales, v_scales=v_scales)


@functools.partial(jax.jit, static_argnames=("scale", "impl", "interpret"))
def paged_attention_mixed(q, k_pages, v_pages, block_tables, q_positions, *,
                          scale=None, impl: str = "auto",
                          interpret: bool = False,
                          k_scales=None, v_scales=None):
    """q: [B,Q,H,hd]; q_positions: [B,Q] per-row sequence positions."""
    if impl not in ("auto", "kernel", "ref"):
        raise ValueError(f"unknown paged_attention impl {impl!r}")
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return paged_attention_mixed_ref(
            q, k_pages, v_pages, block_tables, q_positions, scale=scale,
            k_scales=k_scales, v_scales=v_scales)
    return _kernel_mixed(q, k_pages, v_pages, block_tables, q_positions,
                         scale=scale, interpret=interpret or not _on_tpu(),
                         k_scales=k_scales, v_scales=v_scales)


__all__ = ["paged_attention", "paged_attention_mixed",
           "paged_attention_ref", "paged_attention_mixed_ref"]

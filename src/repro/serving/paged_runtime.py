"""Block-table-driven paged serving runtime (the vLLM-style serving core).

Where the dense ``ServingEngine`` path stores KV in a ``[max_slots,
seq_cap]`` slot cache, this runtime keeps every attention layer's KV in a
fixed pool of ``page_size``-token pages (plus one trash page for masked
lanes) and addresses it through per-sequence block tables owned by
``PagedKVCache``.  Decode memory therefore scales with *live tokens*, the
pool can be overcommitted (admission never reserves prompt+max_new up
front), and the block-table width handed to the attention kernel is
bucketed to the longest live sequence, so per-step attention cost tracks
live context rather than ``max_slots x seq_cap``.

ONE forward pass, pure and jitted — the **fused mixed step**: the batch
is the FLATTENED token stream of the step (the vLLM ragged-batch layout):
every decode lane contributes one row, every prefill chunk contributes
``chunk`` rows, all packed back to back under the scheduler's per-step
token budget (``PagedScheduler.plan()``).  Each row carries its own
sequence position and its lane's block table; the rows' K/V are scattered
into the pages, then every row attends its pages through
``kernels/paged_attention/ops.paged_attention_mixed`` with causal masking
*inside the page walk* (a chunk row sees its own chunk's earlier rows
because the scatter lands before the gather and the mask is positional).
Because decode lanes ride in the same call as prefill chunks, an admitted
prompt never stalls the decode lanes — it only consumes the prefill share
of the step budget — which is what keeps ITL tails flat under admission
churn; and because the batch is packed, step cost tracks REAL tokens
(8 decodes + a 64-token chunk cost ~72 rows, not lanes x max-chunk
padding).  Row counts are bucketed (pow2 then /16 granules) so the jit
shape set stays bounded; pad rows write to the trash page and carry
position 0, so they read one valid slot and their output is discarded.

Page pools may be int8 (``kv_dtype="int8"``): K/V rows are quantized
per-row on scatter with the scales stored in parallel per-page-row pools,
and both attention paths dequantize only the gathered pages.

Prefix-cache sharing (``prefix_cache=True``) lives in ``PagedKVCache``:
prompts sharing a page-aligned prefix map it to existing pages and skip
that prefill compute entirely — see ``serving/kvcache.py``.

Speculative multi-token decode lanes (``spec_k > 0``): the scheduler's
n-gram/prompt-lookup drafter attaches up to k proposed tokens to a decode
lane (see ``serving/sched.py``) and the lane rides the SAME fused ragged
step with q_len = 1+k rows — the base feedback token plus the draft, each
row at its own position, causality inside the page walk making row j see
rows < j's freshly-scattered K/V.  Every decode row's logits come back;
the longest draft prefix agreeing with the model's own argmax chain plus
the first correction is committed (token-identical to sequential greedy
decode), and the rejected tail's over-extended pages are rolled back via
``PagedKVCache.truncate`` — the step's fixed cost (plan, page walk,
dispatch) is amortised over up to k+1 tokens, which is what lifts the ITL
floor left after continuous batching.

Only pure-GQA decoder stacks are supported (no MLA / SSM / RWKV mixers, no
sliding windows, no cross-attention): that covers the paper's serving case
study (OLMo-2, StableLM); everything else keeps the dense backend.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import LayerSpec, ModelConfig
from repro.core.obs import Stopwatch
from repro.kernels.paged_attention.ops import paged_attention_mixed
from repro.models import attention as attn_mod
from repro.models.common import NO_POLICY, ShardPolicy, apply_rope, rms_norm, shard
from repro.models.model import _apply_ffn, _logits, embed_tokens
from repro.serving.engine import StepReport
from repro.serving.kvcache import PagedKVCache
from repro.serving.request import EXCEEDS_SEQ_CAP, Request, SubmitOutcome
from repro.serving.sched import (PagedScheduler, SchedConfig, bucket_rows,
                                 next_pow2)


def paged_unsupported_reason(cfg: ModelConfig) -> Optional[str]:
    """None when the paged runtime can serve this config, else why not."""
    if cfg.encoder is not None:
        return "encoder-decoder models"
    if cfg.frontend.kind != "none":
        return "multimodal frontends"
    if cfg.attn.kind != "gqa":
        return f"attention kind {cfg.attn.kind!r}"
    for layer in cfg.layer_specs():
        if layer.mixer != "attn":
            return f"mixer {layer.mixer!r}"
        if layer.window:
            return "sliding-window layers"
        if layer.cross_attn:
            return "cross-attention layers"
    return None


# row/width bucketing lives in serving/sched.py (the draft planner is
# bucket-aware: rows riding the padding are funded at zero budget cost)
_next_pow2 = next_pow2
_bucket_rows = bucket_rows


class PagedRuntime:
    """One tenant-replica's paged serving state: page pools + scheduler +
    the jitted fused mixed prefill+decode forward pass.  ``device``
    commits the page pools and step inputs there (None = the default
    device); the jitted step runs where its committed inputs live, so
    pass weights already placed on the same device."""

    def __init__(self, cfg: ModelConfig, params, *, max_slots: int = 8,
                 seq_cap: int = 256, page_size: int = 16,
                 pool_pages: Optional[int] = None,
                 chunk_tokens: Optional[int] = None,
                 step_tokens: Optional[int] = None,
                 policy: ShardPolicy = NO_POLICY, attn_impl: str = "auto",
                 kv_dtype: str = "auto", prefix_cache: bool = True,
                 spec_k: int = 0, spec_ngram: int = 3,
                 response_cache=None, seed: int = 0, device=None):
        reason = paged_unsupported_reason(cfg)
        if reason is not None:
            raise ValueError(
                f"paged backend does not support {reason} ({cfg.name}); "
                f"use backend='dense'")
        if kv_dtype not in ("auto", "int8"):
            raise ValueError(f"unknown kv_dtype {kv_dtype!r} "
                             f"(expected 'auto' or 'int8')")
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        self.cfg = cfg
        self.device = device
        self.params = params
        self.policy = policy
        self.page = page_size
        self.pps = -(-seq_cap // page_size)          # block-table width cap
        self.seq_cap = self.pps * page_size
        self.max_slots = max_slots
        self.pool_pages = (pool_pages if pool_pages is not None
                           else max_slots * self.pps)
        chunk = chunk_tokens or min(self.seq_cap, 4 * page_size)
        self.chunk = max(page_size, (chunk // page_size) * page_size)
        self.attn_impl = attn_impl
        self.kv_quant = kv_dtype == "int8"
        self.spec_k = spec_k
        self.kv = PagedKVCache(self.pool_pages, page_size,
                               enable_prefix_cache=prefix_cache)
        self.sched = PagedScheduler(
            self.kv, SchedConfig(chunk_tokens=self.chunk,
                                 max_active=max_slots,
                                 step_tokens=step_tokens,
                                 spec_k=spec_k, spec_ngram=spec_ngram),
            response_cache=response_cache)
        self.pools = self._init_pools()
        # donate the pools so the per-step KV scatter updates in place
        # (without aliasing every step would copy the whole page pool,
        # making step cost O(pool) instead of O(live tokens))
        self._mixed_fn = jax.jit(self._mixed_impl, donate_argnums=(1,))
        # executable cache per (rows, width) bucket: the fused step has
        # more shape buckets than the old split prefill/decode passes, so
        # each bucket is AOT-compiled on first sight OUTSIDE the timed
        # region (production runtimes precompile their bucket grid at
        # startup; compile time must not pollute the virtual clock's
        # measured per-step compute)
        self._mixed_exec: Dict[tuple, Any] = {}
        self.compile_s: Dict[tuple, float] = {}     # seconds per bucket
        self._rng = np.random.default_rng(seed)
        # the engine's tracer (ServingEngine.tracer sets it); with a clock
        # it gets the step.* spans, else the stopwatch times the step
        self.tracer = None
        self._watch = Stopwatch()

    # ------------------------------------------------------------- pools
    def _init_pools(self) -> Dict[str, Any]:
        a = self.cfg.attn
        dt = jnp.int8 if self.kv_quant else jnp.dtype(self.cfg.dtype)
        # [P, KV, page, hd]: the head axis ahead of the page axis is the
        # layout the TPU kernel tiles (see kernels/paged_attention)
        shape = (self.pool_pages + 1, a.num_kv_heads, self.page, a.head_dim)
        sshape = (self.pool_pages + 1, a.num_kv_heads, self.page)

        def pool(stack: int = 0):
            s = (stack,) + shape if stack else shape
            d = {"k": jnp.zeros(s, dt, device=self.device),
                 "v": jnp.zeros(s, dt, device=self.device)}
            if self.kv_quant:
                ss = (stack,) + sshape if stack else sshape
                d["k_scale"] = jnp.zeros(ss, jnp.float32, device=self.device)
                d["v_scale"] = jnp.zeros(ss, jnp.float32, device=self.device)
            return d

        pools: Dict[str, Any] = {}
        if self.cfg.prefix:
            pools["prefix"] = {f"layer{i}": pool()
                               for i in range(len(self.cfg.prefix))}
        if self.cfg.period:
            pools["period"] = {f"sub{i}": pool(self.cfg.repeats)
                               for i in range(len(self.cfg.period))}
        return pools

    # ------------------------------------------------------- forward: shared
    def _scatter(self, pool, k, v, page_ids, offs):
        """Write the K/V rows of every valid (lane, row) into the page
        pools (masked rows land on the trash page).  int8 pools quantize
        per-row and store the scales beside the pages."""
        if not self.kv_quant:
            return {**pool,
                    "k": pool["k"].at[page_ids, :, offs].set(
                        k.astype(pool["k"].dtype)),
                    "v": pool["v"].at[page_ids, :, offs].set(
                        v.astype(pool["v"].dtype))}
        kq, ks = attn_mod._quantize_kv(k)
        vq, vs = attn_mod._quantize_kv(v)
        return {**pool,
                "k": pool["k"].at[page_ids, :, offs].set(kq),
                "v": pool["v"].at[page_ids, :, offs].set(vq),
                "k_scale": pool["k_scale"].at[page_ids, :, offs].set(
                    ks.astype(jnp.float32)),
                "v_scale": pool["v_scale"].at[page_ids, :, offs].set(
                    vs.astype(jnp.float32))}

    def _walk_layers(self, params, pools, h, layer_fn):
        """Run ``layer_fn(lp, h, layer, pool) -> (h, pool)`` over the
        prefix layers and the scanned period stack, threading each layer's
        page-pool dict through (the stacked period pools are
        indexed/updated per scan step, mirroring the dense decode path).
        Named scopes: ``kv_pool`` on the per-layer pool slice and its
        write-back, ``layer_weights`` on the scan, whose body slices each
        layer's weights out of the stack."""
        cfg = self.cfg
        new_pools = dict(pools)
        if cfg.prefix:
            new_pools["prefix"] = dict(pools["prefix"])
            for i, layer in enumerate(cfg.prefix):
                key = f"layer{i}"
                h, p = layer_fn(params["prefix"][key], h, layer,
                                pools["prefix"][key])
                new_pools["prefix"][key] = p
        if cfg.period:
            def body(carry, xs):
                hh, pp = carry
                lp_stack, idx = xs
                for i, layer in enumerate(cfg.period):
                    sub = f"sub{i}"
                    with jax.named_scope("kv_pool"):
                        pool_i = {key: jax.lax.dynamic_index_in_dim(
                            pp[sub][key], idx, 0, keepdims=False)
                            for key in pp[sub]}
                    hh, pool_i = layer_fn(lp_stack[sub], hh, layer, pool_i)
                    with jax.named_scope("kv_pool"):
                        pp = {**pp, sub: {
                            key: jax.lax.dynamic_update_index_in_dim(
                                pp[sub][key], pool_i[key], idx, 0)
                            for key in pp[sub]}}
                return (hh, pp), ()

            idxs = jnp.arange(cfg.repeats, dtype=jnp.int32)
            with jax.named_scope("layer_weights"):
                (h, period_pools), _ = jax.lax.scan(
                    body, (h, pools["period"]), (params["period"], idxs))
            new_pools["period"] = period_pools
        return h, new_pools

    # ------------------------------------------------ forward: fused mixed
    def _mixed_layer(self, lp, h, layer: LayerSpec, positions, qpos,
                     page_ids, offs, block_tables, pool):
        """One GQA layer over the flattened fused batch: ``h`` is
        [1, T, d] packed token rows, KV via the page pool, causality via
        per-row positions inside the page walk.  Mirrors
        ``attn_mod.gqa_prefill`` numerics (same einsums, same f32 masked
        softmax) with the gathered pages standing in for the in-context
        K/V.  Each part runs under the named scope a device trace
        attributes its operations by (``attn_in``, ``kv_scatter``,
        ``attn_kernel``, ``attn_out``, ``ffn``)."""
        cfg, policy = self.cfg, self.policy
        ap = lp["attn"]
        with jax.named_scope("attn_in"):
            xin = rms_norm(h, lp["norm1"], cfg.norm_eps)
            q = jnp.einsum("bsd,dhk->bshk", xin, ap["wq"])
            k = jnp.einsum("bsd,dhk->bshk", xin, ap["wk"])
            v = jnp.einsum("bsd,dhk->bshk", xin, ap["wv"])
            q = shard(apply_rope(q, positions, cfg.rope_theta), policy.heads)
            k = apply_rope(k, positions, cfg.rope_theta)
        with jax.named_scope("kv_scatter"):
            pool = self._scatter(pool, k[0], v[0], page_ids, offs)
        kwargs = {}
        if self.kv_quant:
            kwargs = dict(k_scales=pool["k_scale"],
                          v_scales=pool["v_scale"])
        # each packed row is its own one-row lane of the ragged kernel.
        # deliberate tradeoff: chunk rows re-gather their lane's pages per
        # row (O(rows x pages) gather traffic) but the batch carries ZERO
        # pad rows; the per-lane Q-block form (one Q=chunk lane, decode
        # lanes padded to Q) amortises the gather but measured ~3x slower
        # on the CPU oracle because padding dominates — on TPU the Q>1
        # kernel path is the one to switch to (see ROADMAP)
        with jax.named_scope("attn_kernel"):
            ctx = paged_attention_mixed(q[0][:, None].astype(h.dtype),
                                        pool["k"], pool["v"], block_tables,
                                        qpos[:, None], impl=self.attn_impl,
                                        **kwargs)             # [T, 1, H, hd]
        with jax.named_scope("attn_out"):
            out = jnp.einsum("bshk,hkd->bsd",
                             ctx[None, :, 0].astype(h.dtype), ap["wo"])
            h = h + shard(out, policy.act)
        with jax.named_scope("ffn"):
            h, _, _ = _apply_ffn(lp, h, layer, cfg, policy)
        return h, pool

    def _mixed_impl(self, params, pools, tokens, positions, n_rows,
                    block_tables, last_rows):
        """tokens/positions [T] int32 — the step's packed token rows
        (T bucketed); n_rows scalar int32 (rows beyond it are padding);
        block_tables [T, W] int32 (each row carries its lane's table,
        W bucketed); last_rows [L] int32 (the row whose logits each lane
        needs).  Returns (logits [L, V], pools)."""
        cfg, policy = self.cfg, self.policy
        t = tokens.shape[0]
        width = block_tables.shape[1]
        valid = jnp.arange(t, dtype=jnp.int32) < n_rows
        slot = jnp.clip(positions // self.page, 0, width - 1)
        page_ids = jnp.where(valid, block_tables[jnp.arange(t), slot],
                             self.pool_pages)
        offs = positions % self.page
        # pad rows read slot 0 of their (zero) table so the online softmax
        # never sees an empty row; their output is discarded
        qpos = jnp.where(valid, positions, 0)
        positions2 = qpos[None]
        with jax.named_scope("embed"):
            h = embed_tokens(params, cfg, tokens[None], policy)
        h, new_pools = self._walk_layers(
            params, pools, h,
            lambda lp, hh, layer, pool: self._mixed_layer(
                lp, hh, layer, positions2, qpos, page_ids, offs,
                block_tables, pool))
        with jax.named_scope("logits"):
            h = rms_norm(h, params["final_norm"], cfg.norm_eps)
            h_last = h[0][last_rows][None]               # [1, L, d]
            logits = _logits(params, cfg, h_last, policy)[0]
        return logits, new_pools

    # ------------------------------------------------------------ engine API
    def submit(self, req: Request) -> SubmitOutcome:
        """Rejects only requests that can NEVER fit (footprint beyond the
        block-table width or the whole pool); pool pressure is resolved
        later by SLO-aware preemption instead of at submit.  Rejections
        carry their reason — both are structural (non-transient)."""
        if req.prompt_len + req.max_new_tokens > self.seq_cap:
            return EXCEEDS_SEQ_CAP
        if req.prompt_tokens is None:
            # materialise synthetic prompts once so every chunk (and any
            # post-preemption recompute) sees identical tokens
            req.prompt_tokens = self._rng.integers(
                0, self.cfg.vocab_size, req.prompt_len)
        return self.sched.submit(req)

    def has_work(self) -> bool:
        return self.sched.has_work()

    def running(self) -> List[Request]:
        return self.sched.running()

    @property
    def queue(self):
        return self.sched.waiting

    def set_budget(self, n: int) -> None:
        self.sched.set_budget(n)

    def drain_for_redrive(self) -> List[Request]:
        """Replica death: release every page and hand back the resident
        requests for the dispatcher to redrive (see
        ``PagedScheduler.drain_for_redrive``)."""
        return self.sched.drain_for_redrive()

    # ------------------------------------------------------------ fused step
    def _span(self, name: str, **args):
        """The block as span ``name`` on the attached tracer when it has a
        clock, else under this runtime's stopwatch; either one's ``dur``
        is the block's seconds once it exits."""
        tr = self.tracer
        if tr is None or tr.clock is None:
            return self._watch
        return tr.scope(name, **args)

    def _run_mixed(self, tokens, positions, n_rows, bts, last_rows):
        """Execute the fused forward for this (rows, width, logit-rows)
        bucket, AOT-compiling the bucket on first sight (``step.compile``)
        so compile time never enters the measured compute
        (``step.device``: the call and its ``block_until_ready``).
        Returns (logits, compute_s)."""
        key = (tokens.shape[0], bts.shape[1], last_rows.shape[0])
        fn = self._mixed_exec.get(key)
        if fn is None:
            with self._span("step.compile", rows=key[0], width=key[1],
                            logits=key[2]) as sp:
                fn = self._mixed_fn.lower(
                    self.params, self.pools, tokens, positions, n_rows, bts,
                    last_rows).compile()
            self._mixed_exec[key] = fn
            self.compile_s[key] = sp.dur
        with self._span("step.device") as sp:
            logits, self.pools = fn(self.params, self.pools, tokens,
                                    positions, n_rows, bts, last_rows)
            logits = jax.block_until_ready(logits)
        return logits, sp.dur

    def step(self) -> StepReport:
        """One fused step, as consecutive spans on an attached tracer:
        ``step.plan``, ``step.pack``, ``step.put``, ``step.compile`` (a
        new bucket only), ``step.device``, ``step.fetch`` (argmax and
        host copy), ``step.commit``; and the counters ``steps``, ``rows``
        (real token rows) and ``rows_padded`` (rows of the bucket)."""
        with self._span("step.plan"):
            log_mark = len(self.sched.preempt_log)
            plan = self.sched.plan()
            report = StepReport(kind="idle")
            report.preempted = [s.req for s in plan.preempted]
            # every preemption happens inside plan(): the log's new tail
            # is exactly this step's (victim, beneficiary) pairs — the
            # flight recorder attaches the beneficiary to the victim's
            # timeline
            report.preempt_pairs = list(self.sched.preempt_log[log_mark:])
            report.prefix_hit_tokens = plan.prefix_hit_tokens
        if plan.empty:
            return report
        decodes, prefills = plan.decodes, plan.prefills
        report.kind = ("mixed" if decodes and prefills
                       else "decode" if decodes else "prefill")
        with self._span("step.pack"):
            n_rows, arrays, lanes = self._pack(decodes, prefills)
        with self._span("step.put"):
            args = jax.device_put(arrays, self.device)
        logits, report.compute_s = self._run_mixed(*args)
        with self._span("step.fetch"):
            next_tokens = np.asarray(jnp.argmax(logits, axis=-1))
        with self._span("step.commit"):
            self._commit(lanes, next_tokens, report)
        tr = self.tracer
        if tr is not None:
            tr.count("steps")
            tr.count("rows", n_rows)
            tr.count("rows_padded", len(arrays[0]))
        return report

    def _pack(self, decodes, prefills):
        """Pack the step's real tokens back to back: 1+len(draft) rows per
        decode lane (the base feedback token plus its speculative verify
        rows), ``clen`` rows per prefill chunk — cost tracks live tokens,
        and the row/width/logit buckets keep the jit shape set bounded.
        Returns (real rows, (tokens, positions, n_rows, block tables,
        last rows), lanes)."""
        n_rows = sum(1 + len(s.draft) for s in decodes) \
            + sum(c for _, _, c in prefills)
        # every decode row needs its logits for verification; prefill
        # chunks only need their final row's
        n_logits = sum(1 + len(s.draft) for s in decodes) + len(prefills)
        t = _bucket_rows(n_rows)
        tokens = np.zeros(t, np.int32)
        positions = np.zeros(t, np.int32)
        last_rows = np.zeros(_bucket_rows(n_logits), np.int32)
        lanes: List[tuple] = []
        row_of: List[tuple] = []          # (row_start, n) per lane
        row = 0
        li = 0                            # next logit-row slot
        max_pages = 1
        for s in decodes:
            q = 1 + len(s.draft)          # verify q_len for this lane
            lanes.append(("d", s, li, q))
            pos = s.req.prompt_len + s.req.generated - 1
            tokens[row] = s.last_token
            if s.draft:
                tokens[row + 1:row + q] = np.asarray(s.draft, np.int32)
            positions[row:row + q] = pos + np.arange(q, dtype=np.int32)
            last_rows[li:li + q] = row + np.arange(q, dtype=np.int32)
            li += q
            row_of.append((row, q))
            row += q
            max_pages = max(max_pages, self.kv.pages_needed(pos + q))
        for s, start, clen in prefills:
            lanes.append(("p", s, start, clen, li))
            tokens[row:row + clen] = np.asarray(
                s.req.prompt_tokens, np.int32)[start:start + clen]
            positions[row:row + clen] = start + np.arange(clen,
                                                          dtype=np.int32)
            last_rows[li] = row + clen - 1
            li += 1
            row_of.append((row, clen))
            row += clen
            max_pages = max(max_pages, self.kv.pages_needed(start + clen))
        width = min(self.pps, _next_pow2(max_pages))
        bts = np.zeros((t, width), np.int32)
        for (r0, n), lane in zip(row_of, lanes):
            bts[r0:r0 + n] = self.kv.block_table(lane[1].req.req_id, width)
        return n_rows, (tokens, positions, np.int32(n_rows), bts,
                        last_rows), lanes

    def _commit(self, lanes, next_tokens, report: StepReport) -> None:
        """Commit each lane's tokens from the step's argmax into the
        scheduler, the requests and ``report``."""
        for lane in lanes:
            if lane[0] == "d":
                _, s, li, q = lane
                d = s.draft
                # greedy verify: row j's argmax is the model's token for
                # position pos+j+1 GIVEN the draft prefix d[:j]; the
                # longest draft prefix matching the model's own argmax
                # chain is exactly what sequential decode would have
                # produced, so committing it (plus the first
                # disagreement's correction — the "bonus" token) is
                # token-identical to non-speculative decode
                g = [int(next_tokens[li + j]) for j in range(q)]
                a = 0
                while a < len(d) and d[a] == g[a]:
                    a += 1
                m = min(a + 1, s.req.max_new_tokens - s.req.generated)
                committed = g[:m]
                if d:
                    report.spec.append((s.req, len(d), m - 1))
                    self.sched.commit_verified(s, m, drafted=len(d),
                                               accepted=m - 1)
                else:
                    self.sched.commit_decode(s)
                s.last_token = committed[-1]
                s.req.generated += m
                s.req.output_tokens.extend(committed)
                report.decode_tokens += m
                report.tokens += m
                report.drafted_tokens += len(d)
                report.accepted_tokens += m - 1
                # one decoded entry per committed token: finalize_step
                # stamps them all with this step's end time, so a burst's
                # 2nd..mth tokens record ~zero inter-token latency (the
                # whole point of amortising the per-step fixed cost)
                report.decoded.extend([s.req] * m)
                if s.req.generated >= s.req.max_new_tokens:
                    self.sched.complete(s)
                    report.completed.append(s.req)
            else:
                _, s, start, clen, li = lane
                report.chunks.append((s.req, start, clen, s.chunks_done))
                self.sched.finish_chunk(s, clen)
                report.prefill_tokens += clen
                report.tokens += clen
                if s.prefilled >= s.req.prompt_len:   # final chunk: 1st token
                    first = int(next_tokens[li])
                    s.last_token = first
                    s.req.generated = 1
                    s.req.output_tokens.append(first)
                    # a restart after preemption regenerates the SAME first
                    # token, so only a fresh emission defines TTFT
                    if s.req.prefill_done < 0:
                        report.prefilled.append(s.req)
                    if s.req.generated >= s.req.max_new_tokens:
                        self.sched.complete(s)
                        report.completed.append(s.req)

"""Continuous-batching serving engine with two interchangeable backends.

One engine instance serves one tenant's model on one slice.  The engine
performs *one unit of work* per ``step()`` call — a prefill (or, paged
backend, one prefill *chunk*) or one batched decode step — and reports the
measured compute seconds.  The harness (real-time driver or the cluster
simulator) decides what wall/virtual time the step consumed (e.g. adding
PS-fabric transfer delay) and then calls ``finalize_step`` so TTFT and
completion timestamps reflect the environment.

Backends (``backend=`` ctor arg, same public API either way):

* ``"dense"`` — the original slot cache: ``[max_slots, seq_cap]`` KV per
  layer, whole-prompt prefill, prompt+max_new pages reserved at submit
  (admission rejects when the pool is full).
* ``"paged"`` — the block-table runtime (``serving/paged_runtime.py``):
  KV lives in a page pool addressed through ``PagedKVCache`` block tables,
  prompts prefill in chunks interleaved with decode, and pool exhaustion
  triggers SLO-aware preemption instead of submit-time rejection.

``device`` places the engine: its weights and KV state are committed to
that device, so its jitted steps run there (None = the default device).

Guardrail hook (paper §2.2, MPS-quota analogue): ``set_quota(frac)`` caps
the engine's concurrency — the number of active decode slots and the
prefill admission rate scale with the quota, bounding MXU occupancy the
way CUDA_MPS_ACTIVE_THREAD_PERCENTAGE bounds SM occupancy.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.common import NO_POLICY
from repro.models.model import Model, decode_step, prefill
from repro.models.params import P, specs_from_plan
from repro.serving.kvcache import PagedKVCache
from repro.serving.metrics import TenantMetrics
from repro.serving.request import (ADMITTED, POOL_EXHAUSTED, Request,
                                   SubmitOutcome)


def init_cache_from_plan(plan):
    """Zero-initialised cache (pos arrays get -1)."""
    def leaf(p: P):
        if p.dtype == "int32":
            return jnp.full(p.shape, -1, jnp.int32)
        return jnp.zeros(p.shape, jnp.dtype(p.dtype))
    return jax.tree.map(leaf, plan, is_leaf=lambda x: isinstance(x, P))


@dataclass
class StepReport:
    kind: str                 # "prefill" | "decode" | "mixed" | "idle"
    compute_s: float = 0.0
    tokens: int = 0                      # total tokens this step
    prefill_tokens: int = 0              # prompt tokens written this step
    decode_tokens: int = 0               # decode tokens emitted this step
    # requests whose first token was emitted this step (TTFT events);
    # a fused mixed step can complete several prefills at once
    prefilled: List[Request] = field(default_factory=list)
    decoded: List[Request] = field(default_factory=list)
    completed: List[Request] = field(default_factory=list)
    # paged backend: sequences evicted (pages released, requeued for a full
    # restart) by SLO-aware preemption during this step
    preempted: List[Request] = field(default_factory=list)
    # paged backend: prompt tokens served from the shared prefix cache
    # while planning this step (prefill compute skipped entirely)
    prefix_hit_tokens: int = 0
    # speculative decode lanes (paged backend, spec_k > 0): draft rows
    # verified this step, and how many of them the model accepted —
    # decode_tokens already counts every committed token (base + accepted)
    drafted_tokens: int = 0
    accepted_tokens: int = 0
    # --- flight-recorder detail (serving/trace.py) -----------------------
    # prefill work per request this step: (req, token_start, chunk_len,
    # chunk_index) — the dense backend reports its whole-prompt prefill
    # as chunk 0, the paged runtime one entry per planned chunk
    chunks: List[tuple] = field(default_factory=list)
    # per-lane speculative verify outcome: (req, drafted, accepted),
    # only for lanes that carried a draft this step
    spec: List[tuple] = field(default_factory=list)
    # preemption detail: (victim_req_id, beneficiary_req_id) pairs, the
    # same tuples the scheduler appends to its preempt_log this step
    preempt_pairs: List[tuple] = field(default_factory=list)


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params=None, *, max_slots: int = 8,
                 seq_cap: int = 256, page_size: int = 16, seed: int = 0,
                 policy=NO_POLICY, backend: str = "dense",
                 pool_pages: Optional[int] = None,
                 chunk_tokens: Optional[int] = None,
                 step_tokens: Optional[int] = None, attn_impl: str = "auto",
                 kv_dtype: str = "auto", prefix_cache: bool = True,
                 spec_k: int = 0, spec_ngram: int = 3,
                 response_cache=None, device=None):
        if backend not in ("dense", "paged"):
            raise ValueError(f"unknown backend {backend!r}")
        if kv_dtype != "auto" and backend == "dense":
            raise ValueError(
                "kv_dtype applies to the paged backend's page pools; the "
                "dense slot cache quantizes via REPRO_KV_INT8=1")
        if spec_k and backend == "dense":
            raise ValueError(
                "speculative decode lanes (spec_k) need the paged "
                "runtime's ragged verify step; use backend='paged'")
        if response_cache is not None and response_cache is not False \
                and backend == "dense":
            raise ValueError(
                "the response cache primes speculative draft hints at "
                "submit, which needs the paged scheduler; use "
                "backend='paged'")
        # response_cache: None/False = off, True = a private cache,
        # or a serving/directory.ResponseCache instance — pass ONE
        # instance to every replica of a tenant so a completion on any
        # replica primes speculation fleet-wide.  Identity checks, not
        # truthiness: an EMPTY cache instance is falsy (len() == 0) but
        # very much wanted.
        if response_cache is True:
            from repro.serving.directory import ResponseCache
            response_cache = ResponseCache()
        elif response_cache is False:
            response_cache = None
        self.response_cache = response_cache
        self.cfg = cfg
        self.model = Model(cfg)
        self.policy = policy
        if params is None:
            params = self.model.init(jax.random.key(seed))
        if device is not None:
            params = jax.device_put(params, device)
        self.params = params
        self.device = device
        self.max_slots = max_slots
        self.seq_cap = seq_cap
        self.backend = backend
        self.quota = 1.0
        self.metrics = TenantMetrics()
        # optional serving/trace.FlightRecorder: ``finalize_step`` folds
        # each step into per-request timelines.  None (the default) is
        # the zero-cost path — a single guard, no recorder calls.
        self.tracer = None
        self._rng = np.random.default_rng(seed)
        if backend == "paged":
            from repro.serving.paged_runtime import PagedRuntime
            self.runtime = PagedRuntime(
                cfg, self.params, max_slots=max_slots, seq_cap=seq_cap,
                page_size=page_size, pool_pages=pool_pages,
                chunk_tokens=chunk_tokens, step_tokens=step_tokens,
                policy=policy, attn_impl=attn_impl, kv_dtype=kv_dtype,
                prefix_cache=prefix_cache, spec_k=spec_k,
                spec_ngram=spec_ngram, response_cache=self.response_cache,
                seed=seed, device=device)
            self.kv = self.runtime.kv
            # the scheduler's waiting deque doubles as the engine queue
            # (same object for the lifetime of the engine, so load-based
            # dispatch `len(engine.queue)` works on either backend)
            self.queue = self.runtime.queue
            return
        self.runtime = None
        self.queue: deque = deque()
        self.slots: List[Optional[Request]] = [None] * max_slots
        self.positions = np.zeros(max_slots, np.int32)
        self.last_token = np.zeros(max_slots, np.int32)
        # paged accounting mirrors the dense slot cache capacity
        self.kv = PagedKVCache(num_pages=max_slots * (seq_cap // page_size),
                               page_size=page_size)
        cplan = self.model.cache_plan(max_slots, seq_cap, policy)
        self.cache = init_cache_from_plan(cplan)
        if device is not None:
            self.cache = jax.device_put(self.cache, device)
        self._decode_fn = jax.jit(
            lambda p, c, t, q: decode_step(p, cfg, c, t, q, policy))
        self._prefill_fn = jax.jit(
            lambda p, b: prefill(p, cfg, b, policy, seq_cap=seq_cap))

    # ------------------------------------------------------------------ API
    def set_quota(self, frac: float) -> None:
        self.quota = float(np.clip(frac, 0.1, 1.0))
        if self.runtime is not None:
            self.runtime.set_budget(self.active_slot_budget)

    @property
    def active_slot_budget(self) -> int:
        return max(1, int(np.ceil(self.quota * self.max_slots)))

    def submit(self, req: Request) -> SubmitOutcome:
        """Returns a falsy :class:`SubmitOutcome` if rejected by admission
        control (``outcome.reason`` says why, ``outcome.transient``
        whether a retry may succeed).  The dense backend rejects whenever
        the conservative prompt+max_new page reservation does not fit —
        transient, the pool drains as requests finish; the paged backend
        only rejects requests that could NEVER fit and resolves pressure
        by preemption."""
        if self.runtime is not None:
            return self.runtime.submit(req)
        if not self.kv.can_admit(req.prompt_len, req.max_new_tokens):
            return POOL_EXHAUSTED
        self.kv.allocate(req.req_id, req.prompt_len,
                         req.prompt_len + req.max_new_tokens)
        self.queue.append(req)
        return ADMITTED

    def active(self) -> List[Request]:
        if self.runtime is not None:
            return self.runtime.running()
        return [r for r in self.slots if r is not None]

    def has_work(self) -> bool:
        if self.runtime is not None:
            return self.runtime.has_work()
        return bool(self.queue) or any(s is not None for s in self.slots)

    def drain_requests(self, ship_state: bool = False):
        """Replica death / planned drain: release every KV page and return
        the resident requests (queued, prefilling and decoding alike) so
        the dispatcher can redrive them onto surviving replicas.  Requests
        come back rolled to a restartable state (outputs cleared, original
        ``prefill_done`` stamp kept so TTFT is not double-counted).

        ``ship_state=True`` returns ``serving/migrate.LaneManifest``
        objects instead of bare requests: each resident lane's KV pages
        are serialized (with chain hashes) BEFORE the drain resets its
        cursors, so a ``PageImporter`` on another replica can resume the
        lane warm — and any lane that fails the import's verification
        degrades to the cold redrive exactly as if ``ship_state`` were
        False.  The dense backend holds no shippable page chains, so its
        manifests are always cold (recompute is the only path)."""
        if self.runtime is not None:
            manifests = None
            if ship_state:
                from repro.serving.migrate import PageExporter
                manifests = PageExporter(self.runtime).export_all()
            drained = self.runtime.drain_for_redrive()
            self.kv.release_all()        # safety net: no page outlives death
            return manifests if manifests is not None else drained
        drained = list(self.queue)
        self.queue.clear()
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            self.slots[i] = None
            drained.append(req)
        for req in drained:
            req.generated = 0
            req.slot = -1
            req.output_tokens.clear()
            req.decode_times.clear()
        self.kv.release_all()
        if ship_state:
            from repro.serving.migrate import LaneManifest
            return [LaneManifest(
                req=r,
                prompt_tokens=np.asarray(r.prompt_tokens, np.int64)
                if r.prompt_tokens is not None else np.zeros(0, np.int64))
                for r in drained]
        return drained

    # ----------------------------------------------------------------- step
    def step(self) -> StepReport:
        """One unit of work.  Compute time measured with a real clock."""
        report = self._step_backend()
        self.metrics.observe_kv(self.kv.used_pages, self.kv.reserved_pages,
                                self.kv.num_pages)
        self.metrics.observe_prefill(report.prefill_tokens,
                                     report.prefix_hit_tokens)
        self.metrics.observe_spec(report.drafted_tokens,
                                  report.accepted_tokens)
        if self.runtime is not None:
            self.metrics.observe_response_cache(self.runtime.sched.rc_lookups,
                                                self.runtime.sched.rc_hits)
        return report

    def _step_backend(self) -> StepReport:
        if self.runtime is not None:
            return self.runtime.step()
        free = [i for i, s in enumerate(self.slots) if s is None]
        n_active = self.max_slots - len(free)
        if self.queue and free and n_active < self.active_slot_budget:
            return self._do_prefill(free[0])
        if n_active:
            return self._do_decode()
        return StepReport(kind="idle")

    def finalize_step(self, report: StepReport, end_time: float,
                      start_time: Optional[float] = None) -> None:
        """Record timestamps using the harness-provided completion time.

        ``start_time`` (optional) is the step's virtual start stamp —
        only the flight recorder consumes it, to open this step's spans
        at the step boundary instead of each request's previous event;
        metrics observe ``end_time`` exactly as before."""
        for req in report.prefilled:
            req.prefill_done = end_time
            # door-measured TTFT: from arrival at the front door (includes
            # any gateway-queue wait) — the SLO the paper's per-tenant
            # attainment is measured against
            self.metrics.latency.observe(end_time, (end_time - req.arrival),
                                         slo=(req.slo_ms or 0) / 1e3 or None,
                                         req_id=req.req_id)
            # engine-measured TTFT: from the moment the gateway handed the
            # request to this engine (absent a gateway, never observed)
            if req.submitted >= 0:
                self.metrics.engine_ttft.observe(
                    end_time, end_time - req.submitted, req_id=req.req_id)
        for req in report.decoded:
            # per-token decode timestamp: the gap to the previous emission
            # (prefill for the first decode) is this token's ITL
            prev = req.decode_times[-1] if req.decode_times \
                else req.prefill_done
            req.decode_times.append(end_time)
            if prev >= 0:
                self.metrics.itl.observe(end_time, end_time - prev,
                                         req_id=req.req_id)
        for req in report.completed:
            req.finished = end_time
        if report.tokens:
            self.metrics.observe_tokens(end_time, report.tokens)
        if self.tracer is not None:
            self.tracer.on_step(report, start_time, end_time,
                                engine=self.backend)

    # ------------------------------------------------------------ internals
    def _merge_slot_cache(self, cache1, slot: int) -> None:
        """Merge a single-sequence prefill cache into the batched slot
        cache.  Prefix-layer leaves are [batch, ...] but period leaves are
        stacked [repeats, batch, ...] — indexing them with ``at[slot]``
        would hit the repeats axis and broadcast one request's KV across
        every slot (and silently drop merges for slot >= repeats), so the
        two groups must be merged along different axes."""
        new = dict(self.cache)
        if "prefix" in self.cache:
            new["prefix"] = jax.tree.map(
                lambda full, one: full.at[slot].set(one[0]),
                self.cache["prefix"], cache1["prefix"])
        if "period" in self.cache:
            new["period"] = jax.tree.map(
                lambda full, one: full.at[:, slot].set(one[:, 0]),
                self.cache["period"], cache1["period"])
        self.cache = new

    def _prompt_tokens(self, req: Request):
        if req.prompt_tokens is not None:
            return jnp.asarray(req.prompt_tokens, jnp.int32)[None]
        toks = self._rng.integers(0, self.cfg.vocab_size, req.prompt_len)
        return jnp.asarray(toks, jnp.int32)[None]

    def _do_prefill(self, slot: int) -> StepReport:
        req = self.queue.popleft()
        batch = {"tokens": self._prompt_tokens(req)}
        if self.cfg.frontend.kind == "vision":
            batch["embeds"] = jnp.zeros(
                (1, self.cfg.frontend.num_prefix, self.cfg.frontend.embed_dim),
                jnp.bfloat16)
        if self.cfg.encoder is not None:
            batch["frames"] = jnp.zeros((1, req.prompt_len,
                                         self.cfg.frontend.embed_dim),
                                        jnp.bfloat16)
            batch["tokens"] = jnp.ones((1, 1), jnp.int32)    # BOS
        t0 = time.perf_counter()
        logits, cache1 = self._prefill_fn(self.params, batch)
        logits = jax.block_until_ready(logits)
        dt = time.perf_counter() - t0
        first_tok = int(jnp.argmax(logits[0]))
        self._merge_slot_cache(cache1, slot)
        req.slot = slot
        req.generated = 1
        req.output_tokens.append(first_tok)
        self.slots[slot] = req
        self.positions[slot] = req.prompt_len
        self.last_token[slot] = first_tok
        report = StepReport(kind="prefill", compute_s=dt, tokens=req.prompt_len,
                            prefill_tokens=req.prompt_len, prefilled=[req])
        report.chunks.append((req, 0, req.prompt_len, 0))
        if req.generated >= req.max_new_tokens:
            self._retire(req, report)
        return report

    def _do_decode(self) -> StepReport:
        toks = jnp.asarray(self.last_token)
        pos = jnp.asarray(self.positions)
        t0 = time.perf_counter()
        logits, self.cache = self._decode_fn(self.params, self.cache, toks, pos)
        logits = jax.block_until_ready(logits)
        dt = time.perf_counter() - t0
        next_tokens = np.asarray(jnp.argmax(logits, axis=-1))
        report = StepReport(kind="decode", compute_s=dt)
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            self.positions[i] += 1
            self.last_token[i] = int(next_tokens[i])
            req.generated += 1
            req.output_tokens.append(int(next_tokens[i]))
            self.kv.append_token(req.req_id)
            report.tokens += 1
            report.decode_tokens += 1
            report.decoded.append(req)
            if req.generated >= req.max_new_tokens:
                self._retire(req, report)
        return report

    def _retire(self, req: Request, report: StepReport) -> None:
        if req.slot >= 0:
            self.slots[req.slot] = None
        self.kv.release(req.req_id)
        report.completed.append(req)

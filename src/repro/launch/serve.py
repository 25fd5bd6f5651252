"""Serving launcher: engines + controller co-deployed (the paper's
first-class integration), generalized to N latency tenants x R replicas.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm_3b \
        --requests 32 --qps 4 [--tenants 2] [--replicas 2] \
        [--interfere] [--no-controller] [--admit 2] [--backend paged]

``--backend paged`` swaps every tenant-replica engine onto the
block-table paged runtime (chunked prefill + SLO-aware preemption over a
shared page pool) instead of the dense slot cache; the rest of the
harness — fabric, controller, admission — is unchanged.  ``--spec-k K``
additionally enables speculative multi-token decode lanes (n-gram
prompt-lookup drafts verified in the fused ragged step, adaptive per-lane
depth).

Replica dispatch is cache-aware by default: every paged replica
publishes its prefix cache into a per-tenant content-hash
``PrefixDirectory`` and requests route to the replica holding the
longest prefix of their prompt, falling back to least-loaded when the
directory misses, lags (``--route-staleness``), or the target's queue
lead exceeds ``--route-imbalance``.  ``--route load`` restores blind
least-loaded dispatch (the A/B baseline).  A per-tenant
``ResponseCache`` shared across replicas additionally primes
``draft_hints`` for repeated prompts (``--no-response-cache`` to
disable; only drafts anything when ``--spec-k`` > 0).

Runs one continuous-batching engine per tenant-replica (on the reduced
config unless ``reduced=False`` / ``--published-widths``), all sharing a
FabricState (the PS fabric model injects PCIe-class interference when
--interfere is set), with the multi-tenancy controller
steering quotas, placements and slice profiles per tenant.  Placement
state lives in a shared DeviceLedger built from the TenantRegistry, the
same bookkeeping the simulator uses — and ``--admit K`` exercises the
paper's §2.3 admission path: K late-arriving tenants are scored against
the live ledger mid-run; admitted ones get engines and traffic, the rest
queue or are rejected.  Virtual time: replicas run in parallel — each
engine owns an availability clock and the global clock advances to the
next event (arrival, sample, step finish, admission).

Each tenant's weights are built once and shared by its replicas; replica
``j`` of every tenant lives on ``jax.devices()[j % n]``, so one process
drives every chip of a host and a one-chip host keeps every replica on
its only device.
"""
from __future__ import annotations

import argparse
import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]


def use_checkout_compile_cache() -> None:
    """Entry points call this: JAX's persistent compile cache goes where
    ``JAX_COMPILATION_CACHE_DIR`` says, else to ``<checkout>/.jax_cache``
    (a fixed path, so later runs of this checkout hit it)."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(CHECKOUT / ".jax_cache"))


def warm_engine(eng, name: str, prompt_len: int) -> None:
    """Prime an engine's jit caches WITHOUT polluting observable state.

    The warm request (req_id=-1) runs at virtual time 0, so letting it
    touch shared state plants three lies: a zero-latency sample in
    ``TenantMetrics.latency`` (seeding the controller's p99 signal with
    a bogus 0), its output in the tenant's shared ``ResponseCache``
    (primeable by real traffic), and its prefix pages in the
    ``PrefixDirectory`` (cache-aware routing toward KV no request
    wants).  So: detach the directory listener and the response cache
    for the drain, then reset the engine's metrics to a clean slate.
    """
    from repro.serving.metrics import TenantMetrics
    from repro.serving.request import Request

    listener, eng.kv.listener = getattr(eng.kv, "listener", None), None
    sched = eng.runtime.sched if eng.runtime is not None else None
    rcache = None
    if sched is not None:
        rcache, sched.response_cache = sched.response_cache, None
    try:
        eng.submit(Request(req_id=-1, tenant=name, prompt_len=prompt_len,
                           max_new_tokens=2, arrival=0.0))
        while eng.has_work():
            eng.finalize_step(eng.step(), 0.0)
    finally:
        eng.kv.listener = listener
        if sched is not None:
            sched.response_cache = rcache
            sched.rc_lookups = 0
            sched.rc_hits = 0
    eng.metrics = TenantMetrics()


def serve(arch: str = "stablelm_3b", requests: int = 32, qps: float = 4.0,
          prompt_len: int = 48, max_new: int = 8, slots: int = 4,
          num_tenants: int = 1, replicas: int = 1, interfere: bool = False,
          with_controller: bool = True, seed: int = 0, verbose: bool = True,
          admit: int = 0, backend: str = "dense", kv_dtype: str = "auto",
          prefix_cache: bool = True, spec_k: int = 0, route: str = "cache",
          route_imbalance: int = 4, route_staleness: int = 256,
          response_cache: bool = True, listen: bool = False,
          door_queue: int = 64, door_deadline_ms: float = 1000.0,
          trace: bool = False, trace_out: str = None,
          chaos: bool = False, chaos_seed: int = None,
          recover: bool = True, faults=None,
          watchdog_timeout_s: float = 1.5,
          migrate: bool = False, drains=None,
          gray_threshold: float = 2.5, gray_cooldown_s: float = 2.0,
          det_timing: bool = False, exact_tokens: bool = False,
          unique_prompts: bool = False, reduced: bool = True,
          seq_cap: int = 128):
    """Virtual-time multi-tenant serving run; returns per-tenant stats
    (plus ``out["engines"]``, the engines by tenant, for inspection).

    ``reduced=True`` (the default, what the CPU tests run) cuts the model
    to ``configs.base.reduced``; ``reduced=False`` serves it at its
    published widths and depth.  ``seq_cap`` is each engine's longest
    sequence (prompt plus new tokens).

    ``listen=True`` (the ``--listen`` flag) turns on the gateway's
    backpressure policy: bounded per-tenant door queues of
    ``door_queue``, a ``door_deadline_ms`` dispatch deadline (queued
    requests that outlive it are EXPIRED — the 503 path), and a
    Kingman-derived per-tenant rate limiter (arrivals past the rate
    that keeps rho under the admission bound are REJECTED fast — the
    429 path).  Without it the gateway still fronts every request with
    an effectively unbounded patient door, so the verdict-conservation
    ledger holds on both paths.

    ``trace=True`` (or ``trace_out=<path>``) arms the per-request
    flight recorder: every request accrues a span timeline
    (door_queued -> sched_queued -> prefill chunks -> decode, with
    preemption windows and speculative verify events) whose segments
    sum to its measured E2E, and every controller/actuator action lands
    on a shared virtual-clock timeline.  ``trace_out`` additionally
    dumps a Chrome/Perfetto ``trace_event`` JSON.  Disabled tracing is
    zero-cost (every call site is None-guarded) and tracing never
    perturbs the virtual clock — token output and timings are identical
    either way.

    ``chaos=True`` (or an explicit ``faults=FaultInjector(...)``) arms
    deterministic fault injection: a seeded virtual-clock schedule of
    replica crashes, actuator-call failures, stuck decode lanes and
    fabric degradation windows (``core/faults.py``).  With
    ``recover=True`` (default) a crashed replica's in-flight requests
    are drained and *redriven* onto survivors through the gateway (the
    prefix directory retracts the dead holder, the router stops routing
    to it, the device ledger releases its slots), actuator calls go
    through a bounded-retry wrapper with rollback-to-last-good, and a
    watchdog requeues hung lanes through the scheduler's refcount-safe
    preemption path.  ``recover=False`` keeps the same fault schedule
    but sheds the dead replica's requests — the A/B baseline the
    ``llm_ttft --chaos`` benchmark measures against.  Either way every
    request still gets exactly one terminal verdict and the gateway's
    conservation ledger holds.

    ``migrate=True`` upgrades recovery from recompute to *verified
    state transfer* (``serving/migrate.py``): a failing replica's lanes
    ship their KV page chains (chain-hashed, with int8 scales) to the
    least-loaded live peer, which recomputes every chain hash before
    committing — a mismatch silently degrades that lane to the
    recompute redrive, never a wrong token.  Three triggers: replica
    crash (warm adoption from the shared host pool), ``drains=``
    planned scale-downs (evacuate instead of shed), and gray failure —
    a tail-based detector compares per-token step cost across live
    peers and evacuates a degraded-but-alive replica (quarantined
    for ``gray_cooldown_s``, then readmitted) before the watchdog
    fires.  Transfer time is priced against the ledger's per-root
    fabric demand like any tenant flow.

    ``det_timing=True`` replaces the measured wall-clock step time with
    a deterministic per-token cost model.  Normally each step's
    ``compute_s`` is real measured time, so machine noise perturbs the
    virtual schedule (and with it batching, chunk boundaries and
    ultimately greedy argmax near-ties) run to run.  With the model,
    the whole run is bit-reproducible — which is what lets the
    ``llm_ttft --migrate`` A/B assert exact token parity between arms.
    ``exact_tokens=True`` additionally pins float32 weights and the
    reference attention path, making greedy output a pure function of
    the prompt: batch shape and chunk boundaries stop perturbing argmax
    near-ties, so even recomputed (re-prefilled) lanes regenerate
    byte-identical tokens — the same setup ``tests/test_faults.py``
    uses for its token-parity property.
    """
    from collections import deque

    import jax
    import numpy as np
    from repro.configs.base import get_config, reduced as reduced_cfg
    from repro.serving.directory import (CacheAwareRouter, PrefixDirectory,
                                         ResponseCache, RouterConfig)
    from repro.models.model import Model
    from repro.serving.engine import ServingEngine
    from repro.serving.gateway import DoorConfig, Gateway
    from repro.serving.request import Request
    from repro.serving.actuator import FabricState, ServingActuator
    from repro.core.admission import (AdmissionController, AdmissionConfig,
                                      AdmissionVerdict, RateLimiter)
    from repro.core.controller import Controller, ControllerConfig
    from repro.core.faults import (FaultInjector, RetryingActuator,
                                   StuckLaneWatchdog)
    from repro.core.ledger import DeviceLedger
    from repro.core.policy import PolicyConfig
    from repro.core.profiles import A100_MIG
    from repro.core.signals import Snapshot, SystemSignals, TenantSignals
    from repro.core.tenancy import (BACKGROUND, TenantRegistry, TenantSpec)
    from repro.core.topology import Slot, make_p4d_cluster
    from repro.serving.metrics import LatencyWindow

    if num_tenants < 1 or replicas < 1:
        raise SystemExit("--tenants and --replicas must be >= 1")
    if route not in ("cache", "load"):
        raise SystemExit("--route must be 'cache' or 'load'")
    cfg = get_config(arch)
    if reduced:
        cfg = reduced_cfg(cfg)
    if exact_tokens:
        # float32 + reference attention: greedy argmax becomes a pure
        # function of the prompt, independent of batch shape and chunk
        # boundaries — required for cross-arm token-parity asserts
        import dataclasses as _dc
        cfg = _dc.replace(cfg, dtype="float32")
    paged = backend == "paged"
    names = ["T1"] if num_tenants == 1 else [f"L{i}"
                                             for i in range(num_tenants)]
    # ---- failure domains: deterministic fault schedule ---------------
    injector = faults
    if injector is None and chaos:
        injector = FaultInjector.plan(
            chaos_seed if chaos_seed is not None else seed + 7,
            duration_s=max(1.0, requests / qps),
            tenants=list(names), replicas=replicas,
            # a crash needs a survivor to redrive onto
            crashes=1 if replicas > 1 else 0,
            actuator_failures=2, stuck_lanes=1, fabric_windows=1,
            # gray failure only matters when migration can evacuate it;
            # plain --chaos keeps the historical schedule bit-identical
            slow_replicas=1 if (migrate and replicas > 1) else 0)
    # spec_k is passed unconditionally: requesting speculation on the
    # dense backend must hit the engine's ValueError, not silently no-op
    eng_kw = dict(max_slots=slots, seq_cap=seq_cap, backend=backend,
                  spec_k=spec_k)
    if exact_tokens:
        eng_kw["attn_impl"] = "ref"
    if paged:
        eng_kw.update(kv_dtype=kv_dtype, prefix_cache=prefix_cache)
    # one response cache per tenant, SHARED across its replicas: a
    # completion on any replica primes speculation fleet-wide
    rcaches = {}

    def tenant_kw(name):
        kw = dict(eng_kw)
        if paged and response_cache:
            kw["response_cache"] = rcaches.setdefault(name, ResponseCache())
        return kw

    # one set of weights per TENANT, shared by its replicas: replicas of
    # a model serve the same weights, so a redriven (or page-shipped)
    # request regenerates the same greedy tokens on any of them.  Replica
    # j sits on device j % n; placing arrays already on that device
    # shares their buffers, so one chip holds one copy per tenant
    devices = jax.devices()

    def new_engines(tenant_seed, name, n):
        params = Model(cfg).init(jax.random.key(tenant_seed))
        return [ServingEngine(cfg, params, seed=tenant_seed,
                              device=devices[j % len(devices)],
                              **tenant_kw(name))
                for j in range(n)]

    engines = {name: new_engines(seed + 17 * i, name, replicas)
               for i, name in enumerate(names)}
    # cluster-wide KV reuse: every paged replica publishes its prefix
    # cache into a per-tenant content-hash directory, and dispatch
    # routes to the longest held prefix (least-loaded on fallback).
    # Dense engines never publish, so their lookups all miss and the
    # router degrades to exactly the old least-loaded dispatch.
    directory = PrefixDirectory(page_size=16)
    rcfg = RouterConfig(imbalance_bound=route_imbalance,
                        staleness_bound=route_staleness)

    def wire_tenant(name):
        for j, eng in enumerate(engines[name]):
            if eng.runtime is not None:
                directory.attach(name, j, eng.kv)
        return CacheAwareRouter(directory, name, rcfg,
                                cache_aware=route == "cache")

    routers = {name: wire_tenant(name) for name in names}
    fabric = FabricState()
    fabric.t2_active = interfere
    topo = make_p4d_cluster(2)
    # Spread tenant-replicas over the topology's real slots (2 per
    # device), skipping the background tenants' fixed slots, breadth-
    # first across devices so no GPU hosts more than 2 x 2g.20gb slices
    # (4 units, within the per-GPU 7-unit budget).  The first devices
    # sit on the contended root; the rest see only ambient traffic.
    total = num_tenants * replicas
    reserved = {("h0:g1", 0), ("h0:g0", 1)}      # T2 / T3 below
    pool = [f"h{h}:g{d}" for h in range(2) for d in range(8)]
    free = [Slot(int(dev[1]), dev, idx)
            for idx in range(2) for dev in pool
            if (dev, idx) not in reserved]
    if total > len(free):
        raise SystemExit(
            f"{total} tenant-replicas exceed the cluster's capacity "
            f"({len(free)} free 2g slices)")
    # tenant identity as data: the run's registry pins the breadth-first
    # placement into each spec, and the shared ledger is built from it
    registry = TenantRegistry()
    placements = {}
    k = 0
    for i, name in enumerate(names):
        placements[name] = free[k:k + replicas]
        k += replicas
        registry.add(TenantSpec(
            name=name, replicas=replicas, rate=qps, slo_s=0.200,
            priority=1.0 + 0.25 * i,
            placement=tuple(s.key for s in placements[name])))
    registry.add(TenantSpec(
        name="T2", role=BACKGROUND, profile="7g.80gb", units=0,
        pcie_demand=fabric.t2_demand, ps_weight=fabric.t2_ps_weight,
        placement=("h0:g1:s0",)))
    registry.add(TenantSpec(
        name="T3", role=BACKGROUND, profile="2g.20gb", units=2,
        sm_util=0.95, placement=("h0:g0:s1",)))
    ledger = DeviceLedger.from_registry(
        topo, registry, A100_MIG,
        home_devices=("h0:g0",), ambient_units=3)
    # only tenants with a replica on the contended root (r0 = g0/g1)
    # share the hot fabric path
    contended = topo.root_of("h0:g1")
    for name in names:
        fabric.set_on_root(name, any(
            topo.root_of(s.device) == contended for s in placements[name]))
    now = [0.0]
    actuator = ServingActuator(engines, fabric, topo, lambda: now[0],
                               ledger=ledger,
                               rng=np.random.default_rng(seed + 1))
    # under chaos the controller actuates through the bounded-retry
    # wrapper: injected call failures back off in virtual time (charged
    # to the returned pause), exhaustion rolls back to last-known-good,
    # and retry cycles respect the controller's dwell/cooldown FSM
    # (``controller`` binds later; the lambda resolves at call time)
    retrying = None
    if injector is not None:
        retrying = RetryingActuator(
            actuator, lambda: now[0], faults=injector,
            fsm_for=lambda t: (controller.fsm_for(t)
                               if controller is not None else None))
    watchdog = (StuckLaneWatchdog(timeout_s=watchdog_timeout_s)
                if injector is not None else None)
    windows = {name: LatencyWindow() for name in names}

    # ---- request-plane front door -----------------------------------
    # The gateway fronts EVERY request (both paths), so the verdict
    # ledger always balances; --listen additionally arms backpressure:
    # bounded queues + dispatch deadlines + Kingman-derived rate limits.
    def door_cfg_for(spec):
        if not listen:
            return DoorConfig(max_queue=1_000_000, deadline_s=None)
        return DoorConfig(
            max_queue=door_queue, deadline_s=door_deadline_ms / 1e3,
            rate_limiter=RateLimiter.kingman(spec, AdmissionConfig()))

    door_cfgs = {name: door_cfg_for(registry[name]) for name in names}
    gateway = Gateway(engines, routers, door_cfgs=door_cfgs,
                      default_cfg=door_cfg_for(
                          TenantSpec(name="_default", rate=qps, slo_s=0.200)),
                      paused_until=actuator.paused_until)

    controller = None
    if with_controller:
        controller = Controller(topo, A100_MIG,
                                retrying if retrying is not None
                                else actuator,
                                ControllerConfig(policy=PolicyConfig(
                                    tau_s=0.200, persistence=2,
                                    dwell_obs=20, cooldown_obs=10)))
        controller.register_registry(registry, placements={
            **placements, "T2": [Slot(0, "h0:g1", 0)],
            "T3": [Slot(0, "h0:g0", 1)]})

    recorder = None
    if trace or trace_out:
        from repro.serving.trace import FlightRecorder
        recorder = FlightRecorder()

    def warm(name):
        for eng in engines[name]:
            warm_engine(eng, name, prompt_len)
        # attach the recorder only AFTER warming: the warm request
        # (req_id=-1, virtual time 0) must stay out of the trace just
        # like it stays out of metrics and the caches
        if recorder is not None:
            for eng in engines[name]:
                eng.tracer = recorder

    # warm the jit caches so compile time never enters the virtual clock
    # (warm_engine keeps the warm request out of metrics, the shared
    # response cache, and the prefix directory)
    for name in names:
        warm(name)
    if recorder is not None:
        gateway.tracer = recorder
        actuator.tracer = recorder
        if retrying is not None:
            retrying.tracer = recorder
        if controller is not None:
            controller.tracer = recorder

    rng = np.random.default_rng(seed)
    reqs = {name: [] for name in names}
    pending = {}
    # paged traffic draws each prompt as a shared per-tenant template
    # prefix (page-aligned, so replicas publish identical chain hashes)
    # plus a random tail — the workload shape cache-aware routing is
    # for.  Dense traffic keeps synthetic prompts (tokens unused).
    # unique_prompts drops the shared templates: every prompt is fully
    # distinct, so a crashed replica's KV is genuinely lost state (the
    # prefix directory cannot resurrect it on the survivors) — the
    # workload where page shipping vs recompute differs most honestly
    tmpl_len = (prompt_len * 2 // 3) // 16 * 16 \
        if paged and not unique_prompts else 0

    def make_prompt(templates):
        if unique_prompts:
            # real harness-drawn tokens, distinct per request: engines
            # synthesize from their own rng when handed None, and
            # identically-seeded replicas would then mint COLLIDING
            # prompts for different requests
            return rng.integers(0, cfg.vocab_size,
                                prompt_len).astype(np.int64)
        if templates is None:
            return None
        head = templates[int(rng.integers(len(templates)))]
        tail = rng.integers(0, cfg.vocab_size, prompt_len - tmpl_len)
        return np.concatenate([head, tail]).astype(np.int64)

    def gen_traffic(name, start=0.0):
        templates = (rng.integers(0, cfg.vocab_size, (4, tmpl_len))
                     if tmpl_len else None)
        arrivals = start + np.cumsum(rng.exponential(1.0 / qps, requests))
        reqs[name] = [Request(req_id=i, tenant=name, prompt_len=prompt_len,
                              max_new_tokens=max_new, arrival=float(t),
                              slo_ms=200.0,
                              prompt_tokens=make_prompt(templates))
                      for i, t in enumerate(arrivals)]
        pending[name] = deque(reqs[name])

    for name in names:
        gen_traffic(name)
    preempts = {name: 0 for name in names}
    # ---- lane-migration state ----------------------------------------
    migrations = []                       # completed-migration summaries
    redriven_ids = {name: set() for name in names}   # req_ids that moved
    drain_events = deque(sorted(drains)) if drains else deque()
    step_hist = {}       # (tenant, replica) -> deque of per-token cost
    quarantine = {}      # (tenant, replica) -> readmit time (gray)
    # per-engine availability clock: engines run in parallel
    avail = {(name, j): 0.0 for name in names for j in range(replicas)}
    next_sample = 1.0
    if verbose:
        print(f"serving {cfg.name}: {len(names)} tenant(s) x {replicas} "
              f"replica(s), {requests} req/tenant at {qps} qps "
              f"(backend={backend}, "
              f"interference={'on' if interfere else 'off'}, "
              f"controller={'on' if with_controller else 'off'})")

    # ---- §2.3 admission path: K late tenants arrive mid-run ----------
    admission = None
    admit_events = deque()
    admission_log = []
    if admit > 0:
        admission = AdmissionController(topo, registry, ledger,
                                        AdmissionConfig(), tracer=recorder)
        span = requests / qps
        admit_events = deque(
            (span * 0.3 + j * max(1.0, 1.0 / qps),
             TenantSpec(name=f"A{j}", replicas=1, rate=qps,
                        slo_s=0.200, priority=1.0))
            for j in range(admit))

    def on_admitted(spec, slots_, t):
        name = spec.name
        names.append(name)
        engines[name] = new_engines(seed + 1000 + len(names), name, 1)
        routers[name] = wire_tenant(name)
        actuator.engines[name] = engines[name]
        actuator.compute_scales.setdefault(name, 1.0)
        actuator.pauses.setdefault(name, 0.0)
        warm(name)
        windows[name] = LatencyWindow()
        gateway.door_cfgs[name] = door_cfg_for(spec)
        preempts[name] = 0
        redriven_ids[name] = set()
        avail[(name, 0)] = t
        fabric.set_on_root(name, any(
            topo.root_of(s.device) == contended for s in slots_))
        gen_traffic(name, start=t)
        if controller is not None:
            controller.register_tenant(name, "latency", slots_[0],
                                       A100_MIG[spec.profile],
                                       priority=spec.priority,
                                       slo_s=spec.slo_s, replicas=slots_)
        if verbose:
            print(f"  t={t:6.1f}s admitted {name} -> "
                  f"{[s.key for s in slots_]}")

    def run_admissions():
        while admit_events and admit_events[0][0] <= now[0]:
            t, spec = admit_events.popleft()
            verdict, slots_ = admission.decide(spec, now=t)
            admission_log.append((t, spec.name, verdict.value))
            if verdict == AdmissionVerdict.ADMIT:
                on_admitted(registry[spec.name], slots_, t)
            elif verbose:
                print(f"  t={t:6.1f}s {verdict.value} {spec.name}")
        # departures are rare in this harness, but retry anyway so a
        # queued tenant lands as soon as capacity appears
        if admission is not None and admission.queue:
            for spec, slots_ in admission.retry_queued(now=now[0]):
                admission_log.append((now[0], spec.name, "admit"))
                on_admitted(spec, slots_, now[0])

    # ---- failure-domain recovery handlers ----------------------------
    def migrate_replica(name, j, reason):
        """Evacuate replica ``j`` by KV-page shipping: drain its lanes
        WITH state, price the transfer against the fabric, and import
        each page chain into the least-loaded live peer.  Verified
        lanes are adopted warm (handoff span covers the transfer, TTFT
        stamp conserved); cold / checksum-rejected lanes take the
        recompute redrive — never a wrong token.  Returns
        ``(dst, transfer_s)`` or None when there is no live peer or the
        (possibly fault-injected) actuator call did not land."""
        live = [k for k in gateway.live_replicas(name) if k != j]
        if not live:
            return None
        dst = min(live, key=lambda k: (len(engines[name][k].queue)
                                       + len(engines[name][k].active()), k))
        n_before = len(actuator.migrations)
        act = retrying if retrying is not None else actuator
        act.migrate(name, j, dst)
        if len(actuator.migrations) == n_before:
            return None            # injected failure ate the call
        rec = actuator.migrations.pop()
        arrive = now[0] + rec["transfer_s"]
        moved = rec["warm"] + rec["cold"]
        gateway.adopt_warm(name, rec["warm"], now[0], arrive,
                           from_engine=j, to_engine=dst)
        gateway.redrive(name, rec["cold"], now[0], from_engine=j)
        redriven_ids[name].update(r.req_id for r in moved)
        if watchdog is not None:
            for r in moved:
                watchdog.forget((name, j, r.req_id))
        # the destination stalls for the transfer: migration is fabric
        # traffic like any tenant flow, and it pays in virtual time too
        avail[(name, dst)] = max(avail.get((name, dst), 0.0), arrive)
        migrations.append({
            "t": now[0], "tenant": name, "from": j, "to": dst,
            "reason": reason, "warm": len(rec["warm"]),
            "cold": len(rec["cold"]), "pages": rec["pages"],
            "bytes": rec["bytes"], "transfer_s": rec["transfer_s"],
            "attached_pages": rec["attached_pages"],
            "copied_pages": rec["copied_pages"],
            "verify_failures": rec["verify_failures"]})
        if verbose:
            print(f"  t={now[0]:6.1f}s MIGRATE {name}/r{j}->r{dst} "
                  f"({reason}): {len(rec['warm'])} warm "
                  f"({rec['attached_pages']} attached / "
                  f"{rec['copied_pages']} shipped pages, "
                  f"{rec['bytes'] / 1e6:.2f} MB in "
                  f"{rec['transfer_s'] * 1e3:.1f} ms), "
                  f"{len(rec['cold'])} recompute")
        return dst, rec["transfer_s"]

    def run_drains():
        """Planned scale-down: evacuate the replica's lanes (page
        shipping under ``migrate``, recompute redrive otherwise — never
        shed), then release its slots for good."""
        while drain_events and drain_events[0][0] <= now[0]:
            _, name, j = drain_events.popleft()
            if name not in engines or j >= len(engines[name]):
                continue
            if j not in gateway.live_replicas(name):
                continue
            if len(gateway.live_replicas(name)) <= 1:
                continue             # never drain the last live replica
            gateway.mark_dead(name, j)
            routers[name].mark_dead(j)
            directory.retract_replica(name, j)
            if recorder is not None:
                recorder.on_fault(now[0], "planned_drain", tenant=name,
                                  replica=j)
            res = migrate_replica(name, j, "drain") if migrate else None
            if res is None:
                drained = engines[name][j].drain_requests()
                redriven_ids[name].update(r.req_id for r in drained)
                if watchdog is not None:
                    for r in drained:
                        watchdog.forget((name, j, r.req_id))
                n = gateway.redrive(name, drained, now[0], from_engine=j)
                if verbose:
                    print(f"  t={now[0]:6.1f}s DRAIN {name}/r{j}: "
                          f"redrove {n} request(s) cold")
            ledger.release(name, replica=j)
            avail[(name, j)] = now[0]

    def run_gray_detector():
        """Tail-based gray-failure detection: a replica whose recent
        per-token step cost is ``gray_threshold`` x its best live
        peer's gets evacuated (warm, under ``migrate``) and quarantined
        before the per-lane watchdog would fire."""
        for name in list(names):
            live = [k for k in gateway.live_replicas(name)
                    if (name, k) not in quarantine]
            if len(live) < 2:
                continue
            means = {}
            for k in live:
                h = step_hist.get((name, k))
                if h is not None and len(h) >= 4:
                    means[k] = sum(h) / len(h)
            if len(means) < 2:
                continue
            best = min(means.values())
            if best <= 0:
                continue
            for k, m in sorted(means.items()):
                if m > gray_threshold * best:
                    evacuate_gray(name, k)
                    break            # one evacuation per tenant per tick

    def evacuate_gray(name, j):
        gateway.mark_dead(name, j)       # quarantine: reversible mask
        directory.retract_replica(name, j)
        if recorder is not None:
            recorder.on_fault(now[0], "gray_evacuate", tenant=name,
                              replica=j)
        res = migrate_replica(name, j, "gray")
        if res is None:
            drained = engines[name][j].drain_requests()
            redriven_ids[name].update(r.req_id for r in drained)
            if watchdog is not None:
                for r in drained:
                    watchdog.forget((name, j, r.req_id))
            gateway.redrive(name, drained, now[0], from_engine=j)
        quarantine[(name, j)] = now[0] + gray_cooldown_s
        step_hist.pop((name, j), None)
        if injector is not None:
            injector.log.append((now[0], "gray_evacuate", f"{name}/{j}"))
        if verbose:
            print(f"  t={now[0]:6.1f}s GRAY {name}/r{j}: evacuated, "
                  f"quarantined until t={quarantine[(name, j)]:.1f}s")

    def run_quarantine():
        for (name, j), until in list(quarantine.items()):
            if now[0] >= until:
                del quarantine[(name, j)]
                gateway.mark_live(name, j)
                avail[(name, j)] = max(avail[(name, j)], now[0])
                if verbose:
                    print(f"  t={now[0]:6.1f}s GRAY {name}/r{j}: "
                          f"readmitted")

    def crash_replica(name, j):
        """Replica death: mask it everywhere a request could still reach
        it, release every resource it held, then redrive (or, recovery
        off, shed) its in-flight requests.  Order matters: masking first
        so nothing routes to the corpse, drain releases the pages, the
        verdict/redrive decision comes last."""
        if name not in engines or j >= len(engines[name]):
            return
        live = gateway.live_replicas(name)
        if j not in live:
            if (name, j) in quarantine:
                # the quarantined gray replica died for real: make its
                # mask permanent instead of readmitting a corpse
                del quarantine[(name, j)]
                routers[name].mark_dead(j)
                ledger.release(name, replica=j)
                injector.log.append(
                    (now[0], "crash_in_quarantine", f"{name}/{j}"))
            return                       # already dead
        if len(live) <= 1:
            # never kill the last live replica: redriven work (and all
            # future arrivals) would have nowhere to land — log the
            # skip so replay identity still covers it
            injector.log.append(
                (now[0], "crash_skipped_last_replica", f"{name}/{j}"))
            return
        eng = engines[name][j]
        gateway.mark_dead(name, j)
        routers[name].mark_dead(j)
        directory.retract_replica(name, j)
        if recover and migrate:
            # warm standby adoption: the corpse's pages survive in the
            # shared host pool, so ship them instead of recomputing
            res = migrate_replica(name, j, "crash")
            if res is not None:
                ledger.release(name, replica=j)
                avail[(name, j)] = now[0]
                return
        drained = eng.drain_requests()
        ledger.release(name, replica=j)
        if watchdog is not None:
            for r in drained:
                watchdog.forget((name, j, r.req_id))
        if recover:
            n = gateway.redrive(name, drained, now[0], from_engine=j)
            redriven_ids[name].update(r.req_id for r in drained)
            verb = "redrove"
        else:
            n = gateway.abandon(name, drained, now[0])
            verb = "shed"
        avail[(name, j)] = now[0]        # dead engines never step again
        if verbose:
            print(f"  t={now[0]:6.1f}s CRASH {name}/r{j}: {verb} {n} "
                  f"in-flight request(s) "
                  f"({len(live) - 1} live replica(s) remain)")

    def stick_lane(name, j):
        """Hang one active decode lane (lowest req_id, deterministic) on
        the target replica; the watchdog detects the stalled progress
        and requeues it through the refcount-safe preemption path."""
        if name not in engines or j >= len(engines[name]):
            return
        if j not in gateway.live_replicas(name):
            return
        eng = engines[name][j]
        if eng.runtime is None:
            return
        sched = eng.runtime.sched
        lanes = [s.req.req_id for s in sched.active
                 if s.req.req_id not in sched.stuck]
        if not lanes:
            injector.log.append(
                (now[0], "stuck_skipped_no_lane", f"{name}/{j}"))
            return
        sched.mark_stuck(min(lanes))

    def apply_faults():
        for f in injector.due(now[0]):
            if recorder is not None:
                recorder.on_fault(now[0], f.kind, tenant=f.tenant,
                                  replica=f.replica, method=f.method)
            if f.kind == "replica_crash":
                crash_replica(f.tenant, f.replica)
            elif f.kind == "lane_stuck":
                stick_lane(f.tenant, f.replica)
            # actuator_fail / fabric_degrade armed inside the injector

    def run_watchdog():
        # feed every live lane's token progress, drop lanes that left
        # the active set (completed / preempted / drained), then requeue
        # whatever made no progress for the whole timeout
        live_keys = set()
        for name in names:
            for j in gateway.live_replicas(name):
                eng = engines[name][j]
                if eng.runtime is None:
                    continue
                for s in eng.runtime.sched.active:
                    key = (name, j, s.req.req_id)
                    live_keys.add(key)
                    watchdog.observe(key, s.req.generated, now[0])
        watchdog.prune(live_keys)
        for name, j, rid in watchdog.stale(now[0]):
            sched = engines[name][j].runtime.sched
            seq = sched.find(rid)
            if seq is None or seq in sched.waiting:
                continue
            if recorder is not None:
                recorder.on_preempt(seq.req, now[0],
                                    engine=f"{name}/r{j}")
            sched.preempt(seq)
            preempts[name] += 1
            injector.log.append(
                (now[0], "watchdog_requeue", f"{name}/{j}/{rid}"))
            if verbose:
                print(f"  t={now[0]:6.1f}s WATCHDOG {name}/r{j}: "
                      f"requeued stuck lane req {rid}")

    def submit_due():
        # front door first (SHED/REJECT/ACCEPT verdicts), then drain the
        # door queues into engines via the cache-aware router — a failed
        # engine submit is retried or turned into a REJECTED verdict,
        # never dropped on the floor
        for name in names:
            q = pending[name]
            while q and q[0].arrival <= now[0]:
                gateway.offer(q.popleft(), now[0])
        gateway.dispatch(now[0])

    def has_pending():
        return bool(admit_events) or any(pending[n] for n in names) or \
            gateway.queued_total() > 0 or \
            any(e.has_work() for n in names for e in engines[n])

    while has_pending():
        if admission is not None:
            run_admissions()
        if injector is not None:
            apply_faults()
        if drain_events:
            run_drains()
        if quarantine:
            run_quarantine()
        if injector is not None and migrate and recover:
            run_gray_detector()
        submit_due()
        if controller and now[0] >= next_sample:
            tenants = {}
            for name in names:
                w = windows[name]
                tenants[name] = TenantSignals(
                    p99=w.quantile(0.99, now[0]),
                    miss_rate=w.miss_rate(0.2, now[0]), rps=1.0,
                    ttft_p99=w.quantile(0.99, now[0]))
            sys = SystemSignals()
            for root in topo.roots():
                sys.pcie_bytes[root] = (fabric.t2_demand if fabric.t2_active
                                        and root == "h0:r0" else 1e9)
            controller.on_snapshot(Snapshot(now[0], tenants, sys))
            next_sample += 1.0
        # step every engine that is free, has work, and isn't paused
        stepped = False
        for name in names:
            if now[0] < actuator.paused_until(name):
                continue
            for j, eng in enumerate(engines[name]):
                if avail[(name, j)] > now[0] or not eng.has_work():
                    continue
                rep = eng.step()
                preempts[name] += len(rep.preempted)
                if rep.kind == "idle":
                    continue
                # only the prompt share of a (possibly mixed) step pays
                # fabric transfer
                transfer = (rep.prefill_tokens * 0.4e6
                            / fabric.bandwidth(name))
                # det_timing: deterministic token-cost model instead of
                # measured wall time — bit-reproducible schedules
                comp = (2e-4 + 2e-5 * rep.prefill_tokens
                        + 3e-4 * rep.decode_tokens) if det_timing \
                    else rep.compute_s
                dur = comp * actuator.compute_scale_of(name) + transfer
                if injector is not None:
                    base = dur
                    # transient fabric degradation inflates the step
                    dur *= injector.fabric_factor(now[0])
                    # gray failure: one replica quietly runs slow —
                    # per-replica, so the tail detector can see the
                    # skew against its live peers
                    dur *= injector.replica_factor(name, j, now[0])
                    # detector signal: measured step time over the
                    # model's own prediction.  Batch composition and
                    # tenant-wide effects (compute scale, fabric
                    # windows) hit every replica's ratio alike, so a
                    # sustained cross-replica skew is a sick replica
                    h = step_hist.setdefault((name, j), deque(maxlen=8))
                    h.append(dur / max(base, 1e-12))
                end = now[0] + dur
                avail[(name, j)] = end
                # gateway finalize = engine timestamps + token-stream
                # mirroring + terminal COMPLETED verdicts; start_time
                # lets the trace pin prefill-chunk spans to the step
                # window on the virtual clock
                gateway.finalize(name, eng, rep, end, start_time=now[0])
                for pr in rep.prefilled:
                    windows[name].observe(end, pr.ttft, slo=0.2)
                stepped = True
        if watchdog is not None:
            run_watchdog()
        if stepped:
            continue
        # nothing runnable now: hop to the next event
        horizon = []
        for name in names:
            if pending[name]:
                horizon.append(pending[name][0].arrival)
            if now[0] < actuator.paused_until(name) and \
                    any(e.has_work() for e in engines[name]):
                horizon.append(actuator.paused_until(name))
        horizon.extend(t for t in avail.values() if t > now[0])
        horizon.extend(t for t, _ in admit_events)
        horizon.extend(t for t, _, _ in drain_events)
        horizon.extend(t for t in quarantine.values() if t > now[0])
        # door-queued requests: retry a beat later, and never sleep past
        # a dispatch deadline (expiry is an event too)
        for door in gateway.doors.values():
            if door.queue:
                horizon.append(now[0] + 0.02)
                head = door.queue[0]
                if head.deadline is not None:
                    horizon.append(max(head.deadline, now[0] + 1e-9))
        if controller:
            horizon.append(next_sample)
        now[0] = min(horizon) if horizon else now[0] + 0.02

    out = {}
    for name in names:
        done = [r for r in reqs[name] if r.done]
        ttfts = np.array([r.ttft for r in done]) * 1e3
        itls = [v for r in done for v in r.itls]
        door = gateway.door(name)
        # every offered request carries exactly one verdict; the door's
        # ledger is the authoritative accounting (no silent drops)
        out[name] = {
            "completed": len(done),
            "offered": door.offered,
            "shed": door.shed,
            "rejected": door.rejected,
            "expired": door.expired,
            "reject_reasons": dict(door.reject_reasons),
            "redriven": door.redriven,
            "preempted": preempts[name],
            "ttft_p50_ms": float(np.quantile(ttfts, .5)) if len(done) else 0.0,
            "ttft_p99_ms": float(np.quantile(ttfts, .99)) if len(done) else 0.0,
            "itl_p99_ms": (float(np.quantile(np.array(itls) * 1e3, .99))
                           if itls else 0.0),
            # TTFTs of requests that survived an evacuation (warm or
            # cold) — what the migrate A/B compares — and the token
            # streams for exact-parity checks against a fault-free run
            "redriven_ids": sorted(int(i) for i in redriven_ids[name]),
            "redriven_ttft_ms": sorted(
                float(r.ttft * 1e3) for r in done
                if r.req_id in redriven_ids[name]),
            "outputs": {int(r.req_id): [int(t) for t in r.output_tokens]
                        for r in done},
            "ttft_by_id": {int(r.req_id): float(r.ttft * 1e3)
                           for r in done},
        }
        if verbose:
            print(f"  {name}: completed {len(done)}/{door.offered} "
                  f"(shed {door.shed} rejected {door.rejected} "
                  f"expired {door.expired}) "
                  f"TTFT p50={out[name]['ttft_p50_ms']:.1f}ms "
                  f"p99={out[name]['ttft_p99_ms']:.1f}ms "
                  f"ITL p99={out[name]['itl_p99_ms']:.1f}ms")
    out["routing"] = {name: routers[name].stats.as_dict() for name in names}
    if paged:
        out["directory"] = directory.stats.as_dict()
        if rcaches:
            out["response_cache"] = {
                name: {"hit_rate": rc.hit_rate(), "entries": len(rc)}
                for name, rc in rcaches.items()}
        if verbose:
            routed = sum(r.stats.routed_cache for r in routers.values())
            total = sum(r.stats.total for r in routers.values())
            print(f"routing: {routed}/{total} cache-routed "
                  f"(directory hit rate "
                  f"{directory.stats.hit_rate():.2f})")
    if admission is not None:
        out["admission"] = {"verdicts": admission.counts(),
                            "log": admission_log,
                            "still_queued": [s.name for s in admission.queue]}
        if verbose:
            print("admission verdicts:", out["admission"]["verdicts"])
    if controller:
        out["actions"] = controller.audit.counts()
        out["arbiter_max_units"] = controller.arbiter.max_used()
        if verbose:
            print("controller actions:", out["actions"])
    if injector is not None:
        out["faults"] = {
            "log": list(injector.log),
            "pending": injector.pending(),
            "recover": recover,
            "redriven": {name: gateway.door(name).redriven
                         for name in names},
            "watchdog_fired": watchdog.fired,
        }
        if retrying is not None:
            out["faults"]["actuator"] = dict(retrying.stats)
            out["faults"]["actuator_time_lost_s"] = retrying.time_lost_s
        if verbose and injector.log:
            print(f"faults: {len(injector.log)} event(s), "
                  f"redriven={out['faults']['redriven']}, "
                  f"watchdog_fired={watchdog.fired}, "
                  f"actuator={out['faults'].get('actuator')}")
    if migrations or migrate or drains:
        out["migrations"] = migrations
        if verbose and migrations:
            warm_n = sum(m["warm"] for m in migrations)
            cold_n = sum(m["cold"] for m in migrations)
            print(f"migrations: {len(migrations)} "
                  f"({warm_n} warm lane(s), {cold_n} recompute, "
                  f"{sum(m['bytes'] for m in migrations) / 1e6:.2f} MB "
                  f"shipped)")
    out["engines"] = engines
    out["gateway"] = gateway.counters()
    out["prometheus"] = gateway.prometheus(now[0])
    gateway.check()     # offered == completed+rejected+shed+expired+in_flight
    ledger.check()
    if recorder is not None:
        recorder.check()    # per-request: segments sum to measured E2E
        out["trace"] = recorder.breakdown(now[0])
        if trace_out:
            recorder.dump(trace_out)
        if verbose:
            print(recorder.table())
            if trace_out:
                print(f"trace written to {trace_out}")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm_3b")
    ap.add_argument("--published-widths", action="store_true",
                    help="serve the model at its published widths and depth "
                         "(default: the reduced smoke-test cut)")
    ap.add_argument("--seq-cap", type=int, default=128,
                    help="longest sequence (prompt + new tokens) per engine")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--qps", type=float, default=4.0)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--tenants", type=int, default=1)
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--interfere", action="store_true")
    ap.add_argument("--no-controller", action="store_true")
    ap.add_argument("--admit", type=int, default=0,
                    help="late-arriving tenants pushed through admission")
    ap.add_argument("--backend", choices=("dense", "paged"), default="dense",
                    help="engine KV backend: dense slot cache or the "
                         "block-table paged runtime")
    ap.add_argument("--kv-dtype", choices=("auto", "int8"), default="auto",
                    help="paged backend page-pool dtype; int8 quantizes "
                         "K/V pages with per-page-row scales")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable cross-request prefix-page sharing "
                         "(paged backend)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="paged backend: max speculative draft tokens per "
                         "decode lane (n-gram prompt-lookup drafter, "
                         "verified in the fused ragged step; 0 = off)")
    ap.add_argument("--route", choices=("cache", "load"), default="cache",
                    help="replica dispatch: route-to-longest-held-prefix "
                         "via the prefix directory ('cache') or pure "
                         "least-loaded ('load')")
    ap.add_argument("--route-imbalance", type=int, default=4,
                    help="max load lead of the cache-route target over "
                         "the least-loaded replica before falling back")
    ap.add_argument("--route-staleness", type=int, default=256,
                    help="max pending directory events before routing "
                         "falls back to least-loaded")
    ap.add_argument("--no-response-cache", action="store_true",
                    help="disable the per-tenant response cache that "
                         "self-primes speculative draft hints")
    ap.add_argument("--listen", action="store_true",
                    help="arm the gateway's backpressure policy: bounded "
                         "per-tenant door queues, dispatch deadlines "
                         "(EXPIRED past them — the 503 path) and Kingman-"
                         "derived rate limits (REJECTED fast — the 429 "
                         "path)")
    ap.add_argument("--door-queue", type=int, default=64,
                    help="--listen: bounded door-queue depth per tenant")
    ap.add_argument("--door-deadline-ms", type=float, default=1000.0,
                    help="--listen: queued requests not dispatched within "
                         "this deadline are EXPIRED")
    ap.add_argument("--trace", action="store_true",
                    help="arm the per-request flight recorder (span "
                         "timelines whose segments sum to measured E2E, "
                         "plus controller actions on a shared timeline)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace_event JSON here "
                         "(implies --trace)")
    ap.add_argument("--chaos", action="store_true",
                    help="arm deterministic fault injection: a seeded "
                         "schedule of replica crashes, actuator failures, "
                         "stuck lanes and fabric degradation "
                         "(core/faults.py)")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="fault-schedule seed (default: --seed + 7); the "
                         "same seed replays the same faults bit-identically")
    ap.add_argument("--no-recover", action="store_true",
                    help="keep the fault schedule but disable recovery: "
                         "crashed replicas shed their in-flight requests "
                         "instead of redriving them (A/B baseline)")
    ap.add_argument("--migrate", action="store_true",
                    help="recover by verified KV-page shipping instead of "
                         "recompute: crashed / drained / gray-failed "
                         "replicas ship their lanes' page chains to a live "
                         "peer, chain-hash-verified before commit "
                         "(serving/migrate.py)")
    ap.add_argument("--drain-at", action="append", default=[],
                    metavar="T:TENANT:REPLICA",
                    help="planned scale-down: at virtual time T evacuate "
                         "TENANT's replica REPLICA (repeatable; lanes are "
                         "migrated or redriven, never shed)")
    ap.add_argument("--det-timing", action="store_true",
                    help="deterministic per-token step-cost model instead "
                         "of measured wall time: bit-reproducible virtual "
                         "schedules (token-parity A/Bs need this)")
    ap.add_argument("--unique-prompts", action="store_true",
                    help="no shared prompt templates: each prompt is fully "
                         "distinct, so crashed-replica KV cannot be "
                         "resurrected from the prefix directory")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_checkout_compile_cache()
    drains = []
    for spec in args.drain_at:
        try:
            t, tenant, rep = spec.split(":")
            drains.append((float(t), tenant, int(rep)))
        except ValueError:
            raise SystemExit(f"--drain-at wants T:TENANT:REPLICA, "
                             f"got {spec!r}")
    serve(arch=args.arch, requests=args.requests, qps=args.qps,
          prompt_len=args.prompt_len, max_new=args.max_new,
          slots=args.slots, num_tenants=args.tenants,
          replicas=args.replicas, interfere=args.interfere,
          with_controller=not args.no_controller, seed=args.seed,
          admit=args.admit, backend=args.backend, kv_dtype=args.kv_dtype,
          prefix_cache=not args.no_prefix_cache, spec_k=args.spec_k,
          route=args.route, route_imbalance=args.route_imbalance,
          route_staleness=args.route_staleness,
          response_cache=not args.no_response_cache, listen=args.listen,
          door_queue=args.door_queue,
          door_deadline_ms=args.door_deadline_ms,
          trace=args.trace, trace_out=args.trace_out,
          chaos=args.chaos, chaos_seed=args.chaos_seed,
          recover=not args.no_recover,
          migrate=args.migrate, drains=drains or None,
          det_timing=args.det_timing,
          unique_prompts=args.unique_prompts,
          reduced=not args.published_widths, seq_cap=args.seq_cap)


if __name__ == "__main__":
    main()

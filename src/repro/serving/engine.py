"""Synchronous continuous-batching inference engine.

``Engine`` exposes the classic three-call serving API:

    eng = Engine(cfg)                      # or Engine(cfg, scfg, params)
    eng.add_request([1, 2, 3], max_new_tokens=16)
    while eng.step():                      # one prefill OR one decode step
        pass
    results = eng.collect()                # finished RequestResults

plus ``run_offline(prompts)``, the batch driver used by ``launch/serve.py``
and the throughput benchmark.  The engine serves *every* registered cache
family (see ``models.cache_spec``): token-addressable KV and MLA latent
pages, sliding-window page rings, SSM/RG-LRU state slots, and the enc-dec
pinned cross cache.  Prefill writes straight into the pools
(``prefill_paged``): each admitted request's pages/slot are bound up front
and the prompt — or, with the radix prefix cache enabled, only its uncached
tail — is computed at a bucketed length; several same-bucket queued requests
are admitted in one batched prefill call.  With
``ServeConfig.prefill_chunk_tokens > 0`` long prompts prefill in
page-aligned *chunks* that interleave with decode steps (see
``scheduler``): a mid-prefill request keeps its pages and an ``n_filled``
cursor, completed pages publish to the radix cache after every chunk, and
the first token comes from the final chunk's logits.  The engine compiles a
bounded program set: one chunk prefill per (length bucket, pow2 admission
batch) — with chunking, shapes are keyed by the chunk budget, never by
individual prompt lengths — one fixed-shape ``[max_slots]`` paged decode
step, and one page-copy (COW fork) kernel — traffic mix never triggers
recompilation, and the jitted steps are cached per (``ArchConfig``,
attention backend) so every Engine instance (and test) reuses them.  The
paged attends route through the backend registry
(``ServeConfig.attn_backend``: ``auto|reference|pallas``, see
``models.attn_backend``), and the engine hands each step flat per-step
metadata (``decode_meta`` / ``prefill_meta``) — page-table rows, positions,
physical write targets — derived once on the host per step instead of per
layer.

Frontend inputs for enc-dec (audio frames) and vlm (image embeddings) archs
are synthesized *per request id* (``fold_in(seed key, rid)``, fixed shapes),
so the same request sees identical inputs no matter how it is batched — this
is what makes ``--verify`` meaningful for those families.  The static
baseline keys the same draw on *request index*, so an engine-vs-static
comparison for those archs assumes a fresh Engine (rids 0..N-1, as every
current caller uses); a reused engine's later runs continue the rid
sequence and draw different frontend inputs.

``generate_static`` is the static-batching baseline kept for comparison and
verification: contiguous per-request KV caches, the whole batch padded
together and decoded until its slowest member finishes.

**Overlapped host/device pipeline.**  Every step is internally split into a
*dispatch* half (scheduler decision, host-side meta build, jitted-call
launch — jax dispatch is asynchronous, so control returns while the device
works) and a *collect* half (block on the device output, token bookkeeping,
retirement).  ``step()`` runs them back-to-back (the synchronous loop every
existing caller sees); ``pump()`` additionally *stages* the host plan for
step N+1 between the two halves — while step N's jitted call runs on
device, the engine pre-builds the next decode step's page tables, positions
and ``decode_meta`` pytree, and validates the staged plan against reality
at the next dispatch (a retirement, EOS, admission, preemption or page-
boundary growth invalidates it; validation is an exact fingerprint match,
so a used staged plan is bit-identical to a replan and tokens stay exact).
``run_offline(..., overlap=True)`` and the async streaming front-end
(``serving.server``) drive ``pump()``; overlap hit rates are counted under
``engine.overlap_*`` (``engine.overlap_skipped{reason}`` says why a step
staged nothing).  Both loops time their phases with ``Tracer.phase``:
``dispatch`` (``schedule``, ``plan``, ``upload``, ``launch.<kind>``),
``stage`` and ``collect`` (``sync.<kind>``, ``emit``) appear on the tracer's
engine-loop track, visibly overlapping the step spans in Perfetto, and in a
captured JAX profile on the device trace's clock.

Streaming hooks: ``on_token(rid, index, token, t)`` fires as each token is
collected (a preemption replay re-fires earlier indexes; stream consumers
dedup by index — greedy replay regenerates the identical prefix), and
finished requests are popped with ``collect()``.  ``cancel(rid)`` aborts a
queued or live request (client disconnect), releasing its slot and pages.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig, ServeConfig
from ..models.attn_backend import (
    decode_meta, prefill_meta, resolve_backend, verify_meta)
from ..models.params import init_tree
from ..models.registry import build_model, init_cache, init_params
from ..models.steps import make_serve_step, unpack_counts
from .admission import AdmissionController, HealthState
from .faults import FaultInjector, FaultPlan, RequestFault
from .kv_pool import NULL_PAGE, PagedKVPool, StateSlotPool
from .radix_cache import RadixCache
from .scheduler import Admission, Request, Scheduler
from .speculate import NgramProposer, accept_length, speculation_k
from .telemetry import MetricsRegistry, Tracer, shared_metrics


@dataclasses.dataclass
class RequestResult:
    rid: int
    prompt: List[int]
    tokens: List[int]                 # generated tokens (greedy), incl. EOS
    latency: float                    # arrival -> finish (s)
    ttft: float                       # arrival -> first token (s).  First
                                      # token *ever* produced: a preemption
                                      # replay does not reset it, so this
                                      # agrees with tracer-sourced ttft_s
                                      # (and is what shared_metrics consumes)
    n_preemptions: int = 0
    cached_tokens: int = 0            # prompt tokens reused from the cache
    # --- per-request timing from the lifecycle tracer ---
    ttft_s: float = 0.0               # == ttft (tracer-sourced spelling)
    finish_s: float = 0.0             # == latency (tracer-sourced spelling)
    tpot_s: float = 0.0               # time per output token after the first
    n_prefill_chunks: int = 0         # prefill calls run (incl. replays)
    preempted: bool = False
    error: str = ""                   # nonempty: rejected/cancelled/shed/
                                      # quarantined; tokens hold whatever the
                                      # request produced before the terminal
    retry_after_s: float = 0.0        # backoff hint for shed requests

    @property
    def failed(self) -> bool:
        return bool(self.error)


@dataclasses.dataclass
class _Pending:
    """One dispatched-but-not-collected engine step: the device is (or may
    be) still computing ``out_dev``; ``finish`` blocks on it and runs the
    host-side bookkeeping."""
    kind: str       # prefill | prefill_chunk | restore | decode | verify
    payload: Any                      # scheduler action payload
    rows: Any                         # prefill row tuples / decode active list
    out_dev: Any                      # device logits / next-token array
    t0: float                         # dispatch start (step span start)
    waiting: bool                     # decode-ready slots parked behind this
    staged: bool = False              # decode launched from a staged plan
    moe: Optional[Dict[str, int]] = None   # the step's expert pairs (MoE)


@dataclasses.dataclass
class _StagedDecode:
    """A pre-built host plan for the *next* decode step, computed while the
    current step runs on device.  ``fp`` is the exact post-step fingerprint
    (slot, rid, pos, owned pages, draft len) the plan assumed; dispatch uses
    the plan only when reality still matches, so a used plan is bit-identical
    to a replan.  Only plain decode steps stage (a verify step's draft is
    unknowable a step ahead), so the staged draft length is always 0 — the
    field keeps the fingerprint honest if that ever changes."""
    active: Tuple[int, ...]
    fp: Tuple[Tuple[int, int, int, int, int], ...]
    meta: Dict[str, Any]              # decode_meta, already device-resident


def _copy_page_fn(kv, src, dst):
    """Fork physical page ``src`` into ``dst`` across every layer (COW)."""
    return jax.tree.map(lambda a: a.at[:, dst].set(a[:, src]), kv)


def _zero_pages_fn(kv, pages):
    """Zero physical pages ``pages`` across every layer (quarantine scrub).
    ``pages`` is a fixed-width int32 vector padded with NULL_PAGE — zeroing
    the reserved sink page is harmless, so one compiled shape covers every
    scrub."""
    return jax.tree.map(
        lambda a: a.at[:, pages].set(jnp.zeros((), a.dtype)), kv)


def _poison_pages_fn(kv, pages):
    """NaN-fill the floating leaves of ``pages`` (fault injection only).
    int8 payload leaves can't hold NaN and are left alone — their bf16
    scale leaves carry the poison through dequant instead."""
    def poison(a):
        if not jnp.issubdtype(a.dtype, jnp.floating):
            return a
        return a.at[:, pages].set(jnp.asarray(jnp.nan, a.dtype))
    return jax.tree.map(poison, kv)


@functools.lru_cache(maxsize=None)
def _paged_steps(cfg: ArchConfig, mesh=None, attn_backend: str = "reference"):
    """Jitted (prefill_paged, decode_paged, verify_paged, copy_page,
    zero_pages, poison_pages) steps, cached per (config, attention backend)
    so every Engine instance reuses compilations.  The kv and state pool
    arguments are donated; callers always rebind them.  The verify step is
    built lazily on first use so non-speculative engines never trace it."""
    return (jax.jit(make_serve_step(cfg, mesh, "prefill_paged", attn_backend),
                    donate_argnums=(1, 2)),
            jax.jit(make_serve_step(cfg, mesh, "prefill_paged_cont",
                                    attn_backend), donate_argnums=(1, 2)),
            jax.jit(make_serve_step(cfg, mesh, "decode_paged", attn_backend),
                    donate_argnums=(1, 2)),
            jax.jit(make_serve_step(cfg, mesh, "verify_paged", attn_backend),
                    donate_argnums=(1, 2)),
            jax.jit(_copy_page_fn, donate_argnums=(0,)),
            jax.jit(_zero_pages_fn, donate_argnums=(0,)),
            jax.jit(_poison_pages_fn, donate_argnums=(0,)))


def _synthetic_frontend(cfg: ArchConfig, scfg: ServeConfig, seed: int,
                        rid: int) -> Optional[np.ndarray]:
    """Deterministic per-request frontend input (enc-dec frames / vlm image
    embeddings) — a fixed shape drawn from ``fold_in(PRNGKey(seed), rid)`` so
    every serving path (any batch shape, any engine) sees the same values."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), rid)
    if cfg.enc_dec:
        return np.asarray(jax.random.normal(
            key, (scfg.enc_len, cfg.frontend_dim), jnp.bfloat16))
    if cfg.n_image_tokens:
        return np.asarray(jax.random.normal(
            key, (cfg.n_image_tokens, cfg.frontend_dim), jnp.bfloat16))
    return None


def _upload(host: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
    """A step's host-built inputs, on the device."""
    return {k: jnp.asarray(v) for k, v in host.items()}


def _pow2_pad(n: int, cap: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


class Engine:
    """Continuous-batching engine over paged + state-slot cache pools."""

    def __init__(self, cfg: ArchConfig, scfg: Optional[ServeConfig] = None,
                 params=None, *, mesh=None, seed: int = 0,
                 tracer: Optional[Tracer] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 faults: Optional[FaultPlan] = None):
        self.cfg = cfg
        self.scfg = scfg or ServeConfig()
        self.model = build_model(cfg)
        self.spec = self.model.cache_spec()
        self.seed = seed
        self.params = init_params(cfg, jax.random.PRNGKey(seed)) \
            if params is None else params
        # telemetry: one registry + one lifecycle tracer shared by every
        # layer (pool, radix cache, scheduler, engine) — all host-side
        # appends, so tracing on changes no math and no emitted token
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else Tracer()
        self.pool = PagedKVPool(cfg, self.scfg, metrics=self.metrics)
        self.states = StateSlotPool(cfg, self.scfg) \
            if self.spec.state_slots else None
        if self.scfg.prefix_cache and not self.spec.prefix_cacheable:
            print(f"[engine] WARNING: prefix cache disabled for {cfg.name}: "
                  f"cache family {self.spec.describe()} is not "
                  f"token-addressable/immutable; serving uncached")
            self.radix = None
        else:
            self.radix = RadixCache(self.pool, self.scfg.page_size,
                                    self.scfg.cache_eviction,
                                    metrics=self.metrics) \
                if self.scfg.prefix_cache else None
        self.sched = Scheduler(self.scfg, self.pool, self.radix, self.states,
                               metrics=self.metrics, tracer=self.tracer)
        self._next_rid = 0
        self.attn_backend = resolve_backend(self.scfg.attn_backend)
        (self._prefill, self._prefill_cont, self._decode, self._verify,
         self._copy, self._zero, self._poison) = _paged_steps(
             cfg, mesh, self.attn_backend)
        # fault tolerance: optional chaos injector, health lifecycle, and
        # deadline-aware admission control (serving/{faults,admission})
        self.injector = FaultInjector(faults, self.metrics) \
            if faults is not None else None
        self.health = HealthState()
        self.admission = AdmissionController(
            self.scfg.max_slots, metrics=self.metrics, seed=seed) \
            if self.scfg.admission_control else None
        # speculative decoding: draft length after the family gate (paged
        # non-enc-dec only) and the weight-free prompt-lookup proposer
        self.spec_k = speculation_k(cfg, self.spec, self.scfg)
        self.proposer = NgramProposer(self.spec_k) if self.spec_k else None
        # engine step counters (previously ad-hoc instance fields)
        self._m_prefill_steps = self.metrics.counter(
            "engine.prefill_steps", "prefill calls (admissions + chunks)")
        self._m_multi_admit = self.metrics.counter(
            "engine.multi_admit_prefills", "prefill calls admitting >1 req")
        self._m_chunk_steps = self.metrics.counter(
            "engine.chunked_prefill_steps", "continuation-chunk calls")
        self._m_restores = self.metrics.counter(
            "engine.state_restores", "checkpoint-restore re-admissions")
        # prefill work accounting: padded counts what the device computed
        # (pow2 rows x bucket), actual counts real prompt tokens — the gap is
        # padding waste, the thing chunking + bucketing are trading against
        self._m_padded = self.metrics.counter(
            "engine.prefill_padded_tokens", "device-computed prefill tokens")
        self._m_actual = self.metrics.counter(
            "engine.prefill_actual_tokens", "real prompt tokens prefilled")
        self._h_decode_step = self.metrics.histogram(
            "engine.decode_step_s", "fixed-shape decode step wall time")
        # decode-plan page accounting: walked is each row's live extent
        # (the table columns the paged decode kernel visits), table is
        # rows x table width (what a sweep of the whole table visits)
        pages = self.metrics.counter(
            "engine.decode_pages", "page-table columns of the decode plans "
            "built, by kind (walked | table)", labels=("kind",))
        self._m_pages_walked = pages.labels(kind="walked")
        self._m_pages_table = pages.labels(kind="table")
        # expert-share accounting (MoE): the (token, expert) pairs routed,
        # those whose expert is held here, and the held experts they touch,
        # summed over the MoE layers, read from each step's outputs
        self._moe_layers = cfg.n_layers - cfg.first_k_dense \
            if cfg.is_moe else 0
        pairs = self.metrics.counter(
            "moe.pairs", "(token, expert) pairs of the MoE layers, by kind "
            "(held: computed here | routed: tokens x top_k)",
            labels=("kind",))
        self._m_pairs_held = pairs.labels(kind="held")
        self._m_pairs_routed = pairs.labels(kind="routed")
        self._m_experts_touched = self.metrics.counter(
            "moe.experts_touched", "held experts with at least one pair, "
            "summed over MoE layers and steps, by step kind "
            "(decode | prefill)", labels=("kind",))
        # speculative-decoding accounting: drafts proposed vs accepted
        self._m_spec_proposed = self.metrics.counter(
            "engine.spec_proposed", "draft tokens proposed by the n-gram "
            "speculator")
        self._m_spec_accepted = self.metrics.counter(
            "engine.spec_accepted", "draft tokens accepted by the verify "
            "step (emitted without their own decode launch)")
        # decode-stall bookkeeping: wall time decode-ready slots spend parked
        # behind non-decode steps (the head-of-line cost chunking bounds)
        self._h_stall = self.metrics.histogram(
            "engine.decode_stall_s", "time decode-ready slots sat parked "
            "behind non-decode steps, per decode step")
        self._stall_accum = 0.0
        # overlapped-pipeline bookkeeping (pump()): staged next-step plans
        self._staged: Optional[_StagedDecode] = None
        self._m_overlap_staged = self.metrics.counter(
            "engine.overlap_staged", "next-step plans staged while the "
            "device ran the current step")
        self._m_overlap_used = self.metrics.counter(
            "engine.overlap_used", "staged plans whose fingerprint still "
            "matched at dispatch (host work hidden behind device time)")
        self._m_overlap_dropped = self.metrics.counter(
            "engine.overlap_dropped", "staged plans invalidated by a "
            "retirement/EOS/admission/preemption before dispatch")
        self._m_overlap_skipped = self.metrics.counter(
            "engine.overlap_skipped", "steps after which no plan was "
            "staged, by reason", labels=("reason",))
        # request-lifecycle admission guards
        self._inflight: set = set()   # rids queued, live, or awaiting collect
        self._m_reject_budget = self.metrics.counter(
            "sched.rejections", "admission attempts blocked, by reason",
            labels=("reason",)).labels(reason="no_budget")
        # fault-tolerance accounting: quarantines (NaN logits / step errors),
        # client cancels, deadline evictions, and admission sheds
        self._m_quarantined = self.metrics.counter(
            "engine.quarantined", "requests terminal-failed mid-flight by "
            "the per-step fault guard (nan_logits | step_error)")
        self._m_cancelled = self.metrics.counter(
            "engine.cancelled", "requests cancelled by the client "
            "(disconnects), queued or live")
        self._m_deadline_evict = self.metrics.counter(
            "engine.deadline_evictions", "requests expired by the deadline "
            "sweep (queued or mid-flight)")
        self._m_shed = self.metrics.counter(
            "admission.shed", "Requests shed at admission, by reason.",
            labels=("reason",))
        # streaming hook: called as each token is *collected* (host side).
        # A preemption replay re-fires earlier indexes with identical tokens
        # (greedy determinism); stream consumers dedup by index.
        self.on_token: Optional[Callable[[int, int, int, float], None]] = None

    # legacy spelling kept for callers/tests that read the old counter field
    @property
    def _restores(self) -> int:
        return self._m_restores.value

    # ----------------------------------------------------------- public API

    def add_request(self, prompt: Sequence[int], max_new_tokens: int = 16,
                    rid: Optional[int] = None, *,
                    deadline_s: Optional[float] = None,
                    ttft_deadline_s: Optional[float] = None) -> int:
        """Queue a prompt; returns the request id.

        A request with no token budget under ``max_len`` (prompt too long,
        or a non-positive budget after clamping) is rejected *gracefully*:
        it is counted under ``sched.rejections{reason=no_budget}`` and
        surfaces from ``collect()`` as a failed ``RequestResult`` (empty
        tokens, ``error`` set) instead of raising mid-batch and stranding
        already-admitted requests.  The only submission-time exception is a
        ``rid`` collision with an in-flight request — accepting it would
        corrupt tracer and result bookkeeping, so that raises immediately.

        ``deadline_s`` / ``ttft_deadline_s`` are relative QoS budgets
        (seconds from now; ``ServeConfig.default_*`` fill absent ones).
        With ``ServeConfig.admission_control`` on, a request whose deadline
        the calibrated queue model can't meet is *shed* at the door —
        failed result with ``error="shed: overloaded"`` and a jittered
        ``retry_after_s`` backoff hint — and admitted requests that blow
        their deadline mid-flight are evicted by the scheduler sweep.  A
        draining engine sheds every new request with reason ``draining``."""
        if rid is None:
            rid = self._next_rid
        elif rid in self._inflight:
            raise ValueError(f"request id {rid} collides with an in-flight "
                             f"request (queued, live, or awaiting collect)")
        self._next_rid = max(self._next_rid, rid) + 1
        self._inflight.add(rid)
        prompt = [int(t) for t in prompt]
        now = time.perf_counter()
        max_new = min(int(max_new_tokens), self.scfg.max_len - len(prompt))
        if max_new < 1:
            self._m_reject_budget.inc()
            req = Request(rid=rid, prompt=prompt, max_new=0, arrival=now,
                          error=f"no_budget: prompt len {len(prompt)} leaves "
                                f"no token budget under max_len="
                                f"{self.scfg.max_len}")
            req.t_finish = now
            self.sched.finished.append(req)
            self.tracer.on_rejected(rid, now, "no_budget")
            return rid
        if deadline_s is None and self.scfg.default_deadline_s > 0:
            deadline_s = self.scfg.default_deadline_s
        if ttft_deadline_s is None and self.scfg.default_ttft_deadline_s > 0:
            ttft_deadline_s = self.scfg.default_ttft_deadline_s
        if self.health.draining:
            return self._shed(rid, prompt, now, "draining")
        if self.admission is not None:
            reason = self.admission.check(len(self.sched.queue),
                                          deadline_s, ttft_deadline_s)
            if reason is not None:
                return self._shed(rid, prompt, now, reason)
        req = Request(rid=rid, prompt=prompt, max_new=max_new, arrival=now,
                      deadline=now + deadline_s if deadline_s else None,
                      ttft_deadline=(now + ttft_deadline_s
                                     if ttft_deadline_s else None))
        self.sched.add(req)
        return rid

    def _shed(self, rid: int, prompt: List[int], now: float,
              reason: str) -> int:
        """Refuse a request at the door: failed result, backoff hint, and a
        ``rejected`` tracer terminal — the engine never does work for it."""
        retry = (self.admission.retry_after_s(len(self.sched.queue))
                 if self.admission is not None else 1.0)
        self._m_shed.labels(reason=reason).inc()
        req = Request(rid=rid, prompt=prompt, max_new=0, arrival=now,
                      error=f"shed: {reason}", retry_after_s=retry)
        req.t_finish = now
        self.sched.finished.append(req)
        self.tracer.on_rejected(rid, now, reason)
        return rid

    def cancel(self, rid: int) -> bool:
        """Abort a queued or live request (e.g. a disconnected streaming
        client): its slot/pages are released immediately and it surfaces
        from ``collect()`` as a failed result carrying whatever tokens it
        had produced.  Returns False if ``rid`` is not queued or live."""
        now = time.perf_counter()
        for req in list(self.sched.queue):
            if req.rid == rid:
                self.sched.queue.remove(req)
                self.sched._m_queue.set(len(self.sched.queue))
                req.error = "cancelled"
                req.t_finish = now
                self.sched.finished.append(req)
                self._m_cancelled.inc()
                self.tracer.on_rejected(rid, now, "cancelled")
                return True
        for i, slot in enumerate(self.sched.slots):
            if slot is not None and slot.req.rid == rid:
                self._drop_staged()           # slot set is about to change
                slot.req.error = "cancelled"
                slot.req.t_finish = now
                # retire -> _unbind drops *every* page reference the slot
                # holds — including the not-yet-published tail pages of a
                # mid-chunked-prefill slot (n_filled < len(prompt)); the
                # radix cache keeps only the pages it already co-owns
                self.sched.retire(i)
                self._m_cancelled.inc()
                self.tracer.on_finished(rid, now, len(slot.req.generated),
                                        error="cancelled")
                return True
        return False

    def step(self) -> bool:
        """Run one scheduler action (a prefill, a continuation chunk, a
        restore, or a decode) synchronously. False when idle.

        A :class:`RequestFault` raised at the pre-launch seam (injected
        step error) quarantines only the offending request — the donated
        kv/state buffers were not touched yet, so the surviving slots
        simply run on the next step, token streams intact."""
        try:
            pending = self._dispatch_next()
        except RequestFault as e:
            self._quarantine_rid(e.rid, e.kind)
            return True
        if pending is None:
            return False
        self._finish_step(pending)
        return True

    def pump(self) -> bool:
        """One *overlapped* step: dispatch the next action, stage the host
        plan for the step after it while the device computes, then collect.
        Token-for-token identical to ``step()`` (a staged plan is used only
        when it fingerprints equal to a replan); the win is host time hidden
        behind device time.  False when idle."""
        try:
            pending = self._dispatch_next()
        except RequestFault as e:
            self._quarantine_rid(e.rid, e.kind)
            return True
        if pending is None:
            return False
        self._stage_next(pending)
        self._finish_step(pending, overlap=True)
        return True

    def collect(self) -> List[RequestResult]:
        """Pop every finished request as a RequestResult."""
        out = []
        for req in self.sched.finished:
            rec = self.tracer.requests.get(req.rid)
            latency = (req.t_finish - req.arrival
                       if req.t_finish is not None else 0.0)
            res = RequestResult(
                rid=req.rid, prompt=req.prompt, tokens=list(req.generated),
                latency=latency,
                ttft=(req.t_first - req.arrival
                      if req.t_first is not None else 0.0),
                n_preemptions=req.n_preemptions,
                cached_tokens=req.cached_tokens,
                error=req.error, retry_after_s=req.retry_after_s)
            if rec is not None and rec.t_finish is not None:
                # per-request timing from the lifecycle tracer (one source
                # of truth for spans, results, and the trace report)
                t_first = rec.t_first if rec.t_first is not None \
                    else rec.t_finish
                res.ttft_s = t_first - rec.arrival
                res.finish_s = rec.t_finish - rec.arrival
                res.tpot_s = (rec.t_finish - t_first) \
                    / max(len(req.generated) - 1, 1)
                res.n_prefill_chunks = rec.n_chunks
                res.preempted = rec.n_preemptions > 0
            if self.admission is not None and not res.failed:
                # calibrate the queue model on what actually served
                self.admission.observe_result(res.ttft, res.latency)
            self._inflight.discard(req.rid)
            out.append(res)
        self.sched.finished.clear()
        return out

    def run_offline(self, prompts: Sequence[Sequence[int]],
                    max_new_tokens=16, *,
                    overlap: bool = False) -> Tuple[List[RequestResult], Dict]:
        """Admit every prompt, drive the loop dry, return (results, metrics).

        ``max_new_tokens`` is an int or a per-prompt sequence.  With
        ``overlap=True`` the loop runs the pipelined ``pump()`` instead of
        the synchronous ``step()`` (same tokens, host work hidden behind
        device time)."""
        budgets = ([max_new_tokens] * len(prompts)
                   if isinstance(max_new_tokens, int) else list(max_new_tokens))
        # a reused engine must not leak the previous run's trailing stall
        # time (or a stale staged plan) into this run's accounting
        self._stall_accum = 0.0
        self._staged = None
        self.health.mark_healthy()
        t0 = time.perf_counter()
        for p, m in zip(prompts, budgets):
            self.add_request(p, m)
        drive = self.pump if overlap else self.step
        while drive():
            pass
        wall = time.perf_counter() - t0
        results = sorted(self.collect(), key=lambda r: r.rid)
        # latency/TTFT percentiles come from requests that actually served —
        # a rejected request has no first token and would drag p50 to zero
        ok = [r for r in results if not r.failed]
        metrics = shared_metrics(
            len(results), sum(len(r.tokens) for r in results),
            [r.latency for r in ok], wall,
            ttfts=[r.ttft for r in ok],
            prompt_tokens=sum(len(r.prompt) for r in results),
            cached_tokens=sum(r.cached_tokens for r in results),
            prefill_steps=self._m_prefill_steps.value,
            prefill_padded_tokens=self._m_padded.value,
            prefill_actual_tokens=self._m_actual.value,
            decode_step_s=self._h_decode_step.values,
            decode_stall_s=self._h_stall.values)
        metrics["rejected_requests"] = len(results) - len(ok)
        metrics["multi_admit_prefills"] = self._m_multi_admit.value
        metrics["chunked_prefill_steps"] = self._m_chunk_steps.value
        metrics["state_restores"] = self._m_restores.value
        # decode hot-loop visibility: which attention backend served this run
        metrics["attn_backend"] = self.attn_backend
        if self.spec_k:
            metrics["spec_tokens"] = self.spec_k
            metrics["spec_proposed"] = self._m_spec_proposed.value
            metrics["spec_accepted"] = self._m_spec_accepted.value
            metrics["spec_accept_rate"] = (
                self._m_spec_accepted.value
                / max(self._m_spec_proposed.value, 1))
        if self.radix is not None:
            metrics["cache_pages"] = len(self.radix.cached_pages)
            metrics["cache_evictions"] = self.radix.evictions
        return results, metrics

    def metrics_snapshot(self) -> Dict[str, Any]:
        """Full registry snapshot (counters/gauges/histograms of every
        serving layer) — the ``--metrics-json`` payload."""
        return self.metrics.snapshot()

    # --------------------------------------------------- dispatch / collect

    def _drop_staged(self) -> None:
        if self._staged is not None:
            self._m_overlap_dropped.inc()
            self._staged = None

    def _dispatch_next(self) -> Optional[_Pending]:
        """Scheduler decision + host-side meta build + jitted-call launch
        for one step; returns without blocking on the device (jax dispatch
        is asynchronous).  ``None`` on drain — trailing stall time
        accumulated behind non-decode steps is flushed there so it cannot
        leak into a later run on a reused engine."""
        with self.tracer.phase("dispatch") as ph:
            with self.tracer.phase("schedule"):
                action = self._schedule()
            if action is None:
                ph.discard()                  # an idle poll: no span
                self._drop_staged()
                if self.injector is not None:
                    self.injector.on_drain(self)
                if self._stall_accum:
                    self._h_stall.observe(self._stall_accum)
                    self._stall_accum = 0.0
                return None
            waiting = bool(self.sched.decode_ready())
            kind, payload = action
            if kind != "decode":
                self._drop_staged()
            t0 = time.perf_counter()
            staged = False
            if kind == "prefill":
                rows, out = self._launch_prefill(payload, t0)
            elif kind == "prefill_chunk":
                rows, out = self._launch_chunks(payload, t0)
            elif kind == "restore":
                self._run_restore(payload, t0)
                rows, out = None, None
            elif self.spec_k:
                # speculation on: every decode-ready step runs as a small-q
                # verify step (with an empty draft it degenerates to decode)
                kind = "verify"
                if self.injector is not None:
                    self.injector.before_launch(self, "verify", payload)
                rows, out = payload, self._launch_verify(payload)
            else:
                if self.injector is not None:
                    self.injector.before_launch(self, "decode", payload)
                out, staged = self._launch_decode(payload)
                rows = payload
            return _Pending(kind=kind, payload=payload, rows=rows,
                            out_dev=out, t0=t0, waiting=waiting,
                            staged=staged)

    def _schedule(self) -> Optional[Tuple]:
        """The fault injector's tick, the deadline sweep and the scheduler's
        next action (None when there is no work)."""
        if self.injector is not None:
            self.injector.on_tick(self)
        if self.admission is not None:
            self._evict_deadlines()
        try:
            return self.sched.next_action()
        except RuntimeError:
            # injected pool pressure can manufacture a scheduler deadlock the
            # real pool would never see; give the hostage pages back and
            # retry once before treating it as genuine exhaustion
            if self.injector is None \
                    or not self.injector.release_pressure(self):
                raise
            return self.sched.next_action()

    def _finish_step(self, pending: _Pending, overlap: bool = False) -> None:
        """Block on the pending step's device output and run the host-side
        bookkeeping: token appends, retirement, step span, stall account.
        ``step()`` and ``pump()`` (``overlap``) collect alike."""
        with self.tracer.phase("collect", kind=pending.kind):
            if pending.kind == "decode":
                self._collect_decode(pending)
            elif pending.kind == "verify":
                self._collect_verify(pending)
            elif pending.kind in ("prefill", "prefill_chunk"):
                self._collect_prefill(pending)
            t1 = time.perf_counter()
            n_rows = 1 if pending.kind == "restore" else len(pending.payload)
            extra = {"staged": pending.staged} \
                if pending.kind == "decode" else {}
            if pending.moe is not None:
                extra.update(pending.moe)
            self.tracer.step_span(pending.kind, pending.t0, t1, rows=n_rows,
                                  decode_waiting=pending.waiting, **extra)
        if pending.kind in ("decode", "verify"):
            # verify steps *serve* decode-ready slots: both flush the stall
            self._h_stall.observe(self._stall_accum)
            self._stall_accum = 0.0
        elif pending.waiting:
            # decode-ready slots sat out this step: head-of-line stall
            self._stall_accum += t1 - pending.t0

    # ---------------------------------------------- quarantine / deadlines

    def _pad_pages(self, pages: List[int], fill: int) -> jnp.ndarray:
        """Pad a page list to the fixed table width so the jitted zero /
        poison calls compile exactly once per engine config."""
        width = max(self.pool.table_width, 1)
        return jnp.asarray((list(pages) + [fill] * width)[:width], jnp.int32)

    def poison_slot(self, slot_idx: int) -> None:
        """Fault injection: NaN-fill the slot's most recent exclusively-
        owned KV page (or its state-slot row).  At the decode seam the
        newest page always holds positions past every sharer's prompt, so
        only the target row ever reads it — the poison is strictly
        per-request, which is what makes the exact-survivor contract
        testable."""
        slot = self.sched.slots[slot_idx]
        assert slot is not None
        if self.pool.spec.paged and slot.pages:
            page = next((p for p in reversed(slot.pages)
                         if self.pool.ref(p) == 1), None)
            assert page is not None, \
                f"slot {slot_idx} owns no exclusive page to poison"
            self.pool.kv = self._poison(self.pool.kv,
                                        self._pad_pages([page], fill=page))
        elif self.states is not None:
            self.states.poison(slot_idx)

    def _scrub_slot(self, slot_idx: int) -> None:
        """Zero a quarantined slot's exclusively-owned pages (and state row)
        before they return to the free list.  Mandatory, not cosmetic:
        masked attention is a zero-*weight* multiply, so a NaN in a recycled
        page would poison every future request whose table points at it
        even at softmax weight zero.  Shared (radix) pages are finite by
        construction — prompts are poisoned only past the shared region —
        and co-owned, so they are left alone."""
        slot = self.sched.slots[slot_idx]
        assert slot is not None
        if self.pool.spec.paged and slot.pages:
            excl = [p for p in slot.pages if self.pool.ref(p) == 1]
            if excl:
                self.pool.kv = self._zero(self.pool.kv,
                                          self._pad_pages(excl, NULL_PAGE))
                self.pool.note_scrubbed(len(excl))
        if self.states is not None:
            self.states.scrub(slot_idx)

    def _quarantine_slot(self, slot_idx: int, reason: str,
                         now: float) -> None:
        """Terminal-fail one live request without touching its batchmates:
        drop any staged plan (the slot set changes), scrub the pages it
        exclusively owns, release everything through the normal retire
        path, and emit the failure terminal.  Survivors replay nothing —
        their tokens were never wrong — so their streams stay byte-exact."""
        slot = self.sched.slots[slot_idx]
        assert slot is not None
        req = slot.req
        self._drop_staged()
        self._scrub_slot(slot_idx)
        req.error = reason
        req.t_finish = now
        self.sched.retire(slot_idx)
        self._m_quarantined.inc()
        self.tracer.on_finished(req.rid, now, len(req.generated),
                                error=reason)

    def _quarantine_rid(self, rid: int, reason: str) -> None:
        """Quarantine by request id (the step-error path: the fault names a
        rid, not a slot).  No-op if the rid is no longer live."""
        now = time.perf_counter()
        for i, slot in enumerate(self.sched.slots):
            if slot is not None and slot.req.rid == rid:
                self._quarantine_slot(i, reason, now)
                return

    def _evict_deadlines(self) -> None:
        """Expire queued and mid-flight requests whose deadline passed.
        Mid-flight eviction frees the slot immediately — finishing a request
        its client already gave up on is negative goodput."""
        now = time.perf_counter()
        expired_q, expired_live = self.sched.sweep_deadlines(now)
        for req in expired_q:
            req.error = "deadline_exceeded"
            req.t_finish = now
            self.sched.finished.append(req)
            self._m_deadline_evict.inc()
            self.tracer.on_rejected(req.rid, now, "deadline_exceeded")
        for i in expired_live:
            self._drop_staged()
            slot = self.sched.slots[i]
            req = slot.req
            req.error = "deadline_exceeded"
            req.t_finish = now
            self.sched.retire(i)
            self._m_deadline_evict.inc()
            self.tracer.on_finished(req.rid, now, len(req.generated),
                                    error="deadline_exceeded")

    def _stage_next(self, pending: _Pending) -> bool:
        """While the dispatched step runs on device, pre-build the host plan
        for the *next* decode step.  Staged only when the next action is
        deterministically the same decode batch one position further: the
        pending step is a decode, nothing is queued, no slot is mid-prefill,
        no slot retires on budget at this step's collect (an EOS retirement
        is caught by the dispatch fingerprint instead), and no slot crosses
        a page boundary at its next position.  True when a plan was staged;
        otherwise ``engine.overlap_skipped`` counts the reason."""
        with self.tracer.phase("stage"):
            reason = self._stage_blocker(pending)
            if reason is not None:
                self._m_overlap_skipped.labels(reason=reason).inc()
                return False
            active = list(pending.rows)
            self._staged = _StagedDecode(
                active=tuple(active),
                fp=tuple((i, self.sched.slots[i].req.rid,
                          self.sched.slots[i].pos + 1,
                          len(self.sched.slots[i].pages), 0)
                         for i in active),
                meta=self._decode_plan(active, pos_offset=1))
            self._m_overlap_staged.inc()
            return True

    def _stage_blocker(self, pending: _Pending) -> Optional[str]:
        """Why the step after ``pending`` cannot be staged (None if it can):
        ``not_decode``, ``queued``, ``prefilling``, ``retiring`` or
        ``page_growth``."""
        if pending.kind != "decode":
            return "not_decode"
        if self.sched.queue:
            return "queued"
        if self.sched.prefilling_slots():
            return "prefilling"
        ps = self.scfg.page_size
        cap = self.pool.table_width
        for i in pending.rows:
            slot = self.sched.slots[i]
            if len(slot.req.generated) + 1 >= slot.req.max_new:
                return "retiring"     # retires when this step collects
            p1 = slot.pos + 1
            if self.pool.spec.paged and len(slot.pages) < cap \
                    and p1 % ps == 0 and p1 // ps >= len(slot.pages):
                return "page_growth"  # next decode needs page growth
        return None

    # -------------------------------------------------------------- prefill

    def _extras(self, rids: List[int], B: int) -> Dict[str, np.ndarray]:
        """Frontend inputs for a padded prefill batch, on the host ({} for
        text-only)."""
        cfg = self.cfg
        if not (cfg.enc_dec or cfg.n_image_tokens):
            return {}
        rows = [_synthetic_frontend(cfg, self.scfg, self.seed, r)
                for r in rids]
        n = (self.scfg.enc_len if cfg.enc_dec else cfg.n_image_tokens)
        out = np.zeros((B, n, cfg.frontend_dim), rows[0].dtype)
        for i, r in enumerate(rows):
            out[i] = r
        key = "frames" if cfg.enc_dec else "image_embeds"
        return {key: out}

    def _prefill_launch(self, rows: List[Tuple[int, Any, int, int]],
                        continuation: bool = False):
        """Launch one batched chunk-prefill call.  ``rows`` holds
        (slot_idx, req, n_done, n_chunk): each row prefills prompt tokens
        [n_done, n_done + n_chunk) into its bound pages / state slot.  The
        batch is padded to a pow2 row count and the tokens to a bucket so
        the program set stays bounded (keyed by the chunk budget, not by
        prompt lengths).  ``continuation`` marks a batch of chunks after
        the first: no frontend inputs (vlm never chunks, enc-dec reads its
        pinned cross cache instead of re-encoding).  Returns the per-row
        last-real-token logits *still on device* — the collect half blocks
        on them with ``np.asarray``."""
        with self.tracer.phase("plan"):
            meta, toks, extras = self._prefill_plan(rows, continuation)
        with self.tracer.phase("upload"):
            meta, toks, extras = _upload(meta), jnp.asarray(toks), \
                _upload(extras)
        state = self.states.state if self.states is not None else {}
        step = self._prefill_cont if continuation and self.cfg.enc_dec \
            else self._prefill
        kind = "prefill_chunk" if continuation else "prefill"
        with self.tracer.phase("launch." + kind):
            logits, self.pool.kv, state = step(
                self.params, self.pool.kv, state, meta, toks, extras)
        if self.states is not None:
            self.states.state = state
        self._m_padded.inc(toks.size)
        self._m_actual.inc(sum(c for _, _, _, c in rows))
        return logits

    def _prefill_plan(self, rows: List[Tuple[int, Any, int, int]],
                      continuation: bool):
        """Host-side (numpy) inputs of one batched chunk-prefill call:
        ``prefill_meta``, the bucketed tokens and the frontend extras."""
        bucket = self.scfg.bucket_of(max(c for _, _, _, c in rows))
        B = _pow2_pad(len(rows), self.scfg.max_slots)
        toks = np.zeros((B, bucket), np.int32)
        start = np.zeros((B,), np.int32)
        n_tail = np.zeros((B,), np.int32)
        tables = np.full((B, max(self.pool.table_width, 1)), NULL_PAGE,
                         np.int32)
        slots = np.full((B,), self.scfg.max_slots, np.int32)  # pad rows: drop
        for i, (slot_idx, req, n_done, n_chunk) in enumerate(rows):
            toks[i, :n_chunk] = req.prompt[n_done:n_done + n_chunk]
            start[i] = n_done
            n_tail[i] = n_chunk
            tables[i] = self.sched.slots[slot_idx].table
            slots[i] = slot_idx
        # token-addressable families attend only pages the batch actually
        # reaches: truncate the table view to a pow2 page count (bounded
        # program set) instead of always paying a max_len-wide gather — an
        # early chunk of a long prompt, or a short prompt under a large
        # max_len, attends O(its own length), not O(max_len)
        ps = self.scfg.page_size
        width = tables.shape[1]
        if not self.cfg.sliding_window:        # ring tables are minimal already
            need = -(-(int((start + n_tail).max())
                       + self.pool.spec.prefix_tokens) // ps)
            W = 1
            while W < need:
                W *= 2
            width = max(min(W, tables.shape[1]), 1)
        meta = prefill_meta(self.cfg, ps, tables[:, :width], slots, start,
                            n_tail, bucket)
        extras = {} if continuation \
            else self._extras([req.rid for _, req, _, _ in rows], B)
        return meta, toks, extras

    def _after_chunk(self, slot_idx: int, req, n_done: int, n_chunk: int,
                     logits_row: Optional[np.ndarray], now: float,
                     pages: List[int]) -> None:
        """Advance a slot's prefill cursor past one chunk: publish the newly
        completed full prompt pages (immutable from here on — later chunks
        and decode write strictly past them, so a same-prefix request queued
        behind a long prompt starts hitting the cache mid-prefill), and on
        the final chunk take the first token from this call's logits."""
        slot = self.sched.slots[slot_idx]
        slot.n_filled = n_done + n_chunk
        if self.radix is not None:
            ps = self.scfg.page_size
            full = min(slot.n_filled, len(req.prompt)) // ps
            if full:
                self.radix.insert(req.prompt[:full * ps], pages[:full])
        if slot.n_filled >= len(req.prompt):
            if req.t_first is None:       # replay keeps the original TTFT
                req.t_first = now
            self.tracer.on_first_token(req.rid, now)
            tok = int(logits_row.argmax())
            req.generated.append(tok)
            self._emit_token(req.rid, len(req.generated) - 1, tok, now)
            self._maybe_retire(slot_idx, now)

    def _launch_prefill(self, adms: List[Admission], t0: float):
        """Launch a batch of already-accounted admissions: fork COW pages if
        a cache match ended mid-page, then prefill each request's *first
        chunk* — the whole uncached tail unless chunking caps it — straight
        into the bound pages / state slots in one call."""
        for adm in adms:
            self.tracer.on_admitted(adm.req.rid, t0,
                                    cached_tokens=adm.n_matched)
            if adm.cow_dst is not None:
                self.pool.kv = self._copy(self.pool.kv,
                                          jnp.asarray(adm.cow_src, jnp.int32),
                                          jnp.asarray(adm.cow_dst, jnp.int32))
        rows = [(adm.slot_idx, adm.req, adm.n_matched, adm.n_chunk)
                for adm in adms]
        out = self._prefill_launch(rows)
        self._m_prefill_steps.inc()
        if len(adms) > 1:
            self._m_multi_admit.inc()
        return rows, out

    def _launch_chunks(self, slot_idxs: List[int], t0: float):
        """Launch a batch of continuation chunks for mid-prefill slots."""
        rows = []
        for i in slot_idxs:
            slot = self.sched.slots[i]
            n_done = slot.n_filled
            n_chunk = self.sched._chunk_len(n_done, len(slot.req.prompt))
            rows.append((i, slot.req, n_done, n_chunk))
        out = self._prefill_launch(rows, continuation=True)
        self._m_prefill_steps.inc()
        self._m_chunk_steps.inc()
        return rows, out

    def _collect_prefill(self, pending: _Pending) -> None:
        """Collect half of a prefill/chunk step: block on the device logits,
        then advance every row's cursor (first tokens, cache publishes,
        retirement)."""
        with self.tracer.phase("sync." + pending.kind):
            logits = np.asarray(pending.out_dev)  # blocks: device step done
        now = time.perf_counter()
        if self._moe_layers:
            self._count_moe(pending, "prefill",
                            unpack_counts(logits[-1], 2 * self._moe_layers),
                            sum(n for *_, n in pending.rows))
        with self.tracer.phase("emit"):
            for r, (slot_idx, req, n_done, n_chunk) in enumerate(
                    pending.rows):
                slot = self.sched.slots[slot_idx]
                if slot is None or slot.req is not req:
                    continue          # cancelled/quarantined under our feet
                self.tracer.on_chunk(req.rid, pending.t0, now,
                                     n_done=n_done, n_chunk=n_chunk)
                if not np.isfinite(logits[r]).all():
                    # checked *before* _after_chunk so a poisoned prompt
                    # never publishes its pages to the radix cache
                    self._quarantine_slot(slot_idx, "nan_logits", now)
                    continue
                pages = (pending.payload[r].pages
                         if pending.kind == "prefill" else slot.pages)
                self._after_chunk(slot_idx, req, n_done, n_chunk, logits[r],
                                  now, pages)

    def _run_restore(self, adm: Admission, t0: float) -> None:
        """Re-admit a checkpointed (preempted) request: write its state
        snapshot back into the claimed slot and resume decoding where it
        left off — no prompt replay (the scheduler already bound the slot at
        the checkpointed position)."""
        self.tracer.on_admitted(adm.req.rid, t0, kind="restore")
        _, saved = adm.restore
        self.states.restore(adm.slot_idx, saved)
        self._m_restores.inc()
        self.tracer.on_restored(adm.req.rid, time.perf_counter())

    # --------------------------------------------------------------- decode

    def _decode_plan(self, active: List[int],
                     pos_offset: int = 0) -> Dict[str, Any]:
        """Flat per-step decode metadata, derived once on the host (numpy)
        instead of re-derived by every layer's block inside the scanned
        decode step, and uploaded.  ``pos_offset=1`` builds the *next*
        step's plan while this step's collect hasn't advanced the cursors
        yet (staging)."""
        with self.tracer.phase("plan"):
            meta = self._decode_meta(active, pos_offset)
        with self.tracer.phase("upload"):
            return _upload(meta)

    def _decode_meta(self, active: List[int],
                     pos_offset: int = 0) -> Dict[str, np.ndarray]:
        """``decode_meta`` of the active rows, on the host."""
        B = self.scfg.max_slots
        maxp = max(self.pool.table_width, 1)
        pos = np.zeros((B,), np.int32)
        tables = np.full((B, maxp), NULL_PAGE, np.int32)
        for i in active:
            slot = self.sched.slots[i]
            pos[i] = slot.pos + pos_offset
            tables[i] = slot.table
        # kernels/paged_attention ``live_pages``: through pos's page, at
        # least the one (null) page an idle row attends
        ps = self.scfg.page_size
        self._m_pages_walked.inc(int(np.clip(pos // ps + 1, 1, maxp).sum()))
        self._m_pages_table.inc(B * maxp)
        return decode_meta(self.cfg, ps, tables, pos)

    def _launch_decode(self, active: List[int]):
        """Launch one fixed-shape decode step, reusing a staged plan when
        its fingerprint still matches reality (a used plan is bit-identical
        to a replan — same positions, tables, pages — so tokens are exact).
        Returns ((device next-token array, finite flags, launch time),
        whether the staged plan was used) without blocking."""
        B = self.scfg.max_slots
        with self.tracer.phase("plan"):
            tokens = np.zeros((B,), np.int32)
            for i in active:
                tokens[i] = self.sched.slots[i].req.generated[-1]
            meta = None
            if self._staged is not None:
                st, self._staged = self._staged, None
                fp = tuple(
                    (i, self.sched.slots[i].req.rid, self.sched.slots[i].pos,
                     len(self.sched.slots[i].pages), 0) for i in active)
                if tuple(active) == st.active and fp == st.fp:
                    meta = st.meta
                    self._m_overlap_used.inc()
                else:
                    self._m_overlap_dropped.inc()
            staged = meta is not None
            plan = None if staged else self._decode_meta(active)
        with self.tracer.phase("upload"):
            if plan is not None:
                meta = _upload(plan)
            tokens = jnp.asarray(tokens)
        state = self.states.state if self.states is not None else {}
        t_launch = time.perf_counter()
        with self.tracer.phase("launch.decode"):
            nxt, ok, self.pool.kv, state = self._decode(
                self.params, self.pool.kv, state, meta, tokens)
        if self.states is not None:
            self.states.state = state
        return (nxt, ok, t_launch), staged

    def _collect_decode(self, pending: _Pending) -> None:
        """Collect half of a decode step: block on the device tokens, then
        advance cursors, fire streaming hooks, retire finished slots.  A row
        whose finite flag came back False is quarantined instead of emitting
        its garbage argmax — its survivors' rows are untouched."""
        nxt_dev, ok_dev, t_launch = pending.out_dev
        with self.tracer.phase("sync.decode"):
            nxt = np.asarray(nxt_dev)            # blocks: device step done
            ok = np.asarray(ok_dev)
        now = time.perf_counter()
        if self._moe_layers:
            B = self.scfg.max_slots
            self._count_moe(pending, "decode", nxt[B:], len(pending.rows))
        self._h_decode_step.observe(now - t_launch)
        if self.admission is not None:
            self.admission.observe_step(now - t_launch)
        with self.tracer.phase("emit"):
            for i in pending.rows:
                slot = self.sched.slots[i]
                if slot is None:
                    continue          # quarantined earlier in this collect
                if not ok[i]:
                    self._quarantine_slot(i, "nan_logits", now)
                    continue
                slot.pos += 1
                tok = int(nxt[i])
                slot.req.generated.append(tok)
                self._emit_token(slot.req.rid, len(slot.req.generated) - 1,
                                 tok, now)
                self._maybe_retire(i, now)

    def _count_moe(self, pending: _Pending, kind: str, stats, tokens: int):
        """Count a step's expert pairs from the per-layer (held pairs, held
        experts touched) the step returned, and keep them for its span."""
        stats = np.asarray(stats).reshape(self._moe_layers, 2)
        held, touched = int(stats[:, 0].sum()), int(stats[:, 1].sum())
        routed = tokens * self.cfg.top_k * self._moe_layers
        self._m_pairs_held.inc(held)
        self._m_pairs_routed.inc(routed)
        self._m_experts_touched.labels(kind=kind).inc(touched)
        pending.moe = {"moe_held": held, "moe_routed": routed,
                       "moe_touched": touched}

    # ------------------------------------------------------------- speculate

    def _verify_meta(self, active: List[int],
                     drafts: Dict[int, List[int]]) -> Dict[str, np.ndarray]:
        """Fixed-shape verify-step metadata, on the host: like
        ``_decode_meta`` but with per-row live query counts (1 + draft
        length) and per-query write targets for all Q = spec_k + 1
        positions.  Idle rows keep pos=0, n_q=1 and a NULL_PAGE table, so
        their single query writes to the reserved sink page exactly as an
        idle decode row does."""
        B = self.scfg.max_slots
        Q = self.spec_k + 1
        maxp = max(self.pool.table_width, 1)
        pos = np.zeros((B,), np.int32)
        n_q = np.ones((B,), np.int32)
        tables = np.full((B, maxp), NULL_PAGE, np.int32)
        for i in active:
            slot = self.sched.slots[i]
            pos[i] = slot.pos
            n_q[i] = 1 + len(drafts[i])
            tables[i] = slot.table
        return verify_meta(self.cfg, self.scfg.page_size, tables, pos, n_q,
                           Q)

    def _launch_verify(self, active: List[int]):
        """Launch one fixed-shape speculative verify step: draft up to
        ``spec_k`` tokens per row from the request's own history (prompt +
        generation), then run draft + carried token through the small-q
        verify step in one device call.  Rows whose proposer finds nothing
        run with an empty draft — the step degenerates to a decode step for
        them.  Drafts are clamped so the furthest K/V write (pos + draft
        len) stays inside both the token budget and the page horizon.
        Returns (device [B, Q] next-token array, launch time, drafts)."""
        B = self.scfg.max_slots
        Q = self.spec_k + 1
        with self.tracer.phase("plan"):
            tokens = np.zeros((B, Q), np.int32)
            drafts: Dict[int, List[int]] = {}
            prefix = self.pool.spec.prefix_tokens
            for i in active:
                req = self.sched.slots[i].req
                # a draft token beyond the remaining budget could never be
                # emitted (the bonus token fills the last budget slot), and
                # its K/V write must stay under the max_len page horizon
                kmax = min(self.spec_k,
                           req.max_new - len(req.generated) - 1,
                           prefix + self.scfg.max_len - 1
                           - self.sched.slots[i].pos)
                draft = self.proposer.propose(
                    req.prompt + req.generated)[:max(kmax, 0)]
                drafts[i] = draft
                tokens[i, 0] = req.generated[-1]
                tokens[i, 1:1 + len(draft)] = draft
                if draft:
                    self._m_spec_proposed.inc(len(draft))
            meta = self._verify_meta(active, drafts)
        with self.tracer.phase("upload"):
            meta, tokens = _upload(meta), jnp.asarray(tokens)
        state = self.states.state if self.states is not None else {}
        t_launch = time.perf_counter()
        with self.tracer.phase("launch.verify"):
            nxt, ok, self.pool.kv, state = self._verify(
                self.params, self.pool.kv, state, meta, tokens)
        if self.states is not None:
            self.states.state = state
        return nxt, ok, t_launch, drafts

    def _collect_verify(self, pending: _Pending) -> None:
        """Collect half of a verify step: block on the [B, Q] greedy tokens,
        accept each row's longest draft prefix the argmax reproduced, and
        emit accepted + bonus tokens — the identical stream a sequence of
        one-token decode steps would have produced.  EOS or budget reached
        mid-emit stops the emission there (trailing accepted tokens are
        discarded exactly as decode would never have produced them)."""
        nxt_dev, ok_dev, t_launch, drafts = pending.out_dev
        with self.tracer.phase("sync.verify"):
            nxt = np.asarray(nxt_dev)            # blocks: device step done
            ok = np.asarray(ok_dev)
        now = time.perf_counter()
        self._h_decode_step.observe(now - t_launch)
        if self.admission is not None:
            self.admission.observe_step(now - t_launch)
        with self.tracer.phase("emit"):
            for i in pending.rows:
                slot = self.sched.slots[i]
                if slot is None:
                    continue          # quarantined earlier in this collect
                if not ok[i]:
                    self._quarantine_slot(i, "nan_logits", now)
                    continue
                req = slot.req
                draft = drafts[i]
                a = accept_length(draft, nxt[i, :len(draft)]) if draft else 0
                if draft:
                    self._m_spec_accepted.inc(a)
                for j in range(a + 1):
                    tok = int(nxt[i, j])
                    slot.pos += 1
                    req.generated.append(tok)
                    self._emit_token(req.rid, len(req.generated) - 1, tok,
                                     now)
                    done = len(req.generated) >= req.max_new
                    if self.scfg.eos_id >= 0 and tok == self.scfg.eos_id:
                        done = True
                    if done:
                        req.t_finish = now
                        self.sched.retire(i)
                        self.tracer.on_finished(req.rid, now,
                                                len(req.generated))
                        break

    def _emit_token(self, rid: int, index: int, tok: int, now: float) -> None:
        """Fire the streaming hook and the injector's token seam (the
        client-disconnect fault watches the stream, not the scheduler)."""
        if self.on_token is not None:
            self.on_token(rid, index, tok, now)
        if self.injector is not None:
            self.injector.on_token(rid, index)

    def _maybe_retire(self, slot_idx: int, now: float) -> None:
        req = self.sched.slots[slot_idx].req
        done = len(req.generated) >= req.max_new
        if self.scfg.eos_id >= 0 and req.generated[-1] == self.scfg.eos_id:
            done = True
        if done:
            req.t_finish = now
            self.sched.retire(slot_idx)
            self.tracer.on_finished(req.rid, now, len(req.generated))


# ---------------------------------------------------------- static baseline

@functools.lru_cache(maxsize=None)
def _static_steps(cfg: ArchConfig, mesh=None):
    """Jitted (prefill_at, decode) steps, cached per config so repeated
    generate_static calls (verify replays, benchmarks) reuse compilations.
    The decode step donates its cache argument; callers never reuse it."""
    return (jax.jit(make_serve_step(cfg, mesh, "prefill_at")),
            jax.jit(make_serve_step(cfg, mesh, "decode"), donate_argnums=(1,)))


def generate_static(cfg: ArchConfig, params, prompts: Sequence[Sequence[int]],
                    max_new_tokens=16, scfg: Optional[ServeConfig] = None,
                    *, batch_size: int = 1, mesh=None,
                    eos_id: Optional[int] = None,
                    seed: int = 0) -> Tuple[List[List[int]], Dict]:
    """Static-batching reference: contiguous KV caches, arrival-order batches
    padded to a shared bucket, each batch decoded until its slowest request
    is done.  ``batch_size=1`` is the exact single-request greedy baseline
    the engine's output is verified against.  ``eos_id`` defaults to
    ``scfg.eos_id`` so the stop rule matches the Engine's.

    Right-padding is causally invisible to attention families (masked), but
    recurrent state (ssm/hybrid) absorbs pad tokens: those families are only
    exact when every prompt in a batch has the same length, so they skip
    bucketing and pad to the batch max instead.  Enc-dec (audio) and vlm
    archs get synthetic frontend inputs drawn per *request index*
    (``fold_in(seed, i)``, fixed shapes) — the same inputs the continuous
    engine synthesizes per rid, so the two paths are comparable."""
    scfg = scfg or ServeConfig()
    eos = scfg.eos_id if eos_id is None else eos_id
    budgets = ([max_new_tokens] * len(prompts)
               if isinstance(max_new_tokens, int) else list(max_new_tokens))
    prefill, decode = _static_steps(cfg, mesh)
    model = build_model(cfg)
    n_img = cfg.n_image_tokens

    all_tokens: List[Optional[List[int]]] = [None] * len(prompts)
    latencies: List[float] = [0.0] * len(prompts)
    ttfts: List[float] = [0.0] * len(prompts)
    decode_step_s: List[float] = []
    prefill_padded = prefill_actual = 0
    t0 = time.perf_counter()
    for lo in range(0, len(prompts), batch_size):
        idxs = list(range(lo, min(lo + batch_size, len(prompts))))
        B = len(idxs)
        lens = [len(prompts[i]) for i in idxs]
        budget = [min(budgets[i], scfg.max_len - len(prompts[i])) for i in idxs]
        # recurrent state absorbs pad tokens and the sliding-window ring is
        # filled from the final prompt positions: both need the prompt end to
        # be the sequence end, so those families pad to the batch max instead
        # of a bucket (exact at batch_size=1 / equal lengths)
        bucket = (max(lens)
                  if cfg.family in ("ssm", "hybrid") or cfg.sliding_window
                  else scfg.bucket_of(max(lens)))
        toks = np.zeros((B, bucket), np.int32)
        for r, i in enumerate(idxs):
            toks[r, :lens[r]] = prompts[i]
        batch = {"tokens": jnp.asarray(toks)}
        if cfg.enc_dec or n_img:
            rows = [_synthetic_frontend(cfg, scfg, seed, i) for i in idxs]
            key = "frames" if cfg.enc_dec else "image_embeds"
            batch[key] = jnp.asarray(np.stack(rows))
        # vlm hidden sequence = image tokens ++ text tokens: offset positions
        last_idx = jnp.asarray([n_img + l - 1 for l in lens], jnp.int32)
        logits, cache = prefill(params, batch, last_idx)
        # grow the contiguous cache to max_len (the pre-paging zero-pad copy)
        if cfg.enc_dec:
            fresh = init_tree(
                model.cache_defs(B, scfg.max_len, enc_len=scfg.enc_len),
                jax.random.PRNGKey(0))
        else:
            fresh = init_cache(cfg, B, n_img + scfg.max_len)
        cache = jax.tree.map(
            lambda f, c: c if f.shape == c.shape else jnp.pad(
                c, [(0, fs - cs) for fs, cs in zip(f.shape, c.shape)]),
            fresh, cache)
        # per-row positions: decode writes resume at each prompt's true length
        cache["pos"] = jnp.asarray([n_img + l for l in lens], jnp.int32)
        cur = jnp.asarray(np.asarray(logits).argmax(-1), jnp.int32)
        t_first = time.perf_counter() - t0       # batch's first tokens exist
        prefill_padded += B * bucket
        prefill_actual += sum(lens)
        gen = [np.asarray(cur).copy()]
        # the whole batch decodes until its slowest member is done
        for _ in range(max(budget) - 1):
            t_step = time.perf_counter()
            cur, cache = decode(params, cache, cur)
            gen.append(np.asarray(cur).copy())   # np.asarray blocks: the
            decode_step_s.append(time.perf_counter() - t_step)  # step is done
        jax.block_until_ready(cur)
        t_batch = time.perf_counter() - t0
        stacked = np.stack(gen, axis=1)               # [B, max(budget)]
        for r, i in enumerate(idxs):
            row = stacked[r, :budget[r]].tolist()
            if eos >= 0 and eos in row:
                row = row[:row.index(eos) + 1]
            all_tokens[i] = row
            latencies[i] = t_batch
            ttfts[i] = t_first
    wall = time.perf_counter() - t0
    # the shared schema (same keys as the engine path, column-for-column);
    # stall is honestly zero — the static path has no interleaving to stall
    return all_tokens, shared_metrics(
        len(prompts), sum(len(t) for t in all_tokens), latencies, wall,
        ttfts=ttfts, prompt_tokens=sum(len(p) for p in prompts),
        prefill_steps=-(-len(prompts) // batch_size),
        prefill_padded_tokens=prefill_padded,
        prefill_actual_tokens=prefill_actual,
        decode_step_s=decode_step_s)

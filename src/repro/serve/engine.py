"""Continuous-batching inference engine: pure request lifecycle.

One engine owns: a packed model (serve.registry), an admission policy
(serve.scheduler), metrics, and an EXECUTION BACKEND (serve.backend) that
owns everything about placement — the KV slab, the device-resident loop
state, and the compiled prefill/decode/install steps. The engine never
touches a compiled function or a device buffer directly: it decides WHICH
request runs WHEN; the backend decides WHERE the step executes
(`LocalBackend` = jax-default placement, `ShardedBackend` = SPMD over a
(data, model) mesh with the slab's slot axis sharded like batch). Greedy
outputs are identical across backends and across decode chunk sizes.

Device-resident decode (default, `EngineConfig.device_loop=True`): between
host synchronizations nothing leaves the device. Sampling is fused into the
compiled decode step (argmax + per-slot-temperature Gumbel with a threaded
`jax.random` key — distributed.steps / transformer.sample_tokens), the
token/index/lifecycle state lives in a donated device tree
(`steps.make_decode_state`), and the KV slab is donated into every dispatch
so it updates in place. A dispatch runs `decode_chunk` (K) micro-steps under
one `lax.scan` with on-device EOS/length masking; the ONLY thing pulled back
is the (K, n_slots) int32 token block — not (n_slots, vocab) logits — so
host syncs per decoded token drop from 3/step (logits pull + token and index
uploads, the PR-1 loop kept as `device_loop=False`) to 1 per K-step
dispatch. Host-side emission catches up from the synced block: streaming
callbacks fire in micro-step order and slots that finished mid-block are
freed retroactively.

Speculative decode (`EngineConfig.speculate=K`, PR 4): the dispatch becomes
a fused propose-then-verify cycle over a SELF-DRAFT artifact (the same
weights re-packed at a cheaper Kratos point — serve.speculative): the draft
proposes K tokens, the target verifies the block in one batched forward,
and per-slot accept/reject masking commits the agreeing prefix plus one
target bonus token — 1..K+1 tokens per dispatch per live slot, with
rollback a free per-slot index rewind (the backend pads both slabs by K
positions so speculative writes stay in bounds). Greedy output is
token-identical to non-speculative decode for any draft and any K; a
request can cap or disable its own drafting with `submit(speculate=...)`.

Paged KV + prefix reuse (`EngineConfig.page_size`, PR 5): the backend's
pool becomes a block-paged store (serve.paging) — per-slot page tables over
refcounted fixed-size pages, carried as donated device state through every
dispatch — and admission becomes prefix-match -> suffix-prefill -> page
install: the longest page-aligned cached prefix of the prompt (radix index
over token IDs, serve.prefix) is SHARED by refcount bump, only the
unmatched suffix is prefilled, and the prompt's full pages are published
for future admissions. Slot capacity stops being `mem / max_len` and
becomes `mem / actual_tokens`; redundant prefill FLOPs across requests
sharing a system prompt drop to zero. Page pressure surfaces as
`PoolExhausted` at admission — `step()` requeues the admission at the
front of the waiting deque (counted as `pool_waits`) instead of failing
the step; LRU eviction of prefix pages nobody references runs first.
Greedy decode stays token-identical to the slab: in the NATIVE paged form
(PR 8, `EngineConfig.paged_native`, default) attention reads and writes
the page-major store directly through the per-slot page table — no
per-dispatch gather/scatter materialisation (`gather_bytes_avoided`
counts what the legacy wrap would have moved) — and a finished request
publishes its WHOLE conversation (prompt + generated tokens) into the
prefix tree, so the next turn of the same chat skips prefill over the
entire prior exchange (`conversation_prefix_hits`). `paged_native=False`
keeps the PR-5 gather-run-scatter wrap as the measured baseline and the
token-identity oracle.

The `decode_chunk` knob is a latency/throughput trade: larger K amortizes
dispatch + sync overhead over more tokens but coarsens the admission clock
(new requests join only at block boundaries) and wastes tail micro-steps
when a request finishes mid-block. K=1 is latency-optimal and keeps PR-1
admission granularity; benchmarks run K=4.

Step loop (`step()`):

  1. admission — the scheduler picks arrived requests for free slots (the
     waiting deque is re-partitioned in ONE pass); each admitted request is
     prefilled alone (batch 1, caches allocated inside the compiled step)
     and its cache donated into its slab row. Its first token is sampled
     on device from the prefill logits and its per-slot row (token, index,
     temperature, EOS, remaining budget) is installed into the device state.
  2. slab decode — one dispatch over ALL slots with the per-slot position
     vector (models.attention gathers each row's cache clock); idle slots
     decode garbage that per-slot validity masks keep inert, so the compiled
     shape never changes and requests join/leave with zero recompiles.
  3. lifecycle — the synced token block is emitted per request in micro-step
     order (streaming via `Request.on_token`), finished requests free their
     slots, and freed slots are admissible on the very next step.

Backpressure: `EngineConfig.max_waiting` bounds the waiting deque. A submit
over the bound raises `EngineSaturated` (counted in metrics as `rejected`)
instead of queueing unboundedly — the rejection is the signal the replica
router (serve.router) uses to spill traffic to a sibling engine. The
default (None) keeps the open-ended queue for single-engine use.

Resilience (PR 7): requests can carry deadlines (`deadline_steps` on the
deterministic engine-step clock, `deadline_ms` on the wall) — admission
sheds already-doomed work immediately and a per-step sweep cancels
mid-flight requests whose remaining budget no longer fits, freeing their
slot/pages cleanly (`Request.state == "shed"` + `shed_reason`; ServeMetrics
`shed` / `deadline_missed`). `EngineConfig.pool_wait_retries` bounds the
PoolExhausted requeue loop with exponential step backoff (then sheds as
`shed_pool_pressure`). `EngineConfig.qos` (serve.qos) enables load-driven
QUALITY degradation: the engine swaps the live decode onto a cheaper
resident (sparsity, bits) tier of the same weights under queue/page
pressure — KV-compatible, so every in-flight stream continues — and
re-promotes with hysteresis. A corrupted decode sync (out-of-vocab tokens:
NaN logits, device fault) raises `ReplicaFault`, the signal the replica
router's failover path (serve.router) turns into evacuate-and-re-admit.

Prefill compile-shape policy: prompts are right-padded to power-of-two
buckets (full-logits prefill, read at the true prompt end; the padded cache
tail is never valid under the per-slot masks) so a mixed-length trace
compiles O(log max_len) prefill shapes instead of one per distinct length.
Architectures whose prefill state is cumulative over the padded positions
(SSM/hybrid recurrent state, MoE capacity routing, enc-dec) prefill at exact
length — correctness over compile reuse.

Determinism contract: with temperature=0 every request's output is
independent of what else shares the slab (batch-invariance), EXCEPT
capacity-routed MoE archs where expert-capacity contention is inherently
batch-dependent (true of the lock-step baseline too). Greedy outputs are
identical between the device loop (any K, any backend) and the host loop.
With temperature>0 the device loop samples with jax.random (the host loop
keeps its numpy rng): one rng split per MICRO-step makes a single request's
sampled sequence reproducible for any K grouping of the same steps.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.serve.backend import ExecutionBackend, LocalBackend
from repro.serve.cache_pool import PoolExhausted
from repro.serve.ledger import NULL_LEDGER, LedgerSink
from repro.serve.metrics import ServeMetrics
from repro.serve.registry import PackedModel
from repro.serve.scheduler import (ContinuousScheduler, Request,
                                   SchedulerBase)
from repro.serve.trace import (NULL_TRACER, TraceConfig, Tracer, span,
                               spanned)


class EngineSaturated(RuntimeError):
    """The bounded waiting deque is full: admission must spill or retry."""


class ReplicaFault(RuntimeError):
    """The replica produced provably-corrupt output (out-of-vocab decode
    sync — NaN logits argmax, device fault) or its dispatch crashed. The
    router catches this around `engine.step()`, marks the replica dead,
    and re-admits its evacuated requests to survivors."""


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    n_slots: int = 4
    max_len: int = 96                  # cache positions per slot
    backend: str = "ref"
    cache_dtype: str = "float32"
    prefill_buckets: bool = True       # pow2 right-padding (where exact)
    bucket_min: int = 16
    seed: int = 0                      # sampling rng
    device_loop: bool = True           # fused on-device sampling + state
    decode_chunk: int = 1              # K micro-steps per dispatch (device)
    max_waiting: Optional[int] = None  # waiting-deque bound (None = open)
    # speculative decode (serve.speculative): K draft tokens per propose-
    # then-verify dispatch (0 = off). Requires a model loaded with
    # `draft_spec=`; replaces the decode_chunk loop (one spec cycle IS the
    # dispatch). Both slabs get K extra positions of write headroom so the
    # deepest speculative write stays in bounds before rollback.
    speculate: int = 0
    draft_cache_dtype: Optional[str] = None   # None = cache_dtype
    # paged KV + prefix reuse (serve.paging): page_size carves the cache
    # into fixed pages behind per-slot page tables (None = the slab);
    # n_pages sizes the page pool (None = slab-equivalent capacity,
    # n_slots * pages_per_slot, + the reserved sink page) — fewer pages
    # oversubscribes memory against ACTUAL tokens instead of max_len;
    # prefix_cache shares page-aligned prompt prefixes across requests via
    # the radix index (auto-disabled for archs whose cache state is not
    # purely positional — paging itself still works there).
    page_size: Optional[int] = None
    n_pages: Optional[int] = None
    prefix_cache: bool = True
    # paged_native=True (default) runs decode attention straight off the
    # page-major store through the page table (kernels.ops.paged_attention
    # / the page-table-native Pallas kernel) — no gather/scatter
    # materialisation per dispatch. False keeps the PR-5
    # gather-run-scatter wrap: the measured baseline and the
    # token-identity oracle for the native path.
    paged_native: bool = True
    # resilience (serve.qos): pool_wait_retries bounds the PoolExhausted
    # requeue loop per request — None keeps the legacy unbounded
    # requeue-at-front; N parks the retry behind an exponential step
    # backoff and sheds the request (`shed_pool_pressure`) past N retries.
    # qos (a qos.QoSConfig) enables load-driven tier degradation; requires
    # a model loaded with registry.load(..., tier_specs=...).
    pool_wait_retries: Optional[int] = None
    qos: Optional[Any] = None
    # tracing (serve.trace): None = OFF, served by the shared no-op tracer —
    # the hot path's only residue is one attribute lookup + a fixed-arity
    # no-op call per edge (allocation-free, gated by test_trace). Set a
    # TraceConfig to record every lifecycle/dispatch edge into the ring
    # buffer, with optional JSONL/Chrome export paths and a jax.profiler
    # bracket around the first N traced dispatches.
    trace: Optional[TraceConfig] = None
    # ineffectual-work ledger (serve.ledger): None = OFF, served by the
    # shared NULL_LEDGER no-op sink (allocation-free hot path, gated by
    # test_ledger). Set a ledger.LedgerConfig to carry a device-resident
    # activation-sparsity / effective-FLOPs counter matrix as donated loop
    # state through every fused dispatch, drained inside the dispatch's
    # one existing host sync — plus the per-tier quality probe when
    # LedgerConfig.quality_every > 0. Requires device_loop=True.
    ledger: Optional[Any] = None


class InferenceEngine:
    """Request lifecycle + step loop over a packed model."""

    def __init__(self, model: PackedModel, cfg: EngineConfig = EngineConfig(),
                 scheduler: Optional[SchedulerBase] = None,
                 metrics: Optional[ServeMetrics] = None,
                 backend: Optional[ExecutionBackend] = None):
        if cfg.decode_chunk < 1:
            raise ValueError(f"decode_chunk must be >= 1, got "
                             f"{cfg.decode_chunk}")
        if cfg.decode_chunk > 1 and not cfg.device_loop:
            raise ValueError("decode_chunk > 1 requires device_loop=True "
                             "(the host loop samples every micro-step)")
        if cfg.max_waiting is not None and cfg.max_waiting < 0:
            raise ValueError(f"max_waiting must be >= 0 or None, got "
                             f"{cfg.max_waiting}")
        if cfg.speculate < 0:
            raise ValueError(f"speculate must be >= 0, got {cfg.speculate}")
        if cfg.page_size is not None and cfg.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {cfg.page_size}")
        if cfg.page_size and not cfg.device_loop:
            raise ValueError("page_size requires device_loop=True (the "
                             "page-table decode lives inside the fused "
                             "dispatch; the host loop has no paged form)")
        if cfg.n_pages is not None and not cfg.page_size:
            raise ValueError("n_pages without page_size: the slab pool has "
                             "no page geometry")
        if cfg.pool_wait_retries is not None and cfg.pool_wait_retries < 0:
            raise ValueError(f"pool_wait_retries must be >= 0 or None, got "
                             f"{cfg.pool_wait_retries}")
        if cfg.ledger is not None and not cfg.device_loop:
            raise ValueError("ledger requires device_loop=True (the counter "
                             "matrix rides the fused dispatch's donated "
                             "loop state; the host loop has no fused step)")
        if cfg.speculate:
            from repro.serve import speculative as SP
            if not cfg.device_loop:
                raise ValueError("speculate requires device_loop=True (the "
                                 "propose-then-verify cycle is one fused "
                                 "dispatch)")
            if cfg.decode_chunk != 1:
                raise ValueError("speculate replaces decode_chunk: one spec "
                                 "cycle IS the multi-token dispatch — set "
                                 "decode_chunk=1")
            if not model.has_draft:
                raise ValueError(
                    f"speculate={cfg.speculate} needs a self-draft artifact: "
                    f"load the model with registry.load(..., draft_spec=Draft"
                    f"Spec(...)); '{model.name}' has none")
            SP.check_supported(model.cfg, cfg.max_len + cfg.speculate)
        model.check_backend(cfg.backend)
        self.model = model
        self.cfg = cfg
        mcfg = model.cfg
        self.scheduler = scheduler or ContinuousScheduler()
        self.metrics = metrics or ServeMetrics()
        self.backend = backend or LocalBackend()
        self.backend.build(model, cfg)
        self.pool = self.backend.pool
        # host-static per-dispatch ledger: bytes the legacy gather+scatter
        # wrap would have moved (0 on the slab pool / legacy paged mode)
        self._gather_bytes = self.backend.gather_bytes_per_dispatch()
        if cfg.qos is not None:
            from repro.serve.qos import QoSController
            self._qos = QoSController(cfg.qos, self.backend.n_tiers)
        else:
            self._qos = None
        self._vocab = model.cfg.vocab
        self._has_deadlines = False     # arms the per-step deadline sweep
        self.trace = Tracer(cfg.trace) if cfg.trace is not None \
            else NULL_TRACER
        self.pool.tracer = self.trace
        if self.backend.draft_pool is not None:
            self.backend.draft_pool.tracer = self.trace
        # ineffectual-work ledger (serve.ledger): the sink folds each
        # dispatch's drained counter delta into float64 running totals and
        # fans it out to metrics + tracer; NULL_LEDGER keeps the disabled
        # hot path allocation-free (one attribute lookup + fixed-arity
        # no-op call per dispatch).
        if cfg.ledger is not None:
            self.ledger = LedgerSink(cfg.ledger, mcfg.n_layers,
                                     metrics=self.metrics, tracer=self.trace)
        else:
            self.ledger = NULL_LEDGER
        self._quality_every = cfg.ledger.quality_every \
            if cfg.ledger is not None else 0
        self._quality_count = 0        # full-prefill admissions since probe
        self.quality_log: List[Dict[str, Any]] = []
        # per-dispatch host-sync payload, precomputed so every hot-path
        # tracer call passes only pre-existing values (the zero-allocation
        # contract of the disabled path — tests/test_trace.py)
        if cfg.speculate:
            # commit block (B, K+1) + commit counts (B,) + accepts (B,)
            self._sync_bytes = 4 * cfg.n_slots * (cfg.speculate + 3)
        elif cfg.device_loop:
            self._sync_bytes = 4 * cfg.n_slots * cfg.decode_chunk
        else:
            # full-vocab logits pull + token and index re-uploads
            self._sync_bytes = 4 * cfg.n_slots * (mcfg.vocab + 2)
        if cfg.speculate:
            self.metrics.draft_flop_fraction = model.draft_cost_fraction()
            # target verify forwards per cycle (mirrors the steps builder)
            self._verify_steps = (cfg.speculate + 1) \
                if (mcfg.is_ssm or mcfg.attn_period) else 1
        if not cfg.device_loop:
            self._tokens = np.zeros((cfg.n_slots, 1), np.int32)
            self._indices = np.zeros((cfg.n_slots,), np.int32)
        self._slots: List[Optional[Request]] = [None] * cfg.n_slots
        self._waiting: collections.deque = collections.deque()
        self._rng = np.random.default_rng(cfg.seed)
        self._next_id = 0
        self.step_count = 0
        self.requests: Dict[int, Request] = {}
        # padding past the window would let the circular prefill evict real
        # positions in favor of pad garbage (attention._prefill_cache)
        windows = [w for w in (mcfg.window,) if w]
        self._bucket_cap = min([cfg.max_len] + windows)
        self._exact_prefill = bool(mcfg.is_ssm or mcfg.attn_period
                                   or mcfg.n_experts or mcfg.enc_dec)
        # whether a request's total length is bounded by max_len: pure-SSM
        # state is O(1) and a uniformly-windowed cache is circular, so both
        # serve sequences longer than the slab — the long_500k story.
        self._len_bounded = not (
            mcfg.is_ssm
            or (mcfg.window is not None and not mcfg.local_global_period
                and not mcfg.mla and not mcfg.attn_period and not mcfg.enc_dec))

    # ------------------------------------------------------------------ API

    def submit(self, prompt: Sequence[int], max_new_tokens: int, *,
               arrival_step: int = 0, temperature: float = 0.0,
               eos_id: Optional[int] = None,
               extras: Optional[Dict[str, Any]] = None,
               on_token=None, speculate: Optional[int] = None,
               deadline_steps: Optional[int] = None,
               deadline_ms: Optional[float] = None,
               slo: str = "") -> Request:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        r = Request(id=-1, prompt=prompt,
                    max_new_tokens=max_new_tokens, arrival_step=arrival_step,
                    temperature=temperature, eos_id=eos_id, extras=extras,
                    on_token=on_token, speculate=speculate,
                    deadline_steps=deadline_steps, deadline_ms=deadline_ms,
                    slo=slo)
        return self.adopt(r)

    def adopt(self, r: Request) -> Request:
        """Validate + enqueue a Request object (fresh submit, a waiting
        request moved here by the replica router's rebalancer, or a request
        evacuated off a dead replica — `failover_from` set — resuming its
        generation here). Raises EngineSaturated when the bounded waiting
        deque is full — counted as a rejection; the router spills the
        request to a sibling replica. A request whose deadline provably
        cannot be met is shed HERE (terminal state, never queued) and
        returned — admission-time load shedding."""
        if r.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        # evacuated requests fold prior output into the prompt and keep
        # `generated`, so the budget below is what is still owed
        budget = r.max_new_tokens - len(r.generated)
        need = self.model.cfg.n_img_tokens + len(r.prompt) + budget
        if self._len_bounded and need > self.cfg.max_len:
            raise ValueError(
                f"request needs {need} cache positions "
                f"(img + prompt {len(r.prompt)} + gen {budget}) "
                f"but max_len={self.cfg.max_len}")
        # no page-capacity check needed: `need` clamps at max_len, so a
        # request can require at most pages_per_slot pages, and the paged
        # pool's constructor already guarantees usable >= pages_per_slot —
        # any admissible request eventually fits once pages free up.
        if self.cfg.max_waiting is not None \
                and len(self._waiting) >= self.cfg.max_waiting:
            self.metrics.on_reject()
            self.trace.reject(len(self._waiting))
            raise EngineSaturated(
                f"waiting deque at max_waiting={self.cfg.max_waiting}")
        r.id = self._next_id
        self._next_id += 1
        self.requests[r.id] = r
        self.metrics.on_submit(r.id, r.arrival_step, len(r.prompt))
        self.trace.submit(r.id, len(r.prompt), r.arrival_step)
        if r.failover_from >= 0:
            # counted on the DESTINATION replica (sums cleanly in
            # aggregate); the source's counters died with it
            self.metrics.on_failover()
            self.trace.failover(r.id, r.failover_from)
            r.failover_from = -1
        if r.deadline_steps is not None or r.deadline_ms is not None:
            self._has_deadlines = True
            if self._doomed(r):
                # already-doomed work: shedding it NOW costs nothing and
                # frees the queue slot for requests that can still make it
                self._shed(r, "deadline")
                return r
        self._waiting.append(r)
        return r

    def steal_waiting(self, n: int) -> List[Request]:
        """Pop up to `n` waiting (never started) requests off the TAIL of
        the deque — the most recently queued, i.e. the ones that would wait
        longest here — de-registering them from this engine. The router
        re-`adopt`s them into an underloaded replica; the Request objects
        (the caller's handles) survive the move."""
        out: List[Request] = []
        while self._waiting and len(out) < n:
            r = self._waiting.pop()
            del self.requests[r.id]
            self.metrics.records.pop(r.id, None)
            r.id = -1
            out.append(r)
        return out[::-1]                # preserve relative arrival order

    def cancel(self, r: Request, reason: str = "cancel") -> None:
        """Explicit in-flight cancellation: a terminal 'shed' state with
        the slot, pages (and the draft slab row — it shares the slot id),
        and prefix-tree refcounts all released cleanly. Idempotent on
        finished requests."""
        if r.finished or r.id not in self.requests:
            return
        if r.state == "waiting":
            try:
                self._waiting.remove(r)
            except ValueError:
                pass
        self._shed(r, reason)

    def evacuate(self) -> List[Request]:
        """Strip every non-finished request off this engine for
        re-admission elsewhere (router failover). A running request folds
        what it already generated into its prompt and resets to waiting —
        the survivor's greedy re-prefill of the full history reconstructs
        the causal cache exactly (the same property prefix reuse relies
        on), so the resumed stream is token-identical to an uninterrupted
        run. Requests/records are de-registered here (the router re-adopts
        them, so completions are never double-counted)."""
        out: List[Request] = []
        for slot, r in enumerate(self._slots):
            if r is None:
                continue
            try:
                if self.cfg.device_loop:
                    self.backend.release_slot(slot)
                self.pool.free(slot)
            except Exception:
                pass    # a crashed backend may refuse the dispatch; this
                #         replica is being torn down anyway
            self._slots[slot] = None
            if r.generated:
                r.prompt = np.concatenate(
                    [r.prompt, np.asarray(r.generated, np.int32)])
            r.state = "waiting"
            r.slot, r.index, r.prefix_matched = -1, 0, 0
            out.append(r)
        out.extend(self._waiting)
        self._waiting.clear()
        for r in out:
            self.requests.pop(r.id, None)
            self.metrics.records.pop(r.id, None)
            r.id = -1
        return out

    # -- QoS tiers (serve.qos) ----------------------------------------------

    @property
    def tier(self) -> int:
        """Active quality tier (0 = the model's own spec, full quality)."""
        return self.backend.tier

    def set_tier(self, tier: int) -> None:
        """Swap the live decode onto packed tier `tier`. KV-compatible by
        construction: resident requests continue from their exact stream
        position; each one records the cheapest tier it ever decoded on."""
        old = self.backend.tier
        if tier == old:
            return
        self.backend.set_tier(tier)
        self.metrics.on_tier_change(old, tier)
        self.trace.tier_change(old, tier, len(self._waiting))
        for r in self._slots:
            if r is not None and tier > r.tier:
                r.tier = tier
                self.trace.req_tier(r.id, tier)

    @property
    def n_active(self) -> int:
        return sum(r is not None for r in self._slots)

    @property
    def n_waiting(self) -> int:
        return len(self._waiting)

    @spanned("serve.step")
    def step(self) -> None:
        """One engine step: admissions, then one slab decode dispatch."""
        self.trace.step = self.step_count
        self._expire_deadlines()
        if self._qos is not None:
            self._qos_tick()
        arrived = [r for r in self._waiting
                   if r.arrival_step <= self.step_count
                   and r.retry_at_step <= self.step_count]
        admitted = self.scheduler.admissible(arrived, self.pool.n_active,
                                             self.pool.n_free)
        if admitted:
            # single-pass re-partition of the deque: the per-request
            # deque.remove() of PR 1 was O(waiting) per admission, O(n^2)
            # per step under bursty arrivals.
            chosen = {r.id for r in admitted}
            self._waiting = collections.deque(
                r for r in self._waiting if r.id not in chosen)
            for i, r in enumerate(admitted):
                try:
                    self._start(r)
                except PoolExhausted:
                    # page pressure (free slots but not enough free pages,
                    # even after LRU prefix eviction): requeue this and the
                    # remaining admissions at the FRONT in arrival order —
                    # finishing requests release pages, so they retry on
                    # the very next step instead of crashing it. With
                    # pool_wait_retries set, the failed admission instead
                    # parks behind an exponential step backoff (other
                    # arrivals keep getting tried — no head-of-line
                    # starvation) and is shed past the retry cap.
                    cap = self.cfg.pool_wait_retries
                    r.pool_retries += 1
                    rest = admitted[i + 1:]
                    if cap is not None and r.pool_retries > cap:
                        self._shed(r, "pool")
                    else:
                        if cap is not None:
                            r.retry_at_step = self.step_count + min(
                                1 << r.pool_retries, 64)
                        rest = [r] + rest
                    for rr in reversed(rest):
                        self._waiting.appendleft(rr)
                    self.metrics.on_pool_wait()
                    self.trace.pool_wait()
                    break
        if self.pool.n_active:
            if self.cfg.speculate:
                advanced = self._decode_spec()
            elif self.cfg.device_loop:
                advanced = self._decode_block()
            else:
                advanced = self._decode_step_host()
            stats = self.backend.page_stats()
            if stats is not None:       # per-dispatch page-pool gauge
                self.metrics.on_pages(*stats)
        else:
            self.metrics.on_idle_step()
            advanced = 1
        self.step_count += advanced

    def run(self, max_steps: Optional[int] = None) -> Dict[int, np.ndarray]:
        """Step until every submitted request completes; returns outputs."""
        limit = max_steps if max_steps is not None else \
            10 * sum(r.max_new_tokens + 2 for r in self.requests.values()) \
            + max([r.arrival_step for r in self.requests.values()], default=0)
        while (self._waiting or self.pool.n_active) and limit > 0:
            self.step()
            limit -= 1
        if self._waiting or self.pool.n_active:
            raise RuntimeError("engine did not drain within the step limit")
        return {rid: np.asarray(r.generated, np.int32)
                for rid, r in self.requests.items()}

    # ------------------------------------------------------------- internals

    def _prefill_len(self, s0: int) -> int:
        if self._exact_prefill or not self.cfg.prefill_buckets:
            return s0
        b = self.cfg.bucket_min
        while b < s0:
            b *= 2
        return b if b <= self._bucket_cap else s0

    def _suffix_len(self, s: int, start: int) -> int:
        """Bucketed suffix-prefill length: pow2 like `_prefill_len`, but
        the pad tail must also FIT — it is written (masked) at positions
        start..start+bucket, so the bucket falls back to exact when it
        would run past the slot's cache positions."""
        if self._exact_prefill or not self.cfg.prefill_buckets:
            return s
        b = self.cfg.bucket_min
        while b < s:
            b *= 2
        return b if start + b <= self._bucket_cap else s

    def _sample_host(self, row: np.ndarray, r: Request) -> int:
        if r.temperature <= 0.0:
            return int(np.argmax(row))
        logits = row.astype(np.float64) / r.temperature
        g = self._rng.gumbel(size=logits.shape)
        return int(np.argmax(logits + g))

    def _doomed(self, r: Request) -> bool:
        """True when the request provably cannot finish by its deadline.

        Step clock: a live slot emits at least one token per engine step
        (up to 1 + spec_limit when speculating — the OPTIMISTIC bound, so a
        salvageable request is never shed early), so the earliest possible
        finish is start + ceil(remaining / per_step) - 1. Wall clock: the
        elapsed time since submit (records' monotonic baseline) against
        deadline_ms."""
        if r.deadline_ms is not None:
            rec = self.metrics.records.get(r.id)
            if rec is not None and (time.perf_counter() - rec.submit_mono) \
                    * 1e3 > r.deadline_ms:
                return True
        d = r.deadline_step()
        if d is None:
            return False
        rem = r.max_new_tokens - len(r.generated)
        per = 1 + self._spec_limit(r)
        start = max(self.step_count, r.arrival_step)
        return start - (-rem // per) - 1 > d        # ceil division

    def _shed(self, r: Request, reason: str) -> None:
        """Terminal 'shed' disposition ('deadline' | 'pool' | 'failover' |
        'cancel'): release everything the request holds — slot row parked
        inert on device, pool slot/pages freed (prefix-tree refs drop with
        them), record kept for observability. Never counts as a
        completion."""
        if r.state == "running":
            if self.cfg.device_loop:
                self.backend.release_slot(r.slot)
            self.pool.free(r.slot)
            self._slots[r.slot] = None
        r.state = "shed"
        r.shed_reason = reason
        self.metrics.on_shed(reason)
        self.trace.shed(r.id, r.slot, reason, len(r.generated))

    def _expire_deadlines(self) -> None:
        """Per-step sweep (armed only once a deadline exists): shed waiting
        AND running requests the moment they become doomed — mid-flight
        cancellation frees the slot for work that can still meet its SLO,
        and no completion is ever served past its deadline."""
        if not self._has_deadlines:
            return
        expired = [r for r in self._waiting if self._doomed(r)]
        if expired:
            dead = {r.id for r in expired}
            self._waiting = collections.deque(
                r for r in self._waiting if r.id not in dead)
            for r in expired:
                self._shed(r, "deadline")
        for r in list(self._slots):
            if r is not None and self._doomed(r):
                self._shed(r, "deadline")

    def _qos_tick(self) -> None:
        """Feed the tier controller this step's load signal (queue depth +
        page-pool fullness) and apply its verdict."""
        stats = self.backend.page_stats()
        frac = 0.0
        if stats is not None:
            used, usable = stats
            frac = used / max(1, usable)
        want = self._qos.observe(len(self._waiting), frac)
        if want != self.backend.tier:
            self.set_tier(want)

    def _emit(self, r: Request, tok: int, step: int) -> None:
        r.generated.append(tok)
        self.metrics.on_token(r.id, step)
        if len(r.generated) == 1:
            # explicit step (not tracer.step): micro-steps within a K-block
            # advance the emission clock ahead of the dispatch clock, and
            # spans must reconcile exactly with ServeMetrics
            self.trace.first_token(r.id, r.slot, step)
        if r.on_token is not None:
            r.on_token(r, tok)
        done = len(r.generated) >= r.max_new_tokens \
            or (r.eos_id is not None and tok == r.eos_id)
        if done:
            r.state = "done"
            self.trace.finish(r.id, r.slot, step, len(r.generated))
            if self.backend.paged and not r.extras:
                # publish the WHOLE conversation (prompt + generated) into
                # the prefix tree BEFORE the slot's pages are freed — the
                # next turn of this chat prefix-matches its entire prior
                # exchange and skips that prefill. Only the finish path
                # publishes: shed/cancel/evacuate never promise their
                # pages' contents.
                self.backend.conversation_insert(
                    np.concatenate([r.prompt,
                                    np.asarray(r.generated, np.int32)]),
                    r.slot)
            self.pool.free(r.slot)
            self._slots[r.slot] = None
            self.metrics.on_finish(r.id, step)

    def _start(self, r: Request) -> None:
        with span("serve.admit"):
            self.trace.admit_begin()
            slot = self.pool.alloc()
            s0 = len(r.prompt)
            n_img = self.model.cfg.n_img_tokens
            # what is still owed: a failover-resumed request already
            # generated part of its budget (now folded into the prompt)
            budget = r.max_new_tokens - len(r.generated)
            # paged admission: longest page-aligned cached prefix, then the
            # slot's page-table row (shared prefix pages refcount-bumped,
            # fresh private pages for suffix + generation + speculative
            # headroom). `conv` flags a hit that ran through pages a
            # finished request published from its GENERATED tokens — a chat
            # resuming its own prior turn. PoolExhausted here propagates to
            # step(), requeued.
            matched, shared, conv = (0, (), False) if r.extras else \
                self.backend.prefix_match(r.prompt)
            # Page allocation is sized from the TRUE request footprint —
            # prompt + owed budget + speculative headroom — never from the
            # pow2 prefill bucket. Bucket padding is a compile-shape policy
            # only: padded-tail writes land past the allocated footprint,
            # where the page table reads the reserved sink page (masked
            # garbage), so a bigger bucket must never cost real pages.
            try:
                self.backend.alloc_slot_pages(
                    slot, n_img + s0 + budget + self.cfg.speculate,
                    shared)
            except PoolExhausted:
                self.pool.free(slot)
                raise
            sp = self._prefill_len(s0)
            tokens = np.zeros((1, sp), np.int32)
            tokens[0, :s0] = r.prompt
            batch = {"tokens": jnp.asarray(tokens)}
            if r.extras:
                batch.update({k: jnp.asarray(v) for k, v in r.extras.items()})
            self.trace.admit(r.id, slot, matched, s0)
        with span("serve.prefill"):
            if matched:
                # prefix hit: only the unmatched suffix runs, right-padded
                # into the same pow2 buckets as full prefills (real traffic
                # produces arbitrary suffix lengths — one compile per length
                # would be a compile-shape explosion). The logits column at
                # the TRUE suffix end seeds sampling; the padded tail's
                # writes land past the shared region — in the slot's
                # private pages where the footprint still covers them, in
                # the reserved sink page where it doesn't — masked garbage
                # either way until decode overwrites the real positions.
                # `batch` still carries the full padded prompt for a
                # speculating backend's draft.
                s_sfx = s0 - matched
                sp_sfx = self._suffix_len(s_sfx, n_img + matched)
                sfx = np.zeros((1, sp_sfx), np.int32)
                sfx[0, :s_sfx] = r.prompt[matched:]
                logits = self.backend.prefill_suffix(
                    {"tokens": jnp.asarray(sfx)}, batch, slot,
                    n_img + matched)
                row = logits[:, s_sfx - 1]
            else:
                logits, caches = self.backend.prefill(batch, exact=sp == s0)
                # (1, vocab) on device: the true prompt-end column
                row = logits[:, -1] if sp == s0 \
                    else logits[:, n_img + s0 - 1]
                self.backend.write_slot(slot, caches)
        if matched:
            self.trace.prefill(r.id, slot, s_sfx, sp_sfx, True)
        else:
            self.trace.prefill(r.id, slot, s0, sp, False)
            if self._quality_every:
                # every quality_every-th FULL-prefill admission (prefix-hit
                # suffixes are skipped: their logits depend on page state
                # the offline recompute can't replay standalone)
                self._quality_count += 1
                if self._quality_count >= self._quality_every:
                    self._quality_count = 0
                    self._quality_probe(
                        r, batch, row, sp == s0,
                        -1 if sp == s0 else n_img + s0 - 1)
        if not r.extras:
            # publish the prompt's full pages for future admissions (a
            # no-op on the slab pool / prefix-unsupported archs)
            self.backend.prefix_insert(r.prompt, slot)
        if self.backend.paged:
            self.metrics.on_prefix(matched, s0)
            if conv and matched:
                self.metrics.on_conversation_hit(matched)
                self.trace.conversation_hit(r.id, matched)
        r.prefix_matched = matched
        r.state, r.slot = "running", slot
        r.index = n_img + s0
        r.tier = max(r.tier, self.backend.tier)
        self._slots[slot] = r
        self.metrics.on_start(r.id, self.step_count)
        self.trace.req_tier(r.id, self.backend.tier)
        with span("serve.first_token"):
            if self.cfg.device_loop:
                tok = self.backend.first_token(row, r.id, r.temperature)
                self.metrics.on_host_sync("prefill")  # the one int32 pulled
                self.trace.host_sync("prefill", 4)
                eos = -1 if r.eos_id is None else int(r.eos_id)
                rem = 0 if (r.eos_id is not None and tok == r.eos_id) \
                    else budget - 1
                self.backend.install(slot, tok, r.index, r.temperature, eos,
                                     rem, self._spec_limit(r))
            else:
                tok = self._sample_host(np.asarray(row[0]), r)
                self.metrics.on_host_sync("prefill")
                self.trace.host_sync("prefill", 4)
                self._tokens[slot, 0] = tok
                self._indices[slot] = r.index
        self._emit(r, tok, self.step_count)  # may finish (max_new_tokens == 1)

    def _quality_probe(self, r: Request, batch, row, exact: bool,
                       col: int) -> None:
        """Per-tier quality probe (serve.ledger): shadow-run this
        admission's prefill through TIER-0 params and compare the sampled
        logits column host-side — top-1 agreement + mean |Δlogit| recorded
        per active (sparsity, bits) tier. Two deliberate host pulls,
        metered as kind='quality' so `host_syncs_decode` stays exactly the
        decode-dispatch count. Both prefills are deterministic functions of
        (prompt, params), so an offline recompute of the same slot
        reproduces these gauges EXACTLY (tests/test_ledger.py)."""
        shadow = self.backend.quality_shadow(batch, exact)
        ref = np.asarray(shadow[:, col][0], np.float64)
        mine = np.asarray(row)[0].astype(np.float64)
        self.metrics.on_host_sync("quality", 2)
        self.trace.host_sync("quality", 8 * mine.size)
        top1 = bool(int(np.argmax(mine)) == int(np.argmax(ref)))
        mad = float(np.mean(np.abs(mine - ref)))
        tier = self.backend.tier
        self.metrics.on_quality_probe(tier, top1, mad)
        self.trace.quality_probe(r.id, tier, top1, mad)
        self.quality_log.append({"rid": r.id, "tier": tier,
                                 "top1": top1, "mad": mad})

    def _decode_block(self) -> int:
        """Device-resident path: ONE dispatch = K fused micro-steps; sync a
        (K, B) int32 token block and catch host bookkeeping up to it."""
        k = self.cfg.decode_chunk
        n_active = self.pool.n_active
        self.metrics.on_decode_step(n_active, self.cfg.n_slots,
                                    micro_steps=k)
        self.trace.dispatch_begin()
        with span("serve.decode"):
            block = self.backend.decode_block()
        self.trace.decode_dispatch(k, n_active, self.cfg.n_slots)
        return self._emit_block(block, k)

    @spanned("serve.emit")
    def _emit_block(self, block: np.ndarray, k: int) -> int:
        """Host bookkeeping after a fused dispatch's one sync."""
        if self._gather_bytes:
            self.metrics.on_gather_avoided(self._gather_bytes)
            self.trace.gather_avoided(self._gather_bytes)
        self.metrics.on_host_sync("decode")
        self.trace.host_sync("decode", self._sync_bytes)
        # ledger drain rides the dispatch sync above — no extra crossing
        self.ledger.on_drain(self.backend.last_ledger, self.step_count)
        if self.ledger.enabled and self.backend.maybe_rebase_ledger():
            self.ledger.rebase()
        # mirror the tracer's cumulative ring-buffer drop count so
        # report()/telemetry surface lost trace events (an int attribute
        # store: allocation-free on the disabled path)
        self.metrics.trace_dropped = self.trace.dropped
        # fault detection at the host/device boundary: a healthy fused step
        # emits argmax/Gumbel-argmax indices, ALWAYS in [0, vocab) — an
        # out-of-range token in a live column is proof of a corrupted
        # dispatch (NaN logits, device fault), never a sampling outcome.
        live = [s for s in range(self.cfg.n_slots)
                if self._slots[s] is not None]
        sub = block[:, live]
        if sub.size and (int(sub.min()) < 0 or int(sub.max()) >= self._vocab):
            raise ReplicaFault(
                f"decode sync outside [0, {self._vocab}): corrupted "
                "dispatch (NaN logits or device fault)")
        for j in range(k):
            step = self.step_count + j
            for slot in range(self.cfg.n_slots):
                r = self._slots[slot]
                if r is None:
                    continue
                r.index += 1
                self._emit(r, int(block[j, slot]), step)
        return k

    def _spec_limit(self, r: Request) -> int:
        """Per-slot speculation cap: the engine K, clamped by the request's
        own `speculate` (0 = opt out). Used for both the device install and
        the metrics' proposed-token denominators — a capped slot proposes
        only up to its cap, so acceptance rates stay meaningful."""
        if not self.cfg.speculate:
            return 0
        if r.speculate is None:
            return self.cfg.speculate
        return max(0, min(r.speculate, self.cfg.speculate))

    def _decode_spec(self) -> int:
        """Speculative path: ONE fused propose-then-verify dispatch commits
        1..K+1 tokens per live slot. The sync is (commit block, commit
        counts, accepted counts) — still one crossing; the host replays the
        committed prefix per slot in micro-step order and the engine clock
        advances by the deepest commit (speculation compresses wall
        dispatches, not the step-latency bookkeeping)."""
        k = self.cfg.speculate
        n_active = self.pool.n_active
        # slab forwards actually run per cycle: k+1 draft micro-steps plus
        # the target verify — one batched forward for positional-cache
        # archs, k+1 micro-steps for recurrent ones (steps builder)
        self.metrics.on_decode_step(n_active, self.cfg.n_slots,
                                    micro_steps=(k + 1) + self._verify_steps)
        self.trace.dispatch_begin()
        with span("serve.spec"):
            block, n_commit, n_accept = self.backend.spec_decode_block()
        self.trace.spec_dispatch(k, n_active, self.cfg.n_slots)
        return self._emit_spec(block, n_commit, n_accept, k)

    @spanned("serve.emit")
    def _emit_spec(self, block: np.ndarray, n_commit: np.ndarray,
                   n_accept: np.ndarray, k: int) -> int:
        """Host bookkeeping after a speculative dispatch's one sync."""
        if self._gather_bytes:
            self.metrics.on_gather_avoided(self._gather_bytes)
            self.trace.gather_avoided(self._gather_bytes)
        self.metrics.on_host_sync("decode")
        self.trace.host_sync("decode", self._sync_bytes)
        # ledger drain rides the dispatch sync above — no extra crossing
        self.ledger.on_drain(self.backend.last_ledger, self.step_count)
        if self.ledger.enabled and self.backend.maybe_rebase_ledger():
            self.ledger.rebase()
        # mirror the tracer's cumulative ring-buffer drop count so
        # report()/telemetry surface lost trace events (an int attribute
        # store: allocation-free on the disabled path)
        self.metrics.trace_dropped = self.trace.dropped
        # fault detection (see _decode_block): validate every live slot's
        # committed prefix BEFORE any emission side effects
        for slot in range(self.cfg.n_slots):
            r = self._slots[slot]
            if r is None:
                continue
            m = int(n_commit[slot])
            if not 0 <= m <= k + 1 or (m and (
                    int(block[slot, :m].min()) < 0
                    or int(block[slot, :m].max()) >= self._vocab)):
                raise ReplicaFault(
                    f"speculative sync outside [0, {self._vocab}) or commit "
                    f"count {m} out of [0, {k + 1}]: corrupted dispatch")
        advanced, proposed, accepted = 1, 0, 0
        for slot in range(self.cfg.n_slots):
            r = self._slots[slot]
            if r is None:
                continue
            m = int(n_commit[slot])
            # a draft token only ever had a chance to commit within the
            # slot's remaining budget: clamp the proposed-denominator so
            # short-budget tails don't deflate the acceptance signal
            lim = min(self._spec_limit(r),
                      r.max_new_tokens - len(r.generated))
            advanced = max(advanced, m)
            for j in range(m):
                r.index += 1
                self._emit(r, int(block[slot, j]), self.step_count + j)
            if r.done and r.eos_id is not None and m \
                    and int(block[slot, m - 1]) == r.eos_id:
                # EOS ended the request mid-block: columns past it never
                # had a commit chance either
                lim = min(lim, m)
            proposed += lim
            acc = int(n_accept[slot])
            accepted += acc
            if lim:
                self.metrics.on_slot_speculation(slot, acc, lim)
                self.trace.spec_slot(slot, acc, m, lim)
        self.metrics.on_spec_dispatch(proposed=proposed, accepted=accepted)
        return advanced

    def _decode_step_host(self) -> int:
        """PR-1 host loop: full-vocab logits pulled, numpy sampling, token +
        index vectors re-uploaded every step. Kept as the measured baseline
        (serve_bench 'host' mode) and as the numpy-rng sampling reference."""
        n_active = self.pool.n_active
        self.metrics.on_decode_step(n_active, self.cfg.n_slots)
        self.trace.dispatch_begin()
        with span("serve.decode"):
            rows = self.backend.decode_host(self._tokens, self._indices)
        self.trace.decode_dispatch(1, n_active, self.cfg.n_slots)
        return self._emit_host(rows)

    @spanned("serve.emit")
    def _emit_host(self, rows: np.ndarray) -> int:
        """Host sampling and bookkeeping after a host-loop step."""
        # logits pull + token and index uploads: 3 crossings per step
        self.metrics.on_host_sync("decode", 3)
        self.trace.host_sync("decode", self._sync_bytes)
        for slot, r in enumerate(self._slots):
            if r is None:
                continue
            r.index += 1
            self._indices[slot] = r.index
            tok = self._sample_host(rows[slot], r)
            self._tokens[slot, 0] = tok
            self._emit(r, tok, self.step_count)
        return 1

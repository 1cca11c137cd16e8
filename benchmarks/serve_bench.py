"""Serving benchmark: device-resident decode loop vs the PR-1 host loop,
and static vs continuous batching, under a Poisson trace.

The serving claims of the Kratos stack:

  (1) continuous batching keeps the decode slab full under mixed-length
      traffic, where the lock-step baseline drains to the longest member;
  (2) the decode hot path runs on PACKED weights (kratos.pack once at load,
      apply_packed per step), so the sparsity/precision savings exist at
      serving time;
  (3) [PR 2] the decode loop is device-resident: sampling fused into the
      compiled step, donated KV slab, K micro-steps per dispatch — decode
      syncs drop from 3 per micro-step (full-vocab logits pull + token/index
      uploads) to exactly 1 per K-step dispatch (= 1/K per micro-step, and
      <= 1/K per decoded token whenever the trace sustains K tokens per
      dispatch);
  (4) [PR 2] the decode GEMMs (m = n_slots) dispatch through the Pallas
      kernels' skinny-m path, asserted by trace-time instrumentation
      (pallas_compat.SKINNY_M_EVENTS) the same way apply_packed routing is.

Method: one Poisson arrival trace (exponential inter-arrival steps, mixed
prompt/generation lengths) is replayed against the SAME engine configuration
in three modes per KratosSpec — 'host' (PR-1 loop, continuous), 'device'
(fused loop, K=--decode-chunk, continuous) and 'static' (fused loop, static
scheduler). The primary comparison metric is tokens/decode-dispatch — the
deterministic, compile-noise-free clock — with wall tok/s and host syncs per
decoded token alongside. `--out` writes the records as JSON
({arch, spec, mode, tokens_per_step, wall_tok_s, host_syncs_per_token, ...})
so every future PR has a perf baseline to diff against.

Speculative mode (PR 4): `--speculate K` switches the benchmark to the
self-draft comparison — ONE trace replayed through the PLAIN device loop
(decode_chunk=1: `speculate` replaces the chunk knob, so the un-chunked
loop is the apples-to-apples baseline) and through the speculative engine
(speculate=K) with the draft described by
`--draft-bits/--draft-sparsity/--draft-keep-layers`. The GATE is
deterministic: speculative >= 1.2x TOKENS PER DISPATCH vs the plain loop
(integers, immune to CI timing noise), plus greedy token-identity. Both
engines are additionally WARMED on a full replay and timed on a second
one, and the wall tokens/sec ratio is REPORTED ungated — on the CPU
reference backend the draft re-pack executes at full-precision cost (the
packed Pallas kernels that realize its FLOP discount engage off-ref).
Records carry acceptance rate, rollback counts and the draft/verify FLOP
ratio.

Prefix-trace mode (PR 5): `--prefix-trace` replays a Poisson trace whose
prompts share Zipf-distributed SYSTEM PREFIXES (the chat-fleet shape:
a few long system prompts, many short user suffixes) through the slab
engine and through the paged + prefix-cached engine
(`EngineConfig.page_size`, serve.paging). Gates: greedy token-identity,
paged+prefix >= 1.3x ADMITTED tokens/sec (prompt + generated tokens per
wall second, warm-measured — the number admission latency caps), and >=
50% of prompt tokens skipped at prefill (served from shared prefix pages).
Every JSON record carries the prefill FLOPs saved (2 * N_active * skipped
tokens) and the page-pool occupancy; `--out results/BENCH_prefix.json` is
the CI artifact.

Conversation-trace mode (PR 8): `--conversation-trace` drives multi-turn
CHATS — each turn's prompt is the whole prior conversation (prompt +
the engine's own reply) plus a short follow-up, so the trace cannot be
precomputed: later turns are built live from the tokens the engine
emitted. The paged engine publishes every finished request's FULL
conversation into the radix prefix tree (generated tokens included), so
turn t matches every full page of turns 1..t-1 and prefills only the
follow-up. Gates: >= 70% of ALL prompt tokens skipped at prefill, ZERO
gather/scatter events on the page-table-native decode hot path (with
`gather_bytes_avoided` exactly accounting the traffic the legacy wrap
would have moved), and greedy token-identity against a slab engine fed
the same per-turn prompts. `--out results/BENCH_conv.json` is the CI
artifact, diffed against its golden by benchmarks/qor.py.

Overload-trace mode (PR 7): `--overload-trace` replays a 2x SATURATING
Poisson trace (token arrivals at twice the chunk-1 slab's service rate)
through a baseline engine that admits everything and through the resilient
engine (per-request deadlines + QoS tier ladder + bounded pool-wait
retries). Gates: resilient >= 1.2x GOODPUT per engine step — deadline-met
generated tokens on the deterministic step clock — and ZERO completions
served past their deadline on the resilient side (admission-time doom
shedding + per-step expiry make that exact at decode_chunk=1). Wall tok/s
is reported ungated; `--out results/BENCH_overload.json` is the CI
artifact.

Ledger-trace mode (PR 9): `--ledger-trace` replays one Poisson trace TWICE
through a device-loop engine carrying the ineffectual-work ledger
(serve.ledger) and gates, all step-clock deterministic: measured activation
zeros > 0 (nemotron's squared-ReLU MLP), every counter and per-layer
zero-group histogram bit-identical across the two runs (hist_checksum),
host syncs == decode dispatches (the ledger drains inside the existing
token sync), and an exact tier-0 quality shadow (top1 1.0, MAD 0.0). The
JSON artifact adds an ungated replica roofline (analysis.roofline over
the run's wall time); `--out results/BENCH_ledger.json` is the CI
artifact, diffed against its golden by benchmarks/qor.py.

Provenance (PR 4): every JSON record is stamped with the git commit, jax
version and rng seed, so BENCH trajectories are comparable across runs.

Mesh / router modes (PR 3): `--mesh data,model` adds a 'sharded' mode —
the same trace through `serve.ShardedBackend` on a local mesh of that
shape, gated on emitting exactly the tokens the local device loop emits
(placement must not change outputs). `--replicas N` adds a router
comparison: ONE dense synthetic trace replayed against a single engine and
against `serve.ReplicaRouter` over N replicas (each on its own
data-submesh when `--mesh` is given), gated on aggregate
tokens/router-step >= 1.5x the single replica's tokens/step. Every JSON
record carries `mesh_shape` and `n_replicas` so the CI artifact
distinguishes placements.

  PYTHONPATH=src python -m benchmarks.serve_bench [--smoke] [--arch ...]
      [--requests N] [--slots K] [--seed S] [--decode-chunk K]
      [--mesh D,M] [--replicas N] [--out results/BENCH_serve.json]
      [--trace-dir results/traces]

QoR gates (PR 6): `--out` records are diffed against committed goldens by
`benchmarks/qor.py` (direction-aware per-metric tolerances; deterministic
step-clock integers gate EXACTLY) — regressions fail CI. `--trace-dir`
additionally records every (spec, mode) run with the serve tracer (JSONL +
Chrome trace + telemetry snapshot per mode) for the CI artifact.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np

from benchmarks.common import CSV
from repro.core import kratos as kr
from repro.distributed import steps as ST
from repro.kernels import pallas_compat as PC
from repro.launch import compile_cache
from repro.serve import (DraftSpec, EngineConfig, InferenceEngine,
                         ModelRegistry, StaticScheduler, TelemetryConfig,
                         TelemetryExporter, TraceConfig, engine_sample)


def provenance(seed: int) -> dict:
    """Stamped into EVERY json record: what produced this number."""
    try:
        commit = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            timeout=10).stdout.strip()
    except Exception:
        commit = ""
    return {"git_commit": commit or os.environ.get("GITHUB_SHA", "unknown"),
            "jax_version": jax.__version__, "rng_seed": seed}

SPECS = (
    ("dense", kr.KratosSpec()),
    ("sparse-tree", kr.KratosSpec(sparsity=0.5, bk=8, bn=8)),
    ("w8a8", kr.KratosSpec(bits=8, act_bits=8)),
    ("sparse0.5-w8", kr.KratosSpec(sparsity=0.5, bits=8, bk=8, bn=8)),
)
SMOKE_SPECS = ("dense", "sparse0.5-w8")


def poisson_trace(n_requests: int, mean_interarrival: float, prompt_range,
                  gen_range, vocab: int, seed: int):
    """[(arrival_step, prompt, gen_len)] with exp. inter-arrival steps."""
    rng = np.random.default_rng(seed)
    t, out = 0.0, []
    for _ in range(n_requests):
        t += rng.exponential(mean_interarrival)
        s0 = int(rng.integers(*prompt_range))
        gen = int(rng.integers(*gen_range))
        out.append((int(t), rng.integers(0, vocab, s0), gen))
    return out


class PackedRouteCounter:
    """Counts kratos.apply_packed dispatches (trace-time: hits = packed
    GEMMs baked into the compiled prefill/decode steps)."""

    def __init__(self):
        self.hits = 0
        self._orig = kr.apply_packed

    def __enter__(self):
        def counted(*a, **kw):
            self.hits += 1
            return self._orig(*a, **kw)
        kr.apply_packed = counted
        return self

    def __exit__(self, *exc):
        kr.apply_packed = self._orig
        return False


def _warn_trace_dropped(tracer) -> None:
    """Warn ONCE per process when a trace export lost events to the ring
    buffer — every later export is silently incomplete in the same way, so
    repeating the warning per mode would just bury the bench output."""
    if getattr(tracer, "dropped", 0) and not _warn_trace_dropped.warned:
        _warn_trace_dropped.warned = True
        print(f"# WARNING: trace ring buffer dropped {tracer.dropped} "
              "events — exports are incomplete; raise TraceConfig.capacity",
              file=sys.stderr)


_warn_trace_dropped.warned = False


def run_one(model, trace, n_slots: int, max_len: int, scheduler, *,
            device_loop: bool = True, decode_chunk: int = 1, backend=None,
            trace_cfg=None, telemetry_jsonl: str = ""):
    eng = InferenceEngine(
        model, EngineConfig(n_slots=n_slots, max_len=max_len,
                            device_loop=device_loop,
                            decode_chunk=decode_chunk,
                            trace=trace_cfg),
        scheduler=scheduler, backend=backend)
    for arrival, prompt, gen in trace:
        eng.submit(prompt, gen, arrival_step=arrival)
    eng.run()
    if trace_cfg is not None:
        eng.trace.export()          # the TraceConfig's out/chrome paths
        _warn_trace_dropped(eng.trace)
    if telemetry_jsonl:
        # one end-of-run snapshot per mode: the CI artifact shows the full
        # metric vector per (spec, mode) alongside the event traces
        TelemetryExporter(lambda: engine_sample(eng),
                          TelemetryConfig(jsonl=telemetry_jsonl)).sample()
    return eng.metrics.report()


def run_router(model, trace, n_slots: int, max_len: int, n_replicas: int,
               decode_chunk: int, mesh_shape=None):
    """The SAME trace through a single engine and through the router over
    n_replicas engines; returns (single_report, router_report). With a mesh
    shape, each replica owns a disjoint data-submesh (replica_meshes);
    max_waiting = n_slots bounds each replica's deque so overload exercises
    the spill-over path instead of queueing unboundedly."""
    from repro.serve import ReplicaRouter, ShardedBackend

    def mk_backend(i):
        if mesh_shape is None:
            return None
        return ShardedBackend(mesh=mk_backend.meshes[i])

    if mesh_shape is not None:
        from repro.launch import mesh as M
        mk_backend.meshes = M.replica_meshes(*mesh_shape, n_replicas)

    single = run_one(model, trace, n_slots, max_len, None,
                     decode_chunk=decode_chunk,
                     backend=mk_backend(0) if mesh_shape else None)
    cfg = EngineConfig(n_slots=n_slots, max_len=max_len,
                       decode_chunk=decode_chunk, max_waiting=n_slots)
    router = ReplicaRouter.build(model, cfg, n_replicas,
                                 backend_factory=mk_backend
                                 if mesh_shape else None)
    for arrival, prompt, gen in trace:
        router.submit(prompt, gen, arrival_step=arrival)
    router.run()
    return single, router.report()


def skinny_decode_trace(model, n_slots: int, max_len: int,
                        decode_chunk: int) -> dict:
    """Trace (don't run) one fused decode step with backend='interpret' and
    count packed + skinny-m dispatches baked into the compiled hot loop.

    The Pallas kernels only engage off the 'ref' backend; tracing is enough —
    both counters fire at trace time — so this stays cheap on CPU while
    asserting exactly what a TPU deployment would compile."""
    from repro.models import transformer as T
    decode = ST.make_decode_step(model.cfg, "interpret",
                                 n_steps=decode_chunk)
    caches = T.make_caches(model.cfg, n_slots, max_len)
    state = ST.make_decode_state(n_slots)
    PC.SKINNY_M_EVENTS.clear()
    with PackedRouteCounter() as counter:
        jax.jit(decode).lower(model.params, caches, state)
    events = list(PC.SKINNY_M_EVENTS)
    PC.SKINNY_M_EVENTS.clear()
    return {"apply_packed_hits": counter.hits,
            "skinny_m_dispatches": len(events),
            "skinny_kernels": sorted({e[0] for e in events})}


def timed_throughput(model, trace, n_slots: int, max_len: int, *,
                     tokens: int = 0, fresh_metrics: bool = False,
                     **cfg_kw):
    """Steady-state tokens/sec: the trace is replayed once to warm (jit
    compiles for prefill buckets + the decode/spec step — and, on a paged
    engine, the radix prefix tree — land here), then replayed again on the
    SAME engine and timed. Returns (tok/s, engine).

    tokens: fixed numerator per replay (e.g. ADMITTED prompt+generated
    tokens); 0 = decoded-token delta. fresh_metrics: swap in a clean
    ServeMetrics after the warm replay so the engine's report describes
    ONLY the timed steady state (the prefix-trace artifact wants hit/skip
    rates undiluted by cold-start misses; don't combine with speculate,
    whose engine seeds draft_flop_fraction into the metrics at init).
    Default keeps the speculative mode's documented behavior: metrics span
    both passes, wall timing only the second."""
    eng = InferenceEngine(model, EngineConfig(n_slots=n_slots,
                                              max_len=max_len, **cfg_kw))

    def replay(offset):
        for arrival, prompt, gen in trace:
            eng.submit(prompt, gen, arrival_step=arrival + offset)
        eng.run()

    replay(0)
    if fresh_metrics:
        from repro.serve import ServeMetrics
        eng.metrics = ServeMetrics()
    tok0 = eng.metrics.tokens_generated
    t0 = time.time()
    replay(eng.step_count + 1)
    dt = max(time.time() - t0, 1e-9)
    return (tokens or eng.metrics.tokens_generated - tok0) / dt, eng


def zipf_prefix_trace(n_requests: int, n_sys: int, sys_len: int,
                      sfx_range, gen_range, vocab: int,
                      mean_interarrival: float, seed: int):
    """Poisson arrivals whose prompts share Zipf-weighted system prefixes:
    rank-r system prompt drawn with p ~ 1/(r+1)^1.1 (a few prompts carry
    most of the traffic — the fleet shape prefix caching exists for), each
    followed by a short unique user suffix."""
    rng = np.random.default_rng(seed)
    sys_prompts = [rng.integers(0, vocab, sys_len) for _ in range(n_sys)]
    w = 1.0 / (1.0 + np.arange(n_sys)) ** 1.1
    w /= w.sum()
    t, out = 0.0, []
    for _ in range(n_requests):
        t += rng.exponential(mean_interarrival)
        k = int(rng.choice(n_sys, p=w))
        sfx = rng.integers(0, vocab, int(rng.integers(*sfx_range)))
        prompt = np.concatenate([sys_prompts[k], sfx])
        out.append((int(t), prompt, int(rng.integers(*gen_range))))
    return out


def run_prefix_trace(arch: str, n_requests: int, n_slots: int, seed: int,
                     page_size: int, out: str = "", gate: float = 1.3,
                     skip_gate: float = 0.5) -> bool:
    """Slab vs paged+prefix on one shared-prefix trace, warm-measured.

    The gated metric is ADMITTED tokens/sec — (prompt + generated) tokens
    per wall second — because that is the quantity redundant prefill caps:
    decode work is identical on both sides, so the ratio isolates the
    admission path. Both engines replay the trace once to warm (compiles
    AND the paged engine's radix tree land there — steady state is the
    claim), then swap in fresh metrics and are timed on a second identical
    replay, so the gates AND the JSON records describe only the steady
    state, undiluted by cold-start misses. Greedy outputs must match token
    for token; >= `skip_gate` of all prompt tokens must have been served
    from shared prefix pages rather than prefilled."""
    registry = ModelRegistry()
    model = registry.load(arch)
    # chat-fleet geometry: long shared system prompts, short unique user
    # suffixes, short replies — the regime where admission (prefill) is the
    # binding cost and prefix reuse pays. Both engines decode chunked
    # (K=4), so the decode side is identical and the ratio isolates the
    # prefill economy.
    sys_len, sfx_range, gen_range = 192, (4, 9), (4, 7)
    trace = zipf_prefix_trace(n_requests, 4, sys_len, sfx_range, gen_range,
                              model.cfg.vocab, 1.0, seed)
    max_len = sys_len + sfx_range[1] + gen_range[1] + 4
    pp = -(-max_len // page_size)
    # pool sized for live slots + the retained system-prefix working set —
    # the paged pool budgets pages against ACTUAL tokens, not slots*max_len
    n_pages = (n_slots + 4) * pp + 1
    prov = provenance(seed)
    admitted_tokens = sum(len(p) + g for _, p, g in trace)

    def timed(**kw):
        return timed_throughput(model, trace, n_slots, max_len,
                                tokens=admitted_tokens, fresh_metrics=True,
                                decode_chunk=4, **kw)

    slab_tps, slab_eng = timed()
    paged_tps, paged_eng = timed(page_size=page_size, n_pages=n_pages)
    same = all(
        slab_eng.requests[i].generated == paged_eng.requests[i].generated
        for i in slab_eng.requests)
    rep, rep_s = paged_eng.metrics.report(), slab_eng.metrics.report()
    ratio = paged_tps / max(1e-9, slab_tps)
    skip = rep["prefill_skip_fraction"]
    ok = same and ratio >= gate and skip >= skip_gate
    flops_per_tok = 2.0 * model.cfg.active_param_count()
    print(f"# prefix-trace[{arch}] P={page_size}: paged+prefix "
          f"{paged_tps:.1f} admitted tok/s vs slab {slab_tps:.1f} "
          f"({ratio:.2f}x, gate >= {gate:g}x) "
          f"[{'PASS' if ratio >= gate else 'FAIL'}] | prefill skipped "
          f"{int(rep['prefill_tokens_skipped'])} toks ({skip:.2f}, gate >= "
          f"{skip_gate:g}) [{'PASS' if skip >= skip_gate else 'FAIL'}] | "
          f"token-identical [{'PASS' if same else 'FAIL'}] | hit rate "
          f"{rep['prefix_hit_rate']:.2f}, pages "
          f"{rep['pages_in_use']:.1f}/{paged_eng.pool.n_usable_pages} "
          f"({rep['page_occupancy']:.2f} full), pool waits "
          f"{int(rep['pool_waits'])}")
    records = [{
        "arch": arch, "mode": mode, "page_size": ps,
        "n_pages": np_, "mesh_shape": [1, 1], "n_replicas": 1, **prov,
        "admitted_tok_s": tps, "wall_tok_s": r["tok_per_s"],
        "tokens_generated": r["tokens_generated"],
        "decode_steps": r["decode_steps"],
        "tokens_per_dispatch": r["tokens_per_dispatch"],
        # every record reports the prefill economy + pool pressure, the
        # slab side as the zero baseline
        "prefix_hit_rate": r["prefix_hit_rate"],
        "prefill_tokens_skipped": r["prefill_tokens_skipped"],
        "prefill_skip_fraction": r["prefill_skip_fraction"],
        "prefill_flops_saved": flops_per_tok * r["prefill_tokens_skipped"],
        "pages_in_use": r["pages_in_use"],
        "page_occupancy": r["page_occupancy"],
        "pool_waits": r["pool_waits"],
        "paged_vs_slab_admitted": ratio,
    } for mode, ps, np_, tps, r in (
        ("slab", 0, 0, slab_tps, rep_s),
        ("paged-prefix", page_size, n_pages, paged_tps, rep))]
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            json.dump({"arch": arch, "n_slots": n_slots,
                       "page_size": page_size, "n_pages": n_pages,
                       "gate": gate, "skip_gate": skip_gate,
                       "paged_vs_slab_admitted": ratio,
                       "prefill_skip_fraction": skip, **prov,
                       "records": records}, f, indent=2)
        print(f"# wrote {out} ({len(records)} records)")
    print(f"# serve_bench --prefix-trace: {'PASS' if ok else 'FAIL'} — "
          f"paged+prefix >= {gate:g}x admitted tok/s, >= {skip_gate:.0%} "
          "prefill tokens skipped, greedy token-identical")
    return ok


def conversation_turns(n_conversations: int, n_turns: int, utt_range,
                       gen_range, vocab: int, seed: int):
    """Per-conversation turn schedules [(utterance, gen_len), ...]. Only
    the NEW user text per turn is drawn here — each turn's full prompt is
    assembled live from the engine's own prior replies, because a chat's
    turn-t prompt contains the turn-(t-1) output."""
    rng = np.random.default_rng(seed)
    return [[(rng.integers(0, vocab, int(rng.integers(*utt_range))),
              int(rng.integers(*gen_range))) for _ in range(n_turns)]
            for _ in range(n_conversations)]


def run_conversation_trace(arch: str, n_conversations: int, n_turns: int,
                           n_slots: int, seed: int, page_size: int,
                           out: str = "", skip_gate: float = 0.7) -> bool:
    """Multi-turn chats resuming their own history through the paged
    native engine, vs a slab engine fed the same per-turn prompts.

    The chat shape: short user follow-ups, longer assistant replies —
    so by turn t the prompt is dominated by the prior conversation. With
    whole-conversation publishing (prompt + GENERATED tokens land in the
    prefix tree at finish) every full page of the prior exchange is
    served from cache; prompt-only publishing would re-prefill every
    past reply. Gates, all deterministic: >= `skip_gate` of all prompt
    tokens skipped at prefill; ZERO gather/scatter events on the
    page-table-native decode path with `gather_bytes_avoided` exactly
    2*slab_view_bytes per dispatch; greedy token-identity vs the slab."""
    from repro.serve.paging import GATHER_EVENTS
    registry = ModelRegistry()
    model = registry.load(arch)
    # follow-ups much shorter than replies: the regime where reusing the
    # whole conversation (not just its prompts) carries the economics
    utt_range, gen_range = (4, 9), (18, 25)
    convs = conversation_turns(n_conversations, n_turns, utt_range,
                               gen_range, model.cfg.vocab, seed)
    max_len = n_turns * (utt_range[1] + gen_range[1]) + 8
    pp = -(-max_len // page_size)
    # every retired conversation stays resident in the prefix tree until
    # its last turn, plus the live slots' working pages
    n_pages = (n_conversations + n_slots) * pp + 1
    prov = provenance(seed)

    eng = InferenceEngine(model, EngineConfig(
        n_slots=n_slots, max_len=max_len, decode_chunk=4,
        page_size=page_size, n_pages=n_pages))
    GATHER_EVENTS.clear()
    histories = [np.zeros(0, np.int32) for _ in convs]
    prompts, paged_reqs = [], []
    t0 = time.time()
    for t in range(n_turns):
        round_reqs = []
        for c, turns in enumerate(convs):
            utt, gen = turns[t]
            prompt = np.concatenate([histories[c], utt]).astype(np.int32)
            prompts.append((prompt, gen))
            round_reqs.append((c, eng.submit(prompt, gen)))
        eng.run()                     # turn t finishes fleet-wide before
        for c, r in round_reqs:       # turn t+1 resumes the conversation
            histories[c] = np.concatenate(
                [histories[c], convs[c][t][0],
                 np.asarray(r.generated, np.int32)]).astype(np.int32)
            paged_reqs.append(r)
    wall = max(time.time() - t0, 1e-9)
    rep = eng.metrics.report()

    # slab oracle: the SAME per-turn prompts (histories included), no
    # paging — greedy outputs must match token for token
    slab_eng = InferenceEngine(model, EngineConfig(n_slots=n_slots,
                                                   max_len=max_len,
                                                   decode_chunk=4))
    slab_reqs = [slab_eng.submit(p, g) for p, g in prompts]
    slab_eng.run()
    same = all(pr.generated == sr.generated
               for pr, sr in zip(paged_reqs, slab_reqs))
    rep_s = slab_eng.metrics.report()

    skip = rep["prefill_skip_fraction"]
    gather_events = len(GATHER_EVENTS)
    avoided = rep["gather_bytes_avoided"]
    avoided_exact = avoided == eng.backend.gather_bytes_per_dispatch() \
        * rep["decode_steps"]
    native_ok = gather_events == 0 and avoided > 0 and avoided_exact
    ok = same and skip >= skip_gate and native_ok
    print(f"# conversation-trace[{arch}] {n_conversations} chats x "
          f"{n_turns} turns, P={page_size}: prefill skipped "
          f"{int(rep['prefill_tokens_skipped'])} of "
          f"{eng.metrics.prefill_tokens_skipped + eng.metrics.prefill_tokens_computed} "
          f"prompt toks ({skip:.2f}, gate >= {skip_gate:g}) "
          f"[{'PASS' if skip >= skip_gate else 'FAIL'}] | conversation "
          f"hits {int(rep['conversation_prefix_hits'])}, tokens reused "
          f"{int(rep['conversation_tokens_reused'])} | gather events "
          f"{gather_events}, avoided {avoided / 1e6:.2f} MB over "
          f"{int(rep['decode_steps'])} dispatches "
          f"[{'PASS' if native_ok else 'FAIL'} == 0 events, exact ledger]"
          f" | token-identical [{'PASS' if same else 'FAIL'}] | "
          f"{rep['tokens_generated'] / wall:.1f} tok/s wall, pages "
          f"{rep['pages_in_use']:.1f}/{eng.pool.n_usable_pages}, pool "
          f"waits {int(rep['pool_waits'])}")
    common = {"arch": arch, "decode_chunk": 4, "mesh_shape": [1, 1],
              "n_replicas": 1, "n_conversations": n_conversations,
              "n_turns": n_turns, **prov}
    records = [
        {**common, "mode": "conversation-native", "page_size": page_size,
         "n_pages": n_pages,
         "tokens_generated": rep["tokens_generated"],
         "decode_steps": rep["decode_steps"],
         "tokens_per_dispatch": rep["tokens_per_dispatch"],
         "wall_tok_s": rep["tok_per_s"],
         "prefix_hit_rate": rep["prefix_hit_rate"],
         "prefill_tokens_skipped": rep["prefill_tokens_skipped"],
         "prefill_skip_fraction": skip,
         "conversation_prefix_hits": rep["conversation_prefix_hits"],
         "conversation_tokens_reused": rep["conversation_tokens_reused"],
         "gather_bytes_avoided": avoided,
         "decode_gather_events": float(gather_events),
         "pages_in_use": rep["pages_in_use"],
         "page_occupancy": rep["page_occupancy"],
         "pool_waits": rep["pool_waits"]},
        {**common, "mode": "slab", "page_size": 0, "n_pages": 0,
         "tokens_generated": rep_s["tokens_generated"],
         "decode_steps": rep_s["decode_steps"],
         "tokens_per_dispatch": rep_s["tokens_per_dispatch"],
         "wall_tok_s": rep_s["tok_per_s"],
         "prefill_tokens_skipped": rep_s["prefill_tokens_skipped"],
         "prefill_skip_fraction": rep_s["prefill_skip_fraction"],
         "gather_bytes_avoided": rep_s["gather_bytes_avoided"]}]
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            json.dump({"arch": arch, "n_slots": n_slots,
                       "n_conversations": n_conversations,
                       "n_turns": n_turns, "page_size": page_size,
                       "n_pages": n_pages, "skip_gate": skip_gate,
                       "prefill_skip_fraction": skip,
                       "gather_bytes_avoided": avoided, **prov,
                       "records": records}, f, indent=2)
        print(f"# wrote {out} ({len(records)} records)")
    print(f"# serve_bench --conversation-trace: {'PASS' if ok else 'FAIL'}"
          f" — >= {skip_gate:.0%} prompt tokens skipped across multi-turn "
          "chats, zero decode gather/scatter, greedy token-identical")
    return ok


def run_overload_trace(arch: str, n_requests: int, n_slots: int, seed: int,
                       out: str = "", gate: float = 1.2,
                       deadline_steps: int = 0) -> bool:
    """Resilient vs non-degrading engine under 2x saturating Poisson load.

    One trace whose token arrival rate is TWICE the chunk-1 service
    capacity (n_slots tokens/step) replayed through (a) a BASELINE engine
    that admits everything and serves it however late, and (b) a RESILIENT
    engine with per-request deadlines (admission-time doom shedding +
    in-flight expiry), the QoS tier ladder, and bounded pool-wait retries.

    The gated metric is GOODPUT per engine step — generated tokens of
    completions that finished BY their deadline, per step on the
    deterministic engine-step clock. Under 2x load the baseline's queue
    grows without bound, so late admissions complete far past deadline:
    their tokens count zero while they still consumed slots. The resilient
    engine sheds exactly that doomed work at admission, so surviving
    requests run sooner and finish inside their deadline — the gate is
    resilient >= `gate`x baseline goodput/step, plus ZERO deadline-missed
    completions served on the resilient side (at decode_chunk=1 the
    per-step doom check is exact: any request not shed finishes in time).
    Wall tok/s is reported ungated (tier demotion's wall benefit needs
    the packed Pallas kernels, which engage off the ref CPU backend)."""
    from repro.serve import QoSConfig
    registry = ModelRegistry()
    tiers = (DraftSpec.from_args(8, 0.5, 0), DraftSpec.from_args(8, 0.75, 0))
    model = registry.load(arch, tier_specs=tiers)
    prompt_range, gen_range = (4, 12), (8, 17)
    mean_gen = (gen_range[0] + gen_range[1] - 1) / 2.0
    # 2x saturating: mean token arrival rate = 2 * the n_slots tok/step
    # that a full chunk-1 slab can serve
    trace = poisson_trace(n_requests, mean_gen / (2.0 * n_slots),
                          prompt_range, gen_range, model.cfg.vocab, seed)
    max_len = model.cfg.n_img_tokens + prompt_range[1] + gen_range[1] + 8
    # tight enough that the baseline's growing backlog dooms the later
    # arrivals (queue wait alone exceeds it), loose enough that an
    # immediately-admitted request finishes comfortably inside it
    D = deadline_steps or int(2 * mean_gen)
    prov = provenance(seed)

    def run_side(resilient: bool):
        cfg = EngineConfig(
            n_slots=n_slots, max_len=max_len, decode_chunk=1,
            qos=QoSConfig(demote_depth=4, promote_depth=1, hysteresis=2)
            if resilient else None,
            pool_wait_retries=3 if resilient else None)
        eng = InferenceEngine(model, cfg)
        t0 = time.time()
        for arrival, prompt, gen in trace:
            eng.submit(prompt, gen, arrival_step=arrival,
                       deadline_steps=D if resilient else None)
        eng.run()
        dt = max(time.time() - t0, 1e-9)
        met_tokens, served, late = 0, 0, 0
        for r in eng.requests.values():
            if r.state != "done":
                continue
            fin = eng.metrics.records[r.id].finish_step
            if fin <= r.arrival_step + D:
                met_tokens += len(r.generated)
                served += 1
            else:
                late += 1
        rep = eng.metrics.report()
        return {"engine": eng, "report": rep, "wall_s": dt,
                "goodput_tokens": met_tokens,
                "goodput_tok_per_step": met_tokens / max(1, eng.step_count),
                "served_in_deadline": served,
                "deadline_missed_completions": late,
                "steps": eng.step_count}

    base = run_side(False)
    res = run_side(True)
    ratio = res["goodput_tok_per_step"] / max(1e-9,
                                              base["goodput_tok_per_step"])
    zero_late = res["deadline_missed_completions"] == 0
    ok = ratio >= gate and zero_late
    rep_r, rep_b = res["report"], base["report"]
    print(f"# overload-trace[{arch}] 2x load, D={D} steps: resilient "
          f"{res['goodput_tok_per_step']:.2f} goodput tok/step vs baseline "
          f"{base['goodput_tok_per_step']:.2f} ({ratio:.2f}x, gate >= "
          f"{gate:g}x) [{'PASS' if ratio >= gate else 'FAIL'}] | late "
          f"completions served {res['deadline_missed_completions']} "
          f"(baseline {base['deadline_missed_completions']}) "
          f"[{'PASS' if zero_late else 'FAIL'} == 0] | shed "
          f"{int(rep_r['shed'])} (deadline {int(rep_r['deadline_missed'])}, "
          f"pool {int(rep_r['shed_pool_pressure'])}), demotions "
          f"{int(rep_r['tier_demotions'])} | wall "
          f"{rep_r['tokens_generated'] / res['wall_s']:.1f} vs "
          f"{rep_b['tokens_generated'] / base['wall_s']:.1f} tok/s "
          "(reported not gated)")
    records = [{
        "arch": arch, "mode": mode, "decode_chunk": 1,
        "deadline_steps": D, "mesh_shape": [1, 1], "n_replicas": 1, **prov,
        "tokens_generated": r["tokens_generated"],
        "decode_steps": r["decode_steps"],
        "goodput_tokens": side["goodput_tokens"],
        "goodput_tok_per_step": side["goodput_tok_per_step"],
        "served_in_deadline": side["served_in_deadline"],
        "deadline_missed_completions": side["deadline_missed_completions"],
        "shed": r["shed"], "deadline_missed": r["deadline_missed"],
        "shed_pool_pressure": r["shed_pool_pressure"],
        "tier_demotions": r["tier_demotions"],
        "tier_promotions": r["tier_promotions"],
        "wall_tok_s": r["tokens_generated"] / side["wall_s"],
        "resilient_vs_baseline_goodput": ratio,
    } for mode, side, r in (("baseline", base, rep_b),
                            ("resilient", res, rep_r))]
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            json.dump({"arch": arch, "n_slots": n_slots,
                       "deadline_steps": D, "gate": gate,
                       "resilient_vs_baseline_goodput": ratio, **prov,
                       "records": records}, f, indent=2)
        print(f"# wrote {out} ({len(records)} records)")
    print(f"# serve_bench --overload-trace: {'PASS' if ok else 'FAIL'} — "
          f"resilient >= {gate:g}x goodput tok/step under 2x load, zero "
          "deadline-missed completions served")
    return ok


def run_fleet_trace(arch: str, n_requests: int, n_slots: int, seed: int,
                    n_processes: int = 2, out: str = "", gate: float = 1.5,
                    decode_chunk: int = 4) -> bool:
    """Fleet mode (PR 10): one dense Poisson trace through a single
    in-process engine and through an N-process subprocess fleet
    (launch.fleet workers + serve.FleetRouter over the control plane).

    Gates:
      * TOKEN IDENTITY: every request's fleet output equals the single
        engine's, greedy, across the process boundary — the wire protocol
        and the per-process DistributedBackend meshes change placement,
        never tokens;
      * THROUGHPUT: tokens per FLEET step (completed tokens over the
        SLOWEST process's engine steps — processes decode concurrently,
        so the max is the wall-clock analog on the deterministic step
        clock) >= `gate`x the single engine's tokens per step.

    Wall tok/s is reported ungated (subprocess pacing + control-plane
    sleeps dominate on CPU; the step-clock ratio is the load-bearing
    number)."""
    from repro.launch.fleet import spawn_fleet

    registry = ModelRegistry()
    model = registry.load(arch)
    prompt_range, gen_range = (4, 12), (4, 10)
    # dense means QUEUE-limited, not arrival-limited: arrivals must pile
    # onto the single engine far faster than its slots drain them, or
    # both sides just ride the arrival clock and the ratio pins at 1.0
    dense = poisson_trace(max(n_requests, 12 * n_processes), 0.25,
                          prompt_range, gen_range, model.cfg.vocab, seed)
    max_len = model.cfg.n_img_tokens + prompt_range[1] + gen_range[1] + 8
    prov = provenance(seed)

    eng = InferenceEngine(model, EngineConfig(
        n_slots=n_slots, max_len=max_len, decode_chunk=decode_chunk))
    ref = [eng.submit(p, g, arrival_step=a) for a, p, g in dense]
    eng.run()
    single = eng.metrics.report()
    ref_toks = [list(r.generated) for r in ref]

    t0 = time.time()
    with spawn_fleet(n_processes, arch=arch, n_slots=n_slots,
                     max_len=max_len, decode_chunk=decode_chunk) as fleet:
        reqs = [fleet.router.submit(p, g, arrival_step=a)
                for a, p, g in dense]
        fleet.drive()
        fleet.router.stop()
        routed = fleet.router.report()
    wall = max(time.time() - t0, 1e-9)

    identical = [list(r.tokens) for r in reqs] == ref_toks
    ratio = routed["tokens_per_fleet_step"] / \
        max(1e-9, single["tokens_per_step"])
    win_ratio = ratio >= gate
    ok = identical and win_ratio
    print(f"# fleet[{arch}] {n_processes} processes: "
          f"{routed['tokens_per_fleet_step']:.2f} tok/fleet-step vs single "
          f"{single['tokens_per_step']:.2f} tok/step ({ratio:.2f}x, gate "
          f">= {gate:g}x) [{'PASS' if win_ratio else 'FAIL'}] | "
          f"token-identical [{'PASS' if identical else 'FAIL'}] | "
          f"failovers {int(routed['fleet_failovers'])}, dead "
          f"{int(routed['processes_dead'])}, overflowed "
          f"{int(routed['fleet_overflowed'])} | wall "
          f"{routed['fleet_tokens'] / wall:.1f} tok/s (reported not gated)")
    records = [{
        "arch": arch, "spec": "dense", "mode": "fleet",
        "decode_chunk": decode_chunk, "mesh_shape": [1, 1],
        "n_replicas": 1, "n_processes": n_processes, **prov,
        "fleet_tokens": routed["fleet_tokens"],
        "fleet_steps": routed["fleet_steps"],
        "fleet_requests_completed": routed["fleet_requests_completed"],
        "tokens_per_fleet_step": routed["tokens_per_fleet_step"],
        "fleet_failovers": routed["fleet_failovers"],
        "fleet_overflowed": routed["fleet_overflowed"],
        "resurrections_ignored": routed["resurrections_ignored"],
        "single_tokens_per_step": single["tokens_per_step"],
        "fleet_vs_single": ratio,
        "token_identical": float(identical),
        "wall_tok_s": routed["fleet_tokens"] / wall,
    }]
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            json.dump({"arch": arch, "n_slots": n_slots,
                       "decode_chunk": decode_chunk,
                       "n_processes": n_processes, "gate": gate,
                       "fleet_vs_single": ratio, **prov,
                       "records": records}, f, indent=2)
        print(f"# wrote {out} ({len(records)} records)")
    print(f"# serve_bench --fleet: {'PASS' if ok else 'FAIL'} — fleet >= "
          f"{gate:g}x single tok/step at {n_processes} processes, "
          "token-identical")
    return ok


def run_ledger_trace(arch: str, n_requests: int, n_slots: int, seed: int,
                     out: str = "", k_block: int = 8,
                     quality_every: int = 2) -> bool:
    """Ineffectual-work ledger mode (PR 9): the SAME Poisson trace replayed
    twice through a ledger-instrumented device-loop engine.

    Gates, all deterministic on the step clock:
      * measured activation zeros > 0 — the arch default (nemotron's
        squared-ReLU MLP) makes real zeros, and the ledger must see them;
      * BIT-DETERMINISM: every counter — including the full per-layer
        per-group zero histogram, collapsed to `hist_checksum` — is
        identical across the two runs;
      * NO EXTRA HOST SYNCS: host_syncs_decode == decode dispatches (the
        ledger drains inside the existing token device_get);
      * quality probes: tier-0 shadow prefill of a single-tier engine must
        agree with itself exactly (top1 rate 1.0, logit MAD 0.0).

    The JSON record carries the gated counters plus an UNGATED replica
    roofline (analysis.roofline over the run's wall time) — wall time is
    machine-dependent, the counters are not.
    """
    from repro.analysis import roofline as RL
    from repro.serve import LedgerConfig

    registry = ModelRegistry()
    model = registry.load(arch)
    prompt_range, gen_range = (6, 14), (10, 18)
    trace = poisson_trace(n_requests, 1.5, prompt_range, gen_range,
                          model.cfg.vocab, seed)
    max_len = prompt_range[1] + gen_range[1] + 8
    led_cfg = LedgerConfig(threshold=0.0, group=8, k_block=k_block,
                           quality_every=quality_every)
    prov = provenance(seed)

    def one_run():
        eng = InferenceEngine(model, EngineConfig(
            n_slots=n_slots, max_len=max_len, decode_chunk=4,
            ledger=led_cfg, trace=TraceConfig()))
        for arrival, prompt, gen in trace:
            eng.submit(prompt, gen, arrival_step=arrival)
        t0 = time.time()
        eng.run()
        wall = max(time.time() - t0, 1e-9)
        _warn_trace_dropped(eng.trace)
        return eng, eng.metrics.report(), eng.ledger.summary(), wall

    eng1, rep1, sum1, wall1 = one_run()
    _, rep2, sum2, _ = one_run()

    gated = ("act_probe_elems", "act_zeros", "act_near_zeros",
             "act_kblocks", "act_dead_kblocks", "act_hist_checksum")
    deterministic = all(sum1[k] == sum2[k] for k in gated) \
        and sum1["hist"] == sum2["hist"]
    zeros_ok = sum1["act_zeros"] > 0
    syncs_ok = rep1["host_syncs_decode"] == rep1["decode_steps"] \
        and rep1["ledger_dispatches"] == rep1["decode_steps"]
    quality_ok = rep1["quality_probes"] > 0 \
        and rep1["quality_top1_rate"] == 1.0 \
        and rep1["quality_logit_mad"] == 0.0
    ok = deterministic and zeros_ok and syncs_ok and quality_ok

    # ungated roofline attribution of the replica's drained ledger totals
    # over its wall time (only against the device's published peaks — a
    # CPU run has none)
    kind = jax.devices()[0].device_kind
    if kind in RL.PEAKS:
        replica = RL.replica_roofline(sum1, wall1, peaks=RL.peaks_for(kind))
        bound = (f"skip bound {replica['skip_speedup_bound']:.2f}x "
                 f"({replica['dense']['bound']}-bound)")
    else:
        replica = f"not measured: no published peaks for {kind!r}"
        bound = "skip bound not measured"

    zero_frac = sum1["act_zeros"] / max(sum1["act_probe_elems"], 1.0)
    print(f"# ledger-trace[{arch}] kb={k_block}: "
          f"{int(sum1['act_zeros'])} zeros / "
          f"{int(sum1['act_probe_elems'])} probed elems "
          f"({zero_frac:.3f}) [{'PASS' if zeros_ok else 'FAIL'} > 0] | "
          f"hist checksum {sum1['act_hist_checksum']:.0f} "
          f"[{'PASS' if deterministic else 'FAIL'} bit-identical x2] | "
          f"syncs {int(rep1['host_syncs_decode'])} == dispatches "
          f"{int(rep1['decode_steps'])} "
          f"[{'PASS' if syncs_ok else 'FAIL'}] | quality "
          f"{int(rep1['quality_probes'])} probes top1 "
          f"{rep1['quality_top1_rate']:.2f} mad "
          f"{rep1['quality_logit_mad']:.3g} "
          f"[{'PASS' if quality_ok else 'FAIL'}] | eff flops "
          f"{rep1['effective_flop_fraction']:.3f}, dead k-blocks "
          f"{int(sum1['act_dead_kblocks'])}, {bound}")
    records = [{
        "arch": arch, "mode": "ledger", "n_requests": n_requests,
        "n_slots": n_slots, "decode_chunk": 4, "k_block": k_block,
        "group": led_cfg.group, "quality_every": quality_every,
        "mesh_shape": [1, 1], "n_replicas": 1, **prov,
        "tokens_generated": rep1["tokens_generated"],
        "decode_steps": rep1["decode_steps"],
        "ledger_dispatches": rep1["ledger_dispatches"],
        "host_syncs_decode": rep1["host_syncs_decode"],
        "act_probe_elems": sum1["act_probe_elems"],
        "act_zeros": sum1["act_zeros"],
        "act_near_zeros": sum1["act_near_zeros"],
        "act_kblocks": sum1["act_kblocks"],
        "act_dead_kblocks": sum1["act_dead_kblocks"],
        "act_hist_checksum": sum1["act_hist_checksum"],
        "act_zero_fraction": zero_frac,
        "effective_flop_fraction": rep1["effective_flop_fraction"],
        "quality_probes": rep1["quality_probes"],
        "quality_top1_rate": rep1["quality_top1_rate"],
        "quality_logit_mad": rep1["quality_logit_mad"],
        "trace_dropped": rep1["trace_dropped"],
    }]
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            json.dump({"arch": arch, "n_slots": n_slots,
                       "n_requests": n_requests, "k_block": k_block,
                       "quality_every": quality_every, **prov,
                       "deterministic": deterministic,
                       "roofline": {"replica": replica},
                       "zero_fraction_by_layer":
                           sum1["zero_fraction_by_layer"],
                       "records": records}, f, indent=2)
        print(f"# wrote {out} ({len(records)} records)")
    print(f"# serve_bench --ledger-trace: {'PASS' if ok else 'FAIL'} — "
          "activation zeros measured, counters bit-deterministic across "
          "two runs, ledger drains inside the existing dispatch sync, "
          "tier-0 quality shadow exact")
    return ok


def run_speculative(arch: str, n_requests: int, n_slots: int, seed: int,
                    speculate: int, draft: DraftSpec, out: str = "",
                    gate: float = 1.2) -> bool:
    """Plain device loop vs speculative decode (speculate=K) with a
    self-draft, one trace, warm-measured.

    The PLAIN side runs decode_chunk=1: `speculate` REPLACES the chunk knob
    (the engine refuses both), so the apples-to-apples question is "tokens
    committed per decode dispatch / host sync" against the un-chunked
    device loop. That ratio is the GATE (>= `gate`x at K=4 in CI) because
    it is deterministic — tokens and dispatches are integers, immune to CI
    timing noise — and it is the economy speculation buys on every
    substrate. Wall tokens/sec for both engines is reported alongside,
    ungated: on the CPU *reference* backend the draft re-pack executes at
    full-precision cost (per-step dequantization; the packed Pallas
    kernels that realize the draft's FLOP discount engage off-ref), so
    wall parity there is substrate-limited, not a property of the design.
    Greedy token-identity between the two engines is also gated — the
    speedup must not change a single token."""
    registry = ModelRegistry()
    model = registry.load(arch, draft_spec=draft)
    prompt_range, gen_range = (4, 16), (12, 24)
    trace = poisson_trace(n_requests, 1.5, prompt_range, gen_range,
                          model.cfg.vocab, seed)
    max_len = model.cfg.n_img_tokens + prompt_range[1] + gen_range[1] + 8
    prov = provenance(seed)

    plain_tps, plain_eng = timed_throughput(model, trace, n_slots, max_len,
                                            decode_chunk=1)
    spec_tps, spec_eng = timed_throughput(model, trace, n_slots, max_len,
                                          speculate=speculate)
    same = all(
        plain_eng.requests[i].generated == spec_eng.requests[i].generated
        for i in plain_eng.requests)
    rep = spec_eng.metrics.report()
    rep_p = plain_eng.metrics.report()
    ratio = rep["tokens_per_dispatch"] / max(1e-9,
                                             rep_p["tokens_per_dispatch"])
    wall_ratio = spec_tps / max(1e-9, plain_tps)
    ok = same and ratio >= gate
    print(f"# speculative[{draft.tag}] K={speculate}: "
          f"{rep['tokens_per_dispatch']:.2f} tok/dispatch vs plain loop "
          f"{rep_p['tokens_per_dispatch']:.2f} ({ratio:.2f}x, gate >= "
          f"{gate:.2f}x) [{'PASS' if ratio >= gate else 'FAIL'}] | "
          f"token-identical [{'PASS' if same else 'FAIL'}] | accept "
          f"{rep['acceptance_rate']:.3f}, rolled back "
          f"{int(rep['draft_rolled_back'])}, draft/verify flops "
          f"{rep['draft_verify_flop_ratio']:.2f} | wall {spec_tps:.1f} vs "
          f"{plain_tps:.1f} tok/s ({wall_ratio:.2f}x, reported not gated: "
          f"ref backend runs the draft at full-precision cost)")
    records = [{
        "arch": arch, "mode": mode, "speculate": speculate,
        "draft_spec": draft.tag if mode == "speculative" else None,
        "mesh_shape": [1, 1], "n_replicas": 1, **prov,
        "wall_tok_s": tps,
        "tokens_generated": r["tokens_generated"],
        "decode_steps": r["decode_steps"],
        "tokens_per_dispatch": r["tokens_per_dispatch"],
        "acceptance_rate": r["acceptance_rate"],
        "draft_rolled_back": r["draft_rolled_back"],
        "draft_verify_flop_ratio": r["draft_verify_flop_ratio"],
        "spec_vs_plain_dispatch": ratio,
        "spec_vs_plain_wall": wall_ratio,
    } for mode, tps, r in (("device", plain_tps, rep_p),
                           ("speculative", spec_tps, rep))]
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            json.dump({"arch": arch, "n_slots": n_slots,
                       "speculate": speculate, "draft_spec": draft.tag,
                       "gate": gate, "spec_vs_plain_dispatch": ratio,
                       "spec_vs_plain_wall": wall_ratio, **prov,
                       "records": records}, f, indent=2)
        print(f"# wrote {out} ({len(records)} records)")
    print(f"# serve_bench --speculate: {'PASS' if ok else 'FAIL'} — "
          f"speculative >= {gate:g}x tokens/dispatch vs plain device loop, "
          "greedy token-identical")
    return ok


def run(arch: str = "h2o-danube-1.8b", n_requests: int = 16,
        n_slots: int = 4, mean_interarrival: float = 2.0,
        prompt_range=(4, 24), gen_range=(8, 24), seed: int = 0,
        smoke: bool = False, decode_chunk: int = 4,
        n_replicas: int = 1, mesh_shape=None,
        out: str = "", trace_dir: str = "") -> bool:
    registry = ModelRegistry()
    csv = CSV(["spec", "mode", "toks", "dispatches", "tok_per_step",
               "occupancy", "tok_per_s_wall", "syncs_per_tok",
               "lat_p50_steps", "lat_p99_steps", "packed_MB", "compression",
               "apply_packed_hits"])
    specs = [(n, s) for n, s in SPECS if not smoke or n in SMOKE_SPECS]
    ok = True
    records = []
    mesh_list = list(mesh_shape) if mesh_shape else [1, 1]
    prov = provenance(seed)

    def record(spec_name, mode_name, rep, k, **extra):
        records.append({
            "arch": arch, "spec": spec_name, "mode": mode_name,
            "decode_chunk": k, **prov,
            # per-record placement: only sharded/router modes ran on the
            # mesh; host/device/static are the local-placement baselines
            "mesh_shape": mesh_list if mode_name in ("sharded", "router")
            else [1, 1],
            "n_replicas": extra.pop("n_replicas", 1),
            "tokens_per_step": rep.get("tokens_per_step", 0.0),
            # deterministic step-clock integers: QoR gates these EXACTLY
            # (no EOS in the synthetic traces, so every request generates
            # its full budget on any platform)
            "tokens_generated": rep["tokens_generated"],
            "decode_steps": rep["decode_steps"],
            "wall_tok_s": rep["tok_per_s"],
            "host_syncs_per_token": rep["host_syncs_per_token"],
            "host_syncs_per_dispatch": rep["host_syncs_decode"]
            / max(1.0, rep["decode_steps"]),
            "mean_occupancy": rep["mean_occupancy"],
            "latency_steps_p50": rep["latency_steps_p50"],
            **extra})

    for spec_name, spec in specs:
        model = registry.load(arch, spec, seed=seed)
        cfg = model.cfg
        trace = poisson_trace(n_requests, mean_interarrival, prompt_range,
                              gen_range, cfg.vocab, seed)
        max_len = cfg.n_img_tokens + prompt_range[1] + gen_range[1] + 8
        modes = [
            ("host", dict(scheduler=None, device_loop=False, decode_chunk=1)),
            ("device", dict(scheduler=None, device_loop=True,
                            decode_chunk=decode_chunk)),
            ("static", dict(scheduler=StaticScheduler(), device_loop=True,
                            decode_chunk=decode_chunk)),
        ]
        if mesh_shape is not None:
            from repro.serve import ShardedBackend
            modes.append(("sharded", dict(
                scheduler=None, device_loop=True, decode_chunk=decode_chunk,
                backend=lambda: ShardedBackend(mesh_shape=mesh_shape))))
        results = {}
        for mode_name, kw in modes:
            bk = kw.get("backend")
            # --trace-dir: each (spec, mode) run records the full event
            # trace; tracing is otherwise OFF (the recorded numbers ARE
            # the untraced numbers the QoR goldens gate)
            tcfg = TraceConfig(
                out=os.path.join(trace_dir,
                                 f"{spec_name}_{mode_name}.trace.jsonl"),
                chrome=os.path.join(trace_dir,
                                    f"{spec_name}_{mode_name}.chrome.json")) \
                if trace_dir else None
            with PackedRouteCounter() as counter:
                rep = run_one(model, trace, n_slots, max_len, kw["scheduler"],
                              device_loop=kw["device_loop"],
                              decode_chunk=kw["decode_chunk"],
                              backend=bk() if bk else None,
                              trace_cfg=tcfg,
                              telemetry_jsonl=os.path.join(
                                  trace_dir, "telemetry.jsonl")
                              if trace_dir else "")
            results[mode_name] = rep
            csv.row(spec_name, mode_name, int(rep["tokens_generated"]),
                    int(rep["decode_steps"]), rep["tokens_per_step"],
                    rep["mean_occupancy"], rep["tok_per_s"],
                    rep["host_syncs_per_token"],
                    rep["latency_steps_p50"], rep["latency_steps_p99"],
                    model.packed_bytes / 1e6, model.compression, counter.hits)
            record(spec_name, mode_name, rep, kw["decode_chunk"])
            if counter.hits == 0:
                print(f"# FAIL {spec_name}/{mode_name}: decode did not "
                      "route through apply_packed")
                ok = False
        if mesh_shape is not None:
            # placement must not change the traffic the trace produces:
            # the sharded engine emits exactly as many tokens per dispatch
            # as the local device loop on the same trace (greedy outputs
            # are token-identical; tested leaf-for-leaf in test_serve_*).
            dev, shd = results["device"], results["sharded"]
            win_mesh = (shd["tokens_generated"] == dev["tokens_generated"]
                        and shd["decode_steps"] == dev["decode_steps"])
            ok = ok and win_mesh
            print(f"# {spec_name}: sharded mesh {mesh_list} "
                  f"{shd['tokens_per_step']:.2f} tok/step over "
                  f"{int(shd['decode_steps'])} dispatches "
                  f"[{'PASS' if win_mesh else 'FAIL'}]")
        host, dev, stat = (results[m] for m in ("host", "device", "static"))
        win_sched = dev["tokens_per_step"] >= stat["tokens_per_step"]
        # structural invariant (occupancy-independent): exactly ONE decode
        # sync per dispatch, i.e. 1/K per micro-step (the host loop pays 3
        # per micro-step), and fewer syncs per decoded token than the host
        # loop on the same trace. The per-token <= 1/K bound additionally
        # requires the trace to sustain >= K decoded tokens per dispatch
        # (it does at any reasonable occupancy; a lone short request is
        # tail-dominated), so it is reported, not gated.
        win_sync = (dev["host_syncs_decode"] == dev["decode_steps"]
                    and dev["host_syncs_per_token"]
                    < host["host_syncs_per_token"])
        win_tps = dev["tokens_per_step"] >= host["tokens_per_step"]
        ok = ok and win_sched and win_sync and win_tps
        bound = 1.0 / decode_chunk
        amortized = dev["host_syncs_per_token"] <= bound + 1e-9
        print(f"# {spec_name}: device {dev['tokens_per_step']:.2f} tok/step "
              f"(host {host['tokens_per_step']:.2f}, static "
              f"{stat['tokens_per_step']:.2f}) "
              f"[{'PASS' if win_sched and win_tps else 'FAIL'}]; "
              f"1 sync/dispatch = {1.0 / decode_chunk:.3f}/micro-step "
              f"[{'PASS' if win_sync else 'FAIL'}]; syncs/tok "
              f"{host['host_syncs_per_token']:.2f} -> "
              f"{dev['host_syncs_per_token']:.3f} "
              f"({'<=' if amortized else '>'} 1/K = {bound:.3f})")
        if spec_name != "dense":
            # the decode GEMMs of a packed sparse/quant spec must compile
            # through the Pallas skinny-m path at slab width m = n_slots
            skinny = skinny_decode_trace(model, n_slots, max_len,
                                         decode_chunk)
            # the skinny trace lowers locally (interpret backend), never on
            # the mesh — same placement rule as record()
            records.append({"arch": arch, "spec": spec_name,
                            "mode": "skinny_trace", "mesh_shape": [1, 1],
                            "n_replicas": 1, **prov, **skinny})
            win_skinny = (skinny["skinny_m_dispatches"] > 0
                          and skinny["apply_packed_hits"] > 0)
            ok = ok and win_skinny
            print(f"# {spec_name}: decode compiles "
                  f"{skinny['skinny_m_dispatches']} skinny-m Pallas GEMMs "
                  f"({', '.join(skinny['skinny_kernels'])}) "
                  f"[{'PASS' if win_skinny else 'FAIL'}]")
    if n_replicas > 1:
        # router comparison: ONE dense trace (arrivals fast enough that a
        # single replica saturates) against a single engine and against the
        # router fleet. tokens/router-step vs tokens/step is the apples-to-
        # apples clock: one router step = one dispatch round.
        model = registry.load(arch, specs[0][1], seed=seed)
        dense = poisson_trace(max(n_requests, 12 * n_replicas), 0.75,
                              prompt_range, gen_range, model.cfg.vocab, seed)
        max_len = model.cfg.n_img_tokens + prompt_range[1] + gen_range[1] + 8
        single, routed = run_router(model, dense, n_slots, max_len,
                                    n_replicas, decode_chunk,
                                    mesh_shape=mesh_shape)
        ratio = routed["tokens_per_router_step"] / \
            max(1e-9, single["tokens_per_step"])
        win_router = ratio >= 1.5
        ok = ok and win_router
        print(f"# router: {n_replicas} replicas "
              f"{routed['tokens_per_router_step']:.2f} tok/router-step vs "
              f"single {single['tokens_per_step']:.2f} tok/step "
              f"({ratio:.2f}x, spills {int(routed['spills'])}, "
              f"rebalanced {int(routed['rebalanced'])}) "
              f"[{'PASS' if win_router else 'FAIL'} >= 1.5x]")
        record(specs[0][0], "router", routed, decode_chunk,
               n_replicas=n_replicas,
               tokens_per_router_step=routed["tokens_per_router_step"],
               router_vs_single=ratio, spills=routed["spills"],
               rebalanced=routed["rebalanced"])
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            json.dump({"arch": arch, "n_slots": n_slots,
                       "decode_chunk": decode_chunk, "smoke": smoke,
                       "mesh_shape": mesh_list, "n_replicas": n_replicas,
                       **prov, "records": records}, f, indent=2)
        print(f"# wrote {out} ({len(records)} records)")
    print(f"# serve_bench: {'PASS' if ok else 'FAIL'} — device loop >= host "
          "loop >= static, 1 decode sync per K-step dispatch, packed + "
          "skinny-m decode"
          + (", sharded == device traffic" if mesh_shape else "")
          + (", router >= 1.5x single" if n_replicas > 1 else ""))
    return ok


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: dense + sparse0.5-w8, small trace, <60s")
    ap.add_argument("--arch", default=None,
                    help="default: h2o-danube-1.8b (speculative mode: "
                         "nemotron-4-340b — full attention, no circular "
                         "window cache)")
    ap.add_argument("--requests", type=int, default=0)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--decode-chunk", type=int, default=4,
                    help="K micro-steps per device-loop dispatch")
    ap.add_argument("--mesh", default="",
                    help="'data,model': add a ShardedBackend mode on a local "
                         "mesh of this shape (force CPU devices via "
                         "XLA_FLAGS=--xla_force_host_platform_device_count)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="router comparison: N engine replicas vs a single "
                         "engine on one dense trace (gate: >= 1.5x)")
    ap.add_argument("--speculate", type=int, default=0,
                    help="speculative mode: plain device loop (chunk=1) vs "
                         "self-draft speculation (speculate=K), gated >= "
                         "1.2x tokens/DISPATCH + greedy token-identity; "
                         "wall tok/s reported ungated; skips regular modes")
    ap.add_argument("--prefix-trace", action="store_true",
                    help="prefix-reuse mode: slab vs paged+prefix-cached "
                         "engine on a Zipf shared-system-prompt trace, "
                         "gated >= 1.3x admitted tok/s + >= 50% prefill "
                         "tokens skipped + token-identity; skips regular "
                         "modes")
    ap.add_argument("--page-size", type=int, default=8,
                    help="KV page size for --prefix-trace / "
                         "--conversation-trace")
    ap.add_argument("--conversation-trace", action="store_true",
                    help="multi-turn chat mode: each turn's prompt is the "
                         "whole prior conversation (engine replies "
                         "included) + a follow-up, through the page-table-"
                         "native paged engine; gated >= 70% prompt tokens "
                         "skipped, zero decode gather/scatter events, "
                         "greedy token-identity vs the slab; skips "
                         "regular modes")
    ap.add_argument("--turns", type=int, default=4,
                    help="turns per conversation for --conversation-trace")
    ap.add_argument("--overload-trace", action="store_true",
                    help="resilience mode: deadline+QoS engine vs non-"
                         "degrading engine under 2x saturating Poisson "
                         "load, gated >= 1.2x goodput tok/step with zero "
                         "deadline-missed completions served; skips "
                         "regular modes")
    ap.add_argument("--deadline-steps", type=int, default=0,
                    help="--overload-trace deadline (0 = 3x mean gen len)")
    ap.add_argument("--fleet", type=int, default=0,
                    help="fleet mode: one dense trace through an N-process "
                         "subprocess fleet (launch.fleet + FleetRouter) vs "
                         "a single engine, gated >= 1.5x tokens/fleet-step "
                         "+ token-identity; skips regular modes")
    ap.add_argument("--ledger-trace", action="store_true",
                    help="ineffectual-work ledger mode: one trace replayed "
                         "twice through a ledger-instrumented device-loop "
                         "engine; gated on measured activation zeros > 0, "
                         "bit-identical counters/histograms across runs, "
                         "host syncs == dispatches, exact tier-0 quality "
                         "shadow; skips regular modes")
    ap.add_argument("--ledger-kblock", type=int, default=8,
                    help="--ledger-trace dead-k-block granularity")
    ap.add_argument("--draft-bits", type=int, default=8,
                    help="draft weight bits (0 = native)")
    ap.add_argument("--draft-sparsity", type=float, default=0.0)
    ap.add_argument("--draft-keep-layers", type=int, default=0,
                    help="truncate the draft to its first N layers (0=all)")
    ap.add_argument("--out", default="",
                    help="write result records to this JSON path")
    ap.add_argument("--trace-dir", default="",
                    help="record each (spec, mode) run with the serve "
                         "tracer: JSONL + Chrome traces and one telemetry "
                         "snapshot per mode land here (CI artifacts)")
    a = ap.parse_args()
    compile_cache.configure()
    if a.fleet:
        ok = run_fleet_trace(a.arch or "h2o-danube-1.8b",
                             a.requests or 12, a.slots, a.seed,
                             n_processes=a.fleet, out=a.out,
                             decode_chunk=a.decode_chunk)
        sys.exit(0 if ok else 1)
    if a.ledger_trace:
        ok = run_ledger_trace(a.arch or "nemotron-4-340b",
                              a.requests or 8, a.slots, a.seed,
                              out=a.out, k_block=a.ledger_kblock)
        sys.exit(0 if ok else 1)
    if a.overload_trace:
        ok = run_overload_trace(a.arch or "h2o-danube-1.8b",
                                a.requests or 40, a.slots, a.seed,
                                out=a.out, deadline_steps=a.deadline_steps)
        sys.exit(0 if ok else 1)
    if a.conversation_trace:
        ok = run_conversation_trace(a.arch or "nemotron-4-340b",
                                    a.requests or 6, a.turns, a.slots,
                                    a.seed, a.page_size, out=a.out)
        sys.exit(0 if ok else 1)
    if a.prefix_trace:
        ok = run_prefix_trace(a.arch or "nemotron-4-340b",
                              a.requests or 24, a.slots, a.seed,
                              a.page_size, out=a.out)
        sys.exit(0 if ok else 1)
    if a.speculate:
        draft = DraftSpec.from_args(a.draft_bits, a.draft_sparsity,
                                    a.draft_keep_layers)
        ok = run_speculative(a.arch or "nemotron-4-340b",
                             a.requests or 10, a.slots, a.seed,
                             a.speculate, draft, out=a.out)
        sys.exit(0 if ok else 1)
    mesh_shape = None
    if a.mesh:
        from repro.launch.mesh import parse_mesh_arg
        mesh_shape = parse_mesh_arg(a.mesh)
    if a.smoke:
        ok = run(a.arch or "h2o-danube-1.8b", n_requests=a.requests or 8, n_slots=a.slots,
                 prompt_range=(4, 16), gen_range=(8, 16),
                 mean_interarrival=1.5, seed=a.seed, smoke=True,
                 decode_chunk=a.decode_chunk, n_replicas=a.replicas,
                 mesh_shape=mesh_shape, out=a.out, trace_dir=a.trace_dir)
    else:
        ok = run(a.arch or "h2o-danube-1.8b", n_requests=a.requests or 16, n_slots=a.slots,
                 seed=a.seed, decode_chunk=a.decode_chunk,
                 n_replicas=a.replicas, mesh_shape=mesh_shape, out=a.out,
                 trace_dir=a.trace_dir)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()

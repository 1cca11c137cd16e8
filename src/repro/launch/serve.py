"""Serving launcher: continuous-batching engine over packed Kratos weights.

  PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-1.8b \
      --requests 8 --prompt-len 32 --gen 32 \
      [--full --backend pallas] \
      [--sparsity 0.5 --bits 8 --impl tree --block 128] [--slots 4] \
      [--static] [--temperature 0.8] \
      [--speculate 4 --draft-bits 8 [--draft-sparsity S] \
       [--draft-keep-layers N]] \
      [--page-size P [--n-pages N] [--no-prefix-cache]] \
      [--mesh data,model] [--replicas N] [--max-waiting M] [--dry-run] \
      [--trace-out T.jsonl] [--trace-chrome T.json] [--profile-dir D] \
      [--telemetry-port P] [--telemetry-jsonl S.jsonl] \
      [--ledger [--ledger-threshold T] [--quality-every N]] \
      [--tiers 8:0.5,8:0.75 [--qos-*]] [--deadline-steps D] \
      [--pool-wait-retries R] [--auto-restart]

Resilience (PR 7): `--tiers bits:sparsity[,...]` loads a QoS degradation
ladder — the same weights re-packed at cheaper (sparsity, bits) points,
all resident — and the engine demotes the live decode down the ladder
under sustained queue depth / page pressure (hysteresis via --qos-*),
re-promoting when load clears; in-flight streams continue across swaps.
`--deadline-steps` sheds doomed work at admission and cancels expired
work in flight; `--pool-wait-retries` bounds PoolExhausted requeues with
exponential backoff; `--auto-restart` rebuilds a replica the router
marked dead (serve.qos, serve.chaos, router failover).

Observability: `--trace-out` / `--trace-chrome` switch the engines to the
ring-buffer tracer (serve.trace) and export every lifecycle/dispatch edge
as JSONL / chrome://tracing JSON after the run; `--profile-dir` brackets
the first N traced dispatches with jax.profiler (device timeline next to
the host spans); `--telemetry-port` serves live Prometheus text at
/metrics during the run and `--telemetry-jsonl` appends one metrics
snapshot per `--telemetry-interval` (serve.telemetry). `--ledger` carries
the ineffectual-work counter matrix (serve.ledger) through every decode /
speculative / suffix-prefill dispatch as donated device state — activation
zero fractions, per-group zero histograms, dead k-blocks, effective vs
dense FLOPs/bytes — drained once per dispatch inside the existing token
sync and surfaced through ServeMetrics, the tracer's Chrome counter
tracks, and Prometheus; `--quality-every N` shadow-runs every Nth
full-prefill admission through tier 0 for per-tier logit agreement.

Paged KV + prefix reuse: `--page-size P` switches the KV pool to the
block-paged form (serve.paging) — per-slot page tables over refcounted
fixed-size pages — with the radix prefix index on by default where the
arch supports it: admissions sharing a cached prompt prefix skip its
prefill and share its pages. `--n-pages N` sizes the pool (default:
slab-equivalent capacity); the engine report prints the prefix hit rate,
prefill tokens skipped, and page occupancy to steer P by (smaller pages =
finer sharing granularity + more table entries; start at 8-16).

Speculative decode: `--speculate K` derives a SELF-DRAFT artifact (the same
weights re-packed at the --draft-* Kratos point, serve.speculative) and
serves with the fused propose-then-verify dispatch — 1..K+1 tokens commit
per dispatch, greedy output token-identical to plain decode. Replaces
--decode-chunk. Acceptance-rate tuning: start with --draft-bits 8 (highest
fidelity, ~1.0 acceptance), add sparsity / layer truncation to cut draft
FLOPs while acceptance stays above ~0.8; the engine report prints the
acceptance rate and draft/verify FLOP ratio to steer by.

Loads the reduced smoke config through the serve registry (weights packed
once via kratos.pack) — or, with `--full`, the published config at its
published widths — submits `--requests` generation requests with a small
prompt-length jitter, and drives the engine until the trace drains. The
engine's prefill/decode steps are the SAME `distributed.steps` factories the
decode_32k / long_500k dry-run cells lower for the production mesh — the
per-slot-index decode is a strict generalization of the lock-step step.

Mesh serving: `--mesh 2,4` places every replica's params/slab/state over a
(data=2, model=4) mesh via `serve.ShardedBackend` (force CPU devices first:
XLA_FLAGS=--xla_force_host_platform_device_count=8). `--replicas N` fronts
N engines with `serve.ReplicaRouter` — with a mesh, the data axis splits
into one disjoint submesh per replica (launch.mesh.replica_meshes); without
one, N LocalBackend replicas share the default device. `--dry-run` prints
the RESOLVED placement — one line per cache/state leaf with its
PartitionSpec — plus the loop-aware cost of the lowered sharded decode step
(analysis.hlo: flops, memory bytes, collective wire bytes) and exits
without running traffic.

Fleet serving (PR 10): `--processes N` serves through a local fleet — N
worker processes spawned by launch.fleet, each running its own engines on
its own forced CPU devices, fronted by the cross-process FleetRouter
(serve.control). `--coordinator HOST:PORT --num-processes M
--process-id I` instead identifies THIS process in a real multi-host
launch (jax.distributed.initialize via serve.ensure_distributed).
`--dry-run` with either prints the resolved fleet topology — process ->
local devices -> replica meshes -> per-leaf cache/state shardings —
BEFORE any weight packing, so a short device count, an uneven replica
split, or a wrong num_processes fails in milliseconds, not after a
multi-minute pack.
"""

from __future__ import annotations

import argparse

import numpy as np

from repro.core.kratos import KratosSpec
from repro.serve import (DraftSpec, EngineConfig, InferenceEngine,
                         LedgerConfig, LocalBackend, ModelRegistry,
                         QoSConfig, ReplicaRouter, ShardedBackend,
                         StaticScheduler, TelemetryConfig, TelemetryExporter,
                         TraceConfig, engine_sample, export_chrome,
                         export_jsonl, parse_tiers, router_sample)


def _dry_run(model, cfg: EngineConfig, mesh_shape) -> None:
    """Print resolved shardings per cache/state/param group + decode cost."""
    import jax
    from repro.analysis import hlo as HA
    from repro.distributed import sharding as SH, steps as ST
    from repro.launch import mesh as M
    from repro.models import transformer as T

    mesh = M.make_local_mesh(*mesh_shape)
    print(f"[dry-run] mesh {dict(mesh.shape)} over {mesh.size} devices")
    cache_len = cfg.max_len + cfg.speculate   # +K speculative write headroom
    caches = jax.eval_shape(
        lambda: T.make_caches(model.cfg, cfg.n_slots, cache_len))
    cache_specs = SH.cache_pspecs(caches, mesh, cfg.n_slots, slab=True)
    if not cfg.page_size:
        print(f"[dry-run] KV slab leaves ({cfg.n_slots} slots x "
              f"{cache_len} positions"
              + (f" = max_len + K={cfg.speculate} headroom" if cfg.speculate
                 else "") + "):")
        for path, spec in jax.tree_util.tree_leaves_with_path(
                cache_specs, is_leaf=lambda x: isinstance(
                    x, jax.sharding.PartitionSpec)):
            print(f"    {jax.tree_util.keystr(path):48s} {spec}")
    print("[dry-run] decode state vectors:")
    for k, spec in ST.decode_state_pspecs(mesh, cfg.n_slots).items():
        print(f"    {k:48s} {spec}")
    backend = ShardedBackend(mesh=mesh)
    backend.build(model, cfg)
    if cfg.page_size:
        # resolved page-pool geometry: what the slab stride turned into
        pool = backend.pool
        d = pool.describe()
        print(f"[dry-run] page pool: {d['n_pages']} pages x "
              f"{d['page_size']} positions ({d['pages_per_slot']}/slot x "
              f"{cfg.n_slots} slots"
              + (f", + K={cfg.speculate} headroom in the last page(s)"
                 if cfg.speculate else "")
              + f"), {d['bytes'] / 1e6:.2f} MB, prefix cache "
              + ("ON" if d["prefix_cache"] else
                 "OFF (arch cache state not purely positional)"))
        print("[dry-run] page-store leaves (paged = page-major; resident = "
              "slot-major slab layout):")
        for leaf, spec in zip(pool.layout.specs, pool.shardings):
            kind = "paged   " if leaf.paged else "resident"
            print(f"    {leaf.name:40s} {kind} {spec.spec}")
        print(f"    {'page_table':40s} table    "
              f"{pool.table_sharding.spec}")
    if cfg.speculate:
        # the step that will actually dispatch: fused propose-then-verify
        if cfg.page_size:
            compiled = backend._spec_decode.lower(
                backend.params, backend.draft_params, backend.pool.store,
                backend.pool.page_table, backend.draft_pool.caches,
                backend.state).compile()
        else:
            compiled = backend._spec_decode.lower(
                backend.params, backend.draft_params, backend.pool.caches,
                backend.draft_pool.caches, backend.state).compile()
        label = f"speculative step (K={cfg.speculate}, draft replicated)"
    elif cfg.page_size:
        compiled = backend._decode.lower(
            backend.params, backend.pool.store, backend.pool.page_table,
            backend.state).compile()
        label = f"paged decode step (K={cfg.decode_chunk}, " \
                f"page={cfg.page_size})"
    else:
        compiled = backend._decode.lower(
            backend.params, backend.pool.caches, backend.state).compile()
        label = f"decode step (K={cfg.decode_chunk})"
    r = HA.analyze(compiled.as_text())
    coll = {k: int(v["count"]) for k, v in r["collectives"].items()
            if v["count"]}
    print(f"[dry-run] {label}: "
          f"{r['flops']:.3g} flops, {r['bytes']:.3g} B touched, "
          f"{r['wire_bytes']:.3g} B wire, collectives {coll or 'none'}")


def _dry_run_fleet(args, M) -> None:
    """Resolved fleet topology, NO weight packing: process -> local
    devices -> replica meshes -> per-leaf cache/state shardings. The
    sharding resolution runs over an AbstractMesh of the per-process
    replica shape, so nothing here allocates or packs — a bad topology
    fails in milliseconds."""
    import jax
    from jax.sharding import AbstractMesh

    from repro import configs as C
    from repro.distributed import sharding as SH, steps as ST
    from repro.models import transformer as T

    mesh_shape = (M.parse_mesh_arg(args.mesh) if args.mesh
                  else (args.replicas, 1))
    data, model_ax = mesh_shape
    if args.coordinator:
        from repro.serve import ensure_distributed
        ensure_distributed(args.coordinator, args.num_processes,
                           args.process_id)
        live = M.fleet_topology(data, model_ax, args.replicas)
        print(f"[dry-run] fleet (live): process {live['process_index']} "
              f"of {live['num_processes']}, "
              f"{live['global_device_count']} global devices, "
              f"mesh {data}x{model_ax}, {args.replicas} replicas/process")
        procs = [live]
    else:
        plan = M.plan_fleet_topology(args.processes, data * model_ax,
                                     data, model_ax, args.replicas)
        print(f"[dry-run] fleet (planned): {plan['num_processes']} "
              f"processes x {plan['devices_per_process']} forced CPU "
              f"devices = {plan['global_device_count']} global, "
              f"mesh {data}x{model_ax}, {args.replicas} replicas/process")
        procs = plan["processes"]
    for p in procs:
        print(f"[dry-run]   process {p['process_index']}: "
              + " ".join(p["local_devices"]))
        for rm in p["replica_meshes"]:
            shape = "x".join(str(v) for v in rm["shape"].values())
            print(f"[dry-run]     replica {rm['replica']} ({shape}): "
                  + " ".join(rm["devices"]))

    # per-leaf shardings on the per-replica submesh, shape-only
    sub = AbstractMesh((("data", data // args.replicas),
                        ("model", model_ax)))
    cfg = C.get_smoke(args.arch)
    max_len = args.max_len or (cfg.n_img_tokens + args.prompt_len
                               + args.gen + 8)
    caches = jax.eval_shape(lambda: T.make_caches(cfg, args.slots, max_len))
    print(f"[dry-run] per-replica KV slab leaves ({args.slots} slots x "
          f"{max_len} positions):")
    cache_specs = SH.cache_pspecs(caches, sub, args.slots, slab=True)
    for path, spec in jax.tree_util.tree_leaves_with_path(
            cache_specs, is_leaf=lambda x: isinstance(
                x, jax.sharding.PartitionSpec)):
        print(f"    {jax.tree_util.keystr(path):48s} {spec}")
    print("[dry-run] per-replica decode state vectors:")
    for k, spec in ST.decode_state_pspecs(sub, args.slots).items():
        print(f"    {k:48s} {spec}")


def _serve_fleet(args) -> None:
    """Local-fleet serving: spawn N workers (launch.fleet), front them
    with the FleetRouter, drive the same Poisson-ish trace."""
    import numpy as np

    from repro.launch import fleet as F

    rng = np.random.default_rng(args.seed)
    with F.spawn_fleet(args.processes, arch=args.arch, n_slots=args.slots,
                       max_len=args.max_len or 96,
                       decode_chunk=args.decode_chunk,
                       replicas_per_process=args.replicas) as fl:
        reqs = []
        for i in range(args.requests):
            s0 = max(1, args.prompt_len + int(rng.integers(-4, 5)))
            prompt = rng.integers(0, 32000, s0)
            reqs.append(fl.router.submit(
                list(map(int, prompt)), args.gen,
                temperature=args.temperature))
        fl.drive()
        fl.router.stop()
        rep = fl.router.report()
        print(f"[serve] fleet {args.processes} processes: "
              f"{rep['fleet_tokens']:.0f} tokens, "
              f"{rep['fleet_requests_completed']:.0f} done, "
              f"{rep['tokens_per_fleet_step']:.2f} tok/fleet-step, "
              f"failovers {rep['fleet_failovers']:.0f}")
        for r in reqs[:2]:
            print(f"  req{r.rid}: {np.asarray(r.tokens)[:16]} ...")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h2o-danube-1.8b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=0,
                    help="cache positions per slot (0 = prompt+gen+slack)")
    ap.add_argument("--sparsity", type=float, default=0.0)
    ap.add_argument("--bits", type=int, default=0, help="0 = native bf16/f32")
    ap.add_argument("--act-bits", type=int, default=0, help="8 => w8a8")
    ap.add_argument("--impl", default="tree", choices=("tree", "systolic"))
    ap.add_argument("--block", type=int, default=8,
                    help="sparsity bk=bn (8 serves on ref/interpret only; "
                         "--backend pallas needs a multiple of 128)")
    ap.add_argument("--full", action="store_true",
                    help="serve the published config (all layers, published "
                         "widths; weights from --seed) instead of the "
                         "reduced smoke config")
    ap.add_argument("--backend", default="ref",
                    choices=("ref", "pallas", "interpret"),
                    help="GEMM/attention kernels: XLA reference, Pallas on "
                         "the TPU, or Pallas in interpret mode on the CPU")
    ap.add_argument("--static", action="store_true",
                    help="lock-step drain-then-refill baseline scheduler")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--decode-chunk", type=int, default=1,
                    help="K micro-steps per device-resident decode dispatch "
                         "(throughput up, admission latency up)")
    ap.add_argument("--host-loop", action="store_true",
                    help="PR-1 host decode loop (per-step logits pull + "
                         "numpy sampling) instead of the device-resident one")
    ap.add_argument("--speculate", type=int, default=0,
                    help="K-token self-draft speculation per dispatch "
                         "(derives a draft artifact from the same weights; "
                         "replaces --decode-chunk; greedy output unchanged)")
    ap.add_argument("--draft-bits", type=int, default=8,
                    help="draft weight bits for --speculate (0 = native)")
    ap.add_argument("--draft-sparsity", type=float, default=0.0,
                    help="draft sparsity for --speculate (bk=bn=8 blocks)")
    ap.add_argument("--draft-keep-layers", type=int, default=0,
                    help="truncate the draft to its first N layers (0=all)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="block-paged KV pool with P positions per page "
                         "(0 = slab); enables cross-request prefix reuse "
                         "where the arch supports it")
    ap.add_argument("--n-pages", type=int, default=0,
                    help="page-pool size for --page-size (0 = slab-"
                         "equivalent: slots x pages_per_slot + sink)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="paged pool without the radix prefix index "
                         "(paging only: no cross-request sharing)")
    ap.add_argument("--mesh", default="",
                    help="'data,model' sizes: serve through ShardedBackend "
                         "on a local mesh of that shape")
    ap.add_argument("--replicas", type=int, default=1,
                    help="front N engine replicas with the ReplicaRouter "
                         "(with --mesh, one data-submesh per replica)")
    ap.add_argument("--max-waiting", type=int, default=0,
                    help="bound each replica's waiting deque (0 = unbounded);"
                         " rejections spill across replicas")
    ap.add_argument("--dry-run", action="store_true",
                    help="print resolved cache/state shardings + decode cost "
                         "for --mesh and exit (no traffic)")
    ap.add_argument("--trace-out", default="",
                    help="record every lifecycle/dispatch edge (serve.trace) "
                         "and export the JSONL event stream here")
    ap.add_argument("--trace-chrome", default="",
                    help="export the trace as chrome://tracing JSON (one "
                         "process per replica, one track per slot)")
    ap.add_argument("--profile-dir", default="",
                    help="bracket the first --profile-dispatches traced "
                         "dispatches with jax.profiler (TensorBoard dir)")
    ap.add_argument("--profile-dispatches", type=int, default=3,
                    help="dispatches inside the --profile-dir bracket")
    ap.add_argument("--telemetry-port", type=int, default=-1,
                    help="serve Prometheus text on 127.0.0.1:PORT/metrics "
                         "during the run (0 = ephemeral port; -1 = off)")
    ap.add_argument("--telemetry-interval", type=float, default=1.0,
                    help="telemetry snapshot cadence, seconds")
    ap.add_argument("--telemetry-jsonl", default="",
                    help="append one JSON metrics snapshot per interval here")
    ap.add_argument("--ledger", action="store_true",
                    help="ineffectual-work ledger (serve.ledger): device-"
                         "resident activation-sparsity / effective-FLOP "
                         "counters drained once per dispatch inside the "
                         "existing token sync (device loop only)")
    ap.add_argument("--ledger-threshold", type=float, default=0.0,
                    help="|x| <= t counts as near-zero in the ledger probes "
                         "(0 = exact zeros only)")
    ap.add_argument("--ledger-group", type=int, default=8,
                    help="ledger per-group zero histogram group size")
    ap.add_argument("--ledger-kblock", type=int, default=32,
                    help="ledger dead-k-block granularity (contraction-dim "
                         "block size an activation-skip kernel would use)")
    ap.add_argument("--quality-every", type=int, default=0,
                    help="shadow-run every Nth full-prefill admission "
                         "through tier 0 and record per-tier logit "
                         "agreement (0 = off; implies --ledger wiring)")
    ap.add_argument("--tiers", default="",
                    help="QoS degradation ladder: 'bits:sparsity[,...]' "
                         "cheapest-last (e.g. '8:0.5,8:0.75') — the registry "
                         "keeps each tier resident and the engine demotes "
                         "the live decode to it under load (serve.qos)")
    ap.add_argument("--qos-demote-depth", type=int, default=8,
                    help="waiting-queue depth that (with hysteresis) demotes "
                         "one tier")
    ap.add_argument("--qos-promote-depth", type=int, default=1,
                    help="queue depth at/below which the engine re-promotes")
    ap.add_argument("--qos-hysteresis", type=int, default=4,
                    help="consecutive steps over/under threshold before a "
                         "tier change")
    ap.add_argument("--qos-page-pressure", type=float, default=0.95,
                    help="page-pool occupancy fraction that also counts as "
                         "overload (paged engines)")
    ap.add_argument("--deadline-steps", type=int, default=0,
                    help="per-request deadline in engine steps (0 = none): "
                         "doomed work is shed at admission, expired work "
                         "cancelled in flight")
    ap.add_argument("--pool-wait-retries", type=int, default=-1,
                    help="bound PoolExhausted requeues per request with "
                         "exponential backoff; past the cap the request is "
                         "shed (-1 = unbounded legacy wait)")
    ap.add_argument("--auto-restart", action="store_true",
                    help="router: rebuild a replica marked dead by a "
                         "ReplicaFault instead of serving degraded")
    ap.add_argument("--processes", type=int, default=1,
                    help="serve through a local fleet of N worker "
                         "processes (launch.fleet + serve.FleetRouter); "
                         "each worker runs --replicas engines on its own "
                         "forced CPU devices")
    ap.add_argument("--coordinator", default="",
                    help="jax.distributed coordinator HOST:PORT for a real "
                         "multi-host launch (this process joins the fleet)")
    ap.add_argument("--num-processes", type=int, default=1,
                    help="fleet size for --coordinator")
    ap.add_argument("--process-id", type=int, default=0,
                    help="this process's index for --coordinator")
    args = ap.parse_args()

    from repro.launch import compile_cache, mesh as M
    compile_cache.configure()

    fleet_mode = args.processes > 1 or args.coordinator
    if args.dry_run and fleet_mode:
        # Topology resolution must fail BEFORE the (expensive) weight pack:
        # no registry.load on this path, config comes shape-only.
        _dry_run_fleet(args, M)
        return
    if args.processes > 1:
        # workers pack their own weights; the coordinator never loads
        _serve_fleet(args)
        return
    if args.coordinator:
        # real multi-host: join the fleet before any jax backend touch
        from repro.serve import ensure_distributed
        ensure_distributed(args.coordinator, args.num_processes,
                           args.process_id)

    spec = KratosSpec(sparsity=args.sparsity,
                      bits=args.bits or None,
                      act_bits=args.act_bits or None,
                      impl=args.impl, bk=args.block, bn=args.block)
    draft = None
    if args.speculate:
        draft = DraftSpec.from_args(args.draft_bits, args.draft_sparsity,
                                    args.draft_keep_layers)
    tier_specs = parse_tiers(args.tiers) if args.tiers else ()
    registry = ModelRegistry()
    model = registry.load(args.arch, spec, seed=args.seed, draft_spec=draft,
                          tier_specs=tier_specs, smoke=not args.full)
    print(f"[serve] {model.name}: {model.n_packed} packed projections, "
          f"{model.packed_bytes / 1e6:.2f} MB packed vs "
          f"{model.dense_bytes / 1e6:.2f} MB dense "
          f"({model.compression:.2f}x)")
    if tier_specs:
        print(f"[serve] QoS ladder: tier 0 (target) + "
              + ", ".join(f"tier {i + 1} = {t.tag}"
                          for i, t in enumerate(tier_specs)))
    if draft is not None:
        print(f"[serve] self-draft {draft.tag}: {model.draft_packed} packed "
              f"projections, draft/verify flops "
              f"{model.draft_cost_fraction():.2f}, K={args.speculate}")

    max_len = args.max_len or (model.cfg.n_img_tokens + args.prompt_len
                               + args.gen + 8)
    tracing = bool(args.trace_out or args.trace_chrome or args.profile_dir)
    trace_cfg = TraceConfig(
        out=args.trace_out or None, chrome=args.trace_chrome or None,
        profile_dir=args.profile_dir or None,
        profile_dispatches=args.profile_dispatches) if tracing else None
    ledger_cfg = None
    if args.ledger or args.quality_every:
        if args.host_loop:
            raise SystemExit("--ledger requires the device-resident loop "
                             "(drop --host-loop)")
        ledger_cfg = LedgerConfig(threshold=args.ledger_threshold,
                                  group=args.ledger_group,
                                  k_block=args.ledger_kblock,
                                  quality_every=args.quality_every)
    qos = QoSConfig(demote_depth=args.qos_demote_depth,
                    promote_depth=args.qos_promote_depth,
                    hysteresis=args.qos_hysteresis,
                    page_pressure=args.qos_page_pressure) \
        if tier_specs else None
    cfg = EngineConfig(n_slots=args.slots, max_len=max_len, seed=args.seed,
                       backend=args.backend,
                       device_loop=not args.host_loop,
                       decode_chunk=args.decode_chunk,
                       speculate=args.speculate,
                       max_waiting=args.max_waiting or None,
                       page_size=args.page_size or None,
                       n_pages=args.n_pages or None,
                       prefix_cache=not args.no_prefix_cache,
                       pool_wait_retries=args.pool_wait_retries
                       if args.pool_wait_retries >= 0 else None,
                       qos=qos, trace=trace_cfg, ledger=ledger_cfg)
    mesh_shape = M.parse_mesh_arg(args.mesh) if args.mesh else None

    if args.dry_run:
        if mesh_shape is None:
            raise SystemExit("--dry-run needs --mesh data,model")
        _dry_run(model, cfg, mesh_shape)
        return

    def backend_for(i: int):
        if mesh_shape is None:
            return LocalBackend()
        if args.replicas > 1:
            meshes = backend_for.meshes
            return ShardedBackend(mesh=meshes[i])
        return ShardedBackend(mesh_shape=mesh_shape)

    if mesh_shape is not None and args.replicas > 1:
        backend_for.meshes = M.replica_meshes(*mesh_shape, args.replicas)

    rng = np.random.default_rng(args.seed)

    def trace():
        for i in range(args.requests):
            s0 = max(1, args.prompt_len + int(rng.integers(-4, 5)))
            yield rng.integers(0, model.cfg.vocab, s0), args.gen, i

    def telemetry_for(sample_fn):
        if args.telemetry_port < 0 and not args.telemetry_jsonl:
            return None
        exp = TelemetryExporter(sample_fn, TelemetryConfig(
            interval=args.telemetry_interval,
            port=args.telemetry_port if args.telemetry_port >= 0 else None,
            jsonl=args.telemetry_jsonl or None,
            # fleet processes exporting on one host need distinct series;
            # single-process output stays byte-identical (no label)
            process_index=args.process_id if args.coordinator else None))
        exp.start()
        if exp.port is not None:
            print(f"[serve] telemetry: http://127.0.0.1:{exp.port}/metrics")
        return exp

    if args.replicas > 1:
        router = ReplicaRouter.build(
            model, cfg, args.replicas,
            backend_factory=backend_for,
            scheduler_factory=(lambda i: StaticScheduler()) if args.static
            else None,
            auto_restart=args.auto_restart)
        telemetry = telemetry_for(lambda: router_sample(router))
        reqs = [router.submit(p, g, arrival_step=at,
                              temperature=args.temperature,
                              deadline_steps=args.deadline_steps or None)
                for p, g, at in trace()]
        router.run()
        if telemetry is not None:
            telemetry.stop()
        tracers = router.tracers
        print(f"[serve] router {router.format_report()}")
    else:
        from repro.serve import EngineSaturated
        engine = InferenceEngine(
            model, cfg,
            scheduler=StaticScheduler() if args.static else None,
            backend=backend_for(0))
        telemetry = telemetry_for(lambda: engine_sample(engine))
        reqs = []
        for p, g, at in trace():
            # bounded deque + upfront trace submission: back off like a
            # client would — step the engine until the submit is accepted
            while True:
                try:
                    reqs.append(engine.submit(
                        p, g, arrival_step=at,
                        temperature=args.temperature,
                        deadline_steps=args.deadline_steps or None))
                    break
                except EngineSaturated:
                    engine.step()
        engine.run()
        if telemetry is not None:
            telemetry.stop()
        tracers = [engine.trace] if engine.trace.enabled else []
        print(f"[serve] scheduler={engine.scheduler.name} "
              f"backend={engine.backend.name} "
              f"{engine.metrics.format_report()}")
    if tracers:
        if args.trace_out:
            n = export_jsonl(tracers, args.trace_out)
            print(f"[serve] trace: {n} events -> {args.trace_out}")
        if args.trace_chrome:
            n = export_chrome(tracers, args.trace_chrome)
            print(f"[serve] chrome trace: {n} events -> {args.trace_chrome} "
                  f"(open in chrome://tracing or ui.perfetto.dev)")
        if args.profile_dir:
            print(f"[serve] profiler capture (first "
                  f"{args.profile_dispatches} dispatches) -> "
                  f"{args.profile_dir}")
    for r in reqs[:2]:
        print(f"  req{r.id}: {np.asarray(r.generated)[:16]} ...")


if __name__ == "__main__":
    main()

"""Bring-up smoke run of the serving path on TPU chips.

    python3 chip_smoke.py            # one chip
    python3 chip_smoke.py --chips 4  # the sharded path on four chips

One chip: h2o-danube-1.8b at its published width (24 layers, d_model 2560,
GQA 32/8 with head_dim 80, d_ff 6912, vocab 32000), random weights from
`--seed`, is served through `InferenceEngine` with the Pallas kernels
(`EngineConfig(backend="pallas")`) at two Kratos points in turn: DENSE and
`sparsity=0.5, bits=8, bk=bn=128`. Each serves 4 requests (prompts of
72-120 tokens, 32 generated tokens each) and then is freed. For each point
the script checks:

  * the Pallas logits of a full forward over every prompt and its
    generated tokens against backend="ref" on the same packed weights,
    run at `matmul_precision("highest")` (the float32 reference);
  * that the packed Pallas kernels really dispatched: `apply_packed`
    routed to backend="pallas" on the expected buffers, and the skinny-m
    decode GEMMs recorded their row padding (SKINNY_M_EVENTS).

Four chips (`--chips 4`): only the multi-chip path. The sparse point is
served through `ShardedBackend` on a (data=1, model=4) mesh and through
`LocalBackend` on device 0, in this one process; their logits must agree,
and each of the four devices must hold its share of the KV cache.

Times, compile seconds and peak device memory are printed as one-off
bring-up readings of this run, not benchmark metrics. The script exits
non-zero, without its result line, when JAX finds no TPU, when the repo's
`src/` is missing, or when any check fails; its last line on success is
`{"ok": true, "device": {...}}`.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "h2o-danube-1.8b"
N_SLOTS, DECODE_CHUNK, GEN = 4, 4, 32
PROMPT_LENS = (72, 88, 104, 120)        # one pow2 prefill bucket (128)
# cache positions per slot, >= longest prompt + GEN; also the length of
# the full forwards, so a multiple of the flash kernel's 128-row block
MAX_LEN = 256
# Pallas vs the float32 reference, as max |dlogit| / max |logit|. The
# TPU's MXU multiplies float32 operands in bfloat16 passes unless told
# otherwise (8-bit mantissa: ~4e-3 relative per product); over 24 layers
# that drift stays near 1e-2. A wrong tile, block index or scale is an
# O(1) error.
LOGIT_TOL = 3e-2


def _versions():
    import importlib.metadata as md
    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            out[pkg] = "not installed"
    return out


class Phases:
    """Wall time per phase plus XLA compile seconds, on the host clock."""

    def __init__(self, dev):
        import jax
        self.dev = dev
        self.compile_s = 0.0
        self.failures = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def run(self, name, fn, *args):
        c0, t0 = self.compile_s, time.perf_counter()
        out = fn(*args)
        wall = time.perf_counter() - t0
        peak = (self.dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
        print(f"[smoke] phase {name}: wall {wall:.2f} s, compile "
              f"{self.compile_s - c0:.2f} s, device peak "
              f"{peak / 2**30:.2f} GiB (one-off bring-up reading)",
              flush=True)
        return out

    def check(self, ok, what):
        print(f"[smoke] {'PASS' if ok else 'FAIL'}: {what}", flush=True)
        if not ok:
            self.failures.append(what)


def _requests(vocab, seed):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in PROMPT_LENS]


def _load(spec, seed):
    """The published config, random weights from `seed`, packed at `spec`."""
    import jax
    from repro.serve import ModelRegistry
    model = ModelRegistry().load(ARCH, spec, seed=seed, smoke=False)
    jax.block_until_ready(model.params)
    return model


def _serve(model, backend, prompts):
    from repro.serve import EngineConfig, InferenceEngine
    eng = InferenceEngine(
        model, EngineConfig(backend="pallas", n_slots=N_SLOTS,
                            decode_chunk=DECODE_CHUNK, max_len=MAX_LEN),
        backend=backend)
    reqs = [eng.submit(p, GEN) for p in prompts]
    eng.run()
    return eng, [list(map(int, r.generated)) for r in reqs]


def _full_logits(eng, prompts, outs):
    """(4, MAX_LEN, vocab) logits of one full forward per request over its
    prompt + generated tokens, right-padded (causal: the pad is never
    attended), through the engine's own compiled full prefill."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    rows = []
    for p, g in zip(prompts, outs):
        toks = np.zeros((1, MAX_LEN), np.int32)
        seq = np.concatenate([p, np.asarray(g, np.int32)])
        toks[0, :len(seq)] = seq
        with eng.backend._ctx():
            logits, _ = eng.backend._prefill_full(
                eng.backend.params, {"tokens": jnp.asarray(toks)})
        rows.append(np.asarray(jax.device_get(logits))[0])
    return np.stack(rows)


def _ref_logits(model, prompts, outs):
    """The same full forwards on backend='ref' at highest matmul precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.distributed import steps as ST
    step = jax.jit(ST.make_prefill_step(model.cfg, "ref", last_only=False,
                                        cache_len=MAX_LEN))
    rows = []
    with jax.default_matmul_precision("highest"):
        for p, g in zip(prompts, outs):
            toks = np.zeros((1, MAX_LEN), np.int32)
            seq = np.concatenate([p, np.asarray(g, np.int32)])
            toks[0, :len(seq)] = seq
            logits, _ = step(model.params, {"tokens": jnp.asarray(toks)})
            rows.append(np.asarray(jax.device_get(logits))[0])
    return np.stack(rows)


def _compare(ph, label, got, want, prompts, outs):
    """Logit agreement at each prompt's last position (the prefill logits)
    and over every position, plus greedy-token agreement."""
    import numpy as np
    last = np.stack([got[i, len(p) - 1] for i, p in enumerate(prompts)])
    last_ref = np.stack([want[i, len(p) - 1] for i, p in enumerate(prompts)])
    err_last = float(np.abs(last - last_ref).max() / np.abs(last_ref).max())
    span = [(i, len(p) - 1, len(p) - 1 + GEN) for i, p in enumerate(prompts)]
    g = np.concatenate([got[i, a:b] for i, a, b in span])
    w = np.concatenate([want[i, a:b] for i, a, b in span])
    err_all = float(np.abs(g - w).max() / np.abs(w).max())
    print(f"[smoke] {label}: max|dlogit|/max|logit| = {err_last:.3e} at "
          f"the prompt ends, {err_all:.3e} over all generated positions",
          flush=True)
    ok = bool(np.isfinite(got).all()) and err_last <= LOGIT_TOL
    ph.check(ok, f"{label}: finite, last-position logits within "
                 f"{LOGIT_TOL:g}")
    gen = np.concatenate([np.asarray(o[:GEN]) for o in outs])
    top_got, top_want = g.argmax(-1), w.argmax(-1)
    print(f"[smoke] {label}: greedy agreement over {gen.size} generated "
          f"tokens: served vs its own full forward "
          f"{(top_got == gen).mean():.3f}, vs the other side "
          f"{(top_want == gen).mean():.3f}, argmax(got)==argmax(want) "
          f"{(top_got == top_want).mean():.3f}", flush=True)


def one_chip(ph, seed):
    from repro import configs as C
    from repro.core import kratos as kr
    from repro.instrument import REGISTRY
    from repro.serve import LocalBackend

    published = C.get_config(ARCH)
    specs = (("dense", kr.DENSE),
             ("s0.5-w8-b128", kr.KratosSpec(sparsity=0.5, bits=8,
                                            bk=128, bn=128)))
    for name, spec in specs:
        routes = {}
        orig = kr.apply_packed

        def counted(packed, x, spec_, n_in, n_out, *, backend="ref"):
            key = (backend, "+".join(sorted(packed)))
            routes[key] = routes.get(key, 0) + 1
            return orig(packed, x, spec_, n_in, n_out, backend=backend)

        kr.apply_packed = counted
        try:
            with REGISTRY.scoped("skinny_m") as ev:
                model = ph.run(f"{name}/load+pack", _load, spec, seed)
                cfg = model.cfg
                ph.check((cfg.n_layers, cfg.d_model, cfg.n_heads,
                          cfg.n_kv_heads, cfg.dh, cfg.d_ff, cfg.vocab)
                         == (published.n_layers, published.d_model,
                             published.n_heads, published.n_kv_heads,
                             published.dh, published.d_ff, published.vocab),
                         f"{name}: published width {cfg.n_layers}L "
                         f"d_model {cfg.d_model} vocab {cfg.vocab}")
                print(f"[smoke] {name}: {model.n_packed} packed projections,"
                      f" {model.packed_bytes / 2**30:.2f} GiB packed of "
                      f"{model.dense_bytes / 2**30:.2f} GiB dense", flush=True)
                prompts = _requests(cfg.vocab, seed)
                eng, outs = ph.run(f"{name}/serve (pallas)", _serve, model,
                                   LocalBackend(), prompts)
                skinny = sorted({e[0] for e in ev["skinny_m"]})
            got = ph.run(f"{name}/full forward (pallas)", _full_logits, eng,
                         prompts, outs)
            want = ph.run(f"{name}/full forward (ref, highest)", _ref_logits,
                          model, prompts, outs)
        finally:
            kr.apply_packed = orig
        m = eng.metrics.report()
        print(f"[smoke] {name}: {int(m['tokens_generated'])} tokens in "
              f"{int(m['decode_steps'])} decode dispatches; routes "
              f"{sorted(routes.items())}; skinny-m kernels {skinny}",
              flush=True)
        ph.check(all(len(o) == GEN for o in outs),
                 f"{name}: 4 requests x {GEN} tokens served")
        want_buf = "w" if spec.is_identity else "qblocks+qscale"
        ph.check(routes.get(("pallas", want_buf), 0) > 0
                 and not any(b == "pallas" and k != want_buf
                             for b, k in routes),
                 f"{name}: apply_packed routed to pallas on '{want_buf}'")
        ph.check(bool(skinny), f"{name}: skinny-m Pallas GEMMs dispatched")
        _compare(ph, f"{name} pallas vs ref", got, want, prompts, outs)
        del eng, model, got, want
        gc.collect()
        in_use = (ph.dev.memory_stats() or {}).get("bytes_in_use", 0)
        print(f"[smoke] {name} freed: device 0 holds "
              f"{in_use / 2**30:.2f} GiB", flush=True)


def four_chips(ph, seed):
    import jax
    from repro.core import kratos as kr
    from repro.serve import LocalBackend, ShardedBackend

    spec = kr.KratosSpec(sparsity=0.5, bits=8, bk=128, bn=128)
    model = ph.run("load+pack", _load, spec, seed)
    prompts = _requests(model.cfg.vocab, seed)
    local, outs_l = ph.run("serve local (device 0)", _serve, model,
                           LocalBackend(), prompts)
    sharded, outs_s = ph.run("serve sharded (data=1, model=4)", _serve,
                             model, ShardedBackend(mesh_shape=(1, 4)),
                             prompts)
    got = ph.run("full forward sharded", _full_logits, sharded, prompts,
                 outs_l)
    want = ph.run("full forward local", _full_logits, local, prompts,
                  outs_l)
    _compare(ph, "sharded vs local", got, want, prompts, outs_l)
    same = sum(a == b for a, b in zip(outs_s, outs_l))
    print(f"[smoke] sharded vs local: {same}/{len(outs_l)} requests "
          f"token-identical", flush=True)
    d = sharded.backend.describe()
    print(f"[smoke] sharded backend: {d}", flush=True)
    ph.check(d["n_devices"] == 4, "sharded backend spans 4 devices")
    k = sharded.pool.caches["blocks"][0]["mixer"]["k"]
    held = {s.device for s in k.addressable_shards}
    ph.check(len(held) == 4 and all(
        s.data.shape[2] == k.shape[2] // 4 for s in k.addressable_shards),
        f"KV cache {tuple(k.shape)} split over kv heads on 4 devices")
    for dev in jax.devices()[:4]:
        st = dev.memory_stats() or {}
        used = st.get("bytes_in_use", 0)
        print(f"[smoke] {dev}: in use {used / 2**30:.2f} GiB, peak "
              f"{st.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB "
              f"(one-off bring-up reading)", flush=True)
        ph.check(used > 0, f"{dev} holds its share")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"[smoke] jax.devices(): {devices}", flush=True)
    print(f"[smoke] platform {dev.platform}, device_kind {dev.device_kind!r},"
          f" {len(devices)} devices, versions {_versions()}", flush=True)
    if dev.platform != "tpu":
        print("[smoke] no TPU found: this smoke run needs the chip",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"[smoke] --chips {args.chips} but only {len(devices)} "
              f"devices", file=sys.stderr)
        return 2

    from repro.launch import compile_cache
    cache = compile_cache.configure()
    warm = os.path.isdir(cache) and bool(os.listdir(cache))
    print(f"[smoke] compile cache: {cache} ({'warm' if warm else 'cold'})",
          flush=True)
    ph = Phases(dev)
    (four_chips if args.chips == 4 else one_chip)(ph, args.seed)
    if ph.failures:
        print(f"[smoke] {len(ph.failures)} check(s) failed: {ph.failures}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

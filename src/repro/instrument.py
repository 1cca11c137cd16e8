"""Trace-time instrumentation: event-counter registry and scope contract.

Several modules record *trace-time* evidence that a particular code path
actually compiled — `kernels.pallas_compat.SKINNY_M_EVENTS` (a GEMM padded
its skinny row dim), `PAGED_ATTN_EVENTS` (the paged-attention decode path
dispatched), `serve.paging.GATHER_EVENTS` (a legacy gather/scatter
materialized the slab view). Historically each was a bare module-global
list that tests `.clear()`ed by hand, which leaks events across
parallel/reordered tests: a test that forgets to clear (or that runs while
another module traces) inherits someone else's events.

This module promotes them into ONE registry of named `EventList`s. The
lists are ordinary `list` subclasses, so every existing call site —
`.append(...)`, `.clear()`, `list(...)`, truthiness — keeps working, and
the historical module-global names remain as aliases **of the same
objects**. What the registry adds:

  * `REGISTRY.scoped(...)` — a context manager that snapshots the named
    lists (all of them by default), clears them IN PLACE, runs the body,
    and restores the prior contents in place on exit. Tests wrap their
    trace-and-assert block in it and can neither see events from earlier
    tests nor leak their own into later ones.
  * `REGISTRY.reset(...)` / `REGISTRY.snapshot()` — explicit clear and a
    name -> tuple copy of current contents, for benches that want counts
    without the context-manager shape.

In-place mutation (never rebinding) is the load-bearing detail: aliases in
other modules (`ops.SKINNY_M_EVENTS`, `from ... import GATHER_EVENTS`)
stay live because the identity of each list never changes.

The scope contract names the layers of a compiled step inside the program
itself. `scope(name)` is a `jax.named_scope` over one of `SCOPES`; the
name lands in the op metadata (`op_name`) of every op traced under it, which
a profiler capture carries as the op's `tf_op` stat. An op belongs to the
innermost contract name on its path (`bench/opscope.py` reads them):

  * `attn_core`   scores, mask, softmax, P.V, and the layout of K/V for
                  them: head split/merge, broadcast of K/V to the query
                  heads, the paged view of a cache, the flash kernel;
  * `kv_write`    writing new K/V (or MLA latents) into a cache, and
                  making a cache;
  * `layer_scan`  the `lax.scan` over layers: its per-layer slices of the
                  stacked weights and cache (xs) and their write-back (ys);
  * `decode_loop` the micro-step `lax.scan` of the fused decode dispatch:
                  its carry copies;
  * `gemm`        every Kratos projection and the LM head, with the
                  projection's name below it (`gemm/wq`, `gemm/head`);
  * `norm`        norms and the residual adds around them;
  * `rope`        rotary embedding and the positions it reads;
  * `mlp_act`     the MLP's activation and gating product;
  * `embed`       the token embedding;
  * `sample`      on-device sampling and the decode loop's per-slot
                  bookkeeping.

Scopes touch only op metadata: the compiled program is the same with or
without them (tests/test_instrument.py compares the two).
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Callable, Dict, Iterator, List, Tuple

import jax


class EventList(list):
    """A named, registry-owned trace-time event list.

    Identical to `list` for every caller; the extra `name` exists only so
    diagnostics can say which stream an assertion is about.
    """

    def __init__(self, name: str):
        super().__init__()
        self.name = name

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"EventList({self.name!r}, {list(self)!r})"


class EventRegistry:
    """Named event-list store; all mutation is in place (aliases stay live)."""

    def __init__(self) -> None:
        self._lists: Dict[str, EventList] = {}
        self._lock = threading.Lock()

    def event_list(self, name: str) -> EventList:
        """Get-or-create the named list. Repeat calls return the SAME
        object, which is what makes module-global aliasing safe."""
        with self._lock:
            if name not in self._lists:
                self._lists[name] = EventList(name)
            return self._lists[name]

    def names(self) -> Tuple[str, ...]:
        with self._lock:
            return tuple(sorted(self._lists))

    def reset(self, *names: str) -> None:
        """Clear the named lists (all registered lists when none given)."""
        for n in names or self.names():
            self.event_list(n).clear()

    def snapshot(self) -> Dict[str, Tuple]:
        """name -> tuple copy of current contents (counts for benches)."""
        return {n: tuple(self.event_list(n)) for n in self.names()}

    @contextlib.contextmanager
    def scoped(self, *names: str) -> Iterator[Dict[str, EventList]]:
        """Snapshot + clear the named lists (default: all) in place; restore
        the prior contents in place on exit. Yields name -> list so the body
        can assert on exactly the events IT traced."""
        use = names or self.names()
        stash: Dict[str, List] = {}
        for n in use:
            lst = self.event_list(n)
            stash[n] = list(lst)
            lst.clear()
        try:
            yield {n: self.event_list(n) for n in use}
        finally:
            for n in use:
                lst = self.event_list(n)
                lst.clear()
                lst.extend(stash[n])


REGISTRY = EventRegistry()


# ---------------------------------------------------------------------------
# Scope contract (device side)
# ---------------------------------------------------------------------------

SCOPES: Tuple[str, ...] = ("attn_core", "kv_write", "layer_scan",
                           "decode_loop", "gemm", "norm", "rope", "mlp_act",
                           "embed", "sample")


@contextlib.contextmanager
def scope(name: str, *below: str) -> Iterator[None]:
    """Name the ops traced in the body after contract scope `name`, with
    the non-empty names of `below` (e.g. a projection's name) nested under
    it."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not a contract scope: {SCOPES}")
    with contextlib.ExitStack() as stack:
        for n in (name,) + below:
            if n:
                stack.enter_context(jax.named_scope(n))
        yield


def scoped(name: str) -> Callable[[Callable], Callable]:
    """Decorator form of `scope`: every call runs under contract scope
    `name`."""
    def wrap(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)
        return call
    return wrap

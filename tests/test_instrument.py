"""instrument: the EventRegistry (named event lists, aliasing, scoped
reset) and the scope contract over the served programs."""

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.kratos import KratosSpec
from repro.instrument import REGISTRY, SCOPES, EventList, scope
from repro.kernels.pallas_compat import PAGED_ATTN_EVENTS, SKINNY_M_EVENTS
from repro.serve import EngineConfig, InferenceEngine, ModelRegistry
from repro.serve.paging import GATHER_EVENTS


def test_registry_returns_same_object():
    a = REGISTRY.event_list("test_stream_a")
    b = REGISTRY.event_list("test_stream_a")
    assert a is b
    assert isinstance(a, EventList) and isinstance(a, list)
    a.clear()


def test_legacy_names_are_registry_aliases():
    """The historical module globals must BE the registry's lists — tests
    that clear one must affect the other (same object, never rebound)."""
    assert SKINNY_M_EVENTS is REGISTRY.event_list("skinny_m")
    assert PAGED_ATTN_EVENTS is REGISTRY.event_list("paged_attn")
    assert GATHER_EVENTS is REGISTRY.event_list("gather")


def test_scoped_isolates_and_restores():
    lst = REGISTRY.event_list("test_stream_scoped")
    lst.clear()
    lst.append(("outer", 1))
    with REGISTRY.scoped("test_stream_scoped") as seen:
        inner = seen["test_stream_scoped"]
        assert inner is lst          # in-place: aliases stay live
        assert list(inner) == []     # prior events invisible inside
        inner.append(("inner", 2))
    assert list(lst) == [("outer", 1)]   # inner events did not leak out
    lst.clear()


def test_scoped_restores_on_exception():
    lst = REGISTRY.event_list("test_stream_exc")
    lst.clear()
    lst.append("keep")
    try:
        with REGISTRY.scoped("test_stream_exc"):
            lst.append("dropped")
            raise RuntimeError("boom")
    except RuntimeError:
        pass
    assert list(lst) == ["keep"]
    lst.clear()


def test_reset_and_snapshot():
    lst = REGISTRY.event_list("test_stream_snap")
    lst.clear()
    lst.extend([1, 2])
    snap = REGISTRY.snapshot()
    assert snap["test_stream_snap"] == (1, 2)
    REGISTRY.reset("test_stream_snap")
    assert list(lst) == []


# ---------------------------------------------------------------------------
# scope contract: named scopes over the served programs
# ---------------------------------------------------------------------------

# (arch, kratos point, engine settings): the slab and paged GQA paths with
# packed projections, and MLA
PROGRAMS = [("h2o-danube-1.8b", KratosSpec(sparsity=0.5, bits=8, bk=8, bn=8),
             {}),
            ("h2o-danube-1.8b", None, {"page_size": 8}),
            ("minicpm3-4b", None, {})]
_OPS = re.compile(r"= \S+ (dot|custom-call|dynamic-update-slice|scatter)\(")


def _backend(arch, spec, kw):
    reg = ModelRegistry()
    model = reg.load(arch, spec) if spec is not None else reg.load(arch)
    return InferenceEngine(model, EngineConfig(n_slots=2, max_len=32,
                                               decode_chunk=2, **kw)).backend


def _lowered(arch, spec, kw, b=None):
    """The engine's fused decode and its prefill, lowered (not compiled)."""
    b = b or _backend(arch, spec, kw)
    if b.paged:
        dec = b._decode.lower(b.params, b.pool.store, b.pool.page_table,
                              b.state)
    else:
        dec = b._decode.lower(b.params, b.pool.caches, b.state)
    pre = b._prefill_full.lower(b.params,
                                {"tokens": jnp.zeros((1, 16), jnp.int32)})
    return {"decode": dec, "prefill": pre}


def _with_metadata(lowered) -> str:
    return lowered.compiler_ir("hlo").as_hlo_module().to_string()


@pytest.mark.parametrize("arch,spec,kw", PROGRAMS,
                         ids=["slab-packed", "paged", "mla"])
def test_program_ops_lie_under_contract_scopes(arch, spec, kw):
    """Every dot, custom call and cache update the decode and prefill
    programs emit names a contract scope in its op metadata."""
    for name, lowered in _lowered(arch, spec, kw).items():
        text = _with_metadata(lowered)
        found = 0
        for line in text.splitlines():
            if not _OPS.search(line):
                continue
            found += 1
            op_name = re.search(r'op_name="([^"]*)"', line)
            parts = op_name.group(1).split("/") if op_name else []
            assert set(parts) & set(SCOPES), (name, line.strip()[:160])
        assert found, name
    # the decode loop, the layer scan and the GEMMs name themselves
    decode = _with_metadata(_lowered(*PROGRAMS[0])["decode"])
    for part in ("decode_loop/while", "layer_scan/while", "gemm/wq",
                 "gemm/w_down", "gemm/head", "attn_core", "kv_write"):
        assert part in decode, part


def _innermost(line: str) -> str:
    op_name = re.search(r'op_name="([^"]*)"', line)
    for part in reversed(op_name.group(1).split("/") if op_name else []):
        if part in SCOPES:
            return part
    return ""


def test_slab_decode_ops_carry_their_own_scope():
    """Which scope, not only some: in the slab decode the K/V broadcast to
    the query heads and the score and P.V dots lie under `attn_core`, the
    projections under `gemm`, and the new token's K/V rows, written per
    slot into the layer-stacked cache, under `kv_write`; no op under
    `layer_scan` writes a K/V leaf. A change that moves one of these ops
    to another scope moves `decode_attn_ms` or `decode_cache_ms` without
    changing the work, so it has to show here."""
    arch, spec, kw = PROGRAMS[0]
    cfg = ModelRegistry().load(arch).cfg
    kv, dh = cfg.n_kv_heads, cfg.head_dim
    group = cfg.n_heads // kv
    decode = _with_metadata(_lowered(arch, spec, kw)["decode"])
    ops = [line for line in decode.splitlines() if "op_name=" in line]

    def scopes(pattern: str) -> list:
        return [_innermost(line) for line in ops if re.search(pattern, line)]

    kv_broadcast = scopes(rf"= f32\[\d+,{kv},{group},\d+,{dh}\]\S* broadcast\(")
    assert len(kv_broadcast) >= 2 and set(kv_broadcast) == {"attn_core"}
    dots = [line for line in ops if re.search(r"= \S+ dot\(", line)]
    projections = [d for d in dots if re.search(r'op_name="[^"]*gemm/\w+/', d)]
    assert projections and {_innermost(d) for d in projections} == {"gemm"}
    attention = [_innermost(d) for d in dots if d not in projections]
    assert len(attention) >= 2 and set(attention) == {"attn_core"}
    token_write = scopes(rf"= f32\[{cfg.n_layers},\d+,{kv},\d+,{dh}\]\S* "
                         r"dynamic-update-slice\(")
    assert len(token_write) >= 2 and set(token_write) == {"kv_write"}
    kv_leaf_write = scopes(rf"= f32\[(\d+,)?\d+,{kv},\d+,{dh}\]\S* "
                           r"(dynamic-update-slice|scatter)\(")
    assert "layer_scan" not in kv_leaf_write


_OP = re.compile(r"%([\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(([^)]*)\)")


def _shape(dtype: str, dims: str, squeeze: bool = False) -> tuple:
    """('f32', '1,2,8') -> ('f32', (1, 2, 8)), or with `squeeze` the
    leading unit axes dropped: ('f32', (2, 8))."""
    dims = [int(d) for d in dims.split(",") if d]
    while squeeze and dims and dims[0] == 1:
        dims.pop(0)
    return dtype, tuple(dims)


@pytest.mark.parametrize(
    "arch,spec,kw", PROGRAMS[:2] + [("nemotron-4-340b", None,
                                     {"page_size": 8})],
    ids=["slab-packed", "paged", "paged-store"])
def test_fused_decode_never_copies_the_kv_stack(arch, spec, kw):
    """The compiled fused decode writes each new token row into the
    layer-stacked K/V leaves (slab or page store) where they lie: no op
    copies a whole stacked leaf (a loop carry's copy, a scan's ys buffer)
    and no dynamic-update-slice writes a whole layer's slice back. The
    danube programs' windowed leaves stay resident slabs even in the paged
    engine; the nemotron one reads and writes true page-store leaves."""
    b = _backend(arch, spec, kw)
    tree = b.pool.layout.as_tree(b.pool.store) if b.paged else b.pool.caches
    stacks = {(str(leaf.dtype).replace("float", "f"), tuple(leaf.shape))
              for leaf in jax.tree_util.tree_leaves(tree["blocks"])}
    layers = {_shape(dt, ",".join(map(str, shape[1:])), squeeze=True)
              for dt, shape in stacks}
    text = _lowered(arch, spec, kw, b)["decode"].compile().as_text()
    ops = [m.groups() for m in _OP.finditer(text)]
    shapes = {name: _shape(dt, dims, squeeze=True)
              for name, dt, dims, _, _ in ops}
    row_writes = 0
    for name, dt, dims, op, args in ops:
        result = _shape(dt, dims)
        if op in ("copy", "copy-start"):
            assert result not in stacks, (op, name, result)
        elif op == "dynamic-update-slice":
            update = re.findall(r"%([\w.\-]+)", args)[1]
            assert shapes.get(update) not in layers, (name, shapes[update])
            row_writes += result in stacks
    assert row_writes, "no token row written into a stacked leaf"


def _stripped(text: str) -> str:
    """Compiled HLO without op metadata, the stack-frame tables it points
    into, and the numbers XLA's name uniquer appends to instruction
    names."""
    out, skip = [], False
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            skip = True
        elif skip and not line.strip():
            skip = False
        elif not skip:
            out.append(line)
    text = re.sub(r", metadata=\{[^}]*\}", "", "\n".join(out))
    return re.sub(r"(%[\w\-]+?)(\.\d+)+\b", r"\1", text)


def test_scopes_change_no_compiled_code(monkeypatch):
    """Named scopes touch only metadata: the compiled decode and prefill
    programs are the same with the scopes and with every scope a no-op."""
    arch, spec, kw = PROGRAMS[0]
    scoped = {k: _stripped(v.compile().as_text())
              for k, v in _lowered(arch, spec, kw).items()}
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare_lowered = _lowered(arch, spec, kw)
    assert "attn_core" not in _with_metadata(bare_lowered["decode"])
    bare = {k: _stripped(v.compile().as_text())
            for k, v in bare_lowered.items()}
    assert scoped == bare


def test_scope_refuses_a_name_outside_the_contract():
    with pytest.raises(ValueError, match="contract scope"):
        with scope("attention"):
            pass

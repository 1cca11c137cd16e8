"""The serving kernels compile for a TPU v5e at h2o-danube-1.8b widths.

No chip is needed: the TPU compiler is installed, and it compiles for a
v5e that is described rather than attached. Each test lowers one Pallas
kernel of the served path at the published widths (d_model 2560 -> d_ff
6912, m = 4 decode rows, head_dim 80) and compiles it, so what Mosaic
refuses on the chip (untiled blocks, 8-bit shifts) fails here first.
Nothing runs, so these tests say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import kratos as kr
from repro.kernels import ops

D_MODEL, D_FF, M = 2560, 6912, 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # noqa: BLE001 — skip reason
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("spec", [
    kr.DENSE,
    kr.KratosSpec(bits=8),
    kr.KratosSpec(bits=4),
    kr.KratosSpec(bits=2),
    kr.KratosSpec(bits=1),
    kr.KratosSpec(bits=8, act_bits=8),
    kr.KratosSpec(sparsity=0.5, bk=128, bn=128),
    kr.KratosSpec(sparsity=0.5, bits=8, bk=128, bn=128),
    kr.KratosSpec(sparsity=0.5, bits=4, bk=128, bn=128),
], ids=["dense", "w8", "w4", "w2", "w1", "w8a8", "bsr", "bsr_w8", "bsr_w4"])
def test_packed_gemm_compiles(one_chip, spec):
    """`apply_packed` on the packed buffers of one d_model x d_ff
    projection, backend='pallas' — the exact decode-time dispatch."""
    w = jax.ShapeDtypeStruct((D_MODEL, D_FF), jnp.float32)
    packed = jax.eval_shape(lambda w: kr.pack({"w": w}, spec), w)
    x = jax.ShapeDtypeStruct((M, D_MODEL), jnp.float32)
    _compile(lambda p, x: kr.apply_packed(p, x, spec, D_MODEL, D_FF,
                                          backend="pallas"),
             _on(one_chip, packed), _on(one_chip, x))


def test_flash_attention_prefill_d80_compiles(one_chip):
    """Prefill attention: 32 query heads over 8 kv heads, head_dim 80."""
    q = jax.ShapeDtypeStruct((1, 32, 128, 80), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, 8, 128, 80), jnp.float32)
    _compile(lambda q, k, v: ops.flash_attention(
        q, k, v, causal=True, window=4096, backend="pallas"),
        *_on(one_chip, (q, kv, kv)))


def test_paged_flash_attention_d128_bf16_compiles(one_chip):
    """Page-table-native decode attention over a bf16 page store."""
    n_pages, page, b, pp = 33, 16, 4, 8
    q = jax.ShapeDtypeStruct((b, 32, 1, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((n_pages, 8, page, 128), jnp.bfloat16)
    table = jax.ShapeDtypeStruct((b, pp), jnp.int32)
    last = jax.ShapeDtypeStruct((b,), jnp.int32)
    _compile(lambda q, k, v, t, l: ops.paged_attention(
        q, k, v, t, l, backend="pallas"),
        *_on(one_chip, (q, kv, kv, table, last)))


def test_memory_analysis_reports_device_bytes(one_chip):
    """A described-chip compile also reports its device memory, which is
    what sizes a configuration before any chip run."""
    w = jax.ShapeDtypeStruct((D_MODEL, D_FF), jnp.float32)
    x = jax.ShapeDtypeStruct((M, D_MODEL), jnp.float32)
    compiled = _compile(lambda w, x: ops.matmul(x, w, backend="pallas"),
                        *_on(one_chip, (w, x)))
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= D_MODEL * D_FF * 4
    assert np.isfinite(mem.temp_size_in_bytes)

"""AOT compiles for a described TPU v5e chip: the serving path's kernel and
fused step at published widths must be accepted by the TPU compiler.

Nothing runs here; these compiles catch what interpret mode cannot (block
shapes against the (8, 128) tiling rule, VMEM and SMEM limits, Mosaic
lowering gaps).  The topology is described inside a fixture, never while
a module is imported, and every compile stays in this process.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels.paged_attention import ops
from repro.kernels.paged_attention.kernel import paged_attention_mixed
from repro.models.model import Model
from repro.serving.paged_runtime import PagedRuntime


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler to describe it with
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


# (arch, kv pool dtype): the served models' attention widths, with the
# int8 pools and their scales for the 3B model
@pytest.mark.parametrize("arch,pool_dtype", [
    ("stablelm_3b", "bfloat16"),
    ("stablelm_3b", "int8"),
    ("olmo2_7b", "bfloat16"),
])
def test_paged_kernel_compiles_at_published_widths(one_chip, arch,
                                                    pool_dtype):
    _compile_kernel(one_chip, arch, pool_dtype, rows=80, width=64,
                    pages=289)


@pytest.mark.parametrize("pool_dtype", ["bfloat16", "int8"])
def test_clamped_kernel_compiles_at_widest_bench_bucket(one_chip,
                                                        pool_dtype):
    """The kernel with its second scalar-prefetch operand (each lane's
    last live page, which clamps the page index map) and its dynamic page
    walk at the chat cell's widest fused-step bucket: 96 rows, tables at
    the full 128-page width (seq_cap 2048), 640 pages."""
    _compile_kernel(one_chip, "stablelm_3b", pool_dtype, rows=96, width=128,
                    pages=641)


def _compile_kernel(one_chip, arch, pool_dtype, *, rows, width, pages,
                    layers=4):
    """The kernel on a stack of ``layers`` lane-dense pools, read at a
    traced layer index, as the fused step calls it."""
    a = get_config(arch).attn
    page = 16
    lanes = lambda n: -(-n // 128) * 128
    pool = (layers, pages, a.num_kv_heads, page, lanes(a.head_dim))
    args = [_spec((rows, 1, a.num_heads, a.head_dim), "bfloat16", one_chip),
            _spec(pool, pool_dtype, one_chip),
            _spec(pool, pool_dtype, one_chip),
            _spec((rows, width), "int32", one_chip),
            _spec((rows, 1), "int32", one_chip),
            _spec((), "int32", one_chip)]
    if pool_dtype == "int8":
        scales = _spec((layers, pages, page, lanes(a.num_kv_heads)),
                       "float32", one_chip)
        args += [scales, scales]

        def fn(q, k, v, bt, qpos, layer, ks, vs):
            return paged_attention_mixed(q, k, v, bt, qpos, layer=layer,
                                         k_scales=ks, v_scales=vs)
    else:
        def fn(q, k, v, bt, qpos, layer):
            return paged_attention_mixed(q, k, v, bt, qpos, layer=layer)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _compile_step(one_chip, cfg, kv_dtype, pages):
    rt = PagedRuntime(cfg, None, max_slots=32, seq_cap=2048, page_size=16,
                      pool_pages=pages, kv_dtype=kv_dtype)
    place = lambda t: jax.tree.map(
        lambda x: _spec(x.shape, x.dtype, one_chip), t)
    rows, width, logits = 96, rt.pps, 32
    compiled = rt._mixed_fn.lower(
        place(Model(cfg).param_specs()), place(rt.pools),
        _spec((rows,), "int32", one_chip), _spec((rows,), "int32", one_chip),
        _spec((), "int32", one_chip), _spec((rows, width), "int32", one_chip),
        _spec((logits,), "int32", one_chip)).compile()
    return rt, compiled


# (arch, kv pool dtype, whether the whole step's temporaries fit under one
# layer's K pool): head_dim 80, padded to 128 lanes in the pools, and
# head_dim 128, unpadded, whose 4096-wide FFN weights the step relays out
# per layer (a layer_weights cost of ~180 MB that no pool op adds to)
@pytest.mark.parametrize("arch,kv_dtype,temp_under_layer_pool", [
    ("stablelm_3b", "auto", True),
    ("stablelm_3b", "int8", True),
    ("olmo2_7b", "auto", False),
])
def test_fused_step_compiles_at_published_widths(one_chip, monkeypatch,
                                                 arch, kv_dtype,
                                                 temp_under_layer_pool):
    """One fused mixed step at published widths, depth cut to 2 layers,
    at the chat cell's pool (640 pages) and widest bucket, with the
    Pallas kernel in it and the KV pools donated.  The pools are written
    and read in place: no op copies or dynamic-(update-)slices a layer's
    pool or the stack (or their flat row views), and the step's
    temporaries do not grow with the pool."""
    # the kernel path is chosen by asking for the default backend, which
    # is the CPU here: steer it to the chip's branch for this compile
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config(arch), repeats=2)
    rt, compiled = _compile_step(one_chip, cfg, kv_dtype, 640)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    leaves = jax.tree.leaves(rt.pools)
    # the donated pools alias the returned pools: no second pool copy
    assert mem.alias_size_in_bytes >= sum(x.nbytes for x in leaves)
    layer_k = rt.pools["period"]["sub0"]["k"].nbytes // cfg.repeats
    _, small = _compile_step(one_chip, cfg, kv_dtype, 64)
    grown = mem.temp_size_in_bytes - small.memory_analysis().temp_size_in_bytes
    assert grown < layer_k // 16
    if temp_under_layer_pool:
        assert mem.temp_size_in_bytes < layer_k
    # a layer's pool, the stack, and the flat row views of either
    shapes = set()
    for x in leaves:
        for s in (x.shape, x.shape[1:], (1,) + x.shape[1:]):
            shapes.add(",".join(map(str, s)))
            shapes.add(f"{int(np.prod(s[:-1]))},{s[-1]}")
    op = re.compile(r"^\s*(?:ROOT )?%([\w.-]+) = \w+\[([\d,]*)\]\S* "
                    r"([\w-]+)\(", re.M)
    moves = [m.group(0) for m in op.finditer(text)
             if m.group(2) in shapes
             and (m.group(3) in ("copy", "dynamic-slice",
                                 "dynamic-update-slice")
                  or "dynamic-slice" in m.group(1)
                  or "dynamic-update-slice" in m.group(1))]
    assert not moves, moves

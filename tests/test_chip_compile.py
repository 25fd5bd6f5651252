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
    a = get_config(arch).attn
    rows, width, pages, page = 80, 64, 289, 16
    pool = (pages, a.num_kv_heads, page, a.head_dim)
    args = [_spec((rows, 1, a.num_heads, a.head_dim), "bfloat16", one_chip),
            _spec(pool, pool_dtype, one_chip),
            _spec(pool, pool_dtype, one_chip),
            _spec((rows, width), "int32", one_chip),
            _spec((rows, 1), "int32", one_chip)]
    if pool_dtype == "int8":
        scales = _spec(pool[:3], "float32", one_chip)
        args += [scales, scales]

        def fn(q, k, v, bt, qpos, ks, vs):
            return paged_attention_mixed(q, k, v, bt, qpos, k_scales=ks,
                                         v_scales=vs)
    else:
        fn = paged_attention_mixed
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_step_compiles_at_published_widths(one_chip, monkeypatch):
    """One fused mixed step of stablelm_3b at published widths, depth cut
    to 2 layers, with the Pallas kernel in it and the KV pools donated."""
    # the kernel path is chosen by asking for the default backend, which
    # is the CPU here: steer it to the chip's branch for this compile
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("stablelm_3b"), repeats=2)
    rt = PagedRuntime(cfg, None, max_slots=8, seq_cap=1024, page_size=16,
                      pool_pages=64)
    place = lambda t: jax.tree.map(
        lambda x: _spec(x.shape, x.dtype, one_chip), t)
    rows, width, logits = 80, 64, 16
    compiled = rt._mixed_fn.lower(
        place(Model(cfg).param_specs()), place(rt.pools),
        _spec((rows,), "int32", one_chip), _spec((rows,), "int32", one_chip),
        _spec((), "int32", one_chip), _spec((rows, width), "int32", one_chip),
        _spec((logits,), "int32", one_chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    # the donated pools alias the returned pools: no second pool copy
    pool_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                     for x in jax.tree.leaves(rt.pools))
    assert mem.alias_size_in_bytes >= pool_bytes
    # every op that moves a whole layer's pool (a copy, or a dynamic-slice
    # or dynamic-update-slice fusion, of [(stack,) pages, KV, page, hd])
    # carries the kv_pool or kv_scatter scope in its op_name: a device
    # trace attributes the pool-threading cost by that scope.  Async
    # copy-start/-done pairs carry no metadata and take no time on the
    # op line (the transfer runs beside compute).
    a = cfg.attn
    pool = rf"\[(\d+,)?{rt.pool_pages + 1},{a.num_kv_heads},16,{a.head_dim}\]"
    moves = re.compile(rf"^\s*%(copy(?!-start|-done)[.\w-]*|[\w-]*dynamic-"
                       rf"(update-)?slice_fusion[.\d]*) = \w+{pool}.*$", re.M)
    found = [m.group(0) for m in moves.finditer(text)]
    assert found
    for line in found:
        op_name = re.search(r'op_name="([^"]*)"', line).group(1)
        assert "/kv_pool/" in op_name or "/kv_scatter/" in op_name, line

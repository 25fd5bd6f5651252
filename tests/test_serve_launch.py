"""serve() as a launcher: the width cut, sequence cap, per-tenant weights,
replica placement and the entry points' compile-cache location."""
import os

import jax
import pytest

from repro.launch import serve as serve_mod


def _serve(**kw):
    base = dict(requests=4, qps=500.0, prompt_len=16, max_new=2,
                with_controller=False, verbose=False)
    return serve_mod.serve(**{**base, **kw})


def test_replicas_share_their_tenants_weights():
    out = _serve(backend="paged", replicas=2, num_tenants=2)
    assert out["L0"]["completed"] == out["L1"]["completed"] == 4
    for name in ("L0", "L1"):
        a, b = out["engines"][name]
        assert a.device == b.device == jax.devices()[0]
        for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
            assert x.unsafe_buffer_pointer() == y.unsafe_buffer_pointer()
    # tenants keep their own weights
    x0 = jax.tree.leaves(out["engines"]["L0"][0].params)[0]
    x1 = jax.tree.leaves(out["engines"]["L1"][0].params)[0]
    assert x0.unsafe_buffer_pointer() != x1.unsafe_buffer_pointer()


@pytest.mark.parametrize("backend", ["dense", "paged"])
def test_seq_cap_and_reduced_are_parameters(backend):
    out = _serve(backend=backend, seq_cap=64)
    eng = out["engines"]["T1"][0]
    assert eng.seq_cap == 64
    assert eng.cfg.name.endswith("-reduced")
    if backend == "paged":
        assert eng.runtime.seq_cap == 64


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"])
def test_compile_cache_location(monkeypatch, env_dir):
    """The entry points honour JAX_COMPILATION_CACHE_DIR and otherwise
    use the fixed <checkout>/.jax_cache; importing sets nothing."""
    was = jax.config.jax_compilation_cache_dir
    # importing the launcher (above) left JAX's own setting alone
    assert was == os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        serve_mod.use_checkout_compile_cache()
        now = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    if env_dir is None:
        assert now == str(serve_mod.CHECKOUT / ".jax_cache")
        assert (serve_mod.CHECKOUT / "chip_smoke.py").exists()
    else:
        assert now == was

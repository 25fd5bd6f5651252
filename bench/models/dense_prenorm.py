"""A dense decoder of pre-RMSNorm blocks with full multi-head (or grouped)
attention: the sizes read from a configuration file, the program's
``ModelConfig`` for them, seeded weights, the plain float32 reference
forward pass and its int8 control, and the FLOP and byte counts the
per-layer metrics divide by.  A configuration file names this module
with ``"model": "dense_prenorm"``.

The reference is written from the configuration file alone.  It imports
nothing of the program and reads only the weights this module made: a
pre-RMSNorm decoder block (``x * rsqrt(mean(x^2) + eps) * (1 + w)``),
rotary embedding on the whole head (first half / second half pairing,
frequencies ``theta ** (-2i / head_dim)``), causal softmax attention
scaled by ``1 / sqrt(head_dim)``, a SiLU-gated feed-forward whose input
matrix holds the gate and the up projection side by side, a final norm
and an untied output head.  Departures of that block from the published
models are listed in each configuration file.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class Dims:
    """The sizes the harness needs, read from a configuration file."""
    name: str
    d_model: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    dtype: str

    @property
    def layer_matmul_params(self) -> int:
        """Weights of one layer's projections and feed-forward."""
        d, h, kv, hd, ff = (self.d_model, self.heads, self.kv_heads,
                            self.head_dim, self.d_ff)
        return d * h * hd * 2 + d * kv * hd * 2 + 3 * d * ff


def dims_of(conf: dict) -> Dims:
    """Sizes from a configuration file's published keys."""
    heads = conf["num_attention_heads"]
    if conf.get("tie_word_embeddings", False):
        raise ValueError(f"{conf['name']}: tied embeddings are not modelled")
    if conf.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{conf['name']}: only SiLU-gated FFNs are modelled")
    return Dims(
        name=conf["name"], d_model=conf["hidden_size"],
        layers=conf["num_hidden_layers"], heads=heads,
        kv_heads=conf.get("num_key_value_heads", heads),
        head_dim=conf.get("head_dim", conf["hidden_size"] // heads),
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        rope_theta=float(conf.get("rope_theta", 10_000.0)),
        norm_eps=float(conf.get("rms_norm_eps", conf.get("norm_eps", 1e-5))),
        dtype=conf["serve"]["dtype"])


def program_config(d: Dims):
    """The program's ``ModelConfig`` for these sizes (its interface)."""
    from repro.configs.base import (AttentionSpec, FFNSpec, LayerSpec,
                                    ModelConfig)
    return ModelConfig(
        name=d.name, family="dense", source="bench", d_model=d.d_model,
        vocab_size=d.vocab, period=(LayerSpec(mixer="attn", ffn="dense"),),
        repeats=d.layers,
        attn=AttentionSpec(num_heads=d.heads, num_kv_heads=d.kv_heads,
                           head_dim=d.head_dim, rope_theta=d.rope_theta),
        ffn=FFNSpec(kind="dense", d_ff=d.d_ff, activation="silu"),
        norm_eps=d.norm_eps, rope_theta=d.rope_theta, dtype=d.dtype)


def _key(seed: int, tag: int):
    """A threefry key from any non-negative whole number (64 bits and
    more): SeedSequence folds it to two 32-bit words."""
    words = np.random.SeedSequence([int(seed), tag]).generate_state(2)
    return jax.random.wrap_key_data(words.astype(np.uint32))


def weight_shapes(d: Dims) -> Dict:
    """The served weight tree: shapes and dtypes, in the program's layout."""
    L, dm, h, kv, hd, ff, v = (d.layers, d.d_model, d.heads, d.kv_heads,
                               d.head_dim, d.d_ff, d.vocab)
    w, f32 = d.dtype, "float32"
    return {
        "embed": ((v, dm), w), "final_norm": ((dm,), f32),
        "lm_head": ((dm, v), w),
        "period": {"sub0": {
            "norm1": ((L, dm), f32), "norm2": ((L, dm), f32),
            "attn": {"wq": ((L, dm, h, hd), w), "wk": ((L, dm, kv, hd), w),
                     "wv": ((L, dm, kv, hd), w), "wo": ((L, h, hd, dm), w)},
            "ffn": {"w_in": ((L, dm, 2, ff), w), "w_out": ((L, ff, dm), w)},
        }},
    }


def _init_scales(d: Dims) -> Dict[str, float]:
    """Standard deviation per leaf.  Residual branches end in matrices
    scaled by 1/sqrt(2 L), as GPT-2 initialises them, so the residual
    stream stays conditioned through all layers; the embedding has unit
    scale so every position's token still shows at the last layer."""
    branch = 1.0 / np.sqrt(2 * d.layers)
    return {"embed": 1.0, "final_norm": 0.1, "lm_head": d.d_model ** -0.5,
            "norm1": 0.1, "norm2": 0.1, "wq": d.d_model ** -0.5,
            "wk": d.d_model ** -0.5, "wv": d.d_model ** -0.5,
            "wo": (d.heads * d.head_dim) ** -0.5 * branch,
            "w_in": d.d_model ** -0.5, "w_out": d.d_ff ** -0.5 * branch}


def make_weights(d: Dims, seed: int, device=None):
    """Every weight from ``seed`` in one jitted call on ``device``, in the
    type it is served in."""
    scales = _init_scales(d)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        weight_shapes(d), is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[-1], str))

    def make(key):
        keys = jax.random.split(key, len(flat))
        out = [jax.random.normal(k, shape, jnp.dtype(dt)) *
               jnp.asarray(scales[path[-1].key], jnp.dtype(dt))
               for k, (path, (shape, dt)) in zip(keys, flat)]
        return jax.tree.unflatten(tree, out)

    sharding = (jax.sharding.SingleDeviceSharding(device)
                if device is not None else None)
    return jax.jit(make, out_shardings=sharding)(_key(seed, 1))


# ----------------------------------------------------------------- reference
def _int8(x, axis=-1):
    """Symmetric int8 rounding with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _exact(x, axis=-1):
    return x


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, pos, theta):
    """x [T, H, hd]; rotate (first half, second half) pairs."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv           # [T, hd/2]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * c - b * s, a * s + b * c], -1)


def reference_logits(d: Dims, weights, tokens, quant: Optional[str] = None):
    """Logits [T, V] in float32 for one sequence ``tokens`` [T], causal,
    layer by layer (a scan over the stacked layers, each upcast to
    float32 as it is used).

    ``quant='int8'`` is the control: every value the served model holds
    in its 16-bit type (weights, the residual stream, norm outputs, q, k,
    v, attention and feed-forward outputs) is rounded to int8 instead,
    one scale per row of activations and per output column of weights,
    and everything else is computed as in the reference."""
    if quant not in (None, "int8"):
        raise ValueError(f"unknown control precision {quant!r}")
    r = _int8 if quant == "int8" else _exact
    t = tokens.shape[0]
    f32 = jnp.float32
    pos = jnp.arange(t, dtype=jnp.int32)
    causal = pos[None, :] <= pos[:, None]
    g = d.heads // d.kv_heads

    def mm(x, w):
        return r(jnp.dot(x, r(w, 0), precision=HIGHEST))

    def layer(h, lw):
        lw = jax.tree.map(lambda a: a.astype(f32), lw)
        a, ffn = lw["attn"], lw["ffn"]
        x = r(_norm(h, lw["norm1"], d.norm_eps))
        q = mm(x, a["wq"].reshape(d.d_model, -1)).reshape(
            t, d.heads, d.head_dim)
        k = mm(x, a["wk"].reshape(d.d_model, -1)).reshape(
            t, d.kv_heads, d.head_dim)
        v = mm(x, a["wv"].reshape(d.d_model, -1)).reshape(
            t, d.kv_heads, d.head_dim)
        q, k = r(_rope(q, pos, d.rope_theta)), r(_rope(k, pos, d.rope_theta))
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
            / np.sqrt(d.head_dim)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = r(jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST).reshape(
            t, -1))
        h = r(h + mm(o, a["wo"].reshape(-1, d.d_model)))
        x = r(_norm(h, lw["norm2"], d.norm_eps))
        gu = mm(x, ffn["w_in"].reshape(d.d_model, -1))
        act = r(jax.nn.silu(gu[:, :d.d_ff]) * gu[:, d.d_ff:])
        return r(h + mm(act, ffn["w_out"])), None

    h0 = r(weights["embed"][tokens].astype(f32))
    h, _ = jax.lax.scan(layer, h0, weights["period"]["sub0"])
    h = r(_norm(h, weights["final_norm"].astype(f32), d.norm_eps))
    return jnp.dot(h, r(weights["lm_head"].astype(f32), 0),
                   precision=HIGHEST)


# --------------------------------------------------------------- FLOP counts
def row_flops(d: Dims, ctx: int) -> float:
    """Model FLOPs of one computed token row at context ``ctx`` (the row's
    position + 1): 2 x the layers' matmul weights, plus QK^T and PV."""
    return d.layers * (2.0 * d.layer_matmul_params
                       + 4.0 * d.heads * d.head_dim * ctx)


def logit_flops(d: Dims) -> float:
    return 2.0 * d.d_model * d.vocab


def attn_flops(d: Dims, ctx: int) -> float:
    """Paged-attention FLOPs one query row needs over ``ctx`` keys, all
    layers: QK^T and PV, 2 FLOPs a multiply-add each."""
    return 4.0 * d.layers * d.heads * d.head_dim * ctx


def attn_bytes(d: Dims, lane_ctx: int, lane_rows: int,
               kv_bytes: int = 2, act_bytes: int = 2) -> float:
    """Bytes the kernel needs for one lane, all layers: the lane's live K
    and V read once, plus its query rows in and their outputs out."""
    kv = 2.0 * lane_ctx * d.kv_heads * d.head_dim * kv_bytes
    qo = 2.0 * lane_rows * d.heads * d.head_dim * act_bytes
    return d.layers * (kv + qo)

"""Checkpointing: msgpack + zstd container for parameter/optimizer pytrees.

No orbax dependency — a flat path->array mapping with a JSON-ish manifest,
good enough for single-host saves and the last-known-good rollback the
controller's audit log requires.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import msgpack
import numpy as np
import zstandard


def _flatten(tree, prefix="") -> Dict[str, np.ndarray]:
    flat = {}
    paths_leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in paths_leaves:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        flat[key] = np.asarray(leaf)
    return flat


def save(path: str, tree: Any, metadata: Dict[str, Any] | None = None) -> int:
    flat = _flatten(tree)
    payload = {
        "metadata": metadata or {},
        "arrays": {
            k: {"dtype": str(v.dtype), "shape": list(v.shape),
                "data": v.tobytes()}
            for k, v in flat.items()
        },
    }
    raw = msgpack.packb(payload, use_bin_type=True)
    comp = zstandard.ZstdCompressor(level=3).compress(raw)
    tmp = path + ".tmp"
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(tmp, "wb") as f:
        f.write(comp)
    os.replace(tmp, path)
    return len(comp)


def load(path: str, like: Any | None = None) -> Tuple[Any, Dict[str, Any]]:
    """Returns (tree, metadata).  If ``like`` is given, restores its pytree
    structure; otherwise returns the flat dict."""
    with open(path, "rb") as f:
        blob = f.read()
    raw = zstandard.ZstdDecompressor().decompress(blob)
    payload = msgpack.unpackb(raw, raw=False)
    arrays = {
        k: np.frombuffer(v["data"],
                         dtype=np.dtype(v["dtype"])).reshape(v["shape"])
        for k, v in payload["arrays"].items()
    }
    if like is None:
        return arrays, payload["metadata"]
    flat_like = _flatten(like)
    assert set(flat_like) == set(arrays), "checkpoint/pytree key mismatch"
    leaves_paths, treedef = jax.tree_util.tree_flatten_with_path(like)
    restored = []
    for path_k, leaf in leaves_paths:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path_k)
        restored.append(jnp.asarray(arrays[key]).astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, restored), payload["metadata"]

"""Finds a configuration's model module, ``bench/models/<model>.py``.

A model module holds what belongs to one architecture: ``dims_of(conf)``
(the sizes), ``program_config(dims)`` (the program's ``ModelConfig``),
``make_weights(dims, seed, device)``, the plain float32
``reference_logits(dims, weights, tokens, quant)`` with its int8 control,
and the counts ``row_flops``, ``logit_flops``, ``attn_flops`` and
``attn_bytes``.  A configuration of a new architecture adds its own
module and names it in its file.
"""
from __future__ import annotations

from types import ModuleType

from harness.spec import BENCH, load_file


def load(conf: dict) -> ModuleType:
    name = conf["model"]
    return load_file(BENCH / "models" / f"{name}.py", f"bench_model_{name}")

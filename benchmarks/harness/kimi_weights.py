"""Seeded weights for the ``kimi_k2`` family (``reference/kimi_k2.py``
names the leaves), made on the device one leaf at a time, a stack of
experts one expert at a time, by ``harness/giga_weights.py``'s makers (it
says why). The program and the reference are handed the same rounded
values.

What is drawn how: matrices, the router and both tables N(0, 0.02) (the
head is untied); the router's bias N(0, 0.005) (PR 40's finding: at 0.05
the bias alone decides the choice among the sigmoids' flat end); a norm's
scale ``w`` of ``x / rms(x) * w`` is 1 + N(0, 0.02), drawn in float32 and
then rounded, so every scale is near 1 and none is 1: a path that drops
one changes the result.
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness.giga_weights import _maker  # noqa: E402
from harness.weights import key_from_seed  # noqa: E402
from reference.kimi_k2 import leaf_shapes  # noqa: E402


def kind_of(name: str, shape) -> str:
    if name.endswith(".f.bias"):
        return "bias"
    return "scale" if len(shape) == 1 else "normal"


def make(cfg: dict, seed: int, dtype="bfloat16") -> dict:
    """name -> array in ``dtype``: the values both sides compute from."""
    import jax
    import jax.numpy as jnp
    dtype_name = jnp.dtype(dtype).name
    shapes = leaf_shapes(cfg)
    out = {}
    with jax.enable_x64(False):
        keys = jax.random.key_data(jax.random.split(
            jax.random.wrap_key_data(key_from_seed(seed, stream=11)),
            len(shapes)))
        for i, (name, shape) in enumerate(shapes.items()):
            kind = kind_of(name, shape)
            if kind == "scale":
                w = _maker(tuple(shape), "normal", "float32")(keys[i])
                out[name] = (1.0 + w).astype(dtype_name)
            else:
                out[name] = _maker(tuple(shape), kind, dtype_name)(keys[i])
    return out

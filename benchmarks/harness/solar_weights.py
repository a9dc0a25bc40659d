"""Seeded weights for the ``solar_open2`` family
(``reference/solar_open2.py`` names the leaves), made on the device one
leaf at a time, a stack of experts one expert at a time, by
``harness/giga_weights.py``'s makers (it says why). The program and the
reference are handed the same rounded values.

What is drawn how (``kind_of``): matrices, the router, the convolutions'
taps, the output gate's bias ``gb.bias`` and both tables N(0, 0.02) (the
head is untied); the router's bias N(0, 0.005) (PR 40's finding: at 0.05
the bias alone decides the choice among the sigmoids' flat end); a norm's
scale ``w`` of ``x / rms(x) * w`` (a block's two, the linear heads'
``o_norm.w``, the final one) is 1 + N(0, 0.02), drawn in float32 and then
rounded, so every scale is near 1 and none is 1: a path that drops one
changes the result; ``A_log = ln A`` with ``A ~ U(0, 16)`` (floored at
1e-3), one a head, and ``dt_bias`` the inverse softplus of ``dt``
log-uniform in [1e-3, 0.1], one a KEY CHANNEL: the published initial ranges
of this layer's family, as the GigaChat cell draws them. A channel's log
decay a token is ``-A softplus(. + dt_bias)``, between some -1.6 and -1e-5,
and the 128 channels of a head differ by two orders: the spread that a
scalar decay cannot stand in for.
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness.giga_weights import _maker  # noqa: E402
from harness.weights import key_from_seed  # noqa: E402
from reference.solar_open2 import leaf_shapes  # noqa: E402


def kind_of(name: str) -> str:
    leaf = name.rsplit(".", 1)[-1]
    if leaf in ("A_log", "dt_bias"):
        return leaf
    if name.endswith(".f.bias"):
        return "bias"
    return "scale" if ".n." in name or name.endswith("norm.w") \
        or name == "norm_f.w" else "normal"


def make(cfg: dict, seed: int, dtype="bfloat16") -> dict:
    """name -> array in ``dtype``: the values both sides compute from."""
    import jax
    import jax.numpy as jnp
    dtype_name = jnp.dtype(dtype).name
    shapes = leaf_shapes(cfg)
    out = {}
    with jax.enable_x64(False):
        keys = jax.random.key_data(jax.random.split(
            jax.random.wrap_key_data(key_from_seed(seed, stream=13)),
            len(shapes)))
        for i, (name, shape) in enumerate(shapes.items()):
            kind = kind_of(name)
            if kind == "scale":
                w = _maker(tuple(shape), "normal", "float32")(keys[i])
                out[name] = (1.0 + w).astype(dtype_name)
            else:
                out[name] = _maker(tuple(shape), kind, dtype_name)(keys[i])
    return out

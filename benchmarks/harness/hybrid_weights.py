"""Seeded weights for the ``phi4flash`` family (``reference/phi4flash.py``
names the leaves), made on the device one leaf at a time.

At the published size the model is 3.85e9 parameters: 15.4 GB in float32,
which one chip cannot hold beside anything. So each leaf is drawn in
float32, rounded to the served type and kept in that type; the float32
draw is freed before the next leaf. The program and the reference are
handed the same rounded values, the reference widening one layer at a time.

What is drawn how (``kind_of``):

- matrices and the embedding: N(0, 0.02), as ``harness/weights.py``;
- biases N(0, 0.02), norm scales (LayerNorm and the pair norm) and the
  skip ``D`` 1 + N(0, 0.02) rather than 0 and 1, so that a path which drops
  one changes the result;
- the four ``lam`` vectors N(0, 0.1), the published initial range, so that
  ``lam`` differs from ``lambda_init`` and from layer to layer;
- the depthwise convolution's taps and bias U(-0.5, 0.5), PyTorch's default
  for a kernel of 4 (N(0, 0.02) taps would shrink the signal 25-fold
  before the recurrence);
- ``A_log`` = log(1..d_state) in every channel and ``dt_proj.b`` the
  inverse softplus of a step drawn log-uniformly from [0.001, 0.1]: Mamba's
  published initial ranges. With them a channel forgets over 1 to 1,000
  tokens and ``A`` is negative, so the state neither dies nor grows over
  2,048 tokens.
"""
from __future__ import annotations

import functools
import math
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness.weights import STD, key_from_seed  # noqa: E402
from reference.phi4flash import leaf_shapes  # noqa: E402

DT_MIN, DT_MAX = 1e-3, 1e-1


def kind_of(name: str) -> str:
    leaf = name.split(".", 2)[-1] if name.count(".") >= 2 else name
    if leaf == "A_log":
        return "a_log"
    if leaf == "dt_proj.b":
        return "dt_bias"
    if leaf in ("conv.w", "conv.b"):
        return "conv"
    if leaf == "lam":
        return "lam"
    if leaf == "D" or leaf.endswith(("ln1.w", "ln2.w", "subln.w")) \
            or name == "ln_f.w":
        return "one_plus"
    return "normal"


@functools.lru_cache(maxsize=None)
def _maker(shape, kind, dtype_name):
    import jax
    import jax.numpy as jnp

    def make(key_data):
        key = jax.random.wrap_key_data(key_data)
        if kind == "a_log":
            w = jnp.broadcast_to(
                jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)),
                shape)
        elif kind == "dt_bias":
            u = jax.random.uniform(key, shape, jnp.float32)
            dt = jnp.exp(u * (math.log(DT_MAX) - math.log(DT_MIN))
                         + math.log(DT_MIN))
            w = dt + jnp.log(-jnp.expm1(-dt))
        elif kind == "conv":
            w = jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)
        elif kind == "lam":
            w = 0.1 * jax.random.normal(key, shape, jnp.float32)
        else:
            w = STD * jax.random.normal(key, shape, jnp.float32)
            if kind == "one_plus":
                w = 1.0 + w
        return w.astype(dtype_name)

    return jax.jit(make)


def make(cfg: dict, seed: int, dtype="bfloat16") -> dict:
    """name -> array in ``dtype``: the values both sides compute from."""
    import jax
    import jax.numpy as jnp
    dtype_name = jnp.dtype(dtype).name
    shapes = leaf_shapes(cfg)
    with jax.enable_x64(False):
        keys = jax.random.key_data(jax.random.split(
            jax.random.wrap_key_data(key_from_seed(seed, stream=3)),
            len(shapes)))
        return {name: _maker(tuple(shape), kind_of(name), dtype_name)(keys[i])
                for i, (name, shape) in enumerate(shapes.items())}

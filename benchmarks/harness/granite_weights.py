"""Seeded weights for the ``granitemoehybrid`` family
(``reference/granitemoehybrid.py`` names the leaves), made on the device one
leaf at a time, a big leaf one layer at a time.

At the benchmark's cut the model is 4.76e9 parameters, 9.5 GB in bfloat16,
and its largest leaf (the held experts' first matrices of all ten layers,
``f.w1`` [10, 36, 4096, 1536]) is 4.5 GB: drawn whole in float32 it would
be 9 GB beside what is already made. So every stacked leaf is drawn under a
``lax.map`` over its leading axis: one layer's slice in float32, rounded to
the served type and written into the leaf, the float32 draw gone before the
next. The program and the reference are handed the same rounded values, the
reference widening one layer at a time.

What is drawn how (``kind_of``; Mamba-2's published initial ranges):

- matrices and the router: N(0, 0.02), as ``harness/weights.py``;
- the tied table: N(0, 0.02 / embedding_multiplier). The model multiplies a
  looked-up row by ``embedding_multiplier`` (12), so the stack's input then
  has the scale every other matrix has. A table at 0.02 would enter the
  stack twelve times larger than anything a layer adds to it, and under the
  tied head every position's largest logit would be the token that was fed
  in, by a margin no rounding moves: the served answer would repeat its
  last prompt token, and the comparison that decides ``correct`` would read
  0 for the program and for its fp8 control alike (it did: the first chip
  run of PR 31);
- norm scales (layer norms, the gated norm, the final norm) 1 + N(0, 0.02)
  rather than 1, so that a path which drops one changes the result;
- the depthwise convolution's taps and bias U(-0.5, 0.5), PyTorch's default
  for a kernel of 4;
- ``A_log`` = log U(1, 16) a head, ``dt_bias`` the inverse softplus of a
  step drawn log-uniformly from [0.001, 0.1], ``D`` = 1. With them a head
  forgets over 1 to 1,000 tokens and ``A`` is negative, so the state
  neither dies nor grows over 4,096 tokens.
"""
from __future__ import annotations

import functools
import math
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness.weights import STD, key_from_seed  # noqa: E402
from reference.granitemoehybrid import leaf_shapes  # noqa: E402

DT_MIN, DT_MAX = 1e-3, 1e-1
A_MIN, A_MAX = 1.0, 16.0


def kind_of(name: str) -> str:
    if name == "embed":
        return "table"
    leaf = name.split(".", 1)[-1]
    if leaf == "A_log":
        return "a_log"
    if leaf == "dt_bias":
        return "dt_bias"
    if leaf in ("conv.w", "conv.b"):
        return "conv"
    if leaf == "D":
        return "one"
    if name == "norm_f.w" or leaf in ("norm.w", "gnorm.w"):
        return "one_plus"
    return "normal"


def _draw(key, shape, kind, table_scale=1.0):
    import jax
    import jax.numpy as jnp
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, A_MIN,
                                          A_MAX))
    if kind == "dt_bias":
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(DT_MAX) - math.log(DT_MIN))
                     + math.log(DT_MIN))
        return dt + jnp.log(-jnp.expm1(-dt))
    if kind == "conv":
        return jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)
    if kind == "one":
        return jnp.ones(shape, jnp.float32)
    w = STD * jax.random.normal(key, shape, jnp.float32)
    if kind == "table":
        return w * table_scale
    return 1.0 + w if kind == "one_plus" else w


@functools.lru_cache(maxsize=None)
def _maker(shape, kind, dtype_name, stacked, table_scale):
    import jax

    def make(key_data):
        key = jax.random.wrap_key_data(key_data)
        if not stacked:
            return _draw(key, shape, kind, table_scale).astype(dtype_name)
        keys = jax.random.split(key, shape[0])
        return jax.lax.map(
            lambda k: _draw(k, shape[1:], kind).astype(dtype_name), keys)

    return jax.jit(make)


def make(cfg: dict, seed: int, dtype="bfloat16") -> dict:
    """name -> array in ``dtype``: the values both sides compute from."""
    import jax
    import jax.numpy as jnp
    dtype_name = jnp.dtype(dtype).name
    shapes = leaf_shapes(cfg)
    with jax.enable_x64(False):
        keys = jax.random.key_data(jax.random.split(
            jax.random.wrap_key_data(key_from_seed(seed, stream=5)),
            len(shapes)))
        scale = 1.0 / float(cfg["embedding_multiplier"])
        return {name: _maker(tuple(shape), kind_of(name), dtype_name,
                             name[:2] in ("m.", "a.", "f."), scale)(keys[i])
                for i, (name, shape) in enumerate(shapes.items())}

"""Seeded weights for the ``gigachat3_5`` family
(``reference/gigachat35.py`` names the leaves), made on the device one leaf
at a time, a stack of experts one expert at a time
(``harness/dots3_weights.py`` says why: a layer's 16 held experts' first
matrices are 0.94 GB in bfloat16 and would be 1.9 GB drawn whole in
float32). The program and the reference are handed the same rounded values.

What is drawn how (``kind_of``):

- matrices, the router, the convolution's taps and both tables: N(0, 0.02).
  The head is untied and every block's output passes a norm of its own
  (``pre_post``), so no matrix's scale is anyone's margin;
- the zero-centred norm weights (``w`` of ``N_w(x) = x / rms(x) * 2
  sigmoid(w)``, the linear layers' ``o_norm.w`` of ``1 + w``): N(0, 0.02),
  so every scale is near 1 and none is 1: a path that drops one changes
  the result;
- ``A_log = ln A`` with ``A ~ U(0, 16)`` (floored at 1e-3) and ``dt_bias``
  the inverse softplus of ``dt`` log-uniform in [1e-3, 0.1]: the published
  initial ranges of this layer's family (Gated DeltaNet / Mamba-2). A
  head's log decay a token is ``-A softplus(a + dt_bias)``, between some
  -1.6 and -1e-5: its state lives for tens to thousands of tokens, so a
  state dropped at a chunk boundary shows a long way on;
- the router's bias N(0, 0.005): PR 40's finding (at 0.05 the bias alone
  decides the choice among 256 sigmoids in the flat end, and the share of
  the assignments this chip holds swings by the seed).
"""
from __future__ import annotations

import functools
import math
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness.weights import STD, key_from_seed  # noqa: E402
from reference.gigachat35 import leaf_shapes  # noqa: E402

BIAS_STD = 0.005
A_MIN, A_MAX = 1e-3, 16.0
DT_MIN, DT_MAX = 1e-3, 0.1


def kind_of(name: str) -> str:
    leaf = name.rsplit(".", 1)[-1]
    if leaf in ("A_log", "dt_bias"):
        return leaf
    return "bias" if name.endswith(".f.bias") else "normal"


def _draw(key, shape, kind):
    import jax
    import jax.numpy as jnp
    if kind == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, A_MIN,
                                          A_MAX))
    if kind == "dt_bias":
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(DT_MAX) - math.log(DT_MIN))
                     + math.log(DT_MIN))
        return dt + jnp.log(-jnp.expm1(-dt))
    w = jax.random.normal(key, shape, jnp.float32)
    return BIAS_STD * w if kind == "bias" else STD * w


@functools.lru_cache(maxsize=None)
def _maker(shape, kind, dtype_name):
    import jax

    def make(key_data):
        key = jax.random.wrap_key_data(key_data)
        if len(shape) < 3:
            return _draw(key, shape, kind).astype(dtype_name)
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
            jax.random.wrap_key_data(key_from_seed(seed, stream=9)),
            len(shapes)))
        return {name: _maker(tuple(shape), kind_of(name), dtype_name)(keys[i])
                for i, (name, shape) in enumerate(shapes.items())}

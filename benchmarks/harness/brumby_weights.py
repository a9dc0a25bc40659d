"""Seeded weights for the ``brumby`` family (``reference/brumby.py`` names
the leaves), made on the device one leaf at a time, a stacked leaf one
layer at a time (``harness/granite_weights.py`` says why: the largest leaf,
the MLP's first matrices of all eight layers, is 2.85 GB in bfloat16 and
would be 5.7 GB drawn whole in float32). The program and the reference are
handed the same rounded values, the reference widening one layer at a time.

What is drawn how (``kind_of``):

- matrices, the embedding and the untied head: N(0, 0.02), as
  ``harness/weights.py``;
- norm scales (both layer norms, the per-head q and k norms, the final
  norm) 1 + N(0, 0.02) rather than 1, so that a path which drops one
  changes the result;
- the gate's matrix ``l.gate.w``: N(0, 0.004). The normed hidden state has
  unit mean square over 5,120 values, so ``a W_g`` has a standard deviation
  near 0.29 and a token moves ``log g`` by tens of percent, not by orders;
- the gate's bias ``l.gate.b`` (THIS REPO'S OWN: a checkpoint without one
  loads 0): with a zero-mean matrix and no bias ``g`` centres on 0.5 and the
  state forgets in two tokens, so that no run could tell a carried state
  from a dropped one. The bias is ``-log(expm1(lam))`` with ``lam`` = -log g
  drawn log-uniformly from [1e-4, 1e-2] a head and layer: half-lives of 70
  to 7,000 tokens, across a prompt of 1,024-4,096 and its answer.
"""
from __future__ import annotations

import functools
import math
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness.weights import STD, key_from_seed  # noqa: E402
from reference.brumby import leaf_shapes  # noqa: E402

GATE_STD = 0.004
LAM_MIN, LAM_MAX = 1e-4, 1e-2


def kind_of(name: str) -> str:
    if name == "l.gate.b":
        return "gate_bias"
    if name == "l.gate.w":
        return "gate"
    return "one_plus" if "norm" in name else "normal"


def _draw(key, shape, kind):
    import jax
    import jax.numpy as jnp
    if kind == "gate_bias":
        u = jax.random.uniform(key, shape, jnp.float32)
        lam = jnp.exp(u * (math.log(LAM_MAX) - math.log(LAM_MIN))
                      + math.log(LAM_MIN))
        return -jnp.log(jnp.expm1(lam))
    w = jax.random.normal(key, shape, jnp.float32)
    if kind == "gate":
        return GATE_STD * w
    return 1.0 + STD * w if kind == "one_plus" else STD * w


@functools.lru_cache(maxsize=None)
def _maker(shape, kind, dtype_name, stacked):
    import jax

    def make(key_data):
        key = jax.random.wrap_key_data(key_data)
        if not stacked:
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
            jax.random.wrap_key_data(key_from_seed(seed, stream=6)),
            len(shapes)))
        return {name: _maker(tuple(shape), kind_of(name), dtype_name,
                             name.startswith("l."))(keys[i])
                for i, (name, shape) in enumerate(shapes.items())}

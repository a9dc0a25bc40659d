"""Seeded weights for the ``dots3_note`` family
(``reference/dots3note.py`` names the leaves), made on the device one leaf
at a time, a stack of experts one expert at a time.

At the benchmark's cut the model is 4.09e9 parameters, 8.17 GB in bfloat16,
and its largest leaf (a layer's 32 held experts' first matrices, ``L<i>.f.w1``
[32, 5120, 3072]) is 1.0 GB: drawn whole in float32 it would be 2 GB beside
what is already made. So a three-dimensional leaf is drawn under a
``lax.map`` over its leading axis: one expert's slice in float32, rounded to
the served type and written into the leaf, the float32 draw gone before the
next. The program and the reference are handed the same rounded values.

What is drawn how (``kind_of``):

- matrices, the router and both tables: N(0, 0.02), as
  ``harness/weights.py``. The head is untied and blocks are pre-norm, so the
  table's scale is no one's margin (``harness/granite_weights.py`` says what
  a tied table at the wrong scale did);
- the full layers' ``W_uq``: N(0, 0.01). The indexer of a seeded model is
  not trained to find the keys its attention weighs, so a key at the edge
  of the 2,048 carries as much of a head as any other, and the few keys
  that fall in or out of the selection between bf16 and float32 arithmetic
  move the output by what they weigh. At 0.02 the scores' deviation is 2
  (some 40 keys carry a head), the sound program's logits lay a tenth of
  the largest from the reference's on average, and the comparison read
  0.20-0.48 for it against 0.56 for fp8; at 0.01 the deviation is 1 (some
  750 keys; still far from uniform) and the worst logit's distance halved
  (my chip run, PR 40: PERF.md section 6);
- norm scales (the blocks', the latents', the index key's, the final one)
  1 + N(0, 0.02) and the index key's LayerNorm bias N(0, 0.02) rather than 1
  and 0, so that a path which drops one changes the result;
- the router's ``noaux_tc`` bias N(0, 0.005): small beside what parts the
  scores at the cut and non-zero. The 8 largest of 256 sigmoids of logits
  N(0, 1.43) lie in the sigmoid's flat end, within some 0.03 of one another,
  so a bias of N(0, 0.05), the first tried, decided the choice alone: a held
  expert got 16,000 to 295,000 rows a run, this chip's share of the
  assignments read 12.0 to 15.5% by the seed, and the decode step's expert
  product (it reads the experts HIT) and ``out_tokens_per_s`` with it moved
  1% from seed to seed (my chip run, PR 40: PERF.md section 2). At 0.005
  the bias still changes the chosen set of nearly half the tokens, so a
  router that drops it reads wrong, and the seeds' shares lie within 12.3
  to 13.0% (a draw of 20,000 Gaussian inputs on the CPU, eight seeds; at
  0.05 the same draw reads 10.1 to 18.3%).

With these and the latent rescale (``assumed`` of the configuration) a full
layer's attention scores over its chosen keys have a standard deviation
near 1, a sliding layer's near 2 (softmaxes far from uniform), and the
indexer's scores do not depend on position: what the run's ``selection``
line shows.
"""
from __future__ import annotations

import functools
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness.weights import STD, key_from_seed  # noqa: E402
from reference.dots3note import leaf_shapes  # noqa: E402

BIAS_STD = 0.005
FULL_UQ_STD = 0.01


def kind_of(name: str, full_layers=()) -> str:
    if name.endswith("norm.w") or name == "norm_f.w":
        return "one_plus"
    if name.endswith(".a.uq") and int(name[1:].split(".")[0]) in full_layers:
        return "full_uq"
    return "bias" if name.endswith(".f.bias") else "normal"


def _draw(key, shape, kind):
    import jax
    import jax.numpy as jnp
    w = jax.random.normal(key, shape, jnp.float32)
    if kind == "bias":
        return BIAS_STD * w
    if kind == "full_uq":
        return FULL_UQ_STD * w
    return 1.0 + STD * w if kind == "one_plus" else STD * w


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
    full = tuple(i for i, k in enumerate(
        cfg["layer_types"][:cfg["num_hidden_layers"]])
        if k == "full_attention")
    with jax.enable_x64(False):
        keys = jax.random.key_data(jax.random.split(
            jax.random.wrap_key_data(key_from_seed(seed, stream=7)),
            len(shapes)))
        return {name: _maker(tuple(shape), kind_of(name, full),
                             dtype_name)(keys[i])
                for i, (name, shape) in enumerate(shapes.items())}

"""Three reference training steps and the numbers a training cell compares.

Runs ``reference/gpt2.py`` from the benchmark's own seeded weights over the
same batch: float32, highest precision, AdamW, ``row_block`` rows at a time.
``precision`` other than "f32" is the control: the same steps with every
matrix multiplication's operands rounded one step below what the
configuration states. Returns the three losses, each leaf's gradient norm at
step 1 and each leaf's norm of the parameters' change after step 3, keyed
by the program's parameter names (per layer).
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness.weights import TOP_NAMES  # noqa: E402
from reference import gpt2  # noqa: E402


def leaf_norms(tree: dict) -> dict:
    """{"top","blocks"} of arrays -> {program parameter name: norm}; a
    stacked block leaf gives one norm per layer."""
    import jax.numpy as jnp
    out = {}
    for k, v in tree["top"].items():
        out[TOP_NAMES[k]] = float(jnp.sqrt(jnp.sum(v.astype(jnp.float32) ** 2)))
    for k, v in tree["blocks"].items():
        v = v.astype(jnp.float32)
        per = np.asarray(jnp.sqrt(jnp.sum(
            v.reshape(v.shape[0], -1) ** 2, axis=1)))
        for i, n in enumerate(per):
            out[f"gpt.h.{i}.{k}"] = float(n)
    return out


def run(weights, ids, labels, num_heads, opt: dict, *, precision="f32",
        row_block=2, steps=3):
    """``weights`` float32 {"top","blocks"}; ``ids``/``labels`` int32
    [B, S]."""
    import jax
    import jax.numpy as jnp
    hp = dict(lr=opt["learning_rate"], beta1=opt["beta1"],
              beta2=opt["beta2"], eps=opt["epsilon"],
              weight_decay=opt["weight_decay"])

    def one(w, state, t):
        loss, g = gpt2.loss_and_grads(w, ids, labels, num_heads, precision,
                                      row_block)
        new_w, state = gpt2.adamw_update(w, g, state, t, **hp)
        return loss, g, new_w, state

    with jax.enable_x64(False):
        step = jax.jit(one, donate_argnums=(1,))
        w0 = weights
        state = gpt2.adamw_init(w0)
        w, losses, grad_norms = w0, [], None
        for t in range(1, steps + 1):
            loss, g, w_new, state = step(w, state, jnp.float32(t))
            losses.append(float(loss))
            if t == 1:
                grad_norms = leaf_norms(g)
            del g
            w = w_new
        delta = jax.jit(lambda a, b: jax.tree_util.tree_map(
            jnp.subtract, a, b))(w, w0)
        return {"losses": losses, "grad_norms": grad_norms,
                "delta_norms": leaf_norms(delta)}

"""Seeded GPT-2 weights, made on the device in one jitted call.

The benchmark makes the weights and hands them to both sides: the program
gets them under its own parameter names, the plain reference in the layout
of ``reference/gpt2.py``. Values are float32; a configuration that serves
or trains in bfloat16 rounds them itself, and the reference is given the
values as rounded, so both compute from the same numbers.

Matrices and embeddings are N(0, 0.02) as published. Biases are N(0, 0.02)
and layer-norm scales 1 + N(0, 0.02) rather than the published 0 and 1, so
that a path which drops a bias or a scale changes the result.
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from reference.gpt2 import BLOCK_LEAVES  # noqa: E402

STD = 0.02
# the reference's top-level leaves under the program's state-dict names
TOP_NAMES = {"wte": "gpt.wte.weight", "wpe": "gpt.wpe.weight",
             "ln_f.weight": "gpt.ln_f.weight", "ln_f.bias": "gpt.ln_f.bias"}


def key_from_seed(seed: int, stream: int = 0):
    """A raw threefry key from any whole number: high and low 32 bits, with
    ``stream`` folded into the high word (seeds past 2**31 are fine)."""
    import jax.numpy as jnp
    seed = int(seed)
    hi = ((seed >> 32) ^ (stream * 0x9E3779B9)) & 0xFFFFFFFF
    return jnp.asarray([hi, seed & 0xFFFFFFFF], jnp.uint32)


def shapes(cfg: dict, vocab_rows: int) -> dict:
    h, i, nl = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    block = {
        "ln_1.weight": (h,), "ln_1.bias": (h,),
        "attn.qkv_proj.weight": (h, 3 * h), "attn.qkv_proj.bias": (3 * h,),
        "attn.out_proj.weight": (h, h), "attn.out_proj.bias": (h,),
        "ln_2.weight": (h,), "ln_2.bias": (h,),
        "mlp.fc_in.weight": (h, i), "mlp.fc_in.bias": (i,),
        "mlp.fc_out.weight": (i, h), "mlp.fc_out.bias": (h,),
    }
    return {"top": {"wte": (vocab_rows, h), "wpe": (cfg["n_positions"], h),
                    "ln_f.weight": (h,), "ln_f.bias": (h,)},
            "blocks": {k: (nl,) + block[k] for k in BLOCK_LEAVES}}


def make(cfg: dict, vocab_rows: int, seed: int, round_to=None):
    """``{"top", "blocks"}`` float32 weights from ``seed`` in one jitted
    call. ``round_to`` (a dtype name) rounds every value through that type
    and back, for configurations that hold their weights in it."""
    import jax
    import jax.numpy as jnp
    shp = shapes(cfg, vocab_rows)
    flat = [(g, k, s) for g in ("top", "blocks") for k, s in shp[g].items()]

    def build(key_data):
        keys = jax.random.split(jax.random.wrap_key_data(key_data), len(flat))
        out = {"top": {}, "blocks": {}}
        for (g, k, s), key in zip(flat, keys):
            w = STD * jax.random.normal(key, s, jnp.float32)
            if k.startswith("ln_") and k.endswith(".weight"):
                w = 1.0 + w
            if round_to is not None:
                w = w.astype(round_to).astype(jnp.float32)
            out[g][k] = w
        return out

    with jax.enable_x64(False):
        return jax.jit(build)(key_from_seed(seed, stream=1))


def program_names(weights: dict) -> dict:
    """The same arrays under ``paddle_tpu.models.gpt``'s state-dict names:
    ``gpt.wte.weight``, ``gpt.h.<i>.<leaf>`` and so on (per-layer slices)."""
    top, blocks = weights["top"], weights["blocks"]
    out = {name: top[k] for k, name in TOP_NAMES.items()}
    nl = next(iter(blocks.values())).shape[0]
    for k, stacked in blocks.items():
        for i in range(nl):
            out[f"gpt.h.{i}.{k}"] = stacked[i]
    return out


def from_program_names(named: dict, nl: int) -> dict:
    """Inverse of :func:`program_names` over host arrays (float32)."""
    f = lambda a: np.asarray(a, np.float32)  # noqa: E731
    top = {k: f(named[name]) for k, name in TOP_NAMES.items()}
    blocks = {k: np.stack([f(named[f"gpt.h.{i}.{k}"]) for i in range(nl)])
              for k in BLOCK_LEAVES}
    return {"top": top, "blocks": blocks}

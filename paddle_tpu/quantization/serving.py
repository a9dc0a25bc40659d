"""Weight-only int8 for the serving runtime.

`paddle_tpu/quantization/__init__.py` produces QAT/PTQ abs-max scales at the
LAYER level; this module is the runtime half for the decode stack: the GPT
matmul leaves (qkv/out projections, MLP up/down) convert to int8 with
per-output-channel f32 scales, and every compiled program that consumes the
params dict — the engine's decode/prefill/verify steps, `fast_generate` —
dequantizes AT USE inside the same AOT programs. Nothing about program
identity changes: a :class:`QuantizedLeaf` is a registered jax pytree node,
so the quantized dict traces/lowers exactly like the float one (same program
count, zero extra recompiles — pinned by tests/test_no_retrace.py).

Embeddings (wte/wpe) and LayerNorm params stay full width: wte doubles as
the LM head and its quantization error lands directly on every logit, while
the matmul weights dominate the bytes (docs/QUANTIZATION.md).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.observability import metrics

__all__ = ["QuantizedLeaf", "quantize_gpt_params", "GPT_MATMUL_SUFFIXES",
           "QUANT_LOGIT_BOUND", "margin_gated_parity"]

# docs/QUANTIZATION.md "Parity bounds" — the documented int8-vs-f32 logit
# contract, consumed by bench.py (bench_quant + --smoke kv_quant_ok) and
# tests/test_quantization.py so the contract cannot drift between them
QUANT_LOGIT_BOUND = 0.5


def margin_gated_parity(lg_f, lg_q, bound=QUANT_LOGIT_BOUND):
    """-> ``(max_abs_diff, ok)`` under the documented parity contract:
    quantized logits within ``bound`` of f32, and top-1 tokens identical
    wherever f32's top-2 margin clears twice the bound (a margin inside
    2x the bound means quantization noise could legitimately flip the
    argmax — those positions are not parity evidence either way).
    Accepts any ``[..., vocab]`` logit shape; gates per trailing row."""
    diff = float(jnp.max(jnp.abs(lg_f - lg_q)))
    flat_f = lg_f.reshape(-1, lg_f.shape[-1])
    flat_q = lg_q.reshape(-1, lg_q.shape[-1])
    top2 = jnp.sort(flat_f, axis=-1)[:, -2:]
    gated = (top2[:, 1] - top2[:, 0]) > 2 * bound
    same = jnp.argmax(flat_f, axis=-1) == jnp.argmax(flat_q, axis=-1)
    ok = diff <= bound and bool(jnp.all(jnp.where(gated, same, True)))
    return diff, ok

# the matmul leaves that convert ([in, out] per layer, or [nl, in, out]
# stacked) — everything else passes through untouched
GPT_MATMUL_SUFFIXES = (
    "attn.qkv_proj.weight", "attn.out_proj.weight",
    "mlp.fc_in.weight", "mlp.fc_out.weight",
)


@jax.tree_util.register_pytree_node_class
class QuantizedLeaf:
    """int8 weight + broadcast-ready per-output-channel f32 scale.

    ``dequant()`` reproduces the float weight (within the abs-max rounding
    bound) in the ORIGINAL dtype — the decode math calls it at every use
    site (`models/gpt.py::_deq`), so the dequantization happens in-program
    on whatever device/sharding the leaf landed with."""

    def __init__(self, q, scale, dtype_name: str):
        self.q = q                   # int8, original weight shape
        self.scale = scale           # f32, shape [1, ..., out] (broadcasts)
        self.dtype_name = dtype_name

    def dequant(self):
        return (self.q.astype(jnp.float32) * self.scale).astype(
            jnp.dtype(self.dtype_name))

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):  # the dtype consumers compute in, not the storage dtype
        return jnp.dtype(self.dtype_name)

    @property
    def nbytes(self):
        return int(self.q.size) + 4 * int(np.prod(self.scale.shape))

    def tree_flatten(self):
        return (self.q, self.scale), self.dtype_name

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux)

    def __repr__(self):
        return (f"QuantizedLeaf(shape={tuple(self.q.shape)}, "
                f"dtype={self.dtype_name})")


def _quantize_leaf(arr) -> QuantizedLeaf:
    """Per-output-channel abs-max int8: channel = the LAST axis (the output
    features of every GPT matmul leaf, layer-stacked or not). Per-layer
    granularity is preserved for stacked ``[nl, in, out]`` leaves — the
    scale keeps every axis except the contraction axis."""
    from paddle_tpu.quantization.comms import absmax_int8
    a = jnp.asarray(arr)
    # reduce ONLY the contraction axis (second to last): scale shape
    # [..., 1, out] broadcasts straight back onto the weight
    q, s = absmax_int8(a, axis=-2, keepdims=True)
    sharding = getattr(a, "sharding", None)
    if sharding is not None and getattr(sharding, "spec", None) is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        spec = sharding.spec
        if any(x is not None for x in spec):
            # int8 values keep the float leaf's placement exactly; the
            # scale drops the (now size-1) contraction axis' shard. A
            # PartitionSpec may be shorter than the leaf's rank (trailing
            # axes replicated) — right-pad before indexing from the end,
            # or a rank-1 ('mp',) spec on a 2D leaf would land its shard
            # on the scale's size-1 contraction axis
            q = jax.device_put(q, sharding)
            sspec = list(spec) + [None] * (a.ndim - len(spec))
            sspec[-2] = None
            s = jax.device_put(s, NamedSharding(sharding.mesh,
                                                PartitionSpec(*sspec)))
    return QuantizedLeaf(q, s, str(a.dtype))


def _quantize(v):
    """One matmul leaf, or the served layout's tuple of them, a layer each
    (`models/gpt.py::serving_params`)."""
    return tuple(map(_quantize_leaf, v)) if isinstance(v, tuple) \
        else _quantize_leaf(v)


def _is_matmul_key(key: str) -> bool:
    return any(key.endswith(suf) for suf in GPT_MATMUL_SUFFIXES)


def quantize_gpt_params(params, dtype: str = "int8"):
    """Convert a GPT params pytree's matmul leaves to int8 + per-channel
    scales, in place of the float arrays. Accepts BOTH weight layouts:

    - a flat dict whose matmul leaves are found by the END of their names:
      the served layout the decode engine and `fast_generate` consume
      (`models/gpt.py::serving_params`: ``blocks.attn.qkv_proj.weight`` ...,
      a tuple of ``[in, out]`` arrays, a layer each) or a per-layer
      state_dict (``gpt.h.<i>.attn.qkv_proj.weight`` ...), and
    - the stacked ``{"blocks": {suffix: [nl, ...]}, "top": {...}}`` layout
      from `models/gpt.py::stack_gpt_params` — the per-leaf mp/sp shardings
      survive (int8 values keep the leaf's NamedSharding; the scale drops
      the contraction axis' shard).

    Returns a NEW dict of the same layout where each matmul leaf is a
    :class:`QuantizedLeaf`; everything else is passed through by reference.
    The conversion wall is observed as ``engine.quant_dequant_ms``."""
    if dtype != "int8":
        raise ValueError(f"weight_dtype={dtype!r}: only 'int8' is "
                         "implemented (fp8 needs hardware this container "
                         "does not model)")
    t0 = time.perf_counter()
    if set(params.keys()) == {"blocks", "top"}:
        out = {"blocks": {suf: (_quantize_leaf(v) if suf in
                                GPT_MATMUL_SUFFIXES else v)
                          for suf, v in params["blocks"].items()},
               "top": dict(params["top"])}
    else:
        out = {k: (_quantize(v) if _is_matmul_key(k) else v)
               for k, v in params.items()}
    metrics.histogram("engine.quant_dequant_ms").observe(
        (time.perf_counter() - t0) * 1e3)
    return out

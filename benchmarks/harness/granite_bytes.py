"""Bytes and operations a serving step of the ``granitemoehybrid`` family
must move and make, counted from the configuration's sizes: what the
roofline shares of its cell divide by the chip's published peaks. Nothing
here is measured.

A decode step must read every weight this chip holds once (the tied table
once, for the head; the HELD experts of every layer: at 64 tokens and 10
experts a token a held expert is missed with probability 7e-5), the K and V
of every live token in each attention layer, and must read and write each
live sequence's recurrent state in each Mamba layer.

Live sequences and tokens come from the requests' own marks, as in
``harness/hybrid_bytes.py::live``.
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness.hybrid_bytes import WIDTH, live as _live  # noqa: E402
from reference.granitemoehybrid import param_count, sizes  # noqa: E402


def _served(cfg: dict) -> int:
    return WIDTH[cfg["serve"]["precision"]]


def live(records, t_open: float, t_close: float) -> dict:
    """Means over the measured window: decoding sequences and their
    tokens."""
    out = _live(records, t_open, t_close, 1 << 62)
    return {"sequences": out["sequences"], "tokens": out["tokens"]}


def weight_bytes(cfg: dict) -> int:
    return param_count(cfg) * _served(cfg)


def mamba_layers(cfg: dict) -> int:
    return sizes(cfg).types.count("mamba")


def attention_layers(cfg: dict) -> int:
    return sizes(cfg).types.count("attention")


def expert_params(cfg: dict) -> int:
    """One routed expert: its gated first matrix and its second."""
    s = sizes(cfg)
    return s.d * 2 * s.f + s.f * s.d


def layer_params_outside_experts(cfg: dict, kind: str) -> int:
    """One layer without its routed experts: the mixer, the router, the
    shared expert and the two norms."""
    s = sizes(cfg)
    ffn = s.d * s.experts + s.d * 2 * s.fs + s.fs * s.d + s.d
    if kind == "attention":
        qw, kvw = s.nq * s.hd, s.nkv * s.hd
        return s.d + s.d * (qw + 2 * kvw) + qw * s.d + ffn
    return (s.d + s.d * (s.di + s.cd + s.mh) + s.dc * s.cd + s.cd
            + 3 * s.mh + s.di + s.di * s.d + ffn)


def kv_bytes_per_token_layer(cfg: dict) -> int:
    """K and V of one token in one attention layer."""
    s = sizes(cfg)
    return 2 * s.nkv * s.hd * _served(cfg)


def state_bytes_per_sequence_layer(cfg: dict) -> int:
    """Convolution state (served type) and SSM state of one sequence in one
    Mamba layer."""
    s = sizes(cfg)
    return (s.dc - 1) * s.cd * WIDTH[cfg["serve"]["conv_state"]] \
        + s.mh * s.mp * s.ms * WIDTH[cfg["serve"]["ssm_state"]]


def kv_bytes(cfg: dict, live_tokens: float) -> float:
    return attention_layers(cfg) * kv_bytes_per_token_layer(cfg) \
        * live_tokens


def moe_experts_bytes(cfg: dict) -> float:
    """Every held expert of every layer read once, and the routers."""
    s = sizes(cfg)
    return len(s.types) * (s.n_held * expert_params(cfg)
                           + s.d * s.experts) * _served(cfg)


def moe_first_bytes(cfg: dict) -> float:
    """The FIRST (gated) matrix of every held expert of every layer: what
    the first of the masked dense product's two products reads."""
    s = sizes(cfg)
    return len(s.types) * s.n_held * s.d * 2 * s.f * _served(cfg)


def moe_first_flops(cfg: dict, tokens: int) -> float:
    """The first product's needed operations: ``tokens`` x experts a token,
    of which the share this chip holds lands here; two a weight."""
    s = sizes(cfg)
    return len(s.types) * 2.0 * tokens * s.top_k * (s.n_held / s.experts) \
        * s.d * 2 * s.f


def ssm2_update_bytes(cfg: dict, live_sequences: float) -> float:
    """The recurrent state of every live sequence read and written once in
    every Mamba layer."""
    return 2 * mamba_layers(cfg) * state_bytes_per_sequence_layer(cfg) \
        * live_sequences


def ssm2_scan_bytes(cfg: dict, chunk_tokens: int) -> float:
    """One prefill launch through every Mamba layer's scan: dt, x, B and C
    read, y written (float32), the sequence's state read and written."""
    s = sizes(cfg)
    per_layer = chunk_tokens * (2 * s.di + s.mh + 2 * s.ms) * 4 \
        + 2 * s.mh * s.mp * s.ms * WIDTH[cfg["serve"]["ssm_state"]]
    return mamba_layers(cfg) * per_layer


def ssm2_scan_flops(cfg: dict, chunk_tokens: int, block: int) -> float:
    """The chunked dual form's products for one launch, two operations a
    multiply-add: inside each block of ``block`` tokens C B^T (block x
    block x state), the masked mix times the inputs (heads x block x block
    x head width), the block's closing state and the carried state's
    output (heads x head width x state x block each)."""
    s = sizes(cfg)
    q = min(block, chunk_tokens)
    blocks = -(-chunk_tokens // q)
    per_block = 2.0 * (q * q * s.ms + s.mh * q * q * s.mp
                       + 2 * s.mh * s.mp * s.ms * q)
    return mamba_layers(cfg) * blocks * per_block


def trace_shapes(cfg: dict) -> dict:
    """The sizes that the result shapes of this family's kernels are made
    of, as the patterns of ``layer_metrics/*_roofline_share.json`` name
    them: a retuned ``serve`` block or another share of the experts moves
    the shapes, and the patterns with them."""
    s = sizes(cfg)
    block = cfg["mamba_chunk_size"]
    return {"slots": cfg["serve"]["max_slots"], "held": s.n_held,
            "expert_out": 2 * s.f, "mamba_layers": mamba_layers(cfg),
            "state": s.ms, "heads": s.mh, "head": s.mp, "inner": s.di,
            "block": block,
            "blocks": -(-cfg["serve"]["prefill_chunk_tokens"] // block)}


def decode_step_bytes(cfg: dict, lv: dict) -> dict:
    experts = moe_experts_bytes(cfg)
    parts = {"experts": experts,
             "other_weights": float(weight_bytes(cfg)) - experts,
             "kv": kv_bytes(cfg, lv["tokens"]),
             "state": ssm2_update_bytes(cfg, lv["sequences"])}
    parts["total"] = sum(parts.values())
    return parts

"""Bytes a serving step of the ``phi4flash`` family must move, counted from
the configuration's sizes: what the roofline shares of the new cell divide
by the chip's published HBM rate. Nothing here is measured.

A decode step of 32 layers must read every weight once (the tied table once,
for the head), the K and V of every live token of the ONE paged cache once
for each of the layers that read it (the full layer and every cross layer:
``n_back + 1`` = 8), the K and V of each live sequence's window (at most
``sliding_window`` tokens) in each window layer, and must read and write
each live sequence's recurrent state in each Mamba layer.

Live sequences and tokens come from the requests' own marks, as in
``readers/decode_roofline.py``: a request counts for the part of
``[t_first_token, t_done]`` inside the window, at its mean length while it
decodes (``prompt_len + n_tokens / 2``).
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from reference.phi4flash import param_count, sizes  # noqa: E402

WIDTH = {"bf16": 2, "f32": 4}


def _served(cfg: dict) -> int:
    return WIDTH[cfg["serve"]["precision"]]


def weight_bytes(cfg: dict) -> int:
    return param_count(cfg) * _served(cfg)


def reading_layers(cfg: dict) -> int:
    """Layers that read the paged cache: the full layer and the cross
    layers."""
    s = sizes(cfg)
    return 1 + (s.n - s.half - 2) // 2


def window_layers(cfg: dict) -> int:
    return sizes(cfg).half // 2


def mamba_layers(cfg: dict) -> int:
    return sizes(cfg).half // 2 + 1


def kv_bytes_per_token_layer(cfg: dict) -> int:
    """K and V of one token in one layer."""
    s = sizes(cfg)
    return 2 * s.nkv * s.hd * _served(cfg)


def state_bytes_per_sequence_layer(cfg: dict) -> int:
    """Convolution state (served type) and SSM state of one sequence in one
    Mamba layer."""
    s = sizes(cfg)
    return (s.dc - 1) * s.di * WIDTH[cfg["serve"]["conv_state"]] \
        + s.ds * s.di * WIDTH[cfg["serve"]["ssm_state"]]


def live(records, t_open: float, t_close: float, window: int) -> dict:
    """Means over the measured window: decoding sequences, their tokens,
    and their tokens inside an attention window of ``window``."""
    seqs = toks = wtoks = 0.0
    for r in records:
        t0, t1, n = r.get("t_first_token"), r.get("t_done"), r.get("n_tokens")
        if t0 is None or t1 is None or not n:
            continue
        inside = min(t1, t_close) - max(t0, t_open)
        if inside > 0:
            length = r["prompt_len"] + n / 2.0
            seqs += inside
            toks += length * inside
            wtoks += min(length, window) * inside
    span = t_close - t_open
    return {"sequences": seqs / span, "tokens": toks / span,
            "window_tokens": wtoks / span}


def shared_kv_bytes(cfg: dict, live_tokens: float) -> float:
    return reading_layers(cfg) * kv_bytes_per_token_layer(cfg) * live_tokens


def window_kv_bytes(cfg: dict, live_window_tokens: float) -> float:
    return window_layers(cfg) * kv_bytes_per_token_layer(cfg) \
        * live_window_tokens


def shared_k_bytes(cfg: dict, live_tokens: float) -> float:
    """The K half of :func:`shared_kv_bytes`: what the QK^T pass reads."""
    return shared_kv_bytes(cfg, live_tokens) / 2


def window_k_bytes(cfg: dict, live_window_tokens: float) -> float:
    """The K half of :func:`window_kv_bytes`."""
    return window_kv_bytes(cfg, live_window_tokens) / 2


def ssm_update_bytes(cfg: dict, live_sequences: float) -> float:
    """The recurrent state of every live sequence read and written once in
    every Mamba layer."""
    return 2 * mamba_layers(cfg) * state_bytes_per_sequence_layer(cfg) \
        * live_sequences


def ssm_scan_bytes(cfg: dict, chunk_tokens: int) -> float:
    """One prefill chunk through every Mamba layer's scan: dt and x read,
    y written (float32, ``d_inner`` wide, a row a token), B and C read, and
    the sequence's state read and written."""
    s = sizes(cfg)
    per_layer = chunk_tokens * (3 * s.di + 2 * s.ds) * 4 \
        + 2 * s.ds * s.di * WIDTH[cfg["serve"]["ssm_state"]]
    return mamba_layers(cfg) * per_layer


def decode_step_bytes(cfg: dict, lv: dict) -> dict:
    parts = {"weights": float(weight_bytes(cfg)),
             "shared_kv": shared_kv_bytes(cfg, lv["tokens"]),
             "window_kv": window_kv_bytes(cfg, lv["window_tokens"]),
             "state": ssm_update_bytes(cfg, lv["sequences"])}
    parts["total"] = sum(parts.values())
    return parts

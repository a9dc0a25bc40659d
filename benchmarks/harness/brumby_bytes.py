"""Bytes and operations a serving step of the ``brumby`` family must move
and make, counted from the configuration's sizes: what the roofline shares
of its cell divide by the chip's published peaks. Nothing here is measured.

A decode step must read every layer's weights and the untied head once
(the embedding is gathered a row a token: not counted), and must read and
write each live sequence's retention state in every layer: ``S`` and ``z``,
float32, at the DISTINCT terms of the degree-2 feature map, ``hd (hd + 1) /
2`` = 8,256 at a head width of 128, whatever the program stores (8,320 as
it stands: a share above 100% would say that a program moved fewer bytes
than the floor, which padding cannot).

Live sequences come from the requests' own marks, as in
``harness/hybrid_bytes.py::live``.
"""
from __future__ import annotations

import math
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness.hybrid_bytes import WIDTH, live as _live  # noqa: E402
from reference.brumby import leaf_shapes, sizes  # noqa: E402


def _served(cfg: dict) -> int:
    return WIDTH[cfg["serve"]["precision"]]


def live(records, t_open: float, t_close: float) -> dict:
    """Means over the measured window: decoding sequences and their
    tokens."""
    out = _live(records, t_open, t_close, 1 << 62)
    return {"sequences": out["sequences"], "tokens": out["tokens"]}


def distinct_terms(cfg: dict) -> int:
    """phi's distinct terms a head: hd (hd + 1) / 2."""
    hd = sizes(cfg).hd
    return hd * (hd + 1) // 2


def layer_params(cfg: dict) -> int:
    return sum(math.prod(shape[1:]) for name, shape
               in leaf_shapes(cfg).items() if name.startswith("l."))


def step_weight_bytes(cfg: dict) -> int:
    """What a decode step reads of the weights: every layer, the final
    norm and the head once."""
    s = sizes(cfg)
    return (s.n * layer_params(cfg) + s.d + s.d * s.vocab) * _served(cfg)


def state_bytes_per_sequence_layer(cfg: dict) -> int:
    """S [D, hd] and z [D] of every kv head, at the distinct D."""
    s = sizes(cfg)
    return s.nkv * distinct_terms(cfg) * (s.hd + 1) \
        * WIDTH[cfg["serve"]["retention_state"]]


def state_bytes_per_sequence(cfg: dict) -> int:
    return sizes(cfg).n * state_bytes_per_sequence_layer(cfg)


def retention_update_bytes(cfg: dict, live_sequences: float) -> float:
    """The state of every live sequence read and written once in every
    layer."""
    return 2.0 * state_bytes_per_sequence(cfg) * live_sequences


def retention_chunk_bytes(cfg: dict, chunk_tokens: int) -> float:
    """One prefill launch through every layer's retention: the slot's state
    read and written, q, k, v and the gates read and y written (float32)."""
    s = sizes(cfg)
    per_layer = 2 * state_bytes_per_sequence_layer(cfg) \
        + chunk_tokens * (2 * s.nq * s.hd + 2 * s.nkv * s.hd + s.nkv) * 4
    return float(s.n * per_layer)


def retention_chunk_flops(cfg: dict, chunk_tokens: int) -> float:
    """The chunked form's products for one launch, two operations a
    multiply-add: inside the chunk q k^T and the weights times v (each
    heads x C x C x hd); the carried state's read-out (heads x C x D x (hd
    + 1)); the chunk's addition to the state (kv heads x C x D x (hd +
    1))."""
    s = sizes(cfg)
    c, d = chunk_tokens, distinct_terms(cfg)
    per_layer = 2.0 * (2 * s.nq * c * c * s.hd
                       + (s.nq + s.nkv) * c * d * (s.hd + 1))
    return s.n * per_layer


def trace_shapes(cfg: dict) -> dict:
    """The sizes that the result shapes of this family's kernels are made
    of, as the patterns of ``layer_metrics/retention_*_roofline_share.json``
    name them: a retuned ``serve`` block moves the shapes, and the patterns
    with them."""
    s = sizes(cfg)
    return {"layers": s.n, "slots": cfg["serve"]["max_slots"],
            "kv": s.nkv, "heads": s.nq, "group": s.nq // s.nkv,
            "head": s.hd, "diagonals": s.hd // 2 + 1,
            "chunk": cfg["serve"]["prefill_chunk_tokens"]}


def decode_step_bytes(cfg: dict, lv: dict) -> dict:
    parts = {"weights": float(step_weight_bytes(cfg)),
             "state": retention_update_bytes(cfg, lv["sequences"])}
    parts["total"] = sum(parts.values())
    return parts

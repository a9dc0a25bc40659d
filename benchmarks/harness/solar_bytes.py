"""Bytes and operations a serving step of the ``solar_open2`` family must
move and make, counted from the configuration's sizes: what the roofline
shares of its cell divide by the chip's published peaks. Nothing here is
measured, and nothing here depends on which arm or form the program ran:
the counts are of the work the equations need.

A decode step must read every weight outside the routed experts once (the
head's table once; of the embedding table a row a token), the routed
experts that its tokens HIT (as the program counted them on its counts
chain, ``engine.moe.experts_hit.decode``), the linear layers' state of
every live sequence once and write it once (the matrix state and the
convolutions' last inputs, float32), and the K and V rows of every live
token in each softmax layer.

Live sequences and tokens come from the requests' own marks, as in
``harness/hybrid_bytes.py::live``.
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness.hybrid_bytes import WIDTH, live as _live  # noqa: E402
from reference.solar_open2 import param_count, sizes  # noqa: E402

STATE_WIDTH = 4          # the recurrent state is float32
SUB = 64                 # tokens of a sub-chunk of `kernels/deltanet.py`


def _served(cfg: dict) -> int:
    return WIDTH[cfg["serve"]["precision"]]


def live(records, t_open: float, t_close: float) -> dict:
    """Means over the measured window: decoding sequences and their
    tokens."""
    out = _live(records, t_open, t_close, 0)
    return {"sequences": out["sequences"], "tokens": out["tokens"]}


def linear_layers(cfg: dict) -> int:
    s = sizes(cfg)
    return s.layers - len(s.softmax)


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    s = sizes(cfg)
    return 3 * s.d * s.f


def other_weight_bytes(cfg: dict) -> float:
    """Every weight a decode step reads whatever it routes: all but the
    held routed experts and the embedding table (rows are looked up)."""
    s = sizes(cfg)
    held = s.layers * s.n_held * expert_params(cfg)
    return float(param_count(cfg)["held"] - held - s.vocab * s.d) \
        * _served(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    """A token's K and V rows over the softmax layers."""
    s = sizes(cfg)
    return len(s.softmax) * 2 * s.kv_heads * s.hd * _served(cfg)


def matrix_state_bytes(cfg: dict) -> int:
    """One sequence's matrix state in one linear layer."""
    s = sizes(cfg)
    return s.lin_heads * s.dk * s.dk * STATE_WIDTH


def state_bytes_per_sequence(cfg: dict) -> int:
    """One sequence's recurrent state over the linear layers: the matrix
    state and the convolutions' last inputs."""
    s = sizes(cfg)
    return linear_layers(cfg) * (
        matrix_state_bytes(cfg)
        + (s.taps - 1) * 3 * s.lin_width * STATE_WIDTH)


def decode_step_bytes(cfg: dict, lv: dict, hit: float) -> dict:
    """``hit``: held experts a decode step's tokens hit, summed over the
    layers (the program's count over the steps of the window)."""
    parts = {
        "experts_hit": hit * expert_params(cfg) * _served(cfg),
        "other_weights": other_weight_bytes(cfg),
        "state": 2.0 * state_bytes_per_sequence(cfg) * lv["sequences"],
        "kv_rows": float(kv_bytes_per_token(cfg)) * lv["tokens"]}
    parts["total"] = sum(parts.values())
    return parts


# ---- per kernel: (bytes, operations) of the work the equations need

def kda_update_work(cfg: dict, tokens: float) -> tuple:
    """The decode update for ``tokens`` (token, linear layer) pairs: each
    reads a sequence's matrix state once and writes it once; the rule's
    four passes over it (decay, read with the key, write, read with the
    query) are 4 multiply-adds an element."""
    b = matrix_state_bytes(cfg)
    return 2.0 * b * tokens, 8.0 * (b / STATE_WIDTH) * tokens


def kda_chunk_work(cfg: dict, tokens: float, chunk: int) -> tuple:
    """The chunked form for ``tokens`` (token, linear layer) pairs in
    launches of ``chunk`` tokens cut into sub-chunks of `SUB`: the state
    read and written once a launch; q, k, v, the decay's dk channels and o
    a token (float32); operations a head and sub-chunk of C tokens: K K^T
    and Q K^T with the decay inside (2 C^2 dk), K S and Q S (2 C dk dv), the
    solve applied and the inner mix (2 C^2 dv), the state's update (C dk
    dv); the solve's own products and the decay's exponentials are an
    implementation's and are left out, so the count is a floor whichever a
    later kernel takes."""
    s = sizes(cfg)
    sub = SUB if chunk % SUB == 0 else chunk
    row = 5 * s.dk * s.lin_heads * STATE_WIDTH
    per_sub = 2.0 * (2 * sub * sub * s.dk + 3 * sub * s.dk * s.dk
                     + 2 * sub * sub * s.dk)
    return (2.0 * matrix_state_bytes(cfg) * tokens / chunk + row * tokens,
            per_sub * s.lin_heads * tokens / sub)


def gqa_walk_work(cfg: dict, pairs: float) -> tuple:
    """A decode step's walk of the K/V pool, for ``pairs`` (query, key)
    pairs as the program counted them (over the softmax layers): a pair
    reads one token's K row and V row of all key-value heads (both under
    the one scope that times both) and makes, for each of the query heads,
    a score and a mix of ``head_dim`` multiply-adds each."""
    s = sizes(cfg)
    return (2.0 * s.kv_heads * s.hd * _served(cfg) * pairs,
            s.heads * 4.0 * s.hd * pairs)


def experts_work(cfg: dict, rows_held: float, hit: float) -> tuple:
    """The routed experts' call, whichever arm ran: ``rows_held`` routed
    rows through one WHOLE expert each (gate, up and down: two operations a
    weight), and the three matrices of the ``hit`` experts (both as the
    program counted them), as ``kimi_bytes.experts_work``."""
    p = expert_params(cfg)
    return hit * p * _served(cfg), rows_held * 2.0 * p


def trace_shapes(cfg: dict) -> dict:
    """The sizes in the result shapes of the op families that
    ``layer_metrics/solar_experts_roofline_share.json`` names under
    ``unnamed``."""
    s = sizes(cfg)
    return {"decode_rows": cfg["serve"]["max_slots"] * s.top_k,
            "chunk_rows": cfg["serve"]["prefill_chunk_tokens"] * s.top_k,
            "expert_out": 2 * s.f, "hidden": s.d}

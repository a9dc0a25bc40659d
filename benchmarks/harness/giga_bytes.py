"""Bytes and operations a serving step of the ``gigachat3_5`` family must
move and make, counted from the configuration's sizes: what the roofline
shares of its cell divide by the chip's published peaks. Nothing here is
measured, and nothing here depends on which arm or form the program ran:
the counts are of the work the equations need.

A decode step must read every weight outside the routed experts once (the
head's table once; of the embedding table a row a token), the routed
experts that its tokens HIT (as the program counted them on its counts
chain, ``engine.moe.experts_hit.decode``), the linear layers' state of
every live sequence once and write it once (the matrix state and the
convolution's last inputs, float32), and the latent row of every live
token in each full layer.

Live sequences and tokens come from the requests' own marks, as in
``harness/hybrid_bytes.py::live``.
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness.hybrid_bytes import WIDTH, live as _live  # noqa: E402
from reference.gigachat35 import param_count, sizes  # noqa: E402

STATE_WIDTH = 4          # the recurrent state is float32


def _served(cfg: dict) -> int:
    return WIDTH[cfg["serve"]["precision"]]


def live(records, t_open: float, t_close: float, cfg: dict) -> dict:
    """Means over the measured window: decoding sequences and their
    tokens."""
    out = _live(records, t_open, t_close, 0)
    return {"sequences": out["sequences"], "tokens": out["tokens"]}


def weight_bytes(cfg: dict) -> int:
    return param_count(cfg) * _served(cfg)


def full_layers(cfg: dict) -> int:
    return sizes(cfg).types.count("full_attention")


def linear_layers(cfg: dict) -> int:
    return sizes(cfg).types.count("linear_attention")


def expert_layers(cfg: dict) -> int:
    s = sizes(cfg)
    return len(s.types) - s.first_dense


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    s = sizes(cfg)
    return 3 * s.d * s.f


def latent_row_bytes(cfg: dict) -> int:
    """A token's latent row in one full layer: ckv and the rotated k_rope
    (what the equations use; the pool's row is padded to whole lane
    tiles)."""
    s = sizes(cfg)
    return (s.rank + s.dr) * _served(cfg)


def matrix_state_bytes(cfg: dict) -> int:
    """One sequence's matrix state in one linear layer."""
    s = sizes(cfg)
    return s.hv * s.dk * s.dlv * STATE_WIDTH


def state_bytes_per_sequence(cfg: dict) -> int:
    """One sequence's recurrent state over the linear layers: the matrix
    state and the convolution's last inputs."""
    s = sizes(cfg)
    return linear_layers(cfg) * (matrix_state_bytes(cfg)
                                 + (s.taps - 1) * s.conv_dim * STATE_WIDTH)


def decode_step_bytes(cfg: dict, lv: dict, hit: float) -> dict:
    """``hit``: held experts a decode step's tokens hit, summed over the
    expert layers (the program's count over the steps of the window)."""
    s = sizes(cfg)
    held = expert_layers(cfg) * s.n_held * expert_params(cfg) * _served(cfg)
    embed = s.vocab * s.d * _served(cfg)          # rows are looked up
    parts = {
        "experts_hit": hit * expert_params(cfg) * _served(cfg),
        "other_weights": float(weight_bytes(cfg)) - held - embed,
        "state": 2.0 * state_bytes_per_sequence(cfg) * lv["sequences"],
        "latent_rows": full_layers(cfg) * latent_row_bytes(cfg)
        * lv["tokens"]}
    parts["total"] = sum(parts.values())
    return parts


# ---- per kernel: (bytes, operations) of the work the equations need

def deltanet_update_work(cfg: dict, tokens: float) -> tuple:
    """The decode update for ``tokens`` (token, linear layer) pairs: each
    reads a sequence's matrix state once and writes it once; the rule's
    four passes over it (decay, read with the key, write, read with the
    query) are 4 multiply-adds an element."""
    b = matrix_state_bytes(cfg)
    return 2.0 * b * tokens, 8.0 * (b / STATE_WIDTH) * tokens


def deltanet_chunk_work(cfg: dict, tokens: float, chunk: int,
                        sub: int) -> tuple:
    """The chunked form for ``tokens`` (token, linear layer) pairs in
    launches of ``chunk`` tokens cut into sub-chunks of ``sub``: the state
    read and written once a launch, q, k, v read and o written a token;
    operations a value head and sub-chunk of C tokens: K K^T and Q K^T (2 C^2
    dk), K S and Q S (2 C dk dv), the solve applied and the inner mix (2 C^2
    dv), the state's update (C dk dv); the solve's own products (the
    doubling's, or a substitution's) are an implementation's and are left
    out, so the count is a floor whichever a later kernel takes."""
    s = sizes(cfg)
    launches = tokens / chunk
    row = (2 * s.dk + 2 * s.dlv) * s.hv * STATE_WIDTH
    per_sub = 2.0 * (2 * sub * sub * s.dk + 3 * sub * s.dk * s.dlv
                     + 2 * sub * sub * s.dlv)
    return (2.0 * matrix_state_bytes(cfg) * launches + row * tokens,
            per_sub * s.hv * tokens / sub)


def latent_attention_work(cfg: dict, decode: float, chunk: float,
                          chunk_tokens: int) -> tuple:
    """Attention over everything in sight, for (query, key) pairs that
    ``decode`` steps and ``chunk``s attended, each in the cheapest form it
    can run: a decode step's pair reads one latent row and, absorbed,
    makes ``heads x (rank + rope + rank)`` multiply-adds; a chunk's
    ``chunk_tokens`` queries share their rows (a row read once a chunk:
    pairs / queries at least) and the per-head form makes ``heads x (nope +
    rope + value)`` a pair, the rows' expansion into heads left out."""
    s = sizes(cfg)
    row = latent_row_bytes(cfg)
    return ((decode + chunk / chunk_tokens) * row,
            2.0 * s.heads * (decode * (2 * s.rank + s.dr)
                             + chunk * (s.dn + s.dr + s.dv)))


def experts_first_product_work(cfg: dict, rows_held: float,
                               hit: float) -> tuple:
    """The routed experts' FIRST product (gate and up, two thirds of an
    expert), whichever arm ran: ``rows_held`` routed rows through one
    expert each (two operations a weight), and the first matrices of the
    ``hit`` experts (both as the program counted them). The second product
    (down) is left out of the work because its ops cannot be told in a
    trace: the dense arm's has the result shape of every projection of the
    layer."""
    s = sizes(cfg)
    p = 2 * s.d * s.f
    return hit * p * _served(cfg), rows_held * 2.0 * p


# the blocks `paddle_tpu/kernels/mla.py` and `kernels/deltanet.py` cut
# their walks into, and the lane tiles a page row is rounded up to: they
# are in the result shapes of the ops, and so in the patterns
HEAD_BLOCK, KEY_BLOCK, DECODE_KEY_BLOCK, SUB, LANES = 16, 2048, 512, 64, 128


def trace_shapes(cfg: dict) -> dict:
    """The sizes that the result shapes of this family's kernels are made
    of, as the patterns of ``layer_metrics/*_roofline_share.json`` name
    them."""
    s = sizes(cfg)
    sv = cfg["serve"]
    slots, chunk, page = sv["max_slots"], sv["prefill_chunk_tokens"], \
        sv["page_size"]
    span = min(KEY_BLOCK, sv["max_seq_len"])
    decode_block = min(DECODE_KEY_BLOCK, sv["max_seq_len"])
    sub = SUB if chunk % SUB == 0 else chunk
    return {"slots": slots, "chunk": chunk, "page": page,
            "row": -(-(s.rank + s.dr) // LANES) * LANES, "rank": s.rank,
            "heads": s.heads, "dv": s.dv, "head_kv": s.dn + s.dv,
            "head_k": s.dn + s.dr, "head_block": HEAD_BLOCK,
            "key_block": span, "chunk_pages": span // page,
            "decode_block": decode_block,
            "decode_pages": decode_block // page,
            "lin": linear_layers(cfg), "hv": s.hv, "dk": s.dk, "dlv": s.dlv,
            "sub": sub, "subs": chunk // sub,
            "held": s.n_held, "expert_out": 2 * s.f, "expert_width": s.f,
            "hidden": s.d, "decode_rows": slots * s.top_k,
            "chunk_rows": chunk * s.top_k}

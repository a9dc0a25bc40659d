"""Bytes and operations a serving step of the ``dots3_note`` family must
move and make, counted from the configuration's sizes: what the roofline
shares of its cell divide by the chip's published peaks. Nothing here is
measured, and nothing here depends on which arm or form the program ran:
the counts are of the work the equations need.

A decode step must read every weight outside the routed experts once (the
head's table once; of the embedding table a row a token), the routed
experts that its tokens HIT (as the program counted them on its counts
chain, ``engine.moe.experts_hit.decode``: the seeded router is no even one,
so no expectation stands in for the count), the index keys of every live
token in each full layer (the indexer scores them all), the ``index_topk``
latent rows a sequence a full layer that the selection kept, and each live
sequence's ring in each sliding layer.

Live sequences and tokens come from the requests' own marks, as in
``harness/hybrid_bytes.py::live``.
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness.hybrid_bytes import WIDTH, live as _live  # noqa: E402
from reference.dots3note import param_count, sizes  # noqa: E402


def _served(cfg: dict) -> int:
    return WIDTH[cfg["serve"]["precision"]]


def live(records, t_open: float, t_close: float, cfg: dict) -> dict:
    """Means over the measured window: decoding sequences, their tokens,
    and their tokens among the ``index_topk`` a full layer keeps."""
    out = _live(records, t_open, t_close, sizes(cfg).topk)
    return {"sequences": out["sequences"], "tokens": out["tokens"],
            "kept_tokens": out["window_tokens"]}


def weight_bytes(cfg: dict) -> int:
    return param_count(cfg) * _served(cfg)


def full_layers(cfg: dict) -> int:
    return sizes(cfg).types.count("full_attention")


def sliding_layers(cfg: dict) -> int:
    return sizes(cfg).types.count("sliding_attention")


def expert_layers(cfg: dict) -> int:
    s = sizes(cfg)
    return len(s.types) - s.first_dense


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    s = sizes(cfg)
    return 3 * s.d * s.f


def latent_row_bytes(cfg: dict) -> int:
    """A token's latent row in one full layer: ckv and the rotated
    k_rope."""
    s = sizes(cfg)
    return (s.full.rank + s.full.dr) * _served(cfg)


def index_key_bytes(cfg: dict) -> int:
    return sizes(cfg).di * _served(cfg)


def ring_bytes(cfg: dict) -> int:
    """The window's rows of one sequence in one sliding layer."""
    s = sizes(cfg)
    return s.window * (s.swa.rank + s.swa.dr) * _served(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    """What the equations keep of a token in the page pools, over the full
    layers (the pool's rows are padded to whole lane tiles, `LANES`)."""
    return full_layers(cfg) * (latent_row_bytes(cfg) + index_key_bytes(cfg))


def decode_step_bytes(cfg: dict, lv: dict, hit: float) -> dict:
    """``hit``: held experts a decode step's tokens hit, summed over the
    expert layers (the program's count over the steps of the window)."""
    s = sizes(cfg)
    held = expert_layers(cfg) * s.n_held * expert_params(cfg) * _served(cfg)
    embed = s.vocab * s.d * _served(cfg)          # rows are looked up
    parts = {
        "experts_hit": hit * expert_params(cfg) * _served(cfg),
        "other_weights": float(weight_bytes(cfg)) - held - embed,
        "index_keys": full_layers(cfg) * index_key_bytes(cfg) * lv["tokens"],
        "latent_rows": full_layers(cfg) * latent_row_bytes(cfg)
        * lv["kept_tokens"],
        "rings": sliding_layers(cfg) * ring_bytes(cfg) * lv["sequences"]}
    parts["total"] = sum(parts.values())
    return parts


# ---- per kernel: (bytes, operations) of the work the equations need

def latent_attention_work(cfg: dict, decode: float, chunk: float,
                          chunk_tokens: int) -> tuple:
    """Attention over the chosen keys, for (query, key) pairs that
    ``decode`` steps and ``chunk``s attended, each in the cheapest form it
    can run. A decode step's query has rows of its own: each pair reads one
    latent row and, in the absorbed form, makes ``heads x (rank + rope +
    rank)`` multiply-adds. A chunk's ``chunk_tokens`` queries share their
    rows: a row is read once a chunk (at least pairs / queries of them),
    and the per-head form makes ``heads x (nope + rope + value)``
    multiply-adds a pair; the expansion of the rows into heads is left out,
    so the count stays a floor whichever form a later kernel takes."""
    a = sizes(cfg).full
    row = latent_row_bytes(cfg)
    return ((decode + chunk / chunk_tokens) * row,
            2.0 * a.heads * (decode * (2 * a.rank + a.dr)
                             + chunk * (a.dn + a.dr + a.dv)))


def index_work(cfg: dict, scored: float) -> tuple:
    """The indexer's scores for ``scored`` (query, key) pairs: ``index
    heads x index width`` multiply-adds a pair. The keys' bytes are shared
    by a chunk's queries and are left out (the count stays a floor); the
    selection itself needs no operation a roofline knows."""
    s = sizes(cfg)
    return 0.0, scored * 2.0 * s.hi * s.di


def window_decode_work(cfg: dict, sequences: float) -> tuple:
    """A decode step through the sliding layers: each live sequence's ring
    read once a layer."""
    return sliding_layers(cfg) * ring_bytes(cfg) * sequences, 0.0


def window_prefill_work(cfg: dict, chunk: int) -> tuple:
    """A chunk through the sliding layers in the per-head form: the keys
    and values of the window and the chunk expanded, scores and the mix."""
    s = sizes(cfg)
    a = s.swa
    keys = s.window + chunk
    flops = 2.0 * (keys * a.rank * a.heads * (a.dn + a.dv)
                   + chunk * keys * a.heads * (a.dn + a.dr + a.dv))
    return (sliding_layers(cfg) * keys * (a.rank + a.dr) * _served(cfg),
            sliding_layers(cfg) * flops)


def experts_work(cfg: dict, rows_held: float, hit: float) -> tuple:
    """The expert product, whichever arm ran: ``rows_held`` routed rows
    through one expert each (two operations a weight), and the weights of
    the ``hit`` experts, one for each layer and call in which a held expert
    got a row (both as the program counted them)."""
    p = expert_params(cfg)
    return hit * p * _served(cfg), rows_held * 2.0 * p


# the blocks `paddle_tpu/kernels/mla.py` cuts its walks into, and the lane
# tiles that `paddle_tpu/models/dots3note.py` rounds a page row up to: they
# are in the result shapes of the ops, and so in the patterns
HEAD_BLOCK, KEY_BLOCK, SCORE_BLOCK, DECODE_SELECT_BLOCK = 16, 2048, 1024, 16384
LANES = 128


def trace_shapes(cfg: dict) -> dict:
    """The sizes that the result shapes of this family's kernels are made
    of, as the patterns of ``layer_metrics/*_roofline_share.json`` name
    them: a retuned ``serve`` block or another share of the experts moves
    the shapes, and the patterns with them."""
    s = sizes(cfg)
    sv = cfg["serve"]
    slots, chunk, page = sv["max_slots"], sv["prefill_chunk_tokens"], \
        sv["page_size"]
    row = -(-(s.full.rank + s.full.dr) // LANES) * LANES
    ring = (-(-s.window // page) + 1) * page
    return {"slots": slots, "chunk": chunk, "page": page, "topk": s.topk,
            "heads": s.full.heads, "dv": s.full.dv, "row": row,
            "head_block": HEAD_BLOCK, "key_block": KEY_BLOCK,
            "head_groups": s.full.heads // HEAD_BLOCK,
            "head_kv": s.full.dn + s.full.dv, "slot_rows": slots * s.topk,
            "score_block": SCORE_BLOCK, "index_dim": s.di,
            "decode_sort": s.topk + DECODE_SELECT_BLOCK,
            "decode_pages": slots * DECODE_SELECT_BLOCK // page,
            "swa_heads": s.swa.heads, "ring": ring,
            "window_keys": ring + chunk,
            "swa_kv": s.swa.heads * (s.swa.dn + s.swa.dv),
            "held": s.n_held, "expert_out": 2 * s.f, "hidden": s.d,
            "decode_rows": slots * s.top_k}

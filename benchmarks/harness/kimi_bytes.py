"""Bytes and operations a serving step of the ``kimi_k2`` family must move
and make, counted from the configuration's sizes: what the roofline shares
of its cell divide by the chip's published peaks. Nothing here is measured,
and nothing here depends on which arm or form the program ran: the counts
are of the work the equations need.

A decode step must read every weight outside the routed experts once (the
head's table once; of the embedding table a row a token), the routed
experts that its tokens HIT (as the program counted them,
``engine.moe.experts_hit.decode``), and walk the latent rows its live
sequences reach. The walk's floor is the LARGER of its operations at the
bf16 peak (the absorbed form: ``heads x (2 rank + rope)`` multiply-adds a
(query, key) pair, as the program counted the pairs) and the bytes of the
DISTINCT rows reached at the memory's rate: a shared context that three
live sequences attend is one set of rows, whoever reads it how often, so a
kernel that reads a shared context once for all its sharers cannot read
over 100%, and the yardstick reads the same work whatever implements it.
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness import device  # noqa: E402
from harness.hybrid_bytes import WIDTH  # noqa: E402
from reference.kimi_k2 import param_count, sizes  # noqa: E402


def _served(cfg: dict) -> int:
    return WIDTH[cfg["serve"]["precision"]]


def expert_layers(cfg: dict) -> int:
    s = sizes(cfg)
    return s.layers - s.first_dense


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    s = sizes(cfg)
    return 3 * s.d * s.f


def latent_row_bytes(cfg: dict) -> int:
    """A token's latent row in one layer: ckv and the rotated k_rope (what
    the equations use; the pool's row is padded to whole lane tiles)."""
    s = sizes(cfg)
    return (s.rank + s.dr) * _served(cfg)


def live_rows(records, t_open: float, t_close: float) -> dict:
    """Means over ``[t_open, t_close)`` of what the decoding sequences
    reach: ``sequences`` (a request counts from its first token to its
    end), ``rows`` (each sequence's own: prompt + half its answer) and
    ``distinct_rows``: a shared context (``context`` names it, ``shared``
    is its length) counted ONCE while any sequence on it decodes, beside
    every sequence's rows of its own."""
    span = t_close - t_open
    seqs = rows = own = 0.0
    ctx = {}
    for r in records:
        t0, t1, n = r.get("t_first_token"), r.get("t_done"), r.get("n_tokens")
        if t0 is None or t1 is None or not n:
            continue
        a, b = max(t0, t_open), min(t1, t_close)
        if b <= a:
            continue
        length = r["prompt_len"] + n / 2.0
        shared = r.get("shared") or 0
        seqs += b - a
        rows += length * (b - a)
        own += (length - shared) * (b - a)
        if shared:
            ctx.setdefault((r["context"], shared), []).append((a, b))
    for (_, shared), spans in ctx.items():
        end = t_open
        for a, b in sorted(spans):           # the union of the intervals
            if b > end:
                own += shared * (b - max(a, end))
                end = b
    return {"sequences": seqs / span, "rows": rows / span,
            "distinct_rows": own / span}


def other_weight_bytes(cfg: dict) -> float:
    """Every weight a decode step reads whatever it routes: all but the
    held routed experts and the embedding table (rows are looked up)."""
    s = sizes(cfg)
    held = expert_layers(cfg) * s.n_held * expert_params(cfg)
    return float(param_count(cfg) - held - s.vocab * s.d) * _served(cfg)


def latent_decode_work(cfg: dict, pairs: float, distinct_rows: float) -> tuple:
    """(bytes, operations) of the absorbed walk: ``pairs`` (query, key)
    pairs over all layers, ``distinct_rows`` latent rows reached in EACH
    layer."""
    s = sizes(cfg)
    return (s.layers * latent_row_bytes(cfg) * distinct_rows,
            2.0 * s.heads * (2 * s.rank + s.dr) * pairs)


def latent_chunk_work(cfg: dict, pairs: float, chunk_tokens: int) -> tuple:
    """(bytes, operations) of a chunk's per-head form: ``heads x (nope +
    rope + value)`` multiply-adds a pair, the rows' expansion into heads
    left out; a chunk's queries share their rows (a row read once a chunk:
    pairs / queries at least)."""
    s = sizes(cfg)
    return (latent_row_bytes(cfg) * pairs / chunk_tokens,
            2.0 * s.heads * (s.dn + s.dr + s.dv) * pairs)


def experts_work(cfg: dict, rows_held: float, hit: float) -> tuple:
    """The routed experts' call, whichever arm ran: ``rows_held`` routed
    rows through one WHOLE expert each (gate, up and down: two operations a
    weight), and the three matrices of the ``hit`` experts (both as the
    program counted them). The whole expert, where
    ``giga_bytes.experts_first_product_work`` takes the first product
    alone: this cell's time is its scope's, which holds both products."""
    p = expert_params(cfg)
    return hit * p * _served(cfg), rows_held * 2.0 * p


def trace_shapes(cfg: dict) -> dict:
    """The sizes in the result shapes of the op families that
    ``layer_metrics/kimi_*_roofline_share.json`` name under ``unnamed``."""
    s = sizes(cfg)
    return {"decode_rows": cfg["serve"]["max_slots"] * s.top_k,
            "expert_out": 2 * s.f, "hidden": s.d}


def decode_step_floor(cfg: dict, lv: dict, hit: float, pairs: float,
                      kind: str) -> dict:
    """Seconds a decode step cannot go under: the weights once and the
    experts hit at the memory's rate, then the walk's floor. ``hit`` and
    ``pairs`` are a step's (the program's counts over the steps)."""
    hbm = device.peak(kind, "hbm_bytes_per_s")
    nbytes, flops = latent_decode_work(cfg, pairs, lv["distinct_rows"])
    parts = {"experts_hit": hit * expert_params(cfg) * _served(cfg),
             "other_weights": other_weight_bytes(cfg),
             "latent_rows_distinct": nbytes, "latent_flops": flops,
             "latent_rows_each_its_own": float(
                 sizes(cfg).layers * latent_row_bytes(cfg) * lv["rows"])}
    walk = max(nbytes / hbm, flops / device.peak(kind, "bf16_flops"))
    parts["walk_floor_ms"] = 1e3 * walk
    parts["floor_s"] = (parts["experts_hit"] + parts["other_weights"]) / hbm \
        + walk
    return parts

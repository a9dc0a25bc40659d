"""dots3-note (``dots3_note``) through the engine's model seam, at the tiny
preset (a dense first layer with full attention, then one period full,
sliding, sliding, sliding; hidden 64, 4 + 2 heads, ranks 16 / 8 / 16,
``index_topk`` 8, window 5, 8 experts of which 4 are held, 2 a token; page
4, chunk 8, sequences of some 40 tokens so that the selection and the
window both bite), on the CPU in float32, held to the benchmark's plain
reference (benchmarks/reference/dots3note.py, which imports nothing of
paddle_tpu, keeps the per-head form throughout and selects by a mask).

- the step functions' logits, prefill chunks then decode through the latent
  pool, the index keys' pool and the rings, against the reference's full
  forward with the same share of the experts; chunked against unchunked;
  controls that fail the tolerance: fp8 arithmetic, every key attended, the
  last ``index_topk`` in place of the indexer's choice, a window one
  shorter, the gate left out, the router's softmax for its sigmoid;
- the kernels: the absorbed form against the per-head form, the exact
  selection with ties and a dynamic walk, the grouped arm of ``moe_experts``
  against ``dense`` under skewed routing with an expert that gets no row;
- the shares add up: four chips' routed parts and the shared expert once are
  the uncut layer;
- the seam: a page row that is one latent row and an index key, no twin,
  and the other families' pools as they were;
- the engine: greedy tokens, counts on the tokens' readback, no
  recompilation, every refusal of a model with window state.
"""
import dataclasses
import functools
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from paddle_tpu.inference.cache import DeviceCache  # noqa: E402
from paddle_tpu.inference.engine import DecodeEngine, EngineConfig  # noqa: E402
from paddle_tpu.inference.errors import (RecurrentStateUnsupported,  # noqa: E402
                                         from_wire)
from paddle_tpu.inference.family import family_of  # noqa: E402
from paddle_tpu.kernels import mla, moe  # noqa: E402
from paddle_tpu.models import dots3note as dm  # noqa: E402
from paddle_tpu.observability import metrics  # noqa: E402
from reference import dots3note as ref  # noqa: E402

PAGE, CHUNK, SLOTS, MAX_SEQ = 4, 8, 3, 64
# float32 on both sides; what differs is the form (absorbed against per
# head, a gather of picked rows against a mask, rings against a band) and
# the order of sums. The largest sound reading over the cases below is
# 1.6e-6 of the largest logit: the tolerance is 12x that. The weakest
# control reads 0.18 (the router's softmax), every other over 0.3
TOL = 2e-5


def ref_config(cfg, held=None):
    """The reference's view of a program configuration: the published
    keys, ``assumed`` for what the config leaves open, the share."""
    lo, hi = held or cfg.experts_held
    return dict(
        hidden_size=cfg.hidden_size, num_hidden_layers=cfg.n_layers,
        layer_types=list(cfg.layer_types), vocab_size=cfg.vocab_size,
        first_k_dense_replace=cfg.first_dense,
        intermediate_size=cfg.intermediate_size,
        moe_intermediate_size=cfg.moe_intermediate_size,
        router_outputs=cfg.n_routed_experts, n_routed_experts=hi - lo,
        experts_first=lo, num_experts_per_tok=cfg.experts_per_token,
        routed_scaling_factor=cfg.routed_scaling_factor,
        scoring_func="sigmoid", topk_method="noaux_tc", norm_topk_prob=True,
        n_shared_experts=1, attention_gate_type="headwise",
        swa_attention_gate_type="headwise",
        apply_mla_qkv_lora_rescale=cfg.lora_rescale,
        num_attention_heads=cfg.num_heads, q_lora_rank=cfg.q_lora_rank,
        kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        rope_theta=cfg.rope_theta,
        swa_num_attention_heads=cfg.swa_num_heads,
        swa_q_lora_rank=cfg.swa_q_lora_rank,
        swa_kv_lora_rank=cfg.swa_kv_lora_rank,
        swa_qk_nope_head_dim=cfg.swa_qk_nope_head_dim,
        swa_qk_rope_head_dim=cfg.swa_qk_rope_head_dim,
        swa_v_head_dim=cfg.swa_v_head_dim,
        swa_rope_theta=cfg.swa_rope_theta, index_n_heads=cfg.index_n_heads,
        index_head_dim=cfg.index_head_dim, index_topk=cfg.index_topk,
        assumed=dict(index_rope_dim=cfg.index_rope_dim),
        sliding_window_size=cfg.sliding_window,
        rms_norm_eps=cfg.rms_norm_eps)


@pytest.fixture(scope="module")
def tiny():
    cfg = dm.tiny_config()
    # std 0.2: at these widths attention is far from uniform, the indexer's
    # choice is far from "the last 8", and every part moves the logits by
    # far more than the tolerance
    return cfg, dm.init_params(cfg, seed=7, std=0.2)


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(0, 96, size=n).astype(np.int32)


def _engine(cfg, params, **over):
    kw = dict(page_size=PAGE, max_slots=SLOTS, max_seq_len=MAX_SEQ,
              prefill_chunk_tokens=CHUNK, prefix_cache=False, inflight=2,
              min_bucket=8)
    kw.update(over)
    return DecodeEngine(dm.Dots3NoteForCausalLM(cfg, params),
                        EngineConfig(**kw))


def _reference_logits(cfg, params, ids, precision="f32"):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(params, jnp.asarray(ids),
                                     ref_config(cfg), precision))


@functools.lru_cache(maxsize=None)
def _steps(cfg, slot):
    chunk = jax.jit(lambda *a, state, counts: dm.prefill_chunk_step(
        *a, cfg=cfg, state=state, slot=jnp.int32(slot), counts=counts))
    decode = jax.jit(lambda p, ids, cache, act: dm.decode_step(
        p, ids, cache, act, cfg=cfg))
    return chunk, decode


def step_logits(cfg, params, prompt, n_decode, slot=1, chunk=CHUNK):
    """Logits the step functions give for ``prompt`` prefilled in chunks
    and ``n_decode`` greedy tokens decoded, in slot ``slot`` of SLOTS:
    ``[n_decode + 1, V]``, the tokens, and the counts the steps added up.
    The pools and rings start DIRTY: whatever the last sequence left must
    not show."""
    maxp = MAX_SEQ // PAGE
    pages = 1 + SLOTS * maxp
    n_full = len(cfg.full_layers)
    kc = jnp.full((n_full, pages, PAGE, cfg.latent_width), 2.0, jnp.float32)
    vc = jnp.full((n_full, pages, PAGE, cfg.index_head_dim), -1.0,
                  jnp.float32)
    state = tuple(jnp.zeros(s, d) + 3.0 for _, _, s, d in
                  dm.state_arrays(cfg, SLOTS, PAGE, jnp.float32))
    counts = jnp.zeros(dm.step_counts(cfg), jnp.int32)
    row = np.arange(1 + slot * maxp, 1 + (slot + 1) * maxp, dtype=np.int32)
    table = np.zeros((SLOTS, maxp), np.int32)
    table[slot] = row
    chunk_fn, decode = _steps(cfg, slot)
    for start in range(0, len(prompt), chunk):
        ids = np.zeros(chunk, np.int32)
        part = prompt[start:start + chunk]
        ids[:len(part)] = part
        lg, kc, vc, *state, counts = chunk_fn(
            params, jnp.asarray(ids), jnp.int32(start), jnp.int32(len(part)),
            jnp.asarray(row), kc, vc, state=tuple(state), counts=counts)
    out, toks = [np.asarray(lg)], []
    active = np.zeros(SLOTS, bool)
    active[slot] = True
    length = len(prompt)
    for _ in range(n_decode):
        toks.append(int(out[-1].argmax()))
        ids = np.zeros(SLOTS, np.int32)
        ids[slot] = toks[-1]
        lengths = np.zeros(SLOTS, np.int32)
        lengths[slot] = length
        cache = dict(k_pages=kc, v_pages=vc, page_table=jnp.asarray(table),
                     lengths=jnp.asarray(lengths), state=tuple(state),
                     counts=counts)
        lg, cache = decode(params, jnp.asarray(ids), cache,
                           jnp.asarray(active))
        kc, vc, state, counts = (cache["k_pages"], cache["v_pages"],
                                 cache["state"], cache["counts"])
        out.append(np.asarray(lg[slot]))
        length += 1
    return np.stack(out), toks, np.asarray(counts)


def _gap(cfg, params, prog_params, prompt, n_decode, prog_cfg=None,
         precision="f32", chunk=CHUNK):
    """Largest |logit| difference between the program's prefill-then-decode
    logits and the reference's full forward over the same tokens, as a
    share of the reference's largest |logit|."""
    with jax.default_matmul_precision("highest"):
        got, toks, _ = step_logits(prog_cfg or cfg, prog_params, prompt,
                                   n_decode, chunk=chunk)
    ids = np.concatenate([prompt, np.asarray(toks, np.int32)])
    want = _reference_logits(cfg, params, ids, precision)[len(prompt) - 1:]
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize("n_prompt", [5, 8, 21, 40],
                         ids=["short", "one-chunk", "three-chunks-ragged",
                              "five-chunks-full"])
def test_step_logits_match_the_reference(tiny, n_prompt):
    """Prefill in chunks of 8 (the first by the per-head form, the others
    through the indexer, the selection and the absorbed form; rings that
    wrap: 12 rows for a window of 5), then 10 decode steps, against the
    reference's one full forward with no cache."""
    cfg, params = tiny
    assert _gap(cfg, params, params, _prompt(n_prompt, n_prompt), 10) < TOL


def test_chunked_prefill_is_unchunked_prefill(tiny):
    """One bucket of 64 (everything through the selection, no per-head
    chunk) against chunks of 8: the same logits and the same counts, but
    the experts a chunk hit, which are counted a CALL: five chunks hit a
    held expert up to five times where one bucket hits it once."""
    cfg, params = tiny
    prompt = _prompt(37, 2)
    with jax.default_matmul_precision("highest"):
        whole, _, c_whole = step_logits(cfg, params, prompt, 4, chunk=64)
        parts, _, c_parts = step_logits(cfg, params, prompt, 4)
    assert np.abs(whole - parts).max() / np.abs(whole).max() < TOL
    hit = cfg.n_held + 1 + dm._HIT_PREFILL
    assert np.delete(c_whole, hit).tolist() == np.delete(c_parts, hit).tolist()
    n_moe = cfg.n_layers - cfg.first_dense
    assert 0 < c_whole[hit] <= n_moe * cfg.n_held < c_parts[hit] \
        <= 5 * n_moe * cfg.n_held
    # keys in sight: every prompt position and each decoded one, in both
    # full layers; attended: at most index_topk of them
    sight = sum(t + 1 for t in range(37 + 4)) * len(cfg.full_layers)
    kept = sum(min(t + 1, cfg.index_topk) for t in range(37 + 4)) \
        * len(cfg.full_layers)
    n = cfg.n_held + 1
    assert c_parts[n:n + 2].tolist() == [sight, kept]
    # the decode steps' part: the 4 decoded tokens' queries
    assert c_parts[n + dm._ATTENDED_DECODE] == sum(
        min(t + 1, cfg.index_topk) for t in range(37, 37 + 4)) \
        * len(cfg.full_layers)


def test_every_mechanism_moves_the_logits(tiny):
    """The tolerance means something only if each part shows: zeroing one
    leaf moves the logits by far more."""
    cfg, params = tiny
    prompt = _prompt(21, 3)
    for leaf in ("L1.a.o", "L2.a.o", "L1.a.iw", "L0.f.w2", "L3.f.w2",
                 "L3.f.shared.w2", "L1.f.bias"):
        broken = dict(params, **{leaf: jnp.zeros_like(params[leaf])})
        assert _gap(cfg, params, broken, prompt, 6) > 50 * TOL, leaf


@pytest.mark.parametrize("control", ["fp8", "all_keys", "last_topk",
                                     "window_less_1", "no_gate",
                                     "softmax_router"])
def test_a_model_one_step_off_fails_the_tolerance(tiny, control):
    """What the comparison is for. Against the sound program: the
    reference with every product's operands in fp8; with every key in sight
    attended in place of the chosen; with the last ``index_topk`` in place
    of the indexer's choice; with a window of 4 in place of 5; with the
    gate left out; with the router's softmax in place of its sigmoid."""
    cfg, params = tiny
    gap = _gap(cfg, params, params, _prompt(40, 5), 10, precision=control)
    assert gap > 1000 * TOL, gap


def test_rescale_and_rope_base_show(tiny):
    """The latent rescale and the two rope bases are the program's own
    switches: one set wrong reads far over the tolerance."""
    cfg, params = tiny
    for wrong in (dict(lora_rescale=False), dict(swa_rope_theta=1e4),
                  dict(rope_theta=1e3), dict(index_rope_dim=8)):
        gap = _gap(cfg, params, params, _prompt(21, 5), 6,
                   prog_cfg=dataclasses.replace(cfg, **wrong))
        assert gap > 100 * TOL, (wrong, gap)


# ------------------------------------------------------------ the kernels

def _latent_case(rng, t, h, rank, dr, dn, dv, ps, maxp):
    J = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    pool = J(rng.randn(2, 1 + maxp, ps, rank + dr))
    row = jnp.asarray(1 + rng.permutation(maxp), jnp.int32)
    return (J(rng.randn(t, h, dn)), J(rng.randn(t, h, dr)), pool, row,
            J(rng.randn(rank, h * (dn + dv)) * 0.3))


def test_the_absorbed_form_is_the_per_head_form():
    """16 queries at positions 0..15 over a shuffled page row, everything
    in sight attended: queries carried into the latent and a mix of latent
    rows taken through W_uv (decode's form, over gathered rows), against
    keys and values expanded a head, two blocks of 8 keys with the softmax
    carried across (a chunk's form)."""
    rng = np.random.RandomState(0)
    t, h, rank, dr, dn, dv, ps, maxp = 16, 4, 8, 4, 6, 5, 4, 6
    q_nope, q_rope, pool, row, w_ukv = _latent_case(rng, t, h, rank, dr, dn,
                                                    dv, ps, maxp)
    qpos = jnp.arange(t, dtype=jnp.int32)
    scale = (dn + dr) ** -0.5
    with jax.default_matmul_precision("highest"):
        per_head, n = mla.latent_prefill(
            q_nope, q_rope, pool, 1, row, qpos, w_ukv, rank=rank, rope=dr,
            dv=dv, scale=scale, key_block=8, head_block=2)
        assert int(n) == t * (t + 1) // 2
        w3 = w_ukv.reshape(rank, h, dn + dv)
        q_lat = jnp.concatenate(
            [jnp.einsum("thd,chd->thc", q_nope, w3[..., :dn]), q_rope], -1)
        pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (1, t, t))
        ok = pos <= qpos[None, :, None]
        rows = row[pos // ps] * ps + pos % ps            # the pool's rows
        o_lat = mla.latent_attention(q_lat[None], pool, 1, rows, ok,
                                     rank=rank, scale=scale)[0]
        absorbed = jnp.einsum("thc,chv->thv", o_lat, w3[..., dn:])
    np.testing.assert_allclose(absorbed, per_head, atol=2e-5)
    assert float(jnp.abs(per_head).max()) > 0.1


def _numpy_select(scores, qpos, topk):
    out = []
    for t, p in enumerate(qpos):
        s = scores[t, :p + 1]
        order = sorted(range(len(s)), key=lambda i: (-s[i], i))
        out.append(sorted(order[:topk]))
    return out


@pytest.mark.parametrize("block,packed", [(8, True), (24, True), (64, True),
                                          (24, False)])
def test_selection_is_exact_with_ties_to_the_lower_position(block, packed):
    """Index keys drawn from FOUR distinct rows, so that scores tie in
    droves; 3 sequences of different lengths, one query each (decode's
    shape) and a chunk of queries (prefill's); key blocks of 8, 24 and the
    whole table, position and page packed into the sort's second key or the
    stable sort that a larger pool takes: the walk stops at the furthest
    query, and what it keeps is numpy's top-k with ties to the lower
    position."""
    rng = np.random.RandomState(1)
    b, hi, di, ps, maxp, topk = 3, 2, 8, 4, 16, 6
    rows = rng.randn(4, di).astype(np.float32)
    ki = rows[rng.randint(0, 4, size=(2, 1 + b * maxp, ps))]
    ki_pool = jnp.asarray(ki)
    table = jnp.asarray(1 + np.arange(b * maxp).reshape(b, maxp), jnp.int32)
    qi = jnp.asarray(rng.randn(b, 5, hi, di), jnp.float32)
    w = jnp.asarray(rng.randn(b, 5, hi), jnp.float32)
    qpos = jnp.asarray([[3, 4, 5, 6, -1], [40, 41, 42, 43, 44],
                        [20, 21, -1, -1, -1]], jnp.int32)
    with jax.default_matmul_precision("highest"):
        rows, ok = mla.index_select(qi, w, ki_pool, 1, table, qpos, topk,
                                    block=block, score_block=8,
                                    packed=packed)
        # a sequence's pages are contiguous here: rows back to positions
        pos = rows - (table[:, :1] * ps)[:, :, None]
        for i in range(b):
            keys = jnp.asarray(ki[1, np.asarray(table[i])].reshape(-1, di))
            sc = np.asarray(mla.index_scores(qi[i:i + 1], w[i:i + 1],
                                             keys[None]))[0]
            live = [t for t in range(5) if int(qpos[i, t]) >= 0]
            want = _numpy_select(sc[live], [int(qpos[i, t]) for t in live],
                                 topk)
            for t, w_t in zip(live, want):
                got = sorted(np.asarray(pos[i, t])[np.asarray(ok[i, t])]
                             .tolist())
                assert got == w_t, (i, t)
            for t in set(range(5)) - set(live):
                assert not np.asarray(ok[i, t]).any()
            # the same choice as a mask (a chunk's form): every score, each
            # query's cut, and how many keys on the cut are in
            scores, cut, room = mla.index_threshold(
                qi[i], w[i], ki_pool, 1, table[i], qpos[i], topk,
                block=block, score_block=8)
            seen = jnp.zeros(5, jnp.int32)
            keep = []
            for lo in range(0, scores.shape[1], 16):       # blocks of 16
                s_pos = jnp.arange(lo, min(lo + 16, scores.shape[1]))
                kp, seen = mla.chosen(scores[:, lo:lo + 16], cut, room, seen,
                                      s_pos[None, :] <= qpos[i][:, None])
                keep.append(np.asarray(kp))
            keep = np.concatenate(keep, axis=1)
            for t, w_t in zip(live, want):
                assert np.flatnonzero(keep[t]).tolist() == w_t, (i, t)
            for t in set(range(5)) - set(live):
                assert not keep[t].any()


def test_grouped_arm_is_the_dense_arm_under_skewed_routing():
    """A router tilted so that two of the four held experts take most rows,
    one takes none, and some tokens have no held expert: the sorted,
    grouped product is the masked dense one, both scorings, and the counts
    are the same."""
    rng = np.random.RandomState(3)
    t, d, f, e, k, held = 40, 32, 16, 16, 3, (4, 8)
    x = rng.randn(t, d).astype(np.float32)
    x[:, :3] = 0.0
    x[:, 0], x[:18, 1], x[18:36, 1], x[36:, 2] = 1.0, 1.0, -1.0, 1.0
    x = jnp.asarray(x)
    router = rng.randn(d, e).astype(np.float32) * 0.3
    router[:2, 4:7] = [[0, 0, -30], [4, -4, 0]]    # 6 never, 4 or 5 mostly
    router[2, :4] = 9.0                      # the last four tokens: 0-3 only
    w1 = jnp.asarray(rng.randn(4, d, 2 * f) * 0.2, jnp.float32)
    w2 = jnp.asarray(rng.randn(4, f, d) * 0.2, jnp.float32)
    bias = jnp.asarray(rng.randn(e) * 0.05, jnp.float32)
    bias = bias.at[6].set(-5.0)
    for scoring in ("softmax", "sigmoid"):
        kw = dict(top_k=k, held=held, scoring=scoring,
                  bias=bias if scoring == "sigmoid" else None,
                  counts=jnp.zeros(5, jnp.int32))
        with jax.default_matmul_precision("highest"):
            dense, c_dense = moe.routed_experts(x, jnp.asarray(router), w1,
                                                w2, impl="dense", **kw)
            grouped, c_grouped = moe.routed_experts(
                x, jnp.asarray(router), w1, w2, impl="grouped", **kw)
        np.testing.assert_allclose(grouped, dense, atol=2e-6)
        assert c_dense.tolist() == c_grouped.tolist()
        assert int(c_dense[2]) == 0 and int(c_dense[:4].max()) > 15
        assert float(jnp.abs(dense).max()) > 0.05
        # a token with no held expert gets exactly zero from both arms
        idx, _ = moe.route(x, jnp.asarray(router), k, scoring,
                           kw["bias"])
        orphan = ~((idx >= 4) & (idx < 8)).any(-1)
        assert bool(orphan.any())
        assert float(jnp.abs(grouped[orphan]).max()) == 0.0


def test_grouped_arm_gives_a_dead_token_no_row():
    """With ``valid`` the grouped arm sorts a dead token's assignments past
    the last group: the live tokens get what they get without it, the dead
    ones zero, the groups hold the live rows alone (the counts say how
    many), and a call whose tokens are all dead multiplies nothing."""
    rng = np.random.RandomState(5)
    t, d, f, e, k, held = 12, 32, 16, 8, 2, (0, 4)
    x = jnp.asarray(rng.randn(t, d), jnp.float32)
    router = jnp.asarray(rng.randn(d, e) * 0.3, jnp.float32)
    w1 = jnp.asarray(rng.randn(4, d, 2 * f) * 0.2, jnp.float32)
    w2 = jnp.asarray(rng.randn(4, f, d) * 0.2, jnp.float32)
    valid = jnp.asarray([True, False] * 6)
    kw = dict(top_k=k, held=held, scoring="sigmoid", impl="grouped",
              counts=jnp.zeros(5, jnp.int32))
    with jax.default_matmul_precision("highest"):
        every, c_all = moe.routed_experts(x, router, w1, w2, **kw)
        live, c_live = moe.routed_experts(x, router, w1, w2, valid=valid,
                                          **kw)
        none, c_none = moe.routed_experts(x, router, w1, w2,
                                          valid=jnp.zeros(t, bool), **kw)
    np.testing.assert_allclose(live[::2], every[::2], atol=2e-6)
    assert float(jnp.abs(every[1::2]).max()) > 0.01
    assert float(jnp.abs(live[1::2]).max()) == 0.0
    assert int(c_live[-1]) == 6 * k and int(c_all[-1]) == t * k
    assert 0 < int(c_live[:4].sum()) < int(c_all[:4].sum())
    assert float(jnp.abs(none).max()) == 0.0 and c_none.tolist() == [0] * 5


def test_the_registry_picks_the_arm_by_what_dense_would_waste():
    """Off a TPU (these tests; the rule on one TPU, where ``pallas`` takes
    the sparse calls and the chunks: tests/test_moe_pallas.py): 256 experts
    and 8 a token (32x the needed work under ``dense``) in a decode step's
    few tokens, which leave half the held experts unhit: ``grouped``
    first; the same in a chunk of 512 (every held expert hit), and 72 and
    10, Granite's, at any size: ``dense``; a forced arm wins either way."""
    from paddle_tpu.kernels import registry
    cands = registry.ops()["moe_experts"].candidates
    assert cands(dict(experts=256, top_k=8, tokens=24))[0] == "grouped"
    assert cands(dict(experts=256, top_k=8, tokens=512))[0] == "dense"
    # the cut is in the share of the held experts a call is expected to
    # hit, 1 - (1 - top_k / experts) ** tokens, at 0.8: 70% at 48 tokens of
    # 320 experts, 80.2% at 64
    assert cands(dict(experts=320, top_k=8, tokens=48))[0] == "grouped"
    assert cands(dict(experts=320, top_k=8, tokens=64))[0] == "dense"
    assert cands(dict(experts=384, top_k=8, tokens=32))[0] == "grouped"
    assert cands(dict(experts=256, top_k=8, tokens=128))[0] == "dense"
    assert cands(dict(experts=72, top_k=10, tokens=64))[0] == "dense"
    assert cands({})[0] == "dense"
    assert "pallas" not in cands(dict(experts=256, top_k=8, tokens=24))
    assert registry.dispatch(
        "moe_experts", forced="dense",
        ctx=dict(experts=256, top_k=8, tokens=24)) == "dense"


def test_the_sigmoid_router_is_noaux_tc():
    """Chosen by score + bias, weighed by the scores alone over their sum,
    times the scaling factor."""
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(6, 8), jnp.float32)
    w = jnp.asarray(rng.randn(8, 5), jnp.float32)
    bias = jnp.asarray([0.0, 0.0, 3.0, 0.0, 0.0])       # 2 always chosen
    with jax.default_matmul_precision("highest"):
        idx, gates = moe.route(x, w, 2, "sigmoid", bias, 2.5)
        sc = 1 / (1 + np.exp(-(np.asarray(x) @ np.asarray(w))))
    for t in range(6):
        want = np.argsort(-(sc[t] + np.asarray(bias)))[:2]
        assert sorted(np.asarray(idx[t]).tolist()) == sorted(want.tolist())
        assert 2 in np.asarray(idx[t]).tolist()
        g = sc[t][np.asarray(idx[t])]
        np.testing.assert_allclose(gates[t], 2.5 * g / g.sum(), rtol=1e-5)


# ------------------------------------------------------------- the shares

def test_the_shares_add_up(tiny):
    """Four chips, each with a quarter of the routed experts: their routed
    parts and the shared expert ONCE are the uncut reference layer, and the
    program's kernel, either arm, gives each chip's part."""
    cfg, _ = tiny
    e, n = cfg.n_routed_experts, 4
    full = dm.init_params(dataclasses.replace(cfg, experts_held=(0, e)),
                          seed=11, std=0.3)
    p = {k[len("L2.f."):]: v for k, v in full.items()
         if k.startswith("L2.f.")}
    b = jnp.asarray(np.random.RandomState(1).randn(24, cfg.hidden_size),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.sizes(ref_config(cfg, (0, e)))
        shared = ref.gated(b, p["shared.w1"], p["shared.w2"], "f32")
        uncut = ref.experts(b, p, whole, "f32")
        total = shared
        for lo in range(0, e, e // n):
            hi = lo + e // n
            mine = dict(p, w1=p["w1"][lo:hi], w2=p["w2"][lo:hi])
            part = ref.experts(b, mine, ref.sizes(ref_config(cfg, (lo, hi))),
                               "f32") - shared
            total = total + part
            assert float(jnp.abs(part).max()) > 1e-3      # each is alive
            for arm in ("dense", "grouped"):
                got = moe.routed_experts(
                    b, p["router"], mine["w1"], mine["w2"],
                    top_k=cfg.experts_per_token, held=(lo, hi),
                    scoring="sigmoid", bias=p["bias"], impl=arm)
                np.testing.assert_allclose(got, part, atol=3e-6)
    np.testing.assert_allclose(total, uncut, atol=3e-6)


# --------------------------------------------------------------- the seam

def test_a_latent_family_keeps_one_row_a_token_and_no_twin():
    """At the published widths a token costs a full layer 512 + 64 latent
    values and 128 index-key values, 704 x 2 B in bfloat16, 2,816 B over
    the two full layers: what the equations use. The pool's latent row is
    those 576 values and zeros up to a whole number of 128-lane tiles, 640
    (what the chip's tiling stores either way), so a token holds 3,072 B;
    the pool has no V twin, and the gauges say which part holds what."""
    kw = dict(layer_types=("full_attention",) + dm.PERIOD,
              experts_held=(0, 32), vocab_size=19008)
    ecfg = EngineConfig(page_size=16, max_slots=2, max_seq_len=64,
                        prefix_cache=False)
    cfg = dm.Dots3NoteConfig(**kw)
    used = cfg.kv_lora_rank + cfg.qk_rope_head_dim + cfg.index_head_dim
    assert used == 704 and used * 2 * len(cfg.full_layers) == 2816
    assert cfg.latent_width == 640 and cfg.latent_width % dm.LANES == 0
    assert dm.tiny_config().latent_width == dm.LANES
    fam = dm.family(cfg)
    cache = DeviceCache.allocate(fam, ecfg, 9, jnp.bfloat16)
    assert cache.k.shape == (2, 9, 16, 640) and cache.v.shape == (2, 9, 16,
                                                                  128)
    assert cache.bytes_per_token == 3072
    g = metrics.gauge
    assert g("engine.kv_bytes_per_token").value == 3072
    assert g("engine.cache_bytes.paged").value == 9 * 16 * 3072
    assert g("engine.cache_bytes.paged.latent").value == 9 * 16 * 2 * 640 * 2
    assert g("engine.cache_bytes.paged.index_key").value == \
        9 * 16 * 2 * 128 * 2
    rings = [a.shape for a in cache.state]
    assert rings == [(2, 544, 1088)] * 3
    assert g("engine.cache_bytes.window").value == 3 * 2 * 544 * 1088 * 2
    one = dataclasses.replace(fam, page_rows=(("latent", 640),))
    alone = DeviceCache.allocate(one, ecfg, 9, jnp.bfloat16)
    assert alone.v.size == 0 and alone.bytes_per_token == 2 * 640 * 2
    with pytest.raises(ValueError, match="int8"):
        DeviceCache.allocate(fam, dataclasses.replace(ecfg, kv_dtype="int8"),
                             9, jnp.bfloat16)


def _other_family(name):
    """(model, pooled layers, row width) of a tiny model of ``name``."""
    if name == "gpt":
        import paddle_tpu as paddle
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        paddle.seed(0)
        return GPTForCausalLM(GPTConfig(
            vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
            max_position_embeddings=64)).eval(), 2, 32
    if name == "phi4flash":
        from paddle_tpu.models import phi4flash as pf
        cfg = pf.tiny_config()
        return (pf.Phi4FlashForCausalLM(cfg, pf.init_params(cfg)), 1,
                cfg.kv_width)
    from paddle_tpu.models import granitemoehybrid as gm
    cfg = gm.tiny_config()
    return (gm.GraniteMoeHybridForCausalLM(cfg, gm.init_params(cfg)),
            cfg.n_attention, cfg.kv_width)


@pytest.mark.parametrize("name", ["gpt", "phi4flash", "granitemoehybrid"])
@pytest.mark.parametrize("kv_dtype", ["native", "int8"])
def test_the_other_families_pools_are_as_they_were(name, kv_dtype):
    """K and V twins of ``kv_heads x head_dim``, ``bytes_per_token`` and
    the paged gauge as before the seam's change."""
    model, nl, width = _other_family(name)
    fam = family_of(model)
    assert fam.page_rows == ()
    if kv_dtype == "int8" and fam.state is not None:
        pytest.skip("a family with state refuses an int8 pool")
    ecfg = EngineConfig(page_size=4, max_slots=2, max_seq_len=32,
                        kv_dtype=kv_dtype, prefix_cache=False)
    cache = DeviceCache.allocate(fam, ecfg, 7, jnp.float32)
    assert cache.k.shape == cache.v.shape == (nl, 7, 4, width)
    item = 1 if kv_dtype == "int8" else 4
    per_tok = nl * 2 * (width * item
                        + (fam.kv_heads * 4 if kv_dtype == "int8" else 0))
    assert cache.bytes_per_token == per_tok
    assert metrics.gauge("engine.cache_bytes.paged").value == \
        7 * 4 * per_tok


# ------------------------------------------------------------ the engine

def test_engine_serves_greedy_tokens_of_the_reference(tiny):
    """Three requests of different lengths share the batch (one-shot,
    chunked, chunked with a ragged tail); each one's tokens are the
    reference's greedy continuation of its own prompt."""
    cfg, params = tiny
    eng = _engine(cfg, params)
    assert eng.kv_bytes_per_token == 2 * (cfg.latent_width
                                          + cfg.index_head_dim) * 4
    prompts = [_prompt(37, 11), _prompt(5, 12), _prompt(20, 13)]
    reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
    eng.run_until_idle()
    for p, r in zip(prompts, reqs):
        out = np.asarray(r.result())
        assert out[:len(p)].tolist() == p.tolist()
        lg = _reference_logits(cfg, params, out)[len(p) - 1:-1]
        assert lg.argmax(-1).tolist() == out[len(p):].tolist()


def test_a_reused_slot_serves_like_a_fresh_engine(tiny):
    cfg, params = tiny
    a, b = _prompt(26, 31), _prompt(11, 32)
    eng = _engine(cfg, params, max_slots=1)
    ra = eng.submit(a, max_new_tokens=10)
    rb = eng.submit(b, max_new_tokens=10)
    eng.run_until_idle()
    fresh = _engine(cfg, params, max_slots=1)
    rf = fresh.submit(b, max_new_tokens=10)
    fresh.run_until_idle()
    assert ra.done and np.asarray(rb.result()).tolist() == \
        np.asarray(rf.result()).tolist()


def test_counts_ride_the_tokens_readback_and_nothing_recompiles(tiny):
    """Routing and selection counts of every token the engine computed
    reach the host with the tokens (no readback of their own), each new
    kernel is counted where it is built, and a warm engine compiles nothing
    more whatever joins and retires."""
    cfg, params = tiny
    names = ("engine.moe.assignments", "engine.moe.assignments_held",
             "engine.sparse.keys_scored", "engine.sparse.keys_attended",
             "engine.sparse.keys_attended.decode",
             "engine.moe.experts_hit.decode",
             "engine.moe.experts_hit.prefill", "engine.d2h_transfers",
             "engine.steps", "engine.prefill_launches")
    built = {k: metrics.counter(f"kernel.dispatch.{k}").value for k in
             ("moe_experts.dense", "mla_attention.xla", "mla_index.xla",
              "mla_window.xla", "rotary.xla")}
    eng = _engine(cfg, params)
    assert eng._tok_dev.shape == (SLOTS + cfg.n_held + 6,)
    eng.warmup(prompt_lens=[5, 9, 37])
    assert sorted(k[0] for k in eng._programs) == \
        ["decode", "prefill", "prefill_chunk"]
    for k, v in built.items():
        assert metrics.counter(f"kernel.dispatch.{k}").value > v, k
    n = metrics.counter("engine.compile_count").value
    c0 = {k: metrics.counter(k).value for k in names}
    tot0 = np.asarray(dm.expert_totals(cfg.experts_held) or [0] * cfg.n_held)
    harvests0 = len(metrics.spans("engine.harvest"))
    prompts = [_prompt(37, 41), _prompt(9, 42), _prompt(5, 43)]
    reqs = [eng.submit(p, max_new_tokens=7) for p in prompts]
    for _ in range(3):
        eng.step()
    assert all(len(e) == 3 for e in eng._inflight)
    reqs.append(eng.submit(_prompt(16, 99), max_new_tokens=7))
    prompts.append(_prompt(16, 99))
    eng.run_until_idle()
    assert all(r.done for r in reqs)
    assert metrics.counter("engine.compile_count").value == n
    grew = {k: metrics.counter(k).value - c0[k] for k in names}
    # tokens through the stack: every prompt token once, and each generated
    # token but a request's last (sampled and never fed back)
    computed = sum(len(p) + 7 - 1 for p in prompts)
    n_moe = cfg.n_layers - cfg.first_dense
    assert grew["engine.moe.assignments"] == \
        computed * cfg.experts_per_token * n_moe
    held = grew["engine.moe.assignments_held"]
    assert 0.25 < held / grew["engine.moe.assignments"] < 0.75
    tot = np.asarray(dm.expert_totals(cfg.experts_held)) - tot0
    assert tot.sum() == held and (tot > 0).all()
    positions = [t for p in prompts for t in range(len(p) + 7 - 1)]
    assert grew["engine.sparse.keys_scored"] == \
        sum(t + 1 for t in positions) * len(cfg.full_layers)
    assert grew["engine.sparse.keys_attended"] == \
        sum(min(t + 1, cfg.index_topk) for t in positions) \
        * len(cfg.full_layers)
    # the decode steps' part: every generated token fed back
    fed = [len(p) + i for p in prompts for i in range(7 - 1)]
    assert grew["engine.sparse.keys_attended.decode"] == \
        sum(min(t + 1, cfg.index_topk) for t in fed) * len(cfg.full_layers)
    # held experts hit: at least one wherever a held assignment fell, at
    # most all held a layer and call, and never more than the assignments
    hit = grew["engine.moe.experts_hit.decode"] \
        + grew["engine.moe.experts_hit.prefill"]
    assert grew["engine.moe.experts_hit.decode"] > 0
    assert grew["engine.moe.experts_hit.prefill"] > 0
    assert hit <= held
    calls = grew["engine.steps"] + grew["engine.prefill_launches"]
    assert hit <= calls * n_moe * cfg.n_held
    assert grew["engine.d2h_transfers"] == \
        len(metrics.spans("engine.harvest")) - harvests0


# ------------------------------------------------------------- refusals

@pytest.mark.parametrize("knob", [dict(prefix_cache=True),
                                  dict(speculate_k=2),
                                  dict(kv_host_tier_bytes=1 << 20),
                                  dict(kv_disk_tier_bytes=1 << 20)],
                         ids=["prefix_cache", "speculate_k", "host_tier",
                              "disk_tier"])
def test_configuration_refuses_what_pages_alone_cannot_restore(tiny, knob):
    cfg, params = tiny
    with pytest.raises(RecurrentStateUnsupported):
        _engine(cfg, params, **knob)


@pytest.mark.parametrize("call", ["prefill_export", "submit_prefill_stream",
                                  "import_request", "submit_import",
                                  "drain_migrate"])
def test_calls_refuse_what_pages_alone_cannot_restore(tiny, call):
    cfg, params = tiny
    eng = _engine(cfg, params)
    with pytest.raises(RecurrentStateUnsupported) as e:
        if call == "prefill_export":
            eng.prefill_export(_prompt(9, 1))
        elif call == "submit_prefill_stream":
            eng.submit_prefill_stream(_prompt(9, 1))
        elif call == "import_request":
            eng.import_request(object())
        elif call == "submit_import":
            eng.submit_import(object())
        else:
            eng.drain(migrate=True)
    wire = f"{type(e.value).__name__}: {e.value}"
    assert isinstance(from_wire(wire), RecurrentStateUnsupported)
    assert "dots3_note" in str(e.value)
    r = eng.submit(_prompt(6, 2), max_new_tokens=3)
    eng.run_until_idle()
    assert len(r.result()) == 9


def test_a_config_that_cannot_be_is_refused():
    with pytest.raises(ValueError, match="experts_held"):
        dm.tiny_config(experts_held=(4, 9))
    with pytest.raises(ValueError, match="layer_types"):
        dm.tiny_config(layer_types=("full_attention", "linear_attention"))
    with pytest.raises(ValueError, match="index_rope_dim"):
        dm.tiny_config(index_rope_dim=16)
    with pytest.raises(ValueError, match="scoring"):
        moe.route(jnp.zeros((2, 4)), jnp.zeros((4, 3)), 1, "tanh")
    with pytest.raises(KeyError, match="missing parameter"):
        dm.Dots3NoteForCausalLM(dm.tiny_config(), {})

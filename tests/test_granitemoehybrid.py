"""Granite-4.0-H (``granitemoehybrid``) through the engine's model seam, at
the tiny preset (a period of 4 layers: Mamba-2, Mamba-2, grouped-query
attention, Mamba-2; 8 experts of which 4 are held, 3 a token; page 4, chunk
16 = two blocks of the scan), on the CPU in float32, held to the benchmark's
plain reference (benchmarks/reference/granitemoehybrid.py, which imports
nothing of paddle_tpu).

- the step functions' logits, prefill chunks then decode through pages and
  recurrent state, against the reference's full forward with the same share
  of the experts; controls that fail the tolerance (the reference in fp8 and
  bf16, a state kept in bfloat16, a dropped ``D``, the wrong score scale);
- the shares add up: two chips' routed parts and the shared expert once are
  the uncut layer;
- the kernels: the chunked scan against the token-by-token recurrence, with
  carried state and padded tails; grouped queries with ``scale=`` in both
  attention ops and both arms against dense attention, the GPT-2 signatures
  bit for bit as before; a token none of whose experts is held;
- the engine: greedy tokens, routing counts on the tokens' readback, no
  recompilation, every refusal of a model with recurrent state.
"""
import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from paddle_tpu.inference.engine import DecodeEngine, EngineConfig  # noqa: E402
from paddle_tpu.inference.errors import (RecurrentStateUnsupported,  # noqa: E402
                                         from_wire)
from paddle_tpu.kernels import moe, ssm2  # noqa: E402
from paddle_tpu.kernels import paged_attention as pa  # noqa: E402
from paddle_tpu.models import granitemoehybrid as gm  # noqa: E402
from paddle_tpu.observability import metrics  # noqa: E402
from reference import granitemoehybrid as ref  # noqa: E402

PAGE, CHUNK, SLOTS, MAX_SEQ = 4, 16, 3, 64
# float32 on both sides; what differs is the order of sums (the chunked scan
# against the token-by-token recurrence, paged against dense attention). The
# largest sound reading over the cases below is 2.1e-7 of the largest logit:
# the tolerance is 24x that. The controls read 5.1e-5 (the SSM state kept in
# bfloat16: the weakest), 1.8e-3 (every product in bf16), 0.029 (in fp8),
# 0.039 (scores scaled by 1/sqrt(dh)), 0.17 (no ``D``)
TOL = 5e-6


def ref_config(cfg, held=None):
    """The reference's view of a program configuration: the published
    keys, ``assumed`` for the head width, and the share of the experts."""
    lo, hi = held or cfg.experts_held
    return dict(
        hidden_size=cfg.hidden_size, num_hidden_layers=cfg.n_layers,
        layer_types=list(cfg.layer_types), vocab_size=cfg.vocab_size,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads,
        assumed=dict(head_dim=cfg.head_dim),
        intermediate_size=cfg.intermediate_size,
        shared_intermediate_size=cfg.shared_intermediate_size,
        router_outputs=cfg.num_experts, num_local_experts=hi - lo,
        experts_first=lo, num_experts_per_tok=cfg.experts_per_token,
        mamba_n_heads=cfg.mamba_n_heads, mamba_d_head=cfg.mamba_d_head,
        mamba_d_state=cfg.mamba_d_state, mamba_d_conv=cfg.mamba_d_conv,
        mamba_n_groups=1, rms_norm_eps=cfg.rms_norm_eps,
        embedding_multiplier=cfg.embedding_multiplier,
        residual_multiplier=cfg.residual_multiplier,
        attention_multiplier=cfg.attention_multiplier,
        logits_scaling=cfg.logits_scaling)


@pytest.fixture(scope="module")
def tiny():
    cfg = gm.tiny_config()
    # std 0.2: every mixer and every expert moves the logits by far more
    # than the tolerance
    return cfg, gm.init_params(cfg, seed=7, std=0.2)


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(0, 96, size=n).astype(np.int32)


def _engine(cfg, params, **over):
    kw = dict(page_size=PAGE, max_slots=SLOTS, max_seq_len=MAX_SEQ,
              prefill_chunk_tokens=CHUNK, prefix_cache=False, inflight=2,
              min_bucket=8)
    kw.update(over)
    return DecodeEngine(gm.GraniteMoeHybridForCausalLM(cfg, params),
                        EngineConfig(**kw))


def _reference_logits(cfg, params, ids, precision="f32"):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(params, jnp.asarray(ids),
                                     ref_config(cfg), precision))


def step_logits(cfg, params, prompt, n_decode, slot=1, chunk=CHUNK):
    """Logits the step functions give for ``prompt`` prefilled in chunks
    and ``n_decode`` greedy tokens decoded, in slot ``slot`` of SLOTS:
    ``[n_decode + 1, V]`` (the last prompt position, then each decoded
    one), the tokens, and the routing vector the steps added up."""
    maxp = MAX_SEQ // PAGE
    pool = jnp.zeros((cfg.n_attention, 1 + SLOTS * maxp, PAGE, cfg.kv_width),
                     jnp.float32)
    kc, vc = pool, pool
    # a dirty slot: whatever the last sequence left must not show
    state = tuple(jnp.zeros(s, d) + 3.0 for _, _, s, d in
                  gm.state_arrays(cfg, SLOTS, PAGE, jnp.float32))
    counts = jnp.zeros(gm.step_counts(cfg), jnp.int32)
    row = np.arange(1 + slot * maxp, 1 + (slot + 1) * maxp, dtype=np.int32)
    table = np.zeros((SLOTS, maxp), np.int32)
    table[slot] = row
    chunk_fn = jax.jit(lambda *a, state, counts: gm.prefill_chunk_step(
        *a, cfg=cfg, state=state, slot=jnp.int32(slot), counts=counts))
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
    decode = jax.jit(lambda p, ids, cache, act: gm.decode_step(
        p, ids, cache, act, cfg=cfg))
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


def _gap(cfg, params, prog_params, prompt, n_decode, prog_cfg=None):
    """Largest |logit| difference between the program's prefill-then-decode
    logits and the reference's full forward over the same tokens, as a
    share of the reference's largest |logit|."""
    with jax.default_matmul_precision("highest"):
        got, toks, _ = step_logits(prog_cfg or cfg, prog_params, prompt,
                                   n_decode)
    ids = np.concatenate([prompt, np.asarray(toks, np.int32)])
    want = _reference_logits(cfg, params, ids)[len(prompt) - 1:]
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize("n_prompt", [5, 16, 37, 48],
                         ids=["short", "one-chunk", "three-chunks-ragged",
                              "three-chunks-full"])
def test_step_logits_match_the_reference(tiny, n_prompt):
    """Prefill in chunks of 16 (two blocks of the scan each) with carried
    state, then 12 decode steps, against the reference's one full forward
    with no cache and a token-by-token recurrence."""
    cfg, params = tiny
    assert _gap(cfg, params, params, _prompt(n_prompt, n_prompt), 12) < TOL


def test_every_mechanism_moves_the_logits(tiny):
    """The tolerance means something only if each kind of layer shows:
    zeroing one output projection moves the logits by far more."""
    cfg, params = tiny
    prompt = _prompt(21, 3)
    for leaf in ("m.out_proj", "a.o.w", "f.w2", "f.shared.w2"):
        broken = dict(params, **{leaf: jnp.zeros_like(params[leaf])})
        assert _gap(cfg, params, broken, prompt, 6) > 100 * TOL, leaf


@pytest.mark.parametrize("control", ["fp8", "bf16"])
def test_a_lower_precision_fails_the_tolerance(tiny, control):
    """What the comparison is for: the same forward with every matrix
    product's operands rounded to a lower type reads far over the tolerance
    the sound program holds."""
    cfg, params = tiny
    ids = np.concatenate([_prompt(37, 5), _prompt(12, 6)])
    want = _reference_logits(cfg, params, ids)
    got = _reference_logits(cfg, params, ids, control)
    assert np.abs(got - want).max() / np.abs(want).max() > 100 * TOL


@pytest.mark.parametrize("control", ["ssm_state_bf16", "no_D",
                                     "scale_rsqrt_dh", "renormalised_gates"])
def test_a_program_one_step_off_fails_the_tolerance(tiny, control):
    cfg, params = tiny
    prog_cfg, prog = cfg, params
    if control == "ssm_state_bf16":
        prog_cfg = dataclasses.replace(cfg, ssm_state_dtype="bfloat16")
    elif control == "no_D":
        prog = {k: jnp.zeros_like(v) if k.endswith(".D") else v
                for k, v in params.items()}
    elif control == "scale_rsqrt_dh":
        prog_cfg = dataclasses.replace(
            cfg, attention_multiplier=cfg.head_dim ** -0.5)
    else:
        # gates renormalised over the held experts: what a chip that took
        # its share for the whole model would compute
        prog_cfg = dataclasses.replace(cfg, num_experts=cfg.n_held)
        prog = dict(params, **{"f.router": params["f.router"][
            ..., :cfg.n_held]})
    gap = _gap(cfg, params, prog, _prompt(21, 5), 12, prog_cfg=prog_cfg)
    assert gap > 8 * TOL, gap


# ------------------------------------------------------------- the shares

def test_the_shares_add_up(tiny):
    """Two chips, each with half of the routed experts: their routed parts
    and the shared expert ONCE are the uncut reference layer; and the
    program's kernel gives each chip's part."""
    cfg, params = tiny
    e, half = cfg.num_experts, cfg.num_experts // 2
    full = gm.init_params(dataclasses.replace(cfg, experts_held=(0, e)),
                          seed=11, std=0.3)
    p = {k[2:]: v[1] for k, v in full.items() if k.startswith("f.")}
    b = jnp.asarray(np.random.RandomState(1).randn(24, cfg.hidden_size),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.sizes(ref_config(cfg, (0, e)))
        uncut = ref.routed(b, p, whole, "f32") \
            + ref.gated(b, p["shared.w1"], p["shared.w2"], "f32")
        parts = []
        for lo, hi in ((0, half), (half, e)):
            mine = dict(p, w1=p["w1"][lo:hi], w2=p["w2"][lo:hi])
            part = ref.routed(b, mine, ref.sizes(ref_config(cfg, (lo, hi))),
                              "f32")
            parts.append(part)
            got = moe.routed_experts(
                b, p["router"], mine["w1"], mine["w2"],
                top_k=cfg.experts_per_token, held=(lo, hi))
            np.testing.assert_allclose(got, part, atol=2e-6)
        total = parts[0] + parts[1] \
            + ref.gated(b, p["shared.w1"], p["shared.w2"], "f32")
    np.testing.assert_allclose(total, uncut, atol=2e-6)
    assert float(jnp.abs(parts[0]).max()) > 1e-3        # each half is alive
    assert float(jnp.abs(parts[1]).max()) > 1e-3


@pytest.mark.parametrize("held", [(0, 4), (1, 5)])
def test_a_token_none_of_whose_experts_is_held(held):
    """The router sends token 3 to experts 5, 6, 7 and this chip holds 0-3
    (or 1-4): its routed part is exactly zero, it is counted as routed (3
    assignments) and as held by nobody, and its neighbours are untouched."""
    rng = np.random.RandomState(2)
    t, d, e, f, k = 8, 16, 8, 8, 3
    x = jnp.asarray(rng.randn(t, d), jnp.float32)
    router = rng.randn(d, e).astype(np.float32) * 0.1
    x = x.at[3].set(0.0).at[3, 0].set(1.0)
    router[0] = [-9, -9, -9, -9, 0, 5, 6, 7]
    w1 = jnp.asarray(rng.randn(4, d, 2 * f), jnp.float32)
    w2 = jnp.asarray(rng.randn(4, f, d), jnp.float32)
    y, counts = moe.routed_experts(
        x, jnp.asarray(router), w1, w2, top_k=k, held=held,
        counts=jnp.zeros(5, jnp.int32))
    assert float(jnp.abs(y[3]).max()) == 0.0
    assert float(jnp.abs(y[2]).max()) > 0 and float(jnp.abs(y[4]).max()) > 0
    alone, c1 = moe.routed_experts(
        x[3:4], jnp.asarray(router), w1, w2, top_k=k, held=held,
        counts=jnp.zeros(5, jnp.int32))
    assert np.asarray(c1).tolist() == [0, 0, 0, 0, k]
    assert int(counts[-1]) == t * k and int(counts[:-1].sum()) < t * k


def test_a_chunk_with_an_orphan_token_matches_the_reference(tiny):
    """The same through a whole layer stack: a router that sends one token
    of the chunk past the held experts in every layer."""
    cfg, params = tiny
    prompt = _prompt(16, 9)
    router = np.array(params["f.router"])
    emb = np.asarray(params["embed"][prompt[5]])
    router[0] = 0.0
    router[0, :, 4:] = 50.0 * emb[:, None] / (emb ** 2).sum()
    tilted = dict(params, **{"f.router": jnp.asarray(router)})
    assert _gap(cfg, tilted, tilted, prompt, 4) < TOL


# -------------------------------------------------------------- the scan

def _sequential(dt, x, bm, cm, a, d_skip, s0):
    s, ys = np.array(s0, np.float64), []
    for t in range(dt.shape[0]):
        s = np.exp(dt[t] * a)[:, None, None] * s \
            + (dt[t][:, None] * x[t])[:, :, None] * bm[t][None, None, :]
        ys.append((s * cm[t]).sum(-1) + d_skip[:, None] * x[t])
    return np.stack(ys), s


def test_chunked_scan_is_the_sequential_recurrence():
    """Blocks of 8 over 40 tokens; then the same in two launches, the second
    carrying the first's state and ending in a padded tail whose junk must
    not show; then one decode update continues it."""
    rng = np.random.RandomState(0)
    t, h, p, n = 40, 4, 8, 16
    dt = np.abs(rng.randn(t, h)).astype(np.float32) * 0.3
    x = rng.randn(t, h, p).astype(np.float32)
    bm, cm = (rng.randn(t, n).astype(np.float32) for _ in range(2))
    a = -(np.abs(rng.randn(h)) + 0.5).astype(np.float32)
    d_skip = rng.randn(h).astype(np.float32)
    want, s_end = _sequential(dt, x, bm, cm, a, d_skip, np.zeros((h, p, n)))
    stack = jnp.full((2, 3, n, h * p), 7.0, jnp.float32)            # a dirty slot
    J = jnp.asarray
    with jax.default_matmul_precision("highest"):
        for block in (8, 16, 64):
            y, st = ssm2.ssm2_scan(stack, J(dt), J(x), J(bm), J(cm), J(a),
                                   J(d_skip), 1, True, layer=1, chunk=block)
            np.testing.assert_allclose(y, want, atol=2e-5)
            np.testing.assert_allclose(st[1, 1].T.reshape(h, p, n), s_end,
                                       atol=2e-6)
            assert float(jnp.abs(st[0] - 7).max()) == 0      # others alone
            assert float(jnp.abs(st[1, 0] - 7).max()) == 0
        y1, st = ssm2.ssm2_scan(stack, J(dt[:24]), J(x[:24]), J(bm[:24]),
                                J(cm[:24]), J(a), J(d_skip), 1, True,
                                layer=1, chunk=8)

        def tail(v, junk):
            out = np.full((32,) + v.shape[1:], junk, np.float32)
            out[:16] = v[24:]
            return J(out)
        y2, st = ssm2.ssm2_scan(st, tail(dt, 0.0), tail(x, 5.0),
                                tail(bm, 3.0), tail(cm, 2.0), J(a),
                                J(d_skip), 1, False, layer=1, chunk=8)
        np.testing.assert_allclose(np.concatenate([y1, y2[:16]]), want,
                                   atol=2e-5)
        np.testing.assert_allclose(st[1, 1].T.reshape(h, p, n), s_end,
                                   atol=2e-6)
        one = lambda v: jnp.zeros((3,) + v.shape[1:]).at[1].set(v[0])  # noqa
        more, s_more = _sequential(dt[:1], x[:1], bm[:1], cm[:1], a, d_skip,
                                   s_end)
        for impl in ("xla", "pallas"):      # pallas: the interpreter here
            y3, st2 = ssm2.ssm2_update(st, one(dt), one(x), one(bm), one(cm),
                                       J(a), J(d_skip),
                                       J([False, True, False]), layer=1,
                                       impl=impl)
            np.testing.assert_allclose(y3[1], more[0], atol=2e-5)
            np.testing.assert_allclose(st2[1, 1].T.reshape(h, p, n), s_more,
                                       atol=2e-6)
            # an inactive slot and the other layer are left as they were
            assert float(jnp.abs(st2[1, 0] - st[1, 0]).max()) == 0
            assert float(jnp.abs(st2[1, 2] - st[1, 2]).max()) == 0
            assert float(jnp.abs(st2[0] - st[0]).max()) == 0


# ------------------------------------------------- grouped-query attention

def _pools(rng, nl, b, maxp, ps, width):
    pages = 1 + b * maxp
    k = jnp.asarray(rng.randn(nl, pages, ps, width), jnp.float32)
    v = jnp.asarray(rng.randn(nl, pages, ps, width), jnp.float32)
    table = jnp.asarray(1 + np.arange(b * maxp).reshape(b, maxp), jnp.int32)
    return k, v, table


def _dense(q, k, v, scale, g):
    """q [nq, dh] over keys k, v [L, nkv, dh]; query head h reads h // g."""
    out = np.zeros(q.shape)
    for h in range(q.shape[0]):
        s = (np.asarray(q[h]) * scale) @ k[:, h // g].T
        p = np.exp(s - s.max())
        out[h] = (p / p.sum()) @ v[:, h // g]
    return out


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_grouped_query_decode_attention(impl):
    rng = np.random.RandomState(4)
    b, nq, nkv, dh, ps, maxp, scale = 3, 8, 2, 16, 4, 5, 0.3
    k, v, table = _pools(rng, 2, b, maxp, ps, nkv * dh)
    q = jnp.asarray(rng.randn(b, nq, dh), jnp.float32)
    pos = jnp.asarray([3, 10, 19], jnp.int32)
    got = pa._impl_call(impl, q, k, v, table, pos, 1, scale=scale)
    for i in range(b):
        n = int(pos[i]) + 1
        kk = np.asarray(k[1, table[i]]).reshape(-1, nkv, dh)[:n]
        vv = np.asarray(v[1, table[i]]).reshape(-1, nkv, dh)[:n]
        np.testing.assert_allclose(got[i], _dense(q[i], kk, vv, scale,
                                                  nq // nkv), atol=2e-6)


@pytest.mark.parametrize("start", [0, 4])
def test_grouped_query_prefill_attention(start):
    """A fresh chunk and one after four cached tokens, through the op's own
    dispatch: a grouped signature has the xla arm alone (the Pallas prefill
    kernel takes one K/V head a query head, and says so)."""
    rng = np.random.RandomState(5)
    nq, nkv, dh, ps, maxp, scale = 8, 2, 16, 4, 6, 0.3
    k, v, table = _pools(rng, 2, 3, maxp, ps, nkv * dh)
    c, valid = 8, 6
    q = jnp.asarray(rng.randn(1, c, nq, dh), jnp.float32)
    before = metrics.counter("kernel.dispatch.prefill_attention.xla").value
    got = pa.prefill_attention(q, k, v, table[2], start, valid, layer=1,
                               scale=scale)[0]
    assert metrics.counter(
        "kernel.dispatch.prefill_attention.xla").value == before + 1
    kk = np.asarray(k[1, table[2]]).reshape(-1, nkv, dh)
    vv = np.asarray(v[1, table[2]]).reshape(-1, nkv, dh)
    for i in range(valid):
        n = start + i + 1
        np.testing.assert_allclose(got[i], _dense(q[0, i], kk[:n], vv[:n],
                                                  scale, nq // nkv),
                                   atol=2e-6)
    with pytest.raises(ValueError, match="grouped queries take the xla arm"):
        pa._prefill_impl_call("pallas", q, k, v, table[2], start, valid, 1)


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_a_grouped_signature_takes_the_registry_preference(backend,
                                                           monkeypatch):
    """Grouped queries are not measured at start-up (the probe would build
    a second pool of the model's own size): the registry's viable set
    decides. Decode prefers the kernel where it is viable (a TPU); prefill
    has the xla arm alone, also against a forced flag; and one-to-one heads
    keep both arms in their old order, to be measured."""
    from paddle_tpu.kernels import registry
    monkeypatch.setattr(registry, "backend", lambda: backend)
    ops = registry.ops()
    on_tpu = backend == "tpu"
    assert ops["paged_attention"].candidates({"grouped": True}) == (
        ["pallas", "xla"] if on_tpu else ["xla"])
    assert ops["paged_attention"].candidates({}) == (
        ["xla", "pallas"] if on_tpu else ["xla"])
    assert ops["prefill_attention"].candidates({"grouped": True}) == ["xla"]
    assert pa.prefill_impl(8, 6, 4, 8, 16, jnp.float32,
                           grouped=True) == "xla"
    from paddle_tpu.framework.flags import set_flags
    set_flags({"tpu_prefill_impl": "pallas"})
    try:
        assert pa.prefill_impl(8, 6, 4, 8, 16, jnp.float32,
                               grouped=True) == "xla"
    finally:
        set_flags({"tpu_prefill_impl": "auto"})


def test_one_to_one_heads_read_as_before():
    """GPT-2's signatures (as many query heads as the pool has, no
    ``scale=``): the XLA arms give, bit for bit, what the math they ran
    before this file's PR gives, written out here; and an explicit ``scale``
    of ``1 / sqrt(dh)`` changes nothing in either arm."""
    rng = np.random.RandomState(6)
    b, nh, dh, ps, maxp = 3, 4, 16, 4, 5
    k, v, table = _pools(rng, 2, b, maxp, ps, nh * dh)
    q = jnp.asarray(rng.randn(b, nh, dh), jnp.float32)
    pos = jnp.asarray([3, 10, 19], jnp.int32)
    kk = pa.gather_kv(k, table, 1, nh).astype(jnp.float32)
    vv = pa.gather_kv(v, table, 1, nh).astype(jnp.float32)
    sc = jnp.einsum("bhd,blhd->bhl", q.astype(jnp.float32) * (1 / dh ** 0.5),
                    kk)
    sc = jnp.where((jnp.arange(kk.shape[1])[None] <= pos[:, None])[:, None],
                   sc, -1e30)
    before = jnp.einsum("bhl,blhd->bhd", jax.nn.softmax(sc, -1), vv)
    got = pa.paged_attention(q, k, v, table, pos, layer=1)
    assert np.array_equal(np.asarray(got), np.asarray(before))
    for impl in ("xla", "pallas"):
        a = pa._impl_call(impl, q, k, v, table, pos, 1)
        c = pa._impl_call(impl, q, k, v, table, pos, 1, scale=1 / dh ** 0.5)
        assert np.array_equal(np.asarray(a), np.asarray(c)), impl
    qc = jnp.asarray(rng.randn(1, 8, nh, dh), jnp.float32)
    row = table[1]
    kk = pa.gather_kv(k, row[None], 1, nh).astype(jnp.float32)
    vv = pa.gather_kv(v, row[None], 1, nh).astype(jnp.float32)
    sc = jnp.einsum("bqhd,bkhd->bhqk",
                    qc.astype(jnp.float32) * (1 / dh ** 0.5), kk)
    mask = jnp.arange(kk.shape[1])[None, :] <= (4 + jnp.arange(8))[:, None]
    sc = jnp.where(mask[None, None], sc, -1e30)
    before = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), vv)
    got = pa.prefill_attention(qc, k, v, row, 4, 8, layer=1)
    assert np.array_equal(np.asarray(got), np.asarray(before))


# ------------------------------------------------------------ the engine

def test_engine_serves_greedy_tokens_of_the_reference(tiny):
    """Three requests of different lengths share the batch (one-shot,
    chunked, chunked with a ragged tail); each one's tokens are the
    reference's greedy continuation of its own prompt."""
    cfg, params = tiny
    eng = _engine(cfg, params)
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


def test_routing_counts_ride_the_tokens_readback(tiny):
    """Every assignment of every token the engine computed is counted (in
    the step programs), the counts reach the host with the tokens and cost
    no readback of their own, and the in-flight entries stay triples."""
    cfg, params = tiny
    eng = _engine(cfg, params)
    assert eng._tok_dev.shape == (SLOTS + cfg.n_held + 1,)
    names = ("engine.moe.assignments", "engine.moe.assignments_held",
             "engine.d2h_transfers", "engine.tokens")
    c0 = {k: metrics.counter(k).value for k in names}
    tot0 = np.asarray(gm.expert_totals(cfg.experts_held) or [0] * cfg.n_held)
    harvests0 = len(metrics.spans("engine.harvest"))
    prompts = [_prompt(37, 41), _prompt(9, 42)]
    reqs = [eng.submit(p, max_new_tokens=7) for p in prompts]
    eng.step()
    assert all(len(e) == 3 for e in eng._inflight)
    eng.run_until_idle()
    assert all(r.done for r in reqs)
    grew = {k: metrics.counter(k).value - c0[k] for k in names}
    # tokens through the stack: every prompt token once, and each generated
    # token but a request's last (which is sampled and never fed back)
    computed = sum(len(p) + 7 - 1 for p in prompts)
    assert grew["engine.moe.assignments"] == \
        computed * cfg.experts_per_token * cfg.n_layers
    held = grew["engine.moe.assignments_held"]
    assert 0.25 < held / grew["engine.moe.assignments"] < 0.75
    tot = np.asarray(gm.expert_totals(cfg.experts_held)) - tot0
    assert tot.sum() == held and (tot > 0).all()
    assert grew["engine.d2h_transfers"] == \
        len(metrics.spans("engine.harvest")) - harvests0
    assert metrics.gauge("engine.state_bytes_per_slot").value == \
        cfg.n_mamba * 4 * ((cfg.mamba_d_conv - 1) * cfg.conv_dim
                           + cfg.d_inner * cfg.mamba_d_state)
    assert metrics.gauge("engine.cache_bytes.state").value == SLOTS * \
        metrics.gauge("engine.state_bytes_per_slot").value


def test_a_family_without_counts_keeps_its_chain():
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                                 num_heads=2, max_position_embeddings=64))
    eng = DecodeEngine(m.eval(), EngineConfig(page_size=4, max_slots=2))
    assert eng._tok_dev.shape == (2,) and eng._n_counts == 0


def test_no_step_program_relays_a_state_array_or_recompiles(tiny):
    """Every engine program addresses the state stacks with ``layer=``
    (building them counts nothing in ``kernel.state_relayout``), each new
    kernel is counted where it is built, and a warm engine compiles nothing
    more whatever joins and retires (tests/test_no_retrace.py's rule)."""
    cfg, params = tiny
    ops = ("ssm_update", "ssm_scan", "ssm2_update", "ssm2_scan")

    def relayouts():
        return sum(metrics.counter(f"kernel.state_relayout.{op}").value
                   for op in ops)
    before = relayouts()
    built = {k: metrics.counter(f"kernel.dispatch.{k}").value for k in
             ("moe_experts.dense", "ssm2_update.xla", "ssm2_scan.xla")}
    eng = _engine(cfg, params)
    eng.warmup(prompt_lens=[5, 9, 37])
    assert sorted(k[0] for k in eng._programs) == \
        ["decode", "prefill", "prefill", "prefill_chunk"]
    assert relayouts() == before
    for k, v in built.items():
        assert metrics.counter(f"kernel.dispatch.{k}").value > v, k
    n = metrics.counter("engine.compile_count").value
    reqs = [eng.submit(_prompt(k, 60 + k), max_new_tokens=5)
            for k in (37, 5, 9, 17, 3)]
    for _ in range(3):
        eng.step()
    reqs.append(eng.submit(_prompt(16, 99), max_new_tokens=3))
    eng.run_until_idle()
    assert all(r.done for r in reqs)
    assert metrics.counter("engine.compile_count").value == n
    one = jnp.zeros((SLOTS, cfg.mamba_d_state, cfg.d_inner))
    z = jnp.zeros((SLOTS, cfg.mamba_n_heads))
    ssm2.ssm2_update(one, z, jnp.zeros((SLOTS, cfg.mamba_n_heads,
                                        cfg.mamba_d_head)),
                     jnp.zeros((SLOTS, cfg.mamba_d_state)),
                     jnp.zeros((SLOTS, cfg.mamba_d_state)),
                     jnp.zeros(cfg.mamba_n_heads),
                     jnp.zeros(cfg.mamba_n_heads), jnp.ones(SLOTS, bool))
    assert relayouts() == before + 1


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
    assert "granitemoehybrid" in str(e.value)
    r = eng.submit(_prompt(6, 2), max_new_tokens=3)
    eng.run_until_idle()
    assert len(r.result()) == 9


def test_a_config_that_cannot_be_is_refused():
    with pytest.raises(ValueError, match="experts_held"):
        gm.tiny_config(experts_held=(4, 9))
    with pytest.raises(ValueError, match="layer_types"):
        gm.tiny_config(layer_types=("mamba", "conv"))
    cfg = gm.tiny_config()
    with pytest.raises(ValueError, match="held experts"):
        moe.routed_experts(jnp.zeros((2, 32)), jnp.zeros((32, 8)),
                           jnp.zeros((3, 32, 32)), jnp.zeros((3, 16, 32)),
                           top_k=3, held=cfg.experts_held)

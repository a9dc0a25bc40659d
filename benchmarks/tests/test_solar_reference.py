"""``reference/solar_open2.py`` against cases worked by hand, its count of
the cell's parameters by parts, the cell's traffic and configuration files,
and the cell's readers over observations written by hand."""
import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from harness import solar_bytes, spec, traffic
from reference import solar_open2 as ref

BENCH = Path(__file__).resolve().parents[1]
CFG = json.loads((BENCH / "configs" / "solar-open2-250b.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def small(**over):
    cfg = dict(CFG, hidden_size=8, moe_intermediate_size=4, vocab_size=16,
               num_hidden_layers=2, gqa_layers=[0], n_routed_experts=4,
               router_outputs=4, num_experts_per_tok=2,
               num_attention_heads=4, num_key_value_heads=2, head_dim=2,
               linear_attn_config=dict(CFG["linear_attn_config"], head_dim=4,
                                       num_heads=2))
    cfg.update(over)
    return cfg


def test_param_count_of_the_cell_by_parts():
    """The issue's arithmetic, part by part, and the file's."""
    p = ref.param_count(CFG)
    assert p["kda_mixer"] == 137_740_480
    assert p["softmax_mixer"] == 109_051_904
    assert p["one_expert"] == p["shared_expert"] == 15_728_640
    assert p["router_with_bias"] == 1_311_040 and p["two_norms"] == 8_192
    assert p["linear_layer_outside_experts"] == 154_788_352
    assert p["softmax_layer_outside_experts"] == 126_099_776
    assert p["tables_and_final_norm"] == 201_330_688
    assert 3 * 154_788_352 + 126_099_776 == 590_464_832
    assert p["held"] == 590_464_832 + 4 * 40 * 15_728_640 + 201_330_688 \
        == CFG["parameters"] == 3_308_377_920
    assert p["published"] == CFG["parameters_published"]
    assert round(p["published"] / 1e9, 2) == 250.29
    assert round(p["published_active"] / 1e9, 2) == 14.74
    assert solar_bytes.state_bytes_per_sequence(CFG) == 13_467_648
    assert solar_bytes.kv_bytes_per_token(CFG) == 4096
    assert solar_bytes.other_weight_bytes(CFG) == 2 * (
        3_308_377_920 - 160 * 15_728_640 - 24576 * 4096)


def test_the_configuration_keeps_every_published_number():
    if not CATALOG.is_file():
        pytest.skip("the catalog is not on this machine")
    rows = [json.loads(line) for line in open(CATALOG)]
    entry = next(r for r in rows if r["name"] == "Solar-Open2-250B")
    differs = sorted(k for k, v in entry["config"].items() if CFG.get(k) != v)
    assert differs == sorted(CFG["reduced"]) == [
        "gqa_layers", "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert CFG["source"] == entry["source_url"]
    bm = next(c for c in spec.benchmark()["configs"]
              if c["name"] == "solar-open2-250b")
    assert sorted(bm["reduced"]) == differs and bm["source"] == CFG["source"]
    assert CFG["deployment"]["chips_sharing_a_layer"] == 8
    assert (CFG["num_hidden_layers_published"],
            CFG["n_routed_experts_published"], CFG["router_outputs"],
            CFG["vocab_size_published"]) == (48, 320, 320, 196608)
    assert CFG["gqa_layers_published"] == entry["config"]["gqa_layers"]
    # the floors: a whole period and at least four layers, at least 8
    # experts, at least an eighth of the vocabulary
    assert CFG["num_hidden_layers"] == 4 and CFG["n_routed_experts"] >= 8
    assert CFG["vocab_size"] * 8 >= CFG["vocab_size_published"]


def test_the_cells_traffic_fits_its_engine():
    """Every request of the table fits ``max_seq_len``, and 48 slots of
    1,120 pages and the trash page are the pool."""
    tr = json.loads((BENCH / "traffic" / "docqa-closed-48.json").read_text())
    table = traffic.request_table(tr)
    sv = CFG["serve"]
    assert max(r["prompt"] + r["answer"] for r in table) == 16857 \
        <= sv["max_seq_len"]
    assert min(r["prompt"] for r in table) >= 4096
    assert max(r["prompt"] for r in table) == 16384
    assert min(r["answer"] for r in table) >= 256
    assert max(r["answer"] for r in table) == 2048
    assert sorted(r["prompt"] for r in table)[96] in range(8150, 8250)
    assert tr["clients"] == sv["max_slots"] == 48
    assert sv["num_pages"] == 48 * sv["max_seq_len"] // sv["page_size"] + 1
    assert sv["max_seq_len"] == 35 * sv["prefill_chunk_tokens"]


def test_a_layer_this_file_does_not_write_is_refused():
    for over in (dict(use_rope=True), dict(use_gqa_gate=False),
                 dict(kda_use_full_proj=True),
                 dict(kda_allow_neg_eigval=False),
                 dict(first_k_dense_replace=1), dict(norm_topk_prob=False),
                 dict(gqa_layers=[5]),
                 dict(linear_attn_config=dict(CFG["linear_attn_config"],
                                              num_kv_heads=4))):
        with pytest.raises(ValueError):
            ref.sizes(small(**over))


def test_the_norm_and_the_router_by_hand():
    s = ref.sizes(small())
    x = jnp.asarray([[3.0, 4.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0]])
    w = jnp.arange(1.0, 9.0)
    rms = np.sqrt(25.0 / 8 + 1e-5)
    np.testing.assert_allclose(ref.norm(x, w, s)[0, :2],
                               [3.0 / rms, 8.0 / rms], rtol=1e-6)
    # logits 2, 0, -1, 1; the bias lifts expert 2 over expert 3: chosen 0
    # and 2, weights sigmoid over their sum, times 1
    b = jnp.eye(8)[:1]
    wr = jnp.zeros((8, 4)).at[0].set(jnp.asarray([2.0, 0.0, -1.0, 1.0]))
    bias = jnp.asarray([0.0, 0.0, 0.5, 0.0])
    sig = 1 / (1 + np.exp(-np.asarray([2.0, 0.0, -1.0, 1.0])))
    want = np.zeros(4)
    want[[0, 2]] = sig[[0, 2]] / (sig[0] + sig[2])
    np.testing.assert_allclose(ref.route(b, wr, bias, s, "f32")[0], want,
                               rtol=1e-6)


def _leaves(cfg, prefix, seed, scale=0.5):
    rng = np.random.RandomState(seed)
    return {k[len(prefix):]: jnp.asarray(rng.randn(*v) * scale, jnp.float32)
            for k, v in ref.leaf_shapes(cfg).items() if k.startswith(prefix)}


def test_softmax_attention_is_a_gated_causal_softmax_without_positions():
    """Against numpy written out: query head j reads key-value head j // 2,
    the gate is elementwise, and NO position enters: the same token at two
    places with the same past reads the same."""
    cfg = small()
    s = ref.sizes(cfg)
    p = _leaves(cfg, "L0.a.", 0)
    rng = np.random.RandomState(1)
    a = jnp.asarray(rng.randn(6, 8), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.softmax_attention(a, p, s, "f32"))
    an = np.asarray(a, np.float64)
    pn = {k: np.asarray(v, np.float64) for k, v in p.items()}
    qkv = an @ pn["qkv"]
    q = qkv[:, :8].reshape(6, 4, 2)
    k = qkv[:, 8:12].reshape(6, 2, 2)
    v = qkv[:, 12:].reshape(6, 2, 2)
    gate = 1 / (1 + np.exp(-(an @ pn["gate"]))).reshape(6, 4, 2)
    out = np.zeros((6, 4, 2))
    for t in range(6):
        for h in range(4):
            sc = k[:t + 1, h // 2] @ q[t, h] / np.sqrt(2.0)
            w = np.exp(sc - sc.max())
            out[t, h] = (w / w.sum()) @ v[:t + 1, h // 2]
    np.testing.assert_allclose(got, (out * gate).reshape(6, 8) @ pn["o"],
                               rtol=2e-4, atol=2e-5)
    # a set, not a sequence: the earlier tokens in another order
    perm = np.asarray([2, 0, 1, 3, 4, 5])
    with jax.default_matmul_precision("highest"):
        moved = np.asarray(ref.softmax_attention(a[perm], p, s, "f32"))
    np.testing.assert_allclose(moved[3:], got[3:], rtol=1e-5, atol=1e-6)


def test_linear_attention_is_the_per_channel_rule_by_hand():
    """Against numpy written out, a token at a time: three convolutions of
    4 taps, silu, unit q and k, a decay a key channel, beta up to 2, the
    heads' norm and low-rank gate; and each control is the change its name
    says."""
    cfg = small()
    s = ref.sizes(cfg)
    p = _leaves(cfg, "L1.d.", 2)
    p["A_log"] = jnp.asarray([0.3, -0.5], jnp.float32)
    rng = np.random.RandomState(3)
    a = jnp.asarray(rng.randn(7, 8), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.linear_attention(a, p, s, "f32"))
        scalar = np.asarray(ref.linear_attention(a, p, s, "scalar_decay"))
        half = np.asarray(ref.linear_attention(a, p, s, "beta_half"))
        drop = np.asarray(ref.linear_attention(a, p, s, "drop_state",
                                               jnp.int32(4)))
        tail = np.asarray(ref.linear_attention(a[4:], p, s, "f32"))
    an = np.asarray(a, np.float64)
    pn = {k: np.asarray(v, np.float64) for k, v in p.items()}
    sig = lambda x: 1 / (1 + np.exp(-x))                     # noqa: E731

    def by_hand(beta_scale=2.0, mean_decay=False):
        x = an @ pn["qkv"]
        xp = np.concatenate([np.zeros((3, 24)), x])
        conv = sum(pn["conv"][j] * xp[j:j + 7] for j in range(4))
        x3 = (conv * sig(conv)).reshape(7, 3, 2, 4)
        unit = lambda u: u / np.sqrt((u * u).sum(-1, keepdims=True)  # noqa
                                     + 1e-6)
        q, k, v = unit(x3[:, 0]) / 2.0, unit(x3[:, 1]), x3[:, 2]
        g = -np.exp(pn["A_log"])[:, None] * np.log1p(np.exp(
            an @ pn["fa"] @ pn["fb"] + pn["dt_bias"])).reshape(7, 2, 4)
        if mean_decay:
            g = np.broadcast_to(g.mean(-1, keepdims=True), g.shape)
        beta = beta_scale * sig(an @ pn["b"])
        gate = sig(an @ pn["ga"] @ pn["gb"] + pn["gb.bias"]).reshape(7, 2, 4)
        state, out = np.zeros((2, 4, 4)), []
        for t in range(7):
            state = np.exp(g[t])[:, :, None] * state
            u = beta[t][:, None] * (v[t] - np.einsum("hkv,hk->hv", state,
                                                     k[t]))
            state = state + k[t][:, :, None] * u[:, None, :]
            o = np.einsum("hkv,hk->hv", state, q[t])
            o = o / np.sqrt((o * o).mean(-1, keepdims=True) + s.eps)
            out.append((o * pn["o_norm.w"] * gate[t]).reshape(-1))
        return np.stack(out) @ pn["out"]

    np.testing.assert_allclose(got, by_hand(), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(scalar, by_hand(mean_decay=True), rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(half, by_hand(beta_scale=1.0), rtol=2e-4,
                               atol=2e-5)
    assert np.abs(scalar - got).max() > 1e-3 < np.abs(half - got).max()
    # dropped at 4: before it nothing changes, from it on the layer is the
    # layer over the tail alone
    np.testing.assert_allclose(drop[:4], got[:4], atol=1e-6)
    np.testing.assert_allclose(drop[4:], tail, rtol=1e-5, atol=1e-6)
    assert np.abs(drop[4:] - got[4:]).max() > 1e-3


def test_heads_in_groups_are_the_heads_at_once(monkeypatch):
    cfg = small(linear_attn_config=dict(CFG["linear_attn_config"],
                                        head_dim=4, num_heads=4))
    s = ref.sizes(cfg)
    p = _leaves(cfg, "L1.d.", 5)
    a = jnp.asarray(np.random.RandomState(6).randn(9, 8), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.linear_attention(a, p, s, "f32")
        monkeypatch.setattr(ref, "HEAD_GROUP", 2)
        np.testing.assert_allclose(ref.linear_attention(a, p, s, "f32"),
                                   whole, rtol=1e-5, atol=1e-6)


def test_the_shared_expert_is_added_once_and_ungated():
    cfg = small()
    s = ref.sizes(cfg)
    p = _leaves(cfg, "L0.f.", 7)
    b = jnp.asarray(np.random.RandomState(8).randn(5, 8), jnp.float32)
    with jax.default_matmul_precision("highest"):
        both = ref.experts(b, p, s, "f32")
        routed = ref.experts(b, p, s, "f32", shared=False)
        shared = ref.gated(b, p["shared.w1"], p["shared.w2"], "f32")
    np.testing.assert_allclose(both, routed + shared, rtol=1e-6, atol=1e-6)


# ------------------------------------------------------------- the readers

T0 = 100.0                               # a window of 10 s opens here
RECORDS = [
    {"prompt_len": 8000, "n_tokens": 400, "t_first_token": T0 + 1.0,
     "t_done": T0 + 5.0},
    {"prompt_len": 12000, "n_tokens": 200, "t_first_token": T0 + 3.0,
     "t_done": T0 + 7.0},
    {"prompt_len": 5000, "n_tokens": 1000, "t_first_token": T0 - 2.0,
     "t_done": T0 + 2.0},
    {"prompt_len": 300, "n_tokens": 0, "t_first_token": None, "t_done": None},
]


def _obs(**over):
    names = ("engine.steps", "engine.gqa.pairs.decode",
             "engine.gqa.pairs.prefill", "engine.kda.tokens.decode",
             "engine.kda.tokens.prefill", "engine.moe.experts_hit.decode",
             "engine.moe.experts_hit.prefill", "engine.moe.assignments_held")
    c1 = dict(zip(names, (300, 300 * 450_000, 60 * 512 * 6000, 300 * 46 * 3,
                          60 * 512 * 3, 300 * 112, 60 * 160,
                          300 * 190 + 60 * 2048)))
    out = {"config": CFG, "device_kind": "TPU v5 lite", "t_open": T0,
           "t_close": T0 + 10.0, "records": RECORDS,
           "counters_open": dict.fromkeys(names, 0), "counters_close": c1,
           "engine_steps": [0.030, 0.029, 0.031],
           "trace": {"window_s": 10.0, "families": [],
                     "scopes": {"kda_update": 1.2, "kda_chunk": 2.0,
                                "gqa_decode": 1.5, "moe_experts": 3.0,
                                "conv": 0.2, "gqa_chunk": 1.0}}}
    out.update(over)
    return out


def test_the_whole_steps_share_by_hand():
    r = spec.reader("solar_decode_roofline")
    obs = _obs()
    seqs = (4 + 4 + 2) / 10
    toks = (8200 * 4 + 12100 * 4 + 5500 * 2) / 10
    total = 112 * 31_457_280 + solar_bytes.other_weight_bytes(CFG) \
        + 2 * 13_467_648 * seqs + 4096 * toks
    assert r.read(obs, scopes=["conv", "gqa_chunk"]) == pytest.approx(
        100 * total / 819e9 / 0.030)
    note = obs["notes"]["solar_decode_roofline"]
    assert note["total"] == pytest.approx(total)
    assert r.read(_obs(device_kind=None)) is None      # a rehearsal
    assert r.read(_obs(engine_steps=[])) is None
    # a program that counts no such work (the parent): nothing, no error
    assert r.read(_obs(counters_close={})) is None
    other = json.loads((BENCH / "configs" / "gpt2-medium.json").read_text())
    assert r.read(_obs(config=other)) is None


@pytest.mark.parametrize("work_of,scope,bound", [
    ("kda_update", "kda_update", "memory"),
    ("kda_chunk", "kda_chunk", "memory"),
    ("gqa_walk", "gqa_decode", "memory"),
    ("experts", "moe_experts", "memory")])
def test_a_kernels_share_by_its_scope(work_of, scope, bound):
    r = spec.reader("solar_roofline")
    obs = _obs()
    got = r.read(obs, work_of=work_of, scopes=[scope])
    found = obs["notes"]["solar_roofline"][work_of]
    assert found["by"] == "scopes" and found["bound"] == bound
    floor = max(found["bytes_per_s"] / 819e9, found["flops_per_s"] / 197e12)
    assert got == pytest.approx(
        100 * floor * 10.0 / obs["trace"]["scopes"][scope])
    assert 0 < got < 100
    assert "unnamed_s" not in found
    # a program that names no such scope (the parent): nothing, no error
    bare = _obs(trace={"window_s": 10.0, "families": [], "scopes": {}})
    assert r.read(bare, work_of=work_of, scopes=[scope]) is None
    assert r.read(_obs(trace=None), work_of=work_of, scopes=[scope]) is None
    assert r.read(_obs(counters_close={}), work_of=work_of,
                  scopes=[scope]) is None


def test_the_work_of_each_kernel_by_hand():
    assert solar_bytes.kda_update_work(CFG, 10.0) == (
        10.0 * 2 * 64 * 128 * 128 * 4, 10.0 * 8 * 64 * 128 * 128)
    nbytes, flops = solar_bytes.kda_chunk_work(CFG, 512.0, 512)
    assert nbytes == 2 * 4_194_304 + 512 * 5 * 128 * 64 * 4
    # a head and sub-chunk of 64: 2 x 64^2 x 128 + 3 x 64 x 128^2 + 2 x
    # 64^2 x 128 = 5,242,880 multiply-adds, 81,920 a token
    assert flops == 512 * 64 * 2 * 81_920
    assert solar_bytes.gqa_walk_work(CFG, 10.0) == (10.0 * 4096,
                                                    10.0 * 64 * 512)
    assert solar_bytes.experts_work(CFG, 10.0, 3.0) == (
        3.0 * 31_457_280, 10.0 * 2 * 15_728_640)


def test_what_the_compiler_strips_of_its_scope_is_added_to_the_scopes_time():
    r = spec.reader("solar_roofline")
    spec_ = spec.layer_metric("solar_experts_roofline_share")
    kw = {k: spec_[k] for k in ("work_of", "scopes", "unnamed")}
    alone = r.read(_obs(), **kw)
    fams = [["custom-call f32[384,2560]", 0.9], ["custom-call f32[384,4096]",
            0.6], ["custom-call f32[512,8192]", 9.0]]
    scopes = _obs()["trace"]["scopes"]
    obs = _obs(trace={"window_s": 10.0, "families": fams, "scopes": scopes})
    both = r.read(obs, **kw)
    found = obs["notes"]["solar_roofline"]["experts"]
    assert found["unnamed_s"] == pytest.approx(1.5)
    assert found["seconds"] == pytest.approx(4.5)
    assert both == pytest.approx(alone * 3.0 / 4.5)


def test_every_metric_file_of_the_cell_is_there_and_asks_for_known_scopes():
    cell = spec.cell("solaropen2-serve-docqa")
    names = [m["name"] for m in cell["per_layer"]]
    mine = ["solar_decode_roofline_mfu", "kda_update_roofline_share",
            "kda_chunk_roofline_share", "gqa_walk_roofline_share",
            "solar_experts_roofline_share"]
    assert names[-5:] == mine and len(names) == len(set(names))
    asked = {s for n in names for s in spec.layer_metric(n).get("scopes", ())}
    assert asked == {"kda_update", "kda_chunk", "conv", "gqa_decode",
                     "gqa_chunk", "moe_experts"}
    # `out_tokens_per_s` is NOT this cell's: a request outlasts the window
    # (50 s against 40), so the tokens a window holds go by the phase of
    # the callers' cycle it opens in (PERF.md section 2); nor are the
    # per-layer metrics that move it
    assert [m["name"] for m in cell["end_to_end"]] == ["tpot_p50_ms",
                                                       "setup_s"]
    assert {m["moves"] for m in cell["per_layer"]} == {"tpot_p50_ms",
                                                       "setup_s"}
    assert cell["workload"]["chips"] == 1

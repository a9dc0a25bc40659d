"""``reference/kimi_k2.py`` against cases worked by hand, its count of the
cell's parameters, and the cell's readers over observations written by
hand: the distinct-rows floor counts a context that two live sequences
share once."""
import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from harness import kimi_bytes, spec
from reference import kimi_k2 as ref

CFG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                  / "kimi-k2.7-code.json").read_text())


def small(**over):
    cfg = dict(CFG, hidden_size=8, intermediate_size=12,
               moe_intermediate_size=4, vocab_size=16, num_hidden_layers=2,
               n_routed_experts=4, router_outputs=4, num_experts_per_tok=2,
               num_attention_heads=2, q_lora_rank=4, kv_lora_rank=4,
               qk_nope_head_dim=2, qk_rope_head_dim=2, v_head_dim=2)
    cfg.update(over)
    return cfg


def test_param_count_of_the_cell_by_parts():
    d, h = 7168, 64
    mla = d * 1536 + 1536 + 1536 * h * 192 + d * 576 + 512 \
        + 512 * h * 256 + h * 128 * d
    dense, expert = 3 * d * 18432, 3 * d * 2048
    router = d * 384 + 384
    assert (mla, dense, expert, router) == (101_124_096, 396_361_728,
                                            44_040_192, 2_752_896)
    layer0 = mla + dense + 2 * d
    outside = mla + expert + router + 2 * d
    ends = 2 * 20480 * d + d
    assert (layer0, outside, ends) == (497_500_160, 147_931_520,
                                       293_608_448)
    total = layer0 + 4 * (outside + 12 * expert) + ends
    assert ref.param_count(CFG) == total == CFG["parameters"] == 3_496_763_904
    assert kimi_bytes.latent_row_bytes(CFG) == 1152
    assert kimi_bytes.other_weight_bytes(CFG) == 2 * (
        total - 48 * expert - 20480 * d)


def test_the_configuration_keeps_every_published_number():
    rows = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")] \
        if Path("/opt/skills/guides/model-configs/architectures.jsonl"
                ).is_file() else []
    entry = next((r for r in rows if r["name"] == "Kimi-K2.7-Code"), None)
    if entry is None:
        pytest.skip("the catalog is not on this machine")
    differs = sorted(k for k, v in entry["config"].items() if CFG.get(k) != v)
    assert differs == sorted(CFG["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert CFG["source"] == entry["source_url"]
    assert CFG["deployment"]["chips_sharing_a_layer"] == 32
    assert (CFG["num_hidden_layers_published"],
            CFG["n_routed_experts_published"],
            CFG["vocab_size_published"]) == (61, 384, 163840)


def test_a_router_this_file_does_not_write_is_refused():
    for over in (dict(n_group=8), dict(scoring_func="softmax"),
                 dict(n_shared_experts=2), dict(moe_layer_freq=2)):
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
    # and 2, weights sigmoid over their sum, times 2.827
    b = jnp.eye(8)[:1]
    wr = jnp.zeros((8, 4)).at[0].set(jnp.asarray([2.0, 0.0, -1.0, 1.0]))
    bias = jnp.asarray([0.0, 0.0, 0.5, 0.0])
    sig = 1 / (1 + np.exp(-np.asarray([2.0, 0.0, -1.0, 1.0])))
    want = np.zeros(4)
    want[[0, 2]] = sig[[0, 2]] / (sig[0] + sig[2]) * 2.827
    np.testing.assert_allclose(ref.route(b, wr, bias, s, "f32")[0], want,
                               rtol=1e-6)
    soft = np.exp([2.0, -1.0]) / np.exp([2.0, -1.0]).sum() * 2.827
    np.testing.assert_allclose(
        ref.route(b, wr, bias, s, "softmax_router")[0, [0, 2]], soft,
        rtol=1e-6)


def test_attention_is_a_causal_softmax_per_head_and_the_control_hides():
    """Against numpy written out: one token's output from its own value
    alone; the last token's from all; under ``no_context`` from ``hide``
    on alone."""
    cfg = small()
    s = ref.sizes(cfg)
    rng = np.random.RandomState(0)
    p = {k[len("L0.a."):]: jnp.asarray(rng.randn(*v) * 0.5, jnp.float32)
         for k, v in ref.leaf_shapes(cfg).items() if k.startswith("L0.a.")}
    a = jnp.asarray(rng.randn(6, 8), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.attention(a, p, s, "f32"))
        hid = np.asarray(ref.attention(a, p, s, "no_context", hide=4))
        an, pn = np.asarray(a, np.float64), {k: np.asarray(v, np.float64)
                                             for k, v in p.items()}

        def nrm(x, w):
            return x / np.sqrt((x * x).mean(-1, keepdims=True) + s.eps) * w

        cq = nrm(an @ pn["dq"], pn["q_norm.w"])
        q = (cq @ pn["uq"]).reshape(6, 2, 4)
        kv = an @ pn["dkv"]
        ckv = nrm(kv[:, :4], pn["kv_norm.w"])
        kvh = (ckv @ pn["ukv"]).reshape(6, 2, 4)
        qr = np.asarray(ref._g.rope(jnp.asarray(q[..., 2:], jnp.float32), s,
                                    "f32"), np.float64)
        kr = np.asarray(ref._g.rope(jnp.asarray(kv[:, 4:], jnp.float32), s,
                                    "f32"), np.float64)

        def attend(first):
            out = np.zeros((6, 2, 2))
            for t in range(6):
                lo = first if t >= first else 0
                for h in range(2):
                    sc = (kvh[lo:t + 1, h, :2] @ q[t, h, :2]
                          + kr[lo:t + 1] @ qr[t, h]) * ref._g.softmax_scale(s)
                    w = np.exp(sc - sc.max())
                    out[t, h] = (w / w.sum()) @ kvh[lo:t + 1, h, 2:]
            return out.reshape(6, 4) @ pn["o"]

        np.testing.assert_allclose(got, attend(0), rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(hid, attend(4), rtol=2e-4, atol=2e-5)
    assert np.abs(hid[:4] - got[:4]).max() < 1e-6 < np.abs(hid[5] - got[5]
                                                          ).max()
    m = 0.1 * np.log(64.0) + 1.0
    assert ref._g.softmax_scale(ref.sizes(CFG)) == pytest.approx(
        m * m / np.sqrt(192.0))
    assert m == pytest.approx(1.4159, abs=1e-4)


def test_wide_rows_in_blocks_are_the_rows_at_once(monkeypatch):
    rng = np.random.RandomState(1)
    b, w1, w2 = (jnp.asarray(rng.randn(*sh), jnp.float32)
                 for sh in ((12, 8), (8, 10), (5, 8)))
    whole = ref.gated(b, w1, w2, "f32")
    monkeypatch.setattr(ref, "WIDE_ELEMENTS", 35)     # 12 x 10: 4 blocks
    np.testing.assert_allclose(ref.gated(b, w1, w2, "f32"), whole,
                               rtol=1e-6)


# ------------------------------------------------------------- the readers

T0 = 100.0                               # a window of 10 s opens here
RECORDS = [
    # two sequences on context 3 whose decoding overlaps for 2 s of the
    # 6 s that either decodes; one on context 5; one with no context
    {"prompt_len": 1100, "n_tokens": 40, "t_first_token": T0 + 1.0,
     "t_done": T0 + 5.0, "context": 3, "shared": 1000},
    {"prompt_len": 1200, "n_tokens": 20, "t_first_token": T0 + 3.0,
     "t_done": T0 + 7.0, "context": 3, "shared": 1000},
    {"prompt_len": 1050, "n_tokens": 100, "t_first_token": T0 - 2.0,
     "t_done": T0 + 2.0, "context": 5, "shared": 1000},
    {"prompt_len": 300, "n_tokens": 10, "t_first_token": T0 + 8.0,
     "t_done": T0 + 9.0, "context": -1, "shared": 0},
    {"prompt_len": 300, "n_tokens": 0, "t_first_token": None, "t_done": None},
]


def test_a_context_two_live_sequences_share_counts_once():
    lv = kimi_bytes.live_rows(RECORDS, T0, T0 + 10.0)
    assert lv["sequences"] == pytest.approx((4 + 4 + 2 + 1) / 10)
    assert lv["rows"] == pytest.approx(
        (1120 * 4 + 1210 * 4 + 1100 * 2 + 305 * 1) / 10)
    # own rows, then context 3 for the 6 s of the union, context 5 for 2 s
    assert lv["distinct_rows"] == pytest.approx(
        (120 * 4 + 210 * 4 + 100 * 2 + 305 * 1 + 1000 * 6 + 1000 * 2) / 10)
    alone = kimi_bytes.live_rows(RECORDS[:1], T0, T0 + 10.0)
    assert alone["distinct_rows"] == pytest.approx(alone["rows"])


def _obs(**over):
    c0 = {"engine.steps": 0, "engine.latent.pairs.decode": 0,
          "engine.latent.pairs.prefill": 0, "engine.moe.experts_hit.decode": 0,
          "engine.moe.experts_hit.prefill": 0,
          "engine.moe.assignments_held": 0}
    c1 = {"engine.steps": 200, "engine.latent.pairs.decode": 200 * 4_000_000,
          "engine.latent.pairs.prefill": 30 * 512 * 25_000 * 5,
          "engine.moe.experts_hit.decode": 200 * 24,
          "engine.moe.experts_hit.prefill": 30 * 48,
          "engine.moe.assignments_held": 200 * 32 + 30 * 512}
    out = {"config": CFG, "device_kind": "TPU v5 lite", "t_open": T0,
           "t_close": T0 + 10.0, "records": RECORDS, "counters_open": c0,
           "counters_close": c1, "engine_steps": [0.050, 0.049, 0.051],
           "trace": {"window_s": 10.0, "families": [],
                     "scopes": {"mla_decode": 6.0, "mla_chunk": 1.5,
                                "moe_experts": 1.0}}}
    out.update(over)
    return out


def test_the_whole_steps_share_by_hand():
    r = spec.reader("kimi_decode_roofline")
    obs = _obs()
    lv = kimi_bytes.live_rows(RECORDS, T0, T0 + 10.0)
    weights = kimi_bytes.other_weight_bytes(CFG) \
        + 24 * 3 * 7168 * 2048 * 2
    walk = max(5 * 1152 * lv["distinct_rows"] / 819e9,
               2 * 64 * 1088 * 4_000_000 / 197e12)
    want = 100 * (weights / 819e9 + walk) / 0.050
    assert r.read(obs) == pytest.approx(want)
    note = obs["notes"]["kimi_decode_roofline"]
    assert note["latent_rows_each_its_own"] > note["latent_rows_distinct"]
    assert r.read(_obs(device_kind=None)) is None      # a rehearsal
    assert r.read(_obs(engine_steps=[])) is None
    other = json.loads((Path(__file__).resolve().parents[1] / "configs"
                        / "gpt2-medium.json").read_text())
    assert r.read(_obs(config=other)) is None


@pytest.mark.parametrize("work_of,scope,bound", [
    ("latent_decode", "mla_decode", "compute"),
    ("latent_chunk", "mla_chunk", "compute"),
    ("experts", "moe_experts", "memory")])
def test_a_kernels_share_by_its_scope(work_of, scope, bound):
    r = spec.reader("kimi_roofline")
    obs = _obs()
    got = r.read(obs, work_of=work_of, scopes=[scope])
    found = obs["notes"]["kimi_roofline"][work_of]
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


def test_what_the_compiler_strips_of_its_scope_is_added_to_the_scopes_time():
    """The grouped arm's ragged products run as custom calls named
    `ragged-dot-none` under no scope: the experts' share adds their
    families' seconds to the scope's, and leaves the scope's alone where
    they match nothing (a kernel in their place, the dense arm)."""
    r = spec.reader("kimi_roofline")
    spec_ = spec.layer_metric("kimi_experts_roofline_share")
    kw = {k: spec_[k] for k in ("work_of", "scopes", "unnamed")}
    alone = r.read(_obs(), **kw)
    fams = [["custom-call f32[256,4096]", 0.6], ["custom-call f32[256,7168]",
            0.4], ["custom-call f32[512,8192]", 9.0]]
    obs = _obs(trace={"window_s": 10.0, "families": fams,
                      "scopes": {"moe_experts": 1.0}})
    both = r.read(obs, **kw)
    found = obs["notes"]["kimi_roofline"]["experts"]
    assert found["unnamed_s"] == pytest.approx(1.0)
    assert found["seconds"] == pytest.approx(2.0)
    assert both == pytest.approx(alone / 2)
    assert kimi_bytes.experts_work(CFG, 10.0, 3.0) == (
        3.0 * 3 * 7168 * 2048 * 2, 10.0 * 2 * 3 * 7168 * 2048)

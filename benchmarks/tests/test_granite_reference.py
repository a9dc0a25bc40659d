"""The plain reference of the ``granitemoehybrid`` family on its own (CPU,
tiny sizes): its parts against hand-written arithmetic in numpy loops, the
seeded weights, the configuration file against the published numbers, and
the byte counts the roofline shares divide by against the configuration's
arithmetic."""
import json
from pathlib import Path

import numpy as np
import pytest

import tiny  # noqa: F401 — puts the benchmark on sys.path
from harness import granite_bytes, granite_weights
from harness import spec as harness_spec
from reference import granitemoehybrid as ref

BENCH = Path(__file__).resolve().parents[1]
CFG = dict(hidden_size=32, num_hidden_layers=4,
           layer_types=["mamba", "mamba", "attention", "mamba", "mamba"],
           num_attention_heads=4, num_key_value_heads=2,
           intermediate_size=8, shared_intermediate_size=12, vocab_size=80,
           num_local_experts=3, router_outputs=6, experts_first=2,
           num_experts_per_tok=2, mamba_n_heads=4, mamba_d_head=4,
           mamba_d_state=8, mamba_d_conv=4, mamba_n_groups=1,
           mamba_chunk_size=4, rms_norm_eps=1e-5, embedding_multiplier=12,
           residual_multiplier=0.22, attention_multiplier=0.125,
           logits_scaling=16, assumed=dict(head_dim=8),
           serve=dict(precision="bf16", ssm_state="f32", conv_state="bf16",
                      prefill_chunk_tokens=8, max_slots=4))


def published():
    return json.loads(
        (BENCH / "configs" / "granite-4.0-h-small.json").read_text())


@pytest.fixture(scope="module")
def weights():
    w = granite_weights.make(CFG, seed=3100000019, dtype="float32")
    return {k: np.asarray(v, np.float64) for k, v in w.items()}


def _silu(x):
    return x / (1 + np.exp(-x))


def _rms(x, w):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * w


def _f32(p):
    import jax.numpy as jnp
    return {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}


def test_the_file_keeps_every_published_number_and_states_the_cut():
    cfg = published()
    row = dict(attention_bias=False, attention_multiplier=0.0078125,
               embedding_multiplier=12, hidden_act="silu", hidden_size=4096,
               intermediate_size=768, logits_scaling=16,
               mamba_chunk_size=256, mamba_conv_bias=True, mamba_d_conv=4,
               mamba_d_head=64, mamba_d_state=128, mamba_expand=2,
               mamba_n_groups=1, mamba_n_heads=128, mamba_proj_bias=False,
               max_position_embeddings=131072, num_attention_heads=32,
               num_experts_per_tok=10, num_key_value_heads=8,
               residual_multiplier=0.22, rms_norm_eps=1e-5, rope_theta=10000,
               shared_intermediate_size=1536, tie_word_embeddings=True)
    for k, v in row.items():
        assert cfg[k] == v, k
    assert len(cfg["layer_types"]) == 40            # copied whole
    assert cfg["reduced"] == ["num_hidden_layers", "num_local_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_local_experts"],
            cfg["vocab_size"]) == (10, 36, 50176)
    assert (cfg["num_hidden_layers_published"],
            cfg["num_local_experts_published"], cfg["router_outputs"],
            cfg["vocab_size_published"]) == (40, 72, 72, 100352)
    dep = cfg["deployment"]
    assert dep["chips"] == 8 and dep["chips_sharing_a_layer"] == 2
    s = ref.sizes(cfg)
    assert s.types == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert s.held == (0, 36) and s.experts == 72 and s.top_k == 10
    assert ref.param_count(cfg) == 4757211776 == cfg["assumed"]["parameters"]


def test_byte_counts_are_the_configurations_arithmetic():
    """ISSUE 31's own sums: a Mamba layer outside the routed experts
    121,464,448 parameters, the attention layer 61,120,512, an expert
    9,437,184; 9.51 GB of weights, 38.2 MB of state a slot, 4,096 B of K
    and V a token; a full decode step 14.7 GB."""
    cfg = published()
    assert granite_bytes.layer_params_outside_experts(cfg, "mamba") \
        == 121464448
    assert granite_bytes.layer_params_outside_experts(cfg, "attention") \
        == 61120512
    assert granite_bytes.expert_params(cfg) == 9437184
    assert 9 * 121464448 + 61120512 + 10 * 36 * 9437184 \
        + 50176 * 4096 + 4096 == ref.param_count(cfg)
    assert granite_bytes.weight_bytes(cfg) == 2 * 4757211776
    per_slot = 9 * granite_bytes.state_bytes_per_sequence_layer(cfg)
    assert per_slot == 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2) == 38204928
    assert granite_bytes.kv_bytes_per_token_layer(cfg) == 4096
    assert granite_bytes.moe_experts_bytes(cfg) == \
        10 * (36 * 9437184 + 4096 * 72) * 2
    parts = granite_bytes.decode_step_bytes(
        cfg, {"sequences": 64.0, "tokens": 64 * 1200.0})
    assert parts["state"] == 2 * 64 * per_slot
    assert parts["kv"] == 4096 * 64 * 1200
    assert parts["experts"] + parts["other_weights"] == 2 * 4757211776
    assert 14.6e9 < parts["total"] < 14.8e9
    # a 512-token launch: two blocks of 256 in each of 9 layers
    assert granite_bytes.ssm2_scan_flops(cfg, 512, 256) == 9 * 2 * 2.0 * (
        256 * 256 * 128 + 128 * 256 * 256 * 64 + 2 * 128 * 64 * 128 * 256)
    assert granite_bytes.ssm2_scan_bytes(cfg, 512) == 9 * (
        512 * (2 * 8192 + 128 + 256) * 4 + 2 * 128 * 64 * 128 * 4)
    # of 64 x 10 assignments a layer, half land here
    assert granite_bytes.moe_first_bytes(cfg) == 10 * 36 * 4096 * 1536 * 2
    assert granite_bytes.moe_first_flops(cfg, 64) == \
        10 * 2.0 * 64 * 10 * 0.5 * 4096 * 1536


def test_recurrence_is_the_written_one(weights):
    """``mamba2`` against the equations in a Python loop over tokens and
    heads."""
    import jax
    import jax.numpy as jnp
    s = ref.sizes(CFG)
    p = {k[2:]: v[1] for k, v in weights.items() if k.startswith("m.")}
    a = np.random.RandomState(0).randn(9, s.d)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.mamba2(jnp.asarray(a, jnp.float32), _f32(p), s,
                                    "f32"))
    zxd = a @ p["in_proj"]
    z, xbc, dt = zxd[:, :s.di], zxd[:, s.di:s.di + s.cd], zxd[:, s.di + s.cd:]
    conv = np.zeros_like(xbc)
    for t in range(9):
        for k in range(s.dc):                 # the LAST tap is the current
            if t - (s.dc - 1 - k) >= 0:
                conv[t] += p["conv.w"][k] * xbc[t - (s.dc - 1 - k)]
    xbc = _silu(conv + p["conv.b"])
    x, bm, cm = (xbc[:, :s.di], xbc[:, s.di:s.di + s.ms],
                 xbc[:, s.di + s.ms:])
    dt = np.log1p(np.exp(dt + p["dt_bias"]))
    y = np.zeros((9, s.mh, s.mp))
    for h in range(s.mh):
        state = np.zeros((s.mp, s.ms))
        for t in range(9):
            xh = x[t, h * s.mp:(h + 1) * s.mp]
            state = np.exp(dt[t, h] * -np.exp(p["A_log"][h])) * state \
                + dt[t, h] * np.outer(xh, bm[t])
            y[t, h] = state @ cm[t] + p["D"][h] * xh
    y = _rms(y.reshape(9, s.di) * _silu(z), p["gnorm.w"])  # gate, THEN norm
    np.testing.assert_allclose(got, y @ p["out_proj"], atol=3e-5)


def test_routing_is_the_written_one_and_keeps_to_the_share(weights):
    """Top-2 of 6 by float32 logits, a softmax over those two, the FIRST
    half of the gated matrix activated; only experts 2-4 (held) add."""
    import jax
    import jax.numpy as jnp
    s = ref.sizes(CFG)
    assert s.held == (2, 5)
    p = {k[2:]: v[0] for k, v in weights.items() if k.startswith("f.")}
    b = np.random.RandomState(1).randn(11, s.d)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.routed(jnp.asarray(b, jnp.float32), _f32(p), s,
                                    "f32"))
    want, seen = np.zeros_like(b), set()
    for t in range(11):
        logits = b[t] @ p["router"]
        top = np.argsort(-logits)[:2]
        g = np.exp(logits[top] - logits[top].max())
        g /= g.sum()
        for e, ge in zip(top, g):
            seen.add(int(e))
            if 2 <= e < 5:
                h = b[t] @ p["w1"][e - 2]
                want[t] += ge * ((_silu(h[:s.f]) * h[s.f:]) @ p["w2"][e - 2])
    assert seen - {2, 3, 4} and seen & {2, 3, 4}     # some routed elsewhere
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_attention_is_the_written_one(weights):
    """Grouped queries (head h reads K/V head h // 2), scores times
    ``attention_multiplier`` and no positions: permuting the EARLIER tokens
    leaves the last token's output as it was."""
    import jax
    import jax.numpy as jnp
    s = ref.sizes(CFG)
    p = {k[2:]: v[0] for k, v in weights.items() if k.startswith("a.")}
    a = np.random.RandomState(2).randn(7, s.d)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.attention(jnp.asarray(a, jnp.float32), _f32(p),
                                       s, "f32"))
        mixed = np.asarray(ref.attention(
            jnp.asarray(a[[3, 1, 0, 5, 2, 4, 6]], jnp.float32), _f32(p), s,
            "f32"))
    qkv = a @ p["qkv.w"]
    qw, kvw = s.nq * s.hd, s.nkv * s.hd
    q = qkv[:, :qw].reshape(7, s.nq, s.hd)
    k = qkv[:, qw:qw + kvw].reshape(7, s.nkv, s.hd)
    v = qkv[:, qw + kvw:].reshape(7, s.nkv, s.hd)
    out = np.zeros((7, s.nq, s.hd))
    for t in range(7):
        for h in range(s.nq):
            sc = (q[t, h] @ k[:t + 1, h // 2].T) * 0.125
            pr = np.exp(sc - sc.max())
            out[t, h] = (pr / pr.sum()) @ v[:t + 1, h // 2]
    np.testing.assert_allclose(got, out.reshape(7, qw) @ p["o.w"], atol=2e-5)
    np.testing.assert_allclose(mixed[6], got[6], atol=2e-5)


def test_the_stack_is_the_written_one(weights):
    """Multipliers and order: 12 x the embedding, 0.22 x each half-layer,
    logits over 16; layer_types cut to num_hidden_layers."""
    import jax
    import jax.numpy as jnp
    s = ref.sizes(CFG)
    assert s.types == ("mamba", "mamba", "attention", "mamba")
    ids = np.random.RandomState(3).randint(0, 80, 10)
    w32 = _f32(weights)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.logits(w32, jnp.asarray(ids), CFG))
        h = np.asarray(w32["embed"])[ids] * 12.0
        seen = {"m": 0, "a": 0}
        for i, kind in enumerate(s.types):
            c = kind[0]
            pm = {k[2:]: jnp.asarray(v[seen[c]], jnp.float32)
                  for k, v in weights.items() if k.startswith(c + ".")}
            pf = {k[2:]: jnp.asarray(v[i], jnp.float32)
                  for k, v in weights.items() if k.startswith("f.")}
            seen[c] += 1
            mix = ref.mamba2 if c == "m" else ref.attention
            a = _rms(h, np.asarray(pm["norm.w"]))
            h = h + 0.22 * np.asarray(mix(jnp.asarray(a, jnp.float32), pm, s,
                                          "f32"))
            b = jnp.asarray(_rms(h, np.asarray(pf["norm.w"])), jnp.float32)
            h = h + 0.22 * np.asarray(
                ref.routed(b, pf, s, "f32")
                + ref.gated(b, pf["shared.w1"], pf["shared.w2"], "f32"))
    want = _rms(h, weights["norm_f.w"]) @ weights["embed"].T / 16.0
    np.testing.assert_allclose(got, want, atol=3e-5)


def test_seeded_weights_repeat_and_keep_their_ranges():
    a = granite_weights.make(CFG, seed=2 ** 31 + 12345, dtype="float32")
    b = granite_weights.make(CFG, seed=2 ** 31 + 12345, dtype="float32")
    c = granite_weights.make(CFG, seed=2 ** 31 + 12346, dtype="float32")
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["f.w1"], c["f.w1"])
    assert list(a) == list(ref.leaf_shapes(CFG))
    assert all(tuple(a[k].shape) == tuple(v)
               for k, v in ref.leaf_shapes(CFG).items())
    assert float(np.abs(np.asarray(a["m.D"]) - 1).max()) == 0
    al = np.exp(np.asarray(a["m.A_log"]))
    assert al.min() >= 1 and al.max() <= 16
    dt = np.log1p(np.exp(np.asarray(a["m.dt_bias"])))
    assert dt.min() >= 1e-3 * 0.99 and dt.max() <= 1e-1 * 1.01
    assert abs(float(np.asarray(a["f.norm.w"]).mean()) - 1) < 0.05
    # layers differ: a stacked leaf is not one draw repeated
    assert not np.array_equal(a["f.w1"][0], a["f.w1"][1])


def test_the_new_readers_give_nothing_where_there_is_nothing_to_read():
    """A traced run of the parent, or of a cell without this family: no
    family matches, the metric is left out and nothing raises."""
    reader = harness_spec.reader("granite_roofline")
    obs = {"trace": {"families": [["fusion bf16[24,1024]", 1.0]],
                     "window_s": 4.0},
           "config": published(), "device_kind": "TPU v5 lite",
           "records": [], "t_open": 0.0, "t_close": 40.0,
           "counters_open": {}, "counters_close": {}}
    for name in ("moe_experts_roofline_share", "ssm2_update_roofline_share",
                 "ssm2_scan_roofline_share"):
        spec = harness_spec.layer_metric(name)
        params = {k: v for k, v in spec.items() if k not in ("reader", "doc")}
        assert reader.read(dict(obs), **params) is None
    assert reader.read({"trace": None}, ["x"], "ssm2_scan", "engine.steps") \
        is None
    # a configuration of another family has no such sizes: nothing, quietly
    assert reader.read(dict(obs, config={"serve": {}}), ["{slots}"],
                       "ssm2_scan", "engine.steps") is None


def test_the_kernel_shares_find_their_families_by_the_configurations_sizes():
    """The patterns name sizes, not numbers: filled in from the committed
    configuration they are the families the chip's trace showed (PERF.md
    section 5, PR 31), and with another ``serve`` block they move with it."""
    import re
    cfg = published()
    shapes = granite_bytes.trace_shapes(cfg)
    seen = {"moe_experts_roofline_share": ["fusion f32[64,36,1536]"],
            "ssm2_update_roofline_share": [
                "custom-call (f32[9,64,128,8192], f32[64,1,8192])"],
            "ssm2_scan_roofline_share": [
                "fusion f32[2,256,128,64]", "copy f32[2,256,128,64]",
                "fusion f32[2,128,8192]", "fusion f32[1,256,128,64]",
                "reshape f32[2,256,128,64]", "broadcast f32[2,256,128,64]",
                "copy f32[2,256,8192]"]}
    for name, families in seen.items():
        patterns = harness_spec.layer_metric(name)["patterns"]
        assert len(patterns) == len(families)
        for pattern, family in zip(patterns, families):
            assert re.search(pattern.format(**shapes), family), pattern
    retuned = dict(cfg, serve=dict(cfg["serve"], max_slots=32,
                                   prefill_chunk_tokens=1024))
    moved = granite_bytes.trace_shapes(retuned)
    assert (moved["slots"], moved["blocks"]) == (32, 4)
    pattern = harness_spec.layer_metric(
        "moe_experts_roofline_share")["patterns"][0]
    assert re.search(pattern.format(**moved), "fusion f32[32,36,1536]")
    # a family found: the share is the floor over the family's time
    reader = harness_spec.reader("granite_roofline")
    obs = {"trace": {"families": [["fusion f32[64,36,1536]", 0.8]],
                     "window_s": 4.0},
           "config": cfg, "device_kind": "TPU v5 lite", "records": [],
           "t_open": 0.0, "t_close": 40.0,
           "counters_open": {"engine.steps": 0},
           "counters_close": {"engine.steps": 1200}}
    spec = harness_spec.layer_metric("moe_experts_roofline_share")
    params = {k: v for k, v in spec.items() if k not in ("reader", "doc")}
    share = reader.read(obs, **params)
    floor_s = granite_bytes.moe_first_bytes(cfg) / 819e9
    assert abs(share - 100 * floor_s * 30 / 0.2) < 1e-6 * share
    ratio = harness_spec.reader("counter_ratio")
    spec = harness_spec.layer_metric("moe_held_route_share")
    params = {k: v for k, v in spec.items() if k not in ("reader", "doc")}
    assert ratio.read(obs, **params) is None           # no such counters
    obs["counters_close"] = {"engine.moe.assignments": 200,
                             "engine.moe.assignments_held": 90}
    assert ratio.read(obs, **params) == 45.0

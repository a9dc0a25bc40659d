"""The plain reference of the ``phi4flash`` family on its own (CPU, tiny
sizes): its parts against independent arithmetic, the seeded weights, the
byte counts the roofline shares divide by, and the family reader."""
import json
import math
from pathlib import Path

import numpy as np
import pytest

import tiny  # noqa: F401 — puts the benchmark on sys.path
from harness import hybrid_bytes, hybrid_weights
from harness import spec as harness_spec
from reference import phi4flash as ref

BENCH = Path(__file__).resolve().parents[1]
CFG = dict(hidden_size=32, num_hidden_layers=8, num_attention_heads=4,
           num_key_value_heads=2, intermediate_size=48, vocab_size=80,
           sliding_window=6,
           assumed=dict(mamba_expand=2, mamba_d_state=4, mamba_d_conv=4,
                        mamba_dt_rank=2),
           serve=dict(precision="bf16", ssm_state="f32", conv_state="bf16",
                      prefill_chunk_tokens=8))


def published():
    return json.loads(
        (BENCH / "configs" / "phi-4-mini-flash.json").read_text())


@pytest.fixture(scope="module")
def weights():
    return hybrid_weights.make(CFG, seed=3000000019, dtype="float32")


def test_published_sizes_give_the_published_parameter_count():
    cfg = published()
    assert ref.param_count(cfg) == 3852562944 == cfg["assumed"]["parameters"]
    assert cfg["reduced"] == []
    # every number of the model's public config.json, under its own key
    for k, v in dict(hidden_size=2560, intermediate_size=10240,
                     num_hidden_layers=32, num_attention_heads=40,
                     num_key_value_heads=20, vocab_size=200064,
                     sliding_window=512, mb_per_layer=2,
                     max_position_embeddings=262144, layer_norm_eps=1e-5,
                     embd_pdrop=0, resid_pdrop=0).items():
        assert cfg[k] == v, k


def test_recurrence_is_the_written_one(weights):
    """``mamba`` against the equations in a Python loop over tokens."""
    import jax.numpy as jnp
    s = ref.sizes(CFG)
    p = {k[len("mid.m."):]: np.asarray(v, np.float64)
         for k, v in weights.items() if k.startswith("mid.m.")}
    r = np.random.RandomState(0).randn(9, s.d)
    got, y_got = ref.mamba(jnp.asarray(r, jnp.float32),
                           {k: jnp.asarray(v, jnp.float32)
                            for k, v in p.items()}, s, "f32")
    silu = lambda x: x / (1 + np.exp(-x))  # noqa: E731
    xz = r @ p["in_proj"]
    x, z = xz[:, :s.di], xz[:, s.di:]
    xp = np.concatenate([np.zeros((3, s.di)), x])
    x = silu(sum(p["conv.w"][k] * xp[k:k + 9] for k in range(4))
             + p["conv.b"])
    dbc = x @ p["x_proj"]
    dt = np.log1p(np.exp(dbc[:, :s.dtr] @ p["dt_proj.w"] + p["dt_proj.b"]))
    bm, cm = dbc[:, s.dtr:s.dtr + s.ds], dbc[:, s.dtr + s.ds:]
    a = -np.exp(p["A_log"])
    state, ys = np.zeros((s.di, s.ds)), []
    for t in range(9):
        state = np.exp(dt[t][:, None] * a) * state \
            + (dt[t] * x[t])[:, None] * bm[t][None]
        ys.append(state @ cm[t] + p["D"] * x[t])
    y = np.stack(ys)
    want = (y * silu(z)) @ p["out_proj"]
    assert np.abs(np.asarray(y_got) - y).max() < 1e-5 * np.abs(y).max()
    assert np.abs(np.asarray(got) - want).max() < 1e-5 * np.abs(want).max()


def test_differential_attention_is_the_written_one(weights):
    """``diff_attention`` against a loop over query pairs."""
    import jax.numpy as jnp
    s = ref.sizes(CFG)
    p = {k[len("mid.a."):]: np.asarray(v, np.float64)
         for k, v in weights.items() if k.startswith("mid.a.")}
    rng = np.random.RandomState(1)
    t, hd = 7, s.hd
    q, k, v = rng.randn(t, s.nq * hd), rng.randn(t, s.nkv * hd), \
        rng.randn(t, s.nkv * hd)
    mask = np.tril(np.ones((t, t), bool))
    got = ref.diff_attention(
        *(jnp.asarray(a, jnp.float32) for a in (q, k, v)), jnp.asarray(mask),
        {n: jnp.asarray(a, jnp.float32) for n, a in p.items()},
        ref.lambda_init(5), s, "f32")
    l0 = 0.8 - 0.6 * math.exp(-0.3 * 5)
    lam = math.exp(p["lam"][0] @ p["lam"][1]) \
        - math.exp(p["lam"][2] @ p["lam"][3]) + l0

    def soft(a):
        a = np.where(mask, a, -np.inf)
        e = np.exp(a - a.max(-1, keepdims=True))
        return e / e.sum(-1, keepdims=True)
    outs = []
    for j in range(s.nq // 2):
        g = j // (s.nq // s.nkv)
        q1, q2 = q[:, 2 * j * hd:(2 * j + 1) * hd], \
            q[:, (2 * j + 1) * hd:(2 * j + 2) * hd]
        k1, k2 = k[:, 2 * g * hd:(2 * g + 1) * hd], \
            k[:, (2 * g + 1) * hd:(2 * g + 2) * hd]
        vv = v[:, 2 * g * hd:(2 * g + 2) * hd]
        o = soft(q1 @ k1.T / math.sqrt(hd)) @ vv \
            - lam * soft(q2 @ k2.T / math.sqrt(hd)) @ vv
        o = o / np.sqrt((o * o).mean(-1, keepdims=True) + 1e-5) \
            * p["subln.w"]
        outs.append((1 - l0) * o)
    want = np.concatenate(outs, -1) @ p["out.w"] + p["out.b"]
    assert np.abs(np.asarray(got) - want).max() < 1e-5 * np.abs(want).max()


def test_forward_is_causal_and_the_window_is_a_window(weights):
    import jax.numpy as jnp
    rng = np.random.RandomState(2)
    ids = rng.randint(0, 80, 20).astype(np.int32)
    other = ids.copy()
    other[12:] = rng.randint(0, 80, 8)
    a = np.asarray(ref.logits(weights, jnp.asarray(ids), CFG))
    b = np.asarray(ref.logits(weights, jnp.asarray(other), CFG))
    assert np.abs(a[:12] - b[:12]).max() == 0.0
    assert np.abs(a[12:] - b[12:]).max() > 1e-3
    wide = dict(CFG, sliding_window=20)       # no longer cuts anything off
    c = np.asarray(ref.logits(weights, jnp.asarray(ids), wide))
    assert np.abs(a[:6] - c[:6]).max() < 1e-6   # 6 tokens fit the window
    assert np.abs(a[6:] - c[6:]).max() > 1e-4


def test_weights_held_in_bf16_widen_exactly(weights):
    import jax.numpy as jnp
    held = hybrid_weights.make(CFG, seed=3000000019, dtype="bfloat16")
    wide = {k: v.astype(jnp.float32) for k, v in held.items()}
    ids = jnp.asarray(np.arange(15, dtype=np.int32) * 5 % 80)
    a = np.asarray(ref.logits(held, ids, CFG, rows=slice(3, 9)))
    b = np.asarray(ref.logits(wide, ids, CFG))[3:9]
    assert np.array_equal(a, b)


def test_lower_precisions_read_farther_off_in_order(weights):
    import jax.numpy as jnp
    ids = jnp.asarray(np.random.RandomState(3).randint(0, 80, 24)
                      .astype(np.int32))
    f32 = np.asarray(ref.logits(weights, ids, CFG))
    gaps = [np.abs(np.asarray(ref.logits(weights, ids, CFG, p)) - f32).max()
            for p in ("bf16", "fp8")]
    assert 0 < gaps[0] < gaps[1]


def test_seeded_weights_repeat_and_keep_the_published_ranges(weights):
    again = hybrid_weights.make(CFG, seed=3000000019, dtype="float32")
    other = hybrid_weights.make(CFG, seed=3000000020, dtype="float32")
    assert all(np.array_equal(weights[k], again[k]) for k in weights)
    assert not np.array_equal(weights["embed"], other["embed"])
    assert set(weights) == set(ref.leaf_shapes(CFG))
    a_log = np.asarray(weights["front.m.A_log"])
    assert np.allclose(np.exp(a_log[0, 0]), np.arange(1, 5))
    dt = np.log1p(np.exp(np.asarray(weights["mid.m.dt_proj.b"])))
    assert 1e-3 * 0.99 <= dt.min() and dt.max() <= 1e-1 * 1.01
    assert abs(np.asarray(weights["front.m.D"]).mean() - 1) < 0.05
    assert abs(np.asarray(weights["mid.a.subln.w"]).mean() - 1) < 0.05
    assert np.abs(np.asarray(weights["front.m.conv.w"])).max() <= 0.5
    kinds = {hybrid_weights.kind_of(k) for k in weights}
    assert kinds == {"normal", "one_plus", "lam", "conv", "a_log", "dt_bias"}


def test_byte_counts_at_the_published_sizes():
    cfg = published()
    assert hybrid_bytes.weight_bytes(cfg) == 2 * 3852562944
    assert (hybrid_bytes.reading_layers(cfg), hybrid_bytes.window_layers(cfg),
            hybrid_bytes.mamba_layers(cfg)) == (8, 8, 9)
    assert hybrid_bytes.kv_bytes_per_token_layer(cfg) == 5120
    # 3.23 MB a sequence: conv [3, 5120] bf16 + SSM [5120, 16] f32, 9 layers
    assert 9 * hybrid_bytes.state_bytes_per_sequence_layer(cfg) == 3225600
    recs = [dict(t_first_token=0.0, t_done=10.0, n_tokens=200,
                 prompt_len=300),
            dict(t_first_token=5.0, t_done=20.0, n_tokens=100,
                 prompt_len=900),
            dict(t_first_token=None, t_done=None, n_tokens=0, prompt_len=5)]
    lv = hybrid_bytes.live(recs, 0.0, 10.0, 512)
    assert lv == {"sequences": 1.5, "tokens": 400 + 475.0,
                  "window_tokens": 400 + 256.0}
    parts = hybrid_bytes.decode_step_bytes(cfg, lv)
    assert parts["shared_kv"] == 8 * 5120 * 875
    assert parts["window_kv"] == 8 * 5120 * 656
    assert parts["state"] == 2 * 3225600 * 1.5
    assert parts["total"] == sum(v for k, v in parts.items() if k != "total")


def test_family_reader_needs_exactly_one_family():
    reader = harness_spec.reader("family_roofline")
    cfg = published()
    recs = [dict(t_first_token=0.0, t_done=10.0, n_tokens=200,
                 prompt_len=300)]
    obs = dict(config=cfg, device_kind="TPU v5 lite", records=recs,
               t_open=0.0, t_close=10.0,
               counters_open={"engine.steps": 0},
               counters_close={"engine.steps": 400},
               trace={"window_s": 4.0, "busy_s": 3.9, "families": [
                   ["fusion f32[9,64,16,5120]", 0.8],
                   ["fusion f32[64,4,2048]", 1.0],
                   ["fusion (f32[64,4,2048], f32[64,4])", 0.5]]})
    kw = dict(bytes_of="ssm_update", per="engine.steps")
    got = reader.read(obs, pattern=r"^fusion f32\[9,64,16,5120\]$", **kw)
    # 2 x 3.2256 MB x 1 sequence x 40 steps/s over 819 GB/s, over 0.2 busy
    assert got == pytest.approx(100 * 2 * 3225600 * 40 / 819e9 / 0.2)
    assert reader.read(obs, pattern=r"f32\[64,4,2048\]", **kw) is None
    assert reader.read(obs, pattern=r"nothing like it", **kw) is None
    assert reader.read(dict(obs, trace=None), pattern="fusion", **kw) is None
    assert obs["notes"]["family_roofline"]["ssm_update"]["matched"] == []


def test_every_new_metric_file_names_a_reader_that_exists():
    bm = harness_spec.benchmark()
    mine = [m["name"] for m in bm["per_layer"]
            if m.get("workloads") == ["phi4flash-serve-reason"]]
    assert len(mine) == 6
    for name in mine:
        spec = harness_spec.layer_metric(name)
        assert hasattr(harness_spec.reader(spec["reader"]), "read")
        assert len(spec["doc"]) > 20

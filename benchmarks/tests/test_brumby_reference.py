"""The plain reference of the ``brumby`` family on its own (CPU, tiny
sizes): its parts against hand-written arithmetic in numpy loops (the
attention form against the token-by-token recurrence over the full outer
product, which is neither the reference's code nor the program's), the
seeded weights, the configuration file against the published numbers, and
the byte counts the roofline shares divide by against hand-reckoned
figures."""
import json
from pathlib import Path

import numpy as np
import pytest

import tiny  # noqa: F401 — puts the benchmark on sys.path
from harness import brumby_bytes, brumby_weights
from harness import spec as harness_spec
from reference import brumby as ref

BENCH = Path(__file__).resolve().parents[1]
CFG = dict(hidden_size=32, num_hidden_layers=3, num_attention_heads=4,
           num_key_value_heads=2, head_dim=8, intermediate_size=24,
           vocab_size=80, rms_norm_eps=1e-6, rope_theta=1e6,
           assumed=dict(power=2, retention_eps=1e-6),
           serve=dict(precision="bf16", retention_state="f32",
                      prefill_chunk_tokens=8, max_slots=4))


def published():
    return json.loads(
        (BENCH / "configs" / "brumby-14b-base.json").read_text())


@pytest.fixture(scope="module")
def weights():
    w = brumby_weights.make(CFG, seed=3600000019, dtype="float32")
    return {k: np.asarray(v, np.float64) for k, v in w.items()}


def _rms(x, w, eps=1e-6):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta=1e6):
    t, _, hd = x.shape
    out = np.zeros_like(x)
    for pos in range(t):
        for i in range(hd // 2):
            a = pos / theta ** (2 * i / hd)
            x1, x2 = x[pos, :, i], x[pos, :, i + hd // 2]
            out[pos, :, i] = x1 * np.cos(a) - x2 * np.sin(a)
            out[pos, :, i + hd // 2] = x2 * np.cos(a) + x1 * np.sin(a)
    return out


def _f32(p):
    import jax.numpy as jnp
    return {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}


def test_the_file_keeps_every_published_number_and_states_the_cut():
    cfg = published()
    row = dict(attention_bias=False, head_dim=128, hidden_act="silu",
               hidden_size=5120, intermediate_size=17408,
               max_position_embeddings=32768, max_window_layers=40,
               model_type="brumby", num_attention_heads=40,
               num_key_value_heads=8, rms_norm_eps=1e-06, rope_scaling=None,
               rope_theta=1000000, sliding_window=None,
               tie_word_embeddings=False, use_sliding_window=False,
               vocab_size=151936)
    for k, v in row.items():
        assert cfg[k] == v, k
    assert cfg["num_hidden_layers"] == 8 and \
        cfg["num_hidden_layers_published"] == 40
    assert cfg["reduced"] == ["num_hidden_layers"]
    bm = harness_spec.benchmark()
    entry = next(c for c in bm["configs"] if c["name"] == "brumby-14b-base")
    assert entry["reduced"] == cfg["reduced"] and \
        entry["source"] == cfg["source"]
    dep = cfg["deployment"]
    assert dep["pipeline_stages"] * dep["layers_per_stage"] == 40 and \
        dep["chips_sharing_a_layer"] == 1
    a = cfg["assumed"]
    for key in ("power", "qk", "gate", "gate_bias", "normaliser",
                "retention_eps", "state", "stored_terms", "free_choices"):
        assert key in a, key
    assert a["parameters"] == ref.param_count(cfg) == 4198652992
    wl = next(w for w in bm["workloads"]
              if w["name"] == "brumby14b-serve-longform")
    assert (wl["config"], wl["traffic"], wl["chips"]) == \
        ("brumby-14b-base", "longform-closed-16", 1)
    listed = {m["name"] for m in bm["per_layer"]
              if "brumby14b-serve-longform" in m.get("workloads", [])}
    assert not listed & {"pool_fill_share", "prefix_hit_share",
                         "window_pages_recycled"}
    assert {"brumby_decode_roofline_share", "retention_update_roofline_share",
            "retention_chunk_roofline_share", "step_p50_ms",
            "batch_occupancy"} <= listed


def test_the_traffic_is_the_issues():
    from harness import traffic as T
    tr = json.loads((BENCH / "traffic" / "longform-closed-16.json")
                    .read_text())
    assert (tr["kind"], tr["loop"], tr["clients"], tr["table_size"],
            tr["block"]) == ("serve_brumby", "closed", 16, 128, 8)
    (cls,) = tr["classes"]
    assert cls["name"] == "unshared" and cls["prompt"] == dict(
        dist="lognormal", min=1024, max=4096, median=2048)
    assert cls["answer"] == dict(dist="lognormal", min=192, max=768,
                                 median=384)
    table = T.request_table(tr)
    assert min(r["prompt"] for r in table) >= 1024 and \
        max(r["prompt"] + r["answer"] for r in table) + 256 <= \
        published()["serve"]["reference_pad"]
    assert not any(r["prefix"] for r in table)


def test_byte_counts_are_the_configurations_arithmetic():
    """Against figures reckoned by hand from the published widths."""
    cfg = published()
    # a layer: q 5120x5120, k and v 5120x1024 each, o 5120x5120, the gate
    # 5120x8 + 8, the gated MLP 3 x 5120 x 17408, two layer norms, two
    # head norms
    layer = 5120 * 5120 * 2 + 5120 * 1024 * 2 + 5120 * 8 + 8 \
        + 3 * 5120 * 17408 + 2 * 5120 + 2 * 128
    assert brumby_bytes.layer_params(cfg) == layer == 330352904
    assert ref.param_count(cfg) == 8 * layer + 2 * 151936 * 5120 + 5120
    # the state: 128 x 129 / 2 = 8,256 distinct terms; S [D, 128] and z [D]
    # float32 for each of 8 kv heads: 34.08 MB a layer a sequence
    assert brumby_bytes.distinct_terms(cfg) == 8256
    assert brumby_bytes.state_bytes_per_sequence_layer(cfg) == \
        8 * 8256 * 129 * 4 == 34080768
    assert brumby_bytes.state_bytes_per_sequence(cfg) == 272646144
    # a decode step at 16 live: 8 layers and the head once (the head's
    # 777.9M parameters are 1.556 GB in bf16; ISSUE 36 wrote 0.78 GB and so
    # 14.8 GB a step) + the state read and written: 15.57 GB, 19.0 ms
    step = brumby_bytes.decode_step_bytes(cfg, {"sequences": 16.0})
    assert step["weights"] == 2 * (8 * layer + 5120 + 5120 * 151936)
    assert step["state"] == 2 * 16 * 272646144
    assert abs(step["total"] - 15.566e9) < 1e6
    assert abs(step["state"] / step["total"] - 0.5605) < 1e-3
    # one prefill launch of 512: inside the chunk 2 x 40 x 512 x 512 x 128
    # multiply-adds, the carried state's read-out and the chunk's addition
    # (40 + 8) x 512 x 8,256 x 129, two operations each, 8 layers
    flops = brumby_bytes.retention_chunk_flops(cfg, 512)
    assert flops == 8 * 2.0 * (2 * 40 * 512 * 512 * 128
                               + 48 * 512 * 8256 * 129)
    assert abs(flops - 461.7e9) < 1e8
    nbytes = brumby_bytes.retention_chunk_bytes(cfg, 512)
    assert nbytes == 8 * (2 * 34080768 + 512 * (2 * 5120 + 2 * 1024 + 8) * 4)
    shapes = brumby_bytes.trace_shapes(cfg)
    assert shapes == dict(layers=8, slots=16, kv=8, heads=40, group=5,
                          head=128, diagonals=65, chunk=512)
    assert shapes["diagonals"] * shapes["head"] == \
        cfg["assumed"]["stored_terms"]


def test_retention_is_the_written_recurrence(weights):
    """The attention form against S_t = g_t S_{t-1} + (k_t k_t^T) v_t^T
    over the FULL outer product, token by token in numpy loops: the same
    numbers as any packing of the distinct terms."""
    import jax.numpy as jnp
    s = ref.sizes(CFG)
    t = 13
    p = {k[2:]: v[1] for k, v in weights.items() if k.startswith("l.")}
    a = np.random.RandomState(0).randn(t, s.d)
    got = np.asarray(ref.retention(jnp.asarray(a, jnp.float32), _f32(p), s,
                                   "f32"), np.float64)
    qw, kvw = s.nq * s.hd, s.nkv * s.hd
    qkv = a @ p["qkv.w"]
    q = _rope(_rms(qkv[:, :qw].reshape(t, s.nq, s.hd), p["q_norm.w"]))
    k = _rope(_rms(qkv[:, qw:qw + kvw].reshape(t, s.nkv, s.hd),
                   p["k_norm.w"]))
    v = qkv[:, qw + kvw:].reshape(t, s.nkv, s.hd)
    x = a @ p["gate.w"] + p["gate.b"]
    g = 1 / (1 + np.exp(-x))                              # [T, nkv]
    y = np.zeros((t, s.nq, s.hd))
    for j in range(s.nkv):
        st, zt = np.zeros((s.hd, s.hd, s.hd)), np.zeros((s.hd, s.hd))
        for i in range(t):
            kk = np.outer(k[i, j], k[i, j])
            st = g[i, j] * st + kk[:, :, None] * v[i, j][None, None]
            zt = g[i, j] * zt + kk
            for h in range(j * 2, j * 2 + 2):
                qq = np.outer(q[i, h], q[i, h]) / s.hd ** 2
                y[i, h] = np.einsum("ab,abv->v", qq, st) \
                    / ((qq * zt).sum() + 1e-6)
    want = y.reshape(t, qw) @ p["o.w"]
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()


def test_the_state_control_is_the_recurrence_rounded(weights):
    """``state_bf16`` runs the same layer as the recurrence with the state
    rounded to bfloat16 a token: close to the reference, and not it."""
    import jax.numpy as jnp
    s = ref.sizes(CFG)
    p = _f32({k[2:]: v[0] for k, v in weights.items() if k.startswith("l.")})
    a = jnp.asarray(np.random.RandomState(1).randn(40, s.d), jnp.float32)
    want = np.asarray(ref.retention(a, p, s, "f32"))
    got = np.asarray(ref.retention(a, p, s, "state_bf16"))
    err = np.abs(got - want).max() / np.abs(want).max()
    assert 1e-4 < err < 5e-2, err


def test_the_stack_is_the_written_one(weights):
    import jax.numpy as jnp
    s = ref.sizes(CFG)
    ids = np.random.RandomState(2).randint(0, 80, size=9)
    h = weights["embed"][ids]
    for i in range(s.n):
        p = {k[2:]: v[i] for k, v in weights.items() if k.startswith("l.")}
        a = _rms(h, p["norm1.w"])
        h = h + np.asarray(ref.retention(jnp.asarray(a, jnp.float32),
                                         _f32(p), s, "f32"), np.float64)
        b = _rms(h, p["norm2.w"])
        u, w = np.split(b @ p["mlp.w1"], 2, -1)
        h = h + (u / (1 + np.exp(-u)) * w) @ p["mlp.w2"]
    want = _rms(h, weights["norm_f.w"]) @ weights["head"]
    got = np.asarray(ref.logits(_f32(weights), jnp.asarray(ids), CFG))
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()
    rows = np.asarray(ref.logits(_f32(weights), jnp.asarray(ids), CFG,
                                 rows=jnp.asarray([3, 8])))
    assert np.abs(rows - got[[3, 8]]).max() <= 1e-6 * np.abs(got).max()


def test_seeded_weights_repeat_and_keep_their_ranges():
    a = brumby_weights.make(CFG, seed=2 ** 31 + 12345, dtype="float32")
    b = brumby_weights.make(CFG, seed=2 ** 31 + 12345, dtype="float32")
    c = brumby_weights.make(CFG, seed=2 ** 31 + 12346, dtype="float32")
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["l.mlp.w1"], c["l.mlp.w1"])
    assert list(a) == list(ref.leaf_shapes(CFG))
    assert all(tuple(a[k].shape) == tuple(v)
               for k, v in ref.leaf_shapes(CFG).items())
    # -log g at a zero input: log-uniform in [1e-4, 1e-2]
    lam = np.log1p(np.exp(-np.asarray(a["l.gate.b"], np.float64)))
    assert lam.min() >= 1e-4 * 0.99 and lam.max() <= 1e-2 * 1.01
    assert float(np.asarray(a["l.gate.w"]).std()) < 0.006
    for name in ("l.norm1.w", "l.q_norm.w", "l.k_norm.w", "norm_f.w"):
        assert abs(float(np.asarray(a[name]).mean()) - 1) < 0.05, name
    # layers differ: a stacked leaf is not one draw repeated
    assert not np.array_equal(a["l.mlp.w1"][0], a["l.mlp.w1"][1])


def test_the_new_readers_give_nothing_where_there_is_nothing_to_read():
    """A traced run of the parent, or of a cell without this family: no
    family matches, the metric is left out and nothing raises."""
    reader = harness_spec.reader("brumby_roofline")
    obs = {"trace": {"families": [["fusion bf16[24,1024]", 1.0]],
                     "window_s": 4.0},
           "config": published(), "device_kind": "TPU v5 lite",
           "records": [], "t_open": 0.0, "t_close": 40.0,
           "traced_calls": {"engine.steps": 130,
                            "engine.prefill_launches": 24}}
    for name in ("retention_update_roofline_share",
                 "retention_chunk_roofline_share"):
        spec = harness_spec.layer_metric(name)
        params = {k: v for k, v in spec.items() if k not in ("reader", "doc")}
        assert reader.read(dict(obs), **params) is None
    assert reader.read({"trace": None}, ["x"], "retention_chunk",
                       "engine.steps") is None
    # calls are counted inside the traced part of the window: a family's
    # 2.0 s over 130 steps of 15.5 live sequences is 15.4 ms a step, where
    # the state's bytes take 10.3 at 819 GB/s
    spec = harness_spec.layer_metric("retention_update_roofline_share")
    fam = "custom-call (f32[8,16,8,65,128,128], f32[8,16,8,8320], " \
          "f32[16,8,8,128], f32[16,8,..)"
    live = [{"t_first_token": 0.0, "t_done": 20.0, "prompt_len": 2048,
             "n_tokens": 384}] * 31
    got = reader.read(dict(obs, records=live, trace={
        "families": [[fam, 2.0]], "window_s": 4.0}),
        spec["patterns"], spec["work_of"], spec["per"])
    assert 66.5 < got < 67.5
    assert reader.read(dict(obs, traced_calls=None, trace={
        "families": [[fam, 2.0]], "window_s": 4.0}),
        spec["patterns"], spec["work_of"], spec["per"]) is None
    # a configuration of another family has no such sizes: nothing, quietly
    assert reader.read(dict(obs, config={"serve": {}}), ["{slots}"],
                       "retention_chunk", "engine.steps") is None
    step = harness_spec.reader("brumby_decode_roofline")
    assert step.read(dict(obs, engine_steps=[])) is None

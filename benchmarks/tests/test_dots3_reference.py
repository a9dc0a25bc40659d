"""The plain reference of the ``dots3_note`` family on its own (CPU, tiny
sizes): the whole forward against a second, slower writing of the equations
(numpy float64, a token and a head at a time, the selection by a Python
sort), the controls the reference can name, the seeded weights, the
configuration file against the published numbers, and the byte counts the
roofline shares divide by against the configuration's arithmetic."""
import json
import math
from pathlib import Path

import numpy as np
import pytest

import tiny  # noqa: F401 — puts the benchmark on sys.path
from harness import dots3_bytes, dots3_weights
from reference import dots3note as ref

BENCH = Path(__file__).resolve().parents[1]
CFG = dict(
    hidden_size=32, num_hidden_layers=5,
    layer_types=["full_attention", "full_attention", "sliding_attention",
                 "sliding_attention", "sliding_attention", "full_attention"],
    vocab_size=80, first_k_dense_replace=1, intermediate_size=40,
    moe_intermediate_size=8, n_routed_experts=3, router_outputs=6,
    experts_first=2, num_experts_per_tok=2, routed_scaling_factor=1.5,
    scoring_func="sigmoid", topk_method="noaux_tc", norm_topk_prob=True,
    n_shared_experts=1, attention_gate_type="headwise",
    swa_attention_gate_type="headwise", apply_mla_qkv_lora_rescale=True,
    num_attention_heads=4, q_lora_rank=12, kv_lora_rank=8,
    qk_nope_head_dim=6, qk_rope_head_dim=4, v_head_dim=5, rope_theta=1e4,
    swa_num_attention_heads=2, swa_q_lora_rank=10, swa_kv_lora_rank=12,
    swa_qk_nope_head_dim=8, swa_qk_rope_head_dim=4, swa_v_head_dim=6,
    swa_rope_theta=1e3, index_n_heads=3, index_head_dim=8, index_topk=6,
    assumed=dict(index_rope_dim=4), sliding_window_size=5,
    rms_norm_eps=1e-5,
    serve=dict(precision="bf16", prefill_chunk_tokens=8, max_slots=4))
T = 24


def published():
    return json.loads((BENCH / "configs" / "dots3-note-prev.json").read_text())


@pytest.fixture(scope="module")
def weights():
    import jax.numpy as jnp
    w = dots3_weights.make(CFG, seed=4000000019, dtype="float32")
    # ten times the seeded scale: at these widths attention and the
    # indexer's scores are then far from flat
    return {k: np.asarray(v, np.float64) * (1.0 if "norm" in k else 10.0)
            for k, v in w.items()}, jnp


# ------------------------------------------- the equations, a second time

def _rms(x, w):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * w


def _silu(x):
    return x / (1 + np.exp(-x))


def _sig(x):
    return 1 / (1 + np.exp(-x))


def _rot(v, pos, theta):
    """Half rotation of one vector at one position, pair by pair."""
    half = len(v) // 2
    out = np.array(v, np.float64)
    for i in range(half):
        ang = pos * theta ** (-2.0 * i / len(v))
        c, s = math.cos(ang), math.sin(ang)
        out[i] = v[i] * c - v[i + half] * s
        out[i + half] = v[i + half] * c + v[i] * s
    return out


def _gated(b, w1, w2):
    u, v = np.split(b @ w1, 2)
    return (_silu(u) * v) @ w2


def _attention(a, p, s, kind, variant=None):
    at = s.full if kind == "full_attention" else s.swa
    t_all = a.shape[0]
    scale = 1 / math.sqrt(at.dn + at.dr)
    cq = np.stack([_rms(a[t] @ p["dq"], p["q_norm.w"]) * at.sq
                   for t in range(t_all)])
    kv = a @ p["dkv"]
    ckv = np.stack([_rms(kv[t, :at.rank], p["kv_norm.w"]) * at.skv
                    for t in range(t_all)])
    kr = np.stack([_rot(kv[t, at.rank:], t, at.theta) for t in range(t_all)])
    w_ukv = p["ukv"].reshape(at.rank, at.heads, at.dn + at.dv)
    if kind == "full_attention":
        ki = np.stack([a[t] @ p["ik"] for t in range(t_all)])
        mu = ki.mean(-1, keepdims=True)
        ki = (ki - mu) / np.sqrt(((ki - mu) ** 2).mean(-1, keepdims=True)
                                 + 1e-5) * p["ik_norm.w"] + p["ik_norm.b"]
        for t in range(t_all):
            ki[t, :s.i_rope] = _rot(ki[t, :s.i_rope], t, at.theta)
    out = np.zeros((t_all, at.heads * at.dv))
    for t in range(t_all):
        if kind == "full_attention":
            qi = (cq[t] @ p["iq"]).reshape(s.hi, s.di)
            w = a[t] @ p["iw"] * s.hi ** -0.5 * s.di ** -0.5
            score = []
            for u in range(t + 1):
                tot = 0.0
                for j in range(s.hi):
                    qj = np.array(qi[j])
                    qj[:s.i_rope] = _rot(qj[:s.i_rope], t, at.theta)
                    tot += w[j] * max(0.0, float(qj @ ki[u]))
                score.append(tot)
            keys = sorted(range(t + 1), key=lambda u: (-score[u], u))
            keys = sorted(keys[:s.topk])
            if variant == "all_keys":
                keys = list(range(t + 1))
            elif variant == "last_topk":
                keys = list(range(max(0, t - s.topk + 1), t + 1))
        else:
            win = s.window - (1 if variant == "window_less_1" else 0)
            keys = list(range(max(0, t - win + 1), t + 1))
        q = (cq[t] @ p["uq"]).reshape(at.heads, at.dn + at.dr)
        g = _sig(a[t] @ p["gate"])
        for h in range(at.heads):
            qr = _rot(q[h, at.dn:], t, at.theta)
            sc = np.array([(q[h, :at.dn] @ (ckv[u] @ w_ukv[:, h, :at.dn])
                            + qr @ kr[u]) * scale for u in keys])
            pr = np.exp(sc - sc.max())
            pr /= pr.sum()
            o = sum(pr[n] * (ckv[u] @ w_ukv[:, h, at.dn:])
                    for n, u in enumerate(keys))
            out[t, h * at.dv:(h + 1) * at.dv] = \
                o if variant == "no_gate" else o * g[h]
    return out @ p["o"]


def _experts(b, p, s, variant=None):
    out = np.zeros_like(b)
    for t in range(b.shape[0]):
        logits = b[t] @ p["router"]
        sc = _sig(logits)
        chosen = sorted(range(s.experts),
                        key=lambda e: (-(sc[e] + p["bias"][e]), e))[:s.top_k]
        if variant == "softmax_router":
            z = np.exp(logits[chosen] - logits[chosen].max())
            gates = dict(zip(chosen, z / z.sum()))
        else:
            tot = sum(sc[e] for e in chosen)
            gates = {e: sc[e] / tot * s.route_scale for e in chosen}
        for e, g in gates.items():
            if s.held[0] <= e < s.held[1]:
                k = e - s.held[0]
                out[t] += g * _gated(b[t], p["w1"][k], p["w2"][k])
        out[t] += _gated(b[t], p["shared.w1"], p["shared.w2"])
    return out


def slow_logits(w, ids, cfg, variant=None):
    s = ref.sizes(cfg)
    x = w["embed"][ids]
    for i, kind in enumerate(s.types):
        pa = {k[len(f"L{i}.a."):]: v for k, v in w.items()
              if k.startswith(f"L{i}.a.")}
        pf = {k[len(f"L{i}.f."):]: v for k, v in w.items()
              if k.startswith(f"L{i}.f.")}
        x = x + _attention(_rms(x, pa["norm.w"]), pa, s, kind, variant)
        b = _rms(x, pf["norm.w"])
        if i < s.first_dense:
            x = x + np.stack([_gated(b[t], pf["w1"], pf["w2"])
                              for t in range(len(ids))])
        else:
            x = x + _experts(b, pf, s, variant)
    return _rms(x, w["norm_f.w"]) @ w["head"].T


def _ref_logits(w, jnp, ids, precision="f32"):
    import jax
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(
            {k: jnp.asarray(v, jnp.float32) for k, v in w.items()},
            jnp.asarray(ids), CFG, precision), np.float64)


@pytest.fixture(scope="module")
def ids():
    return np.random.RandomState(3).randint(0, 80, size=T).astype(np.int32)


def test_the_reference_is_the_equations_written_a_second_time(weights, ids):
    """24 tokens through a dense first layer, a full layer, three sliding
    ones (window 5) with selection of 6 keys, a share of 3 of 6 experts from
    the third on: float32 blocks and masks against float64 loops."""
    w, jnp = weights
    want = slow_logits(w, ids, CFG)
    got = _ref_logits(w, jnp, ids)
    assert np.abs(got - want).max() / np.abs(want).max() < 2e-5
    assert np.abs(want).max() > 0.5


@pytest.mark.parametrize("variant", list(ref.WRONG))
def test_each_wrong_model_is_the_one_its_name_says(weights, ids, variant):
    """The controls the reference can compute are the second writing's
    with the same step changed, and each moves the logits far."""
    w, jnp = weights
    want = slow_logits(w, ids, CFG, variant)
    got = _ref_logits(w, jnp, ids, variant)
    sound = _ref_logits(w, jnp, ids)
    assert np.abs(got - want).max() / np.abs(want).max() < 2e-5
    assert np.abs(got - sound).max() / np.abs(sound).max() > 1e-2


def test_a_lower_precision_moves_the_logits(weights, ids):
    w, jnp = weights
    sound = _ref_logits(w, jnp, ids)
    moved = {p: np.abs(_ref_logits(w, jnp, ids, p) - sound).max()
             / np.abs(sound).max() for p in ("bf16", "fp8")}
    # at this size and ten times the seeded scale a key that changes sides
    # of the selection moves a logit by as much as the logits are large:
    # both precisions read near 1, neither near 0
    assert moved["bf16"] > 1e-3 and moved["fp8"] > 1e-2


def test_selection_mask_keeps_the_lower_position_of_a_tie():
    import jax.numpy as jnp
    sc = jnp.asarray([[1.0, 3.0, 3.0, 3.0, 0.0, 9.0],
                      [2.0, 2.0, 2.0, 2.0, 2.0, 2.0]])
    m = np.asarray(ref.select_mask(sc, jnp.asarray([5, 3]), 3, "f32"))
    assert m[0].tolist() == [False, True, True, False, False, True]
    assert m[1].tolist() == [True, True, True, False, False, False]
    few = np.asarray(ref.select_mask(sc, jnp.asarray([1, 2]), 3, "f32"))
    assert few.sum(-1).tolist() == [2, 3]


def test_selection_overlap_tells_a_choice_from_a_window(weights):
    """Under seeded weights the indexer's choice does not follow position:
    of 58 queries past ``index_topk`` nearly all differ from the last 6."""
    w, jnp = weights
    ids = np.random.RandomState(5).randint(0, 80, size=64).astype(np.int32)
    out = ref.selection_overlap(
        {k: jnp.asarray(v, jnp.float32) for k, v in w.items()},
        jnp.asarray(ids), CFG, 1)
    assert int(out["queries_past_topk"]) == 64 - 6
    assert float(out["queries_that_differ"]) > 0.9
    assert float(out["keys_shared_with_last_topk"]) < 0.5


# ------------------------------------------------- weights, file, bytes

def test_weights_are_seeded_and_show_every_switch():
    a = dots3_weights.make(CFG, seed=7, dtype="float32")
    b = dots3_weights.make(CFG, seed=7, dtype="float32")
    c = dots3_weights.make(CFG, seed=8, dtype="float32")
    assert list(a) == list(ref.leaf_shapes(CFG))
    for k, shape in ref.leaf_shapes(CFG).items():
        assert tuple(a[k].shape) == tuple(shape), k
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k
        assert not np.array_equal(np.asarray(a[k]), np.asarray(c[k])), k
    assert abs(float(np.asarray(a["L1.a.q_norm.w"]).mean()) - 1) < 0.05
    assert 0 < float(np.abs(np.asarray(a["L1.a.ik_norm.b"])).max()) < 0.2
    assert 0 < float(np.abs(np.asarray(a["L2.f.bias"])).max()) < 0.5
    assert float(np.asarray(a["L2.f.w1"]).std()) == pytest.approx(0.02,
                                                                  rel=0.1)


def test_the_file_keeps_every_published_number_and_states_the_cut():
    cfg = published()
    rows = [json.loads(line) for line in Path(
        "/opt/skills/guides/model-configs/architectures.jsonl").read_text()
        .splitlines()] if Path(
        "/opt/skills/guides/model-configs/architectures.jsonl").exists() \
        else []
    row = next((r for r in rows if r["name"] == "dots3-note-prev"), None)
    if row is not None:
        assert cfg["source"] == row["source_url"]
        for k, v in row["config"].items():
            if k not in cfg["reduced"]:
                assert cfg[k] == v, k
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_hidden_layers_published"]) \
        == (5, 46)
    assert (cfg["n_routed_experts"], cfg["n_routed_experts_published"],
            cfg["router_outputs"], cfg["experts_first"]) == (32, 256, 256, 0)
    assert (cfg["vocab_size"], cfg["vocab_size_published"]) == (19008,
                                                                152064)
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"]
    assert cfg["layer_types"][:5] == ["full_attention", "full_attention",
                                      "sliding_attention",
                                      "sliding_attention",
                                      "sliding_attention"]
    assert cfg["deployment"]["chips_sharing_a_layer"] == 8
    for key in ("index_rope_dim", "lora_rescale", "selection_ties", "gate",
                "window", "router_groups", "rotation_pairing", "weights",
                "index_keys_precision"):
        assert key in cfg["assumed"], key
    assert ref.param_count(cfg) == cfg["assumed"]["parameters"] \
        == 4087154176
    sv = cfg["serve"]
    assert sv["num_pages"] == 1 + sv["max_slots"] * sv["max_seq_len"] \
        // sv["page_size"]
    assert sv["max_seq_len"] % sv["prefill_chunk_tokens"] == 0
    assert dots3_bytes.kv_bytes_per_token(cfg) == 2816


def test_byte_and_operation_counts_are_the_configurations_arithmetic():
    cfg = published()
    assert dots3_bytes.expert_params(cfg) == 3 * 5120 * 1536
    assert dots3_bytes.latent_row_bytes(cfg) == (512 + 64) * 2
    assert dots3_bytes.ring_bytes(cfg) == 513 * 1088 * 2
    lv = {"sequences": 14.0, "tokens": 14 * 20000.0,
          "kept_tokens": 14 * 2048.0}
    parts = dots3_bytes.decode_step_bytes(cfg, lv, 4 * 11.0)
    assert parts["experts_hit"] == 44 * 3 * 5120 * 1536 * 2
    assert parts["index_keys"] == 2 * 256 * 14 * 20000
    assert parts["latent_rows"] == 2 * 1152 * 14 * 2048
    assert parts["rings"] == 3 * 14 * 513 * 1088 * 2
    held = 4 * 32 * 3 * 5120 * 1536 * 2
    assert parts["other_weights"] == pytest.approx(
        4087154176 * 2 - held - 19008 * 5120 * 2)
    assert parts["total"] == pytest.approx(sum(
        v for k, v in parts.items() if k != "total"))
    # a decode step's pair: a row of its own, the absorbed form
    b, f = dots3_bytes.latent_attention_work(cfg, 1.0, 0.0, 512)
    assert (b, f) == (1152, 2 * 128 * (512 + 64 + 512))     # 570 MFLOP / 2048
    # a chunk's pairs: rows shared by its 512 queries, the per-head form
    b, f = dots3_bytes.latent_attention_work(cfg, 0.0, 512.0, 512)
    assert (b, f) == (1152, 512 * 2 * 128 * (128 + 64 + 128))
    assert dots3_bytes.experts_work(cfg, 10.0, 3.0) == (
        3 * 3 * 5120 * 1536 * 2, 10 * 2 * 3 * 5120 * 1536)
    assert dots3_bytes.index_work(cfg, 1.0) == (0.0, 16384.0)
    shapes = dots3_bytes.trace_shapes(cfg)
    assert (shapes["slots"], shapes["chunk"], shapes["topk"], shapes["row"],
            shapes["ring"], shapes["window_keys"], shapes["decode_sort"],
            shapes["slot_rows"], shapes["decode_rows"]) == (
        24, 512, 2048, 640, 544, 1056, 18432, 49152, 192)


def _obs(families):
    cfg = published()
    recs = [{"t_first_token": 0.0, "t_done": 40.0, "n_tokens": 400,
             "prompt_len": 12000}] * 14
    c0 = {}
    c1 = {"engine.steps": 480, "engine.prefill_launches": 470,
          "engine.sparse.keys_scored": 5.0e9, "engine.sparse.keys_attended":
          1.0e9, "engine.sparse.keys_attended.decode": 2.7e7,
          "engine.moe.assignments_held": 1.0e6,
          "engine.moe.experts_hit.decode": 480 * 4 * 12,
          "engine.moe.experts_hit.prefill": 470 * 4 * 32}
    return {"config": cfg, "records": recs, "t_open": 0.0, "t_close": 40.0,
            "counters_open": c0, "counters_close": c1,
            "device_kind": "TPU v5 lite", "engine_steps": [0.08] * 480,
            "trace": {"window_s": 4.0, "families": families}}


def test_roofline_readers_find_their_families_and_stay_under_100():
    """Every pattern of the four kernel shares filled in from the committed
    configuration names a family of the shapes the chip's trace showed (my
    chip run, PR 40); with those families at the seconds that trace read,
    each share comes out between 0 and 100, and the decode step's too; a
    program without the kernels (no family matches) gives nothing."""
    from harness import spec as harness_spec
    seen = [["fusion (f32[16,512], f32[16,512,2048])", 0.281],
            ["fusion f32[8,16,512,128]", 0.267],
            ["fusion f32[16,512]", 0.228],
            ["fusion bf16[16,2048,256]", 0.132],
            ["fusion bf16[49152,640]", 0.09], ["fusion f32[24,128,2048]",
                                              0.01],
            ["fusion f32[512,1024]", 0.09], ["fusion s32[512]", 0.19],
            ["fusion f32[24,1024]", 0.015],
            ["sort (f32[24,1,18432], u32[24,1,18432])", 0.04],
            ["fusion bf16[24576,16,128]", 0.04],
            ["fusion f32[64,512,1056]", 0.1],
            ["fusion (f32[64,512], f32[64,512,1056])", 0.1],
            ["fusion bf16[1056,20480]", 0.04],
            ["fusion f32[24,64,544]", 0.002],
            ["fusion (f32[24,64], f32[24,64,544])", 0.002],
            ["fusion f32[512,32,3072]", 0.528],
            ["custom-call f32[192,3072]", 0.14],
            ["custom-call f32[192,5120]", 0.07]]
    for name in ("latent_attn", "index_select", "window_latent_attn",
                 "dots3_experts"):
        m = harness_spec.layer_metric(f"{name}_roofline_share")
        params = {k: v for k, v in m.items() if k not in ("reader", "doc")}
        read = harness_spec.reader(m["reader"]).read
        obs = _obs(seen)
        got = read(obs, **params)
        assert got is not None and 0 < got < 100, (name, got)
        note = obs["notes"]["dots3_roofline"][params["work_of"]]
        assert all(note["matched"]), (name, note)
        assert read(_obs([["fusion bf16[64,4096]", 1.0]]), **params) is None
    whole = harness_spec.reader("dots3_decode_roofline").read(_obs(seen))
    assert 0 < whole < 100
    # a program that does not count the experts it hit gives nothing
    blind = _obs(seen)
    for k in ("engine.moe.experts_hit.decode",
              "engine.moe.experts_hit.prefill"):
        del blind["counters_close"][k]
    assert harness_spec.reader("dots3_decode_roofline").read(blind) is None
    m = harness_spec.layer_metric("dots3_experts_roofline_share")
    assert harness_spec.reader(m["reader"]).read(
        blind, patterns=m["patterns"], work_of=m["work_of"]) is None
    other = _obs(seen)
    other["config"] = json.loads(
        (BENCH / "configs" / "granite-4.0-h-small.json").read_text())
    assert harness_spec.reader("dots3_decode_roofline").read(other) is None

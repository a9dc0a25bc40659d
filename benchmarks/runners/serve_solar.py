"""Runner for ``"kind": "serve_solar"`` traffic: a model of the
``solar_open2`` family (a delta rule whose decay is per key channel, with a
matrix state and a convolution state a layer; gated grouped-query softmax
attention without positions over twin K and V page pools; sigmoid-routed
experts in every layer, of which this chip holds a share) behind the same
``InferenceServer`` + ``DecodeEngine`` and the same load generator as
``runners/serve.py``.

Everything that drives, times and checks a serving run is ``serve.run``;
only what is the model's differs: how the engine is built (seeded weights
in the served type: ``harness/solar_weights.py``) and which plain reference
decides ``correct`` (``reference/solar_open2.py``, given the same share of
the experts and of the vocabulary). As ``runners/serve_giga.py`` does, this
runner binds its two functions in its own fresh copy of ``serve`` and calls
that.

``control`` names what the reference computes beside itself when limits are
set: a lower precision (``fp8``), or one of the reference's WRONG models,
each undoing what this family brings (``scalar_decay``: a head's channel
decays replaced by their mean; ``beta_half``: ``beta = sigmoid``, no factor
2; ``drop_state``: the linear layers' carried state forgotten at the
prompt's last chunk boundary; ``drop_handover``: the same wrong model with
the state forgotten before the prompt's LAST token, what a decode step that
does not take the prefill's state would serve: with a write strength near 1
this rule overwrites what it holds within a few hundred tokens, so a state
lost a whole chunk before the answer hardly shows in the answer, PERF.md
section 4).
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness import spec  # noqa: E402

BLOCK = 256          # rows of logits made at a time: [256, V] f32 is 25 MB
_LIVE = {}           # the engine, so that its device buffers can be freed


def run(ctx):
    serve = spec._module("runners", "serve")
    serve._build_engine = _build_engine
    serve._reference_gaps = _reference_gaps
    return serve.run(ctx)


def _dtype(cfg):
    import jax.numpy as jnp
    return {"bf16": jnp.bfloat16,
            "f32": jnp.float32}[cfg["serve"]["precision"]]


def model_config(cfg):
    """The program's configuration from the benchmark's file. A program
    without this family fails here, at once."""
    from paddle_tpu.models.solar_open2 import SolarOpen2Config
    lo = cfg.get("experts_first", 0)
    lin = cfg["linear_attn_config"]
    return SolarOpen2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        gqa_layers=tuple(cfg["gqa_layers"]),
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=cfg.get("router_outputs", cfg["n_routed_experts"]),
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=(lo, lo + cfg["n_routed_experts"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        linear_heads=lin["num_heads"], linear_head_dim=lin["head_dim"],
        linear_conv_kernel=lin["short_conv_kernel_size"],
        linear_gate_rank=lin["head_dim"],
        rms_norm_eps=cfg["rms_norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"])


def _build_engine(cfg, seed):
    """Weights in the served type, the program's model over them, and the
    engine."""
    from paddle_tpu.models.solar_open2 import SolarOpen2ForCausalLM
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    from harness import solar_weights
    from reference.solar_open2 import param_count
    sv = cfg["serve"]
    mcfg = model_config(cfg)
    model = SolarOpen2ForCausalLM(
        mcfg, solar_weights.make(cfg, seed, _dtype(cfg)))
    eng = DecodeEngine(model, EngineConfig(
        page_size=sv["page_size"], max_slots=sv["max_slots"],
        max_seq_len=sv["max_seq_len"], num_pages=sv["num_pages"],
        prefill_chunk_tokens=sv["prefill_chunk_tokens"],
        prefix_cache=sv["prefix_cache"], inflight=sv["inflight"]))
    _LIVE["engine"] = eng
    print(json.dumps({"note": "state", "family": eng._fam.name,
                      "param_count": param_count(cfg),
                      "experts_held": list(mcfg.experts_held),
                      "kv_bytes_per_token": eng.kv_bytes_per_token, **{
        k: _gauge(f"engine.{k}") for k in (
            "cache_bytes.paged", "cache_bytes.state",
            "state_bytes_per_slot")}}), flush=True)
    return model, eng, None


def _gauge(name):
    from paddle_tpu.observability import metrics
    return metrics.gauge(name).value


def _counts_line(eng):
    """What the run routed, attended and carried, as the program counted
    it: on the ``state`` line, after the window."""
    from paddle_tpu.models.solar_open2 import expert_totals
    from paddle_tpu.observability import metrics
    c = metrics.snapshot()["counters"]
    print(json.dumps({
        "note": "state", "routing": {
            "assignments": c.get("engine.moe.assignments", 0),
            "assignments_held": c.get("engine.moe.assignments_held", 0),
            "per_held_expert": expert_totals(eng.cfg.experts_held)},
        "after": {k: c.get(f"engine.{k}", 0) for k in (
            "gqa.pairs.decode", "gqa.pairs.prefill",
            "kda.tokens.decode", "kda.tokens.prefill",
            "moe.experts_hit.decode", "moe.experts_hit.prefill",
            "state_resets", "state_carries")}}), flush=True)


def _free_program():
    """Free the program's device buffers before the reference makes its
    own weights (``serve_hybrid.py::_free_program`` says why): parameters,
    the pool, the state, the token chain."""
    import jax
    eng = _LIVE.pop("engine", None)
    if eng is None:
        return
    _counts_line(eng)
    for a in jax.tree_util.tree_leaves(
            (eng._params, eng._kc, eng._vc, eng._state, eng._tok_dev)):
        if isinstance(a, jax.Array) and not a.is_deleted():
            a.delete()
    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({"note": "program_freed",
                      "bytes_in_use": stats.get("bytes_in_use")}), flush=True)


def _reference_gaps(cfg, seed, sample, control=None):
    """As ``serve._reference_gaps``: the plain reference once over each
    sampled prompt with its served tokens; the widest gap by which a served
    token's logit lies below the reference's best at that position, as a
    share of the largest |logit| compared. The reference walks the layers,
    holding the served-type values and widening one layer at a time; logits
    are made ``BLOCK`` rows at a time."""
    import jax
    import jax.numpy as jnp
    from harness import solar_weights
    from reference import solar_open2 as ref
    _free_program()
    w = solar_weights.make(cfg, seed, _dtype(cfg))
    s = ref.sizes(cfg)
    chunk = int(cfg["serve"]["prefill_chunk_tokens"])
    gaps = spec._module("runners", "serve_hybrid")._block_gaps()
    ref_control = "drop_state" if control == "drop_handover" else control
    worst, top, where, n_tok, ctl_worst = 0.0, 0.0, "", 0, 0.0
    took = []                       # seconds a request: the first compiles

    def head(h, precision):
        return ref.head(h, w["norm_f.w"], w["head"], s, precision)

    with jax.enable_x64(False):
        for r in sample:
            t_req = time.perf_counter()
            toks = np.asarray(r["out"], np.int32)
            n0, n = int(r["prompt_len"]), len(toks)
            # every sequence padded to the engine's limit (causal: the
            # tail is inert) and room for the last block of rows, so the
            # reference compiles each kind of layer once, for every run
            padded = int(cfg["serve"]["max_seq_len"]) + BLOCK
            ids = np.zeros(padded, np.int32)
            ids[:n] = toks
            ids = jnp.asarray(ids)
            hid = ref.hidden(w, ids, cfg, "f32")
            # the control's dropped state: at the prompt's last chunk
            # boundary, where a prefill that does not carry would lose it;
            # ``drop_handover``: before the prompt's LAST token, where a
            # decode that does not take the prefill's state would
            hid_c = ref.hidden(
                w, ids, cfg, ref_control,
                drop_at=n0 - 1 if control == "drop_handover"
                else (n0 - 1) // chunk * chunk) if control else None
            nxt = np.zeros(padded + 1, np.int32)
            nxt[:n - 1] = toks[1:]                 # position t predicts t+1
            for i in range(n0 - 1, n - 1, BLOCK):
                m = min(BLOCK, n - 1 - i)
                lg = head(jax.lax.dynamic_slice_in_dim(hid, i, BLOCK, 0),
                          "f32")
                want = jnp.asarray(nxt[i:i + BLOCK])
                if control:
                    lc = head(jax.lax.dynamic_slice_in_dim(hid_c, i, BLOCK,
                                                           0), ref_control)
                    g, t, gc_ = (np.asarray(x)[:m] for x in gaps(lg, want,
                                                                lc))
                    ctl_worst = max(ctl_worst, float(gc_.max()))
                else:
                    g, t = (np.asarray(x)[:m] for x in gaps(lg, want))
                n_tok += m
                top = max(top, float(t.max()))
                if not g.max() <= worst:
                    worst = float(g.max())
                    where = f"request {r['index']} " \
                            f"+{i + int(g.argmax()) - n0 + 1}"
            took.append(round(time.perf_counter() - t_req, 2))
    print(json.dumps({"note": "reference_requests", "seconds": took}),
          flush=True)
    out = {"gap": worst / top,
           "note": f"{where}; {n_tok} tokens of {len(sample)} requests; "
                   f"max |logit| {top:.4f}"}
    if control:
        out["control"] = control
        out["control_gap"] = ctl_worst / top
    return out

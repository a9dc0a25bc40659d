"""Runner for ``"kind": "serve_granite"`` traffic: a model of the
``granitemoehybrid`` family (Mamba-2 layers, one grouped-query attention
layer a period, routed experts of which this chip holds a share, a shared
expert) behind the same ``InferenceServer`` + ``DecodeEngine`` and the same
load generator as ``runners/serve.py``.

Everything that drives, times and checks a serving run is ``serve.run``;
only what is the model's differs: how the engine is built (seeded weights
in the served type: ``harness/granite_weights.py``) and which plain
reference decides ``correct`` (``reference/granitemoehybrid.py``, given the
same share of the experts). As ``runners/serve_hybrid.py`` does, this
runner binds its two functions in its own fresh copy of ``serve`` and calls
that.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness import spec  # noqa: E402

BLOCK = 256          # rows of logits made at a time: [256, V] f32 is 51 MB
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
    from paddle_tpu.models.granitemoehybrid import GraniteMoeHybridConfig
    n, lo = cfg["num_hidden_layers"], cfg.get("experts_first", 0)
    return GraniteMoeHybridConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(cfg["layer_types"][:n]),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["assumed"]["head_dim"],
        intermediate_size=cfg["intermediate_size"],
        shared_intermediate_size=cfg["shared_intermediate_size"],
        num_experts=cfg.get("router_outputs", cfg["num_local_experts"]),
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=(lo, lo + cfg["num_local_experts"]),
        mamba_n_heads=cfg["mamba_n_heads"], mamba_d_head=cfg["mamba_d_head"],
        mamba_d_state=cfg["mamba_d_state"], mamba_d_conv=cfg["mamba_d_conv"],
        mamba_n_groups=cfg["mamba_n_groups"],
        mamba_chunk_size=cfg["mamba_chunk_size"],
        rms_norm_eps=cfg["rms_norm_eps"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        attention_multiplier=cfg["attention_multiplier"],
        logits_scaling=cfg["logits_scaling"],
        max_position_embeddings=cfg["max_position_embeddings"],
        ssm_state_dtype={"f32": "float32", "bf16": "bfloat16"}[
            cfg["serve"]["ssm_state"]])


def _build_engine(cfg, seed):
    """Weights in the served type, the program's model over them, and the
    engine."""
    from paddle_tpu.models.granitemoehybrid import (
        GraniteMoeHybridForCausalLM)
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    from harness import granite_weights
    sv = cfg["serve"]
    mcfg = model_config(cfg)
    model = GraniteMoeHybridForCausalLM(
        mcfg, granite_weights.make(cfg, seed, _dtype(cfg)))
    eng = DecodeEngine(model, EngineConfig(
        page_size=sv["page_size"], max_slots=sv["max_slots"],
        max_seq_len=sv["max_seq_len"], num_pages=sv["num_pages"],
        prefill_chunk_tokens=sv["prefill_chunk_tokens"],
        prefix_cache=sv["prefix_cache"], inflight=sv["inflight"]))
    _LIVE["engine"] = eng
    print(json.dumps({"note": "state", "family": eng._fam.name,
                      "experts_held": list(mcfg.experts_held), **{
        k: _gauge(f"engine.{k}") for k in (
            "cache_bytes.paged", "cache_bytes.window", "cache_bytes.state",
            "state_bytes_per_slot")}}), flush=True)
    return model, eng, None


def _gauge(name):
    from paddle_tpu.observability import metrics
    return metrics.gauge(name).value


def _routing_line(eng):
    """What the run routed, as the program counted it: on the ``state``
    line, after the window."""
    from paddle_tpu.models.granitemoehybrid import expert_totals
    from paddle_tpu.observability import metrics
    c = metrics.snapshot()["counters"]
    print(json.dumps({
        "note": "state", "routing": {
            "assignments": c.get("engine.moe.assignments", 0),
            "assignments_held": c.get("engine.moe.assignments_held", 0),
            "per_held_expert": expert_totals(eng.cfg.experts_held)}}),
        flush=True)


def _free_program():
    """Free the program's device buffers before the reference makes its
    own weights (``serve_hybrid.py::_free_program`` says why): parameters,
    pools, state, the token chain."""
    import jax
    eng = _LIVE.pop("engine", None)
    if eng is None:
        return
    _routing_line(eng)
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
    from harness import granite_weights
    from reference import granitemoehybrid as ref
    _free_program()
    w = granite_weights.make(cfg, seed, _dtype(cfg))
    s = ref.sizes(cfg)
    gaps = spec._module("runners", "serve_hybrid")._block_gaps()
    worst, top, where, n_tok, ctl_worst = 0.0, 0.0, "", 0, 0.0
    took = []                       # seconds a request: the first compiles

    def head(h, precision):
        return ref.head(h, w["norm_f.w"], w["embed"], s.eps, s.lsc,
                        precision)

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
            hid_c = ref.hidden(w, ids, cfg, control) if control else None
            nxt = np.zeros(padded + 1, np.int32)
            nxt[:n - 1] = toks[1:]                 # position t predicts t+1
            for i in range(n0 - 1, n - 1, BLOCK):
                m = min(BLOCK, n - 1 - i)
                lg = head(jax.lax.dynamic_slice_in_dim(hid, i, BLOCK, 0),
                          "f32")
                want = jnp.asarray(nxt[i:i + BLOCK])
                if control:
                    lc = head(jax.lax.dynamic_slice_in_dim(hid_c, i, BLOCK,
                                                           0), control)
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

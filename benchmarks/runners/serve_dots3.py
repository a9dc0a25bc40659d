"""Runner for ``"kind": "serve_dots3"`` traffic: a model of the
``dots3_note`` family (latent attention over a paged latent pool, a learned
indexer that picks the keys a full layer attends, window layers with a
latent of their own, headwise gates, sigmoid-routed experts of which this
chip holds a share) behind the same ``InferenceServer`` + ``DecodeEngine``
and the same load generator as ``runners/serve.py``.

Everything that drives, times and checks a serving run is ``serve.run``;
only what is the model's differs: how the engine is built (seeded weights
in the served type: ``harness/dots3_weights.py``) and which plain reference
decides ``correct`` (``reference/dots3note.py``, given the same share of
the experts and of the vocabulary). As ``runners/serve_granite.py`` does,
this runner binds its two functions in its own fresh copy of ``serve`` and
calls that.

``control`` names what the reference computes beside itself when limits are
set: a lower precision (``fp8``), or one of the reference's WRONG models
(``all_keys``: every key in sight attended in place of the chosen).
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness import spec  # noqa: E402

BLOCK = 256          # rows of logits made at a time: [256, V] f32 is 19 MB
PAD_TO = 8192        # a sequence is padded to a multiple of this (below)
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
    from paddle_tpu.models.dots3note import Dots3NoteConfig
    n, lo = cfg["num_hidden_layers"], cfg.get("experts_first", 0)
    return Dots3NoteConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(cfg["layer_types"][:n]),
        first_dense=cfg["first_k_dense_replace"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=cfg.get("router_outputs", cfg["n_routed_experts"]),
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=(lo, lo + cfg["n_routed_experts"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
        index_n_heads=cfg["index_n_heads"],
        index_head_dim=cfg["index_head_dim"], index_topk=cfg["index_topk"],
        index_rope_dim=cfg["assumed"]["index_rope_dim"],
        swa_num_heads=cfg["swa_num_attention_heads"],
        swa_q_lora_rank=cfg["swa_q_lora_rank"],
        swa_kv_lora_rank=cfg["swa_kv_lora_rank"],
        swa_qk_nope_head_dim=cfg["swa_qk_nope_head_dim"],
        swa_qk_rope_head_dim=cfg["swa_qk_rope_head_dim"],
        swa_v_head_dim=cfg["swa_v_head_dim"],
        swa_rope_theta=float(cfg["swa_rope_theta"]),
        sliding_window=cfg["sliding_window_size"],
        lora_rescale=bool(cfg["apply_mla_qkv_lora_rescale"]),
        rms_norm_eps=cfg["rms_norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"])


def _build_engine(cfg, seed):
    """Weights in the served type, the program's model over them, and the
    engine."""
    from paddle_tpu.models.dots3note import Dots3NoteForCausalLM
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    from harness import dots3_weights
    sv = cfg["serve"]
    mcfg = model_config(cfg)
    model = Dots3NoteForCausalLM(
        mcfg, dots3_weights.make(cfg, seed, _dtype(cfg)))
    eng = DecodeEngine(model, EngineConfig(
        page_size=sv["page_size"], max_slots=sv["max_slots"],
        max_seq_len=sv["max_seq_len"], num_pages=sv["num_pages"],
        prefill_chunk_tokens=sv["prefill_chunk_tokens"],
        prefix_cache=sv["prefix_cache"], inflight=sv["inflight"]))
    _LIVE["engine"] = eng
    print(json.dumps({"note": "state", "family": eng._fam.name,
                      "experts_held": list(mcfg.experts_held),
                      "kv_bytes_per_token": eng.kv_bytes_per_token, **{
        k: _gauge(f"engine.{k}") for k in (
            "cache_bytes.paged", "cache_bytes.paged.latent",
            "cache_bytes.paged.index_key", "cache_bytes.window")}}),
        flush=True)
    return model, eng, None


def _gauge(name):
    from paddle_tpu.observability import metrics
    return metrics.gauge(name).value


def _counts_line(eng):
    """What the run routed and selected, as the program counted it: on the
    ``state`` line, after the window."""
    from paddle_tpu.models.dots3note import expert_totals
    from paddle_tpu.observability import metrics
    c = metrics.snapshot()["counters"]
    print(json.dumps({
        "note": "state", "routing": {
            "assignments": c.get("engine.moe.assignments", 0),
            "assignments_held": c.get("engine.moe.assignments_held", 0),
            "per_held_expert": expert_totals(eng.cfg.experts_held)},
        "selection": {
            "keys_scored": c.get("engine.sparse.keys_scored", 0),
            "keys_attended": c.get("engine.sparse.keys_attended", 0)}}),
        flush=True)


def _free_program():
    """Free the program's device buffers before the reference makes its
    own weights (``serve_hybrid.py::_free_program`` says why): parameters,
    pools, rings, the token chain."""
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
    are made ``BLOCK`` rows at a time. Once a run, on the first request,
    how far the first full layer's selection is from a window."""
    import jax
    import jax.numpy as jnp
    from harness import dots3_weights
    from reference import dots3note as ref
    _free_program()
    w = dots3_weights.make(cfg, seed, _dtype(cfg))
    s = ref.sizes(cfg)
    gaps = spec._module("runners", "serve_hybrid")._block_gaps()
    worst, top, where, n_tok, ctl_worst = 0.0, 0.0, "", 0, 0.0
    took = []                       # seconds a request: the first compiles

    def head(h, precision):
        return ref.head(h, w["norm_f.w"], w["head"], s.eps, precision)

    with jax.enable_x64(False):
        for k, r in enumerate(sample):
            t_req = time.perf_counter()
            toks = np.asarray(r["out"], np.int32)
            n0, n = int(r["prompt_len"]), len(toks)
            # a sequence padded (causal: the tail is inert) to a whole
            # number of PAD_TO with room for the last block of rows, at
            # most the engine's limit: the reference's attention costs by
            # the square of the length (40 s a sequence at 34,048 on a
            # v5e, 9 at 16,384), so it compiles each kind of layer for up
            # to four lengths rather than walk every prompt at the longest
            padded = min(-(-(n + BLOCK) // PAD_TO) * PAD_TO,
                         int(cfg["serve"]["max_seq_len"]) + BLOCK)
            ids = np.zeros(padded, np.int32)
            ids[:n] = toks
            ids = jnp.asarray(ids)
            if k == 0:
                first = s.types.index("full_attention")
                print(json.dumps({"note": "selection", "tokens": n, **{
                    key: float(v) for key, v in ref.selection_overlap(
                        w, ids[:-(-n // ref.QUERY_BLOCK) * ref.QUERY_BLOCK],
                        cfg, first).items()}}), flush=True)
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

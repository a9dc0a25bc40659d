"""Runner for ``"kind": "serve_kimi"`` traffic: a model of the ``kimi_k2``
family (latent attention in every layer over a paged latent pool with no
state beside it, sigmoid-routed experts of which this chip holds a share)
behind the same ``InferenceServer`` + ``DecodeEngine`` and the same load
generator as ``runners/serve.py``, WITH the engine's prefix store.

Everything that drives, times and checks a serving run is ``serve.run``;
what is the model's differs: how the engine is built (seeded weights in the
served type: ``harness/kimi_weights.py``) and which plain reference decides
``correct`` (``reference/kimi_k2.py``, given the same share of the experts
and of the vocabulary). As ``runners/serve_giga.py`` does, this runner
binds its functions in its own fresh copy of ``serve`` and calls that. It
also wraps that copy's ``_closed_loop``: each shared context of the
traffic is sent once, alone (answer 2), before the callers start, as
``serve.run`` does for an open loop, so that the window sees the prefix
store as a long-running server has it; and it writes on every request's
record which context it carried (``context``, ``shared``), which the
readers of the distinct latent rows need.

``control`` names what the reference computes beside itself when limits are
set: a lower precision (``fp8``), or the reference's WRONG model
``no_context`` (a sequence that attends its own tail alone: what a prefix
attached wrongly would serve).
"""
from __future__ import annotations

import itertools
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness import spec, traffic as T  # noqa: E402

_giga = spec._module("runners", "serve_giga")
BLOCK = _giga.BLOCK
_LIVE = {}           # the engine, so that its device buffers can be freed


def _stream(ctx):
    cell = ctx["cell"]
    return T.request_stream(cell["traffic"], ctx["seed"],
                            cell["config"]["vocab_size"])


def run(ctx):
    serve = spec._module("runners", "serve")
    serve._build_engine = _build_engine
    serve._reference_gaps = _reference_gaps
    closed = serve._closed_loop
    shared = max((c.get("shared_prefix", 0)
                  for c in ctx["cell"]["traffic"]["classes"]), default=0)
    _LIVE["shared"] = shared

    def contexts_first(clients, *a, **k):
        _send_contexts(clients, ctx, shared)
        return closed(clients, *a, **k)

    serve._closed_loop = contexts_first
    obs = serve.run(ctx)
    # which context each request carried: the stream gives the same
    # requests under the same indices again
    top = max((r["index"] for r in obs["records"]), default=-1)
    of = {r["index"]: r["prefix_id"]
          for r in itertools.islice(_stream(ctx), top + 1)}
    for r in obs["records"]:
        # (a negative index: a context sent alone, in set-up)
        r["context"] = of[r["index"]] if r["index"] >= 0 else -1 - r["index"]
        r["shared"] = shared
    return obs


def _send_contexts(clients, ctx, shared):
    """Each of the traffic's shared contexts once, alone and in order, its
    answer cut to 2 tokens: the engine prefills it and its pages stay in
    the prefix store."""
    n = int(ctx["cell"]["traffic"].get("n_prefixes", 0))
    if not n or not shared:
        return
    seen = {}
    for r in _stream(ctx):
        if r["prefix_id"] >= 0 and r["prefix_id"] not in seen:
            seen[r["prefix_id"]] = r["prompt_ids"][:shared]
            if len(seen) == n:
                break
    t0 = time.perf_counter()
    cli = clients.connect()
    for k in sorted(seen):
        clients.send(cli, {"index": -1 - k, "cls": "context",
                           "prompt_ids": seen[k], "answer": 2})
    cli.close()
    print(json.dumps({"note": "contexts_sent", "contexts": n,
                      "tokens_each": shared,
                      "seconds": time.perf_counter() - t0}), flush=True)


def model_config(cfg):
    """The program's configuration from the benchmark's file. A program
    without this family fails here, at once."""
    from paddle_tpu.models.kimi_k2 import KimiK2Config
    lo = cfg.get("experts_first", 0)
    ys = cfg["rope_scaling"]
    return KimiK2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
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
        rope_factor=float(ys["factor"]),
        rope_beta_fast=float(ys["beta_fast"]),
        rope_beta_slow=float(ys["beta_slow"]),
        rope_original_max=int(ys["original_max_position_embeddings"]),
        rope_mscale_all_dim=float(ys["mscale_all_dim"]),
        rms_norm_eps=cfg["rms_norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"])


def _build_engine(cfg, seed):
    """Weights in the served type, the program's model over them, and the
    engine."""
    from paddle_tpu.models.kimi_k2 import KimiK2ForCausalLM
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    from harness import kimi_weights
    from reference.kimi_k2 import param_count
    sv = cfg["serve"]
    mcfg = model_config(cfg)
    model = KimiK2ForCausalLM(
        mcfg, kimi_weights.make(cfg, seed, _giga._dtype(cfg)))
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
        k: _giga._gauge(f"engine.{k}") for k in (
            "cache_bytes.paged", "cache_bytes.paged.latent",
            "cache_bytes.state")}}), flush=True)
    return model, eng, None


def _counts_line(eng):
    """What the run routed and attended, as the program counted it, and
    where the prefix store stands: on the ``state`` line, after the
    window."""
    from paddle_tpu.models.kimi_k2 import expert_totals
    from paddle_tpu.observability import metrics
    c = metrics.snapshot()["counters"]
    print(json.dumps({
        "note": "state", "routing": {
            "assignments": c.get("engine.moe.assignments", 0),
            "assignments_held": c.get("engine.moe.assignments_held", 0),
            "per_held_expert": expert_totals(eng.cfg.experts_held)},
        "after": {k: c.get(f"engine.{k}", 0) for k in (
            "latent.pairs.decode", "latent.pairs.prefill",
            "moe.experts_hit.decode", "moe.experts_hit.prefill",
            "prefix_hit", "prefix_miss", "prefix_pages_reused",
            "prefix_evictions")},
        "prefix_pages": _giga._gauge("engine.prefix_pages"),
        "prefix_store_bytes": _giga._gauge("engine.prefix_store_bytes"),
        "prefix_pages_idle": len(eng._prefix_idle)}), flush=True)


def _free_program():
    """Free the program's device buffers before the reference makes its
    own weights (``serve_hybrid.py::_free_program`` says why)."""
    import jax
    eng = _LIVE.pop("engine", None)
    if eng is None:
        return
    _counts_line(eng)
    for a in jax.tree_util.tree_leaves(
            (eng._params, eng._kc, eng._vc, eng._tok_dev)):
        if isinstance(a, jax.Array) and not a.is_deleted():
            a.delete()
    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({"note": "program_freed",
                      "bytes_in_use": stats.get("bytes_in_use")}), flush=True)


def _reference_gaps(cfg, seed, sample, control=None):
    """As ``serve_giga._reference_gaps``: the plain reference once over
    each sampled prompt with its served tokens; the widest gap by which a
    served token's logit lies below the reference's best at that position,
    as a share of the largest |logit| compared. Logits are made ``BLOCK``
    rows at a time, of the positions compared alone (the answer's): the
    context's 24,576 rows are walked by the stack and never by the head."""
    import jax
    import jax.numpy as jnp
    from harness import kimi_weights
    from reference import kimi_k2 as ref
    _free_program()
    w = kimi_weights.make(cfg, seed, _giga._dtype(cfg))
    s = ref.sizes(cfg)
    hide = _LIVE.get("shared") or None
    gaps = spec._module("runners", "serve_hybrid")._block_gaps()
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
            hid_c = ref.hidden(w, ids, cfg, control, hide=hide) \
                if control else None
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
            del hid, hid_c
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

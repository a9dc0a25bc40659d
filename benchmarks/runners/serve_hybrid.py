"""Runner for ``"kind": "serve_hybrid"`` traffic: a model of the
``phi4flash`` family (Mamba, window, full and cross attention, gated
memory units) behind the same ``InferenceServer`` + ``DecodeEngine`` and
the same load generator as ``runners/serve.py``.

Everything that drives, times and checks a serving run is ``serve.run``;
only what is the model's differs: how the engine is built (seeded weights
in the served type, leaf by leaf: ``harness/hybrid_weights.py``) and which
plain reference decides ``correct`` (``reference/phi4flash.py``). ``run``
finds both as globals of its own module, and ``spec._module`` makes a fresh
module object on every call, so this runner binds its two functions in its
own copy of ``serve`` and calls that.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness import spec  # noqa: E402

BLOCK = 256          # rows of logits made at a time: [256, V] f32 is 205 MB
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


def _build_engine(cfg, seed):
    """Weights in the served type, the program's model over them, and the
    engine. A program without this family fails here, at once."""
    from paddle_tpu.models.phi4flash import (Phi4FlashConfig,
                                             Phi4FlashForCausalLM)
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    from harness import hybrid_weights
    sv, a = cfg["serve"], cfg["assumed"]
    mcfg = Phi4FlashConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        intermediate_size=cfg["intermediate_size"],
        sliding_window=cfg["sliding_window"],
        layer_norm_eps=cfg["layer_norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"],
        mamba_expand=a["mamba_expand"], mamba_d_state=a["mamba_d_state"],
        mamba_d_conv=a["mamba_d_conv"], mamba_dt_rank=a["mamba_dt_rank"],
        ssm_state_dtype={"f32": "float32", "bf16": "bfloat16"}[
            sv["ssm_state"]])
    model = Phi4FlashForCausalLM(
        mcfg, hybrid_weights.make(cfg, seed, _dtype(cfg)))
    eng = DecodeEngine(model, EngineConfig(
        page_size=sv["page_size"], max_slots=sv["max_slots"],
        max_seq_len=sv["max_seq_len"], num_pages=sv["num_pages"],
        prefill_chunk_tokens=sv["prefill_chunk_tokens"],
        prefix_cache=sv["prefix_cache"], inflight=sv["inflight"]))
    _LIVE["engine"] = eng
    print(json.dumps({"note": "state", "family": eng._fam.name, **{
        k: _gauge(f"engine.{k}") for k in (
            "cache_bytes.paged", "cache_bytes.window", "cache_bytes.state",
            "state_bytes_per_slot")}}), flush=True)
    return model, eng, None


def _gauge(name):
    from paddle_tpu.observability import metrics
    return metrics.gauge(name).value


def _block_gaps():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gaps(lg, toks, lc=None):
        """Per row: how far the served token's logit lies below the best;
        the largest |logit|; and the same gap for the token that the
        control's logits ``lc`` put first."""
        best = lg.max(-1)
        served = best - jnp.take_along_axis(lg, toks[:, None], axis=1)[:, 0]
        out = (served, jnp.abs(lg).max(-1))
        if lc is not None:
            first = lc.argmax(-1)
            out += (best - jnp.take_along_axis(lg, first[:, None],
                                               axis=1)[:, 0],)
        return out
    return gaps


def _free_program():
    """Free the program's device buffers before the reference makes its
    own weights. ``serve.run`` drops its names for the model and the
    engine, but its frame still holds the engine through the ``submit`` it
    wrapped; at GPT-2's size nobody notices, at 10 GB the reference's
    weights do not fit beside it. So the buffers are deleted outright:
    parameters, pools, rings, state, the token chain."""
    import jax
    eng = _LIVE.pop("engine", None)
    if eng is None:
        return
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
    from harness import hybrid_weights
    from reference import phi4flash as ref
    _free_program()
    w = hybrid_weights.make(cfg, seed, _dtype(cfg))
    gaps = _block_gaps()
    worst, top, where, n_tok, ctl_worst = 0.0, 0.0, "", 0, 0.0
    took = []                       # seconds a request: the first compiles

    def rows_of(hid, lo, hi):
        for i in range(lo, hi, BLOCK):
            yield i, min(BLOCK, hi - i), jax.lax.dynamic_slice_in_dim(
                hid, i, BLOCK, axis=0)

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
            for i, m, h in rows_of(hid, n0 - 1, n - 1):
                lg = ref.head(h, w["ln_f.w"], w["ln_f.b"], w["embed"], "f32")
                want = jnp.asarray(nxt[i:i + BLOCK])
                if control:
                    lc = ref.head(jax.lax.dynamic_slice_in_dim(
                        hid_c, i, BLOCK, axis=0), w["ln_f.w"], w["ln_f.b"],
                        w["embed"], control)
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

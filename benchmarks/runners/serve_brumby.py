"""Runner for ``"kind": "serve_brumby"`` traffic: a model of the ``brumby``
family (power-retention layers: a matrix state a layer a sequence, no K or
V, rotary positions) behind the same ``InferenceServer`` + ``DecodeEngine``
and the same load generator as ``runners/serve.py``.

Everything that drives, times and checks a serving run is ``serve.run``;
only what is the model's differs: how the engine is built (seeded weights
in the served type: ``harness/brumby_weights.py``; no page pool: the
family's ``kv_layers`` is 0 and ``num_pages`` is not handed on) and which
plain reference decides ``correct`` (``reference/brumby.py``: the attention
form, which never forms a state). As ``runners/serve_hybrid.py`` does, this
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

BLOCK = 256          # rows of logits made at a time: [256, V] f32 is 156 MB
_LIVE = {}           # the engine, so that its device buffers can be freed


# a program counter and the span that is opened where it counts
TRACED_CALLS = {"engine.steps": "engine.dispatch",
                "engine.prefill_launches": "engine.prefill_launch"}


def run(ctx):
    serve = spec._module("runners", "serve")
    serve._build_engine = _build_engine
    serve._reference_gaps = _reference_gaps
    obs = serve.run(ctx)
    obs["traced_calls"] = _traced_calls(ctx["tracer"])
    return obs


def _traced_calls(tracer):
    """Decode steps and prefill launches the program began inside the
    TRACED part of the window, from its own spans: what a kernel's device
    time in the trace is divided by (``readers/brumby_roofline.py``). With
    16 callers and prompts of 2-8 chunks the traced 4 s hold 14% prefill in
    one run and 33% in the next, so the whole window's rate would not do.
    None when no trace was taken."""
    from paddle_tpu.observability import metrics
    if tracer.t_start is None or tracer.t_stop is None:
        return None
    return {counter: len(metrics.spans(name=span, since=tracer.t_start,
                                       until=tracer.t_stop))
            for counter, span in TRACED_CALLS.items()}


def _dtype(cfg):
    import jax.numpy as jnp
    return {"bf16": jnp.bfloat16,
            "f32": jnp.float32}[cfg["serve"]["precision"]]


def model_config(cfg):
    """The program's configuration from the benchmark's file. A program
    without this family fails here, at once."""
    from paddle_tpu.models.brumby import BrumbyConfig
    a = cfg["assumed"]
    if a["power"] != 2:
        raise ValueError("the program's retention is of degree 2")
    return BrumbyConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        max_position_embeddings=cfg["max_position_embeddings"],
        retention_eps=a["retention_eps"],
        state_dtype={"f32": "float32", "bf16": "bfloat16"}[
            cfg["serve"]["retention_state"]])


def _build_engine(cfg, seed):
    """Weights in the served type, the program's model over them, and the
    engine."""
    from paddle_tpu.models.brumby import BrumbyForCausalLM
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    from harness import brumby_weights
    sv = cfg["serve"]
    model = BrumbyForCausalLM(
        model_config(cfg), brumby_weights.make(cfg, seed, _dtype(cfg)))
    eng = DecodeEngine(model, EngineConfig(
        page_size=sv["page_size"], max_slots=sv["max_slots"],
        max_seq_len=sv["max_seq_len"],
        prefill_chunk_tokens=sv["prefill_chunk_tokens"],
        prefix_cache=sv["prefix_cache"], inflight=sv["inflight"]))
    _LIVE["engine"] = eng
    print(json.dumps({"note": "state", "family": eng._fam.name, **{
        k: _gauge(f"engine.{k}") for k in (
            "cache_bytes.paged", "cache_bytes.window", "cache_bytes.state",
            "state_bytes_per_slot", "pages_in_use")}}), flush=True)
    return model, eng, None


def _gauge(name):
    from paddle_tpu.observability import metrics
    return metrics.gauge(name).value


def _free_program():
    """Free the program's device buffers before the reference makes its
    own weights (``serve_hybrid.py::_free_program`` says why): parameters,
    the state, the token chain."""
    import jax
    from paddle_tpu.observability import metrics
    eng = _LIVE.pop("engine", None)
    if eng is None:
        return
    c = metrics.snapshot()["counters"]
    print(json.dumps({"note": "state", "after": {
        k: c.get(f"engine.{k}", 0) for k in ("state_resets",
                                             "state_carries")},
        "pages_in_use": _gauge("engine.pages_in_use")}), flush=True)
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
    from harness import brumby_weights
    from reference import brumby as ref
    _free_program()
    w = brumby_weights.make(cfg, seed, _dtype(cfg))
    s = ref.sizes(cfg)
    gaps = spec._module("runners", "serve_hybrid")._block_gaps()
    worst, top, where, n_tok, ctl_worst = 0.0, 0.0, "", 0, 0.0
    took = []                       # seconds a request: the first compiles

    def head(h, precision):
        return ref.head(h, w["norm_f.w"], w["head"], s.eps, precision)

    with jax.enable_x64(False):
        for r in sample:
            t_req = time.perf_counter()
            toks = np.asarray(r["out"], np.int32)
            n0, n = int(r["prompt_len"]), len(toks)
            # every sequence padded to one length (causal: the tail is
            # inert) with room for the last block of rows, so the reference
            # compiles its layer once, for every run: the traffic's longest
            # prompt and answer, not the engine's limit (the attention form
            # is quadratic, and 32,768 costs this family nothing to allow)
            padded = int(cfg["serve"]["reference_pad"])
            if n + BLOCK > padded:
                raise ValueError(f"a sequence of {n} tokens does not fit "
                                 f"the reference's {padded}")
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

"""A kernel's share of its roofline for the ``kimi_k2`` family, from the
reduced device trace: the least time the work its equations need could take
on the chip (the LARGER of its bytes at the published HBM rate and its
operations at the published bf16 peak; both from ``harness/kimi_bytes.py``)
over the device time of the ops under the ``scopes`` the program names
around the kernel's call, in %.

The work is what the program COUNTED over the traced part of the window
((query, key) pairs attended, routed rows and the held experts they hit;
``harness/window.py::traced``) and, for the decode walk's bytes, the
DISTINCT latent rows its live sequences reach in the traced part (a shared
context once: ``kimi_bytes.live_rows``), never the ops that ran: a change
of arm or of form moves the time and leaves the work. A program that names
no such scope or counts no such work gives nothing (no metric, no error).

``unnamed`` lists op families that run inside the call and that the chip's
compiler strips of their name stack, so that no scope finds them: XLA
expands `jax.lax.ragged_dot` into a custom call whose ``op_name`` is
``ragged-dot-none``, whatever scope the call stood under (the grouped arm
of ``kernels/moe.py`` in a decode step: both of its products). Their
seconds are ADDED to the scope's where every such pattern matches a family
(``{sizes}`` from ``kimi_bytes.trace_shapes``); left out, the scope held a
third of the call's time and the share read 109 (my chip run, PR 47). A
kernel that takes their place under the scope leaves them matching nothing,
and the scope's seconds stand alone.
"""
from harness import kimi_bytes, trace
from harness.window import traced, traced_rate as _rate


def _work_per_s(kind, obs, cfg):
    """(bytes, operations) a second of the traced part."""
    if kind == "latent_decode":
        part = traced(obs)
        lv = kimi_bytes.live_rows(obs.get("records") or [], part["t_open"],
                                  part["t_close"])
        nbytes, flops = kimi_bytes.latent_decode_work(
            cfg, _rate(obs, "engine.latent.pairs.decode"),
            lv["distinct_rows"])
        return nbytes * _rate(obs, "engine.steps"), flops
    if kind == "latent_chunk":
        return kimi_bytes.latent_chunk_work(
            cfg, _rate(obs, "engine.latent.pairs.prefill"),
            cfg["serve"]["prefill_chunk_tokens"])
    if kind == "experts":
        hit = _rate(obs, "engine.moe.experts_hit.decode") \
            + _rate(obs, "engine.moe.experts_hit.prefill")
        if hit <= 0:
            return 0.0, 0.0
        return kimi_bytes.experts_work(
            cfg, _rate(obs, "engine.moe.assignments_held"), hit)
    raise ValueError(f"no count for {kind!r}")


def read(obs, work_of, scopes, unnamed=()):
    tr = obs.get("trace")
    note = obs.setdefault("notes", {}).setdefault("kimi_roofline", {})
    cfg = obs["config"]
    mine = note[work_of] = trace.kernel_seconds(tr, scopes)
    if mine["seconds"] is None or obs.get("device_kind") is None:
        return None
    try:
        nbytes, flops = _work_per_s(work_of, obs, cfg)
        shapes = kimi_bytes.trace_shapes(cfg)
    except KeyError:             # a configuration of another family
        return None
    more = trace.kernel_seconds(
        tr, (), [p.format(**shapes) for p in unnamed], each="some")
    mine["unnamed"] = more["matched"]
    if more.get("pattern_s"):
        mine["unnamed_s"] = more["pattern_s"]
        mine["scope_s"] = mine["seconds"] = mine["seconds"] + more["pattern_s"]
    if max(nbytes, flops) <= 0:
        return None
    mine.update(bytes_per_s=nbytes, flops_per_s=flops)
    return trace.roofline_share(mine, obs["device_kind"], nbytes, flops,
                                tr["window_s"])

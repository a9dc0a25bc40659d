"""A kernel's share of its roofline for the ``solar_open2`` family, from
the reduced device trace: the least time the work its equations need could
take on the chip (the LARGER of its bytes at the published HBM rate and its
operations at the published bf16 peak; both from
``harness/solar_bytes.py``) over the device time of the ops under the
``scopes`` the program names around the kernel's call, in %.

The work is what the program COUNTED over the traced part of the window
(live tokens through a linear layer, (query, key) pairs attended, routed
rows and the held experts they hit; ``harness/window.py::traced``), never
the ops that ran: a change of arm or of form moves the time and leaves the
work. A program that names no such scope or counts no such work gives
nothing (no metric, no error).

``unnamed`` lists op families that run inside the call and that the chip's
compiler strips of their name stack (``readers/kimi_roofline.py`` says
which and why): their seconds are ADDED to the scope's where a pattern
matches a family (``{sizes}`` from ``solar_bytes.trace_shapes``).
"""
from harness import solar_bytes, trace
from harness.window import traced_rate as _rate


def _work_per_s(kind, obs, cfg):
    """(bytes, operations) a second of the traced part."""
    if kind == "kda_update":
        return solar_bytes.kda_update_work(
            cfg, _rate(obs, "engine.kda.tokens.decode"))
    if kind == "kda_chunk":
        return solar_bytes.kda_chunk_work(
            cfg, _rate(obs, "engine.kda.tokens.prefill"),
            cfg["serve"]["prefill_chunk_tokens"])
    if kind == "gqa_walk":
        return solar_bytes.gqa_walk_work(
            cfg, _rate(obs, "engine.gqa.pairs.decode"))
    if kind == "experts":
        hit = _rate(obs, "engine.moe.experts_hit.decode") \
            + _rate(obs, "engine.moe.experts_hit.prefill")
        if hit <= 0:
            return 0.0, 0.0
        return solar_bytes.experts_work(
            cfg, _rate(obs, "engine.moe.assignments_held"), hit)
    raise ValueError(f"no count for {kind!r}")


def read(obs, work_of, scopes, unnamed=()):
    tr = obs.get("trace")
    note = obs.setdefault("notes", {}).setdefault("solar_roofline", {})
    cfg = obs["config"]
    mine = note[work_of] = trace.kernel_seconds(tr, scopes)
    if mine["seconds"] is None or obs.get("device_kind") is None:
        return None
    try:
        nbytes, flops = _work_per_s(work_of, obs, cfg)
        shapes = solar_bytes.trace_shapes(cfg)
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

"""A kernel's share of its roofline for the ``dots3_note`` family, from the
reduced device trace: the least time the work its equations need could take
on the chip (the LARGER of its bytes at the published HBM rate and its
operations at the published bf16 peak; both from
``harness/dots3_bytes.py``) over the device time of ITS op families, in %.

The work is counted from what the program COUNTED over the measured window
(keys scored and attended, routed rows and the held experts they hit,
steps and prefill launches: a rate that the traced seconds share), never
from the ops that ran nor from an expectation: a change of arm or of form
moves the time and leaves the work, so it cannot read as an impossible
gain. A program that does not count a kind of work gives nothing for it.

The families are found among ``obs["trace"]["families"]`` (opcode and
result shape) by ``patterns``, regular expressions whose ``{sizes}`` are
filled in from the run's configuration (``dots3_bytes.trace_shapes``). A
pattern may match several families (a kernel's ops differ between the
decode step and a chunk, and XLA splits a product from its epilogue); their
times add. A pattern that matches none gives nothing (a program without
this kernel, as the parent of the PR that brought it), and the ``readers``
line says which.
"""
import re

from harness import device, dots3_bytes
from harness.window import counter_delta


def _rate(obs, name):
    return counter_delta(obs["counters_open"], obs["counters_close"],
                         name) / (obs["t_close"] - obs["t_open"])


def _work_per_s(kind, obs, cfg, lv):
    """(bytes, operations) a second of the window."""
    if kind == "latent_attention":
        pairs = _rate(obs, "engine.sparse.keys_attended")
        decode = _rate(obs, "engine.sparse.keys_attended.decode")
        return dots3_bytes.latent_attention_work(
            cfg, decode, pairs - decode,
            cfg["serve"]["prefill_chunk_tokens"])
    if kind == "index_select":
        return dots3_bytes.index_work(
            cfg, _rate(obs, "engine.sparse.keys_scored"))
    if kind == "window_attention":
        steps, launches = _rate(obs, "engine.steps"), \
            _rate(obs, "engine.prefill_launches")
        b0, f0 = dots3_bytes.window_decode_work(cfg, lv["sequences"])
        b1, f1 = dots3_bytes.window_prefill_work(
            cfg, cfg["serve"]["prefill_chunk_tokens"])
        return b0 * steps + b1 * launches, f0 * steps + f1 * launches
    if kind == "experts":
        hit = _rate(obs, "engine.moe.experts_hit.decode") \
            + _rate(obs, "engine.moe.experts_hit.prefill")
        if hit <= 0:             # a program that does not count them
            return 0.0, 0.0
        return dots3_bytes.experts_work(
            cfg, _rate(obs, "engine.moe.assignments_held"), hit)
    raise ValueError(f"no count for {kind!r}")


def read(obs, patterns, work_of):
    tr = obs.get("trace")
    note = obs.setdefault("notes", {}).setdefault("dots3_roofline", {})
    if not tr or not tr.get("families") or tr["window_s"] <= 0:
        return None
    cfg = obs["config"]
    try:
        shapes = dots3_bytes.trace_shapes(cfg)
    except KeyError:             # a configuration of another family
        return None
    patterns = [p.format(**shapes) for p in patterns]
    hits = [[(f, s) for f, s in tr["families"] if re.search(p, f)]
            for p in patterns]
    mine = note[work_of] = {"patterns": patterns,
                            "matched": [[f for f, _ in h] for h in hits]}
    seconds = sum(s for h in hits for _, s in h)
    if any(not h for h in hits) or seconds <= 0 \
            or obs.get("device_kind") is None:
        return None
    kind = obs["device_kind"]
    lv = dots3_bytes.live(obs.get("records") or [], obs["t_open"],
                          obs["t_close"], cfg)
    nbytes, flops = _work_per_s(work_of, obs, cfg, lv)
    if max(nbytes, flops) <= 0:
        return None
    mem = nbytes / device.peak(kind, "hbm_bytes_per_s")
    mxu = flops / device.peak(kind, "bf16_flops")
    busy_share = seconds / tr["window_s"]
    mine.update(bytes_per_s=nbytes, flops_per_s=flops,
                bound="memory" if mem >= mxu else "compute",
                family_s_per_s=busy_share)
    return 100.0 * max(mem, mxu) / busy_share

"""A kernel's share of its roofline for the ``gigachat3_5`` family, from
the reduced device trace: the least time the work its equations need could
take on the chip (the LARGER of its bytes at the published HBM rate and its
operations at the published bf16 peak; both from
``harness/giga_bytes.py``) over the device time of ITS op families, in %.

The work is counted from what the program COUNTED over the measured window
(live tokens through a linear layer, (query, key) pairs attended, routed
rows and the held experts they hit: rates that the traced seconds share),
never from the ops that ran nor from an expectation: a change of arm or of
form moves the time and leaves the work. A program that does not count a
kind of work gives nothing for it (the parent of the PR that brought the
family: no such counter, no metric, no error).

The families are found among ``obs["trace"]["families"]`` (opcode and
result shape) by ``patterns``, regular expressions whose ``{sizes}`` are
filled in from the run's configuration (``giga_bytes.trace_shapes``). The
TIME is the added device time of every family that ANY pattern matches: an
op with two arms lists the patterns of both, the arm that did not run
matches nothing, and the metric stays on the line whichever ran. Only when
no pattern matches anything is there nothing to read; the ``readers`` line
says what each pattern matched.
"""
import re

from harness import device, giga_bytes
from harness.window import counter_delta


def _rate(obs, name):
    return counter_delta(obs["counters_open"], obs["counters_close"],
                         name) / (obs["t_close"] - obs["t_open"])


def _work_per_s(kind, obs, cfg):
    """(bytes, operations) a second of the window."""
    chunk = cfg["serve"]["prefill_chunk_tokens"]
    if kind == "deltanet_update":
        return giga_bytes.deltanet_update_work(
            cfg, _rate(obs, "engine.deltanet.tokens.decode"))
    if kind == "deltanet_chunk":
        return giga_bytes.deltanet_chunk_work(
            cfg, _rate(obs, "engine.deltanet.tokens.prefill"), chunk,
            giga_bytes.trace_shapes(cfg)["sub"])
    if kind == "latent_attention":
        return giga_bytes.latent_attention_work(
            cfg, _rate(obs, "engine.latent.pairs.decode"),
            _rate(obs, "engine.latent.pairs.prefill"), chunk)
    if kind == "experts_first_product":
        hit = _rate(obs, "engine.moe.experts_hit.decode") \
            + _rate(obs, "engine.moe.experts_hit.prefill")
        if hit <= 0:
            return 0.0, 0.0
        return giga_bytes.experts_first_product_work(
            cfg, _rate(obs, "engine.moe.assignments_held"), hit)
    raise ValueError(f"no count for {kind!r}")


def read(obs, patterns, work_of):
    tr = obs.get("trace")
    note = obs.setdefault("notes", {}).setdefault("giga_roofline", {})
    if not tr or not tr.get("families") or tr["window_s"] <= 0:
        return None
    cfg = obs["config"]
    try:
        shapes = giga_bytes.trace_shapes(cfg)
    except KeyError:             # a configuration of another family
        return None
    patterns = [p.format(**shapes) for p in patterns]
    hits = {}
    for p in patterns:
        for f, s in tr["families"]:
            if re.search(p, f):
                hits[f] = s
    mine = note[work_of] = {
        "patterns": patterns,
        "matched": {f: round(s, 6) for f, s in sorted(hits.items())}}
    seconds = sum(hits.values())
    if seconds <= 0 or obs.get("device_kind") is None:
        return None
    kind = obs["device_kind"]
    nbytes, flops = _work_per_s(work_of, obs, cfg)
    if max(nbytes, flops) <= 0:
        return None
    mem = nbytes / device.peak(kind, "hbm_bytes_per_s")
    mxu = flops / device.peak(kind, "bf16_flops")
    busy_share = seconds / tr["window_s"]
    mine.update(bytes_per_s=nbytes, flops_per_s=flops,
                bound="memory" if mem >= mxu else "compute",
                family_s_per_s=busy_share)
    return 100.0 * max(mem, mxu) / busy_share

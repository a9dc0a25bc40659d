"""A kernel's share of its roofline, from the reduced device trace: the
least time its calls could take on the chip (the LARGER of the bytes they
must move at the published HBM rate and the operations they must make at
the published bf16 peak; both from ``harness/granite_bytes.py``, per call)
over the device time of ITS op families, in %.

The families are found among ``obs["trace"]["families"]`` (opcode and
result shape: the reduced trace keeps no name of an op) by ``patterns``,
regular expressions whose ``{sizes}`` are filled in from the run's
configuration (``granite_bytes.trace_shapes``: slots, held experts, chunk
and widths are no literals here) and of which EACH has to match exactly
one family (a kernel that XLA splits into two fusions names
both, and their times add): none (a program without this kernel, as the
parent of the PR that brought it) or several give nothing, and the
``readers`` line says which. Calls are not counted in the trace; they are
the growth of ``per`` (a counter of the program: steps or prefill
launches) over the measured window, a rate that the traced seconds share.
"""
import re

from harness import device, granite_bytes
from harness.window import counter_delta


def _per_call(kind, cfg, lv):
    chunk = cfg["serve"]["prefill_chunk_tokens"]
    slots = cfg["serve"]["max_slots"]
    if kind == "moe_decode_first":
        return (granite_bytes.moe_first_bytes(cfg),
                granite_bytes.moe_first_flops(cfg, slots))
    if kind == "ssm2_update":
        return granite_bytes.ssm2_update_bytes(cfg, lv["sequences"]), 0.0
    if kind == "ssm2_scan":
        return (granite_bytes.ssm2_scan_bytes(cfg, chunk),
                granite_bytes.ssm2_scan_flops(cfg, chunk,
                                              cfg["mamba_chunk_size"]))
    raise ValueError(f"no count for {kind!r}")


def read(obs, patterns, work_of, per):
    tr = obs.get("trace")
    note = obs.setdefault("notes", {}).setdefault("granite_roofline", {})
    if not tr or not tr.get("families") or tr["window_s"] <= 0:
        return None
    try:
        shapes = granite_bytes.trace_shapes(obs["config"])
    except KeyError:             # a configuration of another family
        return None
    patterns = [p.format(**shapes) for p in patterns]
    hits = [[(f, s) for f, s in tr["families"] if re.search(p, f)]
            for p in patterns]
    mine = note[work_of] = {"patterns": patterns,
                            "matched": [[f for f, _ in h] for h in hits]}
    if any(len(h) != 1 or h[0][1] <= 0 for h in hits) \
            or obs.get("device_kind") is None:
        return None
    cfg, kind = obs["config"], obs["device_kind"]
    lv = granite_bytes.live(obs.get("records") or [], obs["t_open"],
                            obs["t_close"])
    nbytes, flops = _per_call(work_of, cfg, lv)
    mem_s = nbytes / device.peak(kind, "hbm_bytes_per_s")
    mxu_s = flops / device.peak(kind, "bf16_flops")
    calls_per_s = counter_delta(obs["counters_open"], obs["counters_close"],
                                per) / (obs["t_close"] - obs["t_open"])
    busy_share = sum(h[0][1] for h in hits) / tr["window_s"]
    mine.update(bytes_per_call=nbytes, flops_per_call=flops,
                bound="memory" if mem_s >= mxu_s else "compute",
                calls_per_s=calls_per_s, family_s_per_s=busy_share)
    return 100.0 * max(mem_s, mxu_s) * calls_per_s / busy_share

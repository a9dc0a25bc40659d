"""A retention kernel's share of its roofline, from the reduced device
trace: the least time its calls could take on the chip (the LARGER of the
bytes they must move at the published HBM rate and the operations they must
make at the published bf16 peak; both from ``harness/brumby_bytes.py``, per
call) over the device time of ITS op families, in %.

As ``readers/granite_roofline.py``: the families are found among
``obs["trace"]["families"]`` by ``patterns`` whose ``{sizes}`` are filled
in from the run's configuration (``brumby_bytes.trace_shapes``), EACH of
which has to match exactly one family; none (a program without this
kernel, as the parent of the PR that brought it) or several give nothing,
and the ``readers`` line says which. Calls are those of ``per`` (a counter
of the program) that began inside the TRACED part of the window, which the
runner counts from the program's spans (``runners/serve_brumby.py::
_traced_calls``): the traced 4 s of this cell hold one run's 14% of prefill
and the next run's 33%, so a rate over the whole window would read a share
a third off either way.
"""
import re

from harness import brumby_bytes, device


def _per_call(kind, cfg, lv):
    chunk = cfg["serve"]["prefill_chunk_tokens"]
    if kind == "retention_update":
        return brumby_bytes.retention_update_bytes(cfg, lv["sequences"]), 0.0
    if kind == "retention_chunk":
        return (brumby_bytes.retention_chunk_bytes(cfg, chunk),
                brumby_bytes.retention_chunk_flops(cfg, chunk))
    raise ValueError(f"no count for {kind!r}")


def read(obs, patterns, work_of, per):
    tr = obs.get("trace")
    note = obs.setdefault("notes", {}).setdefault("brumby_roofline", {})
    calls = (obs.get("traced_calls") or {}).get(per)
    if not tr or not tr.get("families") or tr["window_s"] <= 0 or not calls:
        return None
    try:
        shapes = brumby_bytes.trace_shapes(obs["config"])
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
    lv = brumby_bytes.live(obs.get("records") or [], obs["t_open"],
                           obs["t_close"])
    nbytes, flops = _per_call(work_of, cfg, lv)
    mem_s = nbytes / device.peak(kind, "hbm_bytes_per_s")
    mxu_s = flops / device.peak(kind, "bf16_flops")
    family_s = sum(h[0][1] for h in hits)
    mine.update(bytes_per_call=nbytes, flops_per_call=flops,
                bound="memory" if mem_s >= mxu_s else "compute",
                traced_calls=calls, family_ms_per_call=1e3 * family_s / calls)
    return 100.0 * max(mem_s, mxu_s) * calls / family_s

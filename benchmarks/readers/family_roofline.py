"""A kernel's share of its memory roofline, from the reduced device trace:
the bytes its calls must move (a function of ``harness/hybrid_bytes.py``,
per call) at the chip's published HBM rate, over the device time of ITS op
family, in %.

The family is found among ``obs["trace"]["families"]`` (opcode and result
shape) by ``pattern``, a regular expression that has to match exactly one
family: none (a program without this kernel, as the parent of the PR that
brought it) or several (the pattern no longer names the kernel) give
nothing, and the ``readers`` line says which. Calls are not counted in the
trace; they are the growth of ``per`` (a counter of the program: steps or
prefill launches) over the measured window, a rate that the traced seconds
share, so the share is ``bytes_per_call * calls_per_s / (hbm_bytes_per_s *
family_seconds_per_traced_second)``.
"""
import re

from harness import device, hybrid_bytes
from harness.window import counter_delta


def read(obs, pattern, bytes_of, per):
    tr = obs.get("trace")
    note = obs.setdefault("notes", {}).setdefault("family_roofline", {})
    if not tr or not tr.get("families") or tr["window_s"] <= 0:
        return None
    hits = [(f, s) for f, s in tr["families"] if re.search(pattern, f)]
    mine = note[bytes_of] = {"pattern": pattern,
                             "matched": [f for f, _ in hits]}
    if len(hits) != 1 or hits[0][1] <= 0 or obs.get("device_kind") is None:
        return None
    cfg = obs["config"]
    lv = hybrid_bytes.live(obs.get("records") or [], obs["t_open"],
                           obs["t_close"], cfg["sliding_window"])
    per_call = {
        "ssm_update": lambda: hybrid_bytes.ssm_update_bytes(
            cfg, lv["sequences"]),
        "ssm_scan": lambda: hybrid_bytes.ssm_scan_bytes(
            cfg, cfg["serve"]["prefill_chunk_tokens"]),
        "shared_k": lambda: hybrid_bytes.shared_k_bytes(cfg, lv["tokens"]),
        "window_k": lambda: hybrid_bytes.window_k_bytes(
            cfg, lv["window_tokens"]),
    }[bytes_of]()
    calls_per_s = counter_delta(obs["counters_open"], obs["counters_close"],
                                per) / (obs["t_close"] - obs["t_open"])
    busy_share = hits[0][1] / tr["window_s"]
    floor_share = per_call * calls_per_s / device.peak(
        obs["device_kind"], "hbm_bytes_per_s")
    mine.update(bytes_per_call=per_call, calls_per_s=calls_per_s,
                family_s_per_s=busy_share)
    return 100.0 * floor_share / busy_share

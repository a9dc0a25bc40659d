"""How far a decode step is from the chip's memory: the time the bytes a
step must read would take at the published HBM rate, over the median
``engine.step`` span, in %.

Bytes are computed from sizes, not measured. A step must read every weight
once (``harness/flops.py::total_params`` with the table's padded rows; the
tied table is read once, for the head) and the K and V of every token that
is live in a slot. Live tokens come from the requests' own marks: a request
counts ``prompt_len + n_tokens / 2`` (its mean length while it decodes) for
the part of ``[t_first_token, t_done]`` that lies inside the window, over
the window's length. Not from the pool's pages in use: a request holds the
pages of its whole prompt and answer from admission, so pages count tokens
not yet written. Nothing the program could do makes the share pass 100
short of reading less than it must. What it was computed from is left under
``obs["notes"]`` for the run to print.
"""
from harness import device, flops
from harness.window import percentile

BYTES = {"bf16": 2, "f32": 4}


def _width(cfg: dict) -> int:
    return BYTES[cfg["serve"]["precision"]]


def weight_bytes(cfg: dict) -> int:
    return flops.total_params(cfg, cfg["assumed"]["vocab_rows"]) * _width(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    return 2 * cfg["n_layer"] * cfg["n_embd"] * _width(cfg)


def live_tokens(records, t_open: float, t_close: float) -> float:
    """Mean over the window of the tokens whose K and V a decode step
    reads."""
    total = 0.0
    for r in records:
        t0, t1, n = r.get("t_first_token"), r.get("t_done"), r.get("n_tokens")
        if t0 is None or t1 is None or not n:
            continue
        inside = min(t1, t_close) - max(t0, t_open)
        if inside > 0:
            total += (r["prompt_len"] + n / 2.0) * inside
    return total / (t_close - t_open)


def read(obs):
    steps = obs.get("engine_steps") or []
    live = live_tokens(obs.get("records") or [], obs["t_open"], obs["t_close"])
    if not steps or not live:
        return None
    cfg = obs["config"]
    w, k = weight_bytes(cfg), kv_bytes_per_token(cfg) * live
    step_s = percentile(steps, 50)
    note = {"W_bytes": w, "K_bytes": k, "live_tokens": live,
            "step_p50_ms": 1e3 * step_s}
    obs.setdefault("notes", {})["decode_roofline"] = note
    if obs.get("device_kind") is None:
        return None
    floor_s = (w + k) / device.peak(obs["device_kind"], "hbm_bytes_per_s")
    note["floor_ms"] = 1e3 * floor_s
    return 100.0 * floor_s / step_s

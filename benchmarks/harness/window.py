"""Window-edge arithmetic: which samples a window owns, and percentiles.

A window is [t_open, t_close) on one process's ``time.perf_counter``.
Counters are read at its two edges and differenced. A latency sample
belongs to the window in which its closing event fell: a time to first
token where the first token fell, a time per output token where the request
finished. Requests still in flight at the close are drained afterwards and
counted in ``attempted`` only.
"""
from __future__ import annotations

import math


def percentile(values, q: float):
    """Linear-interpolated percentile (q in [0, 100]) of a non-empty list;
    None for an empty one."""
    vals = sorted(values)
    if not vals:
        return None
    if len(vals) == 1:
        return float(vals[0])
    pos = (len(vals) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(vals) - 1)
    return float(vals[lo] + (vals[hi] - vals[lo]) * (pos - lo))


def inside(t, t_open: float, t_close: float) -> bool:
    return t is not None and t_open <= t < t_close


def ttft_samples(records, t_open, t_close, start_key="t_due"):
    """Seconds from ``start_key`` to the first token, over requests whose
    first token fell inside the window."""
    return [r["t_first_token"] - r[start_key] for r in records
            if inside(r.get("t_first_token"), t_open, t_close)
            and r.get(start_key) is not None]


def tpot_samples(records, t_open, t_close):
    """(t_done - t_first_token) / (n_tokens - 1), over requests that
    finished inside the window without an error and made two tokens or
    more."""
    return [(r["t_done"] - r["t_first_token"]) / (r["n_tokens"] - 1)
            for r in records
            if inside(r.get("t_done"), t_open, t_close)
            and not r.get("error") and r.get("n_tokens", 0) > 1
            and r.get("t_first_token") is not None]


def latency_samples(records, t_open, t_close, start_key="t_due"):
    """Seconds from ``start_key`` to the whole reply at the client, over
    requests whose reply arrived inside the window without an error."""
    return [r["t_recv"] - r[start_key] for r in records
            if inside(r.get("t_recv"), t_open, t_close)
            and not r.get("error") and r.get(start_key) is not None]


def counter_delta(at_open: dict, at_close: dict, name: str) -> float:
    return float(at_close.get(name, 0)) - float(at_open.get(name, 0))


def rate(at_open: dict, at_close: dict, name: str, t_open, t_close) -> float:
    return counter_delta(at_open, at_close, name) / (t_close - t_open)

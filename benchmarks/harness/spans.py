"""The program's own spans, as the span readers see them.

``paddle_tpu.observability.metrics.spans(...)`` is the program's public read
of its span ring: one record a host range (``name``, ``t0`` and ``dur`` in
``time.perf_counter`` seconds, ``args``, its ``id`` and its ``parent``'s).
The benchmark runs in the program's process and on its clock, so a reader
cuts the ring to the window (``t_open`` to ``t_close``) or to set-up
(everything before ``t_open``) and reduces what is left.

A program from before ``spans()`` has nothing to read: ``fetch`` gives None
and the metric is left out. So does an interval that lost spans: the ring
is bounded, ``metrics.spans_dropped`` counts what it evicted, and a sum over
a ring with a hole in it is not a smaller sum but no number. A reduction
over no spans at all is the reader's business (a sum is 0.0).
"""
from __future__ import annotations


def fetch(obs, before_window=False, name=None, prefix=None):
    """Spans that began inside the window (or, ``before_window``, before
    it opened), oldest first; None where there is nothing sound to read."""
    from paddle_tpu.observability import metrics
    read = getattr(metrics, "spans", None)
    if read is None:
        return None
    since, until = (None, obs["t_open"]) if before_window \
        else (obs["t_open"], obs["t_close"])
    if _lost(metrics, read, since):
        return None
    if isinstance(prefix, list):
        prefix = tuple(prefix)
    return read(name=name, prefix=prefix, since=since, until=until)


def _lost(metrics, read, since) -> bool:
    """Whether the ring evicted a span that may have begun at or after
    ``since``. Spans are evicted in the order they ended; one that ended
    before ``since`` began before it."""
    if not metrics.spans_dropped.value:
        return False
    if since is None:
        return True
    oldest = read()[:1]
    return not oldest or oldest[0].t0 + oldest[0].dur >= since


def matching(spans, where=None, positive=None):
    """Those whose ``args`` equal ``where`` key by key and hold a number
    above zero under ``positive``."""
    out = []
    for s in spans:
        a = s.args or {}
        if where and any(a.get(k) != v for k, v in where.items()):
            continue
        if positive and not (a.get(positive) or 0) > 0:
            continue
        out.append(s)
    return out


def clipped(s, lo, hi) -> float:
    """Seconds of span ``s`` that lie inside [lo, hi]; None is open."""
    a = s.t0 if lo is None else max(s.t0, lo)
    b = s.t0 + s.dur if hi is None else min(s.t0 + s.dur, hi)
    return max(0.0, b - a)


def self_time(parent, children) -> float:
    """``parent``'s duration less the part of its interval that
    ``children`` (its direct child spans) cover together."""
    end = parent.t0 + parent.dur
    covered, reach = 0.0, parent.t0
    for c in sorted(children, key=lambda c: c.t0):
        a, b = max(c.t0, reach), min(c.t0 + c.dur, end)
        if b > a:
            covered += b - a
            reach = b
    return parent.dur - covered


def weighted_percentile(values, weights, q: float):
    """The smallest value at or below which ``q`` percent of the weight
    lies; None for no weight."""
    total = float(sum(weights))
    if not values or total <= 0:
        return None
    need, run = total * q / 100.0, 0.0
    for v, w in sorted(zip(values, weights)):
        run += w
        if run >= need:
            return float(v)
    return float(max(values))

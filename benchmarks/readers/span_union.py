"""The length of the UNION of the intervals of the program's spans named
``name`` or starting with one of ``prefix``, over all threads, cut to the
window; ``before_window`` reads set-up in its place: the spans that began
before the window opened, each cut where it opens (a request of the ramp
that is still in flight then counts up to there). ``where`` keeps the spans
whose args equal it key by key; neither ``name`` nor ``prefix`` is every
span of the program. Seconds that two spans both cover count once, which a
sum cannot give where spans nest (a ``jit`` traced inside another reports a
trace time of its own, a compile lies under the span that caused it) or run
beside each other on two threads. No matching span is 0.0; a program
without ``metrics.spans`` or an interval that lost spans is None."""
from harness import spans as S


def read(obs, name=None, prefix=None, where=None, before_window=False,
         scale=1.0):
    got = S.fetch(obs, before_window, name=name, prefix=prefix)
    if got is None:
        return None
    lo, hi = (float("-inf"), obs["t_open"]) if before_window \
        else (obs["t_open"], obs["t_close"])
    total, reach = 0.0, lo          # everything up to ``reach`` is counted
    for s in sorted(S.matching(got, where), key=lambda s: s.t0):
        a, b = max(s.t0, reach), min(s.t0 + s.dur, hi)
        if b > a:
            total += b - a
            reach = b
    return scale * total

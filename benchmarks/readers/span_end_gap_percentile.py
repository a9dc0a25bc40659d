"""A percentile of the time between the ENDS of consecutive spans named
``name`` (kept by ``where``) that began in the window, each gap weighted by
the later span's arg ``weight``. With ``engine.harvest``, ``of=decode`` and
``tokens``: the time a stream waits between two of its tokens, over all
tokens delivered in the window, which a prefill program that rides between
two decode steps stretches. None as in ``span_percentile``; two spans make
the first gap."""
from harness import spans as S


def read(obs, name, q, where=None, weight=None, scale=1000.0):
    got = S.fetch(obs, name=name)
    if got is None:
        return None
    ends = sorted((s.t0 + s.dur, (s.args or {}).get(weight, 1) if weight
                   else 1) for s in S.matching(got, where))
    gaps = [b[0] - a[0] for a, b in zip(ends[:-1], ends[1:])]
    v = S.weighted_percentile(gaps, [w for _, w in ends[1:]], q)
    return None if v is None else scale * v

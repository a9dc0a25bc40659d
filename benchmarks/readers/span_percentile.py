"""A percentile of the durations of the program's spans named ``name`` that
began in the window, in the unit ``scale`` gives (1000 for ms). ``where``
keeps the spans whose args equal it key by key, ``positive`` those whose
arg of that name is above zero. None where no such span began in the
window (a median of nothing), where the program has no ``metrics.spans``,
or where the window lost spans."""
from harness import spans as S
from harness.window import percentile


def read(obs, name, q, where=None, positive=None, scale=1000.0):
    got = S.fetch(obs, name=name)
    if got is None:
        return None
    vals = [s.dur for s in S.matching(got, where, positive)]
    if not vals:
        return None
    return scale * percentile(vals, q)

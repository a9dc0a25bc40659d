"""The summed duration of the program's spans named ``name`` or starting
with one of ``prefix``, cut to the window; ``before_window`` reads set-up
in its place (everything that began before the window opened). ``where``
keeps the spans whose args equal it key by key. ``share_of_window`` divides
by the window's length (with ``scale`` 100: the share of the window one
thread spent inside these spans, in %). No matching span is 0.0; a
program without ``metrics.spans`` or an interval that lost spans is None."""
from harness import spans as S


def read(obs, name=None, prefix=None, where=None, before_window=False,
         share_of_window=False, scale=1.0):
    got = S.fetch(obs, before_window, name=name, prefix=prefix)
    if got is None:
        return None
    lo, hi = (None, None) if before_window else (obs["t_open"],
                                                 obs["t_close"])
    total = sum(S.clipped(s, lo, hi) for s in S.matching(got, where))
    if share_of_window:
        total /= obs["t_close"] - obs["t_open"]
    return scale * total

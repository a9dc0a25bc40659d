"""A percentile of the SELF time of the program's spans named ``name`` that
began in the window: each one's duration less the part of its interval
that its direct child spans cover (choosing-metrics, section 4). For
``engine.step`` that is the host work of a step that is neither admission,
nor a launch, nor waiting for the device. None as in ``span_percentile``."""
from harness import spans as S
from harness.window import percentile


def read(obs, name, q, scale=1000.0):
    got = S.fetch(obs)
    if got is None:
        return None
    children = {}
    for s in got:
        if s.parent is not None:
            children.setdefault(s.parent, []).append(s)
    vals = [S.self_time(s, children.get(s.id, ()))
            for s in got if s.name == name]
    if not vals:
        return None
    return scale * percentile(vals, q)

"""A percentile over requests of (sum of ``plus`` marks - sum of ``minus``
marks), in the unit ``scale`` gives (1000 for ms). A request counts where
its ``when`` mark fell inside the window and every mark is there."""
from harness.window import inside, percentile


def read(obs, plus, minus, when, q, scale=1000.0):
    vals = []
    for r in obs["records"]:
        if not inside(r.get(when), obs["t_open"], obs["t_close"]):
            continue
        marks = [r.get(k) for k in plus + minus]
        if any(m is None for m in marks):
            continue
        vals.append(sum(r[k] for k in plus) - sum(r[k] for k in minus))
    if not vals:
        return None
    return scale * percentile(vals, q)

"""A ratio of weighted sums of the program's counters, differenced over the
window: ``num`` and ``den`` are lists of ``[counter, weight]``;
``den_times`` names an observation (such as ``max_slots``) that multiplies
the denominator; ``scale`` multiplies the result (100 for a share in %)."""
from harness.window import counter_delta


def _sum(obs, terms):
    return sum(w * counter_delta(obs["counters_open"], obs["counters_close"],
                                 name) for name, w in terms)


def read(obs, num, den, den_times=None, scale=1.0):
    d = _sum(obs, den) * (obs[den_times] if den_times else 1.0)
    if d <= 0:
        return None
    return scale * _sum(obs, num) / d

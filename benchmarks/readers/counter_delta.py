"""The growth of some of the program's counters inside the window, summed."""
from harness.window import counter_delta


def read(obs, counters):
    return sum(counter_delta(obs["counters_open"], obs["counters_close"], c)
               for c in counters)

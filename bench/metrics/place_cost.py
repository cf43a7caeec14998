"""Mean best placement cost (the placer's Eq. 1 objective) per design in
the window."""

from bench.lib.readers import mean_of


def read(records):
    return mean_of(records, lambda d: d["best_cost"])

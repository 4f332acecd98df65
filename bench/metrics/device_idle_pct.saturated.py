"""Share of the traced window in which no operation ran on the device:
100 x (1 - union of the device's operation intervals / window)."""

from bench.tracing import idle_pct


def read(run):
    return idle_pct(run["trace"])

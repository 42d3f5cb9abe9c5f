"""Requests served per front-end tick over the traced span
(``Frontend.stats``: ``n_served`` and ``ticks``)."""
from harness import metric_util


def read(run):
    ticks = metric_util.ticks(run)
    if not ticks:
        return None
    s = run.stats_traced
    return (s["stop"]["n_served"] - s["start"]["n_served"]) / ticks

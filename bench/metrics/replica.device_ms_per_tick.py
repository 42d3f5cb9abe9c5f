"""Device milliseconds per front-end tick of the replica's compiled delta
apply (``serve/frontend._rep_apply``), from the trace's module events."""
from harness import metric_util


def read(run):
    return metric_util.ms_per_tick(run, "module", "_rep_apply")

"""Device milliseconds per front-end tick of the insert-fold Pallas kernel
(``kernels/closure_update.py``), writer and replica together."""
from harness import metric_util


def read(run):
    return metric_util.ms_per_tick(run, "op", "closure_update_tiled")

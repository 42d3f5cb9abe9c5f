"""Device milliseconds per front-end tick of the delete-repair Pallas
kernel (``kernels/closure_delete.py``), writer and replica together."""
from harness import metric_util


def read(run):
    return metric_util.ms_per_tick(run, "op", "closure_delete_tiled")

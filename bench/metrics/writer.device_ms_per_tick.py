"""Device milliseconds per front-end tick of the writer's compiled steps
(``replica.Primary``, ``jit=True``), from the trace's module events."""
from harness import metric_util

STEPS = ("_add_vertices_step", "_add_edges_step", "_remove_vertices_step",
         "_remove_edges_step")


def read(run):
    return metric_util.ms_per_tick(run, "module", *STEPS)

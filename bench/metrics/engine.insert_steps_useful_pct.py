"""Share of the writer's insert-scan sub-batch steps that held at least
one candidate edge, over the traced span (``Frontend.stats``:
``engine_work["add_edges"]``, ``n_useful_steps`` over ``n_steps``)."""
from harness import counters


def read(run):
    steps = counters.work(run, ["add_edges"], "n_steps")
    if not steps:
        return None
    return 100.0 * counters.work(run, ["add_edges"], "n_useful_steps") \
        / steps

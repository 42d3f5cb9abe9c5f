"""99th percentile of the queue wait, from a request's submit to the
coalescer's pick, of the requests picked in the traced span
(``Frontend.stats``: ``queue_wait_s``, a fixed-bucket histogram)."""
from harness import counters


def read(run):
    return counters.queue_wait_ms(run, 0.99)

"""Median latency from each request's due time to its answer, over every
request due in the window and answered 200 (host clock)."""


def read(run):
    return run.summary["p50_ms"]

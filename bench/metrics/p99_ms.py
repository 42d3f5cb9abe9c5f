"""99th percentile of the latency from due time to answer, over every
request due in the window and answered 200 (host clock)."""


def read(run):
    return run.summary["p99_ms"]

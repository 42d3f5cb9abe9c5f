"""Requests answered 200 and not stale that completed inside the window,
over the window's seconds (host clock)."""


def read(run):
    return run.summary["ops_per_s"]

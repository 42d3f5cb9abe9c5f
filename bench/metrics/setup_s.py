"""Seconds from process start to the first due request: preload,
compilation and warm-up included (host clock)."""


def read(run):
    return run.setup_s

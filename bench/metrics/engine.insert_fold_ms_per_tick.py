"""Device milliseconds per front-end tick under the program's
``insert_fold`` scope, writer and replica together: each instant of
device time goes to the innermost op running then, and the op to the
innermost named scope of its HLO."""
from harness import spans


def read(run):
    return spans.scope_ms_per_tick(run, "insert_fold")

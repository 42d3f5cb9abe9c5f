"""Device milliseconds per front-end tick under the program's
``cycle_check.*`` scopes (the insert scan's three arms), writer and
replica together: each instant of device time goes to the innermost op
running then, and the op to the innermost named scope of its HLO."""
from harness import spans

SCOPES = ("cycle_check.incremental", "cycle_check.partial",
          "cycle_check.closure")


def read(run):
    return spans.scope_ms_per_tick(run, *SCOPES)

"""Share of the traced span in which no operation ran on the device:
1 minus the union of busy intervals over the span."""


def read(run):
    return run.trace.idle_pct if run.trace is not None else None

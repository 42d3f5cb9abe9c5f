"""99th percentile of how late the generator submitted, from the due
time, over the requests due in the traced span (host clock)."""
import numpy as np

from harness import loadgen


def read(run):
    if run.traced is None:
        return None
    late = loadgen.late_ms(run.outcome, *run.traced)
    return float(np.percentile(late, 99)) if late.size else None

"""Mean duration of the front end's ``frontend.tick`` spans that start in
the traced span (the program's host spans, on the profiler's clock)."""
from harness import spans


def read(run):
    got = spans.of(run)
    if got is None:
        return None
    ticks = spans.ticks_in(*got)
    if not ticks:
        return None
    return sum(t.dur for t in ticks) / len(ticks) / 1e6

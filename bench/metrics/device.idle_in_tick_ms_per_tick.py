"""Device-idle milliseconds inside the ``frontend.tick`` spans that start
in the traced span, per tick: each span minus the device's busy union
clipped to it."""
from harness import spans


def read(run):
    got = spans.of(run)
    if got is None:
        return None
    ticks = spans.ticks_in(*got)
    if not ticks:
        return None
    busy = spans.busy(got[0].ops)
    idle = sum(spans.idle_inside(busy, (t.start, t.end)) for t in ticks)
    return idle / len(ticks) / 1e6

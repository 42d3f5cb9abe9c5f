"""Device-idle milliseconds inside the ``frontend.tick`` spans that start
in the traced span, per tick: each span minus the device's busy union
clipped to it; the mean over the cell's chips."""
from harness import spans


def read(run):
    got = spans.of(run)
    if got is None:
        return None
    sp, window = got
    ticks = spans.ticks_in(sp, window)
    if not ticks:
        return None
    idle = []
    for d in sp.devices:
        busy = spans.busy(sp.on(d).ops)
        idle.append(sum(spans.idle_inside(busy, (t.start, t.end))
                        for t in ticks))
    return sum(idle) / len(idle) / len(ticks) / 1e6

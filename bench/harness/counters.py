"""The program's counters (``Frontend.stats``) over the traced span: the
difference of the two reads the harness takes at its ends.  Each helper
returns None where the program does not report the counter."""
from __future__ import annotations

from typing import List, Optional, Sequence

DELETE_PHASES = ("remove_vertices", "remove_edges")


def _ends(run, key: str):
    s = run.stats_traced
    if not s or "stop" not in s or key not in s["stop"]:
        return None
    return s["start"][key], s["stop"][key]


def histogram_quantile(edges: Sequence[float], cumulative: Sequence[int],
                       count: int, q: float) -> Optional[float]:
    """The q-quantile of a cumulative histogram (``cumulative[i]`` samples
    at most ``edges[i]``), linear inside its bucket; None with no samples
    or where the quantile lies past the last edge."""
    target = q * count
    if count <= 0:
        return None
    for i, c in enumerate(cumulative):
        if c >= target:
            lo = edges[i - 1] if i else 0.0
            below = cumulative[i - 1] if i else 0
            return lo + (edges[i] - lo) * (target - below) / (c - below)
    return None


def queue_wait_ms(run, q: float) -> Optional[float]:
    """q-quantile of the queue waits of requests picked in the span."""
    ends = _ends(run, "queue_wait_s")
    if ends is None:
        return None
    a, b = ends
    cum: List[int] = [y - x for x, y in zip(a["cumulative"],
                                            b["cumulative"])]
    v = histogram_quantile(b["edges_s"], cum, b["count"] - a["count"], q)
    return None if v is None else v * 1e3


def work(run, phases: Sequence[str], count: str) -> Optional[int]:
    """A `ReachStats` count summed over writer ``phases`` in the span."""
    ends = _ends(run, "engine_work")
    if ends is None:
        return None
    a, b = ends
    return sum(b[ph][count] - a[ph][count] for ph in phases)

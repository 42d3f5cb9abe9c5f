"""Arithmetic the per-layer readers share."""
from __future__ import annotations

from typing import Optional


def ticks(run) -> int:
    """Front-end ticks committed inside the traced span."""
    s = run.stats_traced
    if not s or "stop" not in s:
        return 0
    return s["stop"]["ticks"] - s["start"]["ticks"]


def ms_per_tick(run, table: str, *patterns: str) -> Optional[float]:
    """Device milliseconds per tick of the events matching ``patterns``.
    A traced span with ticks and without such events reads 0: the code
    did not run."""
    n = ticks(run)
    if run.trace is None or not n:
        return None
    return run.trace.seconds(table, *patterns) * 1e3 / n

"""Open-loop load generator and its latency arithmetic.

Every request is timed from its **due time** on the arrival schedule,
not from when the generator got round to it, so a stall of the event
loop or of the server counts against every request it delays.  The
generator submits each request through ``Frontend.submit`` as soon as
it falls due and never waits for earlier answers.  After the window
closes, requests still in flight are drained (up to ``drain_s``) and
counted.
"""
from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Callable, List, Sequence, Tuple

import numpy as np

STATUS_OK = 200
STATUS_ERROR = -1     # submit raised
STATUS_LOST = -2      # never answered, even after the drain


@dataclasses.dataclass
class Outcome:
    """Per request, in seconds from the window's start (NaN where the
    event never happened)."""

    due: np.ndarray
    submit: np.ndarray
    done: np.ndarray
    status: np.ndarray
    stale: np.ndarray
    ok: np.ndarray
    tick: np.ndarray
    seconds: float
    errors: List[str]


def _now() -> float:
    return time.perf_counter()


async def drive(fe, due: np.ndarray, reqs: Sequence[Tuple], seconds: float,
                hooks: Sequence[Tuple[float, Callable[[], None]]] = (),
                drain_s: float = 60.0) -> Outcome:
    """Offer ``reqs`` at their ``due`` times through ``fe.submit``.

    ``hooks`` are (offset, fn) pairs run from the generator once the
    window's clock passes ``offset`` (the profiler's start and stop)."""
    n = len(reqs)
    submit = np.full(n, np.nan)
    done = np.full(n, np.nan)
    status = np.full(n, STATUS_LOST, np.int32)
    stale = np.zeros(n, bool)
    ok = np.zeros(n, bool)
    tick = np.full(n, -1, np.int64)
    errors: List[str] = []
    pending = sorted(hooks, key=lambda h: h[0])
    t0 = _now()

    async def one(i: int) -> None:
        kind, a, b, tenant = reqs[i]
        submit[i] = _now() - t0
        try:
            resp = await fe.submit(kind, a, b, tenant=tenant)
        except Exception as err:  # recorded: a failed request, not a crash
            done[i] = _now() - t0
            status[i] = STATUS_ERROR
            errors.append(f"{kind}({a}, {b}): {type(err).__name__}: {err}")
            return
        done[i] = _now() - t0
        status[i] = resp.status
        stale[i] = resp.stale
        ok[i] = resp.ok
        tick[i] = resp.tick

    tasks: List[asyncio.Task] = []
    i = 0
    while i < n or pending:
        now = _now() - t0
        while pending and pending[0][0] <= now:
            pending.pop(0)[1]()
            now = _now() - t0
        while i < n and due[i] <= now:
            tasks.append(asyncio.ensure_future(one(i)))
            i += 1
        nxt = min(due[i] if i < n else np.inf,
                  pending[0][0] if pending else np.inf)
        if nxt == np.inf:
            break
        await asyncio.sleep(max(0.0, nxt - (_now() - t0)))
    if tasks:
        left = max(0.0, seconds + drain_s - (_now() - t0))
        _, late = await asyncio.wait(tasks, timeout=left)
        for t in late:
            t.cancel()
    return Outcome(np.asarray(due, np.float64), submit, done, status, stale,
                   ok, tick, float(seconds), errors)


def quantile(x: np.ndarray, q: float) -> float:
    return float(np.percentile(x, q)) if x.size else float("nan")


def summarize(out: Outcome) -> dict:
    """The end-to-end numbers of one window.

    * ``ops_per_s``: requests answered 200 and not stale that completed
      inside the window, over the window's seconds;
    * ``p50_ms`` / ``p99_ms``: latency from the due time to the answer,
      over every request due in the window and answered 200 (drained
      ones included);
    * ``failed``: shed (429), errors, stale reads and requests never
      answered;
    * ``late_*``: how late the generator submitted, from the due time."""
    answered = out.status == STATUS_OK
    good = answered & ~out.stale
    lat = (out.done - out.due)[answered] * 1e3
    late = (out.submit - out.due)[~np.isnan(out.submit)] * 1e3
    in_window = good & (out.done <= out.seconds)
    return {
        "attempted": int(out.due.size),
        "failed": int(out.due.size - good.sum()),
        "n_shed": int((out.status == 429).sum()),
        "n_errors": int((out.status == STATUS_ERROR).sum()),
        "n_lost": int((out.status == STATUS_LOST).sum()),
        "n_stale": int((answered & out.stale).sum()),
        "n_latencies": int(lat.size),
        "ops_per_s": float(in_window.sum() / out.seconds),
        "p50_ms": quantile(lat, 50),
        "p90_ms": quantile(lat, 90),
        "p95_ms": quantile(lat, 95),
        "p99_ms": quantile(lat, 99),
        "late_p50_ms": quantile(late, 50),
        "late_p99_ms": quantile(late, 99),
    }


def late_ms(out: Outcome, lo: float, hi: float) -> np.ndarray:
    """Lateness of the requests due in [lo, hi), in milliseconds."""
    sel = (out.due >= lo) & (out.due < hi) & ~np.isnan(out.submit)
    return (out.submit - out.due)[sel] * 1e3

"""Reduce a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and splits
the events of each of a cell's chips into device operations and device
modules (one event per execution of a compiled program), beside the
host events they share: one `Trace` per chip.  ``reduce`` then
computes, over the traced window:

* ``busy_s_by_device``: for each chip, the union of the intervals in
  which a device operation ran (operations nest and overlap; the union
  counts each instant once); ``busy_s`` is their mean;
* ``by_module`` / ``by_op``: for each chip, device seconds summed by
  name; ``seconds`` reads the slowest chip (a sharded tick waits on its
  slowest shard), ``mean_by`` the mean over the chips;
* ``gaps``: the longest idle intervals of any chip, each labelled with
  the innermost host event running at its midpoint.

On one chip each of these is that chip's own number.

Device events are named by `short_name`: a compiled program by its
function (``jit__add_edges_step``), an operation by its HLO name, which
for a Pallas call is the kernel's (``closure_update_tiled.1``), so a
reader finds a step or a kernel by a stable substring.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

# (label, start_ns, duration_ns)
Event = Tuple[str, int, int]

OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


@dataclasses.dataclass
class Trace:
    """One chip's device events and the host events of the process."""

    ops: List[Event]
    modules: List[Event]
    host: List[Event]


@dataclasses.dataclass
class Summary:
    """The traced window's numbers; each ``*_by_device`` list and each of
    ``by_module`` / ``by_op`` holds one entry per chip of the cell."""

    window_s: float
    busy_s_by_device: List[float]
    by_module: List[Dict[str, float]]
    by_op: List[Dict[str, float]]
    gaps: List[Tuple[str, float]]

    @property
    def busy_s(self) -> float:
        """Busy seconds, the mean over the chips."""
        return sum(self.busy_s_by_device) / len(self.busy_s_by_device)

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def seconds(self, table: str, *patterns: str) -> float:
        """Device seconds of the events whose label holds any pattern, on
        the chip that spent the most on them."""
        rows = self.by_module if table == "module" else self.by_op
        return max(sum(s for name, s in r.items()
                       if any(p in name for p in patterns)) for r in rows)


def short_name(name: str) -> str:
    """``%fusion.3 = (...) fusion(...)`` -> ``fusion.3``;
    ``jit__rep_apply(12345)`` -> ``jit__rep_apply``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    if name.endswith(")") and "(" in name:
        head, _, tail = name.rpartition("(")
        if tail[:-1].isdigit():
            name = head
    return name


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def device_planes(devices) -> List[str]:
    """The trace's plane name of each of ``devices``."""
    return [f"/device:TPU:{d.id}" for d in devices]


def load(trace_dir: str, planes: Sequence[str] = ("/device:TPU:0",)
         ) -> List[Trace]:
    """One `Trace` per device plane of ``planes``, in that order."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(trace_dir))
    ops: Dict[str, List[Event]] = {p: [] for p in planes}
    modules: Dict[str, List[Event]] = {p: [] for p in planes}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name in ops:
            for line in plane.lines:
                if line.name in OP_LINES:
                    ops[plane.name] += [(short_name(e.name), int(e.start_ns),
                                         int(e.duration_ns))
                                        for e in line.events]
                elif line.name in MODULE_LINES:
                    modules[plane.name] += [(short_name(e.name),
                                             int(e.start_ns),
                                             int(e.duration_ns))
                                            for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host += [(e.name, int(e.start_ns), int(e.duration_ns))
                         for e in line.events]
    return [Trace(ops[p], modules[p], host) for p in planes]


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge (start, end) intervals into disjoint ones, in order."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _host_at(host: Sequence[Event], t: int) -> str:
    """The shortest host event that spans instant t, or "idle host"."""
    best = None
    for name, s, d in host:
        if s <= t < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "idle host"


def reduce(traces: Sequence[Trace], window: Tuple[int, int] = None,
           n_gaps: int = 10) -> Summary:
    """The traced window's numbers over the chips of ``traces``.
    ``window`` (start_ns, end_ns) defaults to the span of all events;
    device events are clipped to it."""
    host = traces[0].host
    everything = host + [e for tr in traces for e in tr.ops + tr.modules]
    if window is None:
        if not everything:
            raise ValueError("empty trace and no window")
        window = (min(s for _, s, _ in everything),
                  max(s + d for _, s, d in everything))
    lo, hi = window

    def by_name(events):
        out: Dict[str, float] = {}
        for name, s, d in events:
            clipped = min(s + d, hi) - max(s, lo)
            if clipped > 0:
                out[name] = out.get(name, 0.0) + clipped / 1e9
        return out

    busy_s = []
    idle = []
    for tr in traces:
        busy_src = tr.ops if tr.ops else tr.modules
        spans = union((max(s, lo), min(s + d, hi)) for _, s, d in busy_src
                      if s < hi and s + d > lo)
        busy_s.append(sum(e - s for s, e in spans) / 1e9)
        prev = lo
        for s, e in spans + [(hi, hi)]:
            if s > prev:
                idle.append((s - prev, prev))
            prev = max(prev, e)
    idle.sort(reverse=True)
    gaps = [(_host_at(host, start + dur // 2), dur / 1e9)
            for dur, start in idle[:n_gaps]]
    return Summary((hi - lo) / 1e9, busy_s,
                   [by_name(tr.modules) for tr in traces],
                   [by_name(tr.ops) for tr in traces], gaps)


def mean_by(rows: Sequence[Dict[str, float]]) -> Dict[str, float]:
    """Seconds by name, the mean over the chips' tables."""
    names = dict.fromkeys(name for r in rows for name in r)
    return {name: sum(r.get(name, 0.0) for r in rows) / len(rows)
            for name in names}


def top(rows: Dict[str, float], n: int = 10) -> List[List]:
    return [[name, s] for name, s in
            sorted(rows.items(), key=lambda kv: -kv[1])[:n]]

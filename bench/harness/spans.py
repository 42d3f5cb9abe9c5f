"""The program's own instrumentation, as a profiler trace holds it.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps:

* the program's host spans (``jax.profiler.TraceAnnotation``s: the names
  in ``PROGRAM_SPANS``; ``frontend.tick`` with the ``tick`` and ``n`` it
  was opened with), and the harness's ``bench.window``;
* every device operation (``XLA Ops`` of each device plane read: one
  per chip of the cell) with its chip, the compiled program it ran in and
  the innermost named scope of the program (``SCOPES``) it was traced
  under.

The scope is not a stat of a device event.  It is the ``op_name`` of the
instruction in the program's optimized HLO, which the profiler stores in
the ``/host:metadata`` plane (one ``Hlo Proto`` per program, keyed by the
program's ``name(id)``, the same name its ``XLA Modules`` events carry).
The Python reader of the trace does not expose that plane's metadata, so
``hlo_op_names`` reads it straight from the protobuf wire format.

The reductions below are what the span readers in ``metrics/`` compute,
over one chip's ops (`Spans.on`); all times are nanoseconds on the
profiler's clock.  A reader finds the
loaded spans as ``run.spans`` (``of``), and nothing where the run did not
keep them or the program wrote none.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

from harness import trace as trace_mod

PROGRAM_SPANS = ("frontend.tick", "frontend.coalesce", "frontend.respond",
                 "writer.remove_vertices", "writer.add_vertices",
                 "writer.remove_edges", "writer.add_edges", "log.flush",
                 "replica.apply", "snapshot.take", "reader.read")
WINDOW = "bench.window"
SCOPES = ("slot_lookup", "cycle_check.incremental", "cycle_check.partial",
          "cycle_check.closure", "insert_fold", "delete_repair",
          "summary_upkeep")
UNSCOPED = ""


@dataclasses.dataclass
class Span:
    name: str
    start: int
    dur: int
    meta: Dict[str, object]

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class Op:
    name: str       # HLO instruction (``while.42``)
    module: str     # compiled program (``jit__add_edges_step``)
    scope: str      # innermost of SCOPES, or UNSCOPED
    start: int
    dur: int
    path: str = ""  # the instruction's op_name
    device: int = 0  # index of its chip among the planes read

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class Spans:
    spans: List[Span]
    ops: List[Op]

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    @property
    def devices(self) -> List[int]:
        """The chips that ran ops, in order (chip 0 where none ran)."""
        return sorted({o.device for o in self.ops}) or [0]

    def on(self, device: int) -> "Spans":
        """The host spans with the ops of one chip."""
        return Spans(self.spans, [o for o in self.ops if o.device == device])

    @property
    def window(self) -> Optional[Tuple[int, int]]:
        w = self.named(WINDOW)
        return (w[0].start, w[0].end) if w else None


# ------------------------------------------------------------- loading

def _varint(b: bytes, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def _fields(b: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message; a length-delimited
    value is its bytes, a varint its int."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} not supported")
        yield field, v


def _sub(b: bytes, field: int) -> Iterator[bytes]:
    return (v for f, v in _fields(b) if f == field)


def _hlo_op_names(hlo_proto: bytes) -> Dict[str, str]:
    """instruction name -> metadata op_name, over every computation of an
    ``HloProto`` (hlo_module 1; computations 3; instructions 2; name 1,
    metadata 7; op_name 2)."""
    out: Dict[str, str] = {}
    for module in _sub(hlo_proto, 1):
        for comp in _sub(module, 3):
            for inst in _sub(comp, 2):
                name = op_name = None
                for f, v in _fields(inst):
                    if f == 1:
                        name = v.decode()
                    elif f == 7:
                        for g, w in _fields(v):
                            if g == 2:
                                op_name = w.decode()
                if name and op_name:
                    out[name] = op_name
    return out


def hlo_op_names(xplane: bytes) -> Dict[str, Dict[str, str]]:
    """``name(id)`` of each compiled program -> {instruction: op_name},
    from the ``/host:metadata`` plane of a serialized XSpace (planes 1;
    plane name 2, event_metadata 4 as map entries of key 1 / value 2;
    event metadata name 2, stats 5; stat bytes_value 6)."""
    out: Dict[str, Dict[str, str]] = {}
    for plane in _sub(xplane, 1):
        names = [v for f, v in _fields(plane) if f == 2]
        if not names or names[0] != b"/host:metadata":
            continue
        for entry in _sub(plane, 4):
            for meta in _sub(entry, 2):
                prog = None
                ops: Dict[str, str] = {}
                for f, v in _fields(meta):
                    if f == 2:
                        prog = v.decode()
                    elif f == 5:
                        for proto in _sub(v, 6):
                            ops.update(_hlo_op_names(proto))
                if prog:
                    out[prog] = ops
    return out


def scope_of(op_name: str) -> str:
    """The innermost of ``SCOPES`` on an op_name path, or UNSCOPED."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def _module_at(modules: Sequence[Tuple[str, int, int]], t: int) -> str:
    """The program whose execution covers instant t (``modules`` sorted
    by start; programs run one at a time on the device)."""
    lo, hi = 0, len(modules)
    while lo < hi:
        mid = (lo + hi) // 2
        if modules[mid][1] <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and t < modules[lo - 1][1] + modules[lo - 1][2]:
        return modules[lo - 1][0]
    return ""


def load(trace_dir: str, planes: Sequence[str] = ("/device:TPU:0",)
         ) -> Spans:
    """The program's spans and the ops of each device plane of
    ``planes`` (``Op.device`` is the plane's index there)."""
    from jax.profiler import ProfileData

    with open(trace_mod.find_xplane(trace_dir), "rb") as f:
        raw = f.read()
    names = hlo_op_names(raw)
    pd = ProfileData.from_serialized_xspace(raw)
    spans: List[Span] = []
    ops: Dict[str, list] = {p: [] for p in planes}
    modules: Dict[str, list] = {p: [] for p in planes}
    keep = set(PROGRAM_SPANS) | {WINDOW}
    for plane in pd.planes:
        if plane.name in ops:
            for line in plane.lines:
                if line.name in trace_mod.MODULE_LINES:
                    modules[plane.name] += [(e.name, int(e.start_ns),
                                             int(e.duration_ns))
                                            for e in line.events]
                elif line.name in trace_mod.OP_LINES:
                    ops[plane.name] += [(trace_mod.short_name(e.name),
                                         int(e.start_ns), int(e.duration_ns))
                                        for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                spans += [Span(e.name, int(e.start_ns), int(e.duration_ns),
                               dict(e.stats) if e.name == "frontend.tick"
                               else {})
                          for e in line.events if e.name in keep]
    out = []
    for device, plane in enumerate(planes):
        mods = sorted(modules[plane], key=lambda m: m[1])
        for name, s, d in ops[plane]:
            prog = _module_at(mods, s)
            path = names.get(prog, {}).get(name, "")
            out.append(Op(name, trace_mod.short_name(prog), scope_of(path),
                          s, d, path, device))
    spans.sort(key=lambda sp: sp.start)
    return Spans(spans, out)


def of(run) -> Optional[Tuple[Spans, Tuple[int, int]]]:
    """(spans, traced window) of a run that kept its spans, else None."""
    sp = getattr(run, "spans", None)
    if sp is None or sp.window is None:
        return None
    return sp, sp.window


# ---------------------------------------------------------- reductions

def self_times(ops: Sequence[Op], window: Tuple[int, int]
               ) -> Tuple[List[int], List[int]]:
    """Per op, the nanoseconds of ``window`` in which it is the innermost
    op running (nested ``while`` / ``cond`` events and their bodies are
    not counted twice), and the index of the outermost op it ran inside
    (itself at the top level).  The innermost op is the one that started
    last among those still running."""
    lo, hi = window
    order = sorted((i for i, o in enumerate(ops) if o.start < hi
                    and o.end > lo), key=lambda i: (ops[i].start, -ops[i].dur))
    self_ns = [0] * len(ops)
    root = list(range(len(ops)))
    stack: List[int] = []
    cursor = lo

    def advance(until: int) -> None:
        """Charge [cursor, until) to the innermost running ops, and close
        every op that has ended by ``until``."""
        nonlocal cursor
        while stack:
            top = stack[-1]
            end = min(ops[top].end, until)
            if end > cursor:
                self_ns[top] += end - cursor
                cursor = end
            if ops[top].end > until:
                break
            stack.pop()
        cursor = max(cursor, until)

    for i in order:
        advance(max(ops[i].start, lo))
        root[i] = root[stack[0]] if stack else i
        stack.append(i)
    advance(hi)
    return self_ns, root


def scope_ms(sp: Spans, window: Tuple[int, int]) -> Dict[str, float]:
    """Device self time by scope over ``window``, in milliseconds."""
    self_ns, _ = self_times(sp.ops, window)
    out: Dict[str, float] = {}
    for op, ns in zip(sp.ops, self_ns):
        if ns:
            out[op.scope] = out.get(op.scope, 0.0) + ns / 1e6
    return out


def scope_ms_per_tick(run, *scopes: str) -> Optional[float]:
    """Device self milliseconds under ``scopes`` per front-end tick of the
    traced span (`metric_util.ticks`), on the chip that spent the most
    under them; None where no op carries a scope (a program without
    them), 0 where these scopes did not run."""
    from harness import metric_util

    got = of(run)
    ticks = metric_util.ticks(run)
    if got is None or not ticks:
        return None
    sp, window = got
    if all(o.scope == UNSCOPED for o in sp.ops):
        return None
    per_chip = []
    for d in sp.devices:
        by = scope_ms(sp.on(d), window)
        per_chip.append(sum(by.get(s, 0.0) for s in scopes))
    return max(per_chip) / ticks


def split_by_scope(sp: Spans, window: Tuple[int, int], n: int = 5
                   ) -> List[list]:
    """The ``n`` top-level ops with the most device time in ``window``,
    each as [module, op, ms, {scope: self ms of the ops inside it}]."""
    self_ns, root = self_times(sp.ops, window)
    by_root: Dict[int, Dict[str, float]] = {}
    for i, ns in enumerate(self_ns):
        if ns:
            d = by_root.setdefault(root[i], {})
            d[sp.ops[i].scope] = d.get(sp.ops[i].scope, 0.0) + ns / 1e6
    # the same op of the same program runs once per call: sum the calls
    rows: Dict[Tuple[str, str], Dict[str, float]] = {}
    for r, d in by_root.items():
        row = rows.setdefault((sp.ops[r].module, sp.ops[r].name), {})
        for k, v in d.items():
            row[k] = row.get(k, 0.0) + v
    top = sorted(rows.items(), key=lambda kv: -sum(kv[1].values()))[:n]
    return [[m, o, sum(d.values()), d] for (m, o), d in top]


def top_ops(sp: Spans, window: Tuple[int, int], scope: str, n: int = 8
            ) -> List[list]:
    """The ``n`` ops under ``scope`` with the most device self time in
    ``window``, summed over their calls: [module, op, ms, op_name]."""
    self_ns, _ = self_times(sp.ops, window)
    rows: Dict[Tuple[str, str], list] = {}
    for op, ns in zip(sp.ops, self_ns):
        if ns and op.scope == scope:
            row = rows.setdefault((op.module, op.name),
                                  [op.module, op.name, 0.0, op.path])
            row[2] += ns / 1e6
    return sorted(rows.values(), key=lambda r: -r[2])[:n]


def ticks_in(sp: Spans, window: Tuple[int, int]) -> List[Span]:
    """The ``frontend.tick`` spans that start inside ``window``."""
    lo, hi = window
    return [s for s in sp.named("frontend.tick") if lo <= s.start < hi]


def busy(ops: Iterable[Op]) -> List[Tuple[int, int]]:
    return trace_mod.union((o.start, o.end) for o in ops)


def idle_inside(busy: Sequence[Tuple[int, int]], span: Tuple[int, int]
                ) -> int:
    """Nanoseconds of ``span`` not covered by the ``busy`` union."""
    s, e = span
    covered = sum(max(0, min(b1, e) - max(b0, s)) for b0, b1 in busy
                  if b0 < e and b1 > s)
    return (e - s) - covered


def _idle(sp: Spans, window: Tuple[int, int]) -> List[Tuple[int, int]]:
    """The device-idle intervals of ``window``, in order."""
    lo, hi = window
    out = []
    prev = lo
    for b0, b1 in busy(sp.ops):
        if b1 <= lo or b0 >= hi:
            continue
        if b0 > prev:
            out.append((prev, b0))
        prev = max(prev, b1)
    if hi > prev:
        out.append((prev, hi))
    return out


def idle_by_span(sp: Spans, window: Tuple[int, int]) -> Dict[str, float]:
    """Device-idle milliseconds of ``window``, each instant charged to
    the innermost (shortest) program span running then, or to "idle
    host" where none runs."""
    host = [s for s in sp.spans if s.name != WINDOW]
    marks = sorted([(s.start, 1, k) for k, s in enumerate(host)]
                   + [(s.end, 0, k) for k, s in enumerate(host)])
    active: Dict[int, Span] = {}
    out: Dict[str, float] = {}
    j = 0
    for a, b in _idle(sp, window):
        t = a
        while t < b:
            while j < len(marks) and marks[j][0] <= t:
                _, opened, k = marks[j]
                if opened:
                    active[k] = host[k]
                else:
                    active.pop(k, None)
                j += 1
            nxt = min(b, marks[j][0]) if j < len(marks) else b
            label = min(active.values(), key=lambda s: s.dur).name \
                if active else "idle host"
            out[label] = out.get(label, 0.0) + (nxt - t) / 1e6
            t = nxt
    return out


def gaps_by_span(sp: Spans, window: Tuple[int, int], n: int = 10
                 ) -> List[list]:
    """The ``n`` longest device-idle gaps of ``window``, each labelled with
    the innermost program span at its midpoint: [label, seconds]."""
    host = [(s.name, s.start, s.dur) for s in sp.spans if s.name != WINDOW]
    gaps = sorted(_idle(sp, window), key=lambda g: g[0] - g[1])[:n]
    return [[trace_mod._host_at(host, (a + b) // 2), (b - a) / 1e9]
            for a, b in gaps]

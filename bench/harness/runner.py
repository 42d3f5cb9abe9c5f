"""One run of one cell: set-up, the measured window, the check.

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in ``configs/<config>.json``, its
traffic mix in ``traffic/<traffic>.json``, the mix's generator in
``generators/<generator>.py`` where the mix names one (`generator`),
the configuration's plain reference in ``reference/<name>.py`` and each
metric's reader in ``metrics/<metric>.py`` (a ``read(run)`` function
that returns a number, or None where it finds nothing to read).

A run uses the cell's ``chips`` devices: a sharded engine gets a mesh
over them, memory is read from each, and a trace is read on each.
"""
from __future__ import annotations

import asyncio
import dataclasses
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from harness import check, loadgen, spans, system, traffic
from harness import trace as trace_mod

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
TRACE_SECONDS = 3.0      # traced span of a --trace 1 run
TRACE_LEAD = 2.0         # the profiler starts this long before the span


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(bench: dict, name: str, bench_dir=BENCH_DIR) -> Cell:
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(by_name)})")
    w = by_name[name]
    bench_dir = pathlib.Path(bench_dir)
    cfg = traffic.load(bench_dir / "configs" / f"{w['config']}.json")
    mix = traffic.load(bench_dir / "traffic" / f"{w['traffic']}.json")

    def of_cell(metrics):       # those without a "workloads" list, or on it
        return [m for m in metrics if name in m.get("workloads", [name])]
    return Cell(name, cfg, mix, int(w["chips"]),
                of_cell(bench["end_to_end"]), of_cell(bench["per_layer"]))


@dataclasses.dataclass(frozen=True)
class Generator:
    """A traffic mix's generator (see `harness.traffic`)."""

    make_preload: Callable
    make_schedule: Callable
    write_preload: Callable


def generator(mix: dict, bench_dir=BENCH_DIR) -> Generator:
    """``generators/<name>.py`` for a mix that names ``"generator"``,
    else `harness.traffic`; ``write_preload`` defaults to
    `system.write_preload`."""
    name = mix.get("generator")
    mod = traffic if name is None else load_module(
        pathlib.Path(bench_dir) / "generators" / f"{name}.py")
    return Generator(mod.make_preload, mod.make_schedule,
                     getattr(mod, "write_preload", system.write_preload))


def merged(base: dict, override: dict) -> dict:
    """``base`` with ``override``'s keys; a dict value merges one level
    down (``{"engine": {"subbatches": 1}}`` changes one engine key)."""
    out = dict(base)
    for k, v in override.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = dict(out[k], **v)
        else:
            out[k] = v
    return out


@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    cell: Cell
    seed: int
    seconds: float
    setup_s: float
    outcome: loadgen.Outcome
    summary: dict
    resident_bytes: int                     # summed over the cell's chips
    peak_bytes_by_device: List[int]
    trace: Optional[trace_mod.Summary] = None
    traced: Optional[tuple] = None          # (lo, hi) in window seconds
    stats_traced: Optional[dict] = None     # front-end counters over it
    spans: Optional[spans.Spans] = None     # the program's, over the trace


def read_metrics(specs: List[dict], run: Run,
                 bench_dir=BENCH_DIR) -> Dict[str, dict]:
    out = {}
    for spec in specs:
        mod = load_module(pathlib.Path(bench_dir) / "metrics"
                          / f"{spec['name']}.py")
        value = mod.read(run)
        if value is None:
            continue
        out[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    return out


def memory(devices) -> Tuple[int, List[int]]:
    """(bytes in use summed over ``devices``, each one's peak); -1 for
    what a device does not report (the sum, if any does not)."""
    stats = [d.memory_stats() or {} for d in devices]
    in_use = [int(s.get("bytes_in_use", -1)) for s in stats]
    peaks = [int(s.get("peak_bytes_in_use", -1)) for s in stats]
    return (sum(in_use) if min(in_use) >= 0 else -1), peaks


class Profiler:
    """Traces the last ``TRACE_SECONDS`` of the window.  Starting the
    profiler stalls the host, so it starts ``TRACE_LEAD`` seconds before
    the span; stopping it stalls the host for longer, so it stops when
    the window closes and every request has been submitted.  An
    annotation marks the span, and the front end's counters are read at
    both of its ends."""

    def __init__(self, fe, directory: str):
        self.fe = fe
        self.dir = directory
        self.annotation = None
        self.stats = {}

    def start(self) -> None:
        import jax

        # no Python call tracing: it slows the host path several fold
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def mark(self) -> None:
        import jax

        self.annotation = jax.profiler.TraceAnnotation("bench.window")
        self.annotation.__enter__()
        self.stats["start"] = dict(self.fe.stats)

    def stop(self) -> None:
        import jax

        self.stats["stop"] = dict(self.fe.stats)
        self.annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()


class CompileCounter:
    """Counts the backend compilations JAX reports while ``on``."""

    def __init__(self):
        import jax

        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, secs: float, **kw) -> None:
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._event)


def _window_of(traces: Sequence[trace_mod.Trace]):
    for name, s, d in traces[0].host:
        if name == "bench.window":
            return s, s + d
    return None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devices: Sequence, cfg_override: dict = None,
             bench_dir=BENCH_DIR, on_window=None) -> Tuple[dict, Run]:
    """Set up on ``devices`` (the cell's chips), measure, check; returns
    the result line's object and the `Run` its metrics were read from.

    ``on_window`` is called once set-up is done, just before the window
    opens (a fault planted there leaves the preload sound)."""
    cfg = merged(cell.config, cfg_override or {})
    gen = generator(cell.mix, bench_dir)
    preload = gen.make_preload(seed, cfg["preload"])
    sched = gen.make_schedule(seed, cell.mix, preload, seconds)
    fe = system.make_frontend(cfg, devices)
    rejected = gen.write_preload(fe, preload, int(cfg["preload"]["batch"]))
    say(f"preload: written, {rejected} inserts refused "
        f"({time.perf_counter() - t_start:.1f} s)")
    prof = None
    hooks = []
    tmp = None
    if trace:
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        prof = Profiler(fe, tmp)
        lo = max(0.0, seconds - TRACE_SECONDS)
        hooks = [(max(0.0, lo - TRACE_LEAD), prof.start), (lo, prof.mark),
                 (seconds, prof.stop)]

    compiles = CompileCounter()

    async def serve():
        async with fe:
            fe.warmup()
            setup_s = time.perf_counter() - t_start
            say(f"set-up {setup_s:.2f} s; window {seconds} s, "
                f"{len(sched)} requests ({sched.arrivals} arrivals)")
            if on_window is not None:
                on_window()
            compiles.on = True
            out = await loadgen.drive(fe, sched.due, sched.reqs, seconds,
                                      hooks)
            compiles.on = False
            return setup_s, out

    setup_s, out = asyncio.run(serve())
    compiles.close()
    resident, peaks = memory(devices)
    summary = loadgen.summarize(out)
    say("window: " + json.dumps(summary))
    say(f"compilations inside the window: {compiles.n}; replica health "
        f"{fe.stats['replica_health']}")
    run = Run(cell, seed, seconds, setup_s, out, summary, resident, peaks)
    if prof is not None:
        planes = trace_mod.device_planes(devices)
        tr = trace_mod.load(tmp, planes)
        run.spans = spans.load(tmp, planes)
        shutil.rmtree(tmp, ignore_errors=True)
        run.trace = trace_mod.reduce(tr, _window_of(tr))
        run.traced = (hooks[1][0], hooks[2][0])
        run.stats_traced = prof.stats
    metrics = read_metrics(cell.per_layer if trace else cell.end_to_end,
                           run, bench_dir)

    numbers = compare(cfg, preload, rejected, fe, sched, out, seed,
                      bench_dir)
    correct = all(v <= lim for _, v, lim in numbers)
    dev = devices[0]
    result = {
        "correct": bool(correct),
        "attempted": summary["attempted"],
        "failed": summary["failed"],
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": cell.chips, "memory_peak_bytes": max(peaks),
                   "memory_peak_bytes_by_device": peaks},
    }
    if run.trace is not None:
        result["device"]["busy_s"] = run.trace.busy_s
        result["device"]["busy_s_by_device"] = run.trace.busy_s_by_device
        result["device"]["window_s"] = run.trace.window_s
        result["breakdown"] = {
            "device_ops": trace_mod.top(trace_mod.mean_by(run.trace.by_op)),
            "idle_gaps": [[n, s] for n, s in run.trace.gaps]}
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in numbers}
    return result, run


def compare(cfg, preload, rejected, fe, sched, out, seed,
            bench_dir=BENCH_DIR) -> list:
    """[(name, value, limit)] — see `harness.check`.  The reference is
    built from the preload (``build(*preload)``); its ``READ_KINDS``
    (default ``("reachable",)``) are the request kinds that read."""
    ref_mod = load_module(pathlib.Path(bench_dir) / "reference"
                          / f"{cfg['reference']}.py")
    ref = ref_mod.build(*preload)
    t0 = time.perf_counter()
    want = check.replay(ref, fe.trace)
    bad_mut, bad_read, bad_trace = check.client_mismatches(
        fe.trace, want, sched.reqs, out.status, out.ok, out.tick,
        getattr(ref_mod, "READ_KINDS", ("reachable",)))
    say(f"reference replay of {len(fe.trace)} requests: "
        f"{time.perf_counter() - t0:.1f} s")
    engine = fe.primary.engine
    v_wrong, e_wrong = check.engine_sets_wrong(engine, ref)
    s_wrong, w_wrong, n_w = check.snapshot_reads_wrong(
        fe.primary.snapshot(), ref, fe.trace, seed)
    say(f"final snapshot: {check.N_SNAPSHOT_READS} reads, and {n_w} "
        "through edges accepted in the window")
    unconverged = sum(not r.converged_with(engine) for r in fe._replicas)
    unanswered = int(((out.status == loadgen.STATUS_LOST)
                      | (out.status == loadgen.STATUS_ERROR)).sum())
    for err in out.errors[:5]:
        say(f"error: {err}")
    return [
        ("preload_rejected", int(rejected), 0),
        ("unanswered", unanswered, 0),
        ("accept_bits_wrong", bad_mut, 0),
        ("reads_wrong", bad_read, 0),
        ("trace_wrong", bad_trace, 0),
        ("stale_reads", int(fe.n_degraded_reads), 0),
        ("vertices_wrong", v_wrong, 0),
        ("edges_wrong", e_wrong, 0),
        ("snapshot_reads_wrong", s_wrong, 0),
        ("window_edge_reads_wrong", w_wrong, 0),
        ("replica_unconverged", int(unconverged), 0),
    ]

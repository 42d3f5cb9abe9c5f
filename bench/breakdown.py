"""Where a cell's tick goes, from the program's own spans and scopes.

    python3 bench/breakdown.py --workload <cell> --seed <n> --seconds <s>

One traced run of the cell, as ``run.py --trace 1`` makes it, that also
keeps the program's spans and scoped device ops from the trace
(`harness.spans`) before the trace is removed.  Prints one JSON line:
``correct``; the window's summary (the traced run's own p50 and p99,
for the cost of tracing); the cell's per-layer metrics and the span
metrics (``SPAN_METRICS``, read by their files in ``metrics/``); device
self time by scope; the top-level device ops split by scope; the ops
with the most self time outside every scope; device-idle
milliseconds by innermost program span; and the longest idle gaps,
each named by the program span it fell in.  This is a tool for
attributing a cell's time, not a cell: ``run.py`` does not keep the
spans, so the span metrics are not among the benchmark's.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(ROOT / "src"))

SPAN_METRICS = [
    {"name": "frontend.tick_ms", "unit": "ms"},
    {"name": "device.idle_in_tick_ms_per_tick", "unit": "ms/tick"},
    {"name": "engine.cycle_check_ms_per_tick", "unit": "ms/tick"},
    {"name": "engine.insert_fold_ms_per_tick", "unit": "ms/tick"},
    {"name": "engine.delete_repair_ms_per_tick", "unit": "ms/tick"},
    {"name": "engine.summary_upkeep_ms_per_tick", "unit": "ms/tick"},
]


def run_keeping_spans(cell, seed: int, seconds: float, t_start: float,
                      device, cfg_override: dict = None):
    """One traced run of ``cell`` (`runner.run_cell`) that keeps the
    program's spans: they are read from the trace directory when the
    runner loads it, and handed, with the span readers, to its metric
    read.  Returns (result, spans, run)."""
    from harness import runner, spans
    from harness import trace as trace_mod

    kept = {}
    load, read_metrics = trace_mod.load, runner.read_metrics

    def load_keeping_spans(trace_dir, *a, **kw):
        kept["spans"] = spans.load(trace_dir)
        return load(trace_dir, *a, **kw)

    def read_with_spans(specs, run, *a, **kw):
        run.spans = kept.get("spans")
        kept["run"] = run
        return read_metrics(list(specs) + SPAN_METRICS, run, *a, **kw)

    trace_mod.load = load_keeping_spans
    runner.read_metrics = read_with_spans
    try:
        res = runner.run_cell(cell, seed, seconds, True, t_start, device,
                              cfg_override)
    finally:
        trace_mod.load, runner.read_metrics = load, read_metrics
    return res, kept["spans"], kept["run"]


def report(seed: int, res: dict, sp, run) -> dict:
    from harness import spans

    window = sp.window
    return {
        "seed": seed, "correct": res["correct"], "device": res["device"],
        "window": run.summary,
        "metrics": {k: v["value"] for k, v in res["metrics"].items()},
        "ticks_traced": len(spans.ticks_in(sp, window)),
        "scope_ms": spans.scope_ms(sp, window),
        "split_by_scope": spans.split_by_scope(sp, window),
        "unscoped_top": spans.top_ops(sp, window, spans.UNSCOPED),
        "idle_by_span_ms": spans.idle_by_span(sp, window),
        "gaps_by_span": spans.gaps_by_span(sp, window),
        "idle_gaps": res["breakdown"]["idle_gaps"],
        "device_ops": res["breakdown"]["device_ops"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from harness import runner

    with open(ROOT / "BENCHMARK.json") as f:
        cell = runner.load_cell(json.load(f), args.workload)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro import compile_cache

    compile_cache.enable()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"breakdown: no TPU (JAX found {dev.platform})",
              file=sys.stderr)
        return 2
    res, sp, run = run_keeping_spans(cell, args.seed, args.seconds,
                                     T_START, dev)
    print(json.dumps(report(args.seed, res, sp, run)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Where a cell's tick goes, from the program's own spans and scopes.

    python3 bench/breakdown.py --workload <cell> --seed <n> --seconds <s>

One traced run of the cell, as ``run.py --trace 1`` makes it, with the
program's spans and scoped device ops that the run keeps (``run.spans``,
`harness.spans`).  Prints one JSON line: ``correct``; the window's
summary (the traced run's own p50 and p99, for the cost of tracing);
the cell's per-layer metrics; device self time by scope on each chip;
and, on the cell's first chip, the top-level device ops split by scope,
the ops with the most self time outside every scope, device-idle
milliseconds by innermost program span, and the longest idle gaps, each
named by the program span it fell in.  This is a tool for attributing a
cell's time, not a cell.
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


def report(seed: int, res: dict, run) -> dict:
    from harness import spans

    window = run.spans.window
    sp = run.spans.on(run.spans.devices[0])
    return {
        "seed": seed, "correct": res["correct"], "device": res["device"],
        "window": run.summary,
        "metrics": {k: v["value"] for k, v in res["metrics"].items()},
        "ticks_traced": len(spans.ticks_in(sp, window)),
        "scope_ms": spans.scope_ms(sp, window),
        "scope_ms_by_device": [spans.scope_ms(run.spans.on(d), window)
                               for d in run.spans.devices],
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
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"breakdown: {cell.chips} TPU chips wanted, JAX found "
              f"{len(devices)} {devices[0].platform}", file=sys.stderr)
        return 2
    res, run = runner.run_cell(cell, args.seed, args.seconds, True,
                               T_START, devices[:cell.chips])
    print(json.dumps(report(args.seed, res, run)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

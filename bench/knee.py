"""Knee sweep of one cell: rising offered rates after one set-up.

    python3 bench/knee.py --workload <cell> --seed <n> --seconds <s> \
        --rates r1,r2,...

Builds the cell's system once (as ``run.py`` does), then offers the
cell's traffic mix at each rate in turn (in the mix's arrival unit:
transactions or requests per second) for ``--seconds``, draining between
steps, and prints one JSON line per step: offered and completed
requests per second, p50/p99 latency from the due time, the requests
still unanswered when the step's window closed, and how late the
generator ran.  The knee is the highest rate whose completions keep up
with the offer without a growing backlog; the cell's mix is then set to
0.8 of it.  This is a tool for choosing a cell's rate, not a cell.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]

    from harness import loadgen, runner, system

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
        print(f"knee: {cell.chips} TPU chips wanted, JAX found "
              f"{len(devices)} {devices[0].platform}", file=sys.stderr)
        return 2
    cfg = cell.config
    gen = runner.generator(cell.mix)
    preload = gen.make_preload(args.seed, cfg["preload"])
    fe = system.make_frontend(cfg, devices[:cell.chips])
    gen.write_preload(fe, preload, int(cfg["preload"]["batch"]))

    async def sweep():
        nonlocal preload
        fresh = None
        async with fe:
            fe.warmup()
            runner.say(f"set-up {time.perf_counter() - T_START:.1f} s")
            for i, rate in enumerate(rates):
                sched = gen.make_schedule(args.seed + i, cell.mix, preload,
                                          args.seconds, per_s=rate,
                                          fresh=fresh)
                preload, fresh = sched.after["preload"], sched.after["fresh"]
                t0 = fe.stats["ticks"]
                out = await loadgen.drive(fe, sched.due, sched.reqs,
                                          args.seconds)
                s = loadgen.summarize(out)
                open_at_close = int(np.sum(
                    ~(out.done <= out.seconds)))
                print(json.dumps({
                    "rate": rate, "offered_req_per_s": len(sched)
                    / args.seconds, "ops_per_s": s["ops_per_s"],
                    "p50_ms": s["p50_ms"], "p99_ms": s["p99_ms"],
                    "unanswered_at_close": open_at_close,
                    "failed": s["failed"], "late_p99_ms": s["late_p99_ms"],
                    "ticks": fe.stats["ticks"] - t0}), flush=True)

    asyncio.run(sweep())
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Benchmark entry: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (counted in ``setup_s``, from process start to the first due
request): the preload and the request schedule are generated from
``--seed``; the configuration's front end is built, the preload written
through the writer's mutators in large batches, shipped to the replica,
and every serving phase compiled (``Frontend.warmup``).  Then requests
are offered open loop for ``--seconds``, in-flight ones drained, and
the plain reference checks every answer.

The run needs the chips its cell asks for and exits 2, printing no
result, where JAX finds no TPU or too few.  Diagnostics go to standard
error, whose last lines are the numbers compared with their limits; the
last line of standard output is the result object.  With ``--trace 1``
the metrics are the cell's per-layer ones, read from a profiler trace
of a few seconds in the middle of the window.
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


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        return fail(f"--seed must be >= 0, got {args.seed}")
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"the system under test is not here ({ROOT / 'src'})")

    from harness import runner

    with open(ROOT / "BENCHMARK.json") as f:
        cell = runner.load_cell(json.load(f), args.workload)

    # the compile cache lives inside the checkout, at a fixed path
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro import compile_cache

    compile_cache.enable()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"no TPU: JAX found {devices[0].platform}; nothing run")
    if len(devices) < cell.chips:
        return fail(f"{args.workload} needs {cell.chips} chips, JAX found "
                    f"{len(devices)}")
    dev = devices[0]
    runner.say(f"device: {dev.platform} {dev.device_kind} x {cell.chips} "
               f"(of {len(devices)}); jax {jax.__version__}")
    result, _ = runner.run_cell(cell, args.seed, args.seconds,
                                bool(args.trace), T_START,
                                devices[:cell.chips])
    for name, c in result["checks"].items():
        runner.say(f"{name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

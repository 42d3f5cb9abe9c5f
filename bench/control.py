"""Readings that set the limits of ``correct``: the program, its control
and a planted fault.

    python3 bench/control.py --workload <cell> --seconds <s> \
        --seeds s1,s2,... --control-seeds c1,c2,c3 --fault-seeds f1,f2,f3

In one process (so the compiled programs are shared), runs the cell as
``run.py`` does for each of ``--seeds``, then with the control switched
on for each of ``--control-seeds``, then for each of ``--fault-seeds``
with the insert fold left out from the window's start
(`harness.faults.leave_out_the_fold`), and prints one JSON line per run
with every number compared.  The control is the configuration file's
``control``: a path of the program's own, switched on by overriding
settings, that breaks one guarantee the configuration states (its
``why`` says which).  The control and the fault must each come out not
correct.  The benchmark's own runs never run either.
"""
import time

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args(argv)

    from harness import faults, runner

    with open(ROOT / "BENCHMARK.json") as f:
        cell = runner.load_cell(json.load(f), args.workload)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"control: {cell.chips} TPU chips wanted, JAX found "
              f"{len(devices)} {devices[0].platform}", file=sys.stderr)
        return 2
    control = cell.config["control"]["override"]
    arms = [("program", int(s), None) for s in args.seeds.split(",") if s]
    arms += [("control", int(s), control)
             for s in args.control_seeds.split(",") if s]
    arms += [("fold_left_out", int(s), None)
             for s in args.fault_seeds.split(",") if s]
    for arm, seed, override in arms:
        undo = []
        plant = None
        if arm == "fold_left_out":
            def plant():
                undo.append(faults.leave_out_the_fold())
        res, _ = runner.run_cell(cell, seed, args.seconds, False,
                                 time.perf_counter(), devices[:cell.chips],
                                 override, on_window=plant)
        for fn in undo:
            fn()
        print(json.dumps({
            "arm": arm, "seed": seed, "correct": res["correct"],
            "attempted": res["attempted"], "failed": res["failed"],
            "checks": {k: v["value"] for k, v in res["checks"].items()},
            "metrics": {k: v["value"] for k, v in res["metrics"].items()}}),
            flush=True)
        del res
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())

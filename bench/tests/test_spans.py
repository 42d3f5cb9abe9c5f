"""CPU tests of what reads the program's own instrumentation: the span
loader and its reductions (`harness.spans`), the counter differences
(`harness.counters`), their metric readers, and ``breakdown.py``'s
traced run that keeps the spans."""
import importlib.util
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import counters, loadgen, metric_util, runner, spans
from harness import trace as trace_mod

BENCH = runner.BENCH_DIR
MS = 1_000_000
SPAN_READERS = ("frontend.tick_ms", "device.idle_in_tick_ms_per_tick",
                "engine.cycle_check_ms_per_tick",
                "engine.insert_fold_ms_per_tick",
                "engine.delete_repair_ms_per_tick",
                "engine.summary_upkeep_ms_per_tick")
COUNTER_READERS = ("frontend.queue_wait_p50_ms",
                   "frontend.queue_wait_p99_ms",
                   "engine.insert_steps_useful_pct",
                   "engine.delete_repair_rows_per_product")
EXISTING_READERS = ("loadgen.late_p99_ms", "frontend.requests_per_tick",
                    "writer.device_ms_per_tick", "replica.device_ms_per_tick",
                    "kernel.closure_update_tiled_ms_per_tick",
                    "kernel.closure_delete_tiled_ms_per_tick",
                    "device.idle_pct")


def _read(name, run):
    return runner.load_module(BENCH / "metrics" / f"{name}.py").read(run)


def _op(name, scope, start, dur, module="jit__add_edges_step"):
    return spans.Op(name, module, scope, start * MS, dur * MS)


def _span(name, start, dur, **meta):
    return spans.Span(name, start * MS, dur * MS, meta)


# one 20 ms window, two ticks; the first holds a while loop whose body
# runs a cycle check and a fold, the second a delete repair
OPS = [_op("while.42", "", 0, 10),
       _op("fusion.1", "cycle_check.incremental", 1, 2),
       _op("cond.44", "cycle_check.incremental", 4, 5),
       _op("closure_update_tiled.1", "insert_fold", 5, 2),
       _op("closure_delete_tiled.2", "delete_repair", 12, 3,
           "jit__rep_apply"),
       _op("fusion.9", "summary_upkeep", 15, 1, "jit__rep_apply")]
SPANS = [_span("bench.window", 0, 20),
         _span("frontend.tick", 0, 11, tick=7, n=48),
         _span("writer.add_edges", 0, 10),
         _span("log.flush", 10, 1),
         _span("frontend.tick", 11, 8, tick=8, n=40),
         _span("reader.read", 16, 3)]


def _spans():
    return spans.Spans(list(SPANS), list(OPS))


def test_self_time_goes_to_the_innermost_op():
    self_ns, root = spans.self_times(OPS, (0, 20 * MS))
    # while: [0,1) [3,4) [9,10); cond: [4,5) [7,9); its kernel [5,7)
    assert [ns / MS for ns in self_ns] == [3, 2, 3, 2, 3, 1]
    assert root == [0, 0, 0, 0, 4, 5]
    assert sum(self_ns) == sum(e - s for s, e in spans.busy(OPS))
    clipped, _ = spans.self_times(OPS, (2 * MS, 6 * MS))
    assert [ns / MS for ns in clipped] == [1, 1, 1, 1, 0, 0]
    # an op that starts as its neighbour ends is not inside it
    touching = [_op("while.1", "", 0, 4), _op("copy.2", "", 4, 2)]
    self_ns, root = spans.self_times(touching, (0, 10 * MS))
    assert [ns / MS for ns in self_ns] == [4, 2] and root == [0, 1]


def test_scope_self_time_and_the_split_of_the_top_ops():
    sp = _spans()
    assert spans.scope_ms(sp, (0, 20 * MS)) == pytest.approx(
        {"": 3, "cycle_check.incremental": 5, "insert_fold": 2,
         "delete_repair": 3, "summary_upkeep": 1})
    top = spans.split_by_scope(sp, (0, 20 * MS), n=1)
    assert top == [["jit__add_edges_step", "while.42", pytest.approx(10),
                    pytest.approx({"": 3, "cycle_check.incremental": 5,
                                   "insert_fold": 2})]]
    assert spans.top_ops(sp, (0, 20 * MS), "cycle_check.incremental") == [
        ["jit__add_edges_step", "cond.44", pytest.approx(3), ""],
        ["jit__add_edges_step", "fusion.1", pytest.approx(2), ""]]


def test_idle_inside_ticks_and_outside_them():
    sp = _spans()
    window = sp.window
    busy = spans.busy(sp.ops)          # [0,10) [12,16)
    ticks = spans.ticks_in(sp, window)
    assert [t.meta["tick"] for t in ticks] == [7, 8]
    assert [spans.idle_inside(busy, (t.start, t.end)) / MS
            for t in ticks] == [1, 4]  # [10,11); [11,12) [16,19)
    # idle [10,12) and [16,20): 6 ms, each instant charged to the
    # shortest span running then
    by = spans.idle_by_span(sp, window)
    assert by == pytest.approx({"log.flush": 1, "frontend.tick": 1,
                                "reader.read": 3, "idle host": 1})
    gaps = spans.gaps_by_span(sp, window, n=2)
    assert gaps == [["reader.read", pytest.approx(0.004)],
                    ["frontend.tick", pytest.approx(0.002)]]


def test_scope_of_takes_the_innermost_known_scope():
    assert spans.scope_of("jit(_add_edges_step)/while/body/cond/"
                          "branch_2_fun/cycle_check.incremental/cond/"
                          "insert_fold/transpose") == "insert_fold"
    assert spans.scope_of("jit(_rep_apply)/delete_repair/while/body/"
                          "summary_upkeep/reduce_or") == "summary_upkeep"
    assert spans.scope_of("jit(f)/while/body/add") == spans.UNSCOPED


def test_hlo_op_names_read_the_scopes_from_a_real_trace(tmp_path):
    @jax.jit
    def step(x):
        with jax.named_scope("insert_fold"):
            return jnp.sin(x) @ x

    x = jnp.ones((16, 16))
    step(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        step(x).block_until_ready()
    with open(trace_mod.find_xplane(str(tmp_path)), "rb") as f:
        names = spans.hlo_op_names(f.read())
    progs = [p for p in names if trace_mod.short_name(p) == "jit_step"]
    assert progs, sorted(names)
    scopes = {spans.scope_of(o) for o in names[progs[0]].values()}
    assert "insert_fold" in scopes


def test_histogram_quantile_interpolates_inside_its_bucket():
    edges = [1.0, 2.0, 4.0]
    assert counters.histogram_quantile(edges, [0, 10, 10], 10, 0.5) == \
        pytest.approx(1.5)
    assert counters.histogram_quantile(edges, [5, 5, 10], 10, 0.5) == \
        pytest.approx(1.0)
    assert counters.histogram_quantile(edges, [5, 5, 10], 10, 0.99) == \
        pytest.approx(3.96)
    assert counters.histogram_quantile(edges, [0, 0, 0], 10, 0.5) is None
    assert counters.histogram_quantile(edges, [0, 0, 0], 0, 0.5) is None


def _stats(ticks, waits_ms, work):
    cum = [sum(w <= k for w in waits_ms) for k in range(1, 1001)]
    return {"ticks": ticks, "n_served": 48 * ticks,
            "queue_wait_s": {"edges_s": [k / 1000 for k in range(1, 1001)],
                             "cumulative": cum,
                             "count": len(waits_ms),
                             "sum_s": sum(waits_ms) / 1e3},
            "engine_work": work}


def _work(steps, useful, del_products, del_rows):
    z = {k: 0 for k in ("n_products", "row_products", "n_repair",
                        "n_incremental", "n_partial", "n_steps",
                        "n_useful_steps")}
    return {"remove_vertices": dict(z, n_products=del_products,
                                    row_products=del_rows),
            "remove_edges": dict(z),
            "add_edges": dict(z, n_steps=steps, n_useful_steps=useful)}


def _run(with_spans=True, stats=True):
    tr = trace_mod.Trace(
        [(o.name, o.start, o.dur) for o in OPS],
        [("jit__add_edges_step", 0, 10 * MS),
         ("jit__rep_apply", 12 * MS, 4 * MS)],
        [(s.name, s.start, s.dur) for s in SPANS])
    due = np.array([0.001, 0.005, 0.012, 0.030])
    out = loadgen.Outcome(due, due + [0.001, 0.002, 0.004, 0.0],
                          due + 0.1, np.full(4, 200), np.zeros(4, bool),
                          np.ones(4, bool), np.arange(4), 0.02, [])
    run = runner.Run(None, 1, 20.0, 1.0, out, {}, 0, [0])
    run.trace = trace_mod.reduce([tr], (0, 20 * MS))
    run.traced = (0.0, 0.02)
    if stats:
        start = _stats(10, [5.0] * 100, _work(640, 160, 20, 400))
        stop = _stats(12, [5.0] * 100 + [100.0] * 50 + [300.0] * 50,
                      _work(768, 200, 24, 520))
    else:   # a program without the counters
        start, stop = {"ticks": 10, "n_served": 480}, \
            {"ticks": 12, "n_served": 576}
    run.stats_traced = {"start": start, "stop": stop}
    if with_spans:
        run.spans = _spans()
    return run


def test_counter_readers_read_the_span_difference():
    run = _run()
    assert metric_util.ticks(run) == 2
    assert _read("frontend.queue_wait_p50_ms", run) == pytest.approx(100.0)
    assert _read("frontend.queue_wait_p99_ms", run) == pytest.approx(
        300.0, abs=1.0)
    assert _read("engine.insert_steps_useful_pct", run) == \
        pytest.approx(100 * 40 / 128)
    assert _read("engine.delete_repair_rows_per_product", run) == \
        pytest.approx(120 / 4)


def test_span_readers_read_the_kept_spans():
    run = _run()
    got = {name: _read(name, run) for name in SPAN_READERS}
    assert got == pytest.approx({
        "frontend.tick_ms": 9.5, "device.idle_in_tick_ms_per_tick": 2.5,
        "engine.cycle_check_ms_per_tick": 2.5,
        "engine.insert_fold_ms_per_tick": 1.0,
        "engine.delete_repair_ms_per_tick": 1.5,
        "engine.summary_upkeep_ms_per_tick": 0.5})


def test_readers_find_nothing_where_the_program_reports_nothing():
    bare = _run(with_spans=False, stats=False)
    for name in SPAN_READERS + COUNTER_READERS:
        assert _read(name, bare) is None, name
    unscoped = _run()
    unscoped.spans.ops = [spans.Op(o.name, o.module, spans.UNSCOPED,
                                   o.start, o.dur) for o in OPS]
    assert _read("engine.insert_fold_ms_per_tick", unscoped) is None


def test_existing_readers_unchanged_with_the_spans_kept():
    a, b = _run(with_spans=False), _run()
    for name in EXISTING_READERS:
        assert _read(name, a) == _read(name, b), name
    assert _read("writer.device_ms_per_tick", b) == pytest.approx(5.0)
    assert _read("device.idle_pct", b) == pytest.approx(30.0)


def test_benchmark_lists_the_counter_metrics_for_its_cell():
    with open(BENCH.parent / "BENCHMARK.json") as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in COUNTER_READERS:
        assert listed[name]["source"] == "program_counter"
        assert listed[name]["workloads"] == ["sgt-32k.retire"]
    for name in SPAN_READERS:
        assert listed[name]["source"] in ("device_trace", "program_span")
        assert listed[name]["workloads"] == ["sgt-32k.retire"]
        assert listed[name]["moves"] == "p50_ms"


def test_breakdown_keeps_the_spans_of_a_traced_run():
    spec = importlib.util.spec_from_file_location(
        "bench_breakdown", BENCH / "breakdown.py")
    bd = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bd)
    with open(BENCH.parent / "BENCHMARK.json") as f:
        cell = runner.load_cell(json.load(f), "sgt-32k.retire")
    cell.mix = dict(cell.mix, pred_window=64, read_window=64,
                    arrivals=dict(cell.mix["arrivals"], per_s=20))
    tiny = {"capacity": 512,
            "engine": {"backend": "local", "method": "incremental",
                       "closure_layout": "tiled", "closure_region": 512,
                       "subbatches": 64},
            "preload": {"vertices": 256, "max_preds": 4,
                        "pred_window": 64, "batch": 128}}
    res, run = runner.run_cell(cell, 2**33 + 5, 4.0, True,
                               time.perf_counter(), jax.devices()[:1], tiny)
    assert res["correct"], res["checks"]
    sp = run.spans
    out = bd.report(5, res, run)
    for name in COUNTER_READERS + ("frontend.tick_ms",
                                   "device.idle_in_tick_ms_per_tick"):
        assert out["metrics"][name] is not None, name
    assert out["ticks_traced"] > 5
    assert out["metrics"]["frontend.tick_ms"] > 0
    assert 0 < out["metrics"]["engine.insert_steps_useful_pct"] <= 100
    ticks = spans.ticks_in(sp, sp.window)
    assert {t.meta["tick"] for t in ticks} <= set(
        int(x) for x in run.outcome.tick)
    # a CPU run has no device ops, so the scope readers find nothing
    assert "engine.insert_fold_ms_per_tick" not in out["metrics"]

"""CPU rehearsal of the benchmark harness (run: ``python -m pytest
bench/tests`` with ``JAX_PLATFORMS=cpu``).

Covers the traffic generator, the latency arithmetic, the trace
reduction, finding a cell's files by name, the large-batch preload, and
the comparison that decides ``correct``: it passes on a sound run at a
tiny size and fails when the served path is broken underneath.
"""
import asyncio
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import check, faults, loadgen, runner, system, traffic
from harness import trace as trace_mod

BENCH = runner.BENCH_DIR
SEED = 2**33 + 12345          # seeds may be wider than 32 bits
TINY = {"capacity": 512,
        "engine": {"backend": "local", "method": "incremental",
                   "closure_layout": "tiled", "closure_region": 512,
                   "subbatches": 64},
        "preload": {"vertices": 256, "max_preds": 4, "pred_window": 64,
                    "batch": 128}}


def _bench():
    with open(BENCH.parent / "BENCHMARK.json") as f:
        return json.load(f)


def _mix(name, **arrivals):
    mix = traffic.load(BENCH / "traffic" / f"{name}.json")
    mix = dict(mix, pred_window=64, read_window=64)
    if arrivals:
        mix = dict(mix, arrivals=dict(mix["arrivals"], **arrivals))
    return mix


# ------------------------------------------------------------ traffic

def test_schedule_is_deterministic_per_seed():
    spec = {"vertices": 512, "max_preds": 4, "pred_window": 64}
    k1, e1 = traffic.make_preload(SEED, spec)
    k2, e2 = traffic.make_preload(SEED, spec)
    assert np.array_equal(k1, k2) and np.array_equal(e1, e2)
    m = _mix("retire")
    s1 = traffic.make_schedule(SEED, m, (k1, e1), 2.0)
    s2 = traffic.make_schedule(SEED, m, (k1, e1), 2.0)
    s3 = traffic.make_schedule(SEED + 1, m, (k1, e1), 2.0)
    assert s1.reqs == s2.reqs and np.array_equal(s1.due, s2.due)
    assert s1.reqs != s3.reqs
    # every seed offers the same arrivals and the same set of sizes
    assert s1.arrivals == s3.arrivals == round(m["arrivals"]["per_s"] * 2)
    assert np.array_equal(np.unique(s1.due), np.unique(s3.due))
    kinds = sorted(r[0] for r in s1.reqs)
    assert kinds == sorted(r[0] for r in s3.reqs)


def test_preload_is_acyclic_and_windowed():
    spec = {"vertices": 2048, "max_preds": 4, "pred_window": 128}
    keys, edges = traffic.make_preload(SEED, spec)
    pos = {int(k): i for i, k in enumerate(keys)}
    gap = np.asarray([pos[int(v)] - pos[int(u)] for u, v in edges])
    assert (gap >= 1).all() and (gap <= 128).all()
    assert len(set(map(tuple, edges.tolist()))) == len(edges)
    assert 2.3 < len(edges) / len(keys) < 2.7       # 1..4 uniform: 2.5


def test_retire_mix_keeps_its_proportions():
    keys, edges = traffic.make_preload(SEED, {"vertices": 4096,
                                              "max_preds": 4,
                                              "pred_window": 1024})
    mix = traffic.load(BENCH / "traffic" / "retire.json")
    s = traffic.make_schedule(SEED, mix, (keys, edges), 10.0)
    kinds = [r[0] for r in s.reqs]
    n_tx = s.arrivals
    assert kinds.count("add_vertex") == kinds.count("remove_vertex") == n_tx
    assert kinds.count("reachable") == 2 * n_tx
    assert abs(kinds.count("add_edge") / n_tx - 2.5) < 0.1
    assert abs(len(s) / n_tx - 6.5) < 0.1
    assert abs(kinds.count("reachable") / len(s) - 0.31) < 0.01
    # FIFO retirement holds the live count
    assert len(s.after["preload"][0]) == len(keys)
    # about 5% of the edges are reversed to (t, s)
    rev = cur = 0
    for kind, a, b, _ in s.reqs:
        if kind == "add_vertex":
            cur = a
        elif kind == "add_edge":
            assert cur in (a, b)
            rev += a == cur
    assert rev == round(0.05 * kinds.count("add_edge"))
    # tenants in turn by transaction, all requests of one due together
    assert {r[3] for r in s.reqs} == set(mix["tenants"])
    assert np.all(np.diff(s.due) >= 0)


def test_exact_counts_sum_and_follow_the_weights():
    c = traffic.exact_counts([0.6, 0.2, 0.1, 0.05, 0.05], 1001)
    assert c.sum() == 1001 and list(c) == [601, 200, 100, 50, 50]


# ------------------------------------------------------ latency arithmetic

def _outcome(due, submit, done, status=None, seconds=1.0):
    n = len(due)
    status = np.full(n, 200) if status is None else np.asarray(status)
    return loadgen.Outcome(np.asarray(due, float), np.asarray(submit, float),
                           np.asarray(done, float), status,
                           np.zeros(n, bool), np.ones(n, bool),
                           np.zeros(n, int), seconds, [])


def test_latency_counts_from_the_due_time():
    due = np.linspace(0, 0.99, 100)
    out = _outcome(due, due + 0.010, due + 0.012)
    s = loadgen.summarize(out)
    assert s["p50_ms"] == pytest.approx(12.0)        # not 2 ms
    assert s["late_p50_ms"] == pytest.approx(10.0)
    assert s["ops_per_s"] == pytest.approx(99.0)     # the last ends late


def test_stall_raises_p99_and_drained_requests_count():
    due = np.linspace(0, 0.99, 200)
    done = due + 0.002
    calm = loadgen.summarize(_outcome(due, due, done))
    # a 100 ms stall at 0.5 s delays everything due inside it
    stalled = np.where((due >= 0.5) & (due < 0.6), 0.6 + 0.002, done)
    hit = loadgen.summarize(_outcome(due, due, stalled))
    assert hit["p99_ms"] > 50 > calm["p99_ms"]
    # answered after the window: counted in the latency, not the rate
    late_done = done.copy()
    late_done[-10:] = 1.5
    drained = loadgen.summarize(_outcome(due, due, late_done))
    assert drained["n_latencies"] == 200
    assert drained["p99_ms"] > 400
    assert drained["ops_per_s"] == pytest.approx(190.0)
    lost = _outcome(due, due, done,
                    status=[200] * 199 + [loadgen.STATUS_LOST])
    assert loadgen.summarize(lost)["failed"] == 1


class _FakeFrontend:
    """Answers after ``service_s``; blocks the event loop for
    ``stall_s`` at the first request due after ``stall_at``."""

    def __init__(self, service_s=0.001, stall_at=None, stall_s=0.0):
        self.service_s = service_s
        self.stall_at, self.stall_s = stall_at, stall_s
        self.t0 = None

    async def submit(self, kind, a, b, tenant=None):
        now = time.perf_counter()
        self.t0 = self.t0 or now
        if self.stall_at is not None and now - self.t0 >= self.stall_at:
            self.stall_at = None
            time.sleep(self.stall_s)       # the whole loop stalls
        await asyncio.sleep(self.service_s)

        class R:
            status, stale, ok, tick = 200, False, True, 0
        return R()


def test_drive_times_from_due_and_sees_a_stall():
    due = np.linspace(0, 0.8, 81)
    reqs = [("reachable", 0, 0, "t")] * len(due)
    calm = loadgen.summarize(asyncio.run(loadgen.drive(
        _FakeFrontend(), due, reqs, 1.0)))
    hit = loadgen.summarize(asyncio.run(loadgen.drive(
        _FakeFrontend(stall_at=0.3, stall_s=0.2), due, reqs, 1.0)))
    assert hit["attempted"] == hit["n_latencies"] == 81
    assert calm["p99_ms"] < 60
    # requests due during the stall are submitted late and answered late
    assert hit["late_p99_ms"] > 100 and hit["p99_ms"] > 100


# ---------------------------------------------------------- trace reduction

def test_trace_reduction_on_a_synthetic_event_list():
    ms = 1_000_000
    ops = [("fusion.1 | closure_update_tiled", 0, 2 * ms),
           ("fusion.2", 1 * ms, 2 * ms),            # overlaps the first
           ("custom-call | closure_delete_tiled", 5 * ms, 1 * ms),
           ("copy", 9 * ms, 1 * ms)]
    modules = [("jit__add_edges_step(7)", 0, 3 * ms),
               ("jit__rep_apply(9)", 5 * ms, 1 * ms),
               ("jit__add_edges_step(7)", 9 * ms, 1 * ms)]
    host = [("flush", 3 * ms, 2 * ms), ("tick", 0, 10 * ms)]
    s = trace_mod.reduce([trace_mod.Trace(ops, modules, host)], (0, 10 * ms))
    assert s.window_s == pytest.approx(0.010)
    assert s.busy_s == pytest.approx(0.005)        # [0,3) [5,6) [9,10)
    assert s.idle_pct == pytest.approx(50.0)
    assert s.seconds("module", "_add_edges_step") == pytest.approx(0.004)
    assert s.seconds("module", "_rep_apply") == pytest.approx(0.001)
    assert s.seconds("op", "closure_update_tiled") == pytest.approx(0.002)
    assert s.seconds("op", "closure_delete_tiled") == pytest.approx(0.001)
    assert s.seconds("op", "no_such_kernel") == 0
    # the longest gap [6, 9) ms runs under "tick"; [3, 5) under "flush"
    assert s.gaps[0] == ("tick", pytest.approx(0.003))
    assert s.gaps[1] == ("flush", pytest.approx(0.002))
    clipped = trace_mod.reduce([trace_mod.Trace(ops, modules, host)],
                               (1 * ms, 6 * ms))
    assert clipped.busy_s == pytest.approx(0.003)


def test_short_names_of_ops_and_modules():
    assert trace_mod.short_name(
        "%closure_delete_tiled.3 = (s32[8,128]{1,0}) custom-call(s32[64])"
    ) == "closure_delete_tiled.3"
    assert trace_mod.short_name("jit__rep_apply(3212249330409108345)") == \
        "jit__rep_apply"
    assert trace_mod.short_name("copy") == "copy"


def test_union_merges_nested_and_touching_intervals():
    assert trace_mod.union([(5, 6), (0, 3), (1, 2), (3, 4)]) == \
        [(0, 4), (5, 6)]


# ------------------------------------------------------- found by name

def test_files_dropped_in_by_name_are_found(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "configs" / "cfg-x.json").write_text('{"capacity": 64}')
    (tmp_path / "traffic" / "mix-y.json").write_text(
        '{"arrivals": {"per_s": 5}}')
    (tmp_path / "metrics" / "layer.new_metric.py").write_text(
        "def read(run):\n    return run.seconds * 2\n")
    (tmp_path / "metrics" / "silent.py").write_text(
        "def read(run):\n    return None\n")
    bench = {"workloads": [{"name": "cfg-x.mix-y", "config": "cfg-x",
                            "traffic": "mix-y", "chips": 1}],
             "end_to_end": [],
             "per_layer": [{"name": "layer.new_metric", "unit": "x"},
                           {"name": "silent", "unit": "x"}]}
    cell = runner.load_cell(bench, "cfg-x.mix-y", tmp_path)
    assert cell.config == {"capacity": 64}
    assert cell.mix["arrivals"]["per_s"] == 5
    assert [m["name"] for m in cell.per_layer] == ["layer.new_metric",
                                                   "silent"]

    class R:
        seconds = 3.0
    got = runner.read_metrics(cell.per_layer, R(), tmp_path)
    assert got == {"layer.new_metric": {"value": 6.0, "unit": "x"}}


def test_every_metric_and_cell_of_the_benchmark_has_its_files():
    bench = _bench()
    for w in bench["workloads"]:
        cell = runner.load_cell(bench, w["name"])
        assert cell.config["name"] == w["config"]
        assert (BENCH / "reference"
                / f"{cell.config['reference']}.py").is_file()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]


# ------------------------------------------------ preload in large batches

def test_large_batch_preload_equals_serving_shape_preload():
    keys, edges = traffic.make_preload(SEED, TINY["preload"])
    cfg = dict(_bench_cfg("sgt-32k"), **TINY)
    big = system.make_frontend(cfg)
    small = system.make_frontend(cfg)
    assert system.preload(big.primary, keys, edges, 128) == \
        (len(keys), len(edges))
    # the serving shape: one padded batch of 64 per call
    assert system.preload(small.primary, keys, edges, 64) == \
        (len(keys), len(edges))
    a, b = big.primary.engine, small.primary.engine
    u, v = jnp.asarray(edges[:, 0]), jnp.asarray(edges[:, 1])
    assert bool(jnp.all(a.contains_edges(u, v)))
    assert int(a.edge_count()) == int(b.edge_count()) == len(edges)
    rng = np.random.default_rng(1)
    f = jnp.asarray(keys[rng.integers(0, len(keys), 512)])
    t = jnp.asarray(keys[rng.integers(0, len(keys), 512)])
    ra = np.asarray(big.primary.snapshot().reachable(f, t))
    rb = np.asarray(small.primary.snapshot().reachable(f, t))
    assert np.array_equal(ra, rb) and 0 < ra.sum() < 512


def _bench_cfg(name):
    return traffic.load(BENCH / "configs" / f"{name}.json")


# ------------------------------------------------- the correctness check

def _cell(name, per_s):
    cell = runner.load_cell(_bench(), name)
    cell.mix = _mix(cell.mix.get("name", name.split(".")[1]), per_s=per_s)
    return cell


def _run(name, per_s, seed=SEED, override=None):
    cfg = dict(TINY, **(override or {}))
    return runner.run_cell(_cell(name, per_s), seed, 2.0, False,
                           time.perf_counter(), jax.devices()[:1], cfg)[0]


def test_sound_run_is_correct():
    res = _run("sgt-32k.retire", 40)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 50 and res["failed"] == 0
    assert set(res["metrics"]) == {"ops_per_s", "p50_ms", "p99_ms",
                                   "setup_s"}     # no device memory on CPU
    assert list(res)[-1] == "checks"


def _flip_one(fe_cls, kind):
    """Patch the tick so the first answer of ``kind`` after a few ticks
    goes out flipped."""
    orig = fe_cls._commit_sync
    state = {"done": False}

    def commit(self, batch):
        out = orig(self, batch)
        if state["done"] or self._tick_no < 3:
            return out
        for i, (req, resp) in enumerate(out):
            if req.kind == kind:
                import dataclasses
                out[i] = (req, dataclasses.replace(resp, ok=not resp.ok))
                state["done"] = True
                break
        return out
    return commit


@pytest.mark.parametrize("kind,number", [("add_edge", "accept_bits_wrong"),
                                         ("reachable", "reads_wrong")])
def test_one_flipped_answer_fails(monkeypatch, kind, number):
    from repro.serve import frontend
    monkeypatch.setattr(frontend.Frontend, "_commit_sync",
                        _flip_one(frontend.Frontend, kind))
    res = _run("sgt-32k.retire", 40)
    assert not res["correct"]
    assert res["checks"][number]["value"] == 1


def test_writer_step_that_keeps_its_state_fails(monkeypatch):
    """The edge-insert step answers but returns the engine unchanged."""
    from repro import replica
    orig = replica._add_edges_step

    def unchanged(engine, us, vs, valid):
        _, res, delta = orig(engine, us, vs, valid)
        return engine, res, delta
    monkeypatch.setattr(replica, "_add_edges_step", unchanged)
    res = _run("sgt-32k.retire", 40)
    assert not res["correct"]
    assert res["checks"]["edges_wrong"]["value"] > 0


def test_half_the_batch_left_out_fails(monkeypatch):
    """Every phase drops the second half of its padded batch."""
    from repro.serve import frontend
    orig = frontend.Frontend._pad

    def half(self, reqs):
        return orig(self, reqs[:(len(reqs) + 1) // 2])
    monkeypatch.setattr(frontend.Frontend, "_pad", half)
    res = _run("sgt-32k.retire", 60)
    assert not res["correct"]


def test_control_fails():
    """The configuration's control (a path of the program's own that
    breaks one stated guarantee) comes out not correct."""
    cell = _cell("sgt-32k.retire", 40)
    control = cell.config["control"]["override"]
    res, _ = runner.run_cell(cell, SEED, 2.0, False, time.perf_counter(),
                             jax.devices()[:1], runner.merged(TINY, control))
    assert not res["correct"]
    wrong = {k: v["value"] for k, v in res["checks"].items() if v["value"]}
    assert wrong.keys() & {"accept_bits_wrong", "reads_wrong",
                           "stale_reads"}, wrong


def test_insert_fold_left_out_fails():
    """From the window's start, accepted edges reach the adjacency but
    never the closure."""
    undo = []
    try:
        res, _ = runner.run_cell(
            _cell("sgt-32k.retire", 40), SEED, 2.0, False,
            time.perf_counter(), jax.devices()[:1], TINY,
            on_window=lambda: undo.append(faults.leave_out_the_fold()))
    finally:
        for fn in undo:
            fn()
    assert undo and not res["correct"]
    assert res["checks"]["window_edge_reads_wrong"]["value"] > 0


def test_window_edge_queries_read_through_the_window_edges():
    ref = _reference([0, 1, 2, 3, 4, 5], [(0, 1), (1, 2), (3, 4)])
    trace = [("add_edge", 2, 3, True), ("add_edge", 4, 5, True),
             ("add_edge", 5, 0, False), ("remove_edge", 4, 5, True),
             ("reachable", 0, 5, False)]
    for kind, a, b, _ in trace:
        ref.apply(kind, a, b)
    f, t, want = check.window_edge_queries(ref, trace, SEED)
    # only (2, 3) is accepted in the window and still there; read both ways
    assert len(want) == 2 and list(want) == [True, False]
    assert f[0] in (0, 1, 2) and t[0] in (3, 4)
    assert (f[1], t[1]) == (t[0], f[0])


def _reference(keys, edges):
    ref_mod = runner.load_module(BENCH / "reference" / "seqgraph.py")
    return ref_mod.build(keys, edges)


def test_client_mismatch_matching_within_a_tick():
    trace = [("add_edge", 1, 2, True), ("add_edge", 1, 2, True),
             ("reachable", 1, 2, True)]
    want = [True, True, True]
    reqs = [("reachable", 1, 2, "t"), ("add_edge", 1, 2, "t"),
            ("add_edge", 1, 2, "t")]
    status = [200, 200, 200]
    assert check.client_mismatches(trace, want, reqs, status,
                                   [True, True, True], [0, 0, 0]) == \
        (0, 0, 0)
    assert check.client_mismatches(trace, want, reqs, status,
                                   [False, True, True], [0, 0, 0]) == \
        (0, 1, 0)

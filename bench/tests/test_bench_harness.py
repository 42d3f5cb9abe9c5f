"""CPU tests of what lets a deployment join the benchmark as new files:
the traffic generator found by name, the whole engine block handed to
the program (with a mesh over the cell's chips where it is sharded),
memory and trace reduced over several chips, and the program's spans
kept on a traced `Run` for the metric readers.

The digests pin the work of ``sgt-32k.retire``: the default generator
has to give the preload and schedule it gave before the generator could
be named."""
import hashlib
import json
import shutil
import textwrap
import time

import jax
import numpy as np
import pytest

from harness import check, runner, spans, system, traffic
from harness import trace as trace_mod

BENCH = runner.BENCH_DIR
MS = 1_000_000

# digests of (keys, edges) and of (due, requests, arrivals) of a 2 s
# window, from the generator as it was before it could be named
DIGESTS = {1: ("65530941d2093f45", "3d5058075db67fff"),
           2**33 + 12345: ("31a8580653e94ee6", "25eeb1b800be99d6")}


def _digest(*parts) -> str:
    h = hashlib.sha256()
    for p in parts:
        if isinstance(p, np.ndarray):
            h.update(str(p.dtype).encode())
            h.update(str(p.shape).encode())
            h.update(np.ascontiguousarray(p).tobytes())
        else:
            h.update(json.dumps(p).encode())
    return h.hexdigest()[:16]


def _bench():
    with open(BENCH.parent / "BENCHMARK.json") as f:
        return json.load(f)


# ------------------------------------------------------------- generators

@pytest.mark.parametrize("seed", sorted(DIGESTS))
def test_retire_preload_and_schedule_are_unchanged(seed):
    cell = runner.load_cell(_bench(), "sgt-32k.retire")
    gen = runner.generator(cell.mix)
    keys, edges = gen.make_preload(seed, cell.config["preload"])
    sched = gen.make_schedule(seed, cell.mix, (keys, edges), 2.0)
    assert (_digest(keys, edges),
            _digest(sched.due, [list(r) for r in sched.reqs],
                    sched.arrivals)) == DIGESTS[seed]


def test_mix_without_a_generator_gets_the_default_functions():
    gen = runner.generator(traffic.load(BENCH / "traffic" / "retire.json"))
    assert gen.make_preload is traffic.make_preload
    assert gen.make_schedule is traffic.make_schedule
    assert gen.write_preload is system.write_preload


CHAIN = '''
"""Throwaway generator: a chain preload, then each transaction adds its
vertex, one edge from the newest live vertex, and a path read."""
import numpy as np

from harness import system, traffic


def make_preload(seed, spec):
    rng = np.random.default_rng([seed, 1])
    keys = rng.permutation(int(spec["vertices"])).astype(np.int32)
    return keys, np.stack([keys[:-1], keys[1:]], axis=1)


def make_schedule(seed, mix, preload, seconds, per_s=None, fresh=None):
    keys, edges = preload
    n = int(round((per_s or mix["per_s"]) * seconds))
    due = np.repeat(np.linspace(0, seconds, n, endpoint=False), 3)
    live = [int(k) for k in keys]
    fresh = int(keys.max()) + 1 if fresh is None else fresh
    reqs = []
    for _ in range(n):
        reqs += [("add_vertex", fresh, 0, "t0"),
                 ("add_edge", live[-1], fresh, "t0"),
                 ("reachable", live[0], fresh, "t0")]
        live.append(fresh)
        fresh += 1
    return traffic.Schedule(due, reqs, n, {
        "preload": (np.asarray(live, np.int32), edges), "fresh": fresh})


def write_preload(fe, preload, batch):
    return system.write_preload(fe, preload, batch)
'''


def _new_deployment(root):
    """A deployment of another traffic shape, as new files under ``root``
    (a config, a mix naming a generator, the generator, a copy of the
    reference, a metric reader) and the BENCHMARK.json entries."""
    for d in ("configs", "traffic", "generators", "reference", "metrics"):
        (root / d).mkdir()
    cfg = traffic.load(BENCH / "configs" / "sgt-32k.json")
    cfg = dict(cfg, name="chain-512", capacity=512,
               engine=dict(cfg["engine"], closure_region=512),
               preload={"vertices": 128, "batch": 64})
    (root / "configs" / "chain-512.json").write_text(json.dumps(cfg))
    (root / "traffic" / "chain.json").write_text(json.dumps(
        {"generator": "chain", "per_s": 20}))
    (root / "generators" / "chain.py").write_text(textwrap.dedent(CHAIN))
    shutil.copy(BENCH / "reference" / "seqgraph.py",
                root / "reference" / "seqgraph.py")
    (root / "metrics" / "ops_per_s.py").write_text(
        "def read(run):\n    return run.summary['ops_per_s']\n")
    (root / "metrics" / "chain.ticks.py").write_text(
        "def read(run):\n    return run.stats_traced and "
        "run.stats_traced['stop']['ticks']\n")
    return {"workloads": [{"name": "chain-512.chain", "config": "chain-512",
                           "traffic": "chain", "chips": 1}],
            "end_to_end": [{"name": "ops_per_s", "unit": "ops/s"}],
            "per_layer": [{"name": "chain.ticks", "unit": "ticks",
                           "workloads": ["chain-512.chain"]},
                          {"name": "elsewhere", "unit": "x",
                           "workloads": ["other.cell"]}]}


def test_generator_and_config_dropped_in_by_name_are_found(tmp_path):
    bench = _new_deployment(tmp_path)
    cell = runner.load_cell(bench, "chain-512.chain", tmp_path)
    assert cell.config["capacity"] == 512
    # a metric listed for other cells only is not this cell's
    assert [m["name"] for m in cell.per_layer] == ["chain.ticks"]
    gen = runner.generator(cell.mix, tmp_path)
    assert gen.make_preload.__module__ == gen.write_preload.__module__
    keys, edges = gen.make_preload(7, cell.config["preload"])
    sched = gen.make_schedule(7, cell.mix, (keys, edges), 1.0)
    assert len(sched) == 60 and sched.arrivals == 20
    assert sched.after["fresh"] == 128 + 20


@pytest.fixture(scope="module")
def new_cell_run(tmp_path_factory):
    """One traced run of the dropped-in deployment, at 512 slots."""
    root = tmp_path_factory.mktemp("bench")
    bench = _new_deployment(root)
    cell = runner.load_cell(bench, "chain-512.chain", root)
    return runner.run_cell(cell, 2**33 + 9, 2.0, True, time.perf_counter(),
                           jax.devices()[:1], bench_dir=root)


def test_new_deployment_runs_from_new_files_only(new_cell_run):
    res, run = new_cell_run
    assert res["correct"], res["checks"]
    assert res["checks"]["preload_rejected"]["value"] == 0
    assert res["attempted"] == 120 and res["failed"] == 0
    assert res["metrics"]["chain.ticks"]["value"] > 0
    assert res["device"]["memory_peak_bytes_by_device"] == [-1]  # CPU


def test_a_traced_run_keeps_its_spans(new_cell_run):
    _, run = new_cell_run
    assert isinstance(run.spans, spans.Spans)
    assert run.spans.window is not None
    assert spans.ticks_in(run.spans, run.spans.window)


def test_reads_are_the_references_read_kinds():
    trace = [("add_edge", 1, 2, True), ("path", 1, 2, True)]
    reqs = [("add_edge", 1, 2, "t"), ("path", 1, 2, "t")]
    args = (trace, [True, True], reqs, [200, 200], [True, False], [0, 0])
    assert check.client_mismatches(*args) == (1, 0, 0)
    assert check.client_mismatches(*args, read_kinds=("path",)) == (0, 1, 0)


# ----------------------------------------------------------- engine block

@pytest.fixture
def created(monkeypatch):
    from repro.serve import Frontend

    calls = []
    monkeypatch.setattr(Frontend, "create", classmethod(
        lambda cls, capacity, config, **kw: calls.append(
            (capacity, config, kw)) or "frontend"))
    return calls


def test_make_frontend_hands_over_the_whole_engine_block(created):
    cfg = traffic.load(BENCH / "configs" / "sgt-32k.json")
    assert system.make_frontend(cfg, jax.devices()[:1]) == "frontend"
    (capacity, fe_cfg, kw), = created
    assert capacity == 32768 and fe_cfg.batch_size == 64
    # the options before the engine block was handed over whole, and the
    # backend, which is DagEngine.create's default
    assert kw == {"method": "incremental", "subbatches": 64,
                  "closure_layout": "tiled", "closure_region": 32768,
                  "backend": "local"}


def test_sharded_engine_gets_a_mesh_over_the_cells_chips(created,
                                                         monkeypatch):
    from repro.core import sharded

    meshes = []
    monkeypatch.setattr(sharded, "make_dag_mesh",
                        lambda devices=None: meshes.append(devices) or "mesh")
    cfg = traffic.load(BENCH / "configs" / "sgt-32k.json")
    cfg = dict(cfg, engine=dict(cfg["engine"], backend="sharded"))
    chips = ["chip0", "chip1", "chip2", "chip3"]
    system.make_frontend(cfg, chips)
    assert meshes == [chips]
    assert created[0][2]["mesh"] == "mesh"
    assert created[0][2]["backend"] == "sharded"


@pytest.mark.parametrize("key", ["no_such_option", "mesh", "capacity"])
def test_engine_key_the_engine_does_not_take_is_refused(created, key):
    cfg = traffic.load(BENCH / "configs" / "sgt-32k.json")
    cfg = dict(cfg, engine=dict(cfg["engine"], **{key: 1}))
    with pytest.raises(ValueError, match=key):
        system.make_frontend(cfg)
    assert not created


# ------------------------------------------------ memory over the chips

class _Chip:
    def __init__(self, in_use, peak, id=0):
        self.stats = {"bytes_in_use": in_use, "peak_bytes_in_use": peak}
        self.id = id

    def memory_stats(self):
        return self.stats


def test_memory_is_the_sum_in_use_and_each_chips_peak():
    assert runner.memory([_Chip(100, 300)]) == (100, [300])
    chips = [_Chip(100, 300), _Chip(120, 500), _Chip(90, 200)]
    resident, peaks = runner.memory(chips)
    assert resident == 310 and peaks == [300, 500, 200]
    assert max(peaks) == 500
    silent = _Chip(0, 0)
    silent.stats = None
    assert runner.memory([_Chip(100, 300), silent]) == (-1, [300, -1])


# ------------------------------------------------- trace over the chips

def _trace(busy_ms, step_ms, host=()):
    """A chip that runs one op of ``busy_ms`` inside a writer step of
    ``step_ms``, from 0."""
    return trace_mod.Trace([("fusion.1", 0, busy_ms * MS)],
                           [("jit__add_edges_step", 0, step_ms * MS)],
                           list(host))


def test_one_chip_reduces_to_its_own_numbers():
    host = [("tick", 0, 10 * MS)]
    s = trace_mod.reduce([_trace(4, 6, host)], (0, 10 * MS))
    assert s.busy_s == s.busy_s_by_device[0] == pytest.approx(0.004)
    assert s.idle_pct == pytest.approx(60.0)
    assert s.seconds("module", "_add_edges_step") == pytest.approx(0.006)
    assert s.gaps == [("tick", pytest.approx(0.006))]
    assert trace_mod.mean_by(s.by_op) == s.by_op[0]


def test_busy_is_the_mean_and_step_time_the_slowest_chip():
    chips = [_trace(4, 6), _trace(8, 9), _trace(2, 3), _trace(6, 6)]
    s = trace_mod.reduce(chips, (0, 10 * MS))
    assert s.busy_s_by_device == pytest.approx([0.004, 0.008, 0.002,
                                                0.006])
    assert s.busy_s == pytest.approx(0.005)
    assert s.idle_pct == pytest.approx(50.0)
    assert s.seconds("module", "_add_edges_step") == pytest.approx(0.009)
    assert s.seconds("op", "fusion") == pytest.approx(0.008)
    assert trace_mod.mean_by(s.by_op) == {"fusion.1": pytest.approx(0.005)}
    assert s.gaps[0][1] == pytest.approx(0.008)     # chip 2's idle


def test_trace_planes_are_the_cells_chips():
    assert trace_mod.device_planes([_Chip(0, 0, i) for i in range(4)]) == \
        [f"/device:TPU:{i}" for i in range(4)]


def _spans_on_chips():
    """Two ticks of 10 ms; chip 0 folds 4 ms, chip 1 folds 6 ms and
    repairs 2 ms, both in the first tick."""
    ops = [spans.Op("fold.0", "jit__add_edges_step", "insert_fold", 0,
                    4 * MS, "", 0),
           spans.Op("fold.0", "jit__add_edges_step", "insert_fold", 0,
                    6 * MS, "", 1),
           spans.Op("rep.1", "jit__add_edges_step", "delete_repair", 6 * MS,
                    2 * MS, "", 1)]
    sp = [spans.Span("bench.window", 0, 20 * MS, {}),
          spans.Span("frontend.tick", 0, 10 * MS, {"tick": 1}),
          spans.Span("frontend.tick", 10 * MS, 10 * MS, {"tick": 2})]
    return spans.Spans(sp, ops)


def _read(name, run):
    return runner.load_module(BENCH / "metrics" / f"{name}.py").read(run)


def test_span_readers_take_the_slowest_chip_and_the_mean_idle():
    class R:
        spans = _spans_on_chips()
        stats_traced = {"start": {"ticks": 0}, "stop": {"ticks": 2}}
    assert R.spans.devices == [0, 1]
    assert _read("engine.insert_fold_ms_per_tick", R) == pytest.approx(3.0)
    assert _read("engine.delete_repair_ms_per_tick", R) == pytest.approx(1.0)
    # idle in ticks: chip 0 16 ms, chip 1 12 ms, over 2 ticks
    assert _read("device.idle_in_tick_ms_per_tick", R) == pytest.approx(7.0)
    one = spans.Spans(R.spans.spans, R.spans.on(0).ops)

    class R0(R):
        spans = one
    assert _read("engine.insert_fold_ms_per_tick", R0) == pytest.approx(2.0)
    assert _read("device.idle_in_tick_ms_per_tick", R0) == pytest.approx(8.0)

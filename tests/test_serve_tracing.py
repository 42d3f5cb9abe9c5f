"""The served tick's own instrumentation (`repro.serve.frontend`).

* host spans: ``frontend.tick`` (with the ``tick`` its requests' responses
  carry) and, nested inside it on the executor thread, the writer
  phases, ``log.flush``, ``replica.apply`` and ``reader.read``, written
  into the profiler's trace;
* device scopes: the compiled writer steps carry the engine's named
  scopes in their HLO ``op_name`` metadata;
* counters in ``Frontend.stats``: the queue-wait histogram, and the
  per-phase `ReachStats` sums, which must equal an eager replay of the
  same calls; ``stats`` reads no device value and returns copies, and
  ``warmup`` leaves every counter as it found it.
"""
import asyncio
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import DagEngine
from repro.core.engine import TALLY_COUNTS
from repro.replica import (_add_edges_step, _remove_vertices_step)
from repro.serve import Frontend, FrontendConfig
from repro.serve.frontend import Request, WAIT_EDGES_S, WORK_PHASES

B = 8
CAPACITY = 64
ENGINE = dict(closure_layout="tiled", closure_region=CAPACITY)
NESTED = ("writer.remove_vertices", "writer.add_vertices",
          "writer.remove_edges", "writer.add_edges", "log.flush",
          "replica.apply", "reader.read")


def _frontend():
    return Frontend.create(CAPACITY, FrontendConfig(
        batch_size=B, reader="replica", replicas=1), **ENGINE)


def _req(kind, a, b=0):
    return Request(kind, a, b, "t", None, 0.0)


def _serve(fe, reqs):
    """Submit ``reqs`` concurrently through the coalescer; responses in
    submission order."""

    async def go():
        async with fe:
            return await asyncio.gather(
                *[fe.submit(k, a, b) for k, a, b in reqs])

    return asyncio.run(go())


# a chain 0 -> 1 -> 2 -> 3, a retirement, a rejected back edge and reads
TICKS = [
    [_req("add_vertex", k) for k in range(6)],
    [_req("add_edge", 0, 1), _req("add_edge", 1, 2), _req("add_edge", 2, 3),
     _req("reachable", 0, 3)],
    [_req("add_edge", 3, 0), _req("remove_edge", 1, 2),
     _req("add_edge", 3, 4), _req("reachable", 0, 2)],
    [_req("remove_vertex", 0), _req("add_edge", 4, 5),
     _req("add_vertex", 9), _req("remove_edge", 2, 3)],
]


# ---------------------------------------------------------- host spans

def test_tick_spans_carry_the_responses_tick_and_nest_the_phases(tmp_path):
    from jax.profiler import ProfileData

    fe = _frontend()
    reqs = [("add_vertex", k, 0) for k in range(6)]
    warm = _serve(fe, reqs)
    assert all(r.status == 200 for r in warm)
    with jax.profiler.trace(str(tmp_path)):
        resps = _serve(fe, [("add_edge", 0, 1), ("add_edge", 1, 2),
                            ("reachable", 0, 2), ("remove_vertex", 5, 0)])
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    by_line = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                by_line.append([(e.name, e.start_ns, e.start_ns
                                 + e.duration_ns,
                                 dict(e.stats) if e.name == "frontend.tick"
                                 else {}) for e in line.events])
    ticks = [e for line in by_line for e in line if e[0] == "frontend.tick"]
    assert {int(e[3]["tick"]) for e in ticks} == {r.tick for r in resps}
    assert sum(int(e[3]["n"]) for e in ticks) == len(resps)
    assert any(e[0] == "frontend.coalesce" for line in by_line
               for e in line)
    for line in by_line:
        # the executor thread's line; start() and stop() flush and ship
        # the log from the event loop's, outside any tick
        spans = [e for e in line if e[0] == "frontend.tick"]
        if not spans:
            continue
        for name in NESTED:
            for _, s, t, _ in (e for e in line if e[0] == name):
                assert any(a <= s and t <= b for _, a, b, _ in spans), name
    seen = {e[0] for line in by_line for e in line}
    assert set(NESTED) <= seen


# ------------------------------------------------------- device scopes

def _engine_args():
    eng = DagEngine.create(CAPACITY, method="incremental", subbatches=B,
                           **ENGINE)
    keys = jnp.arange(B, dtype=jnp.int32)
    return eng, keys, jnp.ones(B, bool)


@pytest.mark.parametrize("step,scopes", [
    ("add_edges", ("cycle_check.incremental", "insert_fold",
                   "slot_lookup")),
    ("remove_vertices", ("delete_repair", "summary_upkeep")),
])
def test_compiled_writer_steps_carry_the_engine_scopes(step, scopes):
    eng, keys, valid = _engine_args()
    if step == "add_edges":
        lowered = _add_edges_step.lower(eng, keys, keys[::-1], valid)
    else:
        lowered = _remove_vertices_step.lower(eng, keys, valid)
    names = set(re.findall(r'op_name="([^"]*)"',
                           lowered.compile().as_text()))
    parts = {p for n in names for p in n.split("/")}
    for scope in scopes:
        assert scope in parts, (scope, sorted(parts))


# ------------------------------------------------------------ counters

def test_queue_wait_histogram_counts_every_served_request():
    fe = _frontend()
    reqs = [("add_vertex", k, 0) for k in range(20)] + \
        [("reachable", k, k + 1) for k in range(12)]
    resps = _serve(fe, reqs)
    q = fe.stats["queue_wait_s"]
    assert q["count"] == fe.n_served == len(resps) == len(reqs)
    assert q["edges_s"] == list(WAIT_EDGES_S)
    assert len(q["cumulative"]) == len(WAIT_EDGES_S)
    assert all(a <= b for a, b in zip(q["cumulative"], q["cumulative"][1:]))
    assert q["cumulative"][-1] == q["count"]     # every wait under 100 s
    assert 0 < q["sum_s"] < 100 * q["count"]


def _eager_work(ticks):
    """The same phase calls as the front end's ticks, on an eager engine:
    per phase, the `ReachStats` counts summed."""
    eng = DagEngine.create(CAPACITY, method="incremental", subbatches=B,
                           **ENGINE)
    want = {ph: np.zeros(len(TALLY_COUNTS), np.int64) for ph in WORK_PHASES}

    def pad(rs):
        a, b, m = (np.zeros(B, np.int32), np.zeros(B, np.int32),
                   np.zeros(B, bool))
        for i, r in enumerate(rs):
            a[i], b[i], m[i] = r.a, r.b, True
        return jnp.asarray(a), jnp.asarray(b), jnp.asarray(m)

    for batch in ticks:
        kinds = {k: [r for r in batch if r.kind == k]
                 for k in ("remove_vertex", "add_vertex", "remove_edge",
                           "add_edge")}
        if not any(kinds.values()):
            continue
        for kind, phase in (("remove_vertex", "remove_vertices"),
                            ("add_vertex", "add_vertices"),
                            ("remove_edge", "remove_edges"),
                            ("add_edge", "add_edges")):
            a, b, m = pad(kinds[kind])
            if phase in ("remove_vertices", "add_vertices"):
                eng, res = getattr(eng, phase)(a, valid=m)
            elif phase == "remove_edges":
                eng, res = eng.remove_edges(a, b, valid=m)
            else:
                eng, res = eng.add_edges_acyclic(a, b, valid=m)
            if phase in want:
                want[phase] += [int(getattr(res.stats, k))
                                for k in TALLY_COUNTS]
    return {ph: dict(zip(TALLY_COUNTS, map(int, w)))
            for ph, w in want.items()}


def test_engine_work_equals_an_eager_replay_of_the_same_calls():
    fe = _frontend()
    for batch in TICKS:
        fe._commit_sync(batch)
    got = fe.stats["engine_work"]
    assert got == _eager_work(TICKS)
    assert got["remove_vertices"]["n_repair"] >= 1
    assert got["remove_vertices"]["n_products"] >= 1
    # one incremental check per sub-batch the insert loop ran: a
    # sub-batch of one row, so one per add-edge request
    n_edges = sum(r.kind == "add_edge" for batch in TICKS for r in batch)
    assert got["add_edges"]["n_incremental"] == n_edges


@pytest.mark.parametrize("n_edges", [0, 3, 8])
def test_insert_steps_useful_counts_steps_with_a_candidate(n_edges):
    fe = _frontend()
    fe._commit_sync([_req("add_vertex", k) for k in range(B)])
    fe._commit_sync([_req("add_vertex", B)])
    before = fe.stats["engine_work"]["add_edges"]
    batch = [_req("add_edge", k, k + 1) for k in range(n_edges)]
    fe._commit_sync(batch or [_req("add_vertex", 50)])
    after = fe.stats["engine_work"]["add_edges"]
    ran = after["n_steps"] - before["n_steps"]
    skipped = after["n_skipped_steps"] - before["n_skipped_steps"]
    assert ran == n_edges                 # the all-padding steps skipped
    assert ran + skipped == B
    assert after["n_useful_steps"] - before["n_useful_steps"] == n_edges


def test_stats_reads_no_device_value_and_returns_copies():
    fe = _frontend()
    out = fe._commit_sync(TICKS[0])
    engine = fe.primary.engine
    fe.primary.engine = None            # any device read would now fail
    try:
        s = fe.stats
    finally:
        fe.primary.engine = engine
    assert s["epoch"] == out[-1][1].epoch == int(engine.epoch)
    fe._commit_sync(TICKS[1])
    fe.queue_wait.record(0.01)
    assert s["engine_work"]["add_edges"]["n_useful_steps"] == 0
    assert s["queue_wait_s"]["count"] == 0
    assert fe.stats["engine_work"]["add_edges"]["n_useful_steps"] == 3


def test_warmup_leaves_the_counters_clean():
    fe = _frontend()
    _serve(fe, [("add_vertex", k, 0) for k in range(4)])
    before = fe.stats
    fe.warmup()
    assert fe.stats == before

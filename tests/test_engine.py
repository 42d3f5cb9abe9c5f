"""Unified `DagEngine` session API tests (`core/engine.py`, `repro.api`).

Pins the tentpole contracts:
  1. engine-vs-oracle equivalence on random mixed `OpBatch` streams;
  2. local-vs-sharded backend result equality on identical OpBatch streams
     (the in-process single-device mesh; the 8-device check lives in
     tests/test_sharded_dag.py);
  3. the engine is a real pytree: flatten/unflatten round-trips, sessions
     jit, and a scanned 50-tick SGT session compiles exactly once;
  4. measured deciding depths feed the cost model: the EMA seeds
     `CostModelPolicy`'s depth estimate and can flip its decision;
  5. the mutation epoch leaf versions every commit (bumped by mutators,
     preserved by grow/views) and unknown methods fail at configuration
     time with the nearest valid name.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (CostModelPolicy, DagEngine, FixedPolicy, OpBatch,
                       OpResult, ReachStats)
from repro.core import acyclic, dag, dispatch, reachability, sgt
from repro.core.oracle import SeqGraph, apply_op_batch_oracle

CAP = 64
OP_CODES = [dag.REMOVE_VERTEX, dag.ADD_VERTEX, dag.REMOVE_EDGE,
            dag.ADD_EDGE, dag.CONTAINS_VERTEX, dag.CONTAINS_EDGE]


def arr(xs, dtype=jnp.int32):
    return jnp.asarray(xs, dtype)


def _rand_batch(rng, n=6, key_space=12) -> OpBatch:
    return OpBatch(jnp.asarray(rng.choice(OP_CODES, n), jnp.int32),
                   jnp.asarray(rng.integers(0, key_space, n), jnp.int32),
                   jnp.asarray(rng.integers(0, key_space, n), jnp.int32))


# ------------------------------------------------------- typed batch types

def test_opbatch_constructors_and_concat():
    b = OpBatch.concat(OpBatch.add_vertices(arr([1, 2])),
                       OpBatch.add_edges(arr([1]), arr([2])),
                       OpBatch.contains_vertices(arr([9])))
    np.testing.assert_array_equal(
        np.asarray(b.op), [dag.ADD_VERTEX, dag.ADD_VERTEX, dag.ADD_EDGE,
                           dag.CONTAINS_VERTEX])
    np.testing.assert_array_equal(np.asarray(b.a), [1, 2, 1, 9])
    np.testing.assert_array_equal(np.asarray(b.b), [0, 0, 2, 0])
    assert b.size == 4


def test_create_validation():
    with pytest.raises(ValueError):
        DagEngine.create(CAP, backend="bogus")
    with pytest.raises(ValueError):
        DagEngine.create(CAP, method="bogus")
    with pytest.raises(ValueError):
        DagEngine.create(CAP, subbatches=0)
    with pytest.raises(ValueError):
        FixedPolicy("auto")  # fixed policies pin a concrete algorithm


# ------------------------------------------------- engine == oracle

def test_engine_mixed_ops_match_oracle():
    for seed in range(4):
        rng = np.random.default_rng(500 + seed)
        eng = DagEngine.create(CAP)
        g = SeqGraph(capacity=CAP)
        for _ in range(6):
            batch = _rand_batch(rng)
            eng, r = eng.apply(batch)
            want = apply_op_batch_oracle(
                g, np.asarray(batch.op), np.asarray(batch.a),
                np.asarray(batch.b), acyclic=True, method="partial")
            np.testing.assert_array_equal(np.asarray(r.ok), want)
            assert bool(eng.is_acyclic())
        assert set(np.asarray(eng.state.keys)[np.asarray(eng.state.alive)]) \
            == g.vertices


def test_engine_fixed_policies_decide_identically():
    rng = np.random.default_rng(17)
    engines = {m: DagEngine.create(CAP, method=m)
               for m in ("closure", "partial", "auto")}
    for _ in range(5):
        batch = _rand_batch(rng, n=8, key_space=16)
        results = {}
        for m, eng in engines.items():
            engines[m], results[m] = eng.apply(batch)
        for m in ("partial", "auto"):
            np.testing.assert_array_equal(np.asarray(results[m].ok),
                                          np.asarray(results["closure"].ok))
            np.testing.assert_array_equal(
                np.asarray(engines[m].state.adj),
                np.asarray(engines["closure"].state.adj))


# --------------------------------------- local == sharded on one stream

def test_local_vs_sharded_backend_equal_on_opbatch_stream():
    from repro.core import sharded
    mesh = sharded.make_dag_mesh(jax.devices()[:1])
    rng = np.random.default_rng(23)
    eng_l = DagEngine.create(CAP)
    eng_s = DagEngine.create(CAP, backend="sharded", mesh=mesh)
    for _ in range(5):
        batch = _rand_batch(rng, n=8, key_space=16)
        eng_l, r_l = eng_l.apply(batch)
        eng_s, r_s = eng_s.apply(batch)
        np.testing.assert_array_equal(np.asarray(r_l.ok), np.asarray(r_s.ok))
        np.testing.assert_array_equal(np.asarray(eng_l.state.adj),
                                      np.asarray(eng_s.state.adj))
        np.testing.assert_array_equal(np.asarray(eng_l.state.alive),
                                      np.asarray(eng_s.state.alive))
    f = jnp.asarray(rng.integers(0, 16, 8), jnp.int32)
    t = jnp.asarray(rng.integers(0, 16, 8), jnp.int32)
    np.testing.assert_array_equal(np.asarray(eng_l.reachable(f, t)),
                                  np.asarray(eng_s.reachable(f, t)))


# ------------------------------------------------------ pytree contracts

def test_engine_pytree_roundtrip():
    eng = DagEngine.create(CAP, subbatches=2)
    eng, _ = eng.add_vertices(arr([1, 2, 3]))
    leaves, treedef = jax.tree_util.tree_flatten(eng)
    eng2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert eng2.config == eng.config
    np.testing.assert_array_equal(np.asarray(eng2.state.adj),
                                  np.asarray(eng.state.adj))
    # equal configs -> equal treedefs (one jit trace per config)
    _, treedef3 = jax.tree_util.tree_flatten(DagEngine.create(CAP,
                                                              subbatches=2))
    assert treedef == treedef3


def test_engine_jit_matches_eager():
    rng = np.random.default_rng(29)
    eng = DagEngine.create(CAP)
    eng, _ = eng.add_vertices(jnp.arange(16, dtype=jnp.int32))
    us = jnp.asarray(rng.integers(0, 16, 8), jnp.int32)
    vs = jnp.asarray(rng.integers(0, 16, 8), jnp.int32)
    jitted = jax.jit(lambda e, u, v: e.add_edges_acyclic(u, v))
    eng_j, r_j = jitted(eng, us, vs)
    eng_e, r_e = eng.add_edges_acyclic(us, vs)
    np.testing.assert_array_equal(np.asarray(r_j.ok), np.asarray(r_e.ok))
    np.testing.assert_array_equal(np.asarray(eng_j.state.adj),
                                  np.asarray(eng_e.state.adj))
    np.testing.assert_array_equal(np.asarray(eng_j.depth_ema),
                                  np.asarray(eng_e.depth_ema))


def test_scanned_sgt_session_compiles_once():
    """A full 50-tick SGT session as one lax.scan over the engine pytree:
    compiles exactly once and matches the eager tick-by-tick replay."""
    ticks, n_txn, n_conf = 50, 4, 8
    rng = np.random.default_rng(31)
    begins = jnp.asarray(
        rng.integers(0, 40, (ticks, n_txn)), jnp.int32)
    src = jnp.asarray(rng.integers(0, 40, (ticks, n_conf)), jnp.int32)
    dst = jnp.asarray(rng.integers(0, 40, (ticks, n_conf)), jnp.int32)
    fins = jnp.asarray(rng.integers(0, 40, (ticks, n_txn)), jnp.int32)

    def tick(state, xs):
        b, cs, cd, f = xs
        state, res = sgt.schedule_tick(state, b, cs, cd, f)
        return state, res["accepted"]

    state0 = sgt.new_scheduler(CAP)
    session = jax.jit(
        lambda s, xs: jax.lax.scan(tick, s, xs))
    final, accepted = session(state0, (begins, src, dst, fins))
    assert session._cache_size() == 1
    # eager replay must agree tick for tick
    state_e = sgt.new_scheduler(CAP)
    for i in range(ticks):
        state_e, res = sgt.schedule_tick(state_e, begins[i], src[i],
                                         dst[i], fins[i])
        np.testing.assert_array_equal(np.asarray(accepted[i]),
                                      np.asarray(res["accepted"]))
    assert int(final.n_begun) == int(state_e.n_begun)
    assert int(final.n_aborted) == int(state_e.n_aborted)
    assert float(final.engine.depth_ema[0]) == \
        pytest.approx(float(state_e.engine.depth_ema[0]))
    assert bool(reachability.is_acyclic(final.graph.adj))


# -------------------------------------------- retired shims stay retired

def test_deprecated_shims_are_gone():
    """PR-3's deprecated module-level shims were removed: the engine (or
    the explicit `*_impl` functions) is the only way in, and nothing
    under `repro.core` raises DeprecationWarning anymore (CI greps)."""
    assert not hasattr(dag, "apply_op_batch")
    assert not hasattr(acyclic, "acyclic_add_edges")
    import repro.core as core
    assert not hasattr(core, "apply_op_batch")
    assert not hasattr(core, "acyclic_add_edges")


def test_method_validation_names_nearest():
    """Unknown method names fail at configuration time with the nearest
    valid method named (mirrors validate_capacity's message shape)."""
    with pytest.raises(ValueError, match=r"nearest valid method is "
                                         r"'incremental'"):
        DagEngine.create(CAP, method="incrmental")
    eng = DagEngine.create(CAP)
    with pytest.raises(ValueError, match="nearest valid method is 'auto'"):
        eng.with_options(method="atuo")
    with pytest.raises(ValueError, match="must be one of"):
        dispatch.validate_method("bogus")
    dispatch.validate_method("closure")  # valid names pass silently


def test_apply_op_batch_plumbs_matmul_impl_and_stats():
    """Satellite fix: the mixed-op path accepts matmul_impl and with_stats
    (previously silently dropped / absent)."""
    from repro.kernels import ops as kops
    rng = np.random.default_rng(41)
    st = dag.new_state(CAP)
    st, _ = dag.add_vertices(st, jnp.arange(12, dtype=jnp.int32))
    batch = _rand_batch(rng)
    st2, res, stats = dag.apply_op_batch_impl(
        st, batch.op, batch.a, batch.b, acyclic=True, method="partial",
        matmul_impl=kops.bitmm_packed, with_stats=True)
    st3, res3 = dag.apply_op_batch_impl(st, batch.op, batch.a, batch.b,
                                        acyclic=True, method="partial")
    np.testing.assert_array_equal(np.asarray(res), np.asarray(res3))
    assert set(stats) == {"n_products", "rows_per_product", "row_products",
                          "n_partial", "n_incremental", "n_repair",
                          "deciding_depth", "n_steps", "n_useful_steps",
                          "n_skipped_steps"}
    # non-acyclic path: zero stats, same keys
    _, _, stats0 = dag.apply_op_batch_impl(st, batch.op, batch.a, batch.b,
                                           with_stats=True)
    assert set(stats0) == set(stats)
    assert int(stats0["row_products"]) == 0


def test_overflow_surfaces_in_opresult():
    eng = DagEngine.create(32)
    eng, r = eng.add_vertices(jnp.arange(40, dtype=jnp.int32))
    assert int(r.n_overflow) == 8
    assert int(jnp.sum(r.ok)) == 32
    # the next call reports only ITS overflow, not the running total
    eng, r2 = eng.add_vertices(arr([100, 101]))
    assert int(r2.n_overflow) == 2
    eng, r3 = eng.remove_vertices(arr([0]))
    assert int(r3.n_overflow) == 0


# -------------------------------------------- measured-depth feedback

def test_depth_ema_seeds_and_updates():
    # use_incremental=False: a clean cache would otherwise short-circuit
    # the partial path this test measures (the EMA feedback loop matters
    # exactly when the cache is not clean)
    eng = DagEngine.create(CAP,
                           policy=CostModelPolicy(use_incremental=False))
    assert float(eng.depth_ema[0]) == 0.0
    eng, _ = eng.add_vertices(jnp.arange(8, dtype=jnp.int32))
    # chain 0->1->2->3: the partial check of 3->0's candidate scans depth 3
    eng, r = eng.add_edges_acyclic(arr([0, 1, 2]), arr([1, 2, 3]))
    assert int(r.stats.n_partial) == 1
    first = float(eng.depth_ema[0])
    assert first == float(r.stats.deciding_depth[0]) > 0  # seeded, not blended
    eng2, r2 = eng.add_edges_acyclic(arr([3]), arr([0]))
    alpha = CostModelPolicy().ema_alpha
    want = (1 - alpha) * first + alpha * float(r2.stats.deciding_depth[0])
    assert float(eng2.depth_ema[0]) == pytest.approx(want)
    # a closure-decided call leaves the EMA untouched
    eng3 = DagEngine.create(CAP, method="closure")
    eng3, _ = eng3.add_vertices(arr([1, 2]))
    eng3, _ = eng3.add_edges_acyclic(arr([1]), arr([2]))
    assert float(eng3.depth_ema[0]) == 0.0


def test_measured_depth_overrides_density_guess():
    """A shallow measured depth must flip the cost model toward partial
    where the static density estimate picks closure (and vice versa)."""
    rng = np.random.default_rng(43)
    st = dag.new_state(CAP)
    st, _ = dag.add_vertices(st, jnp.arange(48, dtype=jnp.int32))
    pol = CostModelPolicy()
    b = 48  # sparse, B close to C: static estimate says closure
    assert not bool(pol.prefer_partial(st.adj, b))
    assert bool(pol.prefer_partial(st.adj, b, depth_hint=2.0))
    # unseeded hint (0) falls back to the density guess
    assert not bool(pol.prefer_partial(st.adj, b, depth_hint=0.0))
    # a deep measurement is clipped at the closure's log2 C bound
    deep = pol.prefer_partial(st.adj, 4, depth_hint=1e6)
    assert bool(deep)  # B << C stays partial even at the depth cap

    ema = pol.update_depth_ema(jnp.float32(0.0), jnp.int32(5))
    assert float(ema) == 5.0
    ema2 = pol.update_depth_ema(ema, jnp.int32(0))  # no measurement
    assert float(ema2) == 5.0


def test_with_options_is_a_view():
    eng = DagEngine.create(CAP)
    view = eng.with_options(method="closure", subbatches=2)
    assert view.config.method == "closure"
    assert view.config.subbatches == 2
    assert eng.config.method == "auto" and eng.config.subbatches == 1
    assert view.state is eng.state  # no copy


def test_reachable_agrees_across_policies():
    rng = np.random.default_rng(47)
    engines = {m: DagEngine.create(CAP, method=m)
               for m in ("closure", "partial", "auto")}
    batch = OpBatch.concat(
        OpBatch.add_vertices(jnp.arange(24, dtype=jnp.int32)),
        OpBatch.add_edges(
            jnp.asarray(rng.integers(0, 24, 24), jnp.int32),
            jnp.asarray(rng.integers(0, 24, 24), jnp.int32)))
    for m in engines:
        engines[m], _ = engines[m].apply(batch)
    f = jnp.asarray(rng.integers(0, 24, 16), jnp.int32)
    t = jnp.asarray(rng.integers(0, 24, 16), jnp.int32)
    want = np.asarray(engines["closure"].reachable(f, t))
    for m in ("partial", "auto"):
        np.testing.assert_array_equal(np.asarray(engines[m].reachable(f, t)),
                                      want)


def test_sharded_acyclic_goes_through_policy():
    """The sharded standalone insert routes closure-vs-partial through the
    policy object (ROADMAP gap): a pinned policy forces the branch."""
    from repro.core import sharded
    mesh = sharded.make_dag_mesh(jax.devices()[:1])
    st = dag.new_state(CAP)
    st, _ = dag.add_vertices(st, jnp.arange(12, dtype=jnp.int32))
    us, vs = arr([0, 1, 2]), arr([1, 2, 0])
    outs = {}
    for pol in (FixedPolicy("closure"), FixedPolicy("partial"),
                CostModelPolicy()):
        st2, ok, stats = sharded.acyclic_add_edges_sharded(
            mesh, st, us, vs, policy=pol, with_stats=True)
        outs[pol] = (np.asarray(ok), int(stats["n_partial"]))
    oks = [v[0] for v in outs.values()]
    np.testing.assert_array_equal(oks[0], oks[1])
    np.testing.assert_array_equal(oks[0], oks[2])
    assert outs[FixedPolicy("closure")][1] == 0
    assert outs[FixedPolicy("partial")][1] == 1
    assert outs[CostModelPolicy()][1] == 1  # small sparse batch -> partial

"""The insert loop runs one step per sub-batch holding a valid row.

`acyclic.acyclic_add_edges_impl` skips every all-padding sub-batch and
runs the live ones in their original order.  Skipping must decide
exactly as the same call would with the padding sub-batches removed (a
shorter batch): the same ok bits, adjacency, cache and work counts,
under every method and cache layout, in the sequential
(``subbatches=B``), joint-abort (``subbatches=1``) and mixed modes.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import acyclic, closure_cache, dag

CAP = 64
REGION = 32
B = 8
# chain 0 -> 1 -> 2 -> 3 and 4 -> 5; vertices 0..11
BASE = ([0, 1, 2, 4], [1, 2, 3, 5])
# rows: a back edge, forward edges, an existing edge, an edge that closes
# 4 -> 5 -> 6 -> 4 after row 1, one that closes 0 -> 1 -> 2 -> 7 -> 0
# after row 2, and an endpoint that is no vertex
US = [3, 5, 2, 0, 6, 7, 1, 40]
VS = [0, 6, 7, 1, 4, 0, 3, 1]
MASKS = {
    "empty": [],
    "full": list(range(B)),
    "prefix": [0, 1, 2],
    "scattered": [1, 4, 6],
}
COUNTS = ("n_products", "row_products", "n_partial", "n_incremental",
          "n_useful_steps")


def _graph():
    st = dag.new_state(CAP)
    st, _ = dag.add_vertices(st, jnp.arange(12, dtype=jnp.int32))
    st, _ = dag.add_edges(st, jnp.asarray(BASE[0], jnp.int32),
                          jnp.asarray(BASE[1], jnp.int32))
    return st


def _cache(layout):
    # a stale, dirty cache: the first step that runs refreshes it
    if layout == "tiled":
        return closure_cache.empty_tiled_cache(CAP, REGION, dirty=True)
    return closure_cache.empty_cache(CAP, dirty=True)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _jitted(us, vs, valid, subbatches, method, layout):
    return acyclic.acyclic_add_edges_impl(
        _graph(), us, vs, valid=valid, subbatches=subbatches, method=method,
        with_stats=True, cache=_cache(layout))


def _call(us, vs, valid, subbatches, method, layout):
    # one compile per batch shape: the masks of a case share the program
    return _jitted(jnp.asarray(us, jnp.int32), jnp.asarray(vs, jnp.int32),
                   jnp.asarray(valid), subbatches, method, layout)


@pytest.mark.parametrize("mask", sorted(MASKS))
@pytest.mark.parametrize("subbatches", [1, 4, B])
@pytest.mark.parametrize("layout", ["dense", "tiled"])
@pytest.mark.parametrize("method", ["closure", "partial", "incremental",
                                    "auto"])
def test_skipping_padded_subbatches_matches_the_shorter_batch(
        method, layout, subbatches, mask):
    valid = np.zeros(B, bool)
    valid[MASKS[mask]] = True
    st, ok, cache, stats = _call(US, VS, valid, subbatches, method, layout)
    ok = np.asarray(ok)
    adj = np.asarray(st.adj)
    dense = np.asarray(closure_cache.dense_of(cache.closure))

    assert not ok[~valid].any()                  # padding is never ok
    live = valid.reshape(subbatches, -1).any(axis=1)
    n_live = int(live.sum())
    assert int(stats["n_steps"]) == n_live
    assert int(stats["n_steps"]) + int(stats["n_skipped_steps"]) \
        == subbatches

    if n_live == 0:
        # nothing runs: the graph and the (still dirty) cache as given
        assert not ok.any()
        np.testing.assert_array_equal(adj, np.asarray(_graph().adj))
        assert bool(cache.dirty)
        np.testing.assert_array_equal(
            dense, np.asarray(closure_cache.dense_of(_cache(layout).closure)))
        assert all(int(stats[k]) == 0 for k in COUNTS)
        return

    # the same call with the all-padding sub-batches cut out
    keep = np.repeat(live, B // subbatches)
    st_s, ok_s, cache_s, stats_s = _call(
        np.asarray(US)[keep], np.asarray(VS)[keep], valid[keep], n_live,
        method, layout)
    np.testing.assert_array_equal(ok[keep], np.asarray(ok_s))
    assert not ok[~keep].any()
    np.testing.assert_array_equal(adj, np.asarray(st_s.adj))
    assert bool(cache.dirty) == bool(cache_s.dirty)
    np.testing.assert_array_equal(
        dense, np.asarray(closure_cache.dense_of(cache_s.closure)))
    for k in COUNTS + ("deciding_depth",):
        np.testing.assert_array_equal(np.asarray(stats[k]),
                                      np.asarray(stats_s[k]), err_msg=k)
    if not bool(cache.dirty):                    # trusted: exact
        np.testing.assert_array_equal(
            dense, np.asarray(closure_cache.rebuild_cache(st.adj).closure))


def test_writer_insert_step_compiles_once_across_tick_sizes():
    """The served writer's insert step keeps one compiled program: the
    number of steps run is data (the valid mask), not a shape."""
    from repro.api import DagEngine
    from repro.replica import _add_edges_step

    b, cap = 64, 160                 # a capacity no other test compiles
    eng = DagEngine.create(cap, method="incremental", subbatches=b,
                           closure_layout="tiled", closure_region=cap)
    eng, _ = eng.add_vertices(jnp.arange(b + 1, dtype=jnp.int32))
    us = jnp.arange(b, dtype=jnp.int32)
    before = _add_edges_step._cache_size()
    for n_edges in (0, 3, b):
        valid = np.arange(b) < n_edges
        eng, res, _ = _add_edges_step(eng, us, us + 1, jnp.asarray(valid))
        assert int(res.stats.n_steps) == n_edges
        assert int(res.stats.n_skipped_steps) == b - n_edges
        # a chain 0 -> 1 -> ... : every valid edge is accepted or present
        np.testing.assert_array_equal(np.asarray(res.ok), valid)
    assert _add_edges_step._cache_size() == before + 1

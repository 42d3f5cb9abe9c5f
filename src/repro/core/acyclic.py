"""AcyclicAddEdge — batched, with the paper's relaxed (false-positive) spec.

Paper semantics: a newly inserted edge sits in *transit* state; a reachability
check then either commits it (status -> added) or removes it (cycle).  Two
concurrent inserts lying on one cycle may BOTH abort — a false positive the
paper explicitly allows (for SGT it is only an unnecessary transaction abort,
never a correctness violation).

Batched realization: all candidate edges of a (sub-)batch are inserted in
transit, the cycle check runs over ``G ∪ transit``, and every candidate lying
on a cycle is rejected.  Because each batch edge on a cycle is rejected, the
committed graph stays acyclic (any residual cycle would need all of its batch
edges accepted — impossible).  This reproduces the paper's joint-abort false
positives exactly.

``method`` selects which reachability check decides the batch (all return
identical ok bits — only the work differs):

  "closure"      Algorithm 1: ONE full transitive closure of ``G ∪ transit``
                 (ceil(log2 C) products over C rows), then bit lookups.
  "partial"      Algorithm 2 (`core/snapshot.py`): partial-snapshot scans
                 seeded from the candidates' target slots — per hop one
                 product over B rows, early-exiting at the deciding depth.
  "incremental"  `core/closure_cache.py`: B^2 bit reads against the cached
                 closure of the committed graph plus a B x B candidate-hop
                 closure — ZERO C-row products when the cache is clean; an
                 accepted batch folds back in as one rank-B update (the add
                 side of the delta-commit pipeline, fused here with the
                 check), a dirty cache (a delete the commit chose not to
                 repair) lazily rebuilds first.
  "auto"         Adaptive dispatch (`core/dispatch.py`): clean cache ->
                 incremental, else the cost model prices closure vs partial
                 from B, C, and a popcount density estimate; under jit the
                 choice is a ``lax.switch`` so dispatch is traced, not
                 staged out.

``subbatches=K`` (beyond paper): splits the batch into K priority classes
checked sequentially — K=1 is the paper-faithful maximally-concurrent mode,
K=B is fully sequential with zero false positives.  The abort-rate/throughput
trade-off is benchmarked in `benchmarks/paper_workloads.py`.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import bitset, closure_cache, dispatch, snapshot
from repro.core.closure_cache import ClosureCache
from repro.core.dag import DagState, lookup_slots, _valid
from repro.core.reachability import transitive_closure, MatmulImpl

METHODS = dispatch.METHODS

# branch codes in the per-sub-batch stats (what the dispatcher chose)
CHOSE_CLOSURE, CHOSE_PARTIAL, CHOSE_INCREMENTAL = 0, 1, 2

# prefer_partial_fn signature: (transit adjacency uint32[C, W], sub-batch
# size) -> traced bool scalar.  `core/engine.py` closes a DispatchPolicy
# (plus its measured-depth EMA) over this hook.
PreferPartialFn = Callable[[jax.Array, int], jax.Array]


def acyclic_add_edges_impl(
        state: DagState, us: jax.Array, vs: jax.Array,
        valid=None, subbatches: int = 1,
        matmul_impl: Optional[MatmulImpl] = None,
        method: str = "closure", with_stats: bool = False,
        prefer_partial_fn: Optional[PreferPartialFn] = None,
        partial_matmul_impl: Optional[MatmulImpl] = None,
        cache: Optional[ClosureCache] = None,
        closure_update_impl=None, n_shards: int = 1,
        prefer_incremental_fn=None):
    """Returns (state, ok[B]) — or, with a closure cache in play (``cache``
    passed, or ``method="incremental"``), (state, ok[B], cache'); either
    form appends ``stats`` under ``with_stats``.

    ok semantics (sequential spec, Table 2 + acyclic relaxation):
      - False if either endpoint is not a live vertex.
      - True  if the edge already exists.
      - True  if inserted without creating a cycle.
      - False if the insert lies on a cycle of ``G ∪ transit`` (the edge is
        backed out; false positives under concurrency are allowed).

    stats = {"n_products", "rows_per_product", "row_products", "n_partial",
    "n_incremental", "deciding_depth", "n_steps", "n_useful_steps",
    "n_skipped_steps"} counts
    the boolean matmuls the cycle checks executed (summed over
    sub-batches); row_products is the total number of rows fed through
    the matmul — the comparable work unit between the methods
    (rows_per_product is -1 under ``method="auto"``, where sub-batches
    may mix row widths; row_products stays exact).  n_partial /
    n_incremental count the sub-batch checks algorithm 2 / the closure cache
    decided — under "auto" they expose what the dispatcher chose.
    deciding_depth is int32[n_shards]: the per-shard deciding hop counts of
    the *last* algorithm-2 check (all-zero if none ran) — the measurement
    the engine feeds back into `CostModelPolicy` as its per-shard depth-EMA
    vector (contiguous row blocks map to shards, matching the B-sharded
    scan's partitioning; n_shards=1 collapses to the old scalar).
    n_steps is the sub-batch steps run (one per sub-batch holding a
    valid row), n_useful_steps those that held at least one candidate
    edge, and n_skipped_steps the all-padding sub-batches not run.

    ``prefer_partial_fn`` overrides the ``method="auto"`` choice (default:
    `dispatch.prefer_partial_from_adj`) and ``prefer_incremental_fn``
    (signature: traced dirty bool -> traced bool; default ``~dirty``) the
    cached short-circuit — the engine closes
    `CostModelPolicy.prefer_incremental` over the latter;
    ``partial_matmul_impl`` lets the partial branch run a different matmul
    schedule than the closure branch (the sharded engine's B-sharded vs
    frontier-sharded scans); ``closure_update_impl`` drives the
    incremental rank-B cache update (`kernels/ops.closure_update` on TPU,
    row-sharded on the mesh).
    Incremental decisions are identical to the fixed methods' — the
    candidate-hop construction reproduces the joint-abort spec exactly.
    """
    if method not in METHODS:
        raise ValueError(f"method must be one of {METHODS}, got {method!r}")
    valid = _valid(valid, us)
    b = us.shape[0]
    if b % subbatches != 0:
        raise ValueError(f"batch {b} not divisible by subbatches {subbatches}")
    b_sub = b // subbatches
    capacity = state.capacity
    rows_per_product = {"closure": capacity, "partial": b_sub,
                        "auto": -1, "incremental": capacity}[method]
    p_impl = partial_matmul_impl if partial_matmul_impl is not None \
        else matmul_impl
    prefer = prefer_partial_fn if prefer_partial_fn is not None \
        else dispatch.prefer_partial_from_adj
    prefer_inc = prefer_incremental_fn if prefer_incremental_fn is not None \
        else (lambda dirty: ~dirty)
    cached = cache is not None or method == "incremental"
    if cached and cache is None:
        # standalone incremental call: conservative dirty cache -> the
        # first sub-batch pays one lazy rebuild, the rest ride the cache
        cache = closure_cache.empty_cache(capacity, dirty=True)
    tiled = cached and closure_cache.is_tiled(cache.closure)
    region = cache.closure.region if tiled else capacity

    us_r = us.reshape(subbatches, -1)
    vs_r = vs.reshape(subbatches, -1)
    valid_r = valid.reshape(subbatches, -1)

    zero_depths = jnp.zeros((n_shards,), jnp.int32)

    def shard_depths(decided_at):
        """Per-row deciding hops -> per-shard maxima (contiguous blocks);
        non-divisible batches broadcast the global max to every shard."""
        if n_shards > 1 and b_sub % n_shards == 0:
            return jnp.max(decided_at.reshape(n_shards, -1), axis=1)
        return jnp.broadcast_to(jnp.max(decided_at), (n_shards,))

    def candidates(adj, u, v, val):
        u_slot, u_found = lookup_slots(state._replace(adj=adj), u)
        v_slot, v_found = lookup_slots(state._replace(adj=adj), v)
        vert_ok = val & u_found & v_found
        self_loop = vert_ok & (u == v)
        already = vert_ok & bitset.bit_get(adj, u_slot, v_slot)
        cand = vert_ok & ~already & ~self_loop
        return u_slot, v_slot, already, cand

    def step(carry, xs):
        adj, closure, dirty = carry
        u, v, val = xs
        u_slot, v_slot, already, cand = candidates(adj, u, v, val)
        adj_t = bitset.scatter_set_bits(adj, u_slot, v_slot, cand)  # transit

        # every branch returns (cyc, closure', dirty', n_products,
        # row_products, chose code, per-shard deciding depths)
        @jax.named_scope("cycle_check.closure")
        def closure_check(_):
            cfull, n = transitive_closure(adj_t, matmul_impl,
                                          with_stats=True)
            cyc = bitset.bit_get(cfull, v_slot, u_slot)  # path v -> u
            if cached:
                any_reject = jnp.any(cand & cyc)
                any_accept = jnp.any(cand & ~cyc)
                # opportunistic refresh: with zero rejects the committed
                # graph IS G ∪ transit, so the closure just computed is its
                # exact cache (otherwise rejected transit edges poison it)
                if tiled:
                    # adopt into the tiles window only when the transit
                    # graph fits it (a confined graph has a confined
                    # closure); otherwise the tiles go/stay stale
                    adopt = ~any_reject \
                        & closure_cache.region_confined(adj_t, region)
                    tiles2 = jnp.where(
                        adopt, cfull[:region, : region // bitset.WORD],
                        closure.tiles)
                    closure2 = closure_cache.TiledClosure(
                        tiles2,
                        closure_cache.build_summary(tiles2, capacity))
                    dirty2 = jnp.where(adopt, jnp.asarray(False),
                                       dirty | any_accept)
                else:
                    closure2 = jnp.where(any_reject, closure, cfull)
                    dirty2 = jnp.where(any_reject, dirty | any_accept,
                                       jnp.asarray(False))
            else:
                closure2, dirty2 = closure, dirty
            return (cyc, closure2, dirty2, n, n * jnp.int32(capacity),
                    jnp.int32(CHOSE_CLOSURE), zero_depths)

        @jax.named_scope("cycle_check.partial")
        def partial_check(_):
            cyc, n, decided_at = snapshot.partial_cycle_check(
                adj_t, u_slot, v_slot, cand, p_impl, with_stats=True,
                with_depths=True)
            dirty2 = dirty | jnp.any(cand & ~cyc) if cached \
                else dirty  # accepts stale the cache
            return (cyc, closure, dirty2, n, n * jnp.int32(b_sub),
                    jnp.int32(CHOSE_PARTIAL), shard_depths(decided_at))

        @jax.named_scope("cycle_check.incremental")
        def incremental_check(_):
            # lazy rebuild on a dirty cache (charged as closure products),
            # then the B^2-bit-read check and the rank-B fold-in; always
            # leaves a clean cache on the dense layout
            closure0, n = closure_cache.refresh_closure(
                closure, dirty, adj, matmul_impl)
            if not tiled:
                cyc = closure_cache.incremental_cycle_check(
                    closure0, u_slot, v_slot, cand)
                closure1 = closure_cache.insert_update(
                    closure0, u_slot, v_slot, cand & ~cyc,
                    closure_update_impl)
                return (cyc, closure1, jnp.asarray(False), n,
                        n * jnp.int32(capacity),
                        jnp.int32(CHOSE_INCREMENTAL), zero_depths)

            # tiled: the refresh rebuilds inside the window (O(region)
            # rows).  If the committed graph has spilled past the window
            # (only possible under jit, where the host can't widen it),
            # the tiles stay stale and the batch is decided by the exact
            # from-scratch partial check instead — decisions never read
            # untrusted bits, they just cost more until the engine widens
            # the window host-side.
            stale = dirty & ~closure_cache.region_confined(adj, region)

            def trusted(_):
                cyc = closure_cache.incremental_cycle_check(
                    closure0, u_slot, v_slot, cand)
                closure1, spilled = closure_cache.insert_update_tiled(
                    closure0, u_slot, v_slot, cand & ~cyc,
                    closure_update_impl)
                return cyc, closure1, spilled, n, n * jnp.int32(region)

            def fallback(_):
                cyc, n2, _ = snapshot.partial_cycle_check(
                    adj_t, u_slot, v_slot, cand, p_impl, with_stats=True,
                    with_depths=True)
                return (cyc, closure0, jnp.asarray(True), n2,
                        n2 * jnp.int32(b_sub))

            cyc, closure1, dirty1, n1, rp = jax.lax.cond(
                stale, fallback, trusted, None)
            return (cyc, closure1, dirty1, n1, rp,
                    jnp.int32(CHOSE_INCREMENTAL), zero_depths)

        if method == "closure":
            checked = closure_check(None)
        elif method == "partial":
            checked = partial_check(None)
        elif method == "incremental":
            checked = incremental_check(None)
        elif cached:
            # three-way traced dispatch: the policy's prefer_incremental
            # (default: cache cleanliness — a clean cache's check does
            # zero C-row products) wins outright, else the cost model
            # prices the two from-scratch algorithms on the transit graph
            idx = jnp.where(prefer_inc(dirty), jnp.int32(CHOSE_INCREMENTAL),
                            jnp.where(prefer(adj_t, b_sub),
                                      jnp.int32(CHOSE_PARTIAL),
                                      jnp.int32(CHOSE_CLOSURE)))
            checked = jax.lax.switch(
                idx, [closure_check, partial_check, incremental_check], None)
        else:  # auto without a cache: the PR-2 two-way cost model
            checked = jax.lax.cond(prefer(adj_t, b_sub), partial_check,
                                   closure_check, None)
        cyc, closure_n, dirty_n, n_products, row_products, chose, depths = \
            checked
        reject = cand & cyc
        adj_n = bitset.scatter_clear_bits(adj_t, u_slot, v_slot, reject)
        ok = already | (cand & ~cyc)
        return (adj_n, closure_n, dirty_n), \
            (ok, n_products, row_products, chose, depths, jnp.any(cand))

    carry0 = (state.adj, cache.closure, cache.dirty) if cached else \
        (state.adj, jnp.zeros((0, 0), jnp.uint32), jnp.asarray(True))

    # Only sub-batches holding a valid row run a step, in their original
    # order: an all-padding sub-batch has no candidate, so its ok row is
    # all False and it leaves adj alone.  What it skips is a side effect:
    # a dirty cache is refreshed at the first live step instead (or stays
    # dirty, and says so, when none is live).
    live = jnp.any(valid_r, axis=1)
    order = jnp.argsort(~live, stable=True)
    n_live = jnp.sum(live, dtype=jnp.int32)

    def run_live(loop):
        k, carry, oks, counts, last_depths = loop
        i = order[k]
        carry, (ok, n, rp, chose, depths, useful) = step(
            carry, (us_r[i], vs_r[i], valid_r[i]))
        partial = chose == CHOSE_PARTIAL
        counts = counts + jnp.stack(
            [n, rp, partial, chose == CHOSE_INCREMENTAL, useful]
        ).astype(jnp.int32)
        return (k + 1, carry, oks.at[i].set(ok), counts,
                jnp.where(partial, depths, last_depths))

    _, (adj, closure_f, dirty_f), oks, counts, deciding_depth = \
        jax.lax.while_loop(
            lambda loop: loop[0] < n_live, run_live,
            (jnp.int32(0), carry0, jnp.zeros((subbatches, b_sub), bool),
             jnp.zeros((5,), jnp.int32), zero_depths))
    state = state._replace(adj=adj)
    oks = oks.reshape(b)
    # the insert loop never runs a delete repair: the repair-depth EMA
    # rides through unchanged
    out_cache = ClosureCache(closure_f, dirty_f, cache.repair_ema) \
        if cached else None
    if not with_stats:
        return (state, oks, out_cache) if cached else (state, oks)
    # deciding_depth is the LAST algorithm-2 check's: the freshest
    # measurement for the engine's depth-EMA feedback loop
    n_products, row_products, n_partial, n_incremental, n_useful = counts
    stats = {"n_products": n_products,
             "rows_per_product": rows_per_product,
             "row_products": row_products,
             "n_partial": n_partial,
             "n_incremental": n_incremental,
             "n_repair": jnp.int32(0),  # insert checks never delete-repair
             "deciding_depth": deciding_depth,
             "n_steps": n_live,
             "n_useful_steps": n_useful,
             "n_skipped_steps": jnp.int32(subbatches) - n_live}
    if cached:
        return state, oks, out_cache, stats
    return state, oks, stats

"""Batched non-blocking concurrent DAG — the paper's object, TPU-native.

A batch of operation requests (one per logical "thread") is applied in a
single data-parallel step.  Every operation in the batch completes in a
bounded number of dataflow steps (wait-free by construction); the result is
a deterministic linearization (phase order, then batch-index order) that is
property-tested against a sequential oracle (`core/oracle.py`).

State layout (capacity-bounded slab, slots recycled via a free list):
  keys  : int32[C]    key stored in each slot (EMPTY_KEY when free)
  alive : bool[C]     slot liveness (logical deletion == clearing this)
  adj   : uint32[C,W] bit-packed adjacency rows (out-edges over slots)
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import bitset

EMPTY_KEY = jnp.int32(-1)

# op codes for mixed workloads (phase order == linearization order)
REMOVE_VERTEX = 0
ADD_VERTEX = 1
REMOVE_EDGE = 2
ADD_EDGE = 3
CONTAINS_VERTEX = 4
CONTAINS_EDGE = 5


class DagState(NamedTuple):
    keys: jax.Array       # int32[C]
    alive: jax.Array      # bool[C]
    adj: jax.Array        # uint32[C, W]
    n_overflow: jax.Array  # int32 scalar: vertex adds dropped for capacity

    @property
    def capacity(self) -> int:
        return self.keys.shape[0]


def new_state(capacity: int) -> DagState:
    w = bitset.n_words(capacity)
    return DagState(
        keys=jnp.full((capacity,), EMPTY_KEY, jnp.int32),
        alive=jnp.zeros((capacity,), bool),
        adj=jnp.zeros((capacity, w), jnp.uint32),
        n_overflow=jnp.zeros((), jnp.int32),
    )


def grow_state(state: DagState, new_capacity: int) -> DagState:
    """Re-embed the slab at a larger capacity in one jit-compatible step.

    Slots keep their indices, so growth is pure zero-padding: new key slots
    are EMPTY_KEY (free-list candidates), new alive bits are False, and the
    adjacency pads with zero rows and zero high words — no bit moves, and
    the strict closure of the padded graph IS the padded closure (which is
    what lets `closure_cache.grow_cache` carry a clean cache through a grow
    without a rebuild).  ``n_overflow`` rides through unchanged: it is a
    cumulative drop counter and the engine reasons in deltas.
    """
    c = state.capacity
    if new_capacity == c:
        return state
    if new_capacity < c:
        raise ValueError(
            f"cannot shrink: new capacity {new_capacity} < current {c}")
    w = state.adj.shape[1]
    w_new = bitset.n_words(new_capacity)
    return DagState(
        keys=jnp.concatenate([
            state.keys,
            jnp.full((new_capacity - c,), EMPTY_KEY, jnp.int32)]),
        alive=jnp.concatenate([
            state.alive, jnp.zeros((new_capacity - c,), bool)]),
        adj=jnp.pad(state.adj, ((0, new_capacity - c), (0, w_new - w))),
        n_overflow=state.n_overflow,
    )


@jax.named_scope("slot_lookup")
def lookup_slots(state: DagState, keys: jax.Array):
    """keys int32[B] -> (slot int32[B], found bool[B])."""
    m = state.alive[None, :] & (state.keys[None, :] == keys[:, None])
    found = m.any(axis=1)
    slot = jnp.argmax(m, axis=1).astype(jnp.int32)
    return slot, found


def _valid(valid, like):
    if valid is None:
        return jnp.ones(like.shape[0], bool)
    return valid


# ---------------------------------------------------------------- vertices

def add_vertices(state: DagState, keys: jax.Array, valid=None):
    """AddVertex batch. Returns (state, ok[B]).

    Per the sequential spec AddVertex(u) returns true (unique keys assumed);
    re-adding a live key is a no-op returning true.  Capacity overflow yields
    ok=False and bumps ``n_overflow`` (host controller contract).
    """
    valid = _valid(valid, keys)
    c = state.capacity
    _, exists = lookup_slots(state, keys)
    first = bitset._first_occurrence(
        jnp.where(valid & ~exists, keys, -jnp.arange(keys.shape[0]) - 2))
    need = valid & ~exists & first
    free = ~state.alive
    free_rank = jnp.cumsum(free) - 1
    slot_for_rank = jnp.zeros((c,), jnp.int32).at[
        jnp.where(free, free_rank, c)
    ].set(jnp.arange(c, dtype=jnp.int32), mode="drop")
    n_free = jnp.sum(free)
    need_rank = jnp.cumsum(need) - 1
    overflow = need & (need_rank >= n_free)
    place = need & ~overflow
    tgt = slot_for_rank[jnp.where(place, need_rank, 0)]
    tgt_safe = jnp.where(place, tgt, c)
    keys_new = state.keys.at[tgt_safe].set(keys, mode="drop")
    alive_new = state.alive.at[tgt_safe].set(True, mode="drop")
    state = state._replace(
        keys=keys_new, alive=alive_new,
        n_overflow=state.n_overflow + jnp.sum(overflow, dtype=jnp.int32))
    # ok == "key is live in the post-state" (covers pre-existing keys,
    # placements, and in-batch duplicates; overflowed keys report False)
    _, exists_after = lookup_slots(state, keys)
    return state, valid & exists_after


def remove_vertices(state: DagState, keys: jax.Array, valid=None):
    """RemoveVertex batch: logical+physical removal, plus the paper's
    RemoveIncomingEdges as a single masked column clear. Returns (state, ok)."""
    state, rem, _ = remove_vertices_delta(state, keys, valid=valid)
    return state, rem


def remove_vertices_delta(state: DagState, keys: jax.Array, valid=None):
    """`remove_vertices` that additionally emits the typed `CacheDelta`
    for the delta-commit pipeline (`core/closure_cache.commit`).  The
    delta mask is adjacency-diff exact: only removals whose slot had at
    least one incident edge (a nonzero out-row or in-column) seed a cache
    repair — removing an edge-free vertex commits as a no-op and leaves a
    clean cache clean.  Returns (state, ok, delta)."""
    from repro.core.closure_cache import CacheDelta

    valid = _valid(valid, keys)
    c = state.capacity
    slot, found = lookup_slots(state, keys)
    first = bitset._first_occurrence(
        jnp.where(valid & found, keys, -jnp.arange(keys.shape[0]) - 2))
    rem = valid & found & first
    # adjacency-touching test on the PRE-removal slab (slot is garbage for
    # non-removed rows — masked out by ``rem`` below)
    out_any = jnp.any(state.adj[jnp.where(rem, slot, 0)] != 0, axis=-1)
    word = slot >> 5
    shift = (slot & 31).astype(jnp.uint32)
    col_bits = (state.adj[:, word] >> shift[None, :]) & jnp.uint32(1)
    in_any = jnp.any(col_bits != 0, axis=0)
    touched = rem & (out_any | in_any)
    tgt = jnp.where(rem, slot, c)
    alive_new = state.alive.at[tgt].set(False, mode="drop")
    keys_new = state.keys.at[tgt].set(EMPTY_KEY, mode="drop")
    removed_row = jnp.zeros((c,), bool).at[tgt].set(True, mode="drop")
    colmask = bitset.pack_bits(removed_row)  # (W,)
    adj_new = jnp.where(removed_row[:, None], jnp.uint32(0), state.adj)
    adj_new = adj_new & ~colmask[None, :]
    state = state._replace(keys=keys_new, alive=alive_new, adj=adj_new)
    return state, rem, CacheDelta.vertices_cleared(slot, touched)


# ------------------------------------------------------------------- edges

def add_edges(state: DagState, us: jax.Array, vs: jax.Array, valid=None):
    """Plain AddEdge batch (no acyclicity): ok iff both endpoints live."""
    valid = _valid(valid, us)
    u_slot, u_found = lookup_slots(state, us)
    v_slot, v_found = lookup_slots(state, vs)
    ok = valid & u_found & v_found
    adj = bitset.scatter_set_bits(state.adj, u_slot, v_slot, ok)
    return state._replace(adj=adj), ok


def remove_edges(state: DagState, us: jax.Array, vs: jax.Array, valid=None):
    state, ok, _ = remove_edges_delta(state, us, vs, valid=valid)
    return state, ok


def remove_edges_delta(state: DagState, us: jax.Array, vs: jax.Array,
                       valid=None):
    """`remove_edges` that additionally emits the typed `CacheDelta` for
    the delta-commit pipeline (`core/closure_cache.commit`).  The delta
    mask is adjacency-diff exact: only removals whose bit was actually set
    (edge present pre-batch, first occurrence of a duplicated pair) seed a
    cache repair — no-op and repeated removals commit as empty deltas and
    leave a clean cache clean.  ``ok`` keeps the sequential spec (True for
    live endpoints whether or not the edge existed).  Returns
    (state, ok, delta)."""
    from repro.core.closure_cache import CacheDelta

    valid = _valid(valid, us)
    u_slot, u_found = lookup_slots(state, us)
    v_slot, v_found = lookup_slots(state, vs)
    ok = valid & u_found & v_found
    existed = bitset.bit_get(state.adj, u_slot, v_slot)
    first = bitset._dedupe_enabled(u_slot, v_slot, ok & existed,
                                   state.capacity)
    cleared = ok & existed & first
    adj = bitset.scatter_clear_bits(state.adj, u_slot, v_slot, ok)
    return (state._replace(adj=adj), ok,
            CacheDelta.edges_removed(u_slot, v_slot, cleared))


# ---------------------------------------------------- wait-free reads

def contains_vertices(state: DagState, keys: jax.Array) -> jax.Array:
    _, found = lookup_slots(state, keys)
    return found


def contains_edges(state: DagState, us: jax.Array, vs: jax.Array) -> jax.Array:
    u_slot, u_found = lookup_slots(state, us)
    v_slot, v_found = lookup_slots(state, vs)
    return u_found & v_found & bitset.bit_get(state.adj, u_slot, v_slot)


# ------------------------------------------------- mixed-op workloads

def apply_op_batch_impl(state: DagState, op: jax.Array, a: jax.Array,
                        b: jax.Array, acyclic: bool = False,
                        subbatches: int = 1, method: str = "closure",
                        matmul_impl=None, with_stats: bool = False,
                        prefer_partial_fn=None, partial_matmul_impl=None,
                        cache=None, closure_update_impl=None,
                        n_shards: int = 1, prefer_incremental_fn=None,
                        closure_delete_impl=None, prefer_repair_fn=None):
    """Apply a mixed batch with the documented linearization:
    RemoveVertex -> AddVertex -> RemoveEdge -> AddEdge -> reads.

    ``method`` picks the acyclic cycle-check algorithm ("closure" = paper
    algorithm 1 full closure, "partial" = algorithm 2 partial snapshot,
    "incremental" = the cached-closure check, "auto" = per-batch dispatch;
    see `core/acyclic.py`, `core/closure_cache.py`, `core/dispatch.py`).
    ``matmul_impl`` drives every cycle-check matmul (e.g. the fused Pallas
    kernel on TPU); ``prefer_partial_fn`` / ``partial_matmul_impl`` /
    ``closure_update_impl`` are the engine's policy hooks (see
    `acyclic.acyclic_add_edges_impl`).

    ``cache`` threads the engine's incremental closure cache through the
    linearization as the delta-commit pipeline: the two delete phases
    (RemoveVertex, then RemoveEdge) emit adj-diff-exact `CacheDelta`s
    which are coalesced (`CacheDelta.merge`) into ONE
    `closure_cache.commit` against the post-removal adjacency — a mixed
    add+delete batch pays a single repair pass.  The commit maintains the
    cache by affected-row re-derivation when the delete dispatch arm
    (``prefer_repair_fn``; scan realized by ``closure_delete_impl``) says
    it pays, invalidating otherwise so the AddEdge phase's incremental
    check lazily rebuilds in-step.  The single commit still lands before
    AddEdge, so recycled slots stay safe: a slot freed and re-added in the
    same batch has its closure row/column repaired before any new edge
    consults it, and the repair re-derives rows from the final
    post-removal adjacency, which is exact.  With
    ``cache`` the return gains the updated cache:
    (state, ok[, cache][, stats]); stats is the cycle-check + commit
    accounting (all-zero when ``acyclic=False`` and no repair ran).
    """
    from repro.core import acyclic as acyclic_mod
    from repro.core import closure_cache as cc_mod

    res = jnp.zeros(op.shape[0], bool)
    # acyclic.acyclic_add_edges_impl threads (and returns) a cache for
    # method="incremental" even when none was passed — mirror its notion
    # of "cached" so the unpacking below cannot diverge from it
    cached = cache is not None or (acyclic and method == "incremental")
    z = jnp.int32(0)
    commit_products, commit_rows, commit_repairs = z, z, z

    def commit_phase(cache, delta):
        cache, st = cc_mod.commit(
            cache, delta, state.adj, update_impl=closure_update_impl,
            delete_impl=closure_delete_impl,
            prefer_repair_fn=prefer_repair_fn, with_stats=True)
        return cache, st

    if cache is not None:
        state, r, d_v = remove_vertices_delta(state, a,
                                              valid=op == REMOVE_VERTEX)
    else:
        state, r = remove_vertices(state, a, valid=op == REMOVE_VERTEX)
    res = jnp.where(op == REMOVE_VERTEX, r, res)
    state, r = add_vertices(state, a, valid=op == ADD_VERTEX)
    res = jnp.where(op == ADD_VERTEX, r, res)
    if cache is not None:
        state, r, d_e = remove_edges_delta(state, a, b,
                                           valid=op == REMOVE_EDGE)
        # one coalesced commit for the whole tick's delete work: vertex
        # clears and edge removals repair in a single affected-row pass
        # against the final post-removal adjacency (exact superset of the
        # per-phase affected sets, so accept decisions are unchanged)
        cache, st = commit_phase(cache, cc_mod.CacheDelta.merge(d_v, d_e))
        commit_products += st["n_products"]
        commit_rows += st["row_products"]
        commit_repairs += st["n_repair"]
    else:
        state, r = remove_edges(state, a, b, valid=op == REMOVE_EDGE)
    res = jnp.where(op == REMOVE_EDGE, r, res)
    stats = {"n_products": z, "rows_per_product": 0, "row_products": z,
             "n_partial": z, "n_incremental": z, "n_repair": z,
             "deciding_depth": jnp.zeros((n_shards,), jnp.int32),
             "n_steps": z, "n_useful_steps": z, "n_skipped_steps": z}
    if acyclic:
        out = acyclic_mod.acyclic_add_edges_impl(
            state, a, b, valid=op == ADD_EDGE, subbatches=subbatches,
            method=method, matmul_impl=matmul_impl, with_stats=with_stats,
            prefer_partial_fn=prefer_partial_fn,
            partial_matmul_impl=partial_matmul_impl, cache=cache,
            closure_update_impl=closure_update_impl, n_shards=n_shards,
            prefer_incremental_fn=prefer_incremental_fn)
        if cached and with_stats:
            state, r, cache, stats = out
        elif cached:
            state, r, cache = out
        elif with_stats:
            state, r, stats = out
        else:
            state, r = out
    else:
        adj_pre = state.adj
        state, r = add_edges(state, a, b, valid=op == ADD_EDGE)
        if cache is not None:
            # unconstrained inserts bypass the cycle check (and therefore
            # the rank-B fold-in): the cache goes stale
            cache = cache.invalidated_if(jnp.any(state.adj != adj_pre))
    if with_stats and cache is not None:
        stats = dict(stats)
        stats["n_products"] = stats["n_products"] + commit_products
        stats["row_products"] = stats["row_products"] + commit_rows
        stats["n_repair"] = stats["n_repair"] + commit_repairs
    res = jnp.where(op == ADD_EDGE, r, res)
    r = contains_vertices(state, a)
    res = jnp.where(op == CONTAINS_VERTEX, r, res)
    r = contains_edges(state, a, b)
    res = jnp.where(op == CONTAINS_EDGE, r, res)
    if cached and with_stats:
        return state, res, cache, stats
    if cached:
        return state, res, cache
    if with_stats:
        return state, res, stats
    return state, res


def apply_op_sequential(state: DagState, op: jax.Array, a: jax.Array,
                        b: jax.Array, acyclic: bool = False,
                        method: str = "closure"):
    """Coarse-grained baseline: one op at a time (the moral equivalent of the
    paper's single global lock).  Same linearization as a size-1 batch chain.
    ``method="incremental"`` threads one closure cache through the whole
    chain (so the baseline, too, pays a single build instead of one per op).
    """
    if acyclic and method == "incremental":
        from repro.core import closure_cache

        def body_cached(carry, xs):
            st, cache = carry
            o, aa, bb = xs
            st, r, cache = apply_op_batch_impl(
                st, o[None], aa[None], bb[None], acyclic=True,
                subbatches=1, method=method, cache=cache)
            return (st, cache), r[0]

        cache0 = closure_cache.empty_cache(state.capacity, dirty=True)
        (state, _), res = jax.lax.scan(body_cached, (state, cache0),
                                       (op, a, b))
        return state, res

    def body(st, xs):
        o, aa, bb = xs
        st, r = apply_op_batch_impl(st, o[None], aa[None], bb[None],
                                    acyclic=acyclic, subbatches=1,
                                    method=method)
        return st, r[0]

    return jax.lax.scan(body, state, (op, a, b))


# ------------------------------------------------------------- invariants

def live_vertex_count(state: DagState) -> jax.Array:
    return jnp.sum(state.alive, dtype=jnp.int32)


def edge_count(state: DagState) -> jax.Array:
    return jnp.sum(bitset.popcount(state.adj), dtype=jnp.int32)

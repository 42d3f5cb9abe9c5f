"""Incremental transitive-closure cache — `method="incremental"`.

Both of the paper's reachability algorithms recompute from scratch on every
insert batch: algorithm 1 pays ~ceil(log2 C) full-C boolean products,
algorithm 2 pays B rows per BFS hop.  But an engine session mutates the
*same* graph tick after tick, so the closure of the committed graph can be
carried as session state (the amortization move of Chatterjee et al.,
arXiv:1809.00896, and of the incremental snapshot maintenance in
arXiv:2310.02380):

  * **Check** — with a clean cache, whether candidate edge (u, v) closes a
    cycle through the *committed* graph is one bit read,
    ``closure[v, u]``.  Cycles that only exist through the other candidates
    of the same batch (the paper's transit edges) are decided on the B x B
    *candidate hop graph* ``A[i, j] = reach(v_i, u_j)`` — candidate i lies
    on a cycle of ``G ∪ transit`` iff the strict closure of A has bit
    (i, i).  Total work: B^2 bit reads plus a B x B boolean closure — ZERO
    C-row boolean matmul products.
  * **Commit** — every mutation reaches the cache as a typed `CacheDelta`
    (edges added, edges removed, vertex columns cleared) applied through
    the single `commit` entry point:
      - *adds* fold in with one rank-B boolean update: every vertex w that
        reaches an accepted edge's source u gains that edge's contribution
        ``closure[v] | onehot(v)``; chains of accepted edges are
        pre-composed through the hop graph's reflexive-transitive closure,
        so the update is exact in one shot (`kernels/closure_update.py`
        fuses it on TPU).
      - *removes* are maintained by **affected-region re-derivation**: the
        rows whose reach sets can shrink are exactly the ancestors of each
        removed edge's source (plus the source itself) — read in O(1) per
        row off the packed closure's COLUMN bits — and only those rows are
        re-derived by a bounded masked scan (`masked_delete_scan`) whose
        hop matrix jumps through unaffected rows' still-exact closure rows
        in one step (`kernels/closure_delete.py` fuses the hop on TPU; the
        sharded schedule runs it with zero per-hop collectives).  Vertex
        removals are the same repair seeded at the removed slot: its
        ancestors re-derive without the cleared column, and the slot's own
        row zeroes out — so the slot is safe to recycle immediately.
      - the *delete dispatch arm* (`dispatch.prefer_delete_repair`, wired
        by the engine's policy) weighs the affected-row count against the
        full rebuild's C·log2(C) rows; when repair would not pay, the
        commit falls back to invalidation and the next incremental check
        lazily rebuilds via `transitive_closure` — the two routes are
        decision-identical, only the work differs.

The cache additionally carries ``repair_ema`` — the EMA of measured
delete-repair scan depths — which sharpens the repair-vs-rebuild pricing
the same way the engine's deciding-depth EMA sharpens closure-vs-partial
(and round-trips through `ft/checkpoint.py` with the rest of the cache).

Equivalence (pinned by tests/test_closure_cache.py): for every batch the
incremental check rejects exactly the candidates algorithm 1 rejects —
a path v_i -> u_i in ``G ∪ transit`` either uses no transit edge (the
``closure[v_i, u_i]`` bit) or decomposes into committed-graph segments
between transit edges j1..jk, i.e. a cycle through i in the hop graph —
and a delete-maintained cache equals the from-scratch closure bit for bit.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import bitset
from repro.core.reachability import (MatmulImpl, closure_iteration_bound,
                                     transitive_closure)

# update_impl signature: (closure (C, W), mask (C, B/32), rows (B, W)) ->
# new closure (C, W).  `kernels/ops.closure_update` is the fused TPU
# realization; the default composes the jnp reference inline.
ClosureUpdateImpl = Callable[[jax.Array, jax.Array, jax.Array], jax.Array]

# delete_impl signature: (adj_after (C, W), closure (C, W), affected
# bool[C]) -> (closure' (C, W), n_products int32, row_products int32).
# `masked_delete_scan` is the jnp default (its per-hop product can be the
# fused `kernels/ops.closure_delete`); `sharded.closure_delete_impl` is
# the row-sharded zero-collective schedule.
DeleteScanImpl = Callable[[jax.Array, jax.Array, jax.Array], Tuple]


class ClosureCache(NamedTuple):
    """The packed strict transitive closure of the committed graph, plus a
    staleness flag and the measured repair-depth EMA.  ``dirty=True`` means
    ``closure`` may be stale (a delete was not maintained, or the slab was
    wrapped from unknown state) and must be rebuilt before its bits are
    trusted.

    ``closure`` is either the dense slab ``uint32[C, C/32]`` or a
    `TiledClosure` (32x32-bit tiles confined to a growable region window
    plus a per-tile occupancy summary) — every cache operation dispatches
    on the representation at trace time, so the two layouts share one
    commit protocol."""

    closure: jax.Array     # uint32[C, W] dense, or TiledClosure
    dirty: jax.Array       # bool[]: True -> rebuild before use
    repair_ema: jax.Array  # float32[]: EMA of measured delete-repair scan
    #                        depths (0 = unseeded) — the delete dispatch
    #                        arm's depth estimate

    @property
    def capacity(self) -> int:
        return closure_capacity(self.closure)

    def invalidated_if(self, changed) -> "ClosureCache":
        """Mark dirty when ``changed`` (traced bool) — the fallback for
        mutations that bypass the delta-commit pipeline."""
        return self._replace(dirty=self.dirty | changed)


def empty_cache(capacity: int, dirty: bool = False) -> ClosureCache:
    """Cache for an empty graph (its strict closure IS all-zeros, so
    ``dirty=False`` is exact for a fresh engine).  ``dirty=True`` is the
    conservative wrap of an existing slab of unknown closure."""
    w = bitset.n_words(capacity)
    return ClosureCache(jnp.zeros((capacity, w), jnp.uint32),
                        jnp.asarray(dirty), jnp.zeros((), jnp.float32))


# -------------------------------------------------- tiled representation

TILE = bitset.WORD  # 32x32-bit tiles: one uint32 word per tile row

DEFAULT_REGION = 1024  # fresh tiled caches open a 1024-slot window


class TiledClosure(NamedTuple):
    """Block-sparse packed closure: 32x32-bit tiles confined to a leading
    ``region x region`` window, plus a per-tile occupancy summary bitmap
    over the FULL capacity's tile grid.

    ``tiles`` is bit-identical to the leading ``[:region, :region//32]``
    window of the dense packed closure; every closure bit outside the
    window is guaranteed zero (the *confinement invariant*: the engine
    widens the window before slots beyond it can carry edges, and the
    commit path falls back to invalidation if an accepted edge ever
    spills past it under jit — degrade-to-dirty, never wrong bits).
    ``summary`` packs one bit per 32x32 tile (bit (I, J) set iff the tile
    at rows 32I..32I+31, word-column J is non-empty), so kernels skip
    empty tiles with one word read and the closure's footprint is
    O(region^2 / 8 + C^2 / 1024) bytes instead of the dense C^2 / 8."""

    tiles: jax.Array    # uint32[R, R/32]: closure bits of the window
    summary: jax.Array  # uint32[C/32, ceil(C/1024)]: per-tile occupancy

    @property
    def capacity(self) -> int:
        return self.summary.shape[0] * TILE

    @property
    def region(self) -> int:
        return self.tiles.shape[0]


def is_tiled(closure) -> bool:
    """Trace-time layout dispatch (a pytree-structure fact, not data)."""
    return isinstance(closure, TiledClosure)


def closure_capacity(closure) -> int:
    return closure.capacity if is_tiled(closure) else closure.shape[0]


def closure_nbytes(closure) -> int:
    """Measured closure bytes — the sweep's O(reachable) headline stat
    (tiles + summary for the tiled layout, the slab for dense)."""
    return int(sum(x.nbytes for x in jax.tree_util.tree_leaves(closure)))


def summary_words(capacity: int) -> int:
    """Packed words per summary row (the tile grid is C/32 wide; rows pad
    up to a whole word so capacities below 1024 still pack)."""
    t = capacity // TILE
    return (t + TILE - 1) // TILE


def align_region(n: int, capacity: int) -> int:
    """Smallest valid window >= n: a multiple of 32, capped at capacity."""
    r = max(TILE, ((int(n) + TILE - 1) // TILE) * TILE)
    return min(r, capacity)


def default_region(capacity: int) -> int:
    return align_region(min(capacity, DEFAULT_REGION), capacity)


@jax.named_scope("summary_upkeep")
def build_summary(tiles: jax.Array, capacity: int) -> jax.Array:
    """Per-tile occupancy bitmap of ``tiles`` embedded in the full
    capacity's tile grid — tiles beyond the window are empty under the
    confinement invariant, so their bits stay zero."""
    r, wr = tiles.shape
    t = capacity // TILE
    sw = summary_words(capacity)
    occ = jnp.any((tiles != 0).reshape(r // TILE, TILE, wr), axis=1)
    full = jnp.zeros((t, sw * TILE), bool)
    full = full.at[: r // TILE, :wr].set(occ)
    return bitset.pack_bits(full)


def summary_from_occ(occ: jax.Array, capacity: int) -> jax.Array:
    """Pack the occupancy plane a tiled kernel emitted
    (`kernels/ops.closure_update_tiled` / `closure_delete_tiled` — uint32
    0/1 per tile, region grid) into the capacity summary bitmap.  The
    fused-pass replacement for `build_summary`: no second read of the
    tiles."""
    t = capacity // TILE
    sw = summary_words(capacity)
    tr, tc = occ.shape
    full = jnp.zeros((t, sw * TILE), bool)
    full = full.at[:tr, :tc].set(occ != 0)
    return bitset.pack_bits(full)


def occupied_tiles(closure: TiledClosure) -> jax.Array:
    """int32: live non-empty tile count — the occupancy the dispatch
    pricing reads instead of assuming full capacity."""
    return jnp.sum(bitset.popcount(closure.summary))


def empty_tiled_cache(capacity: int, region: int = 0,
                      dirty: bool = False) -> ClosureCache:
    """Tiled-layout cache for an empty graph (see `empty_cache`)."""
    r = align_region(region or default_region(capacity), capacity)
    tiles = jnp.zeros((r, r // TILE), jnp.uint32)
    return ClosureCache(TiledClosure(tiles, build_summary(tiles, capacity)),
                        jnp.asarray(dirty), jnp.zeros((), jnp.float32))


def region_confined(adj_packed: jax.Array, region: int) -> jax.Array:
    """bool[]: no adjacency bit lies outside the leading region window —
    the precondition for representing the closure in tiles alone."""
    wr = region // TILE
    tail_rows = jnp.any(adj_packed[region:, :] != 0) \
        if adj_packed.shape[0] > region else jnp.asarray(False)
    tail_cols = jnp.any(adj_packed[:region, wr:] != 0) \
        if adj_packed.shape[1] > wr else jnp.asarray(False)
    return ~(tail_rows | tail_cols)


def dense_of(closure) -> jax.Array:
    """The dense uint32[C, C/32] equivalent (zero outside the window) —
    the bit-for-bit bridge the cross-layout property tests compare on."""
    if not is_tiled(closure):
        return closure
    c = closure.capacity
    r, wr = closure.tiles.shape
    return jnp.pad(closure.tiles,
                   ((0, c - r), (0, bitset.n_words(c) - wr)))


def tiled_of(closure: jax.Array, region: int) -> TiledClosure:
    """Re-represent a dense packed closure as tiles — the dense-era
    checkpoint forward-restore path.  ``region`` must already cover every
    set bit; callers check confinement host-side."""
    c = closure.shape[0]
    r = align_region(region, c)
    tiles = closure[:r, : r // TILE]
    return TiledClosure(tiles, build_summary(tiles, c))


def grow_closure(closure, new_capacity: int):
    """Zero-pad a closure to a larger capacity: dense pads the slab;
    tiled pads only the summary grid — the tiles window is untouched, so
    a grow allocates O(C/1024) new bytes instead of O(C^2/8)."""
    if is_tiled(closure):
        if new_capacity == closure.capacity:
            return closure
        t, sw = new_capacity // TILE, summary_words(new_capacity)
        pad = ((0, t - closure.summary.shape[0]),
               (0, sw - closure.summary.shape[1]))
        return TiledClosure(closure.tiles, jnp.pad(closure.summary, pad))
    c, w = closure.shape
    if new_capacity == c:
        return closure
    return jnp.pad(closure, ((0, new_capacity - c),
                             (0, bitset.n_words(new_capacity) - w)))


def grow_region(closure: TiledClosure, new_region: int) -> TiledClosure:
    """Widen the tiles window (summary unchanged — the new tiles are
    empty).  The engine calls this host-side, before traces see the
    window's static shape."""
    r, wr = closure.tiles.shape
    nr = align_region(new_region, closure.capacity)
    if nr <= r:
        return closure
    tiles = jnp.pad(closure.tiles, ((0, nr - r), (0, nr // TILE - wr)))
    return TiledClosure(tiles, closure.summary)


def closure_bit_get(closure, rows, cols) -> jax.Array:
    """Polymorphic `bitset.bit_get`: out-of-window reads are False, which
    is exact under confinement (those slots carry no edges)."""
    if not is_tiled(closure):
        return bitset.bit_get(closure, rows, cols)
    r = closure.region
    rows = jnp.asarray(rows, jnp.int32)
    cols = jnp.asarray(cols, jnp.int32)
    inside = (rows < r) & (cols < r)
    got = bitset.bit_get(closure.tiles, jnp.minimum(rows, r - 1),
                         jnp.minimum(cols, r - 1))
    return got & inside


def grow_cache(cache: ClosureCache, new_capacity: int) -> ClosureCache:
    """Re-embed the cache at a larger capacity in one jit-compatible step.

    `dag.grow_state` keeps slot indices, so the grown graph is the old graph
    plus isolated free slots — its strict closure is exactly the old closure
    zero-padded.  The clean/dirty status and the measured repair-depth EMA
    therefore carry over unchanged: a clean cache stays clean through a grow
    (no spurious rebuild follows), and a dirty one stays merely dirty.
    """
    c = closure_capacity(cache.closure)
    if new_capacity == c:
        return cache
    if new_capacity < c:
        raise ValueError(
            f"cannot shrink: new capacity {new_capacity} < current {c}")
    return ClosureCache(grow_closure(cache.closure, new_capacity),
                        cache.dirty, cache.repair_ema)


def rebuild_cache(adj_packed: jax.Array,
                  matmul_impl: Optional[MatmulImpl] = None) -> ClosureCache:
    """From-scratch rebuild: the lazy-revalidation (and test-oracle) path."""
    return ClosureCache(transitive_closure(adj_packed, matmul_impl),
                        jnp.asarray(False), jnp.zeros((), jnp.float32))


def refresh_closure(closure, dirty: jax.Array, adj_packed: jax.Array,
                    matmul_impl: Optional[MatmulImpl] = None):
    """(trusted closure, n_products): rebuilds iff dirty (a traced
    ``lax.cond``), charging the rebuild's boolean-matmul products.

    A tiled closure rebuilds inside its window — O(region) rows, not
    O(capacity) — and requires the adjacency to be region-confined when
    dirty; the engine widens the window host-side before asking
    (`DagEngine.refresh_cache`), so the precondition holds on every
    host-driven refresh."""
    if is_tiled(closure):
        r = closure.region
        adj_r = adj_packed[:r, : r // TILE]

        def rebuild_t(_):
            cl, n = transitive_closure(adj_r, matmul_impl, with_stats=True)
            return cl, n

        def keep_t(_):
            return closure.tiles, jnp.int32(0)

        confined = region_confined(adj_packed, r)
        tiles, n = jax.lax.cond(dirty & confined, rebuild_t, keep_t, None)
        return TiledClosure(tiles, build_summary(tiles, closure.capacity)), n

    def rebuild(_):
        c, n = transitive_closure(adj_packed, matmul_impl, with_stats=True)
        return c, n

    def keep(_):
        return closure, jnp.int32(0)

    return jax.lax.cond(dirty, rebuild, keep, None)


# ------------------------------------------------------------ typed deltas

def _empty_slots():
    return jnp.zeros((0,), jnp.int32)


def _empty_mask():
    return jnp.zeros((0,), bool)


class CacheDelta(NamedTuple):
    """The typed mutation record every engine mutator emits.

    All masks are *adjacency-diff exact*: a row participates only if the
    mutation actually flipped adjacency bits (the edge existed and was
    cleared — first occurrence of a duplicated pair only; the removed
    vertex had at least one incident edge).  No-op and repeated removals
    therefore commit as empty deltas and leave a clean cache clean, at
    zero repair cost.
    """

    add_u: jax.Array       # int32[Ba]: accepted edge sources (slots)
    add_v: jax.Array       # int32[Ba]: accepted edge targets (slots)
    add_mask: jax.Array    # bool[Ba]: which rows fold in
    rem_u: jax.Array       # int32[Br]: removed edge sources (slots)
    rem_v: jax.Array       # int32[Br]: removed edge targets (slots)
    rem_mask: jax.Array    # bool[Br]: which rows actually cleared a bit
    clear_slots: jax.Array  # int32[Bc]: removed-vertex slots (row+col clear)
    clear_mask: jax.Array   # bool[Bc]: which removals touched adjacency

    @classmethod
    def empty(cls) -> "CacheDelta":
        e, m = _empty_slots(), _empty_mask()
        return cls(e, e, m, e, e, m, e, m)

    @classmethod
    def edges_added(cls, u_slots, v_slots, mask) -> "CacheDelta":
        e, m = _empty_slots(), _empty_mask()
        return cls(u_slots, v_slots, mask, e, e, m, e, m)

    @classmethod
    def edges_removed(cls, u_slots, v_slots, mask) -> "CacheDelta":
        e, m = _empty_slots(), _empty_mask()
        return cls(e, e, m, u_slots, v_slots, mask, e, m)

    @classmethod
    def vertices_cleared(cls, slots, mask) -> "CacheDelta":
        e, m = _empty_slots(), _empty_mask()
        return cls(e, e, m, e, e, m, slots, mask)

    @classmethod
    def merge(cls, *deltas: "CacheDelta") -> "CacheDelta":
        """Concatenate several same-tick deltas into ONE (field-wise).

        Exact for a phase-ordered run (every delete-recording delta before
        every add-recording one — the front-end tick's linearization):
        `commit` applies the merged delete side in one affected-row pass
        against the final adjacency, which is order-free for a set of
        removals, and folds the whole accepted add set last.  A mixed
        add+delete tick therefore pays one repair pass instead of two,
        with accept decisions identical to committing each delta alone
        (pinned in tests/test_tiled_closure.py)."""
        return cls(*[jnp.concatenate([d[i] for d in deltas])
                     for i in range(len(cls._fields))])

    def removal_seeds(self):
        """(seeds int32[Br+Bc], mask bool[Br+Bc]): the slots whose ancestor
        rows need re-derivation.  A removed edge (u, v) can only shrink the
        reach sets of u's ancestors (and u); a removed vertex r can only
        shrink the reach sets of r's ancestors (and r) — every in-neighbor
        of r IS such an ancestor, so one seed covers row and column clears
        alike."""
        return (jnp.concatenate([self.rem_u, self.clear_slots]),
                jnp.concatenate([self.rem_mask, self.clear_mask]))


@jax.named_scope("delete_repair")
def affected_rows(closure: jax.Array, seeds: jax.Array,
                  mask: jax.Array) -> jax.Array:
    """bool[C]: rows whose reach sets a removal at ``seeds`` can shrink —
    the union over enabled seeds s of (ancestors of s, read off the packed
    closure's COLUMN bits: one gather + shift per seed) plus s itself."""
    c = closure.shape[0]
    if seeds.shape[0] == 0:
        return jnp.zeros((c,), bool)
    word = seeds >> 5
    shift = (seeds & 31).astype(jnp.uint32)
    anc = ((closure[:, word] >> shift[None, :]) & jnp.uint32(1)) != 0  # (C,B)
    is_seed = jnp.arange(c, dtype=jnp.int32)[:, None] == seeds[None, :]
    return jnp.any((anc | is_seed) & mask[None, :], axis=1)


@jax.named_scope("delete_repair")
def masked_delete_scan(adj_after: jax.Array, closure: jax.Array,
                       affected: jax.Array, hop_impl=None,
                       square: bool = False):
    """Re-derive the affected rows of a delete-maintained closure.

    The scan's hop matrix ``S = where(affected, adj_after, closure)`` lets
    a frontier jump through an UNAFFECTED row's still-exact closure row in
    one step (those rows are fixed points: everything they reach is already
    transitively closed), so the fixpoint ``R <- R | R @ S`` from ``R = S``
    converges at the depth of the longest chain through *affected* vertices
    — the bounded masked scan, not a full re-closure.  Unaffected rows pass
    through unchanged.

    ``square=True`` iterates ``R <- R | R @ R`` (masked the same way)
    instead: after k products R holds every S-path of up to 2^k steps, so
    the same fixpoint — the strict closure of S on the affected rows, bit
    for bit — is reached in ceil(log2(depth)) + 1 products rather than
    depth + 1.  The tiled layout scans this way, on one device and on a
    mesh (`sharded.closure_delete_impl(square=True)`, one all-gather of R
    per product); the dense layout keeps the fixed-S hop, which on a mesh
    needs no per-hop collective.

    ``hop_impl`` overrides one hop: (R (C, W), S (C, W), affected_packed
    (W,)) -> next R — `kernels/ops.closure_delete` fuses the masked
    product + OR + pack on TPU.

    Returns (closure', n_products, row_products) where row_products counts
    only the affected rows each product re-derives (the comparable work
    unit `benchmarks/compare.py` gates against the rebuild's C-row
    products).
    """
    from repro.core.reachability import bool_matmul_packed

    s = jnp.where(affected[:, None], adj_after, closure)
    affp = bitset.pack_bits(affected)
    if hop_impl is None:
        def hop_impl(r, s_, aff_packed):
            del aff_packed
            return jnp.where(affected[:, None],
                             r | bool_matmul_packed(r, s_), r)

    def cond(carry):
        _, _, changed = carry
        return changed

    def body(carry):
        r, i, _ = carry
        rn = hop_impl(r, r if square else s, affp)
        return rn, i + 1, jnp.any(rn != r)

    r, n, _ = jax.lax.while_loop(
        cond, body, (s, jnp.int32(0), jnp.any(affected)))
    n_aff = jnp.sum(affected, dtype=jnp.int32)
    return r, n, n * n_aff


def _tiled_delete_scan(adj_after: jax.Array, tiles: jax.Array,
                       affected: jax.Array):
    """The default delete side of a tiled cache: `masked_delete_scan` on
    the tiles window by repeated squaring, each product through
    `kernels/ops.closure_delete_tiled` (the fused kernel on TPU, its jnp
    reference elsewhere)."""
    from repro.kernels import ops as kernel_ops

    def hop(r, s, aff_packed):
        return kernel_ops.closure_delete_tiled(r, s, aff_packed)[0]

    return masked_delete_scan(adj_after, tiles, affected, hop_impl=hop,
                              square=True)


def commit(cache: ClosureCache, delta: CacheDelta, adj_after: jax.Array, *,
           update_impl: Optional[ClosureUpdateImpl] = None,
           delete_impl: Optional[DeleteScanImpl] = None,
           prefer_repair_fn=None, ema_alpha: float = 0.25,
           with_stats: bool = False):
    """The single entry point applying a typed `CacheDelta` to the cache.

    Delete side first (a phase's removals precede its adds in the
    linearization): on a clean cache with any adjacency-touching removal,
    ``prefer_repair_fn(n_affected, repair_ema)`` (default:
    `dispatch.prefer_delete_repair` — the cost model's fourth arm) picks
    between the masked affected-row re-derivation (cache stays CLEAN) and
    invalidation (lazy rebuild at the next check).  A dirty cache commits
    removals as a no-op — there is nothing to maintain.  Adds then fold in
    with the rank-B `insert_update` (skipped on a dirty cache).

    Returns ``cache'`` — or ``(cache', stats)`` with ``with_stats``, where
    stats counts the repair's products/row-products and whether a repair
    ran (``n_repair``); invalidation costs zero here (its rebuild is
    charged where it happens, at the next incremental check).
    """
    closure, dirty, ema = cache.closure, cache.dirty, cache.repair_ema
    tiled = is_tiled(closure)
    if tiled:
        region = closure.region
        work = closure.tiles
        summary = closure.summary
        adj_work = adj_after[:region, : region // TILE]
    else:
        region = closure.shape[0]
        work = closure
        adj_work = adj_after
    z = jnp.int32(0)
    n_products, row_products, n_repair = z, z, z
    seeds, smask = delta.removal_seeds()
    if seeds.shape[0]:
        any_removed = jnp.any(smask)
        if tiled:
            # an enabled out-of-window seed contradicts confinement (only
            # possible on an already-stale cache) — force invalidation
            in_region = seeds < region
            smask_w = smask & in_region
            seeds_w = jnp.minimum(seeds, region - 1)
            blocked = jnp.any(smask & ~in_region)
        else:
            smask_w, seeds_w = smask, seeds
            blocked = jnp.asarray(False)
        affected = affected_rows(work, seeds_w, smask_w)
        n_aff = jnp.sum(affected, dtype=jnp.int32)
        if prefer_repair_fn is None:
            from repro.core import dispatch

            def prefer_repair_fn(n, depth_hint):
                # tiled prices repair against the live window's rebuild,
                # not the full-capacity one
                return dispatch.prefer_delete_repair(n, region, depth_hint)

        scan = delete_impl if delete_impl is not None else \
            _tiled_delete_scan if tiled else masked_delete_scan
        do_repair = ~dirty & any_removed & ~blocked \
            & prefer_repair_fn(n_aff, ema)

        def repair(args):
            cl, em = args
            cl2, n, rows = scan(adj_work, cl, affected)
            d = n.astype(jnp.float32)
            em2 = jnp.where(em > 0,
                            (1.0 - ema_alpha) * em + ema_alpha * d, d)
            return cl2, jnp.asarray(False), em2, n, rows, jnp.int32(1)

        def invalidate(args):
            cl, em = args
            return cl, dirty | any_removed, em, z, z, z

        work, dirty, ema, n_products, row_products, n_repair = \
            jax.lax.cond(do_repair, repair, invalidate, (work, ema))
        if tiled:
            summary = build_summary(work, closure.capacity)
    if delta.add_u.shape[0]:
        if tiled:
            # an accepted edge past the window can't fold into the tiles:
            # `insert_update_tiled` skips the fold and reports the spill,
            # and the cache goes dirty (the next check rebuilds in a wider
            # window) — degrade-to-dirty, never wrong bits
            def fold_t(t):
                out, spill = insert_update_tiled(
                    TiledClosure(*t), delta.add_u, delta.add_v,
                    delta.add_mask, update_impl)
                return tuple(out), spill

            (work, summary), spill = jax.lax.cond(
                dirty, lambda t: (t, jnp.asarray(False)), fold_t,
                (work, summary))
            dirty = dirty | spill
        else:
            def fold(cl):
                return insert_update(cl, delta.add_u, delta.add_v,
                                     delta.add_mask, update_impl)

            work = jax.lax.cond(dirty | ~jnp.any(delta.add_mask),
                                lambda cl: cl, fold, work)
    closure = TiledClosure(work, summary) if tiled else work
    out = ClosureCache(closure, dirty, ema)
    if with_stats:
        return out, {"n_products": n_products, "row_products": row_products,
                     "n_repair": n_repair}
    return out


def apply_delta(closure: jax.Array, adj_after: jax.Array, delta: CacheDelta,
                *, update_impl: Optional[ClosureUpdateImpl] = None,
                delete_impl: Optional[DeleteScanImpl] = None) -> jax.Array:
    """Reader-side (replica) application of one shipped `CacheDelta`.

    Unlike `commit`, there is no dispatch arm, no dirty flag, and no cycle
    check: the primary already decided every accept/reject (the delta's
    masks ARE those decisions), so a replica applies the delta with the
    same two kernels unconditionally — removals repair by affected-row
    re-derivation against the post-delta adjacency mirror, adds fold in
    with the rank-B update.  Replaying an already-applied delta is a
    no-op: the add fold is an OR and the repair re-derives the affected
    rows from ``adj_after``, which already reflects the delta — the
    idempotence `repro/replica.py`'s checkpoint-tail recovery leans on.

    Returns the new closure (delete side first, matching the commit
    linearization).  A tiled closure applies inside its window — the
    caller (`repro.replica.Replica.apply`) widens the window to cover
    every slot the delta addresses before applying.
    """
    tiled = is_tiled(closure)
    if tiled:
        region = closure.region
        work = closure.tiles
        adj_work = adj_after[:region, : region // TILE]
    else:
        work = closure
        adj_work = adj_after
    seeds, smask = delta.removal_seeds()
    if seeds.shape[0]:
        if tiled:
            smask_w = smask & (seeds < region)
            seeds_w = jnp.minimum(seeds, region - 1)
        else:
            smask_w, seeds_w = smask, seeds
        affected = affected_rows(work, seeds_w, smask_w)
        scan = delete_impl if delete_impl is not None else \
            _tiled_delete_scan if tiled else masked_delete_scan
        work, _, _ = scan(adj_work, work, affected)
    if tiled:
        out = TiledClosure(work, build_summary(work, closure.capacity)) \
            if seeds.shape[0] else closure
        if delta.add_u.shape[0]:
            out, _ = insert_update_tiled(out, delta.add_u, delta.add_v,
                                         delta.add_mask, update_impl)
        return out
    if delta.add_u.shape[0]:
        def fold(cl):
            return insert_update(cl, delta.add_u, delta.add_v,
                                 delta.add_mask, update_impl)

        work = jax.lax.cond(~jnp.any(delta.add_mask),
                            lambda cl: cl, fold, work)
    return work


# --------------------------------------------------- candidate hop graph

def _closure_bool_small(a: jax.Array, strict: bool = True) -> jax.Array:
    """Transitive closure of a small dense bool[B, B] matrix by repeated
    squaring (f32 matmuls on the VPU/MXU — B is a candidate batch, not the
    capacity, so this is noise next to even one C-row product)."""
    b = a.shape[0]
    n_iter = closure_iteration_bound(b)
    if not strict:
        a = a | jnp.eye(b, dtype=bool)

    def body(_, r):
        rf = r.astype(jnp.float32)
        return r | ((rf @ rf) > 0)

    return jax.lax.fori_loop(0, n_iter, body, a)


def candidate_hop_matrix(closure, u_slots: jax.Array,
                         v_slots: jax.Array, mask: jax.Array) -> jax.Array:
    """A[i, j] = mask[i] & mask[j] & "candidate i's target reaches
    candidate j's source through the committed graph (>= 0 edges)".

    Polymorphic over the layout: tiled closures read their window with
    out-of-window slots contributing zero reach bits — exact under the
    confinement invariant (those slots carry no committed edges)."""
    if is_tiled(closure):
        r = closure.region
        v_in, u_in = v_slots < r, u_slots < r
        rows_v = jnp.where(
            v_in[:, None],
            closure.tiles[jnp.minimum(v_slots, r - 1)], jnp.uint32(0))
        u_c = jnp.minimum(u_slots, r - 1)
        word = u_c >> 5
        shift = (u_c & 31).astype(jnp.uint32)
        reach = ((rows_v[:, word] >> shift[None, :]) & jnp.uint32(1)) != 0
        reach = reach & u_in[None, :]
    else:
        rows_v = closure[v_slots]                   # (B, W)
        word = u_slots >> 5
        shift = (u_slots & 31).astype(jnp.uint32)
        reach = ((rows_v[:, word] >> shift[None, :]) & jnp.uint32(1)) != 0
    hop = reach | (v_slots[:, None] == u_slots[None, :])
    return hop & mask[:, None] & mask[None, :]


def incremental_cycle_check(closure, u_slots: jax.Array,
                            v_slots: jax.Array, cand: jax.Array) -> jax.Array:
    """cyc[b] = True iff candidate edge (u_b, v_b) lies on a cycle of
    ``G ∪ transit`` — decided entirely against the cached closure:
    B^2 bit reads + one B x B boolean closure, zero C-row products."""
    hop = candidate_hop_matrix(closure, u_slots, v_slots, cand)
    hop_closure = _closure_bool_small(hop, strict=True)
    b = u_slots.shape[0]
    idx = jnp.arange(b)
    return hop_closure[idx, idx] & cand


# --------------------------------------------------------- rank-B update

def _pad32(n: int) -> int:
    return ((n + 31) // 32) * 32


def _default_update_impl(closure: jax.Array, mask_packed: jax.Array,
                         rows_packed: jax.Array) -> jax.Array:
    """jnp reference of `kernels/closure_update.py` (kept importable from
    core without a kernels dependency)."""
    from repro.core.reachability import bool_matmul_packed

    return closure | bool_matmul_packed(mask_packed, rows_packed)


def chunked_update_impl(block_rows: int = 1024) -> ClosureUpdateImpl:
    """Memory-bounded jnp realization of the rank-B update.

    The reference `_default_update_impl` unpacks both operands and
    materializes the full (C, C) float product — ~17 GB at C = 2^16 — so it
    cannot run large capacities on a host CPU.  This variant streams the
    closure in ``block_rows``-row blocks via `lax.map`: per block it is a
    (R, B) x (B, C) float product packed straight back to words, bounding
    transient memory at O(block_rows * C) floats while computing the
    identical result.  `benchmarks/capacity_sweep.py` wires it as the
    engine's ``closure_update_impl`` for the large-capacity rows.
    """
    def impl(closure: jax.Array, mask_packed: jax.Array,
             rows_packed: jax.Array) -> jax.Array:
        c = closure.shape[0]
        r = min(block_rows, c)
        if c % r != 0:  # fall back rather than pad the row axis
            return _default_update_impl(closure, mask_packed, rows_packed)
        rows = bitset.unpack_bits(rows_packed).astype(jnp.float32)  # (B, C)

        def block(args):
            cl_blk, mask_blk = args
            m = bitset.unpack_bits(mask_blk).astype(jnp.float32)  # (R, B)
            return cl_blk | bitset.pack_bits((m @ rows) > 0)

        out = jax.lax.map(block, (closure.reshape(c // r, r, -1),
                                  mask_packed.reshape(c // r, r, -1)))
        return out.reshape(c, -1)

    return impl


@jax.named_scope("insert_fold")
def insert_update(closure: jax.Array, u_slots: jax.Array,
                  v_slots: jax.Array, accepted: jax.Array,
                  update_impl: Optional[ClosureUpdateImpl] = None
                  ) -> jax.Array:
    """Fold a jointly-acyclic accepted edge batch into the strict closure
    (the add side of `commit`; `core/acyclic.py` calls it fused with the
    incremental check, one fold per sub-batch).

    new[w, x] = old[w, x]  |  exists accepted edges j1..jk (k >= 1) with
                w ->G* u_{j1}, chained targets->sources through G, and
                v_{jk} ->G* x

    realized as ``old | L @ Sstar @ R`` where L[w, j] = "w reaches u_j"
    (C x B bit reads off the old closure), Sstar is the hop graph's
    reflexive-transitive closure (pre-composing edge chains), and
    R[j] = closure[v_j] | onehot(v_j) (the rows an edge contributes).
    ``L @ Sstar`` collapses into the mask, so the heavy (C x B) x (B x C)
    OR-accumulate is ONE call of ``update_impl`` — the fused Pallas kernel
    on TPU, its jnp reference elsewhere.
    """
    impl = update_impl if update_impl is not None else _default_update_impl
    c = closure.shape[0]
    b = u_slots.shape[0]

    # Sstar: chains of >= 0 accepted edges between a consumed and a
    # starting edge (reflexive-transitive closure of the hop graph)
    hop = candidate_hop_matrix(closure, u_slots, v_slots, accepted)
    sstar = _closure_bool_small(hop, strict=False)

    # L[w, j] = accepted[j] & (w == u_j | closure[w, u_j])
    word = u_slots >> 5
    shift = (u_slots & 31).astype(jnp.uint32)
    reaches_u = ((closure[:, word] >> shift[None, :]) & jnp.uint32(1)) != 0
    is_u = jnp.arange(c, dtype=jnp.int32)[:, None] == u_slots[None, :]
    l_mask = (reaches_u | is_u) & accepted[None, :]

    # mask = L @ Sstar (C x B bool — small next to the rank-B update)
    mask = (l_mask.astype(jnp.float32) @ sstar.astype(jnp.float32)) > 0

    # R[j] = closure[v_j] | onehot(v_j), zeroed for rejected rows
    rows = closure[v_slots] | bitset.onehot_rows(v_slots, c)
    rows = jnp.where(accepted[:, None], rows, jnp.uint32(0))

    # pad B to a word multiple for the packed-mask kernel layout
    bp = _pad32(b)
    if bp != b:
        mask = jnp.pad(mask, ((0, 0), (0, bp - b)))
        rows = jnp.pad(rows, ((0, bp - b), (0, 0)))
    return impl(closure, bitset.pack_bits(mask), rows)


@jax.named_scope("insert_fold")
def insert_update_tiled(closure: TiledClosure, u_slots: jax.Array,
                        v_slots: jax.Array, accepted: jax.Array,
                        update_impl: Optional[ClosureUpdateImpl] = None):
    """The rank-B fold on the tiled layout: `insert_update` runs on the
    tiles window (region-row operands) and the summary comes out of the
    SAME fused pass — with no ``update_impl`` override the fold routes
    through `kernels/ops.closure_update_tiled`, whose epilogue emits the
    per-tile occupancy plane alongside the new tiles (an explicit
    override, e.g. the row-sharded mesh impl, pays one `build_summary`
    pass over the window instead).

    Returns ``(closure', spilled)``: an accepted edge whose endpoint lies
    past the window cannot fold into the tiles, so the whole fold is
    skipped and ``spilled=True`` tells the caller to mark the cache dirty
    (the next check rebuilds once the engine widens the window) — the
    bits in a clean tiled cache are always exact."""
    r = closure.region
    capacity = closure.capacity
    spill = jnp.any(accepted & ((u_slots >= r) | (v_slots >= r)))
    uc = jnp.minimum(u_slots, r - 1)
    vc = jnp.minimum(v_slots, r - 1)

    def keep(t):
        return t, closure.summary

    if update_impl is None:
        def fold(t):
            from repro.kernels import ops as kernel_ops
            occ_box = {}

            def fused(cl, mask_packed, rows_packed):
                out, occ = kernel_ops.closure_update_tiled(
                    cl, mask_packed, rows_packed)
                occ_box["occ"] = occ
                return out

            t2 = insert_update(t, uc, vc, accepted, fused)
            return t2, summary_from_occ(occ_box["occ"], capacity)
    else:
        def fold(t):
            t2 = insert_update(t, uc, vc, accepted, update_impl)
            return t2, build_summary(t2, capacity)

    tiles, summary = jax.lax.cond(spill | ~jnp.any(accepted), keep, fold,
                                  closure.tiles)
    return TiledClosure(tiles, summary), spill


# -------------------------------------------------------------- validation

def cache_matches_state(cache: ClosureCache, adj_packed: jax.Array,
                        matmul_impl: Optional[MatmulImpl] = None) -> jax.Array:
    """True iff a clean cache's closure equals the from-scratch closure of
    ``adj_packed`` (dirty caches vacuously match — their bits are not
    trusted).  The invariant every incremental test asserts.  A tiled
    cache additionally checks its occupancy summary against the tiles."""
    want = transitive_closure(adj_packed, matmul_impl)
    ok = jnp.all(dense_of(cache.closure) == want)
    if is_tiled(cache.closure):
        ok = ok & jnp.all(cache.closure.summary
                          == build_summary(cache.closure.tiles,
                                           cache.closure.capacity))
    return cache.dirty | ok

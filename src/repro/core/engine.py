"""Unified `DagEngine` session API — one façade over the local and sharded
engines.

The paper's object is a single concurrent DAG with a small linearizable
operation set; this module exposes exactly that as an immutable,
pytree-registered session object:

    eng = DagEngine.create(1024)                    # local, method="auto"
    eng, r = eng.add_vertices(keys)                 # r: OpResult
    eng, r = eng.add_edges_acyclic(us, vs)          # cycle-checked inserts
    hit    = eng.reachable(from_keys, to_keys)      # wait-free read
    eng, r = eng.apply(OpBatch(op, a, b))           # mixed typed batch

Design points:

* **Configuration is captured once** in an `EngineConfig` (static pytree
  aux data): capacity, backend ("local" | "sharded"), dispatch policy,
  sub-batch count, and the boolean-matmul implementation.  No per-call
  ``method=``/``subbatches=``/``matmul_impl=`` threading.
* **Every mutating call returns ``(engine, OpResult)``** — the engine is a
  registered pytree whose dynamic leaves are the `DagState` slab, a
  per-shard measured deciding-depth EMA (float32[S]), and the incremental
  transitive-closure cache (`core/closure_cache.ClosureCache`), so whole
  sessions ``jit``, ``lax.scan``, and checkpoint like any other jax state
  (`ft/checkpoint.save_engine_checkpoint`).
* **Dispatch is a pluggable policy** (`core/dispatch.DispatchPolicy`):
  `CostModelPolicy` (the ``method="auto"`` default) short-circuits to the
  cached O(B) incremental check whenever the closure cache is clean, and
  otherwise prices algorithm 1 vs algorithm 2 per batch — seeding its
  depth estimate from the engine's *measured* deciding-depth EMA once one
  exists — while `FixedPolicy("closure" | "partial" | "incremental")`
  pins one algorithm statically.
* **The closure cache amortizes the hot path**: acyclic inserts against a
  clean cache execute ZERO boolean matmul products (B^2 bit reads + a
  B x B candidate-hop closure) and fold accepted edges back in with one
  rank-B update (`kernels/closure_update.py` on TPU, row-sharded on the
  mesh).
* **Every mutation commits a typed delta**: mutators emit a
  `core/closure_cache.CacheDelta` (edges added, edges removed, vertex
  columns cleared — adj-diff exact) applied through the single
  `closure_cache.commit` entry point.  Deletes are MAINTAINED: the commit
  re-derives only the affected rows (ancestors of each removed edge's
  source, read off the packed closure's column bits) with a bounded
  masked scan (`kernels/closure_delete.py` on TPU, row-sharded with zero
  per-hop collectives on the mesh), so delete-heavy serving stays on the
  zero-product fast path; the policy's fourth arm
  (`prefer_delete_repair`) falls back to invalidate + lazy rebuild when
  the affected region approaches the whole graph.
* **The sharded backend routes through the same policy**: acyclic inserts
  dispatch closure-vs-partial exactly like the local backend, and the
  partial scan's schedule (B-sharded vs frontier-sharded,
  `core/sharded.py`) is chosen by ``policy.scan_sharding`` — closing the
  gap where the sharded engine bypassed the auto dispatcher.

Typed batches replace the positional ``(op, a, b)`` arrays: `OpBatch` has
constructors per operation plus ``concat``, `OpResult` carries the ok bits,
the capacity-overflow count of the call, and `ReachStats` (the cycle-check
work accounting, including the last deciding hop depth fed back into the
cost model).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import bitset, closure_cache, dispatch, reachability, snapshot
from repro.core import acyclic as acyclic_mod
from repro.core import dag as dag_mod
from repro.core import snapshot_view
from repro.core.closure_cache import ClosureCache
from repro.core.dag import (
    ADD_EDGE, ADD_VERTEX, CONTAINS_EDGE, CONTAINS_VERTEX, DagState,
    REMOVE_EDGE, REMOVE_VERTEX,
)
from repro.core.reachability import MatmulImpl

BACKENDS = ("local", "sharded")


# ------------------------------------------------------------ typed batches

class OpBatch(NamedTuple):
    """A typed batch of operation requests (one row per logical "thread").

    ``op`` holds the `core/dag.py` op codes; ``a``/``b`` are the operands
    (``b`` is ignored by vertex ops).  Linearization inside one batch is
    the documented phase order: RemoveVertex -> AddVertex -> RemoveEdge ->
    AddEdge -> reads, then batch-index order within a phase.
    """

    op: jax.Array  # int32[B] op codes
    a: jax.Array   # int32[B] first key operand
    b: jax.Array   # int32[B] second key operand (edge target)

    @staticmethod
    def _of(code: int, a, b=None) -> "OpBatch":
        a = jnp.asarray(a, jnp.int32)
        b = jnp.zeros_like(a) if b is None else jnp.asarray(b, jnp.int32)
        return OpBatch(jnp.full(a.shape, code, jnp.int32), a, b)

    @classmethod
    def add_vertices(cls, keys) -> "OpBatch":
        return cls._of(ADD_VERTEX, keys)

    @classmethod
    def remove_vertices(cls, keys) -> "OpBatch":
        return cls._of(REMOVE_VERTEX, keys)

    @classmethod
    def add_edges(cls, us, vs) -> "OpBatch":
        """AcyclicAddEdge requests (the engine's ADD_EDGE is cycle-checked
        under ``apply(..., acyclic=True)``, the default)."""
        return cls._of(ADD_EDGE, us, vs)

    @classmethod
    def remove_edges(cls, us, vs) -> "OpBatch":
        return cls._of(REMOVE_EDGE, us, vs)

    @classmethod
    def contains_vertices(cls, keys) -> "OpBatch":
        return cls._of(CONTAINS_VERTEX, keys)

    @classmethod
    def contains_edges(cls, us, vs) -> "OpBatch":
        return cls._of(CONTAINS_EDGE, us, vs)

    @classmethod
    def concat(cls, *batches: "OpBatch") -> "OpBatch":
        return cls(jnp.concatenate([x.op for x in batches]),
                   jnp.concatenate([x.a for x in batches]),
                   jnp.concatenate([x.b for x in batches]))

    @property
    def size(self) -> int:
        return self.op.shape[0]


class ReachStats(NamedTuple):
    """Cycle-check work accounting (replaces the ad-hoc stats dicts).

    ``deciding_depth`` is int32[S] (S = shard count, 1 on the local
    backend): the per-shard deciding hop counts of the call's last
    algorithm-2 check (all-zero if none ran) — the measurement
    `CostModelPolicy` folds into the engine's per-shard depth-EMA vector.
    ``n_incremental`` counts sub-batch checks the closure cache decided —
    with a clean cache those execute ZERO boolean matmul products.
    ``n_repair`` counts the delete-repair commits of the call (masked
    affected-row re-derivations that kept the cache clean through a
    removal); their products/rows are included in ``n_products`` /
    ``row_products``.  ``n_steps`` counts the insert loop's sub-batch
    steps run (one per sub-batch holding a valid row), ``n_useful_steps``
    those holding at least one candidate edge, and ``n_skipped_steps``
    the all-padding sub-batches the loop did not run.
    """

    n_products: jax.Array      # int32: boolean matmuls executed
    row_products: jax.Array    # int32: total rows fed through the matmul
    n_partial: jax.Array       # int32: sub-batch checks algorithm 2 decided
    n_incremental: jax.Array   # int32: sub-batch checks the cache decided
    deciding_depth: jax.Array  # int32[S]: last partial check's hop counts
    n_repair: jax.Array        # int32: delete-repair commits of this call
    n_steps: jax.Array         # int32: insert-loop sub-batch steps run
    n_useful_steps: jax.Array  # int32: of those, steps with a candidate
    n_skipped_steps: jax.Array  # int32: all-padding sub-batches not run

    @classmethod
    def zeros(cls, n_shards: int = 1) -> "ReachStats":
        z = jnp.int32(0)
        return cls(z, z, z, z, jnp.zeros((n_shards,), jnp.int32), z, z, z,
                   z)

    @classmethod
    def from_raw(cls, stats: dict) -> "ReachStats":
        return cls(stats["n_products"], stats["row_products"],
                   stats["n_partial"], stats["n_incremental"],
                   stats["deciding_depth"], stats["n_repair"],
                   stats["n_steps"], stats["n_useful_steps"],
                   stats["n_skipped_steps"])


# the scalar counts of `ReachStats`, in the order `tally_of` packs them
TALLY_COUNTS = ("n_products", "row_products", "n_repair", "n_incremental",
                "n_partial", "n_steps", "n_useful_steps", "n_skipped_steps")


class OpResult(NamedTuple):
    """Result of one engine call: per-row ok bits, the number of vertex
    adds this call dropped for capacity (serving backpressure signal), and
    the cycle-check stats (zeros when no reachability check ran).
    ``tally`` is `tally_of` the result where a compiled step packed it
    (`repro.replica.Primary` with ``jit=True``), else None."""

    ok: jax.Array          # bool[B]
    n_overflow: jax.Array  # int32: adds dropped for capacity, this call
    stats: ReachStats
    tally: Optional[jax.Array] = None


def tally_of(res: OpResult) -> jax.Array:
    """int32[B + len(TALLY_COUNTS)]: the call's ok bits, then its
    `ReachStats` counts — one array, so a host that waits on the answer
    copies the work accounting in the same transfer."""
    counts = jnp.stack([getattr(res.stats, k) for k in TALLY_COUNTS])
    return jnp.concatenate([res.ok.astype(jnp.int32),
                            counts.astype(jnp.int32)])


# ----------------------------------------------------------- configuration

@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static session configuration (pytree aux data — hashable, compared
    by value so jit caches and scans treat equal configs as one trace)."""

    capacity: int
    backend: str = "local"
    method: str = "auto"
    subbatches: int = 1
    matmul_impl: Optional[MatmulImpl] = None
    policy: Optional[dispatch.DispatchPolicy] = None
    mesh: Optional[object] = None  # jax.sharding.Mesh for backend="sharded"
    # explicit rank-B closure-cache fold-in override (e.g.
    # `kernels/ops.closure_update` on TPU).  None = derived at call time:
    # the row-sharded shard_map schedule on backend="sharded", the jnp
    # reference locally — deriving lazily keeps equal-parameter configs
    # EQUAL (a baked-in closure would be compared by identity and defeat
    # jit cache reuse across engines)
    closure_update_impl: Optional[object] = None
    # explicit delete-repair scan override (signature: (adj_after, closure,
    # affected) -> (closure', n_products, row_products); e.g.
    # `closure_cache.masked_delete_scan` with the fused
    # `kernels/ops.closure_delete` hop on TPU).  None = derived like
    # closure_update_impl: row-sharded on the mesh; locally the jnp scan on
    # a dense cache, the `kernels/ops.closure_delete_tiled` hop on tiles
    closure_delete_impl: Optional[object] = None
    # eager-call backpressure reaction: when a mutating call reports
    # ``n_overflow > 0``, double capacity (via `DagEngine.grow`) until the
    # dropped adds fit and transparently re-run the call.  Host-side only —
    # under jit shapes are static, so traced calls keep the report-and-drop
    # contract and the controller grows between ticks (`launch/serve.py`)
    auto_grow: bool = False
    # closure-cache representation: "dense" keeps the uint32[C, C/32]
    # slab; "tiled" stores 32x32-bit tiles confined to a growable region
    # window plus a per-tile occupancy summary
    # (`closure_cache.TiledClosure`) — closure bytes track the reachable
    # set instead of paying C^2/8, and kernels skip empty tiles
    closure_layout: str = "dense"
    # initial tiles-window size for closure_layout="tiled" (0 = derived:
    # min(capacity, 1024)).  Eager calls widen the window automatically;
    # compiled loops should pre-size it to their working set — an edge
    # past the window under jit degrades to dirty + exact fallback
    # checks, never to wrong answers
    closure_region: int = 0

    @property
    def n_devices(self) -> int:
        return int(self.mesh.devices.size) if self.mesh is not None else 1


def _capacity_alignment(backend: str, n_dev: int) -> Tuple[int, str]:
    """(required multiple, human reason) for a backend's capacity grid."""
    if backend == "sharded":
        return (bitset.WORD * n_dev,
                f"32 bits x {n_dev} devices")
    return bitset.WORD, "32-bit packed words"


def validate_capacity(capacity: int, *, backend: str = "local",
                      n_dev: int = 1, what: str = "capacity") -> None:
    """Raise ValueError unless ``capacity`` sits on the backend's grid
    (local: a multiple of WORD; sharded: of WORD * n_dev), naming the
    nearest valid capacity in the message.  Shared by `DagEngine.create`
    and `DagEngine.grow` so the error fires up front, not post-hoc from
    deep inside `bitset.n_words` or a mesh reshape."""
    align, why = _capacity_alignment(backend, n_dev)
    if capacity <= 0:
        raise ValueError(f"{what} must be positive, got {capacity}")
    if capacity % align != 0:
        down = (capacity // align) * align
        up = down + align
        # ties round UP: the request is a floor (a grow that suggests the
        # current capacity back would be no suggestion at all)
        nearest = up if (down == 0 or capacity - down >= up - capacity) \
            else down
        raise ValueError(
            f"{backend} {what} must be a multiple of {align} ({why}), got "
            f"{capacity}; nearest valid capacity is {nearest}")


@jax.tree_util.register_pytree_node_class
class DagEngine:
    """The unified concurrent-DAG session object.  Immutable: every
    mutating call returns a new engine sharing the static config."""

    __slots__ = ("state", "depth_ema", "cache", "config", "epoch")

    def __init__(self, state: DagState, depth_ema: jax.Array,
                 cache: ClosureCache, config: EngineConfig, epoch=None):
        self.state = state
        self.depth_ema = depth_ema  # float32[S]: per-shard deciding-depth EMA
        self.cache = cache          # incremental transitive-closure cache
        self.config = config
        # version counter: bumped by every mutation commit (not by views,
        # refresh, or grow — growth is a re-embedding of the SAME graph
        # version, which keeps grown-vs-fresh replay equality leaf-exact).
        # The counter names snapshots (`EngineSnapshot.epoch`) and orders
        # the replication log (`repro/replica.py`); it is a dynamic leaf,
        # so checkpoints capture it and crash recovery knows where the
        # delta-log tail starts.
        self.epoch = jnp.zeros((), jnp.int32) if epoch is None else epoch

    # ------------------------------------------------------- construction

    @classmethod
    def create(cls, capacity: int, *, backend: str = "local",
               method: str = "auto", subbatches: int = 1,
               matmul_impl: Optional[MatmulImpl] = None,
               policy: Optional[dispatch.DispatchPolicy] = None,
               mesh=None, closure_update_impl=None,
               closure_delete_impl=None,
               auto_grow: bool = False,
               closure_layout: str = "dense",
               closure_region: int = 0) -> "DagEngine":
        """Create an empty engine.  ``policy`` overrides ``method``; with
        ``policy=None`` the method string resolves to `CostModelPolicy`
        ("auto", the default everywhere) or `FixedPolicy`
        ("closure" | "partial" | "incremental").

        ``backend="sharded"`` places the adjacency (and the closure cache)
        row-sharded over ``mesh`` (default: all devices,
        `core/sharded.make_dag_mesh`) and routes partial scans and cache
        updates through the explicit collective schedules.
        ``closure_update_impl`` overrides the rank-B cache fold-in
        (`repro.kernels.ops.closure_update` fuses it on TPU);
        ``closure_delete_impl`` overrides the delete-repair masked scan
        (e.g. ``lambda adj, cl, aff: closure_cache.masked_delete_scan(
        adj, cl, aff, hop_impl=kernels.ops.closure_delete)`` on TPU).
        ``auto_grow=True`` makes eager mutating calls react to the
        ``n_overflow`` backpressure signal by doubling capacity (via
        `grow`) and re-running the call instead of dropping adds.
        ``closure_layout="tiled"`` stores the closure cache as 32x32-bit
        tiles in a growable region window plus a per-tile occupancy
        summary (O(reachable) closure bytes; ``closure_region`` pre-sizes
        the window for compiled loops).
        """
        if backend not in BACKENDS:
            raise ValueError(
                f"backend must be one of {BACKENDS}, got {backend!r}")
        if subbatches < 1:
            raise ValueError(f"subbatches must be >= 1, got {subbatches}")
        if backend == "sharded":
            from repro.core import sharded as sharded_mod
            mesh = mesh if mesh is not None else sharded_mod.make_dag_mesh()
            validate_capacity(capacity, backend="sharded",
                              n_dev=int(mesh.devices.size))
        else:
            mesh = None
            validate_capacity(capacity, backend="local")
        if closure_layout not in ("dense", "tiled"):
            raise ValueError(
                f"closure_layout must be 'dense' or 'tiled', got "
                f"{closure_layout!r}")
        policy = dispatch.policy_for_method(method, policy)
        method = dispatch.method_name(policy)
        state = dag_mod.new_state(capacity)
        # a fresh engine's cache is exact: the empty graph's strict closure
        # is all-zeros, so the session starts clean (O(B) cycle checks from
        # the first tick)
        if closure_layout == "tiled":
            region = closure_region
            if backend == "sharded":
                # tiles are row-sharded like the dense slab: keep the
                # window on the mesh's capacity grid
                align = bitset.WORD * int(mesh.devices.size)
                want = region or closure_cache.default_region(capacity)
                region = min(capacity, ((want + align - 1) // align) * align)
            cache = closure_cache.empty_tiled_cache(capacity, region)
            closure_region = cache.closure.region
        else:
            cache = closure_cache.empty_cache(capacity)
        if backend == "sharded":
            state = sharded_mod.shard_state(state, mesh)
            cache = sharded_mod.shard_cache(cache, mesh)
        config = EngineConfig(capacity=capacity, backend=backend,
                              method=method, subbatches=subbatches,
                              matmul_impl=matmul_impl, policy=policy,
                              mesh=mesh,
                              closure_update_impl=closure_update_impl,
                              closure_delete_impl=closure_delete_impl,
                              auto_grow=auto_grow,
                              closure_layout=closure_layout,
                              closure_region=closure_region)
        n_dev = config.n_devices
        return cls(state, jnp.zeros((n_dev,), jnp.float32), cache, config)

    @classmethod
    def wrap(cls, state: DagState, config: EngineConfig,
             depth_ema=None, cache=None, epoch=None) -> "DagEngine":
        """Wrap an existing `DagState` slab (e.g. a legacy session) in an
        engine without copying.  Without an explicit ``cache`` the closure
        cache starts DIRTY (the slab's closure is unknown); the first
        incremental check lazily rebuilds it, or call `refresh_cache`.
        Pass the source session's ``epoch`` to keep the version counter
        monotone across a re-wrap (`core/sgt.py` does)."""
        ema = jnp.zeros((config.n_devices,), jnp.float32) \
            if depth_ema is None else depth_ema
        if cache is None:
            if getattr(config, "closure_layout", "dense") == "tiled":
                cache = closure_cache.empty_tiled_cache(
                    config.capacity, config.closure_region, dirty=True)
            else:
                cache = closure_cache.empty_cache(config.capacity,
                                                  dirty=True)
        return cls(state, ema, cache, config, epoch)

    def refresh_cache(self) -> "DagEngine":
        """Rebuild the closure cache from the committed graph iff dirty
        (a traced ``lax.cond``) — the explicit form of the lazy rebuild,
        for pre-warming a session before a latency-sensitive window.  On
        the tiled layout the window is first widened (host-side) to cover
        every committed edge, so the rebuild always lands clean."""
        eng = self._region_synced()
        closure, _ = closure_cache.refresh_closure(
            eng.cache.closure, eng.cache.dirty, eng.state.adj,
            eng.config.matmul_impl)
        return DagEngine(eng.state, eng.depth_ema,
                         ClosureCache(closure, jnp.asarray(False),
                                      eng.cache.repair_ema),
                         eng.config, eng.epoch)

    def snapshot(self) -> "snapshot_view.EngineSnapshot":
        """The versioned wait-free read view of this session — a frozen
        `core/snapshot_view.EngineSnapshot` (epoch + slab view + clean
        packed closure) whose ``reachable``/``contains`` answers are O(1)
        bit reads with ZERO boolean-matmul row products.

        The snapshot shares the engine's immutable arrays (no copy) and
        never blocks on — or is invalidated by — later writer mutations:
        those produce NEW engines.  A dirty closure cache is re-cleaned
        lazily here (a traced ``lax.cond`` rebuild, exactly
        `refresh_cache`); call `refresh_cache` first to also keep the
        rebuilt cache on the writer's side."""
        eng = self._region_synced()
        closure, _ = closure_cache.refresh_closure(
            eng.cache.closure, eng.cache.dirty, eng.state.adj,
            eng.config.matmul_impl)
        return snapshot_view.EngineSnapshot(eng.epoch, eng.state, closure)

    def with_options(self, *, method: Optional[str] = None,
                     subbatches: Optional[int] = None,
                     matmul_impl=dataclasses.MISSING) -> "DagEngine":
        """A view of the same session state under overridden static
        options (legacy per-call knobs).  ``method`` re-resolves the
        policy; unspecified options are inherited."""
        cfg = self.config
        policy = cfg.policy if method is None \
            else dispatch.policy_for_method(method)
        new = dataclasses.replace(
            cfg,
            method=dispatch.method_name(policy),
            subbatches=cfg.subbatches if subbatches is None else subbatches,
            matmul_impl=cfg.matmul_impl
            if matmul_impl is dataclasses.MISSING else matmul_impl,
            policy=policy)
        return DagEngine(self.state, self.depth_ema, self.cache, new,
                         self.epoch)

    # --------------------------------------------------------------- growth

    def grow(self, new_capacity: int) -> "DagEngine":
        """Re-embed the whole session at a larger capacity in one
        jit-compatible migration step -> a new engine at ``new_capacity``.

        Slots keep their indices, so the migration is pure zero-padding:
        the `DagState` slab pads with free slots, the packed closure cache
        pads with zero rows/words — preserving its clean/dirty status and
        the measured repair-depth EMA, so no spurious rebuild follows a
        grow — and the per-shard deciding-depth EMA rides through
        unchanged.  The `EngineConfig` is re-derived at ``new_capacity``;
        on the sharded backend the grown slab and cache are re-placed
        row-sharded over the same mesh (``new_capacity`` must stay a
        multiple of WORD * n_devices — validated up front, with the
        nearest valid capacity named in the error).

        The grown engine is decision-identical to a fresh engine created
        at ``new_capacity`` and replayed (pinned by tests/test_grow.py and
        gated in CI by `benchmarks/capacity_sweep.py`); ``grow`` to the
        current capacity is the identity.
        """
        cfg = self.config
        validate_capacity(new_capacity, backend=cfg.backend,
                          n_dev=cfg.n_devices, what="grown capacity")
        if new_capacity < cfg.capacity:
            raise ValueError(
                f"cannot shrink: grown capacity {new_capacity} < current "
                f"{cfg.capacity}")
        if new_capacity == cfg.capacity:
            return self
        state = dag_mod.grow_state(self.state, new_capacity)
        cache = closure_cache.grow_cache(self.cache, new_capacity)
        if cfg.backend == "sharded":
            from repro.core import sharded as sharded_mod
            state = sharded_mod.shard_state(state, cfg.mesh)
            cache = sharded_mod.shard_cache(cache, cfg.mesh)
        config = dataclasses.replace(cfg, capacity=new_capacity)
        # the epoch rides through: growth re-embeds the SAME graph version
        return DagEngine(state, self.depth_ema, cache, config, self.epoch)

    # ------------------------------------------------ tiled window sizing

    @property
    def closure_region(self) -> Optional[int]:
        """Live tiles-window size (None on the dense layout)."""
        return self.cache.closure.region \
            if closure_cache.is_tiled(self.cache.closure) else None

    def _region_align(self) -> int:
        return bitset.WORD * self.config.n_devices \
            if self.config.backend == "sharded" else bitset.WORD

    def _with_region(self, new_region: int) -> "DagEngine":
        """Engine with the tiles window widened to ``new_region`` (no-op
        on dense or when already wide enough).  Pure zero-padding of the
        tiles leaf — closure bits, dirty flag, and the epoch ride
        through."""
        closure = self.cache.closure
        if not closure_cache.is_tiled(closure):
            return self
        align = self._region_align()
        nr = min(self.capacity,
                 ((int(new_region) + align - 1) // align) * align)
        if nr <= closure.region:
            return self
        grown = closure_cache.grow_region(closure, nr)
        cache = ClosureCache(grown, self.cache.dirty, self.cache.repair_ema)
        if self.config.backend == "sharded":
            from repro.core import sharded as sharded_mod
            cache = sharded_mod.shard_cache(cache, self.config.mesh)
        return DagEngine(self.state, self.depth_ema, cache, self.config,
                         self.epoch)

    def grow_region(self, new_region: int) -> "DagEngine":
        """Widen the tiled closure window so slots below ``new_region``
        fold into the cache (identity on dense, or when already wide
        enough).  Compiled loops call this up front to pre-size the
        window for their working set."""
        return self._with_region(new_region)

    def _live_high_water(self) -> Optional[int]:
        """max live slot index + 1, host-side (None under tracing)."""
        if isinstance(self.state.alive, jax.core.Tracer):
            return None
        import numpy as np
        live = np.nonzero(np.asarray(self.state.alive))[0]
        return int(live.max()) + 1 if live.size else 0

    def _pre_widened(self, n_new_slots: int) -> "DagEngine":
        """Eagerly widen the tiles window before a call that may allocate
        ``n_new_slots`` more slots (slots are lowest-free-first, so the
        post-call high-water is bounded by live high-water + n_new).
        Host-side only: under jit the spill guards keep answers exact and
        the between-ticks controller widens instead."""
        if not closure_cache.is_tiled(self.cache.closure):
            return self
        hw = self._live_high_water()
        if hw is None:
            return self
        need = hw + int(n_new_slots)
        region = self.cache.closure.region
        if need <= region:
            return self
        return self._with_region(max(2 * region, need))

    def _region_synced(self) -> "DagEngine":
        """Engine whose tiles window covers every committed adjacency bit
        (host-side; identity on dense, under tracing, or when already
        confined) — the precondition for a tiled cache refresh."""
        closure = self.cache.closure
        if not closure_cache.is_tiled(closure) \
                or isinstance(self.state.adj, jax.core.Tracer):
            return self
        import numpy as np
        adj = np.asarray(self.state.adj)
        region = closure.region
        if not (adj[region:, :].any() or adj[:, region // 32:].any()):
            return self
        rows = np.nonzero(adj.any(axis=1))[0]
        cols = np.nonzero(adj.any(axis=0))[0]
        need = 0
        if rows.size:
            need = int(rows.max()) + 1
        if cols.size:
            need = max(need, (int(cols.max()) + 1) * 32)
        return self._with_region(need)

    def with_closure_layout(self, layout: str,
                            region: int = 0) -> "DagEngine":
        """Re-represent the closure cache in ``layout`` ("dense" |
        "tiled") without touching the graph or the epoch — the
        dense-era-checkpoint forward-restore path.  Host-side only (the
        minimal confining window is computed from the data)."""
        cfg = self.config
        current = getattr(cfg, "closure_layout", "dense")
        if layout == current:
            return self
        cache = self.cache
        if layout == "tiled":
            import numpy as np
            dense = np.asarray(closure_cache.dense_of(cache.closure))
            adj = np.asarray(self.state.adj)
            occ = dense | adj
            rows = np.nonzero(occ.any(axis=1))[0]
            cols = np.nonzero(occ.any(axis=0))[0]
            need = max(int(region), closure_cache.TILE)
            if rows.size:
                need = max(need, int(rows.max()) + 1)
            if cols.size:
                need = max(need, (int(cols.max()) + 1) * 32)
            align = self._region_align()
            need = min(cfg.capacity, ((need + align - 1) // align) * align)
            tiled = closure_cache.tiled_of(jnp.asarray(dense), need)
            new_cache = ClosureCache(tiled, cache.dirty, cache.repair_ema)
            config = dataclasses.replace(cfg, closure_layout="tiled",
                                         closure_region=tiled.region)
        elif layout == "dense":
            new_cache = ClosureCache(closure_cache.dense_of(cache.closure),
                                     cache.dirty, cache.repair_ema)
            config = dataclasses.replace(cfg, closure_layout="dense",
                                         closure_region=0)
        else:
            raise ValueError(
                f"closure_layout must be 'dense' or 'tiled', got {layout!r}")
        if cfg.backend == "sharded":
            from repro.core import sharded as sharded_mod
            new_cache = sharded_mod.shard_cache(new_cache, cfg.mesh)
        return DagEngine(self.state, self.depth_ema, new_cache, config,
                         self.epoch)

    def _grown_for_overflow(self, result: "OpResult") -> Optional["DagEngine"]:
        """Under ``auto_grow``, the PRE-call engine doubled until the adds
        ``result`` dropped would fit — or None when no growth applies.
        Host-side by design: a traced ``n_overflow`` (static shapes under
        jit) defers to the between-ticks controller, preserving the
        report-and-drop contract for compiled callers."""
        if not self.config.auto_grow:
            return None
        if isinstance(result.n_overflow, jax.core.Tracer):
            return None
        need = int(result.n_overflow)
        if need <= 0:
            return None
        new_cap = self.config.capacity
        while new_cap - self.config.capacity < need:
            new_cap *= 2
        return self.grow(new_cap)

    # ------------------------------------------------------------- pytree

    def tree_flatten(self):
        # epoch is ordered LAST so leaf 0 stays ``state.keys`` — the
        # capacity probe `ft/checkpoint._saved_capacity` reads it by index
        return (self.state, self.depth_ema, self.cache, self.epoch), \
            self.config

    @classmethod
    def tree_unflatten(cls, config, children):
        state, depth_ema, cache, epoch = children
        return cls(state, depth_ema, cache, config, epoch)

    def __repr__(self):
        c = self.config
        return (f"DagEngine(capacity={c.capacity}, backend={c.backend!r}, "
                f"method={c.method!r}, subbatches={c.subbatches})")

    # ---------------------------------------------------------- internals

    @property
    def capacity(self) -> int:
        return self.config.capacity

    def _with_state(self, state: DagState, cache: ClosureCache,
                    stats: Optional[dict] = None) -> "DagEngine":
        ema = self.depth_ema
        if stats is not None:
            update = getattr(self.config.policy, "update_depth_ema", None)
            if update is not None:
                # per-shard elementwise fold: measured (S,) into EMA (S,)
                ema = update(ema, stats["deciding_depth"])
        # every mutation commit bumps the session epoch (all mutators
        # return through here), versioning the snapshots it obsoletes
        return DagEngine(state, ema, cache, self.config, self.epoch + 1)

    def _invalidated_cache(self, state: DagState) -> ClosureCache:
        """Cache after a mutation that bypassed the incremental fold-in:
        dirty iff any adjacency bit actually changed (vertex adds and
        no-op removes keep a clean cache clean).

        Configurations that never READ the cache (FixedPolicy closure/
        partial, opted-out cost models) skip the O(C*W) adjacency diff and
        conservatively mark it stale — dirty is always sound, and a later
        ``with_options(method="incremental")`` view simply lazy-rebuilds.
        """
        if not self._cache_aware(self.config.method):
            return self.cache._replace(dirty=jnp.asarray(True))
        return self.cache.invalidated_if(
            jnp.any(state.adj != self.state.adj))

    def _cache_aware(self, method: str) -> bool:
        """Whether this call threads the closure cache through the cycle
        check (fixed incremental, or auto with an opted-in policy)."""
        if method == "incremental":
            return True
        return method == "auto" and getattr(
            self.config.policy, "use_incremental", False)

    def _closure_update_impl(self):
        """The rank-B cache fold-in for this call: the explicit config
        override, else the row-sharded schedule on the sharded backend
        (derived per call, like `partial_scan_matmul_impl`), else None
        (a tiled cache then folds through `kernels/ops.closure_update_tiled`,
        a dense one through the jnp reference inside
        `closure_cache.insert_update`)."""
        cfg = self.config
        if cfg.closure_update_impl is not None:
            return cfg.closure_update_impl
        if cfg.backend == "sharded":
            from repro.core import sharded as sharded_mod
            return sharded_mod.closure_update_impl(cfg.mesh)
        return None

    def _closure_delete_impl(self):
        """The delete-repair masked scan for this call, derived exactly
        like `_closure_update_impl`: config override, else the row-sharded
        schedule on the mesh (zero-collective hops on a dense cache,
        squaring on a tiled one), else None (`commit` then scans with the
        jnp hop on a dense cache and by squaring through
        `kernels/ops.closure_delete_tiled` on a tiled one)."""
        cfg = self.config
        if cfg.closure_delete_impl is not None:
            return cfg.closure_delete_impl
        if cfg.backend == "sharded":
            from repro.core import sharded as sharded_mod
            return sharded_mod.closure_delete_impl(
                cfg.mesh, square=self.closure_region is not None)
        return None

    def _prefer_repair_fn(self):
        """The policy's delete dispatch arm closed over the capacity:
        (n_affected, repair-depth hint) -> traced bool.  None when the
        policy has no arm — `commit` then uses the module default."""
        policy = self.config.policy
        hook = getattr(policy, "prefer_delete_repair", None)
        if hook is None:
            return None
        # tiled caches rebuild inside their window, so the repair-vs-
        # rebuild break-even prices against the live window's rows (the
        # occupancy bound), not the full capacity
        region = self.closure_region
        capacity = self.config.capacity if region is None else region

        def prefer(n_affected, depth_hint):
            return hook(n_affected, capacity, depth_hint=depth_hint)

        return prefer

    def _commit_cache(self, state: DagState, delta):
        """Apply a mutation's typed `CacheDelta` through the single
        `closure_cache.commit` entry point -> (cache', ReachStats).

        Configurations that never READ the cache (FixedPolicy closure/
        partial, opted-out cost models) skip the commit machinery and
        conservatively mark it stale — dirty is always sound, and a later
        ``with_options(method="incremental")`` view simply lazy-rebuilds.
        """
        zeros = ReachStats.zeros(self.config.n_devices)
        if not self._cache_aware(self.config.method):
            return self.cache._replace(dirty=jnp.asarray(True)), zeros
        cache, st = closure_cache.commit(
            self.cache, delta, state.adj,
            update_impl=self._closure_update_impl(),
            delete_impl=self._closure_delete_impl(),
            prefer_repair_fn=self._prefer_repair_fn(),
            ema_alpha=getattr(self.config.policy, "ema_alpha", 0.25),
            with_stats=True)
        return cache, zeros._replace(n_products=st["n_products"],
                                     row_products=st["row_products"],
                                     n_repair=st["n_repair"])

    def _overflow_delta(self, state: DagState) -> jax.Array:
        return state.n_overflow - self.state.n_overflow

    def _dispatch_hooks(self, batch: int):
        """(method, prefer_partial_fn, partial_matmul_impl) for one
        cycle-checked call of ``batch`` candidate rows."""
        cfg = self.config
        policy = cfg.policy
        fixed = getattr(policy, "fixed_method", None)
        if fixed is not None:
            method, prefer = fixed, None
        else:
            ema = self.depth_ema

            def prefer(adj_t, b_sub):
                return policy.prefer_partial(adj_t, b_sub, depth_hint=ema)

            method = "auto"
        partial_impl = cfg.matmul_impl
        if cfg.backend == "sharded":
            from repro.core import sharded as sharded_mod
            b_sub = max(1, batch // cfg.subbatches)
            plan = policy.scan_sharding(b_sub, cfg.capacity, cfg.n_devices)
            partial_impl = sharded_mod.partial_scan_matmul_impl(
                cfg.mesh, plan)
        return method, prefer, partial_impl

    # ------------------------------------------------------ vertex ops

    def add_vertices(self, keys, valid=None):
        """AddVertex batch -> (engine, OpResult); overflowed adds report
        ok=False and count into ``result.n_overflow`` (unless ``auto_grow``
        and the call is eager, in which case capacity doubles until the
        batch fits and the call transparently re-runs)."""
        # eagerly widen a tiled closure window so this batch's slots can
        # fold into the cache (no-op on dense and under jit)
        eng = self._pre_widened(jnp.asarray(keys).shape[0])
        state, ok = dag_mod.add_vertices(eng.state, keys, valid=valid)
        res = OpResult(ok, eng._overflow_delta(state),
                       ReachStats.zeros(eng.config.n_devices))
        grown = eng._grown_for_overflow(res)
        if grown is not None:
            # immutability makes the retry exact: re-apply the original
            # batch to the grown PRE-call engine
            return grown.add_vertices(keys, valid=valid)
        # vertex adds never touch adjacency: a clean cache stays clean
        return eng._with_state(state, eng.cache), res

    def remove_vertices(self, keys, valid=None):
        """RemoveVertex batch (logical+physical removal, incident edges
        cleared in-step) -> (engine, OpResult).  The removal commits a
        typed `CacheDelta` (column clears, adj-diff exact): a clean cache
        is MAINTAINED by re-deriving the removed slots' ancestor rows
        (the repair's work shows up in ``result.stats``), unless the
        policy's delete arm prefers invalidate + lazy rebuild."""
        state, ok, delta = dag_mod.remove_vertices_delta(self.state, keys,
                                                         valid=valid)
        cache, stats = self._commit_cache(state, delta)
        res = OpResult(ok, self._overflow_delta(state), stats)
        return self._with_state(state, cache), res

    # -------------------------------------------------------- edge ops

    def add_edges_acyclic(self, us, vs, valid=None):
        """AcyclicAddEdge batch -> (engine, OpResult).  The cycle check is
        dispatched by the configured policy (the measured deciding depth
        feeds the next dispatch decision via the engine's per-shard EMA;
        a clean closure cache short-circuits to the O(B) incremental
        check); the paper's relaxed joint-abort semantics apply within a
        sub-batch."""
        cfg = self.config
        method, prefer, partial_impl = self._dispatch_hooks(us.shape[0])
        common = dict(valid=valid, subbatches=cfg.subbatches,
                      matmul_impl=cfg.matmul_impl, method=method,
                      with_stats=True, prefer_partial_fn=prefer,
                      partial_matmul_impl=partial_impl,
                      n_shards=cfg.n_devices)
        if self._cache_aware(method):
            state, ok, cache, stats = acyclic_mod.acyclic_add_edges_impl(
                self.state, us, vs, cache=self.cache,
                closure_update_impl=self._closure_update_impl(),
                prefer_incremental_fn=getattr(cfg.policy,
                                              "prefer_incremental", None),
                **common)
        else:
            state, ok, stats = acyclic_mod.acyclic_add_edges_impl(
                self.state, us, vs, **common)
            cache = self._invalidated_cache(state)
        res = OpResult(ok, self._overflow_delta(state),
                       ReachStats.from_raw(stats))
        return self._with_state(state, cache, stats), res

    def remove_edges(self, us, vs, valid=None):
        """RemoveEdge batch -> (engine, OpResult).  Commits a typed
        `CacheDelta` whose mask is adj-diff exact (edges that actually
        existed, deduplicated), so no-op and repeated removals leave a
        clean cache clean at zero repair cost; real removals are
        maintained by affected-row re-derivation per the policy's delete
        arm."""
        state, ok, delta = dag_mod.remove_edges_delta(self.state, us, vs,
                                                      valid=valid)
        cache, stats = self._commit_cache(state, delta)
        res = OpResult(ok, self._overflow_delta(state), stats)
        return self._with_state(state, cache), res

    # ------------------------------------------------- wait-free reads

    def contains(self, keys) -> jax.Array:
        """ContainsVertex batch -> bool[B]."""
        return dag_mod.contains_vertices(self.state, keys)

    def contains_edges(self, us, vs) -> jax.Array:
        return dag_mod.contains_edges(self.state, us, vs)

    def reachable(self, from_keys, to_keys) -> jax.Array:
        """Batch PathExists(from, to): True iff a path of >= 1 edge exists.

        Local backend: the policy picks the full reach-set scan or the
        early-exit partial scan (a ``lax.cond`` under "auto").  Sharded
        backend: the explicit collective schedule picked by
        ``policy.scan_sharding`` (B-sharded when the batch divides the
        mesh with enough rows per device, frontier-sharded otherwise).
        """
        cfg = self.config
        b = from_keys.shape[0]
        fixed = getattr(cfg.policy, "fixed_method", None)
        if fixed == "incremental":
            # O(1)-per-query read path: a clean cache answers PathExists
            # with B bit lookups; a dirty cache falls back to the full
            # algorithm-1 scan (reads cannot return a rebuilt engine)
            def read(_):
                f_slot, f_found = dag_mod.lookup_slots(self.state, from_keys)
                t_slot, t_found = dag_mod.lookup_slots(self.state, to_keys)
                return f_found & t_found & closure_cache.closure_bit_get(
                    self.cache.closure, f_slot, t_slot)

            def scan(_):
                return reachability.path_exists(self.state, from_keys,
                                                to_keys, cfg.matmul_impl)

            return jax.lax.cond(self.cache.dirty, scan, read, None)
        if cfg.backend == "sharded":
            if fixed == "closure":
                # honor the pinned algorithm-1 scan; GSPMD partitions the
                # full reach-set products over the row-sharded adjacency
                return reachability.path_exists(self.state, from_keys,
                                                to_keys, cfg.matmul_impl)
            from repro.core import sharded as sharded_mod
            src, t_slot, endpoints_ok = reachability.seed_path_queries(
                self.state, from_keys, to_keys)
            plan = cfg.policy.scan_sharding(b, cfg.capacity, cfg.n_devices)
            if plan == "batch":
                hit = sharded_mod.reach_until_decided_batch_sharded(
                    cfg.mesh, self.state.adj, src, t_slot)
            else:
                hit = sharded_mod.reach_until_decided_sharded(
                    cfg.mesh, self.state.adj, src, t_slot)
            return endpoints_ok & hit
        if fixed == "closure":
            return reachability.path_exists(self.state, from_keys, to_keys,
                                            cfg.matmul_impl)
        if fixed == "partial":
            return snapshot.path_exists_partial(self.state, from_keys,
                                                to_keys, cfg.matmul_impl)
        use_partial = cfg.policy.prefer_partial(self.state.adj, b,
                                                depth_hint=self.depth_ema)
        return jax.lax.cond(
            use_partial,
            lambda st: snapshot.path_exists_partial(st, from_keys, to_keys,
                                                    cfg.matmul_impl),
            lambda st: reachability.path_exists(st, from_keys, to_keys,
                                               cfg.matmul_impl),
            self.state)

    def is_acyclic(self) -> jax.Array:
        return reachability.is_acyclic(self.state.adj,
                                       self.config.matmul_impl)

    def live_vertex_count(self) -> jax.Array:
        return dag_mod.live_vertex_count(self.state)

    def edge_count(self) -> jax.Array:
        return dag_mod.edge_count(self.state)

    # ------------------------------------------------- mixed-op batches

    def apply(self, batch: OpBatch, acyclic: bool = True):
        """Apply a typed mixed batch -> (engine, OpResult), with the
        documented linearization (RemoveVertex -> AddVertex -> RemoveEdge
        -> AddEdge -> reads).  ``acyclic=True`` (default — the engine is a
        DAG) cycle-checks the ADD_EDGE rows through the dispatch policy;
        ``acyclic=False`` degrades them to plain directed-graph inserts
        (the paper's unconstrained-graph baseline)."""
        if not isinstance(batch.op, jax.core.Tracer):
            import numpy as np
            n_adds = int(np.sum(np.asarray(batch.op) == ADD_VERTEX))
            self = self._pre_widened(n_adds)
        cfg = self.config
        method, prefer, partial_impl = self._dispatch_hooks(batch.size)
        common = dict(acyclic=acyclic, subbatches=cfg.subbatches,
                      method=method, matmul_impl=cfg.matmul_impl,
                      with_stats=True, prefer_partial_fn=prefer,
                      partial_matmul_impl=partial_impl,
                      n_shards=cfg.n_devices)
        if acyclic and self._cache_aware(method):
            state, ok, cache, stats = dag_mod.apply_op_batch_impl(
                self.state, batch.op, batch.a, batch.b, cache=self.cache,
                closure_update_impl=self._closure_update_impl(),
                closure_delete_impl=self._closure_delete_impl(),
                prefer_repair_fn=self._prefer_repair_fn(),
                prefer_incremental_fn=getattr(cfg.policy,
                                              "prefer_incremental", None),
                **common)
        else:
            state, ok, stats = dag_mod.apply_op_batch_impl(
                self.state, batch.op, batch.a, batch.b, **common)
            cache = self._invalidated_cache(state)
        res = OpResult(ok, self._overflow_delta(state),
                       ReachStats.from_raw(stats))
        grown = self._grown_for_overflow(res)
        if grown is not None:
            # re-apply the original batch to the grown PRE-call engine
            return grown.apply(batch, acyclic=acyclic)
        return self._with_state(state, cache,
                                stats if acyclic else None), res

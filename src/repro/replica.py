"""CacheDelta replication: one writer, N wait-free read replicas.

PR 5 made every mutation commit a typed, adjacency-diff-exact
`core/closure_cache.CacheDelta` — a write-ahead log in all but name.  This
module ships it:

  * `Primary` — the single writer: a `DagEngine` session plus the
    append-only delta log.  Every mutator delegates to the engine and
    records ``LogEntry(epoch, grow_to, delta)``, where the delta's masks
    ARE the primary's accept decisions (an accepted insert batch, the
    edges a removal actually cleared, the slots a vertex retire cleared).
  * `Replica` — a reader: holds the (adjacency, packed closure) pair of
    one engine version and converges to the primary by replaying the log
    with the SAME kernels the writer uses (`closure_cache.insert_update`
    rank-B fold, `closure_cache.masked_delete_scan` affected-row repair —
    or their fused/sharded realizations) and NO reader-side cycle checks:
    the primary already decided every accept/reject.  Reads are O(1)
    closure bit lookups — zero boolean-matmul row products.
  * crash recovery = base image + tail: `ft/checkpoint` checkpoints the
    engine (the epoch is a pytree leaf, so the base image knows its own
    version) and `recover_replica` replays every log entry at or past the
    base epoch.  Replaying the boundary entry twice is safe — the add
    fold is an OR and the repair re-derives affected rows from the
    post-delta adjacency (`closure_cache.apply_delta` idempotence).

Replicas are slot-addressed on purpose: the log carries closure/adjacency
deltas, not key-table traffic, so a replica answers
``reachable_slots(u, v)`` — the paper's reachability read surface.
Same-process versioned reads with the full key-addressed API go through
`DagEngine.snapshot()` (`core/snapshot_view.EngineSnapshot`) instead.

Bit-for-bit convergence (checkpoint + replay == the primary's packed
closure, through randomized mixed insert/delete/grow streams, local and
sharded) is property-tested in tests/test_replica.py.

Integrity (PR 9): every shipped entry carries the epoch it extends
(``prev_epoch``) and a CRC32 over its metadata + delta payload, so the
reader detects corruption in transit (`CorruptLogError`), epoch gaps
from dropped/reordered shipments (`ReplicaDiverged` — the resync
trigger), and duplicate redelivery (skipped: re-applying a STALE delta
onto newer state would undo later mutations, so idempotence-by-skip is
the only safe duplicate handling).  On disk the log is a framed,
versioned, per-record-checksummed format — `load_delta_log` truncates a
torn tail to the last valid entry and raises typed errors (file + byte
offset) for mid-file corruption, and `recover_replica` falls back to
the newest UNcorrupted checkpoint base image.  Fault-injection coverage
lives in tests/test_faults.py and tests/test_chaos.py.
"""
from __future__ import annotations

import functools
import io
import logging
import os
import struct
import zipfile
import zlib
from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitset, closure_cache
from repro.core import dag as dag_mod
from repro.core.closure_cache import CacheDelta
from repro.core.engine import DagEngine, OpResult, tally_of

logger = logging.getLogger(__name__)


class CorruptLogError(RuntimeError):
    """A delta log (file or shipped entry) failed an integrity check.

    Carries the file path (None for an in-memory shipped entry) and the
    byte offset of the first bad byte region (-1 when not applicable),
    so the failure names WHERE the corruption is, not just that npz
    parsing exploded somewhere."""

    def __init__(self, detail: str, path: Optional[str] = None,
                 offset: int = -1):
        self.path = path
        self.offset = int(offset)
        where = ""
        if path is not None:
            where = f" [{path}" + (f" @ byte {offset}]" if offset >= 0
                                   else "]")
        super().__init__(detail + where)


class ReplicaDiverged(RuntimeError):
    """A replica cannot safely apply a log entry: the entry extends an
    epoch the replica never reached (dropped/reordered shipment, or a
    writer restart), or addresses slots beyond the replica's capacity (a
    missed grow entry).  Recover via `recover_replica` (base image +
    tail) or `Replica.resync` from a live engine."""

    def __init__(self, replica_epoch: int, entry_prev: int,
                 entry_epoch: int, detail: Optional[str] = None):
        self.replica_epoch = int(replica_epoch)
        self.entry_prev = int(entry_prev)
        self.entry_epoch = int(entry_epoch)
        msg = detail or (
            f"log entry for epoch {entry_epoch} extends epoch "
            f"{entry_prev}, but this replica is at epoch "
            f"{replica_epoch} — entries were dropped or reordered in "
            "shipping")
        super().__init__(
            msg + "; resync via recover_replica or Replica.resync")


class LogEntry(NamedTuple):
    """One shipped mutation: the engine epoch AFTER the commit, a grow
    marker (``grow_to > 0`` re-embeds the replica at that capacity before
    the delta applies; growth itself does not bump the epoch), and the
    typed delta.  Vertex adds ship an empty delta — adjacency and closure
    are untouched, but the entry keeps replica epochs in lockstep.

    ``prev_epoch`` is the epoch this entry extends (-1 = unknown, for
    legacy entries): coalesced entries span several epochs, so gap
    detection compares prev_epoch — not ``epoch - 1`` — against the
    replica's version.  ``crc`` is `entry_crc` over metadata + delta
    payload (0 = unchecksummed legacy entry)."""

    epoch: int
    grow_to: int
    delta: CacheDelta
    prev_epoch: int = -1
    crc: int = 0


def entry_crc(epoch: int, grow_to: int, prev_epoch: int,
              delta: CacheDelta) -> int:
    """CRC32 over an entry's metadata and every delta array's shape +
    bytes.  Never returns 0, so ``crc == 0`` stays the "no checksum"
    sentinel on legacy entries."""
    h = zlib.crc32(np.asarray([int(epoch), int(grow_to),
                               int(prev_epoch)], np.int64).tobytes())
    for v in delta:
        a = np.ascontiguousarray(np.asarray(v))
        h = zlib.crc32(np.asarray(a.shape, np.int64).tobytes(), h)
        h = zlib.crc32(a.tobytes(), h)
    return (h & 0xFFFFFFFF) or 1


def _host_delta(delta: CacheDelta) -> CacheDelta:
    """Device -> host copy, so the log survives the arrays it was cut
    from and serializes without touching the device."""
    return CacheDelta(*[np.asarray(x) for x in delta])


def _max_slot(delta: CacheDelta) -> int:
    """Largest slot index the delta's MASKED rows address (-1 when the
    delta is empty) — the capacity a replica needs to apply it without
    the scatters silently dropping bits."""
    m = -1
    for slots, mask in ((delta.add_u, delta.add_mask),
                        (delta.add_v, delta.add_mask),
                        (delta.rem_u, delta.rem_mask),
                        (delta.rem_v, delta.rem_mask),
                        (delta.clear_slots, delta.clear_mask)):
        s, k = np.asarray(slots), np.asarray(mask, bool)
        if s.size and k.any():
            m = max(m, int(s[k].max()))
    return m


def _has_adds(delta: CacheDelta) -> bool:
    return delta.add_u.shape[0] > 0


def _has_deletes(delta: CacheDelta) -> bool:
    return delta.rem_u.shape[0] > 0 or delta.clear_slots.shape[0] > 0


def _merge_deltas(deltas: Sequence[CacheDelta]) -> CacheDelta:
    """Concatenate a delete-side-before-add-side run of deltas into one.
    Exact because `closure_cache.apply_delta` applies the delete side
    first against the post-delta adjacency (removal repair re-derives
    affected rows from the FINAL adjacency, which is order-free for a
    set of removals) and folds the whole accepted add set last — the
    same linearization the writer committed the run under."""
    if len(deltas) == 1:
        return deltas[0]
    return CacheDelta(*[jnp.concatenate([d[i] for d in deltas])
                        for i in range(len(CacheDelta._fields))])


def coalesce_entries(entries: Sequence[LogEntry]) -> List[LogEntry]:
    """Merge a recorded run of log entries into the fewest equivalent
    entries: consecutive deltas coalesce while every delete-recording
    entry precedes every add-recording entry (the front-end tick's phase
    order — RemoveVertex, AddVertex, RemoveEdge, AddEdge — always
    qualifies, so one coalesced tick ships as ONE entry); a grow marker
    only ever opens a group (the replica must re-embed before any merged
    delta applies).  Each merged entry carries the LAST epoch of its
    group and the FIRST entry's ``prev_epoch`` (the epoch the whole run
    extends) — replicas land on the same version replaying either form,
    and gap detection stays exact across coalescing."""
    groups: List[List[LogEntry]] = []
    for e in entries:
        if groups and e.grow_to == 0:
            g = groups[-1]
            adds_seen = any(_has_adds(x.delta) for x in g)
            if not (adds_seen and _has_deletes(e.delta)):
                g.append(e)
                continue
        groups.append([e])
    out = []
    for g in groups:
        merged = _merge_deltas([x.delta for x in g])
        out.append(LogEntry(g[-1].epoch, g[0].grow_to, merged,
                            g[0].prev_epoch))
    return out


# --------------------------------------------------- compiled writer steps
#
# One XLA program per mutator: the engine commit AND the log delta come
# out of the same trace, so the delta recomputation (the same pure
# functions the eager path calls beside the engine) CSEs away instead of
# doubling the work, and a fixed-shape writer tick is four compiled
# calls.  `jax.jit` caches per (capacity, config) structure — the serving
# front-end's padded phases hit the cache every tick.  Each step's
# `OpResult` carries its `tally` (ok bits and work counts in one array),
# so a caller that waits on the answer copies both at once.

@jax.jit
def _add_vertices_step(engine, keys, valid):
    engine, res = engine.add_vertices(keys, valid=valid)
    return engine, res._replace(tally=tally_of(res))


@jax.jit
def _add_edges_step(engine, us, vs, valid):
    engine, res = engine.add_edges_acyclic(us, vs, valid=valid)
    u_slot, _ = dag_mod.lookup_slots(engine.state, us)
    v_slot, _ = dag_mod.lookup_slots(engine.state, vs)
    return engine, res._replace(tally=tally_of(res)), \
        CacheDelta.edges_added(u_slot, v_slot, res.ok)


@jax.jit
def _remove_edges_step(engine, us, vs, valid):
    _, _, delta = dag_mod.remove_edges_delta(engine.state, us, vs,
                                             valid=valid)
    engine, res = engine.remove_edges(us, vs, valid=valid)
    return engine, res._replace(tally=tally_of(res)), delta


@jax.jit
def _remove_vertices_step(engine, keys, valid):
    _, _, delta = dag_mod.remove_vertices_delta(engine.state, keys,
                                                valid=valid)
    engine, res = engine.remove_vertices(keys, valid=valid)
    return engine, res._replace(tally=tally_of(res)), delta


# ------------------------------------------------------------------ writer

class Primary:
    """The single writer: a `DagEngine` plus its replication log.

    Mutators mirror the engine's and return the `OpResult`; the engine
    itself advances in place (``primary.engine`` is always the latest
    version — hand it to `ft/checkpoint.save_engine_checkpoint` for the
    base image).  Only the four single-op mutators and `grow` record log
    entries; route mixed `OpBatch` traffic through them (the engine's
    ``apply`` fuses phases and does not expose per-phase deltas).

    Two hot-path modes (both off by default — the eager per-call host
    copy stays the simple, exact-to-PR-7 behavior):

      * ``defer_flush=True`` stages deltas on device and `flush()` ships
        them in one copy, coalescing phase-ordered same-tick runs into
        one `LogEntry` (`coalesce_entries`);
      * ``jit=True`` compiles each mutator + its delta derivation into
        one XLA call (fixed request shapes hit the jit cache every tick).
    """

    def __init__(self, engine: DagEngine,
                 log: Optional[List[LogEntry]] = None, *,
                 defer_flush: bool = False, jit: bool = False,
                 fault_plan=None):
        self.engine = engine
        self.log: List[LogEntry] = list(log) if log is not None else []
        # fault injection hook (ft/faults.FaultPlan): `flush` consults
        # plan.crash_index to crash mid-ship, leaving a durable prefix —
        # the chaos suite's crash-at-arbitrary-point coverage
        self.fault_plan = fault_plan
        # defer_flush=True turns the synchronous log ship into a staged
        # one: _record keeps the delta ON DEVICE (no host copy, no sync)
        # and `flush` ships everything staged since the last flush in one
        # device->host copy, coalescing same-tick runs into one entry —
        # the serving front-end's writer tick never blocks on the log.
        self.defer_flush = bool(defer_flush)
        # jit=True routes each mutator through a compiled step that
        # derives the log delta INSIDE the same XLA program as the commit
        # (the delta recomputation CSEs away), so a fixed-shape writer
        # tick is four compiled calls instead of eager op dispatch.
        self.jit = bool(jit)
        self._staged: List[LogEntry] = []

    @classmethod
    def create(cls, capacity: int, *, defer_flush: bool = False,
               jit: bool = False, fault_plan=None, **options) -> "Primary":
        """A fresh writer; ``options`` mirror `DagEngine.create`."""
        return cls(DagEngine.create(capacity, **options),
                   defer_flush=defer_flush, jit=jit, fault_plan=fault_plan)

    @property
    def epoch(self) -> int:
        return int(self.engine.epoch)

    def _record(self, delta: CacheDelta, grow_to: int = 0,
                bumped: bool = True) -> None:
        # prev_epoch = the epoch this entry extends: mutators bumped the
        # engine (prev = epoch - 1), grow did not (prev = epoch)
        prev = self.engine.epoch - 1 if bumped else self.engine.epoch
        if self.defer_flush:
            # keep the device arrays (and the device epoch scalar — even
            # int(epoch) would force a blocking sync per call); the crc
            # is computed at flush time, where the host copy happens
            self._staged.append(LogEntry(self.engine.epoch, grow_to,
                                         delta, prev))
        else:
            epoch, prev = self.epoch, int(prev)
            host = _host_delta(delta)
            crc = entry_crc(epoch, grow_to, prev, host)
            self.log.append(LogEntry(epoch, grow_to, host, prev, crc))

    @functools.partial(jax.profiler.annotate_function, name="log.flush")
    def flush(self, coalesce: bool = True) -> List[LogEntry]:
        """Ship every staged delta to the host log in one blocking copy.

        With ``coalesce`` (default) same-tick runs merge into one
        `LogEntry` via `coalesce_entries` — a front-end tick's four
        phases (RemoveVertex, AddVertex, RemoveEdge, AddEdge) are
        phase-ordered deletes-before-adds, so the whole tick ships as a
        single entry.  Returns the entries appended (empty when nothing
        is staged — eager primaries append directly and flush is a
        no-op).  Safe to call from a worker thread: the front-end defers
        it off the submit path.

        Entries ship one at a time so an injected crash (`fault_plan`,
        see ft/faults) leaves a durable prefix in ``self.log`` — exactly
        the torn-flush state recovery must handle; the unshipped
        remainder is lost, as it would be in a real crash."""
        if not self._staged:
            return []
        staged, self._staged = self._staged, []
        groups = coalesce_entries(staged) if coalesce else staged
        crash_at = None
        if self.fault_plan is not None:
            crash_at = self.fault_plan.crash_index(
                len(groups), site="Primary.flush")
        shipped: List[LogEntry] = []
        for i, e in enumerate(groups):
            if crash_at is not None and i == crash_at:
                from repro.ft.faults import InjectedCrash
                raise InjectedCrash(
                    f"injected crash in Primary.flush before entry {i} "
                    f"of {len(groups)} (FaultPlan seed "
                    f"{self.fault_plan.seed}); {i} entries shipped "
                    "durably, the rest are lost")
            epoch, grow_to = int(e.epoch), int(e.grow_to)
            prev = int(e.prev_epoch)
            host = _host_delta(e.delta)
            entry = LogEntry(epoch, grow_to, host, prev,
                             entry_crc(epoch, grow_to, prev, host))
            self.log.append(entry)
            shipped.append(entry)
        return shipped

    # ------------------------------------------------------- mutators

    def _valid_arr(self, keys, valid):
        return jnp.ones(jnp.asarray(keys).shape, bool) if valid is None \
            else jnp.asarray(valid)

    def add_vertices(self, keys, valid=None) -> OpResult:
        cap_before = self.engine.capacity
        if self.jit:
            eng, res = _add_vertices_step(self.engine, jnp.asarray(keys),
                                          self._valid_arr(keys, valid))
            # auto_grow cannot fire inside the compiled step (static
            # shapes); mirror the eager engine here: double until the
            # dropped adds fit, re-run on the grown pre-call engine
            while self.engine.config.auto_grow and \
                    int(res.n_overflow) > int(self.engine.state.n_overflow):
                grown = self.engine.grow(2 * self.engine.capacity)
                self.engine = grown
                eng, res = _add_vertices_step(grown, jnp.asarray(keys),
                                              self._valid_arr(keys, valid))
            self.engine = eng
        else:
            self.engine, res = self.engine.add_vertices(keys, valid=valid)
        # auto_grow may have re-run the call on a grown engine; ship the
        # capacity so the replica's slab grows in the same place
        grow_to = self.engine.capacity \
            if self.engine.capacity != cap_before else 0
        self._record(CacheDelta.empty(), grow_to=grow_to)
        return res

    def add_edges_acyclic(self, us, vs, valid=None) -> OpResult:
        if self.jit:
            self.engine, res, delta = _add_edges_step(
                self.engine, jnp.asarray(us), jnp.asarray(vs),
                self._valid_arr(us, valid))
        else:
            self.engine, res = self.engine.add_edges_acyclic(us, vs,
                                                             valid=valid)
            # the delta's mask IS the accept decision: ok rows exist in
            # the post-graph (folding a present edge is an exact no-op)
            u_slot, _ = dag_mod.lookup_slots(self.engine.state, us)
            v_slot, _ = dag_mod.lookup_slots(self.engine.state, vs)
            delta = CacheDelta.edges_added(u_slot, v_slot, res.ok)
        self._record(delta)
        return res

    def remove_edges(self, us, vs, valid=None) -> OpResult:
        if self.jit:
            self.engine, res, delta = _remove_edges_step(
                self.engine, jnp.asarray(us), jnp.asarray(vs),
                self._valid_arr(us, valid))
        else:
            # derive the adj-diff-exact delta the engine commits
            # internally (same pure function on the same pre-state)
            _, _, delta = dag_mod.remove_edges_delta(self.engine.state, us,
                                                     vs, valid=valid)
            self.engine, res = self.engine.remove_edges(us, vs, valid=valid)
        self._record(delta)
        return res

    def remove_vertices(self, keys, valid=None) -> OpResult:
        if self.jit:
            self.engine, res, delta = _remove_vertices_step(
                self.engine, jnp.asarray(keys),
                self._valid_arr(keys, valid))
        else:
            _, _, delta = dag_mod.remove_vertices_delta(self.engine.state,
                                                        keys, valid=valid)
            self.engine, res = self.engine.remove_vertices(keys,
                                                           valid=valid)
        self._record(delta)
        return res

    def grow(self, new_capacity: int) -> None:
        self.engine = self.engine.grow(new_capacity)
        # growth does not bump the epoch: this entry extends the CURRENT
        # epoch, not epoch - 1
        self._record(CacheDelta.empty(), grow_to=new_capacity,
                     bumped=False)

    # ---------------------------------------------------------- reads

    def snapshot(self):
        """The latest `EngineSnapshot` (see `DagEngine.snapshot`)."""
        return self.engine.snapshot()

    def checkpoint(self, directory: str, step: Optional[int] = None) -> str:
        """Write the base image (atomic engine checkpoint; the epoch leaf
        rides along, naming where the log tail starts).  Default step:
        the current epoch.  Staged deltas flush first so the base always
        aligns with a shipped log boundary (coalesced entries carry their
        group's LAST epoch — a base cut mid-group would otherwise replay
        a partial prefix of it)."""
        self.flush()
        from repro.ft import checkpoint as ckpt
        return ckpt.save_engine_checkpoint(
            directory, self.epoch if step is None else step, self.engine)


# ------------------------------------------------------------------ reader

@jax.tree_util.register_pytree_node_class
class Replica:
    """A wait-free read replica: (epoch, adjacency mirror, packed closure).

    Immutable — `apply` returns a new replica; reads are closure bit
    lookups.  ``update_impl``/``delete_impl`` plug the same kernel
    overrides the engine takes (fused Pallas on TPU,
    `core/sharded.closure_update_impl`/`closure_delete_impl` on a mesh)
    and ride as static aux data.
    """

    __slots__ = ("epoch", "adj", "closure", "update_impl", "delete_impl")

    def __init__(self, epoch, adj, closure, update_impl=None,
                 delete_impl=None):
        self.epoch = epoch      # int32 scalar: version this replica is at
        self.adj = adj          # uint32[C, W]: adjacency mirror
        # strict closure mirror: dense uint32[C, W] slab or a
        # closure_cache.TiledClosure (inherited from the primary's layout)
        self.closure = closure
        self.update_impl = update_impl
        self.delete_impl = delete_impl

    def tree_flatten(self):
        return (self.epoch, self.adj, self.closure), \
            (self.update_impl, self.delete_impl)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    def __repr__(self):
        return f"Replica(epoch={self.epoch}, capacity={self.capacity})"

    @property
    def capacity(self) -> int:
        return self.adj.shape[0]

    # --------------------------------------------------- construction

    @classmethod
    def from_snapshot(cls, snap, update_impl=None,
                      delete_impl=None) -> "Replica":
        """Start from an `EngineSnapshot` (shares its arrays)."""
        return cls(snap.epoch, snap.state.adj, snap.closure,
                   update_impl, delete_impl)

    @classmethod
    def from_engine(cls, engine: DagEngine, update_impl=None,
                    delete_impl=None) -> "Replica":
        """Start from a live (or just-restored) engine — e.g. the base
        image of a crash recovery.  Without explicit kernels the replica
        applies deltas with the engine's own: the row-sharded schedules
        on a sharded engine (its arrays stay on the mesh, where the
        Pallas kernels cannot be partitioned), the engine's overrides or
        the layout's defaults otherwise."""
        if update_impl is None and delete_impl is None:
            update_impl = engine._closure_update_impl()
            delete_impl = engine._closure_delete_impl()
        return cls.from_snapshot(engine.snapshot(), update_impl,
                                 delete_impl)

    # ----------------------------------------------------- delta apply

    def _grown(self, new_capacity: int) -> "Replica":
        c, w = self.adj.shape
        if new_capacity <= c:
            return self
        w_new = bitset.n_words(new_capacity)
        pad = ((0, new_capacity - c), (0, w_new - w))
        return Replica(self.epoch, jnp.pad(self.adj, pad),
                       closure_cache.grow_closure(self.closure,
                                                  new_capacity),
                       self.update_impl, self.delete_impl)

    def _windowed(self, need_slots: int) -> "Replica":
        """Tiled closures: widen the tile window to confine slots up to
        ``need_slots`` before a delta touches them (host-side, mirroring
        `DagEngine._pre_widened`'s doubling policy).  Dense: no-op."""
        if not closure_cache.is_tiled(self.closure):
            return self
        region = self.closure.region
        if need_slots <= region:
            return self
        nr = closure_cache.align_region(
            max(2 * region, need_slots), self.capacity)
        return Replica(self.epoch, self.adj,
                       closure_cache.grow_region(self.closure, nr),
                       self.update_impl, self.delete_impl)

    def _adj_after(self, delta: CacheDelta) -> jax.Array:
        """The adjacency mirror after ``delta`` (removes, vertex clears,
        then adds — the commit linearization)."""
        adj = self.adj
        c = adj.shape[0]
        if delta.rem_u.shape[0]:
            adj = bitset.scatter_clear_bits(adj, delta.rem_u, delta.rem_v,
                                            delta.rem_mask)
        if delta.clear_slots.shape[0]:
            slots = delta.clear_slots
            cleared = jnp.zeros((c,), bool).at[
                jnp.where(delta.clear_mask, slots, c)
            ].set(True, mode="drop")
            adj = jnp.where(cleared[:, None], jnp.uint32(0), adj)
            adj = adj & ~bitset.pack_bits(cleared)[None, :]
        if delta.add_u.shape[0]:
            adj = bitset.scatter_set_bits(adj, delta.add_u, delta.add_v,
                                          delta.add_mask)
        return adj

    def _admits(self, entry: LogEntry) -> bool:
        """Integrity + ordering gate for one entry.

        Returns True -> apply it, False -> already reflected here (a
        duplicate or recovery-boundary redelivery: SKIP — re-applying a
        stale delta onto newer state would undo later mutations).
        Raises `CorruptLogError` on a checksum mismatch and
        `ReplicaDiverged` on an epoch gap (dropped/reordered shipment)
        or a delta addressing slots past this replica's capacity (a
        missed grow entry — scatter would silently drop those bits)."""
        if int(entry.crc):
            host = _host_delta(entry.delta)
            want = int(entry.crc)
            got = entry_crc(int(entry.epoch), int(entry.grow_to),
                            int(entry.prev_epoch), host)
            if got != want:
                raise CorruptLogError(
                    f"log entry for epoch {int(entry.epoch)} failed its "
                    f"CRC32 check (stored {want:#010x}, computed "
                    f"{got:#010x}) — payload corrupted in transit")
        ep = int(self.epoch)
        e_ep, prev = int(entry.epoch), int(entry.prev_epoch)
        if prev >= 0 and prev > ep:
            raise ReplicaDiverged(ep, prev, e_ep)
        if e_ep <= ep:
            return False
        cap = max(self.capacity, int(entry.grow_to))
        mx = _max_slot(entry.delta)
        if mx >= cap:
            raise ReplicaDiverged(
                ep, prev, e_ep,
                detail=f"log entry for epoch {e_ep} addresses slot {mx} "
                       f"beyond capacity {cap} — a grow entry is missing "
                       "from the shipment")
        return True

    def apply(self, entry: LogEntry, verify: bool = True) -> "Replica":
        """Apply one log entry -> the replica at ``entry.epoch``.

        No cycle check, no dispatch: the delta's masks carry the
        primary's decisions; the closure advances through
        `closure_cache.apply_delta` (the same two kernels the writer
        commits with).  With ``verify`` (default) the entry first passes
        `_admits`: checksum + epoch-continuity checks, and safe skipping
        of already-applied entries (a skipped grow entry still re-embeds
        — a no-op when the capacity is already there)."""
        if verify and not self._admits(entry):
            return self._grown(int(entry.grow_to)) if entry.grow_to \
                else self
        rep = self._grown(entry.grow_to) if entry.grow_to else self
        rep = rep._windowed(_max_slot(entry.delta) + 1)
        delta = jax.tree.map(jnp.asarray, entry.delta)
        adj = rep._adj_after(delta)
        closure = closure_cache.apply_delta(
            rep.closure, adj, delta, update_impl=rep.update_impl,
            delete_impl=rep.delete_impl)
        return Replica(jnp.asarray(entry.epoch, jnp.int32), adj, closure,
                       rep.update_impl, rep.delete_impl)

    def replay(self, entries: Sequence[LogEntry],
               verify: bool = True) -> "Replica":
        """Replay a log tail.  Entries at or below this replica's epoch
        (the recovery boundary, duplicates) skip safely inside `apply`;
        gaps and corruption raise typed errors when ``verify``."""
        rep = self
        for e in entries:
            rep = rep.apply(e, verify=verify)
        return rep

    def resync(self, engine: DagEngine) -> "Replica":
        """A fresh replica at ``engine``'s current version, keeping this
        replica's kernel overrides — the recovery move after
        `ReplicaDiverged` when the live engine is reachable (otherwise
        use `recover_replica`: base image + tail)."""
        return Replica.from_engine(engine, self.update_impl,
                                   self.delete_impl)

    # ---------------------------------------------------------- reads

    def reachable_slots(self, u_slots, v_slots) -> jax.Array:
        """Batch PathExists over slots — one closure bit read per query,
        zero matmul products (the paper's wait-free read, served off the
        replicated closure)."""
        return closure_cache.closure_bit_get(
            self.closure, jnp.asarray(u_slots, jnp.int32),
            jnp.asarray(v_slots, jnp.int32))

    def converged_with(self, engine: DagEngine) -> bool:
        """True iff this replica's adjacency AND closure equal the
        primary engine's, bit for bit (the engine's cache is re-cleaned
        first so the comparison is against trusted bits).  The comparison
        runs on the dense equivalents, so a tiled replica converges with
        a dense primary (and vice versa) whenever the bits agree."""
        eng = engine.refresh_cache()
        mine = closure_cache.dense_of(self.closure)
        theirs = closure_cache.dense_of(eng.cache.closure)
        return bool(jnp.all(self.adj == eng.state.adj)
                    & jnp.all(mine == theirs))


# ------------------------------------------------------------ log on disk
#
# Framed v2 format (PR 9):
#
#   header:  8-byte magic | uint32 version | uint32 crc32(magic+version)
#   record:  uint32 payload_len | uint32 crc32(payload) | payload
#   payload: npz of meta=[epoch, grow_to, prev_epoch, crc] + delta arrays
#
# Framing + per-record CRCs make torn writes DETECTABLE and LOCALIZABLE:
# a record cut at EOF (or whose trailing checksum fails) is a torn tail
# and loads truncate to the last valid entry (the prefix property —
# recovery replays exactly what survived, never garbage); a checksum
# failure with more records after it is mid-file corruption and raises
# `CorruptLogError` naming the file and byte offset.  v1 (plain npz,
# PR 7) still loads — its "PK" zip magic is the version signal.

LOG_MAGIC = b"NBDAGLOG"
LOG_VERSION = 2
SUPPORTED_LOG_VERSIONS = (1, 2)
_LOG_HEADER = struct.Struct("<8sI")   # magic, version (then uint32 crc)
_LOG_RECORD = struct.Struct("<II")    # payload_len, crc32(payload)


def save_delta_log(path: str, entries: Sequence[LogEntry]) -> str:
    """Serialize a delta log (framed v2, atomic rename) — the
    incremental tail next to the checkpoint base image."""
    chunks = []
    header = _LOG_HEADER.pack(LOG_MAGIC, LOG_VERSION)
    chunks.append(header + struct.pack("<I", zlib.crc32(header)))
    for e in entries:
        delta = _host_delta(e.delta)
        epoch, grow_to = int(e.epoch), int(e.grow_to)
        prev = int(e.prev_epoch)
        crc = int(e.crc) or entry_crc(epoch, grow_to, prev, delta)
        buf = io.BytesIO()
        np.savez(buf,
                 meta=np.asarray([epoch, grow_to, prev, crc], np.int64),
                 **dict(zip(CacheDelta._fields, delta)))
        payload = buf.getvalue()
        chunks.append(_LOG_RECORD.pack(len(payload), zlib.crc32(payload))
                      + payload)
    tmp = path + ".tmp"
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(tmp, "wb") as f:
        f.write(b"".join(chunks))
    os.replace(tmp, path)
    return path


def _entry_from_payload(payload: bytes) -> LogEntry:
    data = np.load(io.BytesIO(payload))
    epoch, grow_to, prev, crc = (int(x) for x in data["meta"])
    delta = CacheDelta(*[data[name] for name in CacheDelta._fields])
    return LogEntry(epoch, grow_to, delta, prev, crc)


def _load_legacy_v1(path: str) -> List[LogEntry]:
    """PR 7's plain-npz log: no framing, no checksums — any zip-level
    damage is unlocalizable, so errors wrap into `CorruptLogError` at
    offset 0 instead of leaking zipfile/KeyError tracebacks."""
    try:
        data = np.load(path)
        out = []
        for i in range(int(data["n_entries"])):
            epoch, grow_to = (int(x) for x in data[f"e{i}_meta"])
            delta = CacheDelta(*[data[f"e{i}_{name}"]
                                 for name in CacheDelta._fields])
            out.append(LogEntry(epoch, grow_to, delta))
        return out
    except (OSError, KeyError, ValueError, EOFError,
            zipfile.BadZipFile, zlib.error) as err:
        raise CorruptLogError(
            f"legacy v1 delta log is truncated or corrupt ({err!r}); "
            "v1 has no per-entry framing, so no valid prefix can be "
            "salvaged — recover from the checkpoint base image alone",
            path=path, offset=0) from err


def load_delta_log(path: str, strict: bool = False) -> List[LogEntry]:
    """Load a delta log, verifying the framing checksums.

    A torn tail — the final record cut short or failing its checksum at
    EOF — truncates to the last valid entry (logged, or raised when
    ``strict``); corruption anywhere BEFORE the final record raises
    `CorruptLogError` with the file and byte offset.  An unsupported
    format version raises with the nearest supported version named."""
    with open(path, "rb") as f:
        data = f.read()
    if data[:2] == b"PK":  # legacy v1: a bare npz (zip) file
        return _load_legacy_v1(path)
    if len(data) < _LOG_HEADER.size + 4:
        raise CorruptLogError(
            f"file is {len(data)} bytes — shorter than a delta-log "
            "header", path=path, offset=0)
    magic, version = _LOG_HEADER.unpack_from(data, 0)
    (header_crc,) = struct.unpack_from("<I", data, _LOG_HEADER.size)
    if magic != LOG_MAGIC:
        raise CorruptLogError(
            f"bad magic {magic!r} — not a delta log (expected "
            f"{LOG_MAGIC!r}, or zip magic for a legacy v1 npz)",
            path=path, offset=0)
    if zlib.crc32(data[:_LOG_HEADER.size]) != header_crc:
        raise CorruptLogError("header failed its CRC32 check",
                              path=path, offset=0)
    if version != LOG_VERSION:
        nearest = min(SUPPORTED_LOG_VERSIONS,
                      key=lambda v: abs(v - version))
        hint = " (v1 logs are plain npz files, loaded transparently)" \
            if nearest == 1 else ""
        raise CorruptLogError(
            f"unsupported log format version {version}; nearest "
            f"supported version is {nearest}{hint}", path=path, offset=8)
    out: List[LogEntry] = []
    off = _LOG_HEADER.size + 4
    end = len(data)
    while off < end:
        torn = None
        if off + _LOG_RECORD.size > end:
            torn = f"record header cut short at byte {off}"
            length = crc = None
        else:
            length, crc = _LOG_RECORD.unpack_from(data, off)
            payload = data[off + _LOG_RECORD.size:
                           off + _LOG_RECORD.size + length]
            if len(payload) < length:
                torn = (f"entry {len(out)} payload cut short "
                        f"({len(payload)} of {length} bytes)")
            elif zlib.crc32(payload) != crc:
                if off + _LOG_RECORD.size + length >= end:
                    torn = (f"entry {len(out)} (the final record) "
                            "failed its CRC32 check")
                else:
                    raise CorruptLogError(
                        f"entry {len(out)} failed its CRC32 check with "
                        "more records after it — mid-file corruption, "
                        "not a torn write", path=path,
                        offset=off + _LOG_RECORD.size)
        if torn is not None:
            msg = (f"torn write: {torn}; truncating to {len(out)} "
                   "valid entries")
            if strict:
                raise CorruptLogError(msg, path=path, offset=off)
            logger.warning("%s: %s", path, msg)
            break
        try:
            out.append(_entry_from_payload(payload))
        except (OSError, KeyError, ValueError, EOFError,
                zipfile.BadZipFile) as err:
            raise CorruptLogError(
                f"entry {len(out)} passed its checksum but failed to "
                f"decode ({err!r})", path=path,
                offset=off + _LOG_RECORD.size) from err
        off += _LOG_RECORD.size + length
    return out


def recover_replica(checkpoint_dir: str, like: DagEngine,
                    entries: Sequence[LogEntry],
                    step: Optional[int] = None, update_impl=None,
                    delete_impl=None) -> "Replica":
    """Crash recovery: restore the base image into the structure of
    ``like`` (`ft/checkpoint.restore_engine_checkpoint` — a base saved at
    a smaller capacity grows forward), then replay the log tail from the
    base's own epoch (a leaf of the checkpointed pytree).  Returns a
    replica bit-for-bit converged with the primary that wrote the log.

    With ``step=None`` the NEWEST checkpoint whose arrays pass their
    manifest CRC32 is the base: a bit-rotted latest image
    (`CorruptCheckpointError`) logs a warning and recovery falls back to
    the next-older step — the tail replay covers the extra distance,
    since `Replica.apply` skips every entry at or below the base epoch.
    An explicit ``step`` is trusted as given (its errors propagate)."""
    from repro.ft import checkpoint as ckpt
    if step is not None:
        base = ckpt.restore_engine_checkpoint(checkpoint_dir, like,
                                              step=step)
    else:
        steps = ckpt.all_steps(checkpoint_dir)
        if not steps:
            raise FileNotFoundError(f"no checkpoint in {checkpoint_dir}")
        base = None
        errors = []
        for s in reversed(steps):  # newest first
            try:
                base = ckpt.restore_engine_checkpoint(checkpoint_dir,
                                                      like, step=s)
                break
            except ckpt.CorruptCheckpointError as err:
                logger.warning(
                    "checkpoint step %d is corrupt (%s); falling back "
                    "to the next-older base image", s, err)
                errors.append(err)
        if base is None:
            raise ckpt.CorruptCheckpointError(
                f"all {len(steps)} checkpoints in {checkpoint_dir} "
                "failed integrity checks; no valid base image") \
                from errors[-1]
    rep = Replica.from_engine(base, update_impl=update_impl,
                              delete_impl=delete_impl)
    return rep.replay(entries)

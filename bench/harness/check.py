"""The comparison that decides ``correct``.

After the window has closed, the plain reference (the configuration's
``reference`` module) is built from the same preload and replays the
front end's commit-order trace; every answer a client received is then
compared with the reference's answer at the same point.  Each number
compared has the limit 0 (the comparison is exact):

* ``preload_rejected``: preload inserts the writer refused (the preload
  is acyclic, so every one must be accepted);
* ``unanswered``: requests due in the window that never got an answer,
  or whose submit raised;
* ``accept_bits_wrong``: mutations whose answer differs from the
  reference's (the writer's cycle check, in commit order);
* ``reads_wrong``: reachability reads whose answer differs (the
  replica's reads at the tick's epoch);
* ``trace_wrong``: entries of the front end's own trace that differ from
  the reference, or a trace that does not match the answers sent;
* ``stale_reads``: reads served from the degraded fallback instead of
  the replica at the tick's epoch;
* ``vertices_wrong`` / ``edges_wrong``: the final vertex and edge sets,
  read through the engine's public ``contains`` / ``contains_edges`` /
  counts, against the reference's;
* ``snapshot_reads_wrong``: reads on the final snapshot, half of them
  between uniform live vertices and half from a vertex to one of its
  descendants, against the reference;
* ``window_edge_reads_wrong``: reads on the final snapshot through edges
  accepted during the window that the final graph still holds: for each
  sampled edge (u, v), from u or one of its nearest ancestors to v or
  one of its nearest descendants, and back (the writer's insert fold and
  the repairs after it);
* ``replica_unconverged``: replicas whose adjacency and closure differ
  from the writer's.
"""
from __future__ import annotations

import collections
from typing import Dict, List, Sequence, Tuple

import numpy as np

CHECK_BATCH = 4096
N_SNAPSHOT_READS = 512
N_WINDOW_EDGES = 512      # window edges read through, each both ways
NEAR = 16                 # nearest ancestors / descendants drawn from


def _chunks(cols: Sequence[np.ndarray], batch: int):
    n = len(cols[0])
    for lo in range(0, n, batch):
        k = min(batch, n - lo)
        out = []
        for c in cols:
            o = np.zeros(batch, np.int32)
            o[:k] = c[lo:lo + k]
            out.append(o)
        yield k, out


def replay(ref, trace: Sequence[Tuple[str, int, int, bool]]) -> List[bool]:
    """The reference's answer to each trace entry, in commit order."""
    return [ref.apply(kind, a, b) for kind, a, b, _ in trace]


def client_mismatches(trace, want, reqs, status, ok, ticks,
                      read_kinds: Sequence[str] = ("reachable",)
                      ) -> Tuple[int, int, int]:
    """(mutations wrong, reads wrong, trace/answer disagreements).

    The trace holds the served requests tick by tick, in commit order;
    the answers a client got carry their tick.  Within a tick, answers
    are matched to trace entries by (kind, a, b).  A request whose kind
    is in ``read_kinds`` (the reference's ``READ_KINDS``) is a read,
    any other a mutation."""
    served = [i for i in range(len(reqs)) if status[i] == 200]
    served.sort(key=lambda i: ticks[i])
    bad_mut = bad_read = bad_trace = 0
    if len(served) != len(trace):
        bad_trace += abs(len(served) - len(trace))
    pos = 0
    j = 0
    while j < len(served):
        tick = ticks[served[j]]
        k = j
        while k < len(served) and ticks[served[k]] == tick:
            k += 1
        seg = range(pos, min(pos + (k - j), len(trace)))
        expect: Dict[tuple, List[bool]] = collections.defaultdict(list)
        for e in seg:
            kind, a, b, got = trace[e]
            expect[(kind, a, b)].append(want[e])
            if got != want[e]:
                bad_trace += 1
        for i in served[j:k]:
            kind, a, b, _ = reqs[i]
            bucket = expect.get((kind, a, b))
            if not bucket:
                bad_trace += 1
                continue
            if bool(ok[i]) in bucket:
                bucket.remove(bool(ok[i]))
            else:
                bucket.pop()
                if kind in read_kinds:
                    bad_read += 1
                else:
                    bad_mut += 1
        pos += k - j
        j = k
    return bad_mut, bad_read, bad_trace


def engine_sets_wrong(engine, ref) -> Tuple[int, int]:
    """(vertices wrong, edges wrong) of the final engine, read through its
    public queries: every reference vertex and edge must be present and
    the counts must agree."""
    import jax

    has_v = jax.jit(lambda e, k: e.contains(k))
    has_e = jax.jit(lambda e, u, v: e.contains_edges(u, v))
    verts = np.asarray(sorted(ref.vertices), np.int32)
    missing_v = 0
    for k, (keys,) in _chunks([verts], CHECK_BATCH):
        missing_v += int((~np.asarray(has_v(engine, keys))[:k]).sum())
    pairs = np.asarray(list(ref.edges()), np.int32).reshape(-1, 2)
    missing_e = 0
    for k, (u, v) in _chunks([pairs[:, 0], pairs[:, 1]], CHECK_BATCH):
        missing_e += int((~np.asarray(has_e(engine, u, v))[:k]).sum())
    n_v = int(engine.live_vertex_count())
    n_e = int(engine.edge_count())
    return (missing_v + abs(n_v - len(ref.vertices)),
            missing_e + abs(n_e - ref.n_edges))


def snapshot_queries(ref, seed: int, n: int = N_SNAPSHOT_READS):
    """(from, to, want): half uniform live pairs, half (a, descendant)."""
    rng = np.random.default_rng([int(seed), 3])
    live = np.asarray(sorted(ref.vertices), np.int64)
    f = list(live[rng.integers(0, len(live), n // 2)])
    t = list(live[rng.integers(0, len(live), n // 2)])
    for a in live[rng.integers(0, len(live), 4 * n)]:
        if len(f) >= n:
            break
        desc = ref.descendants(int(a), 64)
        if desc:
            f.append(int(a))
            t.append(int(desc[int(rng.integers(0, len(desc)))]))
    f = np.asarray(f, np.int32)
    t = np.asarray(t, np.int32)
    want = np.asarray([ref.path_exists(int(a), int(b))
                       for a, b in zip(f, t)])
    return f, t, want


def window_edge_queries(ref, trace, seed: int, n: int = N_WINDOW_EDGES):
    """(from, to, want): for up to n edges accepted in the window (the
    served trace) and still in the reference's final graph, a read from
    u or a near ancestor to v or a near descendant, and its reverse."""
    rng = np.random.default_rng([int(seed), 4])
    edges = sorted({(a, b) for kind, a, b, ok in trace
                    if kind == "add_edge" and ok and ref.has_edge(a, b)})
    f, t = [], []
    for i in rng.permutation(len(edges))[:n]:
        u, v = edges[int(i)]
        up = [u] + ref.ancestors(u, NEAR)
        down = [v] + ref.descendants(v, NEAR)
        f.append(up[int(rng.integers(0, len(up)))])
        t.append(down[int(rng.integers(0, len(down)))])
    f, t = f + t, t + f
    want = np.asarray([ref.path_exists(a, b) for a, b in zip(f, t)], bool)
    return np.asarray(f, np.int32), np.asarray(t, np.int32), want


def snapshot_reads_wrong(snapshot, ref, trace, seed: int
                         ) -> Tuple[int, int, int]:
    """(wrong among `snapshot_queries`, wrong among `window_edge_queries`,
    window-edge queries made) on the final snapshot."""
    import jax

    read = jax.jit(lambda s, f, t: s.reachable(f, t))
    f1, t1, w1 = snapshot_queries(ref, seed)
    f2, t2, w2 = window_edge_queries(ref, trace, seed)
    got = np.asarray(read(snapshot, np.concatenate([f1, f2]),
                          np.concatenate([t1, t2])))
    return (int((got[:len(w1)] != w1).sum()),
            int((got[len(w1):] != w2).sum()), len(w2))

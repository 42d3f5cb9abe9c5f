"""The one traffic generator: preloads and request schedules from data.

A configuration's ``preload`` block and a traffic mix's JSON file are
parameters; this module turns them, with ``--seed``, into the exact
inputs of a run.  The same seed gives the same inputs.

Preload (``make_preload``): ``vertices`` keys in a random topological
order; vertex i gets 1 to ``max_preds`` (uniform) conflict edges from
distinct vertices among the ``pred_window`` before it, so the whole set
is acyclic and every insert is accepted.

Schedule (``make_schedule``): arrivals are a Poisson process conditioned
on its count: ``round(rate * seconds)`` arrivals fall uniformly in the
window.  Every seed gets the same arrival times and the same set of
sizes (edges per transaction, reversed edges), in another order and on
other keys: the seed changes what is asked, not how much or when, so
runs of different seeds measure the same work.  Each arrival is an SGT
transaction that sends, all at its due time, ``add_vertex(t)``;
``edges_min``..``edges_max`` ``add_edge(s, t)`` with distinct s among
the ``pred_window`` most recent live vertices, each reversed to
``(t, s)`` (a share ``reverse_p`` of all edges); ``reads`` ``reachable``
reads between live vertices among the ``read_window`` most recent; and,
with ``retire``, ``remove_vertex`` of the oldest live vertex (FIFO
retirement keeps the live count fixed).

Tenants are taken in turn, by arrival.
"""
from __future__ import annotations

import collections
import dataclasses
import json
from typing import List, Tuple

import numpy as np

Request = Tuple[str, int, int, str]


@dataclasses.dataclass(frozen=True)
class Schedule:
    """Requests in submission order; ``due[i]`` is request i's due time in
    seconds from the window's start."""

    due: np.ndarray
    reqs: List[Request]
    arrivals: int
    after: dict     # the generator's view once all are applied: keys (in
                    # age order), edges written, the next fresh key


    def __len__(self) -> int:
        return len(self.reqs)


ARRIVALS_SEED = 0     # one arrival pattern per mix, rate and window


def _rng(seed: int, stream: int) -> np.random.Generator:
    # seeds are any non-negative integer, wider than 32 bits too
    return np.random.default_rng([int(seed), stream])


def exact_counts(weights, n: int) -> np.ndarray:
    """Counts of each weight's share of n, summing to n exactly
    (largest remainders)."""
    w = np.asarray(weights, np.float64)
    raw = w / w.sum() * n
    counts = np.floor(raw).astype(np.int64)
    for i in np.argsort(counts - raw, kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return counts


def shuffled(rng, values, weights, n: int) -> np.ndarray:
    """n draws of ``values`` in exactly the weights' proportions, in an
    order of the seed's."""
    out = np.repeat(np.asarray(values), exact_counts(weights, n))
    return out[rng.permutation(n)]


def load(path) -> dict:
    with open(path) as f:
        return json.load(f)


def make_preload(seed: int, spec: dict):
    """(keys int32[n] in topological order, edges int32[E, 2] shuffled)."""
    n = int(spec["vertices"])
    max_preds = int(spec["max_preds"])
    window = int(spec["pred_window"])
    rng = _rng(seed, 1)
    keys = rng.permutation(n).astype(np.int32)
    counts = rng.integers(1, max_preds + 1, n)
    src, dst = [], []
    for i in range(1, n):
        lo = max(0, i - window)
        k = min(i - lo, int(counts[i]))
        preds = lo + rng.choice(i - lo, size=k, replace=False)
        src.append(keys[preds])
        dst.append(np.full(k, keys[i], np.int32))
    edges = np.stack([np.concatenate(src), np.concatenate(dst)], axis=1)
    return keys, edges[rng.permutation(len(edges))].astype(np.int32)


def arrival_times(rng: np.random.Generator, arrivals: dict,
                  seconds: float) -> np.ndarray:
    """Sorted due times in [0, seconds): a Poisson process at ``per_s``
    conditioned on its count."""
    n = int(round(float(arrivals["per_s"]) * seconds))
    return np.sort(rng.uniform(0.0, seconds, n))


def make_schedule(seed: int, mix: dict, keys: np.ndarray,
                  edges: np.ndarray, seconds: float,
                  per_s: float = None, fresh: int = None) -> Schedule:
    """The window's requests for ``mix`` after the preload (keys, edges).

    ``per_s`` overrides the mix's rate and ``fresh`` the first new key;
    the knee sweep chains windows through `Schedule.after`."""
    arrivals = dict(mix["arrivals"])
    if per_s is not None:
        arrivals["per_s"] = per_s
    times = arrival_times(_rng(ARRIVALS_SEED, 4), arrivals, seconds)
    rng = _rng(seed, 2)
    tenants = [str(t) for t in mix.get("tenants", ["t0"])]
    if fresh is None:
        fresh = int(keys.max()) + 1 if len(keys) else 0
    reqs, due, after = _transactions(rng, mix, keys, edges, times, tenants,
                                     fresh)
    return Schedule(np.asarray(due, np.float64), reqs, len(times), after)


def _transactions(rng, mix, keys, edges, times, tenants, fresh):
    lo, hi = int(mix["edges_min"]), int(mix["edges_max"])
    window = int(mix["pred_window"])
    read_window = int(mix["read_window"])
    reverse_p = float(mix["reverse_p"])
    n_reads = int(mix["reads"])
    retire = bool(mix["retire"])
    live = collections.deque(int(k) for k in keys)
    sizes = np.arange(lo, hi + 1)
    n_edges = shuffled(rng, sizes, np.ones(len(sizes)), len(times))
    total = int(n_edges.sum())
    reverse = shuffled(rng, [True, False], [reverse_p, 1 - reverse_p],
                       total)
    e = 0
    reqs: List[Request] = []
    due: List[float] = []
    for j, t_due in enumerate(times):
        tenant = tenants[j % len(tenants)]
        t = fresh
        fresh += 1
        out = [("add_vertex", t, 0)]
        recent = min(window, len(live))
        k = min(recent, int(n_edges[j]))
        for i in rng.choice(recent, size=k, replace=False):
            s = live[len(live) - 1 - int(i)]
            if reverse[e]:
                out.append(("add_edge", t, s))
            else:
                out.append(("add_edge", s, t))
            e += 1
        live.append(t)
        recent = min(read_window, len(live))
        for _ in range(n_reads):
            i, i2 = rng.integers(0, recent, 2)
            out.append(("reachable", live[len(live) - 1 - int(i)],
                        live[len(live) - 1 - int(i2)]))
        if retire:
            out.append(("remove_vertex", live.popleft(), 0))
        reqs.extend((kind, a, b, tenant) for kind, a, b in out)
        due.extend([float(t_due)] * len(out))
    after = {"keys": np.asarray(live, np.int32), "edges": edges,
             "fresh": fresh}
    return reqs, due, after

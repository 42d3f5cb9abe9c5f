"""The system under test, built from a configuration file.

Only public entry points of the program are used: ``Frontend.create``
with the configuration's front-end settings and its whole ``engine``
block, the program's mesh builder (``core.sharded.make_dag_mesh``) over
the cell's chips where the engine is sharded, the writer's mutators
(``fe.primary.add_vertices`` / ``add_edges_acyclic``) for the preload,
``Frontend.start`` to ship the preload to the replicas, and
``Frontend.warmup`` to compile every serving phase.  No internal layout
(graph state, closure tiles) is built here.
"""
from __future__ import annotations

import inspect
from typing import Tuple

import numpy as np


def make_frontend(cfg: dict, devices=None):
    """The configuration's front end.  Every key of its ``engine`` block
    goes to ``DagEngine.create`` (through ``Frontend.create``); a key that
    is not an option there raises ``ValueError``.  A sharded engine
    (``"backend": "sharded"``) gets a mesh over ``devices``, the cell's
    chips."""
    from repro.core import DagEngine, sharded
    from repro.serve import Frontend, FrontendConfig

    takes = set(inspect.signature(DagEngine.create).parameters)
    engine = dict(cfg["engine"])
    unknown = sorted(set(engine) - (takes - {"capacity", "mesh"}))
    if unknown:
        raise ValueError(f"engine keys {unknown} are not options of "
                         "DagEngine.create")
    if engine.get("backend") == "sharded":
        engine["mesh"] = sharded.make_dag_mesh(devices)
    return Frontend.create(int(cfg["capacity"]),
                           FrontendConfig(**cfg["frontend"]), **engine)


def _batches(cols, batch: int):
    """Fixed-shape padded slices of the columns, with their valid mask."""
    import jax.numpy as jnp

    n = len(cols[0])
    for lo in range(0, n, batch):
        k = min(batch, n - lo)
        out = [np.zeros(batch, np.int32) for _ in cols]
        for o, c in zip(out, cols):
            o[:k] = c[lo:lo + k]
        valid = np.arange(batch) < k
        yield [jnp.asarray(o) for o in out], jnp.asarray(valid), valid


def write_preload(fe, keys_edges, batch: int) -> int:
    """The default generator's preload writer: `preload` of its
    ``(keys, edges)``; returns the inserts the writer refused."""
    keys, edges = keys_edges
    n_v, n_e = preload(fe.primary, keys, edges, batch)
    return (len(keys) - n_v) + (len(edges) - n_e)


def preload(primary, keys: np.ndarray, edges: np.ndarray,
            batch: int) -> Tuple[int, int]:
    """Write the preload through the writer's public mutators in batches
    of ``batch``, shipping each call's log entry on its own; returns the
    (vertices, edges) the writer accepted."""
    n_v = n_e = 0
    for (k,), m, valid in _batches([keys], batch):
        n_v += int(np.asarray(primary.add_vertices(k, valid=m).ok)[valid]
                   .sum())
        primary.flush()
    for (u, v), m, valid in _batches([edges[:, 0], edges[:, 1]], batch):
        n_e += int(np.asarray(primary.add_edges_acyclic(u, v, valid=m).ok)
                   [valid].sum())
        primary.flush()
    return n_v, n_e

"""The system under test, built from a configuration file.

Only public entry points of the program are used: ``Frontend.create``
with the configuration's engine and front-end settings, the writer's
mutators (``fe.primary.add_vertices`` / ``add_edges_acyclic``) for the
preload, ``Frontend.start`` to ship the preload to the replicas, and
``Frontend.warmup`` to compile every serving phase.  No internal layout
(graph state, closure tiles) is built here.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np


def make_frontend(cfg: dict):
    from repro.serve import Frontend, FrontendConfig

    eng = cfg["engine"]
    fe_cfg = FrontendConfig(**cfg["frontend"])
    return Frontend.create(int(cfg["capacity"]), fe_cfg,
                           method=eng["method"],
                           subbatches=int(eng["subbatches"]),
                           closure_layout=eng["closure_layout"],
                           closure_region=int(eng["closure_region"]))


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

"""Faults planted in the program, to show that ``correct`` catches them.

Neither the benchmark's runs nor its tests leave one in place: each
function here plants its fault and returns the function that takes it
out again.  Both clear JAX's caches, so the programs traced before are
traced anew.
"""
from __future__ import annotations

from typing import Callable


def leave_out_the_fold() -> Callable[[], None]:
    """The tiled closure's insert fold returns the closure unchanged, on
    the writer and in the replica's apply: accepted edges reach the
    adjacency but never the closure that cycle checks and reads use."""
    import jax
    import jax.numpy as jnp
    from repro.core import closure_cache

    orig = closure_cache.insert_update_tiled

    def unchanged(closure, *args, **kwargs):
        return closure, jnp.asarray(False)

    closure_cache.insert_update_tiled = unchanged
    jax.clear_caches()

    def undo() -> None:
        closure_cache.insert_update_tiled = orig
        jax.clear_caches()
    return undo

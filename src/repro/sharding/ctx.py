"""Trace-time sharding-constraint context.

Model code is mesh-agnostic; launch code activates a mesh+rules context while
tracing, and ``constrain(x, logical_axes)`` resolves logical axes to a
``with_sharding_constraint`` (no-op outside the context, e.g. CPU unit
tests).  This is how activation-sharding decisions (vocab-sharded logits,
sequence-parallel residual streams) stay in one place.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.sharding import partition as pt

_CTX: contextvars.ContextVar = contextvars.ContextVar("shard_ctx", default=None)


@contextlib.contextmanager
def sharding_ctx(mesh: Mesh, rules: Optional[Dict] = None):
    tok = _CTX.set((mesh, rules or pt.DEFAULT_RULES))
    try:
        yield
    finally:
        _CTX.reset(tok)


def current_mesh() -> Optional[Mesh]:
    v = _CTX.get()
    return v[0] if v else None


def constrain(x: jax.Array, axes: Tuple[Optional[str], ...]) -> jax.Array:
    """Apply a logical-axis sharding constraint if a context is active."""
    v = _CTX.get()
    if v is None:
        return x
    mesh, rules = v
    spec = pt.spec_for(tuple(x.shape), axes, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain_priority(x: jax.Array, *options: Tuple[Optional[str], ...]
                       ) -> jax.Array:
    """Constrain with the first option that shards the most dims.

    Used for attention activations: shard q-heads over ``model`` when the
    head count divides, otherwise fall back to sequence sharding — keeps
    every arch's attention distributed on the fixed 16-way model axis
    without per-arch special cases.
    """
    v = _CTX.get()
    if v is None:
        return x
    mesh, rules = v
    best, best_n = None, -1
    for axes in options:
        spec = pt.spec_for(tuple(x.shape), axes, mesh, rules)
        n = sum(e is not None for e in spec)
        if n > best_n:
            best, best_n = spec, n
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, best))

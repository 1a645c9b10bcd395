"""Ambient mesh context.

``shard_map`` regions nested inside the jitted train step (ring attention)
need the concrete :class:`jax.sharding.Mesh`, but flax modules only carry
config. The Trainer publishes its mesh here for the duration of tracing —
the JAX-idiomatic alternative to threading a mesh argument through every
module ``__call__``.
"""

from __future__ import annotations

import contextlib
from typing import Iterator

from jax.sharding import Mesh

_CURRENT: list[Mesh] = []


def current_mesh() -> Mesh | None:
    return _CURRENT[-1] if _CURRENT else None


@contextlib.contextmanager
def use_mesh(mesh: Mesh) -> Iterator[Mesh]:
    _CURRENT.append(mesh)
    try:
        yield mesh
    finally:
        _CURRENT.pop()

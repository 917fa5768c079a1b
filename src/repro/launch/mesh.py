"""Mesh construction. FUNCTIONS (not module-level constants) so importing
this module never touches jax device state — the dry-run sets the
512-device host platform before calling them; tests and benches keep their
single real device."""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices: Optional[Sequence] = None) -> Mesh:
    """``jax.make_mesh`` with every axis Auto. The step builders place
    arrays with ``with_sharding_constraint`` and ``shard_map(axis_names=)``
    and leave the rest to the partitioner; under the Explicit axes that
    ``jax.make_mesh`` defaults to, a vocab-sharded gather needs an
    ``out_sharding`` the models never give."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: 16 x 16 = 256 chips (data x model).
    Multi-pod: 2 x 16 x 16 = 512 chips (pod x data x model) — the 'pod' axis
    is the DCN dimension; parameters never shard over it."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """Degenerate (1, 1) data x model mesh on one device (the first one
    unless ``devices`` names it)."""
    return make_mesh((1, 1), ("data", "model"),
                     devices=None if devices is None else list(devices)[:1])

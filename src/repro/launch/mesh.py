"""Production meshes.  Functions, not constants: importing this module never
touches jax device state (smoke tests must keep seeing 1 CPU device)."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """A mesh whose axes GSPMD partitions automatically.  The models place
    tensors with ``with_sharding_constraint`` (``distributed.sharding``) and
    need Auto axes: ``jax.make_mesh`` defaults to Explicit ones, under which
    the sharded embedding gather (``models/layers.py``) derives a spec that
    maps the ``data`` axis twice and raises ``DuplicateSpecError``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod (v5e); 2 pods = 512 chips multi-pod.

    Axis semantics: ``data`` carries DP/FSDP, ``model`` carries TP/EP/SP,
    ``pod`` carries cross-pod DP (gradient all-reduce over DCI only).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1-device mesh with the production axis names — lets the
    examples run the exact same step code on CPU."""
    return auto_mesh((1, 1), ("data", "model"))

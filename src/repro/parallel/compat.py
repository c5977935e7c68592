"""The repo's two mesh helpers, so every call site agrees on the flags.

``shard_map`` maps with ``check_vma=False`` (the halo permutes and the
ensemble vmap produce values the varying-manual-axes check cannot
type), and ``make_mesh`` builds meshes whose axes are all ``Auto``
(jax errors on mixed implicit/explicit axis use otherwise).
"""

from __future__ import annotations

from typing import Optional, Set

import jax
from jax.sharding import AxisType


def shard_map(f, mesh, in_specs, out_specs,
              axis_names: Optional[Set[str]] = None):
    """``jax.shard_map(..., check_vma=False)``.

    ``axis_names``: mesh axes to map manually; the remaining axes stay
    under automatic propagation.  ``None`` = all.
    """
    kwargs = {"check_vma": False}
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))

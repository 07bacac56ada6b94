"""The one spelling of ``shard_map`` the codebase uses.

Every shard_map call goes through :func:`shard_map`, which fixes the
keyword set (``check_vma`` off by default) in one place.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=False):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)

"""The repo's one ``shard_map`` policy (jax 0.9 ``jax.shard_map``)."""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off: the step
    functions mix psum'd and per-shard values the checker cannot type."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

"""The few JAX names ops/, inference/, distributed/ and analysis/ share
that are spelled awkwardly in JAX 0.9.0 (the installed release) — bound
once here, so the ring/ulysses paths cannot diverge from the
pipeline/collective paths.
"""
from __future__ import annotations

import jax
from jax import shard_map
from jax._src.core import extend_axis_env_nd

__all__ = ["shard_map", "shard_map_norep", "axis_size",
           "extend_axis_env"]

#: static mesh-axis size inside shard_map/collective tracing: an
#: axis-env lookup, so the sharded decode jaxpr carries exactly its
#: declared collectives (regression-tested against the audit catalog's
#: ``serving_decode_tp`` jaxpr)
axis_size = jax.lax.axis_size


def shard_map_norep(fn, mesh, in_specs, out_specs):
    """shard_map without the varying-manual-axes (replication) check."""
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def extend_axis_env(pairs):
    """Context manager binding (axis_name, size) pairs in the ambient
    axis env so a bare collective (``psum(x, "tp")`` outside any
    shard_map) can TRACE — the auditor uses this to trace per-shard
    program bodies abstractly (``ProgramSpec.axis_env``) without a mesh
    or devices. JAX exports no public spelling of this."""
    return extend_axis_env_nd([(str(n), int(s)) for n, s in pairs])

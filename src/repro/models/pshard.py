"""Activation-sharding hints for GSPMD.

``lax.scan`` + ``jax.checkpoint`` frequently lose sharding propagation for
intermediates (XLA falls back to replicated, exploding temp memory).  These
helpers annotate activations when an ambient mesh is present and degrade to
no-ops in single-device tests/sims.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def _mesh_axis_size(name: str) -> int | None:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or name not in mesh.shape:
        return None
    return mesh.shape[name]


def axis_size(name: str = "model") -> int:
    """Devices along mesh axis ``name`` of the ambient mesh; 1 without
    one."""
    return _mesh_axis_size(name) or 1


def shard_dim(x: jax.Array, dim: int, axis: str = "model") -> jax.Array:
    """Constrain dimension ``dim`` of x over mesh axis ``axis`` (if the
    ambient mesh has it and the dim divides).

    Other dims stay UNCONSTRAINED — a plain ``None`` would *force
    replication*, making GSPMD insert all-gathers for dims that were happily
    sharded (this exact bug cost 6×16 GB of expert-hidden gathers per Jamba
    MoE layer — EXPERIMENTS.md §Perf iteration 2).
    """
    size = _mesh_axis_size(axis)
    if size is None or x.ndim == 0:
        return x
    d = dim % x.ndim
    if x.shape[d] % size != 0 or x.shape[d] < size:
        return x
    spec = [P.UNCONSTRAINED] * x.ndim
    spec[d] = axis
    try:
        return jax.lax.with_sharding_constraint(x, P(*spec))
    except Exception:
        return x


def shard_last(x: jax.Array, axis: str = "model") -> jax.Array:
    return shard_dim(x, -1, axis)

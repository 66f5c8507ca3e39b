"""Aggregation dispatch: the Pallas kernel on TPU, the jnp oracle elsewhere.

``use_pallas`` selects the path:

* ``None`` — the compiled kernel exactly when the default backend is TPU,
  the jnp oracle otherwise;
* ``True`` — the compiled kernel (fails to lower off-TPU rather than
  silently running something else);
* ``"interpret"`` — the kernel body evaluated by the Pallas interpreter,
  which is how the CPU tests exercise it;
* ``False`` — the jnp oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .fl_aggregate import fl_aggregate as _fl_aggregate_pallas


def _use_kernel(use_pallas) -> bool:
    if use_pallas is None:
        return jax.default_backend() == "tpu"
    return bool(use_pallas)


def _interpret(use_pallas) -> bool:
    return use_pallas == "interpret"


def fl_aggregate(global_p, deltas, mask, use_pallas: bool | str | None = None):
    if _use_kernel(use_pallas):
        return _fl_aggregate_pallas(global_p, deltas, mask,
                                    interpret=_interpret(use_pallas))
    return ref.fl_aggregate_ref(global_p, deltas, mask)


def fl_aggregate_subset(global_p, deltas, valid, num_clients,
                        use_pallas: bool | str | None = None):
    """Participant-subset eq. (3): deltas [P, M] + validity lanes, averaged
    over the *population* ``num_clients`` (may be traced — it is folded into
    the mask so the Pallas kernel shape depends only on the bucket P)."""
    if _use_kernel(use_pallas):
        scaled = (valid.astype(jnp.float32)
                  / jnp.asarray(num_clients, jnp.float32))
        return _fl_aggregate_pallas(global_p, deltas, scaled,
                                    interpret=_interpret(use_pallas), denom=1)
    return ref.fl_aggregate_subset_ref(global_p, deltas, valid, num_clients)


def fl_aggregate_guarded(global_p, deltas, weights,
                         use_pallas: bool | str | None = None):
    """Defensively-weighted eq. (3): ``out = global + Σ_r w_r · sanitize(δ_r)``.

    ``weights`` is the fully-folded per-row coefficient (participation mask ×
    guard weights × 1/K) — the caller owns the averaging semantics; non-finite
    delta elements are zeroed *inside* the reduction, so a quarantined row
    (weight 0) cannot poison the global model.  Pallas path fuses the
    sanitize into the VMEM pass (no [R, M] sanitized copy in HBM)."""
    if _use_kernel(use_pallas):
        return _fl_aggregate_pallas(global_p, deltas, weights,
                                    interpret=_interpret(use_pallas), denom=1,
                                    guard=True)
    return ref.fl_aggregate_guarded_ref(global_p, deltas, weights)


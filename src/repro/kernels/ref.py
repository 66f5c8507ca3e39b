"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def fl_aggregate_ref(global_p: jax.Array, deltas: jax.Array,
                     weights: jax.Array, denom=None) -> jax.Array:
    """Eq. (3): out = global + (Σ_r w_r · δ_r) / denom.

    global_p: [M]; deltas: [R, M]; weights: [R].  ``denom`` defaults to R
    (the rows are the whole population); it may be a traced population
    size, or 1 when the weights already hold the scaling.
    """
    denom = deltas.shape[0] if denom is None else denom
    agg = jnp.sum(deltas.astype(jnp.float32)
                  * weights.astype(jnp.float32)[:, None], axis=0)
    agg = agg / jnp.asarray(denom, jnp.float32)
    return (global_p.astype(jnp.float32) + agg).astype(global_p.dtype)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True,
                        window: int | None = None) -> jax.Array:
    """Causal (optionally sliding-window) GQA attention.

    q: [B, S, H, hd]; k, v: [B, S, KV, hd]; H % KV == 0.  fp32 softmax.
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd).astype(jnp.float32)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, k.astype(jnp.float32))
    scores = scores / jnp.sqrt(hd).astype(jnp.float32)
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= j <= i
    if window is not None:
        mask &= j > i - window
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v.astype(jnp.float32))
    return out.reshape(B, S, H, hd).astype(q.dtype)


def selective_scan_ref(xc: jax.Array, dt: jax.Array, Bm: jax.Array,
                       Cm: jax.Array, A: jax.Array,
                       D: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Mamba S6 recurrence (fp32), by one associative scan over the
    expanded state.

    xc, dt: [B, S, d]; Bm, Cm: [B, S, N]; A: [d, N]; D: [d] → (y [B, S, d],
    h_last [B, d, N]).
    """
    dA = jnp.exp(dt[..., None] * A)                              # [B,S,d,N]
    dBx = (dt[..., None] * Bm[..., None, :]) * xc[..., None]

    def combine(a, b):
        (a1, b1), (a2, b2) = a, b
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (dA, dBx), axis=1)
    y = jnp.einsum("bsdn,bsn->bsd", h, Cm) + D * xc
    return y, h[:, -1]

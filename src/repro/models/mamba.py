"""Mamba (S6 selective-state-space) block.

Train/prefill run the selective scan one of two ways, chosen by
:func:`scan_kernel_engages` from what the code can observe:

* on a TPU, with the channel width a multiple of 128, outside the cost
  probe and with no mesh axis over the channels: the Pallas kernels of
  ``kernels/selective_scan.py`` (:func:`mamba_scan_kernel`), forward and
  backward, which keep the expanded [B, S, di, N] state in VMEM;
* everywhere else: ``jax.lax.associative_scan`` over chunks of the sequence
  (:func:`mamba_scan`), log-depth in each chunk.

Decode is the O(1) recurrence.  The recurrence per channel c and state n:

    h_t = exp(Δ_t·A) ⊙ h_{t-1} + (Δ_t·B_t)·x_t
    y_t = C_t·h_t + D ⊙ x_t

With ``cfg.ssm_inner_norm`` (Jamba) the Δ, B and C inputs from ``x_proj``
each pass an RMSNorm before use.

Cache: (conv tail [B, k-1, di], ssm state [B, di, N]).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..kernels.selective_scan import selective_scan
from .layers import dense_init, mm, rms_norm
from .costmode import cost_mode
from .pshard import axis_size, shard_dim, shard_last


class MambaCache(NamedTuple):
    conv: jax.Array   # [B, k-1, di] last inputs to the causal conv
    ssm: jax.Array    # [B, di, N]


def _dims(cfg: ArchConfig):
    di = cfg.ssm_expand * cfg.d_model
    dt_rank = max(1, math.ceil(cfg.d_model / 16))
    return di, dt_rank, cfg.ssm_state, cfg.ssm_conv


def init_mamba(key, cfg: ArchConfig, dtype):
    d = cfg.d_model
    di, dt_rank, N, k = _dims(cfg)
    ks = jax.random.split(key, 8)
    # S4D-real initialization for A
    A = jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32)[None, :], (di, 1))
    dt = jnp.exp(jax.random.uniform(ks[0], (di,), jnp.float32)
                 * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    dt_bias = dt + jnp.log1p(-jnp.exp(-dt))  # inverse softplus
    p = {
        "in_proj": dense_init(ks[1], d, 2 * di, dtype),
        "conv_w": (jax.random.normal(ks[2], (k, di), jnp.float32)
                   / jnp.sqrt(float(k))).astype(dtype),
        "conv_b": jnp.zeros((di,), dtype),
        "x_proj": dense_init(ks[3], di, dt_rank + 2 * N, dtype),
        "dt_proj": dense_init(ks[4], dt_rank, di, jnp.float32),
        "dt_bias": dt_bias,
        "A_log": jnp.log(A),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": dense_init(ks[5], di, d, dtype),
    }
    if cfg.ssm_inner_norm:
        p.update(dt_norm=jnp.ones((dt_rank,), dtype),
                 b_norm=jnp.ones((N,), dtype), c_norm=jnp.ones((N,), dtype))
    return p


def _ssm_params(p, cfg: ArchConfig, xc):
    """xc: [B,S,di] post-conv activations → (dt [B,S,di] after softplus,
    B [B,S,N], C [B,S,N], A [di,N]), float32."""
    di, dt_rank, N, _ = _dims(cfg)
    proj = xc @ p["x_proj"]
    dt_in, Bc, Cc = jnp.split(proj.astype(jnp.float32),
                              [dt_rank, dt_rank + N], axis=-1)
    if cfg.ssm_inner_norm:
        dt_in = rms_norm(dt_in, p["dt_norm"], cfg.norm_eps)
        Bc = rms_norm(Bc, p["b_norm"], cfg.norm_eps)
        Cc = rms_norm(Cc, p["c_norm"], cfg.norm_eps)
    dt = jax.nn.softplus(dt_in @ p["dt_proj"] + p["dt_bias"])   # [B,S,di]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))               # [di,N]
    return dt, Bc, Cc, A


def _ssm_inputs(p, cfg: ArchConfig, xc):
    """xc: [B,S,di] post-conv activations → (dA [B,S,di,N], dBx [B,S,di,N],
    C [B,S,N])."""
    dt, Bc, Cc, A = _ssm_params(p, cfg, xc)
    dA = jnp.exp(dt[..., None] * A)                             # [B,S,di,N]
    dBx = (dt[..., None] * Bc[..., None, :]) * xc.astype(jnp.float32)[..., None]
    return dA, dBx, Cc


def _conv(p, x, cfg: ArchConfig, tail=None):
    """Causal depthwise conv1d.  x: [B,S,di]; tail: [B,k-1,di] or None.

    Train path uses pad() rather than concat(zeros, x) — the concat version
    made GSPMD gather a [B,S-1,di] fp32 slice across the mesh (§Perf iter 3).
    """
    k = cfg.ssm_conv
    if tail is None:
        xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))           # [B,S+k-1,di]
    else:
        xp = jnp.concatenate([tail, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * p["conv_w"][i] for i in range(k))
    return jax.nn.silu(out + p["conv_b"]), xp[:, -(k - 1):]


def _combine(a, b):
    (a1, b1), (a2, b2) = a, b
    return a1 * a2, a2 * b1 + b2


#: the parameters the selective scan reads (the projections around it are
#: applied outside :func:`mamba_scan`)
SCAN_KEYS = ("x_proj", "dt_proj", "dt_bias", "A_log", "D", "dt_norm",
             "b_norm", "c_norm")


@functools.partial(jax.jit, static_argnames=("cfg", "chunk"))
def mamba_scan(p, cfg: ArchConfig, xc, chunk: int):
    """The selective scan: post-conv activations ``xc [B,S,di]`` →
    ``(y [B,S,di] f32, last state [B,di,N])``.

    *Chunked*: a sequential ``lax.scan`` over S/chunk blocks carrying the
    [B,di,N] state, with a log-depth associative scan inside each block.
    Never materializes the full [B,S,di,N] tensor (68 TB for Jamba at 32k) —
    peak is O(B·chunk·di·N).
    """
    B, S, di = xc.shape
    N = cfg.ssm_state
    nb = S // chunk
    xcb = xc.reshape(B, nb, chunk, di).transpose(1, 0, 2, 3)  # [nb,B,c,di]

    @jax.checkpoint
    def block(h0, xc_blk):
        # rematerialized per-chunk: backward recomputes the chunk's
        # [B,c,di,N] internals from (h0, xc_blk) instead of storing them
        # across all S/c chunks (the difference between ~1 GB and ~100 GB
        # of residuals per Mamba layer at Jamba scale)
        dA, dBx, Cc = _ssm_inputs(p, cfg, xc_blk)          # [B,c,di,N]
        dA = shard_dim(dA, 2)
        dBx = shard_dim(dBx, 2)
        # fold carry into the first element: h_1 = dA_1 h0 + dBx_1
        dBx = dBx.at[:, 0].add(dA[:, 0] * h0)
        _, h = jax.lax.associative_scan(_combine, (dA, dBx), axis=1)
        y = jnp.einsum("bsdn,bsn->bsd", h, Cc) \
            + p["D"] * xc_blk.astype(jnp.float32)
        return h[:, -1], y

    h0 = shard_dim(jnp.zeros((B, di, N), jnp.float32), 1)
    h_last, yb = jax.lax.scan(block, h0, xcb)
    return yb.transpose(1, 0, 2, 3).reshape(B, S, di), h_last


def mamba_scan_kernel(p, cfg: ArchConfig, xc, interpret: bool = False):
    """The selective scan by the Pallas kernels: ``xc [B,S,di]`` → ``(y
    [B,S,di] f32, last state [B,di,N])``.  Δ, B and C go to the kernel
    unexpanded; its VJP is the backward kernel."""
    dt, Bc, Cc, A = _ssm_params(p, cfg, xc)
    return selective_scan(xc, dt, Bc, Cc, A, p["D"], interpret=interpret)


def scan_kernel_engages(backend: str, d_inner: int, cost: bool,
                        channel_shards: int) -> bool:
    """Whether the scan runs as the Pallas kernels: on a TPU, with the
    channels a whole number of 128 lanes, outside the cost probe (whose
    counts need the direct form) and with the channels unsharded (XLA
    cannot partition a Mosaic call)."""
    return (backend == "tpu" and d_inner % 128 == 0 and not cost
            and channel_shards == 1)


def mamba_forward(p, cfg: ArchConfig, x, return_cache=False,
                  chunk: int = 64):
    """x: [B,S,d] → y [B,S,d] (+ cache); the scan is
    :func:`mamba_scan_kernel` where :func:`scan_kernel_engages`, else
    :func:`mamba_scan`, ``chunk`` tokens a step (its [B,chunk,di,N] levels
    set the scan's memory)."""
    B, S, d = x.shape
    xz = shard_last(mm(x, p["in_proj"]))
    xin, z = jnp.split(xz, 2, axis=-1)
    xc, tail = _conv(p, xin, cfg)
    xc = shard_last(xc)

    sp = {k: p[k] for k in SCAN_KEYS if k in p}
    if scan_kernel_engages(jax.default_backend(), xc.shape[-1], cost_mode(),
                           axis_size("model")):
        y, h_last = mamba_scan_kernel(sp, cfg, xc)
    else:
        c = min(chunk, S)
        if S % c != 0 or cost_mode():  # ragged/test shapes or cost probe
            c = S
        y, h_last = mamba_scan(sp, cfg, xc, chunk=c)
    y = shard_last(y)
    y = y.astype(x.dtype) * jax.nn.silu(z)
    out = mm(y, p["out_proj"])
    if return_cache:
        return out, MambaCache(conv=tail, ssm=h_last)
    return out


def init_mamba_cache(cfg: ArchConfig, batch: int, dtype) -> MambaCache:
    di, _, N, k = _dims(cfg)
    return MambaCache(conv=jnp.zeros((batch, k - 1, di), dtype),
                      ssm=jnp.zeros((batch, di, N), jnp.float32))


def mamba_decode(p, cfg: ArchConfig, x, cache: MambaCache):
    """One-token step.  x: [B,1,d]."""
    B = x.shape[0]
    xz = mm(x, p["in_proj"])
    xin, z = jnp.split(xz, 2, axis=-1)
    xc, tail = _conv(p, xin, cfg, tail=cache.conv)
    dA, dBx, Cc = _ssm_inputs(p, cfg, xc)            # S = 1
    h = dA[:, 0] * cache.ssm + dBx[:, 0]             # [B,di,N]
    y = jnp.einsum("bdn,bn->bd", h, Cc[:, 0]) + p["D"] * xc[:, 0].astype(jnp.float32)
    y = y[:, None].astype(x.dtype) * jax.nn.silu(z)
    return mm(y, p["out_proj"]), MambaCache(conv=tail, ssm=h)

"""Pallas TPU kernel: causal GQA flash attention (forward).

Streaming-softmax over KV blocks with VMEM scratch accumulators — the
standard TPU flash schedule:

  grid = (B, H, S/BQ, S/BK)   last axis sequential (reduction)
  q block   (BQ, hd)   — revisited across the KV axis
  k/v block (BK, hd)   — marched along the last grid axis
  scratch   m/l (BQ, 128) fp32, acc (BQ, hd) fp32  (VMEM)

BQ = BK = 128 aligns the MXU (128×128 systolic array).  GQA maps query head
h → kv head h // G in the BlockSpec index_map, so KV is never duplicated in
HBM.  Causal masking is index arithmetic inside the kernel; fully-masked
blocks contribute nothing (NEG_INF scores wash out of the running softmax).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, bq: int, bk: int, causal: bool,
            window: int | None):
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # [BQ, hd]
    k = k_ref[0, 0].astype(jnp.float32)                  # [BK, hd]
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [BQ, BK]

    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, 0]                                 # [BQ]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[:, 0] = l_ref[:, 0] * corr + p.sum(axis=1)
    acc_ref[...] = (acc_ref[...] * corr[:, None]
                    + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ()))))
    m_ref[:, 0] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                    interpret: bool = False) -> jax.Array:
    """q: [B,S,H,hd]; k,v: [B,S,KV,hd] → [B,S,H,hd].  S % bq == S % bk == 0."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    scale = 1.0 / (hd ** 0.5)

    qt = q.transpose(0, 2, 1, 3)   # [B,H,S,hd]
    kt = k.transpose(0, 2, 1, 3)   # [B,KV,S,hd]
    vt = v.transpose(0, 2, 1, 3)

    grid = (B, H, S // bq, S // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bq=bq, bk=bk, causal=causal,
                          window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running max m
            pltpu.VMEM((bq, 128), jnp.float32),   # running sum l
            pltpu.VMEM((bq, hd), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)

"""Pallas TPU kernel: Mamba S6 selective scan (forward).

The recurrence h_t = exp(Δ_t A)⊙h_{t-1} + (Δ_t B_t)x_t is sequential in t but
embarrassingly parallel over (batch, channel-block).  Schedule:

  grid = (B, d/BD, S/SC)   last axis sequential ("arbitrary")
  blocks: xc/dt (1, SC, BD); B/C (1, SC, N); A (BD, N); D (1, BD)
  scratch: h (BD, N) fp32 — the recurrent state, persistent across the S axis

The [B,S,d,N] tensor of the naive formulation is never materialized: VMEM
holds one (SC, BD) input tile and the (BD, N) state (BD=256, N=16, SC=128:
~200 KB).  The channel axis BD=256 is lane-aligned (128×2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BD = 256
DEFAULT_SC = 128


def _kernel(xc_ref, dt_ref, bm_ref, cm_ref, a_ref, d_ref, y_ref, h_ref, *,
            sc: int):
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    A = a_ref[...].astype(jnp.float32)                 # [BD, N]
    Dv = d_ref[...].astype(jnp.float32)[0]             # [BD]

    def step(t, h):
        row = (pl.ds(0, 1), pl.ds(t, 1), slice(None))
        dt_t = dt_ref[row][0, 0]                       # [BD]
        x_t = xc_ref[row][0, 0]
        b_t = bm_ref[row][0, 0]                        # [N]
        c_t = cm_ref[row][0, 0]
        dt_f = dt_t.astype(jnp.float32)
        dA = jnp.exp(dt_f[:, None] * A)                # [BD, N]
        h = dA * h + (dt_f * x_t.astype(jnp.float32))[:, None] \
            * b_t.astype(jnp.float32)[None, :]
        y = jnp.sum(h * c_t.astype(jnp.float32)[None, :], axis=1) \
            + Dv * x_t.astype(jnp.float32)
        y_ref[row] = y.astype(y_ref.dtype)[None, None, :]
        return h

    h = jax.lax.fori_loop(0, sc, step, h_ref[...])
    h_ref[...] = h


@functools.partial(jax.jit, static_argnames=("bd", "sc", "interpret"))
def selective_scan(xc: jax.Array, dt: jax.Array, Bm: jax.Array,
                   Cm: jax.Array, A: jax.Array, D: jax.Array, *,
                   bd: int = DEFAULT_BD, sc: int = DEFAULT_SC,
                   interpret: bool = False) -> jax.Array:
    """xc, dt: [B,S,d]; Bm, Cm: [B,S,N]; A: [d,N]; D: [d] → y [B,S,d] fp32.

    d % bd == 0 and S % sc == 0 (pad upstream if needed).
    """
    B, S, d = xc.shape
    N = Bm.shape[-1]
    bd = min(bd, d)
    sc = min(sc, S)
    assert d % bd == 0 and S % sc == 0, (d, bd, S, sc)

    grid = (B, d // bd, S // sc)
    return pl.pallas_call(
        functools.partial(_kernel, sc=sc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, sc, bd), lambda b, c, s: (b, s, c)),
            pl.BlockSpec((1, sc, bd), lambda b, c, s: (b, s, c)),
            pl.BlockSpec((1, sc, N), lambda b, c, s: (b, s, 0)),
            pl.BlockSpec((1, sc, N), lambda b, c, s: (b, s, 0)),
            pl.BlockSpec((bd, N), lambda b, c, s: (c, 0)),
            pl.BlockSpec((1, bd), lambda b, c, s: (0, c)),
        ],
        out_specs=pl.BlockSpec((1, sc, bd), lambda b, c, s: (b, s, c)),
        out_shape=jax.ShapeDtypeStruct((B, S, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bd, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xc, dt, Bm, Cm, A, D.reshape(1, d))

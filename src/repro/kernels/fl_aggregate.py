"""Pallas TPU kernel: masked pseudo-gradient aggregation (paper eq. 3).

The server update ``x ← x + (1/K) Σ_{k∈C_t} δ_k`` is a pure HBM-bandwidth
op over K × P bytes every round.  Fusing mask·scale·reduce·add into one pass
reads each δ tile once and writes the updated global tile once — ~2× less
HBM traffic than the unfused jnp chain (mask-mul materializes a K×P temp).

Grid: ``(M tiles, row tiles)``.  The M axis is ``"parallel"``; the row axis
is the reduction, ``"arbitrary"`` and last, so each output tile stays
resident while its row tiles stream through an f32 VMEM accumulator.
The delta block keeps the caller's ``[R, M]`` layout (no HBM relayout).
Block shapes:
  deltas  (RT, TILE_M)  — one row tile of one M tile
  global  (1, TILE_M)   — fetched once per M tile
  mask    (RT, 1)
  acc     (1, TILE_M) f32 scratch
TILE_M = BLOCK_R·128 = 8192 lanes.  RT = R when R ≤ ROW_TILE, else
ROW_TILE (rows padded with mask 0 to a multiple).  VMEM per step at RT=64,
fp32: 64·8192·4 = 2 MiB of deltas, 4 MiB double-buffered — inside the
default scoped VMEM for any R.  MXU-free (VPU reduction), 128-lane aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_R = 64
LANE = 128
ROW_TILE = 64


def _kernel(mask_ref, global_ref, deltas_ref, out_ref, acc_ref, *,
            inv_k: float, guard: bool):
    r = pl.program_id(1)

    @pl.when(r == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    d = deltas_ref[...].astype(jnp.float32)          # [RT, TILE_M]
    if guard:
        # non-finite quarantine, fused: a rejected row arrives with mask 0,
        # but 0 · NaN = NaN — zero the poison in VMEM so the zero weight
        # actually rejects it.  One extra VPU pass over data already
        # resident; no sanitized [K, M] copy ever exists in HBM.
        d = jnp.where(jnp.isfinite(d), d, 0.0)
    m = mask_ref[...].astype(jnp.float32)            # [RT, 1]
    acc_ref[...] += jnp.sum(d * m, axis=0, keepdims=True)

    @pl.when(r == pl.num_programs(1) - 1)
    def _finish():
        out_ref[...] = (global_ref[...].astype(jnp.float32)
                        + acc_ref[...] * inv_k).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "denom", "guard"))
def fl_aggregate(global_p: jax.Array, deltas: jax.Array, mask: jax.Array,
                 interpret: bool = False,
                 denom: int | None = None,
                 guard: bool = False) -> jax.Array:
    """global_p: [M]; deltas: [R, M]; mask: [R] → updated global [M].

    ``R`` is the *row* count of the delta block — the full population K in
    the dense path, or a padded participant bucket P in the sparse path.
    ``denom`` is the eq.-3 averaging denominator (the population size K);
    it defaults to ``R``, which is only correct when the rows ARE the whole
    population.  The sparse path passes ``deltas: [P, M]`` for the gathered
    transmitting set with ``mask`` = its validity lanes and ``denom=K``, so
    one compiled kernel shape serves every population size sharing a bucket.

    ``guard=True`` zeroes non-finite delta elements inside the kernel
    (defensive aggregation: a quarantined row carries mask 0, and in-VMEM
    sanitization keeps its NaN/Inf from poisoning the reduction).

    ``interpret=True`` evaluates the kernel body with the Pallas
    interpreter (how the CPU tests run it); the default compiles for TPU.

    M is padded to a (BLOCK_R·128) multiple internally, and R to a
    ROW_TILE multiple when it exceeds one row tile.
    """
    R, M = deltas.shape
    inv_k = 1.0 / (R if denom is None else int(denom))
    tile = BLOCK_R * LANE
    Mp = (M + tile - 1) // tile * tile
    rt = R if R <= ROW_TILE else ROW_TILE
    Rp = (R + rt - 1) // rt * rt
    gp = jnp.pad(global_p, (0, Mp - M)).reshape(1, Mp)
    dp = jnp.pad(deltas, ((0, Rp - R), (0, Mp - M)))
    mp = jnp.pad(mask, (0, Rp - R)).reshape(Rp, 1)
    grid = (Mp // tile, Rp // rt)

    out = pl.pallas_call(
        functools.partial(_kernel, inv_k=inv_k, guard=guard),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rt, 1), lambda i, r: (r, 0)),
            pl.BlockSpec((1, tile), lambda i, r: (0, i)),
            pl.BlockSpec((rt, tile), lambda i, r: (r, i)),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i, r: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Mp), global_p.dtype),
        scratch_shapes=[pltpu.VMEM((1, tile), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(mp, gp, dp)
    return out.reshape(Mp)[:M]

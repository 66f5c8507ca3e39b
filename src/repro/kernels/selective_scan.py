"""Pallas TPU kernels: the Mamba-1 selective scan, forward and backward.

The recurrence, per batch row b, channel c and state n:

    h_t = exp(dt_t·A) ⊙ h_{t-1} + (dt_t·x_t)·B_t
    y_t = Σ_n C_t[n]·h_t[n] + D·x_t

is sequential in t and independent over (b, c).  The expanded state
[B, S, di, N] of the associative form never exists here: each grid step
holds the [N, di-block] state of one batch row in vregs and walks a block
of time steps, reading x_t, dt_t and writing y_t one (8, 128) tile a step.

Layout.  A channel block is CB = 8·128 = 1,024 channels laid out as one
(8, 128) tile (sublane r, lane l is channel 128·r + l), so the state is N
such tiles, one a state index.  ``B_t[n]`` and ``C_t[n]`` are scalars read
from SMEM and broadcast for free; the reduction over n in ``y_t`` is a
tree of N tile adds, with no cross-lane work.  XLA stores ``[B, S, di]`` in
(8, 128) tiles of 8 time steps × 128 channels; the wrappers view it in that
order, ``[B, S/8, 8·di/128, 128]`` (a bitcast, no copy), and the kernels
gather time step t of a channel block, one sublane of each of its 8 tiles,
with one strided load (and write y_t with one strided store).  di is
padded to a multiple of 1,024, S to a multiple of the time block with
dt = x = 0, which leaves the state unchanged.  The time loops run
``UNROLL`` steps an iteration, so that one step's exps and sums overlap
the next step's and only the state update is serial.

Grid ``(B, S/TS, di/CB)``, channel blocks innermost.  The forward keeps one
state tile set per channel block in VMEM scratch across the time blocks.
Under ``jax.vmap`` (the engine's client axis) Pallas prepends a grid axis.

Backward (``mamba_scan_bwd``), as the mamba-ssm CUDA kernel does it: the
forward saves the state at the start of every time block (``[B, S/TS, N,
di]``, 52 MB a Jamba-3B layer at B=10, S=1,024, TS=64); the backward walks
the time blocks in reverse, replays the block's states from its saved start
into VMEM, then runs the adjoint recurrence

    gh_t = g_t + gy_t·C_t,   g_{t-1} = exp(dt_t·A) ⊙ gh_t

giving dx, ddt a step, dB_t/dC_t as sums over the block's channels, and
dA, dD summed over time.  dB and dC accumulate over channel blocks, dA and
dD over time blocks, each in a resident output block in a fixed order; the
batch sums of dA and dD are taken outside.  Nothing depends on scheduling,
so a rerun is bit-identical.

VMEM at TS=64, CB=1,024, N=16: forward 4 KB a step of each of x, dt, y
(double-buffered: 1.5 MB), the state scratch 64 KB per channel block;
backward adds gy, dx, ddt (2.3 MB more double-buffered), the replayed
states (TS+1)·64 KB = 4.2 MB and the resident dA block N·di·4 B (327 KB at
di = 5,120).  SMEM holds the time block's B and C, 2·TS·N floats.

``mamba_scan_fwd`` and ``mamba_scan_bwd`` are jitted functions holding only
their ``pallas_call``: a TPU trace names the custom call after its jit
(``%mamba_scan_fwd.N``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
ROWS = 8                      # sublanes of a channel block
CB = ROWS * LANE              # channels a grid step
DEFAULT_TS = 64               # time steps a grid step
VMEM_LIMIT = 48 * 1024 * 1024
UNROLL = 4                    # time steps a loop iteration


def _rows(c):
    return pl.ds(pl.multiple_of(c * ROWS, ROWS), ROWS)


def _tree_sum(xs):
    """Σ xs pairwise: a dependency chain of log2(len) adds, not len."""
    while len(xs) > 1:
        xs = [a + b for a, b in zip(xs[::2], xs[1::2])] + xs[len(xs) & ~1:]
    return xs[0]


def _loop(ts, step, carry, reverse=False):
    """``step(i, carry)`` for the block's time steps in order (or in
    reverse), ``UNROLL`` of them a loop iteration (Mosaic lowers a
    ``fori_loop`` unrolled only in full)."""
    def body(j, carry):
        for r in range(UNROLL):
            i = j * UNROLL + r
            carry = step(ts - 1 - i if reverse else i, carry)
        return carry
    return jax.lax.fori_loop(0, ts // UNROLL, body, carry)


def _at(i):
    """Time step ``i`` of a block in the tile-order view: one sublane of
    each of the block's 8 channel tiles, gathered by a strided load."""
    return i // 8, pl.ds(i % 8, ROWS, stride=8), slice(None)


def _fwd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, *refs, ts: int,
                n: int, save: bool):
    if save:
        y_ref, hl_ref, hc_ref, h_scr = refs
    else:
        (y_ref, hl_ref, h_scr), hc_ref = refs, None
    t, c = pl.program_id(1), pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        h_scr[c] = jnp.zeros(h_scr.shape[1:], jnp.float32)

    if save:
        hc_ref[...] = h_scr[c]
    A = [a_ref[k] for k in range(n)]              # n × [8, 128]
    Dv = d_ref[...]

    def step(i, h):
        dt, x = dt_ref[_at(i)], x_ref[_at(i)]
        u = dt * x
        out = tuple(jnp.exp(dt * A[k]) * h[k] + u * b_ref[i, k]
                    for k in range(n))
        y_ref[_at(i)] = _tree_sum([Dv * x] + [out[k] * c_ref[i, k]
                                              for k in range(n)])
        return out

    h0 = h_scr[c]
    h = _loop(ts, step, tuple(h0[k] for k in range(n)))
    h = jnp.stack(h)
    h_scr[c] = h

    @pl.when(t == pl.num_programs(1) - 1)
    def _last():
        hl_ref[:, _rows(c), :] = h


def _bwd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, gy_ref, ghl_ref,
                hc_ref, dx_ref, ddt_ref, db_ref, dc_ref, da_ref, dd_ref,
                g_scr, h_scr, *, ts: int, n: int):
    t, c = pl.program_id(1), pl.program_id(2)
    rows = _rows(c)

    @pl.when(t == 0)
    def _init_channels():
        g_scr[c] = ghl_ref[:, rows, :]
        da_ref[:, rows, :] = jnp.zeros((n, ROWS, LANE), jnp.float32)
        dd_ref[rows, :] = jnp.zeros((ROWS, LANE), jnp.float32)

    @pl.when(c == 0)
    def _init_time_block():
        db_ref[...] = jnp.zeros(db_ref.shape, jnp.float32)
        dc_ref[...] = jnp.zeros(dc_ref.shape, jnp.float32)

    A = [a_ref[k] for k in range(n)]
    Dv = d_ref[...]

    # replay the block's states from its saved start: h_scr[i + 1] = h_i
    h_scr[0] = hc_ref[...]

    def replay(i, h):
        dt = dt_ref[_at(i)]
        u = dt * x_ref[_at(i)]
        h = tuple(jnp.exp(dt * A[k]) * h[k] + u * b_ref[i, k]
                  for k in range(n))
        h_scr[i + 1] = jnp.stack(h)
        return h

    h0 = h_scr[0]
    _loop(ts, replay, tuple(h0[k] for k in range(n)))

    lane = jax.lax.broadcasted_iota(jnp.int32, (n, ts), 1)

    def adjoint(i, carry):
        g, acc_b, acc_c, dd = carry
        dt, x, gy = dt_ref[_at(i)], x_ref[_at(i)], gy_ref[_at(i)]
        u = dt * x
        h_now, h_prev = h_scr[i + 1], h_scr[i]
        g_new, p_b, p_c, q_b, q_dt, da = [], [], [], [], [], []
        for k in range(n):
            a = jnp.exp(dt * A[k])
            gh = g[k] + gy * c_ref[i, k]
            p_c.append(gy * h_now[k])
            p_b.append(gh * u)
            q_b.append(gh * b_ref[i, k])
            s = gh * h_prev[k] * a
            q_dt.append(s * A[k])
            da.append(s * dt)
            g_new.append(a * gh)
        s_b = _tree_sum(q_b)
        dx_ref[_at(i)] = gy * Dv + s_b * dt
        ddt_ref[_at(i)] = s_b * x + _tree_sum(q_dt)
        da_ref[:, rows, :] += jnp.stack(da)
        col_b = jnp.sum(jnp.sum(jnp.stack(p_b), axis=1), axis=1,
                        keepdims=True)                       # [n, 1]
        col_c = jnp.sum(jnp.sum(jnp.stack(p_c), axis=1), axis=1,
                        keepdims=True)
        acc_b = jnp.where(lane == i, col_b, acc_b)
        acc_c = jnp.where(lane == i, col_c, acc_c)
        return tuple(g_new), acc_b, acc_c, dd + gy * x

    g0 = g_scr[c]
    zeros = jnp.zeros((n, ts), jnp.float32)
    g, acc_b, acc_c, dd = _loop(
        ts, adjoint, (tuple(g0[k] for k in range(n)), zeros, zeros,
                      jnp.zeros((ROWS, LANE), jnp.float32)), reverse=True)
    g_scr[c] = jnp.stack(g)
    db_ref[...] += acc_b
    dc_ref[...] += acc_c
    dd_ref[rows, :] += dd


def _params(interpret: bool):
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret)


def _smem(ts: int, n: int, index_map):
    """B or C of a time block, read as scalars."""
    return pl.BlockSpec((None, ts, n), index_map, memory_space=pltpu.SMEM)


@functools.partial(jax.jit, static_argnames=("ts", "save", "interpret"))
def mamba_scan_fwd(x, dt, Bm, Cm, A, D, *, ts: int, save: bool,
                   interpret: bool = False):
    """Kernel layout in and out: ``x, dt [B, S/8, 8·G, 128]`` (the
    tile-order view of ``[B, S, di]``, G = di/128 a multiple of 8, S a
    multiple of ``ts``, ``ts`` of 8); ``Bm, Cm [B, S, N]``; ``A [N, G,
    128]``; ``D [G, 128]`` → ``y`` as ``x``, ``h_last [B, N, G, 128]`` and,
    with ``save``, the state at the start of every time block ``[B, S/ts,
    N, G, 128]``."""
    Bsz, S, G = x.shape[0], x.shape[1] * 8, x.shape[2] // 8
    n = Bm.shape[-1]
    nt, nc = S // ts, G // ROWS
    tile = pl.BlockSpec((None, ts // 8, 8 * ROWS, LANE),
                        lambda b, t, c: (b, t, c, 0))
    scalars = _smem(ts, n, lambda b, t, c: (b, t, 0))
    out_shape = [jax.ShapeDtypeStruct(x.shape, jnp.float32),
                 jax.ShapeDtypeStruct((Bsz, n, G, LANE), jnp.float32)]
    out_specs = [tile, pl.BlockSpec((None, n, G, LANE),
                                    lambda b, t, c: (b, 0, 0, 0))]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((Bsz, nt, n, G, LANE),
                                              jnp.float32))
        out_specs.append(pl.BlockSpec((None, None, n, ROWS, LANE),
                                      lambda b, t, c: (b, t, 0, c, 0)))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, ts=ts, n=n, save=save),
        grid=(Bsz, nt, nc),
        in_specs=[tile, tile, scalars, scalars,
                  pl.BlockSpec((n, ROWS, LANE), lambda b, t, c: (0, c, 0)),
                  pl.BlockSpec((ROWS, LANE), lambda b, t, c: (c, 0))],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((nc, n, ROWS, LANE), jnp.float32)],
        **_params(interpret),
    )(x, dt, Bm, Cm, A, D)


@functools.partial(jax.jit, static_argnames=("ts", "interpret"))
def mamba_scan_bwd(x, dt, Bm, Cm, A, D, gy, g_last, h_starts, *, ts: int,
                   interpret: bool = False):
    """The adjoint in kernel layout: the forward's inputs, ``gy`` as
    ``x``, ``g_last [B, N, G, 128]`` and the saved block starts → ``dx,
    ddt`` as ``x``, ``dB, dC [B, S/ts, N, ts]`` (summed over channels),
    ``dA [B, N, G, 128]``, ``dD [B, G, 128]`` (summed over time, not over
    batch)."""
    Bsz, S, G = x.shape[0], x.shape[1] * 8, x.shape[2] // 8
    n = Bm.shape[-1]
    nt, nc = S // ts, G // ROWS
    tile = pl.BlockSpec((None, ts // 8, 8 * ROWS, LANE),
                        lambda b, t, c: (b, nt - 1 - t, c, 0))
    scalars = _smem(ts, n, lambda b, t, c: (b, nt - 1 - t, 0))
    per_b = pl.BlockSpec((None, n, G, LANE), lambda b, t, c: (b, 0, 0, 0))
    sums = pl.BlockSpec((None, None, n, ts),
                        lambda b, t, c: (b, nt - 1 - t, 0, 0))
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_bwd_kernel, ts=ts, n=n),
        grid=(Bsz, nt, nc),
        in_specs=[tile, tile, scalars, scalars,
                  pl.BlockSpec((n, ROWS, LANE), lambda b, t, c: (0, c, 0)),
                  pl.BlockSpec((ROWS, LANE), lambda b, t, c: (c, 0)),
                  tile, per_b,
                  pl.BlockSpec((None, None, n, ROWS, LANE),
                               lambda b, t, c: (b, nt - 1 - t, 0, c, 0))],
        out_specs=[tile, tile, sums, sums, per_b,
                   pl.BlockSpec((None, G, LANE), lambda b, t, c: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, f32),
                   jax.ShapeDtypeStruct(x.shape, f32),
                   jax.ShapeDtypeStruct((Bsz, nt, n, ts), f32),
                   jax.ShapeDtypeStruct((Bsz, nt, n, ts), f32),
                   jax.ShapeDtypeStruct((Bsz, n, G, LANE), f32),
                   jax.ShapeDtypeStruct((Bsz, G, LANE), f32)],
        scratch_shapes=[pltpu.VMEM((nc, n, ROWS, LANE), f32),
                        pltpu.VMEM((ts + 1, n, ROWS, LANE), f32)],
        **_params(interpret),
    )(x, dt, Bm, Cm, A, D, gy, g_last, h_starts)


# --- the user layout ------------------------------------------------------


def _pad_to(v, axis, m):
    extra = -v.shape[axis] % m
    if not extra:
        return v
    pad = [(0, 0)] * v.ndim
    pad[axis] = (0, extra)
    return jnp.pad(v, pad)


def _tiles(v, ts):
    """``[B, S, di]`` → its tile-order view ``[B, Sp/8, 8·G, 128]``,
    zero-padded: row 8·g + s of slab k is time 8·k + s, channels 128·g ...
    128·g + 127, which is how XLA stores ``[B, S, di]`` in (8, 128) tiles,
    so the view is a bitcast."""
    v = _pad_to(_pad_to(v, 1, ts), 2, CB)
    Bsz, S, di = v.shape
    v = v.reshape(Bsz, S // 8, 8, di // LANE, LANE).transpose(0, 1, 3, 2, 4)
    return v.reshape(Bsz, S // 8, -1, LANE)


def _untile(v, S, di):
    Bsz, S8 = v.shape[:2]
    v = v.reshape(Bsz, S8, -1, 8, LANE).transpose(0, 1, 3, 2, 4)
    return v.reshape(Bsz, S8 * 8, -1)[:, :S, :di]


def _state_tiles(h):
    """``[B, di, N]`` → ``[B, N, G, 128]``."""
    h = _pad_to(h, 1, CB).transpose(0, 2, 1)
    return h.reshape(h.shape[0], h.shape[1], -1, LANE)


def _time_block(S: int, ts: int) -> int:
    return min(ts, -(-S // 8) * 8)


def _forward(xc, dt, Bm, Cm, A, D, ts, interpret, save):
    """The forward kernel in the user layout: inputs already float32."""
    S, di = xc.shape[1], xc.shape[2]
    ts = _time_block(S, ts)
    A = _pad_to(A, 0, CB).T
    args = (_tiles(xc, ts), _tiles(dt, ts), _pad_to(Bm, 1, ts),
            _pad_to(Cm, 1, ts), A.reshape(A.shape[0], -1, LANE),
            _pad_to(D, 0, CB).reshape(-1, LANE))
    y, h_last, *starts = mamba_scan_fwd(*args, ts=ts, save=save,
                                        interpret=interpret)
    h_last = h_last.reshape(*h_last.shape[:2], -1)[:, :, :di]
    return (_untile(y, S, di), h_last.transpose(0, 2, 1)), (args, *starts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(xc, dt, Bm, Cm, A, D, ts, interpret):
    return _forward(xc, dt, Bm, Cm, A, D, ts, interpret, save=False)[0]


def _scan_fwd(xc, dt, Bm, Cm, A, D, ts, interpret):
    return _forward(xc, dt, Bm, Cm, A, D, ts, interpret, save=True)


def _scan_bwd(ts, interpret, res, cts):
    args, starts = res
    gy, g_last = cts
    S, di = gy.shape[1], gy.shape[2]
    ts = _time_block(S, ts)
    dx, ddt, dB, dC, dA, dD = mamba_scan_bwd(
        *args, _tiles(gy, ts), _state_tiles(g_last),
        starts, ts=ts, interpret=interpret)
    Bsz, n = dB.shape[0], dB.shape[2]

    def per_step(v):                    # [B, S/ts, N, ts] → [B, S, N]
        return v.transpose(0, 1, 3, 2).reshape(Bsz, -1, n)[:, :S]

    dA = jnp.sum(dA, axis=0).reshape(n, -1)[:, :di].T
    dD = jnp.sum(dD, axis=0).reshape(-1)[:di]
    return (_untile(dx, S, di), _untile(ddt, S, di), per_step(dB),
            per_step(dC), dA, dD)


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(xc: jax.Array, dt: jax.Array, Bm: jax.Array,
                   Cm: jax.Array, A: jax.Array, D: jax.Array, *,
                   ts: int = DEFAULT_TS, interpret: bool = False):
    """``xc, dt [B, S, di]`` (dt after softplus); ``Bm, Cm [B, S, N]``;
    ``A [di, N]`` (negative); ``D [di]`` → ``(y [B, S, di], h_last
    [B, di, N])``, float32, differentiable in every input.  Inputs are
    taken as float32; ``ts`` is the time block (the saved-state stride)."""
    f32 = jnp.float32
    return _scan(xc.astype(f32), dt.astype(f32), Bm.astype(f32),
                 Cm.astype(f32), A.astype(f32), D.astype(f32), ts, interpret)

"""Mega-scale FL train steps for the assigned architectures.

Two modes (DESIGN.md §Arch-applicability):

* **replica** (paper-faithful): per-virtual-client divergent params x_k and
  anchors y_k, stacked on a leading K axis that shards over the mesh's
  data-parallel axes.  ``vmap`` over the client axis gives per-client-weights
  forward/backward; the masked pseudo-gradient aggregation (eq. 3) is an
  einsum over K.  Fits archs ≤ ~34B total params on the 256-chip pod.

* **masked-dp** (scalable adaptation for jamba-398B / llama4-400B): a single
  FSDP-sharded global model; each round the Bernoulli participation mask m_k
  gates which data groups contribute, importance-weighted m_k/p_k so the
  aggregated gradient is unbiased.  The paper's probability/bandwidth
  optimization applies unchanged; continuous local divergence is foregone.
"""
from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..data.device import DeviceDataStore, sample_batch
from ..models import transformer as T


class DistFLState(NamedTuple):
    global_params: Any
    client_params: Any   # [K, ...] stacked (replica mode) or None
    anchor_params: Any   # [K, ...] stacked (replica mode) or None


def mode_for(cfg: ArchConfig, hbm_budget_bytes: float = 3.2e12) -> str:
    """replica if 2·K·P fits comfortably in pod HBM, else masked-dp."""
    n = param_count(cfg)
    bytes_needed = 2 * 16 * n * 2  # 2 copies × K=16 × bf16
    return "replica" if bytes_needed < hbm_budget_bytes else "masked_dp"


def param_count(cfg: ArchConfig) -> int:
    """Analytic parameter count (matches init_params leaf sum)."""
    d, ff, V = cfg.d_model, cfg.d_ff, cfg.vocab
    hd, H, KV = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    total = V * d + d  # embed + final norm
    if not cfg.tie_embeddings:
        total += d * V
    import math
    di = cfg.ssm_expand * d
    dtr = max(1, math.ceil(d / 16))
    N, k = cfg.ssm_state, cfg.ssm_conv
    for li in range(cfg.n_layers):
        mixer = cfg.mixer_pattern[li % len(cfg.mixer_pattern)]
        total += d  # ln1
        if mixer == "attn":
            total += d * H * hd + 2 * d * KV * hd + H * hd * d
            if cfg.qk_norm:
                total += 2 * hd
        elif mixer == "mamba":
            total += (d * 2 * di + k * di + di + di * (dtr + 2 * N)
                      + dtr * di + di + di * N + di + di * d)
            if cfg.ssm_inner_norm:
                total += dtr + 2 * N
        elif mixer == "mlstm":
            total += 5 * d * d + 2 * d * H  # q,k,v,o-gate,out + i/f gates
        elif mixer == "slstm":
            total += 4 * d * d + 4 * (d // H) * d + 4 * d + d * d
        kind = cfg.ffn_kind(li)
        if kind != "none":
            total += d  # ln2
        if kind == "dense":
            total += 3 * d * ff
        elif kind == "moe":
            m = cfg.moe
            total += d * m.num_experts + 3 * m.num_experts * d * m.d_ff_expert
    return int(total)


def init_dist_state(key, cfg: ArchConfig, num_clients: int,
                    mode: str = "replica") -> DistFLState:
    params = T.init_params(key, cfg)
    if mode == "masked_dp":
        return DistFLState(global_params=params, client_params=None,
                           anchor_params=None)
    stacked = jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p[None], (num_clients,) + p.shape),
        params)
    return DistFLState(global_params=params, client_params=stacked,
                       anchor_params=stacked)


def _client_loss(cfg: ArchConfig):
    def f(params, batch):
        return T.loss(params, cfg, batch)
    return f


@partial(jax.jit, static_argnames=("cfg", "local_iters", "micro_batches"))
def fl_train_step(state: DistFLState, cfg: ArchConfig, batch: Any,
                  mask: jax.Array, lr: float, local_iters: int = 1,
                  micro_batches: int = 1) -> tuple[DistFLState, dict]:
    """One paper round in replica mode.

    batch: pytree with leading [K, B, ...]; mask: [K] 0/1 Bernoulli draws of
    the server-optimized probabilities.  ``micro_batches`` splits each
    client's batch into sequential gradient-accumulation chunks (§Perf:
    divides activation memory by the chunk count at identical math — the
    lever that brings 34B replica-mode training under the 16 GB/chip HBM).
    """
    K = mask.shape[0]
    loss_fn = _client_loss(cfg)

    def grad_accum(params, b):
        if micro_batches == 1:
            return jax.value_and_grad(loss_fn)(params, b)
        mb = jax.tree_util.tree_map(
            lambda x: x.reshape((micro_batches, x.shape[0] // micro_batches)
                                + x.shape[1:]), b)

        def one_micro(carry, bm):
            l_acc, g_acc = carry
            l, g = jax.value_and_grad(loss_fn)(params, bm)
            g_acc = jax.tree_util.tree_map(
                lambda a, gg: a + gg.astype(a.dtype), g_acc, g)
            return (l_acc + l, g_acc), None

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (l_sum, g_sum), _ = jax.lax.scan(one_micro, (jnp.zeros(()), zeros),
                                         mb)
        inv = 1.0 / micro_batches
        return l_sum * inv, jax.tree_util.tree_map(lambda g: g * inv, g_sum)

    def local(params, b):
        def one(params, _):
            l, g = grad_accum(params, b)
            params = jax.tree_util.tree_map(
                lambda p, gg: p - lr * gg.astype(p.dtype), params, g)
            return params, l
        params, ls = jax.lax.scan(one, params, None, length=local_iters)
        return params, ls.mean()

    client, losses = jax.vmap(local)(state.client_params, batch)

    # eq. (2)/(3): masked pseudo-gradient aggregation.  Deltas stay in the
    # param dtype (bf16 transport of pseudo-gradients — the wireless uplink
    # analogue); the K-reduction accumulates in fp32 (§Perf iteration 6:
    # halves the aggregation temps vs fp32 delta materialization).
    def agg(g, c, a):
        m = mask.astype(c.dtype).reshape((-1,) + (1,) * (g.ndim))
        delta = (c - a) * m
        s = jnp.sum(delta.astype(jnp.float32), axis=0)
        return (g.astype(jnp.float32) + s / K).astype(g.dtype)

    new_global = jax.tree_util.tree_map(agg, state.global_params, client,
                                        state.anchor_params)

    # broadcast to participants only (protocol step 5)
    def sel(stacked, g):
        m = mask.reshape((-1,) + (1,) * g.ndim).astype(bool)
        return jnp.where(m, g[None].astype(stacked.dtype), stacked)

    client = jax.tree_util.tree_map(sel, client, new_global)
    anchor = jax.tree_util.tree_map(sel, state.anchor_params, new_global)
    metrics = {"loss": losses.mean(), "participants": mask.sum()}
    return DistFLState(new_global, client, anchor), metrics


@partial(jax.jit, static_argnames=("cfg", "batch_size", "local_iters",
                                   "micro_batches"))
def fl_train_step_from_store(state: DistFLState, cfg: ArchConfig,
                             store: DeviceDataStore, data_key: jax.Array,
                             t: jax.Array, mask: jax.Array, lr: float,
                             batch_size: int, local_iters: int = 1,
                             micro_batches: int = 1) -> tuple[DistFLState,
                                                              dict]:
    """Replica-mode round fed from a :class:`DeviceDataStore`.

    The round's ``[K, B, S]`` token batch is gathered on device from the
    ``fold_in(data_key, t)`` stream and fused into the same jitted program
    as the train step — no per-round host stacking, and peak data memory is
    the store itself (independent of the horizon).  This is the mega-arch
    analogue of the scan engine's device data path.
    """
    toks, _ = sample_batch(store, data_key, t, batch_size)
    return fl_train_step(state, cfg, {"tokens": toks}, mask, lr,
                         local_iters=local_iters,
                         micro_batches=micro_batches)


@partial(jax.jit, static_argnames=("cfg",))
def fl_train_step_masked_dp(state: DistFLState, cfg: ArchConfig, batch: Any,
                            mask: jax.Array, probs: jax.Array,
                            lr: float) -> tuple[DistFLState, dict]:
    """One round in masked-DP mode: unbiased inverse-probability weighting.

    E[ (1/K) Σ (m_k/p_k) g_k ] = (1/K) Σ g_k — the synchronous-FL gradient.

    The aggregate is computed as the gradient of the *weighted scalar loss*
    L = (1/K) Σ_k (m_k/p_k)·loss_k — a single backward pass whose gradient
    IS the masked aggregate, so per-client gradients (K × P floats) are
    never materialized.
    """
    K = mask.shape[0]
    loss_fn = _client_loss(cfg)
    wgt = (mask / jnp.maximum(probs, 1e-6)).astype(jnp.float32)

    def weighted_loss(params):
        losses = jax.vmap(lambda b: loss_fn(params, b))(batch)
        return jnp.sum(losses * wgt) / K, losses

    (_, losses), grad = jax.value_and_grad(weighted_loss,
                                           has_aux=True)(state.global_params)

    new_global = jax.tree_util.tree_map(
        lambda g, gg: (g.astype(jnp.float32)
                       - lr * gg.astype(jnp.float32)).astype(g.dtype),
        state.global_params, grad)
    metrics = {"loss": losses.mean(), "participants": mask.sum()}
    return DistFLState(new_global, None, None), metrics

"""Generic decoder stack: embed → lax.scan over super-blocks → norm → logits.

A *super-block* is ``cfg.layer_plan()`` — a short list of (mixer, ffn) layer
specs; its params are stacked on a leading ``n_repeats`` axis and the stack is
a single ``lax.scan``, so compiled HLO size is independent of depth (72-layer
Jamba compiles the same graph as an 8-layer one).

Entry points (all pure):
  init_params(key, cfg)
  forward(params, cfg, tokens|embeds)              → logits          (train)
  loss(params, cfg, batch)                         → scalar (CE + aux)
  accuracy(params, cfg, batch)                     → share of top-1 hits
  prefill(params, cfg, tokens|embeds, capacity)    → (logits, caches)
  decode_step(params, cfg, token, caches)          → (logits, caches)
  init_caches(cfg, batch, capacity, dtype)
"""
from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from . import attention, mamba, moe, xlstm
from .costmode import cost_mode
from .layers import dense_init, init_swiglu, mm, rms_norm, swiglu

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
           "float16": jnp.float16}


def _dtype(cfg: ArchConfig):
    return _DTYPES[cfg.dtype]


def _act_dtype(cfg: ArchConfig):
    return _DTYPES[cfg.compute_dtype or cfg.dtype]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ArchConfig, mixer: str, ffn: str, dtype):
    kmix, kffn = jax.random.split(key)
    p: dict[str, Any] = {"ln1": jnp.ones((cfg.d_model,), dtype)}
    if mixer == "attn":
        p["mixer"] = attention.init_attn(kmix, cfg, dtype)
    elif mixer == "mamba":
        p["mixer"] = mamba.init_mamba(kmix, cfg, dtype)
    elif mixer == "mlstm":
        p["mixer"] = xlstm.init_mlstm(kmix, cfg, dtype)
    elif mixer == "slstm":
        p["mixer"] = xlstm.init_slstm(kmix, cfg, dtype)
    else:
        raise ValueError(mixer)
    if ffn != "none":
        p["ln2"] = jnp.ones((cfg.d_model,), dtype)
    if ffn == "dense":
        p["ffn"] = init_swiglu(kffn, cfg.d_model, cfg.d_ff, dtype)
    elif ffn == "moe":
        p["ffn"] = moe.init_moe(kffn, cfg, dtype)
    return p


def init_params(key, cfg: ArchConfig):
    dtype = _dtype(cfg)
    plan = cfg.layer_plan()
    keys = jax.random.split(key, cfg.n_repeats * len(plan) + 3)

    # stacked super-block params: leaf shape [n_repeats, ...]
    blocks = []
    ki = 0
    for r in range(cfg.n_repeats):
        sb = []
        for (mixer, ffn) in plan:
            sb.append(_init_layer(keys[ki], cfg, mixer, ffn, dtype))
            ki += 1
        blocks.append(sb)
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)

    params = {
        "embed": (jax.random.normal(keys[-1], (cfg.vocab, cfg.d_model),
                                    jnp.float32) * 0.02).astype(dtype),
        "blocks": stacked,
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(keys[-2], cfg.d_model, cfg.vocab, dtype)
    return params


# ---------------------------------------------------------------------------
# super-block application
# ---------------------------------------------------------------------------

def _apply_layer(lp, cfg: ArchConfig, mixer: str, ffn: str, x, positions,
                 mode: str, cache, capacity: int):
    """One layer.  Returns (x, new_cache, aux)."""
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    new_cache = cache
    if mixer == "attn":
        if mode == "train":
            y = attention.attn_forward(lp["mixer"], cfg, h, positions)
        elif mode == "prefill":
            y, new_cache = attention.attn_prefill(lp["mixer"], cfg, h,
                                                  positions, capacity)
        else:
            y, new_cache = attention.attn_decode(lp["mixer"], cfg, h, cache)
    elif mixer == "mamba":
        if mode == "train":
            y = mamba.mamba_forward(lp["mixer"], cfg, h)
        elif mode == "prefill":
            y, new_cache = mamba.mamba_forward(lp["mixer"], cfg, h,
                                               return_cache=True)
        else:
            y, new_cache = mamba.mamba_decode(lp["mixer"], cfg, h, cache)
    elif mixer == "mlstm":
        if mode == "train":
            y = xlstm.mlstm_forward(lp["mixer"], cfg, h)
        elif mode == "prefill":
            y, new_cache = xlstm.mlstm_forward(lp["mixer"], cfg, h,
                                               return_cache=True)
        else:
            y, new_cache = xlstm.mlstm_decode(lp["mixer"], cfg, h, cache)
    elif mixer == "slstm":
        if mode == "train":
            y = xlstm.slstm_forward(lp["mixer"], cfg, h)
        elif mode == "prefill":
            y, new_cache = xlstm.slstm_forward(lp["mixer"], cfg, h,
                                               return_cache=True)
        else:
            y, new_cache = xlstm.slstm_decode(lp["mixer"], cfg, h, cache)
    x = x + y
    aux = jnp.zeros((), jnp.float32)
    if ffn == "dense":
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        x = x + swiglu(h, lp["ffn"]["w1"], lp["ffn"]["w3"], lp["ffn"]["w2"])
    elif ffn == "moe":
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        y, aux = moe.moe_forward(lp["ffn"], cfg, h)
        x = x + y
    return x, new_cache, aux


def _layer_cache(cfg: ArchConfig, mixer: str, batch: int, capacity: int,
                 dtype):
    if mixer == "attn":
        cap = min(capacity, cfg.sliding_window) if cfg.sliding_window \
            else capacity
        return attention.init_cache(cfg, batch, cap, dtype)
    if mixer == "mamba":
        return mamba.init_mamba_cache(cfg, batch, dtype)
    if mixer == "mlstm":
        return xlstm.init_mlstm_cache(cfg, batch, dtype)
    if mixer == "slstm":
        return xlstm.init_slstm_cache(cfg, batch, dtype)
    raise ValueError(mixer)


def init_caches(cfg: ArchConfig, batch: int, capacity: int, dtype=None):
    """Stacked caches: pytree with leading [n_repeats] axis per plan position."""
    dtype = dtype or _dtype(cfg)
    plan = cfg.layer_plan()
    per_pos = [_layer_cache(cfg, m, batch, capacity, dtype) for m, _ in plan]
    return jax.tree_util.tree_map(
        lambda c: jnp.broadcast_to(c[None], (cfg.n_repeats,) + c.shape),
        tuple(per_pos))


def _scan_blocks(params, cfg: ArchConfig, x, positions, mode: str,
                 caches, capacity: int):
    """Scan over the stacked super-blocks.

    ``params["blocks"]`` is a list (per super-block position) of layer-param
    dicts whose leaves carry a leading [n_repeats] axis; ``caches`` (optional)
    is a tuple with the same leading axis.  Returns (x, aux, new_caches).
    """
    plan = cfg.layer_plan()
    aux0 = jnp.zeros((), jnp.float32)

    if cost_mode():
        # unrolled python loop — exact HLO op counts for the cost probes
        aux = aux0
        new_caches = []
        for r in range(cfg.n_repeats):
            bp = jax.tree_util.tree_map(lambda l: l[r], params["blocks"])
            ncs = []
            for i, (mixer, ffn) in enumerate(plan):
                c_i = None if caches is None else \
                    jax.tree_util.tree_map(lambda l: l[r], caches[i])
                x, nc, a = _apply_layer(bp[i], cfg, mixer, ffn, x, positions,
                                        mode, c_i, capacity)
                ncs.append(nc)
                aux = aux + a
            new_caches.append(tuple(ncs))
        if caches is None:
            return x, aux, None
        stacked = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls),
                                         *new_caches)
        return x, aux, stacked

    if caches is None:
        from .pshard import shard_dim

        def layer(lp, x, mixer, ffn):
            x, _, a = _apply_layer(lp, cfg, mixer, ffn, x, positions, mode,
                                   None, capacity)
            return x, a

        def body(carry, bp):
            x, aux = carry
            # sequence parallelism at super-block boundaries (§Perf iter 7):
            # the scan saves its carry for backward — sharding the S dim
            # over "model" cuts the 48×[B,S,d] residual saves 16×; GSPMD
            # all-gathers/reduce-scatters around each block as needed.
            x = shard_dim(x, -2, "model")
            for i, (mixer, ffn) in enumerate(plan):
                f = partial(layer, mixer=mixer, ffn=ffn)
                if mode == "train":
                    # activation checkpointing per layer: backward keeps
                    # each layer's input and rebuilds one layer's
                    # intermediates at a time
                    f = jax.checkpoint(f)
                x, a = f(bp[i], x)
                aux = aux + a
            return (x, aux), None

        (x, aux), _ = jax.lax.scan(body, (x, aux0), params["blocks"])
        return x, aux, None

    def body(carry, scanned):
        x, aux = carry
        bp, cache = scanned
        new_caches = []
        for i, (mixer, ffn) in enumerate(plan):
            x, nc, a = _apply_layer(bp[i], cfg, mixer, ffn, x, positions,
                                    mode, cache[i], capacity)
            new_caches.append(nc)
            aux = aux + a
        return (x, aux), tuple(new_caches)

    (x, aux), new_caches = jax.lax.scan(body, (x, aux0),
                                        (params["blocks"], caches))
    return x, aux, new_caches


# ---------------------------------------------------------------------------
# model-level API
# ---------------------------------------------------------------------------

def _embed(params, cfg: ArchConfig, tokens=None, embeds=None):
    if embeds is not None:
        return embeds.astype(_act_dtype(cfg))
    x = jnp.take(params["embed"], tokens, axis=0)
    return x.astype(_act_dtype(cfg)) if cfg.compute_dtype else x


def _logits(params, cfg: ArchConfig, x):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        return (x @ params["embed"].T).astype(jnp.float32)
    return (x @ params["unembed"]).astype(jnp.float32)


def forward_hidden(params, cfg: ArchConfig, tokens=None, embeds=None):
    """Full-sequence causal forward → (hidden [B,S,d], aux)."""
    x = _embed(params, cfg, tokens, embeds)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x, aux, _ = _scan_blocks(params, cfg, x, positions, "train", None, 0)
    return x, aux


def forward(params, cfg: ArchConfig, tokens=None, embeds=None):
    """Full-sequence causal forward → (logits [B,S,V] fp32, aux)."""
    x, aux = forward_hidden(params, cfg, tokens, embeds)
    return _logits(params, cfg, x), aux


def _batch_hidden(params, cfg: ArchConfig, batch):
    """Final-normed hidden states and their targets of ``batch``: its
    ``labels`` where given, else the next token of ``tokens``."""
    if "embeds" in batch:
        x, aux = forward_hidden(params, cfg, embeds=batch["embeds"])
    else:
        x, aux = forward_hidden(params, cfg, tokens=batch["tokens"])
    if "labels" in batch:
        targets = batch["labels"]
    else:
        # the next-token shift happens on the d-wide hidden states, before
        # the unembedding
        x, targets = x[:, :-1], batch["tokens"][:, 1:]
    return rms_norm(x, params["final_norm"], cfg.norm_eps), targets, aux


def _head_sum(params, cfg: ArchConfig, x, targets, accuracy: bool,
              chunk: int = 128):
    """Sum over positions of the cross-entropy (or, with ``accuracy``, of
    the hits of the largest logit) of ``targets [B,S]`` under hidden states
    ``x [B,S,d]``.

    The unembedding runs ``chunk`` positions at a time inside a
    checkpointed scan, so no [B,S,V] logit or gradient array exists when S
    is a multiple of ``chunk`` (else the S positions are one chunk).  The
    vocabulary dim stays sharded (§Perf iteration 5): the target's logit is
    picked by a masked sum over it, so only [B,chunk]-sized reductions
    cross the mesh, and the pick is exact at any matmul precision.
    """
    from .pshard import shard_last

    B, S, d = x.shape
    c = chunk if S % chunk == 0 else S
    xs = x.reshape(B, S // c, c, d).swapaxes(0, 1)          # [n,B,c,d]
    ts = targets.reshape(B, S // c, c).swapaxes(0, 1)       # [n,B,c]

    @jax.checkpoint
    def one(total, xt):
        xc, tc = xt
        if cfg.tie_embeddings:
            logits = jnp.einsum("bcd,vd->bcv", xc, params["embed"])
        else:
            logits = mm(xc, params["unembed"])
        logits = shard_last(logits.astype(jnp.float32))     # [B,c,V]
        if accuracy:
            v = jnp.sum(jnp.argmax(logits, -1) == tc)
        else:
            hit = jnp.arange(logits.shape[-1]) == tc[..., None]
            tgt = jnp.sum(jnp.where(hit, logits, 0.0), -1)
            v = jnp.sum(jax.scipy.special.logsumexp(logits, -1) - tgt)
        return total + v.astype(jnp.float32), None

    total, _ = jax.lax.scan(one, jnp.zeros((), jnp.float32), (xs, ts))
    return total


def loss(params, cfg: ArchConfig, batch):
    """Mean cross-entropy + MoE aux loss.

    batch: {"tokens": [B,S]} (next-token targets), optionally with
    {"labels": [B,S]}, or {"embeds": [B,S,d], "labels": [B,S]}.
    """
    x, targets, aux = _batch_hidden(params, cfg, batch)
    ce = _head_sum(params, cfg, x, targets, accuracy=False) / targets.size
    if cfg.moe is None:
        # no aux term: the forward is then the one ``accuracy`` runs on the
        # same batch, and XLA computes it once for both
        return ce
    return ce + cfg.moe.aux_loss_weight * aux


def accuracy(params, cfg: ArchConfig, batch):
    """Share of ``batch``'s positions (as :func:`loss` reads them) whose
    largest logit is the target."""
    x, targets, _ = _batch_hidden(params, cfg, batch)
    return _head_sum(params, cfg, x, targets, accuracy=True) / targets.size


def prefill(params, cfg: ArchConfig, tokens=None, embeds=None,
            capacity: int | None = None):
    """Process a prompt, returning (last-position logits, caches)."""
    x = _embed(params, cfg, tokens, embeds)
    B, S, _ = x.shape
    capacity = capacity or S
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    caches = init_caches(cfg, B, capacity)
    x, _, caches = _scan_blocks(params, cfg, x, positions, "prefill", caches,
                                capacity)
    return _logits(params, cfg, x[:, -1:]), caches


def decode_step(params, cfg: ArchConfig, token, caches):
    """One-token decode.  token: [B, 1] ids → (logits [B,1,V], caches)."""
    x = _embed(params, cfg, tokens=token)
    x, _, caches = _scan_blocks(params, cfg, x, None, "decode", caches, 0)
    return _logits(params, cfg, x), caches

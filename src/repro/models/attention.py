"""GQA attention: full-causal / sliding-window for train & prefill, and
single-token decode against a (ring-buffer) KV cache.

Layouts:  q [B,S,H,hd]; k,v [B,S,KV,hd]; cache k/v [B,C,KV,hd] with capacity
C = seq_len (full) or window (sliding).  fp32 softmax throughout.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .costmode import cost_mode
from .layers import apply_rope, dense_init, mm, rms_norm

NEG_INF = -1e30


class KVCache(NamedTuple):
    k: jax.Array      # [B, C, KV, hd]
    v: jax.Array      # [B, C, KV, hd]
    pos: jax.Array    # scalar int32 — number of tokens already cached


def init_attn(key, cfg: ArchConfig, dtype):
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    p = {"wq": dense_init(ks[0], d, H * hd, dtype),
         "wk": dense_init(ks[1], d, KV * hd, dtype),
         "wv": dense_init(ks[2], d, KV * hd, dtype),
         "wo": dense_init(ks[3], H * hd, d, dtype)}
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def init_cache(cfg: ArchConfig, batch: int, capacity: int, dtype) -> KVCache:
    KV, hd = cfg.n_kv_heads, cfg.hd
    return KVCache(k=jnp.zeros((batch, capacity, KV, hd), dtype),
                   v=jnp.zeros((batch, capacity, KV, hd), dtype),
                   pos=jnp.zeros((), jnp.int32))


def _qkv(p, cfg: ArchConfig, x, positions):
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = mm(x, p["wq"]).reshape(B, S, H, hd)
    k = mm(x, p["wk"]).reshape(B, S, KV, hd)
    v = mm(x, p["wv"]).reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.rope:               # NoPE models (Jamba) carry no position here
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _gqa_scores(q, k, cfg: ArchConfig):
    """q [B,S,H,hd], k [B,T,KV,hd] → scores [B,KV,G,S,T] (G = H/KV)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd)
    return jnp.einsum("bskgh,btkh->bkgst", qg.astype(jnp.float32),
                      k.astype(jnp.float32)) / jnp.sqrt(hd).astype(jnp.float32)


def _attend(scores, v, mask):
    """scores [B,KV,G,S,T], v [B,T,KV,hd] → out [B,S,H,hd]."""
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v.astype(jnp.float32))
    B, S, KV, G, hd = out.shape
    return out.reshape(B, S, KV * G, hd)


def _attend_chunked(q, k, v, cfg: ArchConfig, q_chunk: int = 512,
                    kv_chunk: int = 1024):
    """Exact streaming-softmax (flash-style) causal attention in pure jnp.

    Never materializes S×S: memory is O(q_chunk·kv_chunk) per step.  This is
    the lowering/oracle path; the Pallas ``flash_attention`` kernel is the
    TPU production path with the same math.
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qc = min(q_chunk, S)
    kc = min(kv_chunk, S)
    nq, nk = S // qc if S % qc == 0 else -1, S // kc if S % kc == 0 else -1
    if nq < 0 or nk < 0 or cost_mode():  # ragged/test shapes or cost probe
        scores = _gqa_scores(q, k, cfg)
        i = jnp.arange(S)[:, None]
        j = jnp.arange(S)[None, :]
        mask = j <= i
        if cfg.sliding_window is not None:
            mask &= j > i - cfg.sliding_window
        return _attend(scores, v, mask[None, None, None])

    qg = q.reshape(B, nq, qc, KV, G, hd).astype(jnp.float32)
    kg = k.reshape(B, nk, kc, KV, hd).transpose(1, 0, 2, 3, 4).astype(jnp.float32)
    vg = v.reshape(B, nk, kc, KV, hd).transpose(1, 0, 2, 3, 4).astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)

    def q_block(qi, q_blk):
        # q_blk: [B, qc, KV, G, hd]
        m0 = jnp.full((B, KV, G, qc), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, KV, G, qc), jnp.float32)
        a0 = jnp.zeros((B, KV, G, qc, hd), jnp.float32)

        def kv_block(carry, scanned):
            m, l, acc = carry
            kj, (k_blk, v_blk) = scanned
            s = jnp.einsum("bqkgh,btkh->bkgqt", q_blk, k_blk) * scale
            qpos = qi * qc + jnp.arange(qc)[:, None]
            kpos = kj * kc + jnp.arange(kc)[None, :]
            mask = kpos <= qpos
            if cfg.sliding_window is not None:
                mask &= kpos > qpos - cfg.sliding_window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p_ = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p_.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum("bkgqt,btkh->bkgqh",
                                                     p_, v_blk)
            return (m_new, l, acc), None

        (m, l, acc), _ = jax.lax.scan(
            kv_block, (m0, l0, a0), (jnp.arange(nk), (kg, vg)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]     # [B,KV,G,qc,hd]
        return out.transpose(0, 3, 1, 2, 4)              # [B,qc,KV,G,hd]

    # rematerialized per query block: a backward pass holds one block's
    # [B,KV,G,qc,kc] scores at a time, not every block's
    q_block = jax.checkpoint(q_block)
    out = jax.lax.map(lambda args: q_block(*args),
                      (jnp.arange(nq), qg.transpose(1, 0, 2, 3, 4, 5)))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H, hd)
    return out


def attn_forward(p, cfg: ArchConfig, x, positions):
    """Full-sequence causal (optionally sliding-window) attention."""
    B, S, _ = x.shape
    q, k, v = _qkv(p, cfg, x, positions)
    out = _attend_chunked(q, k, v, cfg)
    return mm(out.reshape(B, S, -1).astype(x.dtype), p["wo"]).astype(x.dtype)


def attn_prefill(p, cfg: ArchConfig, x, positions, capacity: int):
    """Forward + build the KV cache (last ``capacity`` positions)."""
    B, S, _ = x.shape
    q, k, v = _qkv(p, cfg, x, positions)
    out = _attend_chunked(q, k, v, cfg)
    y = mm(out.reshape(B, S, -1).astype(x.dtype), p["wo"]).astype(x.dtype)
    if capacity >= S:
        ck = jnp.pad(k, ((0, 0), (0, capacity - S), (0, 0), (0, 0)))
        cv = jnp.pad(v, ((0, 0), (0, capacity - S), (0, 0), (0, 0)))
    else:  # keep the most recent window
        ck, cv = k[:, S - capacity:], v[:, S - capacity:]
    cache = KVCache(k=ck, v=cv, pos=jnp.asarray(S, jnp.int32))
    return y, cache


def attn_decode(p, cfg: ArchConfig, x, cache: KVCache):
    """One-token decode: x [B,1,d]; attends to cache + itself."""
    B, _, _ = x.shape
    C = cache.k.shape[1]
    positions = jnp.full((B, 1), cache.pos, jnp.int32)
    q, k, v = _qkv(p, cfg, x, positions)
    # write new k/v into the ring slot pos % C
    slot = jnp.mod(cache.pos, C)
    ck = jax.lax.dynamic_update_slice(cache.k, k, (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache.v, v, (0, slot, 0, 0))
    scores = _gqa_scores(q, ck, cfg)                    # [B,KV,G,1,C]
    idx = jnp.arange(C)
    valid = idx <= jnp.minimum(cache.pos, C - 1)        # filled slots (ring ⇒ all
    out = _attend(scores, cv, valid[None, None, None, None])  # once pos ≥ C)
    y = mm(out.reshape(B, 1, -1), p["wo"]).astype(x.dtype)
    return y, KVCache(k=ck, v=cv, pos=cache.pos + 1)

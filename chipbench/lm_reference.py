"""Plain reference of federated LoRA fine-tuning of a Jamba model.

Straightforward ``jax.numpy``; it imports nothing of the program and takes
none of its outputs except, where the comparison says so, the participation
masks it must account for.  It follows the published Jamba layer
(HF ``JambaForCausalLM``):

* Mamba-1 mixer: ``in_proj`` → x, z; causal depthwise conv (kernel
  ``d_conv``, with bias) and SiLU; ``x_proj`` → Δ, B, C, each RMS-normalized;
  Δ = softplus(Δ @ ``dt_proj`` + bias); the selective recurrence run token by
  token, ``h_t = exp(Δ_t A) h_{t-1} + Δ_t B_t x_t``, ``y_t = h_t · C_t +
  D x_t``; y ⊙ SiLU(z) → ``out_proj``;
* attention: causal multi-query softmax attention with no position
  encoding (NoPE), scale 1/√head_dim;
* SwiGLU FFN on every layer (the one-expert MoE layers of the published
  config route every token to their one expert with weight 1);
* pre-norm residual layers, a final RMSNorm, tied unembedding, and the mean
  next-token cross-entropy.

LoRA: every adapted projection is ``x @ W + (α/r)(x @ A) @ B``; ``jax.grad``
gives the adapters' gradients, and the FL protocol is replayed as
``reference.py`` replays it: u < p decisions from ``fold_in(key, t)``, the
eq.-5 energy at the configuration's model size (32 bits per adapter
parameter), each client's SGD step on the round's minibatch stream, the
eq.-3 update of the global adapters, the broadcast to transmitters, and the
evaluation of the global adapters.

Clients train one after another, at ``jax.default_matmul_precision
("highest")``, with the activations, the adapters and the eq.-5 ledger in
``dtype``: float32 is the reference; bfloat16 is the control.  A layer is
one jitted function called layer by layer from Python (the same program
serves every layer of a kind) and checkpointed, so that one client's
backward pass holds one layer's activations.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

#: fold_in tag of the minibatch stream (the program's documented stream)
DATA_STREAM = 0x0DA7A


# --- the model ---------------------------------------------------------------


def rms(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g.astype(x.dtype)


def proj(x, w, ad, scale):
    y = x @ w.astype(x.dtype)
    if ad is not None:
        y = y + ((x @ ad["a"]) @ ad["b"]) * scale
    return y


def mamba(p, ad, x, s):
    """One Mamba-1 mixer over ``x [S, d]``."""
    S, dt = x.shape[0], x.dtype
    f = lambda k: p[k].astype(dt)                     # noqa: E731
    xz = proj(x, p["in_proj"], ad.get("in_proj"), s.scale)
    xin, z = xz[:, :s.d_inner], xz[:, s.d_inner:]
    xp = jnp.concatenate([jnp.zeros((s.d_conv - 1, s.d_inner), dt), xin])
    conv = sum(xp[i:i + S] * f("conv_w")[i] for i in range(s.d_conv))
    xc = jax.nn.silu(conv + f("conv_b"))
    dbc = xc @ f("x_proj")
    R, N = s.dt_rank, s.d_state
    delta = rms(dbc[:, :R], p["dt_norm"], s.eps)
    Bm = rms(dbc[:, R:R + N], p["b_norm"], s.eps)
    Cm = rms(dbc[:, R + N:], p["c_norm"], s.eps)
    delta = jax.nn.softplus(delta @ f("dt_proj") + f("dt_bias"))
    A = -jnp.exp(f("A_log"))                          # [di, N]

    def step(h, inp):
        d_t, b_t, c_t, x_t = inp
        h = jnp.exp(d_t[:, None] * A) * h + (d_t * x_t)[:, None] * b_t[None]
        return h, h @ c_t

    _, y = jax.lax.scan(step, jnp.zeros((s.d_inner, N), dt),
                        (delta, Bm, Cm, xc))
    y = (y + f("D") * xc) * jax.nn.silu(z)
    return proj(y, p["out_proj"], ad.get("out_proj"), s.scale)


def attention(p, ad, x, s):
    """Causal multi-query attention with no position encoding."""
    S, hd, H, KV = x.shape[0], s.head_dim, s.heads, s.kv_heads
    q = proj(x, p["wq"], ad.get("wq"), s.scale).reshape(S, H, hd)
    k = proj(x, p["wk"], ad.get("wk"), s.scale).reshape(S, KV, hd)
    v = proj(x, p["wv"], ad.get("wv"), s.scale).reshape(S, KV, hd)
    kv_of = jnp.arange(H) // (H // KV)
    scores = jnp.einsum("shd,thd->hst", q, k[:, kv_of]) / jnp.sqrt(
        jnp.asarray(hd, x.dtype))
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hst,thd->shd", probs, v[:, kv_of]).reshape(S, H * hd)
    return proj(out, p["wo"], ad.get("wo"), s.scale)


def ffn(p, ad, x, s):
    h = jax.nn.silu(proj(x, p["w1"], ad.get("w1"), s.scale)) \
        * proj(x, p["w3"], ad.get("w3"), s.scale)
    return proj(h, p["w2"], ad.get("w2"), s.scale)


@partial(jax.jit, static_argnames=("s", "attn"))
def layer(lp, lad, x, s, attn: bool):
    mix = attention if attn else mamba
    x = x + mix(lp["mixer"], lad.get("mixer", {}), rms(x, lp["ln1"], s.eps), s)
    return x + ffn(lp["ffn"], lad.get("ffn", {}), rms(x, lp["ln2"], s.eps), s)


@partial(jax.jit, static_argnames=("s",))
def head(embed, final_norm, x, y, s):
    """Mean cross-entropy and accuracy of ``x [S, d]`` against ``y [S]``."""
    lg = rms(x, final_norm, s.eps) @ embed.astype(x.dtype).T     # [S, V]
    ce = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(lg, y[:, None],
                                                        -1)[:, 0]
    return jnp.mean(ce), jnp.mean(jnp.argmax(lg, -1) == y)


def hidden(base, adapters, tokens, s, dtype=jnp.float32):
    """``[S, d]`` last hidden states of one sequence ``tokens [S]``."""
    x = base["embed"][tokens].astype(dtype)
    for i, (lp, lad) in enumerate(zip(base["layers"], adapters)):
        x = jax.checkpoint(partial(layer, s=s, attn=s.is_attn(i)))(lp, lad, x)
    return x


def logits(base, adapters, tokens, s, dtype=jnp.float32):
    """``[S, V]`` logits of one sequence ``tokens [S]``."""
    x = rms(hidden(base, adapters, tokens, s, dtype), base["final_norm"],
            s.eps)
    return x @ base["embed"].astype(dtype).T


def token_stats(adapters, base, x, y, s, dtype=jnp.float32):
    """Mean cross-entropy and accuracy over ``[n, S]`` next tokens."""
    stats = [jax.checkpoint(partial(head, s=s))(
        base["embed"], base["final_norm"],
        hidden(base, adapters, xs, s, dtype), ys) for xs, ys in zip(x, y)]
    return (sum(c for c, _ in stats) / len(stats),
            sum(a for _, a in stats) / len(stats))


def client_step(adapters, base, x, y, s, lr, dtype):
    """One client's SGD step on ``[B, S]`` sequences."""
    g = jax.grad(lambda a: token_stats(a, base, x, y, s, dtype)[0])(adapters)
    return jax.tree_util.tree_map(lambda p, d: p - lr * d, adapters, g)


def evaluate(adapters, base, x, y, s, dtype):
    ce, acc = token_stats(adapters, base, x, y, s, dtype)
    return (jnp.asarray(acc, jnp.float32), jnp.asarray(ce, jnp.float32))


# --- one simulation call -------------------------------------------------------


@partial(jax.jit, static_argnames=("n", "K", "L", "B"))
def round_indices(data_key, t, n: int, K: int, L: int, B: int):
    """Round ``t``'s ``[K, L, B]`` example indices, uniform over each
    client's ``n`` sequences, from ``fold_in(data_key, t)``."""
    u = jax.random.uniform(jax.random.fold_in(data_key, t), (K, L, B))
    return jnp.minimum(jnp.floor(u * jnp.float32(n)).astype(jnp.int32), n - 1)


def _stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def sim(world, cfg: dict, h_kt, call_seed: int, data_seed: int,
        masks=None, dtype=jnp.float32) -> dict:
    """One runner call of ``cfg["rounds"]`` rounds.

    ``masks`` ([T, K]) are the decisions to account for: the energy ledger,
    ``last_tx`` and the aggregation follow them.  ``None`` uses the
    reference's own decisions (the control in the program's place).
    """
    s, cell = world.shape, world.cell
    K, T = cfg["num_clients"], cfg["rounds"]
    L, B, lr = cfg["local_iters"], cfg["batch_size"], cfg["lr"]
    pol = cfg["policy"]
    if pol["kind"] != "random":
        raise NotImplementedError("the reference replays the random scheme")
    with jax.default_matmul_precision("highest"):
        p = jnp.full((T, K), pol["p_bar"], dtype)
        w = jnp.full((T, K), 1.0 / K, dtype)
        key = jax.random.PRNGKey(call_seed)
        u = np.stack([np.asarray(jax.random.uniform(
            jax.random.fold_in(key, t), (K,))) for t in range(T)])
        p = np.asarray(p, np.float32)
        m = (u < p).astype(np.float32) if masks is None \
            else np.asarray(masks, np.float32)
        h_tk = jnp.asarray(h_kt, dtype).T
        R = w * cell.bandwidth_hz * jnp.log1p(
            cell.tx_power_w * h_tk / (w * cell.bandwidth_hz
                                      * cell.noise_w_per_hz))
        e = jnp.where(m > 0, m.astype(dtype) * cell.tx_power_w
                      * cell.model_size_nats / jnp.maximum(R, 1e-30), 0)
        energy = jnp.sum(e.astype(jnp.float32), axis=0)
        fired = m > 0
        last_tx = np.max(np.where(fired, np.arange(T)[:, None], 0), axis=0)

        data_key = jax.random.fold_in(jax.random.PRNGKey(data_seed),
                                      DATA_STREAM)
        n_test = cfg["eval_batch"]
        tx, ty = world.x_test[:n_test], world.y_test[:n_test]
        g = jax.tree_util.tree_map(lambda a: jnp.asarray(a, dtype),
                                   world.adapters)
        clients, anchors = [g] * K, [g] * K
        accs, losses, evals = [], [], []
        for t in range(T):
            idx = np.asarray(round_indices(data_key, t, world.x.shape[1],
                                           K, L, B))
            for k in range(K):
                for i in range(L):
                    rows = jnp.asarray(idx[k, i])
                    clients[k] = client_step(clients[k], world.base,
                                             world.x[k][rows],
                                             world.y[k][rows], s, lr, dtype)
            for k in range(K):
                if fired[t, k]:
                    g = jax.tree_util.tree_map(
                        lambda a, c, y0: a + (c - y0) / K, g, clients[k],
                        anchors[k])
            for k in range(K):
                if fired[t, k]:
                    clients[k], anchors[k] = g, g
            if t % cfg["eval_every"] == 0 or t == T - 1:
                a, lo = evaluate(g, world.base, tx, ty, s, dtype)
                accs.append(a)
                losses.append(lo)
                evals.append(t)
    f32 = partial(jax.tree_util.tree_map, lambda a: np.asarray(a, np.float32))
    return {"p": p, "u": u, "masks": m, "energy": np.asarray(energy),
            "last_tx": last_tx, "acc": np.asarray(accs, np.float32),
            "loss": np.asarray(losses, np.float32),
            "eval_rounds": np.asarray(evals), "params": f32(g),
            "clients": f32(_stack(clients))}

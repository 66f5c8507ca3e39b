"""Inputs of the federated-LoRA cells, made from the run's seed.

Nothing here imports the program; the program (``lm_sim.py``) and the plain
reference (``lm_reference.py``) receive the same arrays.

* The frozen base: random weights of the Jamba layers the configuration
  names, in the published widths, stored in bfloat16, as ``{"embed",
  "final_norm", "layers": [one dict per layer]}``.  A layer is ``{"ln1",
  "mixer", "ln2", "ffn"}``; a Mamba mixer holds ``in_proj, conv_w, conv_b,
  x_proj, dt_norm, b_norm, c_norm, dt_proj, dt_bias, A_log, D, out_proj``,
  an attention mixer ``wq, wk, wv, wo``; the FFN is SwiGLU ``w1, w3, w2``.
* The adapters: one ``{"a", "b"}`` pair per adapted projection, in float32,
  ``A ~ N(0, 1/n_in)`` and ``B = 0`` (``b_std`` > 0 draws B too, for tests).
* Token data: each client's sequences follow its own first-order Markov
  chain over the vocabulary.  Every id has ``successors`` next ids with
  Zipf(``zipf_a``) weights by rank; the even ranks are shared by all
  clients, the odd ranks are the client's own, so the clients' data differ
  and are learnable.  The held-out sequences each follow a client drawn
  from the seed (the mixture).
* Client positions and the Rayleigh channel pool, as ``world.py`` makes
  them.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from .world import Cell, positions, sub_seeds


@dataclasses.dataclass(frozen=True)
class Shape:
    """The model's sizes, read from the configuration's published keys."""

    d: int
    ff: int
    heads: int
    kv_heads: int
    head_dim: int
    d_inner: int
    d_state: int
    d_conv: int
    dt_rank: int
    vocab: int
    layers: int
    attn_offset: int
    attn_period: int
    eps: float
    rank: int
    alpha: float
    targets: tuple

    @classmethod
    def of(cls, cfg: dict) -> "Shape":
        d, H = cfg["hidden_size"], cfg["num_attention_heads"]
        lo = cfg["lora"]
        return cls(d=d, ff=cfg["intermediate_size"], heads=H,
                   kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg.get("head_dim") or d // H,
                   d_inner=cfg["mamba_expand"] * d,
                   d_state=cfg["mamba_d_state"], d_conv=cfg["mamba_d_conv"],
                   dt_rank=cfg["mamba_dt_rank"], vocab=cfg["vocab_size"],
                   layers=cfg["num_hidden_layers"],
                   attn_offset=cfg["attn_layer_offset"],
                   attn_period=cfg["attn_layer_period"],
                   eps=cfg["rms_norm_eps"], rank=lo["r"],
                   alpha=lo["alpha"], targets=tuple(lo["targets"]))

    def is_attn(self, i: int) -> bool:
        return i % self.attn_period == self.attn_offset

    @property
    def scale(self) -> float:
        return self.alpha / self.rank

    def adapted(self, i: int) -> dict:
        """``{group: {name: (n_in, n_out)}}`` of layer ``i``'s adapted
        projections."""
        d, hd = self.d, self.head_dim
        if self.is_attn(i):
            mixer = {"wq": (d, self.heads * hd), "wk": (d, self.kv_heads * hd),
                     "wv": (d, self.kv_heads * hd),
                     "wo": (self.heads * hd, d)}
        else:
            mixer = {"in_proj": (d, 2 * self.d_inner),
                     "out_proj": (self.d_inner, d)}
        ffn = {"w1": (d, self.ff), "w3": (d, self.ff), "w2": (self.ff, d)}
        out = {"mixer": {k: v for k, v in mixer.items() if k in self.targets},
               "ffn": {k: v for k, v in ffn.items() if k in self.targets}}
        return {g: v for g, v in out.items() if v}


def num_adapter_params(cfg: dict) -> int:
    """D: the adapters' parameter count, what every upload carries."""
    s = Shape.of(cfg)
    return sum(s.rank * (i + o) for layer in range(s.layers)
               for group in s.adapted(layer).values()
               for i, o in group.values())


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def _gamma(key, n):
    return 1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)


def _mamba(key, s: Shape):
    d, di, N, R, k = s.d, s.d_inner, s.d_state, s.dt_rank, s.d_conv
    ks = jax.random.split(key, 10)
    dt = jnp.exp(jax.random.uniform(ks[0], (di,), jnp.float32)
                 * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    return {
        "in_proj": _normal(ks[1], (d, 2 * di), d ** -0.5),
        "conv_w": _normal(ks[2], (k, di), k ** -0.5),
        "conv_b": _normal(ks[3], (di,), 0.1),
        "x_proj": _normal(ks[4], (di, R + 2 * N), di ** -0.5),
        "dt_norm": _gamma(ks[5], R), "b_norm": _gamma(ks[6], N),
        "c_norm": _gamma(ks[7], N),
        "dt_proj": _normal(ks[8], (R, di), R ** -0.5),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),      # inverse softplus
        "A_log": jnp.log(jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32),
                                  (di, 1))),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": _normal(ks[9], (di, d), di ** -0.5),
    }


def _attn(key, s: Shape):
    d, hd = s.d, s.head_dim
    ks = jax.random.split(key, 4)
    return {"wq": _normal(ks[0], (d, s.heads * hd), d ** -0.5),
            "wk": _normal(ks[1], (d, s.kv_heads * hd), d ** -0.5),
            "wv": _normal(ks[2], (d, s.kv_heads * hd), d ** -0.5),
            "wo": _normal(ks[3], (s.heads * hd, d), (s.heads * hd) ** -0.5)}


@partial(jax.jit, static_argnames=("s", "attn", "dtype"))
def _layer(key, s: Shape, attn: bool, dtype):
    ks = jax.random.split(key, 5)
    layer = {"ln1": _gamma(ks[0], s.d),
             "mixer": _attn(ks[1], s) if attn else _mamba(ks[1], s),
             "ln2": _gamma(ks[2], s.d),
             "ffn": {"w1": _normal(ks[3], (s.d, s.ff), s.d ** -0.5),
                     "w3": _normal(jax.random.fold_in(ks[3], 1),
                                   (s.d, s.ff), s.d ** -0.5),
                     "w2": _normal(ks[4], (s.ff, s.d), s.ff ** -0.5)}}
    return jax.tree_util.tree_map(lambda a: a.astype(dtype), layer)


@partial(jax.jit, static_argnames=("s", "dtype"))
def _outer(key, s: Shape, dtype):
    k1, k2 = jax.random.split(key)
    return {"embed": _normal(k1, (s.vocab, s.d), 0.02).astype(dtype),
            "final_norm": _gamma(k2, s.d).astype(dtype)}


def init_base(key, s: Shape, dtype=jnp.bfloat16) -> dict:
    """The frozen base, one jitted call a layer (nothing but the weights is
    ever held on the device)."""
    keys = jax.random.split(key, s.layers + 1)
    base = _outer(keys[-1], s, dtype)
    base["layers"] = [_layer(keys[i], s, s.is_attn(i), dtype)
                      for i in range(s.layers)]
    return base


@partial(jax.jit, static_argnames=("s", "b_std"))
def init_adapters(key, s: Shape, b_std: float = 0.0) -> list:
    out = []
    for i in range(s.layers):
        layer = {}
        for g, group in s.adapted(i).items():
            layer[g] = {}
            for name, (n_in, n_out) in group.items():
                key, ka, kb = jax.random.split(key, 3)
                layer[g][name] = {
                    "a": _normal(ka, (n_in, s.rank), n_in ** -0.5),
                    "b": _normal(kb, (s.rank, n_out), b_std)}
        out.append(layer)
    return out


@partial(jax.jit, static_argnames=("vocab", "successors", "num_clients"))
def successor_tables(key, vocab: int, successors: int, num_clients: int):
    """``[K, V, successors]`` next ids: even ranks shared, odd ranks own."""
    half = successors // 2
    k1, k2 = jax.random.split(key)
    shared = jax.random.randint(k1, (vocab, half), 0, vocab, jnp.int32)
    own = jax.random.randint(k2, (num_clients, vocab, half), 0, vocab,
                             jnp.int32)
    both = jnp.stack([jnp.broadcast_to(shared, own.shape), own], axis=-1)
    return both.reshape(num_clients, vocab, 2 * half)


@partial(jax.jit, static_argnames=("n_seq", "length", "zipf_a"))
def markov_sequences(key, table, n_seq: int, length: int, zipf_a: float):
    """``[n_seq, length]`` token ids from the chain ``table [V, J]``."""
    V, J = table.shape
    logw = -zipf_a * jnp.log(jnp.arange(1, J + 1, dtype=jnp.float32))
    k0, k1 = jax.random.split(key)
    first = jax.random.randint(k0, (n_seq,), 0, V, jnp.int32)

    def step(tok, k):
        nxt = table[tok, jax.random.categorical(k, logw, shape=(n_seq,))]
        return nxt, nxt

    _, rest = jax.lax.scan(step, first, jax.random.split(k1, length - 1))
    return jnp.concatenate([first[None], rest]).T


@dataclasses.dataclass
class LMWorld:
    seed: int
    shape: Shape
    base: dict                 # frozen, bfloat16, on the device
    adapters: list             # initial adapters, float32, on the device
    x: jax.Array               # [K, n, S] input tokens
    y: jax.Array               # [K, n, S] next tokens
    x_test: jax.Array          # [n_test, S]
    y_test: jax.Array
    dist_m: jax.Array          # [K]
    cell: Cell
    seeds: dict


def build_world(cfg: dict, seed: int, b_std: float = 0.0) -> LMWorld:
    s = dict(zip(("data", "test", "base", "lora", "place", "channel",
                  "calls", "stream", "order"), sub_seeds(seed, 9)))
    shape = Shape.of(cfg)
    K, data = cfg["num_clients"], cfg["data"]
    tables = successor_tables(jax.random.PRNGKey(s["data"]), shape.vocab,
                              data["successors"], K)
    keys = jax.random.split(jax.random.PRNGKey(s["data"] + 1), K)
    seqs = jnp.stack([markov_sequences(keys[k], tables[k],
                                       data["sequences_per_client"],
                                       data["tokens_per_sequence"],
                                       data["zipf_a"]) for k in range(K)])
    kt, kc = jax.random.split(jax.random.PRNGKey(s["test"]))
    owners = jax.random.randint(kc, (data["test_sequences"],), 0, K)
    tests = jnp.stack([markov_sequences(k, tables[o], 1,
                                        data["tokens_per_sequence"],
                                        data["zipf_a"])[0]
                       for k, o in zip(jax.random.split(
                           kt, data["test_sequences"]), owners.tolist())])
    del tables
    cell = Cell(num_clients=K,
                model_size_bits=32.0 * num_adapter_params(cfg))
    return LMWorld(
        seed=seed, shape=shape,
        base=init_base(jax.random.PRNGKey(s["base"]), shape),
        adapters=init_adapters(jax.random.PRNGKey(s["lora"]), shape, b_std),
        x=seqs[..., :-1], y=seqs[..., 1:],
        x_test=tests[:, :-1], y_test=tests[:, 1:],
        dist_m=positions(jax.random.PRNGKey(s["place"]), K,
                         cell.min_radius_m, cell.cell_radius_m),
        cell=cell, seeds=s)

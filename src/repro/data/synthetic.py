"""Deterministic synthetic datasets.

The container is offline, so MNIST/CIFAR-10 cannot be downloaded.  We generate
procedural stand-ins with the same label structure (10 classes, same example
counts by default) so that the paper's *relative* claims — scheme orderings,
ρ tradeoff shape, fairness — are measurable.  Generators are keyed and fully
deterministic.

``make_mnist_like``  : 784-dim inputs, 10 classes — class-prototype clusters
                       with within-class manifold variation (learnable by the
                       paper's 1×200 MLP, not linearly trivial).
``make_cifar_like``  : 32×32×3 inputs, 10 classes — textured class prototypes.
``make_token_stream``: synthetic LM token streams for the LLM architectures.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Dataset(NamedTuple):
    x: jax.Array       # [N, ...] inputs
    y: jax.Array       # [N] int labels
    num_classes: int


def _cluster_classification(key, n, dim, num_classes, noise, hard_frac=0.35):
    """Class prototypes + per-class low-rank manifolds + noise."""
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    protos = jax.random.normal(k1, (num_classes, dim)) * 1.2
    rank = max(dim // 16, 4)
    manifolds = jax.random.normal(k2, (num_classes, rank, dim)) * 0.6
    y = jax.random.randint(k3, (n,), 0, num_classes)
    coeff = jax.random.normal(k4, (n, rank))
    # the per-example manifold gather is [n, rank, dim] (10.7 GB at the
    # paper's 70k MNIST examples), so it is built a chunk of rows at a time
    chunk = 4096
    offsets = jnp.concatenate([
        jnp.einsum("nr,nrd->nd", coeff[i:i + chunk],
                   manifolds[y[i:i + chunk]])
        for i in range(0, n, chunk)])
    base = protos[y] + offsets
    x = base + noise * jax.random.normal(k5, (n, dim))
    return x, y


def make_mnist_like(key: jax.Array, n_train: int = 60_000,
                    n_test: int = 10_000, noise: float = 0.9) -> tuple[Dataset, Dataset]:
    dim, num_classes = 784, 10
    x, y = _cluster_classification(key, n_train + n_test, dim, num_classes,
                                   noise)
    x = jnp.tanh(x)  # bounded like normalized pixels
    tr = Dataset(x[:n_train], y[:n_train], num_classes)
    te = Dataset(x[n_train:], y[n_train:], num_classes)
    return tr, te


def make_cifar_like(key: jax.Array, n_train: int = 50_000,
                    n_test: int = 10_000, noise: float = 1.1) -> tuple[Dataset, Dataset]:
    dim, num_classes = 32 * 32 * 3, 10
    x, y = _cluster_classification(key, n_train + n_test, dim, num_classes,
                                   noise)
    x = jnp.tanh(x).reshape(-1, 32, 32, 3)
    tr = Dataset(x[:n_train], y[:n_train], num_classes)
    te = Dataset(x[n_train:], y[n_train:], num_classes)
    return tr, te


def make_token_stream(key: jax.Array, n_seqs: int, seq_len: int,
                      vocab: int) -> Dataset:
    """Synthetic LM data: per-sequence Markov-ish token chains so that a
    language model has learnable structure (bigram transitions)."""
    k1, k2 = jax.random.split(key)
    # a sparse bigram preference: next ≈ (prev * a + b) mod vocab with noise
    a = int(jax.random.randint(k1, (), 3, 17))
    starts = jax.random.randint(k2, (n_seqs, 1), 0, vocab)

    def step(prev, k):
        noise = jax.random.randint(k, prev.shape, 0, max(vocab // 50, 2))
        nxt = (prev * a + 7 + noise) % vocab
        return nxt, nxt

    keys = jax.random.split(key, seq_len - 1)
    _, rest = jax.lax.scan(step, starts[:, 0], keys)
    toks = jnp.concatenate([starts, rest.T], axis=1)
    return Dataset(toks, jnp.zeros((n_seqs,), jnp.int32), vocab)

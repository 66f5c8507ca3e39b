"""Device-resident federated data store.

The scan engine (PR 1) moved the *simulation* on device but left the data
path host-bound: ``stack_round_batches`` materializes a ``[T, K, L, B, ...]``
tensor whose footprint grows linearly in the horizon T (~125 MB at MNIST
scale, T=50 — 5 GB at T=2000).  This module replaces that pre-stack with a
horizon-independent layout:

* :class:`DeviceDataStore` — each client's shard padded to a shared
  ``[K, N_max, ...]`` block with a per-client ``lengths`` mask.  Peak data
  memory is ``K · N_max``, independent of T.
* **on-device per-round sampling** — :func:`round_indices` draws every
  round's minibatch indices from ``fold_in(data_key, t)`` so the stream
  depends only on ``(data seed, t)``; :func:`sample_round` gathers them
  *inside* the jitted scan.  :func:`stack_rounds_reference` evaluates the
  identical stream eagerly into the legacy ``[T, K, L, B, ...]`` layout, so
  the two data paths are bit-identical by construction (the parity tests
  rely on this).
* **jittable partitioners** — :func:`shard_assignment` (the paper's §V-A
  label-shard scheme) and :func:`dirichlet_assignment` (Dirichlet(α)
  heterogeneity) as pure index ops over static shapes: both ``vmap`` over
  the partition key, so a scenario matrix can give every lane its own
  non-IID realization without leaving the device program.
* **streaming fallback** — :class:`StreamingSampler` keeps the padded
  blocks host-side and serves round-chunks through double-buffered
  ``device_put`` prefetch for datasets exceeding the HBM budget;
  :func:`choose_data_path` picks the path from a footprint estimate.

The participation PRNG uses ``fold_in(base_key, t)`` directly; the data
stream folds :data:`DATA_STREAM` into its key first so the two streams never
alias even when built from the same seed.
"""
from __future__ import annotations

import os
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .synthetic import Dataset

#: fold_in tag separating the minibatch stream from the participation stream.
DATA_STREAM = 0x0DA7A


class DeviceDataStore(NamedTuple):
    """Padded per-client shards, resident where the simulation runs.

    ``x[k, :lengths[k]]`` are client k's examples; rows at or beyond
    ``lengths[k]`` are zero padding and are never selected by the samplers
    (indices are drawn in ``[0, lengths[k])``).
    """

    x: jax.Array        # [K, N_max, ...] inputs, zero-padded
    y: jax.Array        # [K, N_max, ...] int32 labels (a class, or a
                        # sequence of target tokens), zero-padded
    lengths: jax.Array  # [K] int32 valid example counts

    @property
    def num_clients(self) -> int:
        return self.x.shape[0]

    @property
    def capacity(self) -> int:
        return self.x.shape[1]

    @property
    def nbytes(self) -> int:
        return int(self.x.size * self.x.dtype.itemsize
                   + self.y.size * self.y.dtype.itemsize
                   + self.lengths.size * 4)


def data_stream_key(seed_or_key) -> jax.Array:
    """Minibatch-stream key for a simulation seed (or an existing key)."""
    key = (jax.random.PRNGKey(seed_or_key)
           if jnp.ndim(seed_or_key) == 0 else seed_or_key)
    return jax.random.fold_in(key, DATA_STREAM)


def _pack_clients(clients: Sequence[Dataset],
                  pad_to: int | None = None):
    """Host-side pad-and-pack shared by the device store and the streaming
    sampler (one implementation ⇒ the two paths stay bit-identical):
    ``(x [K, cap, ...], y [K, cap, ...], counts [K])`` as numpy arrays; a
    label keeps its trailing shape (``[S]`` target tokens per sequence)."""
    counts = [int(np.asarray(c.y).shape[0]) for c in clients]
    if min(counts) == 0:
        raise ValueError("every client shard must be non-empty")
    cap = pad_to or max(counts)
    if cap < max(counts):
        raise ValueError(f"pad_to={cap} < largest shard ({max(counts)})")
    sample = np.asarray(clients[0].x).shape[1:]
    K = len(clients)
    x = np.zeros((K, cap) + sample, np.asarray(clients[0].x).dtype)
    y = np.zeros((K, cap) + np.asarray(clients[0].y).shape[1:], np.int32)
    for k, c in enumerate(clients):
        x[k, : counts[k]] = np.asarray(c.x)
        y[k, : counts[k]] = np.asarray(c.y)
    return x, y, counts


def from_client_datasets(clients: Sequence[Dataset],
                         pad_to: int | None = None) -> DeviceDataStore:
    """Pack per-client :class:`Dataset` shards into one padded store."""
    x, y, counts = _pack_clients(clients, pad_to)
    return DeviceDataStore(jnp.asarray(x), jnp.asarray(y),
                           jnp.asarray(counts, jnp.int32))


# ---------------------------------------------------------------------------
# per-round sampling (the on-device path's canonical stream)
# ---------------------------------------------------------------------------


def round_indices(data_key: jax.Array, t: jax.Array, lengths: jax.Array,
                  local_iters: int, batch_size: int) -> jax.Array:
    """``[K, L, B]`` example indices for round ``t``, from
    ``fold_in(data_key, t)`` only — uniform over each client's valid rows
    (with replacement), never touching the padding.

    A ``lengths[k] == 0`` client degenerates to sampling padding row 0
    (shape-stable under jit, no way to signal an error from inside a traced
    program) — the host-side constructors (``from_client_datasets``, the
    ``cap=None`` partitioner entries) reject such stores up front; when
    building stores *inside* jit/vmap with an explicit ``cap``, the caller
    owns that check.
    """
    K = lengths.shape[0]
    u = jax.random.uniform(jax.random.fold_in(data_key, t),
                           (K, local_iters, batch_size))
    n = jnp.maximum(lengths, 1).astype(jnp.float32)[:, None, None]
    idx = jnp.floor(u * n).astype(jnp.int32)
    return jnp.minimum(idx, (n - 1.0).astype(jnp.int32))


def gather_round(store: DeviceDataStore,
                 idx: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Gather ``([K, L, B, ...], [K, L, B])`` batches for per-client index
    blocks ``idx: [K, L, B]``."""
    xb = jax.vmap(lambda xs, ii: xs[ii])(store.x, idx)
    yb = jax.vmap(lambda ys, ii: ys[ii])(store.y, idx)
    return xb, yb


def sample_round(store: DeviceDataStore, data_key: jax.Array, t: jax.Array,
                 local_iters: int, batch_size: int):
    """One round's stacked client batches, sampled on device (jit/scan-safe)."""
    return gather_round(store, round_indices(data_key, t, store.lengths,
                                             local_iters, batch_size))


def sample_batch(store: DeviceDataStore, data_key: jax.Array, t: jax.Array,
                 batch_size: int):
    """Single-local-iter convenience: ``([K, B, ...], [K, B])``."""
    xb, yb = sample_round(store, data_key, t, 1, batch_size)
    return xb[:, 0], yb[:, 0]


# ---------------------------------------------------------------------------
# per-client stream: indices a single client can draw without touching the
# other K-1 rows (the sparse participation path samples participants only)
# ---------------------------------------------------------------------------


def client_round_indices(data_key: jax.Array, t: jax.Array,
                         client_id: jax.Array, length: jax.Array,
                         local_iters: int, batch_size: int) -> jax.Array:
    """``[L, B]`` example indices for one client at round ``t``.

    The stream is keyed ``fold_in(fold_in(data_key, t), client_id)`` — a pure
    function of ``(data seed, t, k)``, so any *subset* of clients can be
    sampled without materializing draws for the full population (the
    participant-centric sparse path gathers only the transmitting set).
    Like :func:`round_indices`, draws are uniform over ``[0, length)`` with
    replacement and never land in the padding.
    """
    key = jax.random.fold_in(jax.random.fold_in(data_key, t), client_id)
    u = jax.random.uniform(key, (local_iters, batch_size))
    n = jnp.maximum(length, 1).astype(jnp.float32)
    idx = jnp.floor(u * n).astype(jnp.int32)
    return jnp.minimum(idx, (n - 1.0).astype(jnp.int32))


def round_indices_client_stream(data_key: jax.Array, t: jax.Array,
                                lengths: jax.Array, local_iters: int,
                                batch_size: int) -> jax.Array:
    """Dense ``[K, L, B]`` reference of the per-client stream: row ``k`` is
    exactly :func:`client_round_indices` for client ``k`` — gathering a
    subset of rows equals sampling that subset directly (the sparse-path
    parity tests rely on this)."""
    K = lengths.shape[0]
    ks = jnp.arange(K, dtype=jnp.int32)
    return jax.vmap(lambda k, n: client_round_indices(
        data_key, t, k, n, local_iters, batch_size))(ks, lengths)


def sample_round_client_stream(store: DeviceDataStore, data_key: jax.Array,
                               t: jax.Array, local_iters: int,
                               batch_size: int):
    """Dense-engine sampler on the per-client stream (``SimConfig.data_stream
    = "client"``) — the bit-parity reference for the sparse path."""
    return gather_round(store, round_indices_client_stream(
        data_key, t, store.lengths, local_iters, batch_size))


def gather_participant_rounds(store: DeviceDataStore, data_key: jax.Array,
                              part_idx: jax.Array, local_iters: int,
                              batch_size: int):
    """Batches for the transmitting sets of every round, participant-sized.

    ``part_idx: [T, P]`` int32 client ids (padding rows hold ``K``).  Returns
    ``([T, P, L, B, ...], [T, P, L, B])`` — the only contact with the dense
    ``[K, N_max, ...]`` store is a row gather per participant; no
    ``[K, L, B, ...]`` round batch is ever built.  Padding entries gather
    client ``K-1``'s rows (clamped) on a never-used key stream; the sparse
    engine masks them out of the aggregate.
    """
    K = store.num_clients

    def one_round(t, idx_t):
        kc = jnp.clip(idx_t, 0, K - 1)
        lens = store.lengths[kc]
        bidx = jax.vmap(lambda k_raw, n: client_round_indices(
            data_key, t, k_raw, n, local_iters, batch_size))(idx_t, lens)
        xb = jax.vmap(lambda k, ii: store.x[k][ii])(kc, bidx)
        yb = jax.vmap(lambda k, ii: store.y[k][ii])(kc, bidx)
        return xb, yb

    T = part_idx.shape[0]
    ts = jnp.arange(T, dtype=jnp.int32)
    return jax.vmap(one_round)(ts, part_idx)


def stack_rounds_reference(store: DeviceDataStore, data_key: jax.Array,
                           rounds: int, local_iters: int, batch_size: int):
    """Materialize the on-device stream into the legacy ``[T, K, L, B, ...]``
    layout — the parity/benchmark reference for the pre-stack data path.

    Identical keys and gather source ⇒ bit-identical batches to what
    :func:`sample_round` draws inside the scan at each ``t``.
    """
    ts = jnp.arange(rounds, dtype=jnp.int32)
    return jax.jit(jax.vmap(
        lambda t: sample_round(store, data_key, t, local_iters, batch_size)
    ))(ts)


def label_histogram(store: DeviceDataStore, num_classes: int) -> jax.Array:
    """Per-client label counts ``[K, C]`` honoring the length masks."""
    def one(yk, lk):
        valid = jnp.arange(yk.shape[0]) < lk
        return jnp.bincount(jnp.where(valid, yk, num_classes),
                            length=num_classes + 1)[:num_classes]
    return jax.vmap(one)(store.y, store.lengths)


# ---------------------------------------------------------------------------
# jittable non-IID partitioners (pure index ops; vmap over the key for
# per-scenario-lane partitions)
# ---------------------------------------------------------------------------


def assignment_to_store(x: jax.Array, y: jax.Array, assign: jax.Array,
                        num_clients: int, cap: int) -> DeviceDataStore:
    """Turn an example→client assignment ``[N]`` into a padded store.

    Pure index ops with static output shapes (``cap`` rows per client):
    stable-sort by client, then each client reads its contiguous slice.
    Clients owning more than ``cap`` examples are truncated to ``cap``;
    padding rows are zeroed.
    """
    N = y.shape[0]
    order = jnp.argsort(assign)                       # stable
    counts = jnp.bincount(assign, length=num_clients)
    starts = jnp.cumsum(counts) - counts
    pos = starts[:, None] + jnp.arange(cap)[None, :]  # [K, cap]
    lengths = jnp.minimum(counts, cap).astype(jnp.int32)
    valid = jnp.arange(cap)[None, :] < lengths[:, None]
    idx = order[jnp.clip(pos, 0, N - 1)]
    xk = jnp.where(valid.reshape(valid.shape + (1,) * (x.ndim - 1)),
                   x[idx], 0)
    yk = jnp.where(valid, y[idx].astype(jnp.int32), 0)
    return DeviceDataStore(xk, yk, lengths)


def dirichlet_assignment(key: jax.Array, y: jax.Array, num_clients: int,
                         alpha: float, num_classes: int) -> jax.Array:
    """Dirichlet(α) non-IID assignment ``[N] -> client`` (jittable).

    Each client k draws class preferences ``p_k ~ Dirichlet(α·1_C)``; an
    example with label c goes to client k with probability ∝ ``p_k[c]``
    (Gumbel-argmax over clients).  Small α ⇒ each client concentrates on few
    classes; large α ⇒ IID-like.
    """
    k_prop, k_gum = jax.random.split(key)
    props = jax.random.dirichlet(
        k_prop, jnp.full((num_classes,), alpha, jnp.float32),
        shape=(num_clients,))                          # [K, C]
    logits = jnp.log(jnp.maximum(props[:, y], 1e-30))  # [K, N]
    gum = jax.random.gumbel(k_gum, (num_clients, y.shape[0]))
    return jnp.argmax(logits + gum, axis=0).astype(jnp.int32)


def shard_assignment(key: jax.Array, y: jax.Array, num_clients: int, d: int,
                     num_classes: int) -> jax.Array:
    """Paper §V-A label-shard scheme as pure index ops (jittable).

    Splits each class into ``d·K/C`` equal shards and gives every client
    ``d`` shards with distinct labels (for d ≤ C).  Construction: rank
    examples within their class (random tiebreak) → shard-in-class; arrange
    the ``d·K`` shards column-major in a ``[C, d·K/C]`` grid so that ``d``
    consecutive slots always span ``d`` distinct classes; randomize by
    permuting shard columns within each class and permuting client ids.
    """
    S = d * num_clients
    if S % num_classes != 0:
        raise ValueError(f"d*K must be divisible by C={num_classes} "
                         f"(got d={d}, K={num_clients})")
    spc = S // num_classes                             # shards per class
    N = y.shape[0]
    k_tie, k_col, k_cli = jax.random.split(key, 3)

    # rank within class, random order inside each class
    tie = jax.random.uniform(k_tie, (N,))
    order = jnp.argsort(y.astype(jnp.float32) * 2.0 + tie)
    counts = jnp.bincount(y, length=num_classes)
    starts = jnp.cumsum(counts) - counts
    y_sorted = y[order]
    rank = jnp.arange(N) - starts[y_sorted]
    shard_in_class = jnp.minimum(
        (rank * spc) // jnp.maximum(counts[y_sorted], 1), spc - 1)

    # class-local shard → grid column (random per-class permutation)
    colperm = jnp.argsort(jax.random.uniform(k_col, (num_classes, spc)),
                          axis=1)                      # [C, spc]
    col = colperm[y_sorted, shard_in_class]
    slot = col * num_classes + y_sorted                # column-major: slot%C=c
    cperm = jax.random.permutation(k_cli, num_clients)
    assign_sorted = cperm[slot // d].astype(jnp.int32)

    # scatter back to original example order
    return jnp.zeros((N,), jnp.int32).at[order].set(assign_sorted)


def _default_cap(assign: jax.Array, num_clients: int) -> int:
    """Concrete (host-side) capacity: the largest client's example count.
    Also the host entry's chance to reject degenerate partitions — a
    zero-example client would otherwise sample padding row 0 forever (see
    :func:`round_indices`).

    Guarded for huge-K stores: with K ≫ N no partition can leave every
    client non-empty, so the error fires *before* a ``[K]`` bincount is
    materialized (at K ~ 10⁸ the bincount alone is hundreds of MB); the
    capacity readback goes through Python ints, so downstream byte math
    cannot silently overflow a fixed-width accumulator.
    """
    n = int(assign.shape[0])
    if num_clients > n:
        raise ValueError(
            f"partition is degenerate: num_clients={num_clients} exceeds the "
            f"dataset size N={n}, so some client must end up with no "
            "examples — use a larger dataset or fewer clients")
    counts = jnp.bincount(assign, length=num_clients)
    if int(counts.min()) == 0:
        raise ValueError(
            f"partition left client {int(jnp.argmin(counts))} with no "
            "examples — use a larger alpha/dataset or fewer clients")
    cap = int(counts.max())
    if cap <= 0:
        raise ValueError("partition produced a degenerate zero capacity")
    return cap


def dirichlet_store(key: jax.Array, ds: Dataset, num_clients: int,
                    alpha: float, cap: int | None = None) -> DeviceDataStore:
    """Partition a dataset Dirichlet(α)-style straight into a store.

    Host-convenience entry: when ``cap`` is None it is read back from the
    realized counts (not jittable); pass an explicit ``cap`` to stay inside
    jit/vmap.
    """
    assign = dirichlet_assignment(key, ds.y, num_clients, alpha,
                                  ds.num_classes)
    cap = cap if cap is not None else _default_cap(assign, num_clients)
    return assignment_to_store(ds.x, ds.y, assign, num_clients, cap)


def shard_store(key: jax.Array, ds: Dataset, num_clients: int, d: int,
                cap: int | None = None) -> DeviceDataStore:
    """Paper §V-A partition straight into a store (see ``dirichlet_store``
    for the ``cap`` contract)."""
    assign = shard_assignment(key, ds.y, num_clients, d, ds.num_classes)
    cap = cap if cap is not None else _default_cap(assign, num_clients)
    return assignment_to_store(ds.x, ds.y, assign, num_clients, cap)


# ---------------------------------------------------------------------------
# footprint planning: device store vs host streaming
# ---------------------------------------------------------------------------

#: conservative CPU/unknown-backend budget when the runtime reports nothing.
DEFAULT_BUDGET_BYTES = 4 << 30
#: fraction of the budget the data store may claim (model/state/traces need
#: the rest).
STORE_BUDGET_FRACTION = 0.5


def store_bytes(num_clients: int, cap: int, sample_shape: Sequence[int],
                itemsize: int = 4) -> int:
    """Exact padded-store footprint from its shape parameters.

    Matches :attr:`DeviceDataStore.nbytes` term for term: the ``[K, N_max,
    ...]`` inputs, the ``[K, N_max]`` int32 label/mask block, and the ``[K]``
    int32 lengths vector.  All math is Python-int, so a K ~ 10⁹ planning
    query cannot overflow a fixed-width accumulator the way ``np.int64``
    products silently can.
    """
    row = 1
    for s in sample_shape:
        row *= int(s)
    k, cap = int(num_clients), int(cap)
    return k * cap * (row * int(itemsize) + 4) + k * 4


def estimate_store_bytes(clients: Sequence[Dataset]) -> int:
    """Padded-store footprint for a client list, without building it
    (exactly what :func:`from_client_datasets` would allocate, including the
    ``[K, N_max]`` label/mask block and the ``[K]`` lengths vector)."""
    counts = [int(np.asarray(c.y).shape[0]) for c in clients]
    sample = np.asarray(clients[0].x)
    return store_bytes(len(clients), max(counts), sample.shape[1:],
                       sample.dtype.itemsize)


def device_memory_budget() -> int:
    """Usable accelerator memory: ``memory_stats`` when the backend reports
    it, else the ``REPRO_DATA_BUDGET_BYTES`` env override, else 4 GiB."""
    env = os.environ.get("REPRO_DATA_BUDGET_BYTES")
    if env:
        return int(env)
    stats = jax.devices()[0].memory_stats()
    if stats and stats.get("bytes_limit"):
        return int(stats["bytes_limit"])
    return DEFAULT_BUDGET_BYTES


def choose_data_path(clients: Sequence[Dataset],
                     budget_bytes: int | None = None) -> str:
    """``"device"`` when the padded store fits the budget, else ``"stream"``.

    T never enters the estimate — both paths are horizon-independent; only
    the dataset size decides.
    """
    budget = budget_bytes if budget_bytes is not None \
        else device_memory_budget()
    need = estimate_store_bytes(clients)
    return "device" if need <= STORE_BUDGET_FRACTION * budget else "stream"


# ---------------------------------------------------------------------------
# host-streaming fallback: double-buffered round-chunk prefetch
# ---------------------------------------------------------------------------


class StreamingSampler:
    """Serve round-chunks of the canonical stream from host memory.

    Keeps the padded ``[K, N_max, ...]`` blocks as numpy (host) arrays and
    materializes ``[C, K, L, B, ...]`` chunks on demand: indices come from
    the *same* jitted :func:`round_indices` stream as the on-device path
    (bit-identical batches), the gather runs host-side, and the result is
    ``device_put`` ahead of use — the engine overlaps chunk ``i+1``'s
    transfer with chunk ``i``'s compute (double buffering).
    """

    def __init__(self, clients: Sequence[Dataset], data_key: jax.Array,
                 local_iters: int, batch_size: int,
                 pad_to: int | None = None):
        self._x, self._y, counts = _pack_clients(clients, pad_to)
        self.lengths = jnp.asarray(counts, jnp.int32)
        self.data_key = data_key
        self.local_iters = local_iters
        self.batch_size = batch_size
        self._chunk_indices = jax.jit(jax.vmap(
            lambda t: round_indices(data_key, t, self.lengths, local_iters,
                                    batch_size)))

    @property
    def nbytes_host(self) -> int:
        return int(self._x.nbytes + self._y.nbytes)

    def chunk(self, t0: int, t1: int) -> tuple[jax.Array, jax.Array]:
        """Batches for rounds ``[t0, t1)`` as device arrays
        ``([C, K, L, B, ...], [C, K, L, B])`` (the ``device_put`` is the
        prefetch; call it one chunk ahead)."""
        ts = jnp.arange(t0, t1, dtype=jnp.int32)
        idx = np.asarray(self._chunk_indices(ts))      # [C, K, L, B] small
        k_idx = np.arange(self._x.shape[0])[None, :, None, None]
        xb = self._x[k_idx, idx]                       # [C, K, L, B, ...]
        yb = self._y[k_idx, idx]
        return jax.device_put(xb), jax.device_put(yb)

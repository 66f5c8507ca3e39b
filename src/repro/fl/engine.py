"""On-device scan-based FL simulation engine.

The paper's per-round protocol (§II, Fig. 1) — policy, autonomous Bernoulli
participation, Δ_k forced transmission, bandwidth reservation, energy ledger
(eq. 5), local SGD, masked aggregation (eq. 3), broadcast — is expressed as a
single jittable round transition and executed for all ``T`` rounds inside one
``lax.scan``.  Nothing syncs to the host per round; the only readback is the
stacked per-round trace at the end (masks, energies, strided evals).

Layout:

* **policy interface** — a pure ``PolicyFn`` ``(t, h_t, sim_state) ->
  (probs, w)`` (see :mod:`repro.core.selection`); legacy ``Policy`` objects
  are coerced via ``as_policy_fn``.
* **scan carry** — ``(FLState, energy [K] f32)``: global model, stacked client
  models/anchors, round counter, per-client last-transmission round, and the
  cumulative per-client energy ledger, all device arrays.
* **per-round PRNG** — ``jax.random.fold_in(base_key, t)``: the stream only
  depends on ``(seed, t)``, so the host loop and the scan engine draw
  bit-identical participation masks (the parity tests rely on this).
* **evals** — computed inside the scan at ``eval_every`` strides via
  ``lax.cond`` (off-stride rounds skip the forward pass when not vmapped).
* **scenario fan-out** — ``run_scenario_matrix`` vmaps the whole simulation
  over ρ (the tradeoff coefficient of (P1'), traced through ``solve_online``)
  × scenario lanes (channel realizations + PRNG seeds) in one device program;
  ``run_seed_matrix`` does the lane axis for arbitrary policies.

See ``docs/engine.md`` for the full architecture notes.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.channel import CellConfig, rate_nats
from ..core.selection import PolicyFn, as_policy_fn, online_policy
from ..data.device import (StreamingSampler, choose_data_path,
                           data_stream_key, from_client_datasets,
                           sample_round, sample_round_client_stream)
from ..data.pipeline import BatchIterator, client_batches
from ..data.synthetic import Dataset
from ..obs.taps import (MetricsSpec, init_metrics, metrics_active,
                        metrics_round_update)
from ..obs.telemetry import emit_run_manifest, get_telemetry
from ..optim import Optimizer, sgd
from .faults import (FaultConfig, FaultState, GuardConfig, apply_faults,
                     corrupt_deltas, init_fault_state)
from .state import (AggregatorConfig, FLState, broadcast_to_participants,
                    init_fl_state, masked_aggregate, pseudo_gradients,
                    round_weights)


@dataclasses.dataclass(frozen=True)
class SimConfig:
    rounds: int = 50
    local_iters: int = 5          # paper: 5 for MNIST, 1 for CIFAR
    batch_size: int = 10          # paper: 10 for MNIST, 128 for CIFAR
    lr: float = 0.01              # paper: 0.01
    eval_every: int = 5
    seed: int = 0
    max_staleness: int | None = None   # Δ_k enforcement (None = pure Bernoulli)
    aging_boost: bool = False          # beyond-paper: soft aging — raise p as
                                       # staleness → Δ_k so clients transmit at
                                       # the first decent fade *before* the
                                       # deadline forces a deep-fade upload
    eval_batch: int = 2048
    # data path: "auto" picks "device" (DeviceDataStore + in-scan sampling)
    # when the padded store fits the memory budget, else "stream" (host
    # blocks, double-buffered round-chunk prefetch).  "prestack" is the
    # legacy [T, K, L, B] pre-stack, kept as the parity/benchmark reference.
    data_path: str = "auto"
    stream_chunk: int = 256            # rounds per streamed chunk
    # local-training semantics: "continuous" (paper default — every client
    # runs local SGD every round, cost irreducibly O(K·T)) or "participants"
    # (only the transmitting set trains, from its last received global — the
    # sampled-FedAvg reading; what the sparse path accelerates).
    local_mode: str = "continuous"
    # round execution: "dense" ([K]-shaped round transition), "sparse"
    # (participant-centric two-phase path, see repro.fl.sparse), or "auto"
    # (sparse exactly when its preconditions hold — participants local mode,
    # state_free policy, device data path, per-client stream).
    participation: str = "dense"
    participant_bucket: int | None = None  # static padded transmitting-set
                                           # size (None = auto from E[Σp])
    # minibatch index stream: "round" draws one [K, L, B] block per round
    # from fold_in(data_key, t); "client" keys each client's draw separately
    # (fold_in(fold_in(data_key, t), k)) so a participant's batch can be
    # sampled without touching the other K-1 clients (sparse path needs it).
    data_stream: str = "round"
    # --- robustness layer (docs/robustness.md) -----------------------------
    # fault injection: None leaves the engine's program byte-for-byte
    # unchanged (the bit-parity guarantee); a FaultConfig threads jittable
    # availability/crash/uplink-loss/corruption processes through the scan.
    faults: FaultConfig | None = None
    # defensive aggregation: None (or an all-off GuardConfig) is
    # bit-identical to the plain eq.-3 update; otherwise non-finite
    # quarantine, norm clipping and staleness down-weighting apply.
    guards: GuardConfig | None = None
    # aggregation scheme: None keeps the paper's eq.-3 update on the exact
    # legacy code path (the byte-for-byte bit-parity guarantee); an
    # AggregatorConfig routes through the pluggable weighted path —
    # FedAsync-style s(Δτ) mixing, CSMAAFL importance weighting, or
    # Hu–Chen–Larsson age-aware weighting (docs/schemes.md).  Guards
    # compose with any scheme.
    aggregator: AggregatorConfig | None = None
    # eval placement: "inscan" evaluates at eval_every strides via lax.cond
    # inside the scan (both branches execute under vmap); "replay" skips
    # in-scan evals entirely — the resumable driver evaluates its strided
    # param checkpoints post-hoc in one batched pass (fl/resume.py).
    eval_mode: str = "inscan"
    # resumable execution: segment length for fl.resume.run_resumable (the
    # checkpoint stride); None = eval_every.
    checkpoint_every: int | None = None
    # sparse participant_bucket overflow handling: "spill" regrows the
    # bucket toward the dense width and reruns (warn once), "error" keeps
    # the legacy hard RuntimeError.
    overflow: str = "spill"
    # in-scan metrics taps (docs/observability.md): None (default) adds
    # nothing to any carry or program — the bit-parity guarantee; a
    # MetricsSpec threads fixed-shape accumulators (participation counts,
    # staleness histogram, energy by cause, guard events, weight stats)
    # through the scan carry and returns them on SimResult.metrics.
    metrics: MetricsSpec | None = None


class SimResult(NamedTuple):
    test_acc: np.ndarray        # [n_evals]
    test_loss: np.ndarray       # [n_evals]
    eval_rounds: np.ndarray     # [n_evals]
    energy_per_client: np.ndarray  # [K] cumulative Joules
    energy_timeline: np.ndarray    # [rounds] cumulative total energy
    participation: np.ndarray      # [rounds, K] realized decision masks
    state: FLState
    # fault-injection extras (None on clean runs — the legacy 7-field
    # contract is unchanged): what actually landed at the server after
    # availability/crash/uplink-loss, and which deliveries were corrupted.
    delivered: np.ndarray | None = None   # [rounds, K]
    corrupted: np.ndarray | None = None   # [rounds, K]
    # in-scan metrics accumulators (None unless cfg.metrics enables taps);
    # a repro.obs.taps.MetricsState of numpy arrays — feed metrics_summary.
    metrics: Any = None


class RoundTrace(NamedTuple):
    """Per-round scan outputs (leading axis T after the scan).

    ``delivered``/``corrupt`` mirror ``mask`` when faults are disabled (the
    fault pipeline is not even traced then — they are aliases of ``mask`` /
    zeros, adding nothing to the program).
    """

    mask: jax.Array      # [K] realized participation (the decision)
    e_round: jax.Array   # [K] Joules spent this round (incl. retry cost)
    acc: jax.Array       # scalar (0 when did_eval is False)
    loss: jax.Array      # scalar (0 when did_eval is False)
    did_eval: jax.Array  # bool scalar
    delivered: jax.Array  # [K] updates that actually landed at the server
    corrupt: jax.Array    # [K] bool — delivered but adversarially poisoned


# ---------------------------------------------------------------------------
# shared per-round pieces (scan engine AND legacy host loop use these, so the
# two execution modes agree bit-wise on identical PRNG streams)
# ---------------------------------------------------------------------------


def grant_forced_bandwidth(w: jax.Array, forced: jax.Array,
                           num_clients: int) -> jax.Array:
    """Staleness-aware bandwidth reservation (beyond-paper), corrected.

    A client transmitting only because its Δ_k bound expired would otherwise
    use its (possibly zero) probabilistic slice — grant it an equal 1/K
    share.  When Σw ≤ 1 still holds after granting, non-forced clients keep
    their server-optimal allocation untouched (the old implementation
    renormalized everyone, shrinking optimal slices too).  Only when the
    grant overflows the band do non-forced clients shrink, proportionally,
    into the remaining room — the forced grant is never scaled to zero
    (policies like greedy/age allocate w = 0 to unselected clients, so
    "rescale the granted shares into the leftover slack" would strand a
    forced client at w = 0 and blow up its eq.-5 energy).  Branch-free:
    with no forced client this is the identity.
    """
    forced_f = forced.astype(w.dtype)
    granted = jnp.where(forced, jnp.maximum(w, 1.0 / num_clients), w)
    g = jnp.sum(granted * forced_f)            # requested forced mass
    b = jnp.sum(w * (1.0 - forced_f))          # non-forced (optimal) mass
    # forced keep their grant, capped at the full band
    g_scale = jnp.where(g > 1.0, 1.0 / jnp.maximum(g, 1e-30), 1.0)
    room = 1.0 - jnp.minimum(g, 1.0)
    # non-forced shrink only when the grant leaves too little room
    nf_scale = jnp.where(b > room, room / jnp.maximum(b, 1e-30), 1.0)
    return jnp.where(forced, granted * g_scale, w * nf_scale)


def apply_round_decision(probs: jax.Array, w: jax.Array, t: jax.Array,
                         h_t: jax.Array, state: FLState, base_key: jax.Array,
                         cfg: SimConfig, cell: CellConfig, num_clients: int):
    """Protocol Steps 3-4 + energy ledger given the round's (probs, w).

    Returns ``(mask, forced, w, e_round)``; the PRNG stream is
    ``fold_in(base_key, t)`` so it only depends on ``(seed, t)``.
    """
    K = num_clients
    probs = probs.astype(jnp.float32)
    w = w.astype(jnp.float32)
    staleness = (state.round - state.last_tx).astype(jnp.float32)
    if cfg.aging_boost and cfg.max_staleness is not None:
        boost = jnp.clip(staleness / cfg.max_staleness, 0.0, 1.0) ** 2
        probs = 1.0 - (1.0 - probs) * (1.0 - boost)
    u = jax.random.uniform(jax.random.fold_in(base_key, t), (K,))
    mask = (u < probs).astype(jnp.float32)
    forced = jnp.zeros((K,), bool)
    if cfg.max_staleness is not None:
        stale = (state.round - state.last_tx) >= cfg.max_staleness
        forced = stale & (mask == 0.0)
        mask = jnp.maximum(mask, stale.astype(jnp.float32))
        w = grant_forced_bandwidth(w, forced, K)
    R = rate_nats(w, h_t, cell.tx_power_w, cell.bandwidth_hz,
                  cell.noise_w_per_hz)
    e_round = mask * cell.tx_power_w * cell.model_size_nats \
        / jnp.maximum(R, 1e-30)
    e_round = jnp.where(mask > 0.0, e_round, 0.0)
    return mask, forced, w, e_round


def round_decision(policy_fn: PolicyFn, t: jax.Array, h_t: jax.Array,
                   state: FLState, base_key: jax.Array, cfg: SimConfig,
                   cell: CellConfig, num_clients: int):
    """Protocol Steps 2-4 for one round: policy then
    :func:`apply_round_decision` (the legacy host loop's per-round path)."""
    probs, w = policy_fn(t, h_t, state)
    return apply_round_decision(probs, w, t, h_t, state, base_key, cfg, cell,
                                num_clients)


def make_local_train(loss_fn: Callable, opt: Optimizer):
    """vmapped-over-clients local SGD: ``(params, xb, yb, *frozen) -> params``
    with ``xb: [K, local_iters, B, ...]``.  A ``frozen`` pytree (a model part
    no client trains, such as the base under LoRA adapters) goes to
    ``loss_fn(params, x, y, frozen)`` unbatched and gets no gradient."""

    def local_train(params, xb, yb, *frozen):
        opt_state = opt.init(params)

        def one(carry, batch):
            params, opt_state = carry
            x, y = batch
            g = jax.grad(loss_fn)(params, x, y, *frozen)
            upd, opt_state = opt.update(g, opt_state, params)
            params = jax.tree_util.tree_map(lambda p, u: p + u, params, upd)
            return (params, opt_state), None

        (params, _), _ = jax.lax.scan(one, (params, opt_state), (xb, yb))
        return params

    def vtrain(params, xb, yb, *frozen):
        return jax.vmap(local_train, in_axes=(0, 0, 0) + (None,) * len(frozen)
                        )(params, xb, yb, *frozen)

    return vtrain


def empty_client_batches(client_data: Sequence[Dataset], cfg: SimConfig):
    """``[K, 0, B, ...]`` placeholder pair for protocol-only runs
    (``local_iters=0``): the training scan is a no-op, clients never move."""
    K = len(client_data)
    sample = client_data[0].x.shape[1:]
    b = min(cfg.batch_size, min(len(c.y) for c in client_data))
    return (jnp.zeros((K, 0, b) + tuple(sample)),
            jnp.zeros((K, 0, b), jnp.int32))


def stack_round_batches(client_data: Sequence[Dataset], cfg: SimConfig):
    """Pre-draw every round's batches with the legacy iterator streams.

    Returns ``(xb_all, yb_all)`` shaped ``[T, K, local_iters, B, ...]`` —
    consumption order (round-major, local-iter-minor, per-client seeded
    ``cfg.seed + 17k``) matches the host loop exactly, so both engines train
    on identical data.  MNIST-scale footprint: T·K·L·B·784 fp32 ≈ 125 MB at
    (T=50, K=16, L=5, B=10); for larger worlds switch to on-device sampling.
    """
    K = len(client_data)
    if cfg.local_iters == 0:
        xb, yb = empty_client_batches(client_data, cfg)
        return (jnp.zeros((cfg.rounds,) + xb.shape, xb.dtype),
                jnp.zeros((cfg.rounds,) + yb.shape, yb.dtype))
    iters = [BatchIterator(ds, cfg.batch_size, seed=cfg.seed + 17 * k)
             for k, ds in enumerate(client_data)]
    xs, ys = [], []
    for _ in range(cfg.rounds):
        xt, yt = [], []
        for _ in range(cfg.local_iters):
            xb, yb = client_batches(iters)
            xt.append(xb)
            yt.append(yb)
        xs.append(jnp.stack(xt, axis=1))   # [K, L, B, ...]
        ys.append(jnp.stack(yt, axis=1))
    return jnp.stack(xs), jnp.stack(ys)    # [T, K, L, B, ...]


def resolve_data_path(client_data: Sequence[Dataset], cfg: SimConfig,
                      override: str | None = None,
                      budget_bytes: int | None = None) -> str:
    """Resolve ``cfg.data_path`` to a concrete path name.

    ``"auto"`` consults :func:`repro.data.device.choose_data_path` (padded
    store footprint vs the device memory budget); explicit names pass
    through.  Both engines (scan and legacy host loop) resolve through this
    single function so they always consume the same minibatch stream.
    """
    path = override or cfg.data_path
    if path == "auto":
        path = choose_data_path(client_data, budget_bytes)
    if path not in ("prestack", "device", "stream"):
        raise ValueError(f"unknown data_path {path!r} "
                         "(expected auto|prestack|device|stream)")
    if cfg.data_stream not in ("round", "client"):
        raise ValueError(f"unknown data_stream {cfg.data_stream!r} "
                         "(expected round|client)")
    if cfg.data_stream == "client" and path != "device":
        raise ValueError(
            "the per-client minibatch stream is defined on the device data "
            f"path only (resolved path: {path!r}); pass data_path='device'")
    return path


# ---------------------------------------------------------------------------
# the scan engine
# ---------------------------------------------------------------------------


def _client_mesh(num_clients: int):
    """1-D ``("k",)`` mesh over the largest divisor-of-K device prefix, or
    ``None`` when only one device is visible (sharding becomes a no-op)."""
    devs = jax.devices()
    if len(devs) <= 1:
        return None
    d = max(i for i in range(1, min(len(devs), num_clients) + 1)
            if num_clients % i == 0)
    if d <= 1:
        return None
    from jax.sharding import Mesh
    return Mesh(np.asarray(devs[:d]), ("k",))


def init_carry(params: Any, num_clients: int, cfg: SimConfig):
    """The scan carry: ``(FLState, energy)``, plus the per-client
    :class:`~repro.fl.faults.FaultState` when fault injection is on.  The
    faults-off structure is exactly the pre-robustness carry — existing
    programs are untouched."""
    state0 = init_fl_state(params, num_clients)
    energy0 = jnp.zeros((num_clients,), jnp.float32)
    carry = (state0, energy0)
    if cfg.faults is not None:
        carry = carry + (init_fault_state(num_clients),)
    ms = init_metrics(cfg.metrics, num_clients, cfg.guards)
    if ms is not None:       # metrics taps ride last in the carry
        carry = carry + (ms,)
    return carry


def _make_round_step(vtrain: Callable, loss_fn: Callable, acc_fn: Callable,
                     cfg: SimConfig, cell: CellConfig, num_clients: int,
                     policy_fn: PolicyFn, hoist: bool,
                     use_pallas: bool | None = None):
    """The per-round transition shared by every execution mode (full scan
    over pre-stacked batches, in-scan device-store sampling, streaming
    round-chunks): protocol Steps 1-5, fault pipeline, energy ledger,
    defensive aggregation, strided eval.  ``use_pallas`` goes to the
    aggregation (see :func:`repro.fl.state.masked_aggregate`)."""
    K = num_clients
    faults = cfg.faults
    guards = cfg.guards
    agg = cfg.aggregator
    tapped = metrics_active(cfg.metrics, guards)
    if cfg.eval_mode not in ("inscan", "replay"):
        raise ValueError(f"unknown eval_mode {cfg.eval_mode!r} "
                         "(expected inscan|replay)")

    def round_step(carry, t, h_t, xb, yb, pw, base_key, test_x, test_y,
                   fp=None, ap=None, frozen=()):
        state, energy = carry[0], carry[1]
        if faults is not None:
            fstate = carry[2]
        if tapped:
            mstate = carry[-1]
        # --- Steps 2-4: policy, Bernoulli draws, Δ_k, energy (eq. 5) -------
        probs, w = pw if hoist else policy_fn(t, h_t, state)
        mask, forced, w, e_round = apply_round_decision(
            probs, w, t, h_t, state, base_key, cfg, cell, K)
        # decision energy before the fault pipeline — the taps' retry-
        # overhead lane is Σ relu(paid − decided)
        e_base = e_round
        # --- fault pipeline: availability → crash → lossy uplink -----------
        # (salted fold_in streams — the decision draw above is untouched)
        if faults is not None:
            out, fstate = apply_faults(t, base_key, mask, e_round, fstate,
                                       fp, faults)
            delivered, corrupt, e_round = out.delivered, out.corrupt, \
                out.e_round
        else:
            delivered = mask
            corrupt = jnp.zeros((K,), bool)
        energy = energy + e_round
        # --- Step 1 (local training) + Steps 4-5 ---------------------------
        client = vtrain(state.client_params, xb, yb, *frozen)
        if cfg.local_mode == "participants":
            # only clients whose update lands move; everyone else keeps
            # client == anchor (their pseudo-gradient stays exactly zero —
            # a crashed/lost upload's training is discarded with it)
            def keep(new, old):
                m = delivered.reshape(
                    (-1,) + (1,) * (new.ndim - 1)).astype(bool)
                return jnp.where(m, new, old)

            client = jax.tree_util.tree_map(keep, client,
                                            state.client_params)
        elif cfg.local_mode != "continuous":
            raise ValueError(f"unknown local_mode {cfg.local_mode!r} "
                             "(expected continuous|participants)")
        state = state._replace(client_params=client)
        deltas = pseudo_gradients(state)
        if faults is not None:
            deltas = corrupt_deltas(deltas, corrupt, fp, faults)
        # a scheme's weights come from the staleness ledger and the
        # policy's *nominal* probs (pre-boost — the csmaafl importance
        # weight debiases the policy, not the aging heuristic on top of it)
        staleness = state.round - state.last_tx
        ap_eff = None if agg is None else (agg.params() if ap is None else ap)
        w_agg, denom, d_agg = round_weights(delivered, K, staleness, probs,
                                            ap_eff, guards, deltas)
        new_global = masked_aggregate(state.global_params, d_agg, w_agg,
                                      denom, use_pallas=use_pallas)
        if tapped:
            mstate = metrics_round_update(
                mstate, cfg.metrics, mask=mask, forced=forced, e_base=e_base,
                e_round=e_round, staleness=staleness,
                delivered=delivered, deltas=deltas, probs=probs,
                num_clients=K, guards=guards, agg_params=ap_eff)
        state = broadcast_to_participants(state, new_global, delivered)

        # --- strided eval (stays on device; read back once at the end).
        # "replay" skips the cond entirely — the resumable driver evaluates
        # its strided param checkpoints post-hoc instead (both lax.cond
        # branches execute under vmap, so matrix sweeps want this off) -----
        if cfg.eval_mode == "replay":
            acc = jnp.zeros((), jnp.float32)
            loss = jnp.zeros((), jnp.float32)
            do_eval = jnp.zeros((), bool)
        else:
            def eval_now(p):
                return (jnp.asarray(acc_fn(p, test_x, test_y, *frozen),
                                    jnp.float32),
                        jnp.asarray(loss_fn(p, test_x, test_y, *frozen),
                                    jnp.float32))

            def skip_eval(p):
                del p
                return jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)

            do_eval = jnp.logical_or(t % cfg.eval_every == 0,
                                     t == cfg.rounds - 1)
            acc, loss = jax.lax.cond(do_eval, eval_now, skip_eval,
                                     state.global_params)
        carry = (state, energy)
        if faults is not None:
            carry = carry + (fstate,)
        if tapped:
            carry = carry + (mstate,)
        return carry, RoundTrace(mask, e_round, acc, loss, do_eval,
                                 delivered, corrupt)

    return round_step


def build_scan_sim(loss_fn: Callable, acc_fn: Callable, opt: Optimizer,
                   cfg: SimConfig, cell: CellConfig, num_clients: int,
                   policy_fn: PolicyFn, shard_clients: bool | None = None,
                   data_mode: str = "prestack"):
    """Build the pure simulation function (one ``lax.scan`` over all rounds).

    ``data_mode`` selects how the scan obtains its minibatches:

    * ``"prestack"`` — ``simulate(params, xb_all, yb_all, h_rounds, base_key,
      test_x, test_y)``: batches arrive as ``[T, K, L, B, ...]`` scan inputs
      (the legacy layout; peak memory grows linearly in T).
    * ``"device"`` — ``simulate(params, store, data_key, h_rounds, base_key,
      test_x, test_y)``: each round gathers its batch from a
      :class:`~repro.data.device.DeviceDataStore` *inside* the scan body via
      the ``fold_in(data_key, t)`` stream — no T-proportional buffer exists
      anywhere in the program.

    Either way the returned function yields ``(FLState, energy [K],
    RoundTrace[T])`` and is traceable end-to-end: jit it for a single run,
    vmap it over ``(base_key, h_rounds)`` (and a traced ρ via the policy
    closure) for scenario fan-out.  ``h_rounds`` is round-major ``[T, K]``.
    Its ``frozen`` keyword takes a pytree that every client's
    ``loss_fn``/``acc_fn`` receives as a fourth argument and nobody trains
    (a LoRA base); the FL state, the aggregation and the ledger see only
    ``params``.

    Policies tagged ``state_free`` (all five paper schemes) are hoisted out
    of the sequential scan: every round's ``(probs, w)`` is computed in one
    ``vmap`` over ``t`` before the scan — T serial (P1') solves become one
    batched solve, still inside the same device program.  Untagged policies
    (anything reading the carried ``FLState``) run inside the scan body.

    ``shard_clients`` (default auto): when multiple devices are visible and
    divide K, the client axis — the data-parallel mesh axis of the FL state —
    is sharded via ``shard_map`` for the local-training leg, and GSPMD
    propagates the sharding through the aggregation/broadcast tree ops (in
    device mode the store's client axis is placed on the same mesh by
    ``make_runner``).  Auto-disabled on a single device; pass ``False`` to
    force off (the vmap matrix runners do, sharding does not compose with
    their lane axis).
    """
    K = num_clients
    vtrain = make_local_train(loss_fn, opt)
    hoist = getattr(policy_fn, "state_free", False)
    mesh = _client_mesh(K) if shard_clients in (None, True) else None
    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        local = vtrain

        def vtrain(params, xb, yb, *frozen):   # frozen is replicated
            return jax.shard_map(
                local, mesh=mesh,
                in_specs=(P("k"), P("k"), P("k")) + (P(),) * len(frozen),
                out_specs=P("k"))(params, xb, yb, *frozen)
    # XLA cannot partition a Pallas kernel over the sharded client axis
    # ("Mosaic kernels cannot be automatically partitioned"), so a sharded
    # run aggregates with the jnp path: a per-device fused reduce of its
    # own rows plus one all-reduce of the model
    round_step = _make_round_step(vtrain, loss_fn, acc_fn, cfg, cell, K,
                                  policy_fn, hoist,
                                  use_pallas=False if mesh is not None
                                  else None)

    def hoisted_policy(h_rounds):
        ts = jnp.arange(cfg.rounds, dtype=jnp.int32)
        return jax.vmap(lambda t, h: policy_fn(t, h, None))(ts, h_rounds)

    def _resolve_pw(h_rounds, pw_all):
        if hoist:
            return hoisted_policy(h_rounds) if pw_all is None else pw_all
        # dummy per-round operands; the policy runs in the scan body
        return (jnp.zeros((cfg.rounds, 0)),) * 2

    def _resolve_fp(fault_params):
        if cfg.faults is None:
            return None
        return cfg.faults.params() if fault_params is None else fault_params

    def _resolve_ap(agg_params):
        if cfg.aggregator is None:
            return None
        return cfg.aggregator.params() if agg_params is None else agg_params

    tapped = metrics_active(cfg.metrics, cfg.guards)

    def _scan(params, step, xs):
        carry0 = init_carry(params, K, cfg)
        final, traces = jax.lax.scan(step, carry0, xs)
        state, energy = final[0], final[1]
        if tapped:       # 4-tuple only when taps materialize (static on cfg)
            return state, energy, traces, final[-1]
        return state, energy, traces

    if data_mode == "prestack":
        def simulate(params, xb_all, yb_all, h_rounds, base_key, test_x,
                     test_y, pw_all=None, fault_params=None, agg_params=None,
                     frozen=None):
            ts_all = jnp.arange(cfg.rounds, dtype=jnp.int32)
            pw_all = _resolve_pw(h_rounds, pw_all)
            fp = _resolve_fp(fault_params)
            ap = _resolve_ap(agg_params)
            fz = () if frozen is None else (frozen,)

            def step(carry, xs):
                t, h_t, xb, yb, pw = xs
                return round_step(carry, t, h_t, xb, yb, pw, base_key,
                                  test_x, test_y, fp=fp, ap=ap, frozen=fz)

            return _scan(params, step, (ts_all, h_rounds, xb_all, yb_all,
                                        pw_all))
    elif data_mode == "device":
        def simulate(params, store, data_key, h_rounds, base_key, test_x,
                     test_y, pw_all=None, fault_params=None, agg_params=None,
                     frozen=None):
            ts_all = jnp.arange(cfg.rounds, dtype=jnp.int32)
            pw_all = _resolve_pw(h_rounds, pw_all)
            fp = _resolve_fp(fault_params)
            ap = _resolve_ap(agg_params)
            fz = () if frozen is None else (frozen,)

            sample = (sample_round_client_stream
                      if cfg.data_stream == "client" else sample_round)

            def step(carry, xs):
                t, h_t, pw = xs
                xb, yb = sample(store, data_key, t, cfg.local_iters,
                                cfg.batch_size)
                return round_step(carry, t, h_t, xb, yb, pw, base_key,
                                  test_x, test_y, fp=fp, ap=ap, frozen=fz)

            return _scan(params, step, (ts_all, h_rounds, pw_all))
    else:
        raise ValueError(f"unknown data_mode {data_mode!r}")

    # under client-axis sharding the tiny [T, K] policy solve pays SPMD
    # partitioning overhead inside the main program — callers (make_runner)
    # run it as its own replicated jit and pass pw_all in
    simulate.split_policy = hoist and mesh is not None
    simulate.hoisted_policy = hoisted_policy
    simulate.mesh = mesh
    return simulate


def build_chunk_sim(loss_fn: Callable, acc_fn: Callable, opt: Optimizer,
                    cfg: SimConfig, cell: CellConfig, num_clients: int,
                    policy_fn: PolicyFn, data_mode: str = "prestack"):
    """Streaming/resumable building block: the identical round transition
    scanned over one round-*chunk* with an explicit carry (see
    :func:`init_carry` — ``(FLState, energy[, FaultState])``).

    ``data_mode="prestack"``: ``chunk(carry, ts, h, xb, yb, pw, base_key,
    test_x, test_y, fault_params=None)`` consumes absolute round ids ``ts``
    (so ``fold_in(·, t)`` streams and the eval-stride/final-round conditions
    match the single-scan engines bit-wise) and chunk-major batch arrays
    ``[C, K, L, B, ...]``; the host loop threads the carry across chunks
    (see ``make_runner``'s stream path).

    ``data_mode="device"``: ``chunk(carry, ts, h, pw, store, data_key,
    base_key, test_x, test_y, fault_params=None)`` gathers each round's
    batch from the resident store inside the chunk body — what the
    resumable driver (:mod:`repro.fl.resume`) runs segment by segment.
    """
    vtrain = make_local_train(loss_fn, opt)
    hoist = getattr(policy_fn, "state_free", False)
    round_step = _make_round_step(vtrain, loss_fn, acc_fn, cfg, cell,
                                  num_clients, policy_fn, hoist)

    def _fp(fault_params):
        if cfg.faults is None:
            return None
        return cfg.faults.params() if fault_params is None else fault_params

    def _ap(agg_params):
        if cfg.aggregator is None:
            return None
        return cfg.aggregator.params() if agg_params is None else agg_params

    if data_mode == "prestack":
        def chunk(carry, ts, h, xb, yb, pw, base_key, test_x, test_y,
                  fault_params=None, agg_params=None):
            fp = _fp(fault_params)
            ap = _ap(agg_params)

            def step(c, xs):
                t, h_t, xbt, ybt, pwt = xs
                return round_step(c, t, h_t, xbt, ybt, pwt, base_key,
                                  test_x, test_y, fp=fp, ap=ap)

            return jax.lax.scan(step, carry, (ts, h, xb, yb, pw))
    elif data_mode == "device":
        def chunk(carry, ts, h, pw, store, data_key, base_key, test_x,
                  test_y, fault_params=None, agg_params=None):
            fp = _fp(fault_params)
            ap = _ap(agg_params)
            sample = (sample_round_client_stream
                      if cfg.data_stream == "client" else sample_round)

            def step(c, xs):
                t, h_t, pwt = xs
                xb, yb = sample(store, data_key, t, cfg.local_iters,
                                cfg.batch_size)
                return round_step(c, t, h_t, xb, yb, pwt, base_key,
                                  test_x, test_y, fp=fp, ap=ap)

            return jax.lax.scan(step, carry, (ts, h, pw))
    else:
        raise ValueError(f"unknown data_mode {data_mode!r}")

    chunk.hoist = hoist
    return chunk


def _nbytes(tree) -> int:
    return sum(int(x.size) * np.dtype(x.dtype).itemsize
               for x in jax.tree_util.tree_leaves(tree))


def _to_host(x) -> np.ndarray:
    """One device→host copy, counted in ``engine.d2h_transfers``."""
    get_telemetry().inc("engine.d2h_transfers")
    return np.asarray(x)


def _host_fields(energy, traces, cfg: SimConfig, mstate=None) -> dict:
    """The run's outputs a ``SimResult`` is read from (``None`` where the
    configuration has no such output)."""
    faulty = cfg.faults is not None
    return {"did_eval": traces.did_eval, "e_round": traces.e_round,
            "acc": traces.acc, "loss": traces.loss, "energy": energy,
            "mask": traces.mask,
            "delivered": traces.delivered if faulty else None,
            "corrupt": traces.corrupt if faulty else None,
            "metrics": mstate}


def _host_result(state, host: dict) -> SimResult:
    """``SimResult`` from :func:`_host_fields` already on the host."""
    idx = np.where(host["did_eval"])[0]
    return SimResult(
        test_acc=host["acc"][idx],
        test_loss=host["loss"][idx],
        eval_rounds=idx,
        energy_per_client=host["energy"],
        energy_timeline=np.cumsum(host["e_round"].sum(axis=1)),
        participation=host["mask"],
        state=state,
        delivered=host["delivered"],
        corrupted=host["corrupt"],
        metrics=host["metrics"],
    )


def _to_result(state, energy, traces, cfg: SimConfig,
               mstate=None) -> SimResult:
    """End-of-run host readback → legacy ``SimResult``: one copy per array
    the host reads."""
    return _host_result(state, jax.tree_util.tree_map(
        _to_host, _host_fields(energy, traces, cfg, mstate)))


@functools.partial(jax.tree_util.register_dataclass, data_fields=["words"],
                   meta_fields=["layout"])
@dataclasses.dataclass(frozen=True)
class _Record:
    """A pytree of arrays packed into one flat ``uint32`` buffer.  The
    layout (tree structure, each leaf's dtype and shape) is static pytree
    metadata, so it rides in the compiled program's output structure."""

    words: Any
    layout: tuple


def _pack(tree) -> _Record:
    """Pack ``tree``'s leaves (bool and 32-bit) into one ``uint32`` buffer,
    bit for bit: bools widened, the rest by ``bitcast_convert_type``."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    words = []
    for x in leaves:
        if x.dtype == jnp.bool_:
            words.append(x.astype(jnp.uint32).reshape(-1))
        elif x.dtype.itemsize == 4:
            words.append(jax.lax.bitcast_convert_type(x, jnp.uint32)
                         .reshape(-1))
        else:
            raise TypeError(f"cannot pack a {x.dtype} leaf into 32-bit words")
    layout = (treedef, tuple((np.dtype(x.dtype), x.shape) for x in leaves))
    return _Record(jnp.concatenate(words), layout)


def _unpack(words: np.ndarray, layout: tuple):
    """Inverse of :func:`_pack` on the host copy of ``words``."""
    treedef, specs = layout
    leaves, at = [], 0
    for dt, shape in specs:
        n = math.prod(shape)
        w = words[at:at + n].reshape(shape)
        at += n
        leaves.append(w != 0 if dt == np.bool_ else w.view(dt))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _make_stream_runner(loss_fn: Callable, acc_fn: Callable,
                        client_data: Sequence[Dataset], test_x, test_y,
                        policy_fn: PolicyFn, cell: CellConfig, cfg: SimConfig,
                        opt: Optimizer) -> Callable:
    """Host-streaming execution: the horizon is split into
    ``cfg.stream_chunk``-round segments; chunk ``i+1``'s batches are gathered
    host-side (same ``fold_in`` index stream as the device store — batches
    are bit-identical) and ``device_put`` while chunk ``i`` computes, so
    device-resident data never exceeds two chunks regardless of T or the
    dataset size."""
    K = len(client_data)
    T = cfg.rounds
    sampler = StreamingSampler(client_data, data_stream_key(cfg.seed),
                               cfg.local_iters, cfg.batch_size)
    raw = build_chunk_sim(loss_fn, acc_fn, opt, cfg, cell, K, policy_fn)
    hoist = raw.hoist
    tapped = metrics_active(cfg.metrics, cfg.guards)
    chunk_fn = jax.jit(raw)
    ts_full = jnp.arange(T, dtype=jnp.int32)
    pol = (jax.jit(jax.vmap(lambda t, h: policy_fn(t, h, None)))
           if hoist else None)
    C = max(1, int(cfg.stream_chunk))
    bounds = [(t0, min(t0 + C, T)) for t0 in range(0, T, C)]

    def run(params, h_all, seed):
        key = jax.random.PRNGKey(cfg.seed if seed is None else seed)
        h_rounds = jnp.swapaxes(h_all, 0, 1)
        pw_full = (pol(ts_full, h_rounds) if hoist
                   else (jnp.zeros((T, 0)),) * 2)
        carry = init_carry(params, K, cfg)
        buf = sampler.chunk(*bounds[0])
        traces = []
        for i, (t0, t1) in enumerate(bounds):
            pw_c = jax.tree_util.tree_map(lambda p: p[t0:t1], pw_full)
            carry, tr = chunk_fn(carry, ts_full[t0:t1], h_rounds[t0:t1],
                                 buf[0], buf[1], pw_c, key, test_x, test_y)
            if i + 1 < len(bounds):   # prefetch overlaps the async chunk
                buf = sampler.chunk(*bounds[i + 1])
            traces.append(tr)
        traces = jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=0), *traces)
        return carry, traces

    def runner(params, h_all, seed: int | None = None) -> SimResult:
        tel = get_telemetry()
        with tel.span("engine.call", call_id=tel.inc("engine.calls")):
            carry, traces = run(params, h_all, seed)
            with tel.span("engine.readback"):
                return _to_result(carry[0], carry[1], traces, cfg,
                                  mstate=carry[-1] if tapped else None)

    return runner


def make_runner(loss_fn: Callable, acc_fn: Callable,
                client_data: Sequence[Dataset], test_ds: Dataset, policy,
                cell: CellConfig, cfg: SimConfig,
                opt: Optimizer | None = None,
                shard_clients: bool | None = None,
                data_path: str | None = None,
                data_budget_bytes: int | None = None,
                frozen: Any = None) -> Callable:
    """Pre-build the compiled scan runner for repeated invocations.

    Returns ``runner(params, h_all, seed=None) -> SimResult``; the jitted
    scan program and the data source (device store, streamed blocks, or the
    legacy pre-stack) are built once and reused, so successive calls (new
    channel draws, new PRNG seeds, warm benchmarking) pay zero
    re-trace/re-pack cost.

    ``data_path`` overrides ``cfg.data_path`` (``"auto"`` resolves by
    footprint; see :func:`resolve_data_path`).  On the device path the
    store's client axis is placed on the same mesh as the FL state whenever
    client-axis sharding is active.

    ``frozen`` is a pytree every client's model reads and nobody trains,
    such as the base model under LoRA adapters: ``loss_fn`` and ``acc_fn``
    then take it as a fourth argument, ``params`` holds only what clients
    train, and the FL state, the aggregation, the eq.-5 ledger and the
    packed record see only ``params``.  It goes into the program as an
    argument of every call (never baked into the executable).  The dense
    device and prestack paths take it; the sparse and streaming paths do
    not yet.

    The dense scan runner crosses the host boundary once each way per call:
    the seed and ``h_all`` go into its program as host values (no eager
    device work before the launch), and every array the ``SimResult`` reads
    comes back in one packed ``uint32`` record, copied once and unpacked
    bit for bit on the host; ``SimResult.state`` stays on the device.  It
    counts, per call, ``engine.train_tokens`` (K·T·L·B times the positions
    of one label: tokens for sequence targets, examples for class labels)
    and ``engine.upload_bytes`` (realized uploads × the bytes of
    ``params``).  It also carries ``runner.call_args(params, h_all,
    seed=None)`` and
    ``runner.lower(params, h_all, seed=None)`` — its program's arguments,
    and its program lowered, for that call — and ``runner.mesh``, the
    client-axis mesh (``None`` when unsharded).
    """
    K = len(client_data)
    policy_fn = as_policy_fn(policy)
    test_x = test_ds.x[: cfg.eval_batch]
    test_y = test_ds.y[: cfg.eval_batch]
    path = resolve_data_path(client_data, cfg, data_path, data_budget_bytes)

    from .sparse import make_sparse_runner, resolve_participation
    if resolve_participation(cfg, policy_fn, path, K) == "sparse":
        if frozen is not None:
            raise ValueError("the sparse runner does not take a frozen model "
                             "part; use participation='dense'")
        # opt passed un-defaulted: the sparse runner tokens the default
        # optimizer by (kind, lr) so its participant-program cache hits
        # across runners (a fresh sgd() closure per call would miss on id)
        return make_sparse_runner(loss_fn, acc_fn, client_data, test_ds,
                                  policy_fn, cell, cfg, opt=opt)
    opt = opt or sgd(cfg.lr)
    emit_run_manifest("make_runner", cfg,
                      extra={"path": path, "num_clients": K})

    if path == "stream":
        if frozen is not None:
            raise ValueError("the streaming runner does not take a frozen "
                             "model part; use data_path='device'")
        return _make_stream_runner(loss_fn, acc_fn, client_data, test_x,
                                   test_y, policy_fn, cell, cfg, opt)

    sim = build_scan_sim(loss_fn, acc_fn, opt, cfg, cell, K, policy_fn,
                         shard_clients=shard_clients, data_mode=path)
    tapped = metrics_active(cfg.metrics, cfg.guards)

    def program(params, data, h_all, seed, test_x, test_y, pw_all, *frozen):
        """One call on the device: the key and the round-major channel are
        made here from the raw host values, and what the host reads comes
        back as one packed record beside the device-resident state."""
        key = jax.random.PRNGKey(seed)
        out = sim(params, *data, jnp.swapaxes(h_all, 0, 1), key, test_x,
                  test_y, pw_all=pw_all, frozen=frozen[0] if frozen else None)
        return out[0], _pack(_host_fields(out[1], out[2], cfg,
                                          out[3] if tapped else None))

    simulate = jax.jit(program)
    policy_pre = (jax.jit(lambda h_all: sim.hoisted_policy(
        jnp.swapaxes(h_all, 0, 1))) if sim.split_policy else None)

    if path == "device":
        store = from_client_datasets(client_data)
        if sim.mesh is not None:
            from ..launch.sharding import client_axis_shardings
            store = jax.device_put(
                store, client_axis_shardings(store, sim.mesh, "k"))
        data = (store, data_stream_key(cfg.seed))
    else:
        data = stack_round_batches(client_data, cfg)

    def call_args(params, h_all, seed=None):
        """The program's arguments for this call.  The seed goes in as a
        ``uint32``: in-program ``PRNGKey`` of it is bit-identical to the
        eager ``PRNGKey(seed)`` (32-bit mode keys any integer by its low 32
        bits); ``h_all`` goes in as given."""
        seed = np.uint32(int(cfg.seed if seed is None else seed) % 2 ** 32)
        pw = policy_pre(h_all) if policy_pre is not None else None
        return (params, data, h_all, seed, test_x, test_y, pw) + fz

    fz = () if frozen is None else (frozen,)
    tokens_per_call = (K * cfg.rounds * cfg.local_iters * cfg.batch_size
                       * math.prod(np.shape(client_data[0].y)[1:]))

    def runner(params, h_all, seed: int | None = None) -> SimResult:
        tel = get_telemetry()
        with tel.span("engine.call", call_id=tel.inc("engine.calls")):
            with tel.span("engine.prepare"):
                args = call_args(params, h_all, seed)
            with tel.span("engine.dispatch"):
                state, record = simulate(*args)
            with tel.span("engine.wait"):   # the one copy waits for the device
                words = _to_host(record.words)
            with tel.span("engine.readback"):
                res = _host_result(state, _unpack(words, record.layout))
                up = (res.participation if res.delivered is None
                      else res.delivered)
                tel.inc("engine.train_tokens", tokens_per_call)
                tel.inc("engine.upload_bytes",
                        int(np.sum(up)) * _nbytes(params))
                return res

    def lower(params, h_all, seed: int | None = None):
        """The runner's jitted program, lowered for exactly this call."""
        return simulate.lower(*call_args(params, h_all, seed))

    runner.call_args = call_args
    runner.lower = lower
    runner.mesh = sim.mesh
    return runner


def run_simulation_scan(init_params: Any,
                        loss_fn: Callable,
                        acc_fn: Callable,
                        client_data: Sequence[Dataset],
                        test_ds: Dataset,
                        policy,
                        h_all: jax.Array,           # [K, rounds]
                        cell: CellConfig,
                        cfg: SimConfig,
                        opt: Optimizer | None = None) -> SimResult:
    """Scan-engine drop-in for the legacy ``run_simulation`` signature."""
    return make_runner(loss_fn, acc_fn, client_data, test_ds, policy, cell,
                       cfg, opt)(init_params, h_all)


# ---------------------------------------------------------------------------
# scenario fan-out: vmap the whole simulation over lanes and ρ
# ---------------------------------------------------------------------------


class MatrixResult(NamedTuple):
    """Stacked traces; leading axes are the vmapped ones ([R, S, ...] for
    :func:`run_scenario_matrix`, [S, ...] for :func:`run_seed_matrix`)."""

    acc: np.ndarray          # [..., n_evals]
    loss: np.ndarray         # [..., n_evals]
    eval_rounds: np.ndarray  # [n_evals]
    energy: np.ndarray       # [..., K] cumulative per-client Joules
    e_round: np.ndarray      # [..., T, K]
    participation: np.ndarray  # [..., T, K]
    # per-lane MetricsState (leading axes = the vmapped ones) when
    # cfg.metrics enables taps; None otherwise.
    metrics: Any = None


def _matrix_result(energy, traces, mstate=None) -> MatrixResult:
    did = np.asarray(traces.did_eval)
    # did_eval depends only on t — identical across lanes; collapse to [T].
    did_t = did.reshape(-1, did.shape[-1])[0]
    idx = np.where(did_t)[0]
    return MatrixResult(
        acc=np.asarray(traces.acc)[..., idx],
        loss=np.asarray(traces.loss)[..., idx],
        eval_rounds=idx,
        energy=np.asarray(energy),
        e_round=np.asarray(traces.e_round),
        participation=np.asarray(traces.mask),
        metrics=(jax.tree_util.tree_map(np.asarray, mstate)
                 if mstate is not None else None),
    )


def run_seed_matrix(init_params, loss_fn, acc_fn, client_data, test_ds,
                    policy, h_stack: jax.Array, cell: CellConfig,
                    cfg: SimConfig, seeds: Sequence[int],
                    opt: Optimizer | None = None) -> MatrixResult:
    """vmap the scan engine over scenario lanes for one policy.

    ``h_stack: [S, K, T]`` stacked channel realizations (one per lane —
    seeds, placements, fading draws); ``seeds`` gives each lane its own
    participation PRNG stream.  One compiled device program runs every lane.

    Data rides along un-vmapped: the device store (or the legacy pre-stack
    when ``cfg.data_path`` forces it) is shared by all lanes, and the
    minibatch stream is keyed by ``cfg.seed`` only — lanes differ in
    channel/participation randomness, not in data.  A resolved ``"stream"``
    path falls back to the device store here (lane fan-out multiplies every
    buffer anyway, so host streaming buys nothing under vmap).
    """
    K = h_stack.shape[1]
    opt = opt or sgd(cfg.lr)
    policy_fn = as_policy_fn(policy)
    test_x = test_ds.x[: cfg.eval_batch]
    test_y = test_ds.y[: cfg.eval_batch]
    keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])
    h_rounds = jnp.swapaxes(h_stack, 1, 2)             # [S, T, K]
    path = resolve_data_path(client_data, cfg)
    if path == "prestack":
        xb_all, yb_all = stack_round_batches(client_data, cfg)
        simulate = build_scan_sim(loss_fn, acc_fn, opt, cfg, cell, K,
                                  policy_fn, shard_clients=False)
        fan = jax.jit(jax.vmap(
            lambda key, h: simulate(init_params, xb_all, yb_all, h, key,
                                    test_x, test_y)))
    else:
        store = from_client_datasets(client_data)
        data_key = data_stream_key(cfg.seed)
        simulate = build_scan_sim(loss_fn, acc_fn, opt, cfg, cell, K,
                                  policy_fn, shard_clients=False,
                                  data_mode="device")
        fan = jax.jit(jax.vmap(
            lambda key, h: simulate(init_params, store, data_key, h, key,
                                    test_x, test_y)))
    emit_run_manifest("run_seed_matrix", cfg,
                      extra={"lanes": len(seeds), "num_clients": K})
    out = fan(keys, h_rounds)
    tapped = metrics_active(cfg.metrics, cfg.guards)
    return _matrix_result(out[1], out[2],
                          mstate=out[3] if tapped else None)


def run_scenario_matrix(init_params, loss_fn, acc_fn, client_data, test_ds,
                        spec, h_stack: jax.Array, rhos: Sequence[float],
                        cfg: SimConfig, seeds: Sequence[int],
                        opt: Optimizer | None = None) -> MatrixResult:
    """ρ × lane fan-out of the paper's online scheme in one device program.

    The tradeoff coefficient ρ of (P1') is traced through ``solve_online``
    (see :func:`repro.core.online.solve_online`), so the full Fig. 6-9-style
    sweep — ρ on one vmap axis, channel/seed lanes on the other — compiles
    once and runs entirely on device.  Returns ``MatrixResult`` with leading
    axes ``[R, S]``.  Sweep K by calling once per client count (shapes
    change, so K cannot share a vmap axis).
    """
    K = h_stack.shape[1]
    cell = spec.cell
    opt = opt or sgd(cfg.lr)
    test_x = test_ds.x[: cfg.eval_batch]
    test_y = test_ds.y[: cfg.eval_batch]
    keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])
    h_rounds = jnp.swapaxes(h_stack, 1, 2)             # [S, T, K]
    # stream resolves to the device store here, as in run_seed_matrix
    path = resolve_data_path(client_data, cfg)
    if path == "prestack":
        data = stack_round_batches(client_data, cfg)
    else:
        data = (from_client_datasets(client_data), data_stream_key(cfg.seed))

    def one(rho, key, h):
        simulate = build_scan_sim(loss_fn, acc_fn, opt, cfg, cell, K,
                                  online_policy(spec, rho=rho),
                                  shard_clients=False,
                                  data_mode=("prestack" if path == "prestack"
                                             else "device"))
        return simulate(init_params, data[0], data[1], h, key, test_x, test_y)

    lanes = jax.vmap(one, in_axes=(None, 0, 0))        # scenario lanes
    fan = jax.jit(jax.vmap(lanes, in_axes=(0, None, None)))  # ρ axis
    emit_run_manifest("run_scenario_matrix", cfg,
                      extra={"rhos": len(rhos), "lanes": len(seeds),
                             "num_clients": K})
    out = fan(jnp.asarray(rhos, jnp.float32), keys, h_rounds)
    tapped = metrics_active(cfg.metrics, cfg.guards)
    return _matrix_result(out[1], out[2],
                          mstate=out[3] if tapped else None)

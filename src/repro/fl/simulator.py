"""Paper-faithful asynchronous-FL simulator (§II protocol, Fig. 1).

Per round t:
  1. every client runs ``local_iters`` SGD steps on its own shard
     (clients train continuously, regardless of participation);
  2. the server computes the round's policy (p_{k,t}, w_{k,t});
  3. each client independently draws Bernoulli(p_{k,t}) — optionally forced
     if its staleness exceeds its Δ_k bound;
  4. participants upload δ_k = x_k − y_k on their allocated sub-channel
     (energy ledger: P_k · S / R_{k,t});
  5. the server applies x ← x + (1/K)Σδ_k and broadcasts x to participants.

``run_simulation`` executes the whole horizon inside one ``lax.scan`` on
device (see :mod:`repro.fl.engine`); this module is the compatibility layer
that keeps the original signature.  ``run_simulation_legacy`` is the old
host-side round loop — same per-round helpers, same ``fold_in`` PRNG streams,
so the two agree bit-wise — kept for parity tests and the engine benchmark.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..core.channel import CellConfig
from ..core.selection import Policy, as_policy_fn
from ..data.device import (StreamingSampler, data_stream_key,
                           from_client_datasets, sample_round,
                           sample_round_client_stream)
from ..data.pipeline import BatchIterator, client_batches
from ..data.synthetic import Dataset
from ..obs.taps import (MetricsSpec, init_metrics, merge_metrics,
                        metrics_active, update_ledger_taps, update_train_taps)
from ..optim import Optimizer, sgd
from .engine import (SimConfig, SimResult, apply_round_decision,
                     empty_client_batches, make_local_train,
                     resolve_data_path, round_decision, run_simulation_scan)
from .faults import (FaultConfig, GuardConfig, apply_faults, corrupt_deltas,
                     init_fault_state)
from .state import (AggregatorConfig, FLState, broadcast_to_participants,
                    init_fl_state, masked_aggregate, pseudo_gradients,
                    round_weights)

__all__ = ["SimConfig", "SimResult", "run_simulation",
           "run_simulation_legacy", "make_round_fn"]


def make_round_fn(loss_fn: Callable, opt: Optimizer, local_iters: int,
                  num_clients: int, local_mode: str = "continuous",
                  faults: FaultConfig | None = None,
                  guards: GuardConfig | None = None,
                  aggregator: AggregatorConfig | None = None,
                  metrics: MetricsSpec | None = None):
    """Build the jitted per-round transition over stacked client states.

    With faults/guards the transition takes the fault pipeline's extra
    operands — ``fl_round(state, mask, xb, yb, delivered, corrupt)`` — and
    applies the same corruption transform and defensive aggregation as the
    scan engine's round step (the legacy loop is the bit-parity witness for
    the robustness layer too).  With ``aggregator`` set the transition also
    takes the round's nominal policy ``probs`` and applies the pluggable
    scheme aggregation instead of the paper's 1/K averaging.

    When ``metrics`` enables any train tap the transition additionally takes
    the running :class:`~repro.obs.taps.MetricsState` and returns
    ``(state, metrics_state)`` instead of the bare state (static on the
    spec, so the untapped signature is unchanged).
    """
    vtrain = make_local_train(loss_fn, opt)
    fparams = faults.params() if faults is not None else None
    aparams = aggregator.params() if aggregator is not None else None
    ttap = metrics_active(metrics, guards, parts="train")

    @jax.jit
    def fl_round(state: FLState, mask: jax.Array, xb: jax.Array,
                 yb: jax.Array, delivered: jax.Array | None = None,
                 corrupt: jax.Array | None = None,
                 probs: jax.Array | None = None,
                 mstate=None) -> FLState:
        landed = mask if delivered is None else delivered
        client = vtrain(state.client_params, xb, yb)
        if local_mode == "participants":
            def keep(new, old):
                m = landed.reshape(
                    (-1,) + (1,) * (new.ndim - 1)).astype(bool)
                return jnp.where(m, new, old)

            client = jax.tree_util.tree_map(keep, client,
                                            state.client_params)
        state = state._replace(client_params=client)
        deltas = pseudo_gradients(state)
        if faults is not None and corrupt is not None:
            deltas = corrupt_deltas(deltas, corrupt, fparams, faults)
        staleness = state.round - state.last_tx
        p = (jnp.zeros((num_clients,), jnp.float32) if probs is None
             else probs)
        w_agg, denom, d_agg = round_weights(landed, num_clients, staleness, p,
                                            aparams, guards, deltas)
        new_global = masked_aggregate(state.global_params, d_agg, w_agg,
                                      denom)
        if ttap:
            ms = update_train_taps(
                mstate, metrics, deltas=deltas, delivered=landed,
                staleness=staleness, probs=p,
                num_clients=num_clients, guards=guards, agg_params=aparams)
            return broadcast_to_participants(state, new_global, landed), ms
        return broadcast_to_participants(state, new_global, landed)

    return fl_round


def run_simulation(init_params: Any,
                   loss_fn: Callable,
                   acc_fn: Callable,
                   client_data: list[Dataset],
                   test_ds: Dataset,
                   policy: Policy,
                   h_all: jax.Array,           # [K, rounds] channel gains
                   cell: CellConfig,
                   cfg: SimConfig,
                   opt: Optimizer | None = None) -> SimResult:
    """One jitted ``lax.scan`` over all rounds (no per-round host sync)."""
    return run_simulation_scan(init_params, loss_fn, acc_fn, client_data,
                               test_ds, policy, h_all, cell, cfg, opt)


def run_simulation_legacy(init_params: Any,
                          loss_fn: Callable,
                          acc_fn: Callable,
                          client_data: list[Dataset],
                          test_ds: Dataset,
                          policy: Policy,
                          h_all: jax.Array,
                          cell: CellConfig,
                          cfg: SimConfig,
                          opt: Optimizer | None = None) -> SimResult:
    """Host-side round loop (the pre-scan engine).

    Each round syncs mask/energy through numpy and dispatches the jitted
    round transition separately — kept as the wall-clock baseline for
    ``benchmarks/bench_engine.py`` and as the reference in the scan-parity
    tests.  Decision logic, PRNG streams AND the data path are shared with
    the scan engine (``engine.round_decision`` with ``fold_in(seed, t)``;
    ``resolve_data_path`` picks the same minibatch source — device-store
    ``fold_in`` sampling by default, ``BatchIterator`` pre-stack streams
    when ``cfg.data_path == "prestack"``), so results match the scan engine
    bit-wise on identical configs.
    """
    K = len(client_data)
    opt = opt or sgd(cfg.lr)
    policy_fn = as_policy_fn(policy)
    state = init_fl_state(init_params, K)
    round_fn = make_round_fn(loss_fn, opt, cfg.local_iters, K,
                             local_mode=cfg.local_mode, faults=cfg.faults,
                             guards=cfg.guards, aggregator=cfg.aggregator,
                             metrics=cfg.metrics)
    base_key = jax.random.PRNGKey(cfg.seed)

    # metrics taps: the ledger half accumulates host-side via its own jitted
    # update (same full-[K] vector ops as the scan engines — bit-identical
    # integer counters); the train half rides through fl_round
    ltap = metrics_active(cfg.metrics, None, parts="ledger")
    ttap = metrics_active(cfg.metrics, cfg.guards, parts="train")
    ms_l = init_metrics(cfg.metrics, K, None, parts="ledger")
    ms_t = init_metrics(cfg.metrics, K, cfg.guards, parts="train")
    if ltap:
        ledger_tap = jax.jit(lambda ms, m, f, eb, er, st, d:
                             update_ledger_taps(ms, cfg.metrics, mask=m,
                                                forced=f, e_base=eb,
                                                e_round=er, staleness=st,
                                                delivered=d))

    # split the policy eval from the decision so the nominal probs (pre
    # aging-boost) are available to scheme aggregation — mask/forced/w/e
    # stay bit-identical to round_decision (which composes the same pair)
    def _decide(t, h_t, st):
        probs, w = policy_fn(t, h_t, st)
        mask, forced, w, e_round = apply_round_decision(
            probs, w, t, h_t, st, base_key, cfg, cell, K)
        return probs, mask, forced, w, e_round

    decide = jax.jit(_decide)

    # fault pipeline: same salted fold_in streams as the scan engine, so the
    # two realize identical faults round for round
    if cfg.faults is not None:
        fstate = init_fault_state(K)
        fparams = cfg.faults.params()
        fault_step = jax.jit(lambda t, m, e, fs: apply_faults(
            t, base_key, m, e, fs, fparams, cfg.faults))

    data_path = resolve_data_path(client_data, cfg)
    data_key = data_stream_key(cfg.seed)
    if data_path == "prestack":
        iters = [BatchIterator(ds, cfg.batch_size, seed=cfg.seed + 17 * k)
                 for k, ds in enumerate(client_data)]
    elif data_path == "device":  # per-round jitted draw from the store
        store = from_client_datasets(client_data)
        draw = (sample_round_client_stream if cfg.data_stream == "client"
                else sample_round)
        sample = jax.jit(lambda t: draw(
            store, data_key, t, cfg.local_iters, cfg.batch_size))
    else:  # stream: data stays host-side (it was chosen because the store
        # does not fit on device); same index stream, one-round chunks
        sampler = StreamingSampler(client_data, data_key, cfg.local_iters,
                                   cfg.batch_size)
        sample = lambda t: tuple(c[0] for c in  # noqa: E731
                                 sampler.chunk(int(t), int(t) + 1))

    energy = np.zeros((K,), np.float32)
    energy_tl = np.zeros((cfg.rounds,))
    parts = np.zeros((cfg.rounds, K), np.float32)
    delivered_tl = np.zeros((cfg.rounds, K), np.float32)
    corrupt_tl = np.zeros((cfg.rounds, K), np.float32)
    accs, losses, eval_rounds = [], [], []

    test_x = test_ds.x[: cfg.eval_batch]
    test_y = test_ds.y[: cfg.eval_batch]
    eval_fn = jax.jit(lambda p: (acc_fn(p, test_x, test_y),
                                 loss_fn(p, test_x, test_y)))

    if data_path == "prestack" and cfg.local_iters == 0:
        empty_x, empty_y = empty_client_batches(client_data, cfg)

    for t in range(cfg.rounds):
        # --- per-round batches; each data path must draw exactly what the
        # scan engine consumes at round t (prestack: iterator seeds and
        # consumption order match stack_round_batches; device: the shared
        # fold_in(data_key, t) store stream) or scan-parity breaks ---------
        if data_path != "prestack":
            xb, yb = sample(jnp.int32(t))
        elif cfg.local_iters == 0:
            xb, yb = empty_x, empty_y
        else:
            xs, ys = [], []
            for _ in range(cfg.local_iters):
                xb, yb = client_batches(iters)
                xs.append(xb)
                ys.append(yb)
            xb = jnp.stack(xs, axis=1)  # [K, local_iters, B, ...]
            yb = jnp.stack(ys, axis=1)

        # --- policy + autonomous decisions + energy ledger (eq. 5) ---------
        probs, mask, forced, w, e_round = decide(jnp.int32(t), h_all[:, t],
                                                 state)
        e_base = e_round     # decision energy before the fault pipeline
        # --- fault pipeline (availability → crash → lossy uplink) ----------
        if cfg.faults is not None:
            out, fstate = fault_step(jnp.int32(t), mask, e_round, fstate)
            delivered, corrupt, e_round = (out.delivered, out.corrupt,
                                           out.e_round)
            delivered_tl[t] = np.asarray(delivered)
            corrupt_tl[t] = np.asarray(corrupt)
        else:
            delivered, corrupt = None, None
        energy += np.asarray(e_round)
        energy_tl[t] = energy.sum()
        parts[t] = np.asarray(mask)
        if ltap:
            ms_l = ledger_tap(ms_l, mask, forced, e_base, e_round,
                              state.round - state.last_tx,
                              mask if delivered is None else delivered)

        # --- one protocol round --------------------------------------------
        if ttap:
            state, ms_t = round_fn(state, mask, xb, yb, delivered, corrupt,
                                   probs, ms_t)
        else:
            state = round_fn(state, mask, xb, yb, delivered, corrupt, probs)

        if t % cfg.eval_every == 0 or t == cfg.rounds - 1:
            a, l = eval_fn(state.global_params)
            accs.append(float(a))
            losses.append(float(l))
            eval_rounds.append(t)

    faulty = cfg.faults is not None
    ms = merge_metrics(ms_l, ms_t)
    return SimResult(np.asarray(accs), np.asarray(losses),
                     np.asarray(eval_rounds), energy, energy_tl, parts, state,
                     delivered=delivered_tl if faulty else None,
                     corrupted=corrupt_tl if faulty else None,
                     metrics=(jax.tree_util.tree_map(np.asarray, ms)
                              if ms is not None else None))

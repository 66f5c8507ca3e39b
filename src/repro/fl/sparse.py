"""Participant-centric sparse rounds: per-participant cost at any population.

The dense scan engine (:mod:`repro.fl.engine`) carries every per-round
structure at population width ``[K]`` — client batch gather, local training,
``[K, D]`` deltas — so simulation cost scales with the population even though
only ~pK clients transmit per round.  This module restructures the round
transition so the expensive work scales with the *transmitting set*:

* **Phase A — participation program** (``build_participation_program``):
  a tiny jitted scan over ``[K]`` *vectors only* (probabilities, Bernoulli
  draws, Δ_k staleness dynamics, the eq.-5 energy ledger).  It shares
  :func:`~repro.fl.engine.apply_round_decision` with the dense engine, so
  masks and energies are bit-identical; its outputs are *participant-sized*:
  the transmitting index set per round (padded to a static bucket), each
  participant's anchor slot, and its energy.  Compiled per K, but the
  program is a few K-length vector ops per round — microseconds, not the
  K·D local-training cost.
* **Batch gather** (:func:`repro.data.device.gather_participant_rounds`):
  participants' minibatches come from the per-client stream
  ``fold_in(fold_in(data_key, t), k)``, so only ``[T, P, L, B, ...]`` is
  ever gathered from the resident store — no ``[K, L, B, ...]`` round batch
  exists anywhere.
* **Phase B — training program** (``build_sparse_train_program``): a jitted
  scan whose shapes depend only on ``(bucket, T, model)`` — **never on K**.
  The carry is a global-model *history* ``[T+1, D]`` (slot s = the model
  broadcast after round s-1); each round gathers its participants' anchors
  ``hist[slot_p]``, runs local SGD over the ``[P, ...]`` bucket, and applies
  the eq.-3 update (:func:`repro.fl.state.masked_aggregate` over the bucket
  rows, Pallas-fused on TPU) with the population size as a *traced* scalar.  One
  compile serves every K sharing a bucket — the fix for the engine's
  one-compile-per-K limitation (``TRAIN_TRACE_COUNT`` counts traces; the
  K-sweep test pins it to one).

Semantics: the sparse path implements ``SimConfig.local_mode =
"participants"`` — a client trains ``local_iters`` steps from its last
received global *in the round it transmits* (the standard sampled-FedAvg
reading of the paper's protocol).  The dense engine supports the same mode,
and the two are parity-tested against each other; the paper's default
``"continuous"`` mode (every client trains every round) is irreducibly
O(K·T) compute and keeps the dense path.

Memory: phase B replaces the dense ``[K, D]`` client/anchor stacks with the
``[T+1, D]`` history — a win whenever K ≫ T.  The dense ``[K]`` ledgers
(energy, last_tx) survive in phase A and shard over a mesh via
``launch.sharding.ledger_shardings``.
"""
from __future__ import annotations

import warnings
from typing import Any, Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.channel import CellConfig
from ..core.selection import (as_policy_fn, participant_bucket,
                              participants_from_mask)
from ..data.device import (DeviceDataStore, data_stream_key,
                           from_client_datasets, gather_participant_rounds)
from ..data.synthetic import Dataset
from ..obs.taps import (MetricsState, init_metrics, merge_metrics,
                        metrics_active, update_train_taps)
from ..obs.telemetry import emit_run_manifest, get_telemetry
from ..optim import Optimizer, sgd
from .faults import apply_faults, corrupt_deltas, init_fault_state
from .state import FLState, masked_aggregate, round_weights

#: number of times the participant-shaped training program has been traced.
#: Shapes depend only on (bucket, T, model), so a K-sweep sharing a bucket
#: must not bump this more than once (tests/test_sparse_engine.py).
TRAIN_TRACE_COUNT = 0


def train_trace_count() -> int:
    return TRAIN_TRACE_COUNT


#: process-wide one-shot flag for the bucket-spill warning (a long sweep
#: that overflows every call should not drown the log).
_SPILL_WARNED = False


def _warn_spill_once(bucket: int, grown: int, realized: int) -> None:
    global _SPILL_WARNED
    if _SPILL_WARNED:
        return
    _SPILL_WARNED = True
    warnings.warn(
        f"participant bucket overflow: a round realized {realized} "
        f"transmitters > bucket {bucket}; spilling — regrowing the bucket "
        f"to {grown} and rerunning phase A (exact, but recompiles phase A "
        "and the training program). Pass SimConfig(participant_bucket=...) "
        "with more headroom, or overflow='error' to fail instead.",
        RuntimeWarning, stacklevel=3)


class _DecisionView(NamedTuple):
    """The two FLState fields :func:`apply_round_decision` actually reads —
    phase A never materializes client/anchor parameter stacks."""

    round: jax.Array    # int32 scalar
    last_tx: jax.Array  # [K] int32


class ParticipationTrace(NamedTuple):
    """Phase A per-round outputs (leading axis T after the scan) — all
    participant-sized except the scalar overflow counter.  Compaction is
    always over the *decision* mask (autonomous Bernoulli draws + Δ_k
    forcing); the fault pipeline's outcomes ride along per participant, so
    phase B can drop lost uploads and corrupt/guard the delivered ones
    without any [K]-shaped array."""

    part_idx: jax.Array     # [P] int32 transmitting ids, padded with K
    valid: jax.Array        # [P] bool
    anchor_slot: jax.Array  # [P] int32 history slot of each anchor
    e_p: jax.Array          # [P] f32 Joules (eq. 5, incl. retry energy)
    delivered: jax.Array    # [P] bool — upload survived the fault pipeline
    corrupt: jax.Array      # [P] bool — delivered but adversarially mangled
    stale: jax.Array        # [P] int32 staleness Δτ at transmission time
    prob: jax.Array         # [P] f32 nominal policy prob (pre aging-boost)
    n_tx: jax.Array         # int32 realized transmitter count (overflow check)
    # metrics-tap lanes, emitted only when cfg.metrics enables ledger taps
    # (the ledger accumulators reduce over these post-scan — no per-round
    # [K]-vector tap work rides in the sequential scan)
    forced_p: Any = None    # [P] bool — Δ_k-forced transmission
    base_p: Any = None      # [P] f32 — decision energy before faults


def _reduce_ledger_taps(tr: ParticipationTrace, spec, num_clients: int,
                        rounds: int) -> MetricsState:
    """Batched post-scan reduction of the ledger taps from the ``[T, P]``
    trace lanes — one scatter/sum pass instead of per-round accumulator ops
    inside the sequential scan (which costs ~20% on the tiny-model sparse
    path, where phase A dominates).

    The pad sentinel ``K`` in ``part_idx`` is out of bounds, so
    ``mode="drop"`` scatters discard padded lanes; integer taps stay
    bit-exact with the dense engine's in-scan accumulation (participants
    are exactly the mask fires — the runner hard-errors on bucket
    overflow).  Float energy sums change association order only.
    """
    tx = stale = ec = None
    if spec.participation:
        tx = jnp.zeros((num_clients,), jnp.int32).at[tr.part_idx.ravel()].add(
            tr.valid.ravel().astype(jnp.int32), mode="drop")
    if spec.staleness_hist:
        b = jnp.clip(tr.stale.astype(jnp.int32), 0, spec.staleness_bins - 1)
        stale = jnp.zeros((spec.staleness_bins,), jnp.int32).at[b.ravel()].add(
            tr.delivered.ravel().astype(jnp.int32), mode="drop")
    if spec.energy_by_cause:
        e = tr.e_p.astype(jnp.float32)          # 0 on padded lanes
        f = tr.forced_p.astype(jnp.float32)
        retry = jnp.maximum(e - tr.base_p.astype(jnp.float32), 0.0)
        ec = jnp.stack([jnp.sum(e * (1.0 - f)), jnp.sum(e * f),
                        jnp.sum(retry)])
    return MetricsState(tx_count=tx, stale_hist=stale, energy_cause=ec,
                        rounds=jnp.asarray(rounds, jnp.int32))


def build_participation_program(policy_fn, cfg, cell: CellConfig,
                                num_clients: int, bucket: int,
                                hoist_rounds: bool | None = None) -> Callable:
    """Phase A: ``(h_rounds [T, K], base_key) -> (last_tx [K], energy [K],
    ParticipationTrace[T])``.

    Pure ``[K]``-vector work per round; the policy must be ``state_free``
    (all five paper schemes are) or a *ledger* policy reading only the
    ``(round, last_tx)`` staleness ledger that phase A already carries —
    state_free policies hoist to one vmap over the horizon, ledger policies
    run inside the scan step against the :class:`_DecisionView`.  Decision
    math is byte-for-byte the dense engine's ``apply_round_decision`` on the
    identical ``fold_in(base_key, t)`` stream, so realized masks and the
    energy ledger match the dense scan bit-wise.

    **Full round hoist**: when the decision itself is round-local — a
    state_free policy, no fault processes (the Markov availability chain is
    sequential state) and no ``max_staleness`` forcing (Δ_k reads the
    ledger) — the serial scan over T disappears entirely: the whole
    ``[T, K]`` mask/energy matrix comes from one vmap over the horizon and
    the staleness/anchor ledgers are recovered with two exclusive
    ``cummax`` passes.  Masks, index sets, anchor slots, staleness and
    ``last_tx`` are bit-identical to the scanned path (pure integer
    recurrences); only the energy ledger's summation *order* changes
    (tolerance-level, like every cross-path energy comparison).
    ``hoist_rounds`` forces the choice for parity tests: ``True`` raises
    if the preconditions fail, ``False`` pins the serial scan, ``None``
    (default) auto-selects.
    """
    from .engine import apply_round_decision  # deferred: engine imports us

    hoist = getattr(policy_fn, "state_free", False)
    if not hoist and not getattr(policy_fn, "ledger", False):
        raise ValueError(
            "sparse participation requires a state_free or ledger policy "
            "(phase A carries only the (round, last_tx) ledger); policies "
            "reading trained parameters must use the dense engine")
    K = num_clients
    faults = cfg.faults
    fparams = faults.params() if faults is not None else None
    # ledger taps reduce post-scan from trace lanes (split accumulation: the
    # train taps live in phase B); guards are irrelevant to the ledger subset
    ltap = metrics_active(cfg.metrics, None, parts="ledger")
    full_hoist = hoist and faults is None and cfg.max_staleness is None
    if hoist_rounds is not None:
        if hoist_rounds and not full_hoist:
            raise ValueError(
                "hoist_rounds=True needs a state_free policy, faults=None "
                "and max_staleness=None (everything else carries sequential "
                "state through the round scan)")
        full_hoist = bool(hoist_rounds)

    def phase_a_program(h_rounds, base_key):
        ts = jnp.arange(cfg.rounds, dtype=jnp.int32)
        if hoist:
            pw_all = jax.vmap(lambda t, h: policy_fn(t, h, None))(
                ts, h_rounds)
        else:  # ledger policy: dummy lanes, the policy runs in the step
            pw_all = (jnp.zeros((cfg.rounds, 0)),) * 2

        if full_hoist:
            zeros_ltx = jnp.zeros((K,), jnp.int32)

            def decide(t, h_t, probs, w):
                # the dummy view is never read: max_staleness is None (no
                # Δ_k forcing) and aging_boost gates on it too
                view = _DecisionView(round=t, last_tx=zeros_ltx)
                return apply_round_decision(probs, w, t, h_t, view,
                                            base_key, cfg, cell, K)

            mask_all, _, _, e_all = jax.vmap(decide)(
                ts, h_rounds, pw_all[0], pw_all[1])
            fire = mask_all > 0
            tsc = ts[:, None]
            # ledger recurrences as exclusive cumulative maxima: last_tx
            # before round t = max{s < t : client fired at s} (0 if none),
            # anchor slot before round t = that + 1 (0 if none) — identical
            # integers to the scanned where(delivered, t, ...) updates
            lt_inc = jax.lax.cummax(jnp.where(fire, tsc, 0), axis=0)
            lt_excl = jnp.concatenate(
                [jnp.zeros((1, K), jnp.int32), lt_inc[:-1]], axis=0)
            slot_inc = jax.lax.cummax(jnp.where(fire, tsc + 1, 0), axis=0)
            slot_excl = jnp.concatenate(
                [jnp.zeros((1, K), jnp.int32), slot_inc[:-1]], axis=0)

            def compact(t, mask, e_round, probs, lt_prev, slot_prev):
                idx, valid, n_tx = participants_from_mask(mask, bucket)
                kc = jnp.clip(idx, 0, K - 1)
                e_p = jnp.where(valid, e_round[kc], 0.0)
                tr = ParticipationTrace(
                    idx, valid,
                    jnp.where(valid, slot_prev[kc], 0), e_p,
                    valid, jnp.zeros((bucket,), bool),
                    jnp.where(valid, t - lt_prev[kc], 0),
                    jnp.where(valid, probs.astype(jnp.float32)[kc], 0.0),
                    n_tx)
                if ltap:   # no forcing, no faults: e_base == e_round
                    tr = tr._replace(forced_p=jnp.zeros((bucket,), bool),
                                     base_p=e_p)
                return tr

            tr = jax.vmap(compact)(ts, mask_all, e_all, pw_all[0],
                                   lt_excl, slot_excl)
            energy = jnp.sum(e_all, axis=0)
            if ltap:
                return lt_inc[-1], energy, tr, _reduce_ledger_taps(
                    tr, cfg.metrics, K, cfg.rounds)
            return lt_inc[-1], energy, tr

        def step(carry, xs):
            last_tx, anchor_slot, energy = carry[0], carry[1], carry[2]
            if faults is not None:
                fstate = carry[3]
            t, h_t, probs, w = xs
            view = _DecisionView(round=t, last_tx=last_tx)
            if not hoist:
                probs, w = policy_fn(t, h_t, view)
            mask, forced, w, e_round = apply_round_decision(
                probs, w, t, h_t, view, base_key, cfg, cell, K)
            e_base = e_round        # decision energy before the fault pipeline
            # fault pipeline on the same salted streams as the dense engine:
            # masks above stay untouched, only delivery/energy change
            if faults is not None:
                out, fstate = apply_faults(t, base_key, mask, e_round,
                                           fstate, fparams, faults)
                delivered, corrupt, e_round = (out.delivered, out.corrupt,
                                               out.e_round)
            else:
                delivered = mask
                corrupt = jnp.zeros((K,), bool)
            energy = energy + e_round
            idx, valid, n_tx = participants_from_mask(mask, bucket)
            kc = jnp.clip(idx, 0, K - 1)
            slot_p = jnp.where(valid, anchor_slot[kc], 0)
            e_p = jnp.where(valid, e_round[kc], 0.0)
            del_p = valid & (delivered[kc] > 0)
            cor_p = valid & corrupt[kc]
            stale_p = jnp.where(valid, t - last_tx[kc], 0)
            prob_p = jnp.where(valid, probs.astype(jnp.float32)[kc], 0.0)
            # the server's ledgers advance on *delivered* uploads (the dense
            # engine broadcasts to the delivered set) — a lost upload leaves
            # last_tx/anchor untouched, so its staleness keeps growing
            last_tx = jnp.where(delivered > 0, t, last_tx)
            anchor_slot = jnp.where(delivered > 0, t + 1, anchor_slot)
            carry = (last_tx, anchor_slot, energy)
            if faults is not None:
                carry = carry + (fstate,)
            tr = ParticipationTrace(idx, valid, slot_p, e_p, del_p, cor_p,
                                    stale_p, prob_p, n_tx)
            if ltap:
                # ledger-tap lanes ride the trace instead of the carry: the
                # accumulators reduce over [T, P] post-scan, keeping the
                # sequential scan free of per-round [K]-vector tap work
                tr = tr._replace(forced_p=valid & (forced[kc] > 0),
                                 base_p=jnp.where(valid, e_base[kc], 0.0))
            return carry, tr

        carry0 = (jnp.zeros((K,), jnp.int32), jnp.zeros((K,), jnp.int32),
                  jnp.zeros((K,), jnp.float32))
        if faults is not None:
            carry0 = carry0 + (init_fault_state(K),)
        final, tr = jax.lax.scan(
            step, carry0, (ts, h_rounds, pw_all[0], pw_all[1]))
        if ltap:     # 4-tuple only when ledger taps materialize
            return final[0], final[2], tr, _reduce_ledger_taps(
                tr, cfg.metrics, K, cfg.rounds)
        return final[0], final[2], tr

    return phase_a_program


# ---------------------------------------------------------------------------
# phase B: the K-independent participant training program
# ---------------------------------------------------------------------------

#: (bucket, T, model/cfg signature) -> jitted training program.  One compile
#: per bucket — populations of any size reuse the entry.
_TRAIN_CACHE: dict = {}


def _train_cache_key(cfg, opt_token, loss_fn, acc_fn, params, sample_shape,
                     test_shape, bucket: int):
    shapes = tuple((tuple(l.shape), str(l.dtype))
                   for l in jax.tree_util.tree_leaves(params))
    treedef = str(jax.tree_util.tree_structure(params))
    return (bucket, cfg.rounds, cfg.local_iters, cfg.batch_size,
            cfg.eval_every, opt_token, id(loss_fn), id(acc_fn), treedef,
            shapes, tuple(sample_shape), tuple(test_shape),
            repr(cfg.faults), repr(cfg.guards), repr(cfg.aggregator),
            repr(cfg.metrics))


def build_sparse_train_program(loss_fn: Callable, acc_fn: Callable,
                               opt: Optimizer, cfg) -> Callable:
    """Phase B: ``(params, xb [T,P,L,B,...], yb, valid [T,P], slot [T,P],
    num_clients, test_x, test_y[, delivered, corrupt, stale]) ->
    (global, (acc, loss, did_eval)[T])``.

    No array in this program carries a K-sized axis: the carry is the
    global-model history ``[T+1, D]``, training runs over the ``[P, ...]``
    bucket, and the 1/K averaging receives the population as a traced
    scalar.  Tracing it bumps :data:`TRAIN_TRACE_COUNT`.

    The trailing optional operands are the fault pipeline's per-participant
    outcomes from phase A: lost uploads aggregate with weight 0, corrupt
    flags drive :func:`~repro.fl.faults.corrupt_deltas`, and staleness feeds
    the guard weights of :func:`~repro.fl.state.round_weights` when
    ``cfg.guards`` is active.  Omitted (the faults-off call) they default to
    ``delivered = valid`` / no corruption — the pre-robustness program.

    With ``cfg.aggregator`` set the update takes the pluggable scheme's
    weights (:func:`~repro.fl.state.round_weights`); phase A's
    nominal-prob lane rides in as ``probs_all`` and ``agg_params`` can be a
    traced :class:`~repro.fl.state.AggParams` (vmapped scheme panels).
    """
    from .engine import make_local_train  # deferred: engine imports us

    vtrain = make_local_train(loss_fn, opt)
    T = cfg.rounds
    faults = cfg.faults
    guards = cfg.guards
    agg = cfg.aggregator
    fparams = faults.params() if faults is not None else None
    # train taps (guard events / weight stats) accumulate over the [P]
    # bucket rows here; counts match the dense engine exactly, float
    # reductions to associativity (split accumulation with phase A)
    ttap = metrics_active(cfg.metrics, guards, parts="train")

    def phase_b_program(params, xb_all, yb_all, valid_all, slot_all,
                        num_clients, test_x, test_y, delivered_all=None,
                        corrupt_all=None, stale_all=None, probs_all=None,
                        agg_params=None):
        global TRAIN_TRACE_COUNT
        TRAIN_TRACE_COUNT += 1
        hist0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros((T + 1,) + p.shape, p.dtype).at[0].set(p),
            params)
        if delivered_all is None:
            delivered_all = valid_all
        if corrupt_all is None:
            corrupt_all = jnp.zeros(valid_all.shape, bool)
        if stale_all is None:
            stale_all = jnp.zeros(valid_all.shape, jnp.int32)
        if probs_all is None:
            probs_all = jnp.zeros(valid_all.shape, jnp.float32)
        ap = None
        if agg is not None:
            ap = agg.params() if agg_params is None else agg_params

        def eval_now(p):
            return (jnp.asarray(acc_fn(p, test_x, test_y), jnp.float32),
                    jnp.asarray(loss_fn(p, test_x, test_y), jnp.float32))

        def skip_eval(p):
            del p
            return jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)

        def step(carry, xs):
            hist = carry[0] if ttap else carry
            t, xb, yb, valid, slot, deliv, corr, stale, prob = xs
            g_t = jax.tree_util.tree_map(lambda h: h[t], hist)
            anchors = jax.tree_util.tree_map(lambda h: h[slot], hist)
            trained = vtrain(anchors, xb, yb)
            deltas = jax.tree_util.tree_map(lambda a, b: a - b, trained,
                                            anchors)
            if faults is not None:
                deltas = corrupt_deltas(deltas, corr, fparams, faults)
            w_agg, denom, d_agg = round_weights(deliv, num_clients, stale,
                                                prob, ap, guards, deltas)
            g_new = masked_aggregate(g_t, d_agg, w_agg, denom)
            hist = jax.tree_util.tree_map(
                lambda h, g: h.at[t + 1].set(g), hist, g_new)
            do_eval = jnp.logical_or(t % cfg.eval_every == 0, t == T - 1)
            acc, loss = jax.lax.cond(do_eval, eval_now, skip_eval, g_new)
            if ttap:
                ms = update_train_taps(
                    carry[1], cfg.metrics, deltas=deltas, delivered=deliv,
                    staleness=stale, probs=prob, num_clients=num_clients,
                    guards=guards, agg_params=ap)
                return (hist, ms), (acc, loss, do_eval)
            return hist, (acc, loss, do_eval)

        ts = jnp.arange(T, dtype=jnp.int32)
        carry0 = ((hist0, init_metrics(cfg.metrics, 0, guards,
                                       parts="train"))
                  if ttap else hist0)
        final, traces = jax.lax.scan(
            step, carry0, (ts, xb_all, yb_all, valid_all, slot_all,
                           delivered_all, corrupt_all, stale_all, probs_all))
        hist = final[0] if ttap else final
        g_final = jax.tree_util.tree_map(lambda h: h[T], hist)
        if ttap:     # 3-tuple only when train taps materialize
            return g_final, traces, final[1]
        return g_final, traces

    return phase_b_program


def _cached_train_program(key, builder: Callable) -> Callable:
    tel = get_telemetry()
    if key not in _TRAIN_CACHE:
        tel.inc("sparse.train_cache_miss")
        _TRAIN_CACHE[key] = jax.jit(builder())
    else:
        tel.inc("sparse.train_cache_hit")
    return _TRAIN_CACHE[key]


# ---------------------------------------------------------------------------
# runner: phase A -> participant gather -> phase B -> SimResult
# ---------------------------------------------------------------------------


def _auto_bucket(policy_fn, h_rounds, cfg, num_clients: int) -> int:
    """Bucket from the expected transmitting mass: max over rounds of Σp,
    with Poisson-tail headroom (see :func:`participant_bucket`).

    Ledger policies are probed at zero staleness (``state=None``) — their
    contract requires tolerating it; the Poisson-tail headroom absorbs the
    resulting estimate noise, and the spill path stays exact regardless.
    """
    ts = jnp.arange(cfg.rounds, dtype=jnp.int32)
    probs = jax.jit(jax.vmap(lambda t, h: policy_fn(t, h, None)[0]))(
        ts, h_rounds)
    expected = float(jnp.max(jnp.sum(probs, axis=-1)))
    return participant_bucket(expected, cap=num_clients)


def make_sparse_runner(loss_fn: Callable, acc_fn: Callable,
                       client_data: Sequence[Dataset], test_ds: Dataset,
                       policy, cell: CellConfig, cfg,
                       opt: Optimizer | None = None) -> Callable:
    """Participant-centric counterpart of ``engine.make_runner``.

    Returns ``runner(params, h_all, seed=None) -> SimResult`` with the same
    result contract as the dense engine (dense ``[T, K]`` participation /
    per-round energy are reconstructed host-side from the participant trace;
    ``result.state`` carries the final global model and ``last_tx`` but no
    ``[K, D]`` client stacks — the sparse path never materializes them).
    """
    from .engine import SimResult  # deferred: engine imports us

    # a pre-built store is accepted directly — at mega-populations a
    # million-element Dataset list is not viable, and the jittable
    # partitioners emit stores natively
    store = (client_data if isinstance(client_data, DeviceDataStore)
             else from_client_datasets(client_data))
    K = store.num_clients
    if opt is None:
        # value-token the default optimizer: every runner constructing the
        # default sgd(cfg.lr) shares one cache entry (fresh closures would
        # make the id()-based token miss on every make_runner call)
        opt = sgd(cfg.lr)
        opt_token = ("default-sgd", float(cfg.lr))
    else:
        opt_token = (id(opt.init), id(opt.update))
    policy_fn = as_policy_fn(policy)
    if cfg.local_mode != "participants":
        raise ValueError(
            "the sparse path implements local_mode='participants'; "
            "continuous local training is population-shaped by definition — "
            "use the dense engine for it")
    if cfg.data_stream != "client":
        raise ValueError(
            "sparse participation samples minibatches per participant and "
            "needs the per-client stream: set SimConfig(data_stream='client')")
    if cfg.overflow not in ("spill", "error"):
        raise ValueError(f"unknown overflow policy {cfg.overflow!r} "
                         "(expected spill|error)")
    if cfg.eval_mode == "replay":
        raise ValueError(
            "the sparse path evaluates in-scan; eval_mode='replay' belongs "
            "to the resumable dense driver (repro.fl.resume)")
    data_key = data_stream_key(cfg.seed)
    test_x = test_ds.x[: cfg.eval_batch]
    test_y = test_ds.y[: cfg.eval_batch]
    T = cfg.rounds
    ltap = metrics_active(cfg.metrics, None, parts="ledger")
    ttap = metrics_active(cfg.metrics, cfg.guards, parts="train")
    tel = get_telemetry()
    emit_run_manifest("make_sparse_runner", cfg, extra={"num_clients": K})
    phase_a: dict = {}
    gather = jax.jit(lambda pidx: gather_participant_rounds(
        store, data_key, pidx, cfg.local_iters, cfg.batch_size))

    def _phase_a(bucket: int, h_rounds, key):
        if bucket not in phase_a:
            tel.inc("sparse.phase_a_cache_miss")
            phase_a[bucket] = jax.jit(build_participation_program(
                policy_fn, cfg, cell, K, bucket))
        else:
            tel.inc("sparse.phase_a_cache_hit")
        return phase_a[bucket](h_rounds, key)

    def runner(params, h_all, seed: int | None = None) -> SimResult:
        with tel.span("sparse.call"):
            return _call(params, h_all, seed)

    def _call(params, h_all, seed):
        with tel.span("sparse.prepare"):
            key = jax.random.PRNGKey(cfg.seed if seed is None else seed)
            h_rounds = jnp.swapaxes(h_all, 0, 1)
            bucket = cfg.participant_bucket or _auto_bucket(
                policy_fn, h_rounds, cfg, K)
        with tel.span("sparse.phase_a"):    # ends at its n_tx sync
            pa = _phase_a(bucket, h_rounds, key)
            last_tx, energy, ptr = pa[0], pa[1], pa[2]
            ms_a = pa[3] if ltap else None
            n_tx = np.asarray(ptr.n_tx)
            if (n_tx > bucket).any():
                if cfg.overflow == "error":
                    raise RuntimeError(
                        f"participant bucket overflow: round "
                        f"{int(n_tx.argmax())} realized {int(n_tx.max())} "
                        f"transmitters > bucket {bucket} — pass "
                        "SimConfig(participant_bucket=...) with more "
                        "headroom")
                # spill fallback: regrow toward the dense width (next power
                # of two ≥ the realized max, capped at K) and rerun phase
                # A — decision math is bucket-independent, so the rerun is
                # exact
                grown = max(bucket, 1)
                while grown < int(n_tx.max()):
                    grown *= 2
                grown = min(grown, K)
                _warn_spill_once(bucket, grown, int(n_tx.max()))
                bucket = grown
                pa = _phase_a(bucket, h_rounds, key)
                last_tx, energy, ptr = pa[0], pa[1], pa[2]
                ms_a = pa[3] if ltap else None
        # phase B ends at the first copy of its outputs; the host densifies
        # phase A's participant trace (numpy, O(T·K)) while the device trains
        with tel.span("sparse.phase_b"):
            xb_all, yb_all = gather(ptr.part_idx)
            train = _cached_train_program(
                _train_cache_key(cfg, opt_token, loss_fn, acc_fn, params,
                                 store.x.shape[2:], test_x.shape, bucket),
                lambda: build_sparse_train_program(loss_fn, acc_fn, opt, cfg))
            out = train(
                params, xb_all, yb_all, ptr.valid, ptr.anchor_slot,
                jnp.int32(K), test_x, test_y, ptr.delivered, ptr.corrupt,
                ptr.stale, ptr.prob)
            g_final, (accs, losses, dids) = out[0], out[1]
            ms_b = out[2] if ttap else None

            idx = np.asarray(ptr.part_idx)
            val = np.asarray(ptr.valid)
            e_p = np.asarray(ptr.e_p)
            t_of = np.broadcast_to(np.arange(T)[:, None], idx.shape)
            parts = np.zeros((T, K), np.float32)
            e_round = np.zeros((T, K), np.float32)
            parts[t_of[val], idx[val]] = 1.0
            e_round[t_of[val], idx[val]] = e_p[val]
            did = np.asarray(dids)
        with tel.span("sparse.readback"):
            ev = np.where(did)[0]
            state = FLState(global_params=g_final, client_params=None,
                            anchor_params=None, round=jnp.int32(T),
                            last_tx=last_tx)
            if cfg.faults is not None:
                dlv = np.asarray(ptr.delivered)
                cor = np.asarray(ptr.corrupt)
                delivered = np.zeros((T, K), np.float32)
                corrupted = np.zeros((T, K), np.float32)
                delivered[t_of[val], idx[val]] = dlv[val].astype(np.float32)
                corrupted[t_of[val], idx[val]] = cor[val].astype(np.float32)
            else:
                delivered = corrupted = None
            ms = merge_metrics(ms_a, ms_b)
            return SimResult(
                test_acc=np.asarray(accs)[ev],
                test_loss=np.asarray(losses)[ev],
                eval_rounds=ev,
                energy_per_client=np.asarray(energy),
                energy_timeline=np.cumsum(e_round.sum(axis=1)),
                participation=parts,
                state=state,
                delivered=delivered,
                corrupted=corrupted,
                metrics=(jax.tree_util.tree_map(np.asarray, ms)
                         if ms is not None else None),
            )

    runner.store = store
    return runner


def resolve_participation(cfg, policy_fn, data_path: str,
                          num_clients: int) -> str:
    """Resolve ``cfg.participation`` to ``"dense"`` or ``"sparse"``.

    ``"auto"`` picks sparse exactly when its preconditions hold — the
    participants-only local mode, a state_free or ledger policy (see
    :func:`repro.core.selection.policy_ledger_ok`), the device data path,
    and the per-client minibatch stream; anything else keeps the dense scan.
    ``"sparse"`` raises on unmet preconditions instead of silently changing
    semantics.
    """
    from ..core.selection import policy_ledger_ok

    mode = cfg.participation
    if mode not in ("dense", "sparse", "auto"):
        raise ValueError(f"unknown participation {mode!r} "
                         "(expected dense|sparse|auto)")
    ok = (cfg.local_mode == "participants" and policy_ledger_ok(policy_fn)
          and data_path == "device" and cfg.data_stream == "client")
    if mode == "auto":
        return "sparse" if ok else "dense"
    if mode == "sparse" and data_path != "device":
        raise ValueError("sparse participation gathers from the device "
                         f"store; data path {data_path!r} is not supported")
    return mode

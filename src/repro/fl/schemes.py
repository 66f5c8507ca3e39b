"""Head-to-head async-FL scheme matrix: one device program per path.

The paper's claim is comparative — probabilistic client selection vs the
traditional async-FL designs — so the engine needs every competing scheme
running under *identical* channel realizations, PRNG streams, and energy
accounting.  A scheme here is a pair:

* a **selection policy** (:mod:`repro.core.selection`): who transmits —
  the paper's online solve, random/greedy/age heuristics, CSMA-style
  channel-share contention (arXiv:2306.01207), or Hu–Chen–Larsson
  age-aware scheduling (arXiv:2212.07356, a *ledger* policy);
* an **aggregator** (:class:`repro.fl.state.AggregatorConfig`): how the
  delivered deltas merge — the paper's 1/K average, FedAsync-style
  ``s(Δτ)`` staleness mixing (constant/hinge/poly), CSMAAFL importance
  weighting, or age-aware amplification.

``run_scheme_matrix`` fans schemes × seeds × non-IID severities out as
vmap axes of **one compiled program per execution path**.  Schemes become
a traced axis through two devices:

* the policy panel is blended by a traced one-hot row
  (:func:`repro.core.selection.policy_blend` — 0/1 float blending is
  IEEE-exact, so each lane realizes its policy's probs bit-for-bit);
* the aggregator panel is a stacked :class:`~repro.fl.state.AggParams`
  whose one-hot selectors ride the same vmap axis (the branch-free weight
  program in :func:`~repro.fl.state.scheme_weights`).

Severities vmap over stacked :class:`~repro.data.device.DeviceDataStore`
leaves (same shapes — build them with a shared ``pad_to``); seeds pair a
participation PRNG stream with a channel realization lane, exactly like
:func:`repro.fl.engine.run_seed_matrix`.

Both paths share phase-level machinery with their single-run engines, so
the golden-trace layer (tests/golden/) pins their trajectories.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.channel import CellConfig
from ..core.selection import (as_policy_fn, participant_bucket,
                              policy_blend, policy_ledger_ok)
from ..data.device import (DeviceDataStore, data_stream_key,
                           from_client_datasets, gather_participant_rounds)
from ..obs.taps import merge_metrics, metrics_active
from ..obs.telemetry import emit_run_manifest
from ..optim import Optimizer, sgd
from .state import AggParams, AggregatorConfig

__all__ = ["SchemeSpec", "SchemeMatrixResult", "default_scheme_panel",
           "run_scheme_matrix", "stack_stores"]


@dataclasses.dataclass(frozen=True)
class SchemeSpec:
    """One lane of the comparison: a named (policy, aggregator) pair."""

    name: str
    policy: Any                       # PolicyFn or legacy Policy object
    aggregator: AggregatorConfig

    def policy_fn(self):
        return as_policy_fn(self.policy)


def default_scheme_panel(spec, num_clients: int, rhos: Sequence[float] = (),
                         p_bar: float = 0.25) -> list[SchemeSpec]:
    """The Fig. 6-7 comparison panel: the paper's scheme against the three
    baseline families from the related work.

    ``spec`` is the :class:`~repro.core.problem.ProblemSpec` the paper's
    online solve needs; ``rhos`` adds one paper lane per tradeoff
    coefficient (empty keeps a single ``rho=None`` lane).  ``p_bar`` sets
    the baselines' expected participation fraction so energy budgets are
    comparable across lanes.
    """
    from ..core.selection import (age_aware_policy, csma_policy,
                                  online_policy, random_policy)

    K = num_clients
    k = max(1, int(round(p_bar * K)))
    panel = []
    if rhos:
        for rho in rhos:
            panel.append(SchemeSpec(
                f"paper-rho{rho:g}", online_policy(spec, rho=float(rho)),
                AggregatorConfig(kind="paper")))
    else:
        panel.append(SchemeSpec("paper", online_policy(spec),
                                AggregatorConfig(kind="paper")))
    panel += [
        SchemeSpec("fedasync-poly", random_policy(p_bar, K),
                   AggregatorConfig(kind="fedasync", staleness_fn="poly")),
        SchemeSpec("fedasync-hinge", random_policy(p_bar, K),
                   AggregatorConfig(kind="fedasync", staleness_fn="hinge")),
        SchemeSpec("csmaafl", csma_policy(k, K),
                   AggregatorConfig(kind="csmaafl")),
        SchemeSpec("age-aware", age_aware_policy(k, K),
                   AggregatorConfig(kind="age")),
    ]
    return panel


class SchemeMatrixResult(NamedTuple):
    """Stacked traces with leading axes ``[V, L, S]`` = severities ×
    schemes × seed lanes."""

    schemes: tuple                 # L lane names
    acc: np.ndarray                # [V, L, S, n_evals]
    loss: np.ndarray               # [V, L, S, n_evals]
    eval_rounds: np.ndarray        # [n_evals]
    energy: np.ndarray             # [V, L, S, K] cumulative Joules
    energy_timeline: np.ndarray    # [V, L, S, T] cumulative total Joules
    participation: np.ndarray      # [V, L, S, T, K]
    # per-lane MetricsState ([V, L, S]-leading leaves) when cfg.metrics
    # enables taps; None otherwise.
    metrics: Any = None


def stack_stores(stores: Sequence[DeviceDataStore]) -> DeviceDataStore:
    """Stack same-shaped severity stores onto a leading vmap axis.

    Build the members with a shared ``pad_to`` cap
    (:func:`~repro.data.device.from_client_datasets`) — severity changes
    the per-client *distribution*, not the padded shapes.
    """
    first = jax.tree_util.tree_map(lambda l: (l.shape, l.dtype), stores[0])
    for s in stores[1:]:
        other = jax.tree_util.tree_map(lambda l: (l.shape, l.dtype), s)
        if other != first:
            raise ValueError(
                "severity stores must share shapes/dtypes to ride one vmap "
                "axis — build them with a common pad_to cap")
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *stores)


def _as_store(data) -> DeviceDataStore:
    return (data if isinstance(data, DeviceDataStore)
            else from_client_datasets(data))


def _stack_agg_params(schemes: Sequence[SchemeSpec]) -> AggParams:
    return jax.tree_util.tree_map(
        lambda *ls: jnp.stack(ls), *[s.aggregator.params() for s in schemes])


def _collapse_evals(dids: np.ndarray) -> np.ndarray:
    # did_eval depends only on t — identical across every lane
    did_t = dids.reshape(-1, dids.shape[-1])[0]
    return np.where(did_t)[0]


def run_scheme_matrix(init_params, loss_fn: Callable, acc_fn: Callable,
                      stores, test_ds, schemes: Sequence[SchemeSpec],
                      h_stack: jax.Array, cell: CellConfig, cfg,
                      seeds: Sequence[int], opt: Optimizer | None = None,
                      participation: str = "dense") -> SchemeMatrixResult:
    """Run every scheme × seed lane × severity in one device program.

    ``stores``: one client dataset list / :class:`DeviceDataStore`, or a
    sequence of them (the non-IID severity axis; shapes must match — see
    :func:`stack_stores`).  ``h_stack: [S, K, T]`` channel realizations
    pair with ``seeds`` as in :func:`~repro.fl.engine.run_seed_matrix`.

    ``participation`` picks the execution path — ``"dense"`` (the
    [K]-shaped scan engine) or ``"sparse"`` (the participant-centric
    two-phase path; requires the sparse preconditions on ``cfg`` and
    state-free/ledger policies).  Both fan out with vmap axes
    ``[V severities, L schemes, S seeds]`` and compile exactly once.

    ``cfg.aggregator`` is ignored per-lane: each scheme's
    :class:`AggregatorConfig` rides the scheme axis as traced
    :class:`AggParams`.  ``cfg.faults`` / ``cfg.guards`` thread through
    unchanged (the fault/guard carry is shared machinery with the
    single-run engines).
    """
    from .engine import build_scan_sim
    from .sparse import (build_participation_program,
                         build_sparse_train_program)

    if not schemes:
        raise ValueError("run_scheme_matrix needs at least one SchemeSpec")
    if participation not in ("dense", "sparse"):
        raise ValueError(f"unknown participation {participation!r} "
                         "(expected dense|sparse)")
    K = int(h_stack.shape[1])
    T = int(h_stack.shape[2])
    L = len(schemes)
    opt = opt or sgd(cfg.lr)
    fns = [s.policy_fn() for s in schemes]
    # the compiled program always takes the scheme branch; the per-lane
    # traced AggParams decide which weights each lane realizes
    run_cfg = dataclasses.replace(cfg, rounds=T,
                                  aggregator=schemes[0].aggregator)
    if isinstance(stores, (list, tuple)):
        store_stack = stack_stores([_as_store(s) for s in stores])
    else:
        store_stack = jax.tree_util.tree_map(
            lambda l: l[None], _as_store(stores))
    V = int(store_stack.x.shape[0])
    if int(store_stack.x.shape[1]) != K:
        raise ValueError(
            f"store client axis {int(store_stack.x.shape[1])} != channel "
            f"stack K {K}")

    test_x = test_ds.x[: cfg.eval_batch]
    test_y = test_ds.y[: cfg.eval_batch]
    data_key = data_stream_key(cfg.seed)
    keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])
    h_rounds = jnp.swapaxes(h_stack, 1, 2)              # [S, T, K]
    sel_eye = jnp.eye(L, dtype=jnp.float32)
    ap_stack = _stack_agg_params(schemes)
    tapped = metrics_active(run_cfg.metrics, run_cfg.guards)
    emit_run_manifest("run_scheme_matrix", run_cfg,
                      extra={"path": participation, "schemes": L,
                             "lanes": len(seeds), "severities": V,
                             "num_clients": K})

    if participation == "dense":
        def one(sel, ap, key, h, store):
            pol = policy_blend(fns, sel)
            sim = build_scan_sim(loss_fn, acc_fn, opt, run_cfg, cell, K,
                                 pol, shard_clients=False,
                                 data_mode="device")
            return sim(init_params, store, data_key, h, key, test_x,
                       test_y, agg_params=ap)

        seed_lanes = jax.vmap(one, in_axes=(None, None, 0, 0, None))
        scheme_lanes = jax.vmap(seed_lanes, in_axes=(0, 0, None, None, None))
        fan = jax.jit(jax.vmap(scheme_lanes,
                               in_axes=(None, None, None, None, 0)))
        out = fan(sel_eye, ap_stack, keys, h_rounds, store_stack)
        energy, traces = out[1], out[2]
        ms = out[3] if tapped else None
        e_round = np.asarray(traces.e_round)            # [V, L, S, T, K]
        ev = _collapse_evals(np.asarray(traces.did_eval))
        return SchemeMatrixResult(
            schemes=tuple(s.name for s in schemes),
            acc=np.asarray(traces.acc)[..., ev],
            loss=np.asarray(traces.loss)[..., ev],
            eval_rounds=ev,
            energy=np.asarray(energy),
            energy_timeline=np.cumsum(e_round.sum(axis=-1), axis=-1),
            participation=np.asarray(traces.mask),
            metrics=(jax.tree_util.tree_map(np.asarray, ms)
                     if ms is not None else None),
        )

    # ---- sparse path ------------------------------------------------------
    for s, fn in zip(schemes, fns):
        if not policy_ledger_ok(fn):
            raise ValueError(
                f"scheme {s.name!r}: the sparse path needs a state_free or "
                "ledger policy")
    if run_cfg.local_mode != "participants":
        raise ValueError("sparse scheme matrix requires "
                         "SimConfig(local_mode='participants')")
    if run_cfg.data_stream != "client":
        raise ValueError("sparse scheme matrix requires "
                         "SimConfig(data_stream='client')")
    bucket = run_cfg.participant_bucket
    if bucket is None:
        # shared static bucket: max expected transmitting mass over the
        # panel (ledger policies probed at zero staleness — the Poisson
        # headroom absorbs it, the overflow check below stays exact)
        ts = jnp.arange(T, dtype=jnp.int32)
        expected = 0.0
        for fn in fns:
            probs = jax.jit(jax.vmap(
                lambda t, h, f=fn: f(t, h, None)[0]))(ts, h_rounds[0])
            expected = max(expected, float(jnp.max(jnp.sum(probs, -1))))
        bucket = participant_bucket(expected, cap=K)

    ltap = metrics_active(run_cfg.metrics, None, parts="ledger")
    ttap = metrics_active(run_cfg.metrics, run_cfg.guards, parts="train")

    def one_sparse(sel, ap, key, h, store):
        pol = policy_blend(fns, sel)
        phase_a = build_participation_program(pol, run_cfg, cell, K, bucket)
        pa = phase_a(h, key)
        energy, ptr = pa[1], pa[2]
        ms_a = pa[3] if ltap else None
        xb, yb = gather_participant_rounds(store, data_key, ptr.part_idx,
                                           run_cfg.local_iters,
                                           run_cfg.batch_size)
        train = build_sparse_train_program(loss_fn, acc_fn, opt, run_cfg)
        tout = train(
            init_params, xb, yb, ptr.valid, ptr.anchor_slot, jnp.int32(K),
            test_x, test_y, ptr.delivered, ptr.corrupt, ptr.stale,
            ptr.prob, ap)
        accs, losses, dids = tout[1]
        ms_b = tout[2] if ttap else None
        # None halves are pytree structure — they vmap as absent leaves
        return energy, accs, losses, dids, ptr, merge_metrics(ms_a, ms_b)

    seed_lanes = jax.vmap(one_sparse, in_axes=(None, None, 0, 0, None))
    scheme_lanes = jax.vmap(seed_lanes, in_axes=(0, 0, None, None, None))
    fan = jax.jit(jax.vmap(scheme_lanes,
                           in_axes=(None, None, None, None, 0)))
    energy, accs, losses, dids, ptr, ms = fan(sel_eye, ap_stack, keys,
                                              h_rounds, store_stack)
    n_tx = np.asarray(ptr.n_tx)
    if (n_tx > bucket).any():
        raise RuntimeError(
            f"scheme-matrix participant bucket overflow: a lane realized "
            f"{int(n_tx.max())} transmitters > bucket {bucket} — pass "
            "SimConfig(participant_bucket=...) with more headroom")

    # host-side densification of the [V, L, S, T, P] participant trace
    idx = np.asarray(ptr.part_idx)
    val = np.asarray(ptr.valid)
    e_p = np.asarray(ptr.e_p)
    parts = np.zeros((V, L, len(seeds), T, K), np.float32)
    e_round = np.zeros((V, L, len(seeds), T, K), np.float32)
    vi, li, si, ti, _ = np.nonzero(val)
    parts[vi, li, si, ti, idx[val]] = 1.0
    e_round[vi, li, si, ti, idx[val]] = e_p[val]
    ev = _collapse_evals(np.asarray(dids))
    return SchemeMatrixResult(
        schemes=tuple(s.name for s in schemes),
        acc=np.asarray(accs)[..., ev],
        loss=np.asarray(losses)[..., ev],
        eval_rounds=ev,
        energy=np.asarray(energy),
        energy_timeline=np.cumsum(e_round.sum(axis=-1), axis=-1),
        participation=parts,
        metrics=(jax.tree_util.tree_map(np.asarray, ms)
                 if (ltap or ttap) else None),
    )

"""Device-side metrics taps: pure jittable reducers in the scan carry.

The paper's argument is about *dynamics* — staleness Δ_k, per-client energy
(eq. 5), the selection-probability trade-off — but a scan that only reads
back end-of-run curves cannot show them.  A :class:`MetricsSpec` turns on a
set of per-round reducers whose accumulators are fixed-shape device buffers
carried through the scan:

* **participation counts** — ``tx_count [K] i32``: how often each client's
  Bernoulli/Δ_k decision fired (the realized selection distribution);
* **staleness histogram** — ``stale_hist [bins] i32``: Δτ at transmission
  time over *delivered* uploads (last bin is open-ended);
* **energy by cause** — ``energy_cause [3] f32``: eq.-5 Joules split into
  voluntary uploads, Δ_k-forced uploads, and retry overhead paid to the
  lossy-uplink fault process;
* **guard interventions** — ``guard_events [3] i32``: per-round counts of
  quarantined (non-finite), norm-clipped, and staleness-capped updates
  (only materialized when ``cfg.guards`` is active);
* **aggregation-weight stats** — ``weight_entropy``/``weight_max``: entropy
  of the normalized per-round aggregation weights (summed over rounds) and
  the running max weight — how concentrated the global update is.

Design rules the engines rely on:

* **bit-parity when disabled** — ``SimConfig.metrics=None`` (the default)
  adds nothing to any carry or program; the golden traces and every parity
  test run unchanged.  Taps are read-only: enabling them never perturbs the
  simulated trajectory either.
* **fixed shapes, None leaves** — disabled individual taps are ``None``
  fields of the :class:`MetricsState` NamedTuple.  ``None`` is pytree
  *structure*, not a leaf, so any tap subset is jit/vmap-safe (the matrix
  runners fan MetricsState out over their lane axes like any other carry).
* **split accumulation** — the sparse two-phase path computes the ledger
  taps (participation/staleness/energy) in one batched post-scan reduction
  over phase A's ``[T, P]`` participation-trace lanes (its sequential scan
  carries no tap state) and the train taps (guards/weights) in phase B's
  bucket program; :func:`merge_metrics` joins the halves.  Integer taps
  agree exactly with the dense engine; float reductions agree to
  float-associativity.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["MetricsSpec", "MetricsState", "init_metrics", "metrics_active",
           "update_ledger_taps", "update_train_taps", "metrics_round_update",
           "merge_metrics", "metrics_summary"]


@dataclasses.dataclass(frozen=True)
class MetricsSpec:
    """Which in-scan reducers to run (frozen ⇒ usable in jitted closures).

    The default constructor is the *default tap set* — everything on — whose
    per-round overhead is bounded by ``benchmarks/bench_obs.py`` (a few
    [K]-vector ops against the K·L·B local-training cost).
    ``MetricsSpec.none()`` is all-off, which must trace to the identical
    program as ``metrics=None`` (tests pin the jaxpr).
    """

    participation: bool = True     # tx_count [K]
    staleness_hist: bool = True    # stale_hist [staleness_bins]
    staleness_bins: int = 8        # linear bins 0..bins-2, last bin open
    energy_by_cause: bool = True   # energy_cause [3]
    guard_events: bool = True      # guard_events [3] (needs active guards)
    weight_stats: bool = True      # weight_entropy / weight_max scalars

    def __post_init__(self):
        if self.staleness_bins < 2:
            raise ValueError("staleness_bins must be >= 2 "
                             f"(got {self.staleness_bins})")

    @classmethod
    def none(cls) -> "MetricsSpec":
        return cls(participation=False, staleness_hist=False,
                   energy_by_cause=False, guard_events=False,
                   weight_stats=False)

    @property
    def ledger_active(self) -> bool:
        """Taps computable from the [K] decision/ledger vectors alone."""
        return (self.participation or self.staleness_hist
                or self.energy_by_cause)

    def train_active(self, guards=None) -> bool:
        """Taps that need the deltas / aggregation weights."""
        return self.weight_stats or (
            self.guard_events and guards is not None
            and getattr(guards, "active", False))


class MetricsState(NamedTuple):
    """Fixed-shape accumulators; a disabled tap's field is ``None`` (pytree
    structure, not a leaf — vmap/jit treat any subset uniformly)."""

    tx_count: Any = None        # [K] i32 — decision-mask fires per client
    stale_hist: Any = None      # [bins] i32 — Δτ of delivered uploads
    energy_cause: Any = None    # [3] f32 — (voluntary, forced, retry)
    guard_events: Any = None    # [3] i32 — (quarantined, clipped, capped)
    weight_entropy: Any = None  # scalar f32 — Σ_rounds H(normalized weights)
    weight_max: Any = None      # scalar f32 — running max weight
    rounds: Any = None          # scalar i32 — ledger rounds accumulated
    agg_rounds: Any = None      # scalar i32 — train rounds accumulated


def metrics_active(spec: MetricsSpec | None, guards=None,
                   parts: str = "all") -> bool:
    """Would :func:`init_metrics` materialize any buffer?  Pure predicate —
    the engines use it to decide the carry structure, so it must agree with
    :func:`init_metrics` exactly."""
    if spec is None:
        return False
    ledger = parts in ("all", "ledger") and spec.ledger_active
    train = parts in ("all", "train") and spec.train_active(guards)
    return ledger or train


def init_metrics(spec: MetricsSpec | None, num_clients: int, guards=None,
                 parts: str = "all") -> MetricsState | None:
    """Zeroed accumulators for the enabled taps, or ``None`` when nothing is
    enabled (the carry then stays byte-identical to the untapped program).

    ``parts`` selects the accumulator subset for the sparse path's split
    accumulation: ``"ledger"`` (phase A), ``"train"`` (phase B), or
    ``"all"`` (dense scan / legacy loop).
    """
    if not metrics_active(spec, guards, parts):
        return None
    ledger = parts in ("all", "ledger") and spec.ledger_active
    train = parts in ("all", "train") and spec.train_active(guards)
    ge = (train and spec.guard_events and guards is not None
          and getattr(guards, "active", False))
    ws = train and spec.weight_stats
    return MetricsState(
        tx_count=(jnp.zeros((num_clients,), jnp.int32)
                  if ledger and spec.participation else None),
        stale_hist=(jnp.zeros((spec.staleness_bins,), jnp.int32)
                    if ledger and spec.staleness_hist else None),
        energy_cause=(jnp.zeros((3,), jnp.float32)
                      if ledger and spec.energy_by_cause else None),
        guard_events=jnp.zeros((3,), jnp.int32) if ge else None,
        weight_entropy=jnp.zeros((), jnp.float32) if ws else None,
        weight_max=jnp.zeros((), jnp.float32) if ws else None,
        rounds=jnp.zeros((), jnp.int32) if ledger else None,
        agg_rounds=jnp.zeros((), jnp.int32) if train else None,
    )


def update_ledger_taps(ms: MetricsState, spec: MetricsSpec, *,
                       mask: jax.Array, forced: jax.Array,
                       e_base: jax.Array, e_round: jax.Array,
                       staleness: jax.Array,
                       delivered: jax.Array) -> MetricsState:
    """One round of the [K]-vector taps (dense round step and legacy loop;
    sparse phase A reduces the same quantities post-scan from participant
    trace lanes, bit-exact for the integer accumulators because the lanes
    are exactly the mask fires).

    ``e_base`` is the eq.-5 decision energy *before* the fault pipeline,
    ``e_round`` what was actually paid (retry multipliers, dropped uploads);
    the retry-overhead lane is ``Σ relu(e_round − e_base)``.
    """
    upd = {}
    if ms.tx_count is not None:
        upd["tx_count"] = ms.tx_count + (mask > 0).astype(jnp.int32)
    if ms.stale_hist is not None:
        bins = ms.stale_hist.shape[0]
        b = jnp.clip(staleness.astype(jnp.int32), 0, bins - 1)
        upd["stale_hist"] = ms.stale_hist.at[b].add(
            (delivered > 0).astype(jnp.int32))
    if ms.energy_cause is not None:
        f = forced.astype(jnp.float32)
        e = e_round.astype(jnp.float32)
        retry = jnp.maximum(e - e_base.astype(jnp.float32), 0.0)
        upd["energy_cause"] = ms.energy_cause + jnp.stack(
            [jnp.sum(e * (1.0 - f)), jnp.sum(e * f), jnp.sum(retry)])
    if ms.rounds is not None:
        upd["rounds"] = ms.rounds + 1
    return ms._replace(**upd)


def _effective_weights(deltas, delivered, staleness, probs, num_clients,
                       guards, agg_params):
    """Each row's weight in the round's eq.-3 update: the weights over the
    divisor that :func:`repro.fl.state.round_weights` hands the engines.
    Recomputed here (a few row-vector ops) so the untapped program stays
    untouched."""
    from ..fl.state import round_weights

    w, denom, _ = round_weights(delivered, num_clients, staleness, probs,
                                agg_params, guards, deltas)
    return w.astype(jnp.float32) / jnp.asarray(denom, jnp.float32)


def update_train_taps(ms: MetricsState, spec: MetricsSpec, *,
                      deltas: Any, delivered: jax.Array,
                      staleness: jax.Array, probs: jax.Array,
                      num_clients, guards=None,
                      agg_params=None) -> MetricsState:
    """One round of the delta/weight taps.  The row axis may be the
    population (dense/legacy) or the participant bucket (sparse phase B) —
    counts agree exactly, float reductions to associativity."""
    from ..fl.state import finite_rows, update_norms

    upd = {}
    dlv = (delivered > 0) if delivered.dtype != jnp.bool_ else delivered
    if ms.guard_events is not None:
        q = dlv & ~finite_rows(deltas)
        if guards.clip_norm is not None:
            c = dlv & (update_norms(deltas) > guards.clip_norm)
        else:
            c = jnp.zeros(dlv.shape, bool)
        if guards.staleness_cap is not None:
            s = dlv & (staleness > guards.staleness_cap)
        else:
            s = jnp.zeros(dlv.shape, bool)
        upd["guard_events"] = ms.guard_events + jnp.stack(
            [jnp.sum(q.astype(jnp.int32)), jnp.sum(c.astype(jnp.int32)),
             jnp.sum(s.astype(jnp.int32))])
    if ms.weight_entropy is not None:
        a = _effective_weights(deltas, dlv, staleness, probs, num_clients,
                               guards, agg_params)
        tot = jnp.maximum(jnp.sum(a), 1e-30)
        p = a / tot
        ent = -jnp.sum(jnp.where(a > 0, p * jnp.log(jnp.maximum(p, 1e-30)),
                                 0.0))
        upd["weight_entropy"] = ms.weight_entropy + ent
        upd["weight_max"] = jnp.maximum(ms.weight_max, jnp.max(a))
    if ms.agg_rounds is not None:
        upd["agg_rounds"] = ms.agg_rounds + 1
    return ms._replace(**upd)


def metrics_round_update(ms: MetricsState, spec: MetricsSpec, *,
                         mask, forced, e_base, e_round, staleness,
                         delivered, deltas, probs, num_clients,
                         guards=None, agg_params=None) -> MetricsState:
    """The dense round step's one-call update: ledger taps + train taps."""
    ms = update_ledger_taps(ms, spec, mask=mask, forced=forced,
                            e_base=e_base, e_round=e_round,
                            staleness=staleness, delivered=delivered)
    if ms.agg_rounds is not None:
        ms = update_train_taps(ms, spec, deltas=deltas, delivered=delivered,
                               staleness=staleness, probs=probs,
                               num_clients=num_clients, guards=guards,
                               agg_params=agg_params)
    return ms


def merge_metrics(a: MetricsState | None,
                  b: MetricsState | None) -> MetricsState | None:
    """Join split accumulations (sparse phase A ledger + phase B train):
    fieldwise, taking whichever half materialized the buffer."""
    if a is None:
        return b
    if b is None:
        return a
    return MetricsState(*[(x if x is not None else y)
                          for x, y in zip(a, b)])


def metrics_summary(ms: MetricsState | None) -> dict:
    """Host-side readback: one dict of plain numbers/lists per enabled tap
    (manifest- and JSON-friendly)."""
    import numpy as np

    if ms is None:
        return {}
    out = {}
    if ms.tx_count is not None:
        tx = np.asarray(ms.tx_count)
        out["tx_count"] = tx.tolist()
        out["tx_total"] = int(tx.sum())
    if ms.stale_hist is not None:
        out["stale_hist"] = np.asarray(ms.stale_hist).tolist()
    if ms.energy_cause is not None:
        e = np.asarray(ms.energy_cause)
        out["energy_voluntary"] = float(e[0])
        out["energy_forced"] = float(e[1])
        out["energy_retry_overhead"] = float(e[2])
    if ms.guard_events is not None:
        g = np.asarray(ms.guard_events)
        out["guard_quarantined"] = int(g[0])
        out["guard_clipped"] = int(g[1])
        out["guard_stale_capped"] = int(g[2])
    if ms.weight_entropy is not None:
        n = max(int(np.asarray(ms.agg_rounds)), 1)
        out["weight_entropy_mean"] = float(np.asarray(ms.weight_entropy)) / n
        out["weight_max"] = float(np.asarray(ms.weight_max))
    if ms.rounds is not None:
        out["rounds"] = int(np.asarray(ms.rounds))
    return out

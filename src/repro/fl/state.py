"""FL state containers.

``FLState`` holds the server's global model plus the *stacked* per-client
states (leading axis K): each client's divergent local model ``x_k`` and its
anchor ``y_k`` — the last global model it received (paper eq. 2).  Stacking
makes the whole protocol a handful of vmapped/einsummed pytree ops, and at
mega-scale the same leading axis becomes the data-parallel mesh axis.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..kernels.fl_aggregate import fl_aggregate


class FLState(NamedTuple):
    global_params: Any   # pytree, the server's x_t
    client_params: Any   # pytree with leading K axis, x_{k,t}
    anchor_params: Any   # pytree with leading K axis, y_{k,t}
    round: jax.Array     # int32 scalar
    last_tx: jax.Array   # [K] int32, round of last transmission (staleness)


def replicate(params: Any, k: int) -> Any:
    return jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p[None], (k,) + p.shape), params)


def init_fl_state(params: Any, num_clients: int) -> FLState:
    stacked = replicate(params, num_clients)
    return FLState(
        global_params=params,
        client_params=stacked,
        anchor_params=stacked,
        round=jnp.zeros((), jnp.int32),
        last_tx=jnp.zeros((num_clients,), jnp.int32),
    )


def pseudo_gradients(state: FLState) -> Any:
    """Eq. (2): δ_k = x_k − y_k (stacked over clients)."""
    return jax.tree_util.tree_map(lambda c, a: c - a,
                                  state.client_params, state.anchor_params)


def masked_aggregate(global_params: Any, deltas: Any, mask: jax.Array,
                     num_clients, use_pallas: bool | str | None = None) -> Any:
    """Eq. (3), the one aggregation update: x ← x + (Σ_r mask_r·δ_r) / K.

    ``mask`` is any per-row weight: the {0, 1} delivery mask of the paper's
    update, or the weights :func:`round_weights` builds for guards and
    schemes.  ``num_clients`` is the divisor — the population K, or 1 when
    the weights already hold the scaling.  The row axis may be the
    population (dense engine) or a padded participant bucket (sparse phase
    B, the server's micro-batch).

    ``use_pallas=None`` picks by backend: on a TPU every leaf runs through
    the fused ``kernels.fl_aggregate`` kernel, elsewhere the jnp expression
    below.  ``True``/``False`` force a path (the client-sharded engine
    forces jnp: XLA cannot partition a Mosaic kernel); ``"interpret"`` runs
    the kernel body in the Pallas interpreter (the CPU parity tests).  A
    Python-int divisor reaches the kernel as its static ``denom``; a traced
    one (the sparse path and the server pass the population as data, so one
    program serves every K) is folded into the weights.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    traced = not isinstance(num_clients, int)
    if traced:
        num_clients = jnp.asarray(num_clients, jnp.float32)
    if use_pallas:
        w = mask.astype(jnp.float32)
        w, denom = (w / num_clients, 1) if traced else (w, num_clients)

        def agg_k(g, d):
            out = fl_aggregate(g.reshape(-1), d.reshape(d.shape[0], -1), w,
                               interpret=use_pallas == "interpret",
                               denom=denom)
            return out.reshape(g.shape).astype(g.dtype)

        return jax.tree_util.tree_map(agg_k, global_params, deltas)

    def agg(g, d):
        m = mask.astype(d.dtype).reshape((-1,) + (1,) * (d.ndim - 1))
        return g + jnp.sum(d * m, axis=0) / num_clients

    return jax.tree_util.tree_map(agg, global_params, deltas)


def finite_rows(deltas: Any) -> jax.Array:
    """Per-row (client/participant) finiteness of a stacked delta pytree:
    ``[R] bool``, False when *any* element of the row, in any leaf, is
    NaN/Inf."""
    def leaf_ok(d):
        return jnp.all(jnp.isfinite(d).reshape(d.shape[0], -1), axis=1)

    oks = [leaf_ok(d) for d in jax.tree_util.tree_leaves(deltas)]
    out = oks[0]
    for o in oks[1:]:
        out = out & o
    return out


def update_norms(deltas: Any) -> jax.Array:
    """Per-row global L2 norm across every leaf of a stacked delta pytree
    (``[R] f32``).  Non-finite elements contribute 0 so the clip factor of a
    quarantined row stays well-defined (the row is rejected anyway)."""
    def leaf_sq(d):
        d = d.reshape(d.shape[0], -1).astype(jnp.float32)
        d = jnp.where(jnp.isfinite(d), d, 0.0)
        return jnp.sum(d * d, axis=1)

    sq = sum(leaf_sq(d) for d in jax.tree_util.tree_leaves(deltas))
    return jnp.sqrt(sq)


def guard_weights(deltas: Any, staleness: jax.Array, guards) -> tuple:
    """Defensive per-row weights + sanitized deltas for aggregation.

    ``guards`` is a :class:`repro.fl.faults.GuardConfig`.  Returns
    ``(weights [R] f32, deltas')`` where the effective aggregation mask is
    ``mask · weights``:

    * quarantine: non-finite rows get weight 0 **and** are zeroed in
      ``deltas'`` (``0 · NaN = NaN`` — masking alone cannot reject them);
    * norm clip: finite rows are scaled by ``min(1, clip/‖δ‖)`` — folded
      into the weight, the deltas themselves are untouched;
    * staleness: ``(1 + Δτ)^{-power}`` down-weighting and the optional hard
      cap Δτ ≤ ``staleness_cap``.

    Every defense is a pure per-row scalar, so the weights compose with any
    float participation mask and ride the same fused aggregation kernels.
    """
    rows = staleness.shape[0]
    w = jnp.ones((rows,), jnp.float32)
    out = deltas
    if guards.quarantine:
        ok = finite_rows(deltas)
        w = w * ok.astype(jnp.float32)

        def zap(d):
            m = ok.reshape((-1,) + (1,) * (d.ndim - 1))
            return jnp.where(m, d, jnp.zeros_like(d))

        out = jax.tree_util.tree_map(zap, deltas)
    if guards.clip_norm is not None:
        n = update_norms(deltas)
        w = w * jnp.minimum(1.0, guards.clip_norm / jnp.maximum(n, 1e-30))
    if guards.staleness_power != 0.0:
        s = staleness.astype(jnp.float32)
        w = w * (1.0 + jnp.maximum(s, 0.0)) ** (-guards.staleness_power)
    if guards.staleness_cap is not None:
        w = w * (staleness <= guards.staleness_cap).astype(jnp.float32)
    return w, out


# ---------------------------------------------------------------------------
# pluggable staleness-aware aggregators (the competing async-FL schemes)
#
# The paper's eq.-3 update weighs every delivered pseudo-gradient by 1/K.
# The related-work baselines replace that constant with per-update weights
# built from staleness Δτ, the scheme's selection probability, or the update's
# age — expressed here as one branch-free weight program over *traced*
# parameters (AggParams), so a whole scheme panel can share a single compiled
# simulation with the scheme on a vmap axis (fl/schemes.run_scheme_matrix).
# All baselines are delta-form adaptations: x ← x + Σ_k a_k·δ_k, where the
# a_k of the normalized kinds sum to the mixing rate α over the delivered
# set (docs/schemes.md spells out each scheme's a_k).
# ---------------------------------------------------------------------------

_AGG_KINDS = ("paper", "fedasync", "csmaafl", "age")
_STALENESS_FNS = ("constant", "hinge", "poly")


class AggParams(NamedTuple):
    """Traced counterparts of :class:`AggregatorConfig` — a pytree of f32
    scalars.  The one-hot ``kind_*`` / ``sfn_*`` lanes make the weight
    program branch-free, so stacking AggParams along a leading axis and
    vmapping sweeps *schemes* in one device program (the same trick
    :class:`repro.fl.faults.FaultParams` plays for failure severities)."""

    kind_paper: jax.Array
    kind_fedasync: jax.Array
    kind_csmaafl: jax.Array
    kind_age: jax.Array
    sfn_constant: jax.Array
    sfn_hinge: jax.Array
    sfn_poly: jax.Array
    mix: jax.Array
    hinge_a: jax.Array
    hinge_b: jax.Array
    poly_a: jax.Array
    age_a: jax.Array
    prob_floor: jax.Array


@dataclasses.dataclass(frozen=True)
class AggregatorConfig:
    """Staleness-aware aggregation scheme (frozen ⇒ usable in jitted
    closures; it shapes the program, :meth:`params` carries the math).

    Kinds:

    * ``"paper"`` — eq. 3 verbatim: a_k = m_k / K (the reproduction's
      default; ``SimConfig.aggregator=None`` keeps the byte-identical
      legacy program, ``kind="paper"`` runs the same math through the
      weighted path so it can share a vmapped scheme axis).
    * ``"fedasync"`` — FedAsync-style staleness-attenuated mixing
      (arXiv:1903.03934): raw weight s(Δτ_k), normalized over the delivered
      set and scaled by the server mixing rate α (``mix``) — the delta-form
      reading of averaging the per-client mixed models
      ``(1−α_t)x + α_t x_k`` with ``α_t = α·s(Δτ)``.
    * ``"csmaafl"`` — CSMAAFL-style aggregation (arXiv:2306.01207):
      contention-based scheduling makes participation non-uniform, so the
      delivered updates are importance-weighted by the inverse selection
      probability, raw = s(Δτ_k)/max(p_k, prob_floor), normalized, scaled
      by α — debiasing what the channel-aware contention skewed.
    * ``"age"`` — Hu–Chen–Larsson age-aware weighting (arXiv:2212.07356):
      raw = (1 + Δτ_k)^{+age_a} — updates from long-unheard clients count
      *more*, equalizing each client's effective footprint on the global
      model when the scheduler (``age_aware_policy``) cannot serve everyone.

    ``staleness_fn`` picks s(Δτ) for the fedasync/csmaafl kinds:
    ``"constant"`` (1), ``"hinge"`` (1 for Δτ ≤ b, else 1/(a·(Δτ−b))) or
    ``"poly"`` ((1+Δτ)^{−a}) — the three FedAsync variants.
    """

    kind: str = "paper"
    staleness_fn: str = "constant"
    mix: float = 0.6           # α — server mixing rate of the normalized kinds
    hinge_a: float = 10.0
    hinge_b: float = 4.0
    poly_a: float = 0.5
    age_a: float = 0.5
    prob_floor: float = 1e-2   # csmaafl importance-weight clamp (forced or
                               # near-zero-probability uploads stay bounded)

    def __post_init__(self):
        if self.kind not in _AGG_KINDS:
            raise ValueError(f"unknown aggregator kind {self.kind!r} "
                             f"(expected one of {_AGG_KINDS})")
        if self.staleness_fn not in _STALENESS_FNS:
            raise ValueError(f"unknown staleness_fn {self.staleness_fn!r} "
                             f"(expected one of {_STALENESS_FNS})")

    def params(self) -> AggParams:
        """The traced-parameter view (everything a vmap axis may sweep)."""
        return AggParams(
            kind_paper=jnp.float32(self.kind == "paper"),
            kind_fedasync=jnp.float32(self.kind == "fedasync"),
            kind_csmaafl=jnp.float32(self.kind == "csmaafl"),
            kind_age=jnp.float32(self.kind == "age"),
            sfn_constant=jnp.float32(self.staleness_fn == "constant"),
            sfn_hinge=jnp.float32(self.staleness_fn == "hinge"),
            sfn_poly=jnp.float32(self.staleness_fn == "poly"),
            mix=jnp.float32(self.mix),
            hinge_a=jnp.float32(self.hinge_a),
            hinge_b=jnp.float32(self.hinge_b),
            poly_a=jnp.float32(self.poly_a),
            age_a=jnp.float32(self.age_a),
            prob_floor=jnp.float32(self.prob_floor),
        )


def staleness_scale(staleness: jax.Array, ap: AggParams) -> jax.Array:
    """FedAsync's s(Δτ) per row, branch-free over the one-hot ``sfn_*``
    selector: constant 1, hinge ``1/(a·(Δτ−b))`` past the knee, or
    polynomial ``(1+Δτ)^{−a}``.  Always finite and positive for Δτ ≥ 0."""
    s = jnp.maximum(staleness.astype(jnp.float32), 0.0)
    hinge = jnp.where(s <= ap.hinge_b, 1.0,
                      1.0 / jnp.maximum(ap.hinge_a * (s - ap.hinge_b), 1e-6))
    poly = (1.0 + s) ** (-ap.poly_a)
    return ap.sfn_constant * 1.0 + ap.sfn_hinge * hinge + ap.sfn_poly * poly


def scheme_weights(mask: jax.Array, staleness: jax.Array, probs: jax.Array,
                   ap: AggParams, num_clients) -> jax.Array:
    """Per-row delta weights a_k of the configured aggregation scheme.

    ``mask`` is the effective delivery mask (``{0,1}`` decisions, possibly
    scaled by guard weights), ``staleness`` the per-row Δτ at transmission
    time, ``probs`` the policy's selection probabilities (the csmaafl
    importance weight divides by them), ``num_clients`` the population size
    (may be traced — the sparse path's bucket program passes it that way).

    Invariants (the property tests pin them): weights are finite and
    non-negative for any finite non-negative inputs; for the normalized
    kinds, ``Σ a_k = mix`` whenever any delivered mass exists (0 when the
    round delivered nothing); for the paper kind, ``a_k = m_k / K``.
    """
    m = mask.astype(jnp.float32)
    s = staleness_scale(staleness, ap)
    raw_age = (1.0 + jnp.maximum(staleness.astype(jnp.float32), 0.0)) \
        ** ap.age_a
    inv_p = 1.0 / jnp.maximum(probs.astype(jnp.float32), ap.prob_floor)
    raw = (ap.kind_paper * 1.0
           + ap.kind_fedasync * s
           + ap.kind_csmaafl * s * inv_p
           + ap.kind_age * raw_age)
    mraw = m * raw
    norm = mraw / jnp.maximum(jnp.sum(mraw), 1e-30)
    a_paper = m / jnp.asarray(num_clients, jnp.float32)
    return ap.kind_paper * a_paper + (1.0 - ap.kind_paper) * ap.mix * norm


def round_weights(mask: jax.Array, num_clients, staleness: jax.Array,
                  probs: jax.Array, agg, guards, deltas: Any) -> tuple:
    """The rule for which eq.-3 update a round applies, as the arguments
    of :func:`masked_aggregate`: ``(weights, denom, deltas)``.

    Scheme > guards > plain:

    * plain (no scheme, guards off or ``None``): ``(mask, K, deltas)`` —
      the paper's update, its arguments untouched;
    * guards active: ``(mask·g_w, K, deltas')`` with the guard weights and
      the quarantine-zeroed rows of :func:`guard_weights`;
    * a scheme (``agg`` an :class:`AggregatorConfig` or its traced
      :class:`AggParams`): ``(scheme_weights(...), 1, deltas')`` — the
      weights hold the scaling, and guard weights, when active, fold into
      the mask first, so defenses compose with every scheme.

    Rows are the population or the participant bucket; ``num_clients`` may
    be traced; ``probs`` feeds only the csmaafl importance weight.
    """
    guarded = guards is not None and guards.active
    if agg is None and not guarded:
        return mask, num_clients, deltas
    m = mask.astype(jnp.float32)
    if guarded:
        gw, deltas = guard_weights(deltas, staleness, guards)
        m = m * gw
    if agg is None:
        return m, num_clients, deltas
    ap = agg.params() if isinstance(agg, AggregatorConfig) else agg
    return scheme_weights(m, staleness, probs, ap, num_clients), 1, deltas


def broadcast_to_participants(state: FLState, new_global: Any,
                              mask: jax.Array) -> FLState:
    """Protocol Step 5: participants receive x_t (both x_k and y_k reset)."""
    def sel(stacked, g):
        m = mask.reshape((-1,) + (1,) * (g.ndim)).astype(bool)
        return jnp.where(m, g[None], stacked)

    client = jax.tree_util.tree_map(sel, state.client_params, new_global)
    anchor = jax.tree_util.tree_map(sel, state.anchor_params, new_global)
    last_tx = jnp.where(mask.astype(bool), state.round, state.last_tx)
    return state._replace(global_params=new_global, client_params=client,
                          anchor_params=anchor, round=state.round + 1,
                          last_tx=last_tx)

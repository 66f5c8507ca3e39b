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
                     num_clients: int,
                     use_pallas: bool | str | None = None) -> Any:
    """Eq. (3): x ← x + (1/K) Σ_{k∈C_t} δ_k.

    ``use_pallas=None`` auto-selects by backend: on TPU every leaf routes
    through the fused ``kernels.fl_aggregate`` kernel (the op sits on the hot
    path of the scan engine, one HBM pass per tile); elsewhere the jnp path is
    both the oracle and the fastest option.  ``True``/``False`` force a path;
    ``"interpret"`` runs the kernel body in the Pallas interpreter (the CPU
    parity tests) — see :mod:`repro.kernels.ops`.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        from ..kernels import ops

        def agg_k(g, d):
            out = ops.fl_aggregate(g.reshape(-1),
                                   d.reshape(d.shape[0], -1),
                                   mask.astype(jnp.float32),
                                   use_pallas=use_pallas)
            return out.reshape(g.shape).astype(g.dtype)

        return jax.tree_util.tree_map(agg_k, global_params, deltas)

    def agg(g, d):
        m = mask.astype(d.dtype).reshape((-1,) + (1,) * (d.ndim - 1))
        return g + jnp.sum(d * m, axis=0) / num_clients

    return jax.tree_util.tree_map(agg, global_params, deltas)


def subset_aggregate(global_params: Any, deltas_p: Any, valid: jax.Array,
                     num_clients, use_pallas: bool | str | None = None) -> Any:
    """Participant-subset eq. (3): x ← x + (1/K) Σ_p valid_p · δ_p.

    ``deltas_p`` carries a leading *participant bucket* axis P (the gathered
    transmitting set, padded), not the population axis K; ``valid`` masks the
    padding lanes and ``num_clients`` is the population size the paper's
    1/K averaging divides by — it may be a **traced** scalar, which is what
    lets one compiled sparse round step serve every population sharing a
    bucket.  Backend dispatch matches :func:`masked_aggregate`: the fused
    Pallas kernel on TPU (subset form — see ``kernels.ops.fl_aggregate_subset``),
    the jnp oracle elsewhere.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    kf = jnp.asarray(num_clients, jnp.float32)
    if use_pallas:
        from ..kernels import ops

        def agg_k(g, d):
            out = ops.fl_aggregate_subset(
                g.reshape(-1), d.reshape(d.shape[0], -1),
                valid.astype(jnp.float32), kf, use_pallas=use_pallas)
            return out.reshape(g.shape).astype(g.dtype)

        return jax.tree_util.tree_map(agg_k, global_params, deltas_p)

    def agg(g, d):
        m = valid.astype(d.dtype).reshape((-1,) + (1,) * (d.ndim - 1))
        return g + jnp.sum(d * m, axis=0) / kf

    return jax.tree_util.tree_map(agg, global_params, deltas_p)


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


def guarded_aggregate(global_params: Any, deltas: Any, mask: jax.Array,
                      num_clients, staleness: jax.Array, guards,
                      use_pallas: bool | str | None = None) -> Any:
    """Eq. (3) with server-side defenses: x ← x + (1/K) Σ_k m_k·g_k·δ_k.

    ``guards=None`` (or an all-off config) routes straight to
    :func:`masked_aggregate` — bit-identical to the undefended path.  On TPU
    the quarantine runs *inside* the fused kernel
    (``kernels.ops.fl_aggregate_guarded``: non-finite elements are zeroed in
    VMEM, no sanitized [K, D] copy is ever materialized in HBM).
    """
    if guards is None or not guards.active:
        return masked_aggregate(global_params, deltas, mask, num_clients,
                                use_pallas=use_pallas)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    w, safe = guard_weights(deltas, staleness, guards)
    m = mask.astype(jnp.float32) * w
    if use_pallas:
        from ..kernels import ops
        inv = 1.0 / jnp.asarray(num_clients, jnp.float32)

        def agg_k(g, d):
            out = ops.fl_aggregate_guarded(g.reshape(-1),
                                           d.reshape(d.shape[0], -1),
                                           m * inv, use_pallas=use_pallas)
            return out.reshape(g.shape).astype(g.dtype)

        # the kernel zeroes non-finite elements itself — pass raw deltas
        return jax.tree_util.tree_map(agg_k, global_params, deltas)
    return masked_aggregate(global_params, safe, m, num_clients,
                            use_pallas=False)


def guarded_subset_aggregate(global_params: Any, deltas_p: Any,
                             valid: jax.Array, num_clients,
                             staleness_p: jax.Array, guards,
                             use_pallas: bool | str | None = None) -> Any:
    """Participant-subset form of :func:`guarded_aggregate` (sparse path):
    rows are the padded transmitting bucket, ``num_clients`` may be traced."""
    if guards is None or not guards.active:
        return subset_aggregate(global_params, deltas_p, valid, num_clients,
                                use_pallas=use_pallas)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    w, safe = guard_weights(deltas_p, staleness_p, guards)
    v = valid.astype(jnp.float32) * w
    if use_pallas:
        from ..kernels import ops
        inv = 1.0 / jnp.asarray(num_clients, jnp.float32)

        def agg_k(g, d):
            out = ops.fl_aggregate_guarded(g.reshape(-1),
                                           d.reshape(d.shape[0], -1),
                                           v * inv, use_pallas=use_pallas)
            return out.reshape(g.shape).astype(g.dtype)

        return jax.tree_util.tree_map(agg_k, global_params, deltas_p)
    return subset_aggregate(global_params, safe, v, num_clients,
                            use_pallas=False)


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


def weighted_aggregate(global_params: Any, deltas: Any, weights: jax.Array,
                       use_pallas: bool | str | None = None) -> Any:
    """Generic weighted update: x ← x + Σ_r a_r·δ_r.

    The row axis may be the population (dense engine) or the participant
    bucket (sparse phase B) — the weights carry the masking, the 1/K (or
    normalization), and any guard scaling.  On TPU this is the fused
    ``kernels.ops.fl_aggregate_guarded`` kernel (it computes exactly this
    weighted sum, zeroing non-finite elements in VMEM); elsewhere the jnp
    path is the oracle.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        from ..kernels import ops

        def agg_k(g, d):
            out = ops.fl_aggregate_guarded(g.reshape(-1),
                                           d.reshape(d.shape[0], -1),
                                           weights.astype(jnp.float32),
                                           use_pallas=use_pallas)
            return out.reshape(g.shape).astype(g.dtype)

        return jax.tree_util.tree_map(agg_k, global_params, deltas)

    def agg(g, d):
        a = weights.astype(d.dtype).reshape((-1,) + (1,) * (d.ndim - 1))
        return g + jnp.sum(d * a, axis=0)

    return jax.tree_util.tree_map(agg, global_params, deltas)


def scheme_aggregate(global_params: Any, deltas: Any, mask: jax.Array,
                     num_clients, staleness: jax.Array, probs: jax.Array,
                     agg, guards=None,
                     use_pallas: bool | str | None = None) -> Any:
    """Population-row aggregation under a pluggable scheme (+ optional
    guards).

    ``agg`` is an :class:`AggregatorConfig` or its traced :class:`AggParams`;
    guard weights (quarantine / norm clip / staleness defenses) fold into the
    mask before the scheme weights are computed, so defenses compose with
    every aggregation scheme exactly as they do with the paper's eq. 3.
    """
    ap = agg.params() if isinstance(agg, AggregatorConfig) else agg
    m = mask.astype(jnp.float32)
    safe = deltas
    if guards is not None and guards.active:
        gw, safe = guard_weights(deltas, staleness, guards)
        m = m * gw
    a = scheme_weights(m, staleness, probs, ap, num_clients)
    return weighted_aggregate(global_params, safe, a, use_pallas=use_pallas)


def scheme_subset_aggregate(global_params: Any, deltas_p: Any,
                            valid: jax.Array, num_clients,
                            staleness_p: jax.Array, probs_p: jax.Array,
                            agg, guards=None,
                            use_pallas: bool | str | None = None) -> Any:
    """Participant-subset form of :func:`scheme_aggregate` (sparse phase B):
    rows are the padded transmitting bucket and ``num_clients`` may be a
    traced scalar, so one compiled bucket program serves every population
    *and* every aggregation scheme (AggParams ride a vmap axis)."""
    return scheme_aggregate(global_params, deltas_p, valid, num_clients,
                            staleness_p, probs_p, agg, guards=guards,
                            use_pallas=use_pallas)


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

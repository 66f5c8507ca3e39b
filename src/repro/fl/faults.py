"""Fault-injection processes + defensive-aggregation configuration.

The paper's whole premise is that clients are unreliable — stragglers,
arbitrary transmission times, energy-limited uplinks — yet a clean simulator
tests none of it: every selected client trains, every uplink lands, every
update is finite.  This module models the faults *inside* the jitted scan so
the convergence-vs-energy claims can be stress-tested under realistic failure
regimes (FLGo-style system simulation; staleness-aware aggregation à la
Hu–Chen–Larsson arXiv:2212.07356 / FedAsync):

* **Markov on–off availability** — each client carries a two-state chain in
  the scan carry (``FaultState.avail``); an unavailable client never starts
  its upload (no transmission, no energy).
* **Diurnal availability rate** — the failure probability is modulated by a
  sinusoid of the round index with a per-client phase (staggered
  "timezones"), so scenario lanes see time-varying populations.
* **Mid-round crash/dropout** — a client that passed the Bernoulli draw
  crashes before completing its upload: nothing lands, no uplink energy is
  spent (the dropout happened before transmission).
* **Uplink loss with bounded retry-and-backoff** — each transmission attempt
  is lost with probability ``p_loss``; the client retries up to
  ``max_retries`` extra times, each attempt costing ``backoff^i`` times the
  base eq.-5 energy.  Retries consume extra energy, and a fully-lost upload
  leaves ``last_tx`` untouched — staleness grows — mirroring the paper's
  energy/bandwidth trade-off.
* **Adversarial update corruption** — a delivered update is poisoned with
  probability ``p_corrupt``: NaN / Inf injection or a scaled-norm attack
  (``corrupt_scale`` × the honest update).

Every process is a pure ``(t, key, state) -> (outcome, state)`` function of
*traced* parameters (:class:`FaultParams`), so scenario lanes can ``vmap``
over heterogeneous failure worlds (:func:`run_fault_matrix` sweeps a severity
axis in one device program) and every process composes with every selection
policy in :mod:`repro.core.selection` — faults act on the realized mask,
*after* the policy, never inside it.

The PRNG discipline matters for parity: fault draws consume dedicated
``fold_in(fold_in(base_key, t), _FAULT_SALT + i)`` streams, so enabling
faults never perturbs the participation draws, and ``faults=None`` leaves
the engine's program byte-for-byte unchanged (the existing dense/sparse/
legacy bit-parity tests keep passing untouched).

Server-side defenses are configured here too (:class:`GuardConfig`) and
implemented mask-based in :func:`repro.fl.state.guard_weights`, which
:func:`repro.fl.state.round_weights` applies to every round:
non-finite quarantine (reject-and-reweight instead of poisoning the global
model), update-norm clipping, and staleness-gated down-weighting.

See ``docs/robustness.md`` for the catalog, guard semantics, and the resume
protocol.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: fold_in salts for the per-round fault streams — disjoint from the
#: participation draw (fold_in(base_key, t) itself) and the data streams.
_FAULT_SALT = 0x5AFE


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """Static fault-process configuration (frozen ⇒ usable inside jitted
    closures).  All probabilities are per round; everything defaults to the
    clean world, so ``FaultConfig()`` with one field set isolates one
    process."""

    # Markov on–off availability
    p_fail: float = 0.0        # P(up → down) per round
    p_recover: float = 1.0     # P(down → up) per round
    # diurnal modulation of the failure rate: p_fail·(1 + amp·sin(2πt/period
    # + 2πk/K)) — amp=0 disables; per-client phase staggers the "timezones"
    diurnal_amp: float = 0.0
    diurnal_period: int = 24
    # mid-round crash (selected, available, but dies before the upload)
    p_crash: float = 0.0
    # uplink loss + bounded retry-and-backoff
    p_loss: float = 0.0        # per-attempt loss probability
    max_retries: int = 0       # extra attempts after the first (static)
    backoff: float = 1.0       # attempt i costs backoff^i × the base energy
    # adversarial update corruption
    p_corrupt: float = 0.0
    corrupt_mode: str = "nan"  # "nan" | "inf" | "scale" (static)
    corrupt_scale: float = 100.0

    @classmethod
    def from_trace(cls, avail, attempts=None, delivered=None,
                   max_retries: int = 0, **overrides) -> "FaultConfig":
        """Config-level convenience over :meth:`FaultParams.from_trace`:
        fit the Markov/loss rates from a trace and return a ready-to-use
        ``FaultConfig`` (so ``SimConfig(faults=FaultConfig.from_trace(...))``
        replays the fitted failure world).  ``max_retries`` and any other
        static field ride through ``overrides``."""
        fp = FaultParams.from_trace(avail, attempts=attempts,
                                    delivered=delivered)
        return cls(p_fail=float(fp.p_fail), p_recover=float(fp.p_recover),
                   p_loss=float(fp.p_loss), max_retries=max_retries,
                   **overrides)

    def params(self) -> "FaultParams":
        """The traced-parameter view (everything a vmap axis may sweep)."""
        return FaultParams(
            p_fail=jnp.float32(self.p_fail),
            p_recover=jnp.float32(self.p_recover),
            diurnal_amp=jnp.float32(self.diurnal_amp),
            p_crash=jnp.float32(self.p_crash),
            p_loss=jnp.float32(self.p_loss),
            backoff=jnp.float32(self.backoff),
            p_corrupt=jnp.float32(self.p_corrupt),
            corrupt_scale=jnp.float32(self.corrupt_scale),
        )


class FaultParams(NamedTuple):
    """Traced counterparts of the probabilistic :class:`FaultConfig` fields.

    A pytree of f32 scalars: stack several along a leading axis and ``vmap``
    the simulation over it to sweep failure severities in one device program
    (``max_retries``/``corrupt_mode``/``diurnal_period`` stay static — they
    shape the program, not the math).
    """

    p_fail: jax.Array
    p_recover: jax.Array
    diurnal_amp: jax.Array
    p_crash: jax.Array
    p_loss: jax.Array
    backoff: jax.Array
    p_corrupt: jax.Array
    corrupt_scale: jax.Array

    @classmethod
    def from_trace(cls, avail, attempts=None, delivered=None) -> "FaultParams":
        """Fit the probabilistic fields from an observed trace (MLE).

        ``avail [T, K]`` is an availability history (bool/int — e.g. the
        :class:`FaultOutcome` ``avail`` lane stacked over rounds, or a real
        deployment's presence log): the Markov rates are transition
        frequencies, ``p_fail = #(up→down) / #(up)`` and ``p_recover =
        #(down→up) / #(down)`` over consecutive round pairs.  With no
        observed up (resp. down) dwell the clean-world defaults ``0.0`` /
        ``1.0`` stand.

        ``attempts``/``delivered`` (``[T, K]``, optional, together) fit the
        uplink loss: every delivered upload ends in exactly one success, so
        ``p_loss = (Σ attempts − #delivered) / Σ attempts``.

        Everything unobservable from these traces (diurnal modulation,
        crash/corruption rates, backoff) keeps its clean default — fit what
        the trace pins down, assume nothing else.
        """
        a = np.asarray(avail).astype(bool)
        if a.ndim != 2:
            raise ValueError(f"avail must be [T, K], got shape {a.shape}")
        prev, nxt = a[:-1], a[1:]
        n_up = int(prev.sum())
        n_down = int(prev.size - n_up)
        p_fail = float((prev & ~nxt).sum() / n_up) if n_up else 0.0
        p_recover = float((~prev & nxt).sum() / n_down) if n_down else 1.0
        p_loss = 0.0
        if (attempts is None) != (delivered is None):
            raise ValueError("attempts and delivered must be given together")
        if attempts is not None:
            att = np.asarray(attempts, np.float64)
            dlv = np.asarray(delivered).astype(bool)
            if att.shape != dlv.shape:
                raise ValueError("attempts and delivered shapes differ: "
                                 f"{att.shape} vs {dlv.shape}")
            total = float(att.sum())
            if total > 0:
                p_loss = float(np.clip((total - dlv.sum()) / total, 0.0, 1.0))
        return FaultConfig(p_fail=p_fail, p_recover=p_recover,
                           p_loss=p_loss).params()


def scale_params(fp: FaultParams, rate) -> FaultParams:
    """Scale every *failure* probability by ``rate`` (clipped to [0, 1]) —
    the severity axis of a degradation sweep.  Recovery, backoff and the
    corruption magnitude are left alone: ``rate=0`` is the clean world,
    ``rate=1`` the configured one."""
    r = jnp.asarray(rate, jnp.float32)
    clip = lambda p: jnp.clip(p * r, 0.0, 1.0)  # noqa: E731
    return fp._replace(p_fail=clip(fp.p_fail), p_crash=clip(fp.p_crash),
                       p_loss=clip(fp.p_loss), p_corrupt=clip(fp.p_corrupt))


class FaultState(NamedTuple):
    """Per-client fault state carried in the scan."""

    avail: jax.Array   # [K] bool — Markov on–off chain state (True = up)


class FaultOutcome(NamedTuple):
    """Per-round fault realization (all ``[K]``)."""

    delivered: jax.Array   # f32 — update actually landed at the server
    corrupt: jax.Array     # bool — delivered but adversarially corrupted
    attempts: jax.Array    # f32 — uplink attempts made (0 = never started)
    avail: jax.Array       # bool — availability after this round's step
    e_round: jax.Array     # f32 — energy including retry overhead


def init_fault_state(num_clients: int) -> FaultState:
    """Everyone starts available (the chain mixes within a few rounds)."""
    return FaultState(avail=jnp.ones((num_clients,), bool))


def fault_key(base_key: jax.Array, t: jax.Array, i: int) -> jax.Array:
    """Stream i of round t — disjoint from the participation draw by salt."""
    return jax.random.fold_in(jax.random.fold_in(base_key, t),
                              _FAULT_SALT + i)


# ---------------------------------------------------------------------------
# the individual processes — pure (t, key, state) -> (outcome, state)
# ---------------------------------------------------------------------------


def markov_availability(t, key, avail, fp: FaultParams,
                        cfg: FaultConfig):
    """One step of the per-client on–off chain with diurnal modulation.

    Returns ``(avail', avail')`` — the outcome *is* the new state.  The
    failure rate is ``p_fail·(1 + amp·sin(2πt/period + φ_k))`` clipped to
    [0, 1], with per-client phase ``φ_k = 2πk/K``.
    """
    K = avail.shape[0]
    phase = 2.0 * jnp.pi * jnp.arange(K, dtype=jnp.float32) / K
    tt = t.astype(jnp.float32) if hasattr(t, "astype") else jnp.float32(t)
    mod = 1.0 + fp.diurnal_amp * jnp.sin(
        2.0 * jnp.pi * tt / cfg.diurnal_period + phase)
    p_fail_t = jnp.clip(fp.p_fail * mod, 0.0, 1.0)
    u = jax.random.uniform(key, (K,))
    new_avail = jnp.where(avail, u >= p_fail_t, u < fp.p_recover)
    return new_avail, new_avail


def crash_process(t, key, mask, fp: FaultParams):
    """Mid-round crash: a selected client dies before its upload starts.
    Returns ``(crashed [K] bool, None)`` — memoryless, no carried state."""
    del t
    u = jax.random.uniform(key, mask.shape)
    return (mask > 0) & (u < fp.p_crash), None


def uplink_process(t, key, mask, fp: FaultParams, cfg: FaultConfig):
    """Lossy uplink with bounded retry-and-backoff.

    Each attempt i ∈ {0..max_retries} is independently lost with probability
    ``p_loss``; the client stops at its first success.  Returns
    ``(landed [K] bool, attempts [K] f32, energy_mult [K] f32, None)`` where
    ``energy_mult = Σ_{i<attempts} backoff^i`` multiplies the base eq.-5
    round energy — retries are paid for whether or not the update ever lands.
    """
    del t
    K = mask.shape[0]
    A = cfg.max_retries + 1
    u = jax.random.uniform(key, (A, K))
    ok = u >= fp.p_loss                               # [A, K] attempt success
    # first success index; A if every attempt lost
    first = jnp.argmax(ok, axis=0)
    any_ok = jnp.any(ok, axis=0)
    attempts = jnp.where(any_ok, first + 1, A).astype(jnp.float32)
    # Σ_{i<a} backoff^i, branch-free over the static attempt budget
    i = jnp.arange(A, dtype=jnp.float32)[:, None]
    cost = jnp.where(i < attempts[None, :], fp.backoff ** i, 0.0)
    return any_ok, attempts, jnp.sum(cost, axis=0), None


def corruption_process(t, key, delivered, fp: FaultParams):
    """Adversarial corruption draw over *delivered* updates.  Returns
    ``(corrupt [K] bool, None)``; the transform itself is
    :func:`corrupt_deltas` (applied where the deltas live — dense round step
    or sparse phase B)."""
    del t
    u = jax.random.uniform(key, delivered.shape)
    return (delivered > 0) & (u < fp.p_corrupt), None


def corrupt_deltas(deltas: Any, corrupt: jax.Array, fp: FaultParams,
                   cfg: FaultConfig) -> Any:
    """Apply the configured corruption to the flagged rows of a stacked
    delta pytree (leading axis = clients or participants).

    ``"nan"``/``"inf"`` poison every element of the flagged update;
    ``"scale"`` is the scaled-norm attack (``corrupt_scale × δ`` — finite,
    so it slips past a pure finiteness quarantine and exercises norm
    clipping)."""
    if cfg.corrupt_mode == "scale":
        bad = lambda d: d * fp.corrupt_scale  # noqa: E731
    elif cfg.corrupt_mode == "nan":
        bad = lambda d: jnp.full_like(d, jnp.nan)  # noqa: E731
    elif cfg.corrupt_mode == "inf":
        bad = lambda d: jnp.full_like(d, jnp.inf)  # noqa: E731
    else:
        raise ValueError(f"unknown corrupt_mode {cfg.corrupt_mode!r} "
                         "(expected nan|inf|scale)")

    def one(d):
        c = corrupt.reshape((-1,) + (1,) * (d.ndim - 1))
        return jnp.where(c, bad(d), d)

    return jax.tree_util.tree_map(one, deltas)


# ---------------------------------------------------------------------------
# the composed per-round pipeline (what the engines call)
# ---------------------------------------------------------------------------


def apply_faults(t, base_key, mask, e_round, fstate: FaultState,
                 fp: FaultParams, cfg: FaultConfig):
    """Run every configured process on one round's realized decision.

    ``mask``/``e_round`` are the *decision* outputs of
    ``apply_round_decision`` (who wanted to transmit, at what base cost);
    the pipeline decides what actually lands:

    1. availability — down clients never start (no energy),
    2. crash — dies before upload (no uplink energy),
    3. uplink loss — retries multiply the energy; total loss delivers
       nothing but still pays,
    4. corruption — flags delivered updates for poisoning.

    Returns ``(FaultOutcome, FaultState)``.  Pure, branch-free, and all
    randomness comes from salted ``fold_in`` streams of ``(base_key, t)`` —
    the legacy host loop and the scan engine realize identical faults.
    """
    avail, _ = markov_availability(t, fault_key(base_key, t, 0),
                                   fstate.avail, fp, cfg)
    started = mask * avail.astype(mask.dtype)
    crashed, _ = crash_process(t, fault_key(base_key, t, 1), started, fp)
    uploading = started * (~crashed).astype(mask.dtype)
    landed, attempts, e_mult, _ = uplink_process(
        t, fault_key(base_key, t, 2), uploading, fp, cfg)
    delivered = uploading * landed.astype(mask.dtype)
    # energy: only clients that reached the uplink pay, scaled by retries
    e_round = e_round * uploading * e_mult
    attempts = attempts * uploading
    corrupt, _ = corruption_process(t, fault_key(base_key, t, 3),
                                    delivered, fp)
    return (FaultOutcome(delivered=delivered, corrupt=corrupt,
                         attempts=attempts, avail=avail, e_round=e_round),
            FaultState(avail=avail))


# ---------------------------------------------------------------------------
# defensive aggregation configuration (array code: repro.fl.state)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GuardConfig:
    """Server-side aggregation defenses — all mask-based, so the disabled
    configuration is bit-identical to the unguarded path.

    * ``quarantine`` — reject updates containing NaN/Inf (the whole client
      row) instead of letting one poisoned upload wipe the global model;
      the surviving set keeps the paper's 1/K averaging (reject-and-reweight:
      rejected mass is simply not added).
    * ``clip_norm`` — per-client L2 clip of the pseudo-gradient: δ is scaled
      by ``min(1, clip_norm/‖δ‖)``, which bounds the scaled-norm attack.
    * ``staleness_power`` — FedAsync-style polynomial down-weighting
      ``(1 + Δτ)^{-power}`` of stale updates (Δτ = rounds since the
      client's last delivered transmission).
    * ``staleness_cap`` — hard gate: updates staler than the cap are dropped
      outright (weight 0).
    """

    quarantine: bool = True
    clip_norm: Optional[float] = None
    staleness_power: float = 0.0
    staleness_cap: Optional[int] = None

    @property
    def active(self) -> bool:
        return (self.quarantine or self.clip_norm is not None
                or self.staleness_power != 0.0
                or self.staleness_cap is not None)


class FaultMatrixResult(NamedTuple):
    """Degradation sweep output (:func:`run_fault_matrix`): leading axis =
    the severity rates, one entry per guard setting."""

    rates: np.ndarray            # [R] severity multipliers
    acc: dict                    # {"guarded"/"unguarded": [R, n_evals]}
    loss: dict                   # same shape
    eval_rounds: np.ndarray      # [n_evals]
    energy: dict                 # {...: [R, K]} cumulative Joules
    delivered: dict              # {...: [R, T, K]} realized deliveries
    finite_final: dict           # {...: [R] bool} final params all finite
    # {...: MetricsState with [R]-leading leaves} when cfg.metrics enables
    # taps; None otherwise.
    metrics: Any = None


def run_fault_matrix(init_params, loss_fn, acc_fn, client_data, test_ds,
                     policy, h_all: jax.Array, cell, cfg,
                     rates: Sequence[float],
                     guard: Optional[GuardConfig] = None) -> FaultMatrixResult:
    """One sweep → a degradation curve: accuracy/energy vs fault severity,
    guarded vs unguarded, in one vmapped device program per guard setting.

    ``cfg.faults`` must be set; each lane runs the identical simulation with
    every failure probability scaled by its rate (:func:`scale_params` — rate
    0 is the clean world).  The guarded setting uses ``guard`` (default: the
    all-on :class:`GuardConfig`); the unguarded one runs ``guards=None``.
    """
    import dataclasses as _dc

    from ..data.device import data_stream_key, from_client_datasets
    from ..optim import sgd
    from .engine import build_scan_sim, resolve_data_path

    if cfg.faults is None:
        raise ValueError("run_fault_matrix needs SimConfig(faults=...)")
    guard = guard or GuardConfig(quarantine=True, clip_norm=10.0,
                                 staleness_power=0.5)
    K = h_all.shape[0]
    opt = sgd(cfg.lr)
    from ..core.selection import as_policy_fn
    policy_fn = as_policy_fn(policy)
    test_x = test_ds.x[: cfg.eval_batch]
    test_y = test_ds.y[: cfg.eval_batch]
    h_rounds = jnp.swapaxes(h_all, 0, 1)
    key = jax.random.PRNGKey(cfg.seed)
    path = resolve_data_path(client_data, cfg)
    if path == "prestack":
        from .engine import stack_round_batches
        data = stack_round_batches(client_data, cfg)
    else:  # stream resolves to the device store under vmap fan-out
        data = (from_client_datasets(client_data), data_stream_key(cfg.seed))
    base_fp = cfg.faults.params()
    rates_arr = jnp.asarray(list(rates), jnp.float32)
    fp_stack = jax.vmap(lambda r: scale_params(base_fp, r))(rates_arr)

    from ..obs.taps import metrics_active
    from ..obs.telemetry import emit_run_manifest
    emit_run_manifest("run_fault_matrix", cfg,
                      extra={"rates": len(rates), "num_clients": int(K)})

    out_acc, out_loss, out_energy, out_del, out_fin = {}, {}, {}, {}, {}
    out_ms: dict = {}
    eval_rounds = None
    for name, guards in (("unguarded", None), ("guarded", guard)):
        cfg_g = _dc.replace(cfg, guards=guards)
        tapped = metrics_active(cfg_g.metrics, cfg_g.guards)
        sim = build_scan_sim(loss_fn, acc_fn, opt, cfg_g, cell, K, policy_fn,
                             shard_clients=False,
                             data_mode=("prestack" if path == "prestack"
                                        else "device"))
        fan = jax.jit(jax.vmap(
            lambda fp: sim(init_params, data[0], data[1], h_rounds, key,
                           test_x, test_y, fault_params=fp)))
        out = fan(fp_stack)
        state, energy, traces = out[0], out[1], out[2]
        if tapped:
            out_ms[name] = jax.tree_util.tree_map(np.asarray, out[3])
        did = np.asarray(traces.did_eval)
        idx = np.where(did.reshape(-1, did.shape[-1])[0])[0]
        eval_rounds = idx
        out_acc[name] = np.asarray(traces.acc)[..., idx]
        out_loss[name] = np.asarray(traces.loss)[..., idx]
        out_energy[name] = np.asarray(energy)
        out_del[name] = np.asarray(traces.delivered)
        fin = jnp.stack([
            jnp.all(jnp.stack([jnp.all(jnp.isfinite(l[r]))
                               for l in jax.tree_util.tree_leaves(
                                   state.global_params)]))
            for r in range(len(rates_arr))])
        out_fin[name] = np.asarray(fin)

    return FaultMatrixResult(rates=np.asarray(rates_arr), acc=out_acc,
                             loss=out_loss, eval_rounds=eval_rounds,
                             energy=out_energy, delivered=out_del,
                             finite_final=out_fin,
                             metrics=out_ms or None)

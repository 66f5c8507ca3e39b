"""Fault-injection layer + defensive aggregation: process units, engine
bit-parity under faults (scan == legacy == sparse on the same salted
streams), guard effectiveness at high corruption, the participant-bucket
overflow spill/error paths, and the corruption-can't-pass-silently
properties."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_stub import given, settings, st

from repro.core import CellConfig
from repro.core.channel import channel_gains, sample_positions
from repro.core.selection import RandomScheme
from repro.data import make_mnist_like, shard_noniid
from repro.data.synthetic import Dataset
from repro.fl import (FaultConfig, GuardConfig, SimConfig, run_fault_matrix,
                      run_simulation, run_simulation_legacy)
from repro.fl.faults import (apply_faults, corrupt_deltas, init_fault_state,
                             markov_availability, scale_params,
                             uplink_process)
from repro.fl.sparse import make_sparse_runner
from repro.fl.state import (finite_rows, guard_weights, masked_aggregate,
                            round_weights, update_norms)
from repro.models.small import init_mlp, mlp_accuracy, mlp_loss

DIM = 64


def tiny_world(K=5, rounds=8):
    tr, te = make_mnist_like(jax.random.PRNGKey(0), n_train=1000, n_test=300)
    clients = shard_noniid(jax.random.PRNGKey(1), tr, K, d=2)
    clients = [Dataset(c.x[:, :DIM], c.y, c.num_classes) for c in clients]
    te = Dataset(te.x[:, :DIM], te.y, te.num_classes)
    cell = CellConfig(num_clients=K)
    pos = sample_positions(jax.random.PRNGKey(2), cell)
    h = channel_gains(jax.random.PRNGKey(3), pos, rounds).T
    params = init_mlp(jax.random.PRNGKey(4), dims=(DIM, 24, 10))
    return clients, te, cell, h, params


FAULTS = FaultConfig(p_fail=0.2, p_recover=0.5, p_crash=0.1, p_loss=0.2,
                     max_retries=1, p_corrupt=0.25, corrupt_mode="nan")
GUARDS = GuardConfig(quarantine=True, clip_norm=10.0, staleness_power=0.5)


def leaves_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def all_finite(tree):
    return all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree_util.tree_leaves(tree))


# --- process units -----------------------------------------------------------


def test_markov_availability_absorbing_extremes():
    key = jax.random.PRNGKey(0)
    avail = jnp.ones((16,), bool)
    # p_fail=1, p_recover=0: everyone goes down and stays down
    cfg = FaultConfig(p_fail=1.0, p_recover=0.0)
    fp = cfg.params()
    for t in range(3):
        avail, _ = markov_availability(jnp.int32(t), jax.random.fold_in(
            key, t), avail, fp, cfg)
    assert not bool(avail.any())
    # p_fail=0: everyone stays up
    cfg0 = FaultConfig(p_fail=0.0)
    avail = jnp.ones((16,), bool)
    avail, _ = markov_availability(jnp.int32(0), key, avail, cfg0.params(),
                                   cfg0)
    assert bool(avail.all())


def test_uplink_retry_energy_accounting():
    key = jax.random.PRNGKey(7)
    mask = jnp.ones((8,), jnp.float32)
    # lossless: first attempt lands, unit energy
    cfg = FaultConfig(p_loss=0.0, max_retries=3, backoff=2.0)
    ok, att, mult, _ = uplink_process(jnp.int32(0), key, mask, cfg.params(),
                                      cfg)
    assert bool(ok.all())
    np.testing.assert_array_equal(np.asarray(att), 1.0)
    np.testing.assert_array_equal(np.asarray(mult), 1.0)
    # total loss: every attempt spent, geometric energy, nothing lands
    cfg = FaultConfig(p_loss=1.0, max_retries=2, backoff=2.0)
    ok, att, mult, _ = uplink_process(jnp.int32(0), key, mask, cfg.params(),
                                      cfg)
    assert not bool(ok.any())
    np.testing.assert_array_equal(np.asarray(att), 3.0)
    np.testing.assert_array_equal(np.asarray(mult), 1.0 + 2.0 + 4.0)


def test_apply_faults_energy_only_for_uploaders():
    """Unavailable clients and crashed clients never reach the uplink — no
    energy; lost uploads still pay (with retry overhead)."""
    K = 6
    cfg = FaultConfig(p_fail=1.0, p_recover=0.0)   # all down after 1 step
    fp = cfg.params()
    out, _ = apply_faults(jnp.int32(0), jax.random.PRNGKey(0),
                          jnp.ones((K,), jnp.float32),
                          jnp.full((K,), 2.0, jnp.float32),
                          init_fault_state(K), fp, cfg)
    np.testing.assert_array_equal(np.asarray(out.e_round), 0.0)
    np.testing.assert_array_equal(np.asarray(out.delivered), 0.0)
    cfg = FaultConfig(p_loss=1.0, max_retries=1, backoff=3.0)
    out, _ = apply_faults(jnp.int32(0), jax.random.PRNGKey(0),
                          jnp.ones((K,), jnp.float32),
                          jnp.full((K,), 2.0, jnp.float32),
                          init_fault_state(K), cfg.params(), cfg)
    np.testing.assert_array_equal(np.asarray(out.delivered), 0.0)
    np.testing.assert_allclose(np.asarray(out.e_round), 2.0 * (1 + 3))


def test_corrupt_deltas_modes():
    d = {"w": jnp.ones((4, 3)), "b": jnp.ones((4,))}
    flag = jnp.array([True, False, True, False])
    for mode, check in [
            ("nan", lambda x: np.isnan(x).all()),
            ("inf", lambda x: np.isposinf(x).all()),
            ("scale", lambda x: (x == 100.0).all())]:
        cfg = FaultConfig(p_corrupt=1.0, corrupt_mode=mode)
        out = corrupt_deltas(d, flag, cfg.params(), cfg)
        w = np.asarray(out["w"])
        assert check(w[0]) and check(w[2])
        np.testing.assert_array_equal(w[1], 1.0)
    with pytest.raises(ValueError, match="corrupt_mode"):
        bad = FaultConfig(corrupt_mode="bitflip")
        corrupt_deltas(d, flag, bad.params(), bad)


def test_scale_params_clips_to_unit_interval():
    fp = FaultConfig(p_fail=0.4, p_loss=0.9).params()
    hot = scale_params(fp, 5.0)
    assert float(hot.p_fail) == 1.0 and float(hot.p_loss) == 1.0
    cold = scale_params(fp, 0.0)
    assert float(cold.p_fail) == 0.0 and float(cold.p_loss) == 0.0


# --- guard primitives --------------------------------------------------------


def test_guard_weights_quarantine_clip_staleness():
    deltas = {"w": jnp.array([[3.0, 4.0], [jnp.nan, 1.0], [30.0, 40.0]])}
    stale = jnp.array([0, 0, 4], jnp.float32)
    w, safe = guard_weights(deltas, stale, GuardConfig(
        quarantine=True, clip_norm=5.0, staleness_power=1.0))
    # row 0: ‖δ‖=5 → clip factor 1, staleness 0 → weight 1
    # row 1: non-finite → 0, and the row is zeroed so 0·δ' can't NaN
    # row 2: ‖δ‖=50 → clip 0.1, staleness (1+4)^-1 = 0.2 → 0.02
    np.testing.assert_allclose(np.asarray(w), [1.0, 0.0, 0.02], rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(safe["w"][1]), 0.0)
    # hard cap drops the stale row outright
    w2, _ = guard_weights(deltas, stale, GuardConfig(
        quarantine=False, staleness_cap=2))
    np.testing.assert_array_equal(np.asarray(w2), [1.0, 1.0, 0.0])


def test_finite_rows_and_update_norms():
    d = {"a": jnp.array([[1.0, 2.0], [jnp.inf, 0.0]]),
         "b": jnp.array([[2.0], [3.0]])}
    np.testing.assert_array_equal(np.asarray(finite_rows(d)), [True, False])
    np.testing.assert_allclose(np.asarray(update_norms(d)), [3.0, 3.0])


def test_guarded_aggregate_disabled_is_bitwise_plain():
    g = {"w": jnp.arange(6.0)}
    d = {"w": jax.random.normal(jax.random.PRNGKey(0), (4, 6))}
    m = jnp.array([1.0, 0.0, 1.0, 1.0])
    stale = jnp.zeros((4,), jnp.int32)
    plain = masked_aggregate(g, d, m, 4, use_pallas=False)
    for guards in (None, GuardConfig(quarantine=False)):
        w, denom, dd = round_weights(m, 4, stale, None, None, guards, d)
        assert w is m and denom == 4 and dd is d
        out = masked_aggregate(g, dd, w, denom, use_pallas=False)
        leaves_equal(out, plain)


def test_guarded_aggregate_rejects_poison_keeps_honest_mass():
    """Quarantine = reject-and-reweight: the output equals the plain
    aggregate over the honest subset."""
    g = {"w": jnp.zeros((5,))}
    honest = jax.random.normal(jax.random.PRNGKey(1), (3, 5))
    d = {"w": jnp.concatenate([honest, jnp.full((1, 5), jnp.nan)], axis=0)}
    m = jnp.ones((4,))
    w, denom, d = round_weights(m, 4, jnp.zeros((4,), jnp.int32), None,
                                None, GuardConfig(quarantine=True), d)
    out = masked_aggregate(g, d, w, denom, use_pallas=False)
    want = {"w": jnp.sum(honest, axis=0) / 4.0}
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(want["w"]),
                               atol=1e-6)
    assert all_finite(out)


# --- engine integration: parity + effectiveness ------------------------------


def run_both(cfg, K=5, rounds=8):
    clients, te, cell, h, params = tiny_world(K=K, rounds=rounds)
    scan = run_simulation(params, mlp_loss, mlp_accuracy, clients, te,
                          RandomScheme(p_bar=0.5, num_clients=K), h, cell,
                          cfg)
    legacy = run_simulation_legacy(params, mlp_loss, mlp_accuracy, clients,
                                   te, RandomScheme(p_bar=0.5, num_clients=K),
                                   h, cell, cfg)
    return scan, legacy


def test_faulty_guarded_scan_equals_legacy():
    cfg = SimConfig(rounds=8, local_iters=2, batch_size=8, eval_every=4,
                    eval_batch=200, data_path="device", faults=FAULTS,
                    guards=GUARDS)
    scan, legacy = run_both(cfg)
    np.testing.assert_array_equal(scan.participation, legacy.participation)
    np.testing.assert_array_equal(scan.delivered, legacy.delivered)
    np.testing.assert_array_equal(scan.corrupted, legacy.corrupted)
    np.testing.assert_allclose(scan.energy_per_client,
                               legacy.energy_per_client, rtol=1e-6)
    leaves_equal(scan.state.global_params, legacy.state.global_params)
    assert all_finite(scan.state.global_params)


def test_fault_streams_never_perturb_participation():
    """The salted fold_in fault streams are disjoint from the participation
    draw: the decision masks of a faulty run equal the clean run's."""
    base = dict(rounds=8, local_iters=1, batch_size=8, eval_every=4,
                eval_batch=200, data_path="device")
    clean, _ = run_both(SimConfig(**base))
    faulty, _ = run_both(SimConfig(**base, faults=FAULTS, guards=GUARDS))
    np.testing.assert_array_equal(clean.participation, faulty.participation)
    assert clean.delivered is None and faulty.delivered is not None


def test_guards_keep_model_finite_at_high_corruption():
    """Acceptance gate: ≥10 % corrupted updates, guarded engine stays
    finite; the same run unguarded does not."""
    clients, te, cell, h, params = tiny_world(rounds=10)
    faults = FaultConfig(p_corrupt=0.5, corrupt_mode="nan")
    base = dict(rounds=10, local_iters=1, batch_size=8, eval_every=4,
                eval_batch=200, data_path="device", faults=faults)
    pol = RandomScheme(p_bar=0.6, num_clients=5)
    guarded = run_simulation(params, mlp_loss, mlp_accuracy, clients, te,
                             pol, h, cell, SimConfig(**base, guards=GUARDS))
    assert guarded.corrupted.sum() >= 1, "corruption never fired"
    assert all_finite(guarded.state.global_params)
    assert np.isfinite(guarded.test_loss).all()
    unguarded = run_simulation(params, mlp_loss, mlp_accuracy, clients, te,
                               pol, h, cell, SimConfig(**base))
    assert not all_finite(unguarded.state.global_params)


def test_scaled_norm_attack_bounded_by_clip():
    """The finite scaled-norm attack slips past quarantine but norm clipping
    bounds its influence: the guarded model stays close to clean scale."""
    clients, te, cell, h, params = tiny_world(rounds=8)
    faults = FaultConfig(p_corrupt=0.4, corrupt_mode="scale",
                         corrupt_scale=1e4)
    pol = RandomScheme(p_bar=0.6, num_clients=5)
    base = dict(rounds=8, local_iters=1, batch_size=8, eval_every=4,
                eval_batch=200, data_path="device", faults=faults)
    guarded = run_simulation(
        params, mlp_loss, mlp_accuracy, clients, te, pol, h, cell,
        SimConfig(**base, guards=GuardConfig(quarantine=False,
                                             clip_norm=1.0)))
    unguarded = run_simulation(params, mlp_loss, mlp_accuracy, clients, te,
                               pol, h, cell, SimConfig(**base))
    norm_g = update_norms(jax.tree_util.tree_map(
        lambda g: g[None], guarded.state.global_params))
    norm_u = update_norms(jax.tree_util.tree_map(
        lambda g: g[None], unguarded.state.global_params))
    assert guarded.corrupted.sum() >= 1
    assert float(norm_g[0]) < float(norm_u[0]) / 10.0


def test_fault_matrix_degradation_curve():
    clients, te, cell, h, params = tiny_world(rounds=8)
    faults = FaultConfig(p_loss=0.3, max_retries=1, p_corrupt=0.3,
                         corrupt_mode="nan")
    cfg = SimConfig(rounds=8, local_iters=1, batch_size=8, eval_every=4,
                    eval_batch=200, data_path="device", faults=faults)
    res = run_fault_matrix(params, mlp_loss, mlp_accuracy, clients, te,
                           RandomScheme(p_bar=0.6, num_clients=5), h, cell,
                           cfg, rates=[0.0, 1.0])
    assert res.acc["guarded"].shape == res.acc["unguarded"].shape
    assert res.finite_final["guarded"].all()
    # the rate-0 lane is the clean world — finite even unguarded
    assert res.finite_final["unguarded"][0]
    # delivered mass can only shrink with severity
    d = res.delivered["guarded"].sum(axis=(1, 2))
    assert d[1] <= d[0]


# --- sparse path: faults + overflow fallback ---------------------------------


SPARSE_KW = dict(local_mode="participants", data_path="device",
                 data_stream="client", participation="sparse")


def test_sparse_faulty_matches_dense():
    clients, te, cell, h, params = tiny_world(rounds=8)
    pol = RandomScheme(p_bar=0.5, num_clients=5)
    base = dict(rounds=8, local_iters=1, batch_size=8, eval_every=4,
                eval_batch=200, faults=FAULTS, guards=GUARDS)
    dense = run_simulation(
        params, mlp_loss, mlp_accuracy, clients, te, pol, h, cell,
        SimConfig(**base, **{**SPARSE_KW, "participation": "dense"}))
    sparse = make_sparse_runner(
        mlp_loss, mlp_accuracy, clients, te, pol, cell,
        SimConfig(**base, **SPARSE_KW, participant_bucket=8))(params, h)
    np.testing.assert_array_equal(dense.participation, sparse.participation)
    np.testing.assert_array_equal(dense.delivered, sparse.delivered)
    np.testing.assert_array_equal(dense.corrupted, sparse.corrupted)
    np.testing.assert_allclose(dense.energy_per_client,
                               sparse.energy_per_client, rtol=1e-6)
    np.testing.assert_allclose(dense.energy_timeline, sparse.energy_timeline,
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(dense.state.last_tx),
                                  np.asarray(sparse.state.last_tx))
    for a, b in zip(jax.tree_util.tree_leaves(dense.state.global_params),
                    jax.tree_util.tree_leaves(sparse.state.global_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_sparse_overflow_spills_and_matches():
    """An undersized bucket regrows (warn once) instead of dying; the rerun
    is exact."""
    clients, te, cell, h, params = tiny_world(rounds=8)
    pol = RandomScheme(p_bar=0.9, num_clients=5)
    base = dict(rounds=8, local_iters=1, batch_size=8, eval_every=4,
                eval_batch=200)
    ok = make_sparse_runner(
        mlp_loss, mlp_accuracy, clients, te, pol, cell,
        SimConfig(**base, **SPARSE_KW, participant_bucket=8))(params, h)
    import repro.fl.sparse as sparse_mod
    sparse_mod._SPILL_WARNED = False
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        spilled = make_sparse_runner(
            mlp_loss, mlp_accuracy, clients, te, pol, cell,
            SimConfig(**base, **SPARSE_KW, participant_bucket=2))(params, h)
    assert any("participant bucket overflow" in str(x.message) for x in w)
    np.testing.assert_array_equal(ok.participation, spilled.participation)
    for a, b in zip(jax.tree_util.tree_leaves(ok.state.global_params),
                    jax.tree_util.tree_leaves(spilled.state.global_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_sparse_overflow_error_mode_regression():
    """overflow='error' preserves the legacy hard failure and its message."""
    clients, te, cell, h, params = tiny_world(rounds=8)
    pol = RandomScheme(p_bar=0.9, num_clients=5)
    cfg = SimConfig(rounds=8, local_iters=1, batch_size=8, eval_every=4,
                    eval_batch=200, **SPARSE_KW, participant_bucket=2,
                    overflow="error")
    with pytest.raises(RuntimeError, match=r"participant bucket overflow.*"
                                           r"participant_bucket"):
        make_sparse_runner(mlp_loss, mlp_accuracy, clients, te, pol, cell,
                           cfg)(params, h)


def test_unknown_overflow_policy_rejected():
    clients, te, cell, h, params = tiny_world(rounds=8)
    cfg = SimConfig(rounds=8, **SPARSE_KW, overflow="wrap")
    with pytest.raises(ValueError, match="overflow"):
        make_sparse_runner(mlp_loss, mlp_accuracy, clients, te,
                           RandomScheme(p_bar=0.5, num_clients=5), cell,
                           cfg)(params, h)


# --- properties (hypothesis; skip when the library is absent) ----------------


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 7), st.integers(0, 2 ** 31 - 1))
def test_property_single_poison_row_never_passes_silently(bad_row, seed):
    """One corrupted client among 8: unguarded aggregation is poisoned
    (non-finite), guarded aggregation is finite AND equals the honest-subset
    aggregate — corruption can't slip through unnoticed in either world."""
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    d = jax.random.normal(key, (8, 16))
    d = d.at[bad_row].set(jnp.nan)
    g = jnp.zeros((16,))
    m = jnp.ones((8,))
    unguarded = masked_aggregate({"w": g}, {"w": d}, m, 8, use_pallas=False)
    assert not all_finite(unguarded)
    w, denom, dq = round_weights(m, 8, jnp.zeros((8,), jnp.int32), None,
                                 None, GuardConfig(quarantine=True),
                                 {"w": d})
    guarded = masked_aggregate({"w": g}, dq, w, denom, use_pallas=False)
    assert all_finite(guarded)
    honest = jnp.sum(jnp.delete(d, bad_row, axis=0), axis=0) / 8.0
    np.testing.assert_allclose(np.asarray(guarded["w"]), np.asarray(honest),
                               atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.floats(0.1, 0.9))
def test_property_guarded_round_stays_finite(seed, p_corrupt):
    """Whatever the corruption rate and draw, a guarded aggregation step
    maps finite global params to finite global params."""
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    k1, k2, k3 = jax.random.split(key, 3)
    d = jax.random.normal(k1, (6, 12)) * 10.0
    flags = jax.random.uniform(k2, (6,)) < p_corrupt
    cfg = FaultConfig(p_corrupt=p_corrupt, corrupt_mode="nan")
    d = corrupt_deltas({"w": d}, flags, cfg.params(), cfg)
    m = (jax.random.uniform(k3, (6,)) < 0.7).astype(jnp.float32)
    w, denom, d = round_weights(m, 6, jnp.zeros((6,), jnp.int32), None,
                                None,
                                GuardConfig(quarantine=True, clip_norm=5.0),
                                d)
    out = masked_aggregate({"w": jnp.ones((12,))}, d, w, denom,
                           use_pallas=False)
    assert all_finite(out)


# --- trace-fitting: FaultParams.from_trace round-trip ------------------------


def test_from_trace_recovers_markov_and_loss_rates():
    """Simulate the actual processes at known rates, fit back from the
    observed traces: the MLE must land within sampling error, and the
    config-level convenience must round-trip into a usable FaultConfig."""
    from repro.fl.faults import FaultParams, fault_key
    cfg = FaultConfig(p_fail=0.15, p_recover=0.4, p_loss=0.2, max_retries=2)
    fp = cfg.params()
    K, T = 256, 400
    key = jax.random.PRNGKey(0)
    avail = jnp.ones((K,), bool)
    mask = jnp.ones((K,), jnp.int32)
    tr_a, tr_att, tr_dlv = [], [], []
    for t in range(T):
        tt = jnp.int32(t)
        avail, _ = markov_availability(tt, fault_key(key, tt, 0), avail,
                                       fp, cfg)
        landed, attempts, _, _ = uplink_process(t, fault_key(key, tt, 2),
                                                mask, fp, cfg)
        tr_a.append(np.asarray(avail))
        tr_att.append(np.asarray(attempts))
        tr_dlv.append(np.asarray(landed))
    fit = FaultParams.from_trace(np.stack(tr_a), attempts=np.stack(tr_att),
                                 delivered=np.stack(tr_dlv))
    assert abs(float(fit.p_fail) - cfg.p_fail) < 0.02
    assert abs(float(fit.p_recover) - cfg.p_recover) < 0.03
    assert abs(float(fit.p_loss) - cfg.p_loss) < 0.02
    fc = FaultConfig.from_trace(np.stack(tr_a), attempts=np.stack(tr_att),
                                delivered=np.stack(tr_dlv), max_retries=2,
                                p_corrupt=0.01)
    assert isinstance(fc, FaultConfig)
    assert fc.max_retries == 2 and fc.p_corrupt == 0.01
    assert abs(fc.p_fail - cfg.p_fail) < 0.02


def test_from_trace_degenerate_and_validation():
    """All-up traces keep the clean-world defaults; malformed inputs raise
    instead of silently fitting garbage."""
    from repro.fl.faults import FaultParams
    fit = FaultParams.from_trace(np.ones((10, 4), bool))
    assert float(fit.p_fail) == 0.0 and float(fit.p_recover) == 1.0
    assert float(fit.p_loss) == 0.0
    # all-down: p_recover estimable, p_fail defaults
    fit2 = FaultParams.from_trace(np.zeros((10, 4), bool))
    assert float(fit2.p_fail) == 0.0 and float(fit2.p_recover) == 0.0
    with pytest.raises(ValueError, match=r"\[T, K\]"):
        FaultParams.from_trace(np.ones((10,), bool))
    with pytest.raises(ValueError, match="together"):
        FaultParams.from_trace(np.ones((4, 2), bool),
                               attempts=np.ones((4, 2)))
    with pytest.raises(ValueError, match="shapes differ"):
        FaultParams.from_trace(np.ones((4, 2), bool),
                               attempts=np.ones((4, 2)),
                               delivered=np.ones((4, 3), bool))

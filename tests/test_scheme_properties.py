"""Property tests for the scheme layer: every policy emits probabilities in
[0, 1] and realizes {0,1} masks; every aggregator's weight program stays
finite, non-negative, and correctly normalized under arbitrary staleness,
delivery, and guard inputs.  Fuzzed via `hypothesis` when installed
(tests/_hypothesis_stub.py skips them cleanly otherwise); a deterministic
grid keeps the invariants exercised on clean environments."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_stub import given, settings, st
from repro.core import CellConfig
from repro.core.selection import (age_aware_policy, age_policy, csma_policy,
                                  greedy_policy, policy_blend,
                                  policy_ledger_ok, random_policy)
from repro.fl.state import (AggregatorConfig, scheme_weights,
                            staleness_scale)

K = 7

POLICIES = {
    "random": random_policy(0.3, K),
    "greedy": greedy_policy(3, K),
    "age": age_policy(3, K),
    "csma": csma_policy(3, K),
    "csma-beta2": csma_policy(3, K, beta=2.0),
    "age-aware": age_aware_policy(3, K),
}

AGGS = [
    AggregatorConfig(kind="paper"),
    AggregatorConfig(kind="fedasync", staleness_fn="constant"),
    AggregatorConfig(kind="fedasync", staleness_fn="hinge"),
    AggregatorConfig(kind="fedasync", staleness_fn="poly"),
    AggregatorConfig(kind="csmaafl"),
    AggregatorConfig(kind="age"),
]


def _agg_id(a):
    return f"{a.kind}-{a.staleness_fn}"


def _gains(seed, scale=1.0):
    return jnp.asarray(
        np.random.default_rng(seed).gamma(2.0, scale, size=(K,)),
        jnp.float32)


# ---------------------------------------------------------------------------
# policies
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,fn", POLICIES.items(), ids=POLICIES.keys())
@pytest.mark.parametrize("seed", [0, 3, 9])
def test_policy_probs_and_weights_valid(name, fn, seed):
    h = _gains(seed)
    probs, w = fn(jnp.int32(2), h, None)
    probs, w = np.asarray(probs), np.asarray(w)
    assert probs.shape == (K,) and w.shape == (K,)
    assert np.isfinite(probs).all() and np.isfinite(w).all()
    assert (probs >= 0).all() and (probs <= 1).all()
    assert (w >= 0).all() and w.sum() <= 1.0 + 1e-5


@pytest.mark.parametrize("name,fn", POLICIES.items(), ids=POLICIES.keys())
def test_policy_masks_are_binary(name, fn):
    from repro.fl.engine import apply_round_decision, SimConfig
    from repro.fl.state import init_fl_state
    cfg = SimConfig(rounds=4)
    cell = CellConfig(num_clients=K)
    st8 = init_fl_state({"w": jnp.zeros((3,))}, K)
    probs, w = fn(jnp.int32(1), _gains(4), st8)
    mask, forced, w2, e = apply_round_decision(
        probs, w, jnp.int32(1), _gains(4), st8, jax.random.PRNGKey(0), cfg,
        cell, K)
    m = np.asarray(mask)
    assert set(np.unique(m)) <= {0.0, 1.0}
    assert np.isfinite(np.asarray(e)).all() and (np.asarray(e) >= 0).all()
    # energy is charged exactly to the transmitting set
    assert ((np.asarray(e) > 0) <= (m > 0)).all()


def test_policy_blend_one_hot_is_exact():
    fns = [POLICIES["random"], POLICIES["csma"], POLICIES["age-aware"]]
    h = _gains(7)
    for i, fn in enumerate(fns):
        sel = jnp.zeros((len(fns),)).at[i].set(1.0)
        blended = policy_blend(fns, sel)
        p_ref, w_ref = fn(jnp.int32(3), h, None)
        p_bl, w_bl = blended(jnp.int32(3), h, None)
        np.testing.assert_array_equal(np.asarray(p_ref), np.asarray(p_bl))
        np.testing.assert_array_equal(np.asarray(w_ref), np.asarray(w_bl))
    assert policy_ledger_ok(policy_blend(fns, jnp.ones((3,)) / 3))


def test_ledger_tags():
    assert getattr(POLICIES["csma"], "state_free", False)
    assert not getattr(POLICIES["age-aware"], "state_free", False)
    assert policy_ledger_ok(POLICIES["age-aware"])
    blended_sf = policy_blend([POLICIES["random"], POLICIES["csma"]],
                              jnp.ones((2,)) / 2)
    assert getattr(blended_sf, "state_free", False)


# ---------------------------------------------------------------------------
# aggregation weights
# ---------------------------------------------------------------------------


def _check_weights(agg, mask, staleness, probs):
    ap = agg.params()
    a = np.asarray(scheme_weights(jnp.asarray(mask, jnp.float32),
                                  jnp.asarray(staleness, jnp.int32),
                                  jnp.asarray(probs, jnp.float32), ap, K))
    assert np.isfinite(a).all(), (agg.kind, a)
    assert (a >= 0).all(), (agg.kind, a)
    # weight only flows to delivered rows
    assert (a[np.asarray(mask) == 0] == 0).all()
    total = a.sum()
    m = np.asarray(mask, np.float64)
    if agg.kind == "paper":
        np.testing.assert_allclose(total, m.sum() / K, rtol=1e-5)
    elif m.sum() > 0:
        # normalized kinds: delivered weights sum to the mix coefficient
        np.testing.assert_allclose(total, agg.mix, rtol=1e-5)
    else:
        assert total == 0.0


@pytest.mark.parametrize("agg", AGGS, ids=_agg_id)
@pytest.mark.parametrize("case", ["all", "none", "one", "stale", "tiny-p"])
def test_weights_grid(agg, case):
    rng = np.random.default_rng(11)
    mask = {"all": np.ones(K), "none": np.zeros(K),
            "one": np.eye(K)[2], "stale": rng.integers(0, 2, K),
            "tiny-p": np.ones(K)}[case]
    staleness = {"stale": rng.integers(0, 200, K)}.get(
        case, rng.integers(0, 5, K))
    probs = (np.full(K, 1e-9) if case == "tiny-p"
             else rng.uniform(0.01, 1.0, K))
    _check_weights(agg, mask, staleness, probs)


@given(seed=st.integers(min_value=0, max_value=2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_weights_fuzz(seed):
    rng = np.random.default_rng(seed)
    mask = rng.integers(0, 2, K).astype(np.float64)
    staleness = rng.integers(0, 10_000, K)
    probs = rng.uniform(0.0, 1.0, K)  # zeros exercise the prob_floor clamp
    for agg in AGGS:
        _check_weights(agg, mask, staleness, probs)


@given(s=st.integers(min_value=0, max_value=100_000))
@settings(max_examples=50, deadline=None)
def test_staleness_scale_bounded(s):
    for agg in AGGS:
        ap = agg.params()
        v = float(staleness_scale(jnp.full((1,), s, jnp.int32), ap)[0])
        assert np.isfinite(v) and 0.0 < v <= 1.0 + 1e-6


def test_staleness_scale_monotone_nonincreasing():
    ss = jnp.arange(0, 200, dtype=jnp.int32)
    for agg in AGGS:
        vals = np.asarray(staleness_scale(ss, agg.params()))
        assert (np.diff(vals) <= 1e-7).all(), agg.staleness_fn


def test_guarded_scheme_weights_stay_valid():
    # guards zero some rows; the normalized kinds renormalize over survivors
    from repro.fl.faults import GuardConfig
    from repro.fl.state import masked_aggregate, round_weights
    rng = np.random.default_rng(5)
    D = 4
    g = jnp.zeros((D,))
    deltas = jnp.asarray(rng.normal(size=(K, D)), jnp.float32)
    deltas = deltas.at[1].set(jnp.inf)  # quarantined row
    mask = jnp.ones((K,), jnp.float32)
    stale = jnp.asarray(rng.integers(0, 6, K), jnp.int32)
    probs = jnp.asarray(rng.uniform(0.1, 1.0, K), jnp.float32)
    w, denom, deltas = round_weights(
        mask, K, stale, probs,
        AggregatorConfig(kind="fedasync", staleness_fn="poly"),
        GuardConfig(quarantine=True), deltas)
    assert denom == 1 and float(w[1]) == 0.0
    out = masked_aggregate(g, deltas, w, denom)
    assert np.isfinite(np.asarray(out)).all()


def test_aggregator_config_validation():
    with pytest.raises(ValueError):
        AggregatorConfig(kind="nope")
    with pytest.raises(ValueError):
        AggregatorConfig(staleness_fn="nope")

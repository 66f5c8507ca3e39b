"""FL runtime tests: state algebra, protocol semantics, end-to-end learning."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CellConfig, ProblemSpec
from repro.core.channel import channel_gains, sample_positions
from repro.core.selection import (AgeBasedScheme, GreedyScheme, ProposedOnline,
                                  RandomScheme)
from repro.data import make_mnist_like, shard_noniid
from repro.fl import SimConfig, init_fl_state, masked_aggregate, run_simulation
from repro.fl.state import broadcast_to_participants, pseudo_gradients
from repro.models.small import init_mlp, mlp_accuracy, mlp_loss


def small_world(rounds=12, n_train=3000, K=10, d=5):
    tr, te = make_mnist_like(jax.random.PRNGKey(0), n_train=n_train,
                             n_test=500)
    clients = shard_noniid(jax.random.PRNGKey(1), tr, K, d=d)
    cell = CellConfig(num_clients=K)
    spec = ProblemSpec(cell=cell, rho=0.05, num_rounds=rounds)
    pos = sample_positions(jax.random.PRNGKey(2), cell)
    h = channel_gains(jax.random.PRNGKey(3), pos, rounds).T
    params = init_mlp(jax.random.PRNGKey(4))
    return tr, te, clients, cell, spec, h, params


# --- state algebra ----------------------------------------------------------

def test_masked_aggregate_matches_eq3():
    params = {"w": jnp.zeros((3, 2))}
    deltas = {"w": jnp.stack([jnp.full((3, 2), float(k + 1))
                              for k in range(4)])}
    mask = jnp.array([1.0, 0.0, 1.0, 0.0])
    out = masked_aggregate(params, deltas, mask, num_clients=4)
    # (1 + 3)/4 = 1.0
    assert np.allclose(np.asarray(out["w"]), 1.0)


def test_pseudo_gradient_is_difference():
    p = init_mlp(jax.random.PRNGKey(0), dims=(4, 3, 2))
    st = init_fl_state(p, num_clients=3)
    moved = jax.tree_util.tree_map(lambda x: x + 1.0, st.client_params)
    st = st._replace(client_params=moved)
    d = pseudo_gradients(st)
    for leaf in jax.tree_util.tree_leaves(d):
        assert np.allclose(np.asarray(leaf), 1.0)


def test_broadcast_only_to_participants():
    p = {"w": jnp.zeros((2,))}
    st = init_fl_state(p, num_clients=3)
    new_global = {"w": jnp.full((2,), 5.0)}
    mask = jnp.array([1.0, 0.0, 1.0])
    st2 = broadcast_to_participants(st, new_global, mask)
    cw = np.asarray(st2.client_params["w"])
    assert np.allclose(cw[0], 5.0) and np.allclose(cw[2], 5.0)
    assert np.allclose(cw[1], 0.0)           # non-participant keeps stale model
    assert np.asarray(st2.last_tx).tolist() == [0, 0, 0]  # tx at round index 0
    assert int(st2.round) == 1


def test_nonparticipants_keep_training_on_stale_anchor():
    """The async semantics of [13]: a client that never transmits still
    diverges from its (stale) anchor."""
    tr, te, clients, cell, spec, h, params = small_world(rounds=4,
                                                         n_train=1000)
    cfg = SimConfig(rounds=4, local_iters=2, batch_size=8, eval_every=10)

    class NeverClient0:
        name = "never0"

        def decide(self, t, h_t):
            probs = jnp.ones((10,)).at[0].set(0.0)
            return type("D", (), {"probs": probs,
                                  "w": jnp.full((10,), 0.1)})()

    res = run_simulation(params, mlp_loss, mlp_accuracy, clients, te,
                         NeverClient0(), h, cell, cfg)
    # client 0 never transmitted
    assert res.participation[:, 0].sum() == 0
    # its local model still moved away from its anchor (pseudo-gradient ≠ 0)
    d = pseudo_gradients(res.state)
    leaf = np.asarray(jax.tree_util.tree_leaves(d)[0])
    assert np.abs(leaf[0]).max() > 0.0


def test_learning_happens_and_energy_positive():
    tr, te, clients, cell, spec, h, params = small_world(rounds=15)
    cfg = SimConfig(rounds=15, local_iters=5, batch_size=10, eval_every=14)
    res = run_simulation(params, mlp_loss, mlp_accuracy, clients, te,
                         ProposedOnline(spec), h, cell, cfg)
    assert res.test_acc[-1] > res.test_acc[0] + 0.05
    assert res.energy_per_client.sum() > 0
    assert np.all(np.diff(res.energy_timeline) >= -1e-9)


def test_max_staleness_enforced():
    tr, te, clients, cell, spec, h, params = small_world(rounds=10)
    cfg = SimConfig(rounds=10, local_iters=1, batch_size=8, eval_every=20,
                    max_staleness=2)
    res = run_simulation(params, mlp_loss, mlp_accuracy, clients, te,
                         RandomScheme(p_bar=0.01, num_clients=10), h, cell, cfg)
    # with p̄≈0 every client is forced at least every 2 rounds
    gaps = []
    for k in range(10):
        tx = np.where(res.participation[:, k] > 0)[0]
        if len(tx) > 1:
            gaps.extend(np.diff(tx).tolist())
        assert len(tx) >= 4  # ~rounds/2 forced transmissions
    assert max(gaps) <= 2


def test_deterministic_schemes_select_k():
    g = GreedyScheme(k=3, num_clients=10)
    a = AgeBasedScheme(k=3, num_clients=10)
    h_t = jnp.logspace(-15, -12, 10)
    dg, da = g.decide(0, h_t), a.decide(0, h_t)
    assert float(dg.probs.sum()) == 3.0 and float(da.probs.sum()) == 3.0
    # greedy picks the 3 largest gains
    assert np.asarray(dg.probs)[-3:].tolist() == [1.0, 1.0, 1.0]
    # age-based cycles: rounds 0..3 cover all 10 clients with k=3
    seen = set()
    for t in range(4):
        seen.update(np.where(np.asarray(a.decide(t, h_t).probs) > 0)[0].tolist())
    assert len(seen) == 10


def test_masked_aggregate_pallas_path_matches_oracle():
    """The fused Pallas kernel (interpret mode on CPU) and the jnp oracle
    produce identical server updates over a real parameter pytree."""
    p = init_mlp(jax.random.PRNGKey(0), dims=(16, 8, 4))
    st = init_fl_state(p, num_clients=4)
    moved = jax.tree_util.tree_map(
        lambda x: x + jax.random.normal(jax.random.PRNGKey(1), x.shape) * 0.1,
        st.client_params)
    st = st._replace(client_params=moved)
    d = pseudo_gradients(st)
    mask = jnp.array([1.0, 0.0, 1.0, 1.0])
    ref = masked_aggregate(st.global_params, d, mask, 4)
    fused = masked_aggregate(st.global_params, d, mask, 4,
                             use_pallas="interpret")
    for a, b in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(fused)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_aging_boost_reduces_max_gap_without_forcing():
    """Soft aging (beyond-paper): probability rises with staleness, so max
    transmission gaps shrink vs pure Bernoulli at low p̄."""
    tr, te, clients, cell, spec, h, params = small_world(rounds=16)
    base = SimConfig(rounds=16, local_iters=1, batch_size=8, eval_every=20,
                     max_staleness=4)
    aged = SimConfig(rounds=16, local_iters=1, batch_size=8, eval_every=20,
                     max_staleness=4, aging_boost=True)
    pol = RandomScheme(p_bar=0.02, num_clients=10)
    r_aged = run_simulation(params, mlp_loss, mlp_accuracy, clients, te,
                            pol, h, cell, aged)
    # every client transmits at least every 4 rounds
    for k in range(10):
        tx = np.where(r_aged.participation[:, k] > 0)[0]
        assert len(tx) >= 3
        if len(tx) > 1:
            assert np.diff(tx).max() <= 4
    # aging transmits *more* than the un-boosted baseline on average
    r_base = run_simulation(params, mlp_loss, mlp_accuracy, clients, te,
                            pol, h, cell, base)
    assert r_aged.participation.sum() >= r_base.participation.sum() - 1

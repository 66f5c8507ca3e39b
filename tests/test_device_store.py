"""Device-resident data store: packing, on-device sampling, jittable
partitioners, engine parity across the three data paths, and the
no-T-proportional-buffer guarantee."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CellConfig
from repro.core.selection import RandomScheme, as_policy_fn
from repro.data import (Dataset, DeviceDataStore, StreamingSampler,
                        choose_data_path, data_stream_key, dirichlet_store,
                        from_client_datasets, label_histogram, make_mnist_like,
                        round_indices, sample_round, shard_noniid, shard_store,
                        stack_rounds_reference)
from repro.fl import SimConfig, build_scan_sim, make_runner, run_simulation
from repro.fl.simulator import run_simulation_legacy
from repro.models.small import init_mlp, mlp_accuracy, mlp_loss
from repro.optim import sgd


def small_world(K=8, rounds=12, dim=64, n_train=1200, d=5):
    tr, te = make_mnist_like(jax.random.PRNGKey(0), n_train=n_train,
                             n_test=300)
    clients = shard_noniid(jax.random.PRNGKey(1), tr, K, d=d)
    clients = [Dataset(c.x[:, :dim], c.y, c.num_classes) for c in clients]
    te = Dataset(te.x[:, :dim], te.y, te.num_classes)
    cell = CellConfig(num_clients=K)
    from repro.core.channel import channel_gains, sample_positions
    pos = sample_positions(jax.random.PRNGKey(2), cell)
    h = channel_gains(jax.random.PRNGKey(3), pos, rounds).T
    params = init_mlp(jax.random.PRNGKey(4), dims=(dim, 24, 10))
    return clients, te, cell, h, params


# --- store packing + sampling ----------------------------------------------


def test_store_packing_and_masks():
    clients = [Dataset(jnp.ones((n, 3)) * (i + 1.0),
                       jnp.full((n,), i, jnp.int32), 4)
               for i, n in enumerate((5, 9, 7))]
    store = from_client_datasets(clients)
    assert store.x.shape == (3, 9, 3) and store.y.shape == (3, 9)
    assert store.lengths.tolist() == [5, 9, 7]
    # padding rows are zero
    assert float(jnp.abs(store.x[0, 5:]).max()) == 0.0
    # sampled indices never reach the padding
    idx = round_indices(data_stream_key(0), jnp.int32(7), store.lengths,
                        local_iters=4, batch_size=16)
    assert idx.shape == (3, 4, 16)
    assert bool(jnp.all(idx < store.lengths[:, None, None]))
    xb, yb = sample_round(store, data_stream_key(0), jnp.int32(7), 4, 16)
    # every drawn row belongs to its client (client i holds value i+1/label i)
    for k in range(3):
        assert float(jnp.abs(xb[k] - (k + 1.0)).max()) == 0.0
        assert yb[k].min() == k and yb[k].max() == k


def test_token_targets_keep_their_sequence_shape():
    """``[n, S]`` next-token targets pack to ``[K, cap, S]`` and come back
    whole through the on-device and the streaming samplers."""
    S = 6
    clients = [Dataset(jnp.arange(n * S, dtype=jnp.int32).reshape(n, S) + i,
                       jnp.arange(n * S, dtype=jnp.int32).reshape(n, S)
                       + 100 * i + 1, 50)
               for i, n in enumerate((3, 5))]
    store = from_client_datasets(clients)
    assert store.x.shape == (2, 5, S) and store.y.shape == (2, 5, S)
    assert store.y.dtype == jnp.int32
    for k, c in enumerate(clients):
        n = c.y.shape[0]
        np.testing.assert_array_equal(store.y[k, :n], c.y)
        assert not np.asarray(store.y[k, n:]).any()
    key = data_stream_key(3)
    xb, yb = sample_round(store, key, jnp.int32(2), 2, 4)
    assert yb.shape == (2, 2, 4, S)
    np.testing.assert_array_equal(yb - xb, np.broadcast_to(
        (100 * np.arange(2) + 1 - np.arange(2))[:, None, None, None],
        yb.shape))
    sx, sy = StreamingSampler(clients, key, 2, 4).chunk(2, 3)
    np.testing.assert_array_equal(sx[0], xb)
    np.testing.assert_array_equal(sy[0], yb)


def test_class_labels_pack_as_before():
    """``[n]`` class labels still pack to ``[K, cap]`` int32, zero-padded."""
    clients = [Dataset(jnp.ones((n, 2)), jnp.arange(n) % 3, 3)
               for n in (2, 4)]
    store = from_client_datasets(clients)
    assert store.y.shape == (2, 4) and store.y.dtype == jnp.int32
    np.testing.assert_array_equal(store.y, [[0, 1, 0, 0], [0, 1, 2, 0]])


def test_store_rejects_empty_client():
    clients = [Dataset(jnp.ones((4, 2)), jnp.zeros((4,), jnp.int32), 2),
               Dataset(jnp.ones((0, 2)), jnp.zeros((0,), jnp.int32), 2)]
    with pytest.raises(ValueError, match="non-empty"):
        from_client_datasets(clients)


def test_stream_depends_only_on_key_and_round():
    lengths = jnp.array([10, 20], jnp.int32)
    a = round_indices(data_stream_key(3), jnp.int32(5), lengths, 2, 4)
    b = round_indices(data_stream_key(3), jnp.int32(5), lengths, 2, 4)
    c = round_indices(data_stream_key(3), jnp.int32(6), lengths, 2, 4)
    d = round_indices(data_stream_key(4), jnp.int32(5), lengths, 2, 4)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    assert not np.array_equal(np.asarray(a), np.asarray(d))


# --- engine parity: device path == pre-stacked reference, bit-identical -----


def test_device_sampler_matches_prestacked_reference_T50():
    """The tentpole parity claim: the in-scan sampler and the [T, K, L, B]
    pre-stack of the *same* fold_in stream produce bit-identical loss /
    energy trajectories at T=50."""
    T = 50
    clients, te, cell, h, params = small_world(rounds=T)
    cfg = SimConfig(rounds=T, local_iters=2, batch_size=8, eval_every=10,
                    eval_batch=200, data_path="device")
    policy = RandomScheme(p_bar=0.3, num_clients=8)
    runner = make_runner(mlp_loss, mlp_accuracy, clients, te, policy, cell,
                         cfg)
    res_dev = runner(params, h)

    # same stream, materialized eagerly into the legacy layout
    store = from_client_datasets(clients)
    xb_all, yb_all = stack_rounds_reference(store, data_stream_key(cfg.seed),
                                            T, cfg.local_iters,
                                            cfg.batch_size)
    sim = build_scan_sim(mlp_loss, mlp_accuracy, sgd(cfg.lr), cfg, cell, 8,
                         as_policy_fn(policy), shard_clients=False,
                         data_mode="prestack")
    state, energy, traces = jax.jit(sim)(
        params, xb_all, yb_all, jnp.swapaxes(h, 0, 1),
        jax.random.PRNGKey(cfg.seed), te.x[:200], te.y[:200])

    did = np.asarray(traces.did_eval)
    idx = np.where(did)[0]
    assert np.array_equal(res_dev.test_loss, np.asarray(traces.loss)[idx])
    assert np.array_equal(res_dev.test_acc, np.asarray(traces.acc)[idx])
    assert np.array_equal(res_dev.energy_per_client, np.asarray(energy))
    for a, b in zip(jax.tree_util.tree_leaves(res_dev.state.global_params),
                    jax.tree_util.tree_leaves(state.global_params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_scan_vs_legacy_parity_on_device_path():
    clients, te, cell, h, params = small_world(rounds=10)
    cfg = SimConfig(rounds=10, local_iters=2, batch_size=8, eval_every=4,
                    eval_batch=200, data_path="device")
    policy = RandomScheme(p_bar=0.4, num_clients=8)
    scan = run_simulation(params, mlp_loss, mlp_accuracy, clients, te,
                          policy, h, cell, cfg)
    legacy = run_simulation_legacy(params, mlp_loss, mlp_accuracy, clients,
                                   te, policy, h, cell, cfg)
    np.testing.assert_array_equal(scan.participation, legacy.participation)
    np.testing.assert_allclose(scan.test_loss, legacy.test_loss, atol=1e-5)
    np.testing.assert_allclose(scan.energy_per_client,
                               legacy.energy_per_client, rtol=1e-6)


def test_prestack_path_still_parity_checked():
    """The legacy BatchIterator pre-stack stays available and bit-equal
    across engines when forced via cfg.data_path."""
    clients, te, cell, h, params = small_world(rounds=8)
    cfg = SimConfig(rounds=8, local_iters=2, batch_size=8, eval_every=3,
                    eval_batch=200, data_path="prestack")
    policy = RandomScheme(p_bar=0.4, num_clients=8)
    scan = run_simulation(params, mlp_loss, mlp_accuracy, clients, te,
                          policy, h, cell, cfg)
    legacy = run_simulation_legacy(params, mlp_loss, mlp_accuracy, clients,
                                   te, policy, h, cell, cfg)
    np.testing.assert_array_equal(scan.participation, legacy.participation)
    np.testing.assert_allclose(scan.test_loss, legacy.test_loss, atol=1e-5)


def test_streaming_fallback_bit_identical_to_device_path():
    """Chunked host streaming (double-buffered prefetch) replays the same
    stream: results match the on-device path bit-wise across chunk
    boundaries (T=20, chunk=7 → 3 uneven chunks)."""
    T = 20
    clients, te, cell, h, params = small_world(rounds=T)
    base = dict(rounds=T, local_iters=2, batch_size=8, eval_every=6,
                eval_batch=200)
    policy = RandomScheme(p_bar=0.3, num_clients=8)
    dev = make_runner(mlp_loss, mlp_accuracy, clients, te, policy, cell,
                      SimConfig(**base, data_path="device"))(params, h)
    stream = make_runner(mlp_loss, mlp_accuracy, clients, te, policy, cell,
                         SimConfig(**base, data_path="stream",
                                   stream_chunk=7))(params, h)
    assert np.array_equal(dev.participation, stream.participation)
    assert np.array_equal(dev.test_loss, stream.test_loss)
    assert np.array_equal(dev.test_acc, stream.test_acc)
    # energy crosses two differently-fused XLA programs → ULP-level slack
    np.testing.assert_allclose(dev.energy_per_client,
                               stream.energy_per_client, rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(dev.state.global_params),
                    jax.tree_util.tree_leaves(stream.state.global_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_legacy_loop_stream_path_stays_host_side_and_matches():
    """When the resolver picks "stream" the legacy host loop must serve
    batches from host memory (one-round chunks of the same index stream),
    not materialize the device store — and still match the chunked scan
    engine."""
    T = 10
    clients, te, cell, h, params = small_world(rounds=T)
    cfg = SimConfig(rounds=T, local_iters=2, batch_size=8, eval_every=4,
                    eval_batch=200, data_path="stream", stream_chunk=4)
    policy = RandomScheme(p_bar=0.4, num_clients=8)
    scan = run_simulation(params, mlp_loss, mlp_accuracy, clients, te,
                          policy, h, cell, cfg)
    legacy = run_simulation_legacy(params, mlp_loss, mlp_accuracy, clients,
                                   te, policy, h, cell, cfg)
    np.testing.assert_array_equal(scan.participation, legacy.participation)
    np.testing.assert_allclose(scan.test_loss, legacy.test_loss, atol=1e-5)
    np.testing.assert_allclose(scan.energy_per_client,
                               legacy.energy_per_client, rtol=1e-6)


# --- memory: no T-proportional buffer on the device path --------------------


def _all_avals(jaxpr, out):
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            out.append(v.aval)
        for p in eqn.params.values():
            for j in jax.tree_util.tree_leaves(
                    p, is_leaf=lambda x: hasattr(x, "jaxpr") or
                    hasattr(x, "eqns")):
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    _all_avals(inner, out)
    return out


def _max_var_elems(closed):
    avals = [v.aval for v in closed.jaxpr.invars]
    _all_avals(closed.jaxpr, avals)
    return max(int(np.prod(a.shape)) for a in avals if hasattr(a, "shape")
               and a.shape)


def test_no_T_proportional_buffer_at_T2000():
    """jaxpr allocation check at (T=2000, K=16, MNIST-scale): the largest
    array anywhere in the device-path program must stay far below the
    [T, K, L, B, 784] pre-stack; the prestack-mode program (the reference)
    must contain exactly that buffer."""
    T, K, L, B, dim = 2000, 16, 5, 10, 784
    cap = 500
    cfg = SimConfig(rounds=T, local_iters=L, batch_size=B, eval_every=100,
                    eval_batch=256, data_path="device")
    cell = CellConfig(num_clients=K)
    params = init_mlp(jax.random.PRNGKey(0), dims=(dim, 200, 10))
    policy_fn = as_policy_fn(RandomScheme(p_bar=0.2, num_clients=K))
    store = DeviceDataStore(
        jax.ShapeDtypeStruct((K, cap, dim), jnp.float32),
        jax.ShapeDtypeStruct((K, cap), jnp.int32),
        jax.ShapeDtypeStruct((K,), jnp.int32))
    args = (params, store, jax.ShapeDtypeStruct((2,), jnp.uint32),
            jax.ShapeDtypeStruct((T, K), jnp.float32),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
            jax.ShapeDtypeStruct((256, dim), jnp.float32),
            jax.ShapeDtypeStruct((256,), jnp.int32))
    opt = sgd(cfg.lr)

    sim_dev = build_scan_sim(mlp_loss, mlp_accuracy, opt, cfg, cell, K,
                             policy_fn, shard_clients=False,
                             data_mode="device")
    dev_max = _max_var_elems(jax.make_jaxpr(sim_dev)(*args))

    prestack_elems = T * K * L * B * dim
    # device path: largest live array ≪ the pre-stack (store + test set + a
    # handful of [K, L, B, dim] round batches are the biggest things left)
    assert dev_max < prestack_elems // 20, (dev_max, prestack_elems)

    # the reference path really does carry the [T, K, L, B, dim] buffer —
    # the check above is meaningful
    sim_pre = build_scan_sim(mlp_loss, mlp_accuracy, opt, cfg, cell, K,
                             policy_fn, shard_clients=False,
                             data_mode="prestack")
    pre_args = (params,
                jax.ShapeDtypeStruct((T, K, L, B, dim), jnp.float32),
                jax.ShapeDtypeStruct((T, K, L, B), jnp.int32)) + args[3:]
    pre_max = _max_var_elems(jax.make_jaxpr(sim_pre)(*pre_args))
    assert pre_max >= prestack_elems


# --- jittable partitioners --------------------------------------------------


def test_shard_store_properties():
    tr, _ = make_mnist_like(jax.random.PRNGKey(0), n_train=2000, n_test=100)
    for d in (2, 5, 10):
        st = shard_store(jax.random.PRNGKey(1), tr, 10, d=d)
        hist = np.asarray(label_histogram(st, 10))
        assert int(st.lengths.sum()) == 2000          # every example kept
        assert (hist.sum(1) == np.asarray(st.lengths)).all()
        assert ((hist > 0).sum(1) <= d).all()         # ≤ d labels per client


def test_shard_store_heterogeneity_monotone():
    tr, _ = make_mnist_like(jax.random.PRNGKey(0), n_train=3000, n_test=100)

    def tv(st):
        p = np.asarray(label_histogram(st, 10)).astype(float)
        p /= np.maximum(p.sum(1, keepdims=True), 1)
        return np.mean([0.5 * np.abs(p[i] - p[j]).sum()
                        for i in range(10) for j in range(i + 1, 10)])

    het = [tv(shard_store(jax.random.PRNGKey(1), tr, 10, d=d))
           for d in (2, 5, 10)]
    assert het[0] > het[1] > het[2]


def test_dirichlet_store_alpha_controls_heterogeneity():
    tr, _ = make_mnist_like(jax.random.PRNGKey(0), n_train=3000, n_test=100)
    lo = dirichlet_store(jax.random.PRNGKey(2), tr, 10, alpha=0.05)
    hi = dirichlet_store(jax.random.PRNGKey(2), tr, 10, alpha=100.0)
    assert int(lo.lengths.sum()) == 3000 and int(hi.lengths.sum()) == 3000
    n_lo = (np.asarray(label_histogram(lo, 10)) > 0).sum(1).mean()
    n_hi = (np.asarray(label_histogram(hi, 10)) > 0).sum(1).mean()
    assert n_lo < n_hi                  # small α ⇒ fewer classes per client
    assert n_hi > 9.0                   # large α ⇒ IID-like


def test_partitioner_rejects_zero_example_client():
    """Host entries (cap=None) refuse degenerate partitions — a zero-length
    client would otherwise silently sample padding forever."""
    ds = Dataset(jnp.ones((5, 4)), jnp.arange(5, dtype=jnp.int32) % 10, 10)
    with pytest.raises(ValueError, match="no examples"):  # 5 < K=10
        dirichlet_store(jax.random.PRNGKey(0), ds, 10, alpha=1.0)


def test_partitioners_vmap_over_lane_keys():
    """Per-scenario-lane non-IID realizations in one device program: both
    partitioners vmap over the key with a static capacity."""
    tr, _ = make_mnist_like(jax.random.PRNGKey(0), n_train=1000, n_test=100)
    keys = jax.random.split(jax.random.PRNGKey(9), 4)
    sh = jax.vmap(lambda k: shard_store(k, tr, 5, d=2, cap=420))(keys)
    di = jax.vmap(lambda k: dirichlet_store(k, tr, 5, 0.3, cap=1000))(keys)
    assert sh.x.shape == (4, 5, 420, 784) and di.x.shape == (4, 5, 1000, 784)
    assert (np.asarray(sh.lengths.sum(axis=1)) <= 1000).all()
    assert (np.asarray(di.lengths.sum(axis=1)) == 1000).all()
    # lanes differ (different keys ⇒ different partitions)
    assert not np.array_equal(np.asarray(di.lengths[0]),
                              np.asarray(di.lengths[1]))


# --- mesh placement ---------------------------------------------------------


def test_client_axis_shardings_specs():
    """Store leaves map their leading K axis onto the client mesh axis;
    non-divisible leaves replicate (divisibility guard)."""
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.launch.sharding import client_axis_shardings
    mesh = Mesh(np.array(jax.devices()[:1]), ("k",))
    clients = [Dataset(jnp.ones((4, 3)), jnp.zeros((4,), jnp.int32), 2)
               for _ in range(3)]
    sh = client_axis_shardings(from_client_datasets(clients), mesh, "k")
    assert sh.x.spec == P("k", None, None)
    assert sh.y.spec == P("k", None)
    assert sh.lengths.spec == P("k")
    # a scalar-leaf tree replicates
    rep = client_axis_shardings({"s": jnp.zeros(())}, mesh, "k")
    assert rep["s"].spec == P()


# --- footprint planner + streaming sampler ----------------------------------


def test_choose_data_path_by_footprint():
    clients = [Dataset(jnp.ones((50, 8)), jnp.zeros((50,), jnp.int32), 10)
               for _ in range(4)]
    assert choose_data_path(clients, budget_bytes=1 << 30) == "device"
    assert choose_data_path(clients, budget_bytes=1_000) == "stream"


def test_streaming_sampler_matches_reference():
    clients, te, cell, h, params = small_world(rounds=6)
    dk = data_stream_key(0)
    store = from_client_datasets(clients)
    ref_x, ref_y = stack_rounds_reference(store, dk, 6, 2, 8)
    ss = StreamingSampler(clients, dk, 2, 8)
    cx, cy = ss.chunk(2, 5)
    assert np.array_equal(np.asarray(cx), np.asarray(ref_x[2:5]))
    assert np.array_equal(np.asarray(cy), np.asarray(ref_y[2:5]))

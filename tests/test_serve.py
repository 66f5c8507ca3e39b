"""Async aggregation front door: admission semantics (validation, dedup,
backpressure, FIFO/age ordering), micro-batch bucketing, the no-drop /
no-double-count invariants under submitter races, policy serving, decision
log round-trips, and the replay-parity contract (a served session re-run
offline through the scan engine reproduces ledgers bit-exactly and the
model to the repo's golden tolerance)."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CellConfig
from repro.core.channel import channel_gains, sample_positions
from repro.core.selection import ProblemSpec, online_policy
from repro.fl.faults import GuardConfig
from repro.fl.state import AggregatorConfig
from repro.serve import (AggregationServer, DecisionLog, LoadGenConfig,
                         ServeConfig, make_client_step, pick_bucket,
                         replay_session, run_loadgen, toy_world,
                         verify_replay)


def _world(K=16, seed=0):
    return toy_world(K, dim=8, classes=4, n_per=6, seed=seed)


def _server(params, K, start=False, **kw):
    cfg = ServeConfig(num_clients=K, local_iters=1, batch_size=3,
                      lr=0.05, seed=0, **kw)
    return AggregationServer(params, cfg, start=start), cfg


def _drive(server, store, loss_fn, uploads, seed=1):
    """Submit `uploads` real client deltas, flushing whenever dedup blocks
    (manual-flush servers) — returns the per-client seq counters used."""
    cfg = server.cfg
    step = make_client_step(store, loss_fn, cfg.local_iters, cfg.batch_size,
                            cfg.seed, lr=cfg.lr)
    rng = np.random.default_rng(seed)
    seqs = np.zeros((cfg.num_clients,), np.int64)
    done = 0
    while done < uploads:
        k = int(rng.integers(cfg.num_clients))
        version, g = server.pull()
        seq = int(seqs[k])
        delta = step(g, k, seq)
        tk = server.submit(k, delta, version, seq=seq,
                           energy_j=float(k + 1) * 0.25)
        if tk.admitted:
            seqs[k] += 1
            done += 1
        else:
            assert tk.reason in ("duplicate", "backpressure")
            server.flush()
    return seqs


# --- unit: bucketing ---------------------------------------------------------


def test_pick_bucket_pow2_and_clamps():
    assert pick_bucket(1, 1, 64) == 1
    assert pick_bucket(3, 1, 64) == 4
    assert pick_bucket(5, 8, 64) == 8        # min_bucket floor
    assert pick_bucket(33, 8, 64) == 64
    assert pick_bucket(200, 8, 64) == 64     # max_batch ceiling
    assert pick_bucket(0, 1, 64) == 1


def test_serve_config_validation():
    with pytest.raises(ValueError, match="power of two"):
        ServeConfig(num_clients=4, max_batch=12)
    with pytest.raises(ValueError, match="min_bucket"):
        ServeConfig(num_clients=4, max_batch=8, min_bucket=16)
    with pytest.raises(ValueError, match="admission"):
        ServeConfig(num_clients=4, admission="lifo")


# --- admission semantics -----------------------------------------------------


def test_submit_validation_dedup_and_close():
    params, store, loss_fn, acc_fn = _world(K=4)
    server, _ = _server(params, 4)
    d = jax.tree_util.tree_map(jnp.zeros_like, params)
    assert server.submit(99, d, 0).reason == "bad_client"
    assert server.submit(-1, d, 0).reason == "bad_client"
    assert server.submit(0, d, 5).reason == "bad_version"   # future anchor
    t1 = server.submit(0, d, 0)
    assert t1.admitted and server.in_flight(0)
    assert server.submit(0, d, 0).reason == "duplicate"
    assert server.flush() == 1
    assert t1.wait(timeout=5) == 1 and server.version == 1
    server.close()
    assert server.submit(1, d, 0).reason == "closed"


def test_backpressure_engages_exactly_at_capacity():
    params, store, loss_fn, acc_fn = _world(K=8)
    server, _ = _server(params, 8, queue_capacity=3)
    d = jax.tree_util.tree_map(jnp.zeros_like, params)
    for k in range(3):
        assert server.submit(k, d, 0).admitted
    tk = server.submit(3, d, 0)
    assert not tk.admitted and tk.reason == "backpressure"
    server.flush()                       # drains the pending set
    assert server.submit(3, d, 0).admitted
    server.close()


def test_age_admission_takes_stalest_first():
    params, store, loss_fn, acc_fn = _world(K=8)
    server, _ = _server(params, 8, admission="age", max_batch=2,
                        min_bucket=1)
    d = jax.tree_util.tree_map(jnp.zeros_like, params)
    # advance the version so distinct anchor ages exist
    for _ in range(3):
        server.submit(0, d, server.version)
        server.flush()
    t = server.version
    server.submit(1, d, t)        # freshest
    server.submit(2, d, t - 2)    # stalest
    server.submit(3, d, t - 1)
    server.flush()
    rec = server.log.records[-1]
    assert list(rec.ids) == [2, 3]          # stalest two admitted first
    assert rec.stale[0] == 2 and rec.stale[1] == 1
    server.close()


# --- replay parity -----------------------------------------------------------


def test_manual_session_replays_bit_exactly():
    params, store, loss_fn, acc_fn = _world(K=16)
    server, _ = _server(params, 16, max_batch=8, min_bucket=2)
    _drive(server, store, loss_fn, uploads=40)
    server.close()
    assert server.version == len(server.log.records) > 0
    rep = verify_replay(server, store, params, loss_fn, acc_fn)
    assert rep["ok"] and rep["n_uploads"] == 40
    # verify_replay's contract: ledgers exact (asserted inside), the model
    # within its default rtol/atol — the live width-1 lane and the
    # bucketed replay are different programs, so equality is not promised
    served = jax.tree_util.tree_leaves(server.global_params())
    bound = max(1e-5 + 1e-4 * float(np.max(np.abs(np.asarray(s))))
                for s in served)
    assert rep["model_max_abs_err"] <= bound


def test_guarded_scheme_session_replays():
    """Guards + a pluggable scheme aggregator flow through the same jitted
    path live and in replay — the precedence mirror is load-bearing."""
    params, store, loss_fn, acc_fn = _world(K=12)
    server, _ = _server(
        params, 12, max_batch=4, min_bucket=2,
        guards=GuardConfig(quarantine=True, clip_norm=5.0,
                           staleness_power=0.5),
        aggregator=AggregatorConfig(kind="csmaafl", staleness_fn="poly"))
    _drive(server, store, loss_fn, uploads=24)
    server.close()
    rep = verify_replay(server, store, params, loss_fn, acc_fn)
    assert rep["ok"] and rep["n_batches"] == server.version


def test_decision_log_roundtrips_through_json(tmp_path):
    params, store, loss_fn, acc_fn = _world(K=8)
    server, _ = _server(params, 8, max_batch=4, min_bucket=2)
    _drive(server, store, loss_fn, uploads=10)
    server.close()
    p = str(tmp_path / "session.json")
    server.log.save(p)
    loaded = DecisionLog.load(p)
    assert loaded.header == server.log.header
    assert loaded.records == server.log.records
    # a replay from the loaded log alone matches the served model
    res = replay_session(loaded, store, params, loss_fn, acc_fn)
    for a, b in zip(jax.tree_util.tree_leaves(res.global_params),
                    jax.tree_util.tree_leaves(server.global_params())):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="schema"):
        DecisionLog.from_dict({"header": {"schema": "nope"}, "records": []})


# --- the control plane: p_{k,t} serving --------------------------------------


def test_policy_refresh_serves_probs_and_costs():
    K = 16
    params, store, loss_fn, acc_fn = _world(K=K)
    cell = CellConfig(num_clients=K)
    pos = sample_positions(jax.random.PRNGKey(0), cell)
    gains = channel_gains(jax.random.PRNGKey(1), pos, 8)
    pol = online_policy(ProblemSpec(cell=cell, rho=0.05, num_rounds=8))
    cfg = ServeConfig(num_clients=K, min_bucket=1)
    server = AggregationServer(params, cfg, policy_fn=pol, gains=gains,
                               cell=cell, start=False)
    p = server.transmit_probs()
    assert p.shape == (K,) and np.all(p > 0) and np.all(p <= 1)
    assert server.upload_cost(0) > 0.0
    d = jax.tree_util.tree_map(jnp.zeros_like, params)
    server.submit(3, d, 0)
    server.flush()
    rec = server.log.records[0]
    assert rec.probs[0] == pytest.approx(float(p[3]))  # snapshot at admission
    server.close()
    with pytest.raises(ValueError, match="gains"):
        AggregationServer(params, cfg, policy_fn=pol, start=False)


# --- concurrency: the no-drop / no-double-count stress test ------------------


def test_racing_submitters_never_drop_or_double_count():
    """N threads race the live batcher with a tiny queue: every admitted
    ticket resolves, the ledgers account for exactly the admitted multiset
    (nothing dropped, nothing counted twice), and the bound actually
    engaged (backpressure or dedup rejections were observed)."""
    K = 32
    params, store, loss_fn, acc_fn = _world(K=K)
    cfg = ServeConfig(num_clients=K, queue_capacity=8, max_batch=8,
                      min_bucket=2, flush_interval_s=0.001)
    server = AggregationServer(params, cfg, start=True)
    d = jax.tree_util.tree_map(jnp.zeros_like, params)
    n_threads, per_thread = 8, 40
    admitted: list = []
    rejected: list = []
    alock = threading.Lock()

    def submitter(w):
        rng = np.random.default_rng(w)
        for i in range(per_thread):
            k = int(rng.integers(K))
            tk = server.submit(k, d, server.version)
            with alock:
                (admitted if tk.admitted else rejected).append(tk)

    threads = [threading.Thread(target=submitter, args=(w,))
               for w in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    server.close(drain=True)           # the no-drop invariant
    assert server._batcher is None

    versions = [tk.wait(timeout=10) for tk in admitted]
    assert all(v is not None for v in versions)            # nothing dropped
    snap = server.ledger_snapshot()
    assert int(snap["tx_count"].sum()) == len(admitted)    # nothing doubled
    logged = [(rec.t, i, s) for rec in server.log.records
              for i, s in zip(rec.ids, rec.seqs)]
    assert len(logged) == len(set(logged)) == len(admitted)
    per_client = np.bincount([tk.client_id for tk in admitted], minlength=K)
    np.testing.assert_array_equal(snap["tx_count"], per_client)
    # the bound engaged: the tiny queue + per-client dedup pushed back
    assert len(rejected) > 0
    assert {tk.reason for tk in rejected} <= {"backpressure", "duplicate"}
    # every resolved version is the batch's t+1 (causality)
    for tk, v in zip(admitted, versions):
        assert 1 <= v <= server.version


def test_batcher_close_is_idempotent_and_context_managed():
    params, store, loss_fn, acc_fn = _world(K=4)
    cfg = ServeConfig(num_clients=4, min_bucket=1)
    with AggregationServer(params, cfg, start=True) as server:
        d = jax.tree_util.tree_map(jnp.zeros_like, params)
        tk = server.submit(0, d, 0)
        assert tk.wait(timeout=10) is not None
    server.close()                     # second close is a no-op
    assert server.version >= 1


def test_failed_flush_surfaces_to_waiters_submitters_and_close():
    """A flush that raises inside the batcher thread fails the tickets it
    held and every pending one, refuses later submissions and makes
    ``close`` raise — nothing waits forever on a dead batcher."""
    params, store, loss_fn, acc_fn = _world(K=4)
    cfg = ServeConfig(num_clients=4, min_bucket=1, flush_interval_s=0.001)
    server = AggregationServer(params, cfg, start=True)

    def boom(*args, **kwargs):
        raise ValueError("device fault")

    server._apply = boom
    d = jax.tree_util.tree_map(jnp.zeros_like, params)
    tk = server.submit(0, d, 0)
    assert tk.admitted
    with pytest.raises(RuntimeError, match="failed") as err:
        tk.wait(timeout=10)
    assert isinstance(err.value.__cause__, ValueError)
    with pytest.raises(RuntimeError, match="batcher failed"):
        server.submit(1, d, 0)
    with pytest.raises(RuntimeError, match="batcher failed"):
        server.close()
    assert server._batcher is None


def test_failed_manual_flush_fails_its_tickets():
    params, store, loss_fn, acc_fn = _world(K=4)
    server, _ = _server(params, 4)
    server._apply = lambda *a, **k: (_ for _ in ()).throw(
        ValueError("device fault"))
    d = jax.tree_util.tree_map(jnp.zeros_like, params)
    tk = server.submit(0, d, 0)
    with pytest.raises(ValueError, match="device fault"):
        server.flush()
    with pytest.raises(RuntimeError, match="failed"):
        tk.wait(timeout=1)


# --- end-to-end: the load generator ------------------------------------------


def test_loadgen_session_measures_and_replays():
    K = 24
    params, store, loss_fn, acc_fn = _world(K=K)
    cell = CellConfig(num_clients=K)
    pos = sample_positions(jax.random.PRNGKey(2), cell)
    gains = channel_gains(jax.random.PRNGKey(3), pos, 16)
    pol = online_policy(ProblemSpec(cell=cell, rho=0.05, num_rounds=16))
    cfg = ServeConfig(num_clients=K, queue_capacity=64, max_batch=8,
                      min_bucket=2, flush_interval_s=0.002)
    server = AggregationServer(params, cfg, policy_fn=pol, gains=gains,
                               cell=cell, start=True)
    lg = LoadGenConfig(uploads=60, workers=4, seed=0, respect_probs=False,
                       timeout_s=60.0)
    report = run_loadgen(server, store, loss_fn, lg)
    server.close(drain=True)
    assert report["uploads_admitted"] >= lg.uploads
    assert report["uploads_unresolved"] == 0
    assert report["uploads_per_second"] > 0
    assert report["batches"] == server.version > 0
    assert "p95" in report["admit_ms"] and "mean" in report["occupancy"]
    rep = verify_replay(server, store, params, loss_fn, acc_fn)
    assert rep["ok"] and rep["n_uploads"] == report["uploads_admitted"]


def test_loadgen_requires_running_batcher():
    params, store, loss_fn, acc_fn = _world(K=4)
    server, _ = _server(params, 4, start=False)
    with pytest.raises(ValueError, match="batcher"):
        run_loadgen(server, store, loss_fn, LoadGenConfig(uploads=1))
    server.close()

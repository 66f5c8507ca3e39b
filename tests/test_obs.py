"""Observability layer: in-scan metrics taps (bit-parity when disabled,
cross-path agreement when enabled, no extra carry buffers in the untapped
jaxpr), host telemetry manifests, the benchmark reporter's regression
gate, and the resumable driver's segment manifest."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CellConfig
from repro.core.selection import RandomScheme, as_policy_fn, csma_policy
from repro.data.device import data_stream_key, from_client_datasets
from repro.fl import (FaultConfig, GuardConfig, SimConfig, make_sparse_runner,
                      run_fault_matrix, run_simulation, run_simulation_legacy)
from repro.fl.engine import build_scan_sim, init_carry
from repro.fl.resume import read_segment_manifest, run_resumable
from repro.fl.schemes import run_scheme_matrix
from repro.models.small import mlp_accuracy, mlp_loss
from repro.obs import MetricsSpec, metrics_summary, validate_manifest
from repro.obs import report as obs_report
from repro.obs.telemetry import emit_run_manifest, get_telemetry
from repro.optim import sgd

from test_engine_parity import tiny_world
from test_scheme_parity import _matrix_world, _panel, sparse_cfg

K, T = 5, 8


def _cfg(**kw):
    base = dict(rounds=T, local_iters=2, batch_size=4, eval_every=2,
                local_mode="participants", data_path="device",
                data_stream="client")
    base.update(kw)
    return SimConfig(**base)


def _run_dense(cfg, policy=None):
    clients, te, cell, h, params = tiny_world(K=K, rounds=T, dim=32)
    policy = policy or csma_policy(3, K)
    return run_simulation(params, mlp_loss, mlp_accuracy, clients, te,
                          policy, h, cell, cfg)


def assert_metrics_agree(a, b, err=""):
    """Integer taps bit-exact; float taps to float-associativity tolerance."""
    assert a is not None and b is not None
    for f in type(a)._fields:
        va, vb = getattr(a, f), getattr(b, f)
        if va is None:
            assert vb is None, f"{err}: {f} active on one path only"
            continue
        va, vb = np.asarray(va), np.asarray(vb)
        if np.issubdtype(va.dtype, np.integer):
            np.testing.assert_array_equal(va, vb, err_msg=f"{err}: {f}")
        else:
            np.testing.assert_allclose(va, vb, rtol=1e-5, atol=1e-6,
                                       err_msg=f"{err}: {f}")


# --- disabled taps: bit parity and no extra carry ---------------------------


def test_disabled_taps_bit_parity_dense():
    off = _run_dense(_cfg(metrics=None))
    none = _run_dense(_cfg(metrics=MetricsSpec.none()))
    assert off.metrics is None and none.metrics is None
    np.testing.assert_array_equal(off.participation, none.participation)
    np.testing.assert_array_equal(np.asarray(off.test_acc),
                                  np.asarray(none.test_acc))
    np.testing.assert_array_equal(np.asarray(off.energy_per_client),
                                  np.asarray(none.energy_per_client))


def test_tapped_run_does_not_perturb_trajectory():
    off = _run_dense(_cfg(metrics=None))
    on = _run_dense(_cfg(metrics=MetricsSpec()))
    np.testing.assert_array_equal(off.participation, on.participation)
    np.testing.assert_array_equal(np.asarray(off.test_acc),
                                  np.asarray(on.test_acc))
    np.testing.assert_array_equal(np.asarray(off.energy_per_client),
                                  np.asarray(on.energy_per_client))
    assert off.metrics is None and on.metrics is not None


def test_disabled_taps_identical_jaxpr_and_carry():
    """MetricsSpec.none() must build the byte-identical program to
    metrics=None: no extra carry buffers, no extra ops."""
    clients, te, cell, h, params = tiny_world(K=K, rounds=T, dim=32)
    store = from_client_datasets(clients)
    data_key = data_stream_key(0)
    h_rounds = jnp.swapaxes(h, 0, 1)
    key = jax.random.PRNGKey(0)
    jaxprs, carries = [], []
    for spec in (None, MetricsSpec.none()):
        cfg = _cfg(metrics=spec)
        carries.append(init_carry(params, K, cfg))
        sim = build_scan_sim(mlp_loss, mlp_accuracy, sgd(cfg.lr), cfg, cell,
                             K, as_policy_fn(csma_policy(3, K)),
                             data_mode="device")
        jaxprs.append(str(jax.make_jaxpr(sim)(
            params, store, data_key, h_rounds, key,
            te.x[: cfg.eval_batch], te.y[: cfg.eval_batch])))
    assert (jax.tree_util.tree_structure(carries[0])
            == jax.tree_util.tree_structure(carries[1]))
    # identical up to the memory addresses repr'd into closure names
    import re
    norm = [re.sub(r"0x[0-9a-f]+", "0x", j) for j in jaxprs]
    assert norm[0] == norm[1]


# --- enabled taps: three-path agreement -------------------------------------


def test_taps_agree_across_all_three_paths():
    cfg = _cfg(metrics=MetricsSpec())
    clients, te, cell, h, params = tiny_world(K=K, rounds=T, dim=32)
    pol = csma_policy(3, K)
    dense = run_simulation(params, mlp_loss, mlp_accuracy, clients, te,
                           pol, h, cell, cfg)
    legacy = run_simulation_legacy(params, mlp_loss, mlp_accuracy, clients,
                                   te, pol, h, cell, cfg)
    sp = make_sparse_runner(mlp_loss, mlp_accuracy, clients, te, pol,
                            cell, cfg)(params, h)
    assert_metrics_agree(dense.metrics, legacy.metrics, "dense-legacy")
    assert_metrics_agree(dense.metrics, sp.metrics, "dense-sparse")
    # internal consistency against the realized masks
    ms = dense.metrics
    np.testing.assert_array_equal(np.asarray(ms.tx_count),
                                  dense.participation.sum(axis=0))
    assert int(np.asarray(ms.rounds)) == T
    assert int(np.asarray(ms.stale_hist).sum()) == \
        int(dense.participation.sum())
    summ = metrics_summary(ms)
    assert summ["tx_total"] == int(dense.participation.sum())


def test_partial_spec_subsets_are_jittable():
    spec = MetricsSpec(participation=True, staleness_hist=False,
                       energy_by_cause=False, guard_events=False,
                       weight_stats=False)
    res = _run_dense(_cfg(metrics=spec))
    ms = res.metrics
    assert ms.tx_count is not None and ms.stale_hist is None
    assert ms.energy_cause is None and ms.weight_entropy is None
    np.testing.assert_array_equal(np.asarray(ms.tx_count),
                                  res.participation.sum(axis=0))


def test_guard_event_taps_count_quarantines():
    faults = FaultConfig(p_corrupt=0.5, corrupt_mode="nan")
    guards = GuardConfig(quarantine=True, clip_norm=10.0)
    cfg = _cfg(metrics=MetricsSpec(), faults=faults, guards=guards,
               participation="dense")
    res = _run_dense(cfg, policy=RandomScheme(p_bar=0.6, num_clients=K))
    ge = np.asarray(res.metrics.guard_events)
    assert ge.shape == (3,)
    assert ge[0] >= 1      # quarantined NaN updates counted


# --- matrix fan-outs under vmap ---------------------------------------------


def test_scheme_matrix_taps_dense_sparse_agree():
    _, stores, te, cell, h_stack, params = _matrix_world()
    cfg = sparse_cfg(metrics=MetricsSpec())
    seeds = [0, 1]
    dense = run_scheme_matrix(params, mlp_loss, mlp_accuracy, stores, te,
                              _panel(), h_stack, cell, cfg, seeds,
                              participation="dense")
    sparse = run_scheme_matrix(params, mlp_loss, mlp_accuracy, stores, te,
                               _panel(), h_stack, cell, cfg, seeds,
                               participation="sparse")
    assert dense.metrics is not None and sparse.metrics is not None
    # vmap axes [V severities, L schemes, S seeds] land on every tap
    assert np.asarray(dense.metrics.tx_count).shape == (2, 4, 2, K)
    assert_metrics_agree(dense.metrics, sparse.metrics, "matrix")
    np.testing.assert_array_equal(
        np.asarray(dense.metrics.tx_count),
        dense.participation.sum(axis=3))       # [V, L, S, T, K] -> per-client
    untapped = run_scheme_matrix(params, mlp_loss, mlp_accuracy, stores, te,
                                 _panel(), h_stack, cell, sparse_cfg(), seeds,
                                 participation="dense")
    assert untapped.metrics is None
    np.testing.assert_array_equal(untapped.participation, dense.participation)


def test_fault_matrix_taps_per_guard_setting():
    clients, te, cell, h, params = tiny_world(K=K, rounds=T)
    faults = FaultConfig(p_loss=0.3, max_retries=1, p_corrupt=0.3,
                         corrupt_mode="nan")
    cfg = SimConfig(rounds=T, local_iters=1, batch_size=8, eval_every=4,
                    eval_batch=200, data_path="device", faults=faults,
                    metrics=MetricsSpec())
    res = run_fault_matrix(params, mlp_loss, mlp_accuracy, clients, te,
                           RandomScheme(p_bar=0.6, num_clients=K), h, cell,
                           cfg, rates=[0.0, 1.0])
    assert set(res.metrics) == {"guarded", "unguarded"}
    for name, ms in res.metrics.items():
        assert np.asarray(ms.tx_count).shape == (2, K)   # [rates, K]
        # the rate-0 lane is the clean world: every decision delivers
        np.testing.assert_array_equal(
            np.asarray(ms.tx_count)[0],
            np.asarray(res.delivered[name])[0].sum(axis=0))
    # the unguarded lanes carry no guard pipeline, hence no guard tap
    assert res.metrics["unguarded"].guard_events is None
    assert res.metrics["guarded"].guard_events is not None


# --- telemetry: manifests ----------------------------------------------------


def test_manifest_emit_validate_jsonl_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_OBS_DIR", str(tmp_path))
    m = emit_run_manifest("test_kind", _cfg(), extra={"x": 1})
    assert validate_manifest(m) == []
    path = os.path.join(str(tmp_path), "runs.jsonl")
    assert os.path.exists(path)
    with open(path) as f:
        lines = [json.loads(l) for l in f if l.strip()]
    assert lines[-1]["kind"] == "test_kind"
    assert lines[-1]["extra"] == {"x": 1}
    assert validate_manifest(lines[-1]) == []
    assert obs_report.main(["--validate", path]) == 0
    assert obs_report.main(["--summary", path]) == 0
    # schema violations are caught
    assert validate_manifest({"kind": 1}) != []
    with open(path, "a") as f:
        f.write(json.dumps({"kind": "broken"}) + "\n")
    assert obs_report.main(["--validate", path]) == 1


def test_runners_emit_manifests():
    tel = get_telemetry()
    before = len(tel.manifests)
    _run_dense(_cfg(metrics=None))
    kinds = [m["kind"] for m in tel.manifests[before:]]
    assert "make_runner" in kinds
    for m in tel.manifests[before:]:
        assert validate_manifest(m) == []




# --- reporter: diff gate ----------------------------------------------------


def _write_json(path, payload):
    with open(path, "w") as f:
        json.dump(payload, f)
    return str(path)


def test_report_diff_gates_on_time_regressions(tmp_path):
    old = _write_json(tmp_path / "old.json",
                      {"dense": {"warm_s": 1.0, "count": 5},
                       "fingerprint": {"git_sha": "aaa"}})
    slow = _write_json(tmp_path / "slow.json",
                       {"dense": {"warm_s": 3.0, "count": 500},
                        "fingerprint": {"git_sha": "bbb"}})
    ok = _write_json(tmp_path / "ok.json",
                     {"dense": {"warm_s": 1.05, "count": 500},
                      "fingerprint": {"git_sha": "ccc"}})
    # 3x on a _s key: regression, exit 1; the non-time `count` never gates
    assert obs_report.main(["--diff", old, slow, "--threshold", "2.0"]) == 1
    assert obs_report.main(["--diff", old, ok, "--threshold", "2.0"]) == 0
    # threshold above the ratio: passes
    assert obs_report.main(["--diff", old, slow, "--threshold", "4.0"]) == 0
    d = obs_report.diff_benches(json.load(open(old)), json.load(open(slow)),
                                2.0)
    gated = {r["key"]: r["gated"] for r in d["rows"]}
    assert gated == {"dense.warm_s": True, "dense.count": False}
    assert [r["key"] for r in d["regressions"]] == ["dense.warm_s"]


# --- resumable driver: segment manifest + metrics threading -----------------


def test_resume_segment_manifest_roundtrip(tmp_path):
    clients, te, cell, h, params = tiny_world(K=K, rounds=T, dim=32)
    cfg = _cfg(checkpoint_every=3, metrics=MetricsSpec())
    pol = csma_policy(3, K)
    ckpt = str(tmp_path / "ckpt")
    # simulated kill after the first committed segment, then resume
    assert run_resumable(params, mlp_loss, mlp_accuracy, clients, te, pol,
                         h, cell, cfg, ckpt, stop_after_segment=1) is None
    assert len(read_segment_manifest(ckpt)) == 1
    res = run_resumable(params, mlp_loss, mlp_accuracy, clients, te, pol,
                        h, cell, cfg, ckpt)
    entries = read_segment_manifest(ckpt)
    n_segments = (T + 2) // 3
    assert [e["segment"] for e in entries] == list(range(n_segments))
    for e in entries:
        assert e["seed"] == cfg.seed and e["stride"] == 3
        assert e["t1"] > e["t0"] and e["wall_s"] > 0.0
        assert isinstance(e["config_sha"], str) and e["config_sha"]
        assert "backend" in e["fingerprint"]
    # metrics carry threads through checkpoints: the resumed run's taps
    # match an uninterrupted dense run bit-for-bit
    dense = _run_dense(cfg, policy=pol)
    assert res.metrics is not None
    assert_metrics_agree(res.metrics, dense.metrics, "resume-dense")

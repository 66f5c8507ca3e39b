"""Fault-injection benchmark: guard overhead + the degradation curve.

Three measurements on the same tiny world:

* ``clean``     — no faults, no guards (the pre-robustness fast path).
* ``unguarded`` — full fault cocktail (Markov churn, crashes, lossy uplinks
  with retry, NaN corruption), server takes updates at face value.
* ``guarded``   — same faults behind the defensive aggregation stack
  (quarantine + norm clip + staleness down-weighting).

The headline acceptance: the guarded per-round wall-clock stays within 10%
of the unguarded faulty run — the defenses are mask arithmetic, not a second
pass.  A :func:`repro.fl.faults.run_fault_matrix` sweep then records the
accuracy/energy degradation curve over fault severity and asserts the
guarded lane stays finite at every rate while the unguarded one goes
non-finite once corruption bites.

Writes ``BENCH_faults.json`` (CI uploads it as an artifact).

    PYTHONPATH=src python -m benchmarks.bench_faults [--quick] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.core import CellConfig
from repro.core.channel import channel_gains, sample_positions
from repro.core.selection import RandomScheme
from repro.data import make_mnist_like, shard_noniid
from repro.data.synthetic import Dataset
from repro.fl import (FaultConfig, GuardConfig, SimConfig, make_runner,
                      run_fault_matrix)
from repro.launch.cache import enable_compile_cache
from repro.models.small import init_mlp, mlp_accuracy, mlp_loss

from .common import write_bench

DIM = 64

FAULTS = FaultConfig(p_fail=0.1, p_recover=0.5, diurnal_amp=0.5,
                     p_crash=0.05, p_loss=0.2, max_retries=1, backoff=2.0,
                     p_corrupt=0.2, corrupt_mode="nan")
GUARDS = GuardConfig(quarantine=True, clip_norm=10.0, staleness_power=0.5)


def tiny_world(K: int, T: int):
    tr, te = make_mnist_like(jax.random.PRNGKey(0), n_train=2000, n_test=400)
    clients = shard_noniid(jax.random.PRNGKey(1), tr, K, d=2)
    clients = [Dataset(c.x[:, :DIM], c.y, c.num_classes) for c in clients]
    te = Dataset(te.x[:, :DIM], te.y, te.num_classes)
    cell = CellConfig(num_clients=K)
    pos = sample_positions(jax.random.PRNGKey(2), cell)
    h = channel_gains(jax.random.PRNGKey(3), pos, T).T
    params = init_mlp(jax.random.PRNGKey(4), dims=(DIM, 32, 10))
    return clients, te, cell, h, params


def _timed_runs(runner, params, h, T: int):
    t0 = time.perf_counter()
    res = runner(params, h)
    jax.block_until_ready(res.state.global_params)
    cold_s = time.perf_counter() - t0
    warm = []
    for _ in range(3):
        t1 = time.perf_counter()
        out = runner(params, h)
        jax.block_until_ready(out.state.global_params)
        warm.append(time.perf_counter() - t1)
    warm_s = min(warm)
    leaves = jax.tree_util.tree_leaves(res.state.global_params)
    return {
        "cold_s": cold_s,
        "warm_s": warm_s,
        "per_round_ms": warm_s / T * 1e3,
        "final_acc": float(res.test_acc[-1]),
        "final_params_finite": bool(all(np.isfinite(np.asarray(p)).all()
                                        for p in leaves)),
    }


def bench(quick: bool) -> dict:
    K = 5
    T = 12 if quick else 60
    rates = [0.0, 0.5, 1.0] if quick else [0.0, 0.25, 0.5, 0.75, 1.0]
    clients, te, cell, h, params = tiny_world(K, T)
    policy = RandomScheme(p_bar=0.5, num_clients=K)
    base = dict(rounds=T, local_iters=2, batch_size=16, eval_every=T,
                eval_batch=200, data_path="device")
    out = {"config": {"K": K, "T": T, "rates": rates, "dim": DIM,
                      "backend": jax.default_backend()}}

    # --- guard overhead: clean vs faulty-unguarded vs faulty-guarded --------
    for name, cfg in [
        ("clean", SimConfig(**base)),
        ("unguarded", SimConfig(**base, faults=FAULTS)),
        ("guarded", SimConfig(**base, faults=FAULTS, guards=GUARDS)),
    ]:
        runner = make_runner(mlp_loss, mlp_accuracy, clients, te, policy,
                             cell, cfg)
        rec = _timed_runs(runner, params, h, T)
        out[name] = rec
        print(f"{name:>10s}  per-round {rec['per_round_ms']:8.3f} ms"
              f"  final acc {rec['final_acc']:.3f}"
              f"  finite={rec['final_params_finite']}")

    ratio = out["guarded"]["per_round_ms"] / out["unguarded"]["per_round_ms"]
    fault_cost = (out["unguarded"]["per_round_ms"]
                  / out["clean"]["per_round_ms"])
    out["headline"] = {
        "guard_overhead_ratio": ratio,
        "within_10pct": ratio <= 1.10,
        "fault_process_ratio_vs_clean": fault_cost,
    }
    print(f"guard overhead: {ratio:.3f}x vs unguarded "
          f"({'OK' if ratio <= 1.10 else 'OVER'} the 1.10x bound); "
          f"fault processes cost {fault_cost:.2f}x vs clean")

    # --- degradation curve: accuracy/energy vs fault severity ---------------
    cfg = SimConfig(**{**base, "eval_every": max(T // 4, 1)}, faults=FAULTS)
    mat = run_fault_matrix(params, mlp_loss, mlp_accuracy, clients, te,
                           policy, h, cell, cfg, rates, guard=GUARDS)
    out["degradation"] = {
        "rates": list(mat.rates),
        "eval_rounds": mat.eval_rounds.tolist(),
        "acc_guarded": np.asarray(mat.acc["guarded"]).tolist(),
        "acc_unguarded": np.asarray(mat.acc["unguarded"]).tolist(),
        "energy_guarded_j": np.asarray(
            mat.energy["guarded"]).sum(-1).tolist(),
        "energy_unguarded_j": np.asarray(
            mat.energy["unguarded"]).sum(-1).tolist(),
        "delivered_mass": np.asarray(
            mat.delivered["guarded"]).sum((-1, -2)).tolist(),
        "finite_guarded": np.asarray(mat.finite_final["guarded"]).tolist(),
        "finite_unguarded": np.asarray(
            mat.finite_final["unguarded"]).tolist(),
    }
    finite_g = np.asarray(mat.finite_final["guarded"])
    out["headline"]["guarded_finite_all_rates"] = bool(finite_g.all())
    for r, ag, au, fg, fu in zip(mat.rates,
                                 np.asarray(mat.acc["guarded"])[:, -1],
                                 np.asarray(mat.acc["unguarded"])[:, -1],
                                 finite_g,
                                 np.asarray(mat.finite_final["unguarded"])):
        print(f"rate {r:4.2f}  acc guarded {ag:.3f} (finite={bool(fg)})"
              f"  unguarded {au:.3f} (finite={bool(fu)})")
    assert finite_g.all(), "guarded lane went non-finite"
    return out


def _write(payload, out_path):
    write_bench(out_path, payload)


def main_quick():
    """Entry point for the aggregated ``benchmarks.run`` harness."""
    payload = {"quick": True, **bench(True)}
    _write(payload, "BENCH_faults.json")
    return payload


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small config for CI smoke")
    ap.add_argument("--out", default="BENCH_faults.json")
    args = ap.parse_args()
    payload = {"quick": args.quick, **bench(args.quick)}
    _write(payload, args.out)


if __name__ == "__main__":
    main()

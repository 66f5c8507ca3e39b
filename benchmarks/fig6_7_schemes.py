"""Paper Fig. 6 & 7 head-to-head: convergence vs energy for the full
async-FL scheme panel — the paper's probabilistic selection against
FedAsync-style staleness mixing (hinge/poly s(Δτ)), CSMAAFL-style
importance-weighted aggregation, and age-aware scheduling — at matched
average participation, across non-IID severities.

Runs on :func:`repro.fl.schemes.run_scheme_matrix`: schemes × seeds ×
severities ride vmap axes of ONE compiled device program per execution
path (dense scan and sparse two-phase), replacing the old per-scheme
legacy host loop.  Emits ``BENCH_schemes.json``.

    python -m benchmarks.fig6_7_schemes [--quick] [--dense-only] [--out NAME]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CellConfig, ProblemSpec
from repro.core.channel import channel_gains, sample_positions
from repro.core.selection import (age_aware_policy, average_participants,
                                  csma_policy, online_policy, random_policy)
from repro.data import make_mnist_like, shard_noniid
from repro.data.device import from_client_datasets
from repro.fl import AggregatorConfig, SimConfig
from repro.fl.schemes import SchemeSpec, run_scheme_matrix
from repro.launch.cache import enable_compile_cache

from .common import FULL, row, save_artifact, write_bench

SEVERITIES = (2, 5)            # non-IID shards per client (lower = harsher)


def matched_panel(spec: ProblemSpec, h, K: int) -> tuple[list, float]:
    """The comparison panel at matched average participation: every
    baseline is budgeted to the paper scheme's expected transmitting mass
    (paper §V-A methodology) so energy per round is comparable."""
    proposed = online_policy(spec)
    avg = average_participants(proposed, h)
    k = max(1, round(avg))
    p_bar = min(avg / K, 1.0)
    return [
        SchemeSpec("paper", proposed, AggregatorConfig(kind="paper")),
        SchemeSpec("fedasync-hinge", random_policy(p_bar, K),
                   AggregatorConfig(kind="fedasync", staleness_fn="hinge")),
        SchemeSpec("fedasync-poly", random_policy(p_bar, K),
                   AggregatorConfig(kind="fedasync", staleness_fn="poly")),
        SchemeSpec("csmaafl", csma_policy(k, K),
                   AggregatorConfig(kind="csmaafl")),
        SchemeSpec("age-aware", age_aware_policy(k, K),
                   AggregatorConfig(kind="age")),
    ], float(avg)


def build_matrix_world(K: int, rounds: int, n_train: int, seeds, dim=None):
    tr, te = make_mnist_like(jax.random.PRNGKey(0), n_train=n_train,
                             n_test=1_000)
    if dim is not None:
        from repro.data import Dataset
        tr = Dataset(tr.x[:, :dim], tr.y, tr.num_classes)
        te = Dataset(te.x[:, :dim], te.y, te.num_classes)
    severity_clients = [shard_noniid(jax.random.PRNGKey(1), tr, K, d=d)
                        for d in SEVERITIES]
    pad = max(int(c.y.shape[0]) for cs in severity_clients for c in cs)
    stores = [from_client_datasets(cs, pad_to=pad)
              for cs in severity_clients]
    cell = CellConfig(num_clients=K)
    pos = sample_positions(jax.random.PRNGKey(2), cell)
    h_stack = jnp.stack([
        channel_gains(jax.random.PRNGKey(3 + s), pos, rounds).T
        for s in range(len(seeds))])                    # [S, K, T]
    return stores, te, cell, h_stack


def run_setting(K: int, rho: float, rounds: int, n_train: int, seeds,
                local_iters: int, paths, params, test_ds_dim=None) -> dict:
    stores, te, cell, h_stack = build_matrix_world(K, rounds, n_train,
                                                   seeds, dim=test_ds_dim)
    from repro.models.small import mlp_accuracy, mlp_loss
    spec = ProblemSpec(cell=cell, rho=rho, num_rounds=rounds)
    panel, avg = matched_panel(spec, h_stack[0], K)
    cfg = SimConfig(rounds=rounds, local_iters=local_iters, batch_size=10,
                    lr=0.01, eval_every=max(rounds // 8, 1),
                    local_mode="participants", data_path="device",
                    data_stream="client")
    setting = {"avg_participants": avg, "severities_d": list(SEVERITIES),
               "seeds": list(seeds), "schemes": {}, "paths": {}}
    for path in paths:
        t0 = time.time()
        res = run_scheme_matrix(params, mlp_loss, mlp_accuracy, stores, te,
                                panel, h_stack, cell, cfg, seeds,
                                participation=path)
        secs = time.time() - t0
        setting["paths"][path] = {"wall_s": secs}
        lanes = res.acc.shape[0] * res.acc.shape[1] * res.acc.shape[2]
        row(f"schemes_{path}_k{K}", secs / lanes * 1e6,
            f"lanes={lanes};rounds={rounds}")
        ev = np.asarray(res.eval_rounds).astype(int)
        for v, d in enumerate(SEVERITIES):
            for l, name in enumerate(res.schemes):
                rec = setting["schemes"].setdefault(name, {})
                et = np.asarray(res.energy_timeline[v, l]).mean(axis=0)
                rec[f"d{d}/{path}"] = {
                    # seed-averaged convergence-vs-energy curves
                    "acc_curve": np.asarray(res.acc[v, l]).mean(0).tolist(),
                    "loss_curve": np.asarray(res.loss[v, l]).mean(0).tolist(),
                    "energy_curve": et[ev].tolist(),
                    "final_acc": float(np.asarray(res.acc)[v, l, :, -1]
                                       .mean()),
                    "total_energy_j": float(np.asarray(res.energy)[v, l]
                                            .sum(-1).mean()),
                }
        setting["eval_rounds"] = ev.tolist()
    return setting


def main(argv=None) -> dict:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="tiny CI smoke: short horizon, one seed")
    ap.add_argument("--dense-only", action="store_true",
                    help="skip the sparse two-phase path")
    ap.add_argument("--out", default="BENCH_schemes",
                    help="artifact name (default BENCH_schemes)")
    args = ap.parse_args(argv)

    from repro.models.small import init_mlp
    if args.quick:
        rounds, n_train, seeds, iters, dim = 8, 1_500, [0], 2, 32
        params = init_mlp(jax.random.PRNGKey(4), dims=(dim, 16, 10))
    else:
        rounds = 50 if FULL else 16
        n_train = 60_000 if FULL else 5_000
        seeds, iters, dim = [0, 1], 5, None
        params = init_mlp(jax.random.PRNGKey(4))
    paths = ["dense"] if args.dense_only else ["dense", "sparse"]

    out = {"quick": bool(args.quick)}
    out["fig6_k10"] = run_setting(10, 0.05, rounds, n_train, seeds, iters,
                                  paths, params, test_ds_dim=dim)
    if not args.quick:
        for K in (20, 30):
            out[f"fig7_k{K}"] = run_setting(K, 0.05, rounds, n_train, seeds,
                                            iters, paths, params,
                                            test_ds_dim=dim)
    save_artifact(args.out, out)
    write_bench(f"{args.out}.json", out)         # root copy for CI upload
    return out


if __name__ == "__main__":
    main()

"""Sparse-participation benchmark: population sweep at fixed transmitting mass.

Sweeps the population K with the *expected transmitting count* pinned
(``p̄ = E/K``), so every configuration does the same amount of useful
training work per round; what changes is how much population-shaped overhead
rides along:

* ``dense``  — the [K]-shaped round transition (participants local mode):
  gathers a ``[K, L, B, ...]`` round batch and runs local SGD over all K
  lanes every round, masking non-participants.  Measured at the smaller K
  only (its cost grows linearly with the population).
* ``sparse`` — the participant-centric two-phase path
  (:mod:`repro.fl.sparse`): the [K]-vector decision scan plus a
  bucket-shaped training program shared by the whole sweep (the phase-B
  trace counter is recorded to prove one compile serves every K).

The headline acceptance: sparse per-round wall-clock at K = 10⁵ stays
within 2× of the dense baseline at K = 10³ — per-participant cost, one
hundred times the population.  Memory is reported analytically (resident
store bytes, per-round gather bytes dense vs sparse) plus the tracemalloc
host peak.

Writes ``BENCH_sparse.json`` (CI uploads it as an artifact).

    PYTHONPATH=src python -m benchmarks.bench_sparse [--quick] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import time
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CellConfig
from repro.core.selection import RandomScheme, participant_bucket
from repro.data.device import DeviceDataStore
from repro.data.synthetic import Dataset
from repro.fl import SimConfig, make_runner
from repro.fl import sparse as sparse_mod
from repro.fl.sparse import make_sparse_runner
from repro.launch.cache import enable_compile_cache
from repro.models.small import init_mlp, mlp_accuracy, mlp_loss

from .common import write_bench

DIM, N_PER, CLASSES = 8, 4, 10


def build_store(K: int, seed: int = 0) -> DeviceDataStore:
    """Tiny fixed-size per-client shards, built vectorized (no K-length
    Python loop — at K = 10⁶ a Dataset list is itself the bottleneck)."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((K, N_PER, DIM), dtype=np.float32)
    y = np.tile(np.arange(N_PER, dtype=np.int32) % CLASSES, (K, 1))
    return DeviceDataStore(jnp.asarray(x), jnp.asarray(y),
                           jnp.full((K,), N_PER, jnp.int32))


def store_clients(store: DeviceDataStore) -> list:
    """Dataset-list view of a store (dense-path input; small K only)."""
    return [Dataset(store.x[k], store.y[k], CLASSES)
            for k in range(store.num_clients)]


def test_set(seed: int = 99) -> Dataset:
    rng = np.random.default_rng(seed)
    return Dataset(jnp.asarray(rng.standard_normal((64, DIM), np.float32)),
                   jnp.asarray(np.arange(64, dtype=np.int32) % CLASSES),
                   CLASSES)


def gains(K: int, T: int, seed: int = 5) -> jax.Array:
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.uniform(1e-14, 1e-12, (K, T)).astype(np.float32))


def _timed_runs(runner, params, h, T: int):
    tracemalloc.start()
    t0 = time.perf_counter()
    res = runner(params, h)
    cold_s = time.perf_counter() - t0
    _, host_peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    warm = []
    for _ in range(2):
        t1 = time.perf_counter()
        runner(params, h)
        warm.append(time.perf_counter() - t1)
    warm_s = min(warm)
    return {
        "cold_s": cold_s,
        "warm_s": warm_s,
        "per_round_ms": warm_s / T * 1e3,
        "host_peak_mb": host_peak / 1e6,
        "final_acc": float(res.test_acc[-1]),
        "mean_tx_per_round": float(res.participation.sum(axis=1).mean()),
    }


def bench(quick: bool) -> dict:
    E = 8 if quick else 16                      # expected transmitters/round
    T = 6 if quick else 20
    Ks = (256, 2048) if quick else (10 ** 3, 10 ** 4, 10 ** 5, 10 ** 6)
    K_dense = Ks[0]
    bucket = participant_bucket(E, cap=min(Ks))
    base = dict(rounds=T, local_iters=2, batch_size=4, eval_every=T,
                eval_batch=64, local_mode="participants",
                data_stream="client", data_path="device")
    te = test_set()
    params = init_mlp(jax.random.PRNGKey(4), dims=(DIM, 16, CLASSES))
    out = {"config": {"E": E, "T": T, "bucket": bucket, "Ks": list(Ks),
                      "K_dense_baseline": K_dense, "dim": DIM,
                      "n_per_client": N_PER,
                      "backend": jax.default_backend()},
           "dense": {}, "sparse": {}}

    # --- dense baseline(s): [K]-shaped rounds, small populations only ------
    for K in [k for k in Ks if k <= max(K_dense, 10 ** 4)]:
        store = build_store(K)
        cell = CellConfig(num_clients=K)
        cfg = SimConfig(**base, participation="dense")
        runner = make_runner(mlp_loss, mlp_accuracy, store_clients(store),
                             te, RandomScheme(p_bar=E / K, num_clients=K),
                             cell, cfg)
        rec = _timed_runs(runner, params, gains(K, T), T)
        rec["store_mb"] = store.nbytes / 1e6
        rec["round_gather_mb"] = K * 2 * 4 * DIM * 4 / 1e6  # [K, L, B, dim]
        out["dense"][f"K{K}"] = rec
        print(f"dense  K={K:>8d}  per-round {rec['per_round_ms']:8.2f} ms"
              f"  gather {rec['round_gather_mb']:8.2f} MB/round")

    # --- sparse sweep: one phase-B compile for every K ----------------------
    traces_before = sparse_mod.TRAIN_TRACE_COUNT
    for K in Ks:
        store = build_store(K)
        cell = CellConfig(num_clients=K)
        cfg = SimConfig(**base, participation="sparse",
                        participant_bucket=bucket)
        runner = make_sparse_runner(mlp_loss, mlp_accuracy, store, te,
                                    RandomScheme(p_bar=E / K, num_clients=K),
                                    cell, cfg)
        rec = _timed_runs(runner, params, gains(K, T), T)
        rec["store_mb"] = store.nbytes / 1e6
        rec["round_gather_mb"] = bucket * 2 * 4 * DIM * 4 / 1e6
        out["sparse"][f"K{K}"] = rec
        print(f"sparse K={K:>8d}  per-round {rec['per_round_ms']:8.2f} ms"
              f"  gather {rec['round_gather_mb']:8.2f} MB/round")
    out["phase_b_traces_for_sweep"] = (sparse_mod.TRAIN_TRACE_COUNT
                                       - traces_before)

    # --- the acceptance ratio ----------------------------------------------
    K_target = 2048 if quick else 10 ** 5
    ratio = (out["sparse"][f"K{K_target}"]["per_round_ms"]
             / out["dense"][f"K{K_dense}"]["per_round_ms"])
    out["headline"] = {
        "sparse_K": K_target, "dense_K": K_dense,
        "sparse_vs_dense_per_round_ratio": ratio,
        "within_2x": ratio <= 2.0,
    }
    print(f"sparse K={K_target} vs dense K={K_dense}: {ratio:.2f}x "
          f"({'OK' if ratio <= 2.0 else 'OVER'} the 2x bound); "
          f"phase-B traces for the whole sweep: "
          f"{out['phase_b_traces_for_sweep']}")
    return out


def _write(payload, out_path):
    write_bench(out_path, payload)


def main_quick():
    """Entry point for the aggregated ``benchmarks.run`` harness."""
    payload = {"quick": True, **bench(True)}
    _write(payload, "BENCH_sparse.json")
    return payload


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small config for CI smoke")
    ap.add_argument("--out", default="BENCH_sparse.json")
    args = ap.parse_args()
    payload = {"quick": args.quick, **bench(args.quick)}
    _write(payload, args.out)


if __name__ == "__main__":
    main()

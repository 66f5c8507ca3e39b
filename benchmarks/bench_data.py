"""Data-path benchmark: host pre-stack vs on-device gather vs streaming.

Runs the scan engine on the K=16 MNIST-scale config through the three data
paths at T ∈ {50, 500, 2000} and records wall-clock plus memory:

* ``prestack`` — legacy ``stack_round_batches``: [T, K, L, B, 784] built
  host-side before the scan (footprint grows linearly in T — ~125 MB at
  T=50/L=5, ~1 GB at T=2000/L=1);
* ``device``   — ``DeviceDataStore``: padded [K, N_max, 784] blocks resident
  on device, minibatches gathered inside the scan from the
  ``fold_in(data_key, t)`` stream (footprint independent of T);
* ``stream``   — host-resident blocks, double-buffered ``device_put``
  round-chunk prefetch (device footprint: two chunks, independent of T and
  of the dataset size).

``data_prep_s`` is what each path pays before the first round can run
(stack / pack / first chunk); ``end_to_end_s`` = prep + warm run, the
steady-state cost of a fresh configuration.  ``host_peak_mb`` is the
tracemalloc peak over prep + cold run.  T ≥ 500 drops local_iters to 1 so
the pre-stack reference stays materializable; each T block is
like-for-like across paths.

Writes ``BENCH_data.json`` (CI uploads it as an artifact).

    PYTHONPATH=src python -m benchmarks.bench_data [--quick] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import time
import tracemalloc

import jax

from repro.core import CellConfig
from repro.core.channel import channel_gains, sample_positions
from repro.core.selection import RandomScheme
from repro.data import from_client_datasets, make_mnist_like, shard_noniid
from repro.data.device import estimate_store_bytes
from repro.fl import SimConfig, make_runner, stack_round_batches
from repro.launch.cache import enable_compile_cache
from repro.models.small import init_mlp, mlp_accuracy, mlp_loss

from .common import write_bench


def build_world(K, T, n_train, seed=0):
    tr, te = make_mnist_like(jax.random.PRNGKey(seed), n_train=n_train,
                             n_test=1000)
    clients = shard_noniid(jax.random.PRNGKey(seed + 1), tr, K, d=5)
    cell = CellConfig(num_clients=K)
    pos = sample_positions(jax.random.PRNGKey(seed + 2), cell)
    h = channel_gains(jax.random.PRNGKey(seed + 3), pos, T).T
    params = init_mlp(jax.random.PRNGKey(seed + 4))
    return clients, te, cell, h, params


def _bench_path(path, clients, te, cell, h, params, cfg):
    """One path at one config: prep bytes/time, cold+warm run, host peak."""
    policy = RandomScheme(0.15, cell.num_clients)
    tracemalloc.start()
    t0 = time.perf_counter()
    if path == "prestack":
        xb_all, yb_all = stack_round_batches(clients, cfg)
        jax.block_until_ready(xb_all)
        data_bytes = int(xb_all.nbytes + yb_all.nbytes)
        del xb_all, yb_all  # the runner re-stacks; measured separately
    elif path == "device":
        store = from_client_datasets(clients)
        jax.block_until_ready(store.x)
        data_bytes = store.nbytes
        del store
    else:  # stream: devices hold ≤ 2 chunks at a time
        C = min(cfg.stream_chunk, cfg.rounds)
        sample = clients[0].x.shape[1:]
        import numpy as np
        per_round = (cfg.local_iters * cfg.batch_size
                     * int(np.prod(sample)) * 4
                     + cfg.local_iters * cfg.batch_size * 4)
        data_bytes = 2 * C * len(clients) * per_round
    prep_s = time.perf_counter() - t0

    runner = make_runner(mlp_loss, mlp_accuracy, clients, te, policy, cell,
                         cfg, data_path=path)
    t0 = time.perf_counter()
    res = runner(params, h)
    cold_s = time.perf_counter() - t0
    _, host_peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    t0 = time.perf_counter()
    res = runner(params, h)
    warm_s = time.perf_counter() - t0
    return {
        "data_prep_s": prep_s,
        "cold_s": cold_s,
        "warm_s": warm_s,
        "end_to_end_s": prep_s + warm_s,
        "steps_per_s_warm": cfg.rounds / warm_s,
        "device_data_bytes": data_bytes,
        "host_peak_mb": host_peak / 1e6,
        "final_acc": float(res.test_acc[-1]),
    }


def bench(quick: bool):
    K = 8 if quick else 16
    n_train = 2_000 if quick else 8_000
    horizons = (20, 60) if quick else (50, 500, 2000)
    out = {"config": {"K": K, "n_train": n_train, "batch_size": 10,
                      "backend": jax.default_backend()},
           "horizons": {}}
    clients_cache = {}
    for T in horizons:
        # L=5 is the paper's MNIST config; T ≥ 500 drops to L=1 so the
        # pre-stack reference stays materializable at like-for-like configs
        L = 5 if T <= 100 else 1
        if n_train not in clients_cache:
            clients_cache[n_train] = build_world(K, max(horizons), n_train)
        clients, te, cell, h_full, params = clients_cache[n_train]
        h = h_full[:, :T]
        cfg = SimConfig(rounds=T, local_iters=L, batch_size=10,
                        eval_every=max(T // 4, 1), eval_batch=512,
                        stream_chunk=max(T // 8, 16))
        rec = {"local_iters": L,
               "store_bytes": estimate_store_bytes(clients)}
        for path in ("prestack", "device", "stream"):
            rec[path] = _bench_path(path, clients, te, cell, h, params, cfg)
            print(f"T={T:5d} {path:9s} prep {rec[path]['data_prep_s']:7.2f}s"
                  f"  warm {rec[path]['warm_s']:7.2f}s"
                  f"  end-to-end {rec[path]['end_to_end_s']:7.2f}s"
                  f"  data {rec[path]['device_data_bytes'] / 1e6:8.1f} MB"
                  f"  host-peak {rec[path]['host_peak_mb']:8.1f} MB")
        rec["device_vs_prestack_steps"] = (
            rec["device"]["steps_per_s_warm"]
            / rec["prestack"]["steps_per_s_warm"])
        rec["device_vs_prestack_end_to_end"] = (
            rec["prestack"]["end_to_end_s"] / rec["device"]["end_to_end_s"])
        out["horizons"][f"T{T}"] = rec
    # the headline claim: device data bytes do not grow with T
    sizes = [out["horizons"][f"T{t}"]["device"]["device_data_bytes"]
             for t in horizons]
    out["device_bytes_T_independent"] = len(set(sizes)) == 1
    pre = [out["horizons"][f"T{t}"]["prestack"]["device_data_bytes"]
           for t in horizons]
    out["prestack_bytes_by_T"] = dict(zip([f"T{t}" for t in horizons], pre))
    return out


def _write(payload, out_path):
    write_bench(out_path, payload)


def main_quick():
    """Entry point for the aggregated ``benchmarks.run`` harness."""
    payload = {"quick": True, **bench(True)}
    _write(payload, "BENCH_data.json")
    return payload


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small config for CI smoke")
    ap.add_argument("--out", default="BENCH_data.json")
    args = ap.parse_args()
    payload = {"quick": args.quick, **bench(args.quick)}
    _write(payload, args.out)


if __name__ == "__main__":
    main()

"""Serving-path benchmark: the async aggregation front door under load.

Emulates a ≥10³-client population (``repro.serve.loadgen``) hammering a
live :class:`~repro.serve.AggregationServer` on CPU and records the
numbers the subsystem exists to deliver:

* sustained **uploads/s** (admitted-and-aggregated, not merely enqueued),
* **admission latency** percentiles (submit → aggregated, the
  ``flush_interval_s`` bound in action),
* **micro-batch occupancy** (how full the pow2 buckets run),
* the server-side telemetry counters/spans (PR-9 ``repro.obs.telemetry``),

then **asserts the replay-parity contract** on the very session it
measured — the decision log re-run offline through the scan engine must
reproduce the ledgers bit-exactly and the served model to golden
tolerance.  A parity violation exits nonzero: this benchmark doubles as
the serving smoke gate in CI (``serve-smoke``).

Two load modes: ``throughput`` (clients always transmit — the ingest
ceiling) and ``paper`` (clients gate on the served ``p_{k,t}`` — the
probabilistic-participation regime the paper models).

Writes ``BENCH_serve.json`` (repro-bench/v1).

    PYTHONPATH=src python -m benchmarks.bench_serve [--quick] [--out PATH]
"""
from __future__ import annotations

import argparse
import sys

import jax

from repro.core import CellConfig
from repro.core.channel import channel_gains, sample_positions
from repro.core.selection import ProblemSpec, online_policy
from repro.launch.cache import enable_compile_cache
from repro.obs.telemetry import get_telemetry
from repro.serve import (AggregationServer, LoadGenConfig, ServeConfig,
                         run_loadgen, toy_world, verify_replay)

from .common import write_bench


def _session(K: int, uploads: int, workers: int, respect_probs: bool,
             seed: int = 0) -> dict:
    params, store, loss_fn, acc_fn = toy_world(K, dim=16, classes=10,
                                               n_per=8, seed=seed)
    cell = CellConfig(num_clients=K)
    pos = sample_positions(jax.random.PRNGKey(seed), cell)
    gains = channel_gains(jax.random.PRNGKey(seed + 1), pos, 64)
    pol = online_policy(ProblemSpec(cell=cell, rho=0.05, num_rounds=64))
    cfg = ServeConfig(num_clients=K, queue_capacity=max(256, workers * 8),
                      max_batch=64, min_bucket=8, flush_interval_s=0.002,
                      policy_refresh_min_interval_s=2.0, seed=seed)
    server = AggregationServer(params, cfg, policy_fn=pol, gains=gains,
                               cell=cell, start=True)
    # warmup burst: compiles the client step + every bucket shape of the
    # jitted aggregation, then zeroes the measurement windows — the
    # reported numbers are steady state.  The decision log still covers
    # the warmup, so replay parity is asserted over the full session.
    warm = LoadGenConfig(uploads=max(cfg.max_batch * 2, 128),
                         workers=workers, seed=seed + 100,
                         respect_probs=False, timeout_s=300.0)
    run_loadgen(server, store, loss_fn, warm)
    server.reset_stats()
    lg = LoadGenConfig(uploads=uploads, workers=workers, seed=seed,
                       rate_sigma=1.0, respect_probs=respect_probs,
                       timeout_s=300.0)
    report = run_loadgen(server, store, loss_fn, lg)
    server.close(drain=True)
    parity = verify_replay(server, store, params, loss_fn, acc_fn)
    report["replay"] = parity
    report["uploads_per_second"] = float(report["uploads_per_second"])
    return report


def _flush_ceiling(K: int, reps: int = 20) -> dict:
    """Server-side aggregation capacity, no client emulation in the way:
    fill a full ``max_batch`` of pending updates and time warm flushes.
    This is what the data plane can absorb; the loadgen modes below are
    end-to-end numbers limited by the emulated clients sharing the box."""
    import time as _time

    import jax.numpy as jnp

    params, _, _, _ = toy_world(K, dim=16, classes=10, n_per=8, seed=0)
    cfg = ServeConfig(num_clients=K, queue_capacity=256, max_batch=64,
                      min_bucket=8, seed=0)
    server = AggregationServer(params, cfg, start=False)
    d = jax.tree_util.tree_map(jnp.zeros_like, params)

    def fill():
        for k in range(cfg.max_batch):
            server.submit(k, d, server.version)

    fill()
    server.flush()                     # compile the bucket
    times = []
    for _ in range(reps):
        fill()
        t0 = _time.perf_counter()
        server.flush()
        times.append(_time.perf_counter() - t0)
    server.close()
    best = min(times)
    return {"max_batch": cfg.max_batch, "flush_ms": best * 1e3,
            "uploads_per_second_ceiling": cfg.max_batch / best}


def bench(quick: bool) -> dict:
    K = 1000 if quick else 4000
    uploads = 500 if quick else 2000
    workers = 4 if quick else 8
    tel = get_telemetry()
    tel.reset()

    out: dict = {"clients": K, "modes": {}}
    out["flush_ceiling"] = _flush_ceiling(K)
    print(f"[bench_serve] flush ceiling: "
          f"{out['flush_ceiling']['uploads_per_second_ceiling']:.0f} "
          f"uploads/s ({out['flush_ceiling']['flush_ms']:.2f} ms per "
          f"{out['flush_ceiling']['max_batch']}-batch)")
    for mode, respect in (("throughput", False), ("paper", True)):
        print(f"[bench_serve] {mode}: K={K}, target={uploads} uploads")
        rep = _session(K, uploads, workers, respect_probs=respect)
        print(f"[bench_serve]   {rep['uploads_per_second']:.1f} uploads/s, "
              f"{rep['batches']} batches, "
              f"admit p95 {rep['admit_ms'].get('p95', 0):.2f} ms, "
              f"replay max|err| {rep['replay']['model_max_abs_err']:.2e}")
        out["modes"][mode] = rep

    flush = tel.span_stats("serve.flush")
    policy = tel.span_stats("serve.policy_refresh")
    out["telemetry"] = {
        "counters": {k: v for k, v in tel.snapshot().items()
                     if k.startswith("serve.")},
        "flush_span": flush, "policy_refresh_span": policy,
    }
    out["parity_ok"] = all(m["replay"]["ok"] for m in out["modes"].values())
    return out


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI scale: K=1000, 300 uploads per mode")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args(argv)
    payload = bench(args.quick)
    write_bench(args.out, payload)
    if not payload["parity_ok"]:       # replay divergence = hard failure
        print("[bench_serve] REPLAY PARITY VIOLATED", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

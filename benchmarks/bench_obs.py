"""Observability overhead benchmark: tapped vs untapped per-round cost.

Runs the dense scan engine and the sparse two-phase engine twice each —
metrics taps disabled (``cfg.metrics=None``) and the full default tap set
(``MetricsSpec()``) — and records warm per-round wall-clock for both.
The acceptance bound for the default tap set is ≤ 1.10× the untapped
path; the measured ratio lands in ``BENCH_obs.json`` so
``repro.obs.report --diff`` can gate regressions against it.

Also exercises the host-side telemetry layer end to end: the runners'
call spans, run manifests (set ``REPRO_OBS_DIR`` to persist
``runs.jsonl``), and the compile-cache hit/miss counters around the
sparse train cache.

Writes ``BENCH_obs.json`` (CI uploads it as an artifact).

    PYTHONPATH=src python -m benchmarks.bench_obs [--quick] [--out PATH]
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.core import CellConfig
from repro.core.selection import RandomScheme, participant_bucket
from repro.fl import SimConfig, make_runner
from repro.fl.sparse import make_sparse_runner
from repro.launch.cache import enable_compile_cache
from repro.models.small import init_mlp, mlp_accuracy, mlp_loss
from repro.obs import MetricsSpec, metrics_summary
from repro.obs.telemetry import get_telemetry

from .bench_sparse import (DIM, build_store, gains, store_clients,
                           test_set)
from .common import write_bench

BOUND = 1.10      # acceptance: default tap set ≤ 1.10× untapped per-round


def _warm_per_round(runner, params, h, T: int, reps: int = 3) -> dict:
    t0 = time.perf_counter()
    res = runner(params, h)
    cold_s = time.perf_counter() - t0
    warm = []
    for _ in range(reps):
        t1 = time.perf_counter()
        res = runner(params, h)
        warm.append(time.perf_counter() - t1)
    return {"cold_s": cold_s, "warm_s": min(warm),
            "per_round_ms": min(warm) / T * 1e3}, res


def _pair(make, T: int, reps: int) -> dict:
    """Build + time the untapped and tapped variants of one path."""
    out = {}
    res_tapped = None
    for name, spec in (("untapped", None), ("tapped", MetricsSpec())):
        runner, params, h = make(spec)
        out[name], res = _warm_per_round(runner, params, h, T, reps)
        if name == "tapped":
            res_tapped = res
    out["overhead_ratio"] = (out["tapped"]["warm_s"]
                             / max(out["untapped"]["warm_s"], 1e-12))
    out["bound"] = BOUND
    out["within_bound"] = out["overhead_ratio"] <= BOUND
    out["metrics_summary"] = metrics_summary(res_tapped.metrics)
    return out


def bench(quick: bool) -> dict:
    E = 6
    T = 10 if quick else 40
    K_dense = 32 if quick else 128
    K_sparse = 256 if quick else 4096
    reps = 3 if quick else 5
    te = test_set()
    params = init_mlp(jax.random.PRNGKey(4), dims=(DIM, 16, te.num_classes))
    base = dict(rounds=T, local_iters=2, batch_size=4, eval_every=T,
                eval_batch=64, local_mode="participants",
                data_stream="client", data_path="device")

    def make_dense(spec):
        store = build_store(K_dense)
        cfg = SimConfig(**base, participation="dense", metrics=spec)
        runner = make_runner(mlp_loss, mlp_accuracy, store_clients(store),
                             te, RandomScheme(p_bar=E / K_dense,
                                              num_clients=K_dense),
                             CellConfig(num_clients=K_dense), cfg)
        return runner, params, gains(K_dense, T)

    def make_sparse(spec):
        store = build_store(K_sparse)
        bucket = participant_bucket(E, cap=K_sparse)
        cfg = SimConfig(**base, participation="sparse",
                        participant_bucket=bucket, metrics=spec)
        runner = make_sparse_runner(mlp_loss, mlp_accuracy, store, te,
                                    RandomScheme(p_bar=E / K_sparse,
                                                 num_clients=K_sparse),
                                    CellConfig(num_clients=K_sparse), cfg)
        return runner, params, gains(K_sparse, T)

    out = {"config": {"E": E, "T": T, "K_dense": K_dense,
                      "K_sparse": K_sparse, "reps": reps,
                      "backend": jax.default_backend()}}
    out["dense"] = _pair(make_dense, T, reps)
    print(f"dense  K={K_dense}: tapped/untapped = "
          f"{out['dense']['overhead_ratio']:.3f} (bound {BOUND})")
    out["sparse"] = _pair(make_sparse, T, reps)
    print(f"sparse K={K_sparse}: tapped/untapped = "
          f"{out['sparse']['overhead_ratio']:.3f} (bound {BOUND})")

    tel = get_telemetry()
    snap = tel.snapshot()
    out["telemetry"] = {
        "counters": snap["counters"],
        "spans": snap["spans"],
        "manifests_emitted": len(tel.manifests),
    }
    return out


def main_quick():
    """Entry point for the aggregated ``benchmarks.run`` harness."""
    payload = {"quick": True, **bench(True)}
    write_bench("BENCH_obs.json", payload)
    return payload


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small config for CI smoke")
    ap.add_argument("--out", default="BENCH_obs.json")
    args = ap.parse_args()
    payload = {"quick": args.quick, **bench(args.quick)}
    write_bench(args.out, payload)


if __name__ == "__main__":
    main()

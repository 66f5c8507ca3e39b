#!/usr/bin/env python3
"""Bring-up smoke test: the system's main paths on a TPU, at real sizes.

    python chip_smoke.py             # one chip: the five phases below
    python chip_smoke.py --chips 4   # four chips: client-axis sharded engine

One process runs every phase in order; any failed check raises, so the
script exits non-zero and never prints its result line.  Phases:

1. device     — the default JAX device must be a TPU (no CPU fallback).
2. dense      — the paper cell: MLP 784-200-10, 60k/10k MNIST-like data,
                K=10 non-IID d=5, ProposedOnline, 5 local iterations of
                batch 10 at lr 0.01, through ``make_runner``; the compiled
                program must hold the Pallas kernel, accuracy must rise,
                and masks/ledgers must match ``run_simulation_legacy``.
3. sparse     — the same world at K=1,000 in participants mode; the
                resolver must pick the sparse path, whose masks must equal
                the dense engine's.
4. serve      — an ``AggregationServer`` at K=1,000 answers uploads from
                real client steps; ``verify_replay`` must hold.
5. kernel     — ``fl_aggregate`` (plain, subset, guarded) at the MLP's
                per-leaf sizes for R ∈ {10, 64, 1024} against kernels/ref.py.

With ``--chips 4`` only the multi-chip phase runs: the dense engine at
K=100, auto-sharded over the client axis, against the same runner with
``shard_clients=False``.

Each phase prints one line with its compile and run seconds and its max
error against its reference; the last line is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ROUNDS = 20
ROUNDS_4CHIP = 10
N_TRAIN, N_TEST = 60_000, 10_000
LOCAL = dict(local_iters=5, batch_size=10, lr=0.01)
LEAF_SIZES = (784 * 200, 200, 200 * 10, 10)
KERNEL_ROWS = (10, 64, 1024)
UPLOADS = 48
WAIT_S = 300.0
# the engine-parity contract (tests/test_engine_parity.py)
PARITY = dict(energy_rtol=1e-6, acc_atol=1e-6, loss_atol=1e-5,
              params_atol=1e-6)
KERNEL_TOL = dict(rtol=2e-5, atol=2e-5)


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def report(phase: str, compile_s: float, run_s: float, max_err: float,
           **extra) -> None:
    rest = " ".join(f"{k}={v}" for k, v in extra.items())
    print(f"[{phase}] compile_s={compile_s:.3f} run_s={run_s:.3f} "
          f"max_err={max_err:.3e} {rest}".rstrip(), flush=True)


def timed_twice(fn):
    """Run ``fn`` twice; the first call compiles.  Returns ``(result,
    compile_s, run_s)`` with compile_s = first − second wall time."""
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    t1 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    t2 = time.perf_counter()
    return out, max((t1 - t0) - (t2 - t1), 0.0), t2 - t1


def has_kernel(hlo_text: str) -> bool:
    return "tpu_custom_call" in hlo_text


def max_abs_diff(a, b) -> float:
    import jax
    import numpy as np

    diffs = [float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
             for x, y in zip(jax.tree_util.tree_leaves(a),
                             jax.tree_util.tree_leaves(b)) if np.size(x)]
    return max(diffs, default=0.0)


@dataclasses.dataclass
class World:
    clients: list
    test: object
    cell: object
    policy: object
    h: object          # [K, T] channel gains
    params: object


def build_world(train, test, K: int, rounds: int, seed: int = 0) -> World:
    import jax

    from repro.core import CellConfig, ProblemSpec
    from repro.core.channel import channel_gains, sample_positions
    from repro.core.selection import ProposedOnline
    from repro.data import shard_noniid
    from repro.models.small import init_mlp

    clients = shard_noniid(jax.random.PRNGKey(seed + 1), train, K, d=5)
    cell = CellConfig(num_clients=K)
    spec = ProblemSpec(cell=cell, rho=0.05, num_rounds=rounds)
    pos = sample_positions(jax.random.PRNGKey(seed + 2), cell)
    h = channel_gains(jax.random.PRNGKey(seed + 3), pos, rounds).T
    params = init_mlp(jax.random.PRNGKey(seed + 4))
    return World(clients, test, cell, ProposedOnline(spec), h, params)


def sim_config(rounds: int, **kw):
    from repro.fl import SimConfig

    return SimConfig(rounds=rounds, eval_every=5, seed=0, **LOCAL, **kw)


def run_sim(w: World, cfg, **kw):
    from repro.fl import make_runner
    from repro.models.small import mlp_accuracy, mlp_loss

    runner = make_runner(mlp_loss, mlp_accuracy, w.clients, w.test,
                         w.policy, w.cell, cfg, **kw)
    res, compile_s, run_s = timed_twice(lambda: runner(w.params, w.h))
    return runner, res, compile_s, run_s


def assert_same_decisions(a, b, what: str) -> None:
    import numpy as np

    check(np.array_equal(a.participation, b.participation),
          f"{what}: participation masks differ")
    check(np.array_equal(np.asarray(a.state.last_tx),
                         np.asarray(b.state.last_tx)),
          f"{what}: last_tx ledgers differ")
    check(np.array_equal(a.eval_rounds, b.eval_rounds),
          f"{what}: eval rounds differ")


def assert_parity(a, b, what: str) -> float:
    """Masks and integer ledgers bit-identical, floats to PARITY."""
    import numpy as np

    assert_same_decisions(a, b, what)
    check(np.allclose(a.energy_per_client, b.energy_per_client,
                      rtol=PARITY["energy_rtol"], atol=0.0),
          f"{what}: energy ledgers differ")
    check(np.allclose(a.test_acc, b.test_acc, rtol=0.0,
                      atol=PARITY["acc_atol"]), f"{what}: accuracy differs")
    check(np.allclose(a.test_loss, b.test_loss, rtol=0.0,
                      atol=PARITY["loss_atol"]), f"{what}: loss differs")
    err = max_abs_diff(a.state.global_params, b.state.global_params)
    check(err <= PARITY["params_atol"],
          f"{what}: global params differ by {err:.3e}")
    return err


def assert_sane(res, what: str) -> None:
    import jax
    import numpy as np

    check(np.isfinite(res.test_loss).all(), f"{what}: non-finite loss")
    check(np.isfinite(res.energy_per_client).all(),
          f"{what}: non-finite energy")
    check(all(np.isfinite(np.asarray(p)).all()
              for p in jax.tree_util.tree_leaves(res.state.global_params)),
          f"{what}: non-finite global model")


# --- phases ------------------------------------------------------------------


def phase_device(want_chips: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu",
          f"no TPU: the default JAX device is {d.platform!r}")
    check(len(devs) >= want_chips,
          f"need {want_chips} TPU chips, found {len(devs)}")
    print(f"[device] platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def phase_dense(w: World) -> None:
    from repro.fl import run_simulation_legacy
    from repro.models.small import mlp_accuracy, mlp_loss

    cfg = sim_config(ROUNDS)
    runner, res, compile_s, run_s = run_sim(w, cfg)
    check(runner.mesh is None, "dense paper cell unexpectedly sharded")
    check(has_kernel(runner.lower(w.params, w.h).compile().as_text()),
          "dense engine program holds no Pallas kernel (tpu_custom_call)")
    assert_sane(res, "dense")
    acc0, acc = float(res.test_acc[0]), float(res.test_acc[-1])
    check(acc > acc0, f"dense: accuracy did not rise ({acc0} -> {acc})")
    legacy = run_simulation_legacy(w.params, mlp_loss, mlp_accuracy,
                                   w.clients, w.test, w.policy, w.h, w.cell,
                                   cfg)
    err = assert_parity(res, legacy, "dense vs legacy")
    report("dense", compile_s, run_s, err, kernel="tpu_custom_call",
           acc_round0=acc0, acc_final=acc,
           uploads=int(res.participation.sum()),
           energy_j=float(res.energy_per_client.sum()))


def phase_sparse(w: World) -> None:
    import numpy as np

    cfg = sim_config(ROUNDS, local_mode="participants", participation="auto",
                     data_path="device", data_stream="client")
    _, sparse, compile_s, run_s = run_sim(w, cfg)
    check(sparse.state.client_params is None,
          "the resolver did not pick the sparse path")
    _, dense, _, dense_s = run_sim(
        w, dataclasses.replace(cfg, participation="dense"))
    assert_sane(sparse, "sparse")
    assert_same_decisions(sparse, dense, "sparse vs dense")
    err = max_abs_diff(sparse.state.global_params, dense.state.global_params)
    report("sparse", compile_s, run_s, err, K=len(w.clients),
           uploads=int(sparse.participation.sum()), dense_run_s=dense_s,
           energy_rel_err=float(np.max(np.abs(
               sparse.energy_per_client - dense.energy_per_client))
               / max(float(np.max(dense.energy_per_client)), 1e-30)))


def phase_serve(w: World) -> None:
    import numpy as np

    from repro.data import from_client_datasets
    from repro.models.small import mlp_accuracy, mlp_loss
    from repro.serve import (AggregationServer, ServeConfig,
                             make_client_step, verify_replay)

    K = len(w.clients)
    store = from_client_datasets(w.clients)
    cfg = ServeConfig(num_clients=K, seed=0, **LOCAL)
    step = make_client_step(store, mlp_loss, cfg.local_iters, cfg.batch_size,
                            cfg.seed, lr=cfg.lr)
    _, compile_s, _ = timed_twice(lambda: step(w.params, 0, 0))
    server = AggregationServer(w.params, cfg, start=True)
    batcher = server._batcher
    ids = np.random.default_rng(0).choice(K, size=UPLOADS, replace=False)
    t0 = time.perf_counter()
    tickets = []
    for i, k in enumerate(ids):
        version, g = server.pull()
        tk = server.submit(int(k), step(g, int(k), 0), version, seq=0,
                           energy_j=0.25 * (i + 1))
        check(tk.admitted, f"serve: upload {i} rejected ({tk.reason})")
        tickets.append(tk)
    versions = [tk.wait(timeout=WAIT_S) for tk in tickets]
    run_s = time.perf_counter() - t0
    check(batcher.error is None, f"serve: batcher failed: {batcher.error!r}")
    check(all(v is not None for v in versions),
          "serve: an admitted upload never resolved")
    server.close()
    t1 = time.perf_counter()
    rep = verify_replay(server, store, w.params, mlp_loss, mlp_accuracy)
    replay_s = time.perf_counter() - t1
    check(rep["ok"] and rep["n_uploads"] == UPLOADS,
          f"serve: replay report {rep}")
    report("serve", compile_s, run_s, rep["model_max_abs_err"], K=K,
           uploads=UPLOADS, batches=rep["n_batches"], replay_s=replay_s,
           note="run_s_includes_bucket_compiles")


def phase_kernel() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.fl.faults import GuardConfig
    from repro.fl.state import masked_aggregate, round_weights
    from repro.kernels import ref

    def quarantined(g, d, w):
        w, denom, d = round_weights(w, 1, jnp.zeros(w.shape, jnp.int32),
                                    None, None, GuardConfig(quarantine=True),
                                    d)
        return masked_aggregate(g, d, w, denom, use_pallas=True)

    # static K (the dense engine), traced K (the sparse path and the
    # server), and weights that hold their own scaling (guards, schemes)
    variants = {
        "plain": (lambda g, d, w: masked_aggregate(g, d, w, d.shape[0],
                                                   use_pallas=True),
                  lambda g, d, w: ref.fl_aggregate_ref(g, d, w, d.shape[0])),
        "subset": (lambda g, d, w: masked_aggregate(
            g, d, w, jnp.int32(1000), use_pallas=True),
            lambda g, d, w: ref.fl_aggregate_ref(g, d, w, 1000)),
        "guarded": (quarantined, lambda g, d, w: ref.fl_aggregate_ref(
            g, jnp.where(jnp.isfinite(d), d, 0.0), w, 1)),
    }
    compile_s = run_s = err = 0.0
    n = 0
    for R in KERNEL_ROWS:
        for M in LEAF_SIZES:
            ks = jax.random.split(jax.random.PRNGKey(R * 7 + M), 3)
            g = jax.random.normal(ks[0], (M,), jnp.float32)
            d = jax.random.normal(ks[1], (R, M), jnp.float32)
            w = (jax.random.uniform(ks[2], (R,)) < 0.5).astype(jnp.float32)
            for name, (kern, oracle) in variants.items():
                dd, ww = d, w
                if name == "guarded":
                    # a quarantined row: poisoned, weight 0
                    dd = d.at[R // 2].set(jnp.nan)
                    ww = (w / R).at[R // 2].set(0.0)
                fn = jax.jit(kern)
                t0 = time.perf_counter()
                compiled = fn.lower(g, dd, ww).compile()
                t1 = time.perf_counter()
                check(has_kernel(compiled.as_text()),
                      f"kernel {name} R={R} M={M}: no tpu_custom_call")
                out = jax.block_until_ready(compiled(g, dd, ww))
                run_s += time.perf_counter() - t1
                compile_s += t1 - t0
                with jax.default_matmul_precision("float32"):
                    want = jax.jit(oracle)(g, dd, ww)
                out, want = np.asarray(out), np.asarray(want)
                check(np.isfinite(out).all(),
                      f"kernel {name} R={R} M={M}: non-finite output")
                check(np.allclose(out, want, **KERNEL_TOL),
                      f"kernel {name} R={R} M={M}: differs from the oracle")
                err = max(err, float(np.max(np.abs(out - want))))
                n += 1
    report("kernel", compile_s, run_s, err, cases=n,
           rows=",".join(map(str, KERNEL_ROWS)))


def phase_sharded(w: World) -> dict:
    import numpy as np

    cfg = sim_config(ROUNDS_4CHIP)
    sharded, res, compile_s, run_s = run_sim(w, cfg)
    check(sharded.mesh is not None, "the engine did not shard the client axis")
    mesh = dict(sharded.mesh.shape)
    check(int(np.prod(list(mesh.values()))) == 4,
          f"client mesh {mesh} does not span 4 devices")
    _, plain, _, plain_s = run_sim(w, cfg, shard_clients=False)
    assert_sane(res, "sharded")
    err = assert_parity(res, plain, "sharded vs unsharded")
    report("sharded", compile_s, run_s, err, mesh=json.dumps(mesh),
           K=len(w.clients), unsharded_run_s=plain_s)
    return mesh


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the client-axis sharded engine phase")
    args = ap.parse_args(argv)

    device = phase_device(args.chips)

    import jax

    from repro.data import make_mnist_like
    from repro.launch.cache import enable_compile_cache

    print(f"[cache] dir={enable_compile_cache()}", flush=True)
    train, test = make_mnist_like(jax.random.PRNGKey(0), n_train=N_TRAIN,
                                  n_test=N_TEST)
    if args.chips == 4:
        phase_sharded(build_world(train, test, K=100, rounds=ROUNDS_4CHIP))
    else:
        phase_dense(build_world(train, test, K=10, rounds=ROUNDS))
        big = build_world(train, test, K=1000, rounds=ROUNDS)
        phase_sparse(big)
        phase_serve(big)
        phase_kernel()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

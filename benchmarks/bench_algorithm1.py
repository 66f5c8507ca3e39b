"""Algorithm 1 microbenchmarks: solver latency, outer-iteration counts,
objective vs naive allocations, online-vs-offline gap, damping ablation."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CellConfig, ProblemSpec
from repro.core import algorithm1 as a1
from repro.core.channel import channel_gains, sample_positions
from repro.core.online import solve_online
from repro.launch.cache import enable_compile_cache

from .common import row, save_artifact


def main() -> dict:
    enable_compile_cache()
    cell = CellConfig(num_clients=10)
    spec = ProblemSpec(cell=cell, rho=0.05, num_rounds=20)
    pos = sample_positions(jax.random.PRNGKey(0), cell)
    h = channel_gains(jax.random.PRNGKey(1), pos, spec.T).T

    out = {}

    # offline solve
    res = a1.solve(h, spec)  # compile
    t0 = time.time()
    n = 5
    for _ in range(n):
        res = jax.block_until_ready(a1.solve(h, spec))
    dt = (time.time() - t0) / n
    naive = float(a1.objective_p1(jnp.full_like(res.p, 0.1),
                                  jnp.full_like(res.w, 0.1), h, spec))
    out["offline"] = {"objective": float(res.objective), "naive_p0.1": naive,
                      "iters": int(res.iters), "residual": float(res.residual),
                      "seconds": dt}
    row("alg1_offline_solve", dt * 1e6,
        f"obj={float(res.objective):.3f};naive={naive:.3f};"
        f"iters={int(res.iters)}")

    # online solve (per-round latency — the deployable path)
    r1 = solve_online(h[:, 0], spec)
    t0 = time.time()
    for t in range(spec.T):
        r1 = jax.block_until_ready(solve_online(h[:, t % spec.T], spec))
    dt = (time.time() - t0) / spec.T
    # offline vs online objective gap (same uniform-p structure comparison)
    p_on = jnp.tile(r1.p[:, None], (1, spec.T))
    w_on = jnp.tile(r1.w[:, None], (1, spec.T))
    obj_on = float(a1.objective_p1(p_on, w_on, h, spec))
    out["online"] = {"per_round_seconds": dt, "objective_lastround": obj_on,
                     "iters": int(r1.iters)}
    row("alg1_online_solve", dt * 1e6,
        f"obj={obj_on:.3f};iters={int(r1.iters)}")

    # damping ablation (the convergence fix documented in EXPERIMENTS.md)
    abl = {}
    for zeta in (0.5, 0.3, 0.1, 0.05):
        r = a1.solve(h, spec, zeta=zeta)
        abl[zeta] = {"residual": float(r.residual),
                     "objective": float(r.objective),
                     "iters": int(r.iters)}
        row(f"alg1_zeta_{zeta}", 0.0,
            f"resid={abl[zeta]['residual']:.2e};obj={abl[zeta]['objective']:.3f}")
    out["damping_ablation"] = abl

    save_artifact("bench_algorithm1", out)
    return out


if __name__ == "__main__":
    main()

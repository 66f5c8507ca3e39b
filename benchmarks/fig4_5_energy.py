"""Paper Fig. 4 & 5: total energy vs average participants per round (Fig. 4)
and vs the number of clients K at fixed participation 0.1 (Fig. 5).

Claim under test: the proposed joint optimization spends markedly less
energy than random/greedy/age at every operating point.
"""
from __future__ import annotations

import numpy as np

from repro.core import ProblemSpec
from repro.core.channel import rate_nats
from repro.core.selection import (AgeBasedScheme, GreedyScheme,
                                  ProposedOnline, RandomScheme,
                                  average_participants, realize)
from repro.launch.cache import enable_compile_cache

from .common import build_world, row, save_artifact

import jax
import jax.numpy as jnp


def expected_energy(world, policy, rounds):
    """Expected per-round energy Σ p·P·S/R (eq. 5) summed over rounds —
    energy-only comparison (no model training needed)."""
    c = world.cell
    tot = 0.0
    per_client = np.zeros(c.num_clients)
    for t in range(rounds):
        d = policy.decide(t, world.h[:, t])
        R = rate_nats(d.w, world.h[:, t], c.tx_power_w, c.bandwidth_hz,
                      c.noise_w_per_hz)
        e = np.asarray(d.probs * c.tx_power_w * c.model_size_nats
                       / jnp.maximum(R, 1e-30))
        e = np.where(np.asarray(d.probs) > 0, e, 0.0)
        per_client += e
        tot += e.sum()
    return tot, per_client


def main() -> dict:
    enable_compile_cache()
    out = {"fig4": [], "fig5": []}

    # --- Fig. 4: energy vs avg participants (vary rho) ----------------------
    world = build_world(rounds=30)
    for rho in (0.01, 0.05, 0.15, 0.4):
        spec = ProblemSpec(cell=world.cell, rho=rho, num_rounds=world.rounds)
        prop = ProposedOnline(spec)
        avg = average_participants(prop, world.h)
        k = max(1, round(avg))
        K = world.cell.num_clients
        schemes = [prop, RandomScheme(min(avg / K, 1.0), K),
                   GreedyScheme(k, K), AgeBasedScheme(k, K)]
        rec = {"avg_participants": avg}
        for s in schemes:
            e, _ = expected_energy(world, s, world.rounds)
            rec[s.name] = float(e)
        out["fig4"].append(rec)
        row(f"fig4_avgk_{avg:.2f}", 0.0,
            ";".join(f"{s.name}={rec[s.name]:.2f}J" for s in schemes))

    # --- Fig. 5: energy vs number of clients at participation 0.1 -----------
    for K in (10, 20, 30):
        world = build_world(K=K, rounds=30, d=5 if K * 5 % 10 == 0 else 5)
        spec = ProblemSpec(cell=world.cell, rho=0.05, num_rounds=world.rounds)
        prop = ProposedOnline(spec)
        k = max(1, round(0.1 * K))
        schemes = [prop, RandomScheme(0.1, K), GreedyScheme(k, K),
                   AgeBasedScheme(k, K)]
        rec = {"K": K}
        for s in schemes:
            e, _ = expected_energy(world, s, world.rounds)
            rec[s.name] = float(e)
        out["fig5"].append(rec)
        row(f"fig5_K_{K}", 0.0,
            ";".join(f"{s.name}={rec[s.name]:.2f}J" for s in schemes))

    save_artifact("fig4_5_energy", out)
    return out


if __name__ == "__main__":
    main()

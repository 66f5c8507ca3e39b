"""Paper Fig. 8 & 9: extreme client placements.

Scenario 1: clients 1-5 near the server (100-200 m); Scenario 2: clients 1-5
at the cell edge (900-1000 m); remaining clients uniform.

Claims under test: greedy collapses (always picks the same well-placed
clients → unfair participation → accuracy drop, even below random on MNIST);
proposed keeps top accuracy, AND its per-client energy is balanced
(fairness) while total energy stays lowest.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CellConfig, ProblemSpec
from repro.core.channel import sample_positions
from repro.launch.cache import enable_compile_cache

from .common import build_world, row, run_policy, save_artifact, schemes_matched


def scenario_positions(key, K, near: bool):
    cell = CellConfig(num_clients=5)
    r = (100.0, 200.0) if near else (900.0, 1000.0)
    special = sample_positions(key, cell, r_min=r[0], r_max=r[1])
    rest = sample_positions(jax.random.PRNGKey(77),
                            CellConfig(num_clients=K - 5))
    return jnp.concatenate([special, rest])


def run_scenario(name, near):
    K = 10
    pos = scenario_positions(jax.random.PRNGKey(5), K, near)
    world = build_world(K=K, pos_override=pos)
    spec = ProblemSpec(cell=world.cell, rho=0.05, num_rounds=world.rounds)
    schemes, avg = schemes_matched(world, spec)
    recs = []
    for s in schemes:
        res, secs = run_policy(world, s)
        e = res.energy_per_client
        fairness = float(e.max() / max(e[e > 0].min() if (e > 0).any()
                                       else 1.0, 1e-9))
        gini = float(np.abs(e[:, None] - e[None, :]).sum()
                     / (2 * K * max(e.sum(), 1e-9)))
        recs.append({"scheme": s.name,
                     "final_acc": float(res.test_acc[-1]),
                     "total_energy_j": float(e.sum()),
                     "per_client_energy": [float(x) for x in e],
                     "participation_per_client":
                         [float(x) for x in res.participation.sum(0)],
                     "energy_gini": gini, "max_min_ratio": fairness})
        row(f"{name}_{s.name}", secs / world.rounds * 1e6,
            f"acc={recs[-1]['final_acc']:.3f};"
            f"energy_j={recs[-1]['total_energy_j']:.2f};"
            f"gini={gini:.3f}")
    return {"avg_participants": avg, "schemes": recs}


def main() -> dict:
    enable_compile_cache()
    out = {"scenario1_near": run_scenario("fig8_s1", near=True),
           "scenario2_far": run_scenario("fig8_s2", near=False)}
    save_artifact("fig8_9_scenarios", out)
    return out


if __name__ == "__main__":
    main()

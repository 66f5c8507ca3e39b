"""Beyond-paper ablation: the Δ_k bound (each client must transmit at least
once within Δ_k rounds — paper §II-A) enforced vs pure-Bernoulli selection.

Theory (Lemma 1): bounding the max interval tightens the convergence bound;
with probabilistic selection alone, Δ_k is only bounded in expectation.
"""
from __future__ import annotations

import numpy as np

from repro.core import ProblemSpec
from repro.core.selection import ProposedOnline
from repro.launch.cache import enable_compile_cache

from .common import build_world, row, run_policy, save_artifact


def main() -> dict:
    enable_compile_cache()
    world = build_world(rounds=20, d=2)
    spec = ProblemSpec(cell=world.cell, rho=0.03, num_rounds=world.rounds)
    out = {}
    for name, stale, aging in (("pure_bernoulli", None, False),
                               ("delta_4", 4, False), ("delta_8", 8, False),
                               ("delta_8_soft_aging", 8, True)):
        res, secs = run_policy(world, ProposedOnline(spec),
                               max_staleness=stale, aging=aging)
        gaps = []
        for k in range(world.cell.num_clients):
            tx = np.where(res.participation[:, k] > 0)[0]
            gaps.append(int(np.diff(tx).max()) if len(tx) > 1
                        else world.rounds)
        out[name] = {"final_acc": float(res.test_acc[-1]),
                     "total_energy_j": float(res.energy_per_client.sum()),
                     "max_gap": int(max(gaps))}
        row(f"staleness_{name}", secs / world.rounds * 1e6,
            f"acc={out[name]['final_acc']:.3f};"
            f"energy_j={out[name]['total_energy_j']:.2f};"
            f"max_gap={out[name]['max_gap']}")
    save_artifact("bench_staleness", out)
    return out


if __name__ == "__main__":
    main()

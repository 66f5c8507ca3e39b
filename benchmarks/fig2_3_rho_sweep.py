"""Paper Fig. 2 & 3: accuracy / total energy vs the tradeoff coefficient ρ.

Claim under test: as ρ grows from ~0.01 to ~0.1 both participation and
accuracy rise (convergence-focused); beyond that, accuracy saturates or
degrades under non-IID drift while energy keeps climbing.
"""
from __future__ import annotations

import numpy as np

from repro.core import ProblemSpec

from .common import build_world, row, run_policy, save_artifact
from repro.core.selection import ProposedOnline
from repro.launch.cache import enable_compile_cache


def main() -> list[dict]:
    enable_compile_cache()
    # d=2 (strong heterogeneity) exposes the high-ρ drift the paper reports
    world = build_world(d=2, rounds=24)
    rhos = (0.01, 0.03, 0.1, 0.3, 0.9)
    out = []
    for rho in rhos:
        spec = ProblemSpec(cell=world.cell, rho=rho, lam=0.01,
                           num_rounds=world.rounds)
        res, secs = run_policy(world, ProposedOnline(spec))
        rec = {"rho": rho,
               "final_acc": float(res.test_acc[-1]),
               "total_energy_j": float(res.energy_per_client.sum()),
               "avg_participants": float(res.participation.sum()
                                         / world.rounds)}
        out.append(rec)
        row(f"fig2_rho_{rho}", secs / world.rounds * 1e6,
            f"acc={rec['final_acc']:.3f};energy_j={rec['total_energy_j']:.2f};"
            f"avg_k={rec['avg_participants']:.2f}")
    save_artifact("fig2_3_rho_sweep", {"rows": out})
    return out


if __name__ == "__main__":
    main()

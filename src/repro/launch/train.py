"""End-to-end training driver.

Two modes:

* paper mode (default): the paper's wireless async-FL experiment — MNIST-like
  data, non-IID shards, MLP, probabilistic client selection + bandwidth
  allocation, energy ledger, checkpointing.

    PYTHONPATH=src python -m repro.launch.train --scheme proposed \
        --rounds 30 --clients 10 --noniid-d 5 --rho 0.05

* arch mode: FL training of a (reduced) assigned architecture on synthetic
  token streams through the same probabilistic-selection round loop —
  the mega-arch path that the dry-run lowers at production shapes.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --reduced --rounds 10 --clients 4
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import configs
from ..checkpoint import save_checkpoint
from ..core import CellConfig, ProblemSpec
from ..core.channel import channel_gains, rate_nats, sample_positions
from ..core.selection import (AgeBasedScheme, GreedyScheme, ProposedOnline,
                              RandomScheme, realize)
from ..data import make_mnist_like, make_token_stream, shard_noniid
from ..fl import SimConfig, run_simulation
from ..fl.distributed import fl_train_step, init_dist_state
from ..models.small import init_mlp, mlp_accuracy, mlp_loss
from .cache import enable_compile_cache


def paper_mode(args) -> None:
    K = args.clients
    tr, te = make_mnist_like(jax.random.PRNGKey(args.seed),
                             n_train=args.train_examples, n_test=1000)
    clients = shard_noniid(jax.random.PRNGKey(args.seed + 1), tr, K,
                           d=args.noniid_d)
    cell = CellConfig(num_clients=K)
    spec = ProblemSpec(cell=cell, rho=args.rho, lam=args.lam,
                       num_rounds=args.rounds)
    pos = sample_positions(jax.random.PRNGKey(args.seed + 2), cell)
    h = channel_gains(jax.random.PRNGKey(args.seed + 3), pos, args.rounds).T
    policy = {
        "proposed": lambda: ProposedOnline(spec),
        "random": lambda: RandomScheme(0.1, K),
        "greedy": lambda: GreedyScheme(max(1, K // 10), K),
        "age": lambda: AgeBasedScheme(max(1, K // 10), K),
    }[args.scheme]()
    params = init_mlp(jax.random.PRNGKey(args.seed + 4))
    cfg = SimConfig(rounds=args.rounds, local_iters=args.local_iters,
                    batch_size=args.batch_size, lr=args.lr,
                    eval_every=max(args.rounds // 10, 1), seed=args.seed,
                    max_staleness=args.max_staleness)
    t0 = time.time()
    res = run_simulation(params, mlp_loss, mlp_accuracy, clients, te,
                         policy, h, cell, cfg)
    print(f"[train] scheme={args.scheme} rounds={args.rounds} "
          f"final_acc={res.test_acc[-1]:.4f} "
          f"total_energy_j={res.energy_per_client.sum():.2f} "
          f"({time.time() - t0:.1f}s)")
    if args.ckpt:
        save_checkpoint(args.ckpt, res.state.global_params,
                        {"rounds": args.rounds, "scheme": args.scheme,
                         "acc": float(res.test_acc[-1])})
        print(f"[train] checkpoint → {args.ckpt}.npz")


def arch_mode(args) -> None:
    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    K = args.clients
    spec_cell = CellConfig(num_clients=K)
    spec = ProblemSpec(cell=spec_cell, rho=args.rho, num_rounds=args.rounds)
    pos = sample_positions(jax.random.PRNGKey(args.seed), spec_cell)
    h = channel_gains(jax.random.PRNGKey(args.seed + 1), pos, args.rounds).T
    policy = ProposedOnline(spec)

    S, B = args.seq_len, args.per_client_batch
    ds = make_token_stream(jax.random.PRNGKey(args.seed + 2),
                           n_seqs=K * B * 4, vocab=cfg.vocab, seq_len=S)
    toks = ds.x.reshape(-1, K, B, S)
    state = init_dist_state(jax.random.PRNGKey(args.seed + 3), cfg, K)
    key = jax.random.PRNGKey(args.seed + 4)
    for t in range(args.rounds):
        dec = policy.decide(t, h[:, t])
        key, sub = jax.random.split(key)
        mask = realize(sub, dec)
        batch = {"tokens": toks[t % toks.shape[0]]}
        state, metrics = fl_train_step(state, cfg, batch, mask, args.lr)
        R = rate_nats(dec.w, h[:, t], spec_cell.tx_power_w,
                      spec_cell.bandwidth_hz, spec_cell.noise_w_per_hz)
        e = float(jnp.sum(jnp.asarray(mask) * spec_cell.tx_power_w
                          * spec_cell.model_size_nats / jnp.maximum(R, 1e-30)))
        print(f"[train] round {t}: loss={float(metrics['loss']):.4f} "
              f"participants={int(metrics['participants'])} energy_j={e:.3f}")
    if args.ckpt:
        save_checkpoint(args.ckpt, state.global_params,
                        {"arch": cfg.name, "rounds": args.rounds})
        print(f"[train] checkpoint → {args.ckpt}.npz")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="assigned architecture id")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--scheme", default="proposed",
                    choices=["proposed", "random", "greedy", "age"])
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--clients", type=int, default=10)
    ap.add_argument("--noniid-d", type=int, default=5)
    ap.add_argument("--rho", type=float, default=0.05)
    ap.add_argument("--lam", type=float, default=0.01)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--local-iters", type=int, default=5)
    ap.add_argument("--batch-size", type=int, default=10)
    ap.add_argument("--train-examples", type=int, default=5000)
    ap.add_argument("--max-staleness", type=int, default=None)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--per-client-batch", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    if args.arch:
        arch_mode(args)
    else:
        paper_mode(args)


if __name__ == "__main__":
    main()

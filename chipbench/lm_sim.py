"""Driver for ``lm_sim`` traffic: federated LoRA fine-tuning calls back to
back.

Set-up builds the world from the seed (``lm_world.py``: the frozen base,
the adapters, the clients' token sequences, positions and a pool of channel
draws) and the program's runner through ``repro.fl.make_runner`` with the
base as its ``frozen`` argument, and warms the runner until a call compiles
nothing.  The window calls ``runner(adapters, h_all, seed)`` back to back,
each call with a fresh seed and a fresh channel draw, until ``seconds`` have
passed (a traced run stops after the mix's ``trace_seconds``).  Afterwards
the first, the last and one drawn call are replayed, client by client, by
the plain reference (``lm_reference.py``).

Only the global adapters of those three calls stay on the device through
the window (ten clients' adapters a call would not fit beside the program).
After the window the program runs each of the three again on the same
inputs; the rerun must reproduce the window's answer bit for bit (masks,
energy, ``last_tx``, evaluations, global adapters), and its clients'
adapters are compared with the reference's.  A rerun that differs counts
as an infinite ``model_gap``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import sys
import time

import jax
import numpy as np

from . import check, lm_reference, lm_world as W
from .bench import memory_peak_bytes
from .compiles import CompileCounter
from .world import channel_pool, sub_seeds

#: the program's counters a window's metrics read (``None`` where the
#: program keeps no such counter)
COUNTERS = ("engine.train_tokens", "engine.upload_bytes")


def arch_config(cfg: dict):
    """The program's ``ArchConfig``: the registered Jamba2-3B with the
    configuration's widths and depth."""
    from repro.configs import REGISTRY

    s = W.Shape.of(cfg)
    if s.dt_rank != -(-s.d // 16):
        raise ValueError("the program's Mamba takes dt_rank = d_model / 16")
    return dataclasses.replace(
        REGISTRY["jamba2-3b"], name=cfg["name"], n_layers=s.layers,
        d_model=s.d, n_heads=s.heads, n_kv_heads=s.kv_heads,
        head_dim=s.head_dim, d_ff=s.ff, vocab=s.vocab, norm_eps=s.eps,
        tie_embeddings=cfg["tie_word_embeddings"], ssm_state=s.d_state,
        ssm_conv=s.d_conv, ssm_expand=cfg["mamba_expand"])


def program_params(adapters, base, scale: float):
    """The program's parameter tree (``repro.models.transformer``'s layout,
    one period stacked once) of world-layout ``base`` with ``adapters``
    merged in as LoRA weights."""
    from repro.models import lora

    one = lambda a: a[None]                  # noqa: E731  (n_repeats = 1)
    blocks = [lora.merge(jax.tree_util.tree_map(one, lp),
                         jax.tree_util.tree_map(one, la), scale)
              for lp, la in zip(base["layers"], adapters)]
    return {"embed": base["embed"], "final_norm": base["final_norm"],
            "blocks": blocks}


def model_fns(world: W.LMWorld, cfg: dict):
    """``(loss_fn, acc_fn)`` of the program over ``(adapters, x, y, base)``
    in the world's layout."""
    from repro.models import transformer

    arch = arch_config(cfg)
    if arch.n_repeats != 1:
        raise ValueError("the world's layout holds one period of layers")
    scale = world.shape.scale

    def loss_fn(adapters, x, y, base):
        return transformer.loss(program_params(adapters, base, scale), arch,
                                {"tokens": x, "labels": y})

    def acc_fn(adapters, x, y, base):
        return transformer.accuracy(program_params(adapters, base, scale),
                                    arch, {"tokens": x, "labels": y})

    return loss_fn, acc_fn


def make_runner(world: W.LMWorld, cfg: dict):
    """The system under test: the program's runner for this world."""
    from repro.core import CellConfig
    from repro.core.selection import RandomScheme
    from repro.data import Dataset
    from repro.fl import SimConfig, make_runner as program_runner

    K, V = cfg["num_clients"], world.shape.vocab
    clients = [Dataset(world.x[k], world.y[k], V) for k in range(K)]
    test = Dataset(world.x_test, world.y_test, V)
    cell = CellConfig(num_clients=K,
                      model_size_bits=world.cell.model_size_bits)
    policy = RandomScheme(cfg["policy"]["p_bar"], K)
    sim_cfg = SimConfig(rounds=cfg["rounds"], local_iters=cfg["local_iters"],
                        batch_size=cfg["batch_size"], lr=cfg["lr"],
                        eval_every=cfg["eval_every"],
                        eval_batch=cfg["eval_batch"],
                        seed=world.seeds["stream"], **cfg["engine"])
    loss_fn, acc_fn = model_fns(world, cfg)
    runner = program_runner(loss_fn, acc_fn, clients, test, policy, cell,
                            sim_cfg, frozen=world.base)
    return lambda params, h_all, seed: answer(runner(params, h_all,
                                                     seed=seed))


def answer(res) -> dict:
    """What one call answered, as the check reads it."""
    return {"masks": res.participation, "energy": res.energy_per_client,
            "last_tx": np.asarray(res.state.last_tx), "acc": res.test_acc,
            "loss": res.test_loss, "eval_rounds": res.eval_rounds,
            "params": res.state.global_params,
            "clients": res.state.client_params}


def same_answer(a: dict, b: dict) -> bool:
    """Whether two answers of one call agree bit for bit (clients aside)."""
    pairs = [(a[k], b[k]) for k in ("masks", "energy", "last_tx", "acc",
                                     "loss", "eval_rounds")]
    pairs += list(zip(jax.tree_util.tree_leaves(a["params"]),
                      jax.tree_util.tree_leaves(b["params"])))
    return all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in pairs)


#: a leaf's change is measured against at least this many float32 spacings
#: of its start: a change of fewer is stored in a few one-spacing steps,
#: and where the program's and the reference's exact changes differ by
#: their matmuls' rounding, the flips of those steps are as large as the
#: change itself
SPACINGS = 16


def leaf_gaps(got, want, start) -> list[tuple[str, float, float, float]]:
    """Per leaf: ``(path, ‖got − want‖, ‖want − start‖,
    ‖spacing(start)‖)``, the norms in float64 over float32 leaves."""
    rows = []
    flat = jax.tree_util.tree_flatten_with_path(start)[0]
    for (path, s), g, w in zip(flat, jax.tree_util.tree_leaves(got),
                               jax.tree_util.tree_leaves(want)):
        s32 = np.asarray(s, np.float32)
        g, w, s = (np.asarray(a, np.float64) for a in (g, w, s32))
        rows.append((jax.tree_util.keystr(path), float(np.linalg.norm(g - w)),
                     float(np.linalg.norm(w - s)),
                     float(np.linalg.norm(np.spacing(s32).astype(np.float64)))))
    return rows


def model_gap(got, want, start) -> float:
    """Worst leaf of ‖got − want‖ / max(‖want − start‖, ``SPACINGS`` ·
    ‖spacing(start)‖): each leaf's error relative to how far the reference
    moved it, or to float32's resolution of its start where it moved less
    (an A matrix in the round after B = 0).  A leaf left where it started
    while the reference moved it reads 1."""
    return max(err / max(moved, SPACINGS * spacing, 1e-30)
               for _, err, moved, spacing in leaf_gaps(got, want, start))


def numbers(got: dict, ref: dict, start) -> dict:
    """The compared numbers of one call; ``model_gap`` is the worst over
    the global adapters and every client's adapters."""
    row = check.sim_numbers(got, ref, start)
    row["model_gap"] = model_gap(got["params"], ref["params"], start)
    for k in range(np.shape(got["masks"])[1]):
        pick = lambda a: np.asarray(a[k])    # noqa: E731, B023
        row["model_gap"] = max(row["model_gap"], model_gap(
            jax.tree_util.tree_map(pick, got["clients"]),
            jax.tree_util.tree_map(pick, ref["clients"]), start))
    return row


def _counters() -> dict:
    from repro.obs.telemetry import get_telemetry

    c = get_telemetry().counters
    return {name: c.get(name) for name in COUNTERS}


def run(cell: dict, seed: int, seconds: float, traced,
        program=make_runner) -> dict:
    """One run of the cell: set-up, window, check.  ``traced`` is a context
    manager factory wrapped around the window (or ``None``)."""
    if program is make_runner:
        # fail before the world is built where the program has no LoRA
        # support (its make_runner takes no ``frozen``)
        from repro.models import lora  # noqa: F401
    cfg, mix = cell["config"], cell["traffic"]
    T = cfg["rounds"]
    if traced:
        seconds = min(seconds, mix["trace_seconds"])
    marks = [("start", time.perf_counter())]

    def mark(name):
        marks.append((name, time.perf_counter()))

    world = W.build_world(cfg, seed)
    jax.block_until_ready(world.base)
    mark("world")
    runner = program(world, cfg)
    mark("runner")
    pool_n = mix["channel_pool"]
    pool = np.asarray(channel_pool(
        jax.random.PRNGKey(world.seeds["channel"]), world.dist_m, pool_n, T))
    call_seeds = sub_seeds(world.seeds["calls"], pool_n + 4)
    mark("pool")
    counter = CompileCounter()
    for i in range(mix["max_warmup_calls"]):
        before = counter.count
        jax.block_until_ready(runner(world.adapters, pool[-1 - i],
                                     call_seeds[-1 - i])["params"])
        mark(f"warm{i}")
        if i and counter.count == before:
            break
    print("setup " + " ".join(
        f"{name}_s={b - a:.3f}"
        for (_, a), (name, b) in zip(marks, marks[1:])), file=sys.stderr)
    calls, spans = [], []
    # the call checked besides the first and the last: drawn from the seed,
    # uniform over the window's calls (a reservoir of one), so that only
    # those three keep their adapters on the device
    rng = np.random.default_rng(world.seeds["order"])
    drawn, held = 0, {0}
    with traced() if traced else contextlib.nullcontext():
        with jax.profiler.TraceAnnotation("bench.window"):
            counter.start()
            c_start = _counters()
            t0 = time.perf_counter()
            while True:
                i = len(calls)
                c0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.call"):
                    got = runner(world.adapters, pool[i % pool_n],
                                 call_seeds[i % pool_n])
                    jax.block_until_ready(got["params"])
                c1 = time.perf_counter()
                got.pop("clients")
                calls.append(got)
                spans.append((c0, c1))
                if rng.integers(i + 1) == 0:
                    drawn = i
                for j in held - {0, drawn, i}:
                    calls[j]["params"] = None
                held = {0, drawn, i}
                if c1 - t0 >= seconds:
                    break
            counter.stop()
            c_end = _counters()
    setup_end = t0
    if len(calls) > pool_n:
        print(f"warning: {len(calls)} calls outran the pool of {pool_n} "
              "channel draws and seeds; raise the mix's channel_pool",
              file=sys.stderr)
    window_s = spans[-1][1] - spans[0][0]
    n = len(calls)
    counts = {
        "calls": n, "rounds": n * T,
        "uploads_per_round": np.concatenate(
            [np.sum(c["masks"], axis=1) for c in calls]),
        "eval_tokens": sum(len(c["eval_rounds"]) for c in calls)
        * cfg["eval_batch"] * int(np.shape(world.x_test)[1]),
        "compiles_in_window": counter.window_count,
        "call_ms_p50": 1e3 * float(np.median(np.diff(spans, axis=1))),
        "call_ms_max": 1e3 * float(np.max(np.diff(spans, axis=1))),
    }
    for name in COUNTERS:
        a, b = c_start[name], c_end[name]
        counts[name] = None if a is None or b is None else b - a
    out = {"setup_end": setup_end, "window_s": window_s,
           "e2e": {"rounds_per_s": n * T / window_s}, "counts": counts,
           "memory_peak_bytes": memory_peak_bytes()}
    picks = sorted(held)
    again = {}
    for i in picks:
        got = runner(world.adapters, pool[i % pool_n], call_seeds[i % pool_n])
        clients = got.pop("clients")
        if not same_answer(got, calls[i]):
            print(f"warning: call {i} run again differs from the window's",
                  file=sys.stderr)
            clients = None
        again[i] = None if clients is None else jax.tree_util.tree_map(
            np.asarray, clients)
    del runner, got, clients
    start = jax.tree_util.tree_map(np.asarray, world.adapters)
    rows = []
    for i in picks:
        ref = lm_reference.sim(world, cfg, pool[i % pool_n],
                               call_seeds[i % pool_n], world.seeds["stream"],
                               masks=calls[i]["masks"])
        if again[i] is None:
            rows.append(dict(numbers(dict(calls[i], clients=ref["clients"]),
                                     ref, start), model_gap=float("inf")))
        else:
            rows.append(numbers(dict(calls[i], clients=again[i]), ref, start))
        calls[i]["params"] = again[i] = None
    out["numbers"] = check.worst(rows)
    out["checked_calls"] = len(picks)
    return out


def control(dtype):
    """A program factory that puts the reference, at ``dtype``, in the
    program's place (the control of ``correct``)."""
    def build(world, cfg):
        def runner(params, h_all, seed):
            return lm_reference.sim(world, cfg, h_all, seed,
                                    world.seeds["stream"], dtype=dtype)
        return runner
    return build

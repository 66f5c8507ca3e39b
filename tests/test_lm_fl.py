"""Federated LoRA fine-tuning of a Jamba hybrid through ``make_runner``,
against the plain reference (``chipbench/lm_reference.py``), at a tiny size
of the benchmark cell's pattern: one period of 14 layers (13 Mamba-1, NoPE
multi-query attention at index 7), d=64, 2 query heads / 1 KV head, d_state
16, vocabulary 256, S=32, K=3, T=2, on seeded random weights with B ≠ 0 in
the adapters.

Tolerances, each on float32 throughout (CPU, exact f32 matmuls):

* logits, relative L2 ≤ 1e-5: the two forwards differ only in summation
  order (chunked against direct attention, associative against sequential
  scan), ~1e-7 per op over 14 layers;
* energy ≤ 1e-6 relative: the same eq.-5 formula in float32, one rounding;
* adapters ≤ 1e-4 of how far each leaf moved (global and every client):
  the round's change is stored as ``start + Δ`` in float32, so its error is
  at least float32's resolution of ``start`` over ‖Δ‖ (~1e-5 at lr 0.1,
  ~0.3 at the cell's lr 0.01, where the tiny model's adapters move ~1e-7);
  the test trains at lr 0.1 for that reason (at 0.3 a first-layer leaf
  moves by a quarter of its norm and the two sides part by ~3e-4);
* loss ≤ 1e-5 relative: the forward's rounding, ~1e-7.
"""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import bench, check, lm_reference, lm_sim, lm_world  # noqa: E402
from chipbench.world import channel_pool  # noqa: E402

WORKLOAD = "jamba2_3b_fedlora.lm_sim"
SEED = 4_000_000_123


def tiny_config(lr: float = 0.1) -> dict:
    cfg = bench.find_cell(bench.load_spec(), WORKLOAD)["config"]
    cfg.update(hidden_size=64, num_attention_heads=2, intermediate_size=128,
               mamba_dt_rank=4, vocab_size=256, num_clients=3, eval_batch=2,
               lr=lr)
    cfg["lora"].update(r=4, alpha=8)
    cfg["data"].update(sequences_per_client=8, tokens_per_sequence=33,
                       successors=8, test_sequences=2)
    return cfg


@pytest.fixture(scope="module")
def world():
    cfg = tiny_config()
    return cfg, lm_world.build_world(cfg, SEED, b_std=0.1)


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def test_system_logits_match_the_reference(world):
    from repro.models import transformer

    cfg, w = world
    arch = lm_sim.arch_config(cfg)
    params = lm_sim.program_params(w.adapters, w.base, w.shape.scale)
    tokens = w.x[0, :2]
    got, _ = transformer.forward(params, arch, tokens)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([lm_reference.logits(w.base, w.adapters, t, w.shape)
                          for t in tokens])
    assert rel(got, want) <= 1e-5


def _layer_inputs(w, i):
    x = jax.random.normal(jax.random.PRNGKey(i), (1, 32, w.shape.d))
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a.astype(jnp.float32), t)
    return x, f32(w.base["layers"][i]["mixer"]), w.adapters[i]["mixer"]


def test_nope_attention_matches_the_reference(world):
    from repro.models import attention, lora

    cfg, w = world
    arch = lm_sim.arch_config(cfg)
    x, p, ad = _layer_inputs(w, 7)
    p = lora.merge(p, ad, w.shape.scale)
    pos = jnp.arange(32)[None]
    got = attention.attn_forward(p, arch, x, pos)
    with jax.default_matmul_precision("highest"):
        want = lm_reference.attention(p_plain(w, 7), ad, x[0], w.shape)
    assert rel(got[0], want) <= 1e-5
    # the same layer with rotary positions is another function
    roped = attention.attn_forward(p, dataclasses.replace(arch, rope=True),
                                   x, pos)
    assert rel(roped[0], want) > 1e-2


def p_plain(w, i):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                  w.base["layers"][i]["mixer"])


def test_mamba_dt_b_c_norms_match_the_reference(world):
    from repro.models import lora, mamba

    cfg, w = world
    arch = lm_sim.arch_config(cfg)
    x, p, ad = _layer_inputs(w, 0)
    got = mamba.mamba_forward(lora.merge(p, ad, w.shape.scale), arch, x)
    with jax.default_matmul_precision("highest"):
        want = lm_reference.mamba(p_plain(w, 0), ad, x[0], w.shape)
    assert rel(got[0], want) <= 1e-5
    # each norm weight reaches the output, and the norms are not optional
    for name in ("dt_norm", "b_norm", "c_norm"):
        q = dict(p, **{name: p[name] * 2.0})
        moved = mamba.mamba_forward(lora.merge(q, ad, w.shape.scale), arch, x)
        assert rel(moved[0], want) > 1e-3, name
    plain = {k: v for k, v in p.items()
             if k not in ("dt_norm", "b_norm", "c_norm")}
    unnormed = mamba.mamba_forward(
        lora.merge(plain, ad, w.shape.scale),
        dataclasses.replace(arch, ssm_inner_norm=False), x)
    assert rel(unnormed[0], want) > 1e-2


def test_one_runner_call_matches_the_reference_replay(world):
    cfg, w = world
    runner = lm_sim.make_runner(w, cfg)
    h = np.asarray(channel_pool(jax.random.PRNGKey(1), w.dist_m, 1,
                                cfg["rounds"]))[0]
    got = runner(w.adapters, h, 77)
    ref = lm_reference.sim(w, cfg, h, 77, w.seeds["stream"],
                           masks=got["masks"])
    masks = np.asarray(got["masks"]) > 0
    assert masks.any()                   # the seed makes uploads
    assert np.array_equal(masks, ref["u"] < ref["p"])
    assert np.array_equal(got["last_tx"], ref["last_tx"])
    np.testing.assert_array_equal(got["eval_rounds"], ref["eval_rounds"])
    start = jax.tree_util.tree_map(np.asarray, w.adapters)
    row = lm_sim.numbers(got, ref, start)
    assert row["decision_mismatch"] == row["last_tx_mismatch"] == 0
    assert row["energy_gap"] <= 1e-6
    assert row["loss_gap"] <= 1e-5
    assert row["model_gap"] <= 1e-4      # global and every client's
    # and leaf by leaf, each leaf's error over how far it moved
    assert check.model_gap(got["params"], ref["params"], start) <= 1e-4
    for k in range(cfg["num_clients"]):
        pick = lambda a: np.asarray(a[k])    # noqa: E731, B023
        assert check.model_gap(jax.tree_util.tree_map(pick, got["clients"]),
                               jax.tree_util.tree_map(pick, ref["clients"]),
                               start) <= 1e-4, k


def test_the_bfloat16_control_fails_the_cells_limits(world):
    """The reference in bfloat16 in the program's place, against the
    float32 reference on its own decisions, as the chip's control runs."""
    cfg, w = world
    cell = bench.find_cell(bench.load_spec(), WORKLOAD)
    h = np.asarray(channel_pool(jax.random.PRNGKey(3), w.dist_m, 1,
                                cfg["rounds"]))[0]
    got = lm_reference.sim(w, cfg, h, 11, w.seeds["stream"],
                           dtype=jnp.bfloat16)
    ref = lm_reference.sim(w, cfg, h, 11, w.seeds["stream"],
                           masks=got["masks"])
    assert np.asarray(got["masks"]).any()
    row = lm_sim.numbers(got, ref, jax.tree_util.tree_map(np.asarray,
                                                          w.adapters))
    ok, table = check.judge(row, cell["limits"])
    assert not ok
    assert row["energy_gap"] > cell["limits"]["energy_gap"]
    assert row["model_gap"] > cell["limits"]["model_gap"]


def _leaf(x):
    return {"a": np.asarray(x, np.float32)}


def test_model_gap_reads_a_leaf_against_its_change_or_its_resolution():
    a = np.linspace(0.01, 0.03, 64, dtype=np.float32)
    b = np.zeros(64, np.float32)
    moved = a + np.float32(1e-3)
    # a leaf left where it started while the reference moved it reads 1
    assert lm_sim.model_gap(_leaf(a), _leaf(moved), _leaf(a)) == \
        pytest.approx(1.0)
    # a B leaf from 0: the error over the change
    assert lm_sim.model_gap(_leaf(b + 1.1e-3), _leaf(b + 1e-3), _leaf(b)) \
        == pytest.approx(0.1, rel=1e-3)
    # a change of a few float32 spacings: one-spacing flips in a quarter
    # of the elements read under 1 / SPACINGS, where the change alone
    # would read them as large as itself
    step = np.spacing(a)
    want = a + 2 * step
    got = want.copy()
    got[::4] += step[::4]
    gap = lm_sim.model_gap(_leaf(got), _leaf(want), _leaf(a))
    assert gap <= 1 / lm_sim.SPACINGS
    assert check.model_gap(_leaf(got), _leaf(want), _leaf(a)) > 0.2
    # the same error on a leaf that moved by many spacings is relative
    far = a + 100 * lm_sim.SPACINGS * step
    assert lm_sim.model_gap(_leaf(far + (got - want)), _leaf(far),
                            _leaf(a)) < gap / 10


def test_the_evaluation_runs_one_forward_for_loss_and_accuracy(world):
    """The engine evaluates ``loss_fn`` and ``acc_fn`` on the same batch;
    XLA merges their forwards when the two are the same computation."""
    cfg, w = world
    loss_fn, acc_fn = lm_sim.model_fns(w, cfg)
    x, y = w.x_test, w.y_test

    def flops(f):
        cost = jax.jit(f).lower(w.adapters, w.base).compile().cost_analysis()
        return (cost[0] if isinstance(cost, list) else cost)["flops"]

    one = flops(lambda a, b: loss_fn(a, x, y, b))
    both = flops(lambda a, b: (loss_fn(a, x, y, b), acc_fn(a, x, y, b)))
    assert both < 1.1 * one


def test_adapters_alone_are_trained_and_counted(world):
    from repro.obs.telemetry import get_telemetry

    cfg, w = world
    tel = get_telemetry()
    runner = lm_sim.make_runner(w, cfg)
    h = np.asarray(channel_pool(jax.random.PRNGKey(2), w.dist_m, 1,
                                cfg["rounds"]))[0]
    before = {k: tel.counters.get(k, 0) for k in lm_sim.COUNTERS}
    got = runner(w.adapters, h, 5)
    d = lm_world.num_adapter_params(cfg)
    assert jax.tree_util.tree_structure(got["params"]) == \
        jax.tree_util.tree_structure(w.adapters)
    tokens = (cfg["num_clients"] * cfg["rounds"] * cfg["batch_size"]
              * (cfg["data"]["tokens_per_sequence"] - 1))
    assert tel.counters["engine.train_tokens"] - before[
        "engine.train_tokens"] == tokens
    assert tel.counters["engine.upload_bytes"] - before[
        "engine.upload_bytes"] == int(np.sum(got["masks"])) * d * 4


def test_the_cell_loads_by_name_and_counts_its_model():
    spec = bench.load_spec()
    cell = bench.find_cell(spec, WORKLOAD)
    cfg = cell["config"]
    assert cell["traffic"]["kind"] == "lm_sim"
    assert bench.driver("lm_sim") is lm_sim
    assert [m["name"] for m in cell["end_to_end"]] == ["rounds_per_s",
                                                       "setup_s"]
    names = {m["name"] for m in cell["per_layer"]}
    assert {"mfu.lm_sim", "agg_roofline.lm_sim", "device_idle.lm_sim",
            "compiles_in_window.lm_sim", "d2h_per_call.lm_sim"} <= names
    for m in cell["per_layer"]:
        assert callable(bench.load_reader(m["name"]).read)
    # acc_gap has no control reading above the program's (PERF.md §4)
    assert set(cell["limits"]) == {"decision_mismatch", "last_tx_mismatch",
                                   "energy_gap", "model_gap", "loss_gap"}
    # the published widths, one period of the pattern
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["mamba_d_state"], cfg["mamba_dt_rank"],
            cfg["vocab_size"]) == (2560, 8192, 20, 1, 16, 160, 65536)
    s = lm_world.Shape.of(cfg)
    assert [i for i in range(s.layers) if s.is_attn(i)] == [7]
    d = lm_world.num_adapter_params(cfg)
    assert d == 23_470_080
    assert bench.load_reader("agg_roofline.lm_sim").adapter_params(cfg) == d
    mfu = bench.load_reader("mfu.lm_sim")
    assert mfu.p_lora(cfg) == d
    from repro import configs
    from repro.fl.distributed import param_count
    arch = lm_sim.arch_config(cfg)
    assert arch.layer_plan() == configs.get("jamba2-3b").layer_plan()
    # the base's matrix weights a token meets: every parameter but the
    # norms, the conv, the dt bias, A_log and D
    other = 13 * (4 * 5120 + 5120 + 5120 + 5120 * 16 + 5120 + 160 + 32)
    other += 14 * 2 * 2560 + 2560
    assert mfu.p_mm(cfg) == param_count(arch) - other


def test_the_reader_reads_nothing_without_the_program_counters():
    ctx = {"counts": {"engine.train_tokens": None, "rounds": 2,
                      "engine.upload_bytes": None, "eval_tokens": 0},
           "window_s": 1.0, "trace": None, "peaks": {},
           "config": bench.find_cell(bench.load_spec(), WORKLOAD)["config"]}
    assert bench.load_reader("mfu.lm_sim").read(ctx) is None
    assert bench.load_reader("agg_roofline.lm_sim").read(ctx) is None


@pytest.mark.parametrize("which", ["stream", "sparse"])
def test_runners_without_frozen_support_refuse_it(which):
    from repro.core import CellConfig
    from repro.core.selection import RandomScheme
    from repro.data import Dataset
    from repro.fl import SimConfig, make_runner
    from repro.models.small import mlp_accuracy, mlp_loss

    K = 4
    clients = [Dataset(np.zeros((8, 3), np.float32),
                       np.zeros((8,), np.int32), 2) for _ in range(K)]
    kw = ({"data_path": "stream"} if which == "stream" else {})
    cfg = SimConfig(rounds=2, local_iters=1, batch_size=2,
                    **({"local_mode": "participants",
                        "participation": "sparse", "data_path": "device",
                        "data_stream": "client"} if which == "sparse"
                       else {}))
    with pytest.raises(ValueError, match="frozen"):
        make_runner(mlp_loss, mlp_accuracy, clients, clients[0],
                    RandomScheme(0.5, K), CellConfig(num_clients=K), cfg,
                    frozen={"w": np.zeros(3)}, **kw)


def test_jamba2_3b_arch_is_one_attention_layer_in_fourteen():
    from repro import configs
    from repro.fl.distributed import param_count

    cfg = configs.get("jamba2-3b")
    assert [i for i, m in enumerate(cfg.mixer_pattern) if m == "attn"] == [7]
    assert (cfg.n_layers, cfg.d_model, cfg.hd, cfg.n_kv_heads, cfg.d_ff,
            cfg.vocab) == (28, 2560, 128, 1, 8192, 65536)
    assert not cfg.rope and cfg.ssm_inner_norm and cfg.tie_embeddings
    assert 2.9e9 < param_count(cfg) < 3.1e9
    half = dataclasses.replace(cfg, n_layers=14)
    assert 1.59e9 < param_count(half) < 1.61e9


def test_config_file_keeps_every_published_number():
    """The cell's configuration holds every key of the published config
    with its value, except the one ``reduced`` names."""
    cfg = json.loads((ROOT / "chipbench" / "configs"
                      / "jamba2_3b_fedlora.json").read_text())
    published = {"attn_layer_offset": 7, "attn_layer_period": 14,
                 "expert_layer_offset": 1, "expert_layer_period": 2,
                 "hidden_act": "silu", "hidden_size": 2560,
                 "intermediate_size": 8192, "mamba_conv_bias": True,
                 "mamba_d_conv": 4, "mamba_d_state": 16,
                 "mamba_dt_rank": 160, "mamba_expand": 2,
                 "mamba_proj_bias": False,
                 "max_position_embeddings": 262144, "model_type": "jamba",
                 "num_attention_heads": 20, "num_experts": 1,
                 "num_experts_per_tok": 1, "num_hidden_layers": 28,
                 "num_key_value_heads": 1, "num_logits_to_keep": 1,
                 "rms_norm_eps": 1e-06, "sliding_window": None,
                 "tie_word_embeddings": True, "use_mamba_kernels": True,
                 "vocab_size": 65536}
    changed = {k for k, v in published.items() if cfg.get(k, "absent") != v}
    assert changed == {"num_hidden_layers"} == set(cfg["reduced"])
    spec = bench.load_spec()
    entry = next(c for c in spec["configs"]
                 if c["name"] == "jamba2_3b_fedlora")
    assert entry["reduced"] == ["num_hidden_layers"]

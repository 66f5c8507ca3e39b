"""The dense runner's host boundary: its ``SimResult`` is bit for bit the
scan program's raw outputs read back array by array, and it starts no device
work on the host before the launch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.selection import RandomScheme, as_policy_fn
from repro.fl import FaultConfig, SimConfig, make_runner
from repro.fl.engine import (_to_result, build_scan_sim, resolve_data_path,
                             stack_round_batches)
from repro.data.device import data_stream_key, from_client_datasets
from repro.models.small import mlp_accuracy, mlp_loss
from repro.obs import MetricsSpec
from repro.obs.taps import metrics_active
from repro.optim import sgd

from test_engine_parity import tiny_world

K, T = 5, 8
CONFIGS = {"clean": {}, "faulty": {"faults": FaultConfig(p_loss=0.2)},
           "tapped": {"metrics": MetricsSpec()}}
SEEDS = (0, 5, 2 ** 31 - 1, 3_913_000_201)


def _world(name, data_path=None):
    clients, te, cell, h, params = tiny_world(K=K, rounds=T, dim=32)
    cfg = SimConfig(rounds=T, local_iters=2, batch_size=4, eval_every=2,
                    **CONFIGS[name])
    policy = RandomScheme(p_bar=0.4, num_clients=K)
    runner = make_runner(mlp_loss, mlp_accuracy, clients, te, policy, cell,
                         cfg, data_path=data_path)
    return runner, (clients, te, cell, h, params, cfg, policy)


def _raw_result(world, seed, path):
    """``_to_result`` over ``build_scan_sim``'s outputs, inputs made the
    way the runner made them before its program took host values."""
    clients, te, cell, h, params, cfg, policy = world
    sim = jax.jit(build_scan_sim(mlp_loss, mlp_accuracy, sgd(cfg.lr), cfg,
                                 cell, K, as_policy_fn(policy),
                                 data_mode=path))
    data = ((from_client_datasets(clients), data_stream_key(cfg.seed))
            if path == "device" else stack_round_batches(clients, cfg))
    out = sim(params, *data, jnp.swapaxes(h, 0, 1), jax.random.PRNGKey(seed),
              te.x[: cfg.eval_batch], te.y[: cfg.eval_batch])
    tapped = metrics_active(cfg.metrics, cfg.guards)
    return _to_result(out[0], out[1], out[2], cfg,
                      mstate=out[3] if tapped else None)


def _same_bits(a, b, where):
    if a is None or b is None:
        assert a is None and b is None, where
        return
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape, where
    assert np.array_equal(np.ascontiguousarray(a).view(np.uint8),
                          np.ascontiguousarray(b).view(np.uint8)), where


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", list(CONFIGS))
def test_runner_result_is_bit_identical_to_the_raw_readback(name, seed):
    runner, world = _world(name)
    clients, _, _, h, params, cfg, _ = world
    got = runner(params, np.asarray(h), seed)
    want = _raw_result(world, seed, resolve_data_path(clients, cfg))
    for field in got._fields:
        g, w = getattr(got, field), getattr(want, field)
        if field in ("state", "metrics") and (g is not None
                                               or w is not None):
            gl, gt = jax.tree_util.tree_flatten(g)
            wl, wt = jax.tree_util.tree_flatten(w)
            assert gt == wt, field
            for i, (x, y) in enumerate(zip(gl, wl)):
                _same_bits(x, y, f"{field}[{i}]")
        else:
            _same_bits(g, w, field)


@pytest.mark.parametrize("path", ["device", "prestack"])
def test_call_args_start_no_device_work(path):
    """The host path hands the program host values: it makes no
    ``jax.Array`` (no eager launch) and no host→device transfer."""
    runner, world = _world("clean", data_path=path)
    params, h = world[4], np.asarray(world[3])
    runner(params, h, 7)                     # build and compile first
    before = {id(a) for a in jax.live_arrays()}
    with jax.transfer_guard("disallow"):
        args = runner.call_args(params, h, 3_913_000_201)
    assert not [a for a in jax.live_arrays() if id(a) not in before]
    assert args[2] is h
    assert isinstance(args[3], np.uint32) and int(args[3]) == 3_913_000_201


def test_record_round_trips_every_bit():
    from repro.fl.engine import _pack, _unpack
    f = np.array([0.0, -0.0, np.inf, -np.inf, 1e-45, 3.4e38], np.float32)
    nan = np.array([0x7FC00001, 0xFFC00000], np.uint32).view(np.float32)
    tree = {"f": np.concatenate([f, nan]).reshape(2, 4),
            "i": np.array([-2 ** 31, -1, 0, 2 ** 31 - 1], np.int32),
            "u": np.array([0, 2 ** 32 - 1], np.uint32),
            "b": np.array([[True, False, True]]), "none": None}
    rec = jax.jit(_pack)(tree)
    assert rec.words.dtype == jnp.uint32 and rec.words.shape == (17,)
    back = _unpack(np.asarray(rec.words), rec.layout)
    assert back["none"] is None
    for k in ("f", "i", "u", "b"):
        _same_bits(back[k], tree[k], k)

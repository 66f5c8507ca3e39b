"""Model-zoo correctness: causality, prefill↔decode parity, GQA/MoE/SSM
invariants across every architecture family."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import transformer as T
from repro.models import attention, moe
from repro.configs.base import ArchConfig, MoEConfig

ALL_ARCHS = configs.names()


def tiny(name, **kw):
    return configs.get(name).reduced(**kw)


def make_batch(cfg, key, B=2, S=24):
    if cfg.embeds_input:
        return {"embeds": jax.random.normal(key, (B, S, cfg.d_model),
                                            jnp.float32),
                "labels": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    return {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab)}


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_loss_finite_and_grad_flows(name):
    cfg = tiny(name)
    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg)
    batch = make_batch(cfg, key)
    l, g = jax.value_and_grad(lambda p: T.loss(p, cfg, batch))(params)
    assert np.isfinite(float(l))
    leaves = jax.tree_util.tree_leaves(g)
    gnorm = sum(float(jnp.sum(x.astype(jnp.float32) ** 2)) for x in leaves)
    assert np.isfinite(gnorm) and gnorm > 0
    # embedding gradient must flow for token models
    if not cfg.embeds_input:
        assert float(jnp.abs(g["embed"]).max()) > 0


@pytest.mark.parametrize("S", [24, 257])
def test_loss_and_accuracy_match_the_full_logits(S, name="llama3.2-1b"):
    """``loss`` and ``accuracy`` unembed 128 positions at a time (S − 1 =
    256: two chunks; 23: one); they equal the cross-entropy and top-1 hits
    of ``forward``'s full logits, in value and gradient, and given
    ``labels`` they read the same targets without the shift."""
    cfg = tiny(name)
    key = jax.random.PRNGKey(3)
    params = T.init_params(key, cfg)
    tokens = jax.random.randint(key, (2, S), 0, cfg.vocab)

    def full(p):
        logits, aux = T.forward(p, cfg, tokens)
        lg, tgt = logits[:, :-1], tokens[:, 1:]
        pick = jnp.take_along_axis(lg, tgt[..., None], -1)[..., 0]
        ce = jnp.mean(jax.scipy.special.logsumexp(lg, -1) - pick)
        w = cfg.moe.aux_loss_weight if cfg.moe is not None else 0.0
        return ce + w * aux, jnp.mean(jnp.argmax(lg, -1) == tgt)

    (want, want_acc), want_g = jax.value_and_grad(full, has_aux=True)(params)
    got, got_g = jax.value_and_grad(
        lambda p: T.loss(p, cfg, {"tokens": tokens}))(params)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    assert float(T.accuracy(params, cfg, {"tokens": tokens})) == \
        pytest.approx(float(want_acc))
    labeled = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
    np.testing.assert_allclose(T.loss(params, cfg, labeled), want, rtol=1e-5)


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_causality(name):
    """Changing future inputs must not affect past logits."""
    cfg = tiny(name)
    key = jax.random.PRNGKey(1)
    params = T.init_params(key, cfg)
    B, S, cut = 1, 16, 8
    if cfg.embeds_input:
        e1 = jax.random.normal(key, (B, S, cfg.d_model), jnp.float32)
        e2 = e1.at[:, cut:].set(jax.random.normal(jax.random.PRNGKey(9),
                                                  (B, S - cut, cfg.d_model)))
        l1, _ = T.forward(params, cfg, embeds=e1)
        l2, _ = T.forward(params, cfg, embeds=e2)
    else:
        t1 = jax.random.randint(key, (B, S), 0, cfg.vocab)
        t2 = t1.at[:, cut:].set((t1[:, cut:] + 1) % cfg.vocab)
        l1, _ = T.forward(params, cfg, tokens=t1)
        l2, _ = T.forward(params, cfg, tokens=t2)
    np.testing.assert_allclose(np.asarray(l1[:, :cut]),
                               np.asarray(l2[:, :cut]), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_prefill_decode_parity(name):
    """prefill(t[:k]) then decode steps must reproduce forward() logits.

    Exact for attention; recurrent forms (mamba / mlstm / slstm) use different
    but mathematically equivalent stabilized computations — loose tolerance.
    """
    cfg = tiny(name)
    if cfg.embeds_input:
        cfg = dataclasses.replace(cfg, embeds_input=False)  # decode is tokens
    if cfg.moe is not None:
        # capacity dropping differs between a 12-token forward and 1-token
        # decode batches by design; test routing parity drop-free
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    key = jax.random.PRNGKey(2)
    params = T.init_params(key, cfg)
    B, S, k = 1, 12, 8
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab)
    full, _ = T.forward(params, cfg, tokens=toks)

    lg, caches = T.prefill(params, cfg, tokens=toks[:, :k], capacity=S)
    np.testing.assert_allclose(np.asarray(lg[:, 0]), np.asarray(full[:, k - 1]),
                               atol=2e-2, rtol=2e-2)
    for i in range(k, S):
        lg, caches = T.decode_step(params, cfg, toks[:, i:i + 1], caches)
        np.testing.assert_allclose(np.asarray(lg[:, 0]),
                                   np.asarray(full[:, i]),
                                   atol=5e-2, rtol=5e-2)


def test_gqa_equals_mha_when_kv_equals_heads():
    cfg = tiny("llama3.2-1b", n_heads=4, n_kv_heads=4)
    key = jax.random.PRNGKey(3)
    p = attention.init_attn(key, cfg, jnp.float32)
    x = jax.random.normal(key, (2, 10, cfg.d_model), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(10)[None], (2, 10))
    y = attention.attn_forward(p, cfg, x, pos)
    assert y.shape == x.shape and np.isfinite(np.asarray(y)).all()


def test_sliding_window_equals_full_when_window_large():
    base = tiny("llama3.2-1b")
    key = jax.random.PRNGKey(4)
    params = T.init_params(key, base)
    toks = jax.random.randint(key, (1, 16), 0, base.vocab)
    full, _ = T.forward(params, base, tokens=toks)
    win = dataclasses.replace(base, sliding_window=64)
    lw, _ = T.forward(params, win, tokens=toks)
    np.testing.assert_allclose(np.asarray(full), np.asarray(lw), atol=1e-5)


def test_sliding_window_restricts_receptive_field():
    cfg = dataclasses.replace(tiny("llama3.2-1b"), sliding_window=4)
    key = jax.random.PRNGKey(5)
    params = T.init_params(key, cfg)
    t1 = jax.random.randint(key, (1, 16), 0, cfg.vocab)
    t2 = t1.at[:, 0].set((t1[:, 0] + 1) % cfg.vocab)
    l1, _ = T.forward(params, cfg, tokens=t1)
    l2, _ = T.forward(params, cfg, tokens=t2)
    # with a window of 4 and 2 layers, token 0 cannot influence position 15
    np.testing.assert_allclose(np.asarray(l1[:, -1]), np.asarray(l2[:, -1]),
                               atol=1e-4)


def test_moe_dispatch_conservation():
    """With ample capacity every token is routed to exactly top_k slots and
    combine weights sum to 1."""
    cfg = dataclasses.replace(
        tiny("qwen3-moe-30b-a3b"),
        moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=32,
                      capacity_factor=4.0))
    key = jax.random.PRNGKey(6)
    p = moe.init_moe(key, cfg, jnp.float32)
    x = jax.random.normal(key, (2, 8, cfg.d_model), jnp.float32)

    # reproduce internals to check dispatch mass
    m = cfg.moe
    T_, d = 16, cfg.d_model
    xt = x.reshape(T_, d)
    gates = jax.nn.softmax(xt @ p["router"], axis=-1)
    topv, topi = jax.lax.top_k(gates, m.top_k)
    out, aux = moe.moe_forward(p, cfg, x)
    assert out.shape == x.shape
    assert float(aux) > 0
    # aux loss for a balanced router ≈ 1
    assert 0.5 < float(aux) < 4.0


def test_moe_single_expert_equals_dense():
    """E=1, top1: MoE must equal the dense SwiGLU with that expert's weights."""
    from repro.models.layers import swiglu
    cfg = dataclasses.replace(
        tiny("qwen3-moe-30b-a3b"),
        moe=MoEConfig(num_experts=1, top_k=1, d_ff_expert=32,
                      capacity_factor=8.0))
    key = jax.random.PRNGKey(7)
    p = moe.init_moe(key, cfg, jnp.float32)
    x = jax.random.normal(key, (1, 6, cfg.d_model), jnp.float32)
    out, _ = moe.moe_forward(p, cfg, x)
    ref = swiglu(x, p["w1"][0], p["w3"][0], p["w2"][0])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_moe_capacity_drops_overflow():
    """Tiny capacity ⇒ some tokens dropped (output zero for those slots) but
    no NaNs and shape preserved."""
    cfg = dataclasses.replace(
        tiny("qwen3-moe-30b-a3b"),
        moe=MoEConfig(num_experts=2, top_k=1, d_ff_expert=16,
                      capacity_factor=0.1))
    key = jax.random.PRNGKey(8)
    p = moe.init_moe(key, cfg, jnp.float32)
    x = jax.random.normal(key, (2, 32, cfg.d_model), jnp.float32)
    out, _ = moe.moe_forward(p, cfg, x)
    assert np.isfinite(np.asarray(out)).all()


def test_mamba_decode_matches_scan():
    from repro.models import mamba as M
    cfg = tiny("jamba-1.5-large-398b")
    key = jax.random.PRNGKey(9)
    p = M.init_mamba(key, cfg, jnp.float32)
    x = jax.random.normal(key, (1, 10, cfg.d_model), jnp.float32) * 0.5
    y_par, cache = M.mamba_forward(p, cfg, x, return_cache=True)
    # replay the last token through decode using the cache up to t-1
    y_pre, cache2 = M.mamba_forward(p, cfg, x[:, :9], return_cache=True)
    y_dec, _ = M.mamba_decode(p, cfg, x[:, 9:10], cache2)
    np.testing.assert_allclose(np.asarray(y_dec[:, 0]), np.asarray(y_par[:, 9]),
                               atol=1e-4, rtol=1e-4)


def test_xlstm_decode_matches_parallel():
    from repro.models import xlstm as X
    cfg = tiny("xlstm-125m", d_model=64, n_heads=2)
    key = jax.random.PRNGKey(10)
    x = jax.random.normal(key, (1, 8, cfg.d_model), jnp.float32) * 0.3

    pm = X.init_mlstm(key, cfg, jnp.float32)
    y_par, _ = X.mlstm_forward(pm, cfg, x, return_cache=True)
    y_pre, cache = X.mlstm_forward(pm, cfg, x[:, :7], return_cache=True)
    y_dec, _ = X.mlstm_decode(pm, cfg, x[:, 7:8], cache)
    np.testing.assert_allclose(np.asarray(y_dec[:, 0]), np.asarray(y_par[:, 7]),
                               atol=2e-3, rtol=2e-2)

    ps = X.init_slstm(key, cfg, jnp.float32)
    y_par2, _ = X.slstm_forward(ps, cfg, x, return_cache=True)
    y_pre2, cache2 = X.slstm_forward(ps, cfg, x[:, :7], return_cache=True)
    y_dec2, _ = X.slstm_decode(ps, cfg, x[:, 7:8], cache2)
    np.testing.assert_allclose(np.asarray(y_dec2[:, 0]),
                               np.asarray(y_par2[:, 7]), atol=1e-4, rtol=1e-4)


def test_ring_buffer_decode_beyond_capacity():
    """Decode past the cache capacity (ring wrap) stays finite."""
    cfg = dataclasses.replace(tiny("llama3.2-1b"), sliding_window=8)
    key = jax.random.PRNGKey(11)
    params = T.init_params(key, cfg)
    toks = jax.random.randint(key, (1, 6), 0, cfg.vocab)
    lg, caches = T.prefill(params, cfg, tokens=toks, capacity=8)
    for i in range(12):  # wraps the 8-slot ring
        lg, caches = T.decode_step(params, cfg, toks[:, :1], caches)
        assert np.isfinite(np.asarray(lg)).all()


# ---------------------------------------------------------------------------
# chunked (streaming) sequence-mixer forms vs direct quadratic references
# ---------------------------------------------------------------------------

def test_chunked_attention_matches_direct():
    from repro.models.attention import (_attend, _attend_chunked, _gqa_scores,
                                        init_attn)
    cfg = tiny("llama3.2-1b", n_heads=4, n_kv_heads=2)
    key = jax.random.PRNGKey(20)
    B, S, hd = 2, 64, cfg.hd
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, 4, hd), jnp.float32)
    k = jax.random.normal(kk, (B, S, 2, hd), jnp.float32)
    v = jax.random.normal(kv, (B, S, 2, hd), jnp.float32)
    # direct
    scores = _gqa_scores(q, k, cfg)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    direct = _attend(scores, v, (j <= i)[None, None, None])
    # chunked with several block geometries
    for qc, kc in ((16, 16), (8, 32), (32, 8)):
        out = _attend_chunked(q, k, v, cfg, q_chunk=qc, kv_chunk=kc)
        np.testing.assert_allclose(np.asarray(out), np.asarray(direct),
                                   atol=2e-5, rtol=2e-5)


def test_chunked_attention_sliding_window_matches_direct():
    import dataclasses as dc
    from repro.models.attention import _attend, _attend_chunked, _gqa_scores
    cfg = dc.replace(tiny("llama3.2-1b", n_heads=2, n_kv_heads=2),
                     sliding_window=12)
    key = jax.random.PRNGKey(21)
    B, S, hd = 1, 48, cfg.hd
    q = jax.random.normal(key, (B, S, 2, hd), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(22), (B, S, 2, hd), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(23), (B, S, 2, hd), jnp.float32)
    scores = _gqa_scores(q, k, cfg)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    mask = (j <= i) & (j > i - cfg.sliding_window)
    direct = _attend(scores, v, mask[None, None, None])
    out = _attend_chunked(q, k, v, cfg, q_chunk=16, kv_chunk=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(direct),
                               atol=2e-5, rtol=2e-5)


def test_chunked_mamba_matches_single_block():
    from repro.models import mamba as M
    cfg = tiny("jamba-1.5-large-398b")
    key = jax.random.PRNGKey(24)
    p = M.init_mamba(key, cfg, jnp.float32)
    x = jax.random.normal(key, (2, 64, cfg.d_model), jnp.float32) * 0.5
    y_full = M.mamba_forward(p, cfg, x, chunk=64)    # one block
    y_chunk = M.mamba_forward(p, cfg, x, chunk=16)   # 4 blocks
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_full),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("backend,d_inner,cost,shards,kernel", [
    ("tpu", 5120, False, 1, True),       # the LM cell's scan
    ("tpu", 512, False, 1, True),
    ("cpu", 5120, False, 1, False),      # the CPU tests
    ("gpu", 5120, False, 1, False),
    ("tpu", 5120 + 64, False, 1, False),  # no whole number of lanes
    ("tpu", 5120, True, 1, False),       # the cost probe
    ("tpu", 5120, False, 4, False),      # channels sharded over a mesh
])
def test_scan_path_follows_backend_width_cost_and_mesh(backend, d_inner,
                                                      cost, shards, kernel):
    from repro.models import mamba as M
    assert M.scan_kernel_engages(backend, d_inner, cost, shards) is kernel


def test_this_machine_runs_the_associative_scan():
    from repro.models import mamba as M
    from repro.models.costmode import cost_mode
    from repro.models.pshard import axis_size
    assert not M.scan_kernel_engages(jax.default_backend(), 512, cost_mode(),
                                     axis_size("model"))


@pytest.mark.parametrize("name", ["jamba2-3b", "jamba-1.5-large-398b"])
def test_kernel_scan_path_matches_the_associative_path(name, monkeypatch):
    """``mamba_forward`` with the kernel path forced (interpret mode) gives
    the associative path's output, prefill state and parameter gradients
    (Δ/B/C norms on for jamba2-3b, off for Jamba 1.5)."""
    import functools
    from repro.models import mamba as M
    cfg = tiny(name)
    key = jax.random.PRNGKey(31)
    p = M.init_mamba(key, cfg, jnp.float32)
    x = jax.random.normal(key, (2, 40, cfg.d_model), jnp.float32) * 0.5

    def run(params):
        y, cache = M.mamba_forward(params, cfg, x, return_cache=True,
                                   chunk=8)
        return jnp.sum(y * jnp.sin(y)) + jnp.sum(cache.ssm ** 2), (y, cache)

    (l0, (y0, c0)), g0 = jax.value_and_grad(run, has_aux=True)(p)
    monkeypatch.setattr(M, "scan_kernel_engages", lambda *a: True)
    monkeypatch.setattr(M, "mamba_scan_kernel", functools.partial(
        M.mamba_scan_kernel, interpret=True))
    (l1, (y1, c1)), g1 = jax.value_and_grad(run, has_aux=True)(p)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(c1.ssm), np.asarray(c0.ssm),
                               atol=2e-5, rtol=2e-5)
    assert np.array_equal(np.asarray(c1.conv), np.asarray(c0.conv))
    for k in g0:
        scale = float(jnp.max(jnp.abs(g0[k]))) + 1e-12
        np.testing.assert_allclose(np.asarray(g1[k]), np.asarray(g0[k]),
                                   atol=1e-5 * scale, rtol=1e-4, err_msg=k)


def test_chunked_mlstm_matches_single_block():
    from repro.models import xlstm as X
    cfg = tiny("xlstm-125m", d_model=64, n_heads=2)
    key = jax.random.PRNGKey(25)
    p = X.init_mlstm(key, cfg, jnp.float32)
    x = jax.random.normal(key, (2, 64, cfg.d_model), jnp.float32) * 0.4
    y_full = X.mlstm_forward(p, cfg, x, chunk=64)
    y_chunk = X.mlstm_forward(p, cfg, x, chunk=16)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_full),
                               atol=1e-4, rtol=1e-3)
    # and against the step recurrence, token by token
    cache = X.init_mlstm_cache(cfg, 2, jnp.float32)
    outs = []
    for t in range(64):
        y_t, cache = X.mlstm_decode(p, cfg, x[:, t:t + 1], cache)
        outs.append(y_t)
    y_rec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(y_rec), np.asarray(y_full),
                               atol=1e-3, rtol=1e-2)

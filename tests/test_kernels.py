"""Per-kernel validation (deliverable c): shape/dtype sweeps, interpret-mode
Pallas vs the pure-jnp oracle in ref.py, plus hypothesis property tests."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_stub import given, settings, st

from repro.fl.faults import GuardConfig
from repro.fl.state import AggregatorConfig, masked_aggregate, round_weights
from repro.kernels import ref
from repro.kernels.fl_aggregate import fl_aggregate
from repro.kernels.flash_attention import flash_attention
from repro.kernels.selective_scan import selective_scan

TOL = {jnp.float32: dict(atol=2e-5, rtol=2e-5),
       jnp.bfloat16: dict(atol=3e-2, rtol=3e-2)}


# ---------------------------------------------------------------------------
# fl_aggregate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("K", [1, 4, 16, 32, 130])   # 130: 3 row tiles
@pytest.mark.parametrize("M", [128, 8192, 8193, 77])   # incl. non-tile sizes
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fl_aggregate_sweep(K, M, dtype):
    key = jax.random.PRNGKey(K * 1000 + M)
    g = jax.random.normal(key, (M,), dtype)
    d = jax.random.normal(jax.random.PRNGKey(1), (K, M), dtype)
    m = (jax.random.uniform(jax.random.PRNGKey(2), (K,)) < 0.5
         ).astype(jnp.float32)
    out = fl_aggregate(g, d, m, interpret=True)
    want = ref.fl_aggregate_ref(g, d, m)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_fl_aggregate_zero_mask_is_identity():
    g = jnp.arange(300.0)
    d = jnp.ones((8, 300))
    out = fl_aggregate(g, d, jnp.zeros((8,)), interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(g))


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 12), st.integers(1, 500))
def test_fl_aggregate_property(K, M):
    """Full mask ⇒ exactly global + mean(deltas)."""
    d = jnp.ones((K, M)) * 2.0
    g = jnp.zeros((M,))
    out = fl_aggregate(g, d, jnp.ones((K,)), interpret=True)
    np.testing.assert_allclose(np.asarray(out), 2.0, atol=1e-6)


QUARANTINE = GuardConfig(quarantine=True)


def _quarantined(g, d, w, use_pallas="interpret"):
    """Quarantine through the one update: ``round_weights`` zeroes and
    unweights the non-finite rows, ``masked_aggregate`` runs the kernel."""
    w, denom, d = round_weights(w, 1, jnp.zeros(w.shape, jnp.int32), None,
                                None, QUARANTINE, d)
    return masked_aggregate(g, d, w, denom, use_pallas=use_pallas)


@pytest.mark.parametrize("M", [128, 8193, 77])
def test_fl_aggregate_guard_zeroes_nonfinite(M):
    """Quarantine rejects the rows with NaN/Inf before the kernel — the
    result is the oracle over the honest rows, and never goes non-finite."""
    g = jax.random.normal(jax.random.PRNGKey(0), (M,))
    d = jax.random.normal(jax.random.PRNGKey(1), (4, M))
    d = d.at[1].set(jnp.nan).at[2, 0].set(jnp.inf)
    w = jnp.array([0.25, 0.25, 0.0, 0.25])
    out = _quarantined(g, d, w)
    honest = jnp.array([0, 3])
    want = ref.fl_aggregate_ref(g, d[honest], w[honest], 1)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               **TOL[jnp.float32])


def test_fl_aggregate_guard_across_row_tiles():
    """Rows past one row tile: poison in a later tile is still quarantined,
    and the padded rows (mask 0) add nothing."""
    R, M = 150, 300
    g = jax.random.normal(jax.random.PRNGKey(0), (M,))
    d = jax.random.normal(jax.random.PRNGKey(1), (R, M))
    d = d.at[140].set(jnp.nan).at[70, 5].set(-jnp.inf)
    w = jnp.full((R,), 1.0 / R).at[140].set(0.0).at[70].set(0.0)
    out = _quarantined(g, d, w)
    honest = np.setdiff1d(np.arange(R), [70, 140])
    want = ref.fl_aggregate_ref(g, d[honest], w[honest], 1)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               **TOL[jnp.float32])


def test_fl_aggregate_guard_off_propagates_nan():
    """Without the guard a poisoned row does reach the output — the
    regression that makes quarantine necessary."""
    g = jnp.zeros((128,))
    d = jnp.zeros((2, 128)).at[0].set(jnp.nan)
    out = fl_aggregate(g, d, jnp.ones((2,)), interpret=True)
    assert np.isnan(np.asarray(out)).any()


def test_fl_aggregate_guarded_ref_matches_manual():
    g = jnp.ones((5,))
    d = jnp.stack([jnp.full((5,), 2.0), jnp.full((5,), jnp.nan)])
    w = jnp.array([0.5, 0.5])
    out = _quarantined(g, d, w)
    np.testing.assert_allclose(np.asarray(out), 2.0)  # 1 + 0.5·2 + 0·δ_1


@pytest.mark.parametrize("divisor", ["static", "traced"])
@pytest.mark.parametrize("rule", ["plain", "guarded", "scheme"])
def test_masked_aggregate_kernel_matches_jnp(rule, divisor):
    """Every update a round can apply — plain, guarded, scheme — with the
    population as a Python int (dense engine: the kernel's static denom)
    or traced (sparse path, server: folded into the weights): the
    interpreted kernel matches the jnp path."""
    R, K = 12, 40
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    g = {"w": jax.random.normal(ks[0], (9, 33)), "b": jnp.zeros((7,))}
    d = {"w": jax.random.normal(ks[1], (R, 9, 33)),
         "b": jax.random.normal(ks[2], (R, 7))}
    mask = jax.random.uniform(ks[3], (R,)) < 0.6
    stale = jnp.arange(R, dtype=jnp.int32) % 5
    probs = jnp.linspace(0.1, 0.9, R)
    agg = AggregatorConfig(kind="csmaafl", staleness_fn="poly") \
        if rule == "scheme" else None
    guards = GuardConfig(clip_norm=3.0, staleness_power=0.5) \
        if rule == "guarded" else None

    def update(num_clients, use_pallas):
        w, denom, dd = round_weights(mask, num_clients, stale, probs, agg,
                                     guards, d)
        return masked_aggregate(g, dd, w, denom, use_pallas=use_pallas)

    if divisor == "static":
        jnp_out = update(K, False)
        kernel_out = update(K, "interpret")
    else:
        jnp_out = jax.jit(lambda k: update(k, False))(jnp.int32(K))
        kernel_out = jax.jit(lambda k: update(k, "interpret"))(jnp.int32(K))
    for a, b in zip(jax.tree_util.tree_leaves(jnp_out),
                    jax.tree_util.tree_leaves(kernel_out)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   **TOL[jnp.float32])


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KV,hd", [
    (1, 128, 2, 2, 64),      # MHA
    (2, 256, 4, 2, 64),      # GQA 2:1
    (1, 256, 8, 2, 128),     # GQA 4:1, wide head
    (1, 512, 4, 1, 64),      # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, KV, hd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(S + H), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd), dtype)
    out = flash_attention(q, k, v, bq=128, bk=128, interpret=True)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_window(window):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 2, 64), jnp.float32)
    out = flash_attention(q, k, v, window=window, bq=64, bk=64,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("bq,bk", [(64, 64), (128, 64), (64, 128)])
def test_flash_attention_block_geometry(bq, bk):
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 2, 64), jnp.float32)
    out = flash_attention(q, k, v, bq=bq, bk=bk, interpret=True)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_first_token_attends_self_only():
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 128, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 128, 2, 64), jnp.float32)
    out = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(out[0, 0]), np.asarray(v[0, 0]),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# selective_scan
# ---------------------------------------------------------------------------

def _scan_inputs(B, S, d, N, seed, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    xc = jax.random.normal(ks[0], (B, S, d), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, d), dtype) - 1)
    Bm = jax.random.normal(ks[2], (B, S, N), dtype)
    Cm = jax.random.normal(ks[3], (B, S, N), dtype)
    A = -jnp.exp(jax.random.normal(ks[4], (d, N)) * 0.3)
    Dv = jax.random.normal(ks[5], (d,))
    return xc, dt, Bm, Cm, A, Dv


def _scan_sequential(xc, dt, Bm, Cm, A, D):
    """The recurrence token by token (as ``chipbench/lm_reference.py``
    runs it) → (y, h_last)."""
    def one(x, d, b, c):
        def step(h, inp):
            d_t, b_t, c_t, x_t = inp
            h = (jnp.exp(d_t[:, None] * A) * h
                 + (d_t * x_t)[:, None] * b_t[None])
            return h, jnp.dot(h, c_t, precision="highest")
        h, y = jax.lax.scan(step, jnp.zeros(A.shape), (d, b, c, x))
        return y + D * x, h
    return jax.vmap(one)(xc, dt, Bm, Cm)


@pytest.mark.parametrize("B,S,d,N", [
    (1, 64, 128, 16),
    (2, 256, 512, 16),
    (1, 128, 256, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_selective_scan_sweep(B, S, d, N, dtype):
    xc, dt, Bm, Cm, A, Dv = _scan_inputs(B, S, d, N, S + d, dtype)
    out, h_last = selective_scan(xc, dt, Bm, Cm, A, Dv, ts=64,
                                 interpret=True)
    want, h_want = ref.selective_scan_ref(xc.astype(jnp.float32),
                                          dt.astype(jnp.float32),
                                          Bm.astype(jnp.float32),
                                          Cm.astype(jnp.float32), A, Dv)
    # the kernel takes its inputs as float32, so bfloat16 inputs compare
    # at float32's tolerance once cast
    tol = dict(atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **tol)
    np.testing.assert_allclose(np.asarray(h_last), np.asarray(h_want), **tol)


def test_selective_scan_state_carries_across_blocks():
    """A single long block vs many small sequential blocks must agree —
    proves the VMEM scratch state survives grid steps."""
    xc, dt, Bm, Cm, A, Dv = _scan_inputs(1, 256, 128, 16, 3)
    one = selective_scan(xc, dt, Bm, Cm, A, Dv, ts=256, interpret=True)
    many = selective_scan(xc, dt, Bm, Cm, A, Dv, ts=32, interpret=True)
    for a, b in zip(one, many):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


# S = 40 is no whole number of 16-step blocks (padded), d = 1,152 nine lane
# tiles (padded to two channel blocks): both pads are exercised
@pytest.mark.parametrize("S,d,ts", [(40, 256, 16), (64, 1152, 32)])
def test_selective_scan_matches_the_token_by_token_recurrence(S, d, ts):
    args = _scan_inputs(2, S, d, 16, 7 + S)
    y, h = selective_scan(*args, ts=ts, interpret=True)
    y_seq, h_seq = _scan_sequential(*args)
    y_assoc, h_assoc = ref.selective_scan_ref(*args)
    for got in ((y, h), (y_assoc, h_assoc)):
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(y_seq),
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(h_seq),
                                   atol=2e-5, rtol=2e-5)


def test_selective_scan_under_vmap_is_the_batched_scan():
    """The engine vmaps a batch-1 model over its clients: Pallas then adds
    a grid axis, and the answer is the batch-B call's, bit for bit."""
    xc, dt, Bm, Cm, A, Dv = _scan_inputs(3, 48, 256, 16, 11)
    one = functools.partial(selective_scan, ts=16, interpret=True)
    y, h = one(xc, dt, Bm, Cm, A, Dv)
    yv, hv = jax.vmap(lambda x, d, b, c: one(x[None], d[None], b[None],
                                             c[None], A, Dv))(xc, dt, Bm, Cm)
    assert np.array_equal(np.asarray(yv[:, 0]), np.asarray(y))
    assert np.array_equal(np.asarray(hv[:, 0]), np.asarray(h))


def _scan_loss(scan):
    def loss(*args):
        y, h = scan(*args)
        wy = jnp.cos(jnp.arange(y.size, dtype=jnp.float32)).reshape(y.shape)
        return jnp.sum(y * wy) + jnp.sum(h * h)
    return loss


@pytest.mark.parametrize("S,ts", [(48, 16), (40, 16)])
def test_selective_scan_vjp_matches_the_associative_path(S, ts):
    """All six cotangents (y and h_last both carry one) against jax.grad of
    the associative form; S = 40 pads the last block."""
    args = _scan_inputs(2, S, 256, 16, 5)
    got = jax.grad(_scan_loss(functools.partial(
        selective_scan, ts=ts, interpret=True)), argnums=range(6))(*args)
    want = jax.grad(_scan_loss(ref.selective_scan_ref),
                    argnums=range(6))(*args)
    for name, g, w in zip(("xc", "dt", "B", "C", "A", "D"), got, want):
        assert g.shape == w.shape, name
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2e-6 * scale, rtol=1e-5,
                                   err_msg=name)


def test_selective_scan_backward_repeats_bit_for_bit():
    args = _scan_inputs(2, 32, 256, 16, 13)
    grad = jax.jit(jax.grad(_scan_loss(functools.partial(
        selective_scan, ts=16, interpret=True)), argnums=range(6)))
    for a, b in zip(grad(*args), grad(*args)):
        assert np.array_equal(np.asarray(a), np.asarray(b))

"""Per-kernel validation (deliverable c): shape/dtype sweeps, interpret-mode
Pallas vs the pure-jnp oracle in ref.py, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_stub import given, settings, st

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


@pytest.mark.parametrize("M", [128, 8193, 77])
def test_fl_aggregate_guard_zeroes_nonfinite(M):
    """guard=True quarantines NaN/Inf elements inside the kernel — the
    result matches the sanitizing oracle and never goes non-finite."""
    g = jax.random.normal(jax.random.PRNGKey(0), (M,))
    d = jax.random.normal(jax.random.PRNGKey(1), (4, M))
    d = d.at[1].set(jnp.nan).at[2, 0].set(jnp.inf)
    w = jnp.array([0.25, 0.25, 0.0, 0.25])
    out = fl_aggregate(g, d, w, interpret=True, denom=1, guard=True)
    want = ref.fl_aggregate_guarded_ref(g, d, w)
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
    out = fl_aggregate(g, d, w, interpret=True, denom=1, guard=True)
    want = ref.fl_aggregate_guarded_ref(g, d, w)
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
    out = ref.fl_aggregate_guarded_ref(g, d, w)
    np.testing.assert_allclose(np.asarray(out), 2.0)  # 1 + 0.5·2 + 0.5·0


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

@pytest.mark.parametrize("B,S,d,N", [
    (1, 64, 128, 16),
    (2, 256, 512, 16),
    (1, 128, 256, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_selective_scan_sweep(B, S, d, N, dtype):
    ks = jax.random.split(jax.random.PRNGKey(S + d), 6)
    xc = jax.random.normal(ks[0], (B, S, d), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, d), dtype) - 1)
    Bm = jax.random.normal(ks[2], (B, S, N), dtype)
    Cm = jax.random.normal(ks[3], (B, S, N), dtype)
    A = -jnp.exp(jax.random.normal(ks[4], (d, N)) * 0.3)
    Dv = jax.random.normal(ks[5], (d,))
    out = selective_scan(xc, dt, Bm, Cm, A, Dv, bd=128, sc=64, interpret=True)
    want = ref.selective_scan_ref(xc.astype(jnp.float32),
                                  dt.astype(jnp.float32),
                                  Bm.astype(jnp.float32),
                                  Cm.astype(jnp.float32), A, Dv)
    tol = dict(atol=1e-4, rtol=1e-3) if dtype == jnp.float32 \
        else dict(atol=0.15, rtol=0.1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **tol)


def test_selective_scan_state_carries_across_blocks():
    """A single long block vs many small sequential blocks must agree —
    proves the VMEM scratch state survives grid steps."""
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    B, S, d, N = 1, 256, 128, 16
    xc = jax.random.normal(ks[0], (B, S, d), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, d)) - 1)
    Bm = jax.random.normal(ks[2], (B, S, N))
    Cm = jax.random.normal(ks[3], (B, S, N))
    A = -jnp.exp(jax.random.normal(ks[4], (d, N)) * 0.3)
    Dv = jax.random.normal(ks[5], (d,))
    one = selective_scan(xc, dt, Bm, Cm, A, Dv, bd=128, sc=256,
                         interpret=True)
    many = selective_scan(xc, dt, Bm, Cm, A, Dv, bd=128, sc=32,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(one), np.asarray(many),
                               atol=1e-4, rtol=1e-4)

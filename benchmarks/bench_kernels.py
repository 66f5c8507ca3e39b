"""Kernel microbenchmarks: interpret-mode Pallas vs jnp oracle (correctness +
CPU latency; TPU is the target, so derived figures are the VMEM working-set
and arithmetic-intensity numbers used in DESIGN.md §7)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.fl_aggregate import BLOCK_R, LANE, fl_aggregate
from repro.kernels.flash_attention import flash_attention
from repro.kernels.selective_scan import selective_scan
from repro.launch.cache import enable_compile_cache

from .common import row, save_artifact


def _time(f, n=3):
    f()  # compile
    t0 = time.time()
    for _ in range(n):
        jax.block_until_ready(f())
    return (time.time() - t0) / n


def main() -> dict:
    enable_compile_cache()
    out = {}
    key = jax.random.PRNGKey(0)

    # fl_aggregate: K=16 clients, 1M params
    K, M = 16, 1_000_000
    g = jax.random.normal(key, (M,), jnp.float32)
    d = jax.random.normal(key, (K, M), jnp.float32)
    m = (jax.random.uniform(key, (K,)) < 0.5).astype(jnp.float32)
    t_ref = _time(lambda: ref.fl_aggregate_ref(g, d, m))
    err = float(jnp.abs(fl_aggregate(g, d, m, interpret=True)
                        - ref.fl_aggregate_ref(g, d, m)).max())
    hbm_naive = (K * M * 4) * 2 + M * 8          # unfused: read δ, write temp, rw global
    hbm_fused = K * M * 4 + M * 8                # fused single pass
    out["fl_aggregate"] = {"ref_us": t_ref * 1e6, "maxerr": err,
                           "hbm_bytes_fused": hbm_fused,
                           "hbm_bytes_naive": hbm_naive,
                           "vmem_block_kb": K * BLOCK_R * LANE * 4 / 1024}
    row("kernel_fl_aggregate", t_ref * 1e6,
        f"maxerr={err:.1e};hbm_saving={hbm_naive/hbm_fused:.2f}x")

    # flash attention: 1×512×8h(2kv)×128
    q = jax.random.normal(key, (1, 512, 8, 128), jnp.bfloat16)
    k = jax.random.normal(key, (1, 512, 2, 128), jnp.bfloat16)
    v = jax.random.normal(key, (1, 512, 2, 128), jnp.bfloat16)
    t_ref = _time(lambda: ref.flash_attention_ref(q, k, v))
    errf = float(jnp.abs(
        flash_attention(q, k, v, interpret=True).astype(jnp.float32)
        - ref.flash_attention_ref(q, k, v).astype(jnp.float32)).max())
    out["flash_attention"] = {"ref_us": t_ref * 1e6, "maxerr": errf,
                              "vmem_block_kb": (128 * 128 * 4 * 3
                                                + 2 * 128 * 128 * 4) / 1024}
    row("kernel_flash_attention", t_ref * 1e6, f"maxerr={errf:.1e}")

    # selective scan: 1×512×512, N=16
    B, S, dd, N = 1, 512, 512, 16
    ks = jax.random.split(key, 6)
    xc = jax.random.normal(ks[0], (B, S, dd), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, dd)) - 1)
    Bm = jax.random.normal(ks[2], (B, S, N))
    Cm = jax.random.normal(ks[3], (B, S, N))
    A = -jnp.exp(jax.random.normal(ks[4], (dd, N)) * 0.3)
    Dv = jax.random.normal(ks[5], (dd,))
    t_ref = _time(lambda: ref.selective_scan_ref(xc, dt, Bm, Cm, A, Dv))
    errs = float(jnp.abs(
        selective_scan(xc, dt, Bm, Cm, A, Dv, interpret=True)
        - ref.selective_scan_ref(xc, dt, Bm, Cm, A, Dv)).max())
    out["selective_scan"] = {"ref_us": t_ref * 1e6, "maxerr": errs,
                             "vmem_state_kb": 256 * N * 4 / 1024}
    row("kernel_selective_scan", t_ref * 1e6, f"maxerr={errs:.1e}")

    save_artifact("bench_kernels", out)
    return out


if __name__ == "__main__":
    main()

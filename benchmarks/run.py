"""Benchmark harness — one module per paper table/figure plus kernels,
Algorithm-1 microbenchmarks and the roofline readout.

Prints ``name,us_per_call,derived`` CSV rows (one per measurement) and writes
JSON artifacts to ``artifacts/bench/``.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run fig6       # substring filter
"""
from __future__ import annotations

import sys
import time
import traceback

from repro.launch.cache import enable_compile_cache

from . import (bench_algorithm1, bench_data, bench_engine, bench_faults,
               bench_kernels, bench_staleness, fig2_3_rho_sweep,
               fig4_5_energy, fig6_7_schemes, fig8_9_scenarios)

SUITES = [
    ("bench_algorithm1", bench_algorithm1.main),
    ("bench_data", lambda: bench_data.main_quick()),
    ("bench_engine", lambda: bench_engine.main_quick()),
    ("bench_faults", lambda: bench_faults.main_quick()),
    ("bench_kernels", bench_kernels.main),
    ("bench_staleness", bench_staleness.main),
    ("fig2_3_rho_sweep", fig2_3_rho_sweep.main),
    ("fig4_5_energy", fig4_5_energy.main),
    ("fig6_7_schemes", lambda: fig6_7_schemes.main(["--quick"])),
    ("fig8_9_scenarios", fig8_9_scenarios.main),
]


def main() -> None:
    enable_compile_cache()
    filt = sys.argv[1] if len(sys.argv) > 1 else ""
    print("name,us_per_call,derived")
    failures = []
    for name, fn in SUITES:
        if filt and filt not in name:
            continue
        t0 = time.time()
        try:
            fn()
            print(f"{name}_total,{(time.time() - t0) * 1e6:.0f},ok")
        except Exception as e:  # noqa: BLE001
            failures.append(name)
            traceback.print_exc()
            print(f"{name}_total,0,FAILED:{type(e).__name__}")
    # roofline readout is optional — requires dry-run artifacts
    try:
        from . import roofline
        rows = roofline.main()
        print(f"roofline_total,0,rows={len(rows)}")
    except Exception as e:  # noqa: BLE001
        print(f"roofline_total,0,skipped:{type(e).__name__}")
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()

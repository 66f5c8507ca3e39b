"""Observability: in-scan metrics taps, host telemetry, benchmark reporter.

Three layers (docs/observability.md):

* :mod:`repro.obs.taps` — device-side metrics: a :class:`MetricsSpec` of
  pure jittable reducers accumulated into fixed-shape buffers threaded
  through the scan carry of every execution path.  Disabled (the default)
  the engine programs are byte-for-byte unchanged.
* :mod:`repro.obs.telemetry` — host-side: timing spans (profiler
  annotations with a per-call record ring), compile-cache counters,
  device-memory snapshots, a structured JSONL run manifest (opt-in via
  ``REPRO_OBS_DIR``), and a ``jax.profiler`` capture hook (opt-in via
  ``REPRO_PROFILE_DIR``).
* :mod:`repro.obs.report` — the benchmark ledger reporter: renders run
  summaries and diffs two BENCH_*.json files with tolerance thresholds
  (the CI perf-regression gate).
"""
from .taps import (MetricsSpec, MetricsState, init_metrics, merge_metrics,
                   metrics_active, metrics_round_update, metrics_summary,
                   update_ledger_taps, update_train_taps)
from .telemetry import (config_fingerprint, configure, emit_run_manifest,
                        env_fingerprint, get_telemetry, maybe_profile,
                        run_manifest, validate_manifest)

__all__ = [
    "MetricsSpec", "MetricsState", "init_metrics", "merge_metrics",
    "metrics_active", "metrics_round_update", "metrics_summary",
    "update_ledger_taps", "update_train_taps",
    "config_fingerprint", "configure", "emit_run_manifest",
    "env_fingerprint", "get_telemetry", "maybe_profile", "run_manifest",
    "validate_manifest",
]

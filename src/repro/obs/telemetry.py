"""Host-side telemetry: spans, counters, memory snapshots, run manifests.

Everything here is host Python around the device programs — it never
changes a traced program.  The in-memory session record is always on (it is
just dict updates); *writing* anything to disk is opt-in:

* ``REPRO_OBS_DIR`` (or :func:`configure`) — run manifests append to
  ``<dir>/runs.jsonl`` as one JSON object per line (schema:
  :data:`MANIFEST_SCHEMA`, checked by :func:`validate_manifest` and the CI
  obs-smoke job);
* ``REPRO_PROFILE_DIR`` (or :func:`configure`) — :func:`maybe_profile`
  wraps a block in ``jax.profiler.trace`` emitting a TensorBoard trace.

Spans aggregate per name (count / total / max seconds) so a million runner
calls cost a bounded dict, not an unbounded event log.  The sparse train
compile cache (:mod:`repro.fl.sparse`) bumps the
``sparse.train_cache_{hit,miss}`` counters here.
"""
from __future__ import annotations

import contextlib
import functools
import hashlib
import json
import os
import subprocess
import time
from typing import Any

__all__ = ["Telemetry", "get_telemetry", "configure", "env_fingerprint",
           "config_fingerprint", "run_manifest", "emit_run_manifest",
           "validate_manifest", "maybe_profile", "timed_compile",
           "MANIFEST_SCHEMA", "MANIFEST_SCHEMA_VERSION"]

MANIFEST_SCHEMA_VERSION = 1

#: required manifest keys -> type (the JSONL validation contract; ``extra``
#: is free-form).  ``fingerprint`` is the environment block from
#: :func:`env_fingerprint`; ``config_sha`` hashes the SimConfig repr.
MANIFEST_SCHEMA = {
    "schema_version": int,
    "kind": str,
    "written_unix": float,
    "config_sha": str,
    "fingerprint": dict,
    "extra": dict,
}

_FINGERPRINT_KEYS = ("git_sha", "jax", "jaxlib", "backend", "device_count",
                     "cpu_count", "platform", "python")

#: cap on the in-memory manifest record (append-only; old entries rotate).
_MAX_MANIFESTS = 256


class Telemetry:
    """Process-wide aggregation sink: counters, named spans, manifests."""

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.counters: dict = {}
        self.spans: dict = {}          # name -> [count, total_s, max_s]
        self.manifests: list = []

    def inc(self, name: str, n: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            c = self.spans.setdefault(name, [0, 0.0, 0.0])
            c[0] += 1
            c[1] += dt
            c[2] = max(c[2], dt)

    def span_stats(self, name: str) -> dict | None:
        c = self.spans.get(name)
        if c is None:
            return None
        return {"count": c[0], "total_s": c[1], "max_s": c[2],
                "mean_s": c[1] / max(c[0], 1)}

    def snapshot(self) -> dict:
        return {"counters": dict(self.counters),
                "spans": {k: self.span_stats(k) for k in self.spans}}

    def memory_snapshot(self) -> list:
        """Per-device memory stats where the backend exposes them (TPU/GPU;
        CPU backends typically return an empty stats dict)."""
        import jax

        out = []
        for d in jax.devices():
            try:
                stats = d.memory_stats() or {}
            except Exception:
                stats = {}
            out.append({"device": str(d),
                        "bytes_in_use": stats.get("bytes_in_use"),
                        "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
        return out


_TELEMETRY = Telemetry()
_OBS_DIR: str | None = None
_PROFILE_DIR: str | None = None


def get_telemetry() -> Telemetry:
    return _TELEMETRY


def configure(obs_dir: str | None = None,
              profile_dir: str | None = None) -> None:
    """Programmatic opt-in (overrides the environment variables)."""
    global _OBS_DIR, _PROFILE_DIR
    if obs_dir is not None:
        _OBS_DIR = obs_dir
    if profile_dir is not None:
        _PROFILE_DIR = profile_dir


def _obs_dir() -> str | None:
    return _OBS_DIR or os.environ.get("REPRO_OBS_DIR") or None


def _profile_dir() -> str | None:
    return _PROFILE_DIR or os.environ.get("REPRO_PROFILE_DIR") or None


@functools.lru_cache(maxsize=1)
def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=5, cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def env_fingerprint() -> dict:
    """Where/what produced an artifact: git sha, jax/jaxlib versions,
    backend, device/CPU counts.  Stamped into every BENCH_*.json
    (``benchmarks/common.py``) and every run manifest — without it the
    ledger's numbers are uncomparable across machines."""
    import platform

    import jax

    try:
        import jaxlib
        jaxlib_v = getattr(jaxlib, "__version__", "unknown")
    except Exception:
        jaxlib_v = "unknown"
    return {
        "git_sha": _git_sha(),
        "jax": jax.__version__,
        "jaxlib": jaxlib_v,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "cpu_count": os.cpu_count(),
        "platform": platform.platform(),
        "python": platform.python_version(),
    }


def config_fingerprint(cfg: Any) -> str:
    """Short stable hash of a config's repr (SimConfig is a frozen
    dataclass — its repr is its full field map)."""
    return hashlib.sha256(repr(cfg).encode()).hexdigest()[:16]


def run_manifest(kind: str, cfg: Any = None, extra: dict | None = None) -> dict:
    return {
        "schema_version": MANIFEST_SCHEMA_VERSION,
        "kind": kind,
        "written_unix": time.time(),
        "config_sha": config_fingerprint(cfg) if cfg is not None else "",
        "fingerprint": env_fingerprint(),
        "extra": dict(extra or {}),
    }


def emit_run_manifest(kind: str, cfg: Any = None,
                      extra: dict | None = None) -> dict:
    """Record a manifest in the session telemetry and — when an obs dir is
    configured — append it to ``<dir>/runs.jsonl``.  Called by
    ``make_runner``, the ``run_*_matrix`` fan-outs, and ``run_resumable``;
    with no dir configured this is a dict append, nothing touches disk."""
    m = run_manifest(kind, cfg, extra)
    tel = get_telemetry()
    tel.manifests.append(m)
    del tel.manifests[:-_MAX_MANIFESTS]
    d = _obs_dir()
    if d:
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, "runs.jsonl"), "a") as f:
            f.write(json.dumps(m, default=float) + "\n")
    return m


def validate_manifest(m: dict) -> list:
    """Schema check: returns a list of problems (empty = valid)."""
    problems = []
    if not isinstance(m, dict):
        return [f"manifest is {type(m).__name__}, expected dict"]
    for key, typ in MANIFEST_SCHEMA.items():
        if key not in m:
            problems.append(f"missing key {key!r}")
        elif typ is float and isinstance(m[key], (int, float)):
            pass
        elif not isinstance(m[key], typ):
            problems.append(f"key {key!r}: {type(m[key]).__name__}, "
                            f"expected {typ.__name__}")
    fp = m.get("fingerprint")
    if isinstance(fp, dict):
        for k in _FINGERPRINT_KEYS:
            if k not in fp:
                problems.append(f"fingerprint missing {k!r}")
    return problems


@contextlib.contextmanager
def maybe_profile(out_dir: str | None = None):
    """Opt-in ``jax.profiler`` capture: a no-op unless ``out_dir`` is given
    or ``REPRO_PROFILE_DIR``/:func:`configure` set one."""
    d = out_dir or _profile_dir()
    if not d:
        yield None
        return
    import jax

    os.makedirs(d, exist_ok=True)
    with jax.profiler.trace(d):
        yield d


def timed_compile(fn, *args, label: str = "jit"):
    """AOT-compile ``fn(*args)`` with spans around each stage —
    ``<label>.trace`` / ``<label>.lower`` / ``<label>.compile`` — and return
    the compiled executable.  Wrap its calls in ``span(f"{label}.execute")``
    to complete the pipeline timing."""
    import jax

    tel = get_telemetry()
    jf = fn if hasattr(fn, "trace") else jax.jit(fn)
    with tel.span(f"{label}.trace"):
        traced = jf.trace(*args)
    with tel.span(f"{label}.lower"):
        lowered = traced.lower()
    with tel.span(f"{label}.compile"):
        return lowered.compile()

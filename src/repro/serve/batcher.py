"""Micro-batcher: coalesce async uploads into the jitted aggregation step.

Two pieces:

* :func:`build_apply_fn` — the device side.  Pads a Python list of client
  delta pytrees to a pow2 *bucket* (:func:`pick_bucket`, mirroring the
  sparse engine's ``participant_bucket`` discipline: a handful of bucket
  shapes ⇒ a handful of compiles, whatever the traffic level) and drives
  the **same** participant-subset aggregation family as the scan engine's
  phase B — ``scheme_subset_aggregate`` / ``guarded_subset_aggregate`` /
  ``subset_aggregate``, in the same precedence order, with the population
  size as the 1/K divisor.  Replay parity depends on this: an offline
  re-run through ``build_sparse_train_program`` hits the identical
  aggregation code on identically-padded lanes.
* :class:`MicroBatcher` — the host side.  A daemon thread parked on the
  server's condition variable; it flushes when a full ``max_batch`` is
  pending or the oldest pending update has waited ``flush_interval_s``
  (the latency bound), in the maxtext ``offline_inference`` idiom of
  background threads feeding batched device calls.
"""
from __future__ import annotations

import threading
import time
from typing import Any

import jax
import jax.numpy as jnp

from ..fl.state import (guarded_subset_aggregate, scheme_subset_aggregate,
                        subset_aggregate)


def pick_bucket(n: int, min_bucket: int, max_batch: int) -> int:
    """Smallest power of two ≥ max(n, min_bucket), clamped to max_batch."""
    need = max(int(n), int(min_bucket), 1)
    b = 1 << (need - 1).bit_length()
    return min(b, int(max_batch))


#: (guards, aggregator, num_clients) -> jitted _agg.  Sharing the inner jit
#: across server instances keeps the per-bucket compile cache warm between
#: sessions (a fresh closure per server would recompile every bucket).
_AGG_CACHE: dict = {}


def build_apply_fn(guards, aggregator, num_clients: int):
    """``(global, deltas: list[pytree], bucket, stale [n], probs [n]) ->
    global'`` — one jit specialization per bucket shape (jax retraces on
    the padded shapes; ``pick_bucket`` keeps that set small)."""
    ap = aggregator.params() if aggregator is not None else None
    kf = jnp.int32(num_clients)

    cache_key = (guards, aggregator, int(num_clients))
    cached = _AGG_CACHE.get(cache_key)

    if cached is not None:
        _agg = cached
    else:
        @jax.jit
        def _agg(g, deltas_p, valid, stale_p, probs_p):
            # precedence mirrors fl/sparse.build_sparse_train_program exactly
            if aggregator is not None:
                return scheme_subset_aggregate(g, deltas_p, valid, kf,
                                               stale_p, probs_p, ap,
                                               guards=guards)
            if guards is not None and guards.active:
                return guarded_subset_aggregate(g, deltas_p, valid, kf,
                                                stale_p, guards)
            return subset_aggregate(g, deltas_p, valid, kf)
        _AGG_CACHE[cache_key] = _agg

    def apply(g: Any, deltas: list, bucket: int, stale: jax.Array,
              probs: jax.Array):
        n = len(deltas)

        def stack(*leaves):
            s = jnp.stack(leaves)
            if bucket > n:
                pad = jnp.zeros((bucket - n,) + s.shape[1:], s.dtype)
                s = jnp.concatenate([s, pad], axis=0)
            return s

        deltas_p = jax.tree_util.tree_map(stack, *deltas)
        valid = jnp.arange(bucket) < n
        stale_p = jnp.zeros((bucket,), jnp.int32).at[:n].set(stale)
        probs_p = jnp.zeros((bucket,), jnp.float32).at[:n].set(probs)
        return _agg(g, deltas_p, valid, stale_p, probs_p)

    return apply


class MicroBatcher(threading.Thread):
    """Background flush loop.  Holds the server's condition variable only to
    *decide* when to flush; the flush itself (device work) runs unlocked
    through :meth:`AggregationServer.flush`.  A failed flush is recorded on
    :attr:`error` and stops the loop; the server then fails every pending
    ticket, refuses new submissions and raises it from ``close``."""

    def __init__(self, server):
        super().__init__(daemon=True, name="repro-serve-batcher")
        self._srv = server
        self._halt = threading.Event()
        self.error: BaseException | None = None

    def run(self) -> None:
        srv = self._srv
        cfg = srv.cfg
        while not self._halt.is_set():
            with srv._cv:
                while (not srv._pending and not self._halt.is_set()
                        and not srv._closed):
                    srv._cv.wait(timeout=0.05)
                if self._halt.is_set():
                    return
                if not srv._pending:       # closed and drained
                    return
                if not srv._closed and len(srv._pending) < cfg.max_batch:
                    oldest = min(p.ticket.arrival_s
                                 for p in srv._pending.values())
                    wait_for = (cfg.flush_interval_s
                                - (time.perf_counter() - oldest))
                    if wait_for > 0:
                        srv._cv.wait(timeout=wait_for)
                        continue
            try:
                srv.flush()
            except Exception as e:
                self.error = e
                srv._fail_pending(e)
                return

    def stop(self, timeout: float = 10.0) -> None:
        self._halt.set()
        with self._srv._cv:
            self._srv._cv.notify_all()
        self.join(timeout=timeout)

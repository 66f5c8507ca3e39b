"""Host telemetry spans: call ids and parents, the bounded record ring, the
runners' call phases on a profiler trace, and the device→host copies the
dense runner's readback counts."""
import glob
import os
import sys
import threading

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.selection import RandomScheme, as_policy_fn
from repro.fl import FaultConfig, SimConfig, make_runner
from repro.fl.sparse import (build_participation_program,
                             build_sparse_train_program)
from repro.models.small import mlp_accuracy, mlp_loss
from repro.obs import MetricsSpec
from repro.obs.telemetry import MAX_RECORDS, Telemetry, get_telemetry
from repro.optim import sgd

from test_engine_parity import tiny_world

K, T = 5, 8
PHASES = ("engine.prepare", "engine.dispatch", "engine.wait",
          "engine.readback")
SPARSE_PHASES = ("sparse.prepare", "sparse.phase_a", "sparse.phase_b",
                 "sparse.readback")


def _runner(**kw):
    clients, te, cell, h, params = tiny_world(K=K, rounds=T, dim=32)
    base = dict(rounds=T, local_iters=2, batch_size=4, eval_every=2)
    base.update(kw)
    runner = make_runner(mlp_loss, mlp_accuracy, clients, te,
                         RandomScheme(p_bar=0.4, num_clients=K), cell,
                         SimConfig(**base))
    return runner, params, h


def _last_call(tel):
    """The newest dense runner call's records (``engine.call`` last), oldest
    first."""
    cid = tel.records("engine.call")[-1].call_id
    return [r for r in tel.records()
            if r.call_id == cid and r.name.startswith("engine.")]


# --- the span record ----------------------------------------------------------


def test_span_nesting_gives_children_the_call_id_and_parent():
    tel = Telemetry()
    with tel.span("outer.call", call_id=tel.inc("outer.calls")):
        with tel.span("outer.phase"):
            with tel.span("outer.inner"):
                pass
    with tel.span("loose"):
        pass
    inner, phase, call, loose = tel.records()
    assert (call.call_id, call.parent) == (1, None)
    assert (phase.call_id, phase.parent) == (1, "outer.call")
    assert (inner.call_id, inner.parent) == (1, "outer.phase")
    assert (loose.call_id, loose.parent) == (None, None)
    assert call.start_s <= phase.start_s <= inner.start_s
    assert inner.end_s <= phase.end_s <= call.end_s
    # the next call takes the counter's next value
    with tel.span("outer.call", call_id=tel.inc("outer.calls")):
        with tel.span("outer.phase"):
            pass
    assert [r.call_id for r in tel.records("outer.phase")] == [1, 2]


def test_span_keeps_its_aggregate_and_closes_on_error():
    tel = Telemetry()
    for _ in range(3):
        with tel.span("agg"):
            pass
    with pytest.raises(RuntimeError):
        with tel.span("agg"):
            raise RuntimeError("boom")
    stats = tel.span_stats("agg")
    assert stats["count"] == 4
    assert stats["max_s"] <= stats["total_s"]
    # the failed span was popped: a later span is top level again
    with tel.span("after"):
        pass
    assert tel.records("after")[0].parent is None


def test_threads_keep_their_own_open_spans():
    tel = Telemetry()
    n_threads, n_calls = 8, 200
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(k):
            for i in range(n_calls):
                with tel.span("t.call", call_id=k * n_calls + i):
                    with tel.span(f"t.child{k}"):
                        pass
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    for k in range(n_threads):
        kids = tel.records(f"t.child{k}")
        assert len(kids) == n_calls
        assert all(r.parent == "t.call" for r in kids)
        assert [r.call_id for r in kids] == list(range(k * n_calls,
                                                       (k + 1) * n_calls))


def test_record_ring_stays_bounded():
    tel = Telemetry()
    n = MAX_RECORDS + 100
    for i in range(n):
        with tel.span("tick", call_id=i):
            pass
    recs = tel.records("tick")
    assert len(recs) == MAX_RECORDS
    assert recs[0].call_id == n - MAX_RECORDS and recs[-1].call_id == n - 1
    assert tel.span_stats("tick")["count"] == n      # the aggregate is not
    tel.reset()
    assert tel.records() == []


# --- the runners' calls -----------------------------------------------------------


def test_dense_runner_call_phases_on_the_profiler_trace(tmp_path):
    runner, params, h = _runner()
    runner(params, h)                      # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        runner(params, h, seed=3)
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
              for p in ProfileData.from_file(path).planes
              if p.name.startswith("/host:")
              for line in p.lines for ev in line.events
              if ev.name.startswith("engine.")]
    (call,) = [e for e in events if e[0] == "engine.call"]
    kids = sorted((e for e in events if e[0] != "engine.call"),
                  key=lambda e: e[1])
    assert tuple(e[0] for e in kids) == PHASES
    for (_, s, e), (_, s_next, _) in zip(kids, kids[1:]):
        assert e <= s_next                 # in order, not overlapping
    assert call[1] <= kids[0][1] and kids[-1][2] <= call[2]


def test_dense_runner_records_one_call_with_four_children():
    tel = get_telemetry()
    runner, params, h = _runner()
    runner(params, h)
    recs = _last_call(tel)
    assert [r.name for r in recs] == [*PHASES, "engine.call"]
    assert recs[-1].call_id == tel.counters["engine.calls"]
    assert all(r.parent == "engine.call" for r in recs[:-1])


# every array the SimResult reads (the traces and energy; faults add
# delivered and corrupt, taps every metrics leaf) comes back in the one
# packed record: one copy a call, whatever the configuration
@pytest.mark.parametrize("kw,copies", [
    ({}, 1),
    ({"faults": FaultConfig(p_loss=0.2)}, 1),
    ({"metrics": MetricsSpec()}, 1),
])
def test_d2h_transfers_per_call_equal_the_arrays_converted(kw, copies):
    tel = get_telemetry()
    runner, params, h = _runner(**kw)
    runner(params, h)
    before = tel.counters["engine.d2h_transfers"]
    res = runner(params, h, seed=5)
    got = tel.counters["engine.d2h_transfers"] - before
    assert got == copies
    assert isinstance(res.participation, np.ndarray)
    if "faults" in kw:
        assert isinstance(res.delivered, np.ndarray)
    if "metrics" in kw:
        assert all(isinstance(x, np.ndarray)
                   for x in jax.tree_util.tree_leaves(res.metrics))


def test_stream_runner_records_call_and_readback():
    tel = get_telemetry()
    runner, params, h = _runner(data_path="stream", stream_chunk=3)
    runner(params, h)
    recs = _last_call(tel)
    assert [r.name for r in recs] == ["engine.readback", "engine.call"]
    assert recs[0].parent == "engine.call"


def test_sparse_runner_call_phases_nest_in_order():
    tel = get_telemetry()
    runner, params, h = _runner(local_mode="participants",
                                data_path="device", data_stream="client",
                                participation="sparse")
    runner(params, h)
    call = tel.records("sparse.call")[-1]
    kids = [r for r in tel.records() if r.parent == "sparse.call"
            and call.start_s <= r.start_s and r.end_s <= call.end_s]
    assert tuple(r.name for r in kids) == SPARSE_PHASES
    for r, nxt in zip(kids, kids[1:]):
        assert r.end_s <= nxt.start_s      # in order, not overlapping
    assert call.call_id is None and all(r.call_id is None for r in kids)


def test_sparse_phase_programs_have_distinct_names():
    _, _, cell, _, _ = tiny_world(K=K, rounds=T, dim=32)
    cfg = SimConfig(rounds=T, local_iters=2, batch_size=4,
                    local_mode="participants", data_stream="client")
    a = build_participation_program(
        as_policy_fn(RandomScheme(p_bar=0.4, num_clients=K)), cfg, cell, K,
        bucket=4)
    b = build_sparse_train_program(mlp_loss, mlp_accuracy, sgd(0.1), cfg)
    assert (a.__name__, b.__name__) == ("phase_a_program", "phase_b_program")

"""The runner host path's readers on hand-made span records: the mean
prepare and readback time over the window's calls, the device→host copies
per call, and nothing at all where the program kept no call record."""
from __future__ import annotations

import pytest

from chipbench_tiny import ROOT  # noqa: F401  (puts the repo on sys.path)

from chipbench import bench
from repro.obs import telemetry as obs

READERS = ("prepare_ms.sim", "readback_ms.sim", "d2h_per_call.sim")


@pytest.fixture
def tel(monkeypatch):
    """A fresh telemetry in the program's place."""
    t = obs.Telemetry()
    monkeypatch.setattr(obs, "_TELEMETRY", t)
    return t


def _calls(tel, prepare_s, readback_s, copies=6):
    """One recorded runner call per entry, as the dense runner makes it."""
    for p, r in zip(prepare_s, readback_s):
        cid = tel.inc("engine.calls")
        t0 = 10.0 * cid
        tel.ring.append(obs.SpanRecord("engine.prepare", cid, "engine.call",
                                       t0, t0 + p))
        tel.ring.append(obs.SpanRecord("engine.readback", cid,
                                       "engine.call", t0 + 1, t0 + 1 + r))
        tel.ring.append(obs.SpanRecord("engine.call", cid, None, t0,
                                       t0 + 2 + r))
        tel.inc("engine.d2h_transfers", copies)


@pytest.mark.parametrize("name,want", [
    # two warm-up calls, then a window of three: only the window counts
    ("prepare_ms.sim", 1e3 * (0.002 + 0.003 + 0.004) / 3),
    ("readback_ms.sim", 1e3 * (0.02 + 0.03 + 0.04) / 3),
    ("d2h_per_call.sim", 6.0),
])
def test_runner_path_readers_on_hand_made_records(tel, name, want):
    _calls(tel, [0.5, 0.5, 0.002, 0.003, 0.004], [0.5, 0.5, 0.02, 0.03,
                                                  0.04])
    got = bench.load_reader(name).read({"counts": {"calls": 3}})
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_runner_path_readers_give_nothing_without_records(tel, name):
    assert bench.load_reader(name).read({"counts": {"calls": 3}}) is None
    # counters alone, with no ``engine.call`` span, are no record either
    tel.inc("engine.calls", 2)
    tel.inc("engine.d2h_transfers", 12)
    assert bench.load_reader(name).read({"counts": {"calls": 3}}) is None


@pytest.mark.parametrize("name", READERS)
def test_runner_path_readers_give_nothing_for_a_program_without_the_ring(
        monkeypatch, name):
    class Older:                     # spans as an aggregate alone
        counters = {"engine.calls": 4}
        spans = {"engine.execute": [4, 0.1, 0.05]}

    monkeypatch.setattr(obs, "_TELEMETRY", Older())
    assert bench.load_reader(name).read({"counts": {"calls": 3}}) is None

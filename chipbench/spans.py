"""The program's own call spans and counters, read back after the window.

The dense runner records every call in ``repro.obs.telemetry``'s span ring:
an ``engine.call`` span whose id is the call's number, and child spans
(``engine.prepare``, ``engine.dispatch``, ``engine.wait``,
``engine.readback``) that carry the same id.  The window's calls are the
last ``counts["calls"]`` ids: the warm-up calls come before them and
nothing calls the program after the window.  A program that keeps no such
record gives ``None`` here, and so do the readers built on it.
"""
from __future__ import annotations

CALL = "engine.call"


def telemetry():
    """The program's telemetry, or ``None`` where it keeps no span record
    or recorded no runner call."""
    from repro.obs import telemetry as obs

    tel = obs.get_telemetry()
    if not callable(getattr(tel, "records", None)) or not tel.records(CALL):
        return None
    return tel


def window_calls(tel, calls: int) -> set:
    """The ids of the window's ``calls`` runner calls."""
    ids = sorted({r.call_id for r in tel.records(CALL)})
    return set(ids[-calls:]) if calls > 0 else set()


def mean_ms(ctx, name: str):
    """Mean duration in ms of span ``name`` over the window's calls."""
    tel = telemetry()
    if tel is None:
        return None
    ids = window_calls(tel, ctx["counts"]["calls"])
    secs = [r.end_s - r.start_s for r in tel.records(name)
            if r.call_id in ids]
    return 1e3 * sum(secs) / len(secs) if secs else None

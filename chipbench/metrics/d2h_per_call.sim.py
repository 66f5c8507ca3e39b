"""Device→host copies per runner call: the program's counters
``engine.d2h_transfers`` over ``engine.calls``.  Every call has the same
shapes, so the warm-up calls count as any other."""
from chipbench import spans


def read(ctx):
    tel = spans.telemetry()
    if tel is None:
        return None
    calls = tel.counters.get("engine.calls", 0)
    if not calls:
        return None
    return tel.counters.get("engine.d2h_transfers", 0) / calls

"""Mean host time of the dense runner's ``engine.readback`` span over the
window's calls: the device→host copies of the call's traces but the first
and their conversion into a ``SimResult``.  The first copy, which waits for
the device to finish, is ``engine.wait``'s."""
from chipbench import spans


def read(ctx):
    return spans.mean_ms(ctx, "engine.readback")

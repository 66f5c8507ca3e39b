"""Mean host time of the dense runner's ``engine.prepare`` span over the
window's calls: the call's PRNG key, the channel's transpose and its copy to
the device, and the hoisted policy where there is one."""
from chipbench import spans


def read(ctx):
    return spans.mean_ms(ctx, "engine.prepare")

"""Idle ms of the device per execution of the decode program while the host
was in ``loop.tick`` or ``loop.admit`` (the tick fault site, a pending weight
swap, deadline shedding, admission, the gauges).  Layer: engine scheduler."""

from benchmark.lib import spans


def read(art, ctx):
    return spans.idle_ms(art, ("loop.tick", "loop.admit"))

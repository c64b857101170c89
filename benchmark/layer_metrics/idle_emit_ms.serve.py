"""Idle ms of the device per execution of the decode program while the host
was in ``step.emit`` (the metric records, the per-lane loop, finished requests'
futures).  Layer: engine scheduler."""

from benchmark.lib import spans


def read(art, ctx):
    return spans.idle_ms(art, ("step.emit",))

"""Idle ms of the device per execution of the decode program while the host
was in ``step.fetch``: the device has finished and the tokens have not crossed
yet.  Layer: engine and model step."""

from benchmark.lib import spans


def read(art, ctx):
    return spans.idle_ms(art, ("step.fetch",))

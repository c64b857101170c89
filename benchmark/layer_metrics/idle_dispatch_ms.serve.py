"""Idle ms of the device per execution of the decode program while the host
was in ``step.dispatch`` (the jit call over the parameter tree and the pools,
until it returns).  Layer: engine and model step."""

from benchmark.lib import spans


def read(art, ctx):
    return spans.idle_ms(art, ("step.dispatch",))

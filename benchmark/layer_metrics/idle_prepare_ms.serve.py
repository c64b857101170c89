"""Idle ms of the device per execution of the decode program while the host
was in ``step.prepare`` (the copy-on-write guard, the live table width, the
``xfer.to_device`` puts).  Layer: engine scheduler."""

from benchmark.lib import spans


def read(art, ctx):
    return spans.idle_ms(art, ("step.prepare",))

"""Idle ms of the device per execution of the decode program while the host
was in ``prefill.prepare`` or ``prefill.dispatch`` (one prompt chunk: its
arguments, the jit call, and on the tail chunk the wait for the first token).
Layer: engine scheduler."""

from benchmark.lib import spans


def read(art, ctx):
    return spans.idle_ms(art, ("prefill.prepare", "prefill.dispatch"))

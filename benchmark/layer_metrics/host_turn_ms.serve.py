"""Median over the window's decode turns of the turn's length less its
``step.fetch`` (the wait for the device and the copy out): the host's own work
per token step, from the loop recorder's turn records
(``serving/tracing.py::LoopRecorder``).  Layer: engine scheduler."""

from benchmark.lib import spans


def read(art, ctx):
    return spans.host_turn_ms(art)

"""99th percentile of the gap between one request's successive tokens (a
turn that also ran a prompt chunk, or more), from the loop recorder's
per-token stamps, the later token stamped in the window.  Layer: engine and
model step."""

from benchmark.lib import spans


def read(art, ctx):
    return spans.itl_percentile_ms(art, 0.99)

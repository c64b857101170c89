"""Median gap between one request's successive tokens, from the loop
recorder's per-token stamps, the later token stamped in the window.  Layer:
engine and model step."""

from benchmark.lib import spans


def read(art, ctx):
    return spans.itl_percentile_ms(art, 0.5)

"""Median time to the first token inside the engine, ``first_token -
enqueue`` of the loop recorder's request records finished in the window
(replies do not stream, so no client sees it yet).  Layer: engine
scheduler."""

from benchmark.lib import spans


def read(art, ctx):
    return spans.ttft_percentile_ms(art, 0.5)

"""Median of what the HTTP layer adds around the handler, ``(reply - recv)
- (result - submit)`` of the loop recorder's HTTP records (parse, encode,
write), the replies written in the window.  Layer: HTTP front end."""

from benchmark.lib import spans


def read(art, ctx):
    return spans.http_overhead_percentile_ms(art, 0.5)

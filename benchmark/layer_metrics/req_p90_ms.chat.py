"""90th percentile of client-side request latency (send to last byte) over
all requests completed in the window; the tail that ``req_p50_ms`` leaves out.
Layer: HTTP front end.  Source: the client log."""

from benchmark.lib import readers


def read(art, ctx):
    return readers.latency_percentile(art, 0.9)

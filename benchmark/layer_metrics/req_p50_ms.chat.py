"""Median client-side request latency (send to last byte) over all requests
completed in the window.  ISSUE 25 asked for it end to end; with some 26
requests of 16 sizes in a window the median steps between neighbouring
requests (28,022 and 25,308 ms on two runs of one seed; my chip run, PR 25),
so it stands here until a cell completes hundreds (PERF.md, open questions).
Layer: HTTP front end.  Source: the client log."""

from benchmark.lib import readers


def read(art, ctx):
    return readers.latency_percentile(art, 0.5)

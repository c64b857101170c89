"""Median ms, over the traced decode dispatches, from the end of the step's
execution (on the host's clock, ``lib/dispatch_log.py::fit``) to its
``DCOL_FETCHED``: how long a finished step's tokens take to reach the host.  A
latency: it stays when a later fetch takes the idle time away.  Read at the
middle of the interval the clock fit leaves, so known to half the fit's slack
either way (both ends are printed).  Layer: engine and model step."""

from benchmark.lib import dispatch_log


def read(art, ctx):
    return dispatch_log.token_return_ms(art)

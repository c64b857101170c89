"""Idle ms of the device per execution of a decode program, between two
executions, in which the host neither waited for a finished dispatch nor had
made the next call: it had what it waited for and was at its own work (puts,
guards, the tick).  Behind an execution the host waited for it lies between
two stamps of the host (the fetch, the next call), and the clock fit's slack
does not move it; behind a chunk nobody waited for it begins at a stamp of
the device and moves with the offset (``lib/dispatch_log.py`` prints both
ends).  Named ``idle_host_ms.serve`` in ISSUE 38.  Layer: engine
scheduler."""

from benchmark.lib import dispatch_log


def read(art, ctx):
    return dispatch_log.idle_ms(art, "host")

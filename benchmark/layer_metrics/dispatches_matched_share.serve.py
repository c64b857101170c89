"""Share (%) of the first device's traced executions, of any program, from
the first to the last that the clock fit paired (``lib/dispatch_log.py::fit``:
by program and call order, whatever turn a fetch falls in), that have a
dispatch record of the program's own.  Under 100 the engine sends work to the
device outside ``LoopRecorder.dispatch`` (named on standard error) and the gap
before it has no launch part; None where there is no fit, and then the four
readers on it (``gap_return_ms.serve``, ``gap_launch_ms.serve``,
``gap_host_ms.serve``, ``token_return_ms.serve``) read None too.  Layer:
device."""

from benchmark.lib import dispatch_log


def read(art, ctx):
    return dispatch_log.matched_share(art)

"""Idle ms of the device per execution of a decode program, between two
executions, after ``DCOL_CALL`` of the dispatch that ends the gap: the jit
call's own host time, then the runtime's launch.  Named
``idle_launch_ms.serve`` in ISSUE 38.  Only its sum with
``gap_return_ms.serve`` is measured: the clock fit leaves an interval, this
reading is the interval's middle, and the slack goes half to each of the two
(``lib/dispatch_log.py`` prints both ends).  Layer: engine and model step."""

from benchmark.lib import dispatch_log


def read(art, ctx):
    return dispatch_log.idle_ms(art, "launch")

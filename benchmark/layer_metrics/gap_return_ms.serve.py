"""Idle ms of the device per execution of a decode program, between two
executions, while the host was between ``DCOL_WAIT`` and ``DCOL_FETCHED`` of a
dispatch whose execution had ended: the device has finished and the host does
not know it yet (a decode step's tokens, a tail chunk's first token).  Named
``idle_return_ms.serve`` in ISSUE 38 (``gap_``: ``benchmark/tests/test_spans.py``
counts the names that begin with ``idle_``).  Only its sum with
``gap_launch_ms.serve`` is measured: the clock fit leaves an interval, this
reading is the interval's middle, and the slack goes half to each of the two
(``lib/dispatch_log.py`` prints both ends).  Layer: engine and model step."""

from benchmark.lib import dispatch_log


def read(art, ctx):
    return dispatch_log.idle_ms(art, "return")

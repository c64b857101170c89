"""Median ms of ``DCOL_RETURNED - DCOL_CALL`` over the window's decode
dispatches: the host's time inside the jit call of a decode program (the
chunks' is printed beside it).  The loop recorder's dispatch records alone, no
trace.  Layer: engine and model step."""

from benchmark.lib import dispatch_log


def read(art, ctx):
    found = dispatch_log.jit_call_ms(art)
    return found and found["step"]

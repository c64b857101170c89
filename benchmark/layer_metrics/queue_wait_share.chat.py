"""Share (%) of the finished requests' time in the engine that was spent
waiting for a lane: sum of ``admit - enqueue`` over sum of ``done - enqueue``,
the loop recorder's request records finished in the window.  Layer: engine
scheduler."""

from benchmark.lib import spans


def read(art, ctx):
    return spans.queue_wait_share(art)

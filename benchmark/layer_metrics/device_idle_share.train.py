"""Share (%) of the traced window with no operation on the device, training."""

from benchmark.lib import readers


def read(art, ctx):
    return readers.device_idle_share(art)

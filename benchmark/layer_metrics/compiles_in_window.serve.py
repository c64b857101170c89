"""Backend compilations inside the measured window, serving; must read 0."""

from benchmark.lib import readers


def read(art, ctx):
    return readers.compiles_in_window(art)

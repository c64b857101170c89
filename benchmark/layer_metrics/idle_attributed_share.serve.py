"""Share (%) of the first device's idle seconds, first operation to last of
the trace, that fall inside a phase of a recorded turn once the recorder is on
the trace's clock (``lib/spans.py::fit``); the rest is time no record covers.
Layer: device."""

from benchmark.lib import spans


def read(art, ctx):
    return spans.idle_attributed_share(art)

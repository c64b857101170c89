"""Held experts that a decode step's tokens hit, summed over the expert
layers and averaged over the window's decode turns: what the step program
counted and returned beside its tokens, as the loop recorder kept it per turn
(``serving/tracing.py``, ``COL_MOE_HIT``).  At 32 lanes, 4 of 256 experts a
token and 32 experts held, 12.7 a layer are expected.  Layer: expert layer (ops/moe.py)."""

from benchmark.lib import spans


def read(art, ctx):
    turns = spans.decode_turns(art)
    if turns is None or not len(turns):
        return None
    tracing = spans.recorder(art)["tracing"]
    column = getattr(tracing, "COL_MOE_HIT", None)
    if column is None:
        return None
    return float(turns[:, column].mean())

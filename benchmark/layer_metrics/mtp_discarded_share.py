"""Share (%) of the tokens the verify steps made that no request was owed,
over the window: the server's counters ``spec_tokens_discarded`` over
``spec_tokens_kept + spec_tokens_discarded``.  The host learns a step's count
one fetch late, so a lane whose drafts were accepted ends before the host
knows and rides the steps in flight behind its last token (two at most), and
a second token past ``n_new`` is dropped.  Layer: engine scheduler."""

from benchmark.lib.window import counters_moved


def read(art, ctx):
    moved = counters_moved(art)
    made = moved.get("spec_tokens_kept", 0) \
        + moved.get("spec_tokens_discarded", 0)
    if not made:
        return None
    return 100.0 * moved.get("spec_tokens_discarded", 0) / made

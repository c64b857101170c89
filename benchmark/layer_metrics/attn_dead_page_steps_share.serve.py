"""Page steps that the serving attention kernels skip, over the page steps
they were given (%), summed over the window's turns: what the engine counted
on the host before each dispatch, chunk and decode program alike (lanes x
table width x layers, and of those the pages that hold a key some query row
may see), as the loop recorder kept it per turn (``serving/tracing.py``,
``COL_ATTN_STEPS`` / ``COL_ATTN_LIVE``).  A dead step costs neither a fetch
nor a softmax step; a program that walks every page has no such columns and
the metric is left out.  Layer: Pallas kernels (ops/pallas_kernels.py)."""

from benchmark.lib import spans


def read(art, ctx):
    found = spans.recorder(art)
    if found is None:
        return None
    t, turns = found["tracing"], found["turns"]
    if not hasattr(t, "COL_ATTN_STEPS"):
        return None
    lo, hi = spans.window_ns(art)
    inside = turns[(turns[:, t.COL_STAMPS] >= lo) & (turns[:, t.COL_END] <= hi)]
    given = int(inside[:, t.COL_ATTN_STEPS].sum())
    if not given:
        return None
    return 100.0 * (1.0 - int(inside[:, t.COL_ATTN_LIVE].sum()) / given)

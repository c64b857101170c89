"""Share (%) of the time between the first and the last epoch end in the
window that the epoch driver spent blocked on the window stager (its
``staging_stall_s``, read at those epoch ends).  Layer: input path."""


def read(art, ctx):
    return art["counters"].get("stage_stall_share")

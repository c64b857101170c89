"""Tokens a lane a decode step that reached a request, over the window: the
server's counters ``spec_tokens_kept`` (tokens of verify steps that a
request was still owed) over ``spec_lane_steps`` (live lanes summed over the
decode dispatches).  1 + the acceptance, less what was discarded
(``mtp_discarded_share``); 1 without a module.  Layer: engine scheduler."""

from benchmark.lib.window import counters_moved


def read(art, ctx):
    moved = counters_moved(art)
    if not moved.get("spec_lane_steps"):
        return None
    return moved.get("spec_tokens_kept", 0) / moved["spec_lane_steps"]

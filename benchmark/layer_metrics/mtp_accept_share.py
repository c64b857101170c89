"""Share (%) of the module's drafts that the verify step accepted, over the
window: the server's counters ``draft_accepted`` / ``draft_tokens``
(``/metrics.json``, sampled through the window; one draft a live lane a
step, counted when the step's count reaches the host).  A trained module
reads 85-90 (DeepSeek-V3, section 5.4.3); the configuration's ``mtp_init``
says what the seeded one was calibrated to.  Layer: engine and model step."""

from benchmark.lib.window import counters_moved


def read(art, ctx):
    moved = counters_moved(art)
    if not moved.get("draft_tokens"):
        return None
    return 100.0 * moved.get("draft_accepted", 0) / moved["draft_tokens"]

"""Share (%) of the engine's lanes that hold a request, averaged over samples
of its ``slots_busy`` gauge (``/metrics.json``) taken through the window.
Below 100 with clients waiting means admission held requests back (at 16
lanes on 320 pages a 40-page request at the queue's head did: PERF.md).
Layer: engine scheduler.  Source: the program's own gauge."""


def read(art, ctx):
    samples = art["counters"].get("slots_busy") or []
    if not samples:
        return None
    return 100.0 * sum(samples) / len(samples) / art["counters"]["slots"]

"""What the server's counters moved by over the measured window, for the
readers and drivers that compare counters (``serve_lm_record.Driver`` keeps
every sample of ``/metrics.json`` taken inside the window)."""


def counters_moved(art):
    """{counter: last sample - first sample} over the window's samples of
    ``/metrics.json`` ({} without two samples: a driver that keeps none,
    a program that has no such counter)."""
    samples = art.get("metrics_samples") or []
    if len(samples) < 2:
        return {}
    first, last = samples[0]["counters"], samples[-1]["counters"]
    return {k: v - first.get(k, 0) for k, v in last.items()}

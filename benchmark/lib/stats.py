"""``percentile`` is ``tools/load_gen.py``'s nearest-rank rule, copied."""


def percentile(values, q):
    """Nearest-rank percentile of ``values`` (any order); None if empty."""
    vals = sorted(values)
    if not vals:
        return None
    return vals[min(len(vals) - 1, int(q * len(vals)))]

"""Pages that the sliding layers' pool holds over the pages that the full
layers' pool holds (%), averaged over the window's samples of
``/metrics.json`` (gauges ``kv_pages_free.window`` / ``kv_pages_free.full``
against the pools' totals): both pools serve the same lanes, the full layers'
holds every lane's whole span, the sliding layers' only what the window still
reaches.  Layer: KV pool."""


def read(art, ctx):
    held_window = held_full = 0.0
    for snap in art.get("metrics_samples") or ():
        g = snap["gauges"]
        if "kv_pages_free.window" not in g:
            return None
        held_window += g["kv_pages_total.window"] - g["kv_pages_free.window"]
        held_full += g["kv_pages_total"] - g["kv_pages_free.full"]
    if not held_full:
        return None
    return 100.0 * held_window / held_full

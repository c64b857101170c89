"""Bytes of recurrent and convolution state that the occupied lanes hold over
those plus the bytes of the KV pages the lanes hold (%), averaged over the
window's samples of ``/metrics.json``: gauges ``state_slots_total`` /
``state_slots_free`` x ``state_bytes_per_lane`` against ``kv_pages_total`` /
``kv_pages_free`` x page x ``kv_bytes_per_token``.  A lane's state is a fixed
size whatever it holds; its pages are its reservation (prompt and answer).
Which of the two kinds of cache decides how many lanes fit.  Layer: KV
pool."""


def read(art, ctx):
    page = ctx.config["deployment"]["prefill_chunk"]
    state = pages = 0.0
    for snap in art.get("metrics_samples") or ():
        g = snap["gauges"]
        if "state_slots_free" not in g:
            return None
        state += (g["state_slots_total"] - g["state_slots_free"]) \
            * g["state_bytes_per_lane"]
        pages += (g["kv_pages_total"] - g["kv_pages_free"]) * page \
            * g["kv_bytes_per_token"]
    if not state + pages:
        return None
    return 100.0 * state / (state + pages)

"""Share (%) of its roofline that the paged flash-decode kernel reaches on the
SLIDING layers: the least time its calls on those layers in the traced window
could take on the published peaks (``rooflines/gqa_window_decode.py``: 4 KB a
visible token in bfloat16, a lane's tokens capped at ``sliding_window``) over
the device time they took.  The kernel's calls are the operations named
``attn ...`` inside ``jit_step_all`` whose result is the queries' shape (the
profiler names a Pallas call by the scope around it, ``attn.window`` /
``attn.full``, which the trace reduction cuts to ``attn``), one per layer in
layer order; which of a step's calls are the sliding layers' is the
configuration's ``layer_types``.  The tokens each lane holds come from the client log.
Layer: Pallas kernels."""

from benchmark.lib import trace as trace_lib
from benchmark.lib.files import load_module


def is_kernel(op, cfg):
    # ``attn bf16[lanes,kv heads,query heads per kv head,head]``: the flash
    # kernel's output; the row write under the same scope returns a pool
    return (op.module in ("step_all", "jit_step_all")
            and op.name.startswith("attn ")
            and op.name.endswith("[%d,%d,%d,%d]" % (
                cfg["deployment"]["slots"], cfg["num_key_value_heads"],
                cfg["num_attention_heads"] // cfg["num_key_value_heads"],
                cfg["head_dim"])))


def mean_visible_tokens(log, t0, t1, window, samples=200):
    """(mean visible cached tokens summed over the requests in flight, each
    capped at ``window``; mean requests in flight) over host times [t0, t1];
    a request's cache grows linearly from its prompt to prompt + n_new
    between send and reply (``lib/serving.py::mean_live_tokens``)."""
    tokens = lanes = 0.0
    for i in range(samples):
        t = t0 + (t1 - t0) * (i + 0.5) / samples
        for r in log:
            done = r["t_done"]
            if r["t_send"] <= t and (done is None or done > t):
                share = ((t - r["t_send"]) / (done - r["t_send"])
                         if done is not None else 0.5)
                tokens += min(r["prompt_len"] + share * r["n_new"], window)
                lanes += 1
    return tokens / samples, lanes / samples


def read(art, ctx):
    trace = art["trace"]
    cfg = ctx.config
    kinds = cfg.get("layer_types")
    if not trace["devices"] or not art.get("trace_host_window") or not kinds:
        return None
    kernels = sorted((o for o in trace["devices"][0]["ops"]
                      if is_kernel(o, cfg)), key=lambda o: o.start)
    sliding, at = [], 0
    for run in sorted(trace_lib.module_executions(trace, "step_all"),
                      key=lambda m: m.start):
        while at < len(kernels) and kernels[at].start < run.start:
            at += 1
        end = at
        while end < len(kernels) \
                and kernels[end].start < run.start + run.dur:
            end += 1
        if end - at == len(kinds):          # a whole step in the trace
            sliding += [o for o, kind in zip(kernels[at:end], kinds)
                        if kind == "sliding_attention"]
        at = end
    seconds = sum(o.self_dur for o in sliding) / 1e9
    if not sliding or not seconds:
        return None
    t0, t1 = art["trace_host_window"]
    tokens, lanes = mean_visible_tokens(art["client_log"], t0, t1,
                                        cfg["sliding_window"])
    roofline = load_module("rooflines", "gqa_window_decode")
    least = len(sliding) * roofline.roofline_seconds(cfg, lanes, tokens,
                                                     ctx.peaks())
    return 100.0 * least / seconds

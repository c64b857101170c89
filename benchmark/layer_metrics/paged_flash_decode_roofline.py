"""Share (%) of its roofline that the paged flash-decode kernel reaches: the
least time its calls in the traced window could take on the published peaks
(``rooflines/paged_flash_decode.py``; bytes bound it) over the device time
they took.  The kernel's calls are the operations named ``step_all ...``
inside the decode program ``jit_step_all``; the tokens each call must read
come from the client log (``lib/serving.py``).  Layer: Pallas kernels."""

from benchmark.lib import serving
from benchmark.lib.files import load_module


def is_kernel(op):
    # the profiler names a Pallas call after the jitted function around it:
    # ``step_all f32[lanes,heads,1,head]`` (``lib/trace.py::short_name``)
    return (op.module in ("step_all", "jit_step_all")
            and op.name.startswith("step_all "))


def read(art, ctx):
    trace = art["trace"]
    if not trace["devices"] or not art.get("trace_host_window"):
        return None
    calls = [o for o in trace["devices"][0]["ops"] if is_kernel(o)]
    seconds = sum(o.self_dur for o in calls) / 1e9
    if not calls or not seconds:
        return None
    peaks = ctx.peaks()
    t0, t1 = art["trace_host_window"]
    tokens, lanes = serving.mean_live_tokens(art["client_log"], t0, t1)
    roofline = load_module("rooflines", "paged_flash_decode")
    least = len(calls) * roofline.roofline_seconds(
        ctx.config, lanes, tokens, peaks)
    return 100.0 * least / seconds

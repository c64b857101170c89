"""Share (%) of its roofline that the expert layers' grouped matmuls reach in
the decode program: the least time the traced steps' grouped matmuls could
take on the published peaks (``rooflines/moe_grouped_matmul.py``; the bytes
are those of the experts actually HIT, from the step's own count in the loop
recorder, plus the assignment rows in and out) over the device time of the
``ragged-dot`` operations in ``jit_step_all`` (what ``jax.lax.ragged_dot``
lowers to on the chip: three a layer).  Layer: expert layer (ops/moe.py)."""

from benchmark.lib import spans, trace as trace_lib
from benchmark.lib.files import load_module


def is_grouped(op):
    return (op.module in ("step_all", "jit_step_all")
            and op.name.startswith("ragged-dot"))


def traced_turns(art):
    """(the recorder's module, its decode turns inside the host's traced
    window), or None where the program keeps no expert counts."""
    found = spans.recorder(art)
    if found is None or not art.get("trace_host_window"):
        return None
    t, turns = found["tracing"], found["turns"]
    if not hasattr(t, "COL_MOE_HIT"):
        return None
    lo, hi = (int(x * 1e9) for x in art["trace_host_window"])
    keep = (turns[:, t.COL_STEP_PROGRAM] > 0) \
        & (turns[:, t.COL_STAMPS] >= lo) & (turns[:, t.COL_END] <= hi)
    return t, turns[keep]


def read(art, ctx):
    trace = art["trace"]
    if not trace["devices"]:
        return None
    seconds = sum(o.self_dur for o in trace["devices"][0]["ops"]
                  if is_grouped(o)) / 1e9
    steps = len(trace_lib.module_executions(trace, "step_all"))
    found = traced_turns(art)
    if not seconds or not steps or found is None or not len(found[1]):
        return None
    t, turns = found
    cfg = ctx.config
    layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    rows_all = cfg["deployment"]["slots"] * cfg["num_experts_per_tok"]
    roofline = load_module("rooflines", "moe_grouped_matmul")
    # per step, the layers' sums; the roofline is linear in them
    hit = float(turns[:, t.COL_MOE_HIT].mean())
    held = float(turns[:, t.COL_MOE_HELD].mean())
    least = steps * layers * roofline.roofline_seconds(
        cfg, hit / layers, held / layers, rows_all, ctx.peaks())
    return 100.0 * least / seconds

"""Share (%) of its roofline that the expert layers' grouped matmuls reach in
a VERIFY step, where they are the row-tiled kernel: a step that feeds
``spec_k + 1`` rows a lane hands ``ops/moe.py::held_part`` ``slots x (spec_k +
1) x num_experts_per_tok`` assignment rows (512 in ``joyai-llm-flash-ep8``),
over ``ROW_KERNEL_MIN``, so gate, up and down are three calls of
``pallas_kernels.grouped_matmul`` a layer and no ``ragged-dot`` (which is what
``moe_grouped_matmul_roofline`` reads).  The least time the traced steps'
grouped matmuls could take on the published peaks
(``rooflines/moe_grouped_matmul.py``, as it stands: the three matrices of each
expert HIT, once, from the step's own count in the loop recorder, plus the
assignment rows in and out; bytes bound it) over the device time, in
``jit_step_all``, of the Pallas calls under the scope ``moe.experts``
(``lib/trace.py::short_name`` shows them as ``moe ...``).  The layers are the
stack's expert layers and the module's (the recorder's count sums over both).
Layer: expert layer (ops/moe.py)."""

from benchmark.lib import latent, trace as trace_lib
from benchmark.lib.files import load_module


def is_grouped(op):
    return op.module in latent.DECODE and op.name.startswith("moe ")


def read(art, ctx):
    trace = art.get("trace")
    cfg = ctx.config
    rows = cfg.get("deployment", {}).get("spec_k", 0) + 1
    if not trace or not trace["devices"] or rows < 2 \
            or "moe_intermediate_size" not in cfg:
        return None
    seconds = trace_lib.self_seconds(trace, is_grouped)
    steps = len(trace_lib.module_executions(trace, "step_all"))
    found = load_module(
        "layer_metrics", "moe_grouped_matmul_roofline").traced_turns(art)
    if not seconds or not steps or found is None or not len(found[1]):
        return None
    t, turns = found
    layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"] \
        + cfg.get("num_nextn_predict_layers", 0)
    rows_all = cfg["deployment"]["slots"] * rows * cfg["num_experts_per_tok"]
    # per step, the layers' sums; the roofline is linear in them
    hit = float(turns[:, t.COL_MOE_HIT].mean())
    held = float(turns[:, t.COL_MOE_HELD].mean())
    least = steps * layers * load_module(
        "rooflines", "moe_grouped_matmul").roofline_seconds(
            cfg, hit / layers, held / layers, rows_all, ctx.peaks())
    return 100.0 * least / seconds

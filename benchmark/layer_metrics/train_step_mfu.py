"""Model FLOP/s utilisation (%) of the window program: the operations that
the traced window programs' minibatches require (``rooflines/train_step.py``:
convolutions and dense layers, forward and backward, nothing recomputed) over
the published bf16 peak, divided by the device time those programs took.
It stands where ISSUE 25 asked for ``conv_roofline``: the profiler names a
convolution fusion ``fusion`` like any other and gives no category, so the
convolutions' own time cannot be read yet (PERF.md, open questions).
Layer: ops."""

from benchmark.lib import trace as trace_lib
from benchmark.lib.files import load_module


def read(art, ctx):
    runs = trace_lib.module_executions(art["trace"], "_epoch_train")
    seconds = sum(m.dur for m in runs) / 1e9
    if not runs or not seconds:
        return None
    minibatches = len(runs) * int(ctx.traffic["window_minibatches"])
    least = load_module("rooflines", "train_step").roofline_seconds(
        ctx.config, minibatches, ctx.peaks())
    return 100.0 * least / seconds

"""Device milliseconds per minibatch of the window program
(``FusedRunner.window_scan_fn``, traced as ``jit__epoch_train``): the mean
duration of its executions in the traced window over the minibatches one
execution scans.  Validation passes are other programs and are not in it.
Layer: fused step and epoch scan."""

from benchmark.lib import readers


def read(art, ctx):
    window_ms = readers.module_mean_ms(art, "_epoch_train")
    if window_ms is None:
        return None
    return window_ms / int(ctx.traffic["window_minibatches"])

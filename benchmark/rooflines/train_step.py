"""Operations of one training step on one minibatch: the convolutions and the
dense layers, forward, input gradient and weight gradient (the first layer
has no input gradient), from the configuration's shapes (``lib/flops.py``,
the convention of ``bench.py``).  Compute bounds the step at these shapes, so
its roofline time is operations over the published bf16 peak
(``lib/peaks.py``).  The share is of that published peak even though the work
is float32 at HIGHEST, which the MXU runs as six bf16 passes: such work cannot
pass about a sixth of it."""

from benchmark.lib import flops


def flops_per_minibatch(cfg):
    per_sample = flops.train_flops_per_sample(
        cfg["layers"], tuple(cfg["crop"]), cfg["image"][2])
    return per_sample * cfg["minibatch"]


def roofline_seconds(cfg, minibatches, peaks):
    return minibatches * flops_per_minibatch(cfg) / peaks["bf16_flops_s"]

"""Share (%) of its roofline that the state-space rule reaches over a prompt
chunk: the least time the traced chunks' Mamba layers could take on the
published peaks (``rooflines/ssd_chunk.py``: the chunked form's flops as the
layer's equations write them, each row's inputs in and outputs out at the
model's width, the state in and out; bytes bound it) over the device time of
the chunk kernel's calls, the operations named ``ssd ...`` inside
``jit_chunk_slot`` (``lib/ssd.py``), one per Mamba layer and chunk.  Every call
is counted at the chunk's whole rows (a prompt's last chunk is padded).  What
precedes the kernel (``C B^T``, the running sums of the decay: plain XLA) is in
the flops and not in the time; it is a hundredth of them.  It reads LOW: the
kernel's matmuls are float32 at the highest precision; that is the finding,
not a fault.  Layer: Pallas kernels."""

from benchmark.lib import ssd
from benchmark.lib.files import load_module


def read(art, ctx):
    cfg = ctx.config
    if not ssd.has_ssd(cfg):
        return None
    calls = ssd.kernel_calls(art, ssd.is_chunk_kernel)
    seconds = sum(o.self_dur for o in calls) / 1e9
    if not calls or not seconds:
        return None
    roofline = load_module("rooflines", "ssd_chunk")
    least = len(calls) * roofline.roofline_seconds(
        cfg, cfg["deployment"]["prefill_chunk"], ssd.INNER, ctx.peaks())
    return 100.0 * least / seconds

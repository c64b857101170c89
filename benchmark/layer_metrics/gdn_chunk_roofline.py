"""Share (%) of its roofline that the gated delta rule reaches over a prompt
chunk: the least time the traced chunks' linear layers could take on the
published peaks (``rooflines/gdn_chunk.py``: the flops of the RECURRENT form,
each row's q, k, v in and output out, the state in and out: some 36 us a
1024-row call) over the device time of the sequential pass's calls, the
operations named ``attn f32[1, value heads, inner chunks, 64, value dim]``
inside ``jit_chunk_slot`` (``lib/linear.py``), one per linear layer and chunk.
Every call is counted at the chunk's whole rows (a prompt's last chunk is
padded: 2 % of this table's rows).  What precedes the pass (the intra-chunk
products and the triangular solve, plain XLA) is in neither the time nor the
flops.  It reads LOW: 16 dependent steps a head of four small float32 dots
each; that is the finding, not a fault.  Layer: Pallas kernels."""

from benchmark.lib import linear
from benchmark.lib.files import load_module


def read(art, ctx):
    cfg = ctx.config
    if not linear.has_linear(cfg):
        return None
    calls = linear.kernel_calls(
        art, lambda o: linear.is_chunk_kernel(o, cfg))
    seconds = sum(o.self_dur for o in calls) / 1e9
    if not calls or not seconds:
        return None
    roofline = load_module("rooflines", "gdn_chunk")
    least = len(calls) * roofline.roofline_seconds(
        cfg, cfg["deployment"]["prefill_chunk"], ctx.peaks())
    return 100.0 * least / seconds

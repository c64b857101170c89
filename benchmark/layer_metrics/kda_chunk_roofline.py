"""Share (%) of its roofline that Kimi delta attention reaches over a prompt
chunk: the least time the traced chunks' KDA layers could take on the
published peaks (``rooflines/kda_chunk.py``: the flops of the RECURRENT form,
each row's q, k, v and decay in and output out, the state in and out) over the
device time of the sequential pass's calls, the operations named ``kda f32[1,
heads, inner chunks, 64, head_dim]`` inside ``jit_chunk_slot``
(``lib/kda.py``), one per KDA layer and chunk.  Every call is counted at the
chunk's whole rows (a prompt's last chunk is padded).  What precedes the pass
(the pairwise decays about reference rows, the intra-chunk products and the
triangular solve, plain XLA) is in neither the time nor the flops.  It reads
LOW: 16 dependent steps a head of four small float32 dots each; that is the
finding, not a fault.  Layer: Pallas kernels."""

from benchmark.lib import kda
from benchmark.lib.files import load_module


def read(art, ctx):
    cfg = ctx.config
    if not kda.has_kda(cfg):
        return None
    calls = kda.kernel_calls(art, lambda o: kda.is_chunk_kernel(o, cfg))
    seconds = sum(o.self_dur for o in calls) / 1e9
    if not calls or not seconds:
        return None
    roofline = load_module("rooflines", "kda_chunk")
    least = len(calls) * roofline.roofline_seconds(
        cfg, cfg["deployment"]["prefill_chunk"], ctx.peaks())
    return 100.0 * least / seconds

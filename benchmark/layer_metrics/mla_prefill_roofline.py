"""Share (%) of its roofline that the expanded latent-attention prefill kernel
reaches: the least time its calls in the traced window could take on the
published peaks (``rooflines/mla_prefill.py``: 2 x heads x (192 + 128) flops a
visible (query, key) pair; compute bounds it; the re-expansion of cached
latents is not counted) over the device time they took.  The kernel's calls
are the operations named ``attn ...`` inside ``jit_chunk_slot`` whose result
is the heads' outputs' shape (``lib/latent.py``), one per layer per chunk.
Where each traced chunk started is not in the trace: the loop recorder says in
which turns a chunk went and which requests were in prefill then, and the
engine's round robin gives each an equal share of those turns
(``lib/latent.py::traced_chunks``); the least time of the expected starts is
scaled to the number of chunks the trace really holds.  Layer: Pallas
kernels."""

from benchmark.lib import latent
from benchmark.lib.files import load_module


def read(art, ctx):
    cfg = ctx.config
    if "kv_lora_rank" not in cfg:
        return None
    calls = latent.kernel_calls(
        art, lambda o: latent.is_prefill_kernel(o, cfg))
    seconds = sum(o.self_dur for o in calls) / 1e9
    chunk = cfg["deployment"]["prefill_chunk"]
    starts = latent.traced_chunks(art, chunk)
    if not calls or not seconds or not starts:
        return None
    roofline = load_module("rooflines", "mla_prefill")
    peaks = ctx.peaks()
    per_chunk = sum(roofline.roofline_seconds(cfg, s, chunk, peaks)
                    for s in starts) / len(starts)
    return 100.0 * len(calls) * per_chunk / seconds

"""Share (%) of its roofline that the paged flash-decode kernel reaches on the
FULL layers of a stack whose other layers hold no pages: the least time its
calls in the traced window could take on the published peaks
(``rooflines/gqa_full_decode.py``: 2048 bytes a cached token of a DECODING
lane a layer at 2 KV heads of 256 in bfloat16; bytes bound it) over the device
time they took.  The calls are the operations named ``attn ...[lanes, kv
heads, queries a kv head, head_dim]`` inside ``jit_step_all``
(``lib/linear.py``); the tokens the decoding lanes hold at each traced step
come from the loop recorder's request records.  Lanes still in prefill ride
the step and are read but not counted, so it reads low while many prefill,
never high.  Layer: Pallas kernels."""

from benchmark.lib import latent, linear
from benchmark.lib.files import load_module


def read(art, ctx):
    cfg = ctx.config
    if not linear.has_linear(cfg):
        return None
    calls = linear.kernel_calls(
        art, lambda o: linear.is_full_decode_kernel(o, cfg))
    seconds = sum(o.self_dur for o in calls) / 1e9
    held = latent.decoding_tokens(art)
    if not calls or not seconds or held is None:
        return None
    tokens, lanes = held
    roofline = load_module("rooflines", "gqa_full_decode")
    least = len(calls) * roofline.roofline_seconds(cfg, lanes, tokens,
                                                   ctx.peaks())
    return 100.0 * least / seconds

"""Share (%) of its roofline that the absorbed latent-attention decode kernel
reaches: the least time its calls in the traced window could take on the
published peaks (``rooflines/mla_latent_decode.py``: 1152 bytes a cached token
of a decoding lane, once for all heads; bytes bound it) over the device time
they took.  The kernel's calls are the operations named ``attn ...`` inside
``jit_step_all`` whose result is the absorbed outputs' shape
(``lib/latent.py``); the tokens the decoding lanes hold at each traced step
come from the loop recorder's request records (prompt and tokens emitted so
far).  Lanes still in prefill ride the step program and the kernel reads what
they hold, but no decode step needs it: it is left out of the least time, so
the share reads low while many lanes prefill, never high.  Layer: Pallas
kernels."""

from benchmark.lib import latent
from benchmark.lib.files import load_module


def read(art, ctx):
    cfg = ctx.config
    if "kv_lora_rank" not in cfg:
        return None
    calls = latent.kernel_calls(
        art, lambda o: latent.is_decode_kernel(o, cfg))
    seconds = sum(o.self_dur for o in calls) / 1e9
    held = latent.decoding_tokens(art)
    if not calls or not seconds or held is None:
        return None
    tokens, lanes = held
    roofline = load_module("rooflines", "mla_latent_decode")
    least = len(calls) * roofline.roofline_seconds(cfg, lanes, tokens,
                                                   ctx.peaks())
    return 100.0 * least / seconds

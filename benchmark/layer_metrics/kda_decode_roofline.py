"""Share (%) of its roofline that Kimi delta attention's recurrent decode
kernel reaches: the least time its calls in the traced window could take on
the published peaks (``rooflines/kda_decode.py``: 4 MiB of state in and out a
DECODING lane and layer, its convolution tail and rows beside; bytes bound it)
over the device time they took.  The kernel's calls are the operations named
``kda f32[lanes, heads, head_dim]`` inside ``jit_step_all`` (``lib/kda.py``),
one per KDA layer and step; the lanes that decode at each traced step come
from the loop recorder's request records, as ``gdn_decode_roofline`` takes
them.  The convolution step runs beside the kernel (plain XLA) and its time is
not in the kernel's: its bytes are 2 % of the least time.  Layer: Pallas
kernels."""

from benchmark.lib import kda, latent
from benchmark.lib.files import load_module


def read(art, ctx):
    cfg = ctx.config
    if not kda.has_kda(cfg):
        return None
    calls = kda.kernel_calls(art, lambda o: kda.is_decode_kernel(o, cfg))
    seconds = sum(o.self_dur for o in calls) / 1e9
    held = latent.decoding_tokens(art)
    if not calls or not seconds or held is None:
        return None
    _, lanes = held
    roofline = load_module("rooflines", "kda_decode")
    least = len(calls) * roofline.roofline_seconds(cfg, lanes, ctx.peaks())
    return 100.0 * least / seconds

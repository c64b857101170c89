"""Share (%) of its roofline that the gated delta rule's recurrent decode
kernel reaches: the least time its calls in the traced window could take on
the published peaks (``rooflines/gdn_decode.py``: 4 MiB of state in and out a
DECODING lane and layer, its convolution tail and rows beside; bytes bound it)
over the device time they took.  The kernel's calls are the operations named
``attn f32[lanes, value heads, value dim]`` inside ``jit_step_all``
(``lib/linear.py``), one per linear layer and step; the lanes that decode at
each traced step come from the loop recorder's request records, as
``mla_decode_roofline`` takes them.  The convolution step runs beside the
kernel (plain XLA on 64 x 8192 numbers) and its time is not in the kernel's:
its bytes are 2 % of the least time.  Layer: Pallas kernels."""

from benchmark.lib import latent, linear
from benchmark.lib.files import load_module


def read(art, ctx):
    cfg = ctx.config
    if not linear.has_linear(cfg):
        return None
    calls = linear.kernel_calls(
        art, lambda o: linear.is_decode_kernel(o, cfg))
    seconds = sum(o.self_dur for o in calls) / 1e9
    held = latent.decoding_tokens(art)
    if not calls or not seconds or held is None:
        return None
    _, lanes = held
    roofline = load_module("rooflines", "gdn_decode")
    least = len(calls) * roofline.roofline_seconds(cfg, lanes, ctx.peaks())
    return 100.0 * least / seconds

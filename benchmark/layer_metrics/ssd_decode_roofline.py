"""Share (%) of its roofline that the state-space rule's recurrent decode
kernel reaches: the least time its calls in the traced window could take on
the published peaks (``rooflines/ssd_decode.py``: 4 MiB of state in and out a
DECODING lane and layer, its row's inputs and outputs beside; bytes bound it)
over the device time they took.  The kernel's calls are the operations named
``ssd ...`` inside ``jit_step_all`` (``lib/ssd.py``), one per Mamba layer and
step; the lanes that decode at each traced step come from the loop recorder's
request records, as ``gdn_decode_roofline`` takes them.  The convolution step
runs beside the kernel (plain XLA): neither its time nor its bytes are
counted.  Layer: Pallas kernels."""

from benchmark.lib import latent, ssd
from benchmark.lib.files import load_module


def read(art, ctx):
    cfg = ctx.config
    if not ssd.has_ssd(cfg):
        return None
    calls = ssd.kernel_calls(art, ssd.is_decode_kernel)
    seconds = sum(o.self_dur for o in calls) / 1e9
    held = latent.decoding_tokens(art)
    if not calls or not seconds or held is None:
        return None
    _, lanes = held
    roofline = load_module("rooflines", "ssd_decode")
    least = len(calls) * roofline.roofline_seconds(cfg, lanes, ctx.peaks())
    return 100.0 * least / seconds

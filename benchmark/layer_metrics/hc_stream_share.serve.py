"""Share (%) of the device's busy time in operations whose result is the
n-stream residual or an mHC coefficient, decode and prefill programs alike:
by opcode and result shape, as ``moe_ffn_share.serve`` keys on ``ragged-dot``.
Counted: float32 results shaped ``[.., hc_mult, hidden_size]``, ``[.., hc_mult
x hidden_size]`` (the stream, flattened for its projections) or ``[lanes,
rows, 1, hidden_size]`` (ONE stream of a mix: the compiler writes the new
stream a slab at a time), ``[.., 2 hc_mult + hc_mult^2]`` (the three
projections) and ``[.., hc_mult, hc_mult]`` or ``[hc_mult, hc_mult, tokens]``
(the Sinkhorn).  What the trace cannot attribute: a fusion the compiler gave
another result (the norm's statistic over the flattened stream returns
``[tokens]``, H_pre and H_post ``[hc_mult, tokens]``, the sublayer's pre-norm
fused with the read-out ``H_pre X`` ``[.., hidden_size]``), so this reads a
floor.  Were the mixing
a kernel, the scope ``hc.mix`` would name it ``hc ...``: that is counted too.
Layer: engine and model step."""

import re

from benchmark.lib import readers

_SHAPE = re.compile(r"f32\[([\d,]*)\]$")


def keep(cfg):
    n, d = cfg["hc_mult"], cfg["hidden_size"]
    tails = ((n, d), (n * d,), (2 * n + n * n,), (n, n))

    def is_stream(op):
        if op.name.startswith("hc "):
            return True
        m = _SHAPE.search(op.name)
        if not m or not m.group(1):
            return False
        dims = tuple(int(x) for x in m.group(1).split(","))
        if len(dims) >= 4 and dims[-2:] == (1, d):
            return True               # one stream of a mix
        if len(dims) == 3 and dims[:2] == (n, n):
            return True               # the Sinkhorn, the tokens minor
        return len(dims) >= 2 and any(dims[-len(t):] == t for t in tails)
    return is_stream


def read(art, ctx):
    if "hc_mult" not in ctx.config:
        return None
    return readers.op_share(art, keep(ctx.config))

"""Share (%) of the device's busy time in the gated delta rule's two kernels,
decode and prefill programs alike: the Pallas calls under the scope
``attn.linear`` (the recurrent step in ``jit_step_all``, the chunked rule's
sequential pass in ``jit_chunk_slot``; ``lib/linear.py``).  The trace names
the compiler's own operations by opcode and result shape, not by scope, so
what surrounds the kernels is NOT in it: the projections ``W_qkv``, ``W_z``,
``W_ba`` and ``W_o``, the convolution and its tail, the L2 norms and gates,
and the ``jax.numpy`` part of the chunked rule (the intra-chunk products, the
triangular solve, ``W``, ``U``).  Layer: Pallas kernels."""

from benchmark.lib import linear, readers


def read(art, ctx):
    cfg = ctx.config
    if not linear.has_linear(cfg):
        return None
    return readers.op_share(
        art, lambda o: linear.is_decode_kernel(o, cfg)
        or linear.is_chunk_kernel(o, cfg))

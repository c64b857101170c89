"""Share (%) of the device's busy time in Kimi delta attention's two kernels,
decode and prefill programs alike: the operations the profiler names ``kda
...`` (the Pallas calls under the scopes ``kda.decode`` and ``kda.chunk``: the
recurrent step in ``jit_step_all``, the chunked rule's sequential pass in
``jit_chunk_slot``; ``lib/kda.py``).  The trace names the compiler's own
operations by opcode and result shape, not by scope, so what surrounds the
kernels is NOT in it: the projections ``W_qkv``, ``W_z``, ``W_f``, ``W_b`` and
``W_o``, the convolution and its tail, the L2 norms and gates, and the
``jax.numpy`` part of the chunked rule (the pairwise decays, the intra-chunk
products, the triangular solve, ``W``, ``U``).  Layer: Pallas kernels."""

from benchmark.lib import kda, readers


def read(art, ctx):
    if not kda.has_kda(ctx.config):
        return None
    return readers.op_share(art, kda.is_kda)

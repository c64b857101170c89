"""Share (%) of the device's busy time in the state-space rule's two kernels,
decode and prefill programs alike: the operations the profiler names ``ssd
...`` (the Pallas calls under the scopes ``ssd.decode`` and ``ssd.chunk``: the
recurrent step in ``jit_step_all``, the chunked rule in ``jit_chunk_slot``;
``lib/ssd.py``).  The trace names the compiler's own operations by opcode and
result shape, not by scope, so what surrounds the kernels is NOT in it: the
projections ``W_z``, ``W_xBC``, ``W_dt`` and ``W_out``, the convolution and its
tail, the gated norm, and the ``jax.numpy`` part of the chunked rule (``C
B^T``, the running sums of the decay).  Layer: Pallas kernels."""

from benchmark.lib import readers, ssd


def read(art, ctx):
    if not ssd.has_ssd(ctx.config):
        return None
    return readers.op_share(art, ssd.is_ssd)

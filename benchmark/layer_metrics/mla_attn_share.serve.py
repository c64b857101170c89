"""Share (%) of the device's busy time in the latent-attention kernels, decode
and prefill programs alike: the operations the profiler names ``attn ...``
(the Pallas calls under the scope ``attn.latent``: the absorbed decode kernel,
the expanded prefill kernel, the decode step's row write) inside
``jit_step_all`` and ``jit_chunk_slot``.  The trace names the compiler's own
operations by opcode and result shape, not by scope, so the projections around
the kernels (``W_qa``, ``W_qb``, ``W_kva``, the absorbed queries and outputs,
``W_o``) are not in it.  Layer: Pallas kernels."""

from benchmark.lib import latent, readers


def read(art, ctx):
    if "kv_lora_rank" not in ctx.config:
        return None
    return readers.op_share(art, latent.is_attn)

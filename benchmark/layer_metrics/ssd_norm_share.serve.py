"""Share (%) of the device's busy time in the state-space layers' gated norm,
decode and prefill programs alike: the operations the profiler names ``norm
...`` (the Pallas call under the scope ``norm.gated``,
``ops/pallas_kernels.py::gated_rms_norm``: the gate, the skip, the row's sum of
squares and the scale of ``rms((o + D x) * silu(z)) * w_n`` in one pass, one
call a Mamba layer in ``jit_step_all`` and in ``jit_chunk_slot``).  A program
that leaves the expression to the compiler has no such operation (its fusions
are named by opcode and shape), and the reader returns None.  Layer: Pallas
kernels."""

from benchmark.lib import readers, ssd


def read(art, ctx):
    if not ssd.has_ssd(ctx.config):
        return None
    return readers.op_share(
        art, lambda op: op.module in ssd.DECODE + ssd.PREFILL
        and op.name.startswith("norm "))

"""Share (%) of the device's busy time in the expert layers' grouped matmuls
of the VERIFY step: the Pallas calls under the scope ``moe.experts`` in
``jit_step_all`` (the row-tiled kernel, which a step of ``spec_k + 1`` rows a
lane takes in place of ``ragged-dot``; ``moe_verify_matmul_roofline`` says
why), the stack's expert layers' and the module's.  ``moe_ffn_share.serve``
reads ``ragged-dot`` and the sorts, and so not these.  Layer: expert layer
(ops/moe.py)."""

from benchmark.lib import latent, readers


def read(art, ctx):
    if not art.get("trace") or not art["trace"]["devices"]:
        return None
    return readers.op_share(
        art, lambda o: o.module in latent.DECODE and o.name.startswith("moe "))

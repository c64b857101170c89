"""Share (%) of the device's busy time inside the prefill-chunk program
(``LMEngine._chunk_jit``, traced as ``jit_chunk_slot``).  Layer: engine
scheduler."""

from benchmark.lib import readers


def read(art, ctx):
    return readers.module_share(art, "chunk_slot")

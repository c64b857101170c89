"""What the readers of the state-space (Mamba-2) layers share: the kernels'
calls in the trace.

The profiler names a Pallas call by the innermost scope around it and its
FIRST result's shape.  The state-space rule's two kernels run under
``ssd.decode`` and ``ssd.chunk`` (``ops/linear_attn.py``), which
``lib/trace.py::short_name`` cuts to ``ssd``: the recurrent decode kernel in
``jit_step_all``, the chunked rule's kernel in ``jit_chunk_slot``, one call
each per Mamba layer and dispatch.  They are told apart by the program they
run in and not by a shape, so the readers do not depend on how a kernel tiles
or packs its operands.  The attention layers' kernels of the same model are
named ``attn ...``.  A program without these kernels has no such operations,
and every reader returns None."""

from benchmark.lib.latent import DECODE, PREFILL, kernel_calls  # noqa: F401

#: rows of one inner chunk of the chunked rule as the program runs it
#: (``ops/linear_attn.py::SSD_CHUNK``)
INNER = 128


def has_ssd(cfg):
    return "mamba_d_state" in cfg


def is_ssd(op):
    return op.module in DECODE + PREFILL and op.name.startswith("ssd ")


def is_decode_kernel(op):
    return op.module in DECODE and op.name.startswith("ssd ")


def is_chunk_kernel(op):
    return op.module in PREFILL and op.name.startswith("ssd ")

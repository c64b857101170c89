"""What the readers of the Kimi-delta-attention layers share: the kernels'
calls in the trace.

The profiler names a Pallas call by the innermost scope around it and its
FIRST result's shape.  The rule's two kernels run under ``kda.decode`` and
``kda.chunk`` where the decay is one a key channel (``ops/linear_attn.py``),
which ``lib/trace.py::short_name`` cuts to ``kda``: the recurrent decode
kernel returns the heads' outputs ``f32[lanes, heads, head_dim]`` in
``jit_step_all``, the chunked rule's sequential pass ``f32[1, heads, inner
chunks, 64, head_dim]`` in ``jit_chunk_slot``.  The latent layer's kernels of
the same model are named ``attn ...`` (``lib/latent.py``).  A program without
these kernels has no such operations, and every reader returns None."""

from benchmark.lib.latent import DECODE, PREFILL, kernel_calls  # noqa: F401

#: rows of one inner chunk of the chunked rule (``ops/linear_attn.py::CHUNK``)
INNER = 64


def has_kda(cfg):
    return "kda_lower_bound" in cfg


def is_decode_kernel(op, cfg):
    return (op.module in DECODE and op.name == "kda f32[%d,%d,%d]" % (
        cfg["deployment"]["slots"], cfg["num_attention_heads"],
        cfg["head_dim"]))


def is_chunk_kernel(op, cfg):
    chunk = cfg["deployment"]["prefill_chunk"]
    return (op.module in PREFILL and op.name == "kda f32[1,%d,%d,%d,%d]" % (
        cfg["num_attention_heads"], -(-chunk // INNER), INNER,
        cfg["head_dim"]))


def is_kda(op):
    return op.module in DECODE + PREFILL and op.name.startswith("kda ")

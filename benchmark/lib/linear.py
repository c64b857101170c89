"""What the readers of the linear (Gated DeltaNet) layers share: the kernels'
calls in the trace.

The profiler names a Pallas call by the scope around it (``attn.linear`` and
``attn.full``, both of which ``lib/trace.py::short_name`` cuts to ``attn``)
and its FIRST result's shape: the recurrent decode kernel returns the heads'
outputs ``f32[lanes, value heads, value dim]`` in ``jit_step_all``, the
chunked rule's sequential pass ``f32[1, value heads, inner chunks, 64, value
dim]`` in ``jit_chunk_slot``; the full layers' flash-decode kernel returns
``[lanes, kv heads, queries a kv head, head_dim]`` and their row writes a
pool.  A program without these kernels has no such operations, and every
reader returns None."""

from benchmark.lib.latent import DECODE, PREFILL, kernel_calls  # noqa: F401

#: rows of one inner chunk of the chunked rule (``ops/linear_attn.py::CHUNK``)
INNER = 64


def has_linear(cfg):
    return "linear_num_value_heads" in cfg


def is_decode_kernel(op, cfg):
    return (op.module in DECODE and op.name == "attn f32[%d,%d,%d]" % (
        cfg["deployment"]["slots"], cfg["linear_num_value_heads"],
        cfg["linear_value_head_dim"]))


def is_chunk_kernel(op, cfg):
    chunk = cfg["deployment"]["prefill_chunk"]
    return (op.module in PREFILL and op.name == "attn f32[1,%d,%d,%d,%d]" % (
        cfg["linear_num_value_heads"], -(-chunk // INNER), INNER,
        cfg["linear_value_head_dim"]))


def is_full_decode_kernel(op, cfg):
    return (op.module in DECODE and op.name.startswith("attn ")
            and op.name.endswith("[%d,%d,%d,%d]" % (
                cfg["deployment"]["slots"], cfg["num_key_value_heads"],
                cfg["num_attention_heads"] // cfg["num_key_value_heads"],
                cfg["head_dim"])))


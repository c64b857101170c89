"""Share (%) of the device's busy time in the routed feed forward, as far as
the device trace can name it: the grouped matmuls of the held experts (the
compiler's own ``ragged-dot`` kernels, which ``jax.lax.ragged_dot`` lowers to)
and the routing's sorts (top-k of the scores, the sort of the assignments by
expert), decode and prefill programs alike.  The trace names the compiler's
operations by opcode and result shape, not by the program's scopes
(``moe.router``, ``moe.shared``), so the router's and the shared expert's
plain matmuls (about 58 MB a layer beside 700 of hit experts) are not in it.
Layer: expert layer (ops/moe.py)."""

from benchmark.lib import readers


def is_routed(op):
    return op.name.startswith("ragged-dot") or op.name.startswith("sort ")


def read(art, ctx):
    return readers.op_share(art, is_routed)

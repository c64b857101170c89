"""Share (%) of the device's busy time spent in copies of a whole layer's K
or V page pool (ledger, PR 24: nine of the ten longest operations were such
copies).  A copy counts when its result has the pool's own shape.
Layer: KV pool."""

from benchmark.lib import readers


def pool_copy_name(cfg):
    """``lib/trace.py::short_name`` of a copy whose result is one layer's
    whole K or V pool: (pages + 1 scratch, heads, page, head size), float32."""
    dep = cfg["deployment"]
    heads = cfg["num_attention_heads"]
    return "copy f32[%d,%d,%d,%d]" % (
        dep["paged_kv"] + 1, heads, dep["prefill_chunk"],
        cfg["hidden_size"] // heads)


def read(art, ctx):
    name = pool_copy_name(ctx.config)
    return readers.op_share(art, lambda op: op.name == name)

"""Plain reference for the OPT family (Zhang et al. 2022; the published
``config.json`` of ``facebook/opt-*``): decoder-only, pre-LayerNorm, learned
positions, ReLU FFN with biases, tied output head.  Straightforward
``jax.numpy`` in float32, one whole sequence at a time, no cache, no kernels,
no batching.  It imports nothing of ``veles_tpu`` and makes its own weights
from the seed.

Departures from the release, each stated in the configuration's ``assumed``:
the attention projections carry no bias (the program's block has none; OPT
with those biases at zero), and the position table has ``max_position``
rows with no offset of 2.

The weight tree's layout is the one the served program takes
(``{"embed", "pos", "blocks": [{"attn": {"wq", "wk", "wv", "wo"}, "ln1",
"ln2", "w1", "b1", "w2", "b2"}], "ln_f"}``, matrices as (in, out)), so the
benchmark hands the same seeded tree to the program and builds it again here
after the program's copy is freed."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PRECISIONS = {
    "highest": jax.lax.Precision.HIGHEST,
    "high": jax.lax.Precision.HIGH,
    "default": jax.lax.Precision.DEFAULT,
}
LN_EPS = 1e-5


def matmul(a, b, precision):
    """``a @ b`` at a named precision; ``bfloat16`` casts the operands (what
    a CPU test can hold; on the chip ``default`` is the one-pass form)."""
    if precision == "bfloat16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.matmul(a, b, precision=PRECISIONS[precision])


def seed_key(seed):
    """A key from any whole number up to a little over 2**31 (and beyond):
    the low 31 bits seed it and the rest is folded in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def make_weights(seed, cfg):
    """The whole float32 weight tree on the device, made there from the seed:
    one jitted program for a block, run once per layer, and one for the
    tables (a single program for all 1.3 G parameters held every leaf's
    temporaries at once and set the process's memory peak by itself).
    ``init_std`` (the release's 0.02) for every matrix and table; LayerNorm
    gains near 1 and all biases small and non-zero, so that a dropped gain or
    bias shows in the comparison."""
    d, ff = cfg["hidden_size"], cfg["ffn_dim"]
    vocab, n_layers = cfg["vocab_size"], cfg["num_hidden_layers"]
    max_pos, std = cfg["max_position_embeddings"], cfg["init_std"]

    def normal(k, shape, scale=std):
        return scale * jax.random.normal(k, shape, jnp.float32)

    def ln(k):
        kg, kb = jax.random.split(k)
        return {"g": 1.0 + normal(kg, (d,), 0.1), "b": normal(kb, (d,), 0.05)}

    @jax.jit
    def block(key):
        ks = jax.random.split(key, 10)
        return {
            "attn": {"wq": normal(ks[0], (d, d)), "wk": normal(ks[1], (d, d)),
                     "wv": normal(ks[2], (d, d)), "wo": normal(ks[3], (d, d))},
            "ln1": ln(ks[4]), "ln2": ln(ks[5]),
            "w1": normal(ks[6], (d, ff)), "b1": normal(ks[7], (ff,), 0.05),
            "w2": normal(ks[8], (ff, d)), "b2": normal(ks[9], (d,), 0.05)}

    @jax.jit
    def tables(key):
        k_embed, k_pos, k_lnf = jax.random.split(key, 3)
        return {"embed": normal(k_embed, (vocab, d)),
                "pos": normal(k_pos, (max_pos, d)), "ln_f": ln(k_lnf)}

    k_tables, k_blocks = jax.random.split(seed_key(seed))
    out = tables(k_tables)
    out["blocks"] = [block(k) for k in jax.random.split(k_blocks, n_layers)]
    return out


def _layernorm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["g"] + p["b"]


@functools.partial(jax.jit, static_argnames=("n_heads", "precision"))
def block(h, blk, n_heads, precision):
    """One decoder block over a whole sequence ``h`` (L, d), causal."""
    mm = functools.partial(matmul, precision=precision)
    length, d = h.shape
    dh = d // n_heads
    hn = _layernorm(h, blk["ln1"])

    def heads(w):
        return mm(hn, w).reshape(length, n_heads, dh).transpose(1, 0, 2)

    q, k, v = (heads(blk["attn"][n]) for n in ("wq", "wk", "wv"))
    scores = mm(q, k.transpose(0, 2, 1)) / jnp.sqrt(jnp.float32(dh))
    causal = jnp.tril(jnp.ones((length, length), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    o = mm(jax.nn.softmax(scores, axis=-1), v)
    h = h + mm(o.transpose(1, 0, 2).reshape(length, d), blk["attn"]["wo"])
    hn = _layernorm(h, blk["ln2"])
    ff = jnp.maximum(mm(hn, blk["w1"]) + blk["b1"], 0.0)
    return h + mm(ff, blk["w2"]) + blk["b2"]


@functools.partial(jax.jit, static_argnames=("precision",))
def head(h, ln_f, embed, precision):
    """Final LayerNorm and the tied output head over rows ``h`` (n, d)."""
    return matmul(_layernorm(h, ln_f), embed.T, precision)


def logits(weights, tokens, rows, n_heads, precision="highest"):
    """Logits (len(rows), vocab) after the positions ``rows`` of one
    sequence ``tokens`` (L,), layer by layer.  Padding the sequence at its
    end leaves earlier positions unchanged (causal), so callers pad to one
    length and compile once."""
    tokens = jnp.asarray(tokens, jnp.int32)
    h = weights["embed"][tokens] + weights["pos"][:tokens.shape[0]]
    for blk in weights["blocks"]:
        h = block(h, blk, n_heads, precision)
    return head(h[jnp.asarray(rows)], weights["ln_f"], weights["embed"],
                precision)


def token_gaps(weights, tokens, first, n_heads, pad_to, rows_to,
               control=None):
    """For the tokens ``tokens[first:]`` of one served sequence: how far each
    one's reference logit lies below the reference's best at its position
    (0 where the served token is the reference's choice).  With ``control``
    (a precision name) also the same gap for the token that the reference
    computed in that lower precision puts first, at every position from
    ``first``.  The sequence is padded to ``pad_to`` and the rows to
    ``rows_to``, so every request runs the same compiled programs.  Returns
    (served gaps, control gaps or None) as host arrays."""
    import numpy
    tokens = numpy.asarray(tokens, numpy.int32)
    n = len(tokens)
    padded = numpy.zeros(pad_to, numpy.int32)
    padded[:n] = tokens
    count = n - first
    rows = numpy.minimum(numpy.arange(first - 1, first - 1 + rows_to), n - 2)
    ref = logits(weights, padded, rows, n_heads, "highest")[:count]
    best = ref.max(-1)
    served = best - jnp.take_along_axis(
        ref, jnp.asarray(tokens[first:])[:, None], axis=-1)[:, 0]
    lowered = None
    if control is not None:
        low = logits(weights, padded, rows, n_heads, control)[:count]
        lowered = numpy.asarray(best - jnp.take_along_axis(
            ref, low.argmax(-1)[:, None], axis=-1)[:, 0])
    return numpy.asarray(served), lowered

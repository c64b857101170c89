"""Plain reference for the AFMoE family (``model_type: afmoe``; the published
``config.json`` of ``arcee-ai/Trinity-*``): decoder-only; RMSNorm without bias
before and after attention and feed forward; per-head q/k RMSNorm; grouped
query attention whose heads are ``head_dim`` wide whatever ``hidden_size`` is;
rotary positions on ``sliding_attention`` layers and none on
``full_attention`` layers; a sigmoid output gate on the heads' outputs;
gated-SiLU feed forward, dense in the leading layers, then sigmoid-routed
experts (top-k of the scores plus a selection bias, weights normalised and
scaled) beside a shared expert; embedding scaled by sqrt(hidden_size); untied
head.  What ``config.json`` does not state is listed under ``assumed`` in the
configuration file.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, one whole sequence at a time, no
cache, no kernels, no batching.  It imports nothing of ``veles_tpu`` and makes
its own weights from the seed.

**The share it computes is the configuration's** (``deployment_share``): this
chip holds experts ``held[0] .. held[0] + held[1] - 1`` of the router's
``router_width``, and rows ``0 .. vocab_size - 1`` of embedding and head.  The
router scores all ``router_width`` experts; the routed sum runs over the
chosen experts that are held, and what the absent ones would add is left out,
as the program leaves it out.

The weights are bfloat16 VALUES (what the program serves); the arithmetic
raises them to float32 a matrix or an expert at a time, so that a whole
sequence fits beside them on one chip.

The weight tree's layout is the one the served program takes: ``{"embed"
(V, d), "head" (d, V), "ln_f" (d,), "blocks": [{"attn": {"wq", "wk", "wv",
"wo", "wg", "q_norm", "k_norm"}, "ln_in", "ln_post_attn", "ln_pre_mlp",
"ln_post_mlp", then "w_gate", "w_up", "w_down" (a dense layer) or "moe":
{"router" (d, E), "bias" (E,), "w_gate", "w_up" (n, d, f), "w_down" (n, f, d),
"shared": {"w_gate", "w_up", "w_down"}}}]}``, matrices as (in, out)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

ROUTE_NORM_EPS = 1e-20
BF16 = jnp.bfloat16


def seed_key(seed):
    """A key from any whole number up to a little over 2**31 (and beyond):
    the low 31 bits seed it and the rest is folded in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def sizes(cfg):
    """The sizes the arithmetic needs, from the published keys."""
    lo, n = cfg["held_experts"]
    return {
        "d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "kv": cfg["num_key_value_heads"], "dh": cfg["head_dim"],
        "ff": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"],
        "vocab": cfg["vocab_size"], "layers": cfg["num_hidden_layers"],
        "dense": cfg["num_dense_layers"], "router": cfg["router_width"],
        "lo": lo, "held": n, "top_k": cfg["num_experts_per_tok"],
        "window": cfg["sliding_window"], "theta": cfg["rope_theta"],
        "eps": cfg["rms_norm_eps"], "scale": cfg["route_scale"],
        "kinds": tuple(cfg["layer_types"]),
    }


def balanced_bias(bias, share):
    """``bias`` (router width,) with each run of ``share`` experts (one
    chip's) shifted so that its mean is the mean of all."""
    groups = bias.reshape(-1, share)
    return (groups - groups.mean(1, keepdims=True) + bias.mean()).reshape(-1)


def make_weights(seed, cfg):
    """The whole bfloat16 weight tree on the device, made there from the
    seed, one jitted program per layer (a single program for every leaf held
    all their float32 temporaries at once).  Matrices normal(0,
    ``initializer_std``), norm gains 1 + normal(0, 0.1), the router's
    selection bias normal(0, 0.01): every leaf is drawn in float32 and
    rounded to bfloat16 once.

    The selection bias is BALANCED ACROSS CHIPS (:func:`balanced_bias`):
    after the draw, the experts of each chip's share are shifted together
    so that every share's mean bias is the same.  The buffer exists to
    even out the experts' load, and a deployment that divides the experts
    among chips needs it even between the shares; left as drawn, the mean
    bias of this chip's 32 experts differed from the rest by up to 0.004
    from seed to seed, which moved the share of assignments it receives
    by 3 % and the experts it reads a decode step with it: the work of a
    run depended on its seed (PERF.md section 6, PR 28).  Within a share
    the bias keeps its spread, so the selection path is exercised."""
    z = sizes(cfg)
    d, dh, std = z["d"], z["dh"], cfg["initializer_std"]

    def normal(k, shape, scale=std):
        return (scale * jax.random.normal(k, shape, jnp.float32)).astype(BF16)

    def gain(k, n):
        return (1.0 + 0.1 * jax.random.normal(k, (n,), jnp.float32)) \
            .astype(BF16)

    def ffn(k, width, lead=()):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"w_gate": normal(k1, lead + (d, width)),
                "w_up": normal(k2, lead + (d, width)),
                "w_down": normal(k3, lead + (width, d))}

    @functools.partial(jax.jit, static_argnames=("routed",))
    def block(key, routed):
        ks = jax.random.split(key, 16)
        out = {
            "attn": {"wq": normal(ks[0], (d, z["heads"] * dh)),
                     "wk": normal(ks[1], (d, z["kv"] * dh)),
                     "wv": normal(ks[2], (d, z["kv"] * dh)),
                     "wo": normal(ks[3], (z["heads"] * dh, d)),
                     "wg": normal(ks[4], (d, z["heads"] * dh)),
                     "q_norm": gain(ks[5], dh), "k_norm": gain(ks[6], dh)},
            "ln_in": gain(ks[7], d), "ln_post_attn": gain(ks[8], d),
            "ln_pre_mlp": gain(ks[9], d), "ln_post_mlp": gain(ks[10], d)}
        if not routed:
            return dict(out, **ffn(ks[11], z["ff"]))
        out["moe"] = dict(
            ffn(ks[11], z["fe"], (z["held"],)),
            router=normal(ks[12], (d, z["router"])),
            bias=balanced_bias(0.01 * jax.random.normal(
                ks[13], (z["router"],), jnp.float32), z["held"]).astype(BF16),
            shared=ffn(ks[14], z["fe"]))
        return out

    @jax.jit
    def tables(key):
        k_embed, k_head, k_lnf = jax.random.split(key, 3)
        return {"embed": normal(k_embed, (z["vocab"], d)),
                "head": normal(k_head, (d, z["vocab"])),
                "ln_f": gain(k_lnf, d)}

    k_tables, k_blocks = jax.random.split(seed_key(seed))
    out = tables(k_tables)
    out["blocks"] = [block(k, routed=i >= z["dense"]) for i, k in
                     enumerate(jax.random.split(k_blocks, z["layers"]))]
    return out


# -------------------------------------------------------------- arithmetic
def round_to_e4m3(w):
    """``w`` (float32) rounded to the nearest ``float8_e4m3fn`` value (three
    mantissa bits, normal down to 2**-6, subnormal step 2**-9, largest 448;
    ties to even), in float32 arithmetic: the same values on every backend
    (a chip without the type converts by its own rule; on the CPU this is
    ``w.astype(float8_e4m3fn)`` bit for bit, ``tests/test_afmoe.py``)."""
    a = jnp.abs(w)
    exponent = jnp.clip(jnp.floor(jnp.log2(jnp.maximum(a, 2.0 ** -20))),
                        -6, 8)
    step = jnp.exp2(exponent - 3)
    return jnp.sign(w) * jnp.minimum(jnp.round(a / step) * step, 448.0)


def lowered(w, control):
    """``w`` in float32; under a control, rounded first to that format (the
    control's place of the program: weights in the nearest precision below
    bfloat16)."""
    w = w.astype(jnp.float32)
    if control is None:
        return w
    if control != "float8_e4m3fn":
        raise ValueError("no control %r" % (control,))
    return round_to_e4m3(w)


def rms(x, g, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def rotate(x, theta):
    """Rotary positions 0..L-1 over (L, heads, dh): the half-split
    convention, whole head."""
    length, _, dh = x.shape
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("z", "sliding", "control"))
def attention(h, blk, z, sliding, control):
    """``h + rms(gated attention(rms(h)) W_o)`` over a whole sequence (L, d),
    one query head at a time (a head's scores are L x L)."""
    length = h.shape[0]
    heads, kv, dh, eps = z["heads"], z["kv"], z["dh"], z["eps"]
    p = blk["attn"]
    a = rms(h, blk["ln_in"], eps)
    q = (a @ lowered(p["wq"], control)).reshape(length, heads, dh)
    k = (a @ lowered(p["wk"], control)).reshape(length, kv, dh)
    v = (a @ lowered(p["wv"], control)).reshape(length, kv, dh)
    gate = a @ lowered(p["wg"], control)
    q, k = rms(q, p["q_norm"], eps), rms(k, p["k_norm"], eps)
    if sliding:
        q, k = rotate(q, z["theta"]), rotate(k, z["theta"])
    i = jnp.arange(length)[:, None]
    j = jnp.arange(length)[None, :]
    visible = j <= i
    if sliding:
        visible &= j > i - z["window"]

    def head(n):
        g = n // (heads // kv)
        scores = (q[:, n] @ k[:, g].T) / jnp.sqrt(jnp.float32(dh))
        scores = jnp.where(visible, scores, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v[:, g]

    o = jax.lax.map(head, jnp.arange(heads))            # (heads, L, dh)
    o = o.transpose(1, 0, 2).reshape(length, heads * dh)
    o = o * jax.nn.sigmoid(gate)
    return h + rms(o @ lowered(p["wo"], control), blk["ln_post_attn"], eps)


def gated(m, p, control, pick=None):
    """``(silu(m W_gate) * (m W_up)) W_down``; ``pick`` takes one expert of
    a stacked tree."""
    take = (lambda w: w) if pick is None else (lambda w: w[pick])
    up = m @ lowered(take(p["w_up"]), control)
    gate = jax.nn.silu(m @ lowered(take(p["w_gate"]), control))
    return (gate * up) @ lowered(take(p["w_down"]), control)


def route(m, p, z, control):
    """Per token and HELD expert, the routing weight (0 where the expert was
    not chosen): (L, held).  Scores over all ``router`` experts, the choice by
    scores plus bias, the weight from the scores alone."""
    s = jax.nn.sigmoid(m @ lowered(p["router"], control))
    _, chosen = jax.lax.top_k(s + p["bias"].astype(jnp.float32), z["top_k"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    w = z["scale"] * picked / (picked.sum(-1, keepdims=True) + ROUTE_NORM_EPS)
    dense = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], chosen].set(w)
    return dense[:, z["lo"]:z["lo"] + z["held"]]


@functools.partial(jax.jit, static_argnames=("z", "control"))
def feed_forward(h, blk, z, control):
    """``h + rms(f(rms(h)))``: the dense feed forward, or the shared expert
    plus this share's routed experts, each over every token and weighted."""
    m = rms(h, blk["ln_pre_mlp"], z["eps"])
    if "moe" not in blk:
        f = gated(m, blk, control)
    else:
        p = blk["moe"]
        w = route(m, p, z, control)
        f = gated(m, p["shared"], control)

        def add(e, f):
            return f + w[:, e, None] * gated(m, p, control, pick=e)

        f = jax.lax.fori_loop(0, z["held"], add, f)
    return h + rms(f, blk["ln_post_mlp"], z["eps"])


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def head(h, ln_f, w_head, eps, control):
    return rms(h, ln_f, eps) @ lowered(w_head, control)


class _Sizes(dict):
    """Hashable sizes, so that they can be a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def logits(weights, tokens, rows, cfg, control=None):
    """Logits (len(rows), vocab) after the positions ``rows`` of one sequence
    ``tokens`` (L,), layer by layer.  Padding the sequence at its end leaves
    earlier positions unchanged (causal), so callers pad to one length and
    compile once."""
    z = _Sizes(sizes(cfg))
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        h = weights["embed"][tokens].astype(jnp.float32) \
            * jnp.sqrt(jnp.float32(z["d"]))
        for blk, kind in zip(weights["blocks"], z["kinds"]):
            h = attention(h, blk, z, kind == "sliding_attention", control)
            h = feed_forward(h, blk, z, control)
        return head(h[jnp.asarray(rows)], weights["ln_f"], weights["head"],
                    z["eps"], control)


def token_gaps(weights, tokens, first, cfg, pad_to, rows_to, control=None):
    """For the tokens ``tokens[first:]`` of one served sequence: how far each
    one's reference logit lies below the reference's best at its position
    (0 where the served token is the reference's choice).  With ``control``
    (a dtype name) also the same gap for the token that the reference
    computed with its weights rounded to that dtype puts first, at every
    position from ``first``.  The sequence is padded to ``pad_to`` and the
    rows to ``rows_to``, so every request runs the same compiled programs.
    Returns (served gaps, control gaps or None) as host arrays."""
    import numpy
    tokens = numpy.asarray(tokens, numpy.int32)
    n = len(tokens)
    padded = numpy.zeros(pad_to, numpy.int32)
    padded[:n] = tokens
    count = n - first
    rows = numpy.minimum(numpy.arange(first - 1, first - 1 + rows_to), n - 2)
    ref = logits(weights, padded, rows, cfg)[:count]
    best = ref.max(-1)
    served = best - jnp.take_along_axis(
        ref, jnp.asarray(tokens[first:])[:, None], axis=-1)[:, 0]
    low_gaps = None
    if control is not None:
        low = logits(weights, padded, rows, cfg, control)[:count]
        low_gaps = numpy.asarray(best - jnp.take_along_axis(
            ref, low.argmax(-1)[:, None], axis=-1)[:, 0])
    return numpy.asarray(served), low_gaps

"""Plain reference for the Qwen3-Next family (``model_type: qwen3_next``; the
published ``config.json`` of ``Qwen/Qwen3-Next-80B-A3B-Instruct``):
decoder-only; a pre-RMSNorm block over one residual stream whose norm gains
are stored about zero (``x * rsqrt(mean(x^2) + eps) * (1 + w)``); three of
every ``full_attention_interval`` layers mix tokens through GATED DELTANET
(linear attention, arXiv:2412.06464): a causal depthwise convolution over
``[q | k | v]``, L2-normalised q and k, and per value head a state ``S``
(``linear_key_head_dim`` x ``linear_value_head_dim``) updated token by token
by the gated delta rule::

    S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;
    o_t = S^T q_t

with ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``, the
output ``(rms(o) * w_n * silu(z)) W_o``; the fourth is GATED SOFTMAX
ATTENTION: grouped queries of ``head_dim``, the sigmoid output gate the
second half of each head's ``W_q`` columns, per-head zero-centred q/k norms,
rotary positions on the first ``partial_rotary_factor`` of a head; every
layer has softmax-routed experts (top-k of the softmax over ALL experts,
weights renormalised over the chosen) beside one shared expert times
``sigmoid(x w_sg)``; untied head.

Departures from the published description:

- THE MULTI-TOKEN-PREDICTION MODULE IS NOT LOADED: the release describes one;
  the published ``config.json`` has no key for it and it changes no logit of
  the main model.
- The leaves of a linear layer are named by role and lie apart: ``w_qkv``
  (``[q | k | v]``, what the convolution runs over), ``w_z``, ``w_ba`` (``[b |
  a]``).  The published checkpoint interleaves q, k, v, z by key head in one
  matrix and b, a in another: a fixed permutation of columns, a loader's
  matter, nothing for weights drawn from a seed.
- The rotated dimensions are rotated half-split as they lie (pairs ``i, i +
  rot / 2`` of the first ``rot = head_dim x partial_rotary_factor``).
- What ``config.json`` does not settle is listed under ``assumed`` in the
  configuration file.

**The share it computes is the configuration's** (``deployment_share``): this
chip holds experts ``held[0] .. held[0] + held[1] - 1`` of the router's
``router_width`` and rows ``0 .. vocab_size - 1`` of embedding and head.  The
router scores all ``router_width`` experts; the routed sum runs over the
chosen experts that are held, and what the absent ones would add is left out,
as the program leaves it out.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, one whole sequence at a time, THE
RECURRENT FORM OF THE RULE TOKEN BY TOKEN (``lax.scan``), no chunking, no
cache, no kernels, no batching.  It imports nothing of ``veles_tpu`` and makes
its own weights from the seed.  The weights are bfloat16 VALUES (what the
program serves); the arithmetic raises them to float32 a matrix or an expert
at a time; what is row-wise runs a block of rows at a time and full attention
runs in blocks of queries, a head at a time.

The weight tree is the one the served program takes: ``{"embed" (V, d), "head"
(d, V), "ln_f" (d,), "blocks": [{"attn": (a linear layer) {"w_qkv" (d, 2 hk dk
+ hv dv), "w_z" (d, hv dv), "w_ba" (d, 2 hv), "conv" (taps, 2 hk dk + hv dv),
"A_log", "dt_bias" (hv,), "norm" (dv,), "wo" (hv dv, d)} or (a full layer)
{"wq" (d, h 2 dh), "wk", "wv" (d, kv dh), "q_norm", "k_norm" (dh,), "wo" (h
dh, d)}, "ln_attn", "ln_mlp" (d,), "moe": {"router" (d, E), "w_gate", "w_up"
(n, d, f), "w_down" (n, f, d), "shared": {"w_gate", "w_up", "w_down"},
"shared_gate" (d, 1)}}]}``, matrices as (in, out)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

ROUTE_NORM_EPS = 1e-20
L2_EPS = 1e-6
BF16 = jnp.bfloat16
#: rows of one block of the row-wise parts, and queries of one block of
#: attention
ROWS = 1024


def seed_key(seed):
    """A key from any whole number up to a little over 2**31 (and beyond):
    the low 31 bits seed it and the rest is folded in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


class _Sizes(dict):
    """Hashable sizes, so that they can be a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def layer_types(cfg):
    """``layer_types`` as written, or derived from
    ``full_attention_interval``."""
    if cfg.get("layer_types"):
        return tuple(cfg["layer_types"])
    every = cfg["full_attention_interval"]
    return tuple("full_attention" if (i + 1) % every == 0
                 else "linear_attention"
                 for i in range(cfg["num_hidden_layers"]))


def sizes(cfg):
    """The sizes the arithmetic needs, from the published keys."""
    lo, n = cfg.get("held_experts") or (0, cfg["num_experts"])
    return _Sizes({
        "d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "kv": cfg["num_key_value_heads"], "dh": cfg["head_dim"],
        "rot": int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        "theta": cfg["rope_theta"], "eps": cfg["rms_norm_eps"],
        "hk": cfg["linear_num_key_heads"],
        "hv": cfg["linear_num_value_heads"],
        "dk": cfg["linear_key_head_dim"], "dv": cfg["linear_value_head_dim"],
        "taps": cfg["linear_conv_kernel_dim"],
        "fe": cfg["moe_intermediate_size"],
        "fs": cfg["shared_expert_intermediate_size"],
        "router": cfg.get("router_width", cfg["num_experts"]),
        "lo": lo, "held": n, "top_k": cfg["num_experts_per_tok"],
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "vocab": cfg["vocab_size"], "layers": cfg["num_hidden_layers"],
        "types": layer_types(cfg),
    })


def make_weights(seed, cfg):
    """The whole bfloat16 weight tree on the device, made there from the
    seed, one jitted program per layer.  Matrices normal(0,
    ``initializer_std``); the norms' gains normal(0, 0.1) about their centre
    (0 for the zero-centred ones, 1 for the linear layer's output norm);
    ``A_log = log U(0, 16)`` and ``dt_bias = 1`` (the family's own
    initialisation: heads that forget within a token beside heads that keep
    thousands); the convolution's taps normal(0, taps^-1/2); every leaf
    drawn in float32 and rounded to bfloat16 once."""
    z = sizes(cfg)
    d, std = z["d"], cfg["initializer_std"]
    ch = 2 * z["hk"] * z["dk"] + z["hv"] * z["dv"]

    def normal(k, shape, scale=std):
        return (scale * jax.random.normal(k, shape, jnp.float32)).astype(BF16)

    def gain(k, m, centre=0.0):
        return (centre + 0.1 * jax.random.normal(k, (m,), jnp.float32)) \
            .astype(BF16)

    def ffn(k, width, lead=()):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"w_gate": normal(k1, lead + (d, width)),
                "w_up": normal(k2, lead + (d, width)),
                "w_down": normal(k3, lead + (width, d))}

    @functools.partial(jax.jit, static_argnames=("kind",))
    def block(key, kind):
        ks = jax.random.split(key, 20)
        if kind == "linear_attention":
            attn = {
                "w_qkv": normal(ks[0], (d, ch)),
                "w_z": normal(ks[1], (d, z["hv"] * z["dv"])),
                "w_ba": normal(ks[2], (d, 2 * z["hv"])),
                "conv": normal(ks[3], (z["taps"], ch), z["taps"] ** -0.5),
                "A_log": jnp.log(jax.random.uniform(
                    ks[4], (z["hv"],), jnp.float32, 1e-3, 16.0)).astype(BF16),
                "dt_bias": jnp.ones((z["hv"],), BF16),
                "norm": gain(ks[5], z["dv"], 1.0),
                "wo": normal(ks[6], (z["hv"] * z["dv"], d))}
        else:
            attn = {
                "wq": normal(ks[0], (d, z["heads"] * 2 * z["dh"])),
                "wk": normal(ks[1], (d, z["kv"] * z["dh"])),
                "wv": normal(ks[2], (d, z["kv"] * z["dh"])),
                "q_norm": gain(ks[3], z["dh"]),
                "k_norm": gain(ks[4], z["dh"]),
                "wo": normal(ks[6], (z["heads"] * z["dh"], d))}
        moe = dict(ffn(ks[10], z["fe"], (z["held"],)),
                   router=normal(ks[11], (d, z["router"])),
                   shared=ffn(ks[12], z["fs"]),
                   shared_gate=normal(ks[13], (d, 1)))
        return {"attn": attn, "ln_attn": gain(ks[7], d),
                "ln_mlp": gain(ks[8], d), "moe": moe}

    @jax.jit
    def tables(key):
        k_embed, k_head, k_lnf = jax.random.split(key, 3)
        return {"embed": normal(k_embed, (z["vocab"], d)),
                "head": normal(k_head, (d, z["vocab"])),
                "ln_f": gain(k_lnf, d)}

    k_tables, k_blocks = jax.random.split(seed_key(seed))
    out = tables(k_tables)
    out["blocks"] = [block(k, kind=z["types"][i]) for i, k in
                     enumerate(jax.random.split(k_blocks, z["layers"]))]
    return out


# -------------------------------------------------------------- arithmetic
def round_to_e4m3(w):
    """``w`` (float32) rounded to the nearest ``float8_e4m3fn`` value, in
    float32 arithmetic (``reference/afmoe.py`` has the same)."""
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


def rms(x, w, eps):
    """The zero-centred norm: ``x * rsqrt(mean(x^2) + eps) * (1 + w)``."""
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * (1.0 + w.astype(jnp.float32))


def by_rows(fn, *arrays):
    """``fn`` over blocks of ``ROWS`` rows of the arrays' leading axis (a
    multiple of ``ROWS``, or less than one block), the results put back
    together: what is row-wise never holds a whole sequence's temporaries."""
    length = arrays[0].shape[0]
    if length <= ROWS:
        return fn(*arrays)
    blocks = [a.reshape((length // ROWS, ROWS) + a.shape[1:])
              for a in arrays]
    out = jax.lax.map(lambda xs: fn(*xs), tuple(blocks))
    return jax.tree.map(
        lambda o: o.reshape((length,) + o.shape[2:]), out)


# ---------------------------------------------------------- gated DeltaNet
@functools.partial(jax.jit, static_argnames=("z", "control"))
def delta_layer(x, blk, z, control):
    """One Gated DeltaNet sublayer over the stream ``x`` (L, d): its
    residual added."""
    p = blk["attn"]
    hk, hv, dk, dv = z["hk"], z["hv"], z["dk"], z["dv"]
    length = x.shape[0]
    u = by_rows(lambda xs: rms(xs, blk["ln_attn"], z["eps"]), x)
    qkv = u @ lowered(p["w_qkv"], control)                # (L, ch)
    gate = u @ lowered(p["w_z"], control)                 # (L, hv dv)
    ba = u @ lowered(p["w_ba"], control)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[:, hv:] + p["dt_bias"].astype(jnp.float32))
    # causal depthwise convolution: channel c at row t sees its own rows
    # t - taps + 1 .. t (zeros before the sequence), tap j on row t - taps
    # + 1 + j
    taps = z["taps"]
    conv = lowered(p["conv"], control)
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, qkv.shape[1]), jnp.float32), qkv])
    act = jax.nn.silu(sum(padded[j:j + length] * conv[j]
                          for j in range(taps)))

    def unit(y):
        y = y.reshape(length, hk, dk)
        y = y * jax.lax.rsqrt((y * y).sum(-1, keepdims=True) + L2_EPS)
        return jnp.repeat(y, hv // hk, axis=1)            # (L, hv, dk)

    q = unit(act[:, :hk * dk]) * dk ** -0.5
    k = unit(act[:, hk * dk:2 * hk * dk])
    v = act[:, 2 * hk * dk:].reshape(length, hv, dv)

    def token(s, t):
        q_t, k_t, v_t, beta_t, g_t = t
        s = jnp.exp(g_t)[:, None, None] * s
        d_t = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * d_t[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((hv, dk, dv), jnp.float32),
                        (q, k, v, beta, g))               # (L, hv, dv)
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + z["eps"]) \
        * p["norm"].astype(jnp.float32)
    o = o * jax.nn.silu(gate.reshape(length, hv, dv))
    return x + o.reshape(length, hv * dv) @ lowered(p["wo"], control)


# --------------------------------------------------------- gated attention
def rotate(x, positions, z):
    """Rotary positions (L,) over (L, ..., dh): the first ``rot`` dimensions
    rotated half-split, the rest left."""
    rot = z["rot"]
    half = rot // 2
    freq = z["theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freq
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], -1)


@functools.partial(jax.jit, static_argnames=("z", "control"))
def attention_layer(x, blk, z, control):
    """One gated softmax-attention sublayer over ``x`` (L, d), a head and a
    block of queries at a time: its residual added."""
    p, h, kv, dh = blk["attn"], z["heads"], z["kv"], z["dh"]
    length = x.shape[0]
    block = min(ROWS, length)
    at = jnp.arange(length)
    u = by_rows(lambda xs: rms(xs, blk["ln_attn"], z["eps"]), x)
    qg = (u @ lowered(p["wq"], control)).reshape(length, h, 2 * dh)
    q = rotate(rms(qg[..., :dh], p["q_norm"], z["eps"]), at, z)
    gate = qg[..., dh:]
    k = (u @ lowered(p["wk"], control)).reshape(length, kv, dh)
    k = rotate(rms(k, p["k_norm"], z["eps"]), at, z)
    v = (u @ lowered(p["wv"], control)).reshape(length, kv, dh)
    j = at[None, :]
    firsts = jnp.arange(0, length, block)

    def head(n):
        kn, vn = k[:, n // (h // kv)], v[:, n // (h // kv)]
        qn = q[:, n].reshape(-1, block, dh)

        def queries(args):
            qb, first = args
            s = (qb @ kn.T) * dh ** -0.5
            s = jnp.where(j <= (first + jnp.arange(block))[:, None], s,
                          -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ vn

        return jax.lax.map(queries, (qn, firsts)).reshape(length, dh)

    o = jax.lax.map(head, jnp.arange(h))                  # (h, L, dh)
    o = jnp.moveaxis(o, 0, 1) * jax.nn.sigmoid(gate)
    return x + o.reshape(length, h * dh) @ lowered(p["wo"], control)


# ------------------------------------------------------------ feed forward
def gated(m, p, control, pick=None):
    """``(silu(m W_gate) * (m W_up)) W_down``; ``pick`` takes one expert of
    a stacked tree."""
    take = (lambda w: w) if pick is None else (lambda w: w[pick])
    up = m @ lowered(take(p["w_up"]), control)
    gate = jax.nn.silu(m @ lowered(take(p["w_gate"]), control))
    return (gate * up) @ lowered(take(p["w_down"]), control)


def route(m, p, z, control):
    """Per token and HELD expert, the routing weight (0 where the expert was
    not chosen): (L, held).  ``s = softmax(m W_r)`` over all ``router``
    experts; top-k of ``s``; weights ``s`` of the chosen over their sum
    (+1e-20)."""
    s = jax.nn.softmax(m @ lowered(p["router"], control), axis=-1)
    w, chosen = jax.lax.top_k(s, z["top_k"])
    if z["norm_topk"]:
        w = w / (w.sum(-1, keepdims=True) + ROUTE_NORM_EPS)
    dense = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], chosen].set(w)
    return dense[:, z["lo"]:z["lo"] + z["held"]]


def expert_layer(m, p, z, control):
    """The routed layer over normed rows ``m`` (L, d): the held experts'
    part of the routed sum, each over every token and weighted, plus the
    shared expert times ``sigmoid(m w_sg)``."""
    w = route(m, p, z, control)
    f = jax.nn.sigmoid(m @ lowered(p["shared_gate"], control)) \
        * gated(m, p["shared"], control)

    def add(e, f):
        return f + w[:, e, None] * gated(m, p, control, pick=e)

    return jax.lax.fori_loop(0, z["held"], add, f)


@functools.partial(jax.jit, static_argnames=("z", "control"),
                   donate_argnums=(0,))
def feed_forward(x, blk, z, control):
    return by_rows(lambda xs: xs + expert_layer(
        rms(xs, blk["ln_mlp"], z["eps"]), blk["moe"], z, control), x)


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def head(x, ln_f, w_head, eps, control):
    return rms(x, ln_f, eps) @ lowered(w_head, control)


def logits(weights, tokens, rows, cfg, control=None):
    """Logits (len(rows), vocab) after the positions ``rows`` of one sequence
    ``tokens`` (L,), layer by layer.  Padding the sequence at its end leaves
    earlier positions unchanged (causal), so callers pad to one length and
    compile once; a sequence longer than one block of rows is padded here to
    whole blocks."""
    z = sizes(cfg)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        if tokens.shape[0] > ROWS and tokens.shape[0] % ROWS:
            tokens = jnp.pad(tokens, (0, -tokens.shape[0] % ROWS))
        x = weights["embed"][tokens].astype(jnp.float32)
        for kind, blk in zip(z["types"], weights["blocks"]):
            mixer = (delta_layer if kind == "linear_attention"
                     else attention_layer)
            x = feed_forward(mixer(x, blk, z, control), blk, z, control)
        return head(x[jnp.asarray(rows)], weights["ln_f"], weights["head"],
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

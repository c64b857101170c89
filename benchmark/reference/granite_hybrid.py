"""Plain reference for IBM Granite 4.0-H without routed experts (``model_type:
granitemoehybrid`` with ``num_local_experts`` 0; the published ``config.json``
of ``ibm-granite/granite-4.0-h-micro``): decoder-only, one residual stream in
float32::

    h = embedding_multiplier * E[token]
    each layer:  h <- h + residual_multiplier * Mixer(rms(h) * w1)
                 h <- h + residual_multiplier * MLP(rms(h) * w2)
    logits = (rms(h) * w_f) E^T / logits_scaling          (the head is tied)

``rms(x) = x * rsqrt(mean(x^2) + rms_norm_eps)``; ``MLP(x) = (silu(x W_g) *
(x W_u)) W_d`` without bias (the published ``shared_mlp``; no router and no
expert).  ``layer_types`` says which mixer a layer has:

- ``mamba``: a MAMBA-2 state-space mixer (arXiv:2405.21060).  ``z = x W_z``,
  ``xBC = x W_xBC`` (``[x | B | C]``: ``mamba_n_heads`` heads of
  ``mamba_d_head``, then ``mamba_n_groups`` groups of ``mamba_d_state``
  twice), ``dt = x W_dt`` (one a head): the published ``in_proj``'s columns
  ``[z | xBC | dt]`` as three leaves.  ``xBC <- silu(conv(xBC) + b_c)``:
  causal, depthwise, ``mamba_d_conv`` taps, tap j on the row ``taps - 1 - j``
  back, zeros before the sequence.  ``dt <- softplus(dt + dt_bias)``, ``g =
  -exp(A_log) dt``.  Per head, with a state ``S`` (d_state, d_head) that is
  zero where the sequence starts, and ``B``, ``C`` of the head's group::

      S <- exp(g_t) S + B_t (dt_t x_t)^T;   y_t = S^T C_t + D x_t

  and the mixer's output is ``(rms(y * silu(z)) * w_n) W_out``: the norm runs
  over the WHOLE inner width (all heads at once), AFTER the gate.
- ``attention``: grouped-query softmax attention, ``num_attention_heads``
  query heads on ``num_key_value_heads`` of ``hidden_size /
  num_attention_heads``, no bias, NO rotation and no other position signal
  (``position_embedding_type`` ``nope``), scores times
  ``attention_multiplier`` (NOT ``head_dim ** -0.5``), causal, softmax in
  float32.

Departures from the published description: the leaves of a layer are named
by role and lie apart (``w_qkv`` is ``[x | B | C]``, what the convolution runs
over; ``w_z``; ``w_dt``): a fixed split of ``in_proj``'s columns, a loader's
matter, nothing for weights drawn from a seed.  ``mamba_chunk_size`` is the
published CUDA kernel's tile and enters no sum: the recurrence here runs TOKEN
BY TOKEN.  What ``config.json`` does not settle is listed under ``assumed`` in
the configuration file.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, one whole sequence at a time, the
recurrence by a ``lax.scan`` over the tokens, no chunking of the rule, no
cache, no kernels, no batching.  It imports nothing of ``veles_tpu`` and makes
its own weights from the seed.  The weights are bfloat16 VALUES (what the
program serves); the arithmetic raises them to float32 a matrix at a time; what
is row-wise runs a block of rows at a time and attention in blocks of queries,
a head at a time, so that 4352 positions fit beside 6.4 GB of weights.

The weight tree is the one the served program takes: ``{"embed" (V, d), "ln_f"
(d,), "blocks": [{"attn": (a mamba layer) {"w_qkv" (d, h dv + 2 G dk), "w_z"
(d, h dv), "w_dt" (d, h), "conv" (taps, h dv + 2 G dk), "conv_bias" (h dv + 2
G dk,), "A_log", "dt_bias", "D" (h,), "norm" (h dv,), "wo" (h dv, d)} or (an
attention layer) {"wq" (d, heads dh), "wk", "wv" (d, kv dh), "wo" (heads dh,
d)}, "ln_attn", "ln_mlp" (d,), "w_gate", "w_up" (d, f), "w_down" (f, d)}]}``,
matrices as (in, out); no ``head``: it is ``embed``."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

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


def sizes(cfg):
    """The sizes the arithmetic needs, from the published keys."""
    heads = cfg["num_attention_heads"]
    return _Sizes({
        "d": cfg["hidden_size"], "heads": heads,
        "kv": cfg["num_key_value_heads"],
        "dh": cfg.get("head_dim") or cfg["hidden_size"] // heads,
        "eps": cfg["rms_norm_eps"], "f": cfg["shared_intermediate_size"],
        "h": cfg["mamba_n_heads"], "dv": cfg["mamba_d_head"],
        "groups": cfg["mamba_n_groups"], "dk": cfg["mamba_d_state"],
        "taps": cfg["mamba_d_conv"],
        "embed_mult": float(cfg["embedding_multiplier"]),
        "attn_mult": float(cfg["attention_multiplier"]),
        "res_mult": float(cfg["residual_multiplier"]),
        "logits_div": float(cfg["logits_scaling"]),
        "vocab": cfg["vocab_size"], "layers": cfg["num_hidden_layers"],
        "types": tuple(cfg["layer_types"]),
    })


def make_weights(seed, cfg):
    """The whole bfloat16 weight tree on the device, made there from the
    seed, one jitted program per layer.  Matrices normal(0,
    ``initializer_std``), THE EMBEDDING normal(0, ``initializer_std /
    embedding_multiplier``): its rows enter the stream times the multiplier,
    at the other matrices' scale.  (Drawn at ``initializer_std`` itself, a
    token's own row would lie some ten standard deviations above every other
    row's logit through the tied head: the model would answer every token
    with itself whatever its layers do, and no fault and no lower precision
    would move a served token; a trained checkpoint's rows do not.)  Norm
    gains 1 + normal(0, 0.1); and the family's
    own initialisation of a state-space layer (``mamba_ssm``'s ``Mamba2``):
    ``A_log = log U(1, 16)``, ``dt_bias`` the inverse softplus of ``exp(U(log
    1e-3, log 1e-1))``, ``D = 1``, the convolution's taps and bias U(-k, k)
    with ``k = taps^-1/2`` (a depthwise ``Conv1d``'s default); every leaf
    drawn in float32 and rounded to bfloat16 once."""
    z = sizes(cfg)
    d, std = z["d"], cfg["initializer_std"]
    inner = z["h"] * z["dv"]
    ch = inner + 2 * z["groups"] * z["dk"]

    def normal(k, shape, scale=std):
        return (scale * jax.random.normal(k, shape, jnp.float32)).astype(BF16)

    def uniform(k, shape, lo, hi):
        return jax.random.uniform(k, shape, jnp.float32, lo, hi)

    def gain(k, m):
        return (1.0 + 0.1 * jax.random.normal(k, (m,), jnp.float32)) \
            .astype(BF16)

    @functools.partial(jax.jit, static_argnames=("kind",))
    def block(key, kind):
        ks = jax.random.split(key, 16)
        if kind == "mamba":
            bound = z["taps"] ** -0.5
            dt = jnp.exp(uniform(ks[5], (z["h"],), jnp.log(1e-3),
                                 jnp.log(1e-1)))
            attn = {
                "w_qkv": normal(ks[0], (d, ch)),
                "w_z": normal(ks[1], (d, inner)),
                "w_dt": normal(ks[2], (d, z["h"])),
                "conv": uniform(ks[3], (z["taps"], ch), -bound, bound)
                .astype(BF16),
                "conv_bias": uniform(ks[4], (ch,), -bound, bound)
                .astype(BF16),
                "A_log": jnp.log(uniform(ks[6], (z["h"],), 1.0, 16.0))
                .astype(BF16),
                # softplus(dt_bias) = dt
                "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(BF16),
                "D": jnp.ones((z["h"],), BF16),
                "norm": gain(ks[7], inner),
                "wo": normal(ks[8], (inner, d))}
        else:
            attn = {
                "wq": normal(ks[0], (d, z["heads"] * z["dh"])),
                "wk": normal(ks[1], (d, z["kv"] * z["dh"])),
                "wv": normal(ks[2], (d, z["kv"] * z["dh"])),
                "wo": normal(ks[8], (z["heads"] * z["dh"], d))}
        return {"attn": attn, "ln_attn": gain(ks[9], d),
                "ln_mlp": gain(ks[10], d),
                "w_gate": normal(ks[11], (d, z["f"])),
                "w_up": normal(ks[12], (d, z["f"])),
                "w_down": normal(ks[13], (z["f"], d))}

    @jax.jit
    def tables(key):
        k_embed, k_lnf = jax.random.split(key)
        return {"embed": normal(k_embed, (z["vocab"], d),
                                std / z["embed_mult"]),
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
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


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


# ------------------------------------------------------------- state space
def mamba_mixer(u, p, z, control, leave_out=()):
    """The Mamba-2 mixer over normed rows ``u`` (L, d): (L, d).
    ``leave_out`` names parts a test drops to see that they matter
    (``conv_bias``, ``D``, ``gate_first``: the gate applied AFTER the norm
    instead of before it)."""
    h, dv, groups, dk = z["h"], z["dv"], z["groups"], z["dk"]
    inner, length = h * dv, u.shape[0]
    xbc = u @ lowered(p["w_qkv"], control)                # (L, ch)
    gate = u @ lowered(p["w_z"], control)                 # (L, inner)
    dt = jax.nn.softplus(u @ lowered(p["w_dt"], control)
                         + p["dt_bias"].astype(jnp.float32))      # (L, h)
    g = -jnp.exp(p["A_log"].astype(jnp.float32)) * dt
    taps = z["taps"]
    conv = lowered(p["conv"], control)
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, xbc.shape[1]), jnp.float32), xbc])
    acc = sum(padded[j:j + length] * conv[j] for j in range(taps))
    if "conv_bias" not in leave_out:
        acc = acc + lowered(p["conv_bias"], control)
    act = jax.nn.silu(acc)
    x = act[:, :inner].reshape(length, h, dv)
    per = h // groups

    def of_heads(y):                    # a group's row for each of its heads
        return jnp.repeat(y.reshape(length, groups, dk), per, axis=1)

    b_rows = of_heads(act[:, inner:inner + groups * dk])  # (L, h, dk)
    c_rows = of_heads(act[:, inner + groups * dk:])

    def token(s, t):
        x_t, b_t, c_t, dt_t, g_t = t
        s = jnp.exp(g_t)[:, None, None] * s \
            + b_t[:, :, None] * (dt_t[:, None] * x_t)[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, c_t)

    _, y = jax.lax.scan(token, jnp.zeros((h, dk, dv), jnp.float32),
                        (x, b_rows, c_rows, dt, g))       # (L, h, dv)
    if "D" not in leave_out:
        y = y + p["D"].astype(jnp.float32)[:, None] * x
    y = y.reshape(length, inner)
    if "gate_first" in leave_out:
        y = rms(y, p["norm"], z["eps"]) * jax.nn.silu(gate)
    else:
        y = rms(y * jax.nn.silu(gate), p["norm"], z["eps"])
    return y @ lowered(p["wo"], control)


# --------------------------------------------------------------- attention
def attention_mixer(u, p, z, control, leave_out=()):
    """Grouped-query attention without positions over normed rows ``u`` (L,
    d), a head and a block of queries at a time: (L, d)."""
    h, kv, dh = z["heads"], z["kv"], z["dh"]
    length = u.shape[0]
    block = min(ROWS, length)
    q = (u @ lowered(p["wq"], control)).reshape(length, h, dh)
    k = (u @ lowered(p["wk"], control)).reshape(length, kv, dh)
    v = (u @ lowered(p["wv"], control)).reshape(length, kv, dh)
    scale = dh ** -0.5 if "attention_multiplier" in leave_out \
        else z["attn_mult"]
    j = jnp.arange(length)[None, :]
    firsts = jnp.arange(0, length, block)

    def head(n):
        kn, vn = k[:, n // (h // kv)], v[:, n // (h // kv)]
        qn = q[:, n].reshape(-1, block, dh)

        def queries(args):
            qb, first = args
            s = (qb @ kn.T) * scale
            s = jnp.where(j <= (first + jnp.arange(block))[:, None], s,
                          -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ vn

        return jax.lax.map(queries, (qn, firsts)).reshape(length, dh)

    o = jax.lax.map(head, jnp.arange(h))                  # (h, L, dh)
    return jnp.moveaxis(o, 0, 1).reshape(length, h * dh) \
        @ lowered(p["wo"], control)


# -------------------------------------------------------------- the layers
@functools.partial(jax.jit,
                   static_argnames=("z", "kind", "control", "leave_out"))
def mixer_layer(x, blk, z, kind, control, leave_out=()):
    """One mixer sublayer over the stream ``x`` (L, d): its residual added."""
    u = by_rows(lambda xs: rms(xs, blk["ln_attn"], z["eps"]), x)
    mixer = mamba_mixer if kind == "mamba" else attention_mixer
    mult = 1.0 if "residual_multiplier" in leave_out else z["res_mult"]
    return x + mult * mixer(u, blk["attn"], z, control, leave_out)


@functools.partial(jax.jit, static_argnames=("z", "control", "leave_out"),
                   donate_argnums=(0,))
def feed_forward(x, blk, z, control, leave_out=()):
    mult = 1.0 if "residual_multiplier" in leave_out else z["res_mult"]

    def rows(xs):
        m = rms(xs, blk["ln_mlp"], z["eps"])
        up = m @ lowered(blk["w_up"], control)
        gate = jax.nn.silu(m @ lowered(blk["w_gate"], control))
        return xs + mult * ((gate * up) @ lowered(blk["w_down"], control))

    return by_rows(rows, x)


@functools.partial(jax.jit, static_argnames=("eps", "div", "control"))
def head(x, ln_f, embed, eps, div, control):
    return (rms(x, ln_f, eps) @ lowered(embed, control).T) / div


def logits(weights, tokens, rows, cfg, control=None, leave_out=()):
    """Logits (len(rows), vocab) after the positions ``rows`` of one sequence
    ``tokens`` (L,), layer by layer.  Padding the sequence at its end leaves
    earlier positions unchanged (causal), so callers pad to one length and
    compile once; a sequence longer than one block of rows is padded here to
    whole blocks.  ``leave_out`` (a tuple of names; tests only) drops one
    part of the mathematics: ``embedding_multiplier``,
    ``attention_multiplier``, ``residual_multiplier``, ``logits_scaling``,
    ``conv_bias``, ``D``, ``gate_first``."""
    z = sizes(cfg)
    leave_out = tuple(leave_out)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        if tokens.shape[0] > ROWS and tokens.shape[0] % ROWS:
            tokens = jnp.pad(tokens, (0, -tokens.shape[0] % ROWS))
        x = weights["embed"][tokens].astype(jnp.float32)
        if "embedding_multiplier" not in leave_out:
            x = x * z["embed_mult"]
        for kind, blk in zip(z["types"], weights["blocks"]):
            x = mixer_layer(x, blk, z, kind, control, leave_out)
            x = feed_forward(x, blk, z, control, leave_out)
        div = 1.0 if "logits_scaling" in leave_out else z["logits_div"]
        return head(x[jnp.asarray(rows)], weights["ln_f"], weights["embed"],
                    z["eps"], div, control)


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

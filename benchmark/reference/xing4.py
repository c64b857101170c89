"""Plain reference for the Xing4.0 family (``model_type: xing4_0``; the
published ``config.json`` of ``XingChen-AGI/Xing4.0-29B-A4B``): decoder-only;
LATENT attention (MLA: queries through a rank-``q_lora_rank`` bottleneck, keys
and values re-expanded from one rank-``kv_lora_rank`` latent a token beside
one rotated key shared by all heads, rotary positions scaled by YaRN); a
pre-RMSNorm block whose residual is ``hc_mult`` STREAMS mixed around every
sublayer by input-dependent coefficients, the stream-to-stream matrix made
doubly stochastic by Sinkhorn-Knopp (manifold-constrained hyper-connections,
arXiv:2512.24880); gated-SiLU feed forward, dense in the leading layers, then
sigmoid-routed experts (top-k of the scores plus a selection bias, weights
normalised and scaled) beside a shared expert; untied head.

Departures from the published description:

- THE MULTI-TOKEN-PREDICTION MODULE IS NOT LOADED (``num_nextn_predict_layers``
  1 -> 0): it predicts the token after next and changes no logit of the main
  model; serving it is self-speculation, which the program does not do.
- ``W_kvb`` is held by head in two leaves (``wk_b``, ``wv_b``): head ``h``'s
  columns ``[k_nope | v]`` of the published (kv_lora_rank, heads x 256)
  matrix, apart.  The same numbers, the same sums.
- The rotated parts of q and k are rotated in the half-split convention on
  the columns as they lie; the published code first permutes them from the
  interleaved order, a fixed permutation of ``W_qb``'s and ``W_kva``'s rope
  columns that changes nothing for weights drawn from a seed.
- What ``config.json`` does not settle (the mHC norm, the Sinkhorn's order,
  the readout, how the seed draws the mHC parameters) is listed under
  ``assumed`` in the configuration file.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, one whole sequence at a time,
EXPANDED attention only (keys and values rebuilt for every position), no
cache, no kernels, no batching.  It imports nothing of ``veles_tpu`` and makes
its own weights from the seed.  The weights are bfloat16 VALUES (what the
program serves); the arithmetic raises them to float32 a matrix or an expert
at a time.  So that a 32,896-token replay fits one chip beside them, what is
row-wise (the mHC coefficients and mixes, the feed forward, the projections)
runs a block of rows at a time and ATTENTION RUNS IN BLOCKS OF QUERIES, a head
at a time (the scores of a whole sequence would be 137 GB): the sums are the
same.

The weight tree is the one the served program takes: ``{"embed" (V, d),
"head" (d, V), "ln_f" (d,), "blocks": [{"attn": {"wq_a" (d, rq), "q_norm"
(rq,), "wq_b" (rq, h (nope + rope)), "wkv_a" (d, rkv + rope), "kv_norm"
(rkv,), "wk_b" (h, rkv, nope), "wv_b" (h, rkv, v), "wo" (h v, d)}, "ln_attn",
"ln_mlp" (d,), "hc_attn", "hc_mlp": {"proj" (n d, 2n + n n), "a" (3,), "b"
(2n + n n,)}, then "w_gate", "w_up", "w_down" (a dense layer) or "moe":
{"router" (d, E), "bias" (E,), "w_gate", "w_up" (E, d, f), "w_down" (E, f,
d), "shared": {"w_gate", "w_up", "w_down"}}}]}``, matrices as (in, out)."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

ROUTE_NORM_EPS = 1e-20
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
    scaling = cfg.get("rope_scaling") or {}
    return _Sizes({
        "d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "rq": cfg["q_lora_rank"], "rkv": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v": cfg["v_head_dim"], "ff": cfg["intermediate_size"],
        "fe": cfg["moe_intermediate_size"], "vocab": cfg["vocab_size"],
        "layers": cfg["num_hidden_layers"],
        "dense": cfg["first_k_dense_replace"],
        "experts": cfg["n_routed_experts"],
        "top_k": cfg["num_experts_per_tok"],
        "shared": cfg["n_shared_experts"],
        "route_scale": cfg["routed_scaling_factor"],
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "theta": cfg["rope_theta"], "eps": cfg["rms_norm_eps"],
        "n": cfg["hc_mult"], "hc_iters": cfg["hc_sinkhorn_iters"],
        "hc_eps": cfg["hc_eps"], "clamp_lo": cfg["mhc_h_res_clamp_min"],
        "clamp_hi": cfg["mhc_h_res_clamp_max"],
        "yarn_factor": scaling.get("factor", 1.0),
        "yarn_original": scaling.get("original_max_position_embeddings", 0),
        "beta_fast": scaling.get("beta_fast", 32),
        "beta_slow": scaling.get("beta_slow", 1),
        "mscale": scaling.get("mscale", 1),
        "mscale_all_dim": scaling.get("mscale_all_dim", 0),
    })


def make_weights(seed, cfg):
    """The whole bfloat16 weight tree on the device, made there from the
    seed, one jitted program per layer.  Matrices normal(0,
    ``initializer_std``), norm gains 1 + normal(0, 0.1), the router's
    selection bias normal(0, 0.01); every leaf is drawn in float32 and
    rounded to bfloat16 once.

    The mHC parameters of a sublayer (``assumed`` in the configuration
    file): ``proj`` normal(0, (n d)^-1/2), so that the three projections of
    the normed stream are of order 1; the scales ``a_pre``, ``a_post`` 0.5
    and ``a_res`` 0.2, each times 1 + normal(0, 0.1); the biases of H_pre
    and H_post normal(0, 0.5), and of H_res 1 on the diagonal plus
    normal(0, 0.25): a stream keeps the larger part of itself (about 0.55)
    and takes visibly from the others (H_res is NOT the identity, and
    differs from token to token through ``a_res``), and 20 rounds of
    Sinkhorn bring such a matrix to doubly stochastic within 1e-5 (with
    logits spread over 3 and more they would not)."""
    z = sizes(cfg)
    d, h, n, std = z["d"], z["heads"], z["n"], cfg["initializer_std"]

    def normal(k, shape, scale=std):
        return (scale * jax.random.normal(k, shape, jnp.float32)).astype(BF16)

    def gain(k, m):
        return (1.0 + 0.1 * jax.random.normal(k, (m,), jnp.float32)) \
            .astype(BF16)

    def ffn(k, width, lead=()):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"w_gate": normal(k1, lead + (d, width)),
                "w_up": normal(k2, lead + (d, width)),
                "w_down": normal(k3, lead + (width, d))}

    def hc(k):
        k1, k2, k3, k4 = jax.random.split(k, 4)
        bias = jnp.concatenate([
            0.5 * jax.random.normal(k3, (2 * n,), jnp.float32),
            (jnp.eye(n) + 0.25 * jax.random.normal(
                k4, (n, n), jnp.float32)).reshape(-1)])
        return {"proj": normal(k1, (n * d, 2 * n + n * n), (n * d) ** -0.5),
                "a": (jnp.asarray([0.5, 0.5, 0.2]) * (
                    1.0 + 0.1 * jax.random.normal(
                        k2, (3,), jnp.float32))).astype(BF16),
                "b": bias.astype(BF16)}

    @functools.partial(jax.jit, static_argnames=("routed",))
    def block(key, routed):
        ks = jax.random.split(key, 20)
        out = {
            "attn": {
                "wq_a": normal(ks[0], (d, z["rq"])),
                "q_norm": gain(ks[1], z["rq"]),
                "wq_b": normal(ks[2], (z["rq"],
                                       h * (z["nope"] + z["rope"]))),
                "wkv_a": normal(ks[3], (d, z["rkv"] + z["rope"])),
                "kv_norm": gain(ks[4], z["rkv"]),
                "wk_b": normal(ks[5], (h, z["rkv"], z["nope"])),
                "wv_b": normal(ks[6], (h, z["rkv"], z["v"])),
                "wo": normal(ks[7], (h * z["v"], d))},
            "ln_attn": gain(ks[8], d), "ln_mlp": gain(ks[9], d),
            "hc_attn": hc(ks[10]), "hc_mlp": hc(ks[11])}
        if not routed:
            return dict(out, **ffn(ks[12], z["ff"]))
        out["moe"] = dict(
            ffn(ks[12], z["fe"], (z["experts"],)),
            router=normal(ks[13], (d, z["experts"])),
            bias=(0.01 * jax.random.normal(
                ks[14], (z["experts"],), jnp.float32)).astype(BF16))
        if z["shared"]:
            out["moe"]["shared"] = ffn(ks[15], z["fe"] * z["shared"])
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


def rms(x, g, eps):
    y = x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)
    return y if g is None else y * g.astype(jnp.float32)


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


# -------------------------------------------------------------------- YaRN
def mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(z):
    """``f_i = theta^(-2i/rope)``; ``low``, ``high`` the correction range of
    (beta_fast, beta_slow); ``inv_freq_i = f_i / factor * ramp_i + f_i *
    (1 - ramp_i)``, ``ramp_i = clip((i - low) / (high - low), 0, 1)``."""
    dim, theta = z["rope"], z["theta"]
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    freq = theta ** (-2.0 * i / dim)
    if z["yarn_factor"] <= 1:
        return freq

    def correction(turns):
        return dim * math.log(z["yarn_original"] / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(z["beta_fast"])), 0)
    high = min(math.ceil(correction(z["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return freq / z["yarn_factor"] * ramp + freq * (1.0 - ramp)


def rotate(x, positions, z):
    """Rotary positions ``positions`` (L,) over (L, ..., rope): half-split
    convention, YaRN's frequencies, cos and sin times mscale(factor, mscale)
    / mscale(factor, mscale_all_dim)."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(z)
    mult = mscale(z["yarn_factor"], z["mscale"]) \
        / mscale(z["yarn_factor"], z["mscale_all_dim"])
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos = (jnp.cos(ang) * mult).reshape(shape)
    sin = (jnp.sin(ang) * mult).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def softmax_scale(z):
    m = (mscale(z["yarn_factor"], z["mscale_all_dim"])
         if z["mscale_all_dim"] else 1.0)
    return (z["nope"] + z["rope"]) ** -0.5 * m * m


# --------------------------------------------------------------------- mHC
def sinkhorn(m, iters, eps):
    """Column then row normalisation, ``iters`` times, each sum + eps."""
    for _ in range(iters):
        m = m / (m.sum(-2, keepdims=True) + eps)
        m = m / (m.sum(-1, keepdims=True) + eps)
    return m


def hc_coefficients(x, hc, z, control):
    """(H_pre (L, n), H_post (L, n), H_res (L, n, n)) of the streams ``x``
    (L, n, d)."""
    n = z["n"]
    xb = rms(x.reshape(x.shape[0], -1), None, z["eps"])
    t = xb @ lowered(hc["proj"], control)
    a, b = hc["a"].astype(jnp.float32), hc["b"].astype(jnp.float32)
    pre = jax.nn.sigmoid(a[0] * t[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * t[:, n:2 * n] + b[n:2 * n])
    res = (a[2] * t[:, 2 * n:] + b[2 * n:]).reshape(-1, n, n)
    res = sinkhorn(jnp.exp(jnp.clip(res, z["clamp_lo"], z["clamp_hi"])),
                   z["hc_iters"], z["hc_eps"])
    return pre, post, res


def hc_mix(x, f, post, res):
    """``X' = H_res X + H_post^T F``."""
    return jnp.einsum("lij,ljd->lid", res, x) + post[:, :, None] * f[:, None]


# --------------------------------------------------------------- attention
@functools.partial(jax.jit, static_argnames=("z", "control"))
def attention_inputs(x, blk, z, control):
    """Row-wise, before attention: the mHC coefficients of the attention
    sublayer, and the latent projections of its normed input ``rms(H_pre
    X)``: q (L, h, nope + rope) and the latent rows (c_kv (L, rkv) normed,
    k_rope (L, rope)), neither rotated yet."""
    p, h = blk["attn"], z["heads"]

    def rows(xs):
        pre, post, res = hc_coefficients(xs, blk["hc_attn"], z, control)
        u = rms(jnp.einsum("lj,ljd->ld", pre, xs), blk["ln_attn"], z["eps"])
        cq = rms(u @ lowered(p["wq_a"], control), p["q_norm"], z["eps"])
        q = (cq @ lowered(p["wq_b"], control)).reshape(
            -1, h, z["nope"] + z["rope"])
        kv = u @ lowered(p["wkv_a"], control)
        c_kv = rms(kv[:, :z["rkv"]], p["kv_norm"], z["eps"])
        return post, res, q, c_kv, kv[:, z["rkv"]:]

    return by_rows(rows, x)


@functools.partial(jax.jit, static_argnames=("z", "control"))
def attention_core(q, c_kv, k_rope, blk, z, control):
    """Causal attention in the EXPANDED form, a head and a block of queries
    at a time: per head ``[k_nope | v] = c_kv W_kvb``, ``s = (q_nope .
    k_nope + q_rope . k_rope) * scale``, softmax, ``o = sum p v``; then
    ``concat(o) W_o``: (L, d)."""
    p, h, nope = blk["attn"], z["heads"], z["nope"]
    length = q.shape[0]
    block = min(ROWS, length)
    k_rope = rotate(k_rope, jnp.arange(length), z)
    scale = softmax_scale(z)
    j = jnp.arange(length)[None, :]
    firsts = jnp.arange(0, length, block)

    def head(n):
        k_nope = c_kv @ lowered(p["wk_b"][n], control)          # (L, nope)
        v = c_kv @ lowered(p["wv_b"][n], control)               # (L, v)
        qn = q[:, n].reshape(-1, block, q.shape[-1])

        def queries(args):
            qb, first = args
            at = first + jnp.arange(block)
            s = (qb[:, :nope] @ k_nope.T
                 + rotate(qb[:, nope:], at, z) @ k_rope.T) * scale
            s = jnp.where(j <= at[:, None], s, -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ v

        return jax.lax.map(queries, (qn, firsts)).reshape(length, -1)

    o = jax.lax.map(head, jnp.arange(h))                        # (h, L, v)
    wo = lowered(p["wo"], control).reshape(h, z["v"], -1)
    return jnp.einsum("hlv,hvd->ld", o, wo)


@functools.partial(jax.jit, donate_argnums=(0,))
def mix(x, f, post, res):
    return by_rows(hc_mix, x, f, post, res)


# ------------------------------------------------------------ feed forward
def gated(m, p, control, pick=None):
    """``(silu(m W_gate) * (m W_up)) W_down``; ``pick`` takes one expert of
    a stacked tree."""
    take = (lambda w: w) if pick is None else (lambda w: w[pick])
    up = m @ lowered(take(p["w_up"]), control)
    gate = jax.nn.silu(m @ lowered(take(p["w_gate"]), control))
    return (gate * up) @ lowered(take(p["w_down"]), control)


def route(m, p, z, control):
    """Per token and expert, the routing weight (0 where the expert was not
    chosen): (L, experts).  ``s = sigmoid(m W_r)``; top-k of ``s + b``
    (``noaux_tc``, one group); weights ``s`` of the chosen over their sum
    (+1e-20), times ``routed_scaling_factor``."""
    s = jax.nn.sigmoid(m @ lowered(p["router"], control))
    _, chosen = jax.lax.top_k(s + p["bias"].astype(jnp.float32), z["top_k"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if z["norm_topk"]:
        w = w / (w.sum(-1, keepdims=True) + ROUTE_NORM_EPS)
    w = z["route_scale"] * w
    return jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], chosen].set(w)


@functools.partial(jax.jit, static_argnames=("z", "control"),
                   donate_argnums=(0,))
def feed_forward(x, blk, z, control):
    """The feed-forward sublayer inside its mHC mix, row-wise: the dense
    feed forward, or the shared expert plus the routed experts, each over
    every token and weighted."""

    def rows(xs):
        pre, post, res = hc_coefficients(xs, blk["hc_mlp"], z, control)
        m = rms(jnp.einsum("lj,ljd->ld", pre, xs), blk["ln_mlp"], z["eps"])
        if "moe" not in blk:
            f = gated(m, blk, control)
        else:
            p = blk["moe"]
            w = route(m, p, z, control)
            f = (gated(m, p["shared"], control) if "shared" in p
                 else jnp.zeros_like(m))

            def add(e, f):
                return f + w[:, e, None] * gated(m, p, control, pick=e)

            f = jax.lax.fori_loop(0, z["experts"], add, f)
        return hc_mix(xs, f, post, res)

    return by_rows(rows, x)


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def head(x, ln_f, w_head, eps, control):
    """Final RMSNorm of the streams' sum, and the head."""
    return rms(x.sum(1), ln_f, eps) @ lowered(w_head, control)


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
        e = weights["embed"][tokens].astype(jnp.float32)
        x = jnp.broadcast_to(e[:, None, :], (e.shape[0], z["n"], z["d"]))
        for blk in weights["blocks"]:
            post, res, q, c_kv, k_rope = attention_inputs(x, blk, z, control)
            f = attention_core(q, c_kv, k_rope, blk, z, control)
            x = mix(x, f, post, res)
            x = feed_forward(x, blk, z, control)
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

"""Plain reference for the language model of ``inclusionAI/Ling-3.0-flash-VL``
(``model_type: ling3_flash``, a name of ours: the catalog's row states none):
decoder-only; a pre-RMSNorm block over one residual stream; of every
``layer_group_size`` layers the last mixes tokens through LATENT ATTENTION
(MLA), the others through KIMI DELTA ATTENTION (KDA, arXiv:2510.26692);
gated-SiLU feed forward, dense in the leading layers, then sigmoid-routed
experts chosen within ``topk_group`` of ``n_group`` groups beside one shared
expert; untied head.

A KDA layer (``h`` heads, key and value width ``head_dim`` each): ``[q | k |
v] = x W_qkv`` through a causal depthwise convolution of
``short_conv_kernel_size`` taps and SiLU; q and k L2-normalised over their
head, q times ``head_dim^-0.5``; ``beta = sigmoid(x W_b)`` a head; the log
decay a head AND KEY CHANNEL ``g = kda_lower_bound * sigmoid(exp(A_log) (x W_f
+ dt_bias))`` in ``(kda_lower_bound, 0)`` (``kda_safe_gate``; ``A_log`` a
head, ``dt_bias`` a channel; ``no_kda_lora``: ``W_f`` and ``W_g`` are single
matrices).  Per head, with the state ``S`` (key x value), zeros at a
sequence's start::

    S <- diag(exp(g_t)) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;
    o_t = S^T q_t

(the paper's ``S_t = (I - beta k k^T) Diag(alpha) S_{t-1} + beta k v^T``),
and the output is ``(rms_head(o_t) w_n * sigmoid(x W_g)) W_o``.  No positions.

The MLA layer: ``q = x W_q`` (NO bottleneck: ``q_lora_rank`` null), per head
``[q_nope | q_rope]``; ``[c | k_r] = x W_kva``, ``c = rms(c)``; per head
``[k_nope | v] = c W_kvb``; rotary positions on ``q_rope`` and ``k_r``
(half-split, no scaling); ``s = (q_nope . k_nope + q_rope . k_r) (nope +
rope)^-0.5``, causal softmax; each head's output times ``sigmoid(x w_gate)_h``
(``gated_attention_proj_granularity_type: head_wise``), then ``W_o``.

Routing (DeepSeek-V3's ``noaux_tc``): ``s = sigmoid(x W_r)`` over all
``router_width`` experts; choice scores ``s + bias``; a group's score is the
sum of its two largest choice scores; the ``topk_group`` best of ``n_group``
groups stay; the ``num_experts_per_tok`` best experts among them by choice
score; weights the chosen experts' UNBIASED ``s`` over their sum (+1e-20),
times ``routed_scaling_factor``.

Departures from the published description are listed under ``assumed`` in the
configuration file: no vision tower (token ids only), no multi-token-
prediction module, the SwiGLU limits refused where nonzero, the leaves named
by role (``w_qkv`` is ``[W_q | W_k | W_v]``, ``w_z`` is ``W_g``; ``W_kvb`` by
head in ``wk_b``, ``wv_b``).

**The share it computes is the configuration's** (``deployment_share``): this
chip holds experts ``held[0] .. held[0] + held[1] - 1`` of the router's
``router_width`` and rows ``0 .. vocab_size - 1`` of embedding and head.  The
router scores ALL experts and chooses among all groups; the routed sum runs
over the chosen experts that are held, and what the absent ones would add is
left out, as the program leaves it out.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, one whole sequence at a time, THE
RECURRENT FORM OF THE RULE TOKEN BY TOKEN (``lax.scan``; never the chunked
one), latent attention EXPANDED, no cache, no kernels, no batching.  It
imports nothing of ``veles_tpu`` and makes its own weights from the seed.  The
weights are bfloat16 VALUES (what the program serves); the arithmetic raises
them to float32 a matrix or an expert at a time; what is row-wise runs a block
of rows at a time and attention runs in blocks of queries, a head at a time.

The weight tree is the one the served program takes: ``{"embed" (V, d), "head"
(d, V), "ln_f" (d,), "blocks": [{"attn": (a KDA layer) {"w_qkv" (d, 3 h dh),
"w_z" (d, h dh), "w_b" (d, h), "w_f" (d, h dh), "conv" (taps, 3 h dh), "A_log"
(h,), "dt_bias" (h dh,), "norm" (dh,), "wo" (h dh, d)} or (the MLA layer)
{"wq" (d, h (nope + rope)), "wkv_a" (d, rkv + rope), "kv_norm" (rkv,), "wk_b"
(h, rkv, nope), "wv_b" (h, rkv, v), "w_gate" (d, h), "wo" (h v, d)}, "ln_attn",
"ln_mlp" (d,), then "w_gate", "w_up", "w_down" (a dense layer) or "moe":
{"router" (d, E), "bias" (E,), "w_gate", "w_up" (n, d, f), "w_down" (n, f, d),
"shared": {"w_gate", "w_up", "w_down"}}}]}``, matrices as (in, out)."""

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
KDA, MLA = "linear_attention", "full_attention"


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
    """``layer_types`` as written, or derived from ``layer_group_size``: the
    last layer of every group is latent attention."""
    if cfg.get("layer_types"):
        return tuple(cfg["layer_types"])
    every = cfg["layer_group_size"]
    return tuple(MLA if (i + 1) % every == 0 else KDA
                 for i in range(cfg["num_hidden_layers"]))


def sizes(cfg):
    """The sizes the arithmetic needs, from the published keys."""
    lo, n = cfg.get("held_experts") or (0, cfg["num_experts"])
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any(cfg.get(key) or ()):
            raise ValueError("%s: a nonzero SwiGLU limit is not computed "
                             "(its form is in no source here)" % key)
    return _Sizes({
        "d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "dh": cfg["head_dim"], "taps": cfg["short_conv_kernel_size"],
        "lower": float(cfg["kda_lower_bound"]),
        "rkv": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"], "v": cfg["v_head_dim"],
        "theta": cfg["rope_theta"], "eps": cfg["rms_norm_eps"],
        "ff": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"],
        "fs": cfg["moe_shared_expert_intermediate_size"],
        "dense": cfg["first_k_dense_replace"],
        "router": cfg.get("router_width", cfg["num_experts"]),
        "lo": lo, "held": n, "top_k": cfg["num_experts_per_tok"],
        "groups": cfg["n_group"], "top_groups": cfg["topk_group"],
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "route_scale": float(cfg["routed_scaling_factor"]),
        "vocab": cfg["vocab_size"], "layers": cfg["num_hidden_layers"],
        "types": layer_types(cfg),
    })


def make_weights(seed, cfg):
    """The whole bfloat16 weight tree on the device, made there from the
    seed, one jitted program per layer.  Matrices normal(0,
    ``initializer_std``); norm gains 1 + normal(0, 0.1); the convolution's
    taps normal(0, taps^-1/2); the selection bias normal(0, 0.01), then
    brought to rest by :func:`even_load_biases` where the configuration
    names ``router_calibration_tokens``.

    The decay's parameters are drawn so that the heads span both ends of the
    gate: ``A_log = log U(0.5, 2)`` a head, and ``dt_bias`` a head's centre
    ``U(-10, 6)`` plus normal(0, 1) a channel.  ``x W_f`` is of order 1, so a
    head centred at 6 has ``g`` near ``kda_lower_bound`` on every channel
    (its state forgets within a token) and one centred at -10 has ``g`` of
    some -2e-4 (it keeps thousands of tokens), with every rate between.
    Every leaf is drawn in float32 and rounded to bfloat16 once."""
    z = sizes(cfg)
    d, h, dh, std = z["d"], z["heads"], z["dh"], cfg["initializer_std"]

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

    @functools.partial(jax.jit, static_argnames=("kind", "routed"))
    def block(key, kind, routed):
        ks = jax.random.split(key, 24)
        if kind == KDA:
            centre = jax.random.uniform(ks[5], (h, 1), jnp.float32, -10., 6.)
            attn = {
                "w_qkv": normal(ks[0], (d, 3 * h * dh)),
                "w_z": normal(ks[1], (d, h * dh)),
                "w_b": normal(ks[2], (d, h)),
                "w_f": normal(ks[3], (d, h * dh)),
                "conv": normal(ks[4], (z["taps"], 3 * h * dh),
                               z["taps"] ** -0.5),
                "A_log": jnp.log(jax.random.uniform(
                    ks[6], (h,), jnp.float32, 0.5, 2.0)).astype(BF16),
                "dt_bias": (centre + jax.random.normal(
                    ks[7], (h, dh), jnp.float32)).reshape(-1).astype(BF16),
                "norm": gain(ks[8], dh),
                "wo": normal(ks[9], (h * dh, d))}
        else:
            attn = {
                "wq": normal(ks[0], (d, h * (z["nope"] + z["rope"]))),
                "wkv_a": normal(ks[1], (d, z["rkv"] + z["rope"])),
                "kv_norm": gain(ks[2], z["rkv"]),
                "wk_b": normal(ks[3], (h, z["rkv"], z["nope"])),
                "wv_b": normal(ks[4], (h, z["rkv"], z["v"])),
                "w_gate": normal(ks[5], (d, h)),
                "wo": normal(ks[9], (h * z["v"], d))}
        out = {"attn": attn, "ln_attn": gain(ks[10], d),
               "ln_mlp": gain(ks[11], d)}
        if not routed:
            return dict(out, **ffn(ks[12], z["ff"]))
        out["moe"] = dict(
            ffn(ks[12], z["fe"], (z["held"],)),
            router=normal(ks[13], (d, z["router"])),
            bias=(0.01 * jax.random.normal(
                ks[14], (z["router"],), jnp.float32)).astype(BF16),
            shared=ffn(ks[15], z["fs"]))
        return out

    @jax.jit
    def tables(key):
        k_embed, k_head, k_lnf = jax.random.split(key, 3)
        return {"embed": normal(k_embed, (z["vocab"], d)),
                "head": normal(k_head, (d, z["vocab"])),
                "ln_f": gain(k_lnf, d)}

    k_tables, k_blocks = jax.random.split(seed_key(seed))
    out = tables(k_tables)
    out["blocks"] = [
        block(k, kind=z["types"][i], routed=i >= z["dense"])
        for i, k in enumerate(jax.random.split(k_blocks, z["layers"]))]
    if cfg.get("router_calibration_tokens"):
        even_load_biases(out, seed, cfg)
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


# ---------------------------------------------------- Kimi delta attention
@functools.partial(jax.jit, static_argnames=("z", "control"))
def kda_layer(x, blk, z, control):
    """One KDA sublayer over the stream ``x`` (L, d): its residual added."""
    p, h, dh = blk["attn"], z["heads"], z["dh"]
    length = x.shape[0]
    u = by_rows(lambda xs: rms(xs, blk["ln_attn"], z["eps"]), x)
    qkv = u @ lowered(p["w_qkv"], control)                # (L, 3 h dh)
    gate = u @ lowered(p["w_z"], control)                 # (L, h dh)
    beta = jax.nn.sigmoid(u @ lowered(p["w_b"], control))       # (L, h)
    f = (u @ lowered(p["w_f"], control)).reshape(length, h, dh)
    g = z["lower"] * jax.nn.sigmoid(
        jnp.exp(p["A_log"].astype(jnp.float32))[:, None]
        * (f + p["dt_bias"].astype(jnp.float32).reshape(h, dh)))
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
        y = y.reshape(length, h, dh)
        return y * jax.lax.rsqrt((y * y).sum(-1, keepdims=True) + L2_EPS)

    q = unit(act[:, :h * dh]) * dh ** -0.5
    k = unit(act[:, h * dh:2 * h * dh])
    v = act[:, 2 * h * dh:].reshape(length, h, dh)

    def token(s, t):
        q_t, k_t, v_t, beta_t, g_t = t
        s = jnp.exp(g_t)[:, :, None] * s                  # a row of S each
        d_t = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * d_t[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((h, dh, dh), jnp.float32),
                        (q, k, v, beta, g))               # (L, h, dh)
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + z["eps"]) \
        * p["norm"].astype(jnp.float32)
    o = o * jax.nn.sigmoid(gate.reshape(length, h, dh))
    return x + o.reshape(length, h * dh) @ lowered(p["wo"], control)


# -------------------------------------------------------- latent attention
def rotate(x, positions, z):
    """Rotary positions (L,) over (L, ..., rope): half-split, ``f_i =
    theta^(-2i/rope)``, no scaling."""
    half = x.shape[-1] // 2
    freq = z["theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freq
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("z", "control"))
def mla_layer(x, blk, z, control):
    """The latent-attention sublayer over ``x`` (L, d), expanded, a head and
    a block of queries at a time: its residual added."""
    p, h = blk["attn"], z["heads"]
    nope, rope, rkv = z["nope"], z["rope"], z["rkv"]
    length = x.shape[0]
    block = min(ROWS, length)
    at = jnp.arange(length)
    u = by_rows(lambda xs: rms(xs, blk["ln_attn"], z["eps"]), x)
    q = (u @ lowered(p["wq"], control)).reshape(length, h, nope + rope)
    q_nope, q_rope = q[..., :nope], rotate(q[..., nope:], at, z)
    kv = u @ lowered(p["wkv_a"], control)
    c = rms(kv[:, :rkv], p["kv_norm"], z["eps"])
    k_rope = rotate(kv[:, rkv:], at, z)                   # (L, rope)
    gate = jax.nn.sigmoid(u @ lowered(p["w_gate"], control))     # (L, h)
    scale = (nope + rope) ** -0.5
    j = at[None, :]
    firsts = jnp.arange(0, length, block)

    def head(n):
        k_nope = c @ lowered(p["wk_b"][n], control)       # (L, nope)
        vn = c @ lowered(p["wv_b"][n], control)           # (L, v)
        qn = q_nope[:, n].reshape(-1, block, nope)
        qr = q_rope[:, n].reshape(-1, block, rope)

        def queries(args):
            qb, rb, first = args
            s = (qb @ k_nope.T + rb @ k_rope.T) * scale
            s = jnp.where(j <= (first + jnp.arange(block))[:, None], s,
                          -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ vn

        return jax.lax.map(queries, (qn, qr, firsts)).reshape(length, -1)

    o = jax.lax.map(head, jnp.arange(h))                  # (h, L, v)
    o = jnp.moveaxis(o, 0, 1) * gate[:, :, None]
    return x + o.reshape(length, h * z["v"]) @ lowered(p["wo"], control)


# ------------------------------------------------------------ feed forward
def gated(m, p, control, pick=None):
    """``(silu(m W_gate) * (m W_up)) W_down``; ``pick`` takes one expert of
    a stacked tree."""
    take = (lambda w: w) if pick is None else (lambda w: w[pick])
    up = m @ lowered(take(p["w_up"]), control)
    gate = jax.nn.silu(m @ lowered(take(p["w_gate"]), control))
    return (gate * up) @ lowered(take(p["w_down"]), control)


def choose(choice, z):
    """The experts a token chooses, (L, top_k) of ``router``, by its choice
    scores (L, router): the ``top_groups`` groups whose two largest choice
    scores sum highest stay, and the ``top_k`` best experts among them."""
    groups = choice.reshape(choice.shape[0], z["groups"], -1)
    group_score = jax.lax.top_k(groups, 2)[0].sum(-1)     # (L, groups)
    _, kept = jax.lax.top_k(group_score, z["top_groups"])
    keep = jnp.zeros(group_score.shape, bool).at[
        jnp.arange(choice.shape[0])[:, None], kept].set(True)
    kept_only = jnp.where(keep[:, :, None], groups, -jnp.inf)
    return jax.lax.top_k(kept_only.reshape(choice.shape), z["top_k"])[1]


def route_all(m, p, z, control):
    """Per token and expert of ALL ``router`` experts, the routing weight (0
    where the expert was not chosen): (L, router).  Group-limited: see the
    module's text."""
    s = jax.nn.sigmoid(m @ lowered(p["router"], control))
    chosen = choose(s + p["bias"].astype(jnp.float32), z)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if z["norm_topk"]:
        w = w / (w.sum(-1, keepdims=True) + ROUTE_NORM_EPS)
    w = w * z["route_scale"]
    return jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], chosen].set(w)


def expert_layer(m, p, z, control):
    """The routed layer over normed rows ``m`` (L, d): the held experts'
    part of the routed sum, each over every token and weighted, plus the
    shared expert."""
    w = route_all(m, p, z, control)[:, z["lo"]:z["lo"] + z["held"]]

    def add(e, f):
        return f + w[:, e, None] * gated(m, p, control, pick=e)

    return jax.lax.fori_loop(0, z["held"], add,
                             gated(m, p["shared"], control))


@functools.partial(jax.jit, static_argnames=("z", "control", "routed"),
                   donate_argnums=(0,))
def feed_forward(x, blk, z, control, routed):
    def rows(xs):
        m = rms(xs, blk["ln_mlp"], z["eps"])
        if routed:
            return xs + expert_layer(m, blk["moe"], z, control)
        return xs + gated(m, blk, control)
    return by_rows(rows, x)


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def head(x, ln_f, w_head, eps, control):
    return rms(x, ln_f, eps) @ lowered(w_head, control)


# ------------------------------------------- the selection bias at rest
#: rounds of the bias update, its first step (it falls linearly to 0)
BIAS_ROUNDS, BIAS_STEP = 200, 0.02


@functools.partial(jax.jit, static_argnames=("z",))
def even_bias(m, p, z):
    """The selection bias of one expert layer after ``noaux_tc``'s own
    update (raise an expert's bias while it is chosen less than its even
    share, lower it while more) has come to rest on the normed rows ``m``
    (L, d): every one of the ``router`` experts is then chosen about ``L
    top_k / router`` times.  Mean 0, bfloat16."""
    s = jax.nn.sigmoid(m @ p["router"].astype(jnp.float32))
    even = m.shape[0] * z["top_k"] / z["router"]

    def update(i, bias):
        load = jnp.zeros(z["router"]).at[
            choose(s + bias, z).reshape(-1)].add(1.0)
        return bias + BIAS_STEP * (1.0 - i / BIAS_ROUNDS) * jnp.clip(
            (even - load) / even, -1.0, 1.0)

    bias = jax.lax.fori_loop(0, BIAS_ROUNDS, update,
                             p["bias"].astype(jnp.float32))
    return (bias - bias.mean()).astype(BF16)


def even_load_biases(weights, seed, cfg):
    """What the selection bias is FOR, done to a seeded tree in place: a
    published checkpoint's bias was moved all through training until every
    expert's load was even; a bias drawn near zero leaves the load to the
    seed.  With weights from a seed the stream has a large component common
    to all tokens (the KDA heads that keep thousands of tokens read out a
    running mean), the router's scores of it favour the same experts for
    every token, and a chip's share of the assignments and the experts it
    reads a step moved with the seed (24-33 % and 31-67 of 110 in a
    simulation; 60 hit a layer where an even load gives 81; PERF.md section
    6, PR 42).  So ``router_calibration_tokens`` random tokens (one sequence,
    from the seed) go through the stack as the reference computes it, layer
    by layer, and each expert layer's bias is set by :func:`even_bias` on
    ITS OWN normed input before the stream goes on through it."""
    z = sizes(cfg)
    tokens = jax.random.randint(
        jax.random.fold_in(seed_key(seed), 1),
        (cfg["router_calibration_tokens"],), 0, z["vocab"])
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(jnp.float32)
        for i, (kind, blk) in enumerate(zip(z["types"], weights["blocks"])):
            x = (kda_layer if kind == KDA else mla_layer)(x, blk, z, None)
            if i >= z["dense"]:
                m = by_rows(lambda xs, blk=blk: rms(
                    xs, blk["ln_mlp"], z["eps"]), x)
                blk["moe"]["bias"] = even_bias(m, blk["moe"], z)
            x = feed_forward(x, blk, z, None, routed=i >= z["dense"])


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
        for i, (kind, blk) in enumerate(zip(z["types"], weights["blocks"])):
            mixer = kda_layer if kind == KDA else mla_layer
            x = feed_forward(mixer(x, blk, z, control), blk, z, control,
                             routed=i >= z["dense"])
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

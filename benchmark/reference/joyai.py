"""Plain reference for JoyAI-LLM-Flash (``model_type: joyai_llm_flash``; the
published ``config.json`` of ``jdopensource/JoyAI-LLM-Flash``): decoder-only,
pre-RMSNorm over ONE residual stream; LATENT attention (MLA: queries through
a rank-``q_lora_rank`` bottleneck, keys and values re-expanded from one
rank-``kv_lora_rank`` latent a token beside one rotated key shared by all
heads; plain rotary positions, ``rope_scaling`` null); gated-SiLU feed
forward, dense in the leading layer, then sigmoid-routed experts (top-k of the
scores plus a selection bias, weights normalised and scaled) beside a shared
expert; untied head; and ONE MULTI-TOKEN-PREDICTION MODULE
(``num_nextn_predict_layers`` 1; DeepSeek-V3, arXiv:2412.19437 section 2.2),
LOADED: with ``h_i`` the last block's output at position ``i`` before the
final norm and ``t_{i+1}`` the token that follows, ``x'_i = W_eh
[rms_e(Emb(t_{i+1})) ; rms_h(h_i)]`` (the embedding half first), ``y =
Block(x')`` (one whole expert layer of its own, causal over the sequence, row
``i`` at rotary position ``i``), and ``Head(rms_s(y_i))``, with the module's
own final norm and the MAIN model's embedding and head, scores the token at
``i + 2``.

This chip holds ONE SHARE of the routed experts (``held_experts`` ``[lo, n]``
of ``router_width`` that the router scores): the sum over a token's chosen
experts takes the held ones' terms and leaves the others' out, here as in the
program.

Departures from the published description: ``W_kvb`` is held by head in two
leaves (``wk_b``, ``wv_b``); the rotated columns are rotated half-split as
they lie (``rope_interleave`` is a fixed permutation of weight columns,
nothing for weights drawn from a seed).  What ``config.json`` does not settle
is listed under ``assumed`` in the configuration file, the initialisation
(``mtp_init``) first among it.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, one whole sequence at a time,
EXPANDED attention only, no cache, no kernels, no batching.  It imports
nothing of ``veles_tpu`` and makes its own weights from the seed.  The weights
are bfloat16 VALUES (what the program serves); the arithmetic raises them to
float32 a matrix or an expert at a time.  What is row-wise runs a block of
rows at a time (``by_rows``) and attention a head and a block of queries at a
time, so that an 8,192-token replay over a vocabulary of 129,280 fits one chip
beside the weights; the logits of 4,096 rows are never held whole (``picks``).

The weight tree is the one the served program takes: ``{"embed" (V, d),
"head" (d, V), "ln_f" (d,), "blocks": [{"attn": {"wq_a", "q_norm", "wq_b",
"wkv_a", "kv_norm", "wk_b", "wv_b", "wo"}, "ln_attn", "ln_mlp", then "w_gate",
"w_up", "w_down" (a dense layer) or "moe": {"router" (d, E), "bias" (E,),
"w_gate", "w_up" (held, d, f), "w_down" (held, f, d), "shared": {...}}}],
"mtp": [{"enorm", "hnorm", "norm" (d,), "eh_proj" (2 d, d), "block": an
expert layer's tree}]}``, matrices as (in, out)."""

from __future__ import annotations

import functools

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
    """The sizes the arithmetic needs, from the published keys and the two
    of the deployment's share."""
    lo, held = cfg.get("held_experts") or (0, cfg["n_routed_experts"])
    return _Sizes({
        "d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "rq": cfg["q_lora_rank"], "rkv": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v": cfg["v_head_dim"], "ff": cfg["intermediate_size"],
        "fe": cfg["moe_intermediate_size"], "vocab": cfg["vocab_size"],
        "layers": cfg["num_hidden_layers"],
        "dense": cfg["first_k_dense_replace"],
        "router": cfg.get("router_width", cfg["n_routed_experts"]),
        "lo": lo, "held": held, "top_k": cfg["num_experts_per_tok"],
        "shared": cfg["n_shared_experts"],
        "route_scale": cfg["routed_scaling_factor"],
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "theta": cfg["rope_theta"], "eps": cfg["rms_norm_eps"],
        "nextn": cfg["num_nextn_predict_layers"],
    })


def balanced_bias(bias, share):
    """``bias`` (router width,) with each run of ``share`` experts (one
    chip's) shifted so that its mean is the mean of all
    (``reference/afmoe.py`` says why)."""
    groups = bias.reshape(-1, share)
    return (groups - groups.mean(1, keepdims=True) + bias.mean()).reshape(-1)


def make_weights(seed, cfg):
    """The whole bfloat16 weight tree on the device, made there from the
    seed, one jitted program per layer.  Matrices normal(0,
    ``initializer_std``), norm gains 1 + normal(0, 0.1), the router's
    selection bias normal(0, 0.01), balanced across the chips' shares; every
    leaf is drawn in float32 and rounded to bfloat16 once.

    ``mtp_init`` (a block of the configuration file; absent or null: every
    matrix at ``initializer_std``, the module a stranger to the stack) gives
    the seeded weights the structure that training gives a module, by
    weights alone:

    - THE CONTEXT'S SHARE STAYS IN THE STACK, in its LAST layer, which is
      drawn at ``initializer_std`` like any other; the layers BEFORE it
      write into the residual stream (``wo`` and the down projections) at
      ``residual_std``, so that what the last layer reads at ``i + 1`` is
      the embedding of ``t_{i+1}`` but for their share;
    - THE MODULE IS TIED TO IT: its block starts as the last layer's and
      its final norm as the stack's (copies, held beside them as a
      trained module's are), ``enorm``'s gain is 1, and ``eh_proj =
      initializer_std x [I | h_mix I]``: the module reads the embedding of
      ``t_{i+1}`` at the embedding's own size, as the last layer does at
      ``i + 1``, over its OWN latent rows of the same tokens, and mixes
      the stack's state ``h_i`` in at weight ``h_mix``.

    The smaller ``residual_std`` and ``h_mix``, the more often module and
    verifier agree; neither moves what a step costs."""
    z = sizes(cfg)
    d, h, std = z["d"], z["heads"], cfg["initializer_std"]
    init = cfg.get("mtp_init") or {}

    def normal(k, shape, scale=std):
        return (scale * jax.random.normal(k, shape, jnp.float32)).astype(BF16)

    def gain(k, m):
        return (1.0 + 0.1 * jax.random.normal(k, (m,), jnp.float32)) \
            .astype(BF16)

    def ffn(k, width, res_std, lead=()):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"w_gate": normal(k1, lead + (d, width)),
                "w_up": normal(k2, lead + (d, width)),
                "w_down": normal(k3, lead + (width, d), res_std)}

    @functools.partial(jax.jit, static_argnames=("routed", "res_std"))
    def block(key, routed, res_std=std):
        ks = jax.random.split(key, 16)
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
                "wo": normal(ks[7], (h * z["v"], d), res_std)},
            "ln_attn": gain(ks[8], d), "ln_mlp": gain(ks[9], d)}
        if not routed:
            return dict(out, **ffn(ks[10], z["ff"], res_std))
        out["moe"] = dict(
            ffn(ks[10], z["fe"], res_std, (z["held"],)),
            router=normal(ks[11], (d, z["router"])),
            bias=balanced_bias(0.01 * jax.random.normal(
                ks[12], (z["router"],), jnp.float32), z["held"]).astype(BF16))
        if z["shared"]:
            out["moe"]["shared"] = ffn(ks[13], z["fe"] * z["shared"],
                                       res_std)
        return out

    @jax.jit
    def tables(key):
        k_embed, k_head, k_lnf = jax.random.split(key, 3)
        return {"embed": normal(k_embed, (z["vocab"], d)),
                "head": normal(k_head, (d, z["vocab"])),
                "ln_f": gain(k_lnf, d)}

    @jax.jit
    def module(key, last, ln_f):
        k_e, k_h, k_s, k_eh, k_blk = jax.random.split(key, 5)
        if not init:
            return {"enorm": gain(k_e, d), "hnorm": gain(k_h, d),
                    "norm": gain(k_s, d), "eh_proj": normal(k_eh, (2 * d, d)),
                    "block": block(k_blk, routed=True)}
        eye = std * jnp.eye(d, dtype=jnp.float32)
        return {"enorm": jnp.ones((d,), BF16), "hnorm": gain(k_h, d),
                "norm": jnp.copy(ln_f),
                "eh_proj": jnp.concatenate(
                    [eye, init["h_mix"] * eye]).astype(BF16),
                "block": jax.tree.map(jnp.copy, last)}

    k_tables, k_blocks, k_mtp = jax.random.split(seed_key(seed), 3)
    out = tables(k_tables)
    out["blocks"] = [
        block(k, routed=i >= z["dense"],
              res_std=(std if i == z["layers"] - 1
                       else init.get("residual_std", std)))
        for i, k in enumerate(jax.random.split(k_blocks, z["layers"]))]
    out["mtp"] = [module(k, out["blocks"][-1], out["ln_f"])
                  for k in jax.random.split(k_mtp, z["nextn"])]
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
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


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


def rotate(x, positions, z):
    """Rotary positions ``positions`` (L,) over (L, ..., rope): half-split
    convention, ``f_i = theta^(-2i/rope)``."""
    half = x.shape[-1] // 2
    freq = z["theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freq
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# --------------------------------------------------------------- attention
@functools.partial(jax.jit, static_argnames=("z", "control"))
def attention(x, blk, z, control):
    """``x + MLA(rms(x))`` over a whole sequence (L, d), causal, in the
    EXPANDED form, a head and a block of queries at a time: per head
    ``[k_nope | v] = c_kv W_kvb``, ``s = (q_nope . k_nope + q_rope . k_rope)
    (nope + rope)^-1/2``, softmax, ``o = sum p v``; then ``concat(o) W_o``."""
    p, h, nope = blk["attn"], z["heads"], z["nope"]
    length = x.shape[0]

    def rows(xs):
        u = rms(xs, blk["ln_attn"], z["eps"])
        cq = rms(u @ lowered(p["wq_a"], control), p["q_norm"], z["eps"])
        q = (cq @ lowered(p["wq_b"], control)).reshape(
            -1, h, nope + z["rope"])
        kv = u @ lowered(p["wkv_a"], control)
        return q, rms(kv[:, :z["rkv"]], p["kv_norm"], z["eps"]), \
            kv[:, z["rkv"]:]

    q, c_kv, k_rope = by_rows(rows, x)
    block = min(ROWS, length)
    k_rope = rotate(k_rope, jnp.arange(length), z)
    scale = (nope + z["rope"]) ** -0.5
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
    return x + jnp.einsum("hlv,hvd->ld", o, wo)


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
    not chosen): (L, held).  ``s = sigmoid(m W_r)`` over all ``router``
    experts; top-k of ``s + b`` (``noaux_tc``, one group); weights ``s`` of
    the chosen over their sum (+1e-20), times ``routed_scaling_factor``."""
    s = jax.nn.sigmoid(m @ lowered(p["router"], control))
    _, chosen = jax.lax.top_k(s + p["bias"].astype(jnp.float32), z["top_k"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if z["norm_topk"]:
        w = w / (w.sum(-1, keepdims=True) + ROUTE_NORM_EPS)
    dense = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], chosen].set(z["route_scale"] * w)
    return dense[:, z["lo"]:z["lo"] + z["held"]]


@functools.partial(jax.jit, static_argnames=("z", "control"))
def feed_forward(x, blk, z, control):
    """``x + F(rms(x))``, row-wise: the dense feed forward, or the shared
    expert plus this share's routed experts, each over every token and
    weighted."""

    def rows(xs):
        m = rms(xs, blk["ln_mlp"], z["eps"])
        if "moe" not in blk:
            return xs + gated(m, blk, control)
        p = blk["moe"]
        w = route(m, p, z, control)
        f = (gated(m, p["shared"], control) if "shared" in p
             else jnp.zeros_like(m))

        def add(e, f):
            return f + w[:, e, None] * gated(m, p, control, pick=e)

        return xs + jax.lax.fori_loop(0, z["held"], add, f)

    return by_rows(rows, x)


def layer(x, blk, z, control):
    return feed_forward(attention(x, blk, z, control), blk, z, control)


# ------------------------------------------------------------- the readouts
@functools.partial(jax.jit, static_argnames=("eps", "control"))
def head(x, gain, w_head, eps, control):
    """A final RMSNorm (the stack's, or the module's own) and the head."""
    return rms(x, gain, eps) @ lowered(w_head, control)


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def picks(x, gain, w_head, at, eps, control):
    """Per row of ``x`` (R, d), without holding R x vocab logits: (the best
    logit, its token, the logits of the tokens ``at`` (R, n)), a block of
    rows at a time."""
    w = lowered(w_head, control)

    def rows(xs, ats):
        lg = rms(xs, gain, eps) @ w
        return lg.max(-1), lg.argmax(-1).astype(jnp.int32), \
            jnp.take_along_axis(lg, ats, axis=-1)

    pad = -x.shape[0] % ROWS if x.shape[0] > ROWS else 0
    out = by_rows(rows, jnp.pad(x, ((0, pad), (0, 0))),
                  jnp.pad(at, ((0, pad), (0, 0))))
    return jax.tree.map(lambda o: o[:x.shape[0]], out)


@functools.partial(jax.jit, static_argnames=("z", "control"))
def module_inputs(h, nxt, embed, mod, z, control):
    """``x'_i = W_eh [rms_e(Emb(t_{i+1})) ; rms_h(h_i)]``, row-wise."""
    w = lowered(mod["eh_proj"], control)

    def rows(hs, ts):
        e = embed[ts].astype(jnp.float32)
        return jnp.concatenate([rms(e, mod["enorm"], z["eps"]),
                                rms(hs, mod["hnorm"], z["eps"])], -1) @ w

    return by_rows(rows, h, nxt)


#: the last sequence's hidden states, one entry a control, each by (weights,
#: tokens): a driver that asks for the served tokens' gaps and then for the
#: module's hits and drafts on the same sequence runs the stack once in
#: each precision
_HIDDEN = {}


def _padded(tokens):
    tokens = jnp.asarray(tokens, jnp.int32)
    if tokens.shape[0] > ROWS and tokens.shape[0] % ROWS:
        tokens = jnp.pad(tokens, (0, -tokens.shape[0] % ROWS))
    return tokens


def hidden(weights, tokens, cfg, control=None):
    """The last block's output (L, d), before the final norm, of one sequence
    ``tokens`` (L,), layer by layer."""
    import numpy
    z = sizes(cfg)
    key = (id(weights), numpy.asarray(tokens).tobytes())
    held = _HIDDEN.get(control)
    if held is not None and held[0] == key and not held[1].is_deleted():
        return held[1]
    with jax.default_matmul_precision("highest"):
        tokens = _padded(tokens)
        x = weights["embed"][tokens].astype(jnp.float32)
        for blk in weights["blocks"]:
            x = layer(x, blk, z, control)
    _HIDDEN[control] = (key, x)
    return x


def module_hidden(weights, tokens, cfg, control=None):
    """The module's block's output ``y`` (L, d), before its own final norm:
    row ``i`` from ``h_i`` and ``tokens[i + 1]`` (the last row, which has no
    token behind it, takes token 0 and is of no use)."""
    z = sizes(cfg)
    mod = weights["mtp"][0]
    h = hidden(weights, tokens, cfg, control)
    with jax.default_matmul_precision("highest"):
        tokens = _padded(tokens)
        nxt = jnp.concatenate([tokens[1:], jnp.zeros(1, jnp.int32)])
        x = module_inputs(h, nxt, weights["embed"], mod, z, control)
        return layer(x, mod["block"], z, control)


def logits(weights, tokens, rows, cfg, control=None):
    """Logits (len(rows), vocab) after the positions ``rows`` of one sequence
    ``tokens`` (L,).  Padding the sequence at its end leaves earlier
    positions unchanged (causal), so callers pad to one length and compile
    once; a sequence longer than one block of rows is padded here to whole
    blocks."""
    x = hidden(weights, tokens, cfg, control)
    with jax.default_matmul_precision("highest"):
        return head(x[jnp.asarray(rows)], weights["ln_f"], weights["head"],
                    cfg["rms_norm_eps"], control)


def draft_logits(weights, tokens, rows, cfg, control=None):
    """The module's logits (len(rows), vocab) at the rows ``rows``: row ``i``
    scores the token at ``i + 2``."""
    y = module_hidden(weights, tokens, cfg, control)
    with jax.default_matmul_precision("highest"):
        return head(y[jnp.asarray(rows)], weights["mtp"][0]["norm"],
                    weights["head"], cfg["rms_norm_eps"], control)


def _pad_sequence(tokens, pad_to):
    import numpy
    tokens = numpy.asarray(tokens, numpy.int32)
    padded = numpy.zeros(max(pad_to or 0, len(tokens)), numpy.int32)
    padded[:len(tokens)] = tokens
    return tokens, padded


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
    tokens, padded = _pad_sequence(tokens, pad_to)
    n = len(tokens)
    count = n - first
    rows = numpy.minimum(numpy.arange(first - 1, first - 1 + rows_to), n - 2)
    at = numpy.zeros((rows_to, 2), numpy.int32)
    at[:count, 0] = tokens[first:]
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        if control is not None:
            low = hidden(weights, padded, cfg, control)[jnp.asarray(rows)]
            at[:, 1] = numpy.asarray(picks(
                low, weights["ln_f"], weights["head"], jnp.asarray(at), eps,
                control)[1])
        x = hidden(weights, padded, cfg)[jnp.asarray(rows)]
        best, _, got = picks(x, weights["ln_f"], weights["head"],
                             jnp.asarray(at), eps, None)
    gaps = numpy.asarray(best[:, None] - got)[:count]
    return gaps[:, 0], (gaps[:, 1] if control is not None else None)


def draft_hits(weights, tokens, first, cfg, pad_to=None, rows_to=None,
               control=None):
    """Teacher-forced on one served sequence: (hits, positions) of the
    positions ``i`` from ``first - 1`` (the prompt's last token, where the
    program's first draft is made) to the third from the end, at which the
    reference's MODULE's argmax is the token served two places on,
    ``tokens[i + 2]``.  hits / positions is the acceptance a drafter that
    drafted at every position would see; the program drafts at the ends of
    its steps only.  ``pad_to`` and ``rows_to`` as :func:`token_gaps` takes
    them, so both run the same compiled programs."""
    import numpy
    tokens, padded = _pad_sequence(tokens, pad_to)
    n = len(tokens)
    rows = numpy.arange(first - 1, n - 2)
    if not len(rows):
        return 0, 0
    take = numpy.minimum(
        numpy.arange(first - 1, first - 1 + max(rows_to or 0, len(rows))),
        n - 3)
    y = module_hidden(weights, padded, cfg, control)
    with jax.default_matmul_precision("highest"):
        _, choice, _ = picks(
            y[jnp.asarray(take)], weights["mtp"][0]["norm"], weights["head"],
            jnp.zeros((len(take), 2), jnp.int32), cfg["rms_norm_eps"],
            control)
    choice = numpy.asarray(choice)[:len(rows)]
    return int((choice == tokens[rows + 2]).sum()), len(rows)


def draft_gaps(weights, tokens, first, drafts, cfg, pad_to=None,
               rows_to=None, control=None):
    """The program's DRAFTS of one served sequence, held to the reference's
    module: ``drafts`` is ``[(n, token)]``, the module's choice for the
    ``n``-th new token (``tokens[first + n]``, n >= 1) as the program made
    it, from ``h`` at ``first + n - 2`` and the token behind it; for each,
    how far its logit under the reference's module, teacher-forced on the
    served sequence, lies below that module's best (0 where the program
    drafted what the reference's module puts first).  With ``control`` also
    the same gap for what the reference's module puts first at those rows
    when stack and module are computed with the weights rounded to that
    dtype.  Returns (gaps, control gaps or None) as host arrays."""
    import numpy
    tokens, padded = _pad_sequence(tokens, pad_to)
    drafts = numpy.asarray(drafts, numpy.int64).reshape(-1, 2)
    count = len(drafts)
    if not count:
        return numpy.zeros(0, numpy.float32), None
    width = max(rows_to or 0, count)
    rows = numpy.full(width, first - 1)
    rows[:count] = first + drafts[:, 0] - 2
    at = numpy.zeros((width, 2), numpy.int32)
    at[:count, 0] = drafts[:, 1]
    norm, eps = weights["mtp"][0]["norm"], cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        if control is not None:
            low = module_hidden(weights, padded, cfg, control)
            at[:, 1] = numpy.asarray(picks(
                low[jnp.asarray(rows)], norm, weights["head"],
                jnp.asarray(at), eps, control)[1])
        y = module_hidden(weights, padded, cfg)
        best, _, got = picks(y[jnp.asarray(rows)], norm, weights["head"],
                             jnp.asarray(at), eps, None)
    gaps = numpy.asarray(best[:, None] - got)[:count]
    return gaps[:, 0], (gaps[:, 1] if control is not None else None)

"""Transformer language model — the long-context model family.

Beyond-parity (the reference pre-dates attention, SURVEY §5.7): a decoder-
only transformer as pure functions over a param pytree, plus a trainer unit
whose whole step (forward, loss, backward, adam-style update) is ONE jitted
call — the same non-SGD-trainer shape as Kohonen/RBM, proving the graph
core carries attention models unchanged.

Long-sequence paths: ``block_size`` switches attention to the flash-style
blockwise kernel (single chip); ``ring`` runs sequence-parallel ring
attention over a mesh (veles_tpu.parallel.ring); ``rope``/``n_kv_heads``/
``window``/``attn_sinks`` give rotary positions, grouped-query caches,
sliding windows and StreamingLLM sinks; ``generate_rolling`` decodes
without bound in O(window) memory.
"""

from __future__ import annotations

import numpy

from veles_tpu import prng as prng_mod
from veles_tpu.accel import AcceleratedUnit
from veles_tpu.workflow import DeferredInitError
from veles_tpu.ops import functional as F
from veles_tpu import model_config
from veles_tpu.ops.attention import (cfg_matmul, init_mha_params,
                                     mha_forward, rms_norm)
from veles_tpu.ops.decision import DecisionBase


def init_transformer_params(stream, vocab, d_model=64, n_heads=4,
                            n_layers=2, d_ff=None, max_len=512,
                            dtype="float32", n_experts=0,
                            n_kv_heads=None, rope=False):
    """``n_experts > 0`` replaces every block's dense FFN with a
    top-1-routed mixture of experts (ops/moe.py) — expert weights carry
    an expert-major leading axis, shardable over an 'expert' mesh axis.
    ``n_kv_heads`` < n_heads makes attention grouped-query (smaller
    KV projections and decode caches); ``rope=True`` drops the learned
    positional table entirely — positions enter via rotary q/k."""
    d_ff = d_ff or 4 * d_model
    s_emb = d_model ** -0.5

    def dense(n_in, n_out):
        w = numpy.zeros((n_in, n_out), dtype)
        stream.fill(w, -(6.0 / (n_in + n_out)) ** 0.5,
                    (6.0 / (n_in + n_out)) ** 0.5)
        return w

    embed = numpy.zeros((vocab, d_model), dtype)
    stream.fill_normal(embed, 0.0, s_emb)
    pos = None
    if not rope:
        pos = numpy.zeros((max_len, d_model), dtype)
        stream.fill_normal(pos, 0.0, s_emb)
    blocks = []
    for _ in range(n_layers):
        blk = {
            "attn": init_mha_params(stream, d_model, n_heads, dtype,
                                    n_kv_heads=n_kv_heads),
            "ln1": {"g": numpy.ones(d_model, dtype),
                    "b": numpy.zeros(d_model, dtype)},
            "ln2": {"g": numpy.ones(d_model, dtype),
                    "b": numpy.zeros(d_model, dtype)},
        }
        if n_experts > 0:
            from veles_tpu.ops.moe import init_moe_params
            blk["moe"] = init_moe_params(stream, d_model, d_ff, n_experts,
                                         dtype)
        else:
            blk.update({
                "w1": dense(d_model, d_ff),
                "b1": numpy.zeros(d_ff, dtype),
                "w2": dense(d_ff, d_model),
                "b2": numpy.zeros(d_model, dtype),
            })
        blocks.append(blk)
    out = {"embed": embed, "blocks": blocks,
           "ln_f": {"g": numpy.ones(d_model, dtype),
                    "b": numpy.zeros(d_model, dtype)}}
    if pos is not None:
        out["pos"] = pos
    return out


def _layernorm(x, g, b, eps=1e-5):
    import jax.numpy as jnp
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def _wire(blk, h, cfg, layer, attend, with_aux=False, token_mask=None):
    """One decoder block around ``attend(attention params, normed input)
    -> (attention output, state)`` — THE wiring every path shares (whole
    forward, contiguous and paged decode, pipeline stages), so training
    and serving can never drift on it.  ``pre_ln``: LayerNorm, attention,
    residual; LayerNorm, feed forward, residual.  ``sandwich``: RMSNorm
    before AND after each half, the second norm inside the residual.
    Returns (h, state, extra) with ``extra`` the expert layer's load-
    balancing loss (``with_aux``, pre_ln) or its counts (sandwich; None
    for a dense layer).  ``pre_rms``: RMSNorm, the layer's mixer (latent
    attention; or by kind a linear layer or gated attention), residual;
    RMSNorm, gated feed forward, residual: over one plain stream, or with
    each half inside the n-stream residual's mix (``ops/hyper.py``)."""
    if cfg.block == "pre_rms":
        from veles_tpu.ops import hyper

        def attn_half(u):
            return attend(blk["attn"], rms_norm(u, blk["ln_attn"], cfg.eps,
                                                cfg.dtype, cfg.norm_centred))

        def ffn_half(u):
            normed = rms_norm(u, blk["ln_mlp"], cfg.eps,
                              centred=cfg.norm_centred)
            return _block_ffn(blk, normed.astype(cfg.dtype), cfg, layer,
                              normed)

        def joined(h, out):
            out = out.astype(h.dtype)
            return h + (out if cfg.residual_mult == 1.0
                        else cfg.residual_mult * out)

        if cfg.hyper is None:
            out, state = attn_half(h)
            h = joined(h, out)
            ff, stats = ffn_half(h)
            return joined(h, ff), state, stats
        h, state = hyper.around(blk["hc_attn"], h, cfg, attn_half)
        h, stats = hyper.around(blk["hc_mlp"], h, cfg, ffn_half)
        return h, state, stats
    if cfg.block == "sandwich":
        # the residual stream ``h`` is float32 whatever the model's dtype
        # (``_scaled_embed``); what a sublayer reads and returns is the
        # model's.  A router's near-ties amplify every rounding of its
        # input, so it reads the normed stream before that is rounded
        # (``normed``: float32).
        out, state = attend(blk["attn"], rms_norm(h, blk["ln_in"], cfg.eps,
                                                  cfg.dtype))
        h = h + rms_norm(out, blk["ln_post_attn"], cfg.eps, h.dtype)
        normed = rms_norm(h, blk["ln_pre_mlp"], cfg.eps)
        ff, stats = _block_ffn(blk, normed.astype(cfg.dtype), cfg, layer,
                               normed)
        return (h + rms_norm(ff, blk["ln_post_mlp"], cfg.eps, h.dtype),
                state, stats)
    hn = _layernorm(h, blk["ln1"]["g"], blk["ln1"]["b"])
    out, state = attend(blk["attn"], hn)
    h = h + out
    hn = _layernorm(h, blk["ln2"]["g"], blk["ln2"]["b"])
    if "moe" in blk and with_aux:
        from veles_tpu.ops.moe import moe_ffn
        ff, aux = moe_ffn(blk["moe"], hn, return_aux=True,
                          token_mask=token_mask)
        return h + ff, state, aux
    return h + _block_ffn(blk, hn, cfg, layer)[0], state, 0.0


def block_forward(blk, h, n_heads, block_size=None, attn_fn=None,
                  with_aux=False, token_mask=None, rope=False,
                  window=None, sinks=0, layer=0):
    """One decoder block over a whole sequence — shared by the sequential
    forward and the pipeline-parallel stage runner
    (veles_tpu.parallel.pipeline).  ``n_heads`` is a head count with the
    classic keywords beside it, or the model's record
    (``model_config.ModelConfig``; ``layer`` then picks the layer's
    kinds).  A block carrying ``moe`` params uses the routed expert FFN in
    place of the dense one; ``with_aux=True`` returns (h,
    moe_load_balancing_loss) (0 for dense blocks; ``token_mask`` keeps
    padded rows out of the router statistics)."""
    cfg = model_config.of(n_heads, rope, window, sinks)
    if attn_fn is not None:    # injected attention (ring SP)
        if cfg.rope or cfg.window or cfg.sinks \
                or cfg.attn_kinds is not None:
            # the injected path never rotates q/k or masks the window —
            # running a RoPE model through it would silently drop ALL
            # positional signal (rope params have no pos table)
            raise ValueError("rope/window are not supported with an "
                             "injected attn_fn (ring attention)")

        def attend(p, hn):
            return attn_fn(p, hn), None
    else:
        def attend(p, hn):
            return mha_forward(p, hn, cfg, causal=True,
                               block_size=block_size, layer=layer), None
    h, _, aux = _wire(blk, h, cfg, layer, attend, with_aux, token_mask)
    return (h, aux) if with_aux else h


def _block_ffn(blk, hn, cfg, layer, router_in=None):
    """The feed-forward half of a block, shared by the training forward
    and the KV-cached decode step: (output, the expert layer's counts or
    None).  ``pre_ln``: dense ReLU with biases, or the top-1 expert layer
    of a tree that carries ``moe``.  ``sandwich``: gated SiLU, dense or
    routed beside a shared expert, as the record says for the layer
    (``router_in``: the float32 input the router scores, where it is not
    ``hn`` itself)."""
    import jax.numpy as jnp
    if cfg.wide:
        from veles_tpu.ops.moe import gated_ffn, routed_ffn
        mm = lambda a, b: cfg_matmul(cfg, a, b)  # noqa: E731
        if cfg.ffn_kind(layer, blk) == model_config.MOE:
            return routed_ffn(blk["moe"], hn, cfg.moe, mm, router_in)
        return gated_ffn(blk, hn, mm), None
    if "moe" in blk:
        from veles_tpu.ops.moe import moe_ffn
        return moe_ffn(blk["moe"], hn), None
    ff = jnp.maximum(F.matmul(hn, blk["w1"]) + blk["b1"], 0.0)
    return F.matmul(ff, blk["w2"]) + blk["b2"], None


def _scaled_embed(params, tokens, cfg):
    """Token rows; the ``sandwich`` block scales them by sqrt(d_model)
    and carries them, the residual stream, in float32."""
    import jax.numpy as jnp
    h = jnp.take(params["embed"], tokens, axis=0)
    if cfg is not None and cfg.block == "pre_rms":
        # float32 too, unscaled but for a record that states a multiplier;
        # copied into the residual's streams
        h = h.astype(jnp.float32)
        if cfg.embed_mult is not None:
            h = h * cfg.embed_mult
        if cfg.hyper is None:
            return h
        from veles_tpu.ops import hyper
        return hyper.spread(h, cfg)
    scale = cfg.embed_scale(h.shape[-1]) if cfg is not None else None
    if scale is None:
        return h
    return h.astype(jnp.float32) * scale


def embed_tokens(params, tokens, cfg=None):
    """Token (+ learned positional, absent under RoPE) embedding — the
    pre-block-stack half, shared by the sequential forward and the
    pipeline-parallel path."""
    s = tokens.shape[1]
    h = _scaled_embed(params, tokens, cfg)
    if "pos" in params:
        h = h + params["pos"][:s]
    return h


def head_logits(params, h, cfg=None):
    """Final norm and output head over block-stack activations: LayerNorm
    and the tied head, or (``sandwich``) RMSNorm and the tree's own
    ``head``, the logits in float32."""
    import jax.numpy as jnp
    if cfg is not None and cfg.hyper is not None:
        from veles_tpu.ops import hyper
        h = hyper.gather(h)           # the streams' sum
    if cfg is not None and cfg.wide:
        h = rms_norm(h, params["ln_f"], cfg.eps, cfg.dtype,
                     cfg.norm_centred)
        if cfg.tied:
            # the embedding as it lies, contracted over its columns: no
            # transposed copy of the vocabulary's rows
            return jnp.einsum("...d,vd->...v", h, params["embed"],
                              preferred_element_type=jnp.float32) \
                / cfg.logits_div
        return jnp.matmul(h, params["head"],
                          preferred_element_type=jnp.float32)
    h = _layernorm(h, params["ln_f"]["g"], params["ln_f"]["b"])
    return F.matmul(h, params["embed"].T)


def nll_from_hidden(params, h, targets, mask, cfg=None):
    """Masked mean next-token cross-entropy from block-stack activations —
    the post-block half shared by lm_loss and pipeline_lm_loss."""
    import jax
    import jax.numpy as jnp
    logp = jax.nn.log_softmax(head_logits(params, h, cfg), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    m = mask[:, None]
    denom = jnp.maximum(m.sum() * nll.shape[1], 1.0)
    return (nll * m).sum() / denom


def transformer_forward(params, tokens, n_heads, block_size=None,
                        attn_fn=None, rope=False, window=None, sinks=0):
    """Logits (batch, seq, vocab); ``attn_fn(q_input)`` optionally replaces
    the attention call (ring attention injection point)."""
    cfg = model_config.of(n_heads, rope, window, sinks)
    h = embed_tokens(params, tokens, cfg)
    for i, blk in enumerate(params["blocks"]):
        h = block_forward(blk, h, cfg, block_size, attn_fn, layer=i)
    return head_logits(params, h, cfg)


def lm_loss(params, tokens, mask, n_heads, block_size=None,
            moe_aux_coef=0.0, remat=False, rope=False, window=None,
            sinks=0):
    """Mean next-token cross-entropy (masked rows excluded).

    ``moe_aux_coef > 0`` adds the mean per-MoE-block load-balancing loss
    (ops/moe.py) over LIVE tokens — required for top-1 routing not to
    collapse; padded rows must not steer the router.

    ``remat=True`` wraps each block in ``jax.checkpoint``: activations
    inside a block are recomputed during the backward pass instead of
    stored, cutting peak activation memory from O(layers·seq·d) to
    O(seq·d) + one block — the standard TPU HBM-for-FLOPs trade that
    makes deep stacks on long sequences fit (SURVEY §7 "HBM bandwidth"
    design note)."""
    import jax
    import jax.numpy as jnp
    cfg = model_config.of(n_heads, rope, window, sinks)
    h = embed_tokens(params, tokens[:, :-1], cfg)
    token_mask = jnp.broadcast_to(
        mask[:, None], (h.shape[0], h.shape[1])).reshape(-1)
    aux_total, n_moe = 0.0, 0

    def wrap(fn):
        return jax.checkpoint(fn) if remat else fn

    for i, blk in enumerate(params["blocks"]):
        if moe_aux_coef and "moe" in blk and cfg.block == "pre_ln":
            h, aux = wrap(lambda b, x, i=i: block_forward(
                b, x, cfg, block_size, with_aux=True,
                token_mask=token_mask, layer=i))(blk, h)
            aux_total = aux_total + aux
            n_moe += 1
        else:
            h = wrap(lambda b, x, i=i: block_forward(
                b, x, cfg, block_size, layer=i))(blk, h)
    loss = nll_from_hidden(params, h, tokens[:, 1:], mask, cfg)
    if n_moe:
        loss = loss + moe_aux_coef * aux_total / n_moe
    return loss


# ---------------------------------------------------------------- serving
def prefill(params, tokens, n_heads, max_len, rope=False, window=None,
            sinks=0):
    """Run the prompt through the stack once, capturing each block's
    projected K/V heads into fixed-shape caches (n_kv_heads-wide under
    GQA — the smaller cache is the point).

    Returns (h (b, s, d) block-stack activations, caches) where caches
    is a per-block list of (k, v) arrays shaped
    (batch, heads, max_len, head_dim) with positions [0, s) filled —
    the state KV-cached decoding (``generate``) continues from.  Reuses
    ``block_forward`` via a K/V-capturing ``attn_fn``, so training and
    serving can never drift on block wiring.
    """
    import jax.numpy as jnp
    cfg = model_config.of(n_heads, rope, window, sinks)
    if cfg.latent is not None:
        raise ValueError("latent attention has no contiguous cache: its "
                         "cached path is the paged latent pool "
                         "(paged_chunk_apply, LMEngine(paged_kv=...))")
    if cfg.linear is not None:
        raise ValueError("a stack with linear layers has no contiguous "
                         "cache: its cached path keeps a state slot a lane "
                         "beside the paged pool (paged_chunk_apply, "
                         "LMEngine(paged_kv=...))")
    h = embed_tokens(params, tokens, cfg)
    s = h.shape[1]
    pad = [(0, 0), (0, 0), (0, max_len - s), (0, 0)]
    caches = []
    for i, blk in enumerate(params["blocks"]):
        def attend(p, hn, i=i):
            out, k, v = mha_forward(p, hn, cfg, causal=True,
                                    return_kv=True, layer=i)
            return out, (k, v)

        h, (k, v), _ = _wire(blk, h, cfg, i, attend)
        caches.append((jnp.pad(k, pad), jnp.pad(v, pad)))
    return h, caches


def _cached_block(step, blk, h, k_cache, v_cache, cfg, layer, **kw):
    """:func:`_wire` around one of the cached attention steps of
    ``ops/attention.py`` (``step(attention params, normed, k, v, ...) ->
    (out, k, v)``): (h, k, v, the expert layer's counts or None)."""
    def attend(p, hn):
        out, k, v = step(p, hn, k_cache, v_cache, layer=layer, **kw)
        return out, (k, v)

    h, (k, v), stats = _wire(blk, h, cfg, layer, attend)
    return h, k, v, stats


def block_decode_step(blk, h, k_cache, v_cache, pos, n_heads,
                      rope=False, window=None, sinks=0, layer=0):
    """One block over ONE position against its KV cache (decode path)."""
    from veles_tpu.ops.attention import mha_decode_step
    cfg = model_config.of(n_heads, rope, window, sinks)
    return _cached_block(mha_decode_step, blk, h, k_cache, v_cache, cfg,
                         layer, pos=pos, n_heads=cfg)[:3]


def chunk_embed(params, tokens, pos, cfg=None):
    """Token (+ positional at [pos, pos+c), absent under RoPE) embedding
    for a mid-sequence chunk — :func:`embed_tokens` generalized to a
    traced start position (the chunked-prefill / speculative entry
    half)."""
    import jax
    import jax.numpy as jnp
    c = tokens.shape[1]
    h = _scaled_embed(params, tokens, cfg)
    if "pos" in params:
        h = h + jax.lax.dynamic_slice_in_dim(params["pos"], pos, c,
                                             axis=0)[None]
    return h


def block_paged_chunk_step(blk, h, k_pool, v_pool, ptab, pos, n_heads,
                           rope=False, window=None, sinks=0,
                           attn_kernel=None, write_mask=None, layer=0,
                           base=None):
    """One block over ``c`` positions per lane against the PAGED KV
    pool — the multi-token sibling of :func:`block_decode_step` with the
    storage indirected through a per-lane page table
    (``attention.mha_paged_chunk_step`` core), and batched over lanes
    so decode/verify advance every lane in ONE
    dispatch without vmapping the shared pool.  ``attn_kernel``
    (static: None | 'decode' | 'prefill') routes attention through the
    Pallas serving kernels (ISSUE 7); ``write_mask`` (traced (b,)
    bool; ISSUE 13) diverts masked lanes' K/V writes to the scratch
    page — the megastep's early-exit lanes stay in the program without
    being able to touch an allocated page.  Returns (h, k_pool, v_pool,
    the expert layer's counts or None)."""
    from veles_tpu.ops.attention import mha_paged_chunk_step
    cfg = model_config.of(n_heads, rope, window, sinks)
    return _cached_block(
        mha_paged_chunk_step, blk, h, k_pool, v_pool, cfg, layer,
        ptab=ptab, pos=pos, n_heads=cfg, attn_kernel=attn_kernel,
        write_mask=write_mask, base=base)


def block_latent_chunk_step(blk, h, pool, ptab, pos, cfg,
                            attn_kernel=None, write_mask=None, layer=0,
                            scope="attn.latent"):
    """:func:`block_paged_chunk_step` for the latent kind: one pool a
    layer (``ops/latent.py::latent_paged_chunk_step``; ``scope`` names its
    Pallas calls in the device trace).  Returns (h, pool, the expert
    layer's counts or None)."""
    from veles_tpu.ops.latent import latent_paged_chunk_step

    def attend(p, hn):
        return latent_paged_chunk_step(p, hn, pool, ptab, pos, cfg,
                                       attn_kernel=attn_kernel,
                                       write_mask=write_mask, scope=scope)

    return _wire(blk, h, cfg, layer, attend)


def block_linear_chunk_step(blk, h, state, tail, cfg, rows, slots=None,
                            fresh=None, attn_kernel=None, layer=0):
    """:func:`block_paged_chunk_step` for a linear layer: no pages, the
    lanes' recurrent state and convolution tail in their place
    (``ops/linear_attn.py::linear_paged_chunk_step``).  Returns (h, state,
    tail, the expert layer's counts or None)."""
    from veles_tpu.ops.linear_attn import linear_paged_chunk_step

    def attend(p, hn):
        out, s, t = linear_paged_chunk_step(
            p, hn, state, tail, cfg, rows, slots=slots, fresh=fresh,
            attn_kernel=attn_kernel)
        return out, (s, t)

    h, (state, tail), stats = _wire(blk, h, cfg, layer, attend)
    return h, state, tail, stats


def paged_chunk_embed(params, tokens, pos, cfg=None):
    """Token (+ positional, absent under RoPE) embedding for ``c``
    positions per lane starting at PER-LANE traced ``pos`` (b,) —
    :func:`chunk_embed` generalized to the batched paged step, where
    every lane sits at its own depth.  Positional rows are gathered
    (clipped at the table edge — only a tail chunk's pad positions can
    exceed it, and their outputs are never read)."""
    import jax.numpy as jnp
    c = tokens.shape[1]
    h = _scaled_embed(params, tokens, cfg)
    if "pos" in params:
        idx = jnp.asarray(pos)[:, None] + jnp.arange(c)      # (b, c)
        h = h + jnp.take(params["pos"], idx, axis=0)
    return h


def paged_chunk_apply(params, tokens, pools, ptab, pos, n_heads,
                      rope=False, window=None, sinks=0,
                      attn_kernel=None, write_mask=None, base=None,
                      with_stats=False, rows=None, slots=None):
    """Run ``c`` consecutive tokens PER LANE through the whole stack
    against the paged KV pools in one pass: embed at [pos, pos+c), every
    block via :func:`block_paged_chunk_step` — the building block of
    chunked prefill (c = chunk size), the decode step (c = 1) and
    speculative verification (c = 1 + draft length).

    tokens: (b, c) int32; pools: per-block [(k_pool, v_pool)] each
    (n_pages, kv_heads, page, head_dim); ptab: (b, m); pos: (b,)
    traced.  Returns (h (b, c, d), pools) with each lane's K/V written
    through its table at [pos, pos+c).  Serves ALL THREE paged shapes —
    prefill chunk (b=1, c=chunk), decode step (c=1, b=slots),
    speculative verify (c=k+1, b=slots) — so one function carries the
    whole paged fast path and position j's hidden state equals the
    contiguous path's bit for bit.  ``attn_kernel`` (static: None |
    'decode' | 'prefill') swaps every block's attention for the Pallas
    serving kernel path (ISSUE 7) — same K/V writes, no materialized
    ``paged_view`` gather.  ``write_mask`` (traced (b,) bool; ISSUE
    13) redirects masked lanes' K/V writes to the scratch page — see
    :func:`~veles_tpu.ops.attention.paged_write`.

    A stack with two kinds of cache takes ``ptab`` and
    ``base`` as dicts by kind (``model_config.FULL`` / ``SLIDING``): each
    layer walks its own kind's table, a sliding layer's beginning at the
    lane's first live page (``base``, see ``mha_paged_chunk_step``).
    ``with_stats`` also returns the expert layers' counts, int32 ``[held,
    elsewhere, experts hit, largest load]`` (``ops/moe.py::held_part``),
    summed over the layers (the last: their maximum).

    A stack with ``linear`` layers holds for each of them, in ``pools``'
    place of a (k, v) pair, the pair (recurrent state, convolution tail) of
    every lane's slot, and needs ``rows`` (b,) int32: how many of each
    lane's ``c`` rows are real (0: a lane that rides the step without
    decoding; its state and tail come back bit for bit).  ``slots`` (b,)
    names the lanes' slots where lane ``i`` is not slot ``i`` (the one-lane
    chunk program); a lane at ``pos`` 0 with several rows starts its
    sequence, and what its slot held is read as zeros."""
    import jax.numpy as jnp
    cfg = model_config.of(n_heads, rope, window, sinks)
    h = paged_chunk_embed(params, tokens, pos, cfg)
    by_kind = isinstance(ptab, dict)
    new_pools = []
    counts = []
    for i, (blk, pool) in enumerate(zip(params["blocks"], pools)):
        kind = cfg.kind(i)
        if kind == model_config.LINEAR:
            # lint: allow(recompile-hazard): rows a lane are fixed per program family (one: the step; a chunk: the prefill)
            fresh = jnp.asarray(pos) == 0 if tokens.shape[1] > 1 else None
            h, state, tail, stats = block_linear_chunk_step(
                blk, h, *pool, cfg, rows, slots=slots, fresh=fresh,
                attn_kernel=attn_kernel, layer=i)
            new_pools.append((state, tail))
        elif cfg.latent is not None:
            # one pool a layer, its rows the latents
            h, pool, stats = block_latent_chunk_step(
                blk, h, pool[0], ptab, pos, cfg, attn_kernel=attn_kernel,
                write_mask=write_mask, layer=i)
            new_pools.append((pool,))
        else:
            h, kp, vp, stats = block_paged_chunk_step(
                blk, h, *pool, ptab[kind] if by_kind else ptab, pos, cfg,
                attn_kernel=attn_kernel, write_mask=write_mask, layer=i,
                base=base[kind] if by_kind else base)
            new_pools.append((kp, vp))
        if stats is not None:
            counts.append(stats)
    if not with_stats:
        return h, new_pools
    if not counts:
        return h, new_pools, jnp.zeros(4, jnp.int32)
    return h, new_pools, _sum_counts(counts)


# ------------------------------------------- multi-token prediction (MTP)
def mtp_forward(params, h, nxt, pool, ptab, pos, cfg, attn_kernel=None,
                write_mask=None):
    """The multi-token-prediction module (DeepSeek-V3, arXiv:2412.19437
    section 2.2; ``cfg.nextn``) over ``c`` rows a lane: row ``i`` takes
    the last main block's output ``h_i`` (b, c, d; BEFORE the final norm)
    and the token that follows, ``nxt`` (b, c): ``x'_i = W_eh
    [rms_e(Emb(t_{i+1})) ; rms_h(h_i)]``, then one whole expert layer with
    latent attention over its OWN pool of latent rows (``pool``, the
    module's, at the rotary position of row ``i``).  Returns (y (b, c, d)
    before the module's final norm, the pool, the expert layer's counts).
    Runs under the scope ``mtp.draft``, which its attention's Pallas calls
    take for their own (a call is named by the innermost scope around it,
    and the stack's layers' are ``attn.latent``): the first of them marks,
    in the device trace, where the module's part of a program begins."""
    import jax
    with jax.named_scope("mtp.draft"):
        return block_latent_chunk_step(
            params["mtp"][0]["block"], mtp_inputs(params, h, nxt, cfg), pool,
            ptab, pos, cfg, attn_kernel=attn_kernel, write_mask=write_mask,
            layer=len(params["blocks"]), scope="mtp.draft")


def mtp_inputs(params, h, nxt, cfg):
    """``x'_i = W_eh [rms_e(Emb(t_{i+1})) ; rms_h(h_i)]``, the embedding
    half first: what the module's block reads, as a float32 residual
    stream."""
    import jax.numpy as jnp
    mod = params["mtp"][0]
    e = jnp.take(params["embed"], nxt, axis=0).astype(jnp.float32)
    x = jnp.concatenate(
        [rms_norm(e, mod["enorm"], cfg.eps, cfg.dtype),
         rms_norm(h, mod["hnorm"], cfg.eps, cfg.dtype)], axis=-1)
    return cfg_matmul(cfg, x, mod["eh_proj"]).astype(jnp.float32)


def mtp_logits(params, y, cfg):
    """The module's own final norm, then the MAIN model's head: float32
    logits of the token two places on."""
    import jax.numpy as jnp
    y = rms_norm(y, params["mtp"][0]["norm"], cfg.eps, cfg.dtype)
    return jnp.matmul(y, params["head"], preferred_element_type=jnp.float32)


def _sum_counts(counts):
    """Expert layers' counts in one: assignments held, elsewhere and
    experts hit add up over the layers; the largest expert load is the
    largest of any layer."""
    import jax.numpy as jnp
    counts = jnp.stack(counts)
    return jnp.concatenate([counts[:, :3].sum(0), counts[:, 3:].max(0)])


def mtp_chunk_apply(params, tokens, nxt, pools, ptab, pos, cfg, last_idx,
                    tail, attn_kernel=None):
    """A prompt chunk of ONE lane through the stack and then through the
    module, so that the module's pool holds its rows of the prompt too:
    :func:`paged_chunk_apply` over ``pools[:-1]``, the greedy token after
    row ``last_idx``, and the module over rows ``(h_i, t_{i+1})`` with
    ``nxt`` (1, c) the chunk's tokens one place on (the next chunk's first
    behind the last; in a ``tail`` chunk the token just picked takes its
    place at ``last_idx``: it is what the prompt's last row is followed by).
    Returns
    (pools, that token, the module's draft of the one after it): the first
    draft comes with the first token."""
    import jax
    import jax.numpy as jnp
    h, main = paged_chunk_apply(params, tokens, pools[:-1], ptab, pos, cfg,
                                attn_kernel=attn_kernel)
    row = jax.lax.dynamic_slice_in_dim(h, last_idx, 1, axis=1)
    tok = jnp.argmax(head_logits(params, row, cfg)[:, 0, :],
                     axis=-1).astype(jnp.int32)
    nxt = jnp.where(tail & (jnp.arange(nxt.shape[1])[None] == last_idx),
                    tok[:, None], nxt)
    y, pool, _ = mtp_forward(params, h, nxt, pools[-1][0], ptab, pos, cfg,
                             attn_kernel=attn_kernel)
    y = jax.lax.dynamic_slice_in_dim(y, last_idx, 1, axis=1)
    draft = jnp.argmax(mtp_logits(params, y, cfg)[:, 0, :],
                       axis=-1).astype(jnp.int32)
    return main + [(pool,)], tok[0], draft[0]


def mtp_verify_step(params, pools, ptab, last, draft, pos, live, cfg,
                    attn_kernel=None):
    """ONE decode dispatch of a model that drafts with its own module
    (k = 1): every live lane at position ``p`` feeds ``[last, draft]`` at
    ``p, p + 1`` through the stack (their latent rows written), the float32
    argmaxes after the two rows are ``g_{p+1}, g_{p+2}``; the draft is
    ACCEPTED where it equals ``g_{p+1}`` (scope ``spec.verify``), and the
    lane then yields both, else ``g_{p+1}`` alone: exactly what plain
    greedy decoding yields.  The module runs rows ``(h_p, g_{p+1})`` and
    ``(h_{p+1}, g_{p+2})`` (the second is of use only where the draft was
    accepted; a rejected draft's rows, the stack's and the module's alike,
    are dead and the next step overwrites them) and drafts from the last
    valid one.  A lane that is not live feeds token 0 at position 0 and its
    writes go to the scratch page.

    Returns (pools, (last, draft, pos) as the next dispatch takes them,
    tokens (b, 3): the two tokens and behind them the draft just made,
    count (b,) of the two that are real (0 for a lane that is not live),
    the expert layers' counts)."""
    import jax
    import jax.numpy as jnp
    at = jnp.where(live, pos, 0)
    toks = jnp.where(live[:, None], jnp.stack([last, draft], axis=1), 0)
    h, main, counts = paged_chunk_apply(
        params, toks, pools[:-1], ptab, at, cfg, attn_kernel=attn_kernel,
        write_mask=live, with_stats=True)
    picked = jnp.argmax(head_logits(params, h, cfg),
                        axis=-1).astype(jnp.int32)            # (b, 2)
    with jax.named_scope("spec.verify"):
        accepted = live & (toks[:, 1] == picked[:, 0])
        count = jnp.where(live, 1 + accepted.astype(jnp.int32), 0)
    y, pool, stats = mtp_forward(
        params, h, picked, pools[-1][0], ptab, at, cfg,
        attn_kernel=attn_kernel, write_mask=live)
    y = jnp.where(accepted[:, None], y[:, 1], y[:, 0])
    new_draft = jnp.argmax(mtp_logits(params, y, cfg),
                           axis=-1).astype(jnp.int32)
    state = (jnp.where(live, jnp.where(accepted, picked[:, 1],
                                       picked[:, 0]), last),
             jnp.where(live, new_draft, draft),
             pos + count)
    return (main + [(pool,)], state,
            jnp.concatenate([picked, new_draft[:, None]], axis=1), count,
            _sum_counts([counts, stats]))


def propose_draft_in_graph(hist, hlen, k, max_ngram=3):
    """Prompt-lookup draft proposal as a TRACED function — the in-graph
    sibling of ``serving/lm_engine.py::propose_draft``, so the decode
    megastep (ISSUE 13) can run propose → verify → accept entirely on
    device instead of paying a host round-trip per speculative step.

    hist: (L,) int32 token history (prompt + emitted so far; positions
    >= ``hlen`` are garbage); hlen: traced scalar.  Tries the final
    g-gram for g = ``max_ngram`` down to 1 (largest g wins, matching
    the host version's preference), takes the MOST RECENT earlier
    occurrence that ends strictly before the final position, and
    returns (draft (k,) int32, found bool) — the k tokens following
    the match (zeros when nothing recurs; tokens past ``hlen`` in the
    continuation window may be garbage).

    Draft quality affects SPEED only: the verifier accepts a draft
    token iff it equals its own greedy argmax, so a garbage draft can
    never change output — which is why this function needs no exact
    numerical parity with the host proposer, only the same contract."""
    import jax
    import jax.numpy as jnp
    hist = jnp.asarray(hist, jnp.int32)
    hlen = jnp.asarray(hlen, jnp.int32)
    n = hist.shape[0]
    idx = jnp.arange(n)
    best_start = jnp.asarray(0, jnp.int32)
    best_g = jnp.asarray(0, jnp.int32)
    found = jnp.asarray(False)
    for g in range(max_ngram, 0, -1):       # static unroll, g descends
        # the final g-gram (dynamic_slice clamps a negative start; the
        # validity mask below zeroes those degenerate cases out)
        tail = jax.lax.dynamic_slice_in_dim(
            hist, jnp.maximum(hlen - g, 0), g)
        eq = jnp.ones((n,), bool)
        for t in range(g):
            # hist[j + t] at index j; jnp.roll wraps, but wrapped
            # windows fail the validity mask (j + g <= hlen - 1 < n)
            eq &= jnp.roll(hist, -t) == tail[t]
        valid = (idx + g <= hlen - 1) & (hlen >= g + 1)
        hit = eq & valid
        any_hit = hit.any()
        recent = jnp.where(hit, idx, -1).max().astype(jnp.int32)
        take = any_hit & ~found
        best_start = jnp.where(take, recent, best_start)
        best_g = jnp.where(take, jnp.asarray(g, jnp.int32), best_g)
        found = found | any_hit
    cont = jax.lax.dynamic_slice_in_dim(
        hist, jnp.clip(best_start + best_g, 0, n - k), k)
    return jnp.where(found, cont, jnp.zeros(k, jnp.int32)), found


def lm_param_specs(params, axis="tp"):
    """``jax.sharding.PartitionSpec`` tree (same structure as
    ``params``) for TENSOR-PARALLEL serving over a one-axis mesh — the
    megatron head/column split the training-side TP tests
    (tests/test_parallel.py) already prove out, applied to the decode
    param tree:

    - attention ``wq``/``wk``/``wv`` are COLUMN-sharded over ``axis``
      (heads are contiguous feature groups in the output dim, so an
      ``axis`` size dividing n_heads — and n_kv_heads, for the smaller
      wk/wv — partitions whole heads and each device attends only its
      own head group against its own KV shard);
    - ``wo`` is ROW-sharded (the contraction over the sharded head
      features becomes the one per-block all-reduce);
    - FFN ``w1``/``b1`` column-, ``w2`` row-sharded (same pattern over
      d_ff);
    - embeddings, positional table, layernorms, biases after
      reductions, and MoE expert stacks stay REPLICATED.

    Consumed by ``serving/lm_engine.py`` (``LMEngine(tp=)``): weights
    placed by these specs flow through the UNCHANGED decode/chunk/
    verify programs and GSPMD inserts the collectives — the dataflow
    reconfigures, the kernels stay put."""
    import jax
    from jax.sharding import PartitionSpec as P
    col, row, repl = P(None, axis), P(axis, None), P()

    def replicated(tree):
        return jax.tree.map(lambda _: repl, tree)

    blocks = []
    for blk in params["blocks"]:
        spec = {}
        for key, val in blk.items():
            if key == "attn":
                spec[key] = {"wq": col, "wk": col, "wv": col, "wo": row}
            elif key == "w1":
                spec[key] = col
            elif key == "b1":
                spec[key] = P(axis)
            elif key == "w2":
                spec[key] = row
            else:
                spec[key] = replicated(val)
        blocks.append(spec)
    out = {key: replicated(val) for key, val in params.items()
           if key != "blocks"}
    out["blocks"] = blocks
    return out


def _make_sampler(greedy, top_k, temperature):
    """Token sampler shared by the full-cache and rolling decoders (the
    top-k tie rule and traced-temperature handling must never drift
    between them)."""
    import jax
    import jax.numpy as jnp

    def sample(logits, key):
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        lg = logits
        if top_k is not None:
            # keep only the k most likely tokens; ties at the cutoff
            # stay eligible
            vals = jax.lax.top_k(lg, top_k)[0]
            lg = jnp.where(lg >= vals[..., -1:], lg, NEG_INF_LOGIT)
        # temperature is TRACED: every sampling temperature shares one
        # compilation (serve_lm exposes it to clients)
        return jax.random.categorical(key, lg / temperature,
                                      axis=-1).astype(jnp.int32)

    def next_key(key):
        return jax.random.split(key) if key is not None else (None, None)

    return sample, next_key


def sample_token(key, logits, temperature, top_k=0):
    """Sample ONE token id from a ``(vocab,)`` (or batched ``(...,
    vocab)``) logits row under the shared top-k/temperature rule of
    :func:`_make_sampler` — the serving engine's in-graph seeded
    sampling (ISSUE 19) calls this with a counter-derived key per
    (lane seed, position), so a fused device loop, a per-tick loop and
    :func:`generate` all draw the identical token given the same key.
    ``temperature`` must be > 0 (greedy stays argmax, outside this)."""
    import jax
    import jax.numpy as jnp
    lg = logits
    if top_k:
        vals = jax.lax.top_k(lg, top_k)[0]
        lg = jnp.where(lg >= vals[..., -1:], lg, NEG_INF_LOGIT)
    return jax.random.categorical(key, lg / temperature,
                                  axis=-1).astype(jnp.int32)


def _generate_impl(params, prompt, rng, temperature, true_len, n_new,
                   cfg, greedy, max_len, top_k):
    import jax
    import jax.numpy as jnp
    h, caches = prefill(params, prompt, cfg, max_len)
    # ``true_len`` is TRACED: the prompt may be right-padded to a bucket
    # length so servers compile one program per bucket, not per exact
    # prompt length.  Under causal attention every position < true_len is
    # computed exactly regardless of pad content, decode overwrites the
    # cache from position true_len on, and mha_decode_step masks cache
    # positions > pos — so bucketing is bit-exact, not approximate.
    logits = head_logits(params, jax.lax.dynamic_slice_in_dim(
        h, true_len - 1, 1, axis=1), cfg)[:, 0, :]
    sample, next_key = _make_sampler(greedy, top_k, temperature)

    # the final sampled token never feeds the stack again, so the scan
    # runs n_new - 1 decode steps and the last sample happens outside
    # (no dead block-stack pass)
    def body(carry, i):
        caches, logits, key = carry
        key, sub = next_key(key)
        tok = sample(logits, sub)
        pos = true_len + i
        x = chunk_embed(params, tok[:, None], pos, cfg)
        new_caches = []
        for i, (blk, (kc, vc)) in enumerate(zip(params["blocks"],
                                                caches)):
            x, kc, vc = block_decode_step(blk, x, kc, vc, pos, cfg,
                                          layer=i)
            new_caches.append((kc, vc))
        logits = head_logits(params, x, cfg)[:, 0, :]
        return (new_caches, logits, key), tok

    key0 = None if greedy else rng
    (_, logits, key), toks = jax.lax.scan(body, (caches, logits, key0),
                                          jnp.arange(n_new - 1))
    _, sub = next_key(key)
    last = sample(logits, sub)
    toks = jnp.concatenate([toks.T, last[:, None]], axis=1)
    return jnp.concatenate([prompt, toks.astype(jnp.int32)], axis=1)


#: cached jit of _generate_impl (n_new/cfg/greedy/max_len static,
#: temperature TRACED) — a fresh jax.jit wrapper per call would retrace
#: every time
_GENERATE_JIT = None


NEG_INF_LOGIT = -1e30


def generate(params, prompt, n_new, n_heads, rng=None, temperature=1.0,
             max_len=None, top_k=None, true_len=None, rope=False,
             window=None, sinks=0):
    """Autoregressive sampling with a KV cache, fully under jit.

    prompt: (batch, s) int32; returns (batch, s + n_new) int32.
    ``n_heads`` is a head count with the classic keywords beside it, or
    the model's record (``model_config.ModelConfig``).
    One prefill pass captures the prompt's K/V; each new token then
    attends against the fixed-shape cache via ``dynamic_update_slice``
    (O(seq) per token instead of O(seq²) full recompute — the TPU
    serving shape: static shapes, ``lax.scan`` over positions, no host
    round-trips).  ``temperature=0`` decodes greedily (argmax) and
    needs no rng; otherwise ``rng`` seeds categorical sampling (the
    temperature value is traced — all temperatures share one compile).
    ``max_len`` pins the cache size (default prompt + n_new) so callers
    timing different ``n_new`` can hold the cache shape constant.
    ``top_k`` restricts sampling to the k most likely tokens.
    ``true_len`` (TRACED) says how many leading prompt positions are
    real when the prompt is right-padded to a bucket width — decoding
    continues from position ``true_len`` and the continuation lands at
    ``out[:, prompt_width:]`` as usual (bit-exact; see _generate_impl).
    """
    import jax
    import jax.numpy as jnp
    global _GENERATE_JIT
    if n_new < 1:
        raise ValueError("n_new must be >= 1")
    start = prompt.shape[1] if true_len is None else int(true_len)
    if not 1 <= start <= prompt.shape[1]:
        raise ValueError("true_len %d out of range (prompt width %d)"
                         % (start, prompt.shape[1]))
    if max_len is None:
        max_len = max(prompt.shape[1], start + n_new)
    if prompt.shape[1] > max_len:
        raise ValueError("padded prompt width %d exceeds max_len %d"
                         % (prompt.shape[1], max_len))
    if start + n_new > max_len:
        raise ValueError("prompt + n_new = %d exceeds max_len %d"
                         % (start + n_new, max_len))
    if "pos" in params and max_len > params["pos"].shape[0]:
        raise ValueError("max_len %d exceeds the positional table (%d)"
                         % (max_len, params["pos"].shape[0]))
    greedy = not temperature
    if not greedy and rng is None:
        raise ValueError("sampling (temperature > 0) needs rng")
    if top_k is not None and not 1 <= top_k <= params["embed"].shape[0]:
        raise ValueError("top_k %r out of range (vocab %d)"
                         % (top_k, params["embed"].shape[0]))
    if _GENERATE_JIT is None:
        _GENERATE_JIT = jax.jit(
            _generate_impl,
            static_argnames=("n_new", "cfg", "greedy", "max_len",
                             "top_k"))
    return _GENERATE_JIT(params, prompt, None if greedy else rng,
                         jnp.asarray(temperature or 1.0, jnp.float32),
                         jnp.asarray(start, jnp.int32),
                         n_new=n_new,
                         cfg=model_config.of(n_heads, rope, window,
                                             sinks),
                         greedy=greedy, max_len=max_len,
                         # greedy never reads top_k — null it so distinct
                         # values cannot fork identical compiles
                         top_k=None if greedy else top_k)


_GENERATE_ROLLING_JIT = None


def block_decode_step_rolling(blk, h, k_cache, v_cache, slot, live, pos,
                              n_heads):
    # (slot/live come from attention.rolling_slot_update, which already
    # encodes any sink pinning — this function is sink-agnostic)
    """One block over ONE position against its ring-buffer cache — the
    rolling sibling of :func:`block_decode_step` (same wiring, the
    precomputed slot/live from attention.rolling_slot_update)."""
    from veles_tpu.ops.attention import mha_decode_step_rolling

    def attend(p, hn):
        out, k, v = mha_decode_step_rolling(p, hn, k_cache, v_cache, slot,
                                            live, pos, n_heads)
        return out, (k, v)

    h, (k, v), _ = _wire(blk, h, model_config.classic(n_heads), 0, attend)
    return h, k, v


def _generate_rolling_impl(params, prompt, rng, temperature, n_new,
                           n_heads, greedy, window, top_k, sinks):
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops.attention import rolling_slot_update
    s = prompt.shape[1]
    # prefill at the PROMPT width (no grow-to-max_len cache), windowed
    h, caches = prefill(params, prompt, n_heads, max_len=s, rope=True,
                        window=window, sinks=sinks)
    logits = head_logits(params, h[:, -1:, :])[:, 0, :]
    # fold each block's prompt K/V into the [sinks | W-ring] cache: the
    # first min(sinks, s) positions pin to their own slots, the last
    # min(W, s - kept-sinks) positions land at sinks + (p - sinks) % W
    # (consecutive => distinct)
    n_sink = min(sinks, s)
    tail_lo = max(sinks, s - window)
    ps = jnp.concatenate([jnp.arange(n_sink),
                          jnp.arange(tail_lo, s)])
    slots = jnp.where(ps < sinks, ps,
                      sinks + (ps - sinks) % window)
    cache_len = sinks + window
    slot_pos = jnp.full((cache_len,), -1, jnp.int32).at[slots].set(ps)

    def to_ring(c):
        k, v = c
        shape = k.shape[:2] + (cache_len,) + k.shape[3:]
        kr = jnp.zeros(shape, k.dtype).at[:, :, slots, :].set(
            k[:, :, ps, :])
        vr = jnp.zeros(shape, v.dtype).at[:, :, slots, :].set(
            v[:, :, ps, :])
        return kr, vr

    caches = [to_ring(c) for c in caches]
    sample, next_key = _make_sampler(greedy, top_k, temperature)

    def body(carry, i):
        caches, slot_pos, logits, key = carry
        key, sub = next_key(key)
        tok = sample(logits, sub)
        pos = s + i
        # ring bookkeeping once per step — every block writes the same
        # slot under the same liveness
        slot, slot_pos, live = rolling_slot_update(slot_pos, pos, window,
                                                   sinks=sinks)
        x = jnp.take(params["embed"], tok, axis=0)[:, None, :]
        new_caches = []
        for blk, (kc, vc) in zip(params["blocks"], caches):
            x, kc, vc = block_decode_step_rolling(
                blk, x, kc, vc, slot, live, pos, n_heads)
            new_caches.append((kc, vc))
        logits = head_logits(params, x)[:, 0, :]
        return (new_caches, slot_pos, logits, key), tok

    key0 = None if greedy else rng
    (caches, slot_pos, logits, key), toks = jax.lax.scan(
        body, (caches, slot_pos, logits, key0), jnp.arange(n_new - 1))
    _, sub = next_key(key)
    last = sample(logits, sub)
    toks = jnp.concatenate([toks.T, last[:, None]], axis=1)
    return jnp.concatenate([prompt, toks.astype(jnp.int32)], axis=1)


def generate_rolling(params, prompt, n_new, n_heads, window, rng=None,
                     temperature=1.0, top_k=None, sinks=0):
    """UNBOUNDED autoregressive decode in O(window) memory.

    For RoPE + sliding-window models only (no positional table to
    outgrow, attention never reaches past the window): the KV cache is
    a ring buffer of ``window`` slots
    (attention.mha_decode_step_rolling), so ``n_new`` is limited by
    nothing — where ``generate`` allocates max_len-sized caches and
    rejects ``prompt + n_new > max_len``, this keeps decoding forever
    at constant memory.  Matches ``generate(..., rope=True,
    window=W)`` exactly while the full cache lasts (parity-tested).
    """
    import jax
    import jax.numpy as jnp
    global _GENERATE_ROLLING_JIT
    if "pos" in params:
        raise ValueError("generate_rolling needs a RoPE model (a learned "
                         "positional table bounds the length anyway — "
                         "use generate)")
    if n_new < 1:
        raise ValueError("n_new must be >= 1")
    if not window or window < 1:
        raise ValueError("generate_rolling needs window >= 1")
    greedy = not temperature
    if not greedy and rng is None:
        raise ValueError("sampling (temperature > 0) needs rng")
    if top_k is not None and not 1 <= top_k <= params["embed"].shape[0]:
        raise ValueError("top_k %r out of range (vocab %d)"
                         % (top_k, params["embed"].shape[0]))
    if _GENERATE_ROLLING_JIT is None:
        _GENERATE_ROLLING_JIT = jax.jit(
            _generate_rolling_impl,
            static_argnames=("n_new", "n_heads", "greedy", "window",
                             "top_k", "sinks"))
    return _GENERATE_ROLLING_JIT(
        params, prompt, None if greedy else rng,
        jnp.asarray(temperature or 1.0, jnp.float32),
        n_new=n_new, n_heads=n_heads, greedy=greedy, window=window,
        top_k=None if greedy else top_k, sinks=sinks)


def trainer_sample_tokens(trainer, prompt, n_new=32, temperature=0.0,
                          seed=0, params=None, max_len=None, top_k=None,
                          true_len=None):
    """Continue token sequences with a trained TransformerTrainer —
    the ONE decode entry point shared by the sample helpers
    (char_lm.sample_tokens) and HTTP serving (restful_api.serve_lm):
    marshals params to the portable per-layer form (works on pipelined
    trainers too) and runs the KV-cached ``generate``.  Pass ``params``
    to reuse an already-marshalled tree (servers marshal once, not per
    request); ``max_len`` pins the cache shape across calls.  RoPE and
    sliding-window settings follow the trainer's own configuration."""
    import jax
    import jax.numpy as jnp
    if params is None:
        params = trainer._to_portable(trainer.params)
    rng = jax.random.PRNGKey(seed) if temperature else None
    return numpy.asarray(generate(params,
                                  jnp.asarray(prompt, jnp.int32),
                                  n_new, trainer.model_config, rng=rng,
                                  temperature=temperature,
                                  max_len=max_len, top_k=top_k,
                                  true_len=true_len))


def make_adam_train_step(loss_fn, learning_rate, beta1=0.9, beta2=0.999,
                         eps=1e-8):
    """Pure adam step over a param pytree: ``(params, opt_state, tokens,
    mask, t) -> (params, opt_state, metrics)``.

    THE training step of the transformer family — TransformerTrainer jits
    it per-minibatch and bench.py lax.scans it for throughput, so the
    benched optimizer is the product's by construction.
    """
    import jax
    import jax.numpy as jnp

    def train_step(params, opt_state, tokens, mask, t):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, mask)
        m, v = opt_state
        m = jax.tree.map(lambda a, g: beta1 * a + (1 - beta1) * g,
                         m, grads)
        v = jax.tree.map(lambda a, g: beta2 * a + (1 - beta2) * g * g,
                         v, grads)
        tf = t.astype(jnp.float32) + 1.0
        lr = learning_rate * jnp.sqrt(1.0 - beta2 ** tf) / (1.0 - beta1 ** tf)
        params = jax.tree.map(
            lambda p, m_, v_: p - lr * m_ / (jnp.sqrt(v_) + eps),
            params, m, v)
        count = mask.sum()
        return params, (m, v), {"loss_sum": loss * count, "tokens": count}

    return train_step


class TransformerTrainer(AcceleratedUnit):
    """Whole-model trainer: adam update of the param pytree in one jitted
    step; gates to TRAIN minibatches; evaluation scores loss only."""

    def __init__(self, workflow, vocab=64, d_model=64, n_heads=4,
                 n_layers=2, max_len=512, learning_rate=1e-3,
                 block_size=None, beta1=0.9, beta2=0.999, eps=1e-8,
                 n_experts=0, moe_aux_coef=1e-2, pipeline_stages=0,
                 pipeline_microbatches=4, remat=False, n_kv_heads=None,
                 rope=False, window=None, attn_sinks=0, config=None,
                 **kwargs):
        super().__init__(workflow, **kwargs)
        self.vocab = vocab
        self.d_model = d_model
        #: the model's record (model_config.py): given whole
        #: (``config``), or made from the classic keywords — what
        #: ``serve_lm`` and the loss read
        self.model_config = model_config.of(
            config if config is not None else n_heads, rope, window,
            attn_sinks)
        if config is not None:
            n_heads, n_kv_heads = config.n_heads, config.n_kv_heads
            rope, window = config.rope, config.window
            if config.block != "pre_ln" and pipeline_stages > 0:
                raise ValueError(
                    "the pipeline stage scan (parallel/pipeline.py) runs "
                    "the pre_ln block only: a %r model trains on the "
                    "sequential path (pipeline_stages=0)" % config.block)
        self.n_heads = n_heads
        #: grouped-query attention: kv heads < query heads shrink the
        #: KV projections AND the decode cache by the group factor
        self.n_kv_heads = n_kv_heads
        #: rotary positions (no learned pos table; relative positions)
        self.rope = rope
        #: sliding-window attention: each token sees the last W only
        self.window = window
        #: attention sinks: the first K positions stay attendable under
        #: the window (StreamingLLM form)
        self.attn_sinks = attn_sinks
        if attn_sinks and not window:
            raise ValueError("attn_sinks only means something under a "
                             "window (set window=W)")
        if pipeline_stages > 0 and (rope or window):
            raise ValueError(
                "rope/window are not threaded through the pipeline "
                "stage scan yet — use the sequential path "
                "(pipeline_stages=0) for these options")
        self.n_layers = n_layers
        self.max_len = max_len
        self.learning_rate = learning_rate
        self.block_size = block_size
        self.beta1, self.beta2, self.eps = beta1, beta2, eps
        #: > 0 — every block's FFN is a routed mixture of experts
        self.n_experts = n_experts
        #: load-balancing aux-loss weight (sequential path; see _loss_fn)
        self.moe_aux_coef = moe_aux_coef
        #: > 0 — blocks run as a GPipe pipeline over a 'stage' mesh axis
        #: (parallel.pipeline); n_layers must divide by the stage count
        self.pipeline_stages = pipeline_stages
        self.pipeline_microbatches = pipeline_microbatches
        #: jax.checkpoint each block (sequential path): recompute block
        #: activations in the backward pass instead of storing them —
        #: deep stacks on long sequences fit in HBM at ~1/3 extra FLOPs
        self.remat = remat
        self._pp_mesh = None
        self.params = None
        self.opt_state = None
        self.time = 0
        self.metrics = {}

    # params are a pytree, not Vectors — custom snapshot marshalling.
    # Snapshots always carry blocks in the UNSTACKED per-layer list form,
    # so they are portable between pipelined and sequential trainers.
    def _to_portable(self, tree):
        from veles_tpu.parallel.pipeline import unstack_blocks
        if self.pipeline_stages > 0 and isinstance(tree.get("blocks"), dict):
            tree = dict(tree,
                        blocks=unstack_blocks(tree["blocks"],
                                              self.n_layers))
        return tree

    def _from_portable(self, tree):
        from veles_tpu.parallel.pipeline import stack_blocks
        if self.pipeline_stages > 0 and isinstance(tree.get("blocks"), list):
            tree = dict(tree, blocks=stack_blocks(tree["blocks"]))
        return tree

    def state_dict(self):
        import jax

        def marshal(tree):
            if tree is None:
                return None
            return jax.tree.map(numpy.asarray, self._to_portable(tree))

        return {"params": marshal(self.params),
                "opt_state": (tuple(marshal(t) for t in self.opt_state)
                              if self.opt_state is not None else None),
                "time": self.time}

    def load_state_dict(self, d):
        import jax.numpy as jnp
        import jax
        if d.get("params") is not None:
            self.params = self._from_portable(
                jax.tree.map(jnp.asarray, d["params"]))
            self.opt_state = tuple(
                self._from_portable(jax.tree.map(jnp.asarray, t))
                for t in d["opt_state"])
        self.time = d.get("time", 0)

    def _loss_fn(self, training):
        """(params, tokens, mask) -> loss — sequential or pipelined.

        The MoE load-balancing aux is a TRAINING regularizer only: eval
        metrics stay pure NLL (comparable across coef settings).  On the
        pipeline path the stage scan does not thread the aux term, so
        pipelined MoE trains without it (warned below)."""
        if self.pipeline_stages > 0:
            from veles_tpu.parallel.pipeline import pipeline_lm_loss
            if training and self.remat:
                self.warning("remat is not applied on the pipeline path "
                             "(the stage scan already bounds live "
                             "activations to one microbatch per stage)")
            if training and self.n_experts > 0 and self.moe_aux_coef:
                # never drop an explicit setting silently
                self.warning(
                    "moe_aux_coef is not applied on the pipeline path "
                    "(the stage scan does not thread the aux term); "
                    "pipelined MoE trains without load balancing — set "
                    "moe_aux_coef=0 to silence this warning")

            def loss(params, tokens, mask):
                return pipeline_lm_loss(
                    params, tokens, mask, self.n_heads, self._pp_mesh,
                    self.pipeline_microbatches, self.block_size)
            return loss
        coef = (self.moe_aux_coef
                if training and self.n_experts > 0 else 0.0)
        return lambda params, tokens, mask: lm_loss(
            params, tokens, mask, self.model_config, self.block_size,
            moe_aux_coef=coef, remat=self.remat)

    def initialize(self, device=None, **kwargs):
        import jax
        import jax.numpy as jnp
        if not hasattr(self, "input") or self.input.is_empty:
            raise DeferredInitError(self.name)
        loader_vocab = getattr(getattr(self.workflow, "loader", None),
                               "vocab", None)
        if loader_vocab is not None and loader_vocab > self.vocab:
            # jnp.take CLIPS out-of-range token ids silently — a loader
            # emitting a wider alphabet than the embedding would train
            # to completion on garbage; fail here instead
            raise ValueError(
                "loader vocab %d exceeds trainer vocab %d — set "
                "root.<name>.trainer.vocab to cover the data source"
                % (loader_vocab, self.vocab))
        if self.params is None:
            if self.model_config.block != "pre_ln":
                raise ValueError(
                    "init_transformer_params makes pre_ln trees only: a "
                    "%r trainer is given its params"
                    % self.model_config.block)
            host = init_transformer_params(
                prng_mod.get("init"), self.vocab, self.d_model,
                self.n_heads, self.n_layers, max_len=self.max_len,
                n_experts=self.n_experts, n_kv_heads=self.n_kv_heads,
                rope=self.rope)
            self.params = jax.tree.map(jnp.asarray, host)
            if self.pipeline_stages > 0:
                from veles_tpu.parallel.pipeline import stack_blocks
                self.params = dict(self.params,
                                   blocks=stack_blocks(
                                       self.params["blocks"]))
            self.opt_state = (jax.tree.map(jnp.zeros_like, self.params),
                              jax.tree.map(jnp.zeros_like, self.params))
        if self.pipeline_stages > 0 and self._pp_mesh is None:
            from veles_tpu.parallel.pipeline import make_pipeline_mesh
            self._pp_mesh = make_pipeline_mesh(self.pipeline_stages)
        train_loss_fn = self._loss_fn(training=True)
        eval_loss_fn = self._loss_fn(training=False)

        train_step = make_adam_train_step(
            train_loss_fn, self.learning_rate, self.beta1, self.beta2,
            self.eps)

        def eval_step(params, tokens, mask):
            loss = eval_loss_fn(params, tokens, mask)
            count = mask.sum()
            return {"loss_sum": loss * count, "tokens": count}

        self._train = self.jit("train", train_step, donate_argnums=(0, 1))
        self._evalf = self.jit("eval", eval_step)
        super().initialize(device=device, **kwargs)

    def _is_train_minibatch(self):
        return self.is_train_minibatch()

    def run(self):
        import jax.numpy as jnp
        tokens = jnp.asarray(self.input.devmem, jnp.int32)
        mask = self.mask.devmem
        if not self._is_train_minibatch():
            self.metrics = self._evalf(self.params, tokens, mask)
            return
        self.params, self.opt_state, self.metrics = self._train(
            self.params, self.opt_state, tokens, mask,
            jnp.asarray(self.time, jnp.int32))
        self.time += 1


class TransformerDecision(DecisionBase):
    """Tracks mean next-token loss (improvement = lower)."""

    def should_skip_gd(self, cls):
        return False

    def reduce_metrics(self, host_totals):
        out = dict(host_totals)
        count = max(out.pop("tokens", 1), 1)
        if "loss_sum" in out:
            out["loss"] = out.pop("loss_sum") / count
        return out

    def epoch_metric(self, set_metrics):
        return set_metrics.get("loss")

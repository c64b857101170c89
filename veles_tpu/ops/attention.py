"""Attention ops — the long-context compute core.

The reference pre-dates attention entirely (SURVEY §5.7: "absent"), so this
module is BEYOND-PARITY capability, designed TPU-first rather than ported:

- ``attention``: standard scaled-dot-product (the XLA-fused baseline — on
  short sequences XLA's fusion of softmax(QK^T)V is already near-roofline);
- ``blockwise_attention``: flash-style online-softmax over key/value blocks
  via ``lax.scan`` — O(block) memory instead of O(seq²), the single-chip
  long-context path;
- ``mha_forward`` / ``init_mha_params``: a multi-head layer as a pure
  function over a param pytree (the transformer building block) with
  grouped-query attention (``n_kv_heads``), rotary positions
  (``rope_rotate``), sliding windows and attention sinks — all masking
  flows through ONE ``band_bias`` so every decomposition agrees;
- KV-cached decoding: ``mha_decode_step`` (linear cache) and
  ``mha_decode_step_rolling`` (ring-buffer cache with pinned sink
  slots, O(window) memory) share the ``_decode_attend`` core;
- the multi-chip sequence-parallel path (ring attention over a mesh axis)
  lives in ``veles_tpu.parallel.ring`` and reuses the same online-softmax
  update (``_online_update``) so the two decompositions agree numerically.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from veles_tpu import model_config
from veles_tpu.ops.functional import matmul

NEG_INF = -1e30


def attention(q, k, v, causal=False, bias=None, window=None, sinks=0):
    """Dense scaled-dot-product attention.

    q, k, v: (..., heads, seq, head_dim) — returns the same shape as q.
    ``window=W`` additionally restricts each query to the last W keys
    (sliding-window attention — O(seq·W) effective context, the
    long-context serving trade that bounds KV-cache reads); windowed
    attention is a CAUSAL concept here and requires causal=True (a
    lookback bound with unbounded lookahead is never what anyone means).
    """
    if window and not causal:
        raise ValueError("window requires causal=True")
    dh = q.shape[-1]
    scores = matmul(q, jnp.swapaxes(k, -1, -2)) / jnp.sqrt(
        jnp.asarray(dh, q.dtype))
    if bias is not None:
        scores = scores + bias
    if causal or window:
        s_q, s_k = scores.shape[-2], scores.shape[-1]
        scores = scores + band_bias(jnp.arange(s_q) + (s_k - s_q),
                                    jnp.arange(s_k), causal, window,
                                    scores.dtype, sinks=sinks)
    probs = jax.nn.softmax(scores, axis=-1)
    return matmul(probs, v)


# ----------------------------------------------------------------- rotary
def rope_rotate(x, positions, theta=10000.0):
    """Rotary position embedding over (..., seq, head_dim).

    Rotates feature pairs (i, i + head_dim/2) — the half-split ("NeoX")
    layout, NOT the GPT-J interleaved even/odd pairing — by
    position-dependent angles.  Relative positions then enter attention
    through the q·k product itself, so no learned positional table is
    needed, and decode caches hold PRE-rotated keys (each position's
    rotation is final).  ``positions``: (seq,) int array (traced ok)."""
    dh = x.shape[-1]
    half = dh // 2
    # angles are float32 whatever the activations are (a bfloat16 angle
    # at position 8000 is off by tens of radians)
    ct = jnp.promote_types(x.dtype, jnp.float32)
    freqs = theta ** (-jnp.arange(0, half, dtype=ct) / half)
    ang = positions.astype(ct)[:, None] * freqs[None, :]  # (s, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(ct), x[..., half:].astype(ct)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def rope_rotate_batched(x, positions, theta=10000.0):
    """:func:`rope_rotate` with PER-SEQUENCE positions — x
    (batch, heads, c, head_dim) with ``positions`` (batch, c), each
    batch row rotated at its own (traced) positions.  The paged decode
    path needs this: every lane in the batched step sits at a different
    depth, so one shared (seq,) position vector cannot serve them.
    THE contiguous math, vmapped — not a reimplementation, so the two
    paths cannot drift (the parity suite pins the combination end to
    end)."""
    return jax.vmap(lambda xi, pi: rope_rotate(xi, pi, theta))(
        x, positions)


def cfg_rotate(x, positions, cfg, batched=False):
    """A layer's rotary positions by the record: ``rope_rotate`` (per-lane
    positions: ``rope_rotate_batched``) over the whole head, or over its
    first ``cfg.rotary_dims`` dimensions (half-split within those) with the
    rest left as they are."""
    rotate = rope_rotate_batched if batched else rope_rotate
    dh = x.shape[-1]
    rot = cfg.rotary_dims(dh)
    if rot == dh:
        return rotate(x, positions, cfg.rope_theta)
    return jnp.concatenate(
        [rotate(x[..., :rot], positions, cfg.rope_theta), x[..., rot:]],
        axis=-1)


def band_bias(q_pos, k_pos, causal, window, dtype, sinks=0):
    """Additive score bias for the global-position causal/sliding-window
    band — THE shared mask the dense, blockwise and ring decompositions
    all apply, so a semantics change lands in one place.

    ``sinks=K`` keeps the first K positions attendable from EVERYWHERE
    regardless of the window (attention-sink / StreamingLLM form: the
    softmax dumps excess mass on early positions, and evicting them
    degrades windowed models) — sinks bypass the window bound only,
    never causality."""
    allowed = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        allowed &= q_pos[:, None] >= k_pos[None, :]
    if window:
        in_window = q_pos[:, None] - k_pos[None, :] < window
        if sinks:
            in_window |= (k_pos < sinks)[None, :]
        allowed &= in_window
    return jnp.where(allowed, 0.0, NEG_INF).astype(dtype)


def _online_update(carry, q, k, v, score_bias):
    """One online-softmax accumulation step (flash/ring shared core).

    carry: (o, l, m) with o (..., sq, dh), l/m (..., sq).
    Returns the updated carry given this key/value block.
    """
    o, l, m = carry
    dh = q.shape[-1]
    s = matmul(q, jnp.swapaxes(k, -1, -2)) / jnp.sqrt(
        jnp.asarray(dh, q.dtype))
    if score_bias is not None:
        s = s + score_bias
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * alpha + p.sum(axis=-1)
    o_new = o * alpha[..., None] + matmul(p.astype(v.dtype), v)
    return o_new, l_new, m_new


def blockwise_attention(q, k, v, block_size=128, causal=False,
                        window=None, sinks=0):
    """Flash-style attention: scan over key/value blocks with the online
    softmax — numerically equal to ``attention`` but O(block) live memory,
    so sequence length is bounded by HBM, not by the seq² score matrix.
    ``window`` composes (sliding-window mask inside each block; NEG_INF
    is FINITE, so fully-masked early blocks contribute transient terms
    that the online rescale zeroes once a live block arrives — every
    causal query has at least itself live).
    """
    if window and not causal:
        raise ValueError("window requires causal=True")
    *lead, s_q, dh = q.shape
    s_k = k.shape[-2]
    if s_k % block_size:
        raise ValueError("seq %d not divisible by block %d"
                         % (s_k, block_size))
    n_blocks = s_k // block_size
    kb = k.reshape(*lead, n_blocks, block_size, dh)
    vb = v.reshape(*lead, n_blocks, block_size, dh)
    # scan axis must lead
    kb = jnp.moveaxis(kb, -3, 0)
    vb = jnp.moveaxis(vb, -3, 0)
    q_pos = jnp.arange(s_q)

    def body(carry, blk):
        i, kb_i, vb_i = blk
        bias = None
        if causal:
            bias = band_bias(q_pos + (s_k - s_q),
                             i * block_size + jnp.arange(block_size),
                             causal, window, q.dtype, sinks=sinks)
        return _online_update(carry, q, kb_i, vb_i, bias), None

    o0 = jnp.zeros_like(q)
    l0 = jnp.zeros(q.shape[:-1], q.dtype)
    m0 = jnp.full(q.shape[:-1], NEG_INF, q.dtype)
    (o, l, m), _ = jax.lax.scan(
        body, (o0, l0, m0), (jnp.arange(n_blocks), kb, vb))
    return o / l[..., None]


def _bundled_flash(q, k, v, causal):
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention)
    return flash_attention(q, k, v, causal=causal,
                           sm_scale=float(1.0 / (q.shape[-1] ** 0.5)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_highest(q, k, v, causal):
    """The bundled kernel with every dot at HIGHEST.  Its dots name no
    precision, so they take jax's default at TRACE time; the backward
    kernels are traced when the cotangent is pulled back, outside any
    context around the forward call — hence the vjp of our own."""
    with jax.default_matmul_precision("highest"):
        return _bundled_flash(q, k, v, causal)


def _flash_highest_fwd(q, k, v, causal):
    with jax.default_matmul_precision("highest"):
        return jax.vjp(functools.partial(_bundled_flash, causal=causal),
                       q, k, v)


def _flash_highest_bwd(causal, pullback, g):
    with jax.default_matmul_precision("highest"):
        return pullback(g)


_flash_highest.defvjp(_flash_highest_fwd, _flash_highest_bwd)


def flash_attention_tpu(q, k, v, causal=True):
    """The official TPU Pallas flash-attention kernel (bundled with jax)
    as a drop-in for ``attention``: (b, h, s, dh) in/out, our scaling
    convention (1/√dh) applied via sm_scale.  TPU-only — the kernel has
    no interpret-mode escape hatch, so off-TPU callers get a loud error
    instead of a silent fallback.  Mosaic's default dot precision runs
    fp32 operands through bf16 passes (max abs 0.0117 off ``attention``
    at (2, 4, 256, 64) on a v5e), so under the fp32 policy the kernel is
    traced at HIGHEST — the same repair as ``pallas_kernels._flash_step``."""
    from veles_tpu.ops import functional as F
    from veles_tpu.ops.pallas_kernels import on_tpu
    if not on_tpu():
        raise RuntimeError("flash_attention_tpu needs a TPU backend "
                           "(the bundled Pallas kernel has no CPU "
                           "lowering); use attention/blockwise_attention")
    if q.dtype == jnp.float32 and F._PRECISION == jax.lax.Precision.HIGHEST:
        return _flash_highest(q, k, v, causal)
    return _bundled_flash(q, k, v, causal)


def rolling_slot_update(slot_pos, pos, window, sinks=0):
    """Ring-buffer bookkeeping for one decode step, computed ONCE per
    step (shared by every block — same writes).  Cache layout:
    ``sinks`` PINNED slots (positions 0..sinks-1, never evicted —
    StreamingLLM sinks must survive forever) followed by a ``window``
    -slot ring where position p >= sinks lands in slot
    sinks + (p - sinks) % window.  ``slot_pos``
    (sinks + window,) int32 tracks which absolute position each slot
    holds (-1 = never written).  Returns (write slot, updated slot_pos,
    live mask): a slot is live iff it holds a real position that is a
    sink or inside the window."""
    if sinks:
        in_ring = pos >= sinks
        slot = jnp.where(in_ring, sinks + (pos - sinks) % window, pos)
    else:
        slot = pos % window
    slot_pos = jax.lax.dynamic_update_slice(
        slot_pos, jnp.asarray(pos, slot_pos.dtype)[None], (slot,))
    live = (slot_pos >= 0) & (slot_pos <= pos)
    in_window = slot_pos > pos - window
    if sinks:
        in_window |= slot_pos < sinks
    return slot, slot_pos, live & in_window


def mha_decode_step_rolling(params, x, k_cache, v_cache, slot, live,
                            pos, n_heads):
    """One decode step against a RING-BUFFER KV cache of size W — the
    same `_decode_attend` core as ``mha_decode_step``, writing at the
    precomputed ``slot`` under the precomputed ``live`` mask
    (:func:`rolling_slot_update`).  With RoPE (keys carry their own
    rotation; no positional table bounds the length) this gives
    UNBOUNDED autoregressive decode in O(W) memory.

    k_cache/v_cache: (batch, kv_heads, W, head_dim); returns
    (out, k_cache, v_cache) with position ``pos`` written."""
    return _decode_attend(params, x, k_cache, v_cache, slot, live, pos,
                          n_heads)


#: attention backend for mha_forward's non-windowed causal path:
#: 'xla' (dense or our blockwise scan) | 'flash_pallas' (the bundled
#: TPU Pallas kernel above) | 'flash_serve' (ISSUE 7: 'xla' for
#: mha_forward, but serving engines built while it is set default
#: their ``attn_kernel`` to 'auto' — the paged flash-decode /
#: fused-prefill kernels in ops/pallas_kernels.py, with the engine's
#: XLA fallback rules).  Benchmarked by bench.py's lm config on
#: hardware; the default stays whichever wins there.
_ATTN_BACKEND = "xla"


def set_attention_backend(mode):
    """mode: 'xla' | 'flash_pallas' | 'flash_serve'.  Clears jit caches
    (trace-time flag) — but only on an actual change, so a
    restore-to-current no-op doesn't wipe every compiled function in
    the process."""
    global _ATTN_BACKEND
    if mode not in ("xla", "flash_pallas", "flash_serve"):
        raise ValueError("unknown attention backend %r" % (mode,))
    if mode == _ATTN_BACKEND:
        return
    _ATTN_BACKEND = mode
    jax.clear_caches()


def serving_kernel_default():
    """True when the global backend asks serving engines to default
    ``attn_kernel`` on (``set_attention_backend('flash_serve')``) —
    consulted by ``LMEngine`` at construction, never mid-flight."""
    return _ATTN_BACKEND == "flash_serve"


# ------------------------------------------------------------ MHA as layer
def init_mha_params(stream, d_model, n_heads, dtype="float32",
                    n_kv_heads=None):
    """Param pytree for one multi-head attention layer (wq/wk/wv/wo).

    ``n_kv_heads < n_heads`` makes it grouped-query attention: wk/wv
    project to only n_kv_heads·head_dim features, shrinking BOTH the
    projection weights and the decode KV cache by the group factor (the
    long-context serving memory lever); must divide n_heads."""
    import numpy
    kv = n_kv_heads or n_heads
    if n_heads % kv:
        raise ValueError("n_kv_heads %d must divide n_heads %d"
                         % (kv, n_heads))
    d_kv = d_model // n_heads * kv
    s = (6.0 / (2 * d_model)) ** 0.5

    def mk(n_out=d_model):
        w = numpy.zeros((d_model, n_out), dtype)
        stream.fill(w, -s, s)
        return w

    return {"wq": mk(), "wk": mk(d_kv), "wv": mk(d_kv), "wo": mk()}


def kv_heads_of(params, n_heads, d_model):
    """Number of key/value heads, inferred from wk's width (GQA-aware)."""
    return params["wk"].shape[-1] // (d_model // n_heads)


def _repeat_kv(k, n_heads):
    """Broadcast n_kv_heads → n_heads along the head axis (GQA share)."""
    reps = n_heads // k.shape[-3]
    return k if reps == 1 else jnp.repeat(k, reps, axis=-3)


def rms_norm(x, g, eps, dtype=None, centred=False):
    """``x / sqrt(mean(x^2) + eps) * g`` over the last axis, computed in
    float32 whatever ``x`` is; returns ``dtype`` (default ``x``'s).
    ``centred``: the gain is stored about zero and multiplies as ``1 + g``
    (``ModelConfig.norm_centred``)."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps)
    gain = g.astype(jnp.float32)
    return (y * (1.0 + gain if centred else gain)).astype(dtype or x.dtype)


def cfg_matmul(cfg, a, b):
    """``a @ b`` by the record's rule: a float32 model follows
    ``functional``'s process-wide policy; any other dtype multiplies its
    own operands, accumulates in float32 and rounds once."""
    if cfg.dtype == "float32":
        return matmul(a, b)
    return jnp.matmul(a, b, preferred_element_type=jnp.float32) \
        .astype(a.dtype)


def _flat_qkv(params, x, cfg):
    """The q, k and v projections of ``x`` (b, s, d), heads not yet
    split: plain ``[rows, d] @ [d, n]`` dots on the weights as they lie."""
    return tuple(cfg_matmul(cfg, x, params[w]) for w in ("wq", "wk", "wv"))


def _heads(params, x, flat, cfg):
    """q (b, h, s, dh), k and v (b, kv, s, dh) from their ``flat``
    projections, and the output gate (b, s, h·dh; None where the block has
    none) of ``x`` (b, s, d), not yet rotated.  The ``sandwich`` block
    norms q and k per head; a ``pre_rms`` stack's gated layers too, where
    they are not plain (``cfg.plain_full``)."""
    b, s, d = x.shape
    dh = cfg.head_size(d)
    kv = cfg.kv_heads(params, d)

    def split(y, heads):
        return y.reshape(b, s, heads, dh).transpose(0, 2, 1, 3)

    q, k, v = flat
    if cfg.plain_full:
        # plain grouped-query heads; the record's softmax scale rides on q
        q = split(q, cfg.n_heads) * jnp.asarray(cfg.query_scale(dh), q.dtype)
        return q, split(k, kv), split(v, kv), None
    if cfg.block == "pre_rms":
        # the output gate is the second half of each head's ``wq`` columns
        q = q.reshape(b, s, cfg.n_heads, 2 * dh)
        gate = q[..., dh:].reshape(b, s, cfg.n_heads * dh)
        q = q[..., :dh].transpose(0, 2, 1, 3)
        q = rms_norm(q, params["q_norm"], cfg.eps, centred=cfg.norm_centred)
        k = rms_norm(split(k, kv), params["k_norm"], cfg.eps,
                     centred=cfg.norm_centred)
        return q, k, split(v, kv), gate
    q, k, v = split(q, cfg.n_heads), split(k, kv), split(v, kv)
    if cfg.block != "sandwich":
        return q, k, v, None
    q = rms_norm(q, params["q_norm"], cfg.eps)
    k = rms_norm(k, params["k_norm"], cfg.eps)
    return q, k, v, cfg_matmul(cfg, x, params["wg"])


def _qkv(params, x, cfg):
    """``_heads`` of ``x``'s projections where rows are many (training,
    a whole prompt): the compiler lays the dots' outputs out as the
    attention reads them."""
    return _heads(params, x, _flat_qkv(params, x, cfg), cfg)


def _qkv_cached(params, x, cfg):
    """``_qkv`` for the cached paths, where rows are few and a weight is
    the larger operand.  The barrier keeps the head split (and the pool's
    packing of two heads to a 128-lane row) on each projection's small
    OUTPUT.  Without it the chip's compiler folds them into the WEIGHT
    operand and transposes the whole matrix before the dot, in every
    dispatch (weights are arguments: nothing folds once):
      %copy.21 = f32[2048,2048]{0,1:T(8,128)S(1)} copy(wq)
      %copy.34 = f32[2048,2048]{1,0:T(8,128)S(1)} copy(bitcast(wk))
      %copy.33 = f32[2048,2048]{1,0:T(8,128)S(1)} copy(bitcast(wv))
    72 a dispatch, 12 % of the device's time on OPT-1.3B (ISSUE 31).
    ``tests/test_chip_compile.py`` holds the engine's programs to none."""
    flat = [jax.lax.optimization_barrier(y)
            for y in _flat_qkv(params, x, cfg)]
    return _heads(params, x, flat, cfg)


def _merge(params, o, gate, cfg):
    """Heads' outputs (b, h, s, dh) through the sigmoid output gate (where
    the block has one) and ``wo``."""
    b, h, s, dh = o.shape
    o = o.transpose(0, 2, 1, 3).reshape(b, s, h * dh)
    if gate is not None:
        o = (o.astype(jnp.float32)
             * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(o.dtype)
    return cfg_matmul(cfg, o, params["wo"])


def _attend(q, k, v, live):
    """softmax(q k^T / sqrt(dh), masked by ``live``) v — the dense core of
    every cached path.  float32 operands follow ``functional``'s policy;
    narrower ones keep scores, softmax and accumulation in float32."""
    dh = q.shape[-1]
    kt = jnp.swapaxes(k, -1, -2)
    if q.dtype == jnp.float32:
        scores = matmul(q, kt) / jnp.sqrt(jnp.asarray(dh, q.dtype))
        scores = jnp.where(live, scores, NEG_INF)
        return matmul(jax.nn.softmax(scores, axis=-1), v)
    scores = jnp.matmul(q, kt, preferred_element_type=jnp.float32) \
        / jnp.sqrt(jnp.float32(dh))
    scores = jnp.where(live, scores, NEG_INF)
    return jnp.matmul(jax.nn.softmax(scores, axis=-1).astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(v.dtype)


def mha_forward(params, x, n_heads, causal=True, block_size=None,
                return_kv=False, rope=False, window=None,
                positions=None, sinks=0, layer=0):
    """Multi-head attention over (batch, seq, d_model).  ``n_heads`` is a
    head count with the classic keywords beside it, or a
    :class:`~veles_tpu.ops.model_config.ModelConfig` (then ``layer``
    says which layer's attention kind applies).

    ``return_kv=True`` additionally returns the projected (k, v) heads
    — the prefill half of KV-cached decoding (autoregressive serving
    writes them into the cache once instead of recomputing per token;
    under GQA those are the n_kv_heads, i.e. the smaller cache).
    ``rope`` rotates q/k (``positions`` defaults to 0..s-1); ``window``
    restricts attention to the last W positions."""
    cfg = model_config.of(n_heads, rope, window, sinks)
    if cfg.kind(layer) == model_config.LINEAR:
        from veles_tpu.ops.linear_attn import linear_forward
        if return_kv or not causal:
            raise ValueError("a linear layer is causal and has no KV cache")
        return linear_forward(params, x, cfg)
    if cfg.latent is not None:
        from veles_tpu.ops.latent import latent_forward
        if return_kv or not causal:
            raise ValueError("latent attention is causal and has no "
                             "contiguous cache")
        return latent_forward(params, x, cfg, positions)
    rope, window = cfg.layer_rope(layer), cfg.layer_window(layer)
    n_heads, sinks = cfg.n_heads, cfg.sinks
    s = x.shape[1]
    q, k, v, gate = _qkv(params, x, cfg)
    if rope:
        pos = positions if positions is not None else jnp.arange(s)
        q = cfg_rotate(q, pos, cfg)
        k = cfg_rotate(k, pos, cfg)
    kr, vr = _repeat_kv(k, n_heads), _repeat_kv(v, n_heads)
    if cfg.block != "pre_ln":
        if not causal:
            raise ValueError("the %s block is causal" % cfg.block)
        with jax.named_scope("attn.window" if window else "attn.full"):
            o = _attend(q, kr, vr,
                        chunk_live_mask(0, s, s, window)[None, None])
    elif _ATTN_BACKEND == "flash_pallas" and not window:
        o = flash_attention_tpu(q, kr, vr, causal=causal)
    elif block_size:
        o = blockwise_attention(q, kr, vr, block_size, causal=causal,
                                window=window, sinks=sinks)
    else:
        o = attention(q, kr, vr, causal=causal, window=window,
                      sinks=sinks)
    out = _merge(params, o, gate, cfg)
    return (out, k, v) if return_kv else out


def _decode_attend(params, x, k_cache, v_cache, write_idx, live,
                   rope_pos, cfg):
    """THE decode-step core shared by the linear-cache and ring-buffer
    paths (they must never drift numerically): project q/k/v for one
    position, optionally rotate q/k at ``rope_pos``, write the new k/v
    at cache index ``write_idx``, attend over the cache under the
    precomputed ``live`` mask (cache_len,), and project out."""
    cfg = model_config.of(cfg)
    q, k_new, v_new, gate = _qkv_cached(params, x, cfg)     # (b, h, 1, dh)
    if rope_pos is not None:
        pos_arr = jnp.asarray(rope_pos)[None]
        q = cfg_rotate(q, pos_arr, cfg)
        k_new = cfg_rotate(k_new, pos_arr, cfg)
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k_new, (0, 0, write_idx, 0))
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v_new, (0, 0, write_idx, 0))
    o = _attend(q, _repeat_kv(k_cache, cfg.n_heads),
                _repeat_kv(v_cache, cfg.n_heads),
                live[None, None, None, :])       # (b, h, 1, cache_len)
    return _merge(params, o, gate, cfg), k_cache, v_cache


def chunk_live_mask(pos, c, cache_len, window=None, sinks=0):
    """(c, cache_len) bool mask for ``c`` query positions starting at
    traced ``pos`` attending a linear cache — the multi-query sibling of
    the per-step mask in :func:`mha_decode_step` (same semantics at
    c=1, same window/sink rules as :func:`band_bias`)."""
    q_pos = pos + jnp.arange(c)
    idx = jnp.arange(cache_len)
    live = idx[None, :] <= q_pos[:, None]
    if window:
        in_window = idx[None, :] > q_pos[:, None] - window
        if sinks:
            in_window |= (idx < sinks)[None, :]
        live &= in_window
    return live


def mha_decode_step(params, x, k_cache, v_cache, pos, n_heads,
                    rope=False, window=None, sinks=0, layer=0):
    """One autoregressive decode step with a KV cache.

    x: (batch, 1, d_model) — the current position's activations;
    k_cache/v_cache: (batch, kv_heads, max_len, head_dim) with positions
    [0, pos) filled; ``pos`` is a traced scalar.  Returns
    (out (batch, 1, d_model), k_cache, v_cache) with position ``pos``
    written.  The O(seq) attention against the cache replaces the
    O(seq²) full recompute per generated token — the standard serving
    path on TPU (static cache shape, dynamic_update_slice, no growing
    arrays under jit).  GQA caches hold the n_kv_heads only; ``rope``
    rotates the new q/k at ``pos`` (cached keys are pre-rotated);
    ``window`` masks cache entries older than W positions.
    """
    cfg = model_config.of(n_heads, rope, window, sinks)
    rope, window = cfg.layer_rope(layer), cfg.layer_window(layer)
    idx = jnp.arange(k_cache.shape[2])
    live = idx <= pos
    if window:
        in_window = idx > pos - window
        if cfg.sinks:
            in_window |= idx < cfg.sinks   # sinks bypass the window only
        live &= in_window
    return _decode_attend(params, x, k_cache, v_cache, pos, live,
                          pos if rope else None, cfg)


# ------------------------------------------------------------- paged KV
def paged_view(pool, ptab):
    """Gather a lane's LINEAR cache view out of the shared page pool.

    pool: (n_pages, kv_heads, page, head_dim) — ONE region shared by
    every lane; ptab: (..., m) int32 page table mapping lane-local page
    j to its pool row.  Returns (..., kv_heads, m·page, head_dim) — the
    exact array a contiguous per-lane cache would hold, so the
    attention math downstream is the contiguous math unchanged (the
    indirection-tolerance argument of Flex-TPU: reconfigure the
    dataflow, keep the kernel).  Table entries past a lane's allocated
    pages point at the reserved scratch page; the caller's live mask
    must cover them (it does: live positions never exceed the lane's
    reservation)."""
    g = pool[ptab]                       # (..., m, kv, page, dh)
    g = jnp.moveaxis(g, -4, -3)          # (..., kv, m, page, dh)
    return g.reshape(g.shape[:-3] + (g.shape[-3] * g.shape[-2],
                                     g.shape[-1]))


#: rows of one write from which the serving kernels' engine installs them
#: with ONE Pallas call (``pallas_kernels.paged_row_write``) instead of an
#: update slice each: a chain of 32 slices on each of ten pools took the
#: chip's compiler two minutes a decode program (PERF.md section 6, PR 28)
ROW_KERNEL_MIN = 16


def paged_write(pool, ptab, pos, rows, write_mask=None, kernel=False):
    """Write ``c`` new K (or V) rows into the pool at the lanes'
    LINEAR positions [pos, pos+c) — the paged sibling of the contiguous
    ``dynamic_update_slice`` write.

    rows: (..., kv_heads, c, head_dim); ptab (..., m); pos (...,) —
    leading dims are the lane batch (absent for a single lane).  The
    pool is (n_pages, kv_heads/r, page, r·head_dim): r heads to a row
    where the serving kernels are active (``pallas_kernels.pool_pack``),
    r = 1 otherwise; r is read off the pool's last axis.  Each position
    p maps to (page ptab[p // page], offset p % page), so a write may
    straddle two pages; every position is written on its own.
    Duplicate targets (every free lane parks on the scratch page) are
    resolved by order, the last lane winning — by construction only
    garbage rows collide, and nothing live ever attends them.

    The write is one ``dynamic_update_slice`` of a (1, kv_heads/r, 1,
    r·head_dim) window per position — b·c of them, unrolled — and NOT
    one scatter.  A scatter over the page and offset axes made the TPU
    compiler hold the pool with those two axes major-most: it converted
    the whole pool before and after every write (two copies of each
    layer's pool per dispatch; 1.03 ms for 0.03 on a f32[321,32,32,64]
    pool).  A scatter over the three leading axes
    leaves the layout alone and is slow itself (the cell served 95
    tokens/s with it for 229; PERF.md section 6, PR 27).  An update
    slice takes the pool in whatever layout it lies and touches the
    rows it writes, nothing else; with the pool donated to the program
    it is an update in place.

    ``write_mask`` (traced bool, one per lane) REDIRECTS a masked-out
    lane's whole write onto the reserved scratch page (pool row 0 —
    ``serving/kv_pool.py::KVPagePool.SCRATCH``): the decode megastep
    (ISSUE 13) keeps early-exit lanes inside the batched program, and
    their dead iterations must not be able to touch ANY allocated page
    — not their own (possibly trie-shared) pages, not a clamped table
    edge — no matter what garbage position the frozen carry holds.

    ``kernel`` (static; the caller runs the Pallas serving kernels) hands
    a DECODE write (c = 1) of ``ROW_KERNEL_MIN`` lanes or more to
    ``pallas_kernels.paged_row_write``: the same rows at the same places,
    one call.  Only c = 1: that kernel rewrites a whole tile of pool rows
    per written row, so it is right only while no two rows of a call lie
    in one tile of a live page, which one row a lane guarantees (lanes own
    distinct pages) and c adjacent positions of a lane (the speculative
    verify, a prefill chunk) do not; those keep their update slices."""
    page = pool.shape[2]
    c = rows.shape[-2]
    linear = jnp.asarray(pos)[..., None] + jnp.arange(c)   # (..., c)
    page_ids = jnp.take_along_axis(ptab, linear // page, axis=-1)
    offsets = linear % page
    if write_mask is not None:
        page_ids = jnp.where(write_mask[..., None], page_ids, 0)
    # one (1, kv/r, 1, r·dh) window per written position (a position's
    # (kv, dh) rows ARE its packed rows, reshaped), lanes major like
    # the ids
    new = jnp.moveaxis(rows, -3, -2).reshape(
        -1, pool.shape[1], 1, pool.shape[3])
    # lint: allow(recompile-hazard): the rows of a write are the engine's lanes x c, fixed per program family
    if kernel and c == 1 and new.shape[0] >= ROW_KERNEL_MIN \
            and page % (32 // pool.dtype.itemsize) == 0:
        # (the kernel moves whole tiles of 32 / itemsize pool rows)
        from veles_tpu.ops import pallas_kernels as PK
        return PK.paged_row_write(pool, new[:, :, 0, :],
                                  page_ids.reshape(-1),
                                  offsets.reshape(-1))
    return _write_rows(pool, new, page_ids.reshape(-1),
                       offsets.reshape(-1))


@jax.jit
def _write_rows(pool, new, page_ids, offsets):
    """``paged_write``'s update slices, one per row of ``new``.  A jit
    of its own so that a program traces it ONCE for all its layers and
    the lowering holds one function, called per pool: unrolled inline,
    the 384 slices of a 24-layer decode program doubled the time to
    trace and lower it, for each width of the ladder (setup_s; PERF.md
    section 6, PR 27).  XLA inlines the calls: the compiled program is
    the same."""
    zero = jnp.zeros((), page_ids.dtype)
    for j in range(new.shape[0]):
        pool = jax.lax.dynamic_update_slice(
            pool, new[j:j + 1], (page_ids[j], zero, offsets[j], zero))
    return pool


def mha_paged_chunk_step(params, x, k_pool, v_pool, ptab, pos, n_heads,
                         rope=False, window=None, sinks=0,
                         attn_kernel=None, write_mask=None, layer=0,
                         base=None):
    """``c`` positions per lane against the PAGED KV pool in one pass —
    the multi-token generalization of :func:`mha_decode_step` with the
    storage indirected through a page table, batched over lanes (each at
    its own traced ``pos``).

    x: (b, c, d_model) — b lanes' activations for their positions
    [pos[i], pos[i]+c); k_pool/v_pool: (n_pages, kv_heads, page,
    head_dim) shared across lanes; ptab: (b, m) per-lane page tables;
    pos: (b,) traced.  Writes the c new K/V rows through the table and
    attends each lane's query j causally over its own linear view
    (window/sinks exactly as :func:`chunk_live_mask`).  At c=1 this is
    the paged decode step; at c=k+1 the paged speculative verify; with
    b=1, c=chunk the paged prefill chunk — ONE core, so the paged
    decompositions can never drift from each other.  The gathered view
    has the same (kv, m·page, dh) shape for every lane.  Callers may
    pass a ``ptab`` sliced NARROWER than max_len/page as long as it
    covers every lane's live rows (the engine's live-width ladder,
    ISSUE 7): masked tail columns contribute exactly-zero softmax
    terms, so the shorter reductions agree with the full-width ones
    except under reduction-order reassociation of the SAME live
    values — the greedy parity matrix (tests/test_lm_fastpath.py)
    pins outputs bit-identical to the contiguous path across the
    ladder on the test platform.

    ``base`` (traced (b,), a multiple of the page size; None = 0) says
    where each lane's table BEGINS: entry j of the row maps linear
    positions [base + j·page, base + (j+1)·page).  A sliding layer's
    table holds only the pages its window still reaches, so it starts
    at the lane's first live page instead of at position 0.  Writes,
    the gathered view, the band and the kernels' grids all work on
    ``pos - base``: causality and the window depend on differences of
    positions only (so no sinks with a base).  Rotary angles are the one
    place the absolute ``pos`` enters.

    ``attn_kernel`` (STATIC) routes the attention through the Pallas
    serving kernels (ISSUE 7) instead of the gather + dense softmax:
    'decode' (any c, any alignment — the pool is written first, then
    ``pallas_kernels.paged_flash_decode`` walks the table in-kernel; no
    (b, kv, L, dh) view is ever materialized) or 'prefill' (c must
    equal the page size and ``pos`` be page-aligned — the caller's
    contract; ``paged_flash_prefill`` streams the history and installs
    the chunk's rows in its epilogue).  None/False = the XLA path.
    Kernel outputs match XLA to fp32 roundoff (online softmax), which
    preserves the greedy argmax the serving contract pins.

    ``write_mask`` (traced (b,) bool; ISSUE 13) diverts masked lanes'
    K/V writes to the scratch page (see :func:`paged_write`) — their
    attention still runs (the megastep program's shape never changes)
    but its output is garbage the host discards; the pool is untouched
    for them.  Not supported with ``attn_kernel='prefill'`` (the fused
    install has no mask slot; the megastep never uses that leg —
    prefill chunks stay per-lane host dispatches)."""
    cfg = model_config.of(n_heads, rope, window, sinks)
    rope, window = cfg.layer_rope(layer), cfg.layer_window(layer)
    n_heads, sinks = cfg.n_heads, cfg.sinks
    c = x.shape[1]
    q, k_new, v_new, gate = _qkv_cached(params, x, cfg)    # (b, h, c, dh)
    if rope:
        positions = jnp.asarray(pos)[:, None] + jnp.arange(c)   # (b, c)
        q = cfg_rotate(q, positions, cfg, batched=True)
        k_new = cfg_rotate(k_new, positions, cfg, batched=True)
    if base is not None:
        if sinks:
            raise ValueError("attention sinks need absolute positions: "
                             "not with a table base")
        pos = jnp.asarray(pos) - base
    scope = (jax.named_scope("attn.window" if window else "attn.full")
             if cfg.attn_kinds is not None else contextlib.nullcontext())
    with scope:
        if attn_kernel:
            from veles_tpu.ops import pallas_kernels as PK
            if attn_kernel == "prefill":
                if write_mask is not None:
                    raise ValueError(
                        "write_mask is not supported with "
                        "attn_kernel='prefill' (fused install)")
                o, k_pool, v_pool = PK.paged_flash_prefill(
                    q, k_new, v_new, k_pool, v_pool, ptab, pos,
                    window=window, sinks=sinks)
            else:
                k_pool = paged_write(k_pool, ptab, pos, k_new, write_mask,
                                     kernel=True)
                v_pool = paged_write(v_pool, ptab, pos, v_new, write_mask,
                                     kernel=True)
                o = PK.paged_flash_decode(q, k_pool, v_pool, ptab, pos,
                                          window=window, sinks=sinks)
            return _merge(params, o, gate, cfg), k_pool, v_pool
        k_pool = paged_write(k_pool, ptab, pos, k_new, write_mask)
        v_pool = paged_write(v_pool, ptab, pos, v_new, write_mask)
        kx = paged_view(k_pool, ptab)               # (b, kv, L, dh)
        vx = paged_view(v_pool, ptab)
        live = jax.vmap(lambda p: chunk_live_mask(
            p, c, kx.shape[-2], window, sinks))(jnp.asarray(pos))
        o = _attend(q, _repeat_kv(kx, n_heads), _repeat_kv(vx, n_heads),
                    live[:, None, :, :])            # (b, h, c, L)
    return _merge(params, o, gate, cfg), k_pool, v_pool

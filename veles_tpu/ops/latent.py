"""Latent attention (MLA): the attention of the ``pre_rms`` block
(``model_config.LatentConfig``).

Per token ``x``: ``c_q = rms(x W_qa)``, ``q = c_q W_qb`` (or ``q = x W_q``
where the record has no bottleneck, ``q_rank`` None) and per head
``[q_nope | q_rope]``; ``[c_kv | k_rope] = x W_kva``, ``c_kv = rms(c_kv)``;
``q_rope`` and ``k_rope`` rotated (YaRN), ``k_rope`` one vector for all
heads.  THE CACHE HOLDS ``(c_kv, k_rope)``: one row of ``latent.row`` lanes
a token a layer (``latent.width`` numbers, zeros behind them).  Per head
``[k_nope | v] = c_kv W_kvb``; ``s = (q_nope . k_nope + q_rope . k_rope)
* scale``, causal softmax, ``o = sum p v``, output ``concat(o) W_o`` (each
head's ``o`` first times ``sigmoid(x w_gate)_h`` under ``head_gate``).

Two orders of the same sums, chosen by phase:

- EXPANDED (a prompt chunk, the whole-sequence forward): keys and values
  are rebuilt from the cached latents through ``W_kvb`` and attention runs
  per head at width ``nope + rope`` / ``v``: 320 multiply-adds a head a
  (query, key) pair, and the re-expansion once a cached token a chunk.
- ABSORBED (decode: a few query rows against a long cache): ``q~ = q_nope
  W_kvb[K, h]^T`` is ``kv_rank`` wide, ``s = (q~ . c_kv + q_rope . k_rope)
  * scale``, ``o_lat = sum p c_kv``, ``o = o_lat W_kvb[V, h]``: every head
  reads the SAME cached row, once, and no key or value is ever built; 1088
  multiply-adds a head a pair, which a step bound by the cache's bytes
  does not feel.

Both run under the scope ``attn.latent``; scores, softmax and accumulators
are float32 whatever the model's dtype.  With the serving kernels active
(``attn_kernel``) the absorbed form is ``pallas_kernels.paged_latent_decode``
(a loop of each lane's own length over blocks of its cached rows) and the
expanded form ``pallas_kernels.paged_latent_prefill``, which expands a page
of latents at a time in fast memory and skips the table's dead pages
(``live_pages``).  Without them the lane's rows are gathered and the same
sums are dense XLA.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy

from veles_tpu.ops import functional as F
from veles_tpu.ops.attention import (NEG_INF, cfg_matmul, chunk_live_mask,
                                     paged_view, paged_write, rms_norm)


# ------------------------------------------------------------------- YaRN
def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim, theta, yarn):
    """The ``dim / 2`` rotary frequencies under YaRN (float64, host):
    ``f_i = theta^(-2i/dim)`` kept where a frequency turns more than
    ``beta_fast`` times within the original context, divided by ``factor``
    where it turns fewer than ``beta_slow`` times, and blended linearly by
    index between the two correction dimensions."""
    i = numpy.arange(dim // 2, dtype=numpy.float64)
    freq = theta ** (-2.0 * i / dim)
    if yarn is None:
        return freq

    def correction(turns):
        return dim * math.log(yarn.original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(yarn.beta_fast)), 0)
    high = min(math.ceil(correction(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = numpy.clip((i - low) / (high - low), 0.0, 1.0)
    return freq / yarn.factor * ramp + freq * (1.0 - ramp)


def softmax_scale(cfg):
    """``(nope + rope)^-0.5 * m^2``, ``m = mscale(factor, mscale_all_dim)``
    (1 without YaRN or with ``mscale_all_dim`` 0)."""
    lat, yarn = cfg.latent, cfg.yarn
    scale = (lat.nope + lat.rope) ** -0.5
    if yarn is not None and yarn.mscale_all_dim:
        scale *= _mscale(yarn.factor, yarn.mscale_all_dim) ** 2
    return scale


def rotary(cfg, positions):
    """(cos, sin) float32 of shape ``positions.shape + (rope / 2,)``, times
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``."""
    lat, yarn = cfg.latent, cfg.yarn
    inv = jnp.asarray(yarn_inv_freq(lat.rope, cfg.rope_theta, yarn),
                      jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * inv
    mult = 1.0
    if yarn is not None:
        mult = _mscale(yarn.factor, yarn.mscale) \
            / _mscale(yarn.factor, yarn.mscale_all_dim)
    return jnp.cos(ang) * mult, jnp.sin(ang) * mult


def rotate(x, cos, sin):
    """Half-split rotation of ``x`` (..., rope) by broadcastable angles."""
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


# ------------------------------------------------------------ projections
def _einsum(cfg, spec, a, b, wide=False):
    """``jnp.einsum`` by the record's rule (``attention.cfg_matmul``);
    ``wide`` keeps the float32 accumulator (scores)."""
    if cfg.dtype == "float32":
        return jnp.einsum(spec, a, b, precision=F._PRECISION)
    out = jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)
    return out if wide else out.astype(a.dtype)


def _held(y, cached):
    """A projection's output on a cached path: behind a barrier, so that
    the head split stays on the small output and is not folded into the
    weight operand (``attention._qkv_cached`` says what that costs)."""
    return jax.lax.optimization_barrier(y) if cached else y


def queries(p, x, cfg, cos, sin, cached=False):
    """(q_nope (b, h, s, nope), q_rope (b, h, s, rope) rotated) of ``x``
    (b, s, d); ``cos``/``sin`` (b, s, rope/2) or (s, rope/2)."""
    lat = cfg.latent
    b, s, _ = x.shape
    if lat.q_rank is None:
        q = cfg_matmul(cfg, x, p["wq"])
    else:
        cq = rms_norm(cfg_matmul(cfg, x, p["wq_a"]), p["q_norm"], cfg.eps)
        q = cfg_matmul(cfg, cq, p["wq_b"])
    q = _held(q, cached).reshape(
        b, s, cfg.n_heads, lat.nope + lat.rope).transpose(0, 2, 1, 3)
    cos, sin = (t[..., None, :, :] for t in (cos, sin))
    return q[..., :lat.nope], rotate(q[..., lat.nope:], cos, sin)


def latent_rows(p, x, cfg, cos, sin, cached=False):
    """The rows the cache holds for ``x`` (b, s, d): (b, s, latent.row),
    ``[rms(c_kv) | k_rope rotated | zeros]``."""
    lat = cfg.latent
    kv = _held(cfg_matmul(cfg, x, p["wkv_a"]), cached)
    c = rms_norm(kv[..., :lat.kv_rank], p["kv_norm"], cfg.eps)
    kr = rotate(kv[..., lat.kv_rank:], cos, sin)
    pad = jnp.zeros(kv.shape[:-1] + (lat.row - lat.width,), kv.dtype)
    return jnp.concatenate([c, kr, pad], axis=-1)


def kvb_heads(p):
    """``W_kvb`` by head, as the tree holds it: (``wk_b`` (h, kv_rank,
    nope), ``wv_b`` (h, kv_rank, v)): head ``h``'s columns ``[k_nope | v]``
    of the published (kv_rank, h * (nope + v)) matrix, apart, so that no
    program slices or transposes a weight."""
    return p["wk_b"], p["wv_b"]


def _merge(p, o, cfg, x):
    """Heads' outputs (b, h, s, v) through ``W_o``; under ``head_gate``
    each head's first times its sigmoid gate of the layer's input ``x``
    (b, s, d), one number a token and head, in float32."""
    b, h, s, v = o.shape
    if cfg.latent.head_gate:
        gate = jax.nn.sigmoid(jnp.matmul(
            x, p["w_gate"], preferred_element_type=jnp.float32,
            precision=F._PRECISION if x.dtype == jnp.float32 else None))
        o = (o.astype(jnp.float32)
             * gate.transpose(0, 2, 1)[..., None]).astype(o.dtype)
    return cfg_matmul(cfg, o.transpose(0, 2, 1, 3).reshape(b, s, h * v),
                      p["wo"])


def _softmax(scores, live, scale):
    return jax.nn.softmax(
        jnp.where(live, scores.astype(jnp.float32) * scale, NEG_INF),
        axis=-1)


# ------------------------------------------------------------- two orders
def attend_expanded(p, q_nope, q_rope, rows, live, cfg):
    """Expanded attention of queries (b, h, c, .) over cached ``rows``
    (b, L, latent.row) under ``live`` (b, 1, c, L): (b, h, c, v)."""
    lat = cfg.latent
    wk, wv = kvb_heads(p)
    c_kv = rows[..., :lat.kv_rank]
    k_rope = rows[..., lat.kv_rank:lat.width]
    k_nope = _einsum(cfg, "blr,hrn->bhln", c_kv, wk)
    v = _einsum(cfg, "blr,hrv->bhlv", c_kv, wv)
    s = _einsum(cfg, "bhcn,bhln->bhcl", q_nope, k_nope, wide=True) \
        + _einsum(cfg, "bhcr,blr->bhcl", q_rope, k_rope, wide=True)
    prob = _softmax(s, live, softmax_scale(cfg)).astype(v.dtype)
    return _einsum(cfg, "bhcl,bhlv->bhcv", prob, v)


def absorbed_queries(p, q_nope, q_rope, cfg):
    """The absorbed form's query rows (b, h, c, latent.row): ``[q_nope
    W_kvb[K, h]^T | q_rope | zeros]``, which meet a cached row lane for
    lane."""
    lat = cfg.latent
    wk, _ = kvb_heads(p)
    qt = _einsum(cfg, "bhcn,hrn->bhcr", q_nope, wk)
    pad = jnp.zeros(qt.shape[:-1] + (lat.row - lat.width,), qt.dtype)
    return jnp.concatenate([qt, q_rope, pad], axis=-1)


def absorbed_outputs(p, o_lat, cfg):
    """``o = o_lat W_kvb[V, h]``: (b, h, c, kv_rank) -> (b, h, c, v)."""
    _, wv = kvb_heads(p)
    return _einsum(cfg, "bhcr,hrv->bhcv", o_lat, wv)


def attend_absorbed(p, q_nope, q_rope, rows, live, cfg):
    """Absorbed attention: the same sums as :func:`attend_expanded` in the
    other order, over the cached rows as they lie."""
    lat = cfg.latent
    qa = absorbed_queries(p, q_nope, q_rope, cfg)
    s = _einsum(cfg, "bhcw,blw->bhcl", qa, rows, wide=True)
    prob = _softmax(s, live, softmax_scale(cfg)).astype(rows.dtype)
    o_lat = _einsum(cfg, "bhcl,blr->bhcr", prob, rows[..., :lat.kv_rank])
    return absorbed_outputs(p, o_lat, cfg)


# ------------------------------------------------------------ entry points
def latent_forward(p, x, cfg, positions=None):
    """Latent attention over a whole sequence ``x`` (b, s, d), causal, in
    the expanded form."""
    s = x.shape[1]
    pos = positions if positions is not None else jnp.arange(s)
    cos, sin = rotary(cfg, pos)
    with jax.named_scope("attn.latent"):
        q_nope, q_rope = queries(p, x, cfg, cos, sin)
        rows = latent_rows(p, x, cfg, cos, sin)
        live = chunk_live_mask(0, s, s)[None, None]
        o = attend_expanded(p, q_nope, q_rope, rows, live, cfg)
    return _merge(p, o, cfg, x)


def _write_chunk_pages(pool, ptab, pos, rows):
    """A page-aligned chunk of ``page`` rows a lane into the lane's page,
    one update slice a lane (in place on a donated pool)."""
    page = pool.shape[2]
    zero = jnp.zeros((), jnp.int32)
    for i in range(rows.shape[0]):
        pid = ptab[i, pos[i] // page].astype(jnp.int32)
        pool = jax.lax.dynamic_update_slice(
            pool, rows[i][None, None], (pid, zero, zero, zero))
    return pool


def latent_paged_chunk_step(p, x, pool, ptab, pos, cfg, attn_kernel=None,
                            write_mask=None, scope="attn.latent"):
    """``c`` positions per lane against the PAGED LATENT POOL:
    ``attention.mha_paged_chunk_step`` for the latent kind.

    x: (b, c, d); pool: (n_pages, 1, page, latent.row), one array a
    layer; ptab (b, m); pos (b,) traced.  The new rows are written through
    the table first (decode: ``paged_write``, one kernel call for 16 lanes
    or more, and one a row for a verify step's ``c`` rows a lane; a chunk
    under the prefill kernel: its whole page with one update slice), then attention reads the pool: ABSORBED for a decode
    step (``attn_kernel='decode'``, or one query row a lane without
    kernels), EXPANDED for a chunk (``'prefill'``, or several rows).
    ``scope`` names the Pallas calls in the device trace (the innermost
    scope is the one a call takes): the stack's layers' ``attn.latent``, the
    multi-token-prediction module's its own.  Returns (out (b, c, d),
    pool)."""
    lat = cfg.latent
    b, c, _ = x.shape
    pos = jnp.asarray(pos)
    positions = pos[:, None] + jnp.arange(c)
    cos, sin = rotary(cfg, positions)
    absorbed = attn_kernel == "decode" if attn_kernel else c == 1
    with jax.named_scope(scope):
        q_nope, q_rope = queries(p, x, cfg, cos, sin, cached=True)
        rows = latent_rows(p, x, cfg, cos, sin, cached=True)  # (b, c, row)
        if attn_kernel == "prefill":
            if write_mask is not None:
                raise ValueError("write_mask is not supported with "
                                 "attn_kernel='prefill'")
            # (chunk == page and a page-aligned ``pos``: the kernel's
            # contract, which it checks)
            pool = _write_chunk_pages(pool, ptab, pos, rows)
        elif attn_kernel == "decode" and c > 1:
            # a verify step's c rows a lane: the row kernel takes ONE row a
            # lane a call (two rows of a lane may share a tile), so the
            # rows go a column at a time, each call on the pool the last
            # one left
            for j in range(c):
                pool = paged_write(pool, ptab, pos + j,
                                   rows[:, None, j:j + 1], write_mask,
                                   kernel=True)
        else:
            pool = paged_write(pool, ptab, pos, rows[:, None], write_mask,
                               kernel=attn_kernel == "decode")
        if attn_kernel:
            from veles_tpu.ops import pallas_kernels as PK
            scale = softmax_scale(cfg)
            if absorbed:
                qa = absorbed_queries(p, q_nope, q_rope, cfg)
                o_lat = PK.paged_latent_decode(qa, pool, ptab, pos, scale)
                o = absorbed_outputs(p, o_lat[..., :lat.kv_rank], cfg)
            else:
                wk, wv = kvb_heads(p)
                o = PK.paged_latent_prefill(
                    q_nope, q_rope, wk, wv, pool, ptab, pos, scale,
                    lat.kv_rank)
        else:
            view = paged_view(pool, ptab)[:, 0]           # (b, L, row)
            live = jax.vmap(lambda q: chunk_live_mask(
                q, c, view.shape[1]))(pos)[:, None]
            attend = attend_absorbed if absorbed else attend_expanded
            o = attend(p, q_nope, q_rope, view, live, cfg)
    return _merge(p, o, cfg, x), pool

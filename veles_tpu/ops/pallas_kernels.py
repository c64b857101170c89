"""Pallas TPU kernels for the ops XLA's fusion doesn't fully own.

SURVEY §2.4 names the custom-kernel candidates: the fused GD update (one
VMEM pass over param/velocity/grad instead of several HBM round-trips when
XLA declines to fuse across the update's reshapes) and dropout with a
counter-based in-kernel PRNG (the reference generated masks with device RNG
inside its OpenCL kernels — veles/znicz/dropout.py + ocl kernels [H]).

Kernels run in interpret mode off-TPU (``interpret=None`` auto-detects).
The fused SGD kernel is the same code on both paths; the dropout kernel's
TPU PRNG primitives have no CPU lowering, so its off-TPU branch substitutes
threefry — the real-kernel keep statistics are asserted by a TPU-marked
test (tests/test_pallas.py) that must be run on hardware.  Both kernels
have jax/XLA equivalents in ``functional``; selection is explicit (bench
flags / caller opt-in), never silent.

The SERVING ATTENTION SUITE (ISSUE 7) is the hot-loop half: the paged LM
engine's decode/verify/prefill dispatches spend their bandwidth in
``ops/attention.py::paged_view`` — a gather that materializes every
lane's full (kv, max_len, dh) cache view in HBM before one (c,)-token
query reads a fraction of it.  Two kernels walk the page table INSIDE
the kernel instead, so no densified view ever exists:

- :func:`paged_flash_decode` — flash-decode over the paged KV pool: the
  grid is the lanes, and a loop of the lane's own length copies its live
  pages, several to a block, through VMEM into an online-softmax
  accumulator (the ``attention._online_update`` recurrence), with the
  ``chunk_live_mask`` causal/window/sink band applied in-kernel.  Serves
  the single-token decode step AND the (k+1)-token speculative verify
  (queries are (c,) per lane).
- :func:`paged_flash_prefill` — fused chunked prefill: the chunk's new
  K/V enter as VMEM operands (never read back from HBM), history pages
  stream like decode, and the kernel's EPILOGUE installs the chunk's
  rows into the lane's pool page through aliased outputs — the
  ``paged_write`` row install folded into the same program.

Both run in interpret mode off-TPU (the CPU parity suite,
``tests/test_pallas.py -m kernel_parity`` / ``tools/
check_kernel_parity.py``); the serving engine only routes through them
on real TPU hardware (or when forced) — see ``serving/lm_engine.py``'s
``attn_kernel`` fallback rules.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy

# the XLA reference path's finite masking constant — the kernels MUST
# share it exactly: the all-masked-block rescale argument in
# _flash_step relies on masked scores being bitwise the same value on
# both sides of the parity suite
from veles_tpu.ops import functional as F
from veles_tpu.ops.attention import NEG_INF


def on_tpu():
    """True when the default backend is the TPU."""
    return jax.default_backend() == "tpu"


def _interpret(flag):
    if flag is not None:
        return flag
    return not on_tpu()


# ---------------------------------------------------------- fused SGD update
#: rows of the (rows, 128) layout per grid step: 512 KiB per fp32
#: operand, 5 MiB for the five double-buffered operands
_SGD_BLOCK_ROWS = 1024


def _sgd_kernel(scalars_ref, param_ref, vel_ref, grad_ref, out_p_ref,
                out_v_ref, *, momentum, weight_decay, l1_vs_l2):
    lr, inv_batch = scalars_ref[0], scalars_ref[1]
    g = grad_ref[:] * inv_batch
    if weight_decay:
        p = param_ref[:]
        decay = l1_vs_l2 * jnp.sign(p) + (1.0 - l1_vs_l2) * p
        g = g + weight_decay * decay
    v = momentum * vel_ref[:] - lr * g
    out_v_ref[:] = v
    out_p_ref[:] = param_ref[:] + v


def fused_sgd_update(param, velocity, grad, batch_size, learning_rate,
                     momentum=0.0, weight_decay=0.0, l1_vs_l2=0.0,
                     interpret=None):
    """Momentum-SGD update as ONE Pallas kernel (param, velocity in, new
    param, velocity out — single VMEM round trip).

    Matches ``functional.sgd_update`` (without clipping) bit-for-bit in
    fp32; ``batch_size`` and ``learning_rate`` may be traced scalars.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    shape = param.shape
    n = param.size
    # (rows, 128) layout, rows padded to the (8, 128) fp32 tile and
    # walked by a row grid: one whole-array VMEM block per operand does
    # not fit (64 MiB each at AlexNet's 4096x4096 fc7)
    lanes = 128
    rows = -(-n // (8 * lanes)) * 8
    pad = rows * lanes - n
    block_rows = min(_SGD_BLOCK_ROWS, rows)

    def prep(a):
        a = a.reshape(-1)
        if pad:
            a = jnp.concatenate([a, jnp.zeros(pad, a.dtype)])
        return a.reshape(rows, lanes)

    inv_batch = 1.0 / jnp.maximum(batch_size, 1).astype(param.dtype)
    kernel = functools.partial(
        _sgd_kernel, momentum=momentum, weight_decay=weight_decay,
        l1_vs_l2=l1_vs_l2)
    scalars = jnp.stack([jnp.asarray(learning_rate, param.dtype),
                         inv_batch])
    block = pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))
    new_p, new_v = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, block_rows),),
        out_shape=(jax.ShapeDtypeStruct((rows, lanes), param.dtype),
                   jax.ShapeDtypeStruct((rows, lanes), param.dtype)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  block, block, block],
        out_specs=(block, block),
        interpret=_interpret(interpret),
    )(scalars, prep(param), prep(velocity), prep(grad))
    return (new_p.reshape(-1)[:n].reshape(shape),
            new_v.reshape(-1)[:n].reshape(shape))


# --------------------------------------------------------------- fused LRN
# AlexNet cross-channel LRN is the top memory-bound item left in the
# round-4 trace once convs go bf16 (docs/PERF.md: LRN fwd+bwd chains run
# at ~350-460 GB/s because XLA's loop fusions re-read the activation
# across the shifted-slice window sum).  One Pallas pass instead: read x
# once, take the channel-window sum as a BANDED MATMUL on the MXU
# (x² @ band, band[i,j] = |i-j| <= n//2 — a (C, C) 0/1 matrix), apply
# the power elementwise, write y (+ the denominator, which the fused
# backward reuses: dx = dy·d^-β − 2(α/n)β·x·((dy·x·d^(−β−1)) @ band)).


def _lrn_band(c, n, dtype=jnp.float32):
    """band[j, i] = 1 iff channel j is in i's window — defined to match
    the XLA path EXACTLY: pad (n//2, n//2) + n shifted slices puts
    window(i) = [i - n//2, i + n - 1 - n//2], which is asymmetric for
    even n (symmetric |i-j| <= n//2 would silently change numerics
    under set_lrn_backend).  The backward uses band.T (sum over j with
    i in window(j))."""
    j, i = jnp.meshgrid(jnp.arange(c), jnp.arange(c), indexing="ij")
    off = j - i + n // 2
    return ((off >= 0) & (off < n)).astype(dtype)


def _lrn_fwd_kernel(x_ref, band_ref, y_ref, d_ref, *, alpha_n, beta, k):
    x = x_ref[:]
    s = jnp.dot(x * x, band_ref[:],
                preferred_element_type=jnp.float32)
    d = k + alpha_n * s
    d_ref[:] = d
    y_ref[:] = x * d ** -beta


def _lrn_bwd_kernel(x_ref, d_ref, dy_ref, band_ref, dx_ref, *,
                    alpha_n, beta):
    x, d, dy = x_ref[:], d_ref[:], dy_ref[:]
    dpow = d ** (-beta - 1.0)
    inner = jnp.dot(dy * x * dpow, band_ref[:],
                    preferred_element_type=jnp.float32)
    dx_ref[:] = dy * (d * dpow) - (2.0 * alpha_n * beta) * x * inner


def _lrn_call(kernel, arrays, band, out_n, block_rows=1024,
              interpret=None, pad_values=None):
    """Shared grid/padding plumbing: arrays are (M, C) operands; the
    channel dim pads to the 128-lane tile, rows pad to the block.
    ``pad_values`` gives the fill per operand — the denominator must pad
    with 1.0, not 0.0, or its negative power is inf in the pad region
    (inf·0 = NaN poisons nothing numerically but trips debug checks)."""
    from jax.experimental import pallas as pl

    m, c = arrays[0].shape
    lanes = -(-c // 128) * 128
    rows = -(-m // block_rows) * block_rows
    if pad_values is None:
        pad_values = [0.0] * len(arrays)

    def prep(a, fill):
        return jnp.pad(a, ((0, rows - m), (0, lanes - c)),
                       constant_values=fill)

    band_p = jnp.pad(band, ((0, lanes - c), (0, lanes - c)))
    grid = (rows // block_rows,)
    block = pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))
    whole = pl.BlockSpec((lanes, lanes), lambda i: (0, 0))
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        out_shape=tuple(jax.ShapeDtypeStruct((rows, lanes), jnp.float32)
                        for _ in range(out_n)),
        in_specs=[block] * len(arrays) + [whole],
        out_specs=tuple(block for _ in range(out_n)),
        interpret=_interpret(interpret),
    )(*[prep(a, f) for a, f in zip(arrays, pad_values)], band_p)
    return tuple(o[:m, :c] for o in outs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def lrn_forward(x, alpha=1e-4, beta=0.75, n=5, k=2.0, interpret=None):
    """Cross-channel LRN as one fused Pallas pass (same semantics as
    ``functional.lrn_forward``; ref: veles/znicz/normalization.py [H]).
    Differentiable via a fused custom VJP — the backward is one kernel,
    not XLA's re-derived slice chain."""
    y, _ = _lrn_fwd(x, alpha, beta, n, k, interpret)
    return y


def _lrn_fwd(x, alpha, beta, n, k, interpret):
    shape = x.shape
    c = shape[-1]
    x2 = x.reshape(-1, c).astype(jnp.float32)
    kern = functools.partial(_lrn_fwd_kernel, alpha_n=alpha / n,
                             beta=beta, k=k)
    y, d = _lrn_call(kern, [x2], _lrn_band(c, n), 2,
                     interpret=interpret)
    # residuals must be jax types only (shape/dtype are recovered from
    # the cotangent in the backward)
    return y.reshape(shape).astype(x.dtype), (x2, d)


def _lrn_fwd_vjp(x, alpha, beta, n, k, interpret):
    y, res = _lrn_fwd(x, alpha, beta, n, k, interpret)
    return y, res


def _lrn_bwd_vjp(alpha, beta, n, k, interpret, res, dy):
    x2, d = res
    shape, dtype = dy.shape, dy.dtype
    c = x2.shape[-1]
    dy2 = dy.reshape(-1, c).astype(jnp.float32)
    kern = functools.partial(_lrn_bwd_kernel, alpha_n=alpha / n,
                             beta=beta)
    (dx,) = _lrn_call(kern, [x2, d, dy2], _lrn_band(c, n).T, 1,
                      interpret=interpret, pad_values=[0.0, 1.0, 0.0])
    return (dx.reshape(shape).astype(dtype),)


lrn_forward.defvjp(_lrn_fwd_vjp, _lrn_bwd_vjp)


# -------------------------------------------------- dropout with counter RNG
def _dropout_kernel(seed_ref, x_ref, out_ref, *, keep_threshold_i32,
                    inv_keep):
    from jax.experimental.pallas import tpu as pltpu
    pltpu.prng_seed(seed_ref[0])
    bits = pltpu.prng_random_bits(x_ref.shape)
    # bits are SIGNED int32, uniform over the full range — compare in the
    # signed domain (threshold = keep*2^32 - 2^31) so the keep fraction is
    # keep_prob, not the unsigned-domain misread that made rate<=0.5 a no-op
    keep = bits < keep_threshold_i32
    out_ref[:] = jnp.where(keep, x_ref[:] * inv_keep, 0.0)


def dropout(x, seed, rate, interpret=None):
    """Inverted dropout with the in-kernel counter PRNG.

    ``seed`` is an int32 scalar (derive per step/layer on the host); the
    mask is a pure function of (seed, shape), so backward replays it by
    re-running with the same seed — the reference's stored-mask scheme
    without storing anything.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if rate <= 0.0:
        return x
    keep_prob = 1.0 - rate
    if _interpret(interpret):
        # the TPU PRNG primitives (prng_seed/prng_random_bits) have no CPU
        # lowering even in interpret mode; off-TPU the same (seed, shape) →
        # mask contract is served by threefry.  Masks differ ACROSS
        # backends (both are counter-based and deterministic per backend).
        key = jax.random.PRNGKey(seed)
        mask = jax.random.bernoulli(key, keep_prob, x.shape)
        return jnp.where(mask, x / keep_prob, 0.0).astype(x.dtype)
    shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    lanes = 128
    rows = -(-n // lanes)
    pad = rows * lanes - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros(pad, x.dtype)])
    x2 = flat.reshape(rows, lanes)
    threshold = min(int(round(keep_prob * 2.0 ** 32)) - 2 ** 31,
                    2 ** 31 - 1)
    kernel = functools.partial(
        _dropout_kernel,
        keep_threshold_i32=threshold,
        inv_keep=float(1.0 / keep_prob))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, lanes), x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=_interpret(interpret),
    )(jnp.asarray([seed], jnp.int32), x2)
    return out.reshape(-1)[:n].reshape(shape)


# ------------------------------------------------ paged flash attention
# The serving hot loop (ISSUE 7).  Shared geometry: the KV pool is
# (n_pages, kv_heads, page, head_dim), a lane's page table row maps its
# linear positions [0, m·page) onto pool pages, and queries arrive as
# (b, heads, c, head_dim) — c = 1 (decode), k+1 (speculative verify) or
# the prefill chunk.  Grouped-query attention folds into the kernel by
# reshaping the h = kv·g query heads to (kv, g·c) rows per kv head, so
# the scores matmul runs once per kv head with no repeated K/V — query
# row r serves chunk offset r % c.
#
# A pool ROW may pack several kv heads (``pool_pack``).  A Mosaic operand
# lies row-major with its minor axis tiled to the chip's 128 lanes.  The
# chip's own layout for an array whose minor axis is narrower is another
# (``f32[321,32,32,64]`` lies pages-minor-most), so a pool of head size
# 64 handed to these kernels was converted whole on the way in and back
# on the way out of every dispatch — and the converted copy padded each
# 64-wide row to 128 lanes, so the kernels read twice the bytes.  A pool
# whose rows are lane-wide lies the way the kernels read it: r =
# ``pool_pack(kv_heads, head_dim)`` heads side by side in one row, pool
# (n_pages, kv_heads/r, page, r·head_dim), head k·r+e of a position in
# lanes [e·dh, (e+1)·dh) of row k (``pack_heads``) — the same bytes as
# the plain pool, none padded.  The kernels need no lane slicing for
# it: the queries of a row's r heads are stacked as r·g·c query ROWS,
# each zero outside its own head's lanes (``_pack_queries``), so one
# matmul over the full row gives every head its own scores (the other
# heads' lanes meet exact zeros), and P·V yields r·dh lanes per query
# row of which the wrapper keeps the head's own (``_unpack_outputs``).
# r = 1 is the plain pool, and every helper is the identity there.


def pool_pack(kv_heads, head_dim):
    """How many kv heads share one row of the serving kernels' pool: as
    many as fill the chip's 128 lanes (2 for a head size of 64), as far
    as ``kv_heads`` divides; 1 where the head size does not divide 128
    (128 and wider included)."""
    if 128 % head_dim:
        return 1
    return math.gcd(kv_heads, 128 // head_dim)


def pack_heads(x, r):
    """(..., kv, t, dh) -> (..., kv/r, t, r·dh): the pool's row packing
    applied to K or V rows (or to a whole plain pool)."""
    if r == 1:
        return x
    kv, t, dh = x.shape[-3:]
    x = x.reshape(x.shape[:-3] + (kv // r, r, t, dh))
    return jnp.swapaxes(x, -3, -2).reshape(
        x.shape[:-4] + (kv // r, t, r * dh))


def _pack_queries(qg, r):
    """(b, kv, gc, dh) -> (b, kv/r, r·gc, r·dh): head e of a pack in
    query rows [e·gc, (e+1)·gc), its values in lanes [e·dh, (e+1)·dh)
    and exact zeros in the others."""
    if r == 1:
        return qg
    b, kv, gc, dh = qg.shape
    eye = jnp.eye(r, dtype=qg.dtype)[:, None, :, None]
    return (qg.reshape(b, kv // r, r, gc, 1, dh) * eye).reshape(
        b, kv // r, r * gc, r * dh)


def _unpack_outputs(o, r):
    """(b, kv/r, r·gc, r·dh) -> (b, kv, gc, dh): each query row keeps
    its own head's lanes."""
    if r == 1:
        return o
    b, kvp, rows, lanes = o.shape
    o = o.reshape(b, kvp, r, rows // r, r, lanes // r)
    return jnp.stack([o[:, :, e, :, e] for e in range(r)],
                     axis=2).reshape(b, kvp * r, rows // r, lanes // r)


def _flash_step(q, k, v, live, dh, acc_ref, l_ref, m_ref, scale=None):
    """One online-softmax accumulation against a K/V block — the
    ``attention._online_update`` recurrence on kernel refs; ``dh`` is
    the head size the scores scale by (a packed row is wider), unless
    ``scale`` gives the factor itself (latent attention's).  NEG_INF
    masking (finite) keeps fully-masked blocks harmless: their
    transient terms rescale to exactly 0.0 (fp32 exp underflow) once a
    live block arrives, the same argument ``blockwise_attention``
    documents."""
    # the XLA twin's matmuls follow functional's precision policy (fp32
    # HIGHEST by default); Mosaic's own default runs fp32 operands
    # through bf16 passes, 2e-3 off at head size 128 on the chip
    precision = F._PRECISION if q.dtype == jnp.float32 else None
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            precision=precision,
                            preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.float32(dh)) if scale is None else s * scale
    s = s + jnp.where(live, 0.0, NEG_INF)[None]
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[..., None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        precision=precision, preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _band(k_pos, q_pos, window, sinks, base):
    """The ``chunk_live_mask`` band on in-kernel position grids:
    ``base`` gives the causal half (decode: k <= q; prefill history:
    k < frontier), window/sinks compose exactly as ``band_bias``."""
    live = base
    if window:
        in_w = k_pos > q_pos - window
        if sinks:
            in_w |= k_pos < sinks
        live &= in_w
    return live


def live_pages(pos, c, page, m_pages, window=None, sinks=0, xp=jnp):
    """The pages of a lane's table that hold a key some query row may see:
    ``(first, last, sink)``.  Page ``j`` of ``m_pages`` is LIVE if and only
    if ``j <= last and (j >= first or j < sink)``; for every other page
    ``_band`` is false on every (query row, key) pair, so the kernels
    neither fetch it nor run a softmax step on it.

    ``c`` query rows at ``pos .. pos + c - 1`` see keys ``k <= q`` (decode
    and verify); ``c = 0`` is the prefill kernel's history, the keys
    strictly below the chunk's frontier ``pos`` (no key at all at
    ``pos == 0``: ``last`` is then -1).  Either way the last live page is
    the one position ``pos + c - 1`` lies in, and with a window the first
    is the page of ``pos - window + 1``, the lower edge of the FIRST query
    row (later rows see no further back), clipped at 0.  The ``sink``
    pages at the table's head (a static count) stay live through the
    window's sinks.  ``last`` is clipped to the table, and a range may be
    empty (``first > last``).

    ``pos`` is table-relative like the kernels' own: the lanes' traced
    positions in the kernels' wrappers, a numpy array on the host
    (``xp=numpy``: the engine's count of page steps)."""
    last = xp.minimum((pos + c + page - 1) // page, m_pages) - 1
    if not window:
        return xp.zeros_like(last), last, 0
    first = xp.maximum(pos - window + 1, 0) // page
    return first, last, -(-sinks // page)


def sink_pages_apart(first, last, sink, xp=numpy):
    """How many of a lane's live pages are sink pages that lie before
    ``first`` (``live_pages``), per lane."""
    return xp.minimum(xp.minimum(sink, first), last + 1)


def live_page_count(first, last, sink, xp=numpy):
    """How many pages ``live_pages`` calls live, per lane (numpy: the
    host's side of the rule ``_is_live`` applies in the kernels)."""
    return (xp.maximum(last - first + 1, 0)
            + sink_pages_apart(first, last, sink, xp))


def _is_live(j, first, last, sink):
    """Is page ``j`` of a lane's table live (``live_pages``; ``first`` and
    ``last`` the lane's own, scalars)."""
    live = (j >= first) & (j <= last)
    return live | ((j < sink) & (j <= last)) if sink else live


def _live_entry(j, first, last, sink):
    """The table entry whose page grid step ``j`` of a lane names: its own
    if the page is live, else the nearest live one's (entry 0 for a lane
    with none).  A dead step so names the block a neighbouring live step
    names, and the pipeline issues no copy for it."""
    if sink:
        first = jnp.where(j < sink, 0, first)
    return jnp.maximum(jnp.minimum(jnp.maximum(j, first), last), 0)


#: bytes of keys and values one step of the flash-decode kernel's walk
#: copies and multiplies: as many whole pages as fit (``flash_block_pages``).
#: Chosen on the chip at the three cells' shapes (``tools/
#: flash_decode_sweep.py``; PERF.md section 6, PR 43; us a call, the gridded
#: parent, then the walk at 1 | 2 | 4 | 8 | 16 pages a block, then its
#: copies alone): ``opt-1.3b.chat`` (8 lanes x 16 packed heads x 2 rows in
#: float32, pages of 0.5 MB, 92 live) 125, 78 | 67 | 69 | 71 | 84, 65;
#: ``trinity-large-ep8``'s short table (32 lanes, pages of 1 MB, 289 live)
#: 470, 402 | 405 | 407 | 412, 402 and its full one (332 live) 581, 462 |
#: 464 | 467 | 473, 461; ``qwen3-next-80b-a3b-ep4`` (64 lanes, pages of 2
#: MB, 315 live) 1079, 876 | 879 | 882, 875.  The bfloat16 shapes run at
#: what their copies take at any block (755 GB/s), and a larger block only
#: adds the call's first copy and the slots' zeroing, which nothing hides.
#: On the float32 shape the matmuls cost nearly what the copies do (two
#: query rows a packed head at HIGHEST: 0.5-0.7 us a 32-token page, its
#: copy 0.64), a step costs 0.25 us whatever it holds and a lane's short
#: last block multiplies its stale rows too: two to four pages run at the
#: copies' time + 2-4 us (1 MB would do as well there; 4 MB read 64 where
#: 2 MB read 57 on shallower lanes)
_FLASH_BLOCK_BYTES = 2 << 20
#: blocks whose copies are in flight before the block that is multiplied
#: (with one, ``opt-1.3b.chat``'s call took 72.8 us where two took 68.6)
_FLASH_AHEAD = 2
_FLASH_VMEM = 48 << 20


def flash_block_pages(pool_shape, itemsize, m_pages):
    """The pages a step of :func:`paged_flash_decode`'s walk takes: what
    ``_FLASH_BLOCK_BYTES`` holds of one page's keys and values (a pool
    ``(n_pages, kv/r, page, r·dh)`` of ``itemsize``-byte numbers), at least
    one, at most the table's ``m_pages``.  The kernel and the host's count
    of its blocks (``flash_walk_blocks``) read the same rule."""
    page_bytes = 2 * math.prod(pool_shape[1:]) * itemsize
    return max(1, min(_FLASH_BLOCK_BYTES // page_bytes, m_pages))


def flash_walk_blocks(live, block_pages):
    """The blocks :func:`paged_flash_decode` walks for lanes of ``live``
    live pages each (``live_page_count``): whole blocks of ``block_pages``,
    the last may be short, and a lane with no live page still takes one
    (integer arrays, ``jax.numpy`` in the kernel's wrapper and ``numpy`` on
    the host)."""
    return ((live + block_pages - 1) // block_pages).clip(1)


def paged_flash_decode(q, k_pool, v_pool, ptab, pos, window=None,
                       sinks=0, interpret=None):
    """Flash-decode over the paged KV pool: ``c`` query positions per
    lane (already projected, rotated and GQA-shaped — (b, h, c, dh))
    attend their lane's linear cache view THROUGH the page table, masked
    by the ``chunk_live_mask`` band.

    The pool must already hold the lane's rows for positions
    [0, pos+c) — the caller ``paged_write``s the c new rows first (c
    row-sized update slices; the kernel eliminates the L-row gather,
    which is the asymmetry that matters).  The pool is (n_pages, kv/r,
    page, r·dh), r heads to a row (``pool_pack``; the section's head
    says how the kernel reads it) — r is read off its last axis, and a
    plain (n_pages, kv, page, dh) pool is r = 1.  Numerically the
    online-softmax result of ``blockwise_attention`` — equal to the
    XLA ``mha_paged_chunk_step`` path to fp32 roundoff (the greedy
    argmax downstream is what the serving parity matrix pins).

    The grid is the lanes; the kernel WALKS a lane's live pages itself
    (ISSUE 43, as :func:`paged_latent_decode` walks its rows): the pools
    stay where they lie, and a loop of the lane's own length goes over its
    live table entries in order (``live_pages``: the sink pages before
    ``first``, then ``first .. last``), ``flash_block_pages`` of them a
    block: one softmax step a block, one copy a page and pool into one of
    three slots of fast memory, the copies two blocks ahead of the block
    that is multiplied and across the lanes' edges (a lane's last steps
    start the next lanes' first blocks).  A table entry no query row of
    its lane can see (beyond the frontier, behind the window) costs
    nothing: no grid step, no scalar read, no copy; what it holds never
    reaches the kernel.  A lane's last block may be short: the rest of its
    slot holds an earlier block's rows (the slots are zeroed once a call,
    for a masked NaN would still poison the sums) and is masked.

    Returns (b, h, c, dh)."""
    b, h, c, dh = q.shape
    kvp, page, lanes = k_pool.shape[1:]
    r = lanes // dh
    m_pages = ptab.shape[1]
    g = h // (kvp * r)
    rows = r * g * c            # query rows per pool row
    qp = _pack_queries(q.reshape(b, kvp * r, g * c, dh), r)
    per = flash_block_pages(k_pool.shape, k_pool.dtype.itemsize, m_pages)

    # the lanes' live pages, computed once a call in the program around
    # the kernel and prefetched: how many, how many of them are sink pages
    # that lie apart from the rest and how far, the blocks they make, and
    # where a lane's first block stands in the call's walk
    pos = jnp.asarray(pos, jnp.int32)
    first, last, sink = live_pages(pos, c, page, m_pages, window, sinks)
    apart = sink_pages_apart(first, last, sink, jnp)
    count = live_page_count(first, last, sink, jnp)
    walk = flash_walk_blocks(count, per)
    begin = jnp.cumsum(walk) - walk
    call = _flash_walk_call(
        b, kvp, rows, lanes, page, per, c, dh, window, sinks, _FLASH_AHEAD,
        q.dtype, k_pool.dtype, _interpret(interpret), _flash_step,
        F._PRECISION)
    o = call(jnp.asarray(ptab, jnp.int32), pos, count, walk, begin, apart,
             first - apart, qp, k_pool, v_pool)
    return _unpack_outputs(o, r).reshape(b, h, c, dh)


@functools.lru_cache(maxsize=64)
def _flash_walk_call(b, kvp, rows, lanes, page, per, c, dh, window, sinks,
                     ahead, q_dtype, pool_dtype, interpret, flash_step,
                     precision):
    """:func:`paged_flash_decode`'s Pallas call for ``b`` lanes of ``rows``
    query rows a pool row (``c`` positions a lane) over pools ``(n_pages,
    kvp, page, lanes)``, ``per`` pages a block, the copies ``ahead`` blocks
    ahead: a function of the seven prefetched scalars, the packed queries
    and the two pools.  KEPT for every set of sizes: a ``pallas_call`` is a
    jitted function that inlines, so the layers of a program trace and
    lower the kernel ONCE a table width and not once each (that time is
    set-up time, and the walk's body traces slower than a grid step's
    did), and each call still takes its own place's name in the device
    trace.  ``flash_step`` and ``precision`` are what the trace reads
    beside the sizes (``_flash_step`` and the matmul policy it looks
    up)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    block = per * page
    slots = ahead + 1

    def kernel(ptab_ref, pos_ref, count_ref, walk_ref, begin_ref, apart_ref,
               skip_ref, q_ref, k_ref, v_ref, o_ref, k_buf, v_buf, sem_ref,
               acc_ref, l_ref, m_ref):
        i = pl.program_id(0)

        # (``jax.lax`` on the scalars, not ``jax.numpy``'s operators: every
        # one of those is a jitted function traced and lowered anew for
        # every layer of every program, and that time is set-up time)
        add, mul, lt, rem, select = (jax.lax.add, jax.lax.mul, jax.lax.lt,
                                     jax.lax.rem, jax.lax.select)

        def copies(lane, t, slot, do):
            """``do`` with the copy of each page of block ``t`` of
            ``lane``'s walk into ``slot``, keys then values."""
            def one(n, carry):
                entry = n
                if window:
                    entry = select(lt(n, apart_ref[lane]), n,
                                   add(n, skip_ref[lane]))
                at = pl.ds(pl.multiple_of(
                    mul(jax.lax.sub(n, mul(t, per)), page), page), page)
                for e, (pool_ref, buf_ref) in enumerate(
                        ((k_ref, k_buf), (v_ref, v_buf))):
                    do(pltpu.make_async_copy(
                        pool_ref.at[ptab_ref[lane, entry]],
                        buf_ref.at[slot, :, at], sem_ref.at[e, slot]))
                return carry

            jax.lax.fori_loop(
                mul(t, per),
                jax.lax.min(mul(add(t, 1), per), count_ref[lane]), one, 0)

        def after(lane, t):
            """The block behind block ``t`` of ``lane`` in the call's walk:
            the lane's next, or the next lane's first (lane ``b``: none)."""
            more = lt(add(t, 1), walk_ref[jax.lax.min(lane, b - 1)])
            return (select(more, lane, add(lane, 1)),
                    select(more, add(t, 1), mul(t, 0)))

        if per > 1:
            @pl.when(i == 0)
            def _():
                k_buf[...] = jnp.zeros_like(k_buf)
                v_buf[...] = jnp.zeros_like(v_buf)

        acc_ref[...] = jnp.zeros_like(acc_ref)
        l_ref[...] = jnp.zeros_like(l_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)

        def step(t, carry):
            # the call's blocks take the slots in turn: block g of the
            # whole walk lies in slot g % slots, and its copies start
            # ``ahead`` steps before it is multiplied (into the slot the
            # step before this one read)
            g = add(begin_ref[i], t)
            lane, far = i, t
            for _ in range(ahead):
                lane, far = after(lane, far)

            @pl.when(lt(lane, b))
            def _():
                copies(lane, far, rem(add(g, slots + ahead), slots),
                       lambda copy: copy.start())

            # (the first lane's walk begins ``ahead`` steps early: those
            # only start the call's first blocks)
            @pl.when(jax.lax.ge(t, 0))
            def _():
                slot = rem(g, slots)
                copies(i, t, slot, lambda copy: copy.wait())
                # where each row of the block stands among the rows of
                # the lane's live pages, and from that in its positions
                at = add(jax.lax.broadcasted_iota(
                    jnp.int32, (rows, block), 1), mul(t, block))
                k_pos = at
                if window:
                    k_pos = select(lt(at, mul(apart_ref[i], page)), at,
                                   add(at, mul(skip_ref[i], page)))
                q_pos = add(rem(jax.lax.broadcasted_iota(
                    jnp.int32, (rows, block), 0), c), pos_ref[i])
                live = _band(k_pos, q_pos, window, sinks, jax.lax.bitwise_and(
                    jax.lax.le(k_pos, q_pos),
                    lt(at, mul(count_ref[i], page))))
                flash_step(q_ref[0], k_buf[slot], v_buf[slot], live, dh,
                           acc_ref, l_ref, m_ref)
            return carry

        jax.lax.fori_loop(select(i == 0, -ahead, 0), walk_ref[i], step, 0)
        o_ref[0] = (acc_ref[...] / l_ref[...][..., None]).astype(o_ref.dtype)

    def lane(i, *_):
        return (i, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, kvp, rows, lanes), lane),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, kvp, rows, lanes), lane),
        scratch_shapes=[pltpu.VMEM((slots, kvp, block, lanes), pool_dtype),
                        pltpu.VMEM((slots, kvp, block, lanes), pool_dtype),
                        pltpu.SemaphoreType.DMA((2, slots)),
                        pltpu.VMEM((kvp, rows, lanes), jnp.float32),
                        pltpu.VMEM((kvp, rows), jnp.float32),
                        pltpu.VMEM((kvp, rows), jnp.float32)],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvp, rows, lanes), q_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_FLASH_VMEM),
        interpret=interpret)


#: float32 scores (kv heads x query rows x page) that one grid step of the
#: prefill kernel may hold: with their exponentials, the accumulator and
#: the double-buffered operands this keeps a step under the 16 MiB that
#: the chip's compiler gives a kernel
_SCORES_BYTES = 2 << 20


#: float32 scores (query rows x page) of one grid step where ONE kv head's
#: query rows are cut into blocks (``_query_rows``), and the memory such a
#: kernel is given
_Q_BLOCK_BYTES = 4 << 20
_Q_BLOCK_VMEM = 64 << 20


def _query_rows(rows, page):
    """The query rows one grid step takes of a kv head that alone overruns
    ``_SCORES_BYTES``: the largest divisor of ``rows`` in whole sublane
    tiles whose scores stay under ``_Q_BLOCK_BYTES`` (16 query heads of 256
    on 2 kv heads over a 1024-token chunk are 8192 query rows a kv head, 32
    MiB of scores a page: taken 1024 rows, one query head, at a time)."""
    for qr in range(rows, 7, -1):
        if rows % qr == 0 and qr % 8 == 0 \
                and qr * page * 4 <= _Q_BLOCK_BYTES:
            return qr
    return rows


def _heads_per_step(kvp, rows, page):
    """The (packed) kv heads one grid step of the prefill kernel takes:
    all of them while their scores stay under ``_SCORES_BYTES``, else the
    largest divisor of ``kvp`` that does (at least one)."""
    for hb in range(kvp, 0, -1):
        if kvp % hb == 0 and hb * rows * page * 4 <= _SCORES_BYTES:
            return hb
    return 1


def paged_flash_prefill(q, k_new, v_new, k_pool, v_pool, ptab, pos,
                        window=None, sinks=0, interpret=None):
    """Fused chunked-prefill attention: one page-aligned chunk of
    ``c == page`` positions per lane attends the paged history (streamed
    page-per-grid-step like :func:`paged_flash_decode`, masked strictly
    below the chunk frontier) PLUS the chunk's own K/V — which arrive
    as VMEM operands and are accumulated intra-causally in the
    epilogue, never written-then-gathered through HBM.  The same
    epilogue installs them into the lane's pool page through ALIASED
    outputs: the ``paged_write`` row install is part of this program,
    not a separate write.  The pool's rows may pack r heads
    as :func:`paged_flash_decode` says; ``k_new``/``v_new`` arrive
    plain, (b, kv, c, dh), and are packed here.

    A grid step takes all the pool's (packed) kv heads while their scores
    fit the kernel's memory (``_heads_per_step``), else a block of them:
    the grid then has a head-block axis between the lane's and the
    page's (6 query heads of 128 over a 256-token chunk are 1536 query
    rows per kv head, 1.5 MB of float32 scores a head and page).  Where one
    kv head's query rows alone are too many, a query-block axis stands
    before the page's (``_query_rows``): each block streams the history for
    itself, and all of them install the same chunk rows.

    Of the table's pages only the live history (``live_pages`` with no
    query row: below the frontier, inside the window) is fetched and
    stepped over, as in :func:`paged_flash_decode`; the first and the
    last step of a lane (the accumulator's reset; the chunk's own block,
    the output and the install) run whatever its depth.

    Caller contract (the engine's chunk program guarantees both):
    ``pos`` is page-aligned and the chunk occupies exactly the pool
    page ``ptab[i, pos // page]`` — a fresh, unshared page (COW has
    already run).  Returns (o (b, h, c, dh), k_pool, v_pool) with the
    chunk installed."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, c, dh = q.shape
    kvp, page, lanes = k_pool.shape[1:]
    if c != page:
        raise ValueError("prefill kernel needs chunk (%d) == page (%d)"
                         % (c, page))
    r = lanes // dh
    m_pages = ptab.shape[1]
    g = h // (kvp * r)
    rows = r * g * c            # query rows per pool row
    qp = _pack_queries(q.reshape(b, kvp * r, g * c, dh), r)
    hb = _heads_per_step(kvp, rows, page)
    grid = (b, m_pages) if hb == kvp else (b, kvp // hb, m_pages)
    # one kv head's rows may still be too many: a query-block axis then
    # stands before the page's, and a block's history is streamed anew
    qr = (_query_rows(rows, page)
          if hb == 1 and rows * page * 4 > _SCORES_BYTES else rows)
    if qr != rows:
        grid = grid[:-1] + (rows // qr, m_pages)

    first, last, sink = live_pages(jnp.asarray(pos, jnp.int32), 0, page,
                                   m_pages, window, sinks)

    def kernel(ptab_ref, pos_ref, first_ref, last_ref, q_ref, kn_ref,
               vn_ref, k_ref, v_ref, o_ref, ko_ref, vo_ref, acc_ref, l_ref,
               m_ref):
        i, j = pl.program_id(0), pl.program_id(len(grid) - 1)

        @pl.when(j == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            l_ref[...] = jnp.zeros_like(l_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)

        pos = pos_ref[i]

        # history page j: live strictly below the chunk frontier (the
        # chunk's own page sits in the pool UNWRITTEN — its rows come
        # from the VMEM operands in the epilogue)
        first_row = pl.program_id(len(grid) - 2) * qr if qr != rows else 0

        def chunk_rows(width):
            # the chunk offset each query row of this step serves
            at = jax.lax.broadcasted_iota(jnp.int32, (qr, width), 0)
            return (at + first_row if qr != rows else at) % c

        @pl.when(_is_live(j, first_ref[i], last_ref[i], sink))
        def _():
            q_rows = chunk_rows(page)
            k_pos = j * page + jax.lax.broadcasted_iota(
                jnp.int32, (qr, page), 1)
            live = _band(k_pos, pos + q_rows, window, sinks, k_pos < pos)
            _flash_step(q_ref[0], k_ref[0], v_ref[0], live, dh,
                        acc_ref, l_ref, m_ref)

        @pl.when(j == m_pages - 1)
        def _():
            # the chunk block: intra-chunk causal over the VMEM K/V
            k_pos_new = pos + jax.lax.broadcasted_iota(
                jnp.int32, (qr, c), 1)
            q_pos = pos + chunk_rows(c)
            live_new = _band(k_pos_new, q_pos, window, sinks,
                             k_pos_new <= q_pos)
            _flash_step(q_ref[0], kn_ref[0], vn_ref[0], live_new, dh,
                        acc_ref, l_ref, m_ref)
            o_ref[0] = (acc_ref[...]
                        / l_ref[...][..., None]).astype(o_ref.dtype)
            # fused install: the chunk's rows land in the lane's page
            ko_ref[0] = kn_ref[0]
            vo_ref[0] = vn_ref[0]

    # index maps over (lane, [head block,] page, page table, positions,
    # first and last live page)
    def head_block(idx):
        return idx[1] if hb != kvp else 0

    def lane(*idx):
        return (idx[0], head_block(idx), 0, 0)

    def queries(*idx):
        return (idx[0], head_block(idx),
                idx[len(grid) - 2] if qr != rows else 0, 0)

    def history(*idx):
        (i, j), (pt, _, fs, ls) = (idx[0], idx[-5]), idx[-4:]
        return (pt[i, _live_entry(j, fs[i], ls[i], sink)],
                head_block(idx), 0, 0)

    def tgt(*idx):
        i, pt, ps = idx[0], idx[-4], idx[-3]
        return (pt[i, ps[i] // page], head_block(idx), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, hb, qr, lanes), queries),
            pl.BlockSpec((1, hb, c, lanes), lane),
            pl.BlockSpec((1, hb, c, lanes), lane),
            pl.BlockSpec((1, hb, page, lanes), history),
            pl.BlockSpec((1, hb, page, lanes), history),
        ],
        out_specs=(
            pl.BlockSpec((1, hb, qr, lanes), queries),
            pl.BlockSpec((1, hb, page, lanes), tgt),
            pl.BlockSpec((1, hb, page, lanes), tgt),
        ),
        scratch_shapes=[pltpu.VMEM((hb, qr, lanes), jnp.float32),
                        pltpu.VMEM((hb, qr), jnp.float32),
                        pltpu.VMEM((hb, qr), jnp.float32)],
    )
    more = ({} if qr == rows else {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=_Q_BLOCK_VMEM)})
    o, k_out, v_out = pl.pallas_call(
        kernel, grid_spec=grid_spec, **more,
        out_shape=(jax.ShapeDtypeStruct((b, kvp, rows, lanes), q.dtype),
                   jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)),
        # aliased in-place pool update: operand indices INCLUDE the four
        # scalar-prefetch args, so k_pool/v_pool are operands 7/8
        input_output_aliases={7: 1, 8: 2},
        interpret=_interpret(interpret),
    )(jnp.asarray(ptab, jnp.int32), jnp.asarray(pos, jnp.int32),
      first, last, qp, pack_heads(k_new, r), pack_heads(v_new, r), k_pool,
      v_pool)
    return _unpack_outputs(o, r).reshape(b, h, c, dh), k_out, v_out


# ------------------------------------------------ paged latent attention
# Latent attention (ops/latent.py) keeps ONE row a token a layer, shared by
# every head: pool (n_pages, 1, page, row), row = [c_kv (kv_rank) | k_rope |
# zeros] padded to whole 128-lane tiles.  The two kernels are the two orders
# of the same sums: the decode kernel attends in the ABSORBED form (the
# query rows arrive ``row`` wide and meet a cached row lane for lane; the
# values ARE the cached rows, so a page is fetched once and serves scores
# and accumulation alike), the prefill kernel in the EXPANDED form (it
# rebuilds a page's keys and values through ``W_kvb`` in fast memory, a
# block of heads at a time).  The prefill kernel walks the page table on
# its grid and skips dead pages as the kernels above do (``live_pages``);
# within a grid step its work is ordered for the matrix unit (ISSUE 44): the
# passes it makes, re-expansion and the row's zero half included, take 5.44
# us a head and page of 1024 x 1024 at the chip's peak, the softmax's vector
# work 1.4-2.6 us beside them, and in a rolled loop of one 256-row block
# after another the call took 7.39: a key or value tile stood in the matrix
# unit for 256 rows only, and no block's matmuls could run under another's
# softmax.  Now a unit is the whole chunk against the whole page and a loop
# body holds two heads' units, scores first: 5.85 us, where its matmuls
# alone take 5.52.  The decode kernel's grid is the lanes alone and the
# walk is a loop inside it, as long as the lane is deep, over blocks it
# copies itself (ISSUE 41:
# on the grid every table entry paid a grid step, 0.06 us a dead one and
# 2.4 us a live one whose copy takes 1.8; the loop's copies follow one
# another through the whole call and the call takes what they take).


#: cached rows one step of the absorbed kernel's walk copies and multiplies:
#: a page, or a whole fraction of one (a page that is no multiple of it is
#: walked whole: ``_latent_block``).  Chosen on the chip at both cells'
#: shapes (``tools/latent_decode_sweep.py``; PERF.md section 6, PR 41; us a
#: call at 1024 | 512 | 256 rows): 32 lanes x 64 query rows over 110,611
#: cached rows 212 | 254 | 417, 16 lanes x 32 rows over 337,025 rows 600 |
#: 599 | 1054.  At 1024 the call takes what its copies alone take (211 |
#: 598 us: 745 GB/s); under it ``_flash_step``'s fixed part a block (0.4-0.6
#: us: the running maximum and sum change layout between rows and lanes)
#: costs as much as (16 x 32 query rows) or more than (32 x 64) the rows of
#: the frontier's page that are then neither copied nor multiplied
_LATENT_BLOCK = 1024


def _latent_block(page):
    """The rows a step of :func:`paged_latent_decode`'s walk takes of a
    ``page``-row page."""
    return _LATENT_BLOCK if page % _LATENT_BLOCK == 0 else page


def paged_latent_decode(q, pool, ptab, pos, scale, interpret=None):
    """Absorbed latent attention over the paged latent pool: ``c`` query
    rows a head a lane, q (b, h, c, row) = ``[q_nope W_kvb[K,h]^T | q_rope
    | 0]``, against the lane's cached rows through its table.  ``s = q .
    row * scale`` (the zero lanes meet zeros), causal online softmax in
    float32, ``o = sum p row``: the pool must already hold the rows of
    positions [0, pos + c).

    The grid is the lanes; the kernel WALKS a lane's rows itself: the pool
    stays where it lies, and a loop of the lane's own length,
    ``ceil((pos + c) / block)`` blocks (``_latent_block``), copies block
    after block into one of three slots of fast memory, fetched ONCE for
    scores and values and all ``h`` heads.  The copies run two blocks ahead
    of the block that is multiplied, across the lanes' edges: a lane's last
    steps start the NEXT lanes' first blocks, so the copies follow one
    another without a gap while a lane ends and the next begins (with one
    block ahead the call took a tenth longer: the copies bound it), and
    only the call's first copy is waited for with nothing to do.  A table
    entry no query row of the lane sees, and the rows of the frontier's
    page behind the frontier's block, cost nothing: no grid step, no copy,
    no scalar read.  Returns (b, h, c, row): lanes [:kv_rank] are
    ``o_lat``, the rest is of no use."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, c, row = q.shape
    page = pool.shape[2]
    block = _latent_block(page)
    per_page = page // block
    ahead = 2                   # blocks in flight before the one multiplied
    slots = ahead + 1
    rows = h * c
    qp = q.reshape(b, 1, rows, row)
    pos = jnp.asarray(pos, jnp.int32)
    # the blocks each lane walks (``live_pages``' last page, in blocks:
    # at least one, at most its table) and where its first stands in the
    # call's walk, computed once a call in the program around the kernel
    # and prefetched (``paged_flash_decode`` says why)
    walk = jnp.minimum((pos + c + block - 1) // block,
                       ptab.shape[1] * per_page)
    first = jnp.cumsum(walk) - walk

    def kernel(ptab_ref, pos_ref, walk_ref, first_ref, q_ref, pool_ref,
               o_ref, buf_ref, sem_ref, acc_ref, l_ref, m_ref):
        i = pl.program_id(0)

        # (``jax.lax`` on the scalars, not ``jax.numpy``: every operator of
        # the latter is a jitted function traced and lowered anew for every
        # layer of every program, and that time is set-up time)
        rem, select = jax.lax.rem, jax.lax.select

        def copy(lane, t, slot):
            """Block ``t`` of ``lane``'s walk into ``slot``."""
            entry, at = (t, 0) if per_page == 1 else (
                jax.lax.div(t, per_page), rem(t, per_page) * block)
            return pltpu.make_async_copy(
                pool_ref.at[ptab_ref[lane, entry], 0, pl.ds(at, block)],
                buf_ref.at[slot], sem_ref.at[slot])

        def after(lane, t):
            """The block behind block ``t`` of ``lane`` in the call's walk:
            the lane's next, or the next lane's first (lane ``b``: none)."""
            more = t + 1 < walk_ref[jax.lax.min(lane, b - 1)]
            return select(more, lane, lane + 1), select(more, t + 1, 0 * t)

        def start(lane, t, slot):
            @pl.when(lane < b)
            def _():
                copy(lane, t, slot).start()

        # the call's blocks take the slots in turn: block g of the whole
        # walk lies in slot g % slots
        @pl.when(i == 0)
        def _():
            copy(0, 0, 0).start()
            lane, t = 0, 0
            for slot in range(1, ahead):
                lane, t = after(lane, t)
                start(lane, t, slot)

        acc_ref[...] = jnp.zeros_like(acc_ref)
        l_ref[...] = jnp.zeros_like(l_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)

        def step(t, carry):
            g = first_ref[i] + t
            lane, far = i, t
            for _ in range(ahead):
                lane, far = after(lane, far)
            # (into the slot the step before this one read)
            start(lane, far, rem(g + ahead, slots))
            slot = rem(g, slots)
            copy(i, t, slot).wait()
            k_pos = t * block + jax.lax.broadcasted_iota(
                jnp.int32, (rows, block), 1)
            q_pos = pos_ref[i] + rem(jax.lax.broadcasted_iota(
                jnp.int32, (rows, block), 0), c)
            rows_k = buf_ref[slot][None]
            _flash_step(q_ref[0], rows_k, rows_k, k_pos <= q_pos, row,
                        acc_ref, l_ref, m_ref, scale=scale)
            return carry

        jax.lax.fori_loop(0, walk_ref[i], step, 0)
        o_ref[0] = (acc_ref[...] / l_ref[...][..., None]).astype(o_ref.dtype)

    def lane(i, *_):
        return (i, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, 1, rows, row), lane),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 1, rows, row), lane),
        scratch_shapes=[pltpu.VMEM((slots, block, row), pool.dtype),
                        pltpu.SemaphoreType.DMA((slots,)),
                        pltpu.VMEM((1, rows, row), jnp.float32),
                        pltpu.VMEM((1, rows), jnp.float32),
                        pltpu.VMEM((1, rows), jnp.float32)],
    )
    o = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, rows, row), q.dtype),
        interpret=_interpret(interpret),
    )(jnp.asarray(ptab, jnp.int32), pos, walk, first, qp, pool)
    return o.reshape(b, h, c, row)


#: heads one grid step of the latent prefill kernel takes (their
#: accumulators, queries and outputs stay in fast memory while the lane's
#: pages stream past: a page is fetched heads / this many times); the query
#: rows one softmax update takes on a HISTORY page (a *unit*: float32 scores
#: of rows x page; every expanded key and value tile then serves that many
#: rows while it stays in the matrix unit); the units whose sums do not meet
#: that one loop body holds, the score matmuls of all of them standing
#: before the first's softmax (two halves of a head's rows, or two heads
#: where a unit is the whole chunk); and the rows of a block of the chunk's
#: OWN page, which takes only the keys up to its diagonal.  Chosen on the
#: chip at the cells' one shape (1 lane x 32 heads x 1024 rows in bfloat16,
#: pages of 1024 x 640, a table of 33; ``tools/latent_prefill_sweep.py``;
#: PERF.md section 6, PR 44), us a head and history page (the slope between
#: the chunk at page 4 and at page 31; the floor of the passes is 5.44):
#:
#:     rows a unit      256    512    1024
#:     one a body       7.39   6.73   6.41      (256 | 1: the parent's order)
#:     two a body       6.22   5.96   5.85      (256 rows, four a body: 5.88)
#:
#: matmuls alone 6.19 at 256 | 1 and 5.52 at 1024 | 2, softmax alone 2.56 and
#: 1.43.  Both halves gain from the larger unit, the chains hide what is left
#: of the softmax, and the two together leave 0.33 us over the matmuls alone.
#: 2 | 4 | 8 heads a step are one speed a page (5.84 | 5.85 | 5.85) and differ
#: by the dead steps and first fetches of a call alone (a chunk at page 0:
#: 220 | 194 | 182 us; at page 16: 3249 | 3209 | 3182): 4 stays, at half the
#: fast memory of 8.  The own page's block (128 | 256 | 512) moves nothing.
_LATENT_HEADS = 4
_LATENT_Q_ROWS = 1024
_LATENT_CHAINS = 2
_LATENT_DIAGONAL_ROWS = 256
#: fast memory the latent prefill kernel may take (the chip's default
#: gives a kernel 16 MiB of 128; at the cells' shape it compiles from 24 MiB
#: on: ``tools/aot_compile.py latent``)
_LATENT_VMEM = 48 << 20


def _latent_dot(x, y, transposed, precision):
    """``x (m, k) . y (k, n)``, or ``. y (n, k)^T`` where ``transposed``,
    summed in float32: the latent prefill kernel's every matmul."""
    return jax.lax.dot_general(
        x, y, (((1,), (1 if transposed else 0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)


def _latent_softmax(s, m_prev):
    """One online-softmax step's vector work on float32 scores ``s (rows,
    keys)`` under the running maximum ``m_prev (rows, 1)``: the new
    maximum, the factor the old sums shrink by, the exponentials and their
    row sums."""
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    return m_new, jnp.exp(m_prev - m_new), p, p.sum(axis=-1, keepdims=True)


def paged_latent_prefill(q_nope, q_rope, wk, wv, pool, ptab, pos, scale,
                         kv_rank, interpret=None):
    """Expanded latent attention of one page-aligned chunk of ``c == page``
    positions a lane over the paged latent pool, the chunk's own rows
    included: THE POOL ALREADY HOLDS THEM (the caller writes the chunk's
    page first; the table's entry ``pos // page`` is that page).

    q_nope (b, h, c, nope), q_rope (b, h, c, rope) rotated; wk (h, kv_rank,
    nope), wv (h, kv_rank, v): ``W_kvb`` by head; pool (n_pages, 1, page,
    row) with ``row - kv_rank`` a whole number of lane tiles that hold
    ``[k_rope | zeros]``.  The grid is (lane, head block, page).  A step
    on a live page rebuilds the block's keys and values from the page's
    latents (``c_kv W_k[h]``, ``c_kv W_v[h]``: the re-expansion, once a
    cached token a head a chunk) and runs the online softmax, ``s =
    (q_nope . k_nope + q_rope . k_rope) * scale`` in float32.  History
    pages lie wholly below the chunk's frontier and need no mask; the
    chunk's own page (the last live one) is causal, and a block of query
    rows there takes only the keys up to its own diagonal (``pos`` is
    page-aligned, so which those are is static).  Dead pages (behind the
    chunk) cost neither a fetch nor a step.

    THE ORDER OF A STEP'S WORK (ISSUE 44; ``_latent_prefill_call`` holds
    the body): on a history page a unit of ``_LATENT_Q_ROWS`` query rows
    meets the whole page at once, so a key or value tile that stands in the
    matrix unit serves that many rows before the next is loaded; and a loop
    body holds ``_LATENT_CHAINS`` units whose sums do not meet, every
    unit's score matmuls written before the first unit's softmax, so that
    the vector units' work on one unit (maximum, exponent, sum, cast) has
    another unit's matmuls to run under.  Returns (b, h, c, v)."""
    b, h, c, nope = q_nope.shape
    rope = q_rope.shape[-1]
    page, row = pool.shape[2:]
    if c != page:
        raise ValueError("prefill kernel needs chunk (%d) == page (%d)"
                         % (c, page))
    tail = row - kv_rank
    dtype = q_nope.dtype
    # the queries' rotated part beside zeros, as wide as the row's tail
    q = jnp.concatenate(
        [q_nope, q_rope,
         jnp.zeros((b, h, c, tail - rope), dtype)], axis=-1)
    _, last, _ = live_pages(jnp.asarray(pos, jnp.int32), c, page,
                            ptab.shape[1])
    call = _latent_prefill_call(
        b, h, c, nope, row, kv_rank, wv.shape[-1], ptab.shape[1],
        math.gcd(h, _LATENT_HEADS), math.gcd(c, _LATENT_Q_ROWS),
        _LATENT_CHAINS, math.gcd(c, _LATENT_DIAGONAL_ROWS), _LATENT_VMEM,
        float(scale), jnp.dtype(dtype),
        F._PRECISION if dtype == jnp.float32 else None,
        _interpret(interpret), _latent_dot, _latent_softmax)
    return call(jnp.asarray(ptab, jnp.int32), jnp.asarray(pos, jnp.int32),
                last, q, wk, wv, pool)


@functools.lru_cache(maxsize=64)
def _latent_prefill_call(b, h, c, nope, row, kv_rank, vdim, m_pages, hb, qb,
                         chains, db, vmem, scale, dtype, precision,
                         interpret, dot, softmax):
    """:func:`paged_latent_prefill`'s Pallas call for ``b`` lanes of ``h``
    heads over a table of ``m_pages`` pages of ``c`` rows ``row`` wide:
    ``hb`` heads a grid step, units of ``qb`` query rows on a history page,
    ``chains`` of them a loop body, blocks of ``db`` rows on the chunk's own
    page.  A function of the three prefetched scalars, the queries ``[nope |
    tail]``, the two weights and the pool.  KEPT for every set of sizes, as
    ``_flash_walk_call`` is and for its reason: the latent layers of a chunk
    program trace and lower the body ONCE, which is what pays for a body
    that holds several units (that time is set-up time).  ``dot`` and
    ``softmax`` are what the trace reads beside the sizes (``_latent_dot``,
    ``_latent_softmax``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    blocks = c // qb
    # the units of a loop body: blocks of one head's rows, or heads where a
    # head has one block
    by_rows = math.gcd(blocks, chains) if blocks > 1 else 1
    by_heads = math.gcd(hb, chains) if blocks == 1 else 1

    def loop(n, body):
        """``body(t)`` for t in [0, n): a loop that is not unrolled (every
        unrolled body is compiled again), or the one call."""
        if n == 1:
            body(0)
        else:
            jax.lax.fori_loop(0, n, lambda t, _: body(t) or 0, 0)

    def kernel(ptab_ref, pos_ref, last_ref, q_ref, wk_ref, wv_ref, pool_ref,
               o_ref, acc_ref, l_ref, m_ref):
        i, j = pl.program_id(0), pl.program_id(2)

        @pl.when(j == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            l_ref[...] = jnp.zeros_like(l_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)

        def expand(e):
            lat = pool_ref[0, 0]
            c_kv = lat[:, :kv_rank]
            k_nope = dot(c_kv, wk_ref[e], False, precision).astype(dtype)
            v = dot(c_kv, wv_ref[e], False, precision).astype(dtype)
            return k_nope, lat[:, kv_rank:], v

        def scores(e, rows, k_nope, k_tail):
            qn = q_ref[0, e, rows, :nope]
            qt = q_ref[0, e, rows, nope:]
            return (dot(qn, k_nope, True, precision)
                    + dot(qt, k_tail, True, precision)) * scale

        def update(e, rows, s, v):
            """The online-softmax recurrence on the query rows ``rows`` (a
            slice) of head ``e`` against scores ``s`` (rows, keys) and
            values ``v``."""
            m_new, alpha, p, p_sum = softmax(s, m_ref[e, rows, :])
            l_ref[e, rows, :] = l_ref[e, rows, :] * alpha + p_sum
            acc_ref[e, rows, :] = acc_ref[e, rows, :] * alpha \
                + dot(p.astype(v.dtype), v, False, precision)
            m_ref[e, rows, :] = m_new

        def chained(units):
            """``units`` of (head, rows, k_nope, k_tail, v, mask | None):
            every unit's scores, then every unit's softmax and ``p . v``."""
            ss = []
            for e, rows, k_nope, k_tail, v, mask in units:
                s = scores(e, rows, k_nope, k_tail)
                ss.append(s if mask is None else jnp.where(mask, s, NEG_INF))
            for (e, rows, _, _, v, _), s in zip(units, ss):
                update(e, rows, s, v)

        @pl.when(j < last_ref[i])
        def _():           # a history page: every key visible to every row
            if blocks == 1:
                everything = pl.ds(0, c)

                def heads(g):
                    # (the second head's expansion, matrix-unit work alone,
                    # also stands before the first head's softmax)
                    chained([(e, everything) + expand(e) + (None,)
                             for e in (g * by_heads + n
                                       for n in range(by_heads))])
                loop(hb // by_heads, heads)
            else:
                def head(e):
                    kv = expand(e)

                    def group(g):
                        chained([(e, pl.ds(pl.multiple_of(
                            (g * by_rows + n) * qb, qb), qb)) + kv + (None,)
                            for n in range(by_rows)])
                    loop(blocks // by_rows, group)
                loop(hb, head)

        @pl.when(j == last_ref[i])
        def _():           # the chunk's own page: causal
            own = math.gcd(c // db, chains)

            def head(e):
                k_nope, k_tail, v = expand(e)
                for lo in range(0, c, own * db):
                    units = []
                    for at in range(lo, lo + own * db, db):
                        keys = at + db      # up to this block's diagonal
                        k_pos = jax.lax.broadcasted_iota(
                            jnp.int32, (db, keys), 1)
                        q_pos = at + jax.lax.broadcasted_iota(
                            jnp.int32, (db, keys), 0)
                        units.append((e, pl.ds(at, db), k_nope[:keys],
                                      k_tail[:keys], v[:keys],
                                      k_pos <= q_pos))
                    chained(units)
            loop(hb, head)

        @pl.when(j == m_pages - 1)
        def _():
            o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

    def lane(i, g, j, *_):
        return (i, g, 0, 0)

    def weights(i, g, j, *_):
        return (g, 0, 0)

    def history(i, g, j, pt, ps, ls):
        return (pt[i, jnp.maximum(jnp.minimum(j, ls[i]), 0)], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, h // hb, m_pages),
        in_specs=[pl.BlockSpec((1, hb, c, nope + row - kv_rank), lane),
                  pl.BlockSpec((hb, kv_rank, nope), weights),
                  pl.BlockSpec((hb, kv_rank, vdim), weights),
                  pl.BlockSpec((1, 1, c, row), history)],
        out_specs=pl.BlockSpec((1, hb, c, vdim), lane),
        scratch_shapes=[pltpu.VMEM((hb, c, vdim), jnp.float32),
                        pltpu.VMEM((hb, c, 1), jnp.float32),
                        pltpu.VMEM((hb, c, 1), jnp.float32)],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, c, vdim), dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        interpret=interpret)


def serving_kernels_supported(n_heads, kv_heads, head_dim, page, tp=0):
    """(ok, reason) — can the serving attention kernels carry this
    engine geometry?  The checks are STRUCTURAL (what the kernels
    cannot express), not platform: platform routing (TPU vs interpret
    vs fallback) is the engine's decision.  ``tp >= 2`` (a
    tensor-parallel serving mesh, ISSUE 8) is structural too: a
    pallas_call is a single-device program and the KV pool is
    head-sharded across the mesh, so TP-sharded engines serve through
    the XLA path (GSPMD shards the gather + softmax like any other
    op), metered as fallbacks exactly like the off-TPU case."""
    if tp and tp >= 2:
        return False, ("tensor-parallel mesh (tp=%d): the Pallas "
                       "serving kernels are single-device programs; "
                       "the XLA path serves sharded decode" % tp)
    if n_heads % kv_heads:
        return False, ("n_heads %d not divisible by kv_heads %d"
                       % (n_heads, kv_heads))
    if page < 1 or head_dim < 1:
        return False, "degenerate geometry"
    return True, None


def paged_row_write(pool, new, page_ids, offsets, interpret=None):
    """Write row ``new[i]`` (kv/r, r·dh) of every kv head into pool page
    ``page_ids[i]`` at offset ``offsets[i]``, in place: the decode step's
    K/V install for MANY lanes as one kernel.

    ``attention.paged_write`` writes each row with an update slice of its
    own, which is right for a few lanes; a chain of 32 of them on each of
    ten pools took the chip's compiler two minutes a program (six programs
    a ladder; PERF.md section 6, PR 28).  Here the grid walks the rows; a
    step reads the aligned tile of ``32 / itemsize`` pool rows that holds
    its target (the smallest block the chip's tiling allows), replaces the
    one row, and writes the tile back through the aliased output.

    NO TWO ROWS OF A CALL MAY LIE IN ONE TILE OF A LIVE PAGE.  On the chip
    the grid is pipelined: a step's tile is fetched before the step
    before it has written its own back, and a tile whose block index
    repeats is not fetched again, so of several rows in one tile only the
    last would survive (interpret mode runs the steps in order and cannot
    show it).  The caller (``attention.paged_write``) therefore comes here
    with ONE row a lane: lanes own distinct pages.  Free lanes parked on
    the scratch page may share a tile; nothing attends what they write.

    pool: (n_pages, kv/r, page, r·dh); new: (n, kv/r, r·dh); page_ids,
    offsets: (n,) int32.  Returns the pool."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, kvp, lanes = new.shape
    tile = 32 // pool.dtype.itemsize

    def kernel(pid_ref, off_ref, new_ref, pool_ref, out_ref):
        row = off_ref[pl.program_id(0)] % tile
        rows = jax.lax.broadcasted_iota(jnp.int32, (kvp, tile, lanes), 1)
        fresh = jnp.broadcast_to(new_ref[0][:, None, :], (kvp, tile, lanes))
        out_ref[0] = jnp.where(rows == row, fresh, pool_ref[0])

    def target(i, pid, off):
        return (pid[i], 0, off[i] // tile, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, kvp, lanes), lambda i, pid, off: (i, 0, 0)),
                  pl.BlockSpec((1, kvp, tile, lanes), target)],
        out_specs=pl.BlockSpec((1, kvp, tile, lanes), target),
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # operand indices include the two scalar-prefetch arguments
        input_output_aliases={3: 0},
        interpret=_interpret(interpret),
    )(jnp.asarray(page_ids, jnp.int32), jnp.asarray(offsets, jnp.int32),
      new, pool)


# ------------------------------------------------------- the grouped matmul
# ``ops/moe.py::grouped_matmul`` over MANY rows (a prompt chunk's
# assignments).  The compiler's ``ragged-dot`` streams an expert's matrices
# at 77 % of the memory's speed when a group holds a row or two and at 35 %
# when it holds 64 (PERF.md section 6, PR 35); here a group's matrix is one
# block that stays in fast memory for all the row tiles that meet the group,
# and the next group's streams in meanwhile.

#: rows of one tile: the MXU's height, so a visit of a tile costs what its
#: weight block costs to push through the array and no more
_GMM_ROWS = 128
#: the largest weight block (bytes), held twice: the output columns are cut
#: so that ``k x tn`` stays under it
_GMM_BLOCK = 8 << 20
_GMM_VMEM = 48 << 20


def _gmm_columns(k, n, itemsize):
    """The widest cut of ``n`` output columns in whole 128-lane tiles whose
    ``k x tn`` block fits ``_GMM_BLOCK`` (``n`` itself where no such cut
    divides it)."""
    fits = [tn for tn in range(128, n + 1, 128)
            if n % tn == 0 and k * tn * itemsize <= _GMM_BLOCK]
    return max(fits) if fits else n


def grouped_visits(sizes, m, tm):
    """The walk of a grouped matmul over ``m`` rows sorted by group, in row
    tiles of ``tm``: one VISIT for every (tile, group) pair that shares a
    row, tiles in order and within a tile groups in order.  Returns int32
    ``(group of visit, tile of visit, offsets, [visits])``: the first two
    ``cdiv(m, tm) + groups - 1`` long (what the walk can need), entries
    behind the last visit repeat it; ``offsets[g]`` is group ``g``'s first
    row and ``offsets[-1]`` the first row behind the last group.  A group
    without rows has no visit, a tile behind the last group neither."""
    groups = sizes.shape[0]
    steps = -(-m // tm) + groups - 1
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    n_visits = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(n_visits)                     # visits of groups <= g
    total = upto[-1]
    v = jnp.minimum(jnp.arange(steps, dtype=jnp.int32),
                    jnp.maximum(total - 1, 0))
    group = jnp.minimum(jnp.searchsorted(upto, v, side="right"),
                        groups - 1).astype(jnp.int32)
    tile = first[group] + v - (upto - n_visits)[group]
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return group, tile.astype(jnp.int32), offsets, total.reshape(1)


def grouped_matmul(xs, w, sizes, tm=_GMM_ROWS, interpret=None):
    """Row block *g* of ``xs`` (m, k) (``sizes[g]`` rows, in order) times
    ``w[g]`` (k, n): (m, n) in ``xs``'s dtype, every product accumulated in
    float32 over the WHOLE contraction and rounded once.  Rows behind the
    last group are never touched and come back as they may (not even
    finite); no row of a group is dropped, whatever its size.

    The grid is (column cuts, visits): a visit (:func:`grouped_visits`) is
    one row tile against one group's ``(k, tn)`` block under the mask of
    the group's rows; a tile that straddles groups is visited once a group
    and keeps its output block in fast memory between them.  Consecutive
    visits of one group name the same weight block, which is then not
    fetched again; the next group's block streams while this one's rows
    are on the MXU.  So the matrix of a group with a row is read ONCE per
    column cut, that of a group without never (the steps behind the last
    visit repeat its blocks and compute nothing)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = xs.shape
    n = w.shape[2]
    tm = min(tm, m)
    tn = _gmm_columns(k, n, w.dtype.itemsize)
    group, tile, offsets, total = grouped_visits(sizes, m, tm)

    def kernel(group_ref, tile_ref, off_ref, total_ref, x_ref, w_ref,
               o_ref):
        v = pl.program_id(1)

        @pl.when(v < total_ref[0])
        def _():
            g = group_ref[v]
            rows = tile_ref[v] * tm + jax.lax.broadcasted_iota(
                jnp.int32, (tm, 1), 0)
            mine = (rows >= off_ref[g]) & (rows < off_ref[g + 1])
            y = jnp.dot(x_ref[...], w_ref[0],
                        preferred_element_type=jnp.float32)
            o_ref[...] = jnp.where(
                mine, y, o_ref[...].astype(jnp.float32)).astype(o_ref.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n // tn, group.shape[0]),
        in_specs=[
            pl.BlockSpec((tm, k), lambda j, v, gs, ts, *_: (ts[v], 0)),
            pl.BlockSpec((1, k, tn), lambda j, v, gs, *_: (gs[v], 0, j))],
        out_specs=pl.BlockSpec((tm, tn),
                               lambda j, v, gs, ts, *_: (ts[v], j)),
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_GMM_VMEM),
        interpret=_interpret(interpret),
    )(group, tile, offsets, total, xs, w)


# ------------------------------------------------------ the gated delta rule
# ``ops/linear_attn.py``'s two orders on the lanes' recurrent state
# ``(slots, heads, k_dim, v_dim)`` float32, taken aliased in and out: a call
# touches the slots it is told to and no other, and what it does not touch
# keeps its bytes.  The state-space rule (the same recurrence without the
# delta correction) steps through ``gdn_decode`` with ``correct`` false and
# has a chunk kernel of its own, ``ssd_chunk``: its heads share their keys
# and queries by group, which the delta rule's never do.

_GDN_VMEM = 48 << 20


def gdn_decode(state, q, k, v, beta, g, active, correct=True,
               interpret=None):
    """The recurrent rule for ONE row a lane, on the lanes that decode:
    state (b, h, dk, dv); q, k (b, h, dk); v (b, h, dv); beta (b, h); g (b,
    h), one decay a head, or (b, h, dk), one a key channel (a row of ``S``),
    all float32; ``active`` (b,) bool.  Per lane and head ``S <- exp(g) S;
    d = beta (v - S^T k); S <- S + k d^T; o = S^T q``
    (``linear_attn.recurrent_step``, sum for sum).  Returns (o (b, h, dv),
    zeros for a lane that is not active; the state).

    ``correct`` false (static) is the state-space rule: ``d = beta v``.  Its
    state lies PACKED, ``r`` heads side by side in a row (state (b, h / r,
    dk, r dv); ``r`` read off the shapes), and q, k come one a group (b,
    groups, dk): the ``r`` heads of a row share them, so the row is decayed,
    updated and summed as ONE head of ``r dv`` values whose decay and
    ``beta`` differ by column.

    The grid walks the ACTIVE lanes first (their indices prefetched, in
    order) and a step takes one lane's whole state, 2 MiB at 32 heads of
    128 x 128, through fast memory once; the steps behind the last active
    lane name its blocks again and compute nothing, so a lane that is
    prefilling or empty costs neither a copy nor a write, and its state
    keeps its bits.  With no lane active the first step hands lane 0's
    state back as it came."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, dk, dv = state.shape
    active = jnp.asarray(active, bool)
    n = active.sum().astype(jnp.int32)
    # active lanes first, in order; behind them the last active lane again
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    ids = order[jnp.minimum(jnp.arange(b, dtype=jnp.int32),
                            jnp.maximum(n - 1, 0))]
    # k and q as columns (k_dim on sublanes, a head a lane), v and the
    # scalars of a head as rows; a decay per channel is a column too
    per_channel = g.ndim == 3
    if q.shape[1] != h:
        q, k = (jnp.repeat(y, h // y.shape[1], axis=1) for y in (q, k))
    cols = [jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2)]
    rows = [v, jnp.broadcast_to(beta[..., None], v.shape)]
    if per_channel:
        cols.append(jnp.swapaxes(jnp.exp(g), 1, 2))
    else:
        rows.insert(1, jnp.broadcast_to(jnp.exp(g)[..., None], v.shape))
    cols = jnp.stack(cols, axis=1)
    # (the heads of a packed row lie side by side, as their values do)
    rows = jnp.stack(rows, axis=1).reshape(b, len(rows), h, dv)
    n_cols, n_rows = cols.shape[1], rows.shape[1]

    def kernel(ids_ref, n_ref, s_ref, c_ref, r_ref, o_ref, so_ref):
        i = pl.program_id(0)

        @pl.when(i < n_ref[0])
        def _():
            for e in range(h):
                qc = c_ref[0, 0, :, e:e + 1]                  # (dk, 1)
                kc = c_ref[0, 1, :, e:e + 1]
                ve = r_ref[0, 0, e:e + 1, :]                  # (1, dv)
                s = s_ref[0, e] * (c_ref[0, 2, :, e:e + 1] if per_channel
                                   else r_ref[0, 1, e:e + 1, :])
                beta_e = r_ref[0, n_rows - 1, e:e + 1, :]
                if correct:
                    ve = ve - (s * kc).sum(axis=0, keepdims=True)
                s = s + kc * (beta_e * ve)
                so_ref[0, e] = s
                o_ref[0, e:e + 1, :] = (s * qc).sum(axis=0, keepdims=True)

        @pl.when((n_ref[0] == 0) & (i == 0))
        def _():
            so_ref[...] = s_ref[...]
            o_ref[...] = jnp.zeros_like(o_ref)

    def lane4(i, ids, n):
        return (ids[i], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, h, dk, dv), lane4),
                  pl.BlockSpec((1, n_cols, dk, h), lane4),
                  pl.BlockSpec((1, n_rows, h, dv), lane4)],
        out_specs=(pl.BlockSpec((1, h, dv), lambda i, ids, n: (ids[i], 0, 0)),
                   pl.BlockSpec((1, h, dk, dv), lane4)),
    )
    o, state = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((b, h, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        # operand indices include the two scalar-prefetch arguments
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_GDN_VMEM),
        interpret=_interpret(interpret),
    )(ids, n.reshape(1), state, cols, rows)
    return jnp.where(active[:, None, None], o, 0.0).reshape(v.shape), state


def gdn_chunk(state, slots, fresh, w, u, qg, att, kdt, decay,
              interpret=None):
    """The sequential pass of the chunked rule over the inner chunks
    (``linear_attn.chunk_pass``): per lane and head, from the lane's state
    ``S`` (zeros where ``fresh``), for each inner chunk in order ``V' = U -
    W S; O = Qg S + Att V'; S <- decay S + KdT V'``, the state (k_dim x
    v_dim, float32) in fast memory from the first inner chunk to the last.

    state (slots, h, dk, dv); ``slots`` (b,) int32 the lanes' slots;
    ``fresh`` (b,) bool; w, qg (b, h, n, C, dk); u (b, h, n, C, dv); att
    (b, h, n, C, C); kdt (b, h, n, dk, C); decay (b, h, n), or (b, h, n, dk)
    where it is one a key channel (a row of ``S``): what
    ``linear_attn.chunk_terms`` returns.  Returns (O (b, h, n, C, dv), the
    state with the lanes' slots rewritten, in place)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, n, c, dk = w.shape
    dv = u.shape[-1]
    per_channel = decay.ndim == 4
    if per_channel:
        decay = decay[..., None, :]                       # (b, h, n, 1, dk)
    else:
        decay = jnp.broadcast_to(decay[..., None, None], (b, h, n, 1, dv))

    def dot(x, y):
        return jnp.dot(x, y, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)

    def kernel(slot_ref, fresh_ref, s_ref, w_ref, u_ref, q_ref, a_ref,
               k_ref, d_ref, o_ref, so_ref, acc_ref):
        t = pl.program_id(2)
        old = fresh_ref[pl.program_id(0)] == 0

        @pl.when(t == 0)
        def _():
            acc_ref[...] = jnp.where(old, s_ref[0, 0], 0.0)

        s = acc_ref[...]
        vp = u_ref[0, 0, 0] - dot(w_ref[0, 0, 0], s)
        o_ref[0, 0, 0] = dot(q_ref[0, 0, 0], s) + dot(a_ref[0, 0, 0], vp)
        d = d_ref[0, 0, 0]
        if per_channel:
            # the row (1, dk) as a column (dk, 1): its diagonal's row sums
            at = [jax.lax.broadcasted_iota(jnp.int32, (dk, dk), axis)
                  for axis in (0, 1)]
            d = jnp.where(at[0] == at[1], d, 0.0).sum(axis=1, keepdims=True)
        s = d * s + dot(k_ref[0, 0, 0], vp)
        acc_ref[...] = s

        @pl.when(t == n - 1)
        def _():
            so_ref[0, 0] = s

    def slot(i, j, t, sl, fr):
        return (sl[i], j, 0, 0)

    def inner(i, j, t, *_):
        return (i, j, t, 0, 0)

    def block(*shape):
        return pl.BlockSpec((1, 1, 1) + shape, inner)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h, n),
        in_specs=[pl.BlockSpec((1, 1, dk, dv), slot),
                  block(c, dk), block(c, dv), block(c, dk), block(c, c),
                  block(dk, c), block(1, decay.shape[-1])],
        out_specs=(block(c, dv), pl.BlockSpec((1, 1, dk, dv), slot)),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((b, h, n, c, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_GDN_VMEM),
        interpret=_interpret(interpret),
    )(jnp.asarray(slots, jnp.int32), jnp.asarray(fresh, jnp.int32),
      state, w, u, qg, att, kdt, decay)


def ssd_chunk(state, slots, fresh, q, k, v, beta, g, chunk, interpret=None):
    """The chunked state-space rule (``linear_attn``: the rule without the
    delta correction) over inner chunks of ``chunk`` rows, on the PACKED
    state: state (slots, h / r, dk, r dv) float32, ``r`` heads of a group
    side by side in a row; q, k (b, L, groups, dk): a group's ``C`` and
    ``B``; v (b, L, h, dv); beta (``dt``) and g (b, L, h), all float32, L a
    multiple of ``chunk``; ``slots`` (b,) the lanes' slots, ``fresh`` (b,)
    bool (the slot's state is read as zeros).  Per lane and row of heads,
    for each inner chunk in order, with ``gam`` the running sum of ``g`` in
    the chunk and ``gam_Q`` its last::

        O = exp(gam) (C S) + tril((C B^T) exp(gam_i - gam_j)) (beta V)
        S <- exp(gam_Q) S + B^T (exp(gam_Q - gam) beta V)

    (``linear_attn.chunk_terms`` / ``chunk_pass`` with ``correct`` false, sum
    for sum but that the decay to the chunk's end multiplies ``V`` where
    they scale ``B``).  ``C B^T`` and ``B^T`` are made once a chunk for all
    the heads of a group; a grid step takes one row of ``r`` heads through
    one inner chunk, the row's state (dk, r dv) in fast memory from the
    first inner chunk to the last: ``C S`` and the state's update are one
    matmul each for the ``r`` heads, the pairwise decays (which differ by
    head) one each.  Returns (O (b, L, h, dv), the state with the lanes'
    slots rewritten, in place)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, length, h, dv = v.shape
    groups, dk = q.shape[2:]
    packs = state.shape[1]
    r, n = h // packs, length // chunk
    wide = r * dv
    hi = jax.lax.Precision.HIGHEST

    def by_group(y):                    # (b, L, G, dk) -> (b, G, n, C, dk)
        return jnp.moveaxis(y.reshape(b, n, chunk, groups, dk), 3, 1)

    qn, kn = by_group(q), by_group(k)
    cb = jnp.einsum("bgnik,bgnjk->bgnij", qn, kn, precision=hi)
    kt = jnp.swapaxes(kn, -1, -2)                           # (b, G, n, dk, C)
    gam = jnp.cumsum(g.reshape(b, n, chunk, packs, r), axis=2)
    gcol = jnp.moveaxis(gam, 3, 1)                       # (b, packs, n, C, r)
    grow = jnp.swapaxes(gcol, -1, -2)                    # (b, packs, n, r, C)
    u = (beta[..., None] * v).reshape(b, length, h * dv)

    def dot(x, y):
        return jnp.dot(x, y, precision=hi,
                       preferred_element_type=jnp.float32)

    def kernel(slot_ref, fresh_ref, s_ref, q_ref, kt_ref, cb_ref, u_ref,
               gc_ref, gr_ref, o_ref, so_ref, acc_ref):
        t = pl.program_id(2)
        old = fresh_ref[pl.program_id(0)] == 0

        @pl.when(t == 0)
        def _():
            acc_ref[...] = jnp.where(old, s_ref[0, 0], 0.0)

        s = acc_ref[...]
        gc, gr = gc_ref[0, 0, 0], gr_ref[0, 0, 0]        # (C, r), (r, C)
        ub, cbm = u_ref[0], cb_ref[0, 0, 0]
        head = jax.lax.broadcasted_iota(jnp.int32, (1, wide), 1) // dv

        def lanes(col):                 # (rows, r) -> (rows, r dv)
            out = col[:, 0:1]
            for a in range(1, r):
                out = jnp.where(head == a, col[:, a:a + 1], out)
            return out

        low = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) \
            >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        o = lanes(jnp.exp(gc)) * dot(q_ref[0, 0, 0], s)
        for a in range(r):
            pair = jnp.exp(jnp.where(
                low, gc[:, a:a + 1] - gr[a:a + 1, :], -jnp.inf))
            o = o + dot(cbm * pair,
                        ub if r == 1 else jnp.where(head == a, ub, 0.0))
        o_ref[0] = o
        last = gc[chunk - 1:chunk, :]
        s = lanes(jnp.exp(last)) * s \
            + dot(kt_ref[0, 0, 0], ub * lanes(jnp.exp(last - gc)))
        acc_ref[...] = s

        @pl.when(t == n - 1)
        def _():
            so_ref[0, 0] = s

    per_group = packs // groups

    def slot(i, j, t, sl, fr):
        return (sl[i], j, 0, 0)

    def shared(i, j, t, *_):
        return (i, j // per_group, t, 0, 0)

    def own(i, j, t, *_):
        return (i, j, t, 0, 0)

    def rows(i, j, t, *_):
        return (i, t, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, packs, n),
        in_specs=[pl.BlockSpec((1, 1, dk, wide), slot),
                  pl.BlockSpec((1, 1, 1, chunk, dk), shared),
                  pl.BlockSpec((1, 1, 1, dk, chunk), shared),
                  pl.BlockSpec((1, 1, 1, chunk, chunk), shared),
                  pl.BlockSpec((1, chunk, wide), rows),
                  pl.BlockSpec((1, 1, 1, chunk, r), own),
                  pl.BlockSpec((1, 1, 1, r, chunk), own)],
        out_specs=(pl.BlockSpec((1, chunk, wide), rows),
                   pl.BlockSpec((1, 1, dk, wide), slot)),
        scratch_shapes=[pltpu.VMEM((dk, wide), jnp.float32)],
    )
    o, state = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((b, length, h * dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_GDN_VMEM),
        interpret=_interpret(interpret),
    )(jnp.asarray(slots, jnp.int32), jnp.asarray(fresh, jnp.int32),
      state, qn, kt, cb, u, gcol, grow)
    return o.reshape(b, length, h, dv), state


# --------------------------------------- the state-space layer's gated norm
#: rows of one block: 2 MB of each float32 operand, 12 MB with both buffers
_NORM_ROWS = 128
_NORM_VMEM = 32 << 20
#: the chip's lanes: the width of one tile of a row's sum of squares
_LANES = 128


def gated_rms_norm(o, x, z, d, w, eps, interpret=None):
    """``rms((o + d x) * silu(z)) * w``, the state-space layer's gated norm
    (``linear_attn._output``'s ``rule == "ssd"`` branch, sum for sum but for
    the order of a row's additions), every row read once and nothing of the
    gated row kept in memory between the statistic and the scale: o (rows,
    width) float32; x (rows, >= width) float32, of which the first ``width``
    channels are read where they lie (the convolution's whole output is
    handed in, not a slice of it); z (rows, width) in the model's dtype; d, w
    (1, width) float32.  Returns (rows, width) in ``z``'s dtype.

    The grid walks blocks of ``_NORM_ROWS`` rows (a decode step's 64 are one
    block); a row's squares are added tile on tile over its ``width / 128``
    lane tiles and reduced across lanes once.  A padded or idle row is
    normalised like any other."""
    rows, width = o.shape
    return _gated_norm_call(rows, width, min(_NORM_ROWS, rows), z.dtype,
                            eps, _interpret(interpret))(o, x, z, d, w)


@functools.lru_cache(maxsize=16)
def _gated_norm_call(rows, width, tm, dtype, eps, interpret):
    """:func:`gated_rms_norm`'s Pallas call for ``rows`` rows in blocks of
    ``tm``, KEPT for every set of sizes as ``_flash_walk_call`` is: the 36
    layers of a program trace and lower the kernel once, not once each
    (set-up time)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(o_ref, x_ref, z_ref, d_ref, w_ref, y_ref):
        zf = z_ref[...].astype(jnp.float32)
        y = (o_ref[...] + d_ref[...] * x_ref[...]) * jax.nn.silu(zf)
        sq = y * y
        tile = _LANES if width % _LANES == 0 else width
        tiles = sq[:, :tile]
        for t in range(tile, width, tile):
            tiles = tiles + sq[:, t:t + tile]
        mean = tiles.sum(axis=-1, keepdims=True) / width
        y_ref[...] = (y * jax.lax.rsqrt(mean + eps)
                      * w_ref[...]).astype(y_ref.dtype)

    def block(i):
        return (i, 0)

    def whole(i):
        return (0, 0)

    return pl.pallas_call(
        kernel, grid=(pl.cdiv(rows, tm),),
        in_specs=[pl.BlockSpec((tm, width), block)] * 3
        + [pl.BlockSpec((1, width), whole)] * 2,
        out_specs=pl.BlockSpec((tm, width), block),
        out_shape=jax.ShapeDtypeStruct((rows, width), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_NORM_VMEM),
        interpret=interpret)

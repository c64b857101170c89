"""The n-stream residual: manifold-constrained hyper-connections (mHC,
arXiv:2512.24880), the residual path of the ``pre_rms`` block when its
record carries ``hyper``.

The residual of a token is ``X``, ``n`` streams of ``d`` numbers.  Around
a sublayer ``F`` (its own pre-norm, then attention or a feed forward):

    xb     = vec(X) / rms(vec(X))                       (n*d, no gain)
    H_pre  = sigmoid(a_pre  (xb P_pre)  + b_pre)        (n)
    H_post = 2 sigmoid(a_post (xb P_post) + b_post)     (n)
    H_res  = SinkhornKnopp(exp(clip(a_res mat(xb P_res) + b_res)))  (n, n)
    X'     = H_res X + H_post^T F(H_pre X)

``H_res`` is made doubly stochastic by ``iters`` rounds of column then row
normalisation (each sum + ``eps``), so the streams' mix neither grows nor
shrinks the residual.  All of it is float32 whatever the model's dtype:
the stream, the three projections (one fused matrix ``proj`` of ``n*d`` x
``2n + n*n``: P_pre | P_post | P_res), the coefficients and the Sinkhorn.

The sums over the ``n`` streams are written as ``n`` slices added up, not
as reductions or dots: with n = 4 a reduction is a kernel of its own and a
dot is a 4 x 4 matmul per token; slices and adds fuse into the passes over
the stream that the compiler makes anyway (``_coefficients`` and ``around``
say how many those are).  Everything here runs under the scope ``hc.mix``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _slabs(m, axis):
    """``m.sum(axis)`` over a short leading axis as slabs added up."""
    parts = [jnp.take(m, k, axis=axis) for k in range(m.shape[axis])]
    return sum(parts[1:], parts[0])


def _sinkhorn(m, iters, eps):
    """``iters`` rounds of column then row normalisation (each sum +
    ``eps``) of the positive matrices ``m`` (n, n, tokens): rows ``i`` on
    the first axis, columns ``j`` on the second, THE TOKENS MINOR."""
    for _ in range(iters):
        m = m / (_slabs(m, 0) + eps)[None]
        m = m / (_slabs(m, 1) + eps)[:, None]
    return m


def _coefficients(hc, x, cfg):
    """(H_pre (n, tokens), H_post (n, tokens), H_res (n, n, tokens)) of
    the stream ``x`` (..., n, d) float32, the tokens flattened, from one
    sublayer's ``hc`` = {``proj``, ``a`` (3,), ``b`` (2n + n*n,)}.

    Two things keep this to two passes over the stream and a few small
    fusions.  The norm's scale is a number a token, so it is applied to
    the projections' 2n + n*n outputs and not to the stream (``(r X) P =
    r (X P)``): the normed copy of the stream is never written.  And every
    coefficient lies with the TOKENS MINOR: the Sinkhorn's 20 rounds are
    then elementwise over full lanes; as (..., n, n), whose minor axes of
    4 fill 4 of the chip's 128 lanes, they compiled to 75 operations a
    sublayer (my chip run and compile, PR 34)."""
    hyper = cfg.hyper
    n, d = x.shape[-2:]
    flat = x.reshape(-1, n * d)
    scale = jax.lax.rsqrt((flat * flat).mean(-1) + cfg.eps)
    t = jnp.matmul(flat, hc["proj"].astype(jnp.float32), precision=HIGHEST)
    t = (t * scale[:, None]).T                        # (2n + n*n, tokens)
    a = hc["a"].astype(jnp.float32)
    b = hc["b"].astype(jnp.float32)[:, None]
    pre = jax.nn.sigmoid(a[0] * t[:n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * t[n:2 * n] + b[n:2 * n])
    res = jnp.exp(jnp.clip(a[2] * t[2 * n:] + b[2 * n:], *hyper.clamp))
    return pre, post, _sinkhorn(res.reshape(n, n, -1), hyper.iters,
                                hyper.eps)


def coefficients(hc, x, cfg):
    """(H_pre (..., n), H_post (..., n), H_res (..., n, n)): the
    coefficients laid out by token, for whoever looks at them."""
    pre, post, res = _coefficients(hc, x, cfg)
    lead = x.shape[:-2]
    n = x.shape[-2]
    return (pre.T.reshape(lead + (n,)), post.T.reshape(lead + (n,)),
            jnp.moveaxis(res, -1, 0).reshape(lead + (n, n)))


def around(hc, x, cfg, sublayer):
    """``X' = H_res X + H_post^T F(H_pre X)`` for the stream ``x``
    (..., n, d) float32; ``sublayer`` maps (..., d) float32 to
    ((..., d) any dtype, what it carries out).  Returns (X', carried).
    Each stream of ``X'`` is written as a sum over the streams of ``X``:
    one pass that reads the stream once and writes it once (a broadcast
    ``H_res[.., i, j] X[.., j, :]`` summed over ``j`` afterwards was a
    tensor of n x n x d a token in memory, 235 MB a chunk; my chip run,
    PR 34)."""
    n = x.shape[-2]
    streams = [x[..., j, :] for j in range(n)]

    def by_token(c):          # a coefficient (tokens,) beside a stream
        return c.reshape(x.shape[:-2] + (1,))

    with jax.named_scope("hc.mix"):
        pre, post, res = _coefficients(hc, x, cfg)
        u = sum(by_token(pre[j]) * streams[j] for j in range(n))
    f, carried = sublayer(u)
    with jax.named_scope("hc.mix"):
        f = f.astype(jnp.float32)
        out = jnp.stack(
            [sum(by_token(res[i, j]) * streams[j] for j in range(n))
             + by_token(post[i]) * f for i in range(n)], axis=-2)
    return out, carried


def spread(h, cfg):
    """The embedding copied into the streams: (..., d) -> (..., n, d)."""
    return jnp.broadcast_to(h[..., None, :],
                            h.shape[:-1] + (cfg.hyper.streams, h.shape[-1]))


def gather(x):
    """What the final norm and the head read: the streams' sum."""
    return sum(x[..., j, :] for j in range(x.shape[-2]))

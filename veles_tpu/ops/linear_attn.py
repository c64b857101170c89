"""The gated delta rule: the ``linear`` layers of the ``pre_rms`` block
(``model_config.LinearConfig``), with ONE decay a head (Gated DeltaNet,
arXiv:2412.06464) or one a key channel (Kimi Delta Attention,
arXiv:2510.26692).

Per token ``x``: ``[q | k | v] = x W_qkv`` (``k_heads`` heads of ``k_dim``
twice, ``v_heads`` of ``v_dim``), ``z = x W_z`` (an output gate the values'
width), ``[b | a] = x W_ba`` (one of each a value head).  A causal depthwise
convolution of ``conv`` positions, without bias, runs over the channels of
``[q | k | v]`` (each channel sees its own last ``conv`` positions), then
SiLU.  ``beta = sigmoid(b)``; ``g = -exp(A_log) * softplus(a + dt_bias)``; q
and k are L2-normalised over their head (``x * rsqrt(sum x^2 + 1e-6)``), q
times ``k_dim^-0.5``, and each key head's q and k serve ``v_heads / k_heads``
value heads.  Per value head, with the state ``S`` (k_dim, v_dim), zero where
a sequence starts::

    S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;
    o_t = S^T q_t

and the layer's output is ``(rms(o_t) * w_n * silu(z_t)) W_o``.

With a decay per CHANNEL (``linear.decay == "channel"``) the tree has ``w_b``
(one ``beta`` a head) and ``w_f`` (the keys' width) in ``w_ba``'s place: ``g =
lower_bound * sigmoid(exp(A_log) (x W_f + dt_bias))``, one number a head and
key channel in ``(lower_bound, 0)`` (``A_log`` a head, ``dt_bias`` a
channel), the rule's first line becomes ``S <- diag(exp(g_t)) S``, and the
output gate is ``sigmoid(z_t)`` (``linear.gate``).  Everywhere below ``g`` is
``(.., h)`` or ``(.., h, k_dim)`` and the same functions take both.

THE STATE-SPACE RULE (``linear.rule == "ssd"``: Mamba-2, arXiv:2405.21060) is
the same recurrence WITHOUT the delta correction, in the same slots: ``[x | B
| C] = x W_qkv`` (``v_heads`` heads of ``v_dim``, then ``k_heads`` GROUPS of
``k_dim`` twice: a group's one ``B`` is the key and its one ``C`` the query of
``v_heads / k_heads`` heads), the convolution WITH a bias (``conv_bias``), ``dt
= softplus(x W_dt + dt_bias)`` in ``beta``'s place, ``g = -exp(A_log) dt``, no
L2 norm and no scale::

    S <- exp(g_t) S + B_t (dt_t x_t)^T;  y_t = S^T C_t + D x_t

(``d = beta v`` where the delta rule has ``beta (v - S^T k)``: ``correct`` is
false), and the output is ``(rms(y_t * silu(z_t)) * w_n) W_o`` with the norm
over the WHOLE inner width, after the gate.  Its stored state packs
``linear.pack`` heads of a group side by side in one row (``(slots, v_heads /
pack, k_dim, pack x v_dim)``): the functions here see heads apart
(``_apart`` / ``_packed``), the kernels take the rows as they lie.  Chunked,
``U = beta V``, ``W`` is absent and nothing is solved; the inner chunk is
``SSD_CHUNK`` rows, and ``pallas_kernels.ssd_chunk`` computes ``C B^T`` and the
key-side products once a chunk for all of a group's heads.  The gated norm
``rms((o + D x) * silu(z)) * w_n`` is ``jax.numpy`` here (``_output``: the
CPU, the float32 tests, ``linear_forward``); with the serving kernels, in
the step and the chunk program alike, it is ONE call,
``pallas_kernels.gated_rms_norm``, which reads ``o``, ``z`` and the heads'
inputs (out of the convolution's output, where they lie) once and keeps the
gated row in fast memory between its sum of squares and its scale (the
compiler made two fusions of it and computed the gate in each: PERF.md
section 6, PR 47).

WHAT A SEQUENCE KEEPS is ``S`` of every value head (float32) and the last ``conv -
1`` rows of ``[q | k | v]`` BEFORE the convolution, whatever its length.

Two orders of the same sums, by phase:

- RECURRENT (a decode step: one row a lane): the lines above as they stand.
  With the serving kernels it is ``pallas_kernels.gdn_decode``: one call
  that reads and writes the states of the lanes that decode and of no
  other.
- CHUNKED (a prompt chunk, the whole-sequence forward): rows in inner
  chunks of ``CHUNK``; with ``gam_i`` the sum of ``g`` up to row ``i`` of
  its inner chunk, ``A_ij = beta_i (k_i . k_j) exp(gam_i - gam_j)`` below
  the diagonal, ``W = (I + A)^-1 (beta exp(gam) K)``, ``U = (I + A)^-1
  (beta V)``, all inner chunks at once as plain dots and a triangular
  solve by blocks (``solve_unit_lower``); then ONE sequential pass over the inner chunks carries the state:
  ``V' = U - W S``, ``O = (Q exp(gam)) S + tril((Q K^T) exp(gam_i -
  gam_j)) V'``, ``S <- exp(gam_C) S + (K exp(gam_C - gam))^T V'``.  The
  pass is a ``lax.scan``, or with the serving kernels
  ``pallas_kernels.gdn_chunk`` (the state of a head in fast memory from
  the first inner chunk to the last).  With a decay per channel
  ``exp(gam_i - gam_j)`` sits INSIDE the sum over the key dimension and is
  no one matrix a head: a block of ``SUB`` rows ``I`` computes ``(k_i
  exp(gam_i - ref_I)) . (k_j exp(ref_I - gam_j))`` about its first row's
  ``gam`` (``ref_I``).  Decays only shrink, so the left exponent lies in
  ``[(SUB - 1) lower_bound, 0]`` and the right one under ``(SUB - 1)
  |lower_bound|`` (75 at -5: ``exp`` stays inside float32), and under 0 for
  every row of an earlier block: a factor may underflow to 0, as the product
  it stands for would, and none overflows.

A row behind a lane's ``rows`` (the padding of a prompt's last chunk; the
one row of a lane that does not decode) has ``beta = 0`` and ``g = 0``: it
moves no state, and the convolution tail is taken at the true length.

``g``, ``beta``, ``gam``, ``S``, the L2 norms, the convolution's sums and
every accumulator are float32 whatever the model's dtype; the dots of the
rule itself run at ``HIGHEST`` (they are small: the state is the cost).
Everything here runs under the scope ``attn.linear``; the Pallas calls of a
decay per channel under ``kda.decode`` / ``kda.chunk`` inside it (a call
takes its innermost scope's name, and the benchmark's readers tell the
rules' kernels apart by it), those of the state-space rule under ``ssd.decode``
/ ``ssd.chunk`` and its gated norm under ``norm.gated`` (NOT under ``ssd.``:
the readers count the calls named ``ssd`` as one a layer and dispatch).
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from veles_tpu.ops.attention import cfg_matmul

#: rows of one inner chunk of the chunked rule
CHUNK = 64
#: rows of one inner chunk of the state-space rule (nothing is solved, so
#: the block may be as wide as the chip's matrix unit)
SSD_CHUNK = 128
#: rows of one block of the pairwise decays of a decay per channel (the block
#: of ``solve_unit_lower``)
SUB = 16
L2_EPS = 1e-6
_HI = jax.lax.Precision.HIGHEST


def _f32(x):
    return x.astype(jnp.float32)


# ------------------------------------------------------------ projections
def _inputs(p, x, cfg, cached):
    """(qkv (b, c, conv_width) before the convolution, z (b, c, value
    width), beta (b, c, v_heads; ``dt`` of a state-space layer) and g (b, c,
    v_heads) or (b, c, v_heads, k_dim) float32) of ``x`` (b, c, d)."""
    hold = jax.lax.optimization_barrier if cached else (lambda y: y)
    qkv = hold(cfg_matmul(cfg, x, p["w_qkv"]))
    z = hold(cfg_matmul(cfg, x, p["w_z"]))

    def wide(w):
        return jnp.matmul(x, w, preferred_element_type=jnp.float32,
                          precision=_HI if x.dtype == jnp.float32 else None)

    lin = cfg.linear
    h = lin.v_heads
    if lin.rule == "ssd":
        dt = jax.nn.softplus(wide(p["w_dt"]) + _f32(p["dt_bias"]))
        return qkv, z, dt, -jnp.exp(_f32(p["A_log"])) * dt
    if lin.decay == "channel":
        f = hold(wide(p["w_f"])).reshape(x.shape[:2] + (h, lin.k_dim))
        g = lin.lower_bound * jax.nn.sigmoid(
            jnp.exp(_f32(p["A_log"]))[:, None]
            * (f + _f32(p["dt_bias"]).reshape(h, lin.k_dim)))
        return qkv, z, jax.nn.sigmoid(wide(p["w_b"])), g
    ba = wide(p["w_ba"])
    beta = jax.nn.sigmoid(ba[..., :h])
    g = -jnp.exp(_f32(p["A_log"])) * jax.nn.softplus(
        ba[..., h:] + _f32(p["dt_bias"]))
    return qkv, z, beta, g


def _convolve(tail, qkv, w, rows, bias=None):
    """The causal depthwise convolution (plus ``bias`` (ch,) where the layer
    has one) and SiLU of ``qkv`` (b, c, ch) behind the sequence's last rows
    ``tail`` (b, conv - 1, ch): (float32 (b, c, ch), the new tail: the
    ``conv - 1`` rows that end at row ``rows`` of the chunk; ``rows = 0``
    hands the old tail back)."""
    k = w.shape[0]
    c = qkv.shape[1]

    def act(acc):
        return jax.nn.silu(acc if bias is None else acc + _f32(bias))
    if c == 1:
        # a decode step: the tail moves on by its one row or stays, and no
        # array of ``conv`` rows is built.  (Slices of the two put together,
        # one at a traced row of every lane, made the chip's compiler
        # convert the whole tail's layout on the way in and out at 12288
        # channels: 12 copies a step, ISSUE 42.)
        acc = sum(_f32(tail[:, j:j + 1]) * _f32(w[j]) for j in range(k - 1)) \
            + _f32(qkv) * _f32(w[k - 1])
        moved = jnp.concatenate([tail[:, 1:], qkv], axis=1)
        return act(acc), jnp.where((rows > 0)[:, None, None], moved,
                                   tail)
    seq = jnp.concatenate([tail, qkv], axis=1)           # (b, c + k - 1, ch)
    acc = sum(_f32(seq[:, j:j + c]) * _f32(w[j]) for j in range(k))
    new_tail = jax.vmap(lambda s, r: jax.lax.dynamic_slice_in_dim(
        s, r, k - 1, axis=0))(seq, rows)
    return act(acc), new_tail


def _heads(act, cfg):
    """q, k (b, c, v_heads, k_dim) normalised (q scaled), each key head
    repeated for its value heads, and v (b, c, v_heads, v_dim), float32,
    from the convolved ``act`` (b, c, conv_width).  A state-space layer's
    ``C`` and ``B`` come as they are, one a GROUP (b, c, k_heads, k_dim),
    from ``[x | B | C]``."""
    lin = cfg.linear
    b, c, _ = act.shape
    kw = lin.key_width
    if lin.rule == "ssd":
        vw = lin.value_width
        groups = (b, c, lin.k_heads, lin.k_dim)
        return (act[..., vw + kw:].reshape(groups),
                act[..., vw:vw + kw].reshape(groups),
                act[..., :vw].reshape(b, c, lin.v_heads, lin.v_dim))

    def unit(y):
        y = y.reshape(b, c, lin.k_heads, lin.k_dim)
        y = y * jax.lax.rsqrt((y * y).sum(-1, keepdims=True) + L2_EPS)
        return jnp.repeat(y, lin.v_heads // lin.k_heads, axis=2)

    q = unit(act[..., :kw]) * lin.k_dim ** -0.5
    k = unit(act[..., kw:2 * kw])
    v = act[..., 2 * kw:].reshape(b, c, lin.v_heads, lin.v_dim)
    return q, k, v


def _output(p, o, z, cfg, x=None):
    """``(rms(o) * w_n * gate(z)) W_o``: o (b, c, v_heads, v_dim) float32,
    z (b, c, value width); the gate ``silu`` or ``sigmoid``.  A state-space
    layer: ``(rms((o + D x) * silu(z)) * w_n) W_o``, the norm over all heads
    at once and AFTER the gate (``x``: the heads' inputs, as ``o``).  With
    the serving kernels ``x`` is the convolution's whole output (b, c, conv
    width), whose first channels the heads' inputs are, and the gated norm
    is ONE call, ``pallas_kernels.gated_rms_norm``, under the scope
    ``norm.gated`` (told by ``x``'s rank and not by a sixth argument: the
    benchmark's planted faults wrap this function with these five)."""
    lin = cfg.linear
    b, c = o.shape[:2]
    if lin.rule == "ssd":
        if x.ndim == 3:
            from veles_tpu.ops import pallas_kernels as PK
            rows = b * c
            with jax.named_scope("norm.gated"):
                y = PK.gated_rms_norm(
                    o.reshape(rows, -1), x.reshape(rows, -1),
                    z.reshape(rows, -1),
                    jnp.repeat(_f32(p["D"]), lin.v_dim)[None],
                    _f32(p["norm"])[None], cfg.eps).reshape(b, c, -1)
            return cfg_matmul(cfg, y, p["wo"])
        y = (o + _f32(p["D"])[:, None] * x).reshape(b, c, -1) \
            * jax.nn.silu(_f32(z))
        y = y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True) + cfg.eps) \
            * _f32(p["norm"])
        return cfg_matmul(cfg, y.astype(z.dtype), p["wo"])
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + cfg.eps) \
        * _f32(p["norm"])
    gate = jax.nn.silu if lin.gate == "silu" else jax.nn.sigmoid
    o = o * gate(_f32(z).reshape(b, c, lin.v_heads, lin.v_dim))
    return cfg_matmul(cfg, o.reshape(b, c, -1).astype(z.dtype), p["wo"])


# -------------------------------------------------------------- two orders
def recurrent_step(state, q, k, v, beta, g, correct=True):
    """One row a lane by the rule as written: state (b, h, dk, dv); q, k
    (b, h, dk); v (b, h, dv); beta (b, h); g (b, h) or (b, h, dk).
    ``correct`` false: the state-space rule, ``d = beta v``.  Returns (o (b,
    h, dv), the new state)."""
    decay = jnp.exp(g)
    state = state * (decay[..., None] if g.ndim == 3
                     else decay[..., None, None])
    kv = jnp.einsum("bhkv,bhk->bhv", state, k, precision=_HI) \
        if correct else None
    d = beta[..., None] * (v if kv is None else v - kv)
    state = state + k[..., :, None] * d[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", state, q, precision=_HI), state


def solve_unit_lower(a, rhs, block=16):
    """``(I + a)^-1 rhs`` for ``a`` (..., C, C) strictly lower triangular,
    by blocks of ``block``: a diagonal block's inverse is the finite series
    ``sum (-N)^p = (I - N)(I + N^2)(I + N^4)(I + N^8)`` (``N^16 = 0``; with
    entries of at most 1 its powers stay under C(15, p), where the same
    series over all 64 rows would reach 1e18 and cancel to nothing), then
    forward substitution over the blocks: 14 small batched dots in place of
    the compiler's triangular solve, which took 1.37 ms a layer and chunk
    on the chip against the sequential pass's 0.38 (PERF.md section 6, PR
    36)."""
    c = a.shape[-1]
    nb = c // block
    lead = a.shape[:-2]
    ab = a.reshape(lead + (nb, block, nb, block))
    eye = jnp.eye(block, dtype=a.dtype)
    n = jnp.stack([ab[..., i, :, i, :] for i in range(nb)], axis=-3)
    inv, power = eye - n, n
    for _ in range(block.bit_length() - 2):
        power = jnp.matmul(power, power, precision=_HI)
        inv = jnp.matmul(inv, eye + power, precision=_HI)
    rb = rhs.reshape(lead + (nb, block, rhs.shape[-1]))
    out = []
    for i in range(nb):
        r = rb[..., i, :, :]
        for j in range(i):
            r = r - jnp.matmul(ab[..., i, :, j, :], out[j], precision=_HI)
        out.append(jnp.matmul(inv[..., i, :, :], r, precision=_HI))
    return jnp.concatenate(out, axis=-2)


def _pair_products(q, k, gam):
    """``(sum_c k_i k_j exp(gam_i - gam_j), sum_c q_i k_j exp(gam_i -
    gam_j))`` for ``j <= i`` (what lies above the diagonal is finite and
    means nothing), each (..., C, C), for a decay per channel: q, k, gam
    (..., C, dk).  Block ``I`` of ``SUB`` rows meets the rows up to its
    own end about its first row's ``gam``."""
    kk, qk = [], []
    for lo in range(0, CHUNK, SUB):
        hi = lo + SUB
        ref = gam[..., lo:lo + 1, :]
        left = jnp.exp(gam[..., lo:hi, :] - ref)
        right = k[..., :hi, :] * jnp.exp(ref - gam[..., :hi, :])
        both = jnp.einsum(
            "...ik,...jk->...ij",
            jnp.concatenate([k[..., lo:hi, :] * left,
                             q[..., lo:hi, :] * left], axis=-2),
            right, precision=_HI)
        both = jnp.pad(both, [(0, 0)] * (both.ndim - 1) + [(0, CHUNK - hi)])
        kk.append(both[..., :SUB, :])
        qk.append(both[..., SUB:, :])
    return jnp.concatenate(kk, axis=-2), jnp.concatenate(qk, axis=-2)


def chunk_terms(q, k, v, beta, g, correct=True, chunk=CHUNK):
    """What the sequential pass of the chunked rule reads, for all inner
    chunks at once: q, k (b, L, h, dk), v (b, L, h, dv), beta (b, L, h), g
    (b, L, h) or (b, L, h, dk), L a multiple of ``chunk``.  Returns float32
    ``(W, U, Qg (b, h, n, C, .), Att (b, h, n, C, C), KdT (b, h, n, dk, C),
    decay (b, h, n) or (b, h, n, dk))``: ``V' = U - W S``; ``O = Qg S + Att
    V'``; ``S <- decay S + KdT V'`` (``decay`` by rows of ``S`` where it is
    one a channel).  ``correct`` false (the state-space rule, one decay a
    head): ``W`` is None, ``U = beta V`` and nothing is solved."""
    b, length, h, _ = q.shape
    n = length // chunk

    def split(y):                       # (b, L, h, ...) -> (b, h, n, C, ...)
        y = y.reshape((b, n, chunk, h) + y.shape[3:])
        return jnp.moveaxis(y, 3, 1)

    q, k, v, beta, g = (split(y) for y in (q, k, v, beta, g))
    low = jnp.tril(jnp.ones((chunk, chunk), bool))
    eye = jnp.eye(chunk, dtype=bool)
    if g.ndim == 5:
        gam = jnp.cumsum(g, axis=-2)                        # (b, h, n, C, dk)
        kk, qk = _pair_products(q, k, gam)
        a = beta[..., None] * jnp.where(low & ~eye, kk, 0.0)
        att = jnp.where(low, qk, 0.0)
        scale = jnp.exp(gam)
        last = gam[..., -1:, :]
        to_last, decay = jnp.exp(last - gam), jnp.exp(last[..., 0, :])
    else:
        gam = jnp.cumsum(g, axis=-1)                        # (b, h, n, C)
        diff = gam[..., :, None] - gam[..., None, :]        # gam_i - gam_j
        pair = jnp.exp(jnp.where(low, diff, -jnp.inf))      # 0 above diag
        if correct:
            kk = jnp.einsum("bhnik,bhnjk->bhnij", k, k, precision=_HI)
            a = beta[..., None] * kk * jnp.where(eye, 0.0, pair)
        att = jnp.einsum("bhnik,bhnjk->bhnij", q, k, precision=_HI) * pair
        scale = jnp.exp(gam)[..., None]
        last = gam[..., -1:]
        to_last, decay = jnp.exp(last - gam)[..., None], jnp.exp(last[..., 0])
    if not correct:
        return (None, beta[..., None] * v, q * scale, att,
                jnp.swapaxes(k * to_last, -1, -2), decay)
    rhs = jnp.concatenate([beta[..., None] * scale * k,
                           beta[..., None] * v], axis=-1)
    solved = solve_unit_lower(a, rhs)
    dk = k.shape[-1]
    w, u = solved[..., :dk], solved[..., dk:]
    return (w, u, q * scale, att, jnp.swapaxes(k * to_last, -1, -2), decay)


def chunk_pass(state, terms):
    """The sequential pass over the inner chunks as a ``lax.scan``: state
    (b, h, dk, dv) -> (O (b, h, n, C, dv), the state after the last)."""
    def body(s, t):
        w, u, qg, att, kdt, decay = t
        vp = u if w is None else \
            u - jnp.einsum("bhck,bhkv->bhcv", w, s, precision=_HI)
        o = jnp.einsum("bhck,bhkv->bhcv", qg, s, precision=_HI) \
            + jnp.einsum("bhij,bhjv->bhiv", att, vp, precision=_HI)
        s = (decay[..., None] if decay.ndim == 3
             else decay[..., None, None]) * s \
            + jnp.einsum("bhkc,bhcv->bhkv", kdt, vp, precision=_HI)
        return s, o

    state, o = jax.lax.scan(
        body, state, tuple(None if t is None else jnp.moveaxis(t, 2, 0)
                           for t in terms))
    return jnp.moveaxis(o, 0, 2), state


# ------------------------------------------------------------ entry points
def _kernel_scope(lin, order):
    """The scope a Pallas call of the rule runs under: the layer's own
    (``attn.linear``) for one decay a head, ``kda.<order>`` for one a
    channel, ``ssd.<order>`` for the state-space rule."""
    if lin.rule == "ssd":
        return jax.named_scope("ssd." + order)
    if lin.decay == "channel":
        return jax.named_scope("kda." + order)
    return contextlib.nullcontext()


def _apart(state, lin):
    """The stored state (s, v_heads / r, dk, r x dv) with its heads apart:
    (s, v_heads, dk, dv)."""
    r = lin.pack
    if r == 1:
        return state
    s, packs, dk, _ = state.shape
    return state.reshape(s, packs, dk, r, lin.v_dim).swapaxes(2, 3) \
        .reshape(s, packs * r, dk, lin.v_dim)


def _packed(state, lin):
    """:func:`_apart`'s inverse."""
    r = lin.pack
    if r == 1:
        return state
    s, h, dk, dv = state.shape
    return state.reshape(s, h // r, r, dk, dv).swapaxes(2, 3) \
        .reshape(s, h // r, dk, r * dv)


def linear_paged_chunk_step(p, x, state, tail, cfg, rows, slots=None,
                            fresh=None, attn_kernel=None):
    """``c`` positions per lane through one linear layer against the lanes'
    state: ``attention.mha_paged_chunk_step`` for the kind that holds no
    pages.

    x: (b, c, d); state: (slots, v_heads, k_dim, v_dim) float32 (a
    state-space layer's: ``linear.pack`` heads to a row) and tail: (slots,
    conv - 1, conv_width), one slot a lane of the engine; ``rows``
    (b,) int32: how many of a lane's ``c`` rows are real (the others move
    nothing; 0: the lane's slot comes back bit for bit); ``slots`` (b,)
    int32: each lane's slot, or None where lane ``i`` IS slot ``i``.  One
    row a lane runs the recurrent rule, more the chunked one.  ``fresh``
    (b,) bool: the lane's sequence starts with this chunk, so whatever its
    slot held is read as zeros (how the engine resets a lane at admission
    without a dispatch of its own; the chunked order only).
    ``attn_kernel`` ('decode' | 'prefill' | None) takes the Pallas kernels.
    Returns (out (b, c, d), state, tail)."""
    lin = cfg.linear
    b, c, _ = x.shape
    rows = jnp.asarray(rows, jnp.int32)
    with jax.named_scope("attn.linear"):
        qkv, z, beta, g = _inputs(p, x, cfg, cached=True)
        real = jnp.arange(c)[None, :] < rows[:, None]          # (b, c)
        real = real[..., None]
        beta = jnp.where(real, beta, 0.0)
        g = jnp.where(real if g.ndim == 3 else real[..., None], g, 0.0)
        mine = tail if slots is None else tail[slots]
        if fresh is not None:
            mine = jnp.where(fresh[:, None, None], 0, mine).astype(tail.dtype)
        # (a layer without a bias calls with four arguments, as the
        # benchmark's planted faults wrap it)
        act, new_tail = _convolve(
            mine, qkv, p["conv"], rows,
            **({"bias": p["conv_bias"]} if "conv_bias" in p else {}))
        tail = new_tail if slots is None else tail.at[slots].set(new_tail)
        q, k, v = _heads(act, cfg)
        ssd = lin.rule == "ssd"
        if ssd and not attn_kernel:
            # a group's C and B for each of its heads (the kernels take
            # them one a group)
            q, k = (jnp.repeat(y, lin.v_heads // y.shape[2], axis=2)
                    for y in (q, k))
        if c == 1:
            if attn_kernel:
                from veles_tpu.ops import pallas_kernels as PK
                if slots is not None:
                    raise ValueError("the decode kernel steps every slot")
                with _kernel_scope(lin, "decode"):
                    o, state = PK.gdn_decode(
                        state, q[:, 0], k[:, 0], v[:, 0], beta[:, 0],
                        g[:, 0], rows > 0, correct=not ssd)
            else:
                s0 = _apart(state if slots is None else state[slots], lin)
                o, s1 = recurrent_step(s0, q[:, 0], k[:, 0], v[:, 0],
                                       beta[:, 0], g[:, 0], correct=not ssd)
                s1 = _packed(
                    jnp.where((rows > 0)[:, None, None, None], s1, s0), lin)
                state = s1 if slots is None else state.at[slots].set(s1)
            o = o[:, None]
        else:
            inner = SSD_CHUNK if ssd else CHUNK
            pad = -c % inner
            rule = (q, k, v, beta, g)
            if pad:
                rule = tuple(jnp.pad(
                    y, [(0, 0), (0, pad)] + [(0, 0)] * (y.ndim - 2))
                    for y in rule)
            # (the chunk kernel of the state-space rule takes the rows as
            # they are and makes its own terms)
            terms = None if ssd and attn_kernel else chunk_terms(
                *rule, correct=not ssd, chunk=inner)
            ids = jnp.arange(b) if slots is None else slots
            if fresh is None:
                fresh = jnp.zeros((b,), bool)
            if attn_kernel:
                from veles_tpu.ops import pallas_kernels as PK
                with _kernel_scope(lin, "chunk"):
                    if ssd:
                        o, state = PK.ssd_chunk(state, ids, fresh, *rule,
                                                chunk=inner)
                    else:
                        o, state = PK.gdn_chunk(state, ids, fresh, *terms)
            else:
                s0 = jnp.where(fresh[:, None, None, None], 0.0,
                               _apart(state[ids], lin))
                o, s1 = chunk_pass(s0, terms)
                state = state.at[ids].set(_packed(s1, lin))
            if terms is not None:
                # (b, h, n, C, dv) -> (b, c, h, dv)
                o = jnp.moveaxis(o, 1, 3).reshape(
                    b, c + pad, lin.v_heads, lin.v_dim)
            o = o[:, :c]
        # (the gated norm's kernel reads the heads' inputs where the
        # convolution left them)
        x = None if not ssd else act if attn_kernel else v
        return _output(p, o, z, cfg, x), state, tail


def linear_forward(p, x, cfg):
    """One linear layer over whole sequences ``x`` (b, s, d) from empty
    states, in the chunked order."""
    b, s, _ = x.shape
    state_shape, tail_shape = cfg.linear.state_shapes(b)
    out, _, _ = linear_paged_chunk_step(
        p, x, jnp.zeros(state_shape, jnp.float32),
        jnp.zeros(tail_shape, x.dtype), cfg,
        jnp.full((b,), s, jnp.int32))
    return out

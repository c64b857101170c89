"""Pure numeric functions behind every accelerated unit.

Single source of truth: unit-mode ``run()`` methods jit these individually;
the fused step builder (``veles_tpu.compiled``) composes them into one traced
``train_step``.  All are shape-static, batch-leading, and bf16/f32 friendly so
XLA tiles the matmuls onto the MXU.

Activation semantics follow the reference exactly (ref: veles/znicz/
all2all.py, activation.py [H]):

- ``tanh`` is the LeCun-scaled ``1.7159 * tanh(2/3 x)`` the reference's
  All2AllTanh/ConvTanh used,
- ``relu`` is the smooth ``log(1 + exp(x))`` the reference called RELU,
- ``strict_relu`` is ``max(0, x)``,
- ``sigmoid``, ``softmax`` as usual.

Each activation has a matching ``*_derivative_from_output`` used by the
backward chain: derivatives are expressed in terms of the forward OUTPUT
(exactly like the reference's gradient kernels), so the backward pass never
re-materializes pre-activations.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# LeCun-scaled tanh constants (ref: veles/znicz/all2all.py::All2AllTanh [H])
TANH_A = 1.7159
TANH_B = 0.6666

# Matmul precision: jax's default lets the MXU (and its CPU emulation) use
# reduced-precision passes; the reference computed fp32 GEMMs (OpenCL/cuBLAS),
# so convergence parity requires HIGHEST by default (SURVEY §7 "hard parts").
# Perf runs can opt into bf16 inputs via set_matmul_precision("bfloat16"),
# which casts operands instead (the idiomatic fast path on TPU).
_PRECISION = jax.lax.Precision.HIGHEST
_CAST_BF16 = False


def set_matmul_precision(mode):
    """mode: 'float32' (default, parity) | 'default' | 'bfloat16' (fast).

    The mode is read at TRACE time, so already-jitted functions would keep
    their old precision; jax caches are cleared here to force a retrace on
    the next call — but only on an actual change: a restore-to-current
    no-op must not wipe every compiled program in the process.
    """
    global _PRECISION, _CAST_BF16
    if mode == "float32":
        new = (jax.lax.Precision.HIGHEST, False)
    elif mode == "default":
        new = (jax.lax.Precision.DEFAULT, False)
    elif mode == "bfloat16":
        new = (jax.lax.Precision.DEFAULT, True)
    else:
        raise ValueError("unknown matmul precision mode %r" % (mode,))
    if new == (_PRECISION, _CAST_BF16):
        return
    _PRECISION, _CAST_BF16 = new
    jax.clear_caches()


def matmul_precision(mode):
    """Context manager: run a block under another precision mode and
    restore the PRIOR mode (not a hardcoded default) on exit — the one
    shared implementation for bench/tests/tools that flip to bf16
    temporarily."""
    import contextlib

    @contextlib.contextmanager
    def _cm():
        prior = ("float32" if _PRECISION == jax.lax.Precision.HIGHEST
                 else ("bfloat16" if _CAST_BF16 else "default"))
        set_matmul_precision(mode)
        try:
            yield
        finally:
            set_matmul_precision(prior)
    return _cm()


def matmul(a, b):
    """Precision-pinned matmul every op routes its GEMMs through."""
    if _CAST_BF16:
        out_dtype = jnp.result_type(a, b)
        return jnp.matmul(a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16)).astype(out_dtype)
    return jnp.matmul(a, b, precision=_PRECISION)


def _conv_operands(x, w):
    """Apply the same precision policy to conv operands that ``matmul``
    applies to GEMM operands (bf16 mode casts inputs; the MXU accumulates
    in fp32 either way)."""
    if _CAST_BF16:
        return x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    return x, w


# --------------------------------------------------------------- activations
def activate(z, activation):
    if activation == "linear":
        return z
    if activation == "tanh":
        return TANH_A * jnp.tanh(TANH_B * z)
    if activation == "relu":  # smooth relu, see module docstring
        return jnp.log1p(jnp.exp(-jnp.abs(z))) + jnp.maximum(z, 0.0)
    if activation == "strict_relu":
        return jnp.maximum(z, 0.0)
    if activation == "sigmoid":
        return jax.nn.sigmoid(z)
    if activation == "softmax":
        return jax.nn.softmax(z, axis=-1)
    raise ValueError("unknown activation %r" % (activation,))


def activation_derivative_from_output(y, activation):
    """d(activation)/d(pre-activation) expressed via the forward output y.

    For softmax returns ones: the softmax evaluator emits err_output already
    w.r.t. the logits (the softmax+NLL fusion the reference used — ref:
    veles/znicz/evaluator.py::EvaluatorSoftmax [H]).
    """
    if activation in ("linear", "softmax"):
        return jnp.ones_like(y)
    if activation == "tanh":
        # y = a tanh(bz)  =>  dy/dz = b (a - y^2 / a)
        return TANH_B * (TANH_A - y * y / TANH_A)
    if activation == "relu":
        # y = log(1+e^z)  =>  dy/dz = 1 - e^{-y}
        return 1.0 - jnp.exp(-y)
    if activation == "strict_relu":
        return (y > 0.0).astype(y.dtype)
    if activation == "sigmoid":
        return y * (1.0 - y)
    raise ValueError("unknown activation %r" % (activation,))


# --------------------------------------------------------------------- dense
def dense_forward(x, weights, bias, activation="linear"):
    """All2All forward: y = act(x @ W + b).

    x: (batch, n_in); weights: (n_in, n_out); bias: (n_out,) or None.
    Ref: veles/znicz/all2all.py::All2All [H] (GEMM + fused activation on MXU).
    """
    z = matmul(x.reshape(x.shape[0], -1), weights)
    if bias is not None:
        z = z + bias
    return activate(z, activation)


def dense_backward(x, y, err_output, weights, activation="linear",
                   include_bias=True, need_err_input=True):
    """All2All backward: (err_input, grad_weights, grad_bias).

    err_output is dL/dy (or dL/dlogits for softmax, see above).  Gradients
    are SUMS over the batch; the update rule normalizes by batch size.
    ``need_err_input=False`` (first trainable layer) skips the dL/dx GEMM
    entirely.  Ref: veles/znicz/gd.py::GradientDescent [H].
    """
    x2 = x.reshape(x.shape[0], -1)
    dz = err_output * activation_derivative_from_output(y, activation)
    grad_weights = matmul(x2.T, dz)
    grad_bias = dz.sum(axis=0) if include_bias else None
    err_input = (matmul(dz, weights.T).reshape(x.shape)
                 if need_err_input else None)
    return err_input, grad_weights, grad_bias


# ---------------------------------------------------------------- evaluators
def softmax_loss(probs, labels, valid_mask):
    """Softmax+NLL evaluator math.

    probs: (batch, n_classes) — OUTPUT of All2AllSoftmax;
    labels: (batch,) int; valid_mask: (batch,) 0/1 float (short-minibatch
    padding — the reference tracked the live ``minibatch_size`` instead;
    masking keeps shapes static for XLA).

    Returns (err_output, metrics) with err_output = (probs - onehot) * mask —
    the gradient w.r.t. the LOGITS (softmax+NLL fusion).  Metrics: n_err
    (wrong argmax count), loss sum, per-class confusion counts.
    Ref: veles/znicz/evaluator.py::EvaluatorSoftmax [H].
    """
    n_classes = probs.shape[-1]
    onehot = jax.nn.one_hot(labels, n_classes, dtype=probs.dtype)
    mask = valid_mask.astype(probs.dtype)[:, None]
    err_output = (probs - onehot) * mask
    pred = jnp.argmax(probs, axis=-1)
    wrong = (pred != labels) & (valid_mask > 0)
    n_err = wrong.sum(dtype=jnp.int32)
    eps = jnp.asarray(1e-30, probs.dtype)
    nll = -jnp.log(jnp.maximum(
        jnp.take_along_axis(probs, labels[:, None], axis=-1)[:, 0], eps))
    loss_sum = (nll * valid_mask.astype(probs.dtype)).sum()
    confusion = jnp.zeros((n_classes, n_classes), jnp.int32).at[
        labels, pred].add(valid_mask.astype(jnp.int32))
    return err_output, {"n_err": n_err, "loss_sum": loss_sum,
                        "confusion": confusion}


def mse_loss(output, target, valid_mask):
    """MSE evaluator: err_output = (output - target) * mask, metrics sums.

    Ref: veles/znicz/evaluator.py::EvaluatorMSE [H].
    """
    mask = valid_mask.astype(output.dtype).reshape(
        (-1,) + (1,) * (output.ndim - 1))
    diff = (output - target) * mask
    per_sample = jnp.sqrt((diff * diff).reshape(diff.shape[0], -1).sum(axis=1))
    return diff, {
        "mse_sum": (per_sample * per_sample).sum(),
        "rmse_max": per_sample.max(),
        "loss_sum": 0.5 * (diff * diff).sum(),
    }


# -------------------------------------------------------------- convolution
def _norm_padding(padding):
    """"SAME"/"VALID" pass through; int or (int, int) become symmetric
    per-dimension (lo, hi) pairs."""
    if isinstance(padding, int):
        return [(padding, padding), (padding, padding)]
    if (isinstance(padding, (tuple, list)) and len(padding) == 2
            and all(isinstance(p, int) for p in padding)):
        return [(padding[0], padding[0]), (padding[1], padding[1])]
    return padding


def conv2d_forward(x, weights, bias, stride=(1, 1), padding="VALID",
                   activation="linear"):
    """2-D convolution, NHWC layout, weights HWIO (kh, kw, cin, cout).

    NHWC/HWIO is the TPU-native layout (the reference's kernels were NCHW-ish
    OpenCL — ref: veles/znicz/conv.py + ocl/conv.cl [H]); padding may be
    "SAME", "VALID", or an int/pair of ints applied symmetrically.
    """
    padding = _norm_padding(padding)
    out_dtype = x.dtype
    xc, wc = _conv_operands(x, weights)
    z = jax.lax.conv_general_dilated(
        xc, wc, window_strides=tuple(stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=_PRECISION).astype(out_dtype)
    if bias is not None:
        z = z + bias
    return activate(z, activation)


# ---------------------------------------------------------- transposed conv
def deconv2d_forward(x, weights, bias, stride=(1, 1), padding="SAME",
                     activation="linear", output_padding=(0, 0)):
    """Transposed 2-D convolution (deconvolution), NHWC/HWIO.

    Upsamples spatially by ``stride``.  Ref: veles/znicz/deconv.py::Deconv
    [H] (SURVEY §2.3) — the reference hand-wrote the scatter kernels; here
    ``lax.conv_transpose`` lowers to an input-dilated conv on the MXU.
    weights: (kh, kw, in_c, out_c).

    Int/pair padding means THE TRANSPOSE OF a conv with that padding (the
    autoencoder mirror: deconv(k, s, p) inverts conv(k, s, p)'s spatial
    shape), i.e. the dilated input is raw-padded k-1-p per side —
    lax.conv_transpose's explicit pads are raw, only its string forms
    transpose automatically.  Conv's shape formula floors, so the mirror is
    ambiguous by up to stride-1 pixels; ``output_padding`` (extra bottom/
    right pixels, torch semantics) resolves it:
    ``output_padding = (in + 2p - k) % s`` recovers ``in`` exactly.
    """
    padding = _norm_padding(padding)
    if not isinstance(padding, str):
        kh, kw = weights.shape[0], weights.shape[1]
        oph, opw = ((output_padding, output_padding)
                    if isinstance(output_padding, int) else output_padding)
        padding = [(kh - 1 - padding[0][0], kh - 1 - padding[0][1] + oph),
                   (kw - 1 - padding[1][0], kw - 1 - padding[1][1] + opw)]
    out_dtype = x.dtype
    xc, wc = _conv_operands(x, weights)
    z = jax.lax.conv_transpose(
        xc, wc, strides=tuple(stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=_PRECISION).astype(out_dtype)
    if bias is not None:
        z = z + bias
    return activate(z, activation)


# ------------------------------------------------------------------ depooling
def depool(x, window=(2, 2), mode="nearest"):
    """Unpooling: spatially upsample by the pooling window.

    Ref: veles/znicz/depooling.py::Depooling [H].  The reference scattered
    err values to max-pool argmax offsets recorded device-side; recording
    cross-unit indices breaks functional purity, so the TPU-native unpooling
    is positional: "nearest" replicates each value over its window (the
    adjoint of avg-pooling up to the 1/k factor), "zero" places it top-left
    and zero-fills (the adjoint of a fixed-offset max-pool).
    """
    kh, kw = window
    if mode == "nearest":
        return jnp.repeat(jnp.repeat(x, kh, axis=1), kw, axis=2)
    if mode == "zero":
        b, h, w, c = x.shape
        out = jnp.zeros((b, h, kh, w, kw, c), x.dtype)
        out = out.at[:, :, 0, :, 0, :].set(x)
        return out.reshape(b, h * kh, w * kw, c)
    raise ValueError("unknown depooling mode %r" % (mode,))


# ------------------------------------------------------------------- pooling
def _ceil_pad(size, k, s):
    """Right-pad so every input element is covered (ceil semantics).

    The reference's pooling ceil-covers the input (a 7x7 input with 2x2/2
    pooling yields 4x4, not 3x3 — ref: veles/znicz/pooling.py [H]).
    """
    if size <= k:
        return max(k - size, 0)
    steps = -(-(size - k) // s)  # ceil division
    return steps * s + k - size


def _pool_patches(x, window, stride, pad_value):
    """Extract pooling patches: (batch, oh, ow, kh*kw, c), ceil-padded.

    Built on conv_general_dilated_patches; the patch axis ordering is
    normalized so axis 3 enumerates the kh*kw window positions per channel.
    """
    b, h, w, c = x.shape
    ph = _ceil_pad(h, window[0], stride[0])
    pw = _ceil_pad(w, window[1], stride[1])
    if ph or pw:
        x = jnp.pad(x, [(0, 0), (0, ph), (0, pw), (0, 0)],
                    constant_values=pad_value)
    patches = jax.lax.conv_general_dilated_patches(
        x, filter_shape=tuple(window), window_strides=tuple(stride),
        padding="VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    oh, ow = patches.shape[1], patches.shape[2]
    # features come out channel-major: (c, kh*kw)
    patches = patches.reshape(b, oh, ow, c, window[0] * window[1])
    return jnp.moveaxis(patches, 3, 4), oh, ow  # -> (b, oh, ow, kh*kw, c)


def _reduce_window(x, init, op, window, stride):
    """Ceil-padded 2-D reduce_window over the spatial axes of NHWC.

    ``lax.reduce_window`` is THE native pooling path on TPU: the forward
    lowers to a fused window reduction and the max-monoid vjp lowers to
    select-and-scatter — the hardware form of the reference's
    "record argmax offsets, scatter err" backward kernels (ref:
    veles/znicz/pooling.py, gd_pooling.py [H]).  The patch-materializing
    implementation it replaces inflated HBM traffic by kh*kw (round-3
    bench: 0.2% MFU on the conv nets, VERDICT r3 Weak #2).
    """
    ph = _ceil_pad(x.shape[1], window[0], stride[0])
    pw = _ceil_pad(x.shape[2], window[1], stride[1])
    return jax.lax.reduce_window(
        x, init, op, (1,) + tuple(window) + (1,),
        (1,) + tuple(stride) + (1,),
        [(0, 0), (0, ph), (0, pw), (0, 0)])


def max_pooling(x, window=(2, 2), stride=None):
    """Max pooling; backward (vjp) scatters to the argmax — the same
    record-argmax-offsets scheme the reference's kernels used (ref:
    veles/znicz/pooling.py::MaxPooling, gd_pooling.py [H])."""
    stride = stride or window
    return _reduce_window(x, -jnp.inf, jax.lax.max, window, stride)


def maxabs_pooling(x, window=(2, 2), stride=None):
    """Max-absolute-value pooling (signed value of the abs-max element).

    Ref: veles/znicz/pooling.py::MaxAbsPooling [H].  Computed as two
    native window reductions: out = mx if mx >= -mn else mn picks the
    signed value of the abs-max element (ties at |mx|==|mn| resolve to the
    positive one).  Tail windows are init-padded, which reproduces the
    zero-padding semantics for every non-empty window: a padded -inf/+inf
    never wins either reduction.
    """
    stride = stride or window
    mx = _reduce_window(x, -jnp.inf, jax.lax.max, window, stride)
    mn = _reduce_window(x, jnp.inf, jax.lax.min, window, stride)
    return jnp.where(mx >= -mn, mx, mn)


def avg_pooling(x, window=(2, 2), stride=None):
    """Average pooling; tail windows are zero-padded and divided by the FULL
    window size (include-pad semantics, matching Caffe-era references)."""
    stride = stride or window
    # init MUST be the python literal 0 — an Array init defeats jax's
    # add-monoid detection and binds the non-differentiable generic
    # reduce_window primitive
    s = _reduce_window(x, 0.0, jax.lax.add, window, stride)
    return s / (window[0] * window[1])


def stochastic_pooling(x, window=(2, 2), stride=None, rng=None, train=True,
                       use_abs=False):
    """Zeiler-style stochastic pooling.

    Train: sample one element per window with probability proportional to
    its (abs or relu'd) magnitude — Gumbel-trick sampling so the whole op
    stays inside the jitted step (the reference generated positions with
    in-kernel device RNG — veles/znicz/pooling.py::StochasticAbsPooling
    [H]).  Eval: the probability-weighted average (the standard
    deterministic surrogate).  Output is the SIGNED value at the chosen
    position.
    """
    stride = stride or window
    patches, oh, ow = _pool_patches(x, window, stride, 0.0)
    weights = jnp.abs(patches) if use_abs else jnp.maximum(patches, 0.0)
    total = weights.sum(axis=3, keepdims=True)
    # empty windows (all zero): fall back to uniform
    k = patches.shape[3]
    probs = jnp.where(total > 0, weights / jnp.maximum(total, 1e-30),
                      1.0 / k)
    if train:
        if rng is None:
            raise ValueError("stochastic pooling needs rng when train=True")
        gumbel = jax.random.gumbel(rng, probs.shape, probs.dtype)
        idx = jnp.argmax(jnp.log(jnp.maximum(probs, 1e-30)) + gumbel,
                         axis=3, keepdims=True)
        return jnp.take_along_axis(patches, idx, axis=3)[:, :, :, 0, :]
    return (probs * patches).sum(axis=3)


# ------------------------------------------------- local response norm (LRN)
#: 'xla' = the shifted-slice form below (loop-fused elementwise chain);
#: 'pallas' = the one-pass fused kernel with banded-matmul window sum and
#: fused backward (ops/pallas_kernels.py::lrn_forward) — the top
#: memory-bound item of the post-bf16 AlexNet step (docs/PERF.md).
#: Benchmarked against each other by bench.py's lrn record; the default
#: stays whichever wins on hardware.
_LRN_BACKEND = "xla"


def set_lrn_backend(mode):
    """mode: 'xla' | 'pallas'.  Clears jit caches (trace-time flag) —
    only on an actual change (see set_matmul_precision)."""
    global _LRN_BACKEND
    if mode not in ("xla", "pallas"):
        raise ValueError("unknown lrn backend %r" % (mode,))
    if mode == _LRN_BACKEND:
        return
    _LRN_BACKEND = mode
    jax.clear_caches()


def lrn_forward(x, alpha=1e-4, beta=0.75, n=5, k=2.0):
    """AlexNet cross-channel local response normalization.

    y = x / (k + alpha/n * sum_{j in window(n)} x_j^2)^beta over the channel
    axis.  Ref: veles/znicz/normalization.py::LRNormalizerForward [H].
    """
    if _LRN_BACKEND == "pallas":
        from veles_tpu.ops import pallas_kernels as PK
        return PK.lrn_forward(x, alpha, beta, n, k)
    c = x.shape[-1]
    sq = x * x
    half = n // 2
    padded = jnp.pad(sq, [(0, 0)] * (x.ndim - 1) + [(half, half)])
    # windowed channel sum as n shifted slices: n is small (5 for AlexNet),
    # so this fuses into one elementwise kernel — unlike cumsum, whose TPU
    # lowering is a prefix-scan chain that dominated the round-3 step trace
    window_sums = sum(jax.lax.slice_in_dim(padded, i, i + c, axis=-1)
                      for i in range(n))
    denom = (k + (alpha / n) * window_sums) ** beta
    return x / denom


# ------------------------------------------------------------------- dropout
def dropout(x, rng, rate, train):
    """Inverted Bernoulli dropout; mask regenerated from the same counter key
    in backward (the reference stored and replayed the mask — ref:
    veles/znicz/dropout.py [H]; a counter-based key replay is the TPU way)."""
    if not train or rate <= 0.0:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(rng, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


# -------------------------------------------------------------- augmentation
def random_crop_flip(x, rng, out_hw, flip=True, train=True):
    """AlexNet-style augmentation ON DEVICE: per-sample random crop (+
    horizontal mirror); eval mode center-crops.

    Ref: the reference's ImageNet sample preprocessing (veles/znicz/samples/
    imagenet processor pipelines [M], SURVEY §2.2) did this on the host per
    minibatch; here it traces into the jitted step (vmapped dynamic_slice +
    select), so augmentation is free of host round-trips and fully
    deterministic from the step rng.
    """
    b, h, w, c = x.shape
    oh, ow = out_hw
    if not train or rng is None:
        top, left = (h - oh) // 2, (w - ow) // 2
        return jax.lax.slice(x, (0, top, left, 0),
                             (b, top + oh, left + ow, c))
    k_top, k_left, k_flip = jax.random.split(rng, 3)
    tops = jax.random.randint(k_top, (b,), 0, h - oh + 1)
    lefts = jax.random.randint(k_left, (b,), 0, w - ow + 1)

    def crop_one(img, top, left):
        return jax.lax.dynamic_slice(img, (top, left, 0), (oh, ow, c))

    out = jax.vmap(crop_one)(x, tops, lefts)
    if flip:
        mirror = jax.random.bernoulli(k_flip, 0.5, (b,))
        out = jnp.where(mirror[:, None, None, None], out[:, :, ::-1, :], out)
    return out


# ----------------------------------------------------------------- kohonen
def kohonen_distances(x, weights):
    """Squared euclidean distances (mb, n_neurons) between samples and SOM
    codebook vectors; the cross term is a GEMM so the MXU carries the load.
    Ref: veles/znicz/kohonen.py [H] (SURVEY §2.3)."""
    x = x.reshape(x.shape[0], -1)
    x2 = (x * x).sum(axis=1)[:, None]
    w2 = (weights * weights).sum(axis=1)[None, :]
    return x2 - 2.0 * matmul(x, weights.T) + w2


def kohonen_winners(x, weights):
    """(winner_index, min_sq_distance) per sample — the SOM forward."""
    d = kohonen_distances(x, weights)
    return jnp.argmin(d, axis=1), d.min(axis=1)


def kohonen_update(weights, x, mask, grid, learning_rate, sigma):
    """One batch SOM update: each neuron moves toward the samples it (or a
    grid neighbor) won, weighted by a Gaussian neighborhood.

        w_n += lr/B * Σ_b h(b, n) (x_b - w_n),
        h(b, n) = exp(-||grid_n - grid_win(b)||² / (2σ²))

    Batch-parallel reformulation of the reference's per-sample "gravity"
    kernel (ref: veles/znicz/kohonen.py::KohonenTrainer + ocl kernels [H]);
    both matmuls (winner search + neighborhood gather) hit the MXU.

    Returns (new_weights, metrics) with the quantization-error sum
    (mean min-distance is the SOM's convergence measure).
    """
    x = x.reshape(x.shape[0], -1)
    d = kohonen_distances(x, weights)
    winners = jnp.argmin(d, axis=1)
    qe_sum = (jnp.sqrt(jnp.maximum(d.min(axis=1), 0.0)) * mask).sum()
    wcoord = jnp.take(grid, winners, axis=0)            # (mb, 2)
    gd2 = ((grid[None, :, :] - wcoord[:, None, :]) ** 2).sum(-1)
    h = jnp.exp(-gd2 / (2.0 * sigma * sigma)) * mask[:, None]
    batch = jnp.maximum(mask.sum(), 1.0)
    num = matmul(h.T, x)                                # (n_neurons, n_in)
    den = h.sum(axis=0)[:, None]
    new_w = weights + learning_rate * (num - den * weights) / batch
    return new_w, {"qe_sum": qe_sum, "loss_sum": qe_sum}


# ---------------------------------------------------------------------- rbm
def rbm_hidden(v, weights, hbias):
    """P(h=1 | v) — sigmoid(v @ W + hb).  Ref: veles/znicz/rbm_units.py [M]
    (SURVEY §2.3): the reference split CD over several units (Binarization,
    BatchWeights, GradientsCalculator, WeightsUpdater); here the whole CD-k
    step is one fused function (rbm_cd_step)."""
    return jax.nn.sigmoid(matmul(v.reshape(v.shape[0], -1), weights) + hbias)


def rbm_visible(h, weights, vbias):
    """P(v=1 | h) — sigmoid(h @ W^T + vb)."""
    return jax.nn.sigmoid(matmul(h, weights.T) + vbias)


def rbm_cd_step(weights, vbias, hbias, v0, mask, rng, learning_rate,
                cd_k=1):
    """One contrastive-divergence (CD-k) update on a (0/1-ish) batch.

    Positive phase from the data, negative phase from k Gibbs steps with
    Bernoulli-sampled hiddens (probabilities, not samples, are used for the
    final statistics — standard Hinton recipe, matching the reference's
    gradient calculator).  Gradients are batch means; masked rows contribute
    nothing.  Returns (new_w, new_vb, new_hb, metrics) with the summed
    per-sample reconstruction error.
    """
    v0 = v0.reshape(v0.shape[0], -1)
    m = mask[:, None]
    batch = jnp.maximum(mask.sum(), 1.0)
    h0 = rbm_hidden(v0, weights, hbias)
    vk, hk = v0, h0
    for i in range(cd_k):
        h_samp = jax.random.bernoulli(
            jax.random.fold_in(rng, i), hk).astype(v0.dtype)
        vk = rbm_visible(h_samp, weights, vbias)
        hk = rbm_hidden(vk, weights, hbias)
    grad_w = (matmul((v0 * m).T, h0) - matmul((vk * m).T, hk)) / batch
    grad_vb = ((v0 - vk) * m).sum(axis=0) / batch
    grad_hb = ((h0 - hk) * m).sum(axis=0) / batch
    recon = jnp.sqrt((((v0 - vk) * m) ** 2).sum(axis=1))
    return (weights + learning_rate * grad_w,
            vbias + learning_rate * grad_vb,
            hbias + learning_rate * grad_hb,
            {"recon_sum": recon.sum(), "loss_sum": recon.sum()})


# ------------------------------------------------------------------- updates
#: "xla" (default) or "pallas" — routes sgd_update through the fused Pallas
#: kernel (ops/pallas_kernels.py).  Benchmarked against each other on TPU by
#: bench.py's sgd_update record; the default stays whichever wins there.
_SGD_BACKEND = "xla"


def set_sgd_backend(mode):
    """mode: 'xla' | 'pallas'.  Clears jit caches (trace-time flag) —
    only on an actual change (see set_matmul_precision)."""
    global _SGD_BACKEND
    if mode not in ("xla", "pallas"):
        raise ValueError("unknown sgd backend %r" % (mode,))
    if mode == _SGD_BACKEND:
        return
    _SGD_BACKEND = mode
    jax.clear_caches()


def sgd_update(param, velocity, grad, batch_size, learning_rate, momentum,
               weight_decay, l1_vs_l2, gradient_clip):
    """Momentum-SGD with mixed L1/L2 decay and optional clipping.

    Matches the reference's per-unit update options (lr, momentum,
    weight-decay with l1_vs_l2 mix, clipping — ref: veles/znicz/
    nn_units.py::GradientDescentBase [H]).  Gradients arrive as batch SUMS
    and are normalized by the live batch size here.
    """
    if (_SGD_BACKEND == "pallas"
            and not gradient_clip):   # the kernel has no clipping path
        from veles_tpu.ops.pallas_kernels import fused_sgd_update
        return fused_sgd_update(param, velocity, grad, batch_size,
                                learning_rate, momentum, weight_decay,
                                l1_vs_l2)
    g = _effective_grad(param, grad, batch_size, weight_decay, l1_vs_l2,
                        gradient_clip)
    velocity = momentum * velocity - learning_rate * g
    return param + velocity, velocity


def _effective_grad(param, grad, batch_size, weight_decay, l1_vs_l2,
                    gradient_clip):
    """Batch-normalized gradient + mixed L1/L2 decay + optional clipping —
    the preprocessing every solver shares (ref: veles/znicz/nn_units.py::
    GradientDescentBase options [H])."""
    g = grad / jnp.maximum(batch_size, 1).astype(grad.dtype)
    if gradient_clip is not None and gradient_clip > 0.0:
        g = jnp.clip(g, -gradient_clip, gradient_clip)
    if weight_decay:
        decay = (l1_vs_l2 * jnp.sign(param)
                 + (1.0 - l1_vs_l2) * param)
        g = g + weight_decay * decay
    return g


def adaptive_update(param, velocity, accum, grad, batch_size, learning_rate,
                    momentum, weight_decay, l1_vs_l2, gradient_clip,
                    solver="momentum", rho=0.95, epsilon=1e-6, step=0):
    """Per-parameter update with a selectable solver.

    The reference's ``GradientDescentBase`` carried ADADELTA-style adaptive
    options alongside plain momentum SGD (ref: veles/znicz/nn_units.py::
    GradientDescentBase [H]); this is the TPU-side family, one pure function
    so every solver traces into the fused step identically.

    - ``momentum``: classic velocity SGD (delegates to :func:`sgd_update`,
      which keeps the Pallas fast path).  ``accum`` is ignored.
    - ``adagrad``: ``accum += g²``; ``param -= lr·g/√(accum+ε)``.
      ``velocity`` is ignored.
    - ``adadelta``: ``accum = ρ·accum+(1-ρ)·g²``;
      ``Δx = -lr·√(velocity+ε)/√(accum+ε)·g``;
      ``velocity = ρ·velocity+(1-ρ)·Δx²`` — the velocity slot doubles as
      the E[Δx²] memory, so snapshots stay two-arrays-per-param.
      ``lr`` is the reference-style global multiplier (1.0 = paper form).
    - ``adam`` (beyond parity): first/second-moment estimates in the
      velocity/accum slots with bias correction from the traced global
      ``step``; β1 = ``momentum`` (None/unset means the standard 0.9;
      an explicit 0.0 turns first-moment smoothing off), β2 =
      ``rho`` (set ``solver_rho=0.999`` for the paper constants), ε =
      ``epsilon``.

    Returns ``(param, velocity, accum)``; pass-through slots come back
    unchanged so the fused state pytree keeps a static structure.
    """
    if solver == "momentum":
        new_p, new_v = sgd_update(param, velocity, grad, batch_size,
                                  learning_rate,
                                  0.0 if momentum is None else momentum,
                                  weight_decay, l1_vs_l2, gradient_clip)
        return new_p, new_v, accum
    g = _effective_grad(param, grad, batch_size, weight_decay, l1_vs_l2,
                        gradient_clip)
    if solver == "adagrad":
        accum = accum + g * g
        return (param - learning_rate * g / jnp.sqrt(accum + epsilon),
                velocity, accum)
    if solver == "adadelta":
        accum = rho * accum + (1.0 - rho) * g * g
        dx = -learning_rate * (jnp.sqrt(velocity + epsilon)
                               / jnp.sqrt(accum + epsilon)) * g
        velocity = rho * velocity + (1.0 - rho) * dx * dx
        return param + dx, velocity, accum
    if solver == "adam":
        # None (unset) means the standard 0.9; an EXPLICIT momentum=0.0 is
        # a legal value (first-moment smoothing off, RMSProp-style) — a
        # truthiness test here would silently promote it to 0.9
        beta1 = 0.9 if momentum is None else momentum
        t = jnp.asarray(step, param.dtype) + 1.0
        velocity = beta1 * velocity + (1.0 - beta1) * g
        accum = rho * accum + (1.0 - rho) * g * g
        m_hat = velocity / (1.0 - beta1 ** t)
        v_hat = accum / (1.0 - jnp.asarray(rho, param.dtype) ** t)
        return (param - learning_rate * m_hat
                / (jnp.sqrt(v_hat) + epsilon),
                velocity, accum)
    raise ValueError("unknown solver %r" % (solver,))

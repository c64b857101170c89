"""Plain reference for AlexNet training (Krizhevsky, Sutskever, Hinton 2012)
as the configuration file states it: random 227x227 crop and mirror of a
256x256x3 image, five convolutions with ReLU, local response normalisation
after the first two, three 3x3/2 max-poolings, two dropout + dense layers and
a 1000-way softmax; summed cross-entropy; SGD with momentum and weight decay
on the weights.  Straightforward ``jax.numpy`` in float32 with ``jax.grad``;
no fused step, no scan, no kernels.  It imports nothing of ``veles_tpu`` and
makes its own weights from the seed.

Stochastic draws.  The timed program draws its crops and dropout masks from a
key per window, folded by the minibatch's row in the window and then by the
layer's index (``epoch_driver.py``, ``compiled.py``).  A comparison needs the
same draws, so this file restates that rule: ``fold_in(fold_in(window_key,
row), layer)``, the crop's key split three ways into tops, lefts and mirrors,
a dropout mask as one Bernoulli(keep) draw of the activation's shape.  Only a
change to the program could hand the draws out instead (PERF.md, open
questions)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = {
    "highest": jax.lax.Precision.HIGHEST,
    "high": jax.lax.Precision.HIGH,
    "default": jax.lax.Precision.DEFAULT,
}


def seed_key(seed):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def window_key(seed, call):
    """The key of the ``call``-th window of the first steps."""
    return jax.random.fold_in(seed_key(seed), 1000 + call)


def shapes(cfg):
    """[(layer index, weight shape, bias shape)] of the parameterised layers,
    walking the activation shape through ``cfg["layers"]``."""
    h, w = cfg["crop"]
    c = cfg["image"][2]
    flat = None
    out = []
    for i, layer in enumerate(cfg["layers"]):
        kind = layer["type"]
        if kind == "conv":
            k, s = layer["k"], layer["stride"]
            out.append((i, (k, k, c, layer["n"]), (layer["n"],)))
            if layer["padding"] == "SAME":
                h, w = -(-h // s), -(-w // s)
            else:
                h, w = (h - k) // s + 1, (w - k) // s + 1
            c = layer["n"]
        elif kind == "pool":
            k, s = layer["k"], layer["stride"]
            h, w = (h - k) // s + 1, (w - k) // s + 1
        elif kind in ("dense", "softmax"):
            n_in = flat if flat is not None else h * w * c
            out.append((i, (n_in, layer["n"]), (layer["n"],)))
            flat = layer["n"]
    return out


def make_weights(seed, cfg):
    """{layer index: {"w", "b"}} in float32 on the device, one jitted call.
    By the configuration's ``init``: weights normal with ``gain`` times the He
    scale sqrt(2 / fan_in) (so that no layer's gradient dies on noise
    images), the softmax layer's with ``softmax_std`` (small, so that the
    first steps start from an even prediction and train stably), biases
    normal(0, ``bias_std``)."""
    spec = shapes(cfg)
    init = cfg["init"]
    last = spec[-1][0]

    @jax.jit
    def build(key):
        out = {}
        for (i, w_shape, b_shape), k in zip(
                spec, jax.random.split(key, len(spec))):
            kw, kb = jax.random.split(k)
            fan_in = 1
            for n in w_shape[:-1]:
                fan_in *= n
            std = (init["softmax_std"] if i == last
                   else init["gain"] * (2.0 / fan_in) ** 0.5)
            out[i] = {
                "w": std * jax.random.normal(kw, w_shape, jnp.float32),
                "b": init["bias_std"]
                * jax.random.normal(kb, b_shape, jnp.float32)}
        return out

    return build(jax.random.fold_in(seed_key(seed), 1))


def crop_flip(x, key, out_hw):
    b, h, w, c = x.shape
    oh, ow = out_hw
    k_top, k_left, k_flip = jax.random.split(key, 3)
    tops = jax.random.randint(k_top, (b,), 0, h - oh + 1)
    lefts = jax.random.randint(k_left, (b,), 0, w - ow + 1)
    out = jax.vmap(lambda img, t, l: jax.lax.dynamic_slice(
        img, (t, l, 0), (oh, ow, c)))(x, tops, lefts)
    mirror = jax.random.bernoulli(k_flip, 0.5, (b,))
    return jnp.where(mirror[:, None, None, None], out[:, :, ::-1, :], out)


def lrn(x, p):
    n, c = p["n"], x.shape[-1]
    sq = jnp.pad(x * x, [(0, 0)] * (x.ndim - 1) + [(n // 2, n // 2)])
    total = sum(jax.lax.slice_in_dim(sq, i, i + c, axis=-1)
                for i in range(n))
    return x / (p["k"] + (p["alpha"] / n) * total) ** p["beta"]


def conv(x, w, stride, padding, precision):
    """``bfloat16`` casts operands and result (what a CPU test can hold; on
    the chip ``default`` is the one-pass form); the named precisions do not."""
    dims = ("NHWC", "HWIO", "NHWC")
    if precision == "bfloat16":
        return jax.lax.conv_general_dilated(
            x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), (stride, stride),
            padding, dimension_numbers=dims).astype(jnp.float32)
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding, dimension_numbers=dims,
        precision=PRECISIONS[precision])


def dense(x, w, precision):
    if precision == "bfloat16":
        return jnp.matmul(x.astype(jnp.bfloat16),
                          w.astype(jnp.bfloat16)).astype(jnp.float32)
    return jnp.matmul(x, w, precision=PRECISIONS[precision])


def forward(params, x, key, cfg, precision):
    """Logits of one minibatch ``x`` (b, H, W, 3) float32, training mode."""
    h = x
    for i, layer in enumerate(cfg["layers"]):
        kind = layer["type"]
        k = jax.random.fold_in(key, i)
        if kind == "crop":
            h = crop_flip(h, k, tuple(cfg["crop"]))
        elif kind == "conv":
            h = conv(h, params[i]["w"], layer["stride"], layer["padding"],
                     precision)
            h = jnp.maximum(h + params[i]["b"], 0.0)
        elif kind == "lrn":
            h = lrn(h, cfg["lrn"])
        elif kind == "pool":
            kk, s = layer["k"], layer["stride"]
            h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                      (1, kk, kk, 1), (1, s, s, 1), "VALID")
        elif kind == "dropout":
            keep = 1.0 - layer["rate"]
            mask = jax.random.bernoulli(k, keep, h.shape)
            h = jnp.where(mask, h / keep, 0.0)
        elif kind == "dense":
            h = h.reshape(h.shape[0], -1)
            h = jnp.maximum(dense(h, params[i]["w"], precision)
                            + params[i]["b"], 0.0)
        elif kind == "softmax":
            h = h.reshape(h.shape[0], -1)
            h = dense(h, params[i]["w"], precision) + params[i]["b"]
    return h


def loss_sum(params, x, y, key, cfg, precision):
    logp = jax.nn.log_softmax(forward(params, x, key, cfg, precision))
    return -jnp.take_along_axis(logp, y[:, None], axis=-1).sum()


def make_step(cfg, precision="highest"):
    """Jitted ``(params, velocity, x, y, key) -> (params, velocity, summed
    loss)``: one SGD step on one minibatch.  ``v = m v - lr (g / b + wd w)``
    for weights, the same without decay for biases; ``p += v``."""
    opt = cfg["sgd"]

    @jax.jit
    def step(params, velocity, x, y, key):
        loss, grads = jax.value_and_grad(loss_sum)(params, x, y, key, cfg,
                                                   precision)
        bs = x.shape[0]
        new_p, new_v = {}, {}
        for i in params:
            gw = grads[i]["w"] / bs + opt["weight_decay"] * params[i]["w"]
            gb = grads[i]["b"] / bs
            vw = (opt["momentum"] * velocity[i]["w"]
                  - opt["learning_rate"] * gw)
            vb = (opt["momentum"] * velocity[i]["b"]
                  - opt["learning_rate"] * gb)
            new_v[i] = {"w": vw, "b": vb}
            new_p[i] = {"w": params[i]["w"] + vw, "b": params[i]["b"] + vb}
        return new_p, new_v, loss

    return step


def window(step, params, velocity, x, y, key):
    """The minibatches ``x`` (rows, b, H, W, 3), ``y`` (rows, b) one after
    the other through ``step``, row ``r`` keyed ``fold_in(key, r)``; the
    summed loss of all."""
    total = 0.0
    for r in range(x.shape[0]):
        params, velocity, loss = step(params, velocity, x[r], y[r],
                                      jax.random.fold_in(key, r))
        total = total + loss
    return params, velocity, total


def zeros_like(params):
    return jax.tree.map(jnp.zeros_like, params)

"""Mixture-of-Experts FFN with expert parallelism.

Beyond-parity (the reference pre-dates MoE entirely; SURVEY §2.5 lists
DP as its only strategy): a top-1-routed expert FFN usable in place of the
transformer's dense FFN, plus an expert-parallel execution where the
expert weights are sharded over an ``expert`` mesh axis — each device
holds E/n experts, computes their contribution for the whole batch, and
the combine is one ``psum`` over the axis (XLA collective over ICI).

Design notes (TPU-first):
- routing is computed identically on every device (replicated GEMM, tiny);
- dispatch is mask-based with static shapes (no sorting / dynamic sizes —
  XLA-friendly, capacity factor 1.0 over the full token count);
- the straight-through gate scales each token's output by its router
  probability, so the router receives gradients through the scale.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from veles_tpu.ops import functional as F


def init_moe_params(stream, d_model, d_ff, n_experts, dtype="float32"):
    """Router + per-expert FFN weights (expert-major leading axis —
    the shardable form)."""
    import numpy

    def fill(shape, fan_in, fan_out):
        w = numpy.zeros(shape, dtype)
        s = (6.0 / (fan_in + fan_out)) ** 0.5
        stream.fill(w, -s, s)
        return w

    return {
        "router": fill((d_model, n_experts), d_model, n_experts),
        "w1": fill((n_experts, d_model, d_ff), d_model, d_ff),
        "b1": numpy.zeros((n_experts, d_ff), dtype),
        "w2": fill((n_experts, d_ff, d_model), d_ff, d_model),
        "b2": numpy.zeros((n_experts, d_model), dtype),
    }


def router_probs(params, x):
    """(tokens, E) softmax router probabilities; x: (..., d_model) is
    flattened to tokens."""
    flat = x.reshape(-1, x.shape[-1])
    return jax.nn.softmax(F.matmul(flat, params["router"]), axis=-1)


def _expert_ffn(w1, b1, w2, b2, x):
    """One expert's FFN over all tokens: (T, d) -> (T, d)."""
    h = jnp.maximum(F.matmul(x, w1) + b1, 0.0)
    return F.matmul(h, w2) + b2


def load_balancing_loss(probs, onehot, token_mask=None):
    """Switch-Transformer-style auxiliary loss: E * Σ_e f_e · P_e, where
    f_e is the fraction of tokens routed to expert e and P_e the mean
    router probability of e.  Equals 1.0 at perfect balance and grows as
    routing collapses — without it, top-1 routing degenerates onto one
    expert (the router gradient only flows through chosen experts).
    ``token_mask`` (T,) restricts the statistics to live tokens (padded
    rows must not steer the router)."""
    if token_mask is not None:
        m = token_mask[:, None].astype(probs.dtype)
        denom = jnp.maximum(m.sum(), 1.0)
        f = (onehot * m).sum(axis=0) / denom
        p = (probs * m).sum(axis=0) / denom
    else:
        f = onehot.mean(axis=0)      # (E,) routed fraction
        p = probs.mean(axis=0)       # (E,) mean router prob
    return probs.shape[-1] * jnp.sum(f * p)


def moe_ffn(params, x, return_aux=False, token_mask=None):
    """Top-1 routed MoE FFN, single device: every expert runs over the
    full token set, masked combine keeps only each token's chosen expert
    (static shapes; the EP path partitions the expert loop instead).
    ``return_aux=True`` also returns the load-balancing loss (over live
    tokens only when ``token_mask`` is given)."""
    shape = x.shape
    flat = x.reshape(-1, shape[-1])
    probs = router_probs(params, x)                   # (T, E)
    top = jnp.argmax(probs, axis=-1)                  # (T,)
    gate = jnp.take_along_axis(probs, top[:, None], axis=-1)  # (T, 1)
    onehot = jax.nn.one_hot(top, probs.shape[-1], dtype=flat.dtype)

    expert_out = jax.vmap(_expert_ffn, in_axes=(0, 0, 0, 0, None))(
        params["w1"], params["b1"], params["w2"], params["b2"], flat)
    # combine: token t takes expert top[t]'s row, scaled by its gate
    out = (jnp.einsum("etd,te->td", expert_out, onehot)
           * gate).reshape(shape)
    if return_aux:
        return out, load_balancing_loss(probs, onehot, token_mask)
    return out


def moe_ffn_ep(params, x, mesh, expert_axis="expert"):
    """Expert-parallel MoE FFN: expert weights sharded over
    ``expert_axis``; each device computes its LOCAL experts' masked
    contribution for the whole batch and the combine is one psum.
    Numerically equals :func:`moe_ffn`.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = mesh.shape[expert_axis]
    n_experts = params["w1"].shape[0]
    if n_experts % n:
        raise ValueError("n_experts %d %% mesh axis %d != 0"
                         % (n_experts, n))
    shape = x.shape

    def run(router, w1, b1, w2, b2, xloc):
        flat = xloc.reshape(-1, xloc.shape[-1])
        probs = jax.nn.softmax(F.matmul(flat, router), axis=-1)
        top = jnp.argmax(probs, axis=-1)
        gate = jnp.take_along_axis(probs, top[:, None], axis=-1)
        onehot = jax.nn.one_hot(top, probs.shape[-1], dtype=flat.dtype)
        # my slice of the one-hot dispatch: experts [lo, lo + E/n)
        lo = jax.lax.axis_index(expert_axis) * w1.shape[0]
        local_mask = jax.lax.dynamic_slice_in_dim(
            onehot, lo, w1.shape[0], axis=1)          # (T, E/n)
        expert_out = jax.vmap(_expert_ffn, in_axes=(0, 0, 0, 0, None))(
            w1, b1, w2, b2, flat)                     # (E/n, T, d)
        local = jnp.einsum("etd,te->td", expert_out, local_mask)
        out = jax.lax.psum(local, expert_axis) * gate
        return out.reshape(xloc.shape)

    espec = P(expert_axis)
    fn = jax.shard_map(run, mesh=mesh,
                       in_specs=(P(), espec, espec, espec, espec, P()),
                       out_specs=P(), check_vma=False)
    put = lambda a: jax.device_put(a, NamedSharding(mesh, espec))  # noqa
    return fn(jax.device_put(params["router"], NamedSharding(mesh, P())),
              put(params["w1"]), put(params["b1"]),
              put(params["w2"]), put(params["b2"]), x)

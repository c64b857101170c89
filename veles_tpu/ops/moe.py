"""Mixture-of-Experts feed forward: ONE routed implementation (ROADMAP D7).

Beyond-parity (the reference pre-dates MoE entirely; SURVEY §2.5 lists
DP as its only strategy).  A token's router scores choose ``top_k`` of
``router_width`` experts; the layer is told which of them it HOLDS
(``held = (lo, n)``: all of them on one device, a contiguous share under
expert parallelism) and computes its own experts' part of the sum for the
tokens routed to them.  What the absent experts would add is another
chip's; no code stands in for it here.

How (static shapes, no token dropped): the ``tokens x top_k`` assignments
are sorted by expert, the held ones first; the sorted token rows go
through a GROUPED matmul (``jax.lax.ragged_dot``: row block *e* times
expert *e*'s matrix), which reads the matrices of the experts that were
hit and no others; rows of assignments held elsewhere fall behind the last
group and are masked out.  The routing weight scales each assignment's row
on the way back, so the router receives gradients through it.

Two parameterisations share it:

- the repo's first expert layer (``init_moe_params``): softmax scores,
  top-1, the score itself as weight, ReLU experts with biases
  (``w1, b1, w2, b2``), every expert held — or an ``expert`` mesh axis's
  share each, combined by one ``psum`` (:func:`moe_ffn_ep`);
- the sigmoid-routed layer of ``model_config.MoEConfig``: scores
  ``sigmoid(x W_r)`` in float32, a selection bias that enters the choice
  only, weights normalised over the chosen and scaled, gated-SiLU experts
  without biases (``w_gate, w_up, w_down``) beside a shared expert.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from veles_tpu.ops import functional as F
from veles_tpu.model_config import MoEConfig

#: added to the sum of the chosen scores before it divides them
ROUTE_NORM_EPS = 1e-20


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


def top1_softmax(n_experts):
    """The record of the repo's first expert layer."""
    return MoEConfig(router_width=n_experts)


# ------------------------------------------------------------------ routing
def router_scores(params, flat, moe):
    """(tokens, router_width) scores in float32."""
    if flat.dtype == jnp.float32:
        logits = F.matmul(flat, params["router"].astype(jnp.float32))
    else:
        logits = jnp.matmul(flat, params["router"],
                            preferred_element_type=jnp.float32)
    if moe.score == "sigmoid":
        return jax.nn.sigmoid(logits)
    return jax.nn.softmax(logits, axis=-1)


def router_probs(params, x):
    """(tokens, E) softmax router probabilities; x: (..., d_model) is
    flattened to tokens."""
    flat = x.reshape(-1, x.shape[-1])
    return router_scores(params, flat, top1_softmax(
        params["router"].shape[-1]))


def route(params, flat, moe):
    """(scores (T, E) float32, chosen experts (T, k) int32, their weights
    (T, k) float32).  The selection bias (``params["bias"]``, where the
    tree has one) enters the choice and not the weight."""
    scores = router_scores(params, flat, moe)
    choice = scores
    if "bias" in params:
        choice = scores + params["bias"].astype(jnp.float32)
    _, idx = jax.lax.top_k(choice, moe.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if moe.route_norm:
        w = w / (w.sum(-1, keepdims=True) + ROUTE_NORM_EPS)
    if moe.route_scale != 1.0:
        w = w * moe.route_scale
    return scores, idx.astype(jnp.int32), w


# ------------------------------------------------------------- the experts
def grouped_matmul(xs, w, sizes):
    """Row block *e* of ``xs`` (``sizes[e]`` rows, in order) times ``w[e]``;
    rows behind the last group come back as they may and are the caller's
    to mask.  float32 follows ``functional``'s policy; a narrower dtype
    accumulates in float32 and rounds once."""
    if xs.dtype == jnp.float32:
        return jax.lax.ragged_dot(xs, w, sizes, precision=F._PRECISION)
    return jax.lax.ragged_dot(
        xs, w, sizes, preferred_element_type=jnp.float32).astype(xs.dtype)


def _experts(params, xs, sizes, expert_of_row):
    """The held experts' FFN over the sorted rows: gated SiLU where the
    tree carries ``w_gate``, else ReLU with the row's expert's biases."""
    if "w_gate" in params:
        gate = grouped_matmul(xs, params["w_gate"], sizes)
        up = grouped_matmul(xs, params["w_up"], sizes)
        hidden = (jax.nn.silu(gate.astype(jnp.float32))
                  * up.astype(jnp.float32)).astype(xs.dtype)
        return grouped_matmul(hidden, params["w_down"], sizes)
    hidden = jnp.maximum(grouped_matmul(xs, params["w1"], sizes)
                         + params["b1"][expert_of_row], 0.0)
    return grouped_matmul(hidden, params["w2"], sizes) \
        + params["b2"][expert_of_row]


def gated_ffn(params, x, matmul):
    """``(silu(x W_gate) * (x W_up)) W_down``: the dense feed forward and
    the shared expert of the ``sandwich`` block."""
    gate = matmul(x, params["w_gate"]).astype(jnp.float32)
    up = matmul(x, params["w_up"]).astype(jnp.float32)
    return matmul((jax.nn.silu(gate) * up).astype(x.dtype),
                  params["w_down"])


def held_part(params, flat, idx, w, lo, n):
    """The held experts' part of the routed sum, (T, d), and what the
    layer counted: int32 ``[assignments held, assignments elsewhere,
    experts hit, largest expert load]``.  ``lo`` may be traced (a mesh
    axis's share); ``n``, the number held, is the expert axis of the
    tree."""
    tokens, k = idx.shape
    local = idx - lo
    mine = (local >= 0) & (local < n)
    key = jnp.where(mine, local, n).reshape(-1)         # (T·k,), n = away
    order = jnp.argsort(key, stable=True)
    sorted_key = key[order]
    sizes = jnp.zeros(n + 1, jnp.int32).at[key].add(1)[:n]
    with jax.named_scope("moe.experts"):
        ys = _experts(params, flat[order // k], sizes,
                      jnp.minimum(sorted_key, n - 1))
    held_row = (sorted_key < n)[:, None]
    ws = jnp.where(mine, w, 0.0).reshape(-1)[order]
    ys = jnp.where(held_row, ys.astype(jnp.float32), 0.0) * ws[:, None]
    # back to token order: assignment a's row sits at inverse[a]
    inverse = jnp.argsort(order)
    out = ys[inverse].reshape(tokens, k, -1).sum(1)
    held = mine.sum().astype(jnp.int32)
    stats = jnp.stack([held, jnp.int32(tokens * k) - held,
                       (sizes > 0).sum().astype(jnp.int32), sizes.max()])
    return out.astype(flat.dtype), stats


def routed_ffn(params, x, moe, matmul=None, router_in=None):
    """The routed feed forward of one layer over ``x`` (..., d): the held
    experts' part of the routed sum, plus the shared expert where the
    record names one.  ``router_in`` is what the router scores, where that
    is not ``x`` (the same activations before they were rounded to the
    model's dtype).  Returns (out, stats) — see :func:`held_part`."""
    shape = x.shape
    flat = x.reshape(-1, shape[-1])
    with jax.named_scope("moe.router"):
        _, idx, w = route(params, flat if router_in is None
                          else router_in.reshape(flat.shape), moe)
    lo, _ = moe.held_range()
    out, stats = held_part(params, flat, idx, w, lo,
                           _n_held(params))
    if moe.shared:
        with jax.named_scope("moe.shared"):
            out = out + gated_ffn(params["shared"], flat, matmul)
    return out.reshape(shape), stats


def _n_held(params):
    return (params["w_gate"] if "w_gate" in params
            else params["w1"]).shape[0]


# ------------------------------------------------------- the first layer
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
    """Top-1 softmax-routed MoE FFN with every expert held: the routed
    implementation at ``MoEConfig(router_width=E)``.  ``return_aux=True``
    also returns the load-balancing loss (over live tokens only when
    ``token_mask`` is given)."""
    shape = x.shape
    flat = x.reshape(-1, shape[-1])
    n_experts = params["router"].shape[-1]
    probs, idx, w = route(params, flat, top1_softmax(n_experts))
    out, _ = held_part(params, flat, idx, w, 0, n_experts)
    out = out.reshape(shape)
    if return_aux:
        onehot = jax.nn.one_hot(idx[:, 0], n_experts, dtype=flat.dtype)
        return out, load_balancing_loss(probs, onehot, token_mask)
    return out


def moe_ffn_ep(params, x, mesh, expert_axis="expert"):
    """Expert-parallel MoE FFN: expert weights sharded over
    ``expert_axis``; every device routes alike (a replicated, tiny GEMM),
    computes the part of the experts it holds for the whole batch, and
    the combine is one psum.  Numerically equals :func:`moe_ffn`.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = mesh.shape[expert_axis]
    n_experts = params["w1"].shape[0]
    if n_experts % n:
        raise ValueError("n_experts %d %% mesh axis %d != 0"
                         % (n_experts, n))
    moe = top1_softmax(n_experts)

    def run(router, w1, b1, w2, b2, xloc):
        flat = xloc.reshape(-1, xloc.shape[-1])
        _, idx, w = route({"router": router}, flat, moe)
        lo = jax.lax.axis_index(expert_axis) * w1.shape[0]
        local, _ = held_part({"w1": w1, "b1": b1, "w2": w2, "b2": b2},
                             flat, idx, w, lo, w1.shape[0])
        return jax.lax.psum(local, expert_axis).reshape(xloc.shape)

    espec = P(expert_axis)
    fn = jax.shard_map(run, mesh=mesh,
                       in_specs=(P(), espec, espec, espec, espec, P()),
                       out_specs=P(), check_vma=False)
    put = lambda a: jax.device_put(a, NamedSharding(mesh, espec))  # noqa
    return fn(jax.device_put(params["router"], NamedSharding(mesh, P())),
              put(params["w1"]), put(params["b1"]),
              put(params["w2"]), put(params["b2"]), x)

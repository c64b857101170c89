"""Pipeline parallelism (GPipe) for the uniform transformer block stack.

Beyond-parity capability (the reference scaled by data parallelism only —
SURVEY §2.5): the L decoder blocks are stacked along a leading layer axis,
that axis is sharded over the mesh's ``stage`` axis (each device owns
L/S contiguous blocks), and microbatches stream through the stages with
``lax.ppermute`` hops between neighbors — the classic GPipe schedule
expressed the TPU way: one ``shard_map`` program, activations riding ICI.

The backward pass needs no hand scheduling: `jax.grad` through
``shard_map`` + ``ppermute`` transposes the permutes, so the cooldown of
the reverse pipeline is derived automatically.

Embedding/positional/final-LN/head stay OUTSIDE the pipeline (replicated,
cheap); only the uniform block stack is staged — the shapes through every
stage are identical, which is what makes the single-program formulation
possible (and is why PP targets the transformer family, not the
heterogeneous conv stacks — those scale with DP/TP instead).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def make_pipeline_mesh(n_stages, devices=None):
    """1-axis ('stage',) mesh over the first n_stages devices."""
    import numpy
    from jax.sharding import Mesh
    devices = list(devices if devices is not None else jax.devices())
    if n_stages > len(devices):
        raise ValueError("need %d devices, have %d"
                         % (n_stages, len(devices)))
    return Mesh(numpy.array(devices[:n_stages]), ("stage",))


def stack_blocks(blocks):
    """[per-block param dict] -> one pytree with a leading (L,) layer axis
    (the shardable form; L % n_stages must be 0)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)


def unstack_blocks(stacked, n_layers):
    """Inverse of stack_blocks (snapshot/restore round-trips)."""
    return [jax.tree.map(lambda x: x[i], stacked) for i in range(n_layers)]


def _stage_body(local_blocks, h, n_heads, block_size):
    """Apply this stage's L/S blocks sequentially (scan over the local
    slice of the layer axis)."""
    from veles_tpu.ops.transformer import block_forward

    def body(carry, blk):
        return block_forward(blk, carry, n_heads, block_size), None

    h, _ = jax.lax.scan(body, h, local_blocks)
    return h


def pipeline_blocks(stacked_blocks, h, mesh, n_heads, n_microbatches,
                    block_size=None):
    """Run the block stack over ``h`` (batch, seq, d) with the GPipe
    schedule on ``mesh``'s ``stage`` axis; returns the transformed
    activations, numerically identical to the sequential loop.

    batch must divide by n_microbatches; the layer axis of
    ``stacked_blocks`` must divide by the stage count.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_stages = mesh.shape["stage"]
    n_layers = jax.tree.leaves(stacked_blocks)[0].shape[0]
    if n_layers % n_stages:
        raise ValueError("n_layers %d %% n_stages %d != 0"
                         % (n_layers, n_stages))
    batch = h.shape[0]
    if batch % n_microbatches:
        raise ValueError("batch %d %% n_microbatches %d != 0"
                         % (batch, n_microbatches))
    x = h.reshape((n_microbatches, batch // n_microbatches) + h.shape[1:])

    def run(local_blocks, xloc):
        stage = jax.lax.axis_index("stage")
        n = jax.lax.psum(1, "stage")
        m = xloc.shape[0]
        perm = [(i, (i + 1) % n) for i in range(n)]

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t during warmup+steady ticks
            inject = jax.lax.dynamic_index_in_dim(
                xloc, jnp.clip(t, 0, m - 1), 0, keepdims=False)
            h_in = jnp.where(stage == 0, inject, buf)
            h_out = _stage_body(local_blocks, h_in, n_heads, block_size)
            # the last stage finishes microbatch t-(S-1) at tick t.
            # Select only the SLOT, then update unconditionally — a where
            # around the whole buffer would defeat XLA's in-place
            # dynamic-update inside the loop (full copy per tick)
            out_t = t - (n - 1)
            write = jnp.logical_and(stage == n - 1,
                                    jnp.logical_and(out_t >= 0, out_t < m))
            slot_index = jnp.clip(out_t, 0, m - 1)
            slot = jax.lax.dynamic_index_in_dim(outs, slot_index, 0,
                                                keepdims=False)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(write, h_out, slot), slot_index, 0)
            # activation hop to the next stage (ICI neighbor copy)
            buf = jax.lax.ppermute(h_out, "stage", perm)
            return (buf, outs), None

        outs0 = jnp.zeros_like(xloc)
        buf0 = jnp.zeros_like(xloc[0])
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                    jnp.arange(m + n - 1))
        # replicate the last stage's results to every stage (out_specs P())
        return jax.lax.psum(
            jnp.where(stage == n - 1, outs, jnp.zeros_like(outs)), "stage")

    fn = jax.shard_map(run, mesh=mesh, in_specs=(P("stage"), P()),
                       out_specs=P(), check_vma=False)
    want = NamedSharding(mesh, P("stage"))
    leaf = jax.tree.leaves(stacked_blocks)[0]
    already_placed = (
        not isinstance(leaf, jax.core.Tracer)   # tracers have no .sharding
        and isinstance(leaf, jax.Array)
        and leaf.sharding.is_equivalent_to(want, leaf.ndim))
    if not already_placed:
        # place once; callers in a training loop should pre-place (see
        # place_blocks) so repeated eager calls don't re-transfer params.
        # Under a trace this is the sharding constraint, not a copy.
        stacked_blocks = jax.device_put(stacked_blocks, want)
    out = fn(stacked_blocks, x)
    return out.reshape(h.shape)


def place_blocks(stacked_blocks, mesh):
    """Pre-place a stacked block pytree on the stage sharding (do this
    ONCE before a training loop; pipeline_blocks then skips the copy)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.device_put(stacked_blocks, NamedSharding(mesh, P("stage")))


def pipeline_lm_loss(params, tokens, mask, n_heads, mesh, n_microbatches,
                     block_size=None):
    """``transformer.lm_loss`` with the block stack executed by the GPipe
    pipeline; ``params["blocks"]`` is the STACKED pytree.  Equals the
    sequential loss (and its grads transpose through the pipeline) —
    the embed half and loss tail are the SAME shared helpers lm_loss
    composes, only the block-stack execution is swapped."""
    from veles_tpu.ops.transformer import embed_tokens, nll_from_hidden

    h = embed_tokens(params, tokens[:, :-1])
    h = pipeline_blocks(params["blocks"], h, mesh, n_heads,
                        n_microbatches, block_size)
    return nll_from_hidden(params, h, tokens[:, 1:], mask)

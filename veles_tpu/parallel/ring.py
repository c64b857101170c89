"""Ring attention — sequence/context parallelism over a mesh axis.

Long-context scaling beyond one chip: the sequence axis is sharded over the
``seq`` mesh axis; each device holds its query block permanently while
key/value blocks ROTATE around the ring via ``lax.ppermute`` over ICI, with
the same online-softmax accumulation as the single-chip blockwise kernel
(veles_tpu.ops.attention._online_update), so memory per chip is
O(seq/n_devices) and the KV transfer overlaps compute around the ring.

This is the idiomatic TPU mechanism SURVEY §5.7 names for the roadmap
(shard_map over a context axis + ppermute); the reference has no attention
at all, so this module is pure beyond-parity capability.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from veles_tpu.ops.attention import (_online_update,
                                     band_bias, NEG_INF)


def make_seq_mesh(n_devices=None, data_parallel=1, devices=None):
    """(data, seq) mesh: batch over 'data', sequence ring over 'seq'."""
    import numpy
    from jax.sharding import Mesh
    devices = list(devices if devices is not None else jax.devices())
    n = n_devices or len(devices)
    if n % data_parallel:
        raise ValueError("n_devices %d not divisible by data_parallel %d"
                         % (n, data_parallel))
    grid = numpy.array(devices[:n]).reshape(data_parallel,
                                            n // data_parallel)
    return Mesh(grid, ("data", "seq"))


def _ring_attention_local(q, k, v, axis_name, causal, window=None,
                          sinks=0):
    """Per-shard body (runs under shard_map): q/k/v are the LOCAL sequence
    blocks (batch, heads, s_local, dh)."""
    n = jax.lax.psum(1, axis_name)
    my_index = jax.lax.axis_index(axis_name)
    s_local = q.shape[-2]
    q_pos = my_index * s_local + jnp.arange(s_local)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(carry, step):
        o_l_m, kv = carry
        k_blk, v_blk = kv
        # kv block currently held originated on device (my_index - step) % n
        src = (my_index - step) % n
        def attend(c):
            # the shared global-position band (attention.band_bias):
            # the window just masks across shard borders.  Step 0 is
            # the own block (every query sees itself), so the online
            # max is finite before any fully-masked distant block
            # arrives — same transient-safety argument as
            # blockwise_attention.  Built INSIDE the branch so skipped
            # steps skip the (s_local x s_local) mask too.
            bias = (band_bias(q_pos,
                              src * s_local + jnp.arange(s_local),
                              causal, window, q.dtype, sinks=sinks)
                    if causal else None)
            return _online_update(c, q, k_blk, v_blk, bias)

        if causal:
            # EARLY EXIT: skip the attention math entirely for blocks
            # with no live (query, key) pair — future blocks under
            # causality, too-old blocks under a window; most ring steps
            # are then just the ppermute.  A pair (q, k) is live iff
            # k <= q and (no window or q - k < W); over the block's key
            # span [k_first, k_last] and this device's query span
            # [q_first, q_last] that reduces to the interval test
            #   k_first <= q_last  AND  k_last > q_first - W.
            k_first = src * s_local
            k_last = k_first + s_local - 1
            live = k_first <= q_pos[-1]
            if window:
                in_band = k_last > q_pos[0] - window
                if sinks:
                    in_band |= k_first < sinks
                live &= in_band
            o_l_m = jax.lax.cond(live, attend, lambda c: c, o_l_m)
        else:
            o_l_m = attend(o_l_m)
        # rotate kv around the ring for the next step (ICI neighbor copy)
        kv = jax.tree.map(
            lambda a: jax.lax.ppermute(a, axis_name, perm), kv)
        return (o_l_m, kv), None

    # derive the accumulators from q so they inherit its device-varying
    # axes — fresh constants would make the scan carry types mismatch
    o0 = jnp.zeros_like(q)
    l0 = q[..., 0] * 0.0
    m0 = q[..., 0] * 0.0 + NEG_INF
    (o_l_m, _), _ = jax.lax.scan(body, ((o0, l0, m0), (k, v)),
                                 jnp.arange(n))
    o, l, _ = o_l_m
    return o / l[..., None]


def ring_attention(q, k, v, mesh, causal=True, seq_axis="seq",
                   data_axis="data", window=None, sinks=0):
    """Sequence-parallel attention over ``mesh``.

    q, k, v: (batch, heads, seq, head_dim) GLOBAL arrays; the sequence axis
    is sharded over ``seq_axis``, batch over ``data_axis``; output sharding
    matches q.  Numerically equals dense ``attention(q, k, v, causal)``;
    ``window=W`` composes (equals the dense sliding-window form — global
    positions, so the band crosses shard borders correctly).  Ring steps
    whose whole block is outside the band skip the attention math (only
    the ppermute runs), so per-device compute under a small window is
    O(s_local + W) keys per query block rather than O(seq).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    if window and not causal:
        raise ValueError("window requires causal=True")
    spec = P(data_axis, None, seq_axis, None)
    # check_vma=False: the check is a static analysis only — every
    # array here is device-varying along the ring anyway, so disabling
    # it changes nothing numerically (forward+grad parity pinned in
    # tests/test_attention.py)
    fn = jax.shard_map(
        functools.partial(_ring_attention_local, axis_name=seq_axis,
                          causal=causal, window=window, sinks=sinks),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    q = jax.device_put(q, NamedSharding(mesh, spec))
    k = jax.device_put(k, NamedSharding(mesh, spec))
    v = jax.device_put(v, NamedSharding(mesh, spec))
    return fn(q, k, v)

"""Attention + ring sequence parallelism tests.

Oracle chain: numpy softmax attention → jax dense → blockwise (flash) →
ring over an 8-device CPU mesh — each stage must match the previous one.
"""

import numpy
import pytest

import jax
import jax.numpy as jnp

from veles_tpu.ops import attention as A


def numpy_attention(q, k, v, causal=False):
    dh = q.shape[-1]
    s = q @ numpy.swapaxes(k, -1, -2) / numpy.sqrt(dh)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = numpy.tril(numpy.ones((sq, sk), bool), sk - sq)
        s = numpy.where(mask, s, -1e30)
    e = numpy.exp(s - s.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    return p @ v


def qkv(batch=2, heads=2, seq=32, dh=8, seed=0):
    r = numpy.random.RandomState(seed)
    shape = (batch, heads, seq, dh)
    return (r.randn(*shape).astype(numpy.float32),
            r.randn(*shape).astype(numpy.float32),
            r.randn(*shape).astype(numpy.float32))


class TestDenseAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_numpy(self, causal):
        q, k, v = qkv()
        out = A.attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=causal)
        numpy.testing.assert_allclose(numpy.asarray(out),
                                      numpy_attention(q, k, v, causal),
                                      rtol=1e-4, atol=1e-5)


class TestBlockwiseAttention:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("block", [8, 16, 32])
    def test_matches_dense(self, causal, block):
        q, k, v = qkv(seq=32)
        dense = A.attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                            causal=causal)
        blocked = A.blockwise_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            block_size=block, causal=causal)
        numpy.testing.assert_allclose(numpy.asarray(blocked),
                                      numpy.asarray(dense),
                                      rtol=1e-4, atol=1e-5)

    def test_indivisible_block_raises(self):
        q, k, v = qkv(seq=32)
        with pytest.raises(ValueError):
            A.blockwise_attention(jnp.asarray(q), jnp.asarray(k),
                                  jnp.asarray(v), block_size=7)


class TestMHA:
    def test_shapes_and_grad(self):
        from veles_tpu import prng
        prng.reset()
        prng.seed_all(1)
        params = A.init_mha_params(prng.get("init"), d_model=16, n_heads=4)
        x = jnp.asarray(numpy.random.RandomState(0)
                        .randn(2, 8, 16).astype(numpy.float32))
        out = A.mha_forward(params, x, n_heads=4)
        assert out.shape == (2, 8, 16)
        grads = jax.grad(lambda p: (A.mha_forward(p, x, 4) ** 2).sum())(
            jax.tree.map(jnp.asarray, params))
        for leaf in jax.tree.leaves(grads):
            assert numpy.isfinite(numpy.asarray(leaf)).all()

    def test_blockwise_path_matches(self):
        from veles_tpu import prng
        prng.reset()
        prng.seed_all(1)
        params = jax.tree.map(
            jnp.asarray,
            A.init_mha_params(prng.get("init"), d_model=16, n_heads=2))
        x = jnp.asarray(numpy.random.RandomState(0)
                        .randn(2, 32, 16).astype(numpy.float32))
        dense = A.mha_forward(params, x, 2, causal=True)
        blocked = A.mha_forward(params, x, 2, causal=True, block_size=8)
        numpy.testing.assert_allclose(numpy.asarray(blocked),
                                      numpy.asarray(dense),
                                      rtol=1e-4, atol=1e-5)


class TestRingAttention:
    @pytest.fixture
    def mesh(self):
        devices = jax.devices("cpu")
        if len(devices) < 8:
            pytest.skip("needs 8 virtual devices")
        from veles_tpu.parallel.ring import make_seq_mesh
        return make_seq_mesh(8, data_parallel=2, devices=devices[:8])

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, mesh, causal):
        from veles_tpu.parallel.ring import ring_attention
        q, k, v = qkv(batch=2, heads=2, seq=32, dh=8)
        dense = A.attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                            causal=causal)
        ring = ring_attention(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), mesh, causal=causal)
        numpy.testing.assert_allclose(numpy.asarray(ring),
                                      numpy.asarray(dense),
                                      rtol=1e-4, atol=1e-5)

    def test_output_is_seq_sharded(self, mesh):
        from veles_tpu.parallel.ring import ring_attention
        from jax.sharding import NamedSharding, PartitionSpec as P
        q, k, v = qkv(batch=2, heads=2, seq=32, dh=8)
        out = ring_attention(jnp.asarray(q), jnp.asarray(k),
                             jnp.asarray(v), mesh)
        expect = NamedSharding(mesh, P("data", None, "seq", None))
        assert out.sharding.is_equivalent_to(expect, out.ndim)

    def test_grad_flows_through_ring(self, mesh):
        from veles_tpu.parallel.ring import ring_attention
        q, k, v = qkv(batch=2, heads=2, seq=32, dh=8)

        def loss(q_):
            return (ring_attention(q_, jnp.asarray(k), jnp.asarray(v),
                                   mesh) ** 2).sum()

        g = jax.grad(loss)(jnp.asarray(q))
        assert numpy.isfinite(numpy.asarray(g)).all()
        # compare with dense-attention gradient
        g_dense = jax.grad(lambda q_: (A.attention(
            q_, jnp.asarray(k), jnp.asarray(v), causal=True) ** 2).sum())(
                jnp.asarray(q))
        numpy.testing.assert_allclose(numpy.asarray(g),
                                      numpy.asarray(g_dense),
                                      rtol=1e-3, atol=1e-4)


class TestFlashPallasBackend:
    """The bundled TPU Pallas flash-attention kernel as an opt-in
    backend (attention.set_attention_backend)."""

    def test_backend_flag_validates(self):
        from veles_tpu.ops import attention as A
        with pytest.raises(ValueError):
            A.set_attention_backend("nope")
        A.set_attention_backend("xla")   # restore-is-default no-op

    def test_off_tpu_is_a_loud_error(self):
        """No silent fallback: off-TPU the kernel must refuse, not
        quietly compute something else."""
        from veles_tpu.ops import attention as A
        from veles_tpu.ops.pallas_kernels import on_tpu
        if on_tpu():
            pytest.skip("on-TPU: covered by the parity test")
        q = jnp.zeros((1, 2, 128, 64), jnp.float32)
        with pytest.raises(RuntimeError, match="TPU"):
            A.flash_attention_tpu(q, q, q)

    def test_matches_xla_attention_on_tpu(self):
        from veles_tpu.ops import attention as A
        from veles_tpu.ops.pallas_kernels import on_tpu
        if not on_tpu():
            pytest.skip("the bundled kernel has no CPU lowering")
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (2, 4, 256, 64), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(key, 1), q.shape)
        v = jax.random.normal(jax.random.fold_in(key, 2), q.shape)
        ref = A.attention(q, k, v, causal=True)
        got = A.flash_attention_tpu(q, k, v, causal=True)
        numpy.testing.assert_allclose(numpy.asarray(got),
                                      numpy.asarray(ref),
                                      rtol=2e-3, atol=2e-3)


class TestWindowedRingAttention:
    """Sliding window composes with sequence-parallel ring attention:
    positions are global, so the band crosses shard borders exactly."""

    @pytest.mark.parametrize("window", [1, 5, 12, 999])
    def test_matches_dense_windowed(self, window):
        from veles_tpu.ops.attention import attention
        from veles_tpu.parallel.ring import make_seq_mesh, ring_attention
        mesh = make_seq_mesh(4, devices=jax.devices("cpu")[:4])
        key = jax.random.PRNGKey(0)
        # s_local = 8 => window=5 stays in-shard for some queries and
        # crosses the border for others; 12 always crosses; 999 ≡ causal
        q = jax.random.normal(key, (2, 2, 32, 8), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(key, 1), q.shape)
        v = jax.random.normal(jax.random.fold_in(key, 2), q.shape)
        ref = attention(q, k, v, causal=True, window=window)
        got = ring_attention(q, k, v, mesh, causal=True, window=window)
        numpy.testing.assert_allclose(numpy.asarray(got),
                                      numpy.asarray(ref),
                                      rtol=1e-4, atol=1e-5)

    def test_window_requires_causal(self):
        from veles_tpu.parallel.ring import make_seq_mesh, ring_attention
        mesh = make_seq_mesh(2, devices=jax.devices("cpu")[:2])
        q = jnp.zeros((1, 1, 8, 4), jnp.float32)
        with pytest.raises(ValueError, match="causal"):
            ring_attention(q, q, q, mesh, causal=False, window=2)


@pytest.mark.parametrize("window", [1, 3, 10, 999])
def test_blockwise_windowed_matches_dense(window):
    """Flash-style blockwise + sliding window ≡ dense windowed (incl.
    fully-masked EARLY blocks, whose transient terms the online rescale
    must zero — the finite-NEG_INF subtlety)."""
    from veles_tpu.ops.attention import attention, blockwise_attention
    key = jax.random.PRNGKey(5)
    q = jax.random.normal(key, (2, 2, 32, 8), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), q.shape)
    v = jax.random.normal(jax.random.fold_in(key, 2), q.shape)
    ref = attention(q, k, v, causal=True, window=window)
    got = blockwise_attention(q, k, v, block_size=8, causal=True,
                              window=window)
    numpy.testing.assert_allclose(numpy.asarray(got), numpy.asarray(ref),
                                  rtol=1e-4, atol=1e-5)


class TestAttentionSinks:
    """sinks=K keeps the first K positions attendable under a window
    (StreamingLLM form) — identical across all three decompositions."""

    def _qkv(self, seq=32):
        key = jax.random.PRNGKey(7)
        q = jax.random.normal(key, (2, 2, seq, 8), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(key, 1), q.shape)
        v = jax.random.normal(jax.random.fold_in(key, 2), q.shape)
        return q, k, v

    def test_sinks_widen_the_window_exactly(self):
        """Manual oracle: with window=4, sinks=2, position p attends to
        {0, 1} ∪ (p-4, p] and nothing else."""
        from veles_tpu.ops.attention import attention
        q, k, v = self._qkv(16)
        got = attention(q, k, v, causal=True, window=4, sinks=2)
        # oracle via explicit bias on plain causal attention
        p = numpy.arange(16)
        allowed = (p[None, :] <= p[:, None]) & (
            (p[:, None] - p[None, :] < 4) | (p[None, :] < 2))
        bias = jnp.where(jnp.asarray(allowed), 0.0, -1e30)
        ref = attention(q, k, v, causal=False, bias=bias)
        numpy.testing.assert_allclose(numpy.asarray(got),
                                      numpy.asarray(ref),
                                      rtol=1e-5, atol=1e-6)

    def test_blockwise_and_ring_match_dense(self):
        from veles_tpu.ops.attention import attention, blockwise_attention
        from veles_tpu.parallel.ring import make_seq_mesh, ring_attention
        q, k, v = self._qkv(32)
        ref = attention(q, k, v, causal=True, window=5, sinks=3)
        blk = blockwise_attention(q, k, v, block_size=8, causal=True,
                                  window=5, sinks=3)
        numpy.testing.assert_allclose(numpy.asarray(blk),
                                      numpy.asarray(ref),
                                      rtol=1e-4, atol=1e-5)
        mesh = make_seq_mesh(4, devices=jax.devices("cpu")[:4])
        ring = ring_attention(q, k, v, mesh, causal=True, window=5,
                              sinks=3)
        numpy.testing.assert_allclose(numpy.asarray(ring),
                                      numpy.asarray(ref),
                                      rtol=1e-4, atol=1e-5)

    def test_ring_early_exit_keeps_sink_blocks_live(self):
        """The ring's liveness test must not skip the block holding the
        sinks even when it is far outside the window (the exact bug a
        naive interval test would have)."""
        from veles_tpu.ops.attention import attention
        from veles_tpu.parallel.ring import make_seq_mesh, ring_attention
        q, k, v = self._qkv(32)           # s_local=8, 4 shards
        # window=2 puts shard 0 far outside every later query's band
        ref = attention(q, k, v, causal=True, window=2, sinks=1)
        ring = ring_attention(q, k, v, mesh=make_seq_mesh(
            4, devices=jax.devices("cpu")[:4]), causal=True, window=2,
            sinks=1)
        numpy.testing.assert_allclose(numpy.asarray(ring),
                                      numpy.asarray(ref),
                                      rtol=1e-4, atol=1e-5)

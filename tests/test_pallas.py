"""Pallas kernels (interpret mode on CPU = same kernel code as TPU) and
stochastic pooling (SURVEY §2.4 custom-kernel candidates)."""

import numpy
import pytest

import jax
import jax.numpy as jnp

from veles_tpu.ops import functional as F
from veles_tpu.ops import pallas_kernels as PK


class TestFusedSGD:
    @pytest.mark.parametrize("shape", [(7,), (64, 10), (3, 5, 5, 8)])
    def test_matches_functional(self, shape):
        r = numpy.random.RandomState(0)
        p = r.randn(*shape).astype(numpy.float32)
        v = r.randn(*shape).astype(numpy.float32) * 0.1
        g = r.randn(*shape).astype(numpy.float32)
        args = dict(batch_size=jnp.asarray(32), learning_rate=0.05,
                    momentum=0.9, weight_decay=0.001, l1_vs_l2=0.3)
        ref_p, ref_v = F.sgd_update(jnp.asarray(p), jnp.asarray(v),
                                    jnp.asarray(g), gradient_clip=None,
                                    **args)
        new_p, new_v = PK.fused_sgd_update(jnp.asarray(p), jnp.asarray(v),
                                           jnp.asarray(g), **args)
        numpy.testing.assert_allclose(numpy.asarray(new_p),
                                      numpy.asarray(ref_p), rtol=1e-6,
                                      atol=1e-6)
        numpy.testing.assert_allclose(numpy.asarray(new_v),
                                      numpy.asarray(ref_v), rtol=1e-6,
                                      atol=1e-6)

    def test_backend_flag_routes_hot_path(self):
        """set_sgd_backend('pallas') swaps the kernel into the DEFAULT
        update path (VERDICT r3 Weak #5: wire it, don't shelve it) with
        identical numerics; gradient_clip falls back to the xla path."""
        r = numpy.random.RandomState(1)
        p = jnp.asarray(r.randn(40, 30).astype(numpy.float32))
        v = jnp.zeros_like(p)
        g = jnp.asarray(r.randn(40, 30).astype(numpy.float32))
        args = (jnp.asarray(16), 0.05, 0.9, 0.001, 0.3)
        ref_p, ref_v = F.sgd_update(p, v, g, *args, gradient_clip=None)
        clip_p, clip_v = F.sgd_update(p, v, g, *args, gradient_clip=0.01)
        F.set_sgd_backend("pallas")
        try:
            new_p, new_v = F.sgd_update(p, v, g, *args, gradient_clip=None)
            fb_p, fb_v = F.sgd_update(p, v, g, *args, gradient_clip=0.01)
        finally:
            F.set_sgd_backend("xla")
        numpy.testing.assert_allclose(numpy.asarray(new_p),
                                      numpy.asarray(ref_p), rtol=1e-6,
                                      atol=1e-6)
        numpy.testing.assert_allclose(numpy.asarray(fb_p),
                                      numpy.asarray(clip_p), rtol=1e-6,
                                      atol=1e-6)
        with pytest.raises(ValueError):
            F.set_sgd_backend("nope")

    def test_traced_scalars_jit(self):
        """lr/batch_size as traced values inside jit (lr policies)."""
        r = numpy.random.RandomState(1)
        p = r.randn(100).astype(numpy.float32)

        @jax.jit
        def step(p, lr, bs):
            return PK.fused_sgd_update(p, jnp.zeros_like(p),
                                       jnp.ones_like(p), bs, lr,
                                       momentum=0.5)

        new_p, _ = step(jnp.asarray(p), jnp.asarray(0.1, jnp.float32),
                        jnp.asarray(10))
        numpy.testing.assert_allclose(numpy.asarray(new_p), p - 0.01,
                                      rtol=1e-5, atol=1e-6)


class TestPallasDropout:
    def test_deterministic_per_seed(self):
        x = jnp.ones((130,), jnp.float32)   # forces lane padding
        a = PK.dropout(x, 7, 0.5)
        b = PK.dropout(x, 7, 0.5)
        numpy.testing.assert_array_equal(numpy.asarray(a), numpy.asarray(b))
        c = PK.dropout(x, 8, 0.5)
        assert not numpy.array_equal(numpy.asarray(a), numpy.asarray(c))

    def test_statistics_and_scaling(self):
        x = jnp.ones((100, 128), jnp.float32)
        out = numpy.asarray(PK.dropout(x, 3, 0.3))
        kept = out > 0
        assert abs(kept.mean() - 0.7) < 0.02
        numpy.testing.assert_allclose(out[kept], 1.0 / 0.7, rtol=1e-5)

    def test_zero_rate_identity(self):
        x = jnp.asarray(numpy.random.RandomState(0).randn(16, 16),
                        jnp.float32)
        numpy.testing.assert_array_equal(numpy.asarray(PK.dropout(x, 1, 0.0)),
                                         numpy.asarray(x))

    @pytest.mark.parametrize("rate", [0.3, 0.5, 0.7])
    def test_real_kernel_statistics(self, rate):
        """Keep fraction of the NON-interpret kernel — the signed int32
        random bits must be compared in the signed domain (the unsigned
        misread made rate<=0.5 a silent no-op on hardware)."""
        if not PK.on_tpu():
            pytest.skip("real-kernel path needs the TPU PRNG")
        keep_prob = 1.0 - rate
        x = jnp.ones((256, 512), jnp.float32)
        out = numpy.asarray(PK.dropout(x, 5, rate, interpret=False))
        kept = out > 0
        assert abs(kept.mean() - keep_prob) < 0.01, kept.mean()
        numpy.testing.assert_allclose(out[kept], 1.0 / keep_prob, rtol=1e-5)


class TestStochasticPooling:
    def test_train_samples_from_window(self):
        r = numpy.random.RandomState(0)
        x = r.randn(2, 4, 4, 3).astype(numpy.float32)
        out = F.stochastic_pooling(jnp.asarray(x), (2, 2), None,
                                   jax.random.PRNGKey(0), True, True)
        assert out.shape == (2, 2, 2, 3)
        # every output must equal SOME element of its window
        for b in range(2):
            for oy in range(2):
                for ox in range(2):
                    for c in range(3):
                        window = x[b, oy * 2:oy * 2 + 2,
                                   ox * 2:ox * 2 + 2, c].ravel()
                        assert numpy.isclose(window,
                                             float(out[b, oy, ox, c])).any()

    def test_eval_weighted_average(self):
        x = numpy.zeros((1, 2, 2, 1), numpy.float32)
        x[0, :, :, 0] = [[1.0, 3.0], [0.0, 0.0]]
        out = F.stochastic_pooling(jnp.asarray(x), (2, 2), None, None,
                                   train=False, use_abs=True)
        # probs = [.25, .75, 0, 0] → expected value 0.25*1 + 0.75*3 = 2.5
        numpy.testing.assert_allclose(numpy.asarray(out)[0, 0, 0, 0], 2.5,
                                      rtol=1e-5)

    def test_empty_window_uniform(self):
        x = jnp.zeros((1, 2, 2, 1), jnp.float32)
        out = F.stochastic_pooling(x, (2, 2), None, jax.random.PRNGKey(0),
                                   True, True)
        assert float(out[0, 0, 0, 0]) == 0.0

    def test_unit_in_training(self):
        """The layer type trains end-to-end in a conv net (fused mode)."""
        from veles_tpu import prng
        from veles_tpu.config import root
        prng.reset()
        prng.seed_all(1)
        root.cifar.update({
            "loader": {"minibatch_size": 25, "n_train": 100, "n_valid": 50},
            "decision": {"max_epochs": 2, "fail_iterations": 5},
            "layers": [
                {"type": "conv_relu", "n_kernels": 8, "kx": 3, "ky": 3,
                 "padding": "SAME", "learning_rate": 0.02, "momentum": 0.9},
                {"type": "stochastic_abs_pooling", "kx": 2, "ky": 2},
                {"type": "softmax", "output_sample_shape": 10,
                 "learning_rate": 0.02, "momentum": 0.9},
            ],
        })
        from veles_tpu.samples import cifar
        wf = cifar.train(fused=True)
        errs = [m["validation"]["n_err"] for m in wf.decision.epoch_metrics
                if "validation" in m]
        assert numpy.isfinite(errs).all()
        # 2 epochs x 50 valid samples: just require training stays sane
        assert errs[-1] <= errs[0] + 5


class TestPallasLRN:
    def _x(self, shape=(4, 7, 7, 96), seed=0, scale=1.0):
        return jax.random.normal(jax.random.PRNGKey(seed), shape,
                                 jnp.float32) * scale

    @pytest.mark.parametrize("c", [16, 96, 128, 200])
    def test_forward_matches_functional(self, c):
        """One-pass banded-matmul LRN ≡ the shifted-slice XLA form at
        every channel width (below/at/above the 128-lane tile)."""
        from veles_tpu.ops import pallas_kernels as PK
        x = self._x((3, 5, 5, c), seed=c)
        ref = F.lrn_forward(x)
        got = PK.lrn_forward(x)
        numpy.testing.assert_allclose(numpy.asarray(got),
                                      numpy.asarray(ref),
                                      rtol=2e-6, atol=2e-6)

    @pytest.mark.parametrize("n", [4, 5])
    def test_even_and_odd_window_match_xla(self, n):
        """Even n has an ASYMMETRIC window in the XLA form (pad n//2 +
        n shifted slices); the band must replicate it, values AND
        grads — not the symmetric |i-j|<=n//2 approximation."""
        from veles_tpu.ops import pallas_kernels as PK
        x = self._x((2, 3, 3, 24), seed=n)
        dy = self._x((2, 3, 3, 24), seed=n + 10)
        ref, ref_vjp = jax.vjp(lambda a: F.lrn_forward(a, n=n), x)
        got, got_vjp = jax.vjp(lambda a: PK.lrn_forward(a, n=n), x)
        numpy.testing.assert_allclose(numpy.asarray(got),
                                      numpy.asarray(ref),
                                      rtol=2e-6, atol=2e-6)
        numpy.testing.assert_allclose(numpy.asarray(got_vjp(dy)[0]),
                                      numpy.asarray(ref_vjp(dy)[0]),
                                      rtol=3e-5, atol=3e-6)

    def test_gradient_matches_functional(self):
        """The fused custom VJP ≡ jax autodiff of the XLA form."""
        from veles_tpu.ops import pallas_kernels as PK
        x = self._x((2, 4, 4, 32), seed=1, scale=2.0)
        dy = self._x((2, 4, 4, 32), seed=2)

        ref = jax.vjp(lambda a: F.lrn_forward(a, 2e-4, 0.7, 5, 1.5), x)[1](
            dy)[0]
        got = jax.vjp(lambda a: PK.lrn_forward(a, 2e-4, 0.7, 5, 1.5), x)[1](
            dy)[0]
        numpy.testing.assert_allclose(numpy.asarray(got),
                                      numpy.asarray(ref),
                                      rtol=3e-5, atol=3e-6)

    def test_backend_flag_routes(self):
        """set_lrn_backend('pallas') swaps the kernel into the DEFAULT
        lrn path (what the norm unit calls) and back."""
        x = self._x((2, 3, 3, 24), seed=3)
        ref = numpy.asarray(F.lrn_forward(x))
        F.set_lrn_backend("pallas")
        try:
            got = numpy.asarray(F.lrn_forward(x))
        finally:
            F.set_lrn_backend("xla")
        numpy.testing.assert_allclose(got, ref, rtol=2e-6, atol=2e-6)
        with pytest.raises(ValueError):
            F.set_lrn_backend("nope")

    def test_trains_under_jit(self):
        """The custom-VJP kernel composes with jit + grad at AlexNet-LRN1
        shape fragments (the path the fused step takes)."""
        from veles_tpu.ops import pallas_kernels as PK
        x = self._x((2, 6, 6, 96), seed=4)

        @jax.jit
        def loss(a):
            return (PK.lrn_forward(a) ** 2).sum()
        g = jax.grad(loss)(x)
        assert numpy.isfinite(numpy.asarray(g)).all()


@pytest.mark.kernel_parity
class TestPagedFlashDecode:
    """ISSUE 7: the flash-decode serving kernel (interpret mode = the
    SAME kernel code the TPU compiles) against the XLA paged path —
    ``paged_view`` gather + dense masked softmax — which the serving
    parity matrix has already pinned bit-identical to ``generate``."""

    def _setup(self, b=2, h=4, kv=2, c=1, dh=16, page=8, m=4,
               n_pages=9, seed=0):
        rng = numpy.random.RandomState(seed)
        q = jnp.asarray(rng.randn(b, h, c, dh), jnp.float32)
        kp = jnp.asarray(rng.randn(n_pages, kv, page, dh), jnp.float32)
        vp = jnp.asarray(rng.randn(n_pages, kv, page, dh), jnp.float32)
        ptab = jnp.asarray(rng.choice(
            n_pages, size=(b, m), replace=False).reshape(b, m),
            jnp.int32)
        pos = jnp.asarray(rng.randint(0, m * page - c + 1, b),
                          jnp.int32)
        return q, kp, vp, ptab, pos

    def _xla(self, q, kp, vp, ptab, pos, c, window=None, sinks=0):
        from veles_tpu.ops import attention as A
        h, kv = q.shape[1], kp.shape[1]
        kx, vx = A.paged_view(kp, ptab), A.paged_view(vp, ptab)
        kr = A._repeat_kv(kx, h)
        vr = A._repeat_kv(vx, h)
        s = jnp.einsum("bhcd,bhld->bhcl", q, kr) / jnp.sqrt(
            jnp.float32(q.shape[-1]))
        live = jax.vmap(lambda p: A.chunk_live_mask(
            p, c, kx.shape[-2], window, sinks))(pos)
        s = jnp.where(live[:, None], s, A.NEG_INF)
        return jnp.einsum("bhcl,bhld->bhcd",
                          jax.nn.softmax(s, axis=-1), vr)

    @pytest.mark.parametrize("c,window,sinks", [
        (1, None, 0),          # decode step
        (4, None, 0),          # speculative verify (k+1)
        (1, 10, 0),            # sliding window
        (4, 10, 2),            # window + sinks, multi-query
        (1, 10, 1),            # single query at the sink edge
    ])
    @pytest.mark.parametrize("pack", [1, 2])
    def test_matches_xla_paged_path(self, c, window, sinks, pack):
        """``pack`` heads to a pool row (ISSUE 27: the layout an engine
        with the kernels active holds, ``pool_pack``) read the same."""
        from veles_tpu.ops import pallas_kernels as PK
        q, kp, vp, ptab, pos = self._setup(c=c, m=6, n_pages=13,
                                           seed=c + (window or 0))
        got = PK.paged_flash_decode(
            q, PK.pack_heads(kp, pack), PK.pack_heads(vp, pack), ptab,
            pos, window=window, sinks=sinks)
        ref = self._xla(q, kp, vp, ptab, pos, c, window, sinks)
        numpy.testing.assert_allclose(numpy.asarray(got),
                                      numpy.asarray(ref),
                                      rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("h,kv,pack", [
        (4, 1, 1), (4, 4, 1), (8, 2, 1), (4, 4, 4), (8, 2, 2),
        (8, 4, 2)])
    def test_grouped_query_layouts(self, h, kv, pack):
        """GQA folds into the kernel as a (kv, g·c) row reshape — every
        grouping must agree with jnp.repeat's head mapping, and so must
        every packing of the heads into pool rows."""
        from veles_tpu.ops import pallas_kernels as PK
        q, kp, vp, ptab, pos = self._setup(h=h, kv=kv, c=3, seed=h * kv)
        got = PK.paged_flash_decode(q, PK.pack_heads(kp, pack),
                                    PK.pack_heads(vp, pack), ptab, pos)
        ref = self._xla(q, kp, vp, ptab, pos, 3)
        numpy.testing.assert_allclose(numpy.asarray(got),
                                      numpy.asarray(ref),
                                      rtol=1e-5, atol=1e-6)

    def test_early_position_masks_garbage_pages(self):
        """A lane at pos=0 attends ONE row; the other pages hold
        garbage the NEG_INF band + online rescale must zero exactly
        (the blockwise_attention transient-term argument, in-kernel)."""
        from veles_tpu.ops import pallas_kernels as PK
        q, kp, vp, ptab, _ = self._setup(c=1, seed=5)
        pos = jnp.zeros(q.shape[0], jnp.int32)
        got = PK.paged_flash_decode(q, kp, vp, ptab, pos)
        ref = self._xla(q, kp, vp, ptab, pos, 1)
        numpy.testing.assert_allclose(numpy.asarray(got),
                                      numpy.asarray(ref),
                                      rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("pack", [1, 2])
    def test_mha_paged_chunk_step_kernel_route(self, pack):
        """attention.mha_paged_chunk_step(attn_kernel='decode') —
        the wired route the engine's step/verify programs take —
        matches its own XLA path: same projections, same rope, same
        pool writes (bit-identical, in the packed rows too),
        attention to fp32 roundoff."""
        from veles_tpu.ops.pallas_kernels import pack_heads
        from veles_tpu import prng
        from veles_tpu.ops.attention import (init_mha_params,
                                             mha_paged_chunk_step)
        rng = numpy.random.RandomState(3)
        d_model, n_heads, page, m, n_pages, b, c = 32, 4, 8, 4, 9, 2, 2
        params = jax.tree.map(
            jnp.asarray, init_mha_params(prng.get("init"), d_model,
                                         n_heads, n_kv_heads=2))
        x = jnp.asarray(rng.randn(b, c, d_model), jnp.float32)
        kp = jnp.asarray(rng.randn(n_pages, 2, page, 8), jnp.float32)
        vp = jnp.asarray(rng.randn(n_pages, 2, page, 8), jnp.float32)
        ptab = jnp.asarray(rng.choice(n_pages, (b, m), replace=False)
                           .reshape(b, m), jnp.int32)
        pos = jnp.asarray([5, 13], jnp.int32)
        ref_o, ref_k, ref_v = mha_paged_chunk_step(
            params, x, kp, vp, ptab, pos, n_heads, rope=True,
            window=16, sinks=1)
        got_o, got_k, got_v = mha_paged_chunk_step(
            params, x, pack_heads(kp, pack), pack_heads(vp, pack), ptab,
            pos, n_heads, rope=True, window=16, sinks=1,
            attn_kernel="decode")
        numpy.testing.assert_array_equal(
            numpy.asarray(got_k), numpy.asarray(pack_heads(ref_k, pack)))
        numpy.testing.assert_array_equal(
            numpy.asarray(got_v), numpy.asarray(pack_heads(ref_v, pack)))
        numpy.testing.assert_allclose(numpy.asarray(got_o),
                                      numpy.asarray(ref_o),
                                      rtol=1e-4, atol=1e-5)


@pytest.mark.kernel_parity
class TestPagedFlashPrefill:
    """ISSUE 7: the fused chunked-prefill kernel — history streamed
    below the frontier, the chunk's K/V attended from VMEM, and the
    page install folded into the kernel epilogue (aliased outputs)."""

    def _setup(self, b=1, h=4, kv=2, dh=16, page=8, m=4, n_pages=9,
               n_hist=2, seed=0):
        rng = numpy.random.RandomState(seed)
        q = jnp.asarray(rng.randn(b, h, page, dh), jnp.float32)
        kn = jnp.asarray(rng.randn(b, kv, page, dh), jnp.float32)
        vn = jnp.asarray(rng.randn(b, kv, page, dh), jnp.float32)
        kp = jnp.asarray(rng.randn(n_pages, kv, page, dh), jnp.float32)
        vp = jnp.asarray(rng.randn(n_pages, kv, page, dh), jnp.float32)
        ptab = jnp.asarray(rng.permutation(n_pages)[:b * m]
                           .reshape(b, m), jnp.int32)
        pos = jnp.asarray([n_hist * page] * b, jnp.int32)
        return q, kn, vn, kp, vp, ptab, pos

    def _xla(self, q, kn, vn, kp, vp, ptab, pos, window=None, sinks=0):
        from veles_tpu.ops import attention as A
        h, c = q.shape[1], q.shape[2]
        kp = A.paged_write(kp, ptab, pos, kn)
        vp = A.paged_write(vp, ptab, pos, vn)
        kx, vx = A.paged_view(kp, ptab), A.paged_view(vp, ptab)
        s = jnp.einsum("bhcd,bhld->bhcl", q, A._repeat_kv(kx, h)) \
            / jnp.sqrt(jnp.float32(q.shape[-1]))
        live = jax.vmap(lambda p: A.chunk_live_mask(
            p, c, kx.shape[-2], window, sinks))(pos)
        s = jnp.where(live[:, None], s, A.NEG_INF)
        o = jnp.einsum("bhcl,bhld->bhcd", jax.nn.softmax(s, axis=-1),
                       A._repeat_kv(vx, h))
        return o, kp, vp

    @pytest.mark.parametrize("n_hist,window,sinks", [
        (0, None, 0),          # FIRST chunk: empty history
        (2, None, 0),
        (3, 20, 2),            # window reaching into history + sinks
    ])
    @pytest.mark.parametrize("pack", [1, 2])
    def test_matches_xla_and_installs(self, n_hist, window, sinks, pack):
        from veles_tpu.ops import pallas_kernels as PK
        q, kn, vn, kp, vp, ptab, pos = self._setup(
            n_hist=n_hist, seed=n_hist + (window or 0))
        got_o, got_k, got_v = PK.paged_flash_prefill(
            q, kn, vn, PK.pack_heads(kp, pack), PK.pack_heads(vp, pack),
            ptab, pos, window=window, sinks=sinks)
        ref_o, ref_k, ref_v = self._xla(q, kn, vn, kp, vp, ptab, pos,
                                        window, sinks)
        # the install is a ROW COPY — bit-identical, and pages outside
        # the chunk's target untouched (the aliasing contract)
        numpy.testing.assert_array_equal(
            numpy.asarray(got_k),
            numpy.asarray(PK.pack_heads(ref_k, pack)))
        numpy.testing.assert_array_equal(
            numpy.asarray(got_v),
            numpy.asarray(PK.pack_heads(ref_v, pack)))
        numpy.testing.assert_allclose(numpy.asarray(got_o),
                                      numpy.asarray(ref_o),
                                      rtol=1e-5, atol=1e-6)

    def test_batched_lanes_install_their_own_pages(self):
        from veles_tpu.ops import pallas_kernels as PK
        q, kn, vn, kp, vp, ptab, _ = self._setup(b=2, m=4, n_pages=11,
                                                 seed=9)
        pos = jnp.asarray([8, 24], jnp.int32)   # different frontiers
        got_o, got_k, got_v = PK.paged_flash_prefill(
            q, kn, vn, kp, vp, ptab, pos)
        ref_o, ref_k, ref_v = self._xla(q, kn, vn, kp, vp, ptab, pos)
        numpy.testing.assert_array_equal(numpy.asarray(got_k),
                                         numpy.asarray(ref_k))
        numpy.testing.assert_allclose(numpy.asarray(got_o),
                                      numpy.asarray(ref_o),
                                      rtol=1e-5, atol=1e-6)

    def test_chunk_must_equal_page(self):
        from veles_tpu.ops import pallas_kernels as PK
        q, kn, vn, kp, vp, ptab, pos = self._setup()
        with pytest.raises(ValueError, match="page"):
            PK.paged_flash_prefill(q[:, :, :4], kn[:, :, :4],
                                   vn[:, :, :4], kp, vp, ptab, pos)

    @pytest.mark.parametrize("pack", [1, 4])
    def test_mha_paged_chunk_step_prefill_route(self, pack):
        """The engine's chunk program route ('prefill') against the
        XLA path at a page-aligned frontier — outputs to roundoff,
        pool installs bit-identical (in the packed rows too)."""
        from veles_tpu.ops.pallas_kernels import pack_heads
        from veles_tpu import prng
        from veles_tpu.ops.attention import (init_mha_params,
                                             mha_paged_chunk_step)
        rng = numpy.random.RandomState(4)
        d_model, n_heads, page, m, n_pages = 32, 4, 8, 4, 9
        params = jax.tree.map(
            jnp.asarray, init_mha_params(prng.get("init"), d_model,
                                         n_heads))
        x = jnp.asarray(rng.randn(1, page, d_model), jnp.float32)
        kp = jnp.asarray(rng.randn(n_pages, 4, page, 8), jnp.float32)
        vp = jnp.asarray(rng.randn(n_pages, 4, page, 8), jnp.float32)
        ptab = jnp.asarray(rng.permutation(n_pages)[:m].reshape(1, m),
                           jnp.int32)
        pos = jnp.asarray([2 * page], jnp.int32)
        ref_o, ref_k, ref_v = mha_paged_chunk_step(
            params, x, kp, vp, ptab, pos, n_heads, rope=True)
        got_o, got_k, got_v = mha_paged_chunk_step(
            params, x, pack_heads(kp, pack), pack_heads(vp, pack), ptab,
            pos, n_heads, rope=True, attn_kernel="prefill")
        numpy.testing.assert_array_equal(
            numpy.asarray(got_k), numpy.asarray(pack_heads(ref_k, pack)))
        numpy.testing.assert_array_equal(
            numpy.asarray(got_v), numpy.asarray(pack_heads(ref_v, pack)))
        numpy.testing.assert_allclose(numpy.asarray(got_o),
                                      numpy.asarray(ref_o),
                                      rtol=1e-4, atol=1e-5)


class TestServingKernelSupport:
    def test_structural_checks(self):
        from veles_tpu.ops import pallas_kernels as PK
        assert PK.serving_kernels_supported(True, 4, 2, 16, 8) \
            == (True, None)
        ok, reason = PK.serving_kernels_supported(False, 4, 2, 16, 8)
        assert not ok and "paged_kv" in reason
        ok, reason = PK.serving_kernels_supported(True, 4, 3, 16, 8)
        assert not ok and "divisible" in reason

    @pytest.mark.parametrize("kv,dh,pack", [
        (32, 64, 2),           # OPT-1.3B: two heads fill the 128 lanes
        (16, 128, 1), (8, 256, 1),
        (2, 8, 2), (4, 16, 4),  # as far as the heads divide
        (3, 64, 1), (32, 96, 1)])
    def test_pool_pack(self, kv, dh, pack):
        """ISSUE 27: heads to a pool row — as many as fill 128 lanes, as
        far as kv_heads divides; and packing is a pure relabelling."""
        from veles_tpu.ops import pallas_kernels as PK
        assert PK.pool_pack(kv, dh) == pack
        x = jnp.arange(2 * kv * 3 * dh, dtype=jnp.float32).reshape(
            2, kv, 3, dh)
        packed = numpy.asarray(PK.pack_heads(x, pack))
        assert packed.shape == (2, kv // pack, 3, pack * dh)
        for e in range(pack):
            numpy.testing.assert_array_equal(
                packed[..., e * dh:(e + 1) * dh],
                numpy.asarray(x[:, e::pack]))


class TestFlashAttentionTPUCoverage:
    """Satellite (ISSUE 7): flash_attention_tpu — the bundled jax TPU
    kernel — pinned at its edges.  The kernel itself has no CPU
    lowering in this jax (its interpret path trips a discharge-rule
    bug upstream), so off-TPU coverage pins the ROUTING: the loud
    error and the window/sink fallback; numerics are pinned by the
    TPU-marked leg."""

    def test_window_routes_away_from_kernel(self):
        """mha_forward under backend 'flash_pallas' with a window (or
        sinks) must take the XLA band path — bit-identical to backend
        'xla', even off-TPU where the kernel itself would raise."""
        from veles_tpu import prng
        from veles_tpu.ops import attention as A
        params = jax.tree.map(jnp.asarray, A.init_mha_params(
            prng.get("init"), 32, 4))
        x = jnp.asarray(numpy.random.RandomState(0).randn(2, 16, 32),
                        jnp.float32)
        ref = numpy.asarray(A.mha_forward(params, x, 4, causal=True,
                                          window=8, sinks=2))
        A.set_attention_backend("flash_pallas")
        try:
            got = numpy.asarray(A.mha_forward(params, x, 4,
                                              causal=True, window=8,
                                              sinks=2))
            if not PK.on_tpu():
                with pytest.raises(RuntimeError, match="TPU"):
                    A.mha_forward(params, x, 4, causal=True)
        finally:
            A.set_attention_backend("xla")
        numpy.testing.assert_array_equal(got, ref)

    def test_flash_serve_backend_keeps_mha_on_xla(self):
        """'flash_serve' only flips the SERVING engines' default —
        mha_forward's path stays the XLA one (bit-identical), on any
        platform."""
        from veles_tpu import prng
        from veles_tpu.ops import attention as A
        params = jax.tree.map(jnp.asarray, A.init_mha_params(
            prng.get("init"), 32, 4))
        x = jnp.asarray(numpy.random.RandomState(1).randn(2, 16, 32),
                        jnp.float32)
        ref = numpy.asarray(A.mha_forward(params, x, 4, causal=True))
        A.set_attention_backend("flash_serve")
        try:
            assert A.serving_kernel_default()
            got = numpy.asarray(A.mha_forward(params, x, 4,
                                              causal=True))
        finally:
            A.set_attention_backend("xla")
        assert not A.serving_kernel_default()
        numpy.testing.assert_array_equal(got, ref)

    def test_matches_attention_on_tpu(self):
        """The hardware parity pin: the bundled kernel vs our
        ``attention`` oracle at serving-ish shape."""
        if not PK.on_tpu():
            pytest.skip("the bundled kernel has no CPU lowering")
        from veles_tpu.ops import attention as A
        key = jax.random.PRNGKey(2)
        q = jax.random.normal(key, (2, 4, 256, 64), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(key, 1), q.shape)
        v = jax.random.normal(jax.random.fold_in(key, 2), q.shape)
        w = jax.random.normal(jax.random.fold_in(key, 3), q.shape)

        def grads(attend):
            return jax.grad(
                lambda *a: (attend(*a, causal=True) * w).sum(),
                (0, 1, 2))(q, k, v)
        ref = A.attention(q, k, v, causal=True)
        got = A.flash_attention_tpu(q, k, v, causal=True)
        numpy.testing.assert_allclose(numpy.asarray(got),
                                      numpy.asarray(ref),
                                      rtol=2e-3, atol=2e-3)
        # the backward kernels run under the fp32 policy too
        for g, r in zip(grads(A.flash_attention_tpu),
                        grads(A.attention)):
            numpy.testing.assert_allclose(numpy.asarray(g),
                                          numpy.asarray(r),
                                          rtol=2e-3, atol=2e-3)

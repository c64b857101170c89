"""Pallas kernels (interpret mode on CPU = same kernel code as TPU) and
stochastic pooling (SURVEY §2.4 custom-kernel candidates)."""

import functools

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


def _all_pages_live(monkeypatch):
    """The kernels as they were before ISSUE 29: every page of the table
    fetched and given a softmax step, live or not."""
    from veles_tpu.ops import pallas_kernels as PK

    def everything(pos, c, page, m_pages, window=None, sinks=0, xp=jnp):
        return xp.zeros_like(pos), xp.zeros_like(pos) + m_pages - 1, 0
    monkeypatch.setattr(PK, "live_pages", everything)


def _poisoned(pool, ptab, live, rng):
    """``pool`` and ``ptab`` (numpy) with every table entry that ``live``
    (lanes x entries, bool) calls dead pointed at a page of NaN, and a
    clean twin whose dead entries point at a page of noise."""
    nan_page = pool.shape[0]
    bad = numpy.concatenate(
        [pool, numpy.full((1,) + pool.shape[1:], numpy.nan, pool.dtype)])
    clean = numpy.concatenate(
        [pool, rng.randn(1, *pool.shape[1:]).astype(pool.dtype)])
    return bad, clean, numpy.where(live, ptab, nan_page).astype(numpy.int32)


def _band_pages(pos, c, page, m_pages, window, sinks):
    """Brute force over every (query row, key) pair: which pages of the
    table hold a key that ``_band`` lets some row see.  ``c == 0`` is
    the prefill kernel's history (a chunk of ``page`` rows at ``pos``,
    keys strictly below it)."""
    from veles_tpu.ops import pallas_kernels as PK
    k = numpy.arange(m_pages * page)[None, :]
    q = pos + numpy.arange(c or page)[:, None]
    base = numpy.broadcast_to((k <= q) if c else (k < pos),
                              (len(q), k.shape[1])).copy()
    return PK._band(k, q, window, sinks, base).any(0).reshape(
        m_pages, page).any(1)


class TestLivePages:
    """ISSUE 29: the range of pages the serving kernels neither fetch nor
    step over must be EXACTLY the pages on which ``_band`` is false for
    every (query row, key) pair."""

    PAGE, M = 8, 6

    @pytest.mark.parametrize("window,sinks", [
        (None, 0), (None, 3), (1, 0), (10, 0), (10, 2), (20, 9), (16, 17)])
    @pytest.mark.parametrize("c", [0, 1, 3, 8])
    def test_agrees_with_band(self, c, window, sinks):
        from veles_tpu.ops import pallas_kernels as PK
        page, m = self.PAGE, self.M
        # 0, mid page, a page's last and first row, deep, the table's end
        lanes = numpy.asarray([0, 3, 7, 8, 21, 31, 32, m * page - max(c, 1)])
        if not c:
            lanes = lanes // page * page       # a chunk is page-aligned
        first, last, sink = PK.live_pages(lanes, c, page, m, window, sinks,
                                          xp=numpy)
        j = numpy.arange(m)[None, :]
        got = (j <= last[:, None]) & ((j >= first[:, None]) | (j < sink))
        want = numpy.stack([_band_pages(int(p), c, page, m, window, sinks)
                            for p in lanes])
        numpy.testing.assert_array_equal(got, want)
        numpy.testing.assert_array_equal(
            PK.live_page_count(first, last, sink), want.sum(1))
        # the kernels' wrappers (jax) read what the host (numpy) reads
        traced = PK.live_pages(jnp.asarray(lanes, jnp.int32), c, page, m,
                               window, sinks)
        numpy.testing.assert_array_equal(traced[0], first)
        numpy.testing.assert_array_equal(traced[1], last)
        assert traced[2] == sink
        for i in range(len(lanes)):
            named = []
            for jj in range(m):
                at = (jnp.int32(jj), traced[0][i], traced[1][i], sink)
                live, entry = PK._is_live(*at), PK._live_entry(*at)
                assert bool(live) == bool(want[i, jj])
                assert 0 <= int(entry) < m
                assert int(entry) == jj or not live
                named.append(int(entry))
            # a block is copied when its index changes: the live pages,
            # and at most one more (a lane with an empty range)
            copies = 1 + sum(a != b for a, b in zip(named, named[1:]))
            assert copies <= max(int(want[i].sum()), 1) + (
                first[i] > last[i])

    def test_a_narrow_table_clips_the_range(self):
        """A table cut narrower than the lane's frontier (never handed to
        the kernels; the host's count may ask) ends the range at its last
        entry."""
        from veles_tpu.ops import pallas_kernels as PK
        first, last, sink = PK.live_pages(numpy.asarray([40]), 1, 8, 4,
                                          xp=numpy)
        assert (int(first[0]), int(last[0]), sink) == (0, 3, 0)


@pytest.mark.kernel_parity
class TestPagedFlashDecode:
    """ISSUE 7: the flash-decode serving kernel (interpret mode = the
    SAME kernel code the TPU compiles) against the XLA paged path —
    ``paged_view`` gather + dense masked softmax — which the serving
    parity matrix has already pinned bit-identical to ``generate``.

    ISSUE 43: the kernel walks each lane's live pages itself, several to
    a block.  These pools' pages are a few KB, so the kernel's own rule
    would put a whole table into one block: every case here runs at TWO
    pages a block (``pages_a_block``) unless it asks otherwise, which
    gives whole blocks, short last ones and lanes of a single page."""

    @pytest.fixture(autouse=True)
    def pages_a_block(self, monkeypatch):
        """Sets the pages a block of the kernel's walk (2 until called);
        the kernel and the host's count read the patched rule alike."""
        from veles_tpu.ops import pallas_kernels as PK

        def fix(pages):
            monkeypatch.setattr(
                PK, "flash_block_pages",
                lambda shape, itemsize, m_pages: min(pages, m_pages))
        fix(2)
        return fix

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

    # lanes of mixed depth: parked at 0, mid page, a page's last row and
    # first row, deep, the table's end
    DEPTHS = [0, 3, 7, 8, 21, 40]

    def _mixed(self, c, dh, pack, seed, page=8, m=6):
        rng = numpy.random.RandomState(seed)
        b, kv = len(self.DEPTHS), 2
        n_pages = b * m
        q = jnp.asarray(rng.randn(b, 2 * kv, c, dh), jnp.float32)
        kp = rng.randn(n_pages, kv // pack, page, pack * dh) \
            .astype(numpy.float32)
        vp = rng.randn(n_pages, kv // pack, page, pack * dh) \
            .astype(numpy.float32)
        ptab = rng.permutation(n_pages).reshape(b, m).astype(numpy.int32)
        pos = numpy.minimum(self.DEPTHS, m * page - c).astype(numpy.int32)
        return rng, q, kp, vp, ptab, pos

    @pytest.mark.parametrize("c,window,sinks", [
        (1, None, 0), (3, None, 0), (1, 10, 0), (3, 10, 2), (8, 20, 9)])
    @pytest.mark.parametrize("dh,pack", [(64, 2), (128, 1)])
    def test_skipping_dead_pages_keeps_the_bits(self, monkeypatch, c,
                                                window, sinks, dh, pack):
        """ISSUE 29: a page no query row can see is neither fetched nor
        multiplied; the outputs are those of the kernel walked over the
        WHOLE table, on lanes of mixed depth, on a packed pool (two heads
        of 64 to a row) and a plain one (128).  Without a window the live
        pages are the table's head and the blocks lie where the whole
        walk's do: the SAME BITS (a fully masked block contributed an
        exact 0.0).  Behind a window the walk's blocks are laid on the
        live pages in order (ISSUE 43), not on fixed table positions, so
        the sums associate otherwise: held to the tolerances of
        ``test_matches_xla_paged_path``."""
        from veles_tpu.ops import pallas_kernels as PK
        _, q, kp, vp, ptab, pos = self._mixed(c, dh, pack, seed=c + dh)
        got = PK.paged_flash_decode(q, kp, vp, ptab, pos, window=window,
                                    sinks=sinks)
        _all_pages_live(monkeypatch)
        ref = PK.paged_flash_decode(q, kp, vp, ptab, pos, window=window,
                                    sinks=sinks)
        if window is None:
            numpy.testing.assert_array_equal(numpy.asarray(got),
                                             numpy.asarray(ref))
        numpy.testing.assert_allclose(numpy.asarray(got), numpy.asarray(ref),
                                      rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("c,window,sinks", [
        (1, None, 0), (3, 10, 0), (3, 10, 2)])
    @pytest.mark.parametrize("dh,pack", [(64, 2), (128, 1)])
    def test_dead_pages_of_nan_are_never_read(self, c, window, sinks, dh,
                                              pack):
        """The poison case: every table entry outside a lane's live range
        points at a pool page of NaN.  The outputs are finite and equal
        the clean run's.  (The kernels before ISSUE 29 fail this: they
        multiplied the NaN keys into scores BEFORE masking them, and NaN
        plus the mask's constant is NaN.)"""
        from veles_tpu.ops import pallas_kernels as PK
        rng, q, kp, vp, ptab, pos = self._mixed(c, dh, pack, seed=7 * c)
        m = ptab.shape[1]
        live = numpy.stack([_band_pages(int(p), c, 8, m, window, sinks)
                            for p in pos])
        assert not live.all() and live.any(1).all()
        k_bad, k_clean, table = _poisoned(kp, ptab, live, rng)
        v_bad, v_clean, _ = _poisoned(vp, ptab, live, rng)
        bad = numpy.asarray(PK.paged_flash_decode(
            q, k_bad, v_bad, table, pos, window=window, sinks=sinks))
        clean = PK.paged_flash_decode(
            q, k_clean, v_clean, table, pos, window=window, sinks=sinks)
        assert numpy.isfinite(bad).all()
        numpy.testing.assert_array_equal(bad, numpy.asarray(clean))

    def test_sliding_table_with_a_base_keeps_the_bits(self, monkeypatch):
        """A sliding layer's short table begins at ``base``; the kernels
        work on ``pos - base``, and so does the range they skip by: the
        pools' writes are the same bits, the attention's output (whose
        blocks are laid on the live pages, ISSUE 43) the whole walk's to
        the tolerances of ``test_mha_paged_chunk_step_kernel_route``."""
        from veles_tpu import prng
        from veles_tpu.ops.attention import (init_mha_params,
                                             mha_paged_chunk_step)
        rng = numpy.random.RandomState(11)
        d_model, n_heads, page, m, b = 32, 4, 8, 5, 3
        params = jax.tree.map(
            jnp.asarray, init_mha_params(prng.get("init"), d_model,
                                         n_heads, n_kv_heads=2))
        x = jnp.asarray(rng.randn(b, 1, d_model), jnp.float32)
        kp = jnp.asarray(rng.randn(b * m + 1, 2, page, 8), jnp.float32)
        vp = jnp.asarray(rng.randn(b * m + 1, 2, page, 8), jnp.float32)
        ptab = jnp.asarray(1 + rng.permutation(b * m).reshape(b, m),
                           jnp.int32)
        base = jnp.asarray([0, 16, 40], jnp.int32)
        pos = jnp.asarray([5, 37, 63], jnp.int32)

        def run():
            return mha_paged_chunk_step(
                params, x, kp, vp, ptab, pos, n_heads, rope=True,
                window=20, attn_kernel="decode", base=base)
        got = run()
        _all_pages_live(monkeypatch)
        ref = run()
        for a, e in zip(got[1:], ref[1:]):
            numpy.testing.assert_array_equal(numpy.asarray(a),
                                             numpy.asarray(e))
        numpy.testing.assert_allclose(numpy.asarray(got[0]),
                                      numpy.asarray(ref[0]),
                                      rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("live", [2, 3, 4], ids=[
        "under_a_block", "one_block", "a_block_and_a_page"])
    @pytest.mark.parametrize("c", [1, 2])
    def test_lanes_about_one_block_deep(self, pages_a_block, c, live):
        """ISSUE 43: at three pages a block, lanes whose live pages are
        fewer than a block, exactly one, and one and a page (their
        frontiers mid page and on a page's last row), beside a lane of a
        single row."""
        from veles_tpu.ops import pallas_kernels as PK
        pages_a_block(3)
        q, kp, vp, ptab, _ = self._setup(b=3, c=c, m=6, n_pages=19,
                                         seed=live)
        pos = jnp.asarray([live * 8 - 8 + 3, live * 8 - c, 0], jnp.int32)
        got = PK.paged_flash_decode(q, kp, vp, ptab, pos)
        ref = self._xla(q, kp, vp, ptab, pos, c)
        numpy.testing.assert_allclose(numpy.asarray(got),
                                      numpy.asarray(ref),
                                      rtol=1e-5, atol=1e-6)

    def test_sink_pages_and_the_windows_first_page_share_a_block(
            self, pages_a_block):
        """ISSUE 43: the walk goes over the LIVE entries in order, so two
        sink pages at the table's head and the window's pages, which lie
        entries apart, fill one block of four (and a lane whose window
        still touches its sinks walks them as one range)."""
        from veles_tpu.ops import pallas_kernels as PK
        pages_a_block(4)
        q, kp, vp, ptab, _ = self._setup(b=3, c=2, m=8, n_pages=25, seed=4)
        pos = jnp.asarray([61, 37, 9], jnp.int32)
        first, last, sink = PK.live_pages(numpy.asarray(pos), 2, 8, 8, 10,
                                          12, xp=numpy)
        assert (first.tolist(), last.tolist(), sink) == (
            [6, 3, 0], [7, 4, 1], 2)
        got = PK.paged_flash_decode(q, kp, vp, ptab, pos, window=10,
                                    sinks=12)
        ref = self._xla(q, kp, vp, ptab, pos, 2, 10, 12)
        numpy.testing.assert_allclose(numpy.asarray(got),
                                      numpy.asarray(ref),
                                      rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("dh,pack", [(64, 2), (128, 1)])
    def test_a_short_last_block_beside_nan(self, pages_a_block, dh, pack):
        """ISSUE 43, the hazard of a short block: the rows of its slot
        that no copy of this block fills hold an earlier block's rows or,
        early in the call, whatever the memory held.  The scores mask
        them, but a masked NaN key still poisons a row's maximum and
        ``0 x NaN`` its sum.  Interpret mode hands a kernel its scratch
        memory full of NaN (asserted), every lane here ends on a short
        block of five pages a block and one never fills a slot: the
        outputs are finite and the XLA twin's."""
        from jax._src.pallas import primitives
        from veles_tpu.ops import pallas_kernels as PK
        assert numpy.isnan(primitives.uninitialized_value(
            (1,), jnp.float32)).all()
        pages_a_block(5)
        _, q, kp, vp, ptab, pos = self._mixed(1, dh, pack, seed=43)
        got = numpy.asarray(PK.paged_flash_decode(q, kp, vp, ptab, pos))
        assert numpy.isfinite(got).all()
        kx, vx = (jnp.asarray(a).reshape(-1, 2 // pack, 8, pack, dh)
                  .swapaxes(2, 3).reshape(-1, 2, 8, dh) for a in (kp, vp))
        ref = self._xla(q, kx, vx, jnp.asarray(ptab), jnp.asarray(pos), 1)
        numpy.testing.assert_allclose(got, numpy.asarray(ref),
                                      rtol=1e-5, atol=1e-6)

    def test_verify_rows_on_a_packed_pool(self, pages_a_block):
        """ISSUE 43: ``c`` = 2 query positions a lane (a verify step)
        over two heads to a pool row and two query heads a KV head: the
        query rows of a pool row are pack x group x c, row ``i`` at
        position ``pos + i % c``, through blocks of three pages."""
        from veles_tpu.ops import pallas_kernels as PK
        pages_a_block(3)
        q, kp, vp, ptab, pos = self._setup(b=3, h=8, kv=4, c=2, dh=64,
                                           m=7, n_pages=22, seed=12)
        got = PK.paged_flash_decode(q, PK.pack_heads(kp, 2),
                                    PK.pack_heads(vp, 2), ptab, pos,
                                    window=20)
        ref = self._xla(q, kp, vp, ptab, pos, 2, 20)
        numpy.testing.assert_allclose(numpy.asarray(got),
                                      numpy.asarray(ref),
                                      rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("shape,itemsize,width,pages", [
        ((321, 16, 32, 128), 4, 40, 4),       # opt-1.3b.chat
        ((321, 16, 32, 128), 4, 2, 2),        # ... on a narrow table
        ((577, 8, 256, 128), 2, 18, 2),       # trinity-large-ep8.longmix
        ((1089, 2, 1024, 256), 2, 17, 1),     # qwen3-next-80b-a3b-ep4
        ((9, 1, 4096, 1024), 4, 8, 1),        # a page over the target
    ], ids=["chat", "chat_narrow", "longmix", "longchat", "huge_page"])
    def test_the_pages_of_a_block_follow_the_pools_shape(
            self, monkeypatch, shape, itemsize, width, pages):
        """ISSUE 43: the pages a block are a function of what the kernel
        sees of its pools and nothing else: the bytes of one page of keys
        and values against ONE constant, capped at the table's width."""
        from veles_tpu.ops import pallas_kernels as PK
        monkeypatch.undo()            # the kernel's own rule
        assert PK.flash_block_pages(shape, itemsize, width) == pages

    def test_walk_blocks_against_a_count_by_hand(self):
        """ISSUE 43: ``flash_walk_blocks``, which the kernel's wrapper and
        the engine's ``attn_walk_blocks`` share: whole blocks of the
        lane's live pages, the last may be short, never none."""
        from veles_tpu.ops import pallas_kernels as PK
        live = numpy.asarray([0, 1, 7, 8, 9, 16, 17])
        numpy.testing.assert_array_equal(
            PK.flash_walk_blocks(live, 8), [1, 1, 1, 1, 2, 2, 3])
        numpy.testing.assert_array_equal(
            PK.flash_walk_blocks(jnp.asarray(live), 1),
            [1, 1, 7, 8, 9, 16, 17])
        # lanes of mixed depth behind a window with sinks, by hand: the
        # sink page 0, then the window's pages
        first, last, sink = PK.live_pages(
            numpy.asarray([0, 21, 40]), 1, 8, 6, 10, 2, xp=numpy)
        count = PK.live_page_count(first, last, sink)
        assert count.tolist() == [1, 3, 4]
        assert PK.flash_walk_blocks(count, 2).tolist() == [1, 2, 2]

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

    def _mixed(self, dh, pack, seed, page=8, m=6, kv=2, g=2):
        """Three lanes whose chunks begin at 0, 8 and 32 of a table of
        ``m`` pages."""
        rng = numpy.random.RandomState(seed)
        pos = numpy.asarray([0, page, 4 * page], numpy.int32)
        b = len(pos)
        q = jnp.asarray(rng.randn(b, g * kv, page, dh), jnp.float32)
        kn = jnp.asarray(rng.randn(b, kv, page, dh), jnp.float32)
        vn = jnp.asarray(rng.randn(b, kv, page, dh), jnp.float32)
        kp = rng.randn(b * m, kv // pack, page, pack * dh) \
            .astype(numpy.float32)
        vp = rng.randn(b * m, kv // pack, page, pack * dh) \
            .astype(numpy.float32)
        ptab = rng.permutation(b * m).reshape(b, m).astype(numpy.int32)
        return rng, q, kn, vn, kp, vp, ptab, pos

    @pytest.mark.parametrize("window,sinks", [(None, 0), (12, 0), (12, 3)])
    @pytest.mark.parametrize("dh,pack,head_blocks", [
        (64, 2, False), (128, 1, False), (128, 1, True)])
    def test_skipping_dead_pages_keeps_the_bits(self, monkeypatch, window,
                                                sinks, dh, pack,
                                                head_blocks):
        """ISSUE 29 in the prefill kernel: the history pages at and past
        the chunk's frontier, and those behind a window, cost neither a
        fetch nor a softmax step; outputs and installed pools are the
        same bits as the kernel's that walked the whole table; with the
        3-axis grid (a block of kv heads a step) too."""
        from veles_tpu.ops import pallas_kernels as PK
        if head_blocks:
            monkeypatch.setattr(PK, "_SCORES_BYTES", 2 * 8 * 8 * 4)
            assert PK._heads_per_step(2, 2 * 8, 8) == 1
        _, q, kn, vn, kp, vp, ptab, pos = self._mixed(dh, pack, seed=dh)
        got = PK.paged_flash_prefill(q, kn, vn, kp, vp, ptab, pos,
                                     window=window, sinks=sinks)
        _all_pages_live(monkeypatch)
        ref = PK.paged_flash_prefill(q, kn, vn, kp, vp, ptab, pos,
                                     window=window, sinks=sinks)
        for a, e in zip(got, ref):
            numpy.testing.assert_array_equal(numpy.asarray(a),
                                             numpy.asarray(e))

    @pytest.mark.parametrize("window,sinks", [(None, 0), (12, 0), (12, 3)])
    @pytest.mark.parametrize("dh,pack", [(64, 2), (128, 1)])
    def test_dead_pages_of_nan_are_never_read(self, window, sinks, dh,
                                              pack):
        """The poison case for the history walk: every entry but the live
        history's and the chunk's own page points at a page of NaN (the
        first chunk of a prompt has no live history at all: its steps all
        name entry 0, fetched and never used).  Outputs and installed
        pages are finite and equal the clean run's; the kernel before
        ISSUE 29 fails this (NaN scores before the mask)."""
        from veles_tpu.ops import pallas_kernels as PK
        rng, q, kn, vn, kp, vp, ptab, pos = self._mixed(dh, pack, seed=5)
        page, m = 8, ptab.shape[1]
        live = numpy.stack([_band_pages(int(p), 0, page, m, window, sinks)
                            for p in pos])
        own = numpy.arange(m)[None, :] == (pos // page)[:, None]
        # entry 0 of a lane with no live history is named, never read
        keep = live | own
        keep[:, 0] |= ~live.any(1)
        k_bad, k_clean, table = _poisoned(kp, ptab, keep, rng)
        v_bad, v_clean, _ = _poisoned(vp, ptab, keep, rng)
        bad = PK.paged_flash_prefill(q, kn, vn, k_bad, v_bad, table, pos,
                                     window=window, sinks=sinks)
        clean = PK.paged_flash_prefill(q, kn, vn, k_clean, v_clean, table,
                                       pos, window=window, sinks=sinks)
        assert numpy.isfinite(numpy.asarray(bad[0])).all()
        numpy.testing.assert_array_equal(numpy.asarray(bad[0]),
                                         numpy.asarray(clean[0]))
        for a, e in zip(bad[1:], clean[1:]):    # the NaN page is the last
            numpy.testing.assert_array_equal(numpy.asarray(a)[:-1],
                                             numpy.asarray(e)[:-1])

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


def _gridded_latent_decode(q, pool, ptab, pos, scale):
    """``paged_latent_decode`` as it stood before ISSUE 41 (a grid step a
    table entry, a whole page a live step), kept here as the yardstick:
    the walk at a block of one page must give its bits."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, h, c, row = q.shape
    page = pool.shape[2]
    m_pages = ptab.shape[1]
    rows = h * c
    first, last, sink = PK.live_pages(jnp.asarray(pos, jnp.int32), c, page,
                                      m_pages)

    def kernel(ptab_ref, pos_ref, first_ref, last_ref, q_ref, k_ref, o_ref,
               acc_ref, l_ref, m_ref):
        i, j = pl.program_id(0), pl.program_id(1)

        @pl.when(j == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            l_ref[...] = jnp.zeros_like(l_ref)
            m_ref[...] = jnp.full_like(m_ref, PK.NEG_INF)

        @pl.when(PK._is_live(j, first_ref[i], last_ref[i], sink))
        def _():
            k_pos = j * page + jax.lax.broadcasted_iota(
                jnp.int32, (rows, page), 1)
            q_pos = pos_ref[i] + jax.lax.broadcasted_iota(
                jnp.int32, (rows, page), 0) % c
            rows_k = k_ref[0]
            PK._flash_step(q_ref[0], rows_k, rows_k, k_pos <= q_pos, row,
                           acc_ref, l_ref, m_ref, scale=scale)

        @pl.when(j == m_pages - 1)
        def _():
            o_ref[0] = (acc_ref[...]
                        / l_ref[...][..., None]).astype(o_ref.dtype)

    def lane(i, j, *_):
        return (i, 0, 0, 0)

    def history(i, j, pt, ps, fs, ls):
        return (pt[i, PK._live_entry(j, fs[i], ls[i], sink)], 0, 0, 0)

    o = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(b, m_pages),
            in_specs=[pl.BlockSpec((1, 1, rows, row), lane),
                      pl.BlockSpec((1, 1, page, row), history)],
            out_specs=pl.BlockSpec((1, 1, rows, row), lane),
            scratch_shapes=[pltpu.VMEM((1, rows, row), jnp.float32),
                            pltpu.VMEM((1, rows), jnp.float32),
                            pltpu.VMEM((1, rows), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, 1, rows, row), q.dtype),
        interpret=True,
    )(jnp.asarray(ptab, jnp.int32), jnp.asarray(pos, jnp.int32), first,
      last, q.reshape(b, 1, rows, row), pool)
    return o.reshape(b, h, c, row)


@pytest.mark.kernel_parity
class TestPagedLatentDecode:
    """ISSUE 41: the absorbed latent-attention kernel walks each lane's
    cached rows itself (a loop of the lane's own length over blocks it
    copies by hand): against the absorbed XLA form over the gathered view,
    against the gridded kernel it replaces, and with everything it must
    not touch poisoned."""

    PAGE, M, HEADS, SCALE = 8, 6, 3, 0.17

    def _record(self):
        """As much of a model record as ``ops/latent.py`` reads: rows of
        112 numbers in 128 lanes."""
        import types
        from veles_tpu.model_config import LatentConfig
        return types.SimpleNamespace(
            latent=LatentConfig(q_rank=8, kv_rank=96, nope=16, rope=16, v=16),
            yarn=None, dtype="float32", n_heads=self.HEADS)

    def _depths(self, c, block):
        """Lanes of mixed depth whose LAST query row lies: on position 0
        (a lane that rides the step in prefill), on a block's last and
        first row, on a page's last and first row, deep, and on the
        table's last row (a lane that fills its whole table)."""
        page, m = self.PAGE, self.M
        frontier = numpy.asarray([c - 1, block - 1, block, page - 1, page,
                                  2 * page + block, 21, m * page - 1])
        return numpy.maximum(frontier - (c - 1), 0).astype(numpy.int32)

    def _setup(self, c, pos, seed, m=None, page=None):
        cfg = self._record()
        lat, page, m = cfg.latent, page or self.PAGE, m or self.M
        rng = numpy.random.RandomState(seed)
        b = len(pos)
        p = {"wk_b": jnp.asarray(rng.randn(self.HEADS, lat.kv_rank,
                                           lat.nope) * 0.2, jnp.float32),
             "wv_b": jnp.asarray(rng.randn(self.HEADS, lat.kv_rank,
                                           lat.v) * 0.2, jnp.float32)}
        q_nope = jnp.asarray(rng.randn(b, self.HEADS, c, lat.nope),
                             jnp.float32)
        q_rope = jnp.asarray(rng.randn(b, self.HEADS, c, lat.rope),
                             jnp.float32)
        pool = rng.randn(b * m, 1, page, lat.row).astype(numpy.float32)
        pool[..., lat.width:] = 0.0
        ptab = rng.permutation(b * m).reshape(b, m).astype(numpy.int32)
        return cfg, p, q_nope, q_rope, pool, ptab

    def _kernel(self, cfg, p, q_nope, q_rope, pool, ptab, pos, **how):
        from veles_tpu.ops import latent
        qa = latent.absorbed_queries(p, q_nope, q_rope, cfg)
        return PK.paged_latent_decode(qa, jnp.asarray(pool),
                                      jnp.asarray(ptab), jnp.asarray(pos),
                                      latent.softmax_scale(cfg), **how)

    def _xla(self, cfg, p, q_nope, q_rope, pool, ptab, pos):
        from veles_tpu.ops import attention as A, latent
        c = q_nope.shape[2]
        view = A.paged_view(jnp.asarray(pool), jnp.asarray(ptab))[:, 0]
        live = jax.vmap(lambda at: A.chunk_live_mask(
            at, c, view.shape[1]))(jnp.asarray(pos))[:, None]
        return latent.attend_absorbed(p, q_nope, q_rope, view, live, cfg)

    def _outputs(self, cfg, p, o_lat):
        from veles_tpu.ops import latent
        return latent.absorbed_outputs(p, o_lat[..., :cfg.latent.kv_rank],
                                       cfg)

    @pytest.mark.parametrize("block", [8, 4, 2], ids=["page", "half",
                                                      "quarter"])
    @pytest.mark.parametrize("c", [1, 2])
    def test_matches_the_absorbed_xla_form(self, monkeypatch, c, block):
        """One call over lanes of every depth, at one and at two query
        rows a head, the block a page and whole fractions of it: the
        absorbed form's outputs (``attend_absorbed`` over
        ``paged_view``)."""
        monkeypatch.setattr(PK, "_LATENT_BLOCK", block)
        pos = self._depths(c, block)
        args = self._setup(c, pos, seed=10 * c + block)
        got = self._outputs(args[0], args[1], self._kernel(*args, pos))
        ref = self._xla(*args, pos)
        numpy.testing.assert_allclose(numpy.asarray(got), numpy.asarray(ref),
                                      rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("c", [1, 2])
    def test_a_table_wider_than_any_lane_needs(self, monkeypatch, c):
        """The table's width is no part of the walk: twelve entries over
        lanes of at most three pages give what four entries give, bit for
        bit."""
        monkeypatch.setattr(PK, "_LATENT_BLOCK", 4)
        pos = numpy.asarray([0, 5, 17, 23 - c], numpy.int32)
        args = self._setup(c, pos, seed=c, m=12)
        wide = self._kernel(*args, pos)
        narrow = self._kernel(*args[:5], args[5][:, :4], pos)
        numpy.testing.assert_array_equal(numpy.asarray(wide),
                                         numpy.asarray(narrow))
        numpy.testing.assert_allclose(
            numpy.asarray(self._outputs(args[0], args[1], wide)),
            numpy.asarray(self._xla(*args, pos)), rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("block", [8, 4, 2], ids=["page", "half",
                                                      "quarter"])
    @pytest.mark.parametrize("c", [1, 2])
    def test_what_no_query_sees_is_never_read(self, monkeypatch, c, block):
        """Dead table entries point at a page of NaN and, in the
        frontier's page, the rows behind the frontier's block ARE NaN:
        the outputs are finite and those of the clean run, bit for bit."""
        monkeypatch.setattr(PK, "_LATENT_BLOCK", block)
        page, m = self.PAGE, self.M
        pos = self._depths(c, block)
        cfg, p, q_nope, q_rope, pool, ptab = self._setup(c, pos,
                                                         seed=c + block)
        clean = self._kernel(cfg, p, q_nope, q_rope, pool, ptab, pos)
        frontier = pos + c - 1
        bad = numpy.concatenate(
            [pool, numpy.full((1,) + pool.shape[1:], numpy.nan,
                              pool.dtype)])
        for i, at in enumerate(frontier):
            walked = (at // block + 1) * block      # rows the lane walks
            bad[ptab[i, at // page], 0, walked % page or page:] = numpy.nan
        dead = numpy.arange(m)[None, :] > (frontier // page)[:, None]
        assert dead.any() and not dead.all(1).any()
        nan_tab = numpy.where(dead, len(pool), ptab).astype(numpy.int32)
        got = self._kernel(cfg, p, q_nope, q_rope, bad, nan_tab, pos)
        assert numpy.isfinite(numpy.asarray(got)).all()
        numpy.testing.assert_array_equal(numpy.asarray(got),
                                         numpy.asarray(clean))

    @pytest.mark.parametrize("c", [1, 2])
    def test_a_block_of_one_page_keeps_the_gridded_kernels_bits(
            self, monkeypatch, c):
        """With the block a whole page the walk sums what the gridded
        kernel summed, in its order: the same bits."""
        from veles_tpu.ops import latent
        monkeypatch.setattr(PK, "_LATENT_BLOCK", self.PAGE)
        pos = self._depths(c, self.PAGE)
        cfg, p, q_nope, q_rope, pool, ptab = self._setup(c, pos, seed=3 + c)
        got = self._kernel(cfg, p, q_nope, q_rope, pool, ptab, pos)
        ref = _gridded_latent_decode(
            latent.absorbed_queries(p, q_nope, q_rope, cfg),
            jnp.asarray(pool), jnp.asarray(ptab), jnp.asarray(pos),
            latent.softmax_scale(cfg))
        numpy.testing.assert_array_equal(numpy.asarray(got),
                                         numpy.asarray(ref))

    @pytest.mark.parametrize("page,block", [(8, 8), (24, 24), (1024, None),
                                            (2048, None)],
                             ids=["smaller", "no_multiple", "the_cells",
                                  "twice_the_cells"])
    def test_the_block_for_every_page_size(self, page, block):
        """The one constant holds for every page the kernel is given: a
        page smaller than it, or no multiple of it, is walked whole; the
        cells' page of 1024, and one twice as long, in blocks of the
        constant.  The kernel as it ships (nothing patched) gives the
        absorbed form's outputs."""
        assert PK._latent_block(page) == (block or PK._LATENT_BLOCK)
        assert page % PK._latent_block(page) == 0
        pos = numpy.asarray([0, page - 1, page, 2 * page - 2], numpy.int32)
        args = self._setup(1, pos, seed=page, m=2, page=page)
        got = self._outputs(args[0], args[1], self._kernel(*args, pos))
        numpy.testing.assert_allclose(
            numpy.asarray(got), numpy.asarray(self._xla(*args, pos)),
            rtol=1e-5, atol=1e-5)


def _rolled_latent_prefill(q_nope, q_rope, wk, wv, pool, ptab, pos, scale,
                           kv_rank, heads=4, q_rows=256):
    """``paged_latent_prefill`` as it stood before ISSUE 44 (four heads a
    grid step; on a history page a rolled loop over blocks of 256 query
    rows, one block's scores, softmax and ``p . v`` after another's), kept
    here as the yardstick: the new order sums what it summed."""
    import math
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from veles_tpu.ops import functional as F

    b, h, c, nope = q_nope.shape
    rope = q_rope.shape[-1]
    vdim = wv.shape[-1]
    page, row = pool.shape[2:]
    tail = row - kv_rank
    m_pages = ptab.shape[1]
    hb = math.gcd(h, heads)
    qb = math.gcd(c, q_rows)
    dtype = q_nope.dtype
    precision = F._PRECISION if dtype == jnp.float32 else None
    q = jnp.concatenate(
        [q_nope, q_rope, jnp.zeros((b, h, c, tail - rope), dtype)], axis=-1)
    _, last, _ = PK.live_pages(jnp.asarray(pos, jnp.int32), c, page, m_pages)

    def dot_nt(x, y):
        return jax.lax.dot_general(
            x, y, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)

    def dot_nn(x, y):
        return jax.lax.dot_general(
            x, y, (((1,), (0,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)

    def update(e, rows, s, v, acc_ref, l_ref, m_ref):
        m_prev = m_ref[e, rows, :]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[e, rows, :] = l_ref[e, rows, :] * alpha \
            + p.sum(axis=-1, keepdims=True)
        acc_ref[e, rows, :] = acc_ref[e, rows, :] * alpha \
            + dot_nn(p.astype(v.dtype), v)
        m_ref[e, rows, :] = m_new

    def kernel(ptab_ref, pos_ref, last_ref, q_ref, wk_ref, wv_ref, pool_ref,
               o_ref, acc_ref, l_ref, m_ref):
        i, j = pl.program_id(0), pl.program_id(2)

        @pl.when(j == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            l_ref[...] = jnp.zeros_like(l_ref)
            m_ref[...] = jnp.full_like(m_ref, PK.NEG_INF)

        def expand(e):
            lat = pool_ref[0, 0]
            c_kv = lat[:, :kv_rank]
            k_nope = dot_nn(c_kv, wk_ref[e]).astype(dtype)
            v = dot_nn(c_kv, wv_ref[e]).astype(dtype)
            return k_nope, lat[:, kv_rank:], v

        def scores(e, rows, k_nope, k_tail):
            qn = q_ref[0, e, rows, :nope]
            qt = q_ref[0, e, rows, nope:]
            return (dot_nt(qn, k_nope) + dot_nt(qt, k_tail)) * scale

        @pl.when(j < last_ref[i])
        def _():
            def head(e, _):
                k_nope, k_tail, v = expand(e)

                def block(t, _):
                    rows = pl.ds(pl.multiple_of(t * qb, qb), qb)
                    update(e, rows, scores(e, rows, k_nope, k_tail), v,
                           acc_ref, l_ref, m_ref)
                    return 0
                return jax.lax.fori_loop(0, c // qb, block, 0)
            jax.lax.fori_loop(0, hb, head, 0)

        @pl.when(j == last_ref[i])
        def _():
            def head(e, _):
                k_nope, k_tail, v = expand(e)
                for lo in range(0, c, qb):
                    keys = lo + qb
                    rows = pl.ds(lo, qb)
                    s = scores(e, rows, k_nope[:keys], k_tail[:keys])
                    k_pos = jax.lax.broadcasted_iota(
                        jnp.int32, (qb, keys), 1)
                    q_pos = lo + jax.lax.broadcasted_iota(
                        jnp.int32, (qb, keys), 0)
                    s = jnp.where(k_pos <= q_pos, s, PK.NEG_INF)
                    update(e, rows, s, v[:keys], acc_ref, l_ref, m_ref)
                return 0
            jax.lax.fori_loop(0, hb, head, 0)

        @pl.when(j == m_pages - 1)
        def _():
            o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

    def lane(i, g, j, *_):
        return (i, g, 0, 0)

    def weights(i, g, j, *_):
        return (g, 0, 0)

    def history(i, g, j, pt, ps, ls):
        return (pt[i, jnp.maximum(jnp.minimum(j, ls[i]), 0)], 0, 0, 0)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, h // hb, m_pages),
            in_specs=[pl.BlockSpec((1, hb, c, nope + tail), lane),
                      pl.BlockSpec((hb, kv_rank, nope), weights),
                      pl.BlockSpec((hb, kv_rank, vdim), weights),
                      pl.BlockSpec((1, 1, page, row), history)],
            out_specs=pl.BlockSpec((1, hb, c, vdim), lane),
            scratch_shapes=[pltpu.VMEM((hb, c, vdim), jnp.float32),
                            pltpu.VMEM((hb, c, 1), jnp.float32),
                            pltpu.VMEM((hb, c, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, c, vdim), dtype),
        interpret=True,
    )(jnp.asarray(ptab, jnp.int32), jnp.asarray(pos, jnp.int32), last,
      q, wk, wv, pool)


@pytest.mark.kernel_parity
class TestPagedLatentPrefill:
    """ISSUE 44: the expanded latent-attention kernel of a chunk takes, on
    a history page, units of ``_LATENT_Q_ROWS`` query rows against the whole
    page, ``_LATENT_CHAINS`` of them a loop body with every unit's scores
    before the first's softmax: against the expanded XLA form over the
    gathered view (``attend_expanded``), against the rolled kernel it
    replaces, and with everything it must not touch poisoned."""

    PAGE, M = 8, 6

    def _record(self, heads, dtype="float32"):
        import types
        from veles_tpu.model_config import LatentConfig
        return types.SimpleNamespace(
            latent=LatentConfig(q_rank=8, kv_rank=96, nope=16, rope=16, v=16),
            yarn=None, dtype=dtype, n_heads=heads)

    def _setup(self, starts, seed, heads=4, page=None, m=None,
               dtype="float32"):
        """Lanes whose chunk starts at table entry ``starts[i]``, the pages
        behind a lane's chunk pointing at a page of NaN."""
        cfg = self._record(heads, dtype)
        lat, page, m = cfg.latent, page or self.PAGE, m or self.M
        rng = numpy.random.RandomState(seed)
        b = len(starts)
        as_dtype = jnp.dtype(dtype)
        p = {"wk_b": jnp.asarray(rng.randn(heads, lat.kv_rank, lat.nope)
                                 * 0.2, as_dtype),
             "wv_b": jnp.asarray(rng.randn(heads, lat.kv_rank, lat.v) * 0.2,
                                 as_dtype)}
        q_nope = jnp.asarray(rng.randn(b, heads, page, lat.nope), as_dtype)
        q_rope = jnp.asarray(rng.randn(b, heads, page, lat.rope), as_dtype)
        pool = rng.randn(b * m + 1, 1, page, lat.row).astype(numpy.float32)
        pool[..., lat.width:] = 0.0
        pool[-1] = numpy.nan
        ptab = rng.permutation(b * m).reshape(b, m).astype(numpy.int32)
        starts = numpy.asarray(starts)
        dead = numpy.arange(m)[None, :] > starts[:, None]
        ptab = numpy.where(dead, b * m, ptab).astype(numpy.int32)
        pos = (starts * page).astype(numpy.int32)
        return (cfg, p, q_nope, q_rope, jnp.asarray(pool, as_dtype),
                jnp.asarray(ptab), jnp.asarray(pos))

    def _kernel(self, cfg, p, q_nope, q_rope, pool, ptab, pos, fn=None):
        from veles_tpu.ops import latent
        return (fn or PK.paged_latent_prefill)(
            q_nope, q_rope, p["wk_b"], p["wv_b"], pool, ptab, pos,
            latent.softmax_scale(cfg), cfg.latent.kv_rank)

    def _xla(self, cfg, p, q_nope, q_rope, pool, ptab, pos):
        """``attend_expanded`` over the gathered view, the dead pages' NaN
        taken out of the view first (the plain form multiplies what it
        masks)."""
        from veles_tpu.ops import attention as A, latent
        c = q_nope.shape[2]
        view = A.paged_view(pool, ptab)[:, 0]
        live = jax.vmap(lambda at: A.chunk_live_mask(
            at, c, view.shape[1]))(pos)[:, None]
        view = jnp.where(live[:, 0].any(axis=1)[..., None], view, 0)
        return latent.attend_expanded(p, q_nope, q_rope, view, live, cfg)

    def _close(self, got, ref, dtype="float32"):
        assert numpy.isfinite(numpy.asarray(got, numpy.float32)).all()
        tol = (dict(rtol=1e-5, atol=2e-5) if dtype == "float32"
               else dict(rtol=3e-2, atol=3e-2))
        numpy.testing.assert_allclose(numpy.asarray(got, numpy.float32),
                                      numpy.asarray(ref, numpy.float32),
                                      **tol)

    @pytest.mark.parametrize("start", [0, 3, 5], ids=["first", "middle",
                                                      "last"])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_matches_the_expanded_xla_form(self, dtype, start):
        """The chunk at the table's first, a middle and its last entry:
        the expanded form's outputs, in float32 to rounding and in
        bfloat16."""
        args = self._setup([start], seed=start, dtype=dtype)
        self._close(self._kernel(*args), self._xla(*args), dtype)

    @pytest.mark.parametrize("page", [8, 24, 256, 1024])
    def test_every_page_size_under_the_shipped_constants(self, page):
        """Nothing patched: a page under the constants and no multiple of
        them (8, 24: one unit a head, or three of 8 rows), a page that is
        one block of its own diagonal (256) and the cells' page (1024: the
        whole chunk a unit, the chunk's own page by four blocks, two a
        body) give the expanded form's outputs and the rolled kernel's."""
        args = self._setup([1], seed=page, heads=2, page=page, m=3)
        got = self._kernel(*args)
        self._close(got, self._xla(*args))
        self._close(got, self._kernel(*args, fn=_rolled_latent_prefill))

    @pytest.mark.parametrize("heads", [2, 4, 32])
    def test_heads_a_grid_step(self, heads):
        """Fewer heads than a grid step takes, just as many, and the
        cells' 32 (eight steps a page): every head its own sums."""
        args = self._setup([2], seed=heads, heads=heads)
        got = self._kernel(*args)
        self._close(got, self._xla(*args))
        numpy.testing.assert_array_equal(
            numpy.asarray(got),
            numpy.asarray(self._kernel(*args, fn=_rolled_latent_prefill)))

    @pytest.mark.parametrize("heads,rows,chains", [
        (4, 8, 1), (4, 8, 2), (4, 16, 2), (4, 32, 2), (2, 32, 2), (4, 32, 4),
        (3, 32, 2)])
    def test_every_order_keeps_the_rolled_kernels_bits(self, monkeypatch,
                                                       heads, rows, chains):
        """Pages of 32 rows under orders that take every path of the body
        (units of a quarter, a half and the whole of a head's rows; one,
        two and four a loop body; chains of heads where a head is one unit,
        also where the heads a step do not divide by them): each query
        row's sums are those of the rolled kernel, bit for bit."""
        monkeypatch.setattr(PK, "_LATENT_Q_ROWS", rows)
        monkeypatch.setattr(PK, "_LATENT_CHAINS", chains)
        monkeypatch.setattr(PK, "_LATENT_DIAGONAL_ROWS", 8)
        args = self._setup([0, 2, 3], seed=rows + chains, heads=heads,
                           page=32, m=4)
        got = self._kernel(*args)
        self._close(got, self._xla(*args))
        numpy.testing.assert_array_equal(
            numpy.asarray(got),
            numpy.asarray(self._kernel(
                *args, fn=functools.partial(_rolled_latent_prefill,
                                            q_rows=8))))

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_a_table_wider_than_the_chunk_needs(self, dtype):
        """Twelve entries where the chunk sees three, the other nine at a
        page of NaN: finite, and what a table of three gives, bit for
        bit."""
        args = self._setup([2], seed=7, m=12, dtype=dtype)
        wide = self._kernel(*args)
        narrow = self._kernel(*args[:5], args[5][:, :3], args[6])
        assert numpy.isfinite(numpy.asarray(wide, numpy.float32)).all()
        numpy.testing.assert_array_equal(numpy.asarray(wide),
                                         numpy.asarray(narrow))
        self._close(wide, self._xla(*args), dtype)

    def test_two_lanes_at_different_depths(self):
        """One call, a lane's chunk at its table's first entry and
        another's at its fifth: each lane what it gets alone."""
        args = self._setup([0, 4], seed=11)
        both = self._kernel(*args)
        self._close(both, self._xla(*args))
        for i in range(2):
            alone = self._kernel(args[0], args[1], *(
                a[i:i + 1] for a in args[2:4]), args[4], *(
                    a[i:i + 1] for a in args[5:]))
            numpy.testing.assert_array_equal(numpy.asarray(both[i:i + 1]),
                                             numpy.asarray(alone))

    def test_chunk_must_equal_page(self):
        args = self._setup([1], seed=1)
        with pytest.raises(ValueError, match="chunk"):
            PK.paged_latent_prefill(
                args[2][:, :, :4], args[3][:, :, :4], args[1]["wk_b"],
                args[1]["wv_b"], *args[4:], 0.17, 96)


class TestServingKernelSupport:
    def test_structural_checks(self):
        from veles_tpu.ops import pallas_kernels as PK
        assert PK.serving_kernels_supported(4, 2, 16, 8) == (True, None)
        ok, reason = PK.serving_kernels_supported(4, 2, 16, 8, tp=2)
        assert not ok and "tensor-parallel" in reason
        ok, reason = PK.serving_kernels_supported(4, 3, 16, 8)
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


#: (rows, k, n, sizes, row tile): ``xing4.0-29b-a4b``'s contractions 3584
#: and 1024 and ``trinity-large-ep8``'s 3072 scaled down by 32
GROUPED = {
    "groups_end_off_a_tile": (64, 112, 32, [10, 7, 30, 17], 16),
    "first_group_empty": (64, 112, 32, [0, 20, 30, 14], 16),
    "middle_groups_empty": (64, 32, 112, [21, 0, 0, 43], 16),
    "last_group_empty": (64, 32, 112, [40, 23, 1, 0], 16),
    "one_group_holds_every_row": (64, 112, 32, [0, 0, 64, 0], 16),
    "an_eighth_of_the_rows_held": (128, 96, 96, [3, 0, 5, 1, 0, 2, 4, 1], 16),
    "no_row_held": (64, 96, 96, [0, 0, 0, 0], 16),
    "groups_smaller_than_a_tile": (64, 32, 112, [4] * 16, 16),
    "rows_not_a_whole_number_of_tiles": (72, 112, 32, [20, 1, 40, 11], 16),
    "one_tile": (40, 32, 256, [9, 0, 30], 128),
    "columns_cut": (48, 64, 256, [16, 5, 27], 16),
}


@pytest.mark.parametrize("case", GROUPED)
def test_grouped_matmul_is_ragged_dot_on_the_held_rows(case, monkeypatch):
    """ISSUE 35: the row-tiled grouped matmul against ``jax.lax.ragged_dot``
    on the same bfloat16 operands (float32 accumulation over the whole
    contraction, one rounding): every row of every group agrees, whatever
    the groups' sizes; rows behind the last group may hold anything."""
    rows, k, n, sizes, tm = GROUPED[case]
    if case == "columns_cut":       # two column cuts of 128
        monkeypatch.setattr(PK, "_GMM_BLOCK", 64 * 128 * 2)
        assert PK._gmm_columns(k, n, 2) == 128
    rng = numpy.random.default_rng(len(case))
    xs = jnp.asarray(rng.normal(size=(rows, k)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(len(sizes), k, n)) / k ** 0.5,
                    jnp.bfloat16)
    sizes = jnp.asarray(sizes, jnp.int32)
    held = int(sizes.sum())
    want = jax.lax.ragged_dot(xs, w, sizes,
                              preferred_element_type=jnp.float32)
    got = jax.jit(lambda *a: PK.grouped_matmul(*a, tm=tm))(xs, w, sizes)
    assert got.shape == (rows, n) and got.dtype == jnp.bfloat16
    # one rounding of the float32 result: half a bfloat16 step, and the
    # float32 sums themselves may differ in their last bits
    want = numpy.asarray(want[:held])
    numpy.testing.assert_allclose(
        numpy.asarray(got[:held].astype(jnp.float32)), want,
        atol=2.0 ** -8 * numpy.abs(want).max() if held else 0)


def test_grouped_visits_walk_every_tile_of_every_group_once():
    """The walk the kernel's grid follows: a (tile, group) pair for every
    pair that shares a row, tiles and groups in order; none for a group
    without rows or a tile behind the last group; the entries behind the
    last visit repeat it (their blocks are then not fetched again)."""
    sizes = jnp.asarray([20, 0, 1, 40, 0, 11], jnp.int32)
    group, tile, offsets, total = (
        numpy.asarray(a).tolist() for a in PK.grouped_visits(sizes, 96, 16))
    assert offsets == [0, 20, 20, 21, 61, 61, 72] and total == [8]
    assert len(group) == len(tile) == 96 // 16 + 6 - 1
    assert list(zip(tile, group))[:8] == [
        (0, 0), (1, 0), (1, 2), (1, 3), (2, 3), (3, 3), (3, 5), (4, 5)]
    assert set(zip(tile[8:], group[8:])) == {(4, 5)}

"""The serving-kernel switch of the engine (ISSUE 7): fallback rules,
per-dispatch counters, the page steps handed to the kernels (ISSUE 29) and
the live-width ladder.  Split from ``test_lm_fastpath.py`` (PR 30)."""

import numpy
import pytest

from lm_cases import _greedy, _params, assert_greedy, served_model


class TestAttnKernelRouting:
    """ISSUE 7: the serving-kernel switch — fallback rules, the
    per-dispatch counters, the live-width ladder, and the engine-level
    validation."""

    def test_cpu_auto_falls_back_and_counts(self):
        """On CPU, attn_kernel='auto' must serve through the XLA path
        (parity trivially intact), increment attn_kernel_fallbacks per
        dispatch, record the reason, and render the counter on
        /metrics with one # TYPE line."""
        from veles_tpu.serving import LMEngine
        from veles_tpu.serving import metrics as metrics_mod
        from veles_tpu.ops.pallas_kernels import on_tpu
        if on_tpu():
            pytest.skip("on-TPU: auto resolves to the kernel path")
        params = _params()
        engine = LMEngine(params, n_heads=2, max_len=96, slots=1,
                          paged_kv=True, prefill_chunk=8,
                          attn_kernel="auto", name="ak_auto",
                          metrics=metrics_mod.new("ak_auto")).start()
        try:
            assert not engine._kernel_active
            assert "TPU" in engine._kernel_fallback_reason
            got = numpy.concatenate(
                [[1, 2, 3], engine.submit([1, 2, 3], 4).result(
                    timeout=60)])
            numpy.testing.assert_array_equal(
                got, _greedy(params, [1, 2, 3], 4, 96))
            snap = engine.metrics.snapshot()
            assert snap["counters"]["attn_kernel_fallbacks"] > 0
            assert "attn_kernel_dispatches" not in snap["counters"]
            assert snap["gauges"]["attn_kernel_active"] == 0
            text = metrics_mod.render_prometheus()
            assert text.count("# TYPE veles_serving_"
                              "attn_kernel_fallbacks_total counter") == 1
            assert ('veles_serving_attn_kernel_fallbacks_total'
                    '{engine="ak_auto"}') in text
        finally:
            engine.stop()

    @pytest.mark.parametrize("model", ["pre_ln", "kinds"])
    def test_force_counts_kernel_dispatches(self, model):
        """'force' on CPU runs the interpret-mode kernels for real:
        every decode/prefill dispatch lands in attn_kernel_dispatches
        and none in the fallback counter — over one kind of cache and
        over two."""
        from veles_tpu.serving import LMEngine
        record, params, max_len = served_model(model == "kinds")
        engine = LMEngine(params, record, max_len=max_len, slots=1,
                          paged_kv=True, prefill_chunk=8,
                          attn_kernel="force", name="ak_force").start()
        try:
            assert engine._kernel_active
            assert_greedy(engine, [1, 2, 3],
                          engine.submit([1, 2, 3], 3).result(timeout=120), 3)
            c = engine.metrics.snapshot()["counters"]
            assert c["attn_kernel_dispatches"] > 0
            assert "attn_kernel_fallbacks" not in c
        finally:
            engine.stop()

    @pytest.mark.parametrize("band", [{}, {"window": 20, "sinks": 2}],
                             ids=["full", "window_sinks"])
    def test_page_steps_counted_as_dispatched(self, page_step_census,
                                              band, monkeypatch):
        """ISSUE 29: the engine counts, per dispatch through the kernels,
        the page steps it handed them and the live ones, with the kernels'
        own ``live_pages``: a chunk's are lanes x table width x layers, a
        decode step's the live ones alone (ISSUE 43: the flash-decode
        kernel walks them itself), in ``attn_walk_blocks`` blocks (here of
        two pages, by the kernel's own rule for a page of 2 KB against 4
        KB a block).  They equal a brute-force count over the dispatches
        made, the recorder's two columns sum to the counters, and the
        tokens are ``generate``'s."""
        import jax.numpy as jnp
        from veles_tpu.ops import pallas_kernels as PK
        from veles_tpu.ops.transformer import generate
        from veles_tpu.serving import LMEngine, tracing
        params = _params()
        monkeypatch.setattr(PK, "_FLASH_BLOCK_BYTES", 4096)
        engine = LMEngine(params, n_heads=2, max_len=96, slots=3,
                          paged_kv=True, prefill_chunk=8,
                          attn_kernel="force", name="ak_steps", **band)
        engine.start()
        count = page_step_census(engine)      # after the warm-up's calls
        try:
            rng = numpy.random.RandomState(29)
            prompts = [rng.randint(1, 16, n).tolist()
                       for n in (3, 20, 41, 9, 33)]
            outs = [f.result(timeout=300)
                    for f in [engine.submit(p, 7) for p in prompts]]
            for p, o in zip(prompts, outs):
                want = numpy.asarray(generate(
                    params, jnp.asarray([p], jnp.int32), 7, 2,
                    temperature=0.0, max_len=96, **band))[0]
                numpy.testing.assert_array_equal(
                    numpy.concatenate([p, o]), want)
            c = engine.metrics.snapshot()["counters"]
            given, live = count()
            assert (c["attn_page_steps"], c["attn_page_steps_live"]) \
                == (given, live)
            assert 0 < live < given     # what is dead is the chunks'
            walked = count(per=2)
            assert walked[0] == walked[1]     # a decode step's are live
            assert PK.flash_block_pages(engine._storage_shape, 4, 12) == 2
            assert c["attn_walk_blocks"] == walked[2]
            assert walked[1] / 2 <= walked[2] < walked[1]
            turns = engine.recorder.turns()
            assert int(turns[:, tracing.COL_ATTN_STEPS].sum()) == given
            assert int(turns[:, tracing.COL_ATTN_LIVE].sum()) == live
        finally:
            engine.stop()

    def test_flash_serve_backend_default(self):
        """set_attention_backend('flash_serve') flips the DEFAULT for
        engines built while it is set (attn_kernel=None follows it;
        explicit 0 still wins), without touching mha_forward's path."""
        from veles_tpu.ops import attention as A
        from veles_tpu.serving import LMEngine
        params = _params()
        A.set_attention_backend("flash_serve")
        try:
            eng = LMEngine(params, n_heads=2, max_len=96, slots=1,
                           paged_kv=True, prefill_chunk=8,
                           name="ak_glob")
            assert eng.attn_kernel == "auto"
            off = LMEngine(params, n_heads=2, max_len=96, slots=1,
                           paged_kv=True, prefill_chunk=8,
                           attn_kernel=0, name="ak_glob_off")
            assert off.attn_kernel == 0
        finally:
            A.set_attention_backend("xla")
        plain = LMEngine(params, n_heads=2, max_len=96, slots=1,
                         paged_kv=True, prefill_chunk=8,
                         name="ak_glob_plain")
        assert plain.attn_kernel == 0

    def test_invalid_mode_rejected(self):
        from veles_tpu.serving import LMEngine
        params = _params()
        with pytest.raises(ValueError, match="attn_kernel"):
            LMEngine(params, n_heads=2, max_len=96, slots=1,
                     paged_kv=True, prefill_chunk=8,
                     attn_kernel="sometimes", name="ak_bad")

    def test_live_width_ladder(self):
        """The decode/verify table slice (ISSUE 7 satellite): the
        width ladder is the power-of-two chain capped at max_pages,
        and _live_width covers every slot's frontier — including a
        prefilling lane parked deep in its prompt — so no write can
        clamp onto a live page."""
        from veles_tpu.serving import LMEngine
        params = _params()
        engine = LMEngine(params, n_heads=2, max_len=96, slots=2,
                          paged_kv=True, prefill_chunk=8,
                          name="ak_width")
        assert engine._width_ladder == [1, 2, 4, 8, 12]
        engine._pos[:] = 0
        assert engine._live_width(1) == 1
        engine._pos[0] = 7          # page 0 frontier
        assert engine._live_width(1) == 1
        assert engine._live_width(2) == 2   # straddles into page 1
        engine._pos[1] = 40         # a lane parked 5 pages deep
        assert engine._live_width(1) == 8
        engine._pos[1] = 88         # deepest legal frontier
        assert engine._live_width(8) == 12  # capped at max_pages

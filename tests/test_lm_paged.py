"""The paged KV pool through the engine (ISSUE 6) and the storage every
program takes donated (ISSUE 27): zero-copy prefix sharing, the
compile bound, pool pressure (queue, shed, never a hang), long-context
options through the page table, and every dispatch consuming the arrays
that went into it.  Split from ``test_lm_fastpath.py`` (PR 30)."""

import numpy
import pytest

from veles_tpu import model_config
from lm_cases import (IN_PLACE_SETS, _greedy, _params,  # noqa: F401
                      assert_greedy, check_tokens, jit_guard, make_engine,
                      served_model)


class TestStorageInPlace:
    """ISSUE 27: every engine program that returns the KV storage takes
    it DONATED — the arrays that go into a dispatch are consumed by it
    (no dispatch copies a pool or holds a second one), and the tokens
    are what they were.  Every pool AND every slot of state: a latent
    layer's one pool, a linear layer's (state, convolution tail), the
    drafting module's pool behind the stack's."""

    @staticmethod
    def _leaves(engine):
        return [a for pair in engine._storage() for a in pair]

    @pytest.mark.parametrize("features", IN_PLACE_SETS,
                             ids=lambda f: f.get("kind")
                             or "+".join(sorted(f)))
    def test_dispatches_consume_their_storage(self, features,
                                              serving_mesh):
        from veles_tpu.serving import LMEngine
        if features.get("tp"):
            serving_mesh(features["tp"])
        features = dict(features)
        kind = features.pop("kind", "pre_ln")
        if kind == "pre_ln":
            params = _params()
            engine = LMEngine(params, n_heads=2, max_len=96, slots=2,
                              name="in_place", **features)
        else:
            engine = make_engine(kind, name="in_place_" + kind, slots=2,
                                 **features)
        made = self._leaves(engine)
        # (k, v) pools, or (state, tail), or one pool of latent rows a
        # layer, the module's own layer counted where it drafts
        cfg = engine.cfg
        assert len(made) == sum(
            1 if cfg.latent is not None
            and cfg.kind(i) != model_config.LINEAR else 2
            for i in range(engine._n_pools()))
        assert not any(a.is_deleted() for a in made)
        engine.start()
        try:
            # warm-up ran every family once: what the constructor made
            # went into the first program and never came back
            assert all(a.is_deleted() for a in made)
            assert engine.metrics.snapshot()["gauges"][
                "kv_storage_in_place"] == 1
            warm = self._leaves(engine)
            assert not any(a.is_deleted() for a in warm)
            # the decode program of this engine, watched: what storage
            # each of its dispatches was handed
            name = next(n for n in ("_megastep_jit", "_verify_jit",
                                    "_step_jit")
                        if getattr(engine, n) is not None)
            real, handed = getattr(engine, name), []

            def watched(p, storage, *args):
                handed.append([a for pair in storage for a in pair])
                return real(p, storage, *args)

            setattr(engine, name, watched)
            prompt = [5, 1, 5, 1, 5, 1, 5, 1, 5, 2, 3]
            got = engine.submit(prompt, 9).result(timeout=120)
            if kind == "pre_ln":
                numpy.testing.assert_array_equal(
                    numpy.concatenate([prompt, got]),
                    _greedy(params, prompt, 9, 96))
            else:
                check_tokens(kind, engine, prompt, got, 9)
            assert handed, "no decode dispatch ran"
            assert all(a.is_deleted() for a in warm)
            for leaves in handed:
                assert all(a.is_deleted() for a in leaves)
            live = self._leaves(engine)
            assert len(live) == len(made)
            assert not any(a.is_deleted() for a in live)
            assert engine.metrics.counter("kv_storage_rebuilds") == 0
        finally:
            engine.stop()


class TestPagedKV:
    """ISSUE 6 acceptance: zero-copy prefix sharing, the paged compile
    bound, and pool-pressure behavior (queue/shed, never a hang)."""

    def test_shared_prefix_zero_copy(self):
        """ACCEPTANCE: 8 requests sharing a 40-token system prompt
        — every shared-prefix hit installs a page
        REFERENCE (kv_pages_referenced >= 7 requests × 5 chunks), no
        copy-on-write fires (appends land past the prompt), and every
        reply is bit-identical to the per-request greedy generate."""
        from veles_tpu.serving import LMEngine
        params = _params(max_len=128)
        rng = numpy.random.RandomState(0)
        C = 8
        shared = rng.randint(0, 16, 40).tolist()       # 5 full chunks
        prompts = [shared + rng.randint(0, 16, 5).tolist()
                   for _ in range(8)]
        expected = [_greedy(params, p, 4, 128) for p in prompts]
        engine = LMEngine(params, n_heads=2, max_len=128, slots=2,
                          prefix_cache=64, prefill_chunk=C,
                          paged_kv=True, name="pg_zc").start()
        try:
            for p, exp in zip(prompts, expected):
                got = numpy.concatenate(
                    [p, engine.submit(p, 4).result(timeout=60)])
                numpy.testing.assert_array_equal(got, exp)
            c = engine.metrics.snapshot()["counters"]
            assert c.get("kv_cow_copies", 0) == 0, c
            assert c["kv_pages_referenced"] >= 7 * (len(shared) // C), c
            assert c["prefix_hit_tokens"] >= 7 * len(shared) // C * C
        finally:
            engine.stop()

    @pytest.mark.parametrize("features", [
        {"prefix_cache": 16, "spec_k": 3}, {"attn_kernel": "force"},
        {"kinds": True}], ids=lambda f: "+".join(sorted(f)))
    def test_mixed_length_compile_bound(self, features, jit_guard):
        """Satellite (CI guard): a mixed-length paged workload — with
        speculation, through the kernels, over two kinds of cache —
        compiles ONE program per family: the page-table indirection
        must not reintroduce a shape-keyed compile ladder."""
        from veles_tpu.serving import LMEngine
        features = dict(features)
        record, params, max_len = served_model(
            features.pop("kinds", False))
        rng = numpy.random.RandomState(1)
        engine = LMEngine(params, record, max_len=max_len, slots=3,
                          prefill_chunk=8, paged_kv=True,
                          name="pg_mixed", **features).start()
        try:
            futures = []
            for length in (1, 3, 7, 13, 17, 25, 41):
                p = rng.randint(0, 16, length).tolist()
                futures.append((p, engine.submit(p, 5)))
            for p, f in futures:
                assert_greedy(engine, p, f.result(timeout=120), 5)
            jit_guard(engine)
        finally:
            engine.stop()

    @pytest.mark.parametrize("attn", [
        {"rope": True},
        {"rope": True, "window": 24, "sinks": 2},
        {"rope": True, "window": 24, "sinks": 2,
         "_attn_kernel": "force"},
    ], ids=lambda a: "+".join(sorted(a)))
    def test_rope_window_sinks_parity(self, attn):
        """serve_lm forwards the trainer's rope/window/sinks into the
        engine, so the paged path must hold bit-parity under them too —
        rope_rotate_batched (per-lane traced positions) and the vmapped
        chunk_live_mask against generate's shared-position math, across
        slot reuse and speculation."""
        import jax.numpy as jnp
        from veles_tpu.ops.transformer import generate
        from veles_tpu.serving import LMEngine
        params = _params()
        attn = dict(attn)
        attn_kernel = attn.pop("_attn_kernel", 0)
        prompts = [[1, 2, 3], [2, 4, 6, 8, 10, 12, 14],
                   [5, 1] * 9, list(range(1, 14))]
        n_new = 7

        def greedy(p):
            return numpy.asarray(generate(
                params, jnp.asarray([p], jnp.int32), n_new, 2,
                temperature=0.0, max_len=96, **attn))[0]

        expected = [greedy(p) for p in prompts]
        engine = LMEngine(params, n_heads=2, max_len=96, slots=2,
                          paged_kv=True, prefill_chunk=8, spec_k=2,
                          name="pg_attn", attn_kernel=attn_kernel,
                          **attn).start()
        try:
            futures = [engine.submit(p, n_new) for p in prompts]
            for p, f, exp in zip(prompts, futures, expected):
                got = numpy.concatenate([p, f.result(timeout=120)])
                numpy.testing.assert_array_equal(got, exp)
        finally:
            engine.stop()

    @pytest.mark.parametrize("caches", ["one", "two"])
    def test_pool_pressure_queues_then_completes(self, caches):
        """More concurrent demand than the pool covers: later requests
        QUEUE on pages (slots are free, pages are not) and complete as
        earlier lanes release — nothing hangs, everything stays exactly
        greedy, and the pool drains back to full when done (for two
        kinds of cache the sliding layers' pool as well, which a lane
        is admitted against too)."""
        from veles_tpu.serving import LMEngine
        record, params, max_len = served_model(caches == "two")
        rng = numpy.random.RandomState(3)
        # each request: ceil((16 + 8)/8) = 3 pages; pool of 6 runs at
        # most 2 of the 4 slots concurrently
        engine = LMEngine(params, record, max_len=max_len, slots=4,
                          paged_kv=6, prefill_chunk=8,
                          name="pg_press").start()
        try:
            prompts = [rng.randint(0, 16, 16).tolist() for _ in range(4)]
            futures = [engine.submit(p, 8) for p in prompts]
            for p, f in zip(prompts, futures):
                assert_greedy(engine, p, f.result(timeout=120), 8)
            assert engine._pool.free_pages == engine._pool.num_pages
            assert engine.verify_pool_invariants()["used_pages"] == 0
            if caches == "two":
                assert engine._wt.pool.free_pages \
                    == engine._wt.pool.num_pages
        finally:
            engine.stop()

    def test_pool_flood_rejects_with_pool_exhausted(self):
        """ACCEPTANCE (never a hang): once the queued page demand
        covers 2× the pool, new arrivals 429 with PoolExhausted —
        distinguishable from queue-depth Overloaded — and every
        admitted request still finishes."""
        import time as time_mod
        from veles_tpu.serving import LMEngine, Overloaded, PoolExhausted
        params = _params(max_len=96)
        engine = LMEngine(params, n_heads=2, max_len=96, slots=4,
                          paged_kv=6, prefill_chunk=8,
                          name="pg_flood").start()
        real_step = engine._step_jit

        def slow_step(*a):
            time_mod.sleep(0.05)
            return real_step(*a)

        engine._step_jit = slow_step
        try:
            prompt = list(range(1, 17))          # 3 pages per request
            futures, rejected = [], 0
            for _ in range(12):
                try:
                    futures.append(engine.submit(prompt, 8))
                except PoolExhausted as e:
                    assert isinstance(e, Overloaded)   # same 429 path
                    assert e.retry_after > 0
                    rejected += 1
            engine._step_jit = real_step
            assert rejected > 0
            for f in futures:
                assert len(f.result(timeout=120)) == 8
            snap = engine.metrics.snapshot()
            assert snap["counters"]["rejected_pages"] == 3 * rejected
        finally:
            engine._step_jit = real_step
            engine.stop()

    def test_unplaceable_request_refused_up_front(self):
        """A request whose worst-case span exceeds the WHOLE pool can
        never run — submit raises ValueError immediately instead of
        letting it queue to its deadline."""
        from veles_tpu.serving import LMEngine
        params = _params(max_len=96)
        engine = LMEngine(params, n_heads=2, max_len=96, slots=1,
                          paged_kv=2, prefill_chunk=8,
                          name="pg_big").start()
        try:
            with pytest.raises(ValueError, match="never be placed"):
                engine.submit(list(range(1, 30)), 8)   # needs 5 > 2
            fut = engine.submit([1, 2, 3], 8)          # 2 pages: fits
            assert len(fut.result(timeout=60)) == 8
        finally:
            engine.stop()

    def test_max_len_must_divide_by_page(self):
        from veles_tpu.serving import LMEngine
        params = _params(max_len=96)
        with pytest.raises(ValueError, match="divisible"):
            LMEngine(params, n_heads=2, max_len=96, slots=1,
                     paged_kv=True, prefill_chunk=7, name="pg_div")
        # defaulted page size (no prefill_chunk given) must pick a
        # DIVISOR of max_len, not a flat 32 that 48 can't divide by
        eng = LMEngine(params, n_heads=2, max_len=48, slots=1,
                       paged_kv=True, name="pg_div_def")
        assert eng.prefill_chunk == 24
        assert 48 % eng.prefill_chunk == 0

    def test_pool_gauges_in_metrics(self):
        """Satellite: the KV pool gauges land in the snapshot
        (/metrics.json) and the Prometheus text (/metrics)."""
        from veles_tpu.serving import LMEngine
        from veles_tpu.serving import metrics as metrics_mod
        params = _params(max_len=96)
        engine = LMEngine(params, n_heads=2, max_len=96, slots=1,
                          paged_kv=True, prefill_chunk=8,
                          prefix_cache=8, name="pg_gauge",
                          metrics=metrics_mod.new("pg_gauge")).start()
        try:
            engine.submit([1, 2, 3, 4, 5], 4).result(timeout=60)
            snap = engine.metrics.snapshot()
            g = snap["gauges"]
            assert g["kv_pages_total"] == 12 * 1     # max_pages × slots
            assert g["kv_pages_free"] <= g["kv_pages_total"]
            assert g["kv_pages_pinned"] == 0         # lane finished
            text = metrics_mod.render_prometheus()
            assert text.count(
                "# TYPE veles_serving_kv_pages_total gauge") == 1
            assert 'veles_serving_kv_pages_free{engine="pg_gauge"}' \
                in text
        finally:
            engine.stop()

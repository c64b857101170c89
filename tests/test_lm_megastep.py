"""The fused decode driver (ISSUE 13: ``megastep=K``, one ``lax.scan``
program a dispatch) and tensor-parallel decode under a ('tp',) mesh
(ISSUE 8).  Split from ``test_lm_fastpath.py`` (PR 30)."""

import numpy
import pytest

from lm_cases import MEGASTEP_SETS, _greedy, _params, jit_guard  # noqa: F401


class TestMegastep:
    """ISSUE 13: the fused K-tokens-per-dispatch decode megastep —
    greedy parity across the K × feature matrix, the
    one-program-per-(ladder × K) compile bound, boundary semantics for
    deadlines, fault isolation inside a fused dispatch, and the
    truthful cost-ledger accounting."""

    @pytest.mark.parametrize("K,features", MEGASTEP_SETS,
                             ids=lambda v: str(v) if isinstance(v, int)
                             else "+".join(sorted(v)) or "plain")
    def test_bit_identical_across_matrix(self, K, features, jit_guard,
                                         serving_mesh):
        """4 prompts through 2 slots (forced reuse) at megastep K:
        output equals the direct greedy generate bit for bit, and the
        jit cache holds the (ladder × K) bound.  K=1 must not build a
        fused program at all — the tick path IS the K=1 semantics."""
        from veles_tpu.serving import LMEngine
        if features.get("tp"):
            serving_mesh(features["tp"])
        params = _params()
        prompts = [[1, 2, 3], [2, 4, 6, 8, 10], [7, 7],
                   [5, 1, 5, 1, 5, 1, 5, 1, 5]]
        n_new = 7
        expected = [_greedy(params, p, n_new, 96) for p in prompts]
        engine = LMEngine(params, n_heads=2, max_len=96, slots=2,
                          megastep=K, name="ms_par",
                          **features).start()
        try:
            if K <= 1:
                assert engine._megastep_jit is None
            else:
                assert engine._megastep_jit is not None
            futures = [engine.submit(p, n_new) for p in prompts]
            for p, f, exp in zip(prompts, futures, expected):
                got = numpy.concatenate([p, f.result(timeout=300)])
                numpy.testing.assert_array_equal(got, exp)
            jit_guard(engine)
            if K >= 2:
                c = engine.metrics.snapshot()["counters"]
                assert c["megastep_dispatches"] >= 1
                assert c["decode_dispatches"] == \
                    c["megastep_dispatches"]
        finally:
            engine.stop()

    def test_validation_and_noop(self):
        from veles_tpu.serving import LMEngine
        params = _params()
        with pytest.raises(ValueError, match="megastep"):
            LMEngine(params, n_heads=2, max_len=96, slots=1,
                     megastep=-1, name="ms_bad")
        off = LMEngine(params, n_heads=2, max_len=96, slots=1,
                       name="ms_off")
        assert off.megastep == 0 and off._megastep_jit is None
        one = LMEngine(params, n_heads=2, max_len=96, slots=1,
                       megastep=1, name="ms_one")
        assert one._megastep_jit is None    # K=1 IS the tick path

    def test_deadline_mid_megastep_sheds_at_next_boundary(self):
        """BOUNDARY SEMANTICS (documented): a queued request whose
        deadline expires while a megastep is in flight sheds at the
        NEXT boundary — never mid-program, never wedged — while a
        request already decoding keeps its tokens (the deadline only
        ever governed queue wait, so a request that finished its
        tokens is never 503d)."""
        import time as time_mod
        from veles_tpu.serving import LMEngine
        from veles_tpu.serving.batcher import DeadlineExceeded
        params = _params(max_len=96)
        engine = LMEngine(params, n_heads=2, max_len=96, slots=1,
                          megastep=4, deadline_s=0.35,
                          name="ms_dead").start()
        real = engine._megastep_jit

        def slow(*a):
            time_mod.sleep(0.25)
            return real(*a)

        engine._megastep_jit = slow
        try:
            fa = engine.submit([1, 2, 3], 8)   # admitted instantly
            time_mod.sleep(0.05)
            fb = engine.submit([4, 5, 6], 4)   # queued behind fa
            # fa spends ~0.5s decoding (2 slow megasteps) — well past
            # deadline_s, but it FINISHES: tokens delivered, no 503
            assert len(fa.result(timeout=60)) == 8
            # the shed names its window: the megastep's K iterations
            with pytest.raises(DeadlineExceeded,
                               match="boundary sweep, window <= 4 "):
                fb.result(timeout=60)
            assert engine.metrics.snapshot()["shed"] == 1
        finally:
            engine._megastep_jit = real
            engine.stop()

    def test_fault_inside_megastep_fails_exactly_active_lanes(self):
        """CHAOS: an engine.step fault injected into the fused
        dispatch fails the lanes that were IN that megastep — and only
        them; the queued request decodes exactly greedy afterwards,
        every span tree (including the failed megastep span on the
        failed request's timeline) verifies, and the failed lane's
        pages are home."""
        from veles_tpu.serving import FaultPlan, LMEngine, SpanTracer
        from veles_tpu.serving.faults import InjectedFault
        from veles_tpu.serving.tracing import verify_integrity
        params = _params(max_len=96)
        plan = FaultPlan().arm("engine.step", calls={1})
        tracer = SpanTracer(mode="all", last=16)
        engine = LMEngine(params, n_heads=2, max_len=96, slots=1,
                          megastep=4, faults=plan, tracer=tracer,
                          name="ms_chaos", paged_kv=True,
                          prefill_chunk=8).start()
        try:
            fa = engine.submit([1, 2, 3], 6)
            fb = engine.submit([2, 4, 6, 8], 6)
            with pytest.raises(InjectedFault):
                fa.result(timeout=60)
            got = numpy.concatenate(
                [[2, 4, 6, 8], fb.result(timeout=120)])
            numpy.testing.assert_array_equal(
                got, _greedy(params, [2, 4, 6, 8], 6, 96))
            recs = tracer.requests()
            assert len(recs) == 2
            errs = [r for r in recs if r["error"]]
            assert len(errs) == 1
            verify_integrity(recs)
            assert any(s["name"] == "decode.megastep"
                       and "error" in s["attrs"]
                       for s in errs[0]["spans"])
            assert engine.verify_pool_invariants()["used_pages"] == 0
        finally:
            engine.stop()

    def test_counters_and_ledger_truthful(self):
        """The megastep_* counter family and the ISSUE 12 cost ledger:
        one decode.megastep ledger row family whose deduped dispatch
        count equals the engine's megastep_dispatches — the folded
        per-token work is never double-counted — with per-lane tokens
        riding each request's span copy, and the waste accounting
        closed (tokens + wasted == lane iterations on the plain
        path)."""
        from veles_tpu.serving import LMEngine, SpanTracer
        from veles_tpu.serving.tracing import (cost_ledger,
                                               verify_integrity)
        params = _params(max_len=128)
        tracer = SpanTracer(mode="all", last=64)
        engine = LMEngine(params, n_heads=2, max_len=128, slots=2,
                          megastep=4, paged_kv=True, prefill_chunk=8,
                          tracer=tracer, name="ms_led").start()
        try:
            prompts = [[1, 2, 3], [2, 4, 6, 8]]
            futures = [engine.submit(p, 9) for p in prompts]
            for p, f in zip(prompts, futures):
                got = numpy.concatenate([p, f.result(timeout=120)])
                numpy.testing.assert_array_equal(
                    got, _greedy(params, p, 9, 128))
            c = engine.metrics.snapshot()["counters"]
            assert c["megastep_dispatches"] >= 1
            assert c["megastep_tokens"] == 2 * 8   # n_new minus TTFT
            assert c["megastep_tokens"] \
                + c["megastep_wasted_iterations"] \
                == c["megastep_lane_iterations"]
            assert c["decode_dispatches"] == c["megastep_dispatches"]
            # a lone lane with 5 tokens left burns two whole windows of
            # K=4: the scan never exits early, and says what it wasted
            engine.submit([7, 7], 6).result(timeout=120)
            c2 = engine.metrics.snapshot()["counters"]
            assert c2["megastep_dispatches"] \
                - c["megastep_dispatches"] == 2
            assert c2["megastep_lane_iterations"] \
                - c["megastep_lane_iterations"] == 8
            assert c2["megastep_wasted_iterations"] \
                - c["megastep_wasted_iterations"] == 3
            c = c2
            recs = tracer.requests()
            verify_integrity(recs)
            rows = [r for r in cost_ledger(recs)
                    if r["op"] == "decode.megastep"]
            assert rows, "no decode.megastep ledger rows"
            assert sum(r["dispatches"] for r in rows) \
                == c["megastep_dispatches"]
            assert sum(r["lanes"] for r in rows) \
                >= sum(r["dispatches"] for r in rows)
            span = next(s for r in recs for s in r["spans"]
                        if s["name"] == "decode.megastep")
            assert span["attrs"]["K"] == 4
            assert "lane_tokens" in span["attrs"]
            assert "xK4" in str(span["attrs"]["bucket"])
        finally:
            engine.stop()


class TestShardedDecode:
    """ISSUE 8: tensor-parallel decode under a ('tp',) mesh — the
    acceptance criteria beyond the parity matrix: a 4-device mesh,
    real weight/KV sharding (not silent replication), the
    kernel-fallback rule, device-slice pinning for replicas, and the
    validation surface."""

    @pytest.mark.slow   # tp=2 legs keep sharded decode tier-1; the
    # 4-way width re-proof pays 16s per run (watchdog-headroom)
    def test_tp4_mesh_full_fastpath_parity(self, serving_mesh,
                                           jit_guard):
        """4-way sharded decode with the whole fast path stacked
        (paged + prefix cache + chunking + speculation) is
        bit-identical to single-device generate, at one program per
        family (n_heads=4 so whole heads shard 4 ways)."""
        serving_mesh(4)
        from veles_tpu.serving import LMEngine
        params = _params(n_heads=4)
        prompts = [[1, 2, 3], [2, 4, 6, 8, 10, 12, 14], [5, 1] * 9]
        n_new = 5
        expected = [_greedy(params, p, n_new, 96, n_heads=4)
                    for p in prompts]
        engine = LMEngine(params, n_heads=4, max_len=96, slots=2,
                          tp=4, paged_kv=True, prefill_chunk=8,
                          prefix_cache=32, spec_k=3,
                          name="tp4").start()
        try:
            futures = [engine.submit(p, n_new) for p in prompts]
            for p, f, exp in zip(prompts, futures, expected):
                got = numpy.concatenate([p, f.result(timeout=120)])
                numpy.testing.assert_array_equal(got, exp)
            jit_guard(engine)
        finally:
            engine.stop()

    def test_weights_and_kv_actually_sharded(self, serving_mesh):
        """The mesh must SHARD, not replicate: wq/wk/wv split over
        their output dim, wo over its input dim, and the KV pool over
        its kv_heads axis — each device holds 1/tp of the bytes."""
        serving_mesh(2)
        from veles_tpu.serving import LMEngine
        params = _params()
        engine = LMEngine(params, n_heads=2, max_len=96, slots=1,
                          tp=2, paged_kv=True, prefill_chunk=8,
                          name="tp_shard")
        blk = engine.params["blocks"][0]
        for name, axis in (("wq", 1), ("wk", 1), ("wv", 1), ("wo", 0)):
            arr = blk["attn"][name]
            shards = list(arr.addressable_shards)
            assert len(shards) == 2, name
            assert shards[0].data.shape[axis] \
                == arr.shape[axis] // 2, name
        k_pool, _ = engine._kv_pools[0]
        shards = list(k_pool.addressable_shards)
        assert len(shards) == 2
        assert shards[0].data.shape[1] == k_pool.shape[1] // 2
        # replicated leaves stay whole everywhere
        emb = engine.params["embed"]
        assert all(s.data.shape == emb.shape
                   for s in emb.addressable_shards)

    def test_kernel_fallback_under_mesh(self, serving_mesh):
        """attn_kernel under tp is a structural fallback (a
        pallas_call is single-device): resolved at CONSTRUCTION with a
        reason naming the mesh, even 'force' — the decode-through-
        the-fallback parity and per-dispatch metering ride the
        attn_kernel+tp leg of the parity matrix, so this stays a
        cheap constructor check."""
        serving_mesh(2)
        from veles_tpu.serving import LMEngine
        params = _params()
        engine = LMEngine(params, n_heads=2, max_len=96, slots=1,
                          tp=2, paged_kv=True, prefill_chunk=8,
                          attn_kernel="force", name="tp_kern")
        assert not engine._kernel_active
        assert "tensor-parallel" in engine._kernel_fallback_reason
        assert engine.metrics.gauge("attn_kernel_active") == 0

    def test_single_device_replica_pinned(self, serving_mesh):
        """``devices=[d]`` (a data-parallel replica's slice) commits
        weights and KV to that device — programs run there, output
        unchanged."""
        serving_mesh(2)
        import jax
        from veles_tpu.serving import LMEngine
        dev = jax.devices()[1]
        params = _params()
        engine = LMEngine(params, n_heads=2, max_len=96, slots=1,
                          devices=[dev], prefill_chunk=8,
                          name="dev_pin").start()
        try:
            assert list(engine.params["embed"].devices()) == [dev]
            assert list(engine._kv_pools[0][0].devices()) == [dev]
            got = numpy.concatenate(
                [[5, 6, 7], engine.submit([5, 6, 7], 4).result(
                    timeout=60)])
            numpy.testing.assert_array_equal(
                got, _greedy(params, [5, 6, 7], 4, 96))
        finally:
            engine.stop()

    def test_tp_validation(self, serving_mesh):
        from veles_tpu.serving import LMEngine
        params = _params()          # n_heads=2
        with pytest.raises(ValueError, match="divide n_heads"):
            LMEngine(params, n_heads=2, max_len=96, slots=1, tp=3,
                     name="tp_bad")
        with pytest.raises(ValueError, match="tp must be >= 0"):
            LMEngine(params, n_heads=2, max_len=96, slots=1, tp=-1,
                     name="tp_neg")
        serving_mesh(2)
        import jax
        with pytest.raises(ValueError, match="devices"):
            LMEngine(params, n_heads=2, max_len=96, slots=1,
                     tp=2, devices=jax.devices()[:1], name="tp_short")

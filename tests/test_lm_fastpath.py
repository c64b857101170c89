"""LM serving fast path (ISSUE 4): radix prefix cache, chunked prefill,
prompt-lookup speculative decoding.

The contract under test: WHATEVER fast-path combination is enabled, the
engine's greedy output is BIT-IDENTICAL to ``ops/transformer.py::
generate`` — the features may only change how fast tokens appear, never
which tokens.  Plus the compile-count bound (one program per (bucket,
k) shape, via the jit-cache guard fixture), the cache-poisoning case,
eviction-then-reuse, and the shared-system-prompt hit-rate acceptance
criterion.
"""

import time

import numpy
import pytest

from lm_cases import FEATURE_SETS, _greedy, _params, jit_guard  # noqa: F401


class TestFastPathParity:
    @pytest.mark.parametrize("features", FEATURE_SETS,
                             ids=lambda f: "+".join(sorted(f)) or "off")
    def test_bit_identical_with_slot_reuse(self, features, jit_guard,
                                           serving_mesh):
        """5 prompts of assorted lengths through 2 slots (forced slot
        reuse) under every feature combination: every output equals the
        direct greedy generate, and the jit cache stays at one program
        per family."""
        from veles_tpu.serving import LMEngine
        if features.get("tp"):
            serving_mesh(features["tp"])
        params = _params()
        prompts = [[1, 2, 3], [2, 4, 6, 8, 10], [7, 7],
                   [5, 1, 5, 1, 5, 1, 5, 1, 5],
                   list(range(1, 15)) + list(range(1, 15))]
        n_new = 7
        expected = [_greedy(params, p, n_new, 96) for p in prompts]
        engine = LMEngine(params, n_heads=2, max_len=96, slots=2,
                          name="fp_par", **features).start()
        try:
            futures = [engine.submit(p, n_new) for p in prompts]
            for p, f, exp in zip(prompts, futures, expected):
                got = numpy.concatenate([p, f.result(timeout=120)])
                numpy.testing.assert_array_equal(got, exp)
            jit_guard(engine)
            if features.get("tp") and features.get("attn_kernel"):
                # kernels under a tp mesh are a structural fallback —
                # the XLA path must have served (and metered) every
                # dispatch
                c = engine.metrics.snapshot()["counters"]
                assert c.get("attn_kernel_fallbacks", 0) > 0
                assert "attn_kernel_dispatches" not in c
        finally:
            engine.stop()

    def test_cache_poisoning_diverge_mid_chunk(self):
        """Two prompts share a prefix but diverge MID-chunk: the second
        must not reuse the first's chunk (keys are the literal chunk
        tokens) and both outputs stay exactly greedy."""
        from veles_tpu.serving import LMEngine
        params = _params()
        C = 8
        a = [1, 2, 3, 4, 5, 6, 7, 8,   9, 10, 11, 12, 13, 14, 15, 1, 2]
        b = list(a)
        b[11] = 3          # diverges inside the SECOND chunk
        exp_a = _greedy(params, a, 6, 96)
        exp_b = _greedy(params, b, 6, 96)
        engine = LMEngine(params, n_heads=2, max_len=96, slots=1,
                          prefix_cache=32, prefill_chunk=C,
                          name="fp_poison").start()
        try:
            got_a = numpy.concatenate(
                [a, engine.submit(a, 6).result(timeout=60)])
            got_b = numpy.concatenate(
                [b, engine.submit(b, 6).result(timeout=60)])
            numpy.testing.assert_array_equal(got_a, exp_a)
            numpy.testing.assert_array_equal(got_b, exp_b)
            c = engine.metrics.snapshot()["counters"]
            # b reused ONLY the first (identical) chunk — the diverged
            # second chunk missed and was recomputed
            assert c["prefix_hit_chunks"] == 1
            assert c["prefix_hit_tokens"] == C
        finally:
            engine.stop()

    def test_slot_reuse_after_eviction(self):
        """A capacity-2 cache thrashed by distinct prompts: entries
        evict (LRU), slots recycle, and every output — including a
        RE-submission of the first prompt after its entry was evicted —
        stays exactly greedy."""
        from veles_tpu.serving import LMEngine
        params = _params()
        rng = numpy.random.RandomState(4)
        prompts = [rng.randint(0, 16, 20).tolist() for _ in range(4)]
        prompts.append(list(prompts[0]))     # resubmit the evicted one
        expected = [_greedy(params, p, 5, 96) for p in prompts]
        engine = LMEngine(params, n_heads=2, max_len=96, slots=1,
                          prefix_cache=2, prefill_chunk=8,
                          name="fp_evict").start()
        try:
            for p, exp in zip(prompts, expected):
                got = numpy.concatenate(
                    [p, engine.submit(p, 5).result(timeout=60)])
                numpy.testing.assert_array_equal(got, exp)
            assert engine._trie.size <= 2      # capacity held
        finally:
            engine.stop()

    def test_shared_system_prompt_hit_rate(self):
        """ACCEPTANCE: 8 requests sharing a 40-token system prompt —
        the cache serves >= 7/8 of the shared rows (only the first
        request computes them) and every reply is bit-identical to the
        per-request greedy generate."""
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
                          name="fp_shared").start()
        try:
            for p, exp in zip(prompts, expected):
                got = numpy.concatenate(
                    [p, engine.submit(p, 4).result(timeout=60)])
                numpy.testing.assert_array_equal(got, exp)
            c = engine.metrics.snapshot()["counters"]
            shared_rows = (len(shared) // C) * C       # 40
            assert c["prefix_hit_tokens"] >= 7 * shared_rows, c
            # prefilled-token count dropped by what the cache served
            total = sum(len(p) for p in prompts)
            assert c["prefill_tokens"] == total - c["prefix_hit_tokens"]
        finally:
            engine.stop()

    def test_speculative_sub_unit_dispatches(self):
        """ACCEPTANCE: on repetitive (prompt-lookup-friendly) text the
        engine emits MORE than one token per decode dispatch — and the
        tokens are still exactly the greedy ones."""
        from veles_tpu.serving import LMEngine
        params = _params(max_len=128)
        rep = [3, 1, 4, 1, 5, 9, 2, 6] * 4
        exp = _greedy(params, rep, 32, 128)
        engine = LMEngine(params, n_heads=2, max_len=128, slots=1,
                          spec_k=4, name="fp_spec").start()
        try:
            got = numpy.concatenate(
                [rep, engine.submit(rep, 32).result(timeout=120)])
            numpy.testing.assert_array_equal(got, exp)
            c = engine.metrics.snapshot()["counters"]
            assert c["decode_dispatches"] < c["tokens_out"], c
            assert c["draft_accepted"] > 0
        finally:
            engine.stop()

    def test_mixed_workload_compile_bound(self, jit_guard):
        """ACCEPTANCE: a mixed chunked-prefill/decode/speculative
        workload over many distinct prompt lengths compiles ONE program
        per (bucket, k) shape — the jit-cache guard holds after the
        storm."""
        from veles_tpu.serving import LMEngine
        params = _params(max_len=96)
        rng = numpy.random.RandomState(1)
        engine = LMEngine(params, n_heads=2, max_len=96, slots=3,
                          prefix_cache=16, prefill_chunk=8, spec_k=3,
                          name="fp_mixed").start()
        try:
            futures = []
            for length in (1, 3, 7, 13, 17, 25, 41):
                p = rng.randint(0, 16, length).tolist()
                futures.append((p, engine.submit(p, 5)))
            for p, f in futures:
                got = numpy.concatenate([p, f.result(timeout=120)])
                numpy.testing.assert_array_equal(
                    got, _greedy(params, p, 5, 96))
            jit_guard(engine)
        finally:
            engine.stop()

    def test_spec_headroom_validation(self):
        """spec_k writes up to k positions past the committed front, so
        admission requires that headroom explicitly."""
        from veles_tpu.serving import LMEngine
        params = _params(max_len=32)
        engine = LMEngine(params, n_heads=2, max_len=32, slots=1,
                          spec_k=4, name="fp_head").start()
        try:
            with pytest.raises(ValueError, match="speculative headroom"):
                engine.submit(list(range(1, 21)), 9)   # 20+9+4 > 32
            fut = engine.submit(list(range(1, 20)), 9)  # 19+9+4 == 32
            assert len(fut.result(timeout=60)) == 9
        finally:
            engine.stop()


class TestPromptLookup:
    def test_draft_finds_recent_continuation(self):
        from veles_tpu.serving import propose_draft
        hist = [1, 2, 3, 9, 9, 1, 2, 3]
        d = propose_draft(hist, 2, max_ngram=3)
        # last trigram (1,2,3) occurred at 0 → continuation (9, 9)
        numpy.testing.assert_array_equal(d, [9, 9])

    def test_draft_prefers_most_recent_match(self):
        from veles_tpu.serving import propose_draft
        hist = [1, 2, 5, 7, 1, 2, 6, 8, 1, 2]
        d = propose_draft(hist, 2, max_ngram=3)
        # bigram (1,2) matched at index 4 (most recent) → (6, 8)
        numpy.testing.assert_array_equal(d, [6, 8])

    def test_draft_none_without_recurrence(self):
        from veles_tpu.serving import propose_draft
        assert propose_draft([1, 2, 3, 4, 5], 3) is None
        assert propose_draft([1], 3) is None

    def test_draft_short_continuation_unpadded(self):
        from veles_tpu.serving import propose_draft
        d = propose_draft([5, 6, 5, 6], 4, max_ngram=2)
        # only 2 real continuation tokens exist after the match — the
        # draft is exactly those (the engine pads to k for the fixed
        # program shape, but meters only these real tokens)
        numpy.testing.assert_array_equal(d, [5, 6])


class TestRadixCache:
    def test_match_insert_release(self):
        from veles_tpu.serving import RadixPrefixCache
        trie = RadixPrefixCache(capacity=8, chunk=4)
        a, b = (1, 2, 3, 4), (5, 6, 7, 8)
        n1 = trie.insert(trie.root, a, "rows_a")
        n2 = trie.insert(n1, b, "rows_b")
        assert trie.size == 2
        matched = trie.match([a, b])
        assert [n.rows for n in matched] == ["rows_a", "rows_b"]
        assert trie.match([b]) == []             # not a root child
        assert trie.match([a, (9, 9, 9, 9)]) == [matched[0]]
        trie.release(matched + [n1, n2])
        trie.release(trie.match([a]))            # re-pin/release cycle

    def test_eviction_skips_pinned_lru_leaf_first(self):
        from veles_tpu.serving import RadixPrefixCache
        trie = RadixPrefixCache(capacity=2, chunk=4)
        a = trie.insert(trie.root, (1,) * 4, "a")
        trie.insert(trie.root, (2,) * 4, "b")
        trie.release([a])                        # b stays pinned
        # full: inserting c must evict the LRU UNPINNED leaf — a
        c = trie.insert(trie.root, (3,) * 4, "c")
        assert c is not None and trie.size == 2
        assert trie.match([(1,) * 4]) == []      # a is gone
        assert len(trie.match([(2,) * 4])) == 1  # pinned b survived

    def test_insert_refuses_when_all_pinned(self):
        from veles_tpu.serving import RadixPrefixCache
        trie = RadixPrefixCache(capacity=1, chunk=4)
        trie.insert(trie.root, (1,) * 4, "a")    # pinned by insert
        assert trie.insert(trie.root, (2,) * 4, "b") is None
        assert trie.size == 1


class TestAdmissionTokenBudget:
    def test_long_prompt_flood_rejects_on_token_budget(self):
        """queue_tokens bounds the queued PREFILL BACKLOG: with the
        worker pinned slow, a flood of long prompts 429s once the
        queued-token budget is spent, instead of stacking unbounded
        head-of-line prefill work."""
        from veles_tpu.serving import LMEngine, Overloaded
        params = _params(max_len=96)
        engine = LMEngine(params, n_heads=2, max_len=96, slots=1,
                          queue_depth=64, queue_tokens=50,
                          name="fp_budget").start()
        real_step = engine._step_jit

        def slow_step(*a):
            time.sleep(0.05)
            return real_step(*a)

        engine._step_jit = slow_step
        try:
            prompt = list(range(1, 21))          # 20 tokens each
            futures, rejected = [], 0
            for _ in range(8):
                try:
                    futures.append(engine.submit(prompt, 4))
                except Overloaded:
                    rejected += 1
            assert rejected > 0                  # budget bit
            for f in futures:                    # admitted ones finish
                assert len(f.result(timeout=120)) == 4
            snap = engine.metrics.snapshot()
            assert snap["rejected"] == rejected
            assert snap["counters"]["rejected_tokens"] == 20 * rejected
        finally:
            engine._step_jit = real_step
            engine.stop()


class TestFastPathMetrics:
    def test_ttft_decode_histograms_and_counters_rendered(self):
        """Satellite: TTFT + decode-step histograms and the fast-path
        counters appear in BOTH the snapshot (/metrics.json) and the
        Prometheus text (/metrics), one # TYPE line per family."""
        from veles_tpu.serving import metrics as metrics_mod
        a = metrics_mod.new("fp_m1")
        b = metrics_mod.new("fp_m2")
        for m in (a, b):
            m.record_ttft(0.004)
            m.record_decode_step(0.002)
            m.inc("prefix_hit_tokens", 32)
            m.inc("draft_accepted", 3)
        snap = a.snapshot()
        assert snap["ttft"]["count"] == 1
        assert snap["decode_step"]["count"] == 1
        assert snap["counters"] == {"prefix_hit_tokens": 32,
                                    "draft_accepted": 3}
        text = metrics_mod.render_prometheus()
        assert text.count("# TYPE veles_serving_ttft histogram") == 1
        assert text.count(
            "# TYPE veles_serving_decode_step histogram") == 1
        assert text.count(
            "# TYPE veles_serving_prefix_hit_tokens_total counter") == 1
        assert 'veles_serving_ttft_bucket{engine="fp_m1",le="0.005"} 1' \
            in text
        assert 'veles_serving_draft_accepted_total{engine="fp_m2"} 3' \
            in text

    def test_engine_records_ttft_and_decode_step(self):
        from veles_tpu.serving import LMEngine
        params = _params()
        engine = LMEngine(params, n_heads=2, max_len=96, slots=1,
                          prefill_chunk=8, name="fp_hist").start()
        try:
            engine.submit([1, 2, 3, 4, 5], 4).result(timeout=60)
            snap = engine.metrics.snapshot()
            assert snap["ttft"]["count"] == 1
            assert snap["decode_step"]["count"] >= 1
        finally:
            engine.stop()


class TestLoadGenLM:
    def test_lm_prompts_shared_prefix_and_determinism(self):
        import os
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools"))
        from load_gen import lm_prompts
        a = lm_prompts(4, 3, vocab=16, mean_len=40, shared_frac=0.5,
                       seed=9)
        b = lm_prompts(4, 3, vocab=16, mean_len=40, shared_frac=0.5,
                       seed=9)
        assert a == b                            # deterministic
        shared_len = 20
        shared = a[(0, 0)][:shared_len]
        for key, prompt in a.items():
            assert prompt[:shared_len] == shared  # common system prompt
            assert len(prompt) > shared_len       # unique tail
            assert all(0 <= t < 16 for t in prompt)
        assert len({tuple(p) for p in a.values()}) == len(a)

    def test_lm_mode_end_to_end_token_accounting(self):
        """run_lm_load against a live serve_lm fast-path engine: every
        reply's generated-token count lands in the lm summary and the
        server's fast-path counters move."""
        import json
        import os
        import sys
        import urllib.request
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools"))
        from load_gen import run_lm_load
        from veles_tpu import prng
        from veles_tpu.config import root
        prng.reset()
        prng.seed_all(5)
        root.__dict__.pop("char_lm", None)
        root.char_lm.update({
            "loader": {"minibatch_size": 32, "n_train": 64,
                       "n_valid": 32, "seq_len": 16, "vocab": 16},
            "trainer": {"vocab": 16, "d_model": 32, "n_heads": 2,
                        "n_layers": 1, "max_len": 96,
                        "learning_rate": 3e-3, "n_experts": 0,
                        "pipeline_stages": 0, "remat": False},
            "decision": {"max_epochs": 1, "fail_iterations": 10},
        })
        from veles_tpu.samples import char_lm
        from veles_tpu.restful_api import serve_lm
        wf = char_lm.train()
        api = serve_lm(wf, port=0, max_new=8, slots=2, prefix_cache=32,
                       prefill_chunk=8, spec_k=2)
        try:
            summary = run_lm_load(
                "http://127.0.0.1:%d/predict" % api.port, clients=3,
                requests_per_client=2, vocab=16, mean_len=32,
                shared_frac=0.5, n_new=6, max_len=60, seed=2)
            assert summary["ok"] == summary["sent"] == 6
            assert summary["lm"]["generated_tokens"] == 6 * 6
            assert summary["lm"]["per_request_tokens"]["mean"] == 6
            assert summary["lm"]["tokens_per_sec"] > 0
            # single-engine serving stamps no replica ids — the
            # balance fields must stay absent, not read as 0
            assert "per_replica_requests" not in summary["lm"]
            with urllib.request.urlopen(
                    "http://127.0.0.1:%d/metrics.json" % api.port,
                    timeout=10) as resp:
                snap = json.loads(resp.read())
            assert snap["counters"]["tokens_out"] >= 36
            assert snap["ttft"]["count"] >= 6
        finally:
            api.stop()

        # ---- ISSUE 8: the same workflow behind serve_lm(replicas=2):
        # outputs unchanged, every reply stamped with its replica, the
        # client-side balance ratio computed, per-replica labeled
        # metrics on /metrics and replica snapshots on /metrics.json
        import jax
        if jax.device_count() < 2:
            return                       # mesh-less hosts covered above
        api = serve_lm(wf, port=0, max_new=8, slots=2, prefix_cache=32,
                       prefill_chunk=8, spec_k=2, replicas=2)
        try:
            summary = run_lm_load(
                "http://127.0.0.1:%d/predict" % api.port, clients=3,
                requests_per_client=2, vocab=16, mean_len=32,
                shared_frac=0.5, n_new=6, max_len=60, seed=2)
            assert summary["ok"] == summary["sent"] == 6
            assert summary["lm"]["generated_tokens"] == 6 * 6
            per_rep = summary["lm"]["per_replica_requests"]
            assert sum(per_rep.values()) == 6
            assert set(per_rep) <= {"0", "1"}
            ratio = summary["lm"]["replica_balance_ratio"]
            assert ratio is None or ratio >= 1.0
            with urllib.request.urlopen(
                    "http://127.0.0.1:%d/metrics.json" % api.port,
                    timeout=10) as resp:
                snap = json.loads(resp.read())
            assert len(snap["replicas"]) == 2
            assert sum(r["counters"].get("tokens_out", 0)
                       for r in snap["replicas"]) >= 36
            with urllib.request.urlopen(
                    "http://127.0.0.1:%d/metrics" % api.port,
                    timeout=10) as resp:
                text = resp.read().decode()
            assert text.count(
                "# TYPE veles_serving_requests_total counter") == 1
            assert 'engine="lm",replica="0"' in text
            assert 'engine="lm",replica="1"' in text
        finally:
            api.stop()

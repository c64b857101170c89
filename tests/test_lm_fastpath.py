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


def _params(max_len=96, vocab=16, n_heads=2, n_layers=2, d_model=32):
    import jax
    import jax.numpy as jnp
    from veles_tpu import prng
    from veles_tpu.ops.transformer import init_transformer_params
    host = init_transformer_params(prng.get("init"), vocab,
                                   d_model=d_model, n_heads=n_heads,
                                   n_layers=n_layers, max_len=max_len)
    return jax.tree.map(jnp.asarray, host)


def _greedy(params, prompt, n_new, max_len, n_heads=2):
    import jax.numpy as jnp
    from veles_tpu.ops.transformer import generate
    return numpy.asarray(generate(
        params, jnp.asarray([prompt], jnp.int32), n_new, n_heads,
        temperature=0.0, max_len=max_len))[0]


@pytest.fixture
def jit_guard():
    """Collects an engine's jitted programs and asserts the compile
    count stayed bounded: ONE program per (shape) family — chunk
    prefill, verify, install/extract, step — regardless of how many
    prompt lengths and feature mixes the workload threw at it.  The
    acceptance criterion's guard: a fast path that silently forked a
    compile per prompt length would be a dispatch-latency regression
    dressed as a feature."""
    def check(engine, prefill_buckets=1):
        if engine._paged:
            # paged mode (ISSUE 6): the page-table indirection is
            # traced DATA, so the whole mixed-length workload owns
            # exactly one chunk and one page-copy program; step/verify
            # own one program PER LIVE-WIDTH LADDER ENTRY (ISSUE 7
            # satellite — the table is sliced to the batch's live page
            # span, the paged analogue of the contiguous prompt
            # buckets), still a static bound independent of the
            # workload's prompt-length mix
            widths = len(engine._width_ladder)
            progs = {
                "step": (engine._step_jit, widths),
                "chunk": (engine._chunk_jit, 1),
                "page_copy": (engine._page_copy_jit, 1),
            }
            if engine._verify_jit is not None:
                progs["verify"] = (engine._verify_jit, widths)
            if engine._megastep_jit is not None:
                # ISSUE 13: the fused program's asserted compile bound
                # — ONE megastep program per (live-width ladder entry
                # × K) family, K fixed per engine
                progs["megastep"] = (engine._megastep_jit, widths)
            if engine._whilestep_jit is not None:
                # ISSUE 19: the while-loop megastep keeps the SAME
                # bound — the iteration count is carry data, so early
                # exit adds zero program variants
                progs["whilestep"] = (engine._whilestep_jit, widths)
            for name, (fn, bound) in progs.items():
                size = fn._cache_size()
                assert size <= bound, (
                    "%s program compiled %d variants (bound %d)"
                    % (name, size, bound))
            return
        progs = {
            "step": (engine._step_jit, 1),
            "install": (engine._install_jit, 1),
            "prefill": (engine._prefill_jit, prefill_buckets),
        }
        if engine._chunk_jit is not None:
            progs["chunk"] = (engine._chunk_jit, 1)
            progs["chunk_install"] = (engine._chunk_install_jit, 1)
            progs["chunk_extract"] = (engine._chunk_extract_jit, 1)
        if engine._verify_jit is not None:
            progs["verify"] = (engine._verify_jit, 1)
        if engine._megastep_jit is not None:
            progs["megastep"] = (engine._megastep_jit, 1)
        if engine._whilestep_jit is not None:
            progs["whilestep"] = (engine._whilestep_jit, 1)
        for name, (fn, bound) in progs.items():
            size = fn._cache_size()
            assert size <= bound, (
                "%s program compiled %d variants (bound %d)"
                % (name, size, bound))
    return check


#: the feature-off engine's parity (incl. slot reuse) is already pinned
#: by tests/test_serving.py::TestLMEngine — these legs cover what's new
FEATURE_SETS = [
    {"prefill_chunk": 8},
    {"spec_k": 3},
    {"prefix_cache": 32, "prefill_chunk": 8},
    {"prefix_cache": 32, "prefill_chunk": 8, "spec_k": 3},
    # paged KV (ISSUE 6) — the page-table indirection under every
    # fast-path combination; paged_kv=12 also exercises a pool SMALLER
    # than slots×max_pages (lanes contend for pages and still finish)
    {"paged_kv": True, "prefill_chunk": 8},
    {"paged_kv": 12, "prefill_chunk": 8},
    {"paged_kv": True, "prefill_chunk": 8, "prefix_cache": 32},
    # paged+chunk+spec WITHOUT the cache rides the slow suite: the
    # full-stack superset two lines down keeps the same paths tier-1
    # (the PR 3/8 watchdog-headroom discipline, renewed for ISSUE 17's
    # armed-transfer-guard cost on this suite)
    pytest.param({"paged_kv": True, "prefill_chunk": 8, "spec_k": 3},
                 marks=pytest.mark.slow),
    {"paged_kv": True, "prefill_chunk": 8, "prefix_cache": 32,
     "spec_k": 3},
    # Pallas serving kernels (ISSUE 7): 'force' runs the REAL kernels
    # in interpret mode on CPU — the end-to-end kernel parity leg (the
    # full fast-path combination, so chunked prefill, prefix installs
    # and speculative verify all route through the kernels); 'auto'
    # off-TPU exercises the automatic XLA fallback end to end (parity
    # via the fallback, counter asserted in TestAttnKernelRouting)
    {"paged_kv": True, "prefill_chunk": 8, "prefix_cache": 32,
     "spec_k": 3, "attn_kernel": "force"},
    {"paged_kv": True, "prefill_chunk": 8, "attn_kernel": True},
    # sharded serving (ISSUE 8): the SAME programs under a 2-device
    # tensor-parallel mesh — plain decode, chunked+speculative, the
    # full paged fast path, and kernels-requested (which must fall
    # back to the XLA path under the mesh, metered, parity intact).
    # Skips loudly via the cached conftest probe on 1-device jaxlibs.
    {"tp": 2},
    {"tp": 2, "prefill_chunk": 8, "spec_k": 3},
    # the tp2 FULL paged stack rides the slow suite: tp2+chunk+spec
    # above and the non-tp full stack keep both dimensions tier-1
    # (watchdog-headroom discipline)
    pytest.param({"tp": 2, "paged_kv": True, "prefill_chunk": 8,
                  "prefix_cache": 32, "spec_k": 3},
                 marks=pytest.mark.slow),
    {"tp": 2, "paged_kv": True, "prefill_chunk": 8,
     "attn_kernel": True},
]


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
            # without chunking, whole-prompt prefill legitimately owns
            # one program per power-of-two bucket (incl. the warmup's);
            # with chunking, the chunk program replaces them all
            if features.get("prefill_chunk"):
                buckets = 1
            else:
                from veles_tpu.serving import prompt_bucket
                buckets = len({prompt_bucket(n, 96)
                               for n in [1] + [len(p) for p in prompts]})
            jit_guard(engine, prefill_buckets=buckets)
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


#: ISSUE 27: both KV layouts, with and without speculation and the two
#: fused decode loops — every family that returns the storage
IN_PLACE_SETS = [
    {},
    {"prefill_chunk": 8, "prefix_cache": 32},
    {"spec_k": 3},
    {"megastep": 4},
    {"megastep": 4, "megastep_mode": "while", "spec_k": 3},
    {"paged_kv": True, "prefill_chunk": 8},
    {"paged_kv": True, "prefill_chunk": 8, "prefix_cache": 32,
     "spec_k": 3},
    {"paged_kv": True, "prefill_chunk": 8, "megastep": 4},
    {"paged_kv": True, "prefill_chunk": 8, "megastep": "while",
     "refill_ring": 2},
    {"paged_kv": True, "prefill_chunk": 8, "attn_kernel": "force"},
    {"tp": 2, "paged_kv": True, "prefill_chunk": 8},
]


class TestStorageInPlace:
    """ISSUE 27: every engine program that returns the KV storage takes
    it DONATED — the arrays that go into a dispatch are consumed by it
    (no dispatch copies a pool or holds a second one), and the tokens
    are what they were."""

    @staticmethod
    def _leaves(engine):
        return [a for pair in engine._storage() for a in pair]

    @pytest.mark.parametrize("features", IN_PLACE_SETS,
                             ids=lambda f: "+".join(sorted(f)) or "off")
    def test_dispatches_consume_their_storage(self, features,
                                              serving_mesh):
        from veles_tpu.serving import LMEngine
        if features.get("tp"):
            serving_mesh(features["tp"])
        params = _params()
        engine = LMEngine(params, n_heads=2, max_len=96, slots=2,
                          name="in_place", **features)
        made = self._leaves(engine)
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
            name = next(n for n in ("_whilestep_jit", "_megastep_jit",
                                    "_verify_jit", "_step_jit")
                        if getattr(engine, n) is not None)
            real, handed = getattr(engine, name), []

            def watched(p, storage, *args):
                handed.append([a for pair in storage for a in pair])
                return real(p, storage, *args)

            setattr(engine, name, watched)
            prompt = [5, 1, 5, 1, 5, 1, 5, 1, 5, 2, 3]
            got = numpy.concatenate(
                [prompt, engine.submit(prompt, 9).result(timeout=120)])
            numpy.testing.assert_array_equal(
                got, _greedy(params, prompt, 9, 96))
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

    def test_reading_programs_do_not_donate(self):
        """``chunk_extract`` only READS the caches and ``prefill`` never
        sees them: neither may consume anything — and the parameters go
        into every program and stay."""
        import jax
        from veles_tpu.serving import LMEngine
        params = _params()
        engine = LMEngine(params, n_heads=2, max_len=96, slots=2,
                          prefill_chunk=8, prefix_cache=32,
                          name="in_place_ro").start()
        try:
            p = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3, 2, 3]
            for _ in range(2):           # the second one hits the trie
                got = numpy.concatenate(
                    [p, engine.submit(p, 5).result(timeout=120)])
                numpy.testing.assert_array_equal(
                    got, _greedy(params, p, 5, 96))
            assert engine.metrics.counter("prefix_hit_chunks") >= 1
            # the trie's rows came out of chunk_extract and are alive
            node = next(iter(engine._trie.root.children.values()))
            assert not any(a.is_deleted()
                           for pair in node.rows for a in pair)
            assert not any(a.is_deleted()
                           for a in jax.tree.leaves(engine.params))
        finally:
            engine.stop()


class TestPagedKV:
    """ISSUE 6 acceptance: zero-copy prefix sharing, the paged compile
    bound, and pool-pressure behavior (queue/shed, never a hang)."""

    def test_shared_prefix_zero_copy(self):
        """ACCEPTANCE: 8 requests sharing a 40-token system prompt
        under paged_kv — every shared-prefix hit installs a page
        REFERENCE (kv_pages_referenced >= 7 requests × 5 chunks), the
        row-copy counter stays at ZERO on the pure-hit path, no
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
            assert c.get("kv_row_copies", 0) == 0, c
            assert c.get("kv_cow_copies", 0) == 0, c
            assert c["kv_pages_referenced"] >= 7 * (len(shared) // C), c
            assert c["prefix_hit_tokens"] >= 7 * len(shared) // C * C
        finally:
            engine.stop()

    def test_mixed_length_compile_bound(self, jit_guard):
        """Satellite (CI guard): a mixed-length paged workload with
        speculation compiles ONE program per family — the page-table
        indirection must not reintroduce a shape-keyed compile
        ladder."""
        from veles_tpu.serving import LMEngine
        params = _params(max_len=96)
        rng = numpy.random.RandomState(1)
        engine = LMEngine(params, n_heads=2, max_len=96, slots=3,
                          prefix_cache=16, prefill_chunk=8, spec_k=3,
                          paged_kv=True, name="pg_mixed").start()
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

    @pytest.mark.parametrize("attn", [
        # tier-1 keeps ONE representative: the kernel leg covers the
        # window/sink band, batched rope AND the Pallas in-kernel
        # reproduction in a single run; the two XLA-only geometries
        # ride the slow suite (same discipline as the PR-3 runtime
        # trim — the 870s watchdog pays per redundant heavyweight leg)
        pytest.param({"rope": True}, marks=pytest.mark.slow),
        pytest.param({"rope": True, "window": 24, "sinks": 2},
                     marks=pytest.mark.slow),
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

    def test_pool_pressure_queues_then_completes(self):
        """More concurrent demand than the pool covers: later requests
        QUEUE on pages (slots are free, pages are not) and complete as
        earlier lanes release — nothing hangs, everything stays exactly
        greedy, and the pool drains back to full when done."""
        from veles_tpu.serving import LMEngine
        params = _params(max_len=96)
        rng = numpy.random.RandomState(3)
        # each request: ceil((16 + 8)/8) = 3 pages; pool of 6 runs at
        # most 2 of the 4 slots concurrently
        engine = LMEngine(params, n_heads=2, max_len=96, slots=4,
                          paged_kv=6, prefill_chunk=8,
                          name="pg_press").start()
        try:
            prompts = [rng.randint(0, 16, 16).tolist() for _ in range(4)]
            expected = [_greedy(params, p, 8, 96) for p in prompts]
            futures = [engine.submit(p, 8) for p in prompts]
            for p, f, exp in zip(prompts, futures, expected):
                got = numpy.concatenate([p, f.result(timeout=120)])
                numpy.testing.assert_array_equal(got, exp)
            assert engine._pool.free_pages == engine._pool.num_pages
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

    def test_contiguous_geometry_falls_back(self):
        """attn_kernel on a CONTIGUOUS engine is an unsupported
        geometry — fallback with a reason naming paged_kv, never an
        error, and the serving output stays exactly greedy."""
        from veles_tpu.serving import LMEngine
        params = _params()
        engine = LMEngine(params, n_heads=2, max_len=96, slots=1,
                          prefill_chunk=8, attn_kernel="force",
                          name="ak_contig").start()
        try:
            assert not engine._kernel_active
            assert "paged_kv" in engine._kernel_fallback_reason
            got = numpy.concatenate(
                [[7, 7, 7], engine.submit([7, 7, 7], 4).result(
                    timeout=60)])
            numpy.testing.assert_array_equal(
                got, _greedy(params, [7, 7, 7], 4, 96))
            c = engine.metrics.snapshot()["counters"]
            assert c["attn_kernel_fallbacks"] > 0
        finally:
            engine.stop()

    def test_force_counts_kernel_dispatches(self):
        """'force' on CPU runs the interpret-mode kernels for real:
        every decode/prefill dispatch lands in attn_kernel_dispatches
        and none in the fallback counter."""
        from veles_tpu.serving import LMEngine
        params = _params()
        engine = LMEngine(params, n_heads=2, max_len=96, slots=1,
                          paged_kv=True, prefill_chunk=8,
                          attn_kernel="force", name="ak_force").start()
        try:
            assert engine._kernel_active
            got = numpy.concatenate(
                [[1, 2, 3], engine.submit([1, 2, 3], 3).result(
                    timeout=120)])
            numpy.testing.assert_array_equal(
                got, _greedy(params, [1, 2, 3], 3, 96))
            c = engine.metrics.snapshot()["counters"]
            assert c["attn_kernel_dispatches"] > 0
            assert "attn_kernel_fallbacks" not in c
        finally:
            engine.stop()

    @pytest.mark.parametrize("band", [{}, {"window": 20, "sinks": 2}],
                             ids=["full", "window_sinks"])
    def test_page_steps_counted_as_dispatched(self, page_step_census,
                                              band):
        """ISSUE 29: the engine counts, per dispatch through the kernels,
        the page steps it handed them (lanes x table width x layers) and
        the live ones, with the kernels' own ``live_pages``: they equal
        a brute-force count over the dispatches made, the recorder's two
        columns sum to the counters, and the tokens are ``generate``'s."""
        import jax.numpy as jnp
        from veles_tpu.ops.transformer import generate
        from veles_tpu.serving import LMEngine, tracing
        params = _params()
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
            assert 0 < live < given / 2        # most of a table is dead
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
            assert list(engine._caches[0][0].devices()) == [dev]
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


#: ISSUE 13 parity matrix: K ∈ {1, 4, 8} × the fast-path features.
#: Tier-1 keeps ONE representative per family (contiguous plain, the
#: full paged+spec stack at K=8, tp=2, interpret kernels; the K=1
#: no-op family is pinned by test_validation_and_noop); redundant
#: K × feature geometries ride the slow suite — the PR 3/8 watchdog-
#: headroom discipline.
MEGASTEP_SETS = [
    # K=1 parity rides the slow suite: test_validation_and_noop pins
    # K=1 == tick path (no fused program built), and the tick path's
    # paged+chunk+spec parity is FastPathParity's full-stack leg —
    # this entry re-proved both at 15s (watchdog-headroom discipline)
    pytest.param(1, {"paged_kv": True, "prefill_chunk": 8,
                     "spec_k": 3}, marks=pytest.mark.slow),
    (4, {}),
    (8, {"paged_kv": True, "prefill_chunk": 8, "prefix_cache": 32,
         "spec_k": 3}),
    (4, {"tp": 2, "paged_kv": True, "prefill_chunk": 8, "spec_k": 3}),
    (4, {"paged_kv": True, "prefill_chunk": 8,
         "attn_kernel": "force"}),
    pytest.param(4, {"prefill_chunk": 8}, marks=pytest.mark.slow),
    pytest.param(4, {"spec_k": 3}, marks=pytest.mark.slow),
    pytest.param(8, {}, marks=pytest.mark.slow),
    pytest.param(4, {"paged_kv": True, "prefill_chunk": 8},
                 marks=pytest.mark.slow),
    pytest.param(8, {"paged_kv": True, "prefill_chunk": 8},
                 marks=pytest.mark.slow),
    pytest.param(4, {"paged_kv": True, "prefill_chunk": 8,
                     "prefix_cache": 32, "spec_k": 3},
                 marks=pytest.mark.slow),
    pytest.param(8, {"tp": 2, "paged_kv": True, "prefill_chunk": 8},
                 marks=pytest.mark.slow),
]


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
            if features.get("prefill_chunk"):
                buckets = 1
            else:
                from veles_tpu.serving import prompt_bucket
                buckets = len({prompt_bucket(n, 96)
                               for n in [1] + [len(p) for p in prompts]})
            jit_guard(engine, prefill_buckets=buckets)
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
            with pytest.raises(DeadlineExceeded, match="boundary"):
                fb.result(timeout=60)
            assert engine.metrics.snapshot()["shed"] == 1
        finally:
            engine._megastep_jit = real
            engine.stop()

    def test_fault_inside_megastep_fails_exactly_active_lanes(self):
        """CHAOS: an engine.step fault injected into the fused
        dispatch fails the lanes that were IN that megastep — and only
        them; the queued request decodes exactly greedy afterwards,
        and every span tree (including the failed megastep span on the
        failed request's timeline) verifies."""
        from veles_tpu.serving import FaultPlan, LMEngine, SpanTracer
        from veles_tpu.serving.faults import InjectedFault
        from veles_tpu.serving.tracing import verify_integrity
        params = _params(max_len=96)
        plan = FaultPlan().arm("engine.step", calls={1})
        tracer = SpanTracer(mode="all", last=16)
        engine = LMEngine(params, n_heads=2, max_len=96, slots=1,
                          megastep=4, faults=plan, tracer=tracer,
                          name="ms_chaos").start()
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


#: ISSUE 19 while-megastep matrix: one tier-1 representative per
#: family (contiguous while, the full paged+chunk+cache+spec stack,
#: the refill ring, tp=2); redundant K × feature geometries ride the
#: slow suite (the PR 3/8 watchdog-headroom discipline).
WHILESTEP_SETS = [
    (4, {}),
    (8, {"paged_kv": True, "prefill_chunk": 8, "prefix_cache": 32,
         "spec_k": 3}),
    (4, {"paged_kv": True, "prefill_chunk": 8, "refill_ring": 2}),
    (4, {"tp": 2, "paged_kv": True, "prefill_chunk": 8, "spec_k": 3}),
    pytest.param(4, {"prefill_chunk": 8}, marks=pytest.mark.slow),
    pytest.param(8, {}, marks=pytest.mark.slow),
    pytest.param(4, {"spec_k": 3}, marks=pytest.mark.slow),
    pytest.param(8, {"paged_kv": True, "prefill_chunk": 8},
                 marks=pytest.mark.slow),
    pytest.param(8, {"paged_kv": True, "prefill_chunk": 8,
                     "refill_ring": 2, "spec_k": 3},
                 marks=pytest.mark.slow),
    pytest.param(8, {"tp": 2, "paged_kv": True, "prefill_chunk": 8},
                 marks=pytest.mark.slow),
]


class TestWhilestep:
    """ISSUE 19: the persistent while-loop decode megastep — greedy
    parity across the K × feature matrix (early exit must be invisible
    in outputs), the one-program-per-ladder-entry compile bound,
    realized-iteration early exit (the scan waste tail gone), in-graph
    refill from the standby ring, ring deadline semantics (a
    pre-prefilled request never 503s), and fault isolation including
    ring occupants."""

    @pytest.mark.parametrize("K,features", WHILESTEP_SETS,
                             ids=lambda v: str(v) if isinstance(v, int)
                             else "+".join(sorted(v)) or "plain")
    def test_bit_identical_across_matrix(self, K, features, jit_guard,
                                         serving_mesh):
        """4 prompts through 2 slots (forced reuse) at while-megastep
        cap K: output equals the direct greedy generate bit for bit,
        and the jit cache holds the one-program-per-ladder-entry bound
        — the realized iteration count is carry DATA, so early exit
        adds zero variants."""
        from veles_tpu.serving import LMEngine
        if features.get("tp"):
            serving_mesh(features["tp"])
        params = _params()
        prompts = [[1, 2, 3], [2, 4, 6, 8, 10], [7, 7],
                   [5, 1, 5, 1, 5, 1, 5, 1, 5]]
        n_new = 7
        expected = [_greedy(params, p, n_new, 96) for p in prompts]
        engine = LMEngine(params, n_heads=2, max_len=96, slots=2,
                          megastep=K, megastep_mode="while",
                          name="ws_par", **features).start()
        try:
            assert engine._whilestep_jit is not None
            assert engine._megastep_jit is None
            futures = [engine.submit(p, n_new) for p in prompts]
            for p, f, exp in zip(prompts, futures, expected):
                got = numpy.concatenate([p, f.result(timeout=300)])
                numpy.testing.assert_array_equal(got, exp)
            if features.get("prefill_chunk"):
                buckets = 1
            else:
                from veles_tpu.serving import prompt_bucket
                buckets = len({prompt_bucket(n, 96)
                               for n in [1] + [len(p) for p in prompts]})
            jit_guard(engine, prefill_buckets=buckets)
            c = engine.metrics.snapshot()["counters"]
            assert c["megastep_dispatches"] >= 1
            assert c["decode_dispatches"] == c["megastep_dispatches"]
        finally:
            engine.stop()

    def test_validation_and_alias(self):
        from veles_tpu.serving import LMEngine
        params = _params()
        with pytest.raises(ValueError, match="megastep_mode"):
            LMEngine(params, n_heads=2, max_len=96, slots=1,
                     megastep=4, megastep_mode="unroll", name="ws_bad")
        with pytest.raises(ValueError, match="iteration cap"):
            LMEngine(params, n_heads=2, max_len=96, slots=1,
                     megastep_mode="while", name="ws_cap")
        with pytest.raises(ValueError, match="refill_ring"):
            LMEngine(params, n_heads=2, max_len=96, slots=1,
                     megastep=4, refill_ring=2, name="ws_ring")
        # megastep='while' is the K=16 while-mode shorthand
        alias = LMEngine(params, n_heads=2, max_len=96, slots=1,
                         megastep="while", name="ws_alias")
        assert alias.megastep == 16
        assert alias.megastep_mode == "while"
        assert alias._whilestep_jit is not None
        assert alias._megastep_jit is None

    def test_early_exit_kills_waste_tail(self):
        """THE point of the while loop: a single lane with n_new far
        under the cap exits after its realized iterations — zero
        wasted lane iterations and a truthful `iters` span attr —
        where the scan megastep at the same K burns the full fixed
        window (the 0.225 waste record this PR retires)."""
        from veles_tpu.serving import LMEngine, SpanTracer
        params = _params(max_len=128)
        prompt, n_new = [1, 2, 3], 6
        tracer = SpanTracer(mode="all", last=16)
        engine = LMEngine(params, n_heads=2, max_len=128, slots=1,
                          megastep=16, megastep_mode="while",
                          paged_kv=True, prefill_chunk=8,
                          tracer=tracer, name="ws_exit").start()
        try:
            got = numpy.concatenate(
                [prompt, engine.submit(prompt, n_new).result(timeout=120)])
            numpy.testing.assert_array_equal(
                got, _greedy(params, prompt, n_new, 128))
            c = engine.metrics.snapshot()["counters"]
            # prefill emits the first token; the loop exits after the
            # remaining 5 — no masked tail up to K=16
            assert c["megastep_dispatches"] == 1
            assert c["megastep_tokens"] == n_new - 1
            assert c["megastep_wasted_iterations"] == 0
            assert c["megastep_lane_iterations"] == n_new - 1
            span = next(s for r in tracer.requests()
                        for s in r["spans"]
                        if s["name"] == "decode.megastep")
            assert span["attrs"]["K"] == 16
            assert span["attrs"]["iters"] == n_new - 1
        finally:
            engine.stop()
        scan = LMEngine(params, n_heads=2, max_len=128, slots=1,
                        megastep=16, paged_kv=True, prefill_chunk=8,
                        name="ws_scan").start()
        try:
            scan.submit(prompt, n_new).result(timeout=120)
            sc = scan.metrics.snapshot()["counters"]
            # the scan twin burns the whole fixed-K window
            assert sc["megastep_lane_iterations"] == 16
            assert sc["megastep_wasted_iterations"] == 16 - (n_new - 1)
        finally:
            scan.stop()

    def test_refill_ring_rearm_in_graph(self):
        """5 prompts through ONE slot with a 2-deep standby ring:
        every output exactly greedy, at least one lane re-armed
        inside the loop (megastep_refills > 0), the occupancy gauge
        drains to zero and the pool closes leak-free."""
        from veles_tpu.serving import LMEngine
        params = _params(max_len=128)
        prompts = [[1, 2, 3], [2, 4, 6, 8], [7, 7], [3, 1, 4, 1, 5],
                   [9, 8, 7]]
        n_new = 6
        expected = [_greedy(params, p, n_new, 128) for p in prompts]
        engine = LMEngine(params, n_heads=2, max_len=128, slots=1,
                          megastep=8, megastep_mode="while",
                          paged_kv=True, prefill_chunk=8,
                          refill_ring=2, name="ws_ring").start()
        try:
            futures = [engine.submit(p, n_new) for p in prompts]
            for p, f, exp in zip(prompts, futures, expected):
                got = numpy.concatenate([p, f.result(timeout=300)])
                numpy.testing.assert_array_equal(got, exp)
            c = engine.metrics.snapshot()["counters"]
            assert c["megastep_refills"] >= 1
            g = engine.metrics.snapshot()["gauges"]
            assert g["standby_ring_occupancy"] == 0
            assert g["standby_ring_peak"] >= 1
            summary = engine.verify_pool_invariants()
            assert summary["used_pages"] == 0
        finally:
            engine.stop()

    def test_ring_occupant_never_shed(self):
        """DEADLINE SEMANTICS (ISSUE 19 fix): a request sitting
        pre-prefilled in the standby ring past its deadline is
        ADMITTED work — it must complete, never 503 — while a request
        still in the queue sheds at the boundary with the shed window
        quoted from the while-loop's iteration cap."""
        import time as time_mod
        from veles_tpu.serving import LMEngine
        from veles_tpu.serving.batcher import DeadlineExceeded
        params = _params(max_len=128)
        engine = LMEngine(params, n_heads=2, max_len=128, slots=1,
                          megastep=4, megastep_mode="while",
                          paged_kv=True, prefill_chunk=8,
                          refill_ring=1, deadline_s=0.35,
                          name="ws_dead").start()
        real = engine._whilestep_jit

        def slow(*a):
            time_mod.sleep(0.25)
            return real(*a)

        engine._whilestep_jit = slow
        try:
            fa = engine.submit([1, 2, 3], 12)     # occupies the slot
            time_mod.sleep(0.05)
            fb = engine.submit([4, 5, 6], 4)      # ring-prefilled
            fc = engine.submit([6, 5, 4], 4)      # stays queued
            assert len(fa.result(timeout=60)) == 12
            # fb sat in the ring well past deadline_s — it finishes
            assert len(fb.result(timeout=60)) == 4
            with pytest.raises(DeadlineExceeded, match="window"):
                fc.result(timeout=60)
            assert engine.metrics.snapshot()["shed"] == 1
        finally:
            engine._whilestep_jit = real
            engine.stop()

    def test_fault_fails_participants_including_ring(self):
        """CHAOS: an engine.step fault during a while-megastep with a
        published standby-ring occupant fails exactly the
        participating lanes — the decoding lane AND the ring occupant
        — returns their pages leak-free, keeps sound span trees, and
        the engine serves the next request exactly greedy."""
        import time as time_mod
        from veles_tpu.serving import FaultPlan, LMEngine, SpanTracer
        from veles_tpu.serving.faults import InjectedFault
        from veles_tpu.serving.tracing import verify_integrity
        params = _params(max_len=128)
        plan = FaultPlan()
        tracer = SpanTracer(mode="all", last=32)
        engine = LMEngine(params, n_heads=2, max_len=128, slots=1,
                          megastep=4, megastep_mode="while",
                          paged_kv=True, prefill_chunk=8,
                          refill_ring=1, faults=plan, tracer=tracer,
                          name="ws_chaos").start()
        real = engine._whilestep_jit

        def slow(*a):
            time_mod.sleep(0.05)
            return real(*a)

        engine._whilestep_jit = slow
        try:
            fa = engine.submit([1, 2, 3], 40)
            fb = engine.submit([2, 4, 6, 8], 6)
            deadline = time_mod.monotonic() + 30.0
            while not any(e.ready for e in engine._ring):
                assert time_mod.monotonic() < deadline, \
                    "standby entry never became ready"
                time_mod.sleep(0.005)
            plan.arm("engine.step", kind="error", times=1)
            with pytest.raises(InjectedFault):
                fa.result(timeout=60)
            with pytest.raises(InjectedFault):
                fb.result(timeout=60)
            fc = engine.submit([9, 9, 9], 5)
            got = numpy.concatenate([[9, 9, 9], fc.result(timeout=120)])
            numpy.testing.assert_array_equal(
                got, _greedy(params, [9, 9, 9], 5, 128))
            summary = engine.verify_pool_invariants()
            assert summary["used_pages"] == 0
            recs = tracer.requests()
            verify_integrity(recs)
            errs = [r for r in recs if r["error"]]
            assert len(errs) == 2
            # the ring occupant's copy of the failed megastep span is
            # marked standby — its timeline shows WHERE it died
            assert any(s["name"] == "decode.megastep"
                       and s["attrs"].get("standby")
                       for r in errs for s in r["spans"])
        finally:
            plan.release()
            engine._whilestep_jit = real
            engine.stop()


#: ISSUE 19 seeded-sampling parity matrix: every fast-path feature
#: must sample the SAME token at the same (lane seed, position) —
#: the counter-based prng stream is keyed by coordinates, not by how
#: the engine happened to batch, chunk, speculate or fuse the step.
#: tier-1 keeps one representative per family (chunk, scan-vs-while,
#: paged, the full paged+spec while stack, the refill ring); the
#: single-feature legs the supersets subsume ride the slow suite
#: (watchdog-headroom discipline).
SEEDED_SETS = [
    {"prefill_chunk": 8},
    {"megastep": 4},
    {"megastep": 4, "megastep_mode": "while"},
    {"paged_kv": True, "prefill_chunk": 8},
    {"paged_kv": True, "prefill_chunk": 8, "spec_k": 3,
     "megastep": 4, "megastep_mode": "while"},
    {"paged_kv": True, "prefill_chunk": 8, "refill_ring": 2,
     "megastep": 4, "megastep_mode": "while"},
    pytest.param({"spec_k": 3}, marks=pytest.mark.slow),
    pytest.param({"paged_kv": True, "prefill_chunk": 8,
                  "prefix_cache": 32}, marks=pytest.mark.slow),
]


class TestSeededSampling:
    """ISSUE 19: in-graph temperature/top-k sampling with
    counter-based streams keyed by (lane seed, position) —
    bit-reproducible given sample_seed, identical across the whole
    fast-path matrix, and invisible when off (greedy stays the
    default and stays bit-identical to generate)."""

    SEED_KW = dict(temperature=0.8, top_k=5, sample_seed=123)

    def _run(self, params, features, prompts, n_new,
             name, seed_kw=None):
        from veles_tpu.serving import LMEngine
        engine = LMEngine(params, n_heads=2, max_len=96, slots=2,
                          name=name, **dict(self.SEED_KW,
                                            **(seed_kw or {})),
                          **features).start()
        try:
            futures = [engine.submit(p, n_new) for p in prompts]
            return [list(f.result(timeout=300)) for f in futures]
        finally:
            engine.stop()

    @pytest.mark.parametrize("features", SEEDED_SETS,
                             ids=lambda f: "+".join(sorted(f)))
    def test_identical_across_fastpath_matrix(self, features):
        """The per-tick engine with no features is the reference:
        every feature combination must sample the identical
        continuation for the same (sample_seed, submission order)."""
        params = _params()
        prompts = [[1, 2, 3], [2, 4, 6, 8, 10], [7, 7],
                   [5, 1, 5, 1, 5, 1, 5, 1, 5]]
        n_new = 7
        ref = self._run(params, {}, prompts, n_new, "sd_ref")
        got = self._run(params, features, prompts, n_new, "sd_leg")
        assert got == ref

    def test_tp2_identical(self, serving_mesh):
        """The sharded engine samples the same tokens — the sampling
        key is replicated data, not a per-device stream."""
        serving_mesh(2)
        params = _params()
        prompts = [[1, 2, 3], [2, 4, 6, 8, 10]]
        ref = self._run(params, {}, prompts, 6, "sd_tp_ref")
        got = self._run(params, {"tp": 2}, prompts, 6, "sd_tp")
        assert got == ref

    def test_reproducible_and_seed_sensitive(self):
        """Same seed → the identical stream on a FRESH engine; a
        different seed → a different stream (the knob is live)."""
        params = _params()
        prompts = [[1, 2, 3], [4, 5, 6, 7]]
        a = self._run(params, {}, prompts, 8, "sd_a")
        b = self._run(params, {}, prompts, 8, "sd_b")
        assert a == b
        c = self._run(params, {}, prompts, 8, "sd_c",
                      seed_kw={"sample_seed": 321})
        assert c != a

    def test_greedy_default_unchanged(self):
        """temperature=0 (the default) must not even thread the key:
        outputs stay bit-identical to generate and no sampling knob
        leaks into the dispatch signature."""
        from veles_tpu.serving import LMEngine
        params = _params()
        engine = LMEngine(params, n_heads=2, max_len=96, slots=2,
                          megastep=4, megastep_mode="while",
                          paged_kv=True, prefill_chunk=8,
                          name="sd_greedy").start()
        try:
            assert engine._sample_key_host is None
            p = [1, 2, 3]
            got = numpy.concatenate(
                [p, engine.submit(p, 7).result(timeout=120)])
            numpy.testing.assert_array_equal(
                got, _greedy(params, p, 7, 96))
        finally:
            engine.stop()

    def test_sampling_validation(self):
        from veles_tpu.serving import LMEngine
        params = _params()
        with pytest.raises(ValueError, match="sample_seed"):
            LMEngine(params, n_heads=2, max_len=96, slots=1,
                     temperature=0.8, name="sd_bad")
        with pytest.raises(ValueError, match=">= 0"):
            LMEngine(params, n_heads=2, max_len=96, slots=1,
                     temperature=-1.0, sample_seed=1, name="sd_neg")


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

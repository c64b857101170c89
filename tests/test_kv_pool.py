"""Paged KV-cache allocator lifecycle (ISSUE 6).

``serving/kv_pool.py::KVPagePool`` is pure host-side bookkeeping, so
most of this file is device-free unit coverage of its invariants:
all-or-nothing allocation, ref-counted sharing, pins refusing release,
and the scratch page never entering circulation.  The engine-level legs
pin the three lifecycle behaviors serving correctness leans on —
ref-count release when a lane finishes (shared pages survive in the
trie, owned pages return to the free list), copy-on-write leaving the
shared page bit-identical for its other referents, and pool exhaustion
resolving as 429 (PoolExhausted) or 503 (deadline shed) — never a
hang.
"""

import time

import numpy
import pytest

from lm_cases import (_params, assert_greedy, check_tokens, make_engine,
                      served_model)
from veles_tpu.serving.kv_pool import KVPagePool


class TestPoolUnit:
    def test_alloc_all_or_nothing(self):
        pool = KVPagePool(4, 8)
        assert pool.alloc(0) == []
        got = pool.alloc(3)
        assert len(got) == 3 and len(set(got)) == 3
        assert pool.free_pages == 1
        # 2 > 1 free: refused WITHOUT touching the pool
        assert pool.alloc(2) is None
        assert pool.free_pages == 1
        assert pool.alloc(1) is not None
        assert pool.free_pages == 0

    def test_scratch_page_never_allocated(self):
        pool = KVPagePool(3, 8)
        pages = pool.alloc(3)
        assert KVPagePool.SCRATCH not in pages
        assert pool.alloc(1) is None     # nothing left — 0 stayed out

    def test_refcount_share_and_release(self):
        pool = KVPagePool(2, 8)
        (p,) = pool.alloc(1)
        assert not pool.shared(p)
        pool.retain(p)                   # second referent (trie / lane)
        assert pool.shared(p) and pool.refs(p) == 2
        assert pool.release(p) is False  # survivor keeps it
        assert pool.free_pages == 1
        assert pool.release(p) is True   # last referent frees it
        assert pool.free_pages == 2

    def test_release_unallocated_raises(self):
        pool = KVPagePool(2, 8)
        with pytest.raises(RuntimeError, match="unallocated"):
            pool.release(1)              # never allocated
        (p,) = pool.alloc(1)
        pool.release(p)
        with pytest.raises(RuntimeError, match="unallocated"):
            pool.release(p)              # double free
        with pytest.raises(RuntimeError, match="unallocated"):
            pool.retain(KVPagePool.SCRATCH)

    def test_pinned_page_refuses_free(self):
        """A lane's pin turns freeing the page it still reads into a
        loud error (and leaves the reference intact) instead of a
        silent use-after-free recycle."""
        pool = KVPagePool(2, 8)
        (p,) = pool.alloc(1)
        pool.pin(p)
        with pytest.raises(RuntimeError, match="pinned"):
            pool.release(p)
        assert pool.refs(p) == 1         # reference restored
        assert pool.free_pages == 1      # not recycled
        pool.unpin(p)
        assert pool.release(p) is True
        with pytest.raises(RuntimeError, match="unpinned"):
            pool.unpin(p)

    def test_pin_unallocated_raises(self):
        pool = KVPagePool(2, 8)
        with pytest.raises(RuntimeError, match="pin of unallocated"):
            pool.pin(1)

    def test_constructor_validation(self):
        with pytest.raises(ValueError):
            KVPagePool(0, 8)
        with pytest.raises(ValueError):
            KVPagePool(4, 0)
        with pytest.raises(ValueError):
            KVPagePool(4, 8).alloc(-1)


class TestTrieEvictionReleasesPages:
    def test_on_evict_returns_pages_pinned_entries_refuse(self):
        """The paged engine wires ``RadixPrefixCache(on_evict=
        pool.release)``: evicting an unpinned entry returns its page to
        the pool, while entries a lane still pins (trie refs > 0) are
        refused — the reclamation path can never steal pages out from
        under an active lane."""
        from veles_tpu.serving import RadixPrefixCache
        pool = KVPagePool(4, 4)
        trie = RadixPrefixCache(capacity=8, chunk=4,
                                on_evict=pool.release)
        (pa,) = pool.alloc(1)
        (pb,) = pool.alloc(1)
        na = trie.insert(trie.root, (1,) * 4, pa)    # pinned by insert
        nb = trie.insert(na, (2,) * 4, pb)
        trie.release([nb])                           # b evictable
        assert pool.free_pages == 2
        assert trie.evict_one() is True              # drops b → pool
        assert pool.free_pages == 3
        assert trie.evict_one() is False             # a still pinned
        assert pool.free_pages == 3
        trie.release([na])
        assert trie.evict_one() is True
        assert pool.free_pages == 4


def _paged_engine(caches, name, **kw):
    """An engine over ``caches`` kinds of cache: ``one`` is the
    ``pre_ln`` model; ``two`` is the small ``sandwich`` model (sliding
    and full layers: a table and an allocator each); ``latent``,
    ``linear`` and ``mtp`` are :func:`lm_cases.make_engine`'s (one pool
    of latent rows; slots of state beside a pool; the drafting module's
    pool behind the stack's, two steps' headroom a lane)."""
    if caches not in ("one", "two"):
        return make_engine(caches, name=name, **kw)
    from veles_tpu.serving import LMEngine
    record, params, max_len = served_model(caches == "two")
    return LMEngine(params, record, max_len=max_len, prefill_chunk=8,
                    name=name, **kw)


def _served(caches, engine, prompt, out, n_new):
    """``out`` is the reference's ``n_new`` tokens after ``prompt``."""
    if caches in ("one", "two"):
        assert_greedy(engine, prompt, out, n_new)
    else:
        check_tokens(caches, engine, prompt, out, n_new)


def _trie(caches, capacity):
    """The prefix cache's keyword for ``one`` kind of cache; none for
    the others: those engines refuse it, and their legs say what they
    check in the trie's place."""
    return {"prefix_cache": capacity} if caches == "one" else {}


def _home_whole(engine):
    """No lane holds anything: the cross-check is clean, no page is
    pinned, and what the trie does not hold is free — in the sliding
    layers' allocator every page."""
    engine.verify_pool_invariants()
    assert engine._pool.pinned_pages == 0
    held = engine._trie.size if engine._trie is not None else 0
    assert engine._pool.free_pages + held == engine._pool.num_pages
    if engine._wt is not None:
        window = engine._wt.pool
        assert window.free_pages == window.num_pages


CACHES = pytest.mark.parametrize("caches", ["one", "two"])
KINDS = pytest.mark.parametrize(
    "caches", ["one", "two", "latent", "linear", "mtp"])


class TestEngineLifecycle:
    @KINDS
    def test_refcount_release_on_lane_finish(self, caches):
        """Two shared-prefix requests through a paged engine: while the
        trie holds the shared chunks their pages stay allocated (refs
        from the trie), every lane-owned page returns to the free list
        at finish, and evicting the trie drains the pool back to
        FULL — no page leaks across the request lifecycle.  Two kinds
        of cache (no trie): both allocators are whole at finish; a
        lane's slot of state and the drafting module's pages (and the
        headroom of its two steps in flight) come home with them."""
        rng = numpy.random.RandomState(7)
        shared = rng.randint(0, 16, 16).tolist()     # 2 full chunks
        prompts = [shared + rng.randint(0, 16, 3).tolist()
                   for _ in range(2)]
        engine = _paged_engine(caches, "kv_life", slots=2, paged_kv=True,
                               **_trie(caches, 16)).start()
        try:
            for p in prompts:
                engine.submit(p, 4).result(timeout=60)
            _home_whole(engine)
            pool, trie = engine._pool, engine._trie
            if caches == "one":
                # only the trie's references remain
                assert pool.used_pages == trie.size == 2
                while trie.evict_one():
                    pass
            else:
                assert trie is None
            assert pool.free_pages == pool.num_pages
        finally:
            engine.stop()

    def test_hopeless_reservation_keeps_cache_warm(self):
        """Pool-pressure eviction is bounded by what it can actually
        reclaim: a reservation that even a FULL trie flush could not
        cover evicts nothing (the cache stays warm for the lanes that
        will run), while a reachable one evicts just enough."""
        from veles_tpu.serving import LMEngine
        params = _params()
        engine = LMEngine(params, n_heads=2, max_len=96, slots=1,
                          paged_kv=4, prefill_chunk=8, prefix_cache=8,
                          name="kv_warm")
        pool, trie = engine._pool, engine._trie
        (pa,) = pool.alloc(1)
        node = trie.insert(trie.root, (1,) * 8, pa)
        trie.release([node])             # evictable, page refs=1
        assert trie.evictable() == 1
        # free 3 + evictable 1 < 5: hopeless — entry must survive
        assert engine._alloc_pages(5) is None
        assert trie.size == 1
        # free 3 + evictable 1 >= 4: evicts exactly what it needs
        got = engine._alloc_pages(4)
        assert got is not None and len(got) == 4
        assert trie.size == 0

    def test_cow_leaves_shared_page_bit_identical(self):
        """COPY-ON-WRITE: a lane about to append into a page another
        referent shares gets a private copy; the original page's rows
        stay bit-identical for the other referent, the copy starts
        bit-identical too, and the ref/pin bookkeeping moves the lane
        (not the sibling) onto the fresh page."""
        import jax.numpy as jnp
        from veles_tpu.serving import LMEngine
        from veles_tpu.serving.lm_engine import _Request, _Slot
        params = _params()
        engine = LMEngine(params, n_heads=2, max_len=96, slots=1,
                          paged_kv=4, prefill_chunk=8, name="kv_cow")
        pool = engine._pool
        (p,) = pool.alloc(1)
        # fill page p with recognizable rows on every block
        engine._kv_pools = [
            (kp.at[p].set(float(i + 1)), vp.at[p].set(float(-i - 1)))
            for i, (kp, vp) in enumerate(engine._kv_pools)]
        before = [(numpy.asarray(kp[p]), numpy.asarray(vp[p]))
                  for kp, vp in engine._kv_pools]
        pool.retain(p)                   # the sibling's reference
        pool.pin(p)                      # this lane's pin
        lane = _Slot(_Request([1, 2, 3], 4, 30.0, pages=1))
        lane.pages = [p]
        engine._page_tables[0, 0] = p
        engine._cow_guard(0, lane, 0, 1)
        q = lane.pages[0]
        assert q != p and engine._page_tables[0, 0] == q
        for (kb, vb), (kp_, vp_) in zip(before, engine._kv_pools):
            numpy.testing.assert_array_equal(kb, numpy.asarray(kp_[p]))
            numpy.testing.assert_array_equal(vb, numpy.asarray(vp_[p]))
            numpy.testing.assert_array_equal(kb, numpy.asarray(kp_[q]))
            numpy.testing.assert_array_equal(vb, numpy.asarray(vp_[q]))
        assert pool.refs(p) == 1 and not pool.pinned(p)   # sibling's
        assert pool.refs(q) == 1 and pool.pinned(q)       # the lane's
        assert engine.metrics.counter("kv_cow_copies") == 1
        # a second write into the now-exclusive page copies nothing
        engine._cow_guard(0, lane, 1, 2)
        assert engine.metrics.counter("kv_cow_copies") == 1

    @pytest.mark.parametrize("caches,requests", [
        pytest.param("one", 32, marks=pytest.mark.slow), ("two", 8)])
    def test_sustained_pool_churn_no_leaks(self, caches, requests):
        """Sustained pool-stress (SLOW for one kind of cache) — 32
        mixed-length requests (8 of them for two kinds: that model's
        step is the costlier), some sharing a prefix, some unique,
        churn through a pool far smaller than their total demand, with
        trie eviction reclaiming pages throughout.  Every request
        completes exactly greedy, and the pool drains back to FULL once
        the trie is emptied — no page leaks under sustained pressure.
        Two kinds of cache: the sliding layers' pages come and go with
        every window crossed, and both allocators end whole."""
        rng = numpy.random.RandomState(11)
        shared = rng.randint(0, 16, 16).tolist()
        prompts = []
        for i in range(requests):
            tail = rng.randint(0, 16, rng.randint(1, 24)).tolist()
            prompts.append((shared + tail) if i % 2 else tail)
        from veles_tpu.serving import PoolExhausted
        engine = _paged_engine(caches, "kv_churn", slots=4, paged_kv=10,
                               queue_depth=64, deadline_s=120.0,
                               **_trie(caches, 4))
        engine.start()
        try:
            futures = []
            for p in prompts:
                # closed-loop client: honor the 429's Retry-After when
                # the backlog bound trips (the stress IS the point)
                for _ in range(400):
                    try:
                        futures.append(engine.submit(p, 6))
                        break
                    except PoolExhausted as e:
                        time.sleep(min(e.retry_after, 0.05))
                else:
                    raise AssertionError("submit never admitted")
            for p, f in zip(prompts, futures):
                assert_greedy(engine, p, f.result(timeout=300), 6)
            _home_whole(engine)
            pool, trie = engine._pool, engine._trie
            if caches == "one":
                assert pool.used_pages == trie.size <= 4
                while trie.evict_one():
                    pass
            else:
                assert trie is None
                assert engine.metrics.counter(
                    "kv_pages_released_window") > 0
            assert pool.free_pages == pool.num_pages
        finally:
            engine.stop()

    @CACHES
    def test_mid_prefill_faults_leak_no_pages(self, caches):
        """ISSUE 10 satellite: injected mid-prefill dispatch failures
        (the engine.chunk site) across several shared-prefix requests
        — every faulted request fails alone, the survivors stay
        exactly greedy, and afterwards the pool returns to baseline
        with zero orphan trie pins (one kind of cache: the engine with
        the trie) and the allocator invariants intact (of both
        allocators, for two kinds of cache)."""
        from veles_tpu.serving import FaultPlan, InjectedFault
        rng = numpy.random.RandomState(3)
        shared = rng.randint(0, 16, 16).tolist()     # 2 full chunks
        prompts = [shared + rng.randint(0, 16, 1 + i).tolist()
                   for i in range(6)]
        # every 3rd chunk dispatch faults — mid-prefill, because these
        # prompts are almost all prefill chunks
        plan = FaultPlan().arm("engine.chunk", every=3)
        engine = _paged_engine(caches, "kv_fault", slots=2,
                               paged_kv=True, faults=plan,
                               **_trie(caches, 16))
        engine.start()
        try:
            futures = [engine.submit(p, 4) for p in prompts]
            failed = ok = 0
            for p, f in zip(prompts, futures):
                try:
                    assert_greedy(engine, p, f.result(timeout=60), 4)
                    ok += 1
                except InjectedFault:
                    failed += 1
            assert failed > 0 and ok > 0     # both paths exercised
            assert plan.fired("engine.chunk") >= failed
            # leak-freedom: no lane active, no orphan pins, and once
            # the trie is pressed empty the pool refills WHOLE
            _home_whole(engine)
            if caches == "one":
                assert engine._trie.live_pins() == 0
                while engine._trie.evict_one():
                    pass
            assert engine._pool.free_pages == engine._pool.num_pages
        finally:
            engine.stop()

    def test_mid_cow_fault_releases_orphan_page(self):
        """ISSUE 10 satellite: a faulted copy-on-write dispatch (the
        engine.cow site fires inside the page-copy try) releases the
        just-allocated destination page instead of leaking it, and
        the shared source page's bookkeeping is untouched."""
        from veles_tpu.serving import FaultPlan, InjectedFault, LMEngine
        from veles_tpu.serving.lm_engine import _Request, _Slot
        params = _params()
        plan = FaultPlan().arm("engine.cow", times=1)
        engine = LMEngine(params, n_heads=2, max_len=96, slots=1,
                          paged_kv=4, prefill_chunk=8, name="kv_cowf",
                          faults=plan)
        pool = engine._pool
        (p,) = pool.alloc(1)
        pool.retain(p)                   # the sibling's reference
        pool.pin(p)                      # this lane's pin
        lane = _Slot(_Request([1, 2, 3], 4, 30.0, pages=1))
        lane.pages = [p]
        engine._page_tables[0, 0] = p
        free_before = pool.free_pages
        with pytest.raises(InjectedFault):
            engine._cow_guard(0, lane, 0, 1)
        # the orphan destination went back; the shared page still has
        # both referents and the lane's pin — nothing leaked or lost
        assert pool.free_pages == free_before
        assert pool.refs(p) == 2 and pool.pinned(p)
        assert engine.metrics.counter("kv_cow_copies") == 0
        # disarmed, the same write now copies cleanly
        plan.disarm()
        engine._cow_guard(0, lane, 0, 1)
        q = lane.pages[0]
        assert q != p and pool.refs(q) == 1 and pool.pinned(q)
        assert engine.metrics.counter("kv_cow_copies") == 1

    @CACHES
    def test_pool_exhaustion_sheds_503_never_hangs(self, caches):
        """A request queued on pool pressure whose pages never free in
        time sheds DeadlineExceeded (503) at its deadline — it does not
        wedge the queue, and the lane holding the pool finishes
        normally."""
        from veles_tpu.serving import DeadlineExceeded
        engine = _paged_engine(caches, "kv_shed", slots=2, paged_kv=3,
                               deadline_s=1.0).start()
        real_step = engine._step_jit

        def slow_step(*a):
            time.sleep(0.08)
            return real_step(*a)

        engine._step_jit = slow_step
        try:
            # A takes all 3 pages and decodes ~2.6s; B (3 pages) can
            # only wait — its 1s deadline fires first
            fut_a = engine.submit(list(range(1, 9)), 16)
            fut_b = engine.submit(list(range(2, 10)), 16)
            with pytest.raises(DeadlineExceeded):
                fut_b.result(timeout=30)
            assert len(fut_a.result(timeout=60)) == 16
            assert engine.metrics.snapshot()["shed"] == 1
            assert engine._pool.free_pages == engine._pool.num_pages
            _home_whole(engine)
        finally:
            engine._step_jit = real_step
            engine.stop()


class TestStorageLost:
    """ISSUE 27: the engine's programs take the KV storage DONATED, so a
    dispatch that raises once the runtime has consumed its arguments
    leaves no storage behind.  The rule (``LMEngine._donating``): inputs
    intact, behave as before (the faulted request or lanes fail, the
    survivors finish on their rows); inputs consumed, fail every request
    that held rows, drop the trie, bring the allocator home whole, put
    fresh storage in place, count it, keep serving."""

    LONG = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3, 2, 3, 8, 4,
            6, 2, 6, 4, 3, 3, 8, 3, 2, 7, 9, 5, 1, 2, 8, 8, 4, 1, 9, 7]

    #: ``paged``: the classic block with the prefix cache; ``kinds``: two
    #: kinds of paged cache; and what else a lane may hold that goes down
    #: with the storage: rows of a ``latent`` pool, a slot of recurrent
    #: state (``linear``), rows of the drafting module's pool (``mtp``)
    LAYOUTS = ["paged", "kinds", "latent", "linear", "mtp"]

    @staticmethod
    def _engine(layout, **extra):
        if layout != "paged":
            return _paged_engine({"kinds": "two"}.get(layout, layout),
                                 "kv_lost_" + layout, slots=2,
                                 paged_kv=True, **extra)
        from veles_tpu.serving import LMEngine
        return LMEngine(_params(), n_heads=2, max_len=96, slots=2,
                        name="kv_lost", paged_kv=True, prefill_chunk=8,
                        prefix_cache=32, **extra)

    @staticmethod
    def _served(layout, engine, prompt, out, n_new):
        _served({"paged": "one", "kinds": "two"}.get(layout, layout),
                engine, prompt, out, n_new)

    @staticmethod
    def _consuming(engine, attr, ready):
        """Replace the program at ``attr`` by a stub that, the first time
        ``ready()`` holds, DELETES the storage it was handed and raises —
        what a dispatch does that fails after the runtime took its
        donated arguments.  Every other call goes through."""
        real = getattr(engine, attr)
        fired = []

        def stub(p, storage, *args):
            if not fired and ready():
                fired.append(True)
                for pair in storage:
                    for a in pair:
                        a.delete()
                raise RuntimeError("device lost mid-dispatch")
            return real(p, storage, *args)

        setattr(engine, attr, stub)
        return fired

    @pytest.mark.parametrize("layout", LAYOUTS)
    @pytest.mark.parametrize("path", ["decode", "chunk"])
    def test_consumed_storage_fails_holders_and_rebuilds(self, layout,
                                                         path):
        engine = self._engine(layout).start()
        lanes = engine._lanes
        if path == "decode":
            # the first decode dispatch that runs beside a prefilling lane
            fired = self._consuming(engine, "_step_jit", lambda: any(
                ln is not None and ln.pending for ln in lanes))
        else:
            # the first prompt chunk that runs beside a decoding lane
            fired = self._consuming(engine, "_chunk_jit", lambda: any(
                ln is not None and not ln.pending and ln.emitted
                for ln in lanes))
        try:
            # something for the trie to hold, and pages with it
            seed_prompt = self.LONG[:20]
            self._served(layout, engine, seed_prompt,
                         engine.submit(seed_prompt, 3).result(timeout=120), 3)
            assert layout != "paged" or engine._trie.size >= 2
            fa = engine.submit([1, 2, 3], 30)        # decodes
            fb = engine.submit(self.LONG[::-1], 4)   # prefills, 5 chunks
            fc = engine.submit([2, 4, 6, 8], 6)      # queued: no slot
            for f in (fa, fb):
                with pytest.raises(RuntimeError, match="device lost"):
                    f.result(timeout=120)
            assert fired
            # the queued request held nothing: served token for token
            self._served(layout, engine, [2, 4, 6, 8],
                         fc.result(timeout=120), 6)
            assert engine.metrics.counter("kv_storage_rebuilds") == 1
            # dropped with the rows
            assert layout != "paged" or engine._trie.size == 0
            leaves = [a for pair in engine._storage() for a in pair]
            assert not any(a.is_deleted() for a in leaves)
            # and the next one, through the fresh storage, as well
            self._served(layout, engine, self.LONG,
                         engine.submit(self.LONG, 5).result(timeout=120), 5)
            assert engine.metrics.counter("kv_storage_rebuilds") == 1
        finally:
            engine.stop()
        # the allocator is whole: what is not free, the trie holds
        # for the prompts served since
        _home_whole(engine)
        if layout == "paged":
            engine._trie.clear()
            assert engine._pool.free_pages == engine._pool.num_pages

    @pytest.mark.parametrize("layout", ["paged", "kinds"])
    def test_allocator_whole_right_after_the_loss(self, layout):
        """Between the loss and the next admission nothing is held:
        ``kv_pages_free`` equals the total, no pin, no trie entry, every
        table row on scratch — of both allocators and both tables, for
        two kinds of cache."""
        engine = self._engine(layout).start()
        lanes = engine._lanes
        self._consuming(engine, "_step_jit", lambda: any(
            ln is not None and ln.pending for ln in lanes))
        try:
            engine.submit(self.LONG[:20], 3).result(timeout=120)
            fa = engine.submit([1, 2, 3], 30)
            fb = engine.submit(self.LONG[::-1], 4)
            for f in (fa, fb):
                with pytest.raises(RuntimeError, match="device lost"):
                    f.result(timeout=120)
            deadline = time.monotonic() + 30.0
            while engine.metrics.counter("kv_storage_rebuilds") < 1:
                assert time.monotonic() < deadline
                time.sleep(0.01)
            g = engine.metrics.snapshot()["gauges"]
            assert g["kv_pages_free"] == g["kv_pages_total"]
            assert g["kv_pages_pinned"] == 0
            assert (engine._page_tables == KVPagePool.SCRATCH).all()
            if layout == "kinds":
                assert g["kv_pages_free.window"] \
                    == g["kv_pages_total.window"]
                assert (engine._wt.tables == KVPagePool.SCRATCH).all()
            else:
                assert g["prefix_cache_chunks"] == 0
            _home_whole(engine)
        finally:
            engine.stop()

    @pytest.mark.parametrize("layout", LAYOUTS)
    @pytest.mark.parametrize("site", ["engine.step", "engine.chunk"])
    def test_injected_fault_keeps_todays_behaviour(self, layout, site):
        """An injected fault fires BEFORE the program is called: the
        storage is intact, so only the faulted lanes fail and the
        survivor finishes on its rows, token for token — 0 rebuilds."""
        from veles_tpu.serving import FaultPlan
        from veles_tpu.serving.faults import InjectedFault
        # the first decode dispatch runs while the long prompt still
        # prefills (its lane survives a step fault); the 4th chunk is
        # the long prompt's (the short one has a single chunk), run
        # while the short one decodes (ITS lane survives a chunk fault)
        plan = FaultPlan().arm(site, calls={1 if site == "engine.step"
                                            else 4})
        engine = self._engine(layout, faults=plan).start()
        try:
            long_prompt = self.LONG[::-1]
            fa = engine.submit([1, 2, 3], 30)
            fb = engine.submit(long_prompt, 4)
            failed, survivor, prompt = (
                (fa, fb, long_prompt) if site == "engine.step"
                else (fb, fa, [1, 2, 3]))
            with pytest.raises(InjectedFault):
                failed.result(timeout=120)
            self._served(layout, engine, prompt,
                         survivor.result(timeout=120),
                         30 if survivor is fa else 4)
            assert engine.metrics.counter("kv_storage_rebuilds") == 0
        finally:
            engine.stop()
        engine.verify_pool_invariants()

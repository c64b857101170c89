"""Paged KV-cache allocator — host-side page bookkeeping (ISSUE 6).

The memory half of the paged serving refactor: device KV storage is ONE
pool of fixed-size pages per transformer block (``(n_pages, kv_heads,
page, head_dim)``, see ``ops/attention.py::paged_view``), and THIS
class decides which lane (or prefix-cache entry) owns which page.  All
state is host-side integers — allocation never touches the device, so
a prefix-cache hit that installs page REFERENCES into a lane's page
table is zero-copy and zero-dispatch by construction.

Three invariants the engine leans on:

- REF-COUNTED sharing: a page lives until its last referent (lanes
  and/or the radix prefix cache) releases it; ``alloc`` never hands
  out a page with live references, so one lane's decode can never
  scribble on rows another lane still attends.
- PINS mark in-flight use: a lane pins every page in its table while
  active.  Pins don't keep a page alive (refs do) — they make
  "eviction" (the trie dropping its reference under pool pressure)
  refuse pages a lane still reads, and releasing a still-pinned page
  is an engine bug this class turns into a loud error instead of a
  silent use-after-free.
- COPY-ON-WRITE discipline: writers must own their page exclusively.
  :meth:`shared` is the check; the engine's write paths consult it and
  copy the page (``_page_copy_jit``) before appending — the OTHER
  referents keep the original rows bit-identical.

Single-threaded by design: every call happens on the engine worker
thread (the same discipline as :class:`RadixPrefixCache`), so there is
no lock to contend on the per-token path.
"""

from __future__ import annotations

import collections

import numpy


class KVPagePool:
    """Allocator over page ids ``1..num_pages`` (id 0 is the reserved
    SCRATCH page: free lanes park their page tables on it and warmup
    writes land there — it is never allocated, so its garbage content
    is never attended by a live mask)."""

    SCRATCH = 0

    #: ISSUE 15 annotation: the allocator is deliberately lock-free —
    #: every mutation happens on the engine worker thread (the engine
    #: lock is the module docstring's "single-threaded by design"
    #: rule), so the per-token path pays no contention.  checkpoint()
    #: documents the torn-read consequence for its best-effort reads.
    _synchronized_externally = "LMEngine worker thread (single owner)"

    def __init__(self, num_pages, page_size):
        if num_pages < 1:
            raise ValueError("kv pool needs at least one page")
        if page_size < 1:
            raise ValueError("page size must be >= 1")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self._refs = [0] * (self.num_pages + 1)
        self._pins = [0] * (self.num_pages + 1)
        self._free = collections.deque(range(1, self.num_pages + 1))

    # ------------------------------------------------------------ queries
    @property
    def free_pages(self):
        return len(self._free)

    @property
    def used_pages(self):
        return self.num_pages - len(self._free)

    @property
    def pinned_pages(self):
        """Pages held by an active lane (the gauge /metrics exposes)."""
        return sum(1 for p in self._pins[1:] if p > 0)

    @property
    def occupancy(self):
        """Used fraction of the pool (0..1) — the resident-KV pressure
        signal behind the ``kv_pages_free``/``kv_pages_total`` gauges
        the serving router weighs when placing requests (ISSUE 8)."""
        return self.used_pages / float(self.num_pages)

    def refs(self, page):
        return self._refs[page]

    def snapshot(self):
        """JSON-safe copy of the full allocator bookkeeping — what
        ``LMEngine.checkpoint`` (ISSUE 10) embeds so a crash leaves a
        post-mortem record of who owned what."""
        return {"num_pages": self.num_pages,
                "page_size": self.page_size,
                "refs": list(self._refs),
                "pins": list(self._pins),
                "free": list(self._free)}

    def verify(self):
        """Self-consistency audit (ISSUE 10): the free list holds
        exactly the zero-ref pages (each once, never the scratch
        page), no negative counts, and no pinned page without a
        referent.  Raises RuntimeError naming the first violation;
        returns a summary dict when sound — the crash-recovery path
        runs this before re-admitting any work."""
        free = set(self._free)
        if len(free) != len(self._free):
            raise RuntimeError("free list holds duplicate pages")
        if self.SCRATCH in free:
            raise RuntimeError("scratch page entered the free list")
        for p in range(1, self.num_pages + 1):
            refs, pins = self._refs[p], self._pins[p]
            if refs < 0 or pins < 0:
                raise RuntimeError(
                    "page %d has negative bookkeeping (refs=%d, "
                    "pins=%d)" % (p, refs, pins))
            if (refs == 0) != (p in free):
                raise RuntimeError(
                    "page %d refs=%d but free-list membership is %s "
                    "— leaked or double-freed" % (p, refs, p in free))
            if pins and not refs:
                raise RuntimeError(
                    "page %d pinned (%d) with no referent" % (p, pins))
        return {"free": len(free), "used": self.used_pages,
                "pinned": self.pinned_pages}

    def shared(self, page):
        """True when appending into ``page`` needs copy-on-write."""
        return self._refs[page] > 1

    # --------------------------------------------------------- allocation
    def alloc(self, n=1):
        """Take ``n`` pages (refs=1 each) — ALL-OR-NOTHING: returns the
        page-id list, or None leaving the pool untouched when fewer
        than ``n`` are free (the engine then presses the prefix cache
        for evictions or requeues the request; partial grants would
        strand pages on a request that cannot run)."""
        if n < 0:
            raise ValueError("alloc(%d)" % n)
        if len(self._free) < n:
            return None
        pages = [self._free.popleft() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        return pages

    def retain(self, page):
        """One more referent (a sharing lane, or the prefix cache)."""
        if not 1 <= page <= self.num_pages or self._refs[page] < 1:
            raise RuntimeError("retain of unallocated page %d" % page)
        self._refs[page] += 1

    def release(self, page):
        """Drop one reference; the page returns to the free list at
        zero.  Returns True when this release freed it.  Releasing an
        unallocated page, or freeing one that is still PINNED, is an
        engine bug — fail loudly, never recycle rows a lane reads."""
        if not 1 <= page <= self.num_pages or self._refs[page] < 1:
            raise RuntimeError("release of unallocated page %d" % page)
        self._refs[page] -= 1
        if self._refs[page] == 0:
            if self._pins[page]:
                self._refs[page] += 1
                raise RuntimeError(
                    "page %d freed while still pinned by a lane" % page)
            self._free.append(page)
            return True
        return False

    # --------------------------------------------------------------- pins
    def pin(self, page):
        if not 1 <= page <= self.num_pages or self._refs[page] < 1:
            raise RuntimeError("pin of unallocated page %d" % page)
        self._pins[page] += 1

    def unpin(self, page):
        if self._pins[page] < 1:
            raise RuntimeError("unpin of unpinned page %d" % page)
        self._pins[page] -= 1

    def pinned(self, page):
        return self._pins[page] > 0


class WindowTables:
    """Per-lane page tables of the SLIDING kind of layer: a lane holds only
    the pages its window still reaches.

    A full layer's table maps linear page ``j`` at entry ``j`` and keeps every
    page until the request ends.  A sliding layer's keys stop being read once
    they are ``window`` or more behind the lane's position, so its table is a
    short row that SLIDES: entry ``j`` maps linear page ``base + j``, a page is
    released when its last token has left the window of the lane's next query
    (prefill chunks included), and the page the frontier enters is taken then.
    The device programs take the row and ``base * page`` and work on positions
    less that base (``ops/attention.py::mha_paged_chunk_step``).

    Admission COMMITS a lane's largest holding (``min(pages of its span,
    width)``) against the pool, so taking a page on the way can never fail
    and decode can never deadlock on pages; ``width`` is the window's pages,
    the frontier's page, and one taken before the oldest is released.
    Host-side integers only, owned by the engine's worker thread like
    :class:`KVPagePool`."""

    _synchronized_externally = "LMEngine worker thread (single owner)"

    def __init__(self, pool, slots, window):
        self.pool = pool
        self.page = pool.page_size
        self.window = int(window)
        self.width = -(-self.window // self.page) + 2
        self.tables = numpy.zeros((slots, self.width), numpy.int32)
        #: linear page index of each row's entry 0, and live entries
        self.base = numpy.zeros(slots, numpy.int32)
        self.count = numpy.zeros(slots, numpy.int32)
        self._commit = [0] * slots
        self.committed = 0
        #: pages released because they left the window (not at finish)
        self.released = 0

    def holding(self, pages):
        """The most pages a request of ``pages`` pages of span ever holds."""
        return min(int(pages), self.width)

    def can_admit(self, pages):
        return self.committed + self.holding(pages) <= self.pool.num_pages

    def admit(self, slot, pages):
        if self._commit[slot] or self.count[slot]:
            raise RuntimeError("slot %d admitted twice" % slot)
        self._commit[slot] = self.holding(pages)
        self.committed += self._commit[slot]

    def first_live(self, pos):
        """Linear index of the oldest page a query at ``pos`` still reads."""
        return max(0, (int(pos) - self.window + 1) // self.page)

    def advance(self, slot, lo, hi):
        """Before the lane writes (and reads from) positions [lo, hi):
        release the pages wholly behind the window of a query at ``lo``,
        take the pages up to the one ``hi - 1`` lies in.  Returns the number
        released."""
        row, page = self.tables[slot], self.page
        gone = min(self.first_live(lo) - int(self.base[slot]),
                   int(self.count[slot]))
        if gone > 0:
            for p in row[:gone].tolist():
                self.pool.release(p)
            n = int(self.count[slot]) - gone
            row[:n] = row[gone:gone + n]
            row[n:] = KVPagePool.SCRATCH
            self.count[slot] = n
            self.base[slot] += gone
            self.released += gone
        if not self.count[slot]:
            # nothing held: the row begins at the first page still read
            self.base[slot] = self.first_live(lo)
        want = (int(hi) - 1) // page - int(self.base[slot]) + 1
        have = int(self.count[slot])
        if want > have:
            if want > self._commit[slot]:
                raise RuntimeError(
                    "slot %d needs %d window pages, committed %d"
                    % (slot, want, self._commit[slot]))
            fresh = self.pool.alloc(want - have)
            if fresh is None:
                raise RuntimeError("window pool exhausted under its own "
                                   "commitments")
            row[have:want] = fresh
            self.count[slot] = want
        return max(gone, 0)

    def due(self, pos):
        """Bool per slot: a query at ``pos[slot]`` needs :meth:`advance`
        (its page is not held yet, or the oldest held has left the
        window)."""
        first = numpy.maximum(0, (pos - self.window + 1) // self.page)
        return (pos // self.page >= self.base + self.count) \
            | ((first > self.base) & (self.count > 0))

    def vacate(self, slot):
        n = int(self.count[slot])
        for p in self.tables[slot, :n].tolist():
            self.pool.release(p)
        self.tables[slot, :] = KVPagePool.SCRATCH
        self.base[slot] = 0
        self.count[slot] = 0
        self.committed -= self._commit[slot]
        self._commit[slot] = 0

    def verify(self):
        """The pool's own audit, then: every held page has exactly one
        referent (its lane), no lane holds more than ``width`` or than it
        committed, and the commitments add up.  Raises RuntimeError; returns
        a summary when sound."""
        self.pool.verify()
        held = []
        for slot in range(len(self.count)):
            n = int(self.count[slot])
            if n > self.width or n > self._commit[slot]:
                raise RuntimeError(
                    "slot %d holds %d window pages (width %d, committed "
                    "%d)" % (slot, n, self.width, self._commit[slot]))
            held.extend(self.tables[slot, :n].tolist())
            if (self.tables[slot, n:] != KVPagePool.SCRATCH).any():
                raise RuntimeError("slot %d: entries past its %d live "
                                   "pages are not scratch" % (slot, n))
        if len(set(held)) != len(held) or KVPagePool.SCRATCH in held:
            raise RuntimeError("a window page is held twice, or scratch "
                               "is held")
        if len(held) != self.pool.used_pages:
            raise RuntimeError("lanes hold %d window pages, the pool says "
                               "%d are used" % (len(held),
                                                self.pool.used_pages))
        if self.committed != sum(self._commit) \
                or self.committed > self.pool.num_pages:
            raise RuntimeError("window commitments do not add up")
        return {"held": len(held), "committed": self.committed,
                "released": self.released}

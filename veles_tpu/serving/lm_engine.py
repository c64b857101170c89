"""Continuous LM decode — slot-based batching over one paged KV pool.

The LM-traffic half of the serving subsystem (ISSUE 1).  ``serve_lm``'s
direct path decodes one prompt at a time: a second client waits for the
whole first decode even though the decode step is embarrassingly
batchable.  :class:`LMEngine` keeps ``slots`` decode lanes over ONE pool
of KV pages a layer, each lane behind its own page table, and runs ONE
batched decode step per token across every active lane — vLLM-style
continuous batching on a jit substrate:

- an arriving prompt takes any free slot mid-flight and is PREFILLED one
  chunk of ``prefill_chunk`` tokens (one page) a turn, interleaved with
  the decode steps (``ops/transformer.py::paged_chunk_apply``): ONE chunk
  program serves every prompt length, and a long prompt never
  head-of-line-blocks the decode lanes;
- every engine tick advances ALL active slots by one token in a single
  jitted step over the shared pools (per-slot positions — each lane is
  at its own depth in its own sequence);
- a finished sequence frees its slot and its pages immediately and the
  next queued prompt takes them, so decode throughput scales with slot
  count instead of serializing per prompt.

Two optimizations ride it (ISSUE 4), each preserving the greedy contract
below:

- ``prefix_cache=N`` — a chunk-granular RADIX PREFIX CACHE
  (:class:`RadixPrefixCache`) over prompt tokens: prompts sharing a
  prefix (system prompts, few-shot headers) reuse the already-computed
  KV pages for their shared full chunks instead of re-running prefill
  FLOPs.  Entries are ref-counted while a lane uses their trie path and
  LRU-evicted at capacity ``N`` chunks; a shared page is copied before
  anybody writes into it, so a later eviction (or poisoning attempt) can
  never corrupt an in-flight decode — correctness never depends on cache
  state, only speed does.
- ``spec_k=K`` — PROMPT-LOOKUP SPECULATIVE DECODING: an n-gram match
  against the lane's own prompt+output proposes K draft tokens (no
  draft model), verified in ONE batched chunk dispatch; every accepted
  token is by construction exactly the greedy token (acceptance
  compares the draft against the verifier's own argmax), so accepted
  runs yield multiple tokens per dispatch — sub-1 dispatches/token on
  repetitive or structured text — while a full miss still yields the
  one greedy token a plain step would have.

The KV CACHE IS PAGED (ISSUE 6): fixed-size PAGES (page =
``prefill_chunk`` tokens) drawn from one global pool per block, indexed
through a per-lane page table (``ops/attention.py::paged_view``/
``paged_write``; allocator in ``serving/kv_pool.py``).  ``paged_kv=N`` is
the pool's size in pages (0, the default: every lane's whole table,
``slots × max_len / page``):

- a lane RESERVES only the pages its own ``len(prompt) + n_new +
  spec_k`` span needs, so slot count is bounded by the POOL, not by
  ``slots × max_len`` — lanes of wildly different lengths share one
  region and the mixed-length bench fits ≥2× the lanes in the same KV
  bytes;
- prefix-cache hits become page REFERENCES: the trie stores page ids,
  a hit bumps a ref-count and writes the id into the lane's table —
  zero device copies, zero dispatches;
- appends into a SHARED page copy-on-write first (one page-copy
  dispatch; the other referents keep bit-identical rows) — structurally
  rare, because shared pages are exactly full prompt chunks and lanes
  append past their prompt;
- a request whose reservation cannot be met QUEUES (its page demand is
  re-tried every tick, after pressing the prefix cache to drop
  unpinned entries) and sheds 503 at its deadline; a backlog already
  covering the whole pool rejects new arrivals with
  ``PoolExhausted`` (HTTP 429) — pool pressure never wedges a lane.

The SERVING ATTENTION KERNELS (ISSUE 7, ``attn_kernel=``) swap the
programs' attention core for the Pallas suite in
``ops/pallas_kernels.py``: the decode/verify dispatches run
:func:`~veles_tpu.ops.pallas_kernels.paged_flash_decode` (the page
table walked INSIDE the kernel — no ``paged_view`` gather ever
materializes a lane's dense cache view) and the chunk program runs
:func:`~veles_tpu.ops.pallas_kernels.paged_flash_prefill` (chunk K/V
attended from VMEM and installed into the pool in the kernel
epilogue).  Routing resolves ONCE at construction: 'auto' (or True)
uses the kernels on real TPU hardware and falls back to the XLA path
everywhere else (off-TPU, unsupported geometry
— logged once, metered per dispatch as ``attn_kernel_fallbacks`` vs
``attn_kernel_dispatches``); 'force' insists even off-TPU (interpret
mode — the parity tests' end-to-end gear, far too slow for traffic).
Decode/verify additionally slice the page table to the LIVE width
ladder (``_live_width``): a step pays for the pages the batch actually
occupies, one program per power-of-two ladder entry.  Inside the
kernels a page no query of its lane can see costs neither a fetch nor a
softmax step (ISSUE 29), and the decode kernels walk a lane's live pages
alone (ISSUES 41, 43); the host counts what each dispatch handed them,
what of it was live and the blocks the flash-decode kernel made of it
(``attn_page_steps`` / ``attn_page_steps_live`` / ``attn_walk_blocks``,
:meth:`LMEngine._note_attn_dispatch`).

SHARDED SERVING (ISSUE 8, ``tp=N``) runs every program above under a
one-axis ``('tp',)`` mesh: weights are head-/column-sharded by
``ops/transformer.py::lm_param_specs`` (megatron split — wq/wk/wv by
head group, wo/w2 by row so GSPMD inserts one all-reduce per block),
the KV pool/caches shard over their kv_heads axis, and the page
tables, host allocator and every program stay EXACTLY as above — the
head shard and the page indirection compose because neither is a
shape.  Output shardings are pinned to the input layout so the mesh
adds zero programs (the jit-guard bound holds per replica).  The
Pallas kernels are single-device programs, so a TP engine serves
through the XLA path (metered as ``attn_kernel_fallbacks`` when
kernels were requested).  ``devices=`` narrows the engine to a device
slice — N independent engine REPLICAS (each optionally TP-sharded
over a disjoint slice) stack behind ``serving/router.py`` for the
data-parallel axis.

ZERO-DOWNTIME WEIGHT UPDATES (ISSUE 11, :meth:`LMEngine.swap_weights`)
hot-install a new checkpoint into a LIVE engine: the new tree is
validated structurally (shape/dtype/treedef — a mismatch refuses
loudly and the old weights keep serving), ``device_put`` under the
engine's existing placement (the tp mesh re-shards shard-by-shard via
``lm_param_specs``; same shapes → the already-compiled programs serve
the new weights, zero recompiles), and applied by the worker at a tick
boundary.  In-flight lanes either FINISH on the old weights (the
default: admission holds, the old tree stays pinned until its last
lane completes, then one pointer assignment swaps) or — ``drain=True``
— are withdrawn whole and re-queued at the head, re-decoding from
scratch on the new weights with their futures resolving exactly once
(the engine-internal analogue of the router's drain re-placement).
Every result is stamped with the ``weights_version`` that produced it,
so mixed-fleet replies are attributable during a rolling deploy
(``serving/router.py::Router.deploy``).

The DECODE MEGASTEP (ISSUE 13, ``megastep=K``) fuses K decode
iterations into ONE jitted ``lax.scan`` program, so the host pays one
dispatch (and one lock-guarded tick of admission/tracing/bookkeeping)
per K generated tokens instead of per token — the whole-loop-on-device
move PR 2's ``window_scan_fn`` made for training epochs, applied to
the serving inner loop:

- the scan body is exactly today's batched step (or, with ``spec_k``,
  a propose → verify → accept leg whose n-gram draft proposal runs
  IN-GRAPH over a carried token-history buffer —
  ``ops/transformer.py::propose_draft_in_graph`` — so speculation
  composes with the megastep instead of forcing a host round-trip per
  draft);
- greedy argmax selection, ``paged_write`` KV appends through the
  traced page tables, and per-lane position/frontier advance all stay
  inside the program;
- a lane that exhausts its ``n_new`` mid-program is MASKED, not
  returned: its carry freezes (position/last token stop advancing),
  its emitted slots read -1, and its K/V writes are
  redirected to the scratch page (``paged_write(write_mask=)``), so a
  dead iteration can never touch an allocated page.  The wasted
  iterations are metered (``megastep_wasted_iterations``) so the K
  tradeoff is measured, not guessed;
- the HOST operates at MEGASTEP BOUNDARIES: admission, deadline
  shedding (one queue sweep per boundary — ``_boundary_shed``),
  completion detection (the per-lane emitted-token buffers are scanned
  for each lane's exact ``n_new``), swap application
  (``_maybe_apply_swap``) and fault sites all run once per megastep,
  and tracing records ONE ``decode.megastep`` span per dispatch
  (carrying K and each lane's tokens emitted) so the ISSUE 12 cost
  ledger counts the fused program once, never the folded per-token
  work.

``megastep=1`` (and 0, the default) keeps today's per-tick path
bit-for-bit; any K is bit-identical to it anyway (the scan body IS the
step program), which the parity matrix pins across the full
{prefix_cache, spec_k, attn_kernel, tp} feature set.  With the Pallas
``paged_flash_decode`` kernel active the whole K-step loop never leaves
the device.  The scan is the ONE fused
driver: a turn of the loop decodes through exactly one of
``_step_megastep`` (``megastep >= 2``), ``_step_speculative``
(``spec_k``) or ``_step_plain``, all three through
``_dispatch_decode``.

The KV STORAGE IS UPDATED IN PLACE (ISSUE 27).  The pools are one tree
of device arrays that every program takes and returns.
Each program takes it DONATED (:meth:`LMEngine._jit`,
``storage=``): the compiled program aliases the output to the input and
writes the new rows into the arrays as they lie — no dispatch copies a
pool, none holds a second one, and the jit call allocates no output
buffers for them.  The caller's tree is dead when the call returns;
every call site rebinds the storage to the first output in the same
statement.  What donation makes necessary is a rule for a dispatch
that raises once its arguments are consumed: :meth:`LMEngine._donating`
(requests that held rows fail, fresh storage goes in, the engine keeps
serving).  Where the Pallas kernels are active a pool row packs as many
kv heads as fill the chip's 128 lanes (``ops/pallas_kernels.py::
pool_pack``: pool (pages, kv_heads/r, page, r·head_dim)): the kernels
take the pool row-major, the chip's own layout for a row narrower than
its lanes is another, and that cost a conversion of each layer's whole
pool into and out of every kernel dispatch.  The gauge
``kv_storage_in_place`` (1 after warm-up when the last warm-up dispatch
consumed its storage) and the counter ``kv_storage_rebuilds`` are the
witnesses in ``/metrics.json``.

THE ORDER OF A TURN (ISSUE 37).  One turn of ``_serve_loop`` dispatches
at most one prompt chunk and one decode program, the chunk first.  Of
everything a turn does, ONE value needs the tokens the host waits for:
the ``last`` argument of the next step.  The engine has no stop token —
a lane ends by count — so positions, pages, windows, widths and tables
all follow from what the host already knows when a step goes out.  The
plain driver therefore does the rest of its turn between the
step's jit call and the wait for its tokens, while the device runs the
step (:meth:`LMEngine._under_step`; the recorder's ``ahead.emit``,
``ahead.admit``, ``ahead.prepare``): the step is counted into its lanes
(a lane that owes no further token is freed at once, its reply made
when the token lands), the tokens of the step BEFORE go to their lanes,
the queue is shed and admitted, and the next turn's chunk and step get
their guards, slides, width and every put but ``last``.  The turn that
begins when the tokens arrive is then: tick, the prepared chunk's jit
call, the put of ``last``, the step's jit call.  Arguments made ahead
are put from copies (the loop changes ``_pos`` and the tables in place
under them), and are dropped — the turn falls back to the old order,
tick -> deliver -> admit -> chunk -> step — when a lane left its slot
meanwhile (a failed fetch, a fault site, a teardown), a lane was
withdrawn or a weight swap waits; ``turns_prepared_ahead`` and
``ahead_discarded`` in ``/metrics.json`` count both.  The speculative
driver (it drafts from the emitted tokens) and the megastep (its tokens
stay inside the scan) cannot split their turn: they leave nothing
prepared and the same loop runs them in the old order.

TWO DISPATCHES IN FLIGHT (ISSUE 39).  That one value never leaves the
device either: the tokens are the step program's second output, and the
programs take the lanes' last tokens as the dispatch before left
them there (``LMEngine._last_dev``: a step returns every lane's token, a
tail chunk writes its first token at its lane's slot, a lane that does
not decode is masked to token 0 inside the step).  The host needs the
tokens only to answer requests, so the plain driver waits for the
tokens of step N only AFTER it has called turn N+1's chunk and step:
tick -> the prepared chunk's jit call -> the step's jit call -> [under
the step: count it in, deliver, shed and admit, prepare] -> wait for the
tokens of the step BEFORE (and of the tail chunk before it) -> they go
to ``_undelivered``.  The device always has its next program queued
while the host fetches, delivers, admits and prepares.  What is in
flight is a queue of :class:`_Flight` records; a tail chunk's bookkeeping
that needs no token (the lane decodes, owes one fewer, is freed if that
was all) happens at its call, the token, its stamp and the time to it
when it is fetched, up to one decode step later than the old order gave
it.  The pipeline DRAINS — the outstanding fetches are made first, then
the turn as ever — wherever the old order is taken: nothing was prepared
(or it was dropped: a lane left, was withdrawn, a weight swap waits, a
tick fault), no step follows (no lane decodes next turn), the loop ends.
A fetch that raises fails the lanes of every dispatch in flight: the
younger ones read what the failed one wrote
(:meth:`LMEngine._storage_lost`).  ``dispatches_sent_ahead`` (decode
dispatches called while the step before was unfetched) and
``pipeline_drains`` (steps whose tokens were fetched in the old order)
in ``/metrics.json`` add up to ``decode_dispatches`` on this driver.
``checkpoint()`` reads the host's books only, which the count keeps
exact whatever is in flight.

A MODEL THAT DRAFTS WITH ITS OWN MODULE (ISSUE 40).  A record with
``nextn`` (``joyai_llm_flash``: latent attention and one multi-token-
prediction module, ``ops/transformer.py::mtp_forward``) under ``spec_k=1``
takes that same order, and what breaks its one assumption (a step yields
one token a lane) is carried through it.  The step program, still
``step_all``, feeds every live lane ``[last, draft]`` at its position and
the next, picks the greedy token after each row, ACCEPTS the draft where it
is the first pick (so what is emitted is plain greedy decoding's, whatever
was drafted), runs the module over both rows and drafts from the last valid
one; the chunk program runs the module over the prompt too and hands the
first draft with the first token.  ``(last, draft, position)`` stay on the
device from dispatch to dispatch (``_last_dev`` is that triple); a lane
that does not decode is masked to position 0 and writes to the scratch
page.  The host fetches, late as ever, ``(tokens (slots, 2), count
(slots,))`` and settles there what it could not know at the call
(:meth:`LMEngine._settle_counts`): the confirmed position ``_pos`` and
``remaining`` move by the count, a second token past ``n_new`` is dropped
and counted.  Before that it knows a BOUND: every step in flight yields one
token at least and ``spec_k + 1`` at most, so tables, widths and guards
cover ``_pos + _unseen`` (:attr:`headroom` positions are reserved past
``prompt + n_new``), and a lane that is owed no more than it has steps in
flight is freed by count when its last step goes out
(:meth:`_advance_by_count`).  A lane whose drafts were accepted has all its
tokens a fetch or two earlier than the bound says: it rides the steps in
flight behind its last token, what they make is dropped
(``spec_tokens_discarded``), and it leaves with the next step's count; no
preparation is dropped for it.  Counters: ``draft_tokens`` /
``draft_accepted`` (one draft a live lane a step, counted at the fetch),
``spec_dispatches``, ``spec_lane_steps``, ``spec_tokens_kept``,
``spec_tokens_discarded``.  A record without a module keeps the host-side
n-gram drafts of ``_step_speculative``, a synchronous driver; per-layer
kinds (a sliding layer's released pages, a linear layer's state) refuse
``spec_k``: a rejected draft there needs a snapshot to go back to.

Decoding is GREEDY (temperature 0) — bit-identical to
``ops/transformer.py::generate`` for the same prompt WHATEVER fast-path
combination is enabled, which is the serving contract (sampled
requests fall back to the direct path upstream; the Pallas kernels'
online softmax matches the XLA softmax to fp32 roundoff, preserving
every greedy argmax the parity matrix pins).  Compile count is
bounded: one chunk and one page-copy program, and one step (and
verify, or fused megastep) program per live-width ladder entry — the
page-table indirection is traced data, never a shape, and K is fixed per
engine: the jit-guard-asserted bound.
"""

from __future__ import annotations

import array
import collections
import contextlib
import threading
import time
from concurrent.futures import Future

import numpy

from veles_tpu.logger import Logger
from veles_tpu import model_config
from veles_tpu.serving import lockcheck, tracing, xfer
from veles_tpu.serving.batcher import (DeadlineExceeded, Overloaded,
                                       PoolExhausted)
from veles_tpu.serving.kv_pool import KVPagePool, WindowTables
from veles_tpu.serving.metrics import ServingMetrics


class _Request:
    __slots__ = ("prompt", "true_len", "n_new", "future", "t_enq",
                 "deadline", "cancelled", "pages", "trace", "tspan",
                 "t_enq_ns", "t_admit_ns", "token_ns", "lane")

    def __init__(self, prompt, n_new, deadline_s, pages=0):
        self.prompt = prompt          # (s,) int32, unpadded
        self.true_len = len(prompt)
        self.n_new = n_new
        self.future = Future()
        self.future.request = self    # cancellation handle
        #: the loop recorder's stamps (ISSUE 26), ``time.monotonic_ns()``:
        #: enqueue, lane assigned (0 until then), one per emitted token;
        #: ``lane`` is the slot (-1: none yet)
        self.t_enq_ns = time.monotonic_ns()
        self.t_admit_ns = 0
        self.token_ns = array.array("q")
        self.lane = -1
        self.t_enq = self.t_enq_ns * 1e-9
        self.deadline = self.t_enq + deadline_s
        self.cancelled = False
        #: worst-case page demand (admission reservation)
        self.pages = pages
        #: tracing (ISSUE 12): the request's TraceContext (or None) and
        #: its open queue-wait span handle — how the worker thread
        #: attributes its dispatch spans to the right request
        self.trace = None
        self.tspan = None


class _Slot:
    """Host-side lane state; device state lives in the shared pools."""

    __slots__ = ("request", "emitted", "remaining", "pending", "pinned",
                 "cursor", "pages", "inflight", "drafts")

    def __init__(self, request):
        self.request = request
        self.emitted = []
        self.remaining = request.n_new
        #: chunked prefill still to run: [(tokens (C,), start, is_tail)]
        self.pending = []
        #: prefix-cache nodes pinned by this lane (released at finish)
        self.pinned = []
        #: trie node of the last matched/inserted chunk (None once the
        #: cache refused an insert — stop extending this lane's path)
        self.cursor = None
        #: page ids backing this lane's table row, in
        #: lane-local order (owned AND referenced; released at finish)
        self.pages = []
        #: a lane that drafts with the model's own module (ISSUE 40): its
        #: decode steps called and not fetched yet, each of which yields
        #: one token or two, the host cannot know which
        self.inflight = 0
        #: and what its steps drafted: (n, token) where the module put
        #: ``token`` for the request's n-th new token (from 0), whether or
        #: not the next step accepted it; handed over with the reply
        #: (``future.drafts``)
        self.drafts = []


#: one prompt chunk with its arguments on the device and its page steps
#: counted (:meth:`LMEngine._prepare_chunk_paged`), ready for its jit call
_Chunk = collections.namedtuple(
    "_Chunk", "slot lane tokens start is_tail args steps")

#: one plain decode step's arguments, all but ``last`` (the tokens of the
#: step before, which pass on the device from output to argument), on the
#: device (:meth:`LMEngine._prepare_step`): the lanes it advances as
#: ``(slot, lane)`` pairs, the table width, the table argument, the
#: positions and the mask of those lanes on the device, and the page steps
#: it hands the attention kernels (:meth:`LMEngine._attn_page_steps`)
_Step = collections.namedtuple(
    "_Step", "pairs width tables pos_dev live_dev steps")


class _Flight:
    """One dispatch of the plain driver whose tokens the host has
    not fetched yet (ISSUE 39): its recorder handle, its outputs but the
    storage as they lie on the device (the lanes' tokens first, indexed
    by slot; a step's expert counts behind them), the lanes whose tokens
    it makes as ``(slot, lane)`` pairs, for each whether the token is its
    request's last (known by count when the dispatch goes out), and
    whether it is a tail chunk (the token is its lane's first)."""

    __slots__ = ("sent", "outs", "pairs", "lasts", "first")

    def __init__(self, sent, outs, pairs, lasts=(), first=False):
        self.sent = sent
        self.outs = outs
        self.pairs = pairs
        self.lasts = lasts
        self.first = first


class _Ahead:
    """The next turn as :meth:`LMEngine._under_step` prepared it while the
    device ran a decode step (ISSUE 37): its prompt chunk (None: no lane
    prefills, or the chunk was a late hit), its decode step (None: no lane
    will decode), the lanes' generation both were made for and what
    admission left for the recorder's row."""

    __slots__ = ("chunk", "step", "gen", "busy", "queued")

    def __init__(self, chunk, step, gen, busy, queued):
        self.chunk = chunk
        self.step = step
        self.gen = gen
        self.busy = busy
        self.queued = queued


def propose_draft(history, k, max_ngram=3):
    """Prompt-lookup draft (arXiv:2304.04487 / prompt-lookup decoding):
    find the most recent earlier occurrence of the sequence's final
    n-gram (n = ``max_ngram`` down to 1) and propose the (up to ``k``)
    tokens that followed it.  Returns (m,) int32 with 1 <= m <= k —
    exactly the continuation that was found, unpadded, so callers can
    meter real draft tokens — or None when no n-gram recurs.

    Draft quality only affects SPEED: the verifier accepts a draft
    token only when it equals the verifier's own greedy argmax, so even
    an adversarial draft cannot change output."""
    history = numpy.asarray(history, numpy.int32).reshape(-1)
    n = len(history)
    for g in range(min(max_ngram, n - 1), 0, -1):
        # candidate windows must END strictly before the final position
        # (the tail itself is not a match for itself)
        if n - 1 < g:
            continue
        tail = history[n - g:]
        windows = numpy.lib.stride_tricks.sliding_window_view(
            history[:n - 1], g)
        hits = numpy.flatnonzero((windows == tail).all(axis=1))
        if not len(hits):
            continue
        s = int(hits[-1])               # most recent occurrence
        cont = history[s + g:s + g + k]
        if len(cont):
            return numpy.asarray(cont, numpy.int32)
    return None


def compiled_storage_report(text, leaf):
    """What one COMPILED engine program's text (``compiled.as_text()``)
    says about the KV storage, whose leaves all have ``leaf``'s shape
    and dtype: ``(copies, aliased)`` — the instructions whose result is
    a copy with a whole leaf's shape, in any layout (each is a second
    pool held and 2x its bytes moved), and the number of outputs the
    module header lists under ``input_output_alias`` (each donated
    leaf that the program updates in place).  The check
    ``tools/aot_compile.py`` and ``tests/test_chip_compile.py`` make for
    a described chip, no chip attached: no copy, every leaf aliased."""
    import re
    copies = re.findall(
        r"= %s(?:\{[^}]*\})? copy(?:-start)?\(" % re.escape(_hlo_shape(leaf)),
        text)
    header = text.split("\n", 1)[0]
    aliased = re.findall(r"\{\d+\}: \(\d+, \{\}, (?:may|must)-alias\)",
                         header)
    return len(copies), len(aliased)


def compiled_param_copies(text, params):
    """The number of ``copy`` instructions in one COMPILED engine
    program's text whose result has the shape and dtype of a leaf of
    ``params`` with two or more dimensions, in any layout: a weight
    rewritten (transposed, re-tiled) in every dispatch before the dot
    that reads it — the compiler folding a reshape or a transposition of
    a projection's small output into its weight operand (ISSUE 31: three
    a layer through ``ops/attention.py::_qkv_cached``).  The asynchronous
    ``copy-start`` of a parameter (the prefetch of ``wo`` into fast
    memory across programs) is not one.  Checked beside
    :func:`compiled_storage_report`: none."""
    import re
    import jax
    shapes = {_hlo_shape(leaf) for leaf in jax.tree.leaves(params)
              if len(leaf.shape) >= 2}
    return sum(len(re.findall(
        r"= %s(?:\{[^}]*\})? copy\(" % re.escape(shape), text))
        for shape in shapes)


def compiled_grouped_matmuls(text):
    """(the compiler's ``ragged-dot`` operations, calls of the row-tiled
    kernel) in one COMPILED engine program's text: how
    ``ops/moe.py::grouped_matmul`` was carried out there, three a layer
    of experts either way.  The choice is static (rows of the call, dtype,
    platform), so it is counted where programs are compiled (ISSUE 35)."""
    import re
    ragged = re.findall(r"%ragged-dot-(?!metadata)[\w.\-]* = ", text)
    kernel = re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*'
        r'op_name="[^"]*moe\.experts/pallas_call', text)
    return len(ragged), len(kernel)


def compiled_kernel_scopes(text):
    """The innermost ``jax.named_scope`` of every Pallas call in one
    COMPILED engine program's text, in order: the name the device trace
    gives the call, and what the benchmark's readers find a kernel by (a
    roofline that counts the calls named ``ssd`` as one a layer must find
    one: ISSUE 47)."""
    import re
    return re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*'
        r'op_name="[^"]*?([\w.]+)/pallas_call', text)


def _hlo_shape(leaf):
    """``leaf``'s shape and dtype as compiled text writes them."""
    name = {"float32": "f32", "bfloat16": "bf16", "float16": "f16"}[
        str(leaf.dtype)]
    return "%s[%s]" % (name, ",".join(str(n) for n in leaf.shape))


class _PrefixNode:
    __slots__ = ("key", "rows", "children", "refs", "last_use", "parent")

    def __init__(self, key, rows, parent):
        self.key = key                # tuple of the chunk's tokens
        self.rows = rows              # the page that holds their rows
        self.children = {}
        self.refs = 0
        self.last_use = 0
        self.parent = parent


class RadixPrefixCache:
    """Radix trie over prompt tokens at CHUNK granularity.

    A node holds the page of KV rows of exactly ``chunk`` tokens whose
    absolute positions are [depth·chunk, (depth+1)·chunk) — valid for
    ANY prompt sharing that token prefix, because causal attention makes
    a position's K/V depend only on the tokens at and before it.  Keys
    are the chunk's literal tokens, so two prompts diverging mid-chunk
    hash to different keys and can never cross-contaminate (the
    poisoning case the parity suite pins).

    Entries are PINNED (ref-counted) while a lane's admission walk or
    insert path uses them and LRU-evicted leaf-first at ``capacity``
    chunks.  Lookup/insert/evict all run on the single engine worker
    thread — no locking.

    ``rows`` is opaque to the trie: the engine stores a PAGE ID
    (zero-copy sharing).  ``on_evict(rows)`` fires whenever an entry is
    dropped — the engine releases the page's pool reference there, so
    trie eviction IS the pool's reclamation path under pressure (and
    pinned entries refusing eviction is what keeps lane-held pages safe).
    """

    def __init__(self, capacity, chunk, on_evict=None):
        if capacity < 1:
            raise ValueError("prefix cache capacity must be >= 1")
        self.capacity = int(capacity)
        self.chunk = int(chunk)
        self.on_evict = on_evict
        self.root = _PrefixNode(None, None, None)
        self.size = 0
        self._tick = 0

    def match(self, keys):
        """Longest cached prefix along ``keys`` (chunk-token tuples);
        returns the matched nodes in order, each pinned — pass them to
        :meth:`release` when the lane finishes."""
        self._tick += 1
        node, out = self.root, []
        for key in keys:
            child = node.children.get(key)
            if child is None:
                break
            child.refs += 1
            child.last_use = self._tick
            out.append(child)
            node = child
        return out

    def insert(self, parent, key, rows):
        """Add one computed chunk under ``parent`` (root or the lane's
        previous node); returns the PINNED node — existing nodes are
        reused (first writer wins; identical content by construction) —
        or None when every entry is pinned and nothing can be evicted."""
        self._tick += 1
        node = parent.children.get(key)
        if node is None:
            while self.size >= self.capacity:
                if not self._evict_one():
                    return None
            node = _PrefixNode(key, rows, parent)
            parent.children[key] = node
            self.size += 1
        node.refs += 1
        node.last_use = self._tick
        return node

    def lookup_child(self, parent, key):
        """The one-chunk extension of ``parent`` by ``key``, PINNED, or
        None.  Called per pending chunk right before computing it: a
        sibling lane prefilling the same prompt may have inserted the
        chunk since this lane was admitted, and late hits are what make
        CONCURRENT shared-prefix arrivals converge on one prefill
        instead of all missing the cache they are about to fill."""
        node = parent.children.get(key)
        if node is None:
            return None
        self._tick += 1
        node.refs += 1
        node.last_use = self._tick
        return node

    def release(self, nodes):
        for node in nodes:
            node.refs -= 1

    def evict_one(self):
        """Drop the LRU unpinned leaf NOW (pool-pressure reclamation:
        the engine calls this until its page reservation fits or
        nothing more can go).  Returns True when an entry was dropped."""
        return self._evict_one()

    def clear(self):
        """Drop EVERY entry, pinned or not, firing ``on_evict`` for
        each (the engine's page references go home) — the rows
        the entries pointed at are gone with the KV storage
        (``LMEngine._storage_lost``); the lanes that pinned them were
        failed first."""
        stack = list(self.root.children.values())
        self.root.children = {}
        self.size = 0
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            if self.on_evict is not None:
                self.on_evict(node.rows)

    def evictable(self):
        """Upper bound on entries pool-pressure eviction can reclaim:
        the UNPINNED count (an unpinned interior node above a pinned
        child is counted but unreachable — close enough, since lanes
        pin whole root-anchored paths).  The engine checks this
        BEFORE evicting, so a hopeless reservation cannot flush the
        whole cache for nothing."""
        count, stack = 0, [self.root]
        while stack:
            for child in stack.pop().children.values():
                if child.refs == 0:
                    count += 1
                stack.append(child)
        return count

    def live_pins(self):
        """Total outstanding pin count across the trie — 0 whenever no
        lane is active (ISSUE 10: the orphan-pin leak check after
        faulted requests; a nonzero value at idle means a fault path
        forgot to release its admission walk)."""
        total, stack = 0, [self.root]
        while stack:
            for child in stack.pop().children.values():
                total += child.refs
                stack.append(child)
        return total

    def _evict_one(self):
        """Evict the least-recently-used unpinned LEAF (interior nodes
        keep their children's prefix reachable; they become leaves —
        and evictable — once their subtree ages out)."""
        best = None
        stack = list(self.root.children.values())
        while stack:
            node = stack.pop()
            if node.children:
                stack.extend(node.children.values())
            elif node.refs == 0 and (best is None
                                     or node.last_use < best.last_use):
                best = node
        if best is None:
            return False
        del best.parent.children[best.key]
        self.size -= 1
        if self.on_evict is not None:
            self.on_evict(best.rows)
        return True


class LMEngine(Logger):
    """Slot-based continuous batching over ``params`` (a portable
    transformer param tree, see ``TransformerTrainer._to_portable``).

    One worker thread owns the device state; clients :meth:`submit`
    single prompts (or :meth:`generate` a batch) and block on futures.
    ``max_len`` pins a lane's page table: every request must satisfy
    ``len(prompt) + n_new <= max_len`` (+ ``spec_k`` of speculation
    headroom when ``spec_k > 0`` — a verify dispatch writes up to k
    positions past the committed front).

    The cache (see the module docstring): ``prefill_chunk=C`` is the page
    and the prompt chunk in tokens (0: the largest divisor of ``max_len``
    not above 32; it must divide ``max_len``), ``paged_kv=N`` the pool's
    size in pages (0 or True: every lane's whole table, ``slots x
    max_len / C``).  ``prefix_cache=N`` radix KV reuse over N cached
    chunks, ``spec_k=K`` prompt-lookup speculative decoding with
    ``spec_ngram`` match length.  ``queue_tokens=T`` budgets ADMISSION
    by queued prompt tokens (not just request count): a long-prompt
    flood 429s early instead of building an unbounded prefill backlog
    (the head request always admits, so a single oversized prompt can
    not wedge an empty queue).

    ``megastep=K`` (ISSUE 13) fuses K decode iterations — or K
    propose→verify→accept legs under ``spec_k`` — into ONE jitted
    ``lax.scan`` dispatch, moving all host bookkeeping (admission,
    deadline shedding, completion, swaps, tracing) to megastep
    boundaries; 0/1 keeps the per-tick path.  See the module
    docstring.
    """

    #: lock-discipline map (ISSUE 15, checked by tools/veles_lint.py):
    #: the CROSS-THREAD state — client admission vs the worker loop —
    #: lives under ``_cond``.  Everything else (_lanes, _free, _pos,
    #: _last, _last_dev, _kv_pools, _page_tables, _pool, _trie,
    #: _pool_blocked) is owned by the worker thread alone and is
    #: deliberately NOT guarded (checkpoint() documents the torn-read
    #: consequences for its best-effort pool section).
    _guarded_by = {
        "_queue": "_cond",
        "_queued_tokens": "_cond",
        "_queued_pages": "_cond",
        "_journal": "_cond",
        "_rid": "_cond",
        "_pending_swap": "_cond",
        "_stop": "_cond",
    }

    def __init__(self, params, n_heads, max_len, slots=4, rope=False,
                 window=None, sinks=0, queue_depth=64, deadline_s=30.0,
                 metrics=None, name="lm", prefill_chunk=0,
                 prefix_cache=0, spec_k=0, spec_ngram=3,
                 queue_tokens=0, paged_kv=0, attn_kernel=None,
                 tp=0, devices=None, faults=None, version=0,
                 tracer=None, megastep=0):
        if slots < 1:
            raise ValueError("slots must be >= 1")
        self.name = name
        #: optional serving/faults.py FaultPlan — every engine.* site
        #: is one is-None check when unarmed (ISSUE 10)
        self._faults = faults
        #: optional serving/tracing.py SpanTracer (ISSUE 12) — same
        #: unarmed discipline: every site is one is-None check
        self._tracer = tracer
        self.params = params
        #: the model's record (model_config.py): ``n_heads`` is one,
        #: or a head count with the classic keywords beside it
        self.cfg = model_config.of(n_heads, rope, window, sinks)
        self.n_heads = self.cfg.n_heads
        self.max_len = int(max_len)
        # ---- sharded serving (ISSUE 8): ``tp >= 2`` runs EVERY engine
        # program under a one-axis ('tp',) mesh — weights head-/column-
        # sharded by ops/transformer.py::lm_param_specs, KV storage
        # sharded over its kv_heads axis — with the decode/chunk/verify
        # math UNCHANGED (GSPMD inserts the per-block all-reduce).
        # ``devices`` narrows the engine to a device SLICE: a
        # data-parallel replica (serving/router.py) owns devices
        # [i*tp, (i+1)*tp) of the host; tp<2 with ``devices`` pins a
        # single-device replica there.  Output shardings are pinned to
        # the input layout in _build_jits, so the compile count stays
        # at one program per family (the jit-guard bound) under the
        # mesh too.
        self.tp = int(tp or 0)
        if self.tp < 0:
            raise ValueError("tp must be >= 0 (got %d)" % self.tp)
        devices = list(devices) if devices is not None else None
        self._mesh = None
        self._device = None
        self._kv_shard = None
        self._repl_shard = None
        if self.tp >= 2:
            from veles_tpu.parallel import make_tp_mesh
            if self.n_heads % self.tp:
                raise ValueError(
                    "tp=%d must divide n_heads %d (whole attention "
                    "heads shard)" % (self.tp, self.n_heads))
            self._mesh = make_tp_mesh(self.tp, devices)
        elif devices:
            self._device = devices[0]
        self.slots = int(slots)
        self.rope = self.cfg.rope
        self.window = self.cfg.window
        self.sinks = self.cfg.sinks
        self.queue_depth = int(queue_depth)
        self.deadline_s = float(deadline_s)
        self.queue_tokens = int(queue_tokens)
        if not prefill_chunk:
            # the page size must divide max_len (the bit-parity
            # condition below) — default to the largest divisor
            prefill_chunk = min(32, self.max_len)
            while self.max_len % prefill_chunk:
                prefill_chunk -= 1
        self.prefill_chunk = int(prefill_chunk)
        self.spec_k = int(spec_k)
        self.spec_ngram = int(spec_ngram)
        if self.prefill_chunk < 0 or self.prefill_chunk > self.max_len:
            raise ValueError("prefill_chunk %d out of range (max_len %d)"
                             % (self.prefill_chunk, self.max_len))
        if self.spec_k < 0 or self.spec_k + 1 >= self.max_len:
            raise ValueError("spec_k %d out of range (max_len %d)"
                             % (self.spec_k, self.max_len))
        if self.spec_k + 1 > self.prefill_chunk:
            # a prefilling lane parks its step position at the chunk
            # frontier; the next chunk overwrites the verify dispatch's
            # k+1 garbage writes only when they fit inside one chunk
            raise ValueError("spec_k + 1 (%d) must not exceed "
                             "prefill_chunk (%d)"
                             % (self.spec_k + 1, self.prefill_chunk))
        if self.spec_ngram < 1:
            raise ValueError("spec_ngram must be >= 1")
        #: decode megastep (ISSUE 13): K >= 2 fuses K decode (or
        #: propose/verify) iterations into one lax.scan dispatch;
        #: 0/1 = the per-tick path, bit-identical and unchanged.
        self.megastep = int(megastep or 0)
        if self.megastep < 0:
            raise ValueError("megastep must be >= 0 (got %d)"
                             % self.megastep)
        if self.cfg.by_kind or self.cfg.block != "pre_ln":
            # what was not widened to this family says so here, by
            # mechanism — never a wrong answer
            for on, what in (
                    (prefix_cache, "prefix_cache (the radix trie shares "
                     "pages of ONE table; a sliding layer's pages are "
                     "released under it)"),
                    (self.spec_k and self.cfg.by_kind, "spec_k (a "
                     "rejected draft's rows are dead until the next step "
                     "overwrites them, which pages of ONE table allow: a "
                     "sliding layer's released pages and a linear layer's "
                     "recurrent state would need a snapshot to go back to)"),
                    (self.megastep, "megastep (the fused scan program "
                     "carries one table and no window release)"),
                    (self.tp >= 2, "tp >= 2 (lm_param_specs shards the "
                     "pre_ln tree only)")):
                if on:
                    raise ValueError(
                        "LMEngine: %s is not supported for a %r model%s"
                        % (what, self.cfg.block,
                           " with per-layer attention kinds"
                           if self.cfg.by_kind else ""))
        #: the model drafts with its own multi-token-prediction module
        #: (ISSUE 40): the decode step verifies the draft and makes the
        #: next one in the graph, and yields one token or two a lane
        self._mtp = bool(self.spec_k and self.cfg.nextn)
        if self._mtp and self.spec_k != 1:
            raise ValueError(
                "LMEngine: a model with one multi-token-prediction module "
                "drafts one token a step: spec_k must be 1 (got %d)"
                % self.spec_k)
        #: cache positions a lane may write past ``prompt + n_new``: the
        #: drafts of one verify step; with the module drafting, two steps
        #: may be in flight that the host has not seen the counts of
        self.headroom = (2 * (self.spec_k + 1) if self._mtp
                         else self.spec_k)
        if self.max_len % self.prefill_chunk:
            # the paged lane view must tile max_len exactly: a partial
            # tail page would either truncate placeable rows or attend
            # rows past max_len (the chunk program additionally relies
            # on page-aligned starts)
            raise ValueError(
                "paged_kv needs max_len (%d) divisible by the page size "
                "(prefill_chunk, %d)" % (self.max_len,
                                         self.prefill_chunk))
        self.metrics = metrics or ServingMetrics(name)
        self.metrics.set_gauge("slots_total", self.slots)
        self.metrics.set_gauge("slots_busy", 0)
        self.metrics.set_gauge("tp_devices", self.tp or 1)
        #: the checkpoint generation currently serving (ISSUE 11):
        #: swap_weights bumps it, every finished request is stamped
        #: with the version that produced its tokens
        self.weights_version = int(version)
        self.metrics.set_gauge("weights_version", self.weights_version)
        #: in-flight swap_weights request (worker applies at tick
        #: boundaries; None almost always)
        self._pending_swap = None

        embed = params["embed"]
        d_model = embed.shape[1]
        if self.cfg.latent is not None:
            # one row a token a layer, shared by every head: the latent
            # and the rotated key, padded to whole lane tiles
            head_dim, kv_heads = self.cfg.latent.row, 1
        else:
            head_dim = self.cfg.head_size(d_model)
            kv_heads = self.cfg.kv_heads(params["blocks"][0]["attn"],
                                         d_model)
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            if kv_heads % self.tp:
                raise ValueError(
                    "tp=%d must divide kv_heads %d (the KV cache "
                    "shards head-wise)" % (self.tp, kv_heads))
            # the KV arrays below shard over their kv_heads axis so
            # paged_view / mha_paged_chunk_step stay
            # one-program-per-family — the page-table
            # indirection and the head shard compose, neither is a
            # shape
            self._kv_shard = NamedSharding(
                self._mesh, P(None, "tp", None, None))
            self._repl_shard = NamedSharding(self._mesh, P())
        self.params = self._place_params(self.params)
        # ---- serving attention kernels (ISSUE 7): resolve the routing
        # ONCE here — platform and geometry are fixed for the engine's
        # lifetime, so the fallback decision never flaps mid-traffic.
        # attn_kernel: None = follow set_attention_backend
        # ('flash_serve' => 'auto'); 0/False = off; True/'auto' = Pallas
        # kernels on real TPU, XLA fallback elsewhere; 'force' = Pallas
        # even off-TPU (interpret mode — parity tests, not production).
        if attn_kernel is None:
            from veles_tpu.ops.attention import serving_kernel_default
            attn_kernel = "auto" if serving_kernel_default() else 0
        if attn_kernel is True:
            attn_kernel = "auto"
        if attn_kernel not in (0, False, "auto", "force"):
            raise ValueError("attn_kernel must be one of 0/False, "
                             "'auto', 'force' (got %r)" % (attn_kernel,))
        self.attn_kernel = attn_kernel or 0
        self._kernel_active = False
        self._kernel_fallback_reason = None
        if self.attn_kernel:
            from veles_tpu.ops.pallas_kernels import (
                on_tpu, serving_kernels_supported)
            ok, reason = serving_kernels_supported(
                self.n_heads, kv_heads, head_dim, self.prefill_chunk,
                tp=self.tp)
            if ok and (self.attn_kernel == "force" or on_tpu()):
                self._kernel_active = True
            else:
                self._kernel_fallback_reason = reason or (
                    "no TPU backend (interpret-mode kernels are test "
                    "gear; pass attn_kernel='force' to insist)")
                # logged ONCE, here — not per dispatch
                self.warning(
                    "attn_kernel requested but using the XLA path: %s",
                    self._kernel_fallback_reason)
        self.metrics.set_gauge("attn_kernel_active",
                               int(self._kernel_active))
        #: the cost ledger's backend axis (ISSUE 12): which attention /
        #: program path this engine's device spans actually ran
        self._backend = ("pallas" if self._kernel_active
                         else "xla-tp%d" % self.tp if self.tp >= 2
                         else "xla")
        #: the sliding layers' tables and allocator (kv_pool.WindowTables;
        #: None for a stack of one kind)
        self._wt = None
        self._window_shape = None
        #: shapes of a linear layer's (recurrent state, convolution tail),
        #: one slot a lane (None: the stack has no such layer), and which
        #: lanes decode in the step being dispatched
        self._state_shapes = None
        self._decoding = numpy.zeros(self.slots, bool)
        self._max_pages = self.max_len // self.prefill_chunk
        # decode/verify table-width ladder (ISSUE 7 satellite): a
        # step only needs pages up to the batch's live frontier,
        # not the full max_len span — the table is sliced to the
        # smallest power-of-two width covering every lane, so the
        # per-token gather (or kernel grid) scales with what's
        # actually resident.  Power-of-two steps bound the compile
        # count at one step/verify program per LADDER ENTRY (the
        # jit-guard's per-family bound).
        self._width_ladder = []
        w = 1
        while w < self._max_pages:
            self._width_ladder.append(w)
            w *= 2
        self._width_ladder.append(self._max_pages)
        # the pool's size in pages: N, or (0, True, a negative flag
        # value) every lane's whole table
        num_pages = 0 if paged_kv is True else int(paged_kv)
        if num_pages < 1:
            num_pages = self.slots * self._max_pages
        self._pool = KVPagePool(num_pages, self.prefill_chunk)
        # +1: the scratch page.  With the serving kernels active a
        # row packs as many heads as fill the chip's lanes
        # (pool_pack) — the pool then lies on the chip the way the
        # kernels read it, and no dispatch converts it
        pack = 1
        if self._kernel_active:
            from veles_tpu.ops.pallas_kernels import pool_pack
            pack = pool_pack(kv_heads, head_dim)
        self._storage_shape = (num_pages + 1, kv_heads // pack,
                               self.prefill_chunk, head_dim * pack)
        self._page_tables = numpy.zeros(
            (self.slots, self._max_pages), numpy.int32)
        self.metrics.set_gauge("kv_pages_total", num_pages)
        #: the layers that hold pages (a linear layer holds a slot of
        #: state), the module's own among them where it drafts
        n_paged = sum(self.cfg.kind(i) != model_config.LINEAR
                      for i in range(self._n_pools()))
        if self.cfg.latent is not None:
            # the pool's real bytes a token over the latent layers,
            # padding included (width x 2 x layers if nothing were
            # padded)
            self.metrics.set_gauge(
                "kv_bytes_per_token",
                head_dim * embed.dtype.itemsize * n_paged)
        if self.cfg.linear is not None:
            # two kinds of cache in one manager (ISSUE 36): a slot of
            # recurrent state and convolution tail a lane for every
            # linear layer, of a fixed size whatever the lane holds,
            # beside ONE page table for the full layers (k and v
            # pools, or one pool of latent rows, ISSUE 42).  A lane's
            # slot IS its lane: taken at admission, reset by the
            # chunk that starts at 0, freed with the lane's pages
            self._state_shapes = self.cfg.linear.state_shapes(
                self.slots)
            state, tail = self._state_shapes
            self.metrics.set_gauge("state_slots_total", self.slots)
            self.metrics.set_gauge(
                "state_bytes_per_lane", len(self.cfg.state_layers) * (
                    4 * int(numpy.prod(state[1:]))
                    + embed.dtype.itemsize * int(numpy.prod(tail[1:]))))
            if self.cfg.latent is None:
                self.metrics.set_gauge(
                    "kv_bytes_per_token", 2 * kv_heads * head_dim
                    * embed.dtype.itemsize * n_paged)
        if model_config.SLIDING in self.cfg.kinds:
            # two kinds of cache (ISSUE 28): a page table, an
            # allocator and pools of their own for the sliding
            # layers, where a lane never holds more than the
            # window's pages
            wpages = min(num_pages, self.slots
                         * self.cfg.window_pages(self.prefill_chunk))
            self._wt = WindowTables(
                KVPagePool(wpages, self.prefill_chunk), self.slots,
                self.cfg.window)
            self._window_shape = (wpages + 1,) + self._storage_shape[1:]
            self.metrics.set_gauge("kv_pages_total.window", wpages)
        self._storage_dtype = embed.dtype
        self._kv_pools = self._zero_storage()
        #: (cache kind, its layers) for the count of the attention
        #: kernels' page steps (:meth:`_note_attn_dispatch`)
        self._layers_of_kind = sorted(collections.Counter(
            self.cfg.kind(i) for i in range(self._n_pools())
            if self.cfg.kind(i) != model_config.LINEAR).items())
        self._trie = (RadixPrefixCache(
            prefix_cache, self.prefill_chunk, on_evict=self._pool.release)
            if prefix_cache else None)
        #: per-slot device-facing scalars, host-owned between ticks
        self._pos = numpy.zeros(self.slots, numpy.int32)
        self._last = numpy.zeros(self.slots, numpy.int32)
        #: positions a lane may be ahead of ``_pos`` (ISSUE 40): with the
        #: module drafting ``_pos`` is what the fetched counts add up to,
        #: and every step in flight moves its lane ``spec_k + 1`` on at
        #: most; all zeros on every other driver
        self._unseen = numpy.zeros(self.slots, numpy.int32)
        self._lanes = [None] * self.slots
        self._free = list(range(self.slots))
        #: the turn's early stretch (ISSUE 37), the worker thread's own:
        #: what was prepared for the next turn under the step in flight;
        #: the tokens fetched and not yet given to their lanes, as
        #: ``(slot, lane, token, whether it is the request's last,
        #: whether its first)``; a count of the lanes that left a slot, by
        #: which a preparation knows the lanes are still those it was made
        #: for; the prefilling lanes' round robin
        self._ahead = None
        self._undelivered = []
        self._lanes_gen = 0
        self._rr = 0
        #: two dispatches in flight (ISSUE 39), the worker thread's own:
        #: the programs' ``last`` argument as the dispatch before
        #: left it on the device (warm-up makes the first); the
        #: dispatches whose tokens are not fetched yet, oldest first
        #: (:class:`_Flight`; only the plain driver leaves any),
        #: and how many of them were there when the turn began
        self._last_dev = None
        self._flights = collections.deque()
        self._older = 0

        self._queue = collections.deque()
        self._queued_tokens = 0
        self._queued_pages = 0
        self._pool_blocked = False
        self._cond = lockcheck.make_condition("lm_engine._cond")
        self._thread = None
        self._stop = False
        #: admission journal (ISSUE 10): rid -> _Request for every
        #: request not yet resolved — checkpoint() snapshots it so a
        #: supervisor can re-admit in-flight work after a crash
        self._journal = {}
        self._rid = 0
        #: the always-on loop recorder (ISSUE 26, serving/tracing.py):
        #: made in start(), kept after stop() for whoever reads later
        self.recorder = None
        self._build_jits()
        #: whether a decode step's tokens are fetched one dispatch late
        #: (ISSUE 39): the plain driver's order, read off what was built
        #: (the speculative driver drafts from the tokens, the megastep
        #: fetches once in K)
        self._late_fetch = (self._verify_jit is None
                            and self._megastep_jit is None)
        self._update_pool_gauges()

    # ----------------------------------------------------------- placement
    def _fault(self, site):
        """Fault-injection hook (ISSUE 10): free when no plan is
        attached — one attribute-is-None check on the hot path.  The
        lock-order witness (ISSUE 15) piggybacks here: every dispatch-
        class site doubles as a lock-held-across-dispatch probe, one
        module-global None-check when unarmed."""
        if self._faults is not None:
            self._faults.fire(site)
        if lockcheck._witness is not None:
            lockcheck._witness.dispatch(site)

    # ------------------------------------------------------------- tracing
    def _tfence(self, state, traced=True):
        """Dispatch fencing (ISSUE 12): jit returns before the device
        finishes, so a traced span must block on the outputs to time
        device wall, not enqueue.  ONLY called when tracing is armed
        AND the dispatch serves at least one SAMPLED request
        (``traced``) — ``sample:P`` traffic pays the sync only on its
        sampled fraction, and the unarmed path never syncs."""
        if self._tracer is not None and traced:
            import jax
            if lockcheck._witness is not None:
                lockcheck._witness.dispatch("engine.fence")
            jax.block_until_ready(state)

    def _trace_admitted(self, req, slot):
        """Lane assignment (``slot``): the recorder's ``admit`` stamp,
        and the request's queue-wait span closes when it is traced."""
        req.t_admit_ns = time.monotonic_ns()
        req.lane = slot
        if req.tspan is not None:
            req.trace.tracer.end(req.tspan, attrs={
                "wait_s": round(time.monotonic() - req.t_enq, 6)})
            req.tspan = None

    def _trace_queue_end(self, req, error):
        """Close the queue-wait span on a non-admission exit (shed,
        cancel) so the finished tree carries no unclosed spans."""
        if req.tspan is not None:
            req.trace.tracer.end(req.tspan, error=error)
            req.tspan = None

    def _place_params(self, params):
        """Place one param tree per the engine's layout: megatron
        specs over the tp mesh (``lm_param_specs`` — weights head-/
        column-sharded, shard-by-shard device_put), committed to the
        replica's device, or left as given (the single-device
        default).  THE one placement path — construction and
        :meth:`swap_weights` share it, so a hot-swapped tree lands in
        exactly the layout the compiled programs expect (same shapes +
        same shardings = zero recompiles)."""
        import jax
        if self._mesh is not None:
            from jax.sharding import NamedSharding
            from veles_tpu.ops.transformer import lm_param_specs
            return jax.tree.map(
                lambda a, s: jax.device_put(
                    a, NamedSharding(self._mesh, s)),
                params, lm_param_specs(params))
        if self._device is not None:
            return jax.device_put(params, self._device)
        # single-device default: an EXPLICIT one-time placement — host
        # numpy weights left in place would re-transfer implicitly on
        # every dispatch (and trip the armed transfer guard)
        return jax.device_put(params)

    def _n_pools(self):
        """Layers that hold a cache: the stack's, and the module's own
        where it drafts (ISSUE 40)."""
        return len(self.params["blocks"]) + (self.cfg.nextn if self._mtp
                                             else 0)

    def _zero_storage(self):
        """Fresh zero KV storage — one (k, v) pair per block of the
        pool's shape — placed per the engine's layout: head-sharded
        over the tp mesh, committed to the replica's device, or left
        uncommitted (the single-device default)."""
        import jax
        import jax.numpy as jnp
        where = (self._kv_shard if self._mesh is not None
                 else self._device)

        def zeros(shape, dtype=None):
            arr = jnp.zeros(shape, dtype or self._storage_dtype)
            return arr if where is None else jax.device_put(arr, where)

        if self._state_shapes is not None:
            # a linear layer's pair is (state, float32; tail), a full
            # layer's (k pool, v pool), or the one pool of latent rows
            state, tail = self._state_shapes
            pools = 1 if self.cfg.latent is not None else 2
            return [(zeros(state, jnp.float32), zeros(tail))
                    if self.cfg.kind(i) == model_config.LINEAR
                    else tuple(zeros(self._storage_shape)
                               for _ in range(pools))
                    for i in range(len(self.params["blocks"]))]
        shapes = [self._window_shape if self._wt is not None
                  and self.cfg.kind(i) == model_config.SLIDING
                  else self._storage_shape
                  for i in range(self._n_pools())]
        if self.cfg.latent is not None:
            # ONE pool a layer: its rows are (c_kv, k_rope)
            return [(zeros(shape),) for shape in shapes]
        return [(zeros(shape), zeros(shape)) for shape in shapes]

    def _zero_last(self):
        """The programs' ``last`` argument before any dispatch has
        made one (ISSUE 39): zeros, placed where the programs return it
        (replicated over the tp mesh, committed to the replica's device,
        or left uncommitted), so the first call compiles the program every
        later call finds."""
        import jax
        where = (self._repl_shard if self._mesh is not None
                 else self._device)
        zeros = numpy.zeros(self.slots, numpy.int32)
        if self._mtp:
            # (last, draft, position): all three stay on the device
            return tuple(jax.device_put(zeros, where) for _ in range(3))
        return jax.device_put(zeros, where)

    def _storage(self):
        return self._kv_pools

    def _jit(self, fn, out_shardings, storage):
        """``jax.jit`` of one engine program.  ``storage`` is the
        position of the KV storage (the pools) among ``fn``'s
        arguments: every program RETURNS the storage, and that argument
        is DONATED, so the compiled program updates it in place — no
        dispatch copies a pool or holds a second one, and the caller's
        old tree is dead the moment the call returns (every call site
        rebinds the storage to the program's first output in the same
        statement; :meth:`_donating` is the rule for a call that
        raises).  The parameters are never donated.

        Under a tp mesh the output layout is PINNED: without the pin,
        GSPMD's chosen output sharding compares unequal to the
        device_put input layout and the second call of every family
        silently compiles a twin program — the exact recompile ladder
        the jit-guard forbids (and an output laid out otherwise could
        not take the donated buffer)."""
        import jax
        kwargs = {"donate_argnums": (storage,)}
        if out_shardings is not None:
            kwargs["out_shardings"] = out_shardings
        return jax.jit(fn, **kwargs)

    def _out_shard_trees(self):
        """(kv_tree, repl) building blocks for out_shardings under a
        tp mesh — one (k, v) sharding pair per block, and the
        replicated sharding for token outputs — and (None, None) off
        it, where nothing is pinned."""
        if self._mesh is None:
            return None, None
        kv_pair = (self._kv_shard, self._kv_shard)
        return [kv_pair] * len(self.params["blocks"]), self._repl_shard

    # ------------------------------------------------------------- jitted core
    def _build_jits(self):
        """The program set — every shape is fixed by (slots, max_pages,
        chunk, k), so the whole mixed-length workload compiles exactly
        one program per family: ``_chunk_jit`` (one lane, one prompt
        chunk), ``_step_jit`` (every lane, one token, batched over the
        shared pool — vmap cannot carry a shared mutable pool, so the
        batching is explicit), ``_verify_jit`` (every lane, k+1
        speculative positions) and ``_page_copy_jit`` (copy-on-write).
        Prefill is always chunked; prefix hits install page IDS, not
        rows.

        Two ISSUE 7 refinements: when the engine resolved
        ``attn_kernel`` active, every program's attention routes
        through the Pallas serving kernels ('prefill' for the chunk
        program, 'decode' for step/verify — same K/V writes, no
        materialized ``paged_view``); and step/verify accept tables
        SLICED to the live width ladder (one program per ladder entry,
        see ``_live_width``), so the per-token cost follows the batch's
        actual residency, not max_len."""
        import jax
        import jax.numpy as jnp
        from veles_tpu.ops.transformer import (head_logits,
                                               paged_chunk_apply)
        cfg = self.cfg
        kern = self._kernel_active

        full, sliding = model_config.FULL, model_config.SLIDING
        # the step of a record with an expert layer also returns the
        # layers' counts, fetched with the tokens
        stats = cfg.moe is not None

        def tables_of(ptab):
            # (tables, where they begin, the lanes' state) as
            # ``paged_chunk_apply`` takes them: the plain table of a stack
            # of one kind, or ``_table_args``' pair: for two kinds of
            # paged cache (a table per kind, the sliding kind's beginning
            # in tokens per lane), for linear layers beside ONE table
            # (the table, and the lane's slot or the lanes that decode)
            if not isinstance(ptab, tuple):
                return ptab, None, None
            if cfg.linear is not None:
                return ptab[0], None, ptab[1]
            tabs, wbase = ptab
            return tabs, {full: None, sliding: wbase}, None

        def chunk_slot(params, pools, ptab, tokens, start, last_idx,
                       first_at, last):
            # one lane's prompt chunk through its page table; takes the
            # lanes' last tokens ``last`` as they lie on the device and
            # returns them with the argmax after ``last_idx`` (the
            # chunk's last real row: behind it lies padding, which a
            # linear layer must be told of) written at slot ``first_at``:
            # a tail chunk's lane decodes from it (ISSUE 39); a chunk
            # that is no tail gives -1 and ``last`` back as it came
            tokens = tokens[None]
            tabs, base, slot = tables_of(
                jax.tree.map(lambda t: t[None], ptab))
            state = ({} if slot is None else
                     {"slots": slot, "rows": (last_idx + 1)[None]})
            h, pools = paged_chunk_apply(
                params, tokens, pools, tabs, start[None],
                cfg, attn_kernel="prefill" if kern else None, base=base,
                **state)
            logits = head_logits(params, jax.lax.dynamic_slice_in_dim(
                h, last_idx, 1, axis=1), cfg)[:, 0, :]
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[0]
            return pools, jnp.where(
                jnp.arange(last.shape[0]) == first_at, tok, last)

        def step_all(params, pools, ptabs, toks, pos, live):
            # ONE dispatch advances every lane by one token at its own
            # position through its own page table.  ``toks`` is the
            # output of the dispatch before (the step's own, or the tail
            # chunk's that wrote into it), never fetched in between; a
            # lane that does not decode (``live`` false: free, or in its
            # prompt) steps on token 0 whatever that output left there
            toks = jnp.where(live, toks, 0)
            tabs, base, decoding = tables_of(ptabs)
            state = ({} if decoding is None else
                     {"rows": decoding.astype(jnp.int32)})
            h, pools, *counts = paged_chunk_apply(
                params, toks[:, None], pools, tabs, pos, cfg,
                attn_kernel="decode" if kern else None, base=base,
                with_stats=stats, **state)
            logits = head_logits(params, h, cfg)[:, 0, :]
            toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (pools, toks, *counts)

        if self._mtp:
            # the model drafts with its own module (ISSUE 40): the same
            # two programs under the same names, the chunk with the
            # module's rows of the prompt behind the stack's, the step
            # verifying a draft a lane and making the next
            from veles_tpu.ops.transformer import (mtp_chunk_apply,
                                                   mtp_verify_step)
            slots = self.slots

            def chunk_slot(params, pools, ptab, tokens, start, last_idx,
                           first_at, state):
                # ``tokens``: the chunk's and the one behind them (the
                # module's row i takes token i + 1); ``state``: the lanes'
                # (last, draft, position) as they lie on the device, given
                # back with the chunk's first token, the module's draft of
                # the one after it and the prompt's length written at slot
                # ``first_at`` (a tail chunk's; -1: as they came)
                pools, tok, draft = mtp_chunk_apply(
                    params, tokens[None, :-1], tokens[None, 1:], pools,
                    ptab[None], start[None], cfg, last_idx, first_at >= 0,
                    attn_kernel="prefill" if kern else None)
                here = jnp.arange(slots) == first_at
                last, drafts, pos = state
                return pools, (jnp.where(here, tok, last),
                               jnp.where(here, draft, drafts),
                               jnp.where(here, start + last_idx + 1, pos))

            def step_all(params, pools, ptabs, state, live):
                # ONE dispatch verifies every live lane's draft and makes
                # its next: (pools, the state the next dispatch takes,
                # tokens (slots, 2), how many of them are real, the expert
                # layers' counts); the host fetches the last three, late
                return mtp_verify_step(
                    params, pools, ptabs, *state, live, cfg,
                    attn_kernel="decode" if kern else None)

        def page_copy(pools, src, dst):
            # copy-on-write: duplicate one page across every block so
            # the writer owns ``dst`` exclusively and the other
            # referents of ``src`` keep bit-identical rows
            # (a linear layer's pair is slots of state, not pages)
            return [layer if cfg.kind(i) == model_config.LINEAR
                    else tuple(p.at[dst].set(p[src]) for p in layer)
                    for i, layer in enumerate(pools)]

        kv_tree, repl = self._out_shard_trees()
        pair = (kv_tree, repl) if kv_tree is not None else None
        # programs: chunk
        self._chunk_jit = self._jit(chunk_slot, pair, storage=1)
        # programs: step
        self._step_jit = self._jit(step_all, pair, storage=1)
        # programs: page_copy
        self._page_copy_jit = self._jit(page_copy, kv_tree, storage=0)
        self._verify_jit = None
        if self.spec_k and not self._mtp:
            def verify_all(params, pools, ptabs, toks, pos):
                # toks (slots, k+1) = [last committed, draft…] per lane;
                # returns the greedy argmax AFTER each fed position
                h, pools = paged_chunk_apply(
                    params, toks, pools, ptabs, pos, cfg,
                    attn_kernel="decode" if kern else None)
                logits = head_logits(params, h, cfg)  # (slots, k+1, v)
                return pools, jnp.argmax(
                    logits, axis=-1).astype(jnp.int32)

            # programs: verify
            self._verify_jit = self._jit(verify_all, pair, storage=1)

        # decode megastep (ISSUE 13): the fused K-iteration program —
        # the page-table slice stays a traced-data argument, so the
        # compile bound is one program per (live-width ladder entry × K)
        # family, K fixed per engine; its outputs are (storage, last,
        # pos, emitted[, accs])
        self._megastep_jit = None
        if self.megastep >= 2:
            n_out = 5 if self.spec_k else 4
            out_sh = ((kv_tree,) + (repl,) * (n_out - 1)
                      if kv_tree is not None else None)
            # programs: megastep
            self._megastep_jit = self._jit(self._make_megastep_body(),
                                           out_sh, storage=1)

    # --------------------------------------------------------- megastep
    def _make_megastep_body(self):
        """Build the fused K-iteration decode program (ISSUE 13) for
        this engine's speculation mode — the scan body IS the per-tick
        batched step (or propose → verify → accept leg), so any K is
        bit-identical to K repeated host ticks by construction.

        Signature of the returned function, one per ``spec_k``:
        ``(params, storage, ptabs, last, pos, left[, hist, hlen]) ->
        (storage, last, pos, emitted[, accs])`` — ``hist, hlen`` and
        ``accs`` with ``spec_k`` — where ``storage`` is the pools,
        ``emitted`` is (K, slots) int32 — or (K, slots, spec_k+1)
        speculative — with -1 marking positions a frozen (early-exited
        or never-active) lane did not emit, and ``accs`` (K, slots)
        carries each iteration's draft-acceptance count (-1 when
        frozen) for the host's metering.

        EARLY-EXIT MASKING: a lane whose ``left`` hits 0 freezes — its
        last token, position and history stop advancing, its emitted
        slots read -1, and its K/V writes are redirected to the scratch
        page via ``write_mask`` so a dead iteration can never touch an
        allocated (possibly trie-shared) page.

        SPECULATIVE leg: the draft comes from
        ``ops/transformer.py::propose_draft_in_graph`` over a carried
        (slots, max_len) token-history buffer — accepted tokens are by
        construction the verifier's own argmax (``emit = out[:acc+1]``,
        since a draft token only counts as accepted when it EQUALS the
        argmax), so greedy output is exact whatever the draft, and
        spec_k composes with the megastep at zero host round-trips."""
        import jax
        import jax.numpy as jnp
        from veles_tpu.ops.transformer import (head_logits,
                                               paged_chunk_apply)
        K, k = self.megastep, self.spec_k
        cfg = self.cfg
        kern = self._kernel_active
        L = self.max_len

        if not k:
            def plain_iter(params, storage, ptabs, carry):
                last, pos, left = carry
                active = left > 0
                h, storage = paged_chunk_apply(
                    params, last[:, None], storage, ptabs, pos, cfg,
                    attn_kernel="decode" if kern else None,
                    write_mask=active)
                logits = head_logits(params, h)[:, 0, :]
                toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                emit = jnp.where(active, toks, -1)
                last = jnp.where(active, toks, last)
                pos = jnp.where(active, pos + 1, pos)
                left = left - jnp.where(active, 1, 0)
                return storage, (last, pos, left), emit

            def mega_plain(params, storage, ptabs, last, pos, left):
                def body(carry, _):
                    storage, rest = carry
                    storage, rest, emit = plain_iter(
                        params, storage, ptabs, rest)
                    return (storage, rest), emit

                (storage, rest), emitted = jax.lax.scan(
                    body, (storage, (last, pos, left)), None, length=K)
                return storage, rest[0], rest[1], emitted

            return mega_plain

        from veles_tpu.ops.transformer import propose_draft_in_graph
        ngram = self.spec_ngram
        propose_all = jax.vmap(
            lambda h, hl: propose_draft_in_graph(h, hl, k, ngram))
        cols = xfer.to_device(numpy.arange(k + 1)[None, :])
        # frozen-lane feed clamp: an active lane's legitimate feed
        # positions never reach it (admission reserves n_new + spec_k
        # headroom), and a finished lane's garbage verify window
        # [pos, pos+k] must stay inside [0, max_len)
        cap = xfer.to_device(L - 1 - k, numpy.int32)

        def spec_iter(params, storage, ptabs, carry):
            last, pos, left, hist, hlen = carry
            active = left > 0
            draft, _found = propose_all(hist, hlen)
            toks = jnp.concatenate([last[:, None], draft], axis=1)
            h, storage = paged_chunk_apply(
                params, toks, storage, ptabs, pos, cfg,
                attn_kernel="decode" if kern else None,
                write_mask=active)
            logits = head_logits(params, h)
            out = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # leading draft/argmax matches; accepted tokens ARE
            # out[:acc], so the emit window is simply out[:take]
            matches = (draft == out[:, :k]).astype(jnp.int32)
            acc = jnp.cumprod(matches, axis=1).sum(axis=1)
            take = jnp.minimum(acc + 1, left)
            emit = jnp.where(
                active[:, None] & (cols < take[:, None]), out, -1)
            # history append: the full (k+1) window lands at hlen
            # (start clamped so the update can never shift); rows
            # past `take` are overwritten by the next append or
            # never read — draft quality is speed-only
            hist = jax.vmap(
                lambda h_, hl, row, act: jnp.where(
                    act, jax.lax.dynamic_update_slice(
                        h_, row, (jnp.minimum(hl, L - (k + 1)),)), h_))(
                hist, hlen, out, active)
            hlen = jnp.where(active, jnp.minimum(hlen + take, L), hlen)
            last = jnp.where(active, jnp.take_along_axis(
                out, acc[:, None], axis=1)[:, 0], last)
            pos = jnp.where(active, jnp.minimum(pos + acc + 1, cap), pos)
            left = left - jnp.where(active, take, 0)
            return storage, (last, pos, left, hist, hlen), \
                (emit, jnp.where(active, acc, -1))

        def mega_spec(params, storage, ptabs, last, pos, left, hist,
                      hlen):
            def body(carry, _):
                storage, rest = carry
                storage, rest, out = spec_iter(params, storage, ptabs,
                                               rest)
                return (storage, rest), out

            (storage, rest), (emitted, accs) = jax.lax.scan(
                body, (storage, (last, pos, left, hist, hlen)), None,
                length=K)
            return storage, rest[0], rest[1], emitted, accs

        return mega_spec

    # --------------------------------------------------------------- lifecycle
    def _warmup(self):
        """Compile every program family before traffic, with every
        dispatch argument an explicit transfer (xfer shims) — the
        first code to run under the armed transfer guard.  Ends by
        saying whether the storage is updated in place: the gauge
        ``kv_storage_in_place`` is 1 when the leaves that went into the
        last dispatch (a decode program) came back consumed."""
        went_in = self._warm_programs()
        in_place = went_in.is_deleted()
        self.metrics.set_gauge("kv_storage_in_place", int(in_place))
        if not in_place:
            self.warning(
                "the KV storage was NOT donated to the last warm-up "
                "dispatch: every dispatch copies it and holds two")

    def _warm_programs(self):
        """One dispatch of every program family and table width;
        returns one storage leaf as it went into the last."""
        zero = xfer.to_device(0, numpy.int32)
        zeros = xfer.to_device(numpy.zeros(self.slots, numpy.int32))
        ptabs = numpy.zeros((self.slots, self._max_pages),
                            numpy.int32)
        # (the lanes' last tokens pass from one program's output to
        # the next one's argument, as they will in traffic)
        self._kv_pools, self._last_dev = self._chunk_jit(
            self.params, self._kv_pools,
            self._table_args(ptabs[0], 0),
            xfer.to_device(numpy.zeros(
                self.prefill_chunk + int(self._mtp), numpy.int32)),
            zero, zero,
            xfer.to_device(-1, numpy.int32), self._zero_last())
        self._kv_pools = self._page_copy_jit(self._kv_pools, zero,
                                             zero)
        none = xfer.to_device(numpy.zeros(self.slots, bool))
        # step/verify (or the fused megastep, which REPLACES them
        # on the decode loop) compile one program per
        # live-width ladder entry (ISSUE 7) — warm EVERY entry now,
        # or the first request to cross each width boundary pays
        # its compile inside the serving loop
        for w in self._width_ladder:
            wtab = self._table_args(ptabs[:, :w], slice(None))
            if self._megastep_jit is not None:
                args = [self.params, self._kv_pools, wtab,
                        zeros, zeros, zeros]
                if self.spec_k:
                    args += [xfer.to_device(numpy.zeros(
                        (self.slots, self.max_len), numpy.int32)),
                        zeros]
                went_in = self._kv_pools[0][0]
                self._kv_pools = self._megastep_jit(*args)[0]
                continue
            if self._verify_jit is not None:
                self._kv_pools, _ = self._verify_jit(
                    self.params, self._kv_pools, wtab,
                    xfer.to_device(numpy.zeros(
                        (self.slots, self.spec_k + 1),
                        numpy.int32)), zeros)
            went_in = self._kv_pools[0][0]
            self._kv_pools, self._last_dev = self._step_jit(
                self.params, self._kv_pools, wtab, self._last_dev,
                *(() if self._mtp else (zeros,)), none)[:2]
        return went_in

    def start(self):
        # warm every program before traffic: the discarded warmup
        # writes land on the scratch page.  Warmup runs
        # under the transfer-guard witness (dispatch arguments built
        # through the explicit xfer shims, like the worker loop).
        with xfer.guard():
            self._warmup()
        self.recorder = tracing.register(tracing.LoopRecorder(self.name))
        with self._cond:
            self._stop = False
        self._thread = threading.Thread(target=self._worker, daemon=True,
                                        name="lm-engine-%s" % self.name)
        self._thread.start()
        return self

    def stop(self):
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=60)
            self._thread = None

    # ---------------------------------------------------------------- hot swap
    def _check_swap_structure(self, params):
        """Refuse a structurally incompatible tree LOUDLY before
        anything is placed: the compiled programs are specialized on
        the current shapes/dtypes, so a mismatch would either recompile
        every family mid-traffic or crash a dispatch.  Old weights keep
        serving on refusal — nothing is touched here."""
        import jax
        from jax.tree_util import keystr, tree_flatten_with_path
        old, old_def = tree_flatten_with_path(self.params)
        new, new_def = jax.tree_util.tree_flatten(params)
        if old_def != new_def:
            raise ValueError(
                "swap refused: new param tree structure differs from "
                "the serving tree (%s vs %s) — old weights keep "
                "serving" % (new_def, old_def))
        for (path, o), n in zip(old, new):
            shape = tuple(getattr(n, "shape", ()) or ())
            dtype = getattr(n, "dtype", None)
            if shape != tuple(o.shape) or dtype != o.dtype:
                raise ValueError(
                    "swap refused: param %s is %s%s but the serving "
                    "tree holds %s%s — old weights keep serving"
                    % (keystr(path), shape, dtype, tuple(o.shape),
                       o.dtype))

    def swap_weights(self, params, version=None, drain=False,
                     timeout_s=120.0):
        """Hot-install ``params`` (same structure/shapes/dtypes as the
        serving tree) into this LIVE engine without dropping lanes.

        The tree is validated and ``device_put`` under the engine's
        existing placement HERE, on the caller's thread (off the decode
        hot path; tp engines re-shard by ``lm_param_specs`` shard-by-
        shard); the worker applies the swap at a tick boundary.  By
        default in-flight lanes FINISH on the old weights first —
        admission holds while they do, the old tree stays pinned until
        its last lane completes, and the apply itself is one pointer
        assignment (no decode tick stalls longer than a step).  With
        ``drain=True`` active lanes are withdrawn whole and re-queued
        at the head instead: they re-decode from scratch on the new
        weights, resolving their (unchanged) futures exactly once.

        ``version`` (int; default: current + 1) becomes
        :attr:`weights_version` — stamped on every result produced by
        the new weights and exported as the ``weights_version`` gauge.
        Returns the installed version; raises ValueError on structural
        mismatch and re-raises an apply-time fault (``engine.swap``
        site), in both cases leaving the old weights serving.  Blocks
        until applied (``timeout_s`` bounds a wedged worker)."""
        self._check_swap_structure(params)
        placed = self._place_params(params)
        if version is None:
            version = self.weights_version + 1
        version = int(version)
        with self._cond:
            if self._pending_swap is not None:
                raise RuntimeError("a weight swap is already in flight")
            if self._thread is None or self._stop:
                # not serving: apply directly (start() warms the new
                # tree like any other)
                self.params = placed
                self._set_version(version)
                self.metrics.inc("weight_swaps")
                return version
            swap = {"params": placed, "version": version,
                    "drain": bool(drain), "done": threading.Event(),
                    "exc": None, "t0": time.monotonic()}
            self._pending_swap = swap
            self._cond.notify_all()
        if not swap["done"].wait(timeout_s):
            with self._cond:
                withdrawn = self._pending_swap is swap
                if withdrawn:
                    self._pending_swap = None
            if not withdrawn:
                # the worker CLAIMED the swap right at the deadline —
                # the apply is a pointer assignment, so give it a
                # moment rather than reporting a state we know is
                # about to be wrong
                swap["done"].wait(5.0)
            if not swap["done"].is_set():
                raise RuntimeError(
                    "weight swap did not apply within %.0fs (worker "
                    "wedged or lanes never finished); %s"
                    % (timeout_s,
                       "old weights keep serving" if withdrawn else
                       "swap state INDETERMINATE — the worker claimed "
                       "it but never finished applying"))
        if swap["exc"] is not None:
            raise swap["exc"]
        return version

    def _set_version(self, version):
        self.weights_version = int(version)
        self.metrics.set_gauge("weights_version", self.weights_version)

    def _peek_swap(self):
        """Racy worker peek at the pending weight swap.  Read-only:
        every consumer that acts on the result re-checks (and claims)
        under ``_cond`` — ``_admit`` only uses it to hold work back for
        a tick, and ``_maybe_apply_swap`` re-validates identity before
        claiming."""
        # lint: allow(lock-discipline): racy worker peek; claim re-checked under _cond
        return self._pending_swap

    def _maybe_apply_swap(self):
        """Worker-side swap application (one is-None check per tick).
        Finish-on-old waits for the active lanes (admission is held in
        ``_admit`` so the wait is bounded by their remaining n_new);
        drain mode re-queues them whole first.  The apply itself is a
        pointer assignment — the tree was placed on the caller's
        thread."""
        swap = self._peek_swap()
        if swap is None:
            return
        # the turn runs in the old order while a swap waits, and every
        # reply owed is made before it may apply: a lane the plain step
        # freed by count still owes its last token's delivery, on the
        # weights that made it
        self._drop_ahead()
        self._drain()
        self._deliver()
        active = [i for i, lane in enumerate(self._lanes)
                  if lane is not None]
        if active and not swap["drain"]:
            return           # lanes finish on the OLD weights first
        with self._cond:
            # CLAIM before mutating anything: a timed-out caller may
            # have withdrawn the swap — applying (or requeueing lanes
            # for) a withdrawn swap would serve weights the caller was
            # told never installed
            if self._pending_swap is not swap:
                return
            self._pending_swap = None
        if active:
            self._requeue_active(active)
        t0a = time.monotonic()
        try:
            self._fault("engine.swap")
            self.params = swap["params"]
        except Exception as e:   # noqa: BLE001 — refuse, keep serving
            swap["exc"] = e
            self.metrics.record_error()
            self.metrics.inc("weight_swap_failures")
            self.warning("weight swap refused at apply: %s (old "
                         "weights keep serving)", e)
            if self._tracer is not None:
                self._tracer.event(
                    "swap.refused", cat="swap", t0=t0a,
                    attrs={"engine": self.name, "error": str(e)})
        else:
            self._set_version(swap["version"])
            self.metrics.inc("weight_swaps")
            self.metrics.set_gauge("swap_quiesce_s",
                                   time.monotonic() - swap["t0"])
            if self._tracer is not None:
                self._tracer.event(
                    "swap.apply", cat="swap", t0=t0a,
                    attrs={"engine": self.name,
                           "version": swap["version"],
                           "drain": swap["drain"],
                           "quiesce_s": round(
                               time.monotonic() - swap["t0"], 4)})
        swap["done"].set()

    def _requeue_active(self, active):
        """Drain-mode swap: withdraw every active lane WHOLE and put
        its request back at the queue head in original admission order
        — the engine-internal analogue of the router's drain
        re-placement.  The futures are untouched: each request
        re-decodes from scratch (on the new weights) and resolves
        exactly once."""
        order = sorted(active,
                       key=lambda s: self._lanes[s].request.t_enq)
        reqs = []
        fresh_deadline = time.monotonic() + self.deadline_s
        for slot in order:
            lane = self._lanes[slot]
            self._vacate_slot(slot, lane)
            # the re-decode gets a fresh admission-sized budget: the
            # request already spent its wait DECODING — shedding it
            # 503 at its original deadline would turn the deploy into
            # a client-visible error
            lane.request.deadline = max(lane.request.deadline,
                                        fresh_deadline)
            req = lane.request
            if req.trace is not None:
                req.trace.tracer.instant(
                    req.trace, "swap.requeue", cat="engine")
                # back in the queue: a fresh queue-wait span, ended by
                # the re-admission like any other
                req.tspan = req.trace.tracer.begin(
                    req.trace, "queue.wait", cat="queue",
                    attrs={"engine": self.name, "requeued": True})
            reqs.append(req)
        with self._cond:
            for req in reversed(reqs):
                self._queue.appendleft(req)
                self._queued_tokens += req.true_len
                self._queued_pages += req.pages
            self.metrics.set_gauge("queue_depth", len(self._queue))
            self.metrics.set_gauge("queue_tokens", self._queued_tokens)
            self.metrics.set_gauge("queue_pages", self._queued_pages)
        self.metrics.inc("requests_requeued_for_swap", len(reqs))

    # ------------------------------------------------------------------ client
    def submit(self, prompt, n_new):
        """Queue one prompt ((s,) ints) for ``n_new`` greedy tokens;
        returns a Future resolving to the (n_new,) continuation."""
        prompt = numpy.asarray(prompt, numpy.int32).reshape(-1)
        if len(prompt) < 1:
            raise ValueError("empty prompt")
        if n_new < 1:
            raise ValueError("n_new must be >= 1")
        if len(prompt) + n_new + self.headroom > self.max_len:
            extra = (" (+%d speculative headroom, spec_k)" % self.headroom
                     if self.spec_k else "")
            raise ValueError("prompt %d + n_new %d%s exceeds the engine "
                             "cache length %d"
                             % (len(prompt), n_new, extra, self.max_len))
        span = len(prompt) + n_new + self.headroom
        demand = -(-span // self.prefill_chunk)
        if demand > self._pool.num_pages:
            raise ValueError(
                "prompt %d + n_new %d needs %d KV pages but the "
                "pool holds %d — this request can never be placed"
                % (len(prompt), n_new, demand, self._pool.num_pages))
        self._fault("engine.submit")
        # tracing (ISSUE 12): join the caller's request context (HTTP /
        # router) or root one here (direct engine use, benches) —
        # whoever STARTED the trace finishes it, so own_root marks
        # ours; a sampled-out decision anywhere above sticks
        tctx, own_root = None, False
        if self._tracer is not None:
            tctx, own_root = tracing.join_or_root(
                self._tracer, "engine.request", "engine",
                attrs={"engine": self.name})
            if tctx is tracing.SAMPLED_OUT:
                tctx = None
        try:
            return self._submit_admit(prompt, n_new, demand, tctx,
                                      own_root)
        except Exception as e:
            if own_root:
                tctx.tracer.finish_request(tctx, error=e)
            raise

    def _submit_admit(self, prompt, n_new, demand, tctx, own_root):
        with self._cond:
            if self._stop or self._thread is None:
                raise RuntimeError("LM engine is not running")
            if len(self._queue) >= self.queue_depth:
                self.metrics.record_reject()
                raise Overloaded()
            if self.queue_tokens and self._queue and \
                    self._queued_tokens + len(prompt) > self.queue_tokens:
                # prompt-length budgeting: queued PREFILL WORK is
                # bounded, not just request count — a burst of long
                # prompts sheds early instead of stacking seconds of
                # head-of-line prefill behind the queue
                self.metrics.record_reject()
                self.metrics.inc("rejected_tokens", len(prompt))
                raise Overloaded()
            if self._queue and \
                    self._queued_pages + demand > 2 * self._pool.num_pages:
                # pool-pressure admission: once TWO full pools' worth
                # of page demand is queued (one generation decoding,
                # one waiting), a new arrival would only sit until its
                # deadline — 429 it NOW with an exception that names
                # the resource (never a hang; the head request always
                # enqueues, so a single large request cannot wedge an
                # empty queue)
                self.metrics.record_reject()
                self.metrics.inc("rejected_pages", demand)
                raise PoolExhausted(demand, 2 * self._pool.num_pages)
            req = _Request(prompt, int(n_new), self.deadline_s,
                           pages=demand)
            if tctx is not None:
                req.trace = tctx
                req.tspan = tctx.tracer.begin(
                    tctx, "queue.wait", cat="queue",
                    attrs={"engine": self.name})
                if own_root:
                    req.future.add_done_callback(
                        lambda f, ctx=tctx:
                        tracing.finish_from_future(ctx, f))
            # admission journal (ISSUE 10): the entry lives until the
            # request's future settles (result, exception or cancel) —
            # checkpoint() snapshots exactly the unresolved set.  The
            # pop re-takes the (reentrant) engine lock so a concurrent
            # checkpoint never iterates a mutating dict.
            self._rid += 1
            rid = self._rid
            self._journal[rid] = req
            req.future.add_done_callback(
                lambda f, rid=rid, req=req: self._settled(rid, req, f))
            self._queue.append(req)
            self._queued_tokens += req.true_len
            self._queued_pages += req.pages
            self.metrics.record_enqueue()
            self.metrics.set_gauge("queue_depth", len(self._queue))
            # the router/bench-visible high-water mark of this
            # replica's backlog (an instantaneous gauge under-reads
            # between scrapes)
            self.metrics.set_gauge_max("queue_depth_peak",
                                       len(self._queue))
            self.metrics.set_gauge("queue_tokens", self._queued_tokens)
            self.metrics.set_gauge("queue_pages", self._queued_pages)
            self._cond.notify()
        return req.future

    def generate(self, prompts, n_new, return_versions=False,
                 return_drafts=False):
        """Decode a whole (b, s) prompt batch; returns (b, s + n_new)
        int32 — prompt plus greedy continuation per row (rows decode
        concurrently across slots; with ``return_versions`` also the
        ``weights_version`` that served each row — rows straddling a
        hot swap carry different stamps; with ``return_drafts`` also, per
        row, what the model's own module drafted: ``[n, token]`` where it
        put ``token`` for the row's n-th new token, accepted or not;
        empty where nothing drafts).  All-or-nothing: if a later
        row is refused (Overloaded/...), the rows already queued are
        CANCELLED instead of decoding to discarded results — a rejected
        batch must not keep consuming slots exactly when the engine is
        overloaded."""
        prompts = numpy.asarray(prompts, numpy.int32)
        futures = []
        try:
            for row in prompts:
                futures.append(self.submit(row, n_new))
            news = numpy.stack([f.result() for f in futures])
        except Exception:
            # one row refused (Overloaded) or failed (shed, prefill
            # fault): withdraw ALL siblings — they must not keep
            # consuming slots for output nobody will read
            for f in futures:
                self._cancel(f.request)
            raise
        out = (numpy.concatenate([prompts, news], axis=1),)
        if return_versions:
            out += ([getattr(f, "version", None) for f in futures],)
        if return_drafts:
            out += ([[list(d) for d in getattr(f, "drafts", ())]
                     for f in futures],)
        return out if len(out) > 1 else out[0]

    def _cancel(self, req):
        """Withdraw a request: dequeue it if still queued; if already in
        a slot, flag it so the worker frees the slot at the next tick."""
        req.cancelled = True
        with self._cond:
            try:
                self._queue.remove(req)
                self._queued_tokens -= req.true_len
                self._queued_pages -= req.pages
            except ValueError:
                return           # admitted (or done) — worker handles it
        self._trace_queue_end(req, "cancelled")
        req.future.cancel()

    # --------------------------------------------------- crash-safe recovery
    def _settled(self, rid, req, future):
        """A request's future settled (result, exception or cancel; the
        thread that settled it): its recorder record is written — THE
        one site, whatever path ended the request — and it leaves the
        admission journal."""
        exc = None if future.cancelled() else future.exception()
        if future.cancelled():
            outcome = "cancelled"
        elif exc is None:
            outcome = "ok"
        elif isinstance(exc, DeadlineExceeded):
            outcome = "shed"
        else:
            outcome = "failed"
        self.recorder.finished(req, outcome)
        with self._cond:
            self._journal.pop(rid, None)

    def checkpoint(self):
        """JSON-safe snapshot of the HOST-side serving state (ISSUE
        10): every ADMITTED-but-unresolved request (the admission
        journal), the slot frontiers, the page tables
        and the pool's full ref/pin/free bookkeeping.  Taken under the
        engine lock, so the request set is consistent; cheap enough to
        take per admission tick.

        A crash loses DEVICE state (KV rows) unconditionally, so the
        checkpoint deliberately carries no tensors: :meth:`restore`
        re-admits the journaled work on a fresh engine and prefill
        re-derives the KV — greedy decode is deterministic, so the
        resumed outputs are bit-identical to what the crashed engine
        would have served.  The pool/page-table sections exist for
        POST-MORTEM diagnostics (what the allocator looked like at
        the crash), not for reattachment — and since the worker
        mutates the allocator without this lock, they can be torn
        mid-tick on a LIVE engine: treat them as best-effort evidence
        (a phantom inconsistency in a live-traffic snapshot is the
        tear, not a leak); only the request set is exact.
        :meth:`restore` never reads them."""
        with self._cond:
            entries = [{"rid": rid,
                        "prompt": [int(t) for t in req.prompt],
                        "n_new": int(req.n_new)}
                       for rid, req in sorted(self._journal.items())
                       if not req.future.done() and not req.cancelled]
            state = {
                "format": 1,
                "config": {"max_len": self.max_len,
                           "slots": self.slots,
                           "prefill_chunk": self.prefill_chunk,
                           "spec_k": self.spec_k,
                           # (format 1 has both; there is one layout)
                           "paged_kv": True,
                           "pool_pages": self._pool.num_pages},
                "requests": entries,
                "slot_frontiers": {
                    "pos": [int(x) for x in self._pos],
                    "last": [int(x) for x in self._last]},
            }
            state["pool"] = self._pool.snapshot()
            state["page_tables"] = self._page_tables.tolist()
            if self._trie is not None:
                state["prefix_cache_chunks"] = self._trie.size
        return state

    def restore(self, state):
        """Re-admit a :meth:`checkpoint`'s unresolved requests into
        THIS (fresh, already :meth:`start`-ed) engine after a crash:
        verifies the new pool's allocator invariants first (a restore
        must never begin on a corrupt pool), then submits each
        journaled request afresh.  Returns ``{rid: Future}`` so the
        supervisor can hand results back to whoever was waiting.

        In-flight-at-crash work is resumed AT-LEAST-ONCE from the
        engine's point of view (a request that completed between the
        checkpoint and the crash re-runs); exactly-once delivery is
        the caller's layer (the router's drain/requeue discipline —
        an old future that already delivered is simply gone with the
        crashed process)."""
        if not isinstance(state, dict) or state.get("format") != 1:
            raise ValueError("not an LMEngine checkpoint (format %r)"
                             % (state.get("format")
                                if isinstance(state, dict) else state))
        cfg = state.get("config", {})
        if int(cfg.get("max_len", self.max_len)) > self.max_len:
            raise ValueError(
                "checkpoint was taken at max_len %d but this engine "
                "holds %d — journaled prompts may not fit"
                % (cfg["max_len"], self.max_len))
        self.verify_pool_invariants()
        futures = {}
        entries = list(state.get("requests", ()))
        # validate EVERY entry against this engine's geometry before
        # admitting ANY: a structural refusal (span beyond max_len, a
        # page demand the restoring pool can never cover) must be an
        # all-or-nothing ValueError up front, not a mid-loop escape
        # that strands already-re-admitted futures
        for entry in entries:
            span = len(entry["prompt"]) + int(entry["n_new"]) \
                + self.headroom
            if span > self.max_len:
                raise ValueError(
                    "journaled request rid=%s needs %d cache positions "
                    "but this engine holds %d"
                    % (entry.get("rid"), span, self.max_len))
            if -(-span // self.prefill_chunk) > self._pool.num_pages:
                raise ValueError(
                    "journaled request rid=%s needs %d KV pages but "
                    "this engine's pool holds %d — restore into a "
                    "pool at least as large as the checkpoint's "
                    "(pool_pages=%s)"
                    % (entry.get("rid"),
                       -(-span // self.prefill_chunk),
                       self._pool.num_pages, cfg.get("pool_pages")))
        # a full-at-crash journal can exceed the fresh queue's capacity
        # momentarily — the worker drains it, so re-admission is a
        # closed loop honoring Retry-After, never a partial restore
        # that strands already-admitted futures on an exception
        stop = time.monotonic() + 30.0
        for entry in entries:
            while True:
                try:
                    futures[entry["rid"]] = self.submit(
                        entry["prompt"], entry["n_new"])
                    break
                except Overloaded as e:
                    if time.monotonic() > stop:
                        raise RuntimeError(
                            "restore stalled: %d/%d journaled requests "
                            "re-admitted before the engine stopped "
                            "accepting" % (len(futures), len(entries)))
                    time.sleep(min(getattr(e, "retry_after", 0.05),
                                   0.05))
        self.metrics.inc("engine_restores")
        self.metrics.inc("requests_restored", len(futures))
        return futures

    def verify_pool_invariants(self):
        """Cross-check the page allocator against the engine's OWN
        references (ISSUE 10): every page's refcount must equal the
        lane references (one per lane holding it, each also pinned)
        plus the trie references (one per node storing it), and the
        pool's internal free-list/ref/pin bookkeeping must be
        self-consistent.  Raises RuntimeError naming the first
        violated page; returns a summary dict when sound.  Call
        quiesced (no worker mid-tick) — the chaos tests run it after
        traffic drains and after restore."""
        if self._wt is not None:
            self._wt.verify()
            for slot, lane in enumerate(self._lanes):
                if lane is None and self._wt.count[slot]:
                    raise RuntimeError(
                        "free slot %d holds %d window pages"
                        % (slot, self._wt.count[slot]))
        if self._state_shapes is not None:
            # a lane's state slot is its lane: held exactly while the
            # lane is, and a free slot's step position parks at 0 (the
            # chunk that starts there ignores what the slot holds)
            for slot, lane in enumerate(self._lanes):
                if (lane is None) != (slot in self._free):
                    raise RuntimeError(
                        "state slot %d is %s but its lane is %s"
                        % (slot, "free" if slot in self._free else "held",
                           "empty" if lane is None else "occupied"))
                if lane is None and self._pos[slot]:
                    raise RuntimeError(
                        "free state slot %d parks at position %d"
                        % (slot, self._pos[slot]))
        self._pool.verify()
        n = self._pool.num_pages
        want_refs = [0] * (n + 1)
        want_pins = [0] * (n + 1)
        for lane in self._lanes:
            if lane is None:
                continue
            for p in lane.pages:
                want_refs[p] += 1
                want_pins[p] += 1
        if self._trie is not None:
            stack = list(self._trie.root.children.values())
            while stack:
                node = stack.pop()
                stack.extend(node.children.values())
                want_refs[node.rows] += 1
        for p in range(1, n + 1):
            if self._pool.refs(p) != want_refs[p]:
                raise RuntimeError(
                    "page %d holds %d refs but lanes+trie account for "
                    "%d — leaked or double-released"
                    % (p, self._pool.refs(p), want_refs[p]))
            if self._pool._pins[p] != want_pins[p]:
                raise RuntimeError(
                    "page %d holds %d pins but active lanes account "
                    "for %d" % (p, self._pool._pins[p], want_pins[p]))
        return {"paged": True, "free_pages": self._pool.free_pages,
                "used_pages": self._pool.used_pages,
                "pinned_pages": self._pool.pinned_pages}

    # ------------------------------------------------------------------ worker
    def _admit(self):   # hot-path
        """Move queued prompts into free slots.  A lane only LOOKS UP
        the prefix cache and takes its hits here (:meth:`_admit_paged`)
        — compute chunks run one per tick, interleaved with decode (no
        head-of-line block) — and RESERVES its worst-case pages; when
        the pool cannot cover them the request goes BACK to the queue
        head (FIFO — retried next tick as lanes free pages, shed at its
        deadline) instead of wedging or being skipped."""
        if self._peek_swap() is not None:
            # a finish-on-old swap is quiescing: admitting now would
            # extend old-weights serving indefinitely — the queue
            # waits the (bounded) remaining lane ticks instead
            return
        self._pool_blocked = False
        while self._free:
            with self._cond:
                req = self._queue.popleft() if self._queue else None
                if req is not None:
                    self._queued_tokens -= req.true_len
                    self._queued_pages -= req.pages
                self.metrics.set_gauge("queue_depth", len(self._queue))
                self.metrics.set_gauge("queue_tokens",
                                       self._queued_tokens)
                self.metrics.set_gauge("queue_pages", self._queued_pages)
            if req is None:
                return
            if req.cancelled:            # raced _cancel's dequeue
                self._trace_queue_end(req, "cancelled")
                req.future.cancel()
                continue
            if time.monotonic() > req.deadline:
                self.metrics.record_shed()
                self._trace_queue_end(req, "shed")
                req.future.set_exception(DeadlineExceeded(
                    "prompt shed after %.3fs in queue" % (
                        time.monotonic() - req.t_enq)))
                continue
            slot = self._free.pop()
            if not self._admit_paged(slot, req):
                # pool pressure: back to the HEAD (order preserved;
                # deadline still sheds it) and stop admitting
                self._free.append(slot)
                self._pool_blocked = True
                with self._cond:
                    self._queue.appendleft(req)
                    self._queued_tokens += req.true_len
                    self._queued_pages += req.pages
                    self.metrics.set_gauge("queue_depth",
                                           len(self._queue))
                    self.metrics.set_gauge("queue_tokens",
                                           self._queued_tokens)
                    self.metrics.set_gauge("queue_pages",
                                           self._queued_pages)
                return

    def _admit_paged(self, slot, req):   # hot-path
        """Admission: match the prefix cache (full chunks only, never
        the chunk holding the last prompt token — the tail must run to
        produce the first token's logits), queue the rest as per-tick
        chunk work, and reserve the lane's WORST-CASE page span up
        front (no mid-decode allocation, so decode can never deadlock
        on pages), with prefix-cache hits substituting page REFERENCES
        (ref-count bump, no device work at all) for fresh pages.
        Returns False — nothing committed — when the pool cannot cover
        the reservation even after pressing the prefix cache."""
        C = self.prefill_chunk
        n_full = (req.true_len - 1) // C
        lane = _Slot(req)
        nodes = []
        if self._trie is not None:
            keys = [tuple(int(t) for t in req.prompt[i * C:(i + 1) * C])
                    for i in range(n_full)]
            nodes = self._trie.match(keys)
        if self._wt is not None and not self._wt.can_admit(req.pages):
            return False         # the sliding layers' pool is committed
        fresh = self._alloc_pages(req.pages - len(nodes))
        if fresh is None:
            if nodes:            # nothing committed — undo the pins
                self._trie.release(nodes)
            return False
        if self._wt is not None:
            self._wt.admit(slot, req.pages)
        lane.pinned.extend(nodes)
        lane.cursor = (nodes[-1] if nodes else
                       self._trie.root if self._trie is not None
                       else None)
        for node in nodes:
            self._pool.retain(node.rows)     # the lane's reference
            self._pool.pin(node.rows)
            lane.pages.append(node.rows)
        for p in fresh:
            self._pool.pin(p)
        lane.pages.extend(fresh)
        self._page_tables[slot, :len(lane.pages)] = lane.pages
        self._page_tables[slot, len(lane.pages):] = KVPagePool.SCRATCH
        if nodes:
            self.metrics.inc("prefix_hit_chunks", len(nodes))
            self.metrics.inc("prefix_hit_tokens", len(nodes) * C)
            self.metrics.inc("kv_pages_referenced", len(nodes))
            self.metrics.set_gauge("prefix_cache_chunks",
                                   self._trie.size)
        for i in range(len(nodes), n_full):
            lane.pending.append((req.prompt[i * C:(i + 1) * C], i * C,
                                 False))
        tail = req.prompt[n_full * C:]
        if len(tail) < C:
            tail = numpy.pad(tail, (0, C - len(tail)))
        lane.pending.append((tail, n_full * C, True))
        self.metrics.record_queue_wait(time.monotonic() - req.t_enq)
        self._trace_admitted(req, slot)
        if nodes and req.trace is not None:
            req.trace.tracer.instant(
                req.trace, "prefix.hit", cat="prefill",
                attrs={"chunks": len(nodes),
                       "tokens": len(nodes) * C, "paged": True})
        self._lanes[slot] = lane
        self._pos[slot] = lane.pending[0][1]
        self._update_pool_gauges()
        return True

    def _alloc_pages(self, n):
        """``n`` pages from the pool, pressing the prefix cache to drop
        LRU unpinned entries (each eviction releases its page) until
        the allocation fits or nothing more can be evicted.  Returns
        the page list or None; never blocks."""
        if n <= 0:
            return []
        pages = self._pool.alloc(n)
        if pages is None and self._trie is not None:
            # each eviction frees at most ONE page — when even a full
            # flush cannot cover the deficit, keep the cache warm (the
            # request is only ever placed by lanes finishing anyway)
            if self._pool.free_pages + self._trie.evictable() < n:
                return None
            while pages is None and self._trie.evict_one():
                self.metrics.set_gauge("prefix_cache_chunks",
                                       self._trie.size)
                pages = self._pool.alloc(n)
        return pages

    def _cow_guard(self, slot, lane, lo, hi):   # hot-path
        """COPY-ON-WRITE: before a device write covering linear
        positions [lo, hi), replace any SHARED page in that range with
        a private copy (one page-copy dispatch) so the other referents
        — sibling lanes, the prefix cache — keep their rows
        bit-identical.  Structurally rare (shared pages are full prompt
        chunks; appends land past the prompt), kept as the safety net
        that makes sharing unconditionally sound.  Raises on pool
        exhaustion — the caller fails THIS lane, never wedges.

        ``hi`` is clamped to the lane's reservation: a megastep quotes
        its WORST-CASE span (K iterations all advancing), but a lane's
        real writes never pass its reserved pages (the program freezes
        an exhausted lane and masks its writes to scratch), so pages
        past the reservation need no copy — and indexing them would be
        out of range."""
        P = self.prefill_chunk
        hi = min(hi, len(lane.pages) * P)
        if hi <= lo:
            return
        for j in range(lo // P, (hi - 1) // P + 1):
            p = lane.pages[j]
            if not self._pool.shared(p):
                continue
            fresh = self._alloc_pages(1)
            if fresh is None:
                raise Overloaded()
            q = fresh[0]
            t0c = time.monotonic()
            try:
                self._fault("engine.cow")
                with self._donating():
                    self._kv_pools = self._page_copy_jit(
                        self._kv_pools, xfer.to_device(p, numpy.int32),
                        xfer.to_device(q, numpy.int32))
                    self._tfence(self._kv_pools,
                                 lane.request.trace is not None)
            except Exception:
                # nobody owns q yet (not in lane.pages) — hand it back
                # or a faulting device shrinks the pool for good
                self._pool.release(q)
                raise
            if lane.request.trace is not None:
                lane.request.trace.tracer.add(
                    lane.request.trace, "cow.copy", "kv", t0c,
                    time.monotonic(),
                    attrs={"page": p, "bucket": self.prefill_chunk,
                           "backend": self._backend})
            self._pool.pin(q)
            self._pool.unpin(p)
            self._pool.release(p)
            lane.pages[j] = q
            self._page_tables[slot, j] = q
            self.metrics.inc("kv_cow_copies")
            self._update_pool_gauges()

    def _cow_guard_active(self, active, span):
        """:meth:`_cow_guard` over every active lane's next
        ``span``-position write, BEFORE the batched dispatch: a lane
        whose copy cannot be made (pool exhausted on the safety-net
        path) is torn down ALONE — its siblings keep decoding, per the
        engine's fault-isolation discipline.  Returns the surviving
        active list (a torn-down lane's table row parks on scratch, so
        the batched step stays safe to run)."""
        alive = []
        for slot in active:
            lane = self._lanes[slot]
            if lane is None:     # failed with the storage (_donating)
                continue
            try:
                self._cow_guard(slot, lane, int(self._pos[slot]),
                                int(self._pos[slot]) + span)
            except Exception as e:   # noqa: BLE001 — fails THIS lane
                self.metrics.record_error()
                self.warning("copy-on-write failed: %s", e)
                self._teardown_slot(slot, lane, e)
                continue
            alive.append(slot)
        return alive

    def _update_pool_gauges(self):
        self.metrics.set_gauge("kv_pages_free", self._pool.free_pages)
        self.metrics.set_gauge("kv_pages_pinned",
                               self._pool.pinned_pages)
        if self._wt is not None:
            self.metrics.set_gauge("kv_pages_free.full",
                                   self._pool.free_pages)
            self.metrics.set_gauge("kv_pages_free.window",
                                   self._wt.pool.free_pages)
        if self._state_shapes is not None:
            self.metrics.set_gauge(
                "state_slots_free",
                sum(lane is None for lane in self._lanes))

    def _slide_window(self, slot, lo, hi):   # hot-path
        """The sliding layers' table of ``slot`` before it writes
        positions [lo, hi): pages behind the window released, the
        frontier's page taken (``kv_pool.WindowTables.advance``)."""
        gone = self._wt.advance(slot, lo, hi)
        if gone:
            self.metrics.inc("kv_pages_released_window", gone)

    def _table_args(self, full, rows):   # hot-path
        """The programs' table argument after the table ``full`` of the
        lanes ``rows`` (a slot, or a slice of all), already cut to its
        width: that table on the device; for two kinds of cache the pair
        (a table per kind, where the sliding kind's begins in tokens per
        lane), the sliding kind's rows cut to the same width or to its
        own, whichever is less.  Every argument is put from a COPY: a put
        may read host memory after it returns, and the loop changes the
        tables and ``_pos`` in place while the step it dispatched runs
        (ISSUE 37)."""
        wt = self._wt
        full = full.copy()
        if self._state_shapes is not None:
            # the table, and whose state the program touches: the chunk
            # program's one lane's slot, or the lanes that decode in this
            # step (``_step_plain`` marks them; none while warming up)
            return xfer.to_device(full), (
                xfer.to_device(self._decoding.copy())
                if isinstance(rows, slice)
                else xfer.to_device(rows, numpy.int32))
        if wt is None:
            return xfer.to_device(full)
        width = min(full.shape[-1], wt.width)
        tables = {model_config.FULL: xfer.to_device(full),
                  model_config.SLIDING: xfer.to_device(
                      wt.tables[rows, :width].copy())}
        return tables, xfer.to_device(wt.base[rows] * wt.page,
                                      numpy.int32)

    def _live_width(self, span):
        """Ladder-bucketed page-table width for a decode/verify step
        writing ``span`` positions per lane: the smallest power-of-two
        (capped at max_pages) covering EVERY slot's frontier —
        ``_pos`` includes prefilling lanes' parked frontiers and the
        inactive lanes' 0 (and ``_unseen`` what the steps in flight may
        have added to a lane that drafts, ISSUE 40), so the batched step's
        garbage writes always land inside the sliced table
        (take_along_axis would otherwise CLAMP an out-of-range page lookup onto a live page)."""
        need = -(-(int((self._pos + self._unseen).max()) + span)
                 // self.prefill_chunk)
        for w in self._width_ladder:
            if w >= need:
                return w
        return self._max_pages

    def _note_moe(self, counts):   # hot-path
        """The expert layers' counts of one decode step, fetched with its
        tokens (``ops/moe.py::held_part``): the counters of
        ``/metrics.json`` and the open turn of the loop recorder."""
        held, away, hit, load = (int(c) for c in counts)
        self.metrics.inc("moe_assignments_held", held)
        self.metrics.inc("moe_assignments_elsewhere", away)
        self.metrics.inc("moe_experts_hit", hit)
        self.metrics.set_gauge_max("moe_max_expert_load", load)
        self.recorder.moe(held, hit)

    def _attn_page_steps(self, pos, width, span=0, rows=slice(None)):
        """``(given, live, blocks)``: the page steps a dispatch hands the
        attention kernels, the live ones among them (ISSUE 29) and the
        blocks the flash-decode kernel walks for them (ISSUE 43), or None
        where the kernels are not active: ``pos`` the positions of the
        lanes ``rows`` as the program gets them, ``width`` its table's,
        ``span`` the query rows a lane (0: a prefill chunk, whose kernel
        walks the history below ``pos``; the latent kind's also walks the
        chunk's own page, written before it: its query rows count like a
        decode's).  A decode or verify step is handed only what it walks
        (``paged_latent_decode`` and ``paged_flash_decode`` loop over a
        lane's own live pages, whatever the table's width, ISSUES 41, 43):
        ``given`` is ``live`` there, and what the two leave apart is the
        gridded prefill kernels' chunk dispatches.  ``blocks`` counts the
        flash-decode kernel's alone, by its own rule for the pages a block
        (``flash_block_pages`` of this pool at this width): live pages
        over blocks x that many is how full its blocks run.  Read where the
        dispatch's tables are made (the sliding kind's short table begins
        at its base THEN).  Host integers over at most ``slots`` lanes, by
        the kernels' own ``live_pages``."""
        if not self._kernel_active:
            return None
        from veles_tpu.ops import pallas_kernels as PK
        pos = numpy.atleast_1d(pos).astype(numpy.int64)
        latent = self.cfg.latent is not None
        walked = span > 0
        if latent and not span:
            span = self.prefill_chunk
        given = live = blocks = 0
        for kind, layers in self._layers_of_kind:
            p, w = pos, width
            if self._wt is not None and kind == model_config.SLIDING:
                # the sliding kind's short table begins at its base
                p = pos - self._wt.base[rows] * self._wt.page
                w = min(width, self._wt.width)
            window = (self.cfg.window if self._wt is None
                      or kind == model_config.SLIDING else None)
            seen = PK.live_page_count(*PK.live_pages(
                p, span, self.prefill_chunk, w, window, self.sinks,
                xp=numpy))
            total = int(seen.sum())
            live += layers * total
            given += layers * (total if walked else p.size * w)
            if walked and not latent:
                blocks += layers * int(PK.flash_walk_blocks(
                    seen, PK.flash_block_pages(
                        self._storage_shape, self._storage_dtype.itemsize,
                        w)).sum())
        return given, live, blocks

    def _note_attn_dispatch(self, steps=None, calls=1):
        """Per-dispatch kernel accounting (ISSUE 7): which path the
        engine's attention actually took.  Only metered when the caller
        ASKED for kernels — an untouched engine carries no new
        counters.

        A dispatch through the kernels also counts its page steps
        (``steps``, :meth:`_attn_page_steps`): the counters
        ``attn_page_steps`` / ``attn_page_steps_live`` and the recorder's
        open turn, and ``attn_walk_blocks`` where the flash-decode kernel
        walked; ``calls`` the steps of a fused program (counted at the
        positions it entered with)."""
        if not self.attn_kernel:
            return
        self.metrics.inc("attn_kernel_dispatches" if self._kernel_active
                         else "attn_kernel_fallbacks")
        if steps is None:
            return
        given, live, blocks = steps
        self.metrics.inc("attn_page_steps", calls * given)
        self.metrics.inc("attn_page_steps_live", calls * live)
        if blocks:
            self.metrics.inc("attn_walk_blocks", calls * blocks)
        self.recorder.attn_pages(calls * given, calls * live)

    def kv_bytes_resident(self):
        """Device bytes held for KV storage (the pools, and the state
        slots where layers keep one); what the bench reports as
        footprint."""
        return sum(a.size * a.dtype.itemsize
                   for pair in self._kv_pools for a in pair)

    def _pick_prefill(self, prefilling):   # hot-path
        """The lane whose prompt chunk goes next, as ``(slot, lane)``: at
        most ONE chunk a turn, round-robin across the prefilling lanes.
        None where that lane was withdrawn (``generate()``'s sibling
        cancellation) mid-prefill: its slot is freed now instead of
        finishing the prompt for a result nobody will read."""
        self._rr += 1
        slot = prefilling[self._rr % len(prefilling)]
        lane = self._lanes[slot]
        if lane.request.cancelled:
            self._teardown_slot(slot, lane)
            return None
        return slot, lane

    def _prepare_chunk_paged(self, slot, lane, req):   # hot-path
        """The lane's next pending prompt chunk (ONE a turn — decode
        lanes step in between, so a long prompt never head-of-line-blocks
        them) with everything but its jit call done, as a
        :class:`_Chunk`, or None where nothing is left to dispatch: a
        LATE HIT (a sibling lane prefilling the same prompt has inserted
        this very chunk since admission, so concurrent shared-prefix
        arrivals converge on ONE prefill) swaps the lane's reserved page
        for a REFERENCE to the sibling's page (release one, retain the
        other — zero device work), and a guard that fails tears the lane down.
        The lane's step position moves to where the chunk will leave it
        (the next chunk's start; ``true_len`` behind a tail chunk), so a
        decode step prepared before the chunk goes out (ISSUE 37) parks
        the lane, or takes it in, as it would have after."""
        C = self.prefill_chunk
        tokens, start, is_tail = lane.pending.pop(0)
        page_idx = start // C
        if not is_tail and self._trie is not None \
                and lane.cursor is not None:
            node = self._trie.lookup_child(
                lane.cursor, tuple(int(t) for t in tokens))
            if node is not None:
                # late hit: drop the page reserved for this chunk and
                # reference the already-computed one instead
                own = lane.pages[page_idx]
                self._pool.unpin(own)
                self._pool.release(own)
                self._pool.retain(node.rows)
                self._pool.pin(node.rows)
                lane.pages[page_idx] = node.rows
                self._page_tables[slot, page_idx] = node.rows
                lane.pinned.append(node)
                lane.cursor = node
                self.metrics.inc("prefix_hit_chunks")
                self.metrics.inc("prefix_hit_tokens", len(tokens))
                self.metrics.inc("kv_pages_referenced")
                if req.trace is not None:
                    req.trace.tracer.instant(
                        req.trace, "prefix.hit", cat="prefill",
                        attrs={"late": True, "start": start,
                               "paged": True})
                self._update_pool_gauges()
                self._pos[slot] = lane.pending[0][1]
                return None
        # (the chunk's last real row: a whole chunk's own last, whose
        # token nobody reads)
        last_idx = (req.true_len - 1 - start) if is_tail else C - 1
        fed = tokens
        if self._mtp:
            # the module's row i takes token i + 1: the chunk's tokens and
            # the one behind them (a tail chunk's is made in the graph)
            fed = numpy.zeros(C + 1, numpy.int32)
            upto = min(start + C + 1, req.true_len)
            fed[:upto - start] = req.prompt[start:upto]
        try:
            self._cow_guard(slot, lane, start, start + C)
            if self._wt is not None:
                self._slide_window(slot, start, start + C)
            args = (self._table_args(self._page_tables[slot], slot),
                    xfer.to_device(fed, numpy.int32),
                    xfer.to_device(start, numpy.int32),
                    xfer.to_device(last_idx, numpy.int32),
                    # (where the program writes its token among the
                    # lanes' last ones: a tail chunk's own slot)
                    xfer.to_device(slot if is_tail else -1, numpy.int32))
            steps = self._attn_page_steps(start, self._max_pages, rows=slot)
        except Exception as e:   # noqa: BLE001 — fails THIS request
            self.metrics.record_error()
            self.warning("paged chunk prefill failed: %s", e)
            self._teardown_slot(slot, lane, e)
            return None
        self._pos[slot] = req.true_len if is_tail else lane.pending[0][1]
        return _Chunk(slot, lane, tokens, start, is_tail, args, steps)

    def _dispatch_chunk_paged(self, chunk):   # hot-path
        """The jit call of a prepared chunk and what follows it: a
        computed full chunk SHARES the lane's own page with the trie
        (retain — the insert itself copies nothing); a tail chunk's lane
        becomes a decode lane.  Its first token the program writes among
        the lanes' last tokens on the device, where the next step reads
        it; the plain driver fetches it one dispatch late with that
        step's (:meth:`_sent_first`, ISSUE 39), the drivers that need it
        on the host at once wait for it here."""
        slot, lane, tokens, start, is_tail, args, steps = chunk
        req = lane.request
        if req.cancelled:
            # withdrawn since its arguments were made
            self._teardown_slot(slot, lane)
            return
        page_idx = start // self.prefill_chunk
        t0 = time.monotonic()
        try:
            self._fault("engine.chunk")
            rec = self.recorder
            sent = rec.dispatch(tracing.PREFILL_DISPATCH, self._chunk_jit)
            with self._donating():
                self._kv_pools, last = self._chunk_jit(
                    self.params, self._kv_pools, *args, self._last_dev)
                rec.returned(sent)
                if self._late_fetch:
                    self._last_dev = last
                    if self._mtp:
                        last = last[0]       # (last, draft, position)
                    if is_tail:
                        xfer.start_to_host(last)
                self._tfence(self._kv_pools, req.trace is not None)
                # the device has the chunk to run: the tokens the last
                # fetch brought reach their lanes now
                self._deliver()
                if is_tail and not self._late_fetch:
                    rec.waiting(sent)
                    tok = xfer.to_host(last).tolist()[slot]
                    rec.fetched(sent)
            if not is_tail and self._trie is not None \
                    and lane.cursor is not None:
                page = lane.pages[page_idx]
                node = self._trie.insert(
                    lane.cursor, tuple(int(t) for t in tokens), page)
                if node is not None:
                    lane.pinned.append(node)
                    if node.rows == page:
                        # fresh entry: the trie now references the
                        # lane's own page (released on trie eviction)
                        self._pool.retain(page)
                lane.cursor = node
                self.metrics.set_gauge("prefix_cache_chunks",
                                       self._trie.size)
                self._update_pool_gauges()
        except Exception as e:   # noqa: BLE001 — fails THIS request
            self.metrics.record_error()
            self.warning("paged chunk prefill failed: %s", e)
            if req.trace is not None:
                req.trace.tracer.add(
                    req.trace, "prefill.chunk", "prefill", t0,
                    time.monotonic(),
                    attrs={"start": start, "paged": True,
                           "error": str(e)})
            self._teardown_slot(slot, lane, e)
            return
        self.metrics.inc("prefill_dispatches")
        if self._state_shapes is not None and start == 0:
            self.metrics.inc("state_resets")
        self._note_attn_dispatch(steps)
        self.metrics.inc("prefill_tokens",
                         (req.true_len - start) if is_tail
                         else len(tokens))
        # enqueue time by design (with the wait for a tail chunk's token
        # where a driver waits for it here); device wall rides traced
        # spans (_tfence)
        self.metrics.record_decode_step(time.monotonic() - t0)
        if req.trace is not None:
            req.trace.tracer.add(
                req.trace, "prefill.chunk", "prefill", t0,
                time.monotonic(),
                attrs={"start": start, "tail": is_tail,
                       "bucket": self.prefill_chunk, "paged": True,
                       "backend": self._backend})
        if not is_tail:
            return
        if self._late_fetch:
            self._sent_first(slot, lane, sent, last)
        else:
            self._emit_first(slot, lane, tok)

    def _count_tokens(self, req, n=1):
        """THE emit site: ``n`` tokens of ``req`` reached the host.  The
        ``tokens_out`` counter and the recorder's per-token stamps move
        here and nowhere else, so the two agree by construction."""
        self.metrics.inc("tokens_out", n)
        self.recorder.emitted(req, n)

    def _emit_first(self, slot, lane, tok):
        """First generated token (prefill just finished): the lane
        becomes a decode lane (or finishes outright at n_new=1)."""
        req = lane.request
        lane.emitted.append(tok)
        lane.remaining -= 1
        self._count_tokens(req)
        self.metrics.record_ttft(time.monotonic() - req.t_enq)
        self._pos[slot] = req.true_len
        self._last[slot] = tok
        self._lanes[slot] = lane
        if lane.remaining == 0 or req.cancelled:
            self._finish(slot)

    def _sent_first(self, slot, lane, sent, last):
        """:meth:`_emit_first` by count, for a tail chunk whose token
        stays on the device (ISSUE 39; ``sent`` the chunk's dispatch
        record, ``last`` its output): the lane owes one token fewer and
        decodes from here on (its position is the prompt's length since
        the chunk was prepared); where that token is its last the lane is
        freed now, as :meth:`_advance_by_count` frees one.  The token
        itself, its stamp and the time to it wait for the fetch."""
        lane.remaining -= 1
        self._flights.append(_Flight(
            sent, (last,), [(slot, lane)], (lane.remaining == 0,), True))
        if lane.remaining == 0:
            self._vacate_slot(slot, lane)

    def _release_lane(self, lane):
        if self._trie is not None and lane.pinned:
            self._trie.release(lane.pinned)
            lane.pinned = []
        if lane.pages:
            # ref-count release on lane finish: owned pages return to
            # the free list; shared (trie/sibling-referenced) pages
            # just lose this lane's reference and survive
            for p in lane.pages:
                self._pool.unpin(p)
                self._pool.release(p)
            lane.pages = []
            self._update_pool_gauges()

    def _vacate_slot(self, slot, lane):
        """Release a lane's trie pins/pages and free its slot WITHOUT
        touching the request future — finish, teardown and the swap
        requeue all funnel here so none can forget a step.  The step
        position parks at 0 (a free slot's garbage writes land where
        the next admission overwrites them)."""
        if self._wt is not None:
            self._wt.vacate(slot)
        self._release_lane(lane)
        self._lanes[slot] = None
        self._lanes_gen += 1
        if slot not in self._free:
            self._free.append(slot)
        self._pos[slot] = 0
        self._last[slot] = 0
        self._unseen[slot] = 0
        self._page_tables[slot, :] = KVPagePool.SCRATCH
        if self._state_shapes is not None:
            self._update_pool_gauges()

    def _teardown_slot(self, slot, lane, exc=None):
        """THE failure/cancellation teardown: vacate the slot and fail
        — or, when ``exc`` is None, cancel — the request's future."""
        self._vacate_slot(slot, lane)
        fut = lane.request.future
        if exc is None:
            fut.cancel()
        elif not fut.done():     # cancelled, or failed by _storage_lost
            fut.set_exception(exc)

    def _finish(self, slot):
        lane = self._lanes[slot]
        self._vacate_slot(slot, lane)
        self._reply(lane)

    def _reply(self, lane):
        """The request's result, once its lane has left the slot: at once
        (:meth:`_finish`), or — a lane the plain step freed by count when
        its last step went out — when that step's token is delivered.  A
        weight swap applies only after every reply owed is made
        (``_serve_loop``), so the stamp is the tokens' own generation."""
        fut = lane.request.future
        if not fut.cancelled():          # withdrawn mid-decode
            # stamped with the generation that produced these tokens —
            # the mixed-fleet attribution a rolling deploy needs
            fut.version = self.weights_version
            fut.drafts = lane.drafts
            fut.set_result(numpy.asarray(lane.emitted, numpy.int32))

    @contextlib.contextmanager
    def _donating(self):   # hot-path
        """THE failure rule of a donating dispatch — every call of a
        program that takes the KV storage donated, and the fetch of its
        outputs, runs inside this context, under the site's own
        ``except``.  A program that raises before the runtime has taken
        its arguments (an injected ``_fault`` fires before the call;
        trace and compile errors) leaves the storage as it was, and the
        site's handler does what it always did: it fails its own
        request or lanes, and the survivors' rows are intact.  One that
        raises AFTER — in the call once the buffers are consumed, or in
        the fetch when the program failed on the device — leaves none:
        the arrays that went in are deleted, and what came out, if
        anything, cannot be trusted.  So on any exception this looks at
        the storage that was passed in (``is_deleted()`` on one leaf);
        consumed, :meth:`_storage_lost` fails every request that held
        rows in it and installs fresh storage BEFORE the exception
        reaches the handler, which then finds its own work already
        failed (``_teardown_slot`` tolerates that).
        ``_kv_pools`` never points at deleted or poisoned buffers when
        the loop takes its next turn."""
        leaf = self._kv_pools[0][0]
        try:
            yield
        except Exception as e:
            if leaf.is_deleted():
                self._storage_lost(e)
            raise

    def _storage_lost(self, exc):
        """The KV storage went down with a failed dispatch
        (:meth:`_donating`): every request that holds pages or a slot
        of state — decoding lanes, prefilling lanes — fails with ``exc``;
        the prefix trie is dropped (its rows are gone); the page
        allocator comes home whole through those
        releases and every table row parks on scratch; fresh zero
        storage takes the place of the lost one; ``kv_storage_rebuilds``
        counts it.  Queued requests are untouched: they hold nothing
        yet, and are served from the fresh storage.  The dispatches whose
        tokens are still on the device (ISSUE 39) go too: the oldest of
        them failed or a younger one read what it wrote, so the lanes
        they had freed by count fail with the rest, and ``last`` on the
        device starts from zeros again."""
        self._deliver()
        flights, self._flights = self._flights, collections.deque()
        self._older = 0
        held = [i for i, lane in enumerate(self._lanes)
                if lane is not None]
        self.warning(
            "KV storage consumed by a failed dispatch (%s): failing %d "
            "lane(s), rebuilding the storage", exc, len(held))
        for slot in held:
            self._teardown_slot(slot, self._lanes[slot], exc)
        if self._trie is not None:
            self._trie.clear()
            self.metrics.set_gauge("prefix_cache_chunks", 0)
        self._page_tables[:] = KVPagePool.SCRATCH
        self._update_pool_gauges()
        for flight in flights:
            for _, lane in flight.pairs:
                if not lane.request.future.done():
                    lane.request.future.set_exception(exc)
        # a declared boundary: making the zeros is no hot-path transfer
        with xfer.boundary():
            self._kv_pools = self._zero_storage()
            if self._last_dev is not None:
                self._last_dev = self._zero_last()
        self.metrics.inc("kv_storage_rebuilds")

    def _fail_active(self, active, exc):
        """A step/verify fault fails every in-flight decode lane of the
        dispatch to its client and keeps serving — never wedge with
        futures that no one will ever resolve.  The lanes' rows are
        simply abandoned: the storage itself is intact here (the fault
        fired before the program took it), or :meth:`_donating` has
        already replaced it and failed these lanes with the rest, in
        which case there is nothing left to do for them.  The tokens
        that reached the host before the fault are the lanes' own: they
        are delivered first (a lane they complete has its result)."""
        self._deliver()
        self.metrics.record_error()
        self.warning("decode step failed: %s", exc)
        for slot in active:
            if self._lanes[slot] is not None:
                self._teardown_slot(slot, self._lanes[slot], exc)

    def _dispatch_decode(self, decode_jit, args, lanes, tctxs, under=None, pairs=None):   # hot-path
        """THE decode dispatch all three drivers share (tick, verify and
        megastep): ``decode_jit`` over the parameters, the KV
        storage — DONATED: the program updates it in place and the tree
        passed in is dead when the call returns — and ``args``, already
        on the device (the puts belong to ``step.prepare``); then the
        storage rebound to the first output, ``under()`` if the driver
        gave one — the part of its turn that needs no token, run while
        the device runs the program (the ``ahead.*`` phases, ISSUE 37) —
        and the other outputs fetched to the host.  Call, ``under`` and
        fetch run under :meth:`_donating`, the rule for a dispatch that
        raises once its storage is consumed; the drivers' own ``except``
        follows it.  The recorder's ``step.dispatch`` spans the jit call
        until it returns, ``step.fetch`` the wait for the device and the
        copy out (an armed tracer's fence too, when a sampled lane rides
        the dispatch), and ``step.emit`` opens as this returns; the
        dispatch's own record (ISSUE 38) takes the same stamps, and the
        jit call's return, under the handle ``sent``.

        With ``pairs`` (the plain driver: the lanes the step
        advances) the fetch is ONE DISPATCH LATE (ISSUE 39): the outputs
        stay on the device as a :class:`_Flight`, the tokens as the next
        dispatch's ``last`` argument, and what the host waits for behind
        ``under()`` are the dispatches of the turn BEFORE, whose handles
        take the stamps (``DCOL_FETCH_TURN`` says the turn).  The device
        has this step queued while the host fetches, delivers, admits and
        prepares.  Where ``under()`` prepared no step to follow, this
        one's tokens are fetched too, the old order (a drain).  Nothing
        is returned: :meth:`_fetch_flights` hands over what it brings."""
        rec = self.recorder
        sent = rec.dispatch(tracing.STEP_DISPATCH, decode_jit, lanes)
        with self._donating():
            out = decode_jit(self.params, self._kv_pools, *args)
            rec.returned(sent)
            self._kv_pools = out[0]
            if pairs is not None:
                if any(not flight.first for flight in self._flights):
                    # (the step before is still unfetched)
                    self.metrics.inc("dispatches_sent_ahead")
                self._last_dev = out[1]
                # (what the host fetches: the tokens, which are the state
                # itself but where the module drafts: there the state is
                # (last, draft, position) and the tokens and their count
                # come behind it)
                outs = out[2:] if self._mtp else out[1:]
                xfer.start_to_host(outs)
                self._flights.append(_Flight(sent, outs, pairs))
                under()
                n = self._older
                if self._ahead is None or self._ahead.step is None:
                    self.metrics.inc("pipeline_drains")
                    n = len(self._flights)
                self._fetch_flights(n, True)
                self._tfence(self._kv_pools,
                             any(c is not None for c in tctxs))
                return None
            rec.waiting(sent, tracing.STEP_FETCH)
            host = xfer.to_host(tuple(out[1:]))
            # (the storage as it is NOW: a copy-on-write made under the
            # step has donated ``out[0]`` on)
            self._tfence(self._kv_pools,
                         any(c is not None for c in tctxs))
        rec.fetched(sent, tracing.STEP_EMIT)
        return host

    def _fetch_flights(self, n, in_step=False):   # hot-path
        """Wait for the ``n`` oldest dispatches whose tokens are still on
        the device (ISSUE 39) and hand over what they bring: a step's
        expert counts to :meth:`_note_moe`, every token to
        ``_undelivered`` with what the count said of it when its dispatch
        went out, and to the host's ``_last``.  Each wait is stamped on
        its own dispatch record; ``in_step`` (the late fetch behind a
        step's ``under()``) makes the waits the turn's ``step.fetch``,
        empty where nothing is owed.  A fetch that raises leaves its
        flight and the younger ones where they are, for
        :meth:`_storage_lost`: they read what the failed one wrote."""
        rec = self.recorder
        if in_step and not n:
            rec.mark(tracing.STEP_FETCH)
            rec.mark(tracing.STEP_EMIT)
        for i in range(n):
            flight = self._flights[0]
            rec.waiting(flight.sent,
                        tracing.STEP_FETCH if in_step and not i else None)
            toks, *counts = xfer.to_host(flight.outs)
            drafted = self._mtp and not flight.first
            rec.fetched(flight.sent,
                        tracing.STEP_EMIT if in_step and i == n - 1
                        else None,
                        int(counts[0].sum()) if drafted
                        else len(flight.pairs))
            self._flights.popleft()
            if drafted:
                self._note_moe(counts[1])
                self._settle_counts(flight.pairs, toks.tolist(),
                                    counts[0].tolist())
                continue
            if counts:
                self._note_moe(counts[0])
            toks = toks.tolist()
            for (slot, lane), last in zip(flight.pairs, flight.lasts):
                if self._lanes[slot] is lane:
                    self._last[slot] = toks[slot]
                self._undelivered.append((slot, lane, toks[slot], last,
                                          flight.first))
        self._older = max(0, self._older - n)

    def _settle_counts(self, pairs, toks, counts):   # hot-path
        """One fetched step of a model that drafts with its own module
        (ISSUE 40): what the host could not know when the step went out is
        settled here.  ``counts[slot]`` of the two tokens ``toks[slot][:2]`` are
        real (the third is the draft made behind them): the lane's confirmed position moves by that, the request is
        owed that many fewer, and what it is not owed is dropped and
        counted (``spec_tokens_discarded``: a second token past ``n_new``,
        and every token of a lane that had all its tokens already and rode
        the steps in flight behind its last).  The kept ones go to
        ``_undelivered``; the last of a request is marked so."""
        made = kept = drafts = accepted = 0
        for slot, lane in pairs:
            n = counts[slot]
            made += n
            lane.inflight -= 1
            if self._lanes[slot] is lane:
                self._pos[slot] += n
                self._unseen[slot] -= self.spec_k + 1
            if not lane.remaining:
                continue                 # it rode behind its last token
            drafts += 1
            accepted += n - 1
            take = min(n, lane.remaining)
            kept += take
            lane.remaining -= take
            for j in range(take):
                self._undelivered.append((
                    slot, lane, toks[slot][j],
                    j == take - 1 and not lane.remaining, False))
            if lane.remaining:
                lane.drafts.append((lane.request.n_new - lane.remaining,
                                    toks[slot][2]))
        self.metrics.inc("draft_tokens", drafts)
        self.metrics.inc("draft_accepted", accepted)
        self.metrics.inc("spec_tokens_kept", kept)
        self.metrics.inc("spec_tokens_discarded", made - kept)

    def _drain(self):
        """The outstanding fetches made first (ISSUE 39), wherever the
        old order is taken: no turn was prepared, no lane decodes, a
        weight swap waits, a fault fails lanes, the loop ends.  Counted
        in ``pipeline_drains`` where a step was among them.  A fetch that
        raises here has no dispatch around it to say so: the program
        failed on the device, so the storage it returned goes
        (:meth:`_storage_lost`)."""
        if not self._flights:
            return
        steps = sum(not flight.first for flight in self._flights)
        if steps:
            self.metrics.inc("pipeline_drains", steps)
        try:
            self._fetch_flights(len(self._flights))
        except Exception as e:   # noqa: BLE001 — fails the lanes
            self.metrics.record_error()
            self.warning("fetch of a dispatch in flight failed: %s", e)
            self._storage_lost(e)

    def _prepare_step(self, active):   # hot-path
        """Everything a plain decode step over the lanes ``active`` needs
        but the tokens of the step before (ISSUE 37), as a
        :class:`_Step`: the copy-on-write guard and the sliding layers'
        tables at the positions the step will write, the live table
        width, and the table, ``_decoding`` and position arguments put on
        the device from copies.  It reads the lanes' positions as they
        are, so it serves a turn of the old order and, once the step in
        flight is counted in (:meth:`_advance_by_count`), the turn after
        alike.  None where no lane is left to step: a guard that fails
        tears its lane down, a slide that fails fails them all."""
        # (rows a lane writes, and how far a lane that drafts may be
        # ahead of what the host has seen of it: ISSUE 40)
        rows = self.spec_k + 1 if self._mtp else 1
        active = self._cow_guard_active(active, self.headroom + rows)
        if not active:
            return None
        try:
            if self._wt is not None:
                due = self._wt.due(self._pos)
                for slot in active:
                    if due[slot]:
                        p = int(self._pos[slot])
                        self._slide_window(slot, p, p + 1)
            w = self._live_width(rows)
            if self._state_shapes is not None:
                self._decoding[:] = False
                self._decoding[active] = True
            tables = (self._table_args(self._page_tables[:, :w],
                                       slice(None)),)
            # the lanes that decode, for the program to know whose
            # token on the device is one (ISSUE 39): the linear
            # kind's table argument carries the same mask
            if self._state_shapes is not None:
                live = tables[0][1]
            else:
                live = numpy.zeros(self.slots, bool)
                live[active] = True
                live = xfer.to_device(live)
            pos = self._pos + self._unseen
            return _Step([(slot, self._lanes[slot]) for slot in active],
                         w, tables,
                         # (the positions of lanes that draft stay on the
                         # device; the page steps are counted at the
                         # farthest they may be)
                         None if self._mtp else xfer.to_device(pos), live,
                         self._attn_page_steps(pos, w, rows))
        except Exception as e:   # noqa: BLE001 — fails the lanes
            self._fail_active(active, e)
            return None

    def _step_plain(self, active, step=None):   # hot-path
        """ONE dispatch advances every active lane by one token;
        inactive lanes step too (their writes land at a frozen position
        that the next prefill/chunk overwrites before attending — see
        the module docstring), so the step program never respecializes
        on the active set.

        ``step`` holds the arguments made under the step before
        (:meth:`_under_step`; None: they are made here, the old order,
        behind the outstanding fetches).  The step takes ``last`` from
        the device as the dispatch before left it; the rest of the turn
        is done WHILE the device runs the step (:meth:`_under_step`, the
        ``ahead.*`` phases), and then the host waits for the tokens of
        the step BEFORE this one (:meth:`_dispatch_decode`, ISSUE 39);
        they wait for the next stretch (:meth:`_deliver`)."""
        made_ahead = step is not None
        if step is None:
            self._drain()
            step = self._prepare_step(active)
            if step is None:
                return
        pairs = step.pairs
        tctxs = ()
        if self._tracer is not None:
            # only the SAMPLED lanes carry a context — an all-None
            # batch records nothing and (sample:P) skips the fence
            tctxs = [lane.request.trace for _, lane in pairs]
        # (a step that verifies a draft keeps the verify span's name)
        span = "decode.verify" if self._mtp else "decode.step"
        went = []

        def under():
            went.append(True)
            self._under_step(step, made_ahead)
        t0 = time.monotonic()
        try:
            self._fault("engine.step")
            self._dispatch_decode(
                self._step_jit, step.tables + (
                    (self._last_dev, step.live_dev) if self._mtp else
                    (self._last_dev, step.pos_dev, step.live_dev)),
                len(pairs), tctxs, under, pairs)
        except Exception as e:   # noqa: BLE001 — fails the lanes
            if self._tracer is not None:
                self._tracer.add_many(
                    tctxs, span, "decode", t0, time.monotonic(),
                    attrs={"batch": len(pairs), "error": str(e)})
            self._fail_step(step, e, made_ahead and not went)
            return
        self.metrics.record_decode_step(time.monotonic() - t0)
        if self._tracer is not None:
            attrs = {"batch": len(pairs), "bucket": step.width,
                     "backend": self._backend}
            if self._mtp:
                attrs["k"] = self.spec_k
            self._tracer.add_many(tctxs, span, "decode", t0,
                                  time.monotonic(), attrs=attrs)

    def _note_step(self, step, made_ahead):
        """The counters of one plain decode dispatch."""
        self.metrics.record_dispatch(len(step.pairs))
        self.metrics.inc("decode_dispatches")
        if made_ahead:
            self.metrics.inc("turns_prepared_ahead")
        if self._mtp:
            self.metrics.inc("spec_dispatches")
            self.metrics.inc("spec_lane_steps", len(step.pairs))
        self._note_attn_dispatch(step.steps)

    def _advance_by_count(self, pairs):   # hot-path
        """What a plain decode step does to its lanes is known without its
        tokens (the engine has no stop token: a lane ends by count): each
        moves one position on and owes one token fewer.  A lane that owes
        none now is FREED here — pages, tables, slot — so the next
        admission finds it; its request is answered when the step's token
        is delivered (:meth:`_deliver`).  Returns, per lane, whether this
        step's token is its request's last.  The writes of the steps in
        flight (two at most, ISSUE 39: the one counted here and the one
        before it, unfetched) land in pages the host has released: the
        device runs dispatches in order, and whoever takes such a page
        writes a row before attending it, the rule every free slot's
        garbage write already lives by; a dispatch that takes the page
        is called after both and so runs after both."""
        lasts = []
        if self._mtp:
            # (ISSUE 40) a step yields one token or two a lane and the
            # host learns which when it fetches it: what is owed is
            # settled there (:meth:`_settle_counts`).  Known now: every
            # step in flight yields one at least, so a lane that is owed
            # no more than it has steps in flight ends with this one (a
            # lane whose drafts were accepted has ended before: it rode
            # this step and the one before, and what they made is
            # dropped), and its slot is free for the next admission
            for slot, lane in pairs:
                lane.inflight += 1
                self._unseen[slot] += self.spec_k + 1
                if lane.remaining <= lane.inflight:
                    self._vacate_slot(slot, lane)
            return lasts
        for slot, lane in pairs:
            self._pos[slot] += 1
            lane.remaining -= 1
            lasts.append(lane.remaining == 0)
            if lane.remaining == 0:
                self._vacate_slot(slot, lane)
        return lasts

    def _deliver(self):   # hot-path
        """The tokens the last fetch brought go to their lanes: the
        reply's list, THE emit site (:meth:`_count_tokens`), and the result
        of a request whose last token this is; a lane withdrawn meanwhile
        leaves its slot as it always did, with the tokens it had.  Runs
        where the device is busy or nothing is in flight: behind the next
        turn's chunk call, else under the next step (``ahead.emit``), or
        first thing in a turn of the old order; and before anything that
        fails lanes."""
        if not self._undelivered:
            return
        pending, self._undelivered = self._undelivered, []
        for slot, lane, tok, last, first in pending:
            if lane.request.future.done():
                # (two steps' tokens may land at once, ISSUE 39: the first
                # of them found the lane withdrawn, and its reply is made)
                continue
            lane.emitted.append(tok)
            self._count_tokens(lane.request)
            if first:
                self.metrics.record_ttft(time.monotonic()
                                         - lane.request.t_enq)
            if last:
                self._reply(lane)
                if self._lanes[slot] is lane and not lane.inflight:
                    # (a lane that drafts is freed by count when its last
                    # step goes out; where none is in flight, the old
                    # order, it leaves here)
                    self._vacate_slot(slot, lane)
            elif lane.request.cancelled and self._lanes[slot] is lane:
                self._finish(slot)

    def _fail_step(self, step, exc, unused):
        """A plain step's dispatch or fetch raised: its lanes fail
        (:meth:`_fail_active`), those it had already freed by count with
        them, and what was prepared under it goes (``unused``: so did its
        own arguments, made ahead, before the program took them).  Where
        the storage stands (the fault fired before the program took it)
        the dispatches still in flight are older and sound: their tokens
        are fetched and delivered first.  Where it went down
        (:meth:`_storage_lost`) they went with it, lanes and all."""
        if unused:
            self.metrics.inc("ahead_discarded")
        self._drop_ahead()
        if self._flights and self._flights[-1].pairs is step.pairs:
            self._flights.pop()          # its own, if it got that far
        self._drain()
        self._fail_active([slot for slot, lane in step.pairs
                           if self._lanes[slot] is lane], exc)
        for _, lane in step.pairs:
            if not lane.request.future.done():
                lane.request.future.set_exception(exc)

    def _under_step(self, step, made_ahead):   # hot-path
        """The part of a turn that needs no token, run between the decode
        step's jit call and the wait for its tokens (ISSUE 37; the
        ``ahead.*`` phases): the step's own counters and its effect on
        the lanes by count; the tokens of the step BEFORE to their lanes;
        the queue shed and admitted; then the next turn's prompt chunk
        (the round robin as it is) and decode step with every argument
        but ``last`` on the device, kept in ``_ahead`` for the turn that
        begins when this step's tokens arrive."""
        rec = self.recorder
        rec.mark(tracing.AHEAD_EMIT)
        self._note_step(step, made_ahead)
        # (the step's own flight is the newest: what the count says of
        # each token now is read when the token is fetched, a turn on)
        self._flights[-1].lasts = self._advance_by_count(step.pairs)
        self._deliver()
        rec.mark(tracing.AHEAD_ADMIT)
        busy = self._admit_turn()
        rec.mark(tracing.AHEAD_PREPARE)
        if not busy:
            return
        chunk = None
        prefilling = [i for i in busy if self._lanes[i].pending]
        picked = self._pick_prefill(prefilling) if prefilling else None
        if picked is not None:
            slot, lane = picked
            chunk = self._prepare_chunk_paged(slot, lane, lane.request)
        active = [i for i, lane in enumerate(self._lanes)
                  if lane is not None and not lane.pending
                  # (a tail chunk's first token may be its lane's last)
                  and (chunk is None or lane is not chunk.lane
                       or lane.remaining > 1)]
        nxt = self._prepare_step(active) if active else None
        with self._cond:
            queued = len(self._queue)
        self._ahead = _Ahead(chunk, nxt, self._lanes_gen, len(busy), queued)

    def _step_speculative(self, active):   # hot-path
        """ONE verify dispatch advances every active lane by 1..k+1
        tokens: each lane feeds [last, draft…] (draft = prompt-lookup
        n-gram continuation, zeros when none) and accepts the longest
        draft prefix matching the verifier's own greedy argmax, plus
        the correction/bonus token after it — bit-identical to plain
        greedy decode by construction, at < 1 dispatch/token whenever
        drafts hit."""
        k = self.spec_k
        active = self._cow_guard_active(active, k + 1)
        if not active:
            return
        toks_in = numpy.zeros((self.slots, k + 1), numpy.int32)
        drafts = [None] * self.slots
        real_lens = [0] * self.slots
        for slot in active:
            lane = self._lanes[slot]
            toks_in[slot, 0] = self._last[slot]
            history = numpy.concatenate(
                [lane.request.prompt,
                 numpy.asarray(lane.emitted, numpy.int32)])
            draft = propose_draft(history, k, self.spec_ngram)
            if draft is not None:
                # zero-pad to the program's fixed k (padding is free:
                # a pad only "accepts" when it IS the greedy token) but
                # METER only the real continuation — acceptance rates
                # must not be diluted by padding nor inflated by
                # coincidental token-0 matches
                padded = numpy.zeros(k, numpy.int32)
                padded[:len(draft)] = draft
                toks_in[slot, 1:] = padded
                drafts[slot] = padded
                real_lens[slot] = len(draft)
                self.metrics.inc("draft_tokens", len(draft))
        w = self._live_width(k + 1)
        tctxs = ()
        if self._tracer is not None:
            tctxs = [self._lanes[s].request.trace for s in active]
        t0 = time.monotonic()
        try:
            self._fault("engine.verify")
            args = (xfer.to_device(self._page_tables[:, :w]),
                    xfer.to_device(toks_in), xfer.to_device(self._pos))
            out, = self._dispatch_decode(self._verify_jit, args,
                                         len(active), tctxs)
        except Exception as e:   # noqa: BLE001 — fails the lanes
            if self._tracer is not None:
                self._tracer.add_many(
                    tctxs, "decode.verify", "decode", t0,
                    time.monotonic(),
                    attrs={"batch": len(active), "error": str(e)})
            self._fail_active(active, e)
            return
        self.metrics.record_dispatch(len(active))
        self.metrics.record_decode_step(time.monotonic() - t0)
        self.metrics.inc("decode_dispatches")
        self.metrics.inc("spec_dispatches")
        self._note_attn_dispatch(self._attn_page_steps(self._pos, w, k + 1))
        if self._tracer is not None:
            self._tracer.add_many(
                tctxs, "decode.verify", "decode", t0, time.monotonic(),
                attrs={"batch": len(active), "k": k, "bucket": w,
                       "backend": self._backend})
        for slot in active:
            lane = self._lanes[slot]
            draft = drafts[slot]
            accepted = 0
            if draft is not None:
                while accepted < k and \
                        out[slot, accepted] == draft[accepted]:
                    accepted += 1
                self.metrics.inc("draft_accepted",
                                 min(accepted, real_lens[slot]))
            # accepted drafts ARE the greedy tokens (they matched the
            # verifier's argmax); out[accepted] is the greedy token
            # after them (correction on mismatch, bonus on full hit)
            emit = [int(t) for t in
                    (draft[:accepted].tolist() if draft is not None
                     else [])]
            emit.append(int(out[slot, accepted]))
            take = min(len(emit), lane.remaining)
            lane.emitted.extend(emit[:take])
            lane.remaining -= take
            self._count_tokens(lane.request, take)
            self._pos[slot] += accepted + 1
            self._last[slot] = int(out[slot, accepted])
            if lane.remaining == 0 or lane.request.cancelled:
                self._finish(slot)

    def _step_megastep(self, active):   # hot-path
        """ONE fused dispatch advances every active lane by up to K
        tokens (up to K·(spec_k+1) speculative): the ``lax.scan``
        program from :meth:`_make_megastep_body`.  The host's only
        per-token work is reading the returned emitted-token buffer at
        the BOUNDARY — admission, completion, deadline shedding, swap
        application and tracing all happen once per megastep, not per
        token, which is the whole point (ISSUE 13)."""
        K, k = self.megastep, self.spec_k
        # worst-case per-lane span this dispatch can write (the cow
        # guard and the live-width slice must cover every real write;
        # _cow_guard clamps to each lane's reservation, _live_width to
        # max_pages)
        span = K * (k + 1) + k if k else K
        active = self._cow_guard_active(active, span)
        if not active:
            return
        left = numpy.zeros(self.slots, numpy.int32)
        for slot in active:
            left[slot] = self._lanes[slot].remaining
        extra = ()
        if k:
            # the in-graph proposer's token history: prompt + emitted
            # so far per lane, rebuilt from host truth each boundary
            hist = numpy.zeros((self.slots, self.max_len), numpy.int32)
            hlen = numpy.zeros(self.slots, numpy.int32)
            for slot in active:
                lane = self._lanes[slot]
                row = numpy.concatenate(
                    [lane.request.prompt,
                     numpy.asarray(lane.emitted, numpy.int32)])
                hist[slot, :len(row)] = row
                hlen[slot] = len(row)
            extra = (xfer.to_device(hist), xfer.to_device(hlen))
        w = self._live_width(span)
        tctxs = ()
        if self._tracer is not None:
            tctxs = [self._lanes[s].request.trace for s in active]
        t0 = time.monotonic()
        try:
            self._fault("engine.step")
            args = (xfer.to_device(self._page_tables[:, :w]),
                    xfer.to_device(self._last),
                    xfer.to_device(self._pos),
                    xfer.to_device(left)) + extra
            last, pos, emitted, *accs = self._dispatch_decode(
                self._megastep_jit, args, len(active), tctxs)
            accs = accs[0] if k else None
        except Exception as e:   # noqa: BLE001 — fails the lanes
            if self._tracer is not None:
                self._tracer.add_many(
                    tctxs, "decode.megastep", "decode", t0,
                    time.monotonic(),
                    attrs={"batch": len(active), "K": K,
                           "error": str(e)})
            self._fail_active(active, e)
            return
        t1 = time.monotonic()
        entered = self._pos
        # sync the host frontiers from the program's final carry
        # (frozen lanes returned their entry values, so this is a
        # wholesale assignment)
        self._pos = numpy.array(pos, numpy.int32)
        self._last = numpy.array(last, numpy.int32)
        lane_tokens = {}
        wasted = 0
        for slot in active:
            lane = self._lanes[slot]
            rows = (emitted[:, slot, :] if k
                    else emitted[:, slot][:, None])        # (K, c)
            toks = rows[rows >= 0]       # iteration-major real tokens
            wasted += int((rows[:, 0] < 0).sum())
            lane.emitted.extend(int(t) for t in toks)
            lane.remaining -= len(toks)
            lane_tokens[slot] = int(len(toks))
            if len(toks):
                self._count_tokens(lane.request, len(toks))
        if accs is not None:
            # in-graph drafts are always k wide (padded), so the
            # megastep meters k proposed per live iteration — the
            # acceptance-rate column reads conservatively vs the host
            # proposer's real-length metering (documented in USAGE.md)
            live_iters = int((accs >= 0).sum())
            self.metrics.inc("draft_tokens", k * live_iters)
            self.metrics.inc("draft_accepted",
                             int(numpy.clip(accs, 0, k).sum()))
        total = sum(lane_tokens.values())
        self.metrics.record_dispatch(len(active))
        self.metrics.record_decode_step(t1 - t0)
        self.metrics.inc("decode_dispatches")
        self.metrics.record_megastep(K, len(active), total, wasted)
        self._note_attn_dispatch(self._attn_page_steps(entered, w, k + 1),
                                 calls=K)
        if self._tracer is not None:
            # ONE decode.megastep span per dispatch, shared did so the
            # cost ledger counts the fused program once — never the
            # folded per-token work; per-lane tokens ride each copy's
            # own attrs (ISSUE 12 stays truthful)
            self._tracer.add_many(
                tctxs, "decode.megastep", "decode", t0, t1,
                attrs={"batch": len(active), "K": K, "tokens": total,
                       "bucket": "%sxK%d" % (w, K),
                       "backend": self._backend},
                each_attrs=[{"lane_tokens": lane_tokens[s]}
                            for s in active])
        for slot in active:
            lane = self._lanes[slot]
            if lane.remaining == 0 or lane.request.cancelled:
                self._finish(slot)

    def _boundary_shed(self):
        """Deadline shedding at the MEGASTEP BOUNDARY (ISSUE 13
        satellite): one sweep of the whole queue per boundary, instead
        of the admission loop's per-pop head checks paying a lock round
        per tick.  A deadline expiring MID-megastep sheds at the NEXT
        boundary — the documented semantics: the fused program is never
        interrupted, a request already admitted keeps decoding (its
        deadline only ever governed queue wait), and a request whose
        tokens completed inside the megastep resolves its future before
        this sweep can ever see it.  Queue-token/page gauges re-read
        once per sweep, at the boundary, not per pop.  The worst-case
        shed LATENCY is one dispatch window: the megastep's K
        iterations."""
        now = time.monotonic()
        shed = []
        with self._cond:
            if not self._queue:
                return
            if all(now <= req.deadline or req.cancelled
                   for req in self._queue):
                return
            keep = collections.deque()
            for req in self._queue:
                if not req.cancelled and now > req.deadline:
                    shed.append(req)
                    self._queued_tokens -= req.true_len
                    self._queued_pages -= req.pages
                else:
                    keep.append(req)
            self._queue = keep
            self.metrics.set_gauge("queue_depth", len(self._queue))
            self.metrics.set_gauge("queue_tokens", self._queued_tokens)
            self.metrics.set_gauge("queue_pages", self._queued_pages)
        window = self.megastep if self.megastep >= 2 else 1
        for req in shed:
            self.metrics.record_shed()
            self._trace_queue_end(req, "shed")
            req.future.set_exception(DeadlineExceeded(
                "prompt shed after %.3fs in queue (boundary sweep, "
                "window <= %d iterations)"
                % (time.monotonic() - req.t_enq, window)))

    def _worker(self):
        # the transfer-guard witness must be entered ON this thread
        # (JAX guard state is thread-local); a null context unarmed
        with xfer.guard():
            self._serve_loop()

    def _admit_turn(self):   # hot-path
        """A turn's admission, wherever the turn does it (``loop.admit``,
        or ``ahead.admit`` under the step in flight): the boundary sweep
        (one pass per loop turn = per megastep when fused decode is on)
        sheds EVERY expired queued request now, not just those the
        admission loop happens to pop; then the free slots fill.  Returns
        the slots that hold a request."""
        self._boundary_shed()
        self._admit()
        busy = [i for i, lane in enumerate(self._lanes)
                if lane is not None]
        self.metrics.set_gauge("slots_busy", len(busy))
        self.metrics.set_gauge_max("slots_busy_peak", len(busy))
        return busy

    def _drop_ahead(self):
        """What was prepared for the next turn is not used (counted in
        ``ahead_discarded``).  A chunk not yet dispatched goes back to the
        head of its lane's pending list, if the lane is still there; its
        guards and slides stand (both are idempotent), its arguments are
        made again."""
        ahead, self._ahead = self._ahead, None
        if ahead is None:
            return
        self.metrics.inc("ahead_discarded")
        chunk = ahead.chunk
        if chunk is not None and self._lanes[chunk.slot] is chunk.lane:
            chunk.lane.pending.insert(
                0, (chunk.tokens, chunk.start, chunk.is_tail))
            self._pos[chunk.slot] = chunk.start

    def _take_ahead(self):   # hot-path
        """The turn prepared under the step before, if it still holds:
        no lane has left its slot since (a failed fetch, a tick fault) and
        none was withdrawn (a pending weight swap has dropped it already,
        :meth:`_maybe_apply_swap`).  Else it is dropped and the turn runs
        in the old order, from the lanes as they are."""
        ahead = self._ahead
        if ahead is None:
            return None
        if ahead.gen != self._lanes_gen \
                or any(lane is not None and lane.request.cancelled
                       for lane in self._lanes):
            self._drop_ahead()
            return None
        return ahead

    def _take_step(self, active):   # hot-path
        """The decode step prepared ahead for exactly the lanes ``active``
        (the lanes that decode NOW), or None: none was prepared, or the
        lanes moved after it was (the turn's chunk failed, or its first
        token was its request's last)."""
        ahead, self._ahead = self._ahead, None
        if ahead is None or ahead.step is None:
            return None
        if ahead.gen != self._lanes_gen \
                or [slot for slot, _ in ahead.step.pairs] != active:
            self.metrics.inc("ahead_discarded")
            return None
        return ahead.step

    def _serve_loop(self):   # hot-path
        """The engine's worker loop: one turn dispatches at most one
        prompt chunk and one decode program.

        A turn begins when the step before has its tokens on the host
        (``rec.turn()``).  The old order, which every driver takes when
        no step was in flight (an idle engine, only prefilling lanes, the
        first turn) and the speculative driver and the megastep take
        always: tick (fault site, weight swap) ->
        the tokens owed to the lanes -> shed and admit -> one prompt
        chunk's arguments and jit call -> the decode step's arguments,
        jit call, wait, emit.  The plain driver does everything of
        that which needs no token UNDER its step (:meth:`_under_step`,
        between the jit call's return and the wait), so the turn after is
        tick -> the prepared chunk's jit call -> the step's jit call
        (``last`` is on the device) -> [under the step: deliver, shed and
        admit, prepare] -> wait for the tokens of the step BEFORE (ISSUE
        39: two dispatches in flight).  Which it is, the loop reads off
        ``_ahead``: whatever a driver left there is used if it still
        holds (:meth:`_take_ahead`), and a driver that cannot split its
        turn leaves nothing; the old order begins with the outstanding
        fetches (:meth:`_drain`)."""
        rec = self.recorder
        while True:
            # the recorder's turn (ISSUE 26): the phases marked below
            # partition it; no lock, no fence, no transfer
            rec.turn()
            self._older = len(self._flights)
            # per-tick fault site (latency spikes / replica freezes —
            # a freeze here wedges the worker exactly like a hung
            # device call, the shape the health prober must catch);
            # free when unarmed
            if self._faults is not None:
                try:
                    self._faults.fire("engine.tick")
                except Exception as e:   # noqa: BLE001 — injected
                    # a raised tick fault poisons the whole engine
                    # loop's turn: fail the in-flight lanes (the
                    # fault-isolation discipline) and keep ticking
                    self._drop_ahead()
                    self._drain()
                    self._fail_active(
                        [i for i, ln in enumerate(self._lanes)
                         if ln is not None], e)
            self._maybe_apply_swap()
            ahead = self._take_ahead()
            if ahead is None:
                self._drain()
                self._deliver()
                rec.mark(tracing.ADMIT)
                busy = self._admit_turn()
                # lint: allow(lock-discipline): the recorder takes no lock; len() of a deque is one atomic read
                rec.lanes(len(busy), len(self._queue))
                if not busy:
                    rec.mark(tracing.WAIT)
                    with self._cond:
                        if self._stop:
                            break
                        if not self._queue:
                            self._cond.wait(0.5)
                        elif self._pool_blocked:
                            # head request waiting on pages with no lane
                            # running to free any: only trie eviction or
                            # its deadline can resolve it — poll briefly so
                            # the shed fires on time without a hot spin
                            self._cond.wait(0.05)
                    continue
                # chunked prefill interleaving: at most ONE prompt chunk
                # per tick (round-robin across prefilling lanes), then one
                # decode dispatch for the lanes that are past prefill — a
                # long prompt costs the decode lanes one chunk of latency
                # per token, never its whole prefill
                prefilling = [i for i in busy if self._lanes[i].pending]
                if prefilling:
                    rec.mark(tracing.PREFILL_PREPARE)
                    picked = self._pick_prefill(prefilling)
                    if picked is not None:
                        slot, lane = picked
                        chunk = self._prepare_chunk_paged(slot, lane,
                                                          lane.request)
                        if chunk is not None:
                            self._dispatch_chunk_paged(chunk)
            else:
                # admitted, chosen and prepared under the step before:
                # the chunk goes out at once
                rec.lanes(ahead.busy, ahead.queued)
                chunk, ahead.chunk = ahead.chunk, None
                if chunk is not None:
                    self._dispatch_chunk_paged(chunk)
            rec.mark(tracing.STEP_PREPARE)
            active = [i for i, lane in enumerate(self._lanes)
                      if lane is not None and not lane.pending]
            step = self._take_step(active)
            if not active:
                # (a tail chunk's token that was its request's only one)
                self._drain()
                continue
            if self._megastep_jit is not None:
                self._step_megastep(active)
            elif self._verify_jit is not None:
                self._step_speculative(active)
            else:
                self._step_plain(active, step)
        self._drain()
        self._deliver()
        rec.close()
        # drain: engine stopping fails whatever is still queued
        with self._cond:
            pending = list(self._queue)
            self._queue.clear()
            self._queued_tokens = 0
            self._queued_pages = 0
            swap = self._pending_swap
            self._pending_swap = None
        if swap is not None:
            # never strand a swap_weights caller on a stopping engine
            swap["exc"] = RuntimeError("LM engine stopped before the "
                                       "swap applied")
            swap["done"].set()
        for req in pending:
            self._trace_queue_end(req, "engine stopped")
            req.future.set_exception(RuntimeError("LM engine stopped"))
        for slot, lane in enumerate(self._lanes):
            if lane is not None:
                if not lane.request.future.done():
                    lane.request.future.set_exception(
                        RuntimeError("LM engine stopped"))
                self._lanes[slot] = None

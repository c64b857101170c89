"""End-to-end request tracing for the serving stack (ISSUE 12).

The serving tier's metrics (``serving/metrics.py``) answer aggregate
questions — p95 latency, dispatch counts, EWMAs — but not "where did
THIS request's 400 ms go?".  This module adds a lock-cheap SPAN TRACER
threaded through the whole request path: ``restful_api.py`` opens an
``http.request`` root span, ``serving/router.py`` records one child
span per placement ATTEMPT (retries, hedges and drains included),
``serving/batcher.py`` and ``serving/lm_engine.py`` record queue wait,
admission, every prefill chunk, every decode/verify dispatch, COW page
copies and weight-swap applies.  A fused decode megastep (ISSUE 13)
records ONE ``decode.megastep`` span per K-token dispatch — shared
dispatch id, per-lane tokens-emitted on each request's copy — so the
cost ledger counts the fused program once, never the folded per-token
work.  Spans carry the request id, replica,
weights_version and fast-path attributes (bucket, live width, backend),
so a single request's timeline reads end to end across threads and
engines.

Design rules (the ``faults.py`` discipline):

- UNARMED IS FREE.  Engines hold ``self._tracer = None`` by default and
  every site is one attribute-is-None check — no lock, no allocation.
  The chaos bench's overhead leg pins the unarmed cost inside the same
  <2% bound as the fault layer.
- DEVICE SPANS ARE FENCED.  jit dispatch is asynchronous — a span that
  closed at dispatch-return would measure enqueue, not execution.  When
  (and only when) tracing is armed, each dispatch site calls
  ``jax.block_until_ready`` on its outputs before closing the span, so
  durations are device wall time.  That sync is the documented cost of
  ARMED tracing; unarmed engines never fence.  It also serialises host
  and device, so a traced run is not the run that is measured: the
  tracer's spans are for POST-MORTEMS, the loop recorder's (below) for
  MEASUREMENT.
- THE FLIGHT RECORDER IS BOUNDED.  Finished requests land in a ring
  buffer (``last`` requests), so the recent past is always
  reconstructable after the fact; a request that errors or blows its
  deadline is additionally DUMPED (waterfall text, kept in a second
  small ring and logged) the moment it finishes — post-mortems need no
  foresight.
- ONE DISPATCH, ONE COST.  A batched decode tick serves many lanes; the
  tracer records the span once per PARTICIPATING request (each request's
  timeline is complete) but stamps every copy with a shared dispatch id
  (``did``) so the COST LEDGER counts the dispatch once.

Modes (``serve_lm(trace=)`` / ``--serve-trace``):

=============== ======================================================
``off``         no tracer (the default — zero overhead)
``all``         every request traced and retained in the ring
``sample:P``    a seeded coin traces fraction P of requests
``errors``      every request traced, but only errored/deadline-blown
                requests are RETAINED (the ring holds exactly the
                post-mortem set)
=============== ======================================================

Consumers: ``GET /trace.json?last=N`` exports the ring as
Chrome-trace/Perfetto JSON (load at https://ui.perfetto.dev or
chrome://tracing — one track per request), and ``tools/trace_report.py``
renders per-request waterfalls and aggregates spans into the per-op
cost ledger (op family x bucket x backend -> p50/p95 duration, dispatch
count).

THE LOOP RECORDER (ISSUE 26) is the other half of this module and does
not depend on the tracer: :class:`LoopRecorder` is on in EVERY engine,
whatever ``--serve-trace`` says, costs no lock, no fence and no device
transfer, and stamps ``time.monotonic_ns()`` — the clock of the
benchmark's window and (through ``benchmark/lib/spans.py``'s fit) of
the device trace.  One record per turn of ``LMEngine._serve_loop``
(:data:`PHASES` partition the turn), one per finished request (with
the stamp of every emitted token), one per HTTP POST
(:func:`note_http`), and (ISSUE 38) one per DEVICE DISPATCH: every call
of a jitted program by the engine's worker thread is a thing of its
own there (a second ring, ``DCOL_*``), with the program, the turn whose
code made the call, the lanes, and four stamps: the call, its return
to the host, the moment the host began to wait for the outputs, the
moment it had them, and the turn that was open then.  The engine holds
a dispatch's handle with its outputs for as long as their fetch is
outstanding, so the record says the same whether the outputs are
fetched in the turn of the call, one dispatch late, or behind a
program called in between; the turn row (two program columns, one
``step.emit``) cannot.  Readers: ``GET /trace.json`` (a track of
dispatch slices under each loop track),
``benchmark/lib/dispatch_log.py`` (pairs the device trace's executions
with the records by program and order, and splits the device's idle
time by what the host was doing: waiting for outputs already made,
inside or behind the next call, or at its own work).
:func:`recorders` keeps the newest four of the
process, stopped engines' included, so a reader that runs after
``api.stop()`` still finds them.  All span times of this module — the
tracer's too — are on that one clock, against the process origin
``metrics._ORIGIN``.

Context plumbing: the REQUEST context travels two ways.  Down a call
stack, :func:`use` binds a :class:`TraceContext` to the thread and
:func:`current` reads it back (HTTP handler -> router -> engine submit
all run on the caller's thread).  Across threads, the context rides the
request object itself (``_Request.trace``), so the engine worker
thread attributes its dispatch spans to the right requests.  Whoever
STARTED a request's trace finishes it (``TraceContext.owns``); layers
below only add child spans.
"""

from __future__ import annotations

import collections
import threading
import time

import numpy

from veles_tpu.logger import Logger
from veles_tpu.serving import lockcheck
from veles_tpu.serving.metrics import _ORIGIN

_tls = threading.local()


def current():
    """The calling thread's active :class:`TraceContext` (bound by
    :func:`use`), :data:`SAMPLED_OUT`, or None — how a lower serving
    layer (router, engine, batcher) joins the request its caller
    already started instead of rooting a second one."""
    return getattr(_tls, "ctx", None)


#: sentinel an outer layer binds (via :func:`use`) when ITS sampler
#: skipped the request: lower layers must not re-roll the coin —
#: without this, ``sample:P`` behind HTTP would trace ~1-(1-P)^3 of
#: traffic as partial router-/engine-rooted trees
SAMPLED_OUT = object()


def join_or_root(tracer, name, cat="request", attrs=None):
    """THE join-or-root decision every traced layer makes on its
    submit path: returns ``(ctx, own_root)`` where ``ctx`` is the
    caller's existing context (own_root False), a fresh root this
    layer now OWNS (own_root True — it must ``finish_request``), or
    :data:`SAMPLED_OUT` when the sampler — here or upstream — skipped
    the request (record nothing, but PROPAGATE the sentinel to layers
    below via :func:`use`)."""
    up = current()
    if up is not None:          # a real ctx OR the sentinel
        return up, False
    ctx = tracer.start_request(name=name, cat=cat, attrs=attrs)
    if ctx is None:
        return SAMPLED_OUT, False
    return ctx, True


class use:
    """Bind ``ctx`` as the thread's current trace context for a
    ``with`` block (restored on exit, exception or not)."""

    __slots__ = ("ctx", "_prev")

    def __init__(self, ctx):
        self.ctx = ctx

    def __enter__(self):
        self._prev = getattr(_tls, "ctx", None)
        _tls.ctx = self.ctx
        return self.ctx

    def __exit__(self, *exc):
        _tls.ctx = self._prev
        return False


class TraceContext:
    """One traced request's handle: the owning tracer, the request id,
    the root span and the parent span new child spans attach under.
    ``at(sid)`` derives a context parented at ``sid`` (the router hands
    the engine a context under the current ATTEMPT span, so engine
    spans nest per attempt).  ``owns`` marks the layer that must call
    :meth:`SpanTracer.finish_request`."""

    __slots__ = ("tracer", "rid", "root", "parent", "owns")

    def __init__(self, tracer, rid, root, parent=None, owns=False):
        self.tracer = tracer
        self.rid = rid
        self.root = root
        self.parent = parent if parent is not None else root
        self.owns = owns

    def at(self, sid):
        return TraceContext(self.tracer, self.rid, self.root,
                            parent=sid, owns=False)


class _Span:
    __slots__ = ("sid", "parent", "name", "cat", "t0", "t1", "attrs")

    def __init__(self, sid, parent, name, cat, t0, t1=None, attrs=None):
        self.sid = sid
        self.parent = parent
        self.name = name
        self.cat = cat
        self.t0 = t0
        self.t1 = t1
        self.attrs = attrs


class SpanTracer(Logger):
    """The serving stack's span recorder; see the module docstring.

    Thread-safe: every mutation is a few dict/list operations under one
    lock.  ``last`` bounds the flight recorder (finished requests),
    ``max_spans`` bounds any single request's span count (a runaway
    long decode cannot grow without bound — excess spans are counted,
    not stored), ``seed`` makes ``sample:P`` reproducible."""

    MODES = ("all", "errors", "sample")

    #: lock-discipline map (ISSUE 15): the span store is mutated from
    #: every serving thread (handlers, workers, timers) — all of it
    #: under the one tracer lock, including the seeded sampler RNG.
    _guarded_by = {
        "_sid": "_lock", "_did": "_lock", "_auto_rid": "_lock",
        "_live": "_lock", "_ring": "_lock", "_dumps": "_lock",
        "_events": "_lock", "_rng": "_lock",
        "started": "_lock", "finished": "_lock",
        "sampled_out": "_lock", "dropped_spans": "_lock",
        "dump_count": "_lock",
    }

    def __init__(self, mode="all", sample=1.0, last=64, max_spans=4096,
                 seed=0, name="trace"):
        if mode not in self.MODES:
            raise ValueError("trace mode %r (one of %r)"
                             % (mode, self.MODES))
        self.name = name
        self.mode = mode
        self.sample = float(sample)
        self.max_spans = int(max_spans)
        #: ONE clock (ISSUE 26): span times are ``time.monotonic_ns()``
        #: readings against the PROCESS origin every observability
        #: endpoint shares — not a per-tracer origin — so a request's
        #: spans line up with the loop recorder's phases and with
        #: ``sampled_at`` stamps
        self._origin = _ORIGIN
        self._lock = lockcheck.make_lock("tracing._lock")
        self._rng = numpy.random.RandomState(seed)
        self._sid = 0
        self._did = 0
        self._auto_rid = 0
        self._live = {}                  # rid -> building record
        self._ring = collections.deque(maxlen=int(last))
        self._dumps = collections.deque(maxlen=32)
        #: engine-scope spans with no request (weight-swap applies,
        #: router drains/deploys) — exported on their own track
        self._events = collections.deque(maxlen=512)
        self.started = 0
        self.finished = 0
        self.sampled_out = 0
        self.dropped_spans = 0
        self.dump_count = 0

    @classmethod
    def from_spec(cls, spec, **kw):
        """Build a tracer from the CLI/`serve_lm(trace=)` spec:
        ``None``/``False``/``0``/``'off'`` -> None (tracing disabled),
        ``True``/``'all'``/``'errors'`` -> that mode, ``'sample:P'``
        -> seeded sampling at probability P, an existing
        :class:`SpanTracer` passes through."""
        if spec is None or spec is False or spec == 0 or spec == "off":
            return None
        if isinstance(spec, SpanTracer):
            return spec
        if spec is True:
            return cls(mode="all", **kw)
        s = str(spec)
        if s.startswith("sample:"):
            return cls(mode="sample", sample=float(s.split(":", 1)[1]),
                       **kw)
        if s in ("all", "errors"):
            return cls(mode=s, **kw)
        raise ValueError(
            "trace spec %r (off|errors|all|sample:P or a SpanTracer)"
            % (spec,))

    def _now(self):
        return time.monotonic_ns() * 1e-9 - self._origin

    # ------------------------------------------------------------ recording
    def start_request(self, rid=None, name="request", cat="request",
                      attrs=None):
        """Open a request's trace; returns its (owning)
        :class:`TraceContext`, or None when the sampler skipped it —
        callers treat None exactly like tracing-off.  ``rid`` is the
        join key across layers (the HTTP ``X-Request-Id``); omitted,
        one is generated."""
        with self._lock:
            self.started += 1
            if self.mode == "sample" \
                    and self._rng.random_sample() >= self.sample:
                self.sampled_out += 1
                return None
            if rid is None:
                self._auto_rid += 1
                rid = "r%05d" % self._auto_rid
            rid = str(rid)
            if rid in self._live:       # client-reused id: keep both
                self._auto_rid += 1
                rid = "%s#%d" % (rid, self._auto_rid)
            self._sid += 1
            sid = self._sid
            self._live[rid] = {
                "rid": rid,
                "spans": {sid: _Span(sid, None, name, cat,
                                     self._now(), attrs=attrs)},
                "open": {sid},
                "root": sid,
            }
        return TraceContext(self, rid, sid, owns=True)

    def begin(self, ctx, name, cat="span", attrs=None, parent=None):
        """Open a child span under ``ctx``; returns an opaque handle
        for :meth:`end` (None when the request is gone or at its span
        cap — safe to pass back to ``end``)."""
        if ctx is None:
            return None
        with self._lock:
            rec = self._live.get(ctx.rid)
            if rec is None:
                return None
            if len(rec["spans"]) >= self.max_spans:
                self.dropped_spans += 1
                return None
            self._sid += 1
            sid = self._sid
            rec["spans"][sid] = _Span(
                sid, parent if parent is not None else ctx.parent,
                name, cat, self._now(), attrs=attrs)
            rec["open"].add(sid)
        return (ctx.rid, sid)

    def end(self, handle, attrs=None, error=None):
        """Close a span (idempotent: a handle already closed — or None
        — is a no-op, so racing completion paths cannot corrupt a
        timeline)."""
        if handle is None:
            return
        rid, sid = handle
        t1 = self._now()
        with self._lock:
            rec = self._live.get(rid)
            if rec is None:
                return
            span = rec["spans"].get(sid)
            if span is None or span.t1 is not None:
                return
            span.t1 = t1
            rec["open"].discard(sid)
            if attrs:
                span.attrs = dict(span.attrs or (), **attrs)
            if error is not None:
                span.attrs = dict(span.attrs or (),
                                  error=_err_str(error))

    def instant(self, ctx, name, cat="mark", attrs=None):
        """A zero-duration marker span (retry scheduled, prefix hit,
        swap requeue, ...)."""
        if ctx is None:
            return
        t = self._now()
        with self._lock:
            rec = self._live.get(ctx.rid)
            if rec is None or len(rec["spans"]) >= self.max_spans:
                return
            self._sid += 1
            rec["spans"][self._sid] = _Span(
                self._sid, ctx.parent, name, cat, t, t, attrs)

    def add_many(self, ctxs, name, cat, t0, t1, attrs=None,
                 each_attrs=None):
        """Record one COMPLETED span per context — the batched-dispatch
        path (one decode tick advances many lanes): each participating
        request's timeline gets the span, all copies share one
        dispatch id (``did``) so the cost ledger counts the device
        dispatch once.  ``t0``/``t1`` are raw clock readings
        (``time.monotonic()`` — the caller already timed the fenced
        dispatch).  ``each_attrs`` (same length as ``ctxs``) merges
        per-participant attributes into that context's copy ON TOP of
        the shared ``attrs`` — the decode megastep (ISSUE 13) stamps
        each lane's own tokens-emitted count on a span the ledger
        still counts once.  Returns the did (None when nothing
        recorded)."""
        did = None
        t0 -= self._origin
        t1 -= self._origin
        with self._lock:
            for i, ctx in enumerate(ctxs):
                if ctx is None:
                    continue
                rec = self._live.get(ctx.rid)
                if rec is None:
                    continue
                if len(rec["spans"]) >= self.max_spans:
                    self.dropped_spans += 1
                    continue
                if did is None:
                    self._did += 1
                    did = self._did
                span_attrs = dict(attrs or (), did=did)
                if each_attrs is not None and each_attrs[i]:
                    span_attrs.update(each_attrs[i])
                self._sid += 1
                rec["spans"][self._sid] = _Span(
                    self._sid, ctx.parent, name, cat, t0, t1,
                    span_attrs)
        return did

    def add(self, ctx, name, cat, t0, t1, attrs=None):
        """One completed span on one request (unbatched dispatches)."""
        return self.add_many((ctx,), name, cat, t0, t1, attrs)

    def event(self, name, cat="engine", t0=None, t1=None, attrs=None):
        """An ENGINE-scope span with no owning request (weight-swap
        apply, router drain/deploy) — bounded side channel, exported on
        its own track, excluded from per-request tree checks.
        ``t0``/``t1`` are raw clock readings (``time.monotonic()``);
        omitted, the event is an instant at now."""
        now = self._now()
        t0 = now if t0 is None else t0 - self._origin
        t1 = now if t1 is None else t1 - self._origin
        with self._lock:
            self._events.append({
                "name": name, "cat": cat, "t0": t0, "t1": t1,
                "attrs": dict(attrs or ())})

    def finish_request(self, ctx, error=None, deadline=False,
                       attrs=None):
        """Close a request's trace: the root (and any span a fault path
        left open — flagged ``unclosed``) is ended, the record moves to
        the flight-recorder ring (mode ``errors`` retains only
        errored/deadline requests), and an errored or deadline-blown
        request is DUMPED (waterfall text logged + kept).  Idempotent —
        racing finishers (a timed-out caller and a late worker) cannot
        double-record.  Returns the finished record, or None when the
        request was already finished or discarded by ``errors``-mode
        retention."""
        rid = ctx.rid if isinstance(ctx, TraceContext) else str(ctx)
        t1 = self._now()
        dump = error is not None or deadline
        keep = self.mode != "errors" or dump
        with self._lock:
            rec = self._live.pop(rid, None)
            if rec is None:
                return None
            self.finished += 1
            if not keep:
                # errors-mode discard: no O(spans) record build under
                # the lock for the (common) successful case — the armed
                # decode hot path shares this lock
                return None
            unclosed = []
            root = rec["root"]
            for sid in rec["open"]:
                span = rec["spans"][sid]
                span.t1 = t1
                if sid != root:
                    span.attrs = dict(span.attrs or (), unclosed=True)
                    unclosed.append(span.name)
            if attrs:
                rspan = rec["spans"][root]
                rspan.attrs = dict(rspan.attrs or (), **attrs)
            out = {
                "rid": rid,
                "error": _err_str(error) if error is not None else None,
                "deadline_blown": bool(deadline),
                "unclosed": unclosed,
                "spans": [{"sid": s.sid, "parent": s.parent,
                           "name": s.name, "cat": s.cat,
                           "t0": s.t0, "t1": s.t1,
                           "attrs": dict(s.attrs) if s.attrs else {}}
                          for s in rec["spans"].values()],
            }
            self._ring.append(out)
            if dump:
                self.dump_count += 1
        if dump:
            # render OUTSIDE the lock: the waterfall is O(spans) string
            # work, and an error burst must not stall the armed trace
            # sites (add_many on the decode hot path) behind it
            text = format_waterfall(out)
            with self._lock:
                self._dumps.append({"rid": rid, "text": text})
            self.warning("flight recorder dump (%s):\n%s",
                         "deadline" if deadline and error is None
                         else "error", text)
        return out

    # -------------------------------------------------------------- reading
    def requests(self, last=None):
        """The flight recorder's finished requests, oldest first
        (``last`` trims to the newest N)."""
        with self._lock:
            out = list(self._ring)
        if last is not None:
            last = int(last)
            out = out[-last:] if last > 0 else []
        return out

    def find(self, rid):
        """The NEWEST finished record for ``rid`` — the after-the-fact
        reconstruction path ("what happened to request X?")."""
        with self._lock:
            for rec in reversed(self._ring):
                if rec["rid"] == rid:
                    return rec
        return None

    def dumps(self):
        """Auto-dumped waterfalls ({"rid", "text"}), newest last."""
        with self._lock:
            return list(self._dumps)

    def stats(self):
        with self._lock:
            return {"mode": self.mode, "started": self.started,
                    "finished": self.finished,
                    "sampled_out": self.sampled_out,
                    "live": len(self._live),
                    "retained": len(self._ring),
                    "dropped_spans": self.dropped_spans,
                    "dumps": self.dump_count}

    def export_chrome(self, last=None, loops=()):
        """The ring (newest ``last`` requests) + engine events as a
        Chrome-trace/Perfetto JSON object — one track (tid) per
        request, engine events on tid 0, ts/dur in microseconds.
        ``loops`` (:class:`LoopRecorder` objects — ``GET /trace.json``
        passes the served engines') adds two tracks each, ``engine loop
        <name>`` with the newest turns' phases and ``engine loop <name>
        dispatches`` with one slice per dispatch of those turns, below
        the request tracks and on the same clock: a request's
        ``decode.step`` spans stand above the loop phases that produced
        them.  Load at
        https://ui.perfetto.dev or chrome://tracing."""
        recs = self.requests(last)
        with self._lock:
            events = list(self._events)
        out = [{"ph": "M", "pid": 1, "tid": 0, "name": "thread_name",
                "args": {"name": "engine events"}}]
        for ev in events:
            out.append({"ph": "X", "pid": 1, "tid": 0,
                        "name": ev["name"], "cat": ev["cat"],
                        "ts": round(ev["t0"] * 1e6, 1),
                        "dur": round(max(0.0, ev["t1"] - ev["t0"])
                                     * 1e6, 1),
                        "args": ev["attrs"]})
        for tid, rec in enumerate(recs, start=1):
            label = "req %s" % rec["rid"]
            if rec["error"]:
                label += " [ERROR]"
            elif rec["deadline_blown"]:
                label += " [DEADLINE]"
            # rid/error/deadline ride as structured args too — the
            # label is for humans, and a rid containing spaces must
            # not confuse trace_report's rebuild
            out.append({"ph": "M", "pid": 1, "tid": tid,
                        "name": "thread_name",
                        "args": {"name": label, "rid": rec["rid"],
                                 "error": rec["error"],
                                 "deadline_blown":
                                     rec["deadline_blown"]}})
            for sp in rec["spans"]:
                args = dict(sp["attrs"], rid=rec["rid"],
                            sid=sp["sid"], parent=sp["parent"])
                out.append({"ph": "X", "pid": 1, "tid": tid,
                            "name": sp["name"], "cat": sp["cat"],
                            "ts": round(sp["t0"] * 1e6, 1),
                            "dur": round(max(0.0, (sp["t1"] or sp["t0"])
                                         - sp["t0"]) * 1e6, 1),
                            "args": args})
        for i, loop in enumerate(loops):     # two tracks a loop
            out.extend(loop.chrome_events(len(recs) + 1 + 2 * i))
        return {"traceEvents": out, "displayTimeUnit": "ms",
                "otherData": {"tracer": self.name, "mode": self.mode,
                              "stats": self.stats()}}

    def ledger(self, last=None):
        """The per-op cost ledger over the flight recorder — see
        :func:`cost_ledger`."""
        return cost_ledger(self.requests(last))


def _err_str(error):
    if isinstance(error, BaseException):
        return "%s: %s" % (type(error).__name__, error)
    return str(error)


def finish_from_future(ctx, future):
    """Future-settlement hook for engine-/router-owned roots: finish
    the request's trace with the future's outcome (result, exception —
    deadline sheds flagged — or cancellation)."""
    error, deadline = None, False
    if future.cancelled():
        error = "cancelled"
    else:
        exc = future.exception()
        if exc is not None:
            error = exc
            from veles_tpu.serving.batcher import DeadlineExceeded
            deadline = isinstance(exc, DeadlineExceeded)
    ctx.tracer.finish_request(ctx, error=error, deadline=deadline)


def verify_integrity(records):
    """Assert every finished request's span tree is sound: exactly one
    root (parent None), every parent resolves INSIDE the same request,
    every span closed with t1 >= t0, nothing flagged ``unclosed``.
    Raises AssertionError naming the first violation; returns
    ``{"requests", "spans"}`` when clean — the bench/test contract
    (a traced run whose trees do not verify is a bug, not data)."""
    total = 0
    for rec in records:
        rid = rec["rid"]
        spans = rec["spans"]
        sids = {s["sid"] for s in spans}
        roots = [s for s in spans if s["parent"] is None]
        if len(roots) != 1:
            raise AssertionError(
                "request %s has %d root spans (want exactly 1): %r"
                % (rid, len(roots), [s["name"] for s in roots]))
        if rec["unclosed"]:
            raise AssertionError(
                "request %s finished with unclosed span(s): %r"
                % (rid, rec["unclosed"]))
        for s in spans:
            if s["parent"] is not None and s["parent"] not in sids:
                raise AssertionError(
                    "request %s span %s (sid %d) is an ORPHAN: parent "
                    "%d is not in this request"
                    % (rid, s["name"], s["sid"], s["parent"]))
            if s["t1"] is None:
                raise AssertionError(
                    "request %s span %s never closed" % (rid, s["name"]))
            if s["t1"] < s["t0"]:
                raise AssertionError(
                    "request %s span %s closed before it opened"
                    % (rid, s["name"]))
            if s["attrs"].get("unclosed"):
                raise AssertionError(
                    "request %s span %s flagged unclosed"
                    % (rid, s["name"]))
        total += len(spans)
    return {"requests": len(records), "spans": total}


def _pct(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    return sorted_vals[min(len(sorted_vals) - 1,
                           int(q * len(sorted_vals)))]


def cost_ledger(records):
    """Aggregate DEVICE spans (those stamped with a ``backend`` attr)
    into the per-op cost table: one row per
    (op family x bucket x backend) with dispatch count and p50/p95/mean
    duration (ms).  Batched spans are deduplicated by dispatch id, so
    ``dispatches`` counts device programs launched, not lanes served
    (``lanes`` keeps the participation count)."""
    table = {}
    seen = set()
    for rec in records:
        for sp in rec["spans"]:
            attrs = sp["attrs"]
            backend = attrs.get("backend")
            if backend is None:
                continue
            key = (sp["name"], str(attrs.get("bucket", "-")),
                   str(backend))
            row = table.setdefault(key, {"durs": [], "lanes": 0})
            row["lanes"] += 1
            did = attrs.get("did")
            if did is not None and (key, did) in seen:
                continue
            if did is not None:
                seen.add((key, did))
            row["durs"].append(
                max(0.0, (sp["t1"] or sp["t0"]) - sp["t0"]) * 1e3)
    rows = []
    for (op, bucket, backend), row in table.items():
        durs = sorted(row["durs"])
        rows.append({
            "op": op, "bucket": bucket, "backend": backend,
            "dispatches": len(durs),
            "lanes": row["lanes"],
            "p50_ms": round(_pct(durs, 0.50), 4),
            "p95_ms": round(_pct(durs, 0.95), 4),
            "mean_ms": round(sum(durs) / len(durs), 4) if durs else 0.0,
            "total_ms": round(sum(durs), 3),
        })
    rows.sort(key=lambda r: -r["total_ms"])
    return rows


def format_waterfall(record, width=40):
    """One finished request as an indented ASCII waterfall — the
    flight-recorder dump format (and ``tools/trace_report.py``'s
    per-request view)."""
    spans = sorted(record["spans"], key=lambda s: (s["t0"], s["sid"]))
    if not spans:
        return "request %s: no spans" % record["rid"]
    t0 = min(s["t0"] for s in spans)
    t1 = max((s["t1"] if s["t1"] is not None else s["t0"])
             for s in spans)
    total = max(t1 - t0, 1e-9)
    depth = {}
    by_sid = {s["sid"]: s for s in spans}
    for s in spans:
        d, p = 0, s["parent"]
        while p is not None and p in by_sid:
            d += 1
            p = by_sid[p]["parent"]
        depth[s["sid"]] = d
    head = "request %s  (%.3f ms total%s%s)" % (
        record["rid"], total * 1e3,
        ", ERROR: %s" % record["error"] if record["error"] else "",
        ", DEADLINE BLOWN" if record["deadline_blown"] else "")
    lines = [head]
    for s in spans:
        end = s["t1"] if s["t1"] is not None else s["t0"]
        lo = int((s["t0"] - t0) / total * width)
        hi = max(lo + 1, int((end - t0) / total * width))
        bar = " " * lo + "#" * (hi - lo) + " " * (width - hi)
        attrs = s["attrs"]
        extras = " ".join(
            "%s=%s" % (k, v) for k, v in sorted(attrs.items())
            if k not in ("did",))
        lines.append("  [%s] %8.3fms %s%s%s" % (
            bar, (end - s["t0"]) * 1e3, "  " * depth[s["sid"]],
            s["name"], (" {%s}" % extras) if extras else ""))
    return "\n".join(lines)


# ------------------------------------------------------- the loop recorder
#: the phases of one turn of ``LMEngine._serve_loop``, in the order a turn
#: passes them.  They PARTITION the turn: phase ``i`` runs from stamp ``i``
#: to stamp ``i + 1`` of the turn's record, a phase the turn skipped has
#: no length, and a turn ends at the instant the next one begins.  A phase
#: includes any wait for the interpreter lock inside it.
#:
#: A turn begins when the step before has its tokens on the host.  The
#: ``loop.*``, ``prefill.*`` and ``step.*`` phases are what the device may
#: wait for: the tick, admission, a prompt chunk's arguments and its jit
#: call, the decode step's arguments, its jit call, the wait for its tokens
#: and what the next dispatch needs of them.  The ``ahead.*`` phases
#: (ISSUE 37) lie between the step's jit call and the wait, so the device
#: runs the step under them: the tokens of the step BEFORE go to their
#: lanes (``ahead.emit``), the queue is shed and admitted
#: (``ahead.admit``), and the next turn's chunk and step get every
#: argument that needs no token (``ahead.prepare``).  A turn that follows
#: such a stretch skips ``loop.admit`` and ``prefill.prepare`` and its
#: ``step.prepare`` is one put; a driver that cannot split its turn (the
#: speculative one, the megastep) leaves the three
#: empty and every turn in the old order.
PHASES = ("loop.tick", "loop.admit", "loop.wait", "prefill.prepare",
          "prefill.dispatch", "step.prepare", "step.dispatch",
          "ahead.emit", "ahead.admit", "ahead.prepare",
          "step.fetch", "step.emit")
(TICK, ADMIT, WAIT, PREFILL_PREPARE, PREFILL_DISPATCH, STEP_PREPARE,
 STEP_DISPATCH, AHEAD_EMIT, AHEAD_ADMIT, AHEAD_PREPARE, STEP_FETCH,
 STEP_EMIT) = range(len(PHASES))

#: columns of a turn record (one int64 row of ``LoopRecorder.turns()``):
#: the sequence number (from 1), the ``len(PHASES) + 1`` stamps
#: (``time.monotonic_ns()``), the ids of the prefill and the decode
#: program the turn dispatched (indices into ``LoopRecorder.programs``,
#: 0 for none), lanes holding a request, lanes the decode dispatch
#: advanced, queue depth after admission, tokens emitted
COL_SEQ = 0
COL_STAMPS = 1
COL_END = COL_STAMPS + len(PHASES)
COL_PREFILL_PROGRAM = COL_END + 1
COL_STEP_PROGRAM = COL_END + 2
COL_BUSY = COL_END + 3
COL_ACTIVE = COL_END + 4
COL_QUEUE = COL_END + 5
COL_TOKENS = COL_END + 6
#: the expert layers' counts of the turn's decode step (0 for a model
#: without any; ``ops/moe.py::held_part``): assignments to experts this
#: chip holds, and experts hit (summed over the expert layers)
COL_MOE_HELD = COL_END + 7
COL_MOE_HIT = COL_END + 8
#: the page steps the turn's dispatches handed the attention kernels
#: (lanes x table width, summed over layers, chunk and decode program
#: alike) and those of them whose page holds a key a query row may see
#: (``ops/pallas_kernels.py::live_pages``); the others the kernels skip
COL_ATTN_STEPS = COL_END + 9
COL_ATTN_LIVE = COL_END + 10
TURN_WIDTH = COL_END + 11
#: the column that holds the program a dispatch phase called
_PROGRAM_COL = {PREFILL_DISPATCH: COL_PREFILL_PROGRAM,
                STEP_DISPATCH: COL_STEP_PROGRAM}

#: columns of a dispatch record (one int64 row of
#: ``LoopRecorder.dispatches()``): one per call of a jitted program by the
#: engine's worker thread, whatever turn its outputs are fetched in.
#: ``DCOL_SEQ`` the dispatch's number, from 1, in call order (the order the
#: device runs them); ``DCOL_TURN`` the ``COL_SEQ`` of the turn whose code
#: made the call; ``DCOL_PROGRAM`` an index into ``LoopRecorder.programs``;
#: ``DCOL_PHASE`` the dispatch phase it was called from (``PREFILL_DISPATCH``:
#: a prompt chunk, ``STEP_DISPATCH``: a decode program, whatever its name);
#: ``DCOL_LANES`` the lanes a decode dispatch advances, 0 for a chunk; then
#: four stamps (``time.monotonic_ns()``, 0 where the dispatch never got
#: that far): ``DCOL_CALL`` just before the jit call (the turn row's phase
#: stamp: one clock read), ``DCOL_RETURNED`` the jit call back on the host,
#: ``DCOL_WAIT`` the host began to wait for the dispatch's outputs (never,
#: for a chunk that is no tail), ``DCOL_FETCHED`` the host had them (never,
#: where it did not wait or the dispatch raised); ``DCOL_FETCH_TURN`` the
#: ``COL_SEQ`` of the turn open at ``DCOL_FETCHED`` (``DCOL_TURN`` while
#: every fetch falls in the turn of its call); ``DCOL_TOKENS`` the tokens
#: the dispatch made, summed over its lanes, as its fetch found them (a
#: step that verifies a draft makes one or two a lane; 0: a driver that
#: does not say, or never fetched)
(DCOL_SEQ, DCOL_TURN, DCOL_PROGRAM, DCOL_PHASE, DCOL_LANES, DCOL_CALL,
 DCOL_RETURNED, DCOL_WAIT, DCOL_FETCHED, DCOL_FETCH_TURN,
 DCOL_TOKENS) = range(11)
DISPATCH_WIDTH = 11

#: one finished (or failed, shed, cancelled) request: stamps in
#: nanoseconds on the monotonic clock, 0 where the request never got
#: that far; ``token_ns`` (an ``array('q')``) holds the stamp of every
#: token the engine emitted for it, in order — ``n_new`` of them for a
#: request that finished, and their sum over requests is the engine's
#: ``tokens_out`` counter (a request a weight swap put back in the queue
#: keeps the stamps of the tokens that swap threw away: the counter
#: counted them too)
RequestRecord = collections.namedtuple(
    "RequestRecord", "enqueue admit first_token done prompt_len n_new "
    "tokens_out lane outcome token_ns")

#: one HTTP POST: stamps at the request line read, the handler entered,
#: the handler returned, the reply written; and the status code.  The
#: HTTP layer's own time is ``(reply - recv) - (result - submit)``.
HttpRecord = collections.namedtuple(
    "HttpRecord", "recv submit result reply status")

_recorders = collections.deque(maxlen=4)
_http = collections.deque(maxlen=8192)


def recorders():
    """The loop recorders of this process, oldest first: the newest four
    that an engine's ``start()`` made, stopped engines' included (tier-1
    builds hundreds of engines in one process; the benchmark reads after
    ``api.stop()``)."""
    return list(_recorders)


def register(recorder):
    """Make ``recorder`` findable by :func:`recorders` (an engine's
    ``start()`` does)."""
    _recorders.append(recorder)
    return recorder


def note_http(recv, submit, result, reply, status):
    """One HTTP POST's stamps (``restful_api.py::do_POST``); ``deque``
    appends need no lock."""
    _http.append(HttpRecord(recv, submit, result, reply, status))


def http_records():
    """The newest HTTP records of this process, oldest first."""
    return list(_http)


def _ring_copy(ring, head, last):
    """The rows a ring of sequence-numbered records (column 0, from 1;
    ``head()`` the newest committed) holds, oldest first, as a copy that
    the ring's one writer cannot tear: see :meth:`LoopRecorder.turns`."""
    capacity = len(ring)
    h0 = head()
    used = min(h0, capacity)            # a ring not yet full: its head
    rows = ring[:used].copy()
    h1 = head()
    seq = rows[:, 0]
    # the writer replaced (h0, h1] while the copy ran and may be
    # committing h1 + 1 now: what those slots held before is dropped
    keep = (seq > 0) & (seq <= h0) & (seq > h1 + 1 - capacity) \
        & (((seq - 1) & (capacity - 1)) == numpy.arange(used))
    out = rows[keep]
    out = out[numpy.argsort(out[:, 0], kind="stable")]
    if last is not None:
        out = out[len(out) - min(int(last), len(out)):]
    return out


class LoopRecorder:
    """The engine loop's always-on recorder; see the module docstring.

    ONE writer, the engine's worker thread, fills the turn ring: it
    builds the open turn in a scratch list and commits it to the ring as
    one row assignment, so a reader's copy never holds a half-written
    turn.  The same thread fills the dispatch ring (``DCOL_*``): a row
    goes in whole at the jit call, and each later stamp of it is one
    aligned store into the row its handle names, so a reader finds a
    dispatch in flight with the stamps it has and never a mix of two.
    Request records go to a bounded deque when the request's
    future settles (the worker thread but for a client's own cancel of
    a queued request; a deque append needs no lock).  Nothing here takes
    a lock, touches the device, or depends on a :class:`SpanTracer`."""

    #: turns kept: a power of two; an hour and a half at 12 turns a
    #: second, seven minutes at 150
    CAPACITY = 1 << 16
    #: finished requests kept
    REQUESTS = 4096

    _synchronized_externally = ("engine worker thread (single writer); "
                                "readers copy")

    def __init__(self, name="lm", capacity=CAPACITY, requests=REQUESTS):
        if capacity < 1 or capacity & (capacity - 1):
            raise ValueError("capacity must be a power of two (got %r)"
                             % (capacity,))
        self.name = name
        self.capacity = capacity
        self._mask = capacity - 1
        self._ring = numpy.zeros((capacity, TURN_WIDTH), numpy.int64)
        self._cur = [0] * TURN_WIDTH
        self._blank = (0,) * TURN_WIDTH
        #: turns committed so far (the newest record's sequence number)
        self.head = 0
        #: the dispatch ring outlasts the turn ring: a turn makes at most
        #: two dispatches
        self._dmask = 2 * capacity - 1
        self._dring = numpy.zeros((2 * capacity, DISPATCH_WIDTH),
                                  numpy.int64)
        #: dispatches recorded so far (the newest row's ``DCOL_SEQ``)
        self.dispatch_head = 0
        #: program names by id; id 0 is "none dispatched"
        self.programs = [""]
        self._program_ids = {}
        self._requests = collections.deque(maxlen=requests)

    # ------------------------------------------------ writer: engine thread
    def turn(self):
        """A turn of the loop begins now; the open one ends at the same
        instant."""
        t = time.monotonic_ns()
        cur = self._cur
        if cur[COL_STAMPS]:
            self._commit(t)
        cur[COL_STAMPS] = t

    def close(self):
        """The loop has left its last turn."""
        if self._cur[COL_STAMPS]:
            self._commit(time.monotonic_ns())

    def _commit(self, t_end):
        cur = self._cur
        cur[COL_END] = t_end
        # a phase the turn skipped starts where the next one starts
        for i in range(COL_END - 1, COL_STAMPS, -1):
            if not cur[i]:
                cur[i] = cur[i + 1]
        seq = self.head + 1
        cur[COL_SEQ] = seq
        self._ring[(seq - 1) & self._mask] = cur
        self.head = seq
        cur[:] = self._blank

    def mark(self, phase):
        """Phase ``phase`` of the open turn begins now (and the one
        before it ends)."""
        self._cur[COL_STAMPS + phase] = time.monotonic_ns()

    def dispatch(self, phase, fn, lanes=0):
        """:meth:`mark` for a dispatch phase, and the dispatch's own
        record: ``fn`` is the jitted program about to be called (recorded
        by its name, the one the device trace shows with ``jit_`` before
        it); ``lanes`` the lanes a decode dispatch advances.  Returns the
        record's handle (its ``DCOL_SEQ``) for :meth:`returned`,
        :meth:`waiting` and :meth:`fetched`; the engine may keep it with
        the program's outputs for as long as their fetch is outstanding.
        A dispatch that raises leaves its record with the stamps it had."""
        name = fn.__name__
        pid = self._program_ids.get(name)
        if pid is None:
            pid = self._program_ids[name] = len(self.programs)
            self.programs.append(name)
        cur = self._cur
        cur[_PROGRAM_COL[phase]] = pid
        if lanes:
            cur[COL_ACTIVE] = lanes
        t = cur[COL_STAMPS + phase] = time.monotonic_ns()
        seq = self.dispatch_head + 1
        self._dring[(seq - 1) & self._dmask] = (
            seq, self.head + 1, pid, phase, lanes, t, 0, 0, 0, 0, 0)
        self.dispatch_head = seq
        return seq

    def returned(self, seq):
        """The jit call of dispatch ``seq`` is back on the host."""
        self._dring[(seq - 1) & self._dmask, DCOL_RETURNED] = \
            time.monotonic_ns()

    def waiting(self, seq, phase=None):
        """The host begins to wait for the outputs of dispatch ``seq``;
        with ``phase``, that phase of the open turn begins at the same
        clock reading (:meth:`mark`)."""
        t = time.monotonic_ns()
        self._dring[(seq - 1) & self._dmask, DCOL_WAIT] = t
        if phase is not None:
            self._cur[COL_STAMPS + phase] = t

    def fetched(self, seq, phase=None, tokens=0):
        """The host has the outputs of dispatch ``seq``; ``phase`` as for
        :meth:`waiting`; ``tokens`` how many tokens they hold, over all
        lanes, where the driver says."""
        t = time.monotonic_ns()
        row = self._dring[(seq - 1) & self._dmask]
        row[DCOL_FETCH_TURN] = self.head + 1
        row[DCOL_FETCHED] = t
        row[DCOL_TOKENS] = tokens
        if phase is not None:
            self._cur[COL_STAMPS + phase] = t

    def lanes(self, busy, queued):
        self._cur[COL_BUSY] = busy
        self._cur[COL_QUEUE] = queued

    def moe(self, held, hit):
        """One more decode step's expert counts reached the host in the
        open turn (a turn that drains fetches two steps' outputs)."""
        cur = self._cur
        cur[COL_MOE_HELD] += held
        cur[COL_MOE_HIT] += hit

    def attn_pages(self, given, live):
        """One more dispatch of the open turn through the attention
        kernels (a turn may hold a chunk and a decode step)."""
        cur = self._cur
        cur[COL_ATTN_STEPS] += given
        cur[COL_ATTN_LIVE] += live

    def emitted(self, request, n):
        """``n`` tokens of ``request`` reached the host now: the stamp of
        each, and the turn's count."""
        t = time.monotonic_ns()
        if n == 1:
            request.token_ns.append(t)
        else:
            request.token_ns.extend([t] * n)
        self._cur[COL_TOKENS] += n

    def finished(self, request, outcome):
        """The request's future settled (any thread)."""
        stamps = request.token_ns
        self._requests.append(RequestRecord(
            request.t_enq_ns, request.t_admit_ns,
            stamps[0] if stamps else 0, time.monotonic_ns(),
            request.true_len, request.n_new, len(stamps), request.lane,
            outcome, stamps))

    # -------------------------------------------------------------- readers
    def turns(self, last=None):
        """A copy of the turns kept, oldest first, as an int64 array of
        ``TURN_WIDTH`` columns (``COL_*``).  A record the writer replaced
        while the copy was taken, or whose sequence number is not the one
        its place in the ring calls for, is dropped."""
        return _ring_copy(self._ring, lambda: self.head, last)

    def dispatches(self, last=None):
        """:meth:`turns`' twin over the dispatch ring: a copy of the
        dispatch records kept, oldest first, ``DISPATCH_WIDTH`` columns
        (``DCOL_*``), replaced and misplaced rows dropped alike.  The
        newest rows may be dispatches in flight: later stamps still 0."""
        return _ring_copy(self._dring, lambda: self.dispatch_head, last)

    def requests(self):
        """The request records kept, oldest first."""
        return list(self._requests)

    def chrome_events(self, tid, last=256):
        """The newest ``last`` turns as Chrome-trace events on track
        ``tid`` (one slice per phase that took time), and on track
        ``tid + 1`` their dispatches (one slice each, named by program,
        from its call to the moment the host had its outputs, or to the
        jit call's return where it never fetched them: a dispatch in
        flight lies over the ``ahead.*`` phases it covers); microseconds
        against the process origin like the tracer's spans."""
        origin = int(_ORIGIN * 1e9)
        out = [{"ph": "M", "pid": 1, "tid": tid, "name": "thread_name",
                "args": {"name": "engine loop %s" % self.name}},
               {"ph": "M", "pid": 1, "tid": tid + 1, "name": "thread_name",
                "args": {"name": "engine loop %s dispatches" % self.name}}]
        turns = self.turns(last).tolist()
        for row in turns:
            args = {"turn": row[COL_SEQ], "busy": row[COL_BUSY],
                    "active": row[COL_ACTIVE], "queue": row[COL_QUEUE],
                    "tokens": row[COL_TOKENS]}
            for i, phase in enumerate(PHASES):
                t0, t1 = row[COL_STAMPS + i], row[COL_STAMPS + i + 1]
                if t1 <= t0:
                    continue
                named = args
                if i in _PROGRAM_COL:
                    named = dict(
                        args, program=self.programs[row[_PROGRAM_COL[i]]])
                out.append({"ph": "X", "pid": 1, "tid": tid, "name": phase,
                            "cat": "loop", "ts": (t0 - origin) / 1e3,
                            "dur": (t1 - t0) / 1e3, "args": named})
        first = turns[0][COL_SEQ] if turns else self.head + 1
        for row in self.dispatches(2 * len(turns) + 2).tolist():
            t0 = row[DCOL_CALL]
            t1 = row[DCOL_FETCHED] or row[DCOL_RETURNED]
            if row[DCOL_TURN] < first or t1 <= t0:
                continue
            out.append({"ph": "X", "pid": 1, "tid": tid + 1,
                        "name": self.programs[row[DCOL_PROGRAM]],
                        "cat": "dispatch", "ts": (t0 - origin) / 1e3,
                        "dur": (t1 - t0) / 1e3,
                        "args": {"dispatch": row[DCOL_SEQ],
                                 "turn": row[DCOL_TURN],
                                 "fetch_turn": row[DCOL_FETCH_TURN],
                                 "lanes": row[DCOL_LANES],
                                 "tokens": row[DCOL_TOKENS]}})
        return out

"""REST serving — HTTP JSON in, forward pass out.

Ref: veles/restful_api.py::RESTfulAPI [M] (SURVEY §2.1, §3.4): feed JSON
input through a trained forward pass over HTTP.  stdlib http.server on a
background thread (the reference used Twisted web); the forward is the
fused chain jitted once, so per-request work is one device dispatch.

Two serving modes (ISSUE 1):

- DIRECT (default) — each request runs its own dispatch; right for
  single-user/debug serving.
- BATCHED — :meth:`RESTfulAPI.enable_batching` routes ``/predict``
  through :class:`veles_tpu.serving.MicroBatcher`: concurrent requests
  coalesce into one padded power-of-two-bucket dispatch, a full queue
  answers HTTP 429 with ``Retry-After``, and requests queued past their
  deadline are shed with 503.  ``serve_lm(slots=N)`` likewise routes
  greedy decode through :class:`veles_tpu.serving.LMEngine` (continuous
  batching over a shared KV cache); sampled requests keep the direct
  path.

Error contract: every non-200 reply is structured JSON
(``{"error": ...}``) with a meaningful status — 400 malformed request,
404 unknown path, 413 oversized body (``max_body``), 429 overload
(+``Retry-After`` seconds), 500 server fault, 503 shed past deadline.
``GET /metrics.json`` (snapshot) and ``GET /metrics`` (Prometheus text)
expose the serving counters on the serving port itself.

Usage::

    api = RESTfulAPI(workflow)          # a trained StandardWorkflow
    api.start(port=0)                   # 0 → ephemeral
    ... POST {"input": [[...]]} to http://host:port/predict ...
    api.stop()
"""

from __future__ import annotations

import json
import threading
import time
import urllib.parse
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy

from veles_tpu.logger import Logger


class RESTfulAPI(Logger):
    def __init__(self, workflow, normalizer=None, forward=None,
                 handler=None, metrics=None, max_body=16 << 20,
                 faults=None, tracer=None, telemetry=None, slo=None):
        self.workflow = workflow
        #: optional TimeSeriesStore (ISSUE 14): continuous telemetry
        #: over the serving metrics — ``GET /timeseries.json?window=S``
        #: (owned by serve_lm; stopped with the server)
        self.telemetry = telemetry
        #: optional SLOMonitor (ISSUE 14): burn-rate objectives over
        #: the store — ``GET /slo.json``
        self.slo = slo
        #: optional serving FaultPlan (ISSUE 10): the ``http.request``
        #: site fires per POST — transient InjectedHTTPError replies
        #: (the retryable-infrastructure-blip shape) and latency
        #: spikes; a no-op when None
        self.faults = faults
        #: optional serving SpanTracer (ISSUE 12): every POST opens an
        #: ``http.request`` root span keyed by the request id, and
        #: ``GET /trace.json?last=N`` exports the flight recorder as
        #: Chrome-trace JSON; a no-op when None
        self.tracer = tracer
        #: optional HealthChecker owned by serve_lm (stopped with the
        #: server)
        self.health_checker = None
        #: optional ModelManager publisher loop owned by serve_lm
        #: (stopped with the server, before the engines)
        self.model_manager = None
        #: optional input normalizer (a loader's fitted normalizer) applied
        #: before the forward, so clients send raw feature scale
        self.normalizer = normalizer
        self._server = None
        self._thread = None
        #: explicit forward callable (batch ndarray -> ndarray) — used by
        #: artifact serving, where there is no workflow at all
        self._forward = forward
        #: full-request handler (payload dict -> response dict); when set
        #: it replaces the predict flow entirely — used by serve_lm, whose
        #: requests carry decoding knobs beyond "input"
        self._handler = handler
        #: serving counters (ServingMetrics) — end-to-end latency and
        #: response counts are recorded HERE (engines own queue/dispatch
        #: facts), so sharing one instance with an engine double-counts
        #: nothing
        self.metrics = metrics
        #: request bodies beyond this are refused with 413 before parsing
        self.max_body = int(max_body)
        #: optional MicroBatcher the predict path routes through
        self.batcher = None
        #: optional LMEngine owned by serve_lm (stopped with the server)
        self.lm_engine = None

    # ------------------------------------------------------------- inference
    def _ensure_forward(self):
        if self._forward is not None:
            return self._forward
        runner = getattr(self.workflow, "_fused_runner", None)
        if runner is not None:
            fn = runner.eval_forward()

            def forward(x):
                return numpy.asarray(fn(runner.state, x))
        else:
            units = self.workflow.forwards

            def forward(x):
                import jax.numpy as jnp
                h = jnp.asarray(x)
                for unit in units:
                    entry = {}
                    if unit.has_params:
                        entry = {"w": unit.weights.devmem}
                        if unit.include_bias:
                            entry["b"] = unit.bias.devmem
                    h = unit.apply_fused(h, entry, None, False)
                return numpy.asarray(h)
        self._forward = forward
        return forward

    def _infer_sample_shape(self):
        """Best-effort input sample shape (for bucket warmup): the
        loader's minibatch row shape when a workflow is attached."""
        data = getattr(getattr(self.workflow, "loader", None),
                       "minibatch_data", None)
        shape = getattr(data, "shape", None)
        return tuple(shape[1:]) if shape and len(shape) > 1 else None

    def enable_batching(self, max_batch=64, queue_depth=128,
                        batch_wait_s=0.002, deadline_s=2.0,
                        sample_shape=None, metrics=None,
                        name="predict"):
        """Route ``/predict`` through a :class:`MicroBatcher` (started
        with the server).  Call before :meth:`start`.  ``name`` labels
        this engine's metrics row — give each server its own when
        several batched servers share one process (same-name engines
        replace each other in the /metrics registry: the RESTART
        semantics)."""
        from veles_tpu.serving import MicroBatcher
        from veles_tpu.serving import metrics as metrics_mod
        if sample_shape is None:
            sample_shape = self._infer_sample_shape()
        # a FRESH registered instance per enable: a (re)started server
        # must start its counters at zero, not atop the previous run's
        m = metrics or metrics_mod.new(name)
        self.batcher = MicroBatcher(
            self._ensure_forward(), max_batch=max_batch,
            queue_depth=queue_depth, batch_wait_s=batch_wait_s,
            deadline_s=deadline_s, sample_shape=sample_shape,
            metrics=m, name=name, faults=self.faults,
            tracer=self.tracer)
        self.metrics = m
        return self

    def predict(self, batch):
        x = numpy.asarray(batch, numpy.float32)
        if self.normalizer is not None:
            x = self.normalizer.apply(x)
        if self.batcher is not None:
            probs = self.batcher.submit(x)
        else:
            probs = self._ensure_forward()(x)
        return {"output": probs.tolist(),
                "argmax": probs.reshape(len(probs), -1)
                               .argmax(axis=1).tolist()}

    # ---------------------------------------------------------------- server
    def start(self, host="127.0.0.1", port=8180):
        from veles_tpu.serving import tracing
        from veles_tpu.serving.batcher import DeadlineExceeded, Overloaded
        api = self
        if self.batcher is not None:
            self.batcher.start()

        class Handler(BaseHTTPRequestHandler):
            def _drain(self, length, cap=64 << 20):
                """Discard an unread request body (bounded) before an
                early error reply — closing with bytes still in flight
                RSTs the connection and the client never sees the
                structured error it was owed."""
                left = min(length, cap)
                while left > 0:
                    chunk = self.rfile.read(min(left, 1 << 16))
                    if not chunk:
                        return
                    left -= len(chunk)

            def _reply(self, code, payload, headers=()):
                body = json.dumps(payload).encode("utf-8")
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in headers:
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                split = urllib.parse.urlsplit(self.path)
                path = split.path.rstrip("/")
                if path == "/metrics.json" and api.metrics is not None:
                    self._reply(200, api.metrics.snapshot())
                elif path == "/timeseries.json" \
                        and api.telemetry is not None:
                    # continuous telemetry (ISSUE 14): every metrics
                    # family's windowed rates/gauges/percentiles plus
                    # raw ring points — ?window=S trims the window
                    query = urllib.parse.parse_qs(split.query)
                    window = 60.0
                    try:
                        if query.get("window"):
                            window = float(query["window"][0])
                            # not (window > 0) also catches NaN —
                            # 'nan <= 0' is False, and a NaN window
                            # would serialize as a non-strict literal
                            if not (window > 0) \
                                    or window == float("inf"):
                                raise ValueError
                    except ValueError:
                        self._reply(400, {"error": "window must be a "
                                          "positive number of "
                                          "seconds"})
                        return
                    self._reply(200, api.telemetry.snapshot(
                        window_s=window))
                elif path == "/slo.json" and api.slo is not None:
                    # burn-rate objectives (ISSUE 14)
                    self._reply(200, api.slo.snapshot())
                elif path == "/status":
                    # the human panel (ISSUE 14): plain text, curl-able
                    body = render_status(
                        metrics=api.metrics, telemetry=api.telemetry,
                        slo=api.slo).encode()
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "text/plain; charset=utf-8")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                elif path == "/trace.json" and api.tracer is not None:
                    # the flight recorder as Chrome-trace/Perfetto JSON
                    # (ISSUE 12): ?last=N trims to the newest N
                    # requests; the served engines' loop recorders ride
                    # along as `engine loop` tracks (ISSUE 26), each
                    # with its dispatches' track (ISSUE 38); load at
                    # ui.perfetto.dev
                    query = urllib.parse.parse_qs(split.query)
                    last = None
                    try:
                        if query.get("last"):
                            last = int(query["last"][0])
                    except ValueError:
                        self._reply(400, {"error": "last must be an "
                                          "integer"})
                        return
                    engines = getattr(api.lm_engine, "replicas",
                                      [api.lm_engine])
                    self._reply(200, api.tracer.export_chrome(
                        last=last,
                        loops=[e.recorder for e in engines
                               if getattr(e, "recorder", None)
                               is not None]))
                elif path == "/metrics":
                    from veles_tpu.serving import metrics as metrics_mod
                    # merge this server's instance into the registry
                    # render (one # TYPE line per family) even when a
                    # later engine evicted it from the registry
                    instances = metrics_mod.registered()
                    if api.metrics is not None \
                            and api.metrics not in instances:
                        instances.append(api.metrics)
                    body = metrics_mod.render_instances(instances).encode()
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "text/plain; version=0.0.4")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                else:
                    self._reply(404, {"error": "unknown path %r"
                                      % self.path})

            def do_POST(self):
                # request-id stamping (ISSUE 12 satellite): echo the
                # client's X-Request-Id (or mint one) on EVERY reply —
                # success and structured error — so client logs,
                # traces, and load_gen records join on one key
                recv = time.monotonic_ns()
                t0 = recv * 1e-9
                #: the loop recorder's HTTP record (ISSUE 26): stamps
                #: around the handler call, written with the reply
                self._stamps = [0, 0]
                rid = (self.headers.get("X-Request-Id") or "").strip()
                rid = rid[:64] or uuid.uuid4().hex[:16]
                ctx = None
                if api.tracer is not None:
                    ctx = api.tracer.start_request(
                        rid=rid, name="http.request", cat="http",
                        attrs={"path": self.path})
                #: set by _handle_post's DeadlineExceeded branch — a
                #: 503 alone is not proof of a deadline (injected
                #: transient 503s are not sheds)
                self._shed = False
                code, payload, headers = 500, {"error": "internal"}, []
                try:
                    if api.tracer is not None:
                        # ctx None = the sampler skipped this request:
                        # bind the sentinel so the router/engine below
                        # do not re-roll and root partial trees
                        with tracing.use(ctx if ctx is not None
                                         else tracing.SAMPLED_OUT):
                            code, payload, headers = \
                                self._handle_post(t0)
                    else:
                        code, payload, headers = self._handle_post(t0)
                finally:
                    if ctx is not None:
                        # 5xx replies dump the flight recorder; only a
                        # real DeadlineExceeded is the deadline-blown
                        # shape (an injected transient 503 is not)
                        api.tracer.finish_request(
                            ctx,
                            error=("http %d" % code) if code >= 500
                            else None,
                            deadline=self._shed,
                            attrs={"status": code})
                if isinstance(payload, dict):
                    payload.setdefault("request_id", rid)
                self._reply(code, payload,
                            list(headers) + [("X-Request-Id", rid)])
                tracing.note_http(recv, *self._stamps,
                                  time.monotonic_ns(), code)

            def _handle_post(self, t0):
                """Run one POST; returns (code, json_payload, headers)
                — the reply itself (request-id stamp, trace-root
                closure) happens in do_POST."""
                try:
                    length = int(self.headers.get("Content-Length", 0))
                except ValueError:
                    return 400, {"error": "malformed "
                                 "Content-Length header"}, []
                if self.path.rstrip("/") != "/predict":
                    self._drain(length)
                    return 404, {"error": "unknown path %r — POST "
                                 "/predict" % self.path}, []
                if length > api.max_body:
                    self._drain(length)
                    return 413, {
                        "error": "request body %d bytes exceeds the "
                                 "%d limit" % (length, api.max_body)}, []
                try:    # parse: malformed payloads are 400, full stop
                    payload = json.loads(self.rfile.read(length))
                    batch = payload["input"]     # both flows require it
                except (json.JSONDecodeError, KeyError, TypeError) as e:
                    return 400, {"error": "%s: %s"
                                 % (type(e).__name__, e)}, []
                if api.faults is not None:
                    from veles_tpu.serving.faults import InjectedHTTPError
                    try:
                        api.faults.fire("http.request")
                    except InjectedHTTPError as e:
                        # a transient HTTP-level fault: structured
                        # reply at the injected status, Retry-After on
                        # the retryable codes — the shape load_gen's
                        # failure classes and the chaos harness assert
                        headers = []
                        if e.code in (429, 503):
                            headers = [("Retry-After", "%d" % max(
                                1, int(e.retry_after + 0.999)))]
                        return e.code, {
                            "error": str(e),
                            "retry_after": e.retry_after}, headers
                try:    # dispatch
                    self._stamps[0] = time.monotonic_ns()
                    try:
                        result = (api._handler(payload)
                                  if api._handler is not None
                                  else api.predict(batch))
                    finally:
                        self._stamps[1] = time.monotonic_ns()
                except Overloaded as e:
                    # Retry-After is integer delta-seconds per RFC 9110
                    # (the exact float rides in the JSON body)
                    return 429, {"error": str(e),
                                 "retry_after": e.retry_after}, \
                        [("Retry-After", "%d" % max(
                            1, int(e.retry_after + 0.999)))]
                except DeadlineExceeded as e:
                    self._shed = True
                    return 503, {"error": str(e)}, [("Retry-After",
                                                     "1")]
                except (TypeError, ValueError) as e:
                    # input-validation contract: shape/range/length
                    # complaints raised while processing the payload
                    # (batcher shape check, serve_lm prompt bounds, bad
                    # knob types) are the CLIENT's error
                    return 400, {"error": "%s: %s"
                                 % (type(e).__name__, e)}, []
                except Exception as e:   # noqa: BLE001 — server fault
                    if api.metrics is not None:
                        api.metrics.record_error()
                    api.warning("request failed: %s", e)
                    return 500, {"error": "%s: %s"
                                 % (type(e).__name__, e)}, []
                if api.metrics is not None:
                    api.metrics.record_response(time.monotonic() - t0)
                return 200, result, []

            def log_message(self, fmt, *args):
                api.debug("restful: " + fmt, *args)

        self._server = ThreadingHTTPServer((host, port), Handler)
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        daemon=True)
        self._thread.start()
        self.port = self._server.server_address[1]
        self.info("REST serving on http://%s:%d/predict", host, self.port)
        return self

    def stop(self):
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None
        if self.telemetry is not None:
            # the sampler reads engine metrics: stop it before the
            # engines so a mid-shutdown tick never races a teardown
            self.telemetry.stop()
        if self.batcher is not None:
            self.batcher.stop()
        if self.model_manager is not None:
            # the publisher must stop BEFORE the fleet it deploys to
            self.model_manager.stop()
            self.model_manager = None
        if self.health_checker is not None:
            # the prober must stop BEFORE its engines do, or its next
            # probe lands on a stopped engine and counts a fake failure
            self.health_checker.stop()
            self.health_checker = None
        if self.lm_engine is not None:
            self.lm_engine.stop()


def render_status(metrics=None, telemetry=None, slo=None, window_s=60.0):
    """The ``GET /status`` text panel (ISSUE 14): the operator's
    one-glance view — live gauges, windowed rates and tail latency
    from the telemetry store, every SLO objective's state and burn.
    Plain text by design: readable in a terminal over curl, no client
    tooling required."""
    from veles_tpu.serving.metrics import monotonic_offset
    lines = ["veles_tpu serving status",
             "sampled_at %.3fs (monotonic offset)"
             % monotonic_offset(), ""]
    if metrics is not None:
        snap = metrics.snapshot()
        lines.append("[engine %s]" % snap["name"])
        lines.append(
            "  requests %d  responses %d  errors %d  429 %d  shed %d"
            % (snap["requests"], snap["responses"], snap["errors"],
               snap["rejected"], snap["shed"]))
        g = snap["gauges"]
        lines.append(
            "  queue_depth %g  slots %g/%g  kv_pages_free %g/%g  "
            "compile_programs %g"
            % (g.get("queue_depth", 0), g.get("slots_busy", 0),
               g.get("slots_total", 0), g.get("kv_pages_free", 0),
               g.get("kv_pages_total", 0),
               g.get("compile_programs", 0)))
        lines.append(
            "  ewma ttft %.4fs  decode_step %.4fs  mfu_live %s"
            % (snap["ewma"].get("ttft", 0.0),
               snap["ewma"].get("decode_step", 0.0),
               g.get("mfu_live", "-")))
        lines.append("")
    if telemetry is not None:
        lines.append("[telemetry — last %gs of %d samples @ %gs]"
                     % (window_s, telemetry.samples,
                        telemetry.interval_s))
        for key in telemetry.sources():
            rq = telemetry.window("%s.counter.responses" % key,
                                  window_s)
            er = telemetry.window("%s.counter.errors" % key, window_s)
            tt = telemetry.window("%s.hist.ttft" % key, window_s)
            ds = telemetry.window("%s.hist.decode_step" % key,
                                  window_s)
            lines.append(
                "  %-24s %7.2f resp/s  %5.2f err/s  "
                "ttft p95 %ss  step p95 %ss"
                % (key,
                   rq["rate_per_s"] if rq else 0.0,
                   er["rate_per_s"] if er else 0.0,
                   tt["p95"] if tt else "-",
                   ds["p95"] if ds else "-"))
        lines.append("")
    if slo is not None:
        snap = slo.snapshot()
        lines.append("[slo — worst state: %s, %d page(s) total]"
                     % (snap["worst_state_name"],
                        snap["pages_total"]))
        for row in snap["objectives"]:
            burns = " ".join("%gs=%.2fx" % (b["window_s"], b["burn"])
                             for b in row["burn_rates"])
            lines.append("  %-5s %-24s %-12s target %g  burn %s"
                         % (row["state_name"].upper(), row["source"],
                            row["objective"], row["target"], burns))
        lines.append("")
    return "\n".join(lines) + "\n"


def serve_lm(workflow, host="127.0.0.1", port=8180, max_new=256,
             slots=0, queue_depth=64, deadline_s=30.0,
             prefix_cache=0, prefill_chunk=0, spec_k=0,
             queue_tokens=0, paged_kv=0, attn_kernel=None,
             megastep=0, tp=0, replicas=1, router="metrics",
             health=False, health_interval_s=1.0, hedge=0.0,
             retries=0, fault_plan=None, model_dir=None,
             publish_interval_s=5.0, canary=1, canary_watch_s=2.0,
             auto_rollback=True, trace=None, trace_last=256,
             telemetry=0.0, slo=None):
    """Serve a trained transformer-trainer workflow (e.g. char_lm) for
    autoregressive continuation: POST ``{"input": [[tok, ...]],
    "n_new": N, "temperature": T, "top_k": K, "seed": S}`` to
    ``/predict`` returns ``{"tokens": [[...]]}`` — prompt plus
    continuation per row (with ``"drafts": true`` and an engine whose
    model drafts with its own module also ``"drafts"``: per row, ``[n,
    token]`` where the module put ``token`` for the n-th new token).

    ``slots > 0`` starts a :class:`veles_tpu.serving.LMEngine` and
    routes GREEDY requests (temperature 0, the default) through
    slot-based continuous batching: concurrent prompts decode side by
    side over one paged KV pool, each request gets its exact
    ``n_new`` (no tier overshoot), and output is bit-identical to the
    direct path.  Sampled requests (temperature > 0) always take the
    direct path below.

    The engine keeps KV in fixed-size pages behind per-lane page
    tables (ISSUE 6): ``prefill_chunk=C`` is the page and the prompt
    chunk in tokens (prompts run as C-token chunks interleaved with
    decode; ``max_len`` must be divisible by it; 0 = the largest
    divisor of ``max_len`` not above 32) and ``paged_kv=N`` the pool's
    size in pages (0 or ``True`` = every lane's whole table, ``slots x
    max_len / C``) — lanes reserve only their own span, and a request
    the pool cannot place queues or sheds (429/503) instead of wedging.
    On it ride (ISSUE 4) ``prefix_cache=N``, which caches N chunks of
    prompt KV in a radix trie (shared system prompts prefill once; hits
    are zero-copy page references with copy-on-write), ``spec_k=K``,
    prompt-lookup speculative decoding (several tokens per dispatch on
    repetitive text), and ``queue_tokens=T``, which budgets admission
    by queued prompt tokens.
    ``attn_kernel='auto'`` (ISSUE 7) swaps the engine's
    attention for the Pallas flash-decode / fused-prefill kernels on
    real TPU hardware, with an automatic XLA fallback (off-TPU or
    unsupported geometry — logged once, counted on ``/metrics`` as
    ``attn_kernel_fallbacks``); ``'force'`` insists off-TPU (interpret
    mode, test gear); ``None`` follows
    ``attention.set_attention_backend('flash_serve')``.
    ``megastep=K`` (ISSUE 13) fuses K decode iterations — propose →
    verify → accept legs when ``spec_k`` is on — into one jitted
    ``lax.scan`` dispatch per engine tick: admission, deadline
    shedding, completion detection, weight-swap application and
    tracing all move to MEGASTEP BOUNDARIES (a deadline expiring
    mid-megastep sheds at the next boundary; see USAGE.md "Megastep
    decode").  All preserve bit-identical greedy output; see
    ``veles_tpu/serving/lm_engine.py``.

    SHARDED SERVING (ISSUE 8): ``tp=N`` runs each engine's decode
    tensor-parallel over an N-device mesh (weights head-sharded,
    KV head-wise — greedy output still bit-identical); ``replicas=R``
    builds R independent engines (each on its own device slice —
    ``R×max(tp,1)`` devices when tp >= 2) behind a
    :class:`veles_tpu.serving.Router` placing each request by live
    metrics signals (``router='metrics'``; ``'round_robin'`` for the
    skew baseline).  Routed responses carry a per-row ``"replicas"``
    list so closed-loop clients (``tools/load_gen.py --lm``) can
    measure balance; ``/metrics`` renders per-replica
    ``{replica="i"}`` labeled families and ``/metrics.json`` embeds
    every replica snapshot.  Admission (429/503) is unchanged behind
    the router.

    The RESILIENCE layer (ISSUE 10, all default-off): ``retries=N``
    re-places a request whose replica FAULTED (not sheds, not client
    errors) on a different replica with exponential jittered backoff;
    ``hedge=T`` duplicates a request outstanding past T seconds (T<0:
    1.5× the live latency p95) on a second replica, first complete
    wins, loser cancelled; ``health=True`` starts a
    :class:`veles_tpu.serving.HealthChecker` that auto-quarantines a
    wedged/failing replica through the router's drain path and
    re-admits it after a cooldown (half-open circuit breaker;
    ``replica_health_state`` / ``circuit_open_total`` on /metrics).
    Any of the three wraps a single replica in the bit-identical
    degenerate router.  ``fault_plan`` attaches a
    :class:`veles_tpu.serving.FaultPlan` (CLI ``--fault-plan FILE``)
    arming the deterministic fault-injection sites — test/chaos gear,
    never armed in production.  See USAGE.md "Failure semantics".

    ZERO-DOWNTIME WEIGHT UPDATES (ISSUE 11): ``model_dir=DIR`` starts
    a :class:`veles_tpu.serving.ModelManager` publisher loop watching
    DIR for the snapshotter's ``*_current.*`` checkpoints every
    ``publish_interval_s`` seconds — each new file is validated and
    loaded OFF the hot path, then rolled across the fleet via
    ``Router.deploy``: ``canary=N`` replicas swap and answer a
    parity probe first, traffic steers at them for ``canary_watch_s``
    seconds while the deploy watches the live health signals (0
    reduces the watch to one instantaneous signal check), and a bad
    canary auto-rolls back
    (``auto_rollback=False`` leaves the mixed fleet for the operator).
    In-flight requests finish on the weights they started on; every
    engine-path reply carries a per-row ``"weights_version"`` stamp
    so clients can observe the cutover (``tools/load_gen.py --lm``
    aggregates it).  See USAGE.md "Zero-downtime weight updates".

    REQUEST TRACING (ISSUE 12): ``trace='all'|'errors'|'sample:P'``
    arms a :class:`veles_tpu.serving.SpanTracer` threaded through the
    whole request path — HTTP root span, router attempt spans, queue
    wait, every prefill chunk / decode tick / speculative verify / COW
    copy, with device dispatches fenced so durations are device wall
    time.  The last ``trace_last`` finished requests stay
    reconstructable in a flight-recorder ring (errored/deadline-blown
    requests are auto-dumped as waterfall text), ``GET
    /trace.json?last=N`` exports Chrome-trace/Perfetto JSON, and
    ``tools/trace_report.py`` renders waterfalls + the per-op cost
    ledger.  Default off: every site is one attribute-is-None check
    (the ``faults.py`` discipline; the chaos bench pins unarmed
    overhead <2%% of a decode step).  An armed tracer FENCES every
    traced dispatch, so its spans are for post-mortems, not for
    measuring.  Every JSON reply (success and error) is stamped with a
    ``request_id`` echoed from the ``X-Request-Id`` header or generated
    server-side, whether or not tracing is armed.

    THE LOOP RECORDER (ISSUE 26) is on in every engine whatever
    ``trace`` says: ``serving/tracing.py::LoopRecorder`` keeps one
    record per turn of the engine loop (phases that partition the
    turn), one per device dispatch (ISSUE 38), one per request
    (enqueue, admit, first token, done, a stamp per emitted token) and
    one per HTTP POST, all on
    ``time.monotonic_ns()``, with no lock, fence or transfer.
    ``tracing.recorders()`` returns them, after ``stop()`` too; the
    benchmark's per-layer readers are their consumer.

    ENDPOINTS, and what each is for: ``POST /predict`` the service;
    ``GET /metrics`` (Prometheus text) and ``/metrics.json`` the
    counters, gauges and fixed-bucket histograms for scraping and for
    the router's placement; ``/timeseries.json?window=S`` and
    ``/slo.json`` (with ``telemetry`` / ``slo``) windowed rates and
    burn-rate states for alerting; ``/status`` the same as a text panel
    for a human; ``/trace.json?last=N`` (with ``trace``) the flight
    recorder's newest requests as Chrome-trace tracks, for the
    post-mortem of one request, with the engine loop's newest turns as
    an ``engine loop`` track beside them so that a request's
    ``decode.step`` spans stand above the loop phases that produced
    them, and those turns' dispatches on a track under it (a slice
    each, from the jit call to the outputs' arrival on the host).

    CONTINUOUS TELEMETRY + SLOs (ISSUE 14, engine path only):
    ``telemetry=S`` starts a
    :class:`veles_tpu.serving.TimeSeriesStore` sampling every engine
    (and router) metrics family into bounded rings every ``S``
    seconds (``True`` = 1 s) — counters become windowed rates, gauges
    keep min/max/mean, histogram deltas resolve windowed p50/p95 —
    plus per-engine runtime gauges (live jit ``compile_programs`` +
    ``compiles_total``, process RSS, device memory where reported,
    ``mfu_live`` from the lm_bench FLOPs model, megastep waste
    fraction), served at ``GET /timeseries.json?window=S``.
    ``slo=`` (a JSON objective file path, a parsed spec dict, or
    ``True`` for the stock objectives) arms a
    :class:`veles_tpu.serving.SLOMonitor` riding the store's tick:
    multi-window error-budget burn rates per objective per replica,
    ok→warn→page state machine at ``GET /slo.json``, and — when
    ``health=True`` — a page-level burn on ONE replica feeds the
    HealthChecker (``note_slo_page``) toward the same quarantine path
    a failed probe takes.  ``slo`` implies ``telemetry`` (default
    1 s).  Every server serves the human-readable ``GET /status``
    text panel.  The hot path has zero telemetry sites: the store
    samples on its own thread (the pull model) — overhead is bounded
    by the chaos bench's ``fault_free_overhead`` leg (<1%% of a decode
    step).

    The direct path decodes one prompt batch at a time via the
    KV-cached ``transformer.generate``, one jitted dispatch per
    request.  Compile count and per-request cost are both BOUNDED
    against adversarial or merely varied clients:

    - prompt lengths are BUCKETED — the prompt is right-padded to the
      next power of two and decoded with a traced ``true_len`` (bit-exact
      under causal attention, see ``transformer._generate_impl``), so
      compiles grow with log2(max_len), not with every distinct prompt
      length;
    - ``n_new`` is quantized into a few static TIERS (clamped to
      ``max_new``), so an n_new=1 request pays a short tier's decode,
      not the full ``max_new``, while per-value recompiles stay
      impossible.  top_k remains jit-static but vocab-bounded.
    """
    from veles_tpu.ops.transformer import trainer_sample_tokens
    trainer = workflow.trainer
    # marshalled ONCE (params are frozen while serving; pipelined
    # trainers pay the block unstack here, not per request)
    params = trainer._to_portable(trainer.params)
    cache_len = int(trainer.max_len)
    # geometric ladder bounds BOTH the compile count (one generate
    # program per tier) and the decode overshoot (≤4× the requested
    # n_new; {8,32,max} alone made an n_new=40 request pay a full
    # max_new=256 decode)
    tiers = sorted({t for t in (8, 32, 128, max_new) if t <= max_new})
    from veles_tpu.serving.tracing import SpanTracer
    tracer = SpanTracer.from_spec(trace, last=int(trace_last))
    engine = None
    checker = None
    manager = None
    routed = False
    if slots > 0:
        from veles_tpu.serving import (HealthChecker, LMEngine,
                                       ModelManager, Router,
                                       RouterMetrics,
                                       replica_device_slices)
        from veles_tpu.serving import metrics as metrics_mod
        n_rep = max(1, int(replicas))
        tp_n = int(tp or 0)
        # the RESILIENCE layer (ISSUE 10) lives on the Router — a
        # single replica wraps in the (bit-identical) degenerate
        # router when health/hedge/retries are requested; the
        # publisher loop (ISSUE 11) deploys through the router too
        resilient = bool(health) or bool(hedge) or int(retries) > 0 \
            or bool(model_dir)
        slices = (replica_device_slices(n_rep, tp_n)
                  if n_rep > 1 else None)

        def build_engine(i=None):
            """One engine — replica ``i`` owns its own device slice
            (replica_device_slices — the same mapping the bench
            measures) and a metrics row labeled {replica="i"} under
            the shared 'lm' family."""
            devices = None
            label = None
            eng_name = "lm"
            if i is not None:
                devices = slices[i]
                label = {"replica": str(i)}
                eng_name = "lm_r%d" % i
            return LMEngine(
                params, n_heads=trainer.model_config, max_len=cache_len,
                slots=slots,
                queue_depth=queue_depth, deadline_s=deadline_s,
                prefix_cache=prefix_cache, prefill_chunk=prefill_chunk,
                spec_k=spec_k, queue_tokens=queue_tokens,
                paged_kv=paged_kv, attn_kernel=attn_kernel,
                megastep=megastep,
                tp=tp_n, devices=devices, name=eng_name,
                metrics=metrics_mod.new("lm", labels=label),
                faults=fault_plan, tracer=tracer)

        if slo and not telemetry:
            telemetry = 1.0         # objectives need the store
        if n_rep > 1 or resilient:
            routed = True
            engine = Router(
                [build_engine(i if n_rep > 1 else None)
                 for i in range(n_rep)],
                metrics=metrics_mod.register(RouterMetrics("lm_router")),
                policy=router, retries=int(retries),
                hedge_after_s=float(hedge or 0.0),
                faults=fault_plan, tracer=tracer).start()
            if health:
                checker = HealthChecker(
                    engine, interval_s=float(health_interval_s),
                    probe_timeout_s=max(5.0, deadline_s / 2)).start()
            if model_dir:
                manager = ModelManager(
                    engine, model_dir,
                    interval_s=float(publish_interval_s),
                    canary=int(canary),
                    watch_s=float(canary_watch_s),
                    auto_rollback=bool(auto_rollback)).start()
        else:
            engine = build_engine().start()

    store = None
    monitor = None
    if engine is not None and telemetry:
        from veles_tpu.serving import timeseries as ts_mod
        from veles_tpu.serving.metrics import _registry_key
        interval = 1.0 if telemetry is True else float(telemetry)
        store = ts_mod.telemetry_for(engine, interval_s=interval)
        if slo:
            from veles_tpu.serving.slo import SLOMonitor
            replica_engines = getattr(engine, "replicas", [engine])
            source_replicas = {
                _registry_key(e.metrics): i
                for i, e in enumerate(replica_engines)}
            # SLO gauges/counters land in the router's (or the solo
            # engine's) own family, so /metrics carries slo_state too
            kw = dict(checker=checker,
                      source_replicas=source_replicas,
                      metrics=engine.metrics)
            if slo is True:
                monitor = SLOMonitor(
                    store, SLOMonitor.default_objectives(), **kw)
            else:
                monitor = SLOMonitor.from_spec(slo, store, **kw)
            # the monitor rides the store's tick: one evaluation per
            # sampling window, deterministic under sample_once()
            store.add_listener(monitor.sample_once)
        ts_mod.set_default(store)
        store.start()

    def handler(request):
        prompt = numpy.asarray(request["input"], numpy.int32)
        want = min(int(request.get("n_new", 32)), max_new)
        if want < 1:        # n_new=0: echo/validation probe, no decode
            return {"tokens": prompt.tolist()}
        s_true = prompt.shape[1]
        headroom = cache_len - s_true
        if headroom < 1:
            raise ValueError("prompt length %d leaves no room to decode "
                             "(max_len %d)" % (s_true, cache_len))
        temperature = float(request.get("temperature", 0.0))
        # speculative decoding needs spec_k cache positions of write
        # headroom; a prompt too close to the cache cap falls back to
        # the direct path instead of being refused
        eng_headroom = headroom - (engine.headroom if engine is not None
                                   else 0)
        if engine is not None and temperature == 0.0 \
                and eng_headroom >= 1:
            # continuous batching: exact n_new (no tier), concurrent
            # prompts share the decode step across slots
            if routed:
                toks, reps, vers = engine.generate(
                    prompt, min(want, eng_headroom),
                    return_replicas=True, return_versions=True)
                # per-row replica ids and weights_version stamps: the
                # client-side balance and swap-cutover evidence
                # load_gen --lm aggregates
                return {"tokens": toks.tolist(), "replicas": reps,
                        "weights_version": vers}
            toks, vers, drafts = engine.generate(
                prompt, min(want, eng_headroom), return_versions=True,
                return_drafts=True)
            reply = {"tokens": toks.tolist(), "weights_version": vers}
            if request.get("drafts"):
                # what the model's own module drafted, accepted or not
                reply["drafts"] = drafts
            return reply
        # decode length: round the request UP to a tier; near the cache
        # cap fall back to the largest tier that fits (or the exact
        # headroom when even the smallest doesn't — rare, self-limiting)
        run = next((t for t in tiers if t >= want), tiers[-1])
        if run > headroom:
            fitting = [t for t in tiers if t <= headroom]
            run = fitting[-1] if fitting else headroom
        # prompt bucket: right-pad to the next power of two that still
        # fits the cache; true_len keeps decoding bit-exact
        bucket = 16
        while bucket < s_true:
            bucket *= 2
        bucket = min(bucket, cache_len - run)
        if bucket > s_true:
            prompt = numpy.pad(prompt, ((0, 0), (0, bucket - s_true)))
        top_k = request.get("top_k")
        out = trainer_sample_tokens(
            trainer, prompt, n_new=run,
            temperature=temperature,
            seed=int(request.get("seed", 0)), params=params,
            max_len=cache_len,
            top_k=int(top_k) if top_k is not None else None,
            true_len=s_true)
        # the continuation lands after the PADDED width; reply with the
        # true prompt plus min(want, run) new tokens
        new = out[:, prompt.shape[1]:prompt.shape[1] + min(want, run)]
        return {"tokens": numpy.concatenate(
            [out[:, :s_true], new], axis=1).tolist()}

    api = RESTfulAPI(None, handler=handler,
                     metrics=engine.metrics if engine is not None
                     else None, faults=fault_plan, tracer=tracer,
                     telemetry=store, slo=monitor)
    api.lm_engine = engine
    api.health_checker = checker
    api.model_manager = manager
    return api.start(host=host, port=port)


def serve_artifact(path, host="127.0.0.1", port=8180, max_batch=0):
    """Serve a StableHLO export artifact (veles_tpu.export) WITHOUT
    constructing any training workflow — the libVeles serving path
    (SURVEY §2.4/§3.4): load weights + compiled forward, start HTTP.
    ``max_batch > 0`` coalesces concurrent requests through the
    micro-batcher (the artifact's symbolic batch dim makes every bucket
    a warm program)."""
    from veles_tpu.export import load_model
    model = load_model(path)
    api = RESTfulAPI(None, forward=model.predict)
    if max_batch > 0:
        api.enable_batching(
            max_batch=max_batch,
            sample_shape=tuple(model.manifest["input_sample_shape"]))
    return api.start(host=host, port=port)


def serve_snapshot(path, host="127.0.0.1", port=8180, build=None):
    """CLI helper: restore a snapshot into a rebuilt workflow and serve it.

    ``build`` is a zero-arg callable returning the (initialized) workflow —
    usually a sample's ``build`` + ``initialize``; the snapshot then restores
    the trained weights (SURVEY §3.3/§3.4 snapshot-is-the-artifact flow).
    """
    from veles_tpu import snapshotter
    wf = build()
    snapshotter.restore(wf, path)
    return RESTfulAPI(wf).start(host=host, port=port)

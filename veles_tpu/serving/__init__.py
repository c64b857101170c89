"""Serving subsystem — inference traffic at scale (ISSUE 1).

The inference-traffic counterpart of ``veles_tpu.parallel``: where the
direct REST path (``restful_api.py``) pays one device dispatch per HTTP
request, this package amortizes dispatch across concurrent clients.

- :mod:`veles_tpu.serving.batcher` — :class:`MicroBatcher`: dynamic
  micro-batching of ``/predict`` traffic into padded power-of-two batch
  buckets (warmed at start), with admission control (bounded queue →
  :class:`Overloaded` / HTTP 429 + ``Retry-After``) and per-request
  deadlines (:class:`DeadlineExceeded` / HTTP 503).
- :mod:`veles_tpu.serving.lm_engine` — :class:`LMEngine`: slot-based
  continuous batching for autoregressive LM decode over one shared KV
  cache (greedy path bit-identical to ``ops.transformer.generate``),
  plus the ISSUE 4 fast path: :class:`RadixPrefixCache` prompt-KV
  reuse, chunked prefill, and prompt-lookup speculative decoding.
  A turn of its loop decodes through one of three drivers: one token
  a lane, the speculative verify (``spec_k``), or the fused
  ``lax.scan`` megastep (``megastep=K``, the one fused driver).
  ``attn_kernel=`` (ISSUE 7) routes the paged engine's attention
  through the Pallas serving kernels in ``ops/pallas_kernels.py``
  (flash-decode over the page table + fused chunked-prefill with
  in-kernel row install) on TPU hardware, with an automatic XLA
  fallback metered as ``attn_kernel_dispatches`` /
  ``attn_kernel_fallbacks`` on ``/metrics``.
- :mod:`veles_tpu.serving.kv_pool` — :class:`KVPagePool`: the paged
  KV-cache allocator (ISSUE 6).  ``LMEngine(paged_kv=N)`` stores KV in
  fixed-size pages from one global pool behind per-lane page tables;
  prefix-cache hits become zero-copy page references (ref-counts +
  copy-on-write), and slot count is bounded by the pool, not by
  ``slots × max_len``.
- :mod:`veles_tpu.serving.router` — :class:`Router` (ISSUE 8): N
  data-parallel :class:`LMEngine` replicas — each optionally
  tensor-parallel over its own device slice (``LMEngine(tp=)``, mesh
  from ``parallel.make_tp_mesh``, weights by
  ``ops.transformer.lm_param_specs``) — placed by live metrics
  signals (queue depth, resident KV pages, TTFT/decode-step EWMAs),
  with hot-unregister draining that requeues a sick replica's pending
  requests.  ``serve_lm(tp=, replicas=)``, CLI ``--serve-tp`` /
  ``--serve-replicas`` / ``--serve-router``.
- :mod:`veles_tpu.serving.faults` — :class:`FaultPlan` (ISSUE 10):
  deterministic, seedable fault injection at named sites compiled into
  the engine/batcher/router/HTTP layers (dispatch errors, latency
  spikes, freezes, admission storms, transient HTTP errors) — each
  site a no-op when unarmed.  Drives the resilience layer:
  :class:`HealthChecker` (auto-quarantine via the router's drain path
  + half-open circuit breaker), ``Router(retries=, hedge_after_s=)``
  (re-place faulted requests on another replica with backoff; hedge
  tail-latency stragglers, first-complete wins), and
  ``LMEngine.checkpoint()/restore()`` (crash-safe re-admission of
  journaled work with allocator invariants re-verified).  CLI
  ``--serve-health`` / ``--serve-hedge`` / ``--serve-retries`` /
  ``--fault-plan``; harness ``tools/chaos_bench.py`` /
  ``tools/chaos_smoke.py``.
- :mod:`veles_tpu.serving.model_manager` — :class:`ModelManager`
  (ISSUE 11): the publisher loop closing trainer→serving — watches a
  snapshot directory (the snapshotter's atomic output), validates and
  loads new checkpoints off the hot path, and drives zero-downtime
  weight updates: ``LMEngine.swap_weights()`` hot-installs a
  checkpoint into a live engine (in-flight lanes finish on the old
  weights or drain-and-requeue; structural mismatch refuses loudly),
  ``Router.deploy()`` rolls it out canary-first with a parity probe,
  live-signal watch and automatic rollback, and every reply is
  stamped with the ``weights_version`` that served it.
  ``serve_lm(model_dir=, canary=, auto_rollback=)``, CLI
  ``--serve-model-dir`` / ``--serve-canary`` /
  ``--serve-publish-interval``.
- :mod:`veles_tpu.serving.tracing` — :class:`SpanTracer` (ISSUE
  12): end-to-end request tracing — an ``http.request`` root span, one
  span per router placement attempt, queue wait, every prefill chunk /
  decode tick / speculative verify / COW copy, device dispatches fenced
  via ``block_until_ready`` only when armed.  Finished requests land in
  a bounded flight-recorder ring (errored/deadline-blown requests
  auto-dump a waterfall), export as Chrome-trace/Perfetto JSON (``GET
  /trace.json?last=N``), and aggregate into the per-op cost ledger
  (``tools/trace_report.py``).  ``serve_lm(trace=)``, CLI
  ``--serve-trace off|errors|sample:P|all``; unarmed cost is one
  attribute-is-None check per site (the ``faults.py`` discipline).
  The same module holds :class:`~tracing.LoopRecorder` (ISSUE 26),
  ON in every engine whatever ``--serve-trace`` says: one record per
  turn of the engine loop (twelve phases that partition it), one per
  device dispatch (ISSUE 38: program, turn, call, return, wait,
  outputs on the host), one per request with the stamp of every
  emitted token, one per HTTP POST, all on ``time.monotonic_ns()``;
  no lock, no fence, no transfer.  ``tracing.recorders()`` keeps the
  newest four, stopped engines' included; ``benchmark/lib/spans.py``
  and ``benchmark/lib/dispatch_log.py`` put them on the device
  trace's clock.
- :mod:`veles_tpu.serving.metrics` — :class:`ServingMetrics`:
  lock-cheap counters/histograms (queue wait, batch size, latency
  percentiles, shed/429, slot occupancy) with a snapshot API and a
  Prometheus renderer (served by ``web_status.py`` at ``/metrics``).
- :mod:`veles_tpu.serving.timeseries` — :class:`TimeSeriesStore`
  (ISSUE 14): continuous telemetry — every metrics family sampled on
  a background cadence into bounded rings (counters → windowed rates,
  gauges → min/max/mean, histogram deltas → windowed p50/p95), plus
  runtime/device gauges (live jit ``compile_programs``, process RSS,
  ``jax`` device memory, live MFU from the lm_bench FLOPs model,
  megastep waste fraction) written by :func:`runtime_probe` each
  tick.  ``GET /timeseries.json?window=S``; the serving hot path has
  zero telemetry sites (pull model).
- :mod:`veles_tpu.serving.lockcheck` — :class:`LockOrderWitness`
  (ISSUE 15): the runtime half of the concurrency-analysis layer.
  Serving locks are built through :func:`lockcheck.make_lock` /
  :func:`lockcheck.make_condition` (one module-global None-check per
  operation when unarmed); an armed witness records the per-thread
  lock-acquisition graph, flags ordering cycles (potential deadlocks)
  and locks held across device dispatches, with both stacks as
  evidence.  Armed around the serving test suites by
  ``tests/conftest.py``; the static half — which attribute needs
  which lock, traced-purity of jitted bodies — is
  ``tools/veles_lint.py`` (rides tier-1 as ``tests/test_lint.py``).
- :mod:`veles_tpu.serving.slo` — :class:`SLOMonitor` (ISSUE 14):
  declarative objectives (availability, TTFT/decode-step latency,
  shed rate) evaluated as multi-window error-budget BURN RATES over
  the store, ok→warn→page state machine per (source, objective)
  (``slo_state`` gauges, ``slo_pages_total``), ``GET /slo.json``, and
  a router hook: a page-level burn on one replica feeds the PR 10
  :class:`HealthChecker` (``note_slo_page``) as a first-class health
  signal.  ``serve_lm(telemetry=, slo=)``, CLI ``--serve-telemetry``
  / ``--serve-slo FILE``; human panel at ``GET /status``.

The engines are OPTIONAL: ``restful_api.py`` keeps the direct
one-dispatch-per-request path for single-user/debug use and routes
through here when asked (``RESTfulAPI.enable_batching``, ``serve_lm``'s
``slots=``, CLI ``--serve-batch`` / ``--serve-slots``).
"""

from veles_tpu.serving.batcher import (DeadlineExceeded, MicroBatcher,
                                       Overloaded, PoolExhausted,
                                       batch_buckets)
from veles_tpu.serving.faults import (FaultPlan, InjectedFault,
                                      InjectedHTTPError)
from veles_tpu.serving.kv_pool import KVPagePool
from veles_tpu.serving.lockcheck import (LockOrderViolation,
                                         LockOrderWitness)
from veles_tpu.serving.lm_engine import (LMEngine, RadixPrefixCache,
                                         propose_draft)
from veles_tpu.serving.metrics import (ServingMetrics, get,
                                       render_prometheus)
from veles_tpu.serving.model_manager import (ModelManager,
                                             load_lm_params,
                                             validate_lm_params)
from veles_tpu.serving.router import (HealthChecker, NoLiveReplicas,
                                      Router, RouterMetrics,
                                      replica_device_slices)
from veles_tpu.serving.slo import Objective, SLOMonitor
from veles_tpu.serving.timeseries import (TimeSeriesStore,
                                          decode_flops_per_token,
                                          peak_flops_estimate,
                                          runtime_probe,
                                          telemetry_for)
from veles_tpu.serving.tracing import (SpanTracer, TraceContext,
                                       cost_ledger, format_waterfall,
                                       verify_integrity)

__all__ = ["MicroBatcher", "LMEngine", "RadixPrefixCache",
           "SpanTracer", "TraceContext", "cost_ledger",
           "format_waterfall", "verify_integrity",
           "TimeSeriesStore", "SLOMonitor", "Objective",
           "telemetry_for", "runtime_probe",
           "decode_flops_per_token", "peak_flops_estimate",
           "KVPagePool", "LockOrderViolation", "LockOrderWitness",
           "Router", "RouterMetrics", "HealthChecker",
           "ModelManager", "ServingMetrics", "FaultPlan",
           "InjectedFault",
           "InjectedHTTPError", "NoLiveReplicas", "Overloaded",
           "DeadlineExceeded",
           "PoolExhausted", "batch_buckets",
           "propose_draft", "get", "load_lm_params",
           "render_prometheus",
           "replica_device_slices", "validate_lm_params"]

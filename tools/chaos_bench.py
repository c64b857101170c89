"""Chaos bench (ISSUE 10): the serving resilience layer under
deterministic injected faults.

Seven scenarios, each driven by a seeded
``veles_tpu/serving/faults.py::FaultPlan`` so a given run always
injects at the same dispatches:

- ``kill_one_replica_under_load`` — replica 0's worker FREEZES
  mid-traffic (the wedged-device shape).  The health checker's
  staleness watch quarantines it through the router's drain path,
  drained work re-places (wedged mid-decode lanes force-replace after
  the drain timeout), and EVERY admitted request completes exactly
  once with output bit-identical to ``transformer.generate`` — no
  loss, no duplicate, no wedge.
- ``slow_replica_tail`` — replica 0 pays an injected per-dispatch
  latency spike.  The same workload runs hedging OFF then ON:
  requests outstanding past the hedge threshold duplicate onto the
  fast replica, first complete wins (parity unchanged), and the
  record carries both latency distributions plus the
  ``requests_hedged`` / ``hedge_wins`` evidence.
- ``pool_exhaustion_storm`` — a page-pool flood (many concurrent
  mixed-length requests against a tiny pool) plus injected admission
  storms.  Every request either completes exactly greedy or sheds as
  429/PoolExhausted/503 — never another error class, never a hang —
  and afterwards the pool drains back to FULL with allocator
  invariants re-verified (leak-freedom).
- ``weight_swap_under_load`` — requests straddle a canary-first
  ``Router.deploy`` (ISSUE 11): all complete exactly once with zero
  5xx, every delivered row is bit-identical to the weights version
  its reply is stamped with (pre-swap → old, post-swap → new), and an
  injected bad canary (``engine.swap`` fault) auto-rolls back with no
  client-visible errors.
- ``traced_flight_recorder`` — requests run TRACED (ISSUE 12) under
  injected chunk faults: a retried request's trace shows both
  attempts (the errored one included), every retained span tree
  verifies (one root, no orphans, no unclosed spans), the faulted
  request's timeline reconstructs from the flight-recorder ring
  after the fact, and its waterfall was auto-dumped the moment it
  failed.
- ``slo_burn_alert`` (ISSUE 14) — a fault-slowed replica burns its
  decode-step latency SLO: the telemetry store samples both replicas,
  the SLO monitor's burn-rate state machine reaches PAGE on the slow
  one, and within TWO sampling windows the page signal walks the
  health checker (``note_slo_page``) to quarantine through the
  router's drain path — in-flight work re-places on the survivor and
  every request completes exactly once, bit-identical to greedy.
- ``fault_free_overhead`` — the acceptance leg for "unarmed is
  free": measures the per-call cost of an UNARMED fault hook, an
  UNARMED trace site (ISSUE 12) and the health checker's per-scan
  cost, expresses them as a fraction of a measured decode step, and
  asserts the sum < 2% (armed tracing's span cost is recorded for
  PERF.md, not bounded).  The ISSUE 14 telemetry bound rides here
  too: the ARMED sampler (one ``sample_once()`` amortized over its
  interval) is measured and asserted < 1% of a decode step.

A bench.py-style summary JSON line streams after EVERY completed
scenario (last-line-wins under an outer watchdog kill), and the final
line carries the full record.

Standalone (CPU is fine — every scenario is about control flow, not
device speed)::

    python tools/chaos_bench.py [--smoke] [--json out.json]

``tools/chaos_smoke.py`` runs the tier-1 subset (one scenario, tiny
model, <60s) — the CI guard that keeps this plumbing from rotting
between TPU sessions.
"""

from __future__ import annotations

import argparse
import array
import json
import os
import sys
import time

import numpy

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from lm_bench import (build_params, expected_rows,  # noqa: E402
                      mixed_length_prompts)
from load_gen import _percentile  # noqa: E402 — the ONE quantile helper


def _lat_summary(lats):
    lats = sorted(lats)
    return {"mean": round(sum(lats) / len(lats), 4) if lats else 0.0,
            "p50": round(_percentile(lats, 0.50), 4),
            "p95": round(_percentile(lats, 0.95), 4),
            "p99": round(_percentile(lats, 0.99), 4),
            "max": round(lats[-1], 4) if lats else 0.0}


def _build_replicas(params, n_heads, max_len, n, slots, plans,
                    tag="chaos", **engine_kw):
    """N single-device replicas; ``plans[i]`` (or None) arms replica
    i's fault sites."""
    from veles_tpu.serving import LMEngine, ServingMetrics
    return [LMEngine(params, n_heads=n_heads, max_len=max_len,
                     slots=slots, name="%s_r%d" % (tag, i),
                     metrics=ServingMetrics(
                         tag, labels={"replica": str(i)}),
                     faults=plans[i], **engine_kw)
            for i in range(n)]


def _submit_all(server, prompts, n_new, deadline_s=120.0):
    """Closed-loop admission: back off on 429s so a storm measures
    shedding, not a crashed client."""
    from veles_tpu.serving import Overloaded
    futures = []
    stop = time.monotonic() + deadline_s
    for p in prompts:
        while True:
            try:
                futures.append(server.submit(p, n_new))
                break
            except Overloaded as e:
                if time.monotonic() > stop:
                    raise
                time.sleep(min(getattr(e, "retry_after", 0.05), 0.1))
    return futures


# --------------------------------------------------------------- scenarios
def scenario_kill_replica(params, n_heads, max_len, prompts, n_new,
                          expect, slots=2, freeze_after_ticks=6,
                          drain_timeout_s=0.5):
    """Kill-one-replica-under-load: see the module docstring."""
    from veles_tpu.serving import FaultPlan, HealthChecker, Router
    plan = FaultPlan(seed=0)
    # CHUNKED prefill: every program is warmed at start, so the
    # staleness watch sees only real wedges — a lazily-compiled prompt
    # bucket would stall the progress counters exactly like a freeze
    # (the stall_s sizing rule the HealthChecker docstring documents)
    replicas = _build_replicas(params, n_heads, max_len, 2, slots,
                               [plan, None], tag="chaos_kill",
                               prefill_chunk=16)
    router = Router(replicas, retries=2,
                    drain_timeout_s=drain_timeout_s)
    checker = HealthChecker(router, interval_s=0.05,
                            probe_timeout_s=2.0, fail_threshold=2,
                            cooldown_s=600.0, stall_s=0.3)
    router.start()
    plan.arm("engine.tick", kind="freeze",
             after=plan.calls("engine.tick") + freeze_after_ticks,
             duration_s=600.0)
    t0 = time.monotonic()
    try:
        futures = _submit_all(router, prompts, n_new)
        # drive the health state machine synchronously until the wedge
        # is detected and every request resolved (deterministic: the
        # freeze always fires at the same tick)
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            checker.step()
            if all(f.done() for f in futures):
                break
            time.sleep(0.05)
        completed = 0
        for p, f, exp in zip(prompts, futures, expect):
            out = f.result(timeout=60)     # raises on any failure
            if len(out) != n_new:
                raise AssertionError("partial result delivered: %d/%d"
                                     % (len(out), n_new))
            if not numpy.array_equal(numpy.concatenate([p, out]), exp):
                raise AssertionError(
                    "post-fault output diverged from greedy generate "
                    "for prompt of length %d" % len(p))
            completed += 1
        m = router.metrics
        quarantined = not router._live[0]
        record = {
            "scenario": "kill_one_replica_under_load",
            "requests": len(prompts),
            "completed_exactly_once": completed,
            "parity_vs_generate": True,
            "replica0_quarantined": quarantined,
            "circuit_open_total": m.counter("circuit_open_total"),
            "requeued_requests": m.counter("requeued_requests"),
            "requests_retried": m.counter("requests_retried"),
            "drain_forced_replacements":
                m.counter("drain_forced_replacements"),
            "freeze_fired": plan.fired("engine.tick"),
            "wall_s": round(time.monotonic() - t0, 3),
        }
        if not quarantined:
            raise AssertionError("health checker never quarantined the "
                                 "frozen replica")
        if completed != len(prompts):
            raise AssertionError("%d/%d requests completed"
                                 % (completed, len(prompts)))
        return record
    finally:
        plan.release()
        checker.stop()
        router.stop()


def scenario_slow_replica(params, n_heads, max_len, prompts, n_new,
                          expect, slots=2, spike_s=0.15,
                          hedge_after_s=0.25):
    """Slow-replica tail: the same workload with hedging off then on;
    hedging must fire, win, and keep parity."""
    from veles_tpu.serving import FaultPlan, Router

    def run(hedge):
        plan = FaultPlan(seed=0).arm("engine.step", kind="latency",
                                     latency_s=spike_s)
        replicas = _build_replicas(params, n_heads, max_len, 2, slots,
                                   [plan, None], tag="chaos_slow",
                                   prefill_chunk=16)
        router = Router(replicas,
                        hedge_after_s=hedge_after_s if hedge else 0.0)
        router.start()
        try:
            lats = []
            futures = _submit_all(router, prompts, n_new)
            t_sub = {id(f): time.monotonic() for f in futures}
            for p, f, exp in zip(prompts, futures, expect):
                out = f.result(timeout=120)
                lats.append(time.monotonic() - t_sub[id(f)])
                if not numpy.array_equal(
                        numpy.concatenate([p, out]), exp):
                    raise AssertionError(
                        "hedged output diverged from greedy generate")
            m = router.metrics
            return {"latency_s": _lat_summary(lats),
                    "requests_hedged": m.counter("requests_hedged"),
                    "hedge_wins": m.counter("hedge_wins")}
        finally:
            plan.release()
            router.stop()

    base = run(hedge=False)
    hedged = run(hedge=True)
    if not hedged["requests_hedged"]:
        raise AssertionError("hedging never fired on the slow replica")
    return {
        "scenario": "slow_replica_tail",
        "requests": len(prompts),
        "parity_vs_generate": True,
        "injected_step_spike_s": spike_s,
        "hedge_after_s": hedge_after_s,
        "no_hedge": base,
        "hedge": hedged,
        "p99_ratio_hedge_vs_none": (
            round(hedged["latency_s"]["p99"]
                  / base["latency_s"]["p99"], 3)
            if base["latency_s"]["p99"] else None),
    }


def scenario_pool_storm(params, n_heads, max_len, prompts, n_new,
                        expect, slots=2, pool_pages=6, chunk=8,
                        deadline_s=2.0):
    """Pool-exhaustion storm: shed (429/503), never errored, never
    wedged; pool drains leak-free afterwards."""
    from veles_tpu.serving import (DeadlineExceeded, FaultPlan,
                                  LMEngine, Overloaded, ServingMetrics)
    # the pool must be able to place the LARGEST single request (an
    # up-front 400 otherwise) while staying far below the aggregate
    # demand — that gap IS the storm
    need = max(-(-(len(p) + n_new) // chunk) for p in prompts)
    pool_pages = max(pool_pages, need + 1)
    # the storm site: every 7th admission also 429s by injection, on
    # top of the natural pool pressure
    plan = FaultPlan(seed=0).arm("engine.submit", kind="error",
                                 exc="PoolExhausted", every=7)
    engine = LMEngine(params, n_heads=n_heads, max_len=max_len,
                      slots=slots, paged_kv=pool_pages,
                      prefill_chunk=chunk, deadline_s=deadline_s,
                      queue_depth=len(prompts) + 8,
                      name="chaos_pool",
                      metrics=ServingMetrics("chaos_pool"),
                      faults=plan).start()
    t0 = time.monotonic()
    try:
        outcomes = {"ok": 0, "rejected_429": 0, "shed_503": 0}
        futures = []
        for p in prompts:
            try:
                futures.append((p, engine.submit(p, n_new)))
            except Overloaded:
                outcomes["rejected_429"] += 1
        for p, f in futures:
            try:
                out = f.result(timeout=120)
                exp = expect[[i for i, q in enumerate(prompts)
                              if q is p][0]]
                if not numpy.array_equal(
                        numpy.concatenate([p, out]), exp):
                    raise AssertionError(
                        "storm survivor diverged from greedy generate")
                outcomes["ok"] += 1
            except DeadlineExceeded:
                outcomes["shed_503"] += 1
            except Overloaded:
                outcomes["rejected_429"] += 1
            # any OTHER exception propagates: the storm must shed, not
            # error — the scenario fails loudly on a 500-class fault
        while engine._trie is not None and engine._trie.evict_one():
            pass
        invariants = engine.verify_pool_invariants()
        if engine._pool.free_pages != engine._pool.num_pages:
            raise AssertionError(
                "pool leaked %d page(s) after the storm"
                % (engine._pool.num_pages - engine._pool.free_pages))
        total = sum(outcomes.values())
        if total != len(prompts):
            raise AssertionError("accounted %d of %d requests"
                                 % (total, len(prompts)))
        return {
            "scenario": "pool_exhaustion_storm",
            "requests": len(prompts),
            "pool_pages": pool_pages,
            "outcomes": outcomes,
            "shed_not_errored": True,       # else we raised above
            "injected_admission_storms": plan.fired("engine.submit"),
            "pool_leak_free": True,
            "allocator_invariants": invariants,
            "wall_s": round(time.monotonic() - t0, 3),
        }
    finally:
        engine.stop()


def scenario_traced_flight_recorder(params, n_heads, max_len, prompts,
                                    n_new, expect, slots=2):
    """Traced serving under injected faults (ISSUE 12): the flight
    recorder must reproduce a faulted request's timeline AFTER the
    fact, auto-dump it the moment it fails, and keep every retained
    span tree sound (one root, no orphans, no unclosed spans) while
    parity holds for the survivors.

    Two sub-legs: (a) a 2-replica ROUTER with retries — a request whose
    first attempt dies on the faulted replica completes on the second,
    and its trace shows BOTH attempts (the errored one included); (b) a
    single engine with a recurring chunk fault and no retry — the
    failed requests' traces land in the 'errors'-mode ring exactly,
    each auto-dumped as waterfall text."""
    from veles_tpu.serving import (FaultPlan, LMEngine, Router,
                                   ServingMetrics, SpanTracer,
                                   cost_ledger, format_waterfall,
                                   verify_integrity)

    # ---- (a) routed retry: the errored attempt stays in the timeline
    plan = FaultPlan(seed=0).arm("engine.chunk", kind="error",
                                 calls={2})
    tracer = SpanTracer(mode="all", last=4 * len(prompts) + 16)
    replicas = _build_replicas(params, n_heads, max_len, 2, slots,
                               [plan, None], tag="chaos_trace",
                               prefill_chunk=16, tracer=tracer)
    router = Router(replicas, retries=2, tracer=tracer)
    router.start()
    t0 = time.monotonic()
    try:
        futures = _submit_all(router, prompts, n_new)
        for p, f, exp in zip(prompts, futures, expect):
            out = f.result(timeout=120)
            if not numpy.array_equal(numpy.concatenate([p, out]), exp):
                raise AssertionError(
                    "traced+faulted output diverged from greedy "
                    "generate")
    finally:
        plan.release()
        router.stop()
    recs = tracer.requests()
    integrity = verify_integrity(recs)      # raises on a broken tree
    retried = [r for r in recs
               if sum(1 for s in r["spans"]
                      if s["name"] == "attempt") > 1]
    if not retried:
        raise AssertionError("no request shows a second attempt after "
                             "the injected chunk fault")
    errored_attempts = [
        s for r in retried for s in r["spans"]
        if s["name"] == "attempt" and "error" in s["attrs"]]
    if not errored_attempts:
        raise AssertionError("the retried request's first attempt did "
                             "not record its error")
    ledger = cost_ledger(recs)
    if not ledger:
        raise AssertionError("traced run produced an empty cost ledger")

    # ---- (b) flight recorder: errors-only retention + auto-dump
    plan_b = FaultPlan(seed=0).arm("engine.chunk", kind="error",
                                   every=3)
    rec_tracer = SpanTracer(mode="errors", last=16)
    engine = LMEngine(params, n_heads=n_heads, max_len=max_len,
                      slots=slots, prefill_chunk=16,
                      name="chaos_recorder",
                      metrics=ServingMetrics("chaos_recorder"),
                      faults=plan_b, tracer=rec_tracer).start()
    try:
        futures = [(p, engine.submit(p, n_new)) for p in prompts]
        failed, ok = [], 0
        for i, (p, f) in enumerate(futures):
            try:
                out = f.result(timeout=120)
            except Exception:   # noqa: BLE001 — the injected fault
                failed.append((p, f))
                continue
            if not numpy.array_equal(numpy.concatenate([p, out]),
                                     expect[i]):
                raise AssertionError(
                    "survivor diverged from greedy generate beside "
                    "injected faults")
            ok += 1
        if not failed:
            raise AssertionError("the every=3 chunk fault never fired")
    finally:
        plan_b.release()
        engine.stop()
    # reconstruction AFTER the fact: the failed request's rid pulls its
    # full timeline out of the ring, and the auto-dump already fired
    rid = failed[0][1].request.trace.rid
    rec = rec_tracer.find(rid)
    if rec is None:
        raise AssertionError("faulted request %s not in the flight "
                             "recorder ring" % rid)
    if not rec["error"] or "InjectedFault" not in rec["error"]:
        raise AssertionError("recorded error %r does not name the "
                             "injected fault" % (rec["error"],))
    fault_spans = [s for s in rec["spans"]
                   if "error" in s["attrs"]
                   and s["name"] == "prefill.chunk"]
    if not fault_spans:
        raise AssertionError("the faulted dispatch is missing from "
                             "the reconstructed timeline")
    waterfall = format_waterfall(rec)
    if "InjectedFault" not in waterfall:
        raise AssertionError("waterfall does not show the fault")
    dump_rids = {d["rid"] for d in rec_tracer.dumps()}
    if rid not in dump_rids:
        raise AssertionError("faulted request %s was not auto-dumped"
                             % rid)
    retained = rec_tracer.requests()
    verify_integrity(retained)
    if len(retained) != len(failed):
        raise AssertionError(
            "'errors' mode retained %d records for %d failed requests"
            % (len(retained), len(failed)))
    return {
        "scenario": "traced_flight_recorder",
        "requests": 2 * len(prompts),
        "parity_vs_generate": True,
        "span_integrity": integrity,
        "retried_request_attempts": max(
            sum(1 for s in r["spans"] if s["name"] == "attempt")
            for r in retried),
        "ledger_rows": len(ledger),
        "ledger_dispatches": int(sum(r["dispatches"] for r in ledger)),
        "faulted_requests": len(failed),
        "recorder_retained": len(retained),
        "auto_dumps": len(dump_rids),
        "fault_timeline_reconstructed": True,
        "wall_s": round(time.monotonic() - t0, 3),
    }


def recorder_turn_cost(slots, turns=20000):
    """Seconds the always-on loop recorder (ISSUE 26) costs per turn of
    the engine loop: every call a decode turn with one prefill chunk
    makes (turn, six marks, two dispatches with the stamps of their
    own records (ISSUE 38: returned, waiting, fetched), the lane counts,
    one token stamp per lane), timed over ``turns`` turns on this host.
    The fetches are those of the turn BEFORE (ISSUE 39: the same calls,
    on the handles kept from it)."""
    from veles_tpu.serving import tracing

    class _Req:
        pass

    def program():
        pass

    rec = tracing.LoopRecorder("cost")
    req = _Req()
    req.token_ns = array.array("q")
    before = ()
    t0 = time.perf_counter()
    for _ in range(turns):
        rec.turn()
        rec.mark(tracing.ADMIT)
        rec.lanes(slots, 0)
        rec.mark(tracing.PREFILL_PREPARE)
        chunk = rec.dispatch(tracing.PREFILL_DISPATCH, program)
        rec.returned(chunk)
        rec.mark(tracing.STEP_PREPARE)
        step = rec.dispatch(tracing.STEP_DISPATCH, program, slots)
        rec.returned(step)
        rec.mark(tracing.AHEAD_EMIT)
        for _lane in range(slots):
            rec.emitted(req, 1)
        rec.mark(tracing.AHEAD_ADMIT)
        rec.mark(tracing.AHEAD_PREPARE)
        # (a tail chunk and the step behind it: the dearest kind)
        for i, sent in enumerate(before):
            rec.waiting(sent, None if i else tracing.STEP_FETCH)
            rec.fetched(sent, tracing.STEP_EMIT if i else None)
        before = (chunk, step)
    rec.close()
    return (time.perf_counter() - t0) / turns


def scenario_overhead(params, n_heads, max_len, prompts, n_new,
                      slots=2, hook_calls=200000):
    """Fault-free overhead: the UNARMED fault layer, the UNARMED
    tracing layer (ISSUE 12) and the health prober must together cost
    <2% of a decode step (the acceptance bound).

    Measured facts: (a) the per-call cost of an unarmed fault hook
    (one attribute-is-None check — timed over ``hook_calls``
    iterations) scaled by the hooks a decode tick crosses; (b) the
    unarmed TRACE site — literally ``engine._tracer is None`` —
    scaled the same way; (c) the health checker's per-scan cost on a
    BUSY fleet (counter reads, no probe) amortized over its interval.
    (d) the ALWAYS-ON loop recorder (ISSUE 26): every call one turn
    of the engine loop makes, per decode step.
    All expressed against a decode-step wall measured live on this
    host.  ARMED tracing cost (span begin/end pair, scaled to the
    spans a traced tick records) is measured and RECORDED for the
    PERF.md armed-vs-unarmed row, but not bounded — arming the tracer
    buys the fence + record cost knowingly."""
    from veles_tpu.serving import HealthChecker, LMEngine, Router, \
        ServingMetrics, SpanTracer
    engine = LMEngine(params, n_heads=n_heads, max_len=max_len,
                      slots=slots, name="chaos_ovh",
                      metrics=ServingMetrics("chaos_ovh")).start()
    router = Router([engine])
    checker = HealthChecker(router, interval_s=1.0)
    try:
        # a real decode-step wall from live traffic (warm programs)
        futures = [engine.submit(p, n_new) for p in prompts]
        for f in futures:
            f.result(timeout=120)
        step_s = engine.metrics.ewma("decode_step") or 1e-4
        # (a) the unarmed hook, exactly as compiled into the engine
        t0 = time.perf_counter()
        for _ in range(hook_calls):
            engine._fault("engine.step")
        hook_s = (time.perf_counter() - t0) / hook_calls
        # a decode tick crosses 2 sites (engine.tick + engine.step);
        # admission-path sites are per REQUEST, not per token — charge
        # them too, conservatively, as one more per tick
        hooks_per_tick = 3
        hook_frac = hooks_per_tick * hook_s / step_s
        # (b) the unarmed TRACE sites (ISSUE 12) — the literal check
        # every site compiles down to; a traced tick crosses the step
        # site, the per-lane ctx reads and the fence guard — charge 4
        t0 = time.perf_counter()
        for _ in range(hook_calls):
            if engine._tracer is not None:
                raise AssertionError("tracer must be unarmed here")
        trace_site_s = (time.perf_counter() - t0) / hook_calls
        trace_sites_per_tick = 4
        trace_frac = trace_sites_per_tick * trace_site_s / step_s
        # (b2) the UNARMED lock-order shim (ISSUE 15): serving locks
        # are lockcheck wrappers whose unarmed acquire/release adds a
        # module-global None-check over the raw primitive — measured
        # as the DELTA of a with-block round trip, scaled by the lock
        # acquisitions a decode tick crosses (queue pop + gauge
        # updates + metrics records, ~8 conservatively)
        import threading as _threading
        from veles_tpu.serving import lockcheck
        shim_cond = lockcheck.make_condition("chaos_ovh.shim")
        raw_cond = _threading.Condition()
        pairs0 = 50000
        t0 = time.perf_counter()
        for _ in range(pairs0):
            with shim_cond:
                pass
        shim_pair_s = (time.perf_counter() - t0) / pairs0
        t0 = time.perf_counter()
        for _ in range(pairs0):
            with raw_cond:
                pass
        raw_pair_s = (time.perf_counter() - t0) / pairs0
        lock_shim_s = max(0.0, shim_pair_s - raw_pair_s)
        lock_acquires_per_tick = 8
        lock_frac = lock_acquires_per_tick * lock_shim_s / step_s
        # ARMED tracing: one begin/end span pair, scaled to a traced
        # tick's records (batch lanes + bookkeeping) — recorded for
        # the PERF.md armed row, not part of the unarmed bound
        pairs = 20000
        tr = SpanTracer(mode="all", last=4, max_spans=2 * pairs + 16)
        ctx = tr.start_request(name="overhead", cat="bench")
        t0 = time.perf_counter()
        for _ in range(pairs):
            tr.end(tr.begin(ctx, "decode.step", cat="decode"))
        span_pair_s = (time.perf_counter() - t0) / pairs
        tr.finish_request(ctx)
        armed_spans_per_tick = slots + 2
        armed_frac = armed_spans_per_tick * span_pair_s / step_s
        # (c) one health scan over a busy replica (staleness math
        # only: the engine has queued work during the scan)
        fut = engine.submit(prompts[0], max(8, n_new))
        t0 = time.perf_counter()
        scans = 50
        for _ in range(scans):
            checker.step()
        scan_s = (time.perf_counter() - t0) / scans
        fut.result(timeout=120)
        # the prober runs once per interval_s of wall time, whatever
        # the decode rate — its amortized cost is simply the fraction
        # of wall clock a scan occupies
        health_frac = scan_s / checker.interval_s
        # (d) the always-on loop recorder: one turn's calls per step
        recorder_s = recorder_turn_cost(slots)
        recorder_frac = recorder_s / step_s
        overhead = hook_frac + trace_frac + lock_frac + health_frac \
            + recorder_frac
        # ---- ISSUE 14: the ARMED continuous-telemetry bound: the
        # sampler, one full sample_once() — runtime probes + source
        # snapshots + ring folds — amortized over its interval_s of
        # wall clock, exactly like the health scan; it must stay
        # under 1% of a decode step
        from veles_tpu.serving import telemetry_for
        store = telemetry_for(router, interval_s=1.0)
        store.sample_once()          # warm the probes' first pass
        t0 = time.perf_counter()
        samples = 20
        for _ in range(samples):
            store.sample_once()
        sample_s = (time.perf_counter() - t0) / samples
        sampler_frac = sample_s / store.interval_s
        telemetry_frac = sampler_frac
        record = {
            "scenario": "fault_free_overhead",
            "decode_step_ewma_s": round(step_s, 6),
            "unarmed_hook_ns": round(hook_s * 1e9, 1),
            "hooks_per_decode_tick": hooks_per_tick,
            "hook_frac_of_decode_step": round(hook_frac, 6),
            # ISSUE 12: the tracing layer's three rows — unarmed site
            # (bounded), armed span pair (recorded; arming also buys
            # the block_until_ready fence, which is the dispatch
            # itself, not overhead)
            "unarmed_trace_site_ns": round(trace_site_s * 1e9, 1),
            "trace_sites_per_tick": trace_sites_per_tick,
            "trace_frac_of_decode_step": round(trace_frac, 6),
            "armed_span_pair_ns": round(span_pair_s * 1e9, 1),
            "armed_spans_per_tick": armed_spans_per_tick,
            "armed_trace_frac_of_decode_step": round(armed_frac, 6),
            # ISSUE 15: the unarmed lock-order witness shim's rows —
            # folded into overhead_frac, same 2% bound
            "lock_shim_pair_ns": round(shim_pair_s * 1e9, 1),
            "raw_lock_pair_ns": round(raw_pair_s * 1e9, 1),
            "lock_shim_delta_ns": round(lock_shim_s * 1e9, 1),
            "lock_acquires_per_tick": lock_acquires_per_tick,
            "lock_shim_frac_of_decode_step": round(lock_frac, 6),
            "health_scan_s": round(scan_s, 6),
            "health_scan_interval_s": checker.interval_s,
            "health_frac_of_decode_step": round(health_frac, 6),
            "recorder_turn_ns": round(recorder_s * 1e9, 1),
            "recorder_frac_of_decode_step": round(recorder_frac, 6),
            "overhead_frac": round(overhead, 6),
            "bound": 0.02,
            # ISSUE 14: the armed-telemetry rows and their own bound
            "telemetry_sample_s": round(sample_s, 6),
            "telemetry_interval_s": store.interval_s,
            "sampler_frac_of_decode_step": round(sampler_frac, 6),
            "telemetry_frac": round(telemetry_frac, 6),
            "telemetry_bound": 0.01,
        }
        if overhead >= 0.02:
            raise AssertionError(
                "unarmed fault layer + unarmed tracing + unarmed "
                "lock shim + health prober + loop recorder cost "
                "%.3f%% of a decode step (bound: 2%%)"
                % (100 * overhead))
        if telemetry_frac >= 0.01:
            raise AssertionError(
                "armed telemetry sampler cost "
                "%.3f%% of a decode step (bound: 1%%)"
                % (100 * telemetry_frac))
        return record
    finally:
        checker.stop()
        router.stop()


def scenario_weight_swap(params_old, params_new, n_heads, max_len,
                         prompts, n_new, expect_old, expect_new,
                         slots=2):
    """Weight-swap-under-load (ISSUE 11): N requests STRADDLE a
    canary-first ``Router.deploy`` — every request completes exactly
    once with zero 5xx, each delivered row is bit-identical to the
    weights version its reply is stamped with (pre-swap rows → old
    weights, post-swap rows → new), and an injected BAD canary
    (``engine.swap`` fault) auto-rolls back with no client-visible
    errors."""
    from veles_tpu.serving import FaultPlan, Router
    plan = FaultPlan(seed=0)        # replica 0: armed for the BAD deploy
    replicas = _build_replicas(params_old, n_heads, max_len, 2, slots,
                               [plan, None], tag="chaos_swap",
                               prefill_chunk=16)
    router = Router(replicas)
    router.start()
    t0 = time.monotonic()
    try:
        # ---- phase 1: a GOOD deploy with requests in flight
        futures = _submit_all(router, prompts, n_new)
        rec1 = router.deploy(params_new, version=1, canary=1,
                             canary_fraction=0.5, watch_s=0.0)
        if rec1["rolled_back"] or not rec1["completed"]:
            raise AssertionError("good deploy did not complete: %r"
                                 % rec1)
        # post-swap wave: every row must decode on the NEW weights
        futures2 = _submit_all(router, prompts, n_new)
        versions_seen = {}
        completed = 0
        for wave, fleet_version in ((futures, None), (futures2, 1)):
            for p, f in zip(prompts, wave):
                out = f.result(timeout=120)   # raises on ANY failure
                if len(out) != n_new:
                    raise AssertionError(
                        "partial result delivered: %d/%d"
                        % (len(out), n_new))
                ver = f.job.version
                if fleet_version is not None and ver != fleet_version:
                    raise AssertionError(
                        "post-swap row stamped v%s, fleet is v%s"
                        % (ver, fleet_version))
                idx = [i for i, q in enumerate(prompts) if q is p][0]
                exp = (expect_old if ver == 0 else expect_new)[idx]
                if not numpy.array_equal(
                        numpy.concatenate([p, out]), exp):
                    raise AssertionError(
                        "row stamped v%s is not bit-identical to that "
                        "version's greedy generate" % ver)
                versions_seen[ver] = versions_seen.get(ver, 0) + 1
                completed += 1
        # ---- phase 2: injected BAD canary — the swap apply faults
        plan.arm("engine.swap", kind="error",
                 calls={plan.calls("engine.swap") + 1})
        futures3 = _submit_all(router, prompts, n_new)
        rec2 = router.deploy(params_old, version=2, canary=1,
                             canary_fraction=0.5, watch_s=0.0)
        if not rec2["rolled_back"]:
            raise AssertionError("bad canary did not roll back: %r"
                                 % rec2)
        for p, f in zip(prompts, futures3):
            out = f.result(timeout=120)       # no client-visible errors
            if len(out) != n_new:
                raise AssertionError("partial result after rollback")
            idx = [i for i, q in enumerate(prompts) if q is p][0]
            if not numpy.array_equal(numpy.concatenate([p, out]),
                                     expect_new[idx]):
                raise AssertionError(
                    "post-rollback row diverged from the serving (v1) "
                    "weights")
            completed += 1
        m = router.metrics
        for i, e in enumerate(replicas):
            if e.weights_version != 1:
                raise AssertionError(
                    "replica %d serves v%s after the rollback (fleet "
                    "must still be v1)" % (i, e.weights_version))
        snap = m.snapshot()
        record = {
            "scenario": "weight_swap_under_load",
            "requests": 3 * len(prompts),
            "completed_exactly_once": completed,
            "zero_5xx": True,               # else we raised above
            "versions_observed": {str(k): v for k, v
                                  in sorted(versions_seen.items())},
            "parity_per_stamped_version": True,
            "deploys_total": m.counter("deploys_total"),
            "rollbacks_total": m.counter("rollbacks_total"),
            "bad_canary_rolled_back": rec2["rolled_back"],
            "rollback_reason": rec2["reason"],
            "weights_version_gauges": {
                k: v for k, v in snap["gauges"].items()
                if k.startswith("weights_version")},
            "wall_s": round(time.monotonic() - t0, 3),
        }
        if m.counter("rollbacks_total") != 1:
            raise AssertionError("expected exactly one rollback, saw %d"
                                 % m.counter("rollbacks_total"))
        if completed != 3 * len(prompts):
            raise AssertionError("%d/%d requests completed"
                                 % (completed, 3 * len(prompts)))
        return record
    finally:
        plan.release()
        router.stop()


def scenario_slo_burn_alert(params, n_heads, max_len, prompts, n_new,
                            expect, slots=2, spike_s=0.06):
    """SLO burn-rate alerting end to end (ISSUE 14): replica 0 pays an
    injected per-step latency spike, the telemetry store samples both
    replicas' metrics, the SLO monitor's decode-step objective burns
    to PAGE on replica 0 only, and the page signal must walk the
    health checker to quarantine WITHIN TWO SAMPLING WINDOWS — with
    in-flight work drained onto the survivor and every request
    completing exactly once, bit-identical to greedy."""
    from veles_tpu.serving import (FaultPlan, HealthChecker, Objective,
                                   Router, SLOMonitor, telemetry_for)
    from veles_tpu.serving.metrics import _registry_key
    plan = FaultPlan(seed=0).arm("engine.step", kind="latency",
                                 latency_s=spike_s)
    replicas = _build_replicas(params, n_heads, max_len, 2, slots,
                               [plan, None], tag="chaos_slo",
                               prefill_chunk=16)
    # round_robin: the placement baseline that KEEPS sending traffic
    # at the slow replica — exactly the regime burn alerting is for
    # (the metrics policy would route around it and hide the burn)
    router = Router(replicas, policy="round_robin")
    checker = HealthChecker(router, interval_s=600.0,
                            fail_threshold=2, cooldown_s=600.0)
    store = telemetry_for(router, interval_s=600.0)  # manual ticks
    monitor = SLOMonitor(
        store,
        [Objective("decode_step", "latency", 0.9,
                   series="decode_step", threshold_s=spike_s / 2)],
        windows_s=(30.0, 60.0), min_events=3, checker=checker,
        source_replicas={_registry_key(e.metrics): i
                         for i, e in enumerate(replicas)},
        metrics=router.metrics)
    store.add_listener(monitor.sample_once)
    router.start()
    t0 = time.monotonic()
    try:
        # baseline tick: rates and histogram deltas need a pre-fault
        # point; no events yet, so the monitor holds OK (min_events)
        store.sample_once()
        # wave 1 establishes the burn evidence in the rings
        futures = _submit_all(router, prompts, n_new)
        for f in futures:
            f.result(timeout=120)
        # wave 2 is IN FLIGHT while the page fires — the quarantine
        # must drain it onto the survivor, exactly once
        futures2 = _submit_all(router, prompts, n_new)
        windows = 0
        for _ in range(2):               # the acceptance bound
            store.sample_once()          # listener runs the monitor
            windows += 1
            if not router._live[0]:
                break
        quarantined = not router._live[0]
        completed = 0
        for wave in (futures, futures2):
            for p, f in zip(prompts, wave):
                out = f.result(timeout=120)   # raises on any failure
                if len(out) != n_new:
                    raise AssertionError(
                        "partial result delivered: %d/%d"
                        % (len(out), n_new))
                idx = [i for i, q in enumerate(prompts)
                       if q is p][0]
                if not numpy.array_equal(
                        numpy.concatenate([p, out]), expect[idx]):
                    raise AssertionError(
                        "post-quarantine output diverged from greedy "
                        "generate")
                completed += 1
        src0 = _registry_key(replicas[0].metrics)
        state0 = monitor.state(src0, "decode_step")
        src1 = _registry_key(replicas[1].metrics)
        state1 = monitor.state(src1, "decode_step")
        m = router.metrics
        record = {
            "scenario": "slo_burn_alert",
            "requests": 2 * len(prompts),
            "completed_exactly_once": completed,
            "parity_vs_generate": True,
            "injected_step_spike_s": spike_s,
            "slo_threshold_s": spike_s / 2,
            "sampling_windows_to_quarantine": windows,
            "replica0_slo_state": state0,
            "replica1_slo_state": state1,
            "replica0_quarantined": quarantined,
            "circuit_state": checker.states()[0],
            "slo_pages_total": m.counter("slo_pages_total"),
            "slo_page_signals": m.counter("slo_page_signals"),
            "requeued_requests": m.counter("requeued_requests"),
            "wall_s": round(time.monotonic() - t0, 3),
        }
        if state0 != 2:
            raise AssertionError(
                "slow replica's objective never reached PAGE "
                "(state %d)" % state0)
        if state1 == 2:
            raise AssertionError(
                "healthy replica's objective paged too — the alert "
                "is not replica-scoped")
        if not quarantined:
            raise AssertionError(
                "burn-rate page did not reach the health checker "
                "within %d sampling windows" % windows)
        if checker.states()[0] != checker.OPEN:
            raise AssertionError(
                "health circuit is not OPEN after the SLO page")
        if completed != 2 * len(prompts):
            raise AssertionError("%d/%d requests completed"
                                 % (completed, 2 * len(prompts)))
        return record
    finally:
        plan.release()
        checker.stop()
        router.stop()
        store.stop()


# ------------------------------------------------------------------- bench
def summary_record(results):
    """(record, exit_code) in the bench.py shape — metric priority in
    ONE place: scenarios completed / total once any ran."""
    done = [k for k in ("kill_one_replica_under_load",
                        "slow_replica_tail", "pool_exhaustion_storm",
                        "weight_swap_under_load",
                        "traced_flight_recorder",
                        "slo_burn_alert",
                        "fault_free_overhead") if k in results]
    if done:
        return {
            "metric": "chaos_scenarios_passed",
            "value": len(done),
            "unit": "scenarios",
            "vs_baseline": 7,
            "configs": results,
        }, 0
    return {"metric": "chaos_no_scenarios_completed", "value": None,
            "unit": None, "vs_baseline": None, "configs": results}, 1


def run_lint_leg(results):
    """The dispatch-hygiene assertion leg (ISSUE 17): every
    ``tools/veles_lint.py`` pass over the shipped tree before the
    chaos scenarios — resilience numbers for an engine whose hot path
    regressed into an implicit host sync describe a different engine
    than the one the repo ships.  Streams the bench-schema
    ``lint_clean`` record and ASSERTS zero findings."""
    import veles_lint
    findings, _, stats = veles_lint.run_check()
    record = veles_lint.clean_record(findings, stats)[0]
    print(json.dumps(record), flush=True)
    assert not findings, (
        "lint_clean leg: %d finding(s) on the shipped tree — %s"
        % (len(findings), "; ".join(str(f) for f in findings[:5])))
    results["lint_clean"] = record["configs"]


def run_bench(smoke=False, n_new=16, requests=12, seed=0):
    if smoke:
        n_new, requests = 8, 6
    vocab, max_len = 16, 64
    params = build_params(vocab=vocab, d_model=32, n_heads=2,
                          n_layers=2, max_len=max_len, seed=7)
    n_heads = 2
    prompts = mixed_length_prompts(requests, vocab, 4,
                                   max_len - n_new - 8, seed=seed + 13)
    expect = expected_rows(params, prompts, n_new, n_heads, max_len)
    results = {"model": {"vocab": vocab, "max_len": max_len},
               "requests": requests, "n_new": n_new}

    def stream():
        record, _ = summary_record(results)
        print(json.dumps(record), flush=True)

    # lint_clean first (ISSUE 17): cheap, and a dirty tree should
    # refuse the run before any scenario burns wall clock
    run_lint_leg(results)
    results["kill_one_replica_under_load"] = scenario_kill_replica(
        params, n_heads, max_len, prompts, n_new, expect)
    stream()
    results["slow_replica_tail"] = scenario_slow_replica(
        params, n_heads, max_len, prompts[:max(4, requests // 2)],
        n_new, expect)
    stream()
    results["pool_exhaustion_storm"] = scenario_pool_storm(
        params, n_heads, max_len, prompts, n_new, expect)
    stream()
    params_new = build_params(vocab=vocab, d_model=32, n_heads=2,
                              n_layers=2, max_len=max_len, seed=11)
    expect_new = expected_rows(params_new, prompts, n_new, n_heads,
                               max_len)
    results["weight_swap_under_load"] = scenario_weight_swap(
        params, params_new, n_heads, max_len, prompts, n_new, expect,
        expect_new)
    stream()
    results["traced_flight_recorder"] = scenario_traced_flight_recorder(
        params, n_heads, max_len, prompts, n_new, expect)
    stream()
    results["slo_burn_alert"] = scenario_slo_burn_alert(
        params, n_heads, max_len, prompts[:max(4, requests // 2)],
        n_new, expect)
    stream()
    results["fault_free_overhead"] = scenario_overhead(
        params, n_heads, max_len, prompts[:4], n_new)
    stream()
    return results


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--smoke", action="store_true",
                        help="tiny sizes (CI validation)")
    parser.add_argument("--n-new", type=int, default=16)
    parser.add_argument("--requests", type=int, default=12)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--json", default=None, metavar="FILE",
                        help="also write the final record here")
    args = parser.parse_args(argv)
    results = run_bench(smoke=args.smoke, n_new=args.n_new,
                        requests=args.requests, seed=args.seed)
    record, rc = summary_record(results)
    line = json.dumps(record)
    print(line)                  # final full record — last line wins
    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            f.write(line + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())

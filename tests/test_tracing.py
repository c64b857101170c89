"""End-to-end request tracing (ISSUE 12): the span tracer, the flight
recorder, the cost ledger, and the tracer threaded through engine /
router / HTTP — including the acceptance combo (prefix_cache +
prefill_chunk + spec_k + paged_kv + tp dryrun) exporting a valid
Chrome trace with complete span trees."""

import json
import time
import urllib.request

import numpy
import pytest

import jax.numpy as jnp

from lm_cases import assert_greedy, check_tokens, kinds_model, make_engine
from veles_tpu import prng
from veles_tpu.ops.transformer import generate, init_transformer_params


def tiny_params(vocab=16, d_model=32, n_heads=2, n_layers=2,
                max_len=64, seed=7):
    import jax
    prng.reset()
    prng.seed_all(seed)
    host = init_transformer_params(prng.get("init"), vocab,
                                   d_model=d_model, n_heads=n_heads,
                                   n_layers=n_layers, max_len=max_len)
    return jax.tree.map(jnp.asarray, host)


def greedy_rows(params, prompts, n_new, n_heads=2, max_len=64):
    return [numpy.asarray(generate(
        params, jnp.asarray([p], jnp.int32), n_new, n_heads,
        temperature=0.0, max_len=max_len))[0] for p in prompts]


class TestSpanTracer:
    def test_span_tree_ring_and_waterfall(self):
        from veles_tpu.serving.tracing import (SpanTracer,
                                               format_waterfall,
                                               verify_integrity)
        tr = SpanTracer(mode="all", last=2)
        ctx = tr.start_request(rid="abc", name="http.request",
                               cat="http")
        h = tr.begin(ctx, "queue.wait", cat="queue")
        tr.end(h, attrs={"wait_s": 0.001})
        h2 = tr.begin(ctx, "attempt", cat="router",
                      attrs={"replica": 0})
        child = ctx.at(h2[1])
        t = time.monotonic()
        tr.add_many([child], "decode.step", "decode", t, t + 0.002,
                    attrs={"backend": "xla", "bucket": 4})
        tr.end(h2)
        rec = tr.finish_request(ctx)
        assert rec["rid"] == "abc" and rec["error"] is None
        assert verify_integrity([rec])["spans"] == 4
        # the decode span nests under the attempt, not the root
        step = next(s for s in rec["spans"]
                    if s["name"] == "decode.step")
        assert step["parent"] == h2[1]
        text = format_waterfall(rec)
        assert "http.request" in text and "decode.step" in text
        # ring bound: a third request evicts the first
        for i in range(2):
            c = tr.start_request(rid="r%d" % i)
            tr.finish_request(c)
        rids = [r["rid"] for r in tr.requests()]
        assert rids == ["r0", "r1"]
        assert tr.find("abc") is None and tr.find("r1") is not None

    def test_modes_errors_and_sampling(self):
        from veles_tpu.serving.tracing import SpanTracer
        tr = SpanTracer(mode="errors")
        ok = tr.start_request()
        tr.finish_request(ok)
        bad = tr.start_request()
        tr.finish_request(bad, error=RuntimeError("boom"))
        recs = tr.requests()
        assert len(recs) == 1 and "boom" in recs[0]["error"]
        # errored requests auto-dump their waterfall
        assert len(tr.dumps()) == 1 and tr.dumps()[0]["text"]
        # deadline-blown requests are retained and dumped too
        shed = tr.start_request()
        tr.finish_request(shed, deadline=True)
        assert tr.requests()[-1]["deadline_blown"]
        assert len(tr.dumps()) == 2
        # sample:0 traces nothing, sample:1 everything — seeded
        none = SpanTracer(mode="sample", sample=0.0)
        assert none.start_request() is None
        assert none.stats()["sampled_out"] == 1
        full = SpanTracer(mode="sample", sample=1.0)
        assert full.start_request() is not None

    def test_from_spec(self):
        from veles_tpu.serving.tracing import SpanTracer
        assert SpanTracer.from_spec(None) is None
        assert SpanTracer.from_spec("off") is None
        assert SpanTracer.from_spec(False) is None
        assert SpanTracer.from_spec("all").mode == "all"
        assert SpanTracer.from_spec(True).mode == "all"
        assert SpanTracer.from_spec("errors").mode == "errors"
        s = SpanTracer.from_spec("sample:0.25")
        assert s.mode == "sample" and s.sample == 0.25
        t = SpanTracer(mode="all")
        assert SpanTracer.from_spec(t) is t
        with pytest.raises(ValueError):
            SpanTracer.from_spec("sometimes")

    def test_unclosed_span_flagged_and_caught(self):
        from veles_tpu.serving.tracing import (SpanTracer,
                                               verify_integrity)
        tr = SpanTracer(mode="all")
        ctx = tr.start_request()
        tr.begin(ctx, "leaky")           # never ended
        rec = tr.finish_request(ctx)
        assert rec["unclosed"] == ["leaky"]
        with pytest.raises(AssertionError, match="unclosed"):
            verify_integrity([rec])
        # an orphan parent is caught too
        orphan = {"rid": "x", "error": None, "deadline_blown": False,
                  "unclosed": [],
                  "spans": [{"sid": 1, "parent": None, "name": "root",
                             "cat": "r", "t0": 0.0, "t1": 1.0,
                             "attrs": {}},
                            {"sid": 2, "parent": 99, "name": "lost",
                             "cat": "s", "t0": 0.0, "t1": 1.0,
                             "attrs": {}}]}
        with pytest.raises(AssertionError, match="ORPHAN"):
            verify_integrity([orphan])

    def test_ledger_dedups_batched_dispatches(self):
        from veles_tpu.serving.tracing import SpanTracer, cost_ledger
        tr = SpanTracer(mode="all")
        a, b = tr.start_request(), tr.start_request()
        t = time.monotonic()
        # one batched dispatch serving two requests...
        tr.add_many([a, b], "decode.step", "decode", t, t + 0.004,
                    attrs={"backend": "xla", "bucket": 2})
        # ...and one single-lane dispatch
        tr.add_many([a], "decode.step", "decode", t, t + 0.002,
                    attrs={"backend": "xla", "bucket": 2})
        recs = [tr.finish_request(a), tr.finish_request(b)]
        rows = cost_ledger(recs)
        assert len(rows) == 1
        row = rows[0]
        assert row["dispatches"] == 2 and row["lanes"] == 3
        # spans without a backend attr (non-device marks) stay out
        assert cost_ledger([{"rid": "x", "spans": [
            {"sid": 1, "parent": None, "name": "queue.wait",
             "cat": "queue", "t0": 0.0, "t1": 1.0, "attrs": {}}],
            "error": None, "deadline_blown": False,
            "unclosed": []}]) == []

    def test_max_spans_bounds_a_request(self):
        from veles_tpu.serving.tracing import SpanTracer
        tr = SpanTracer(mode="all", max_spans=4)
        ctx = tr.start_request()
        handles = [tr.begin(ctx, "s%d" % i) for i in range(6)]
        assert sum(1 for h in handles if h is not None) == 3  # + root
        for h in handles:
            tr.end(h)
        rec = tr.finish_request(ctx)
        assert len(rec["spans"]) == 4
        assert tr.stats()["dropped_spans"] == 3


class TestEngineTracing:
    N_NEW = 8

    def _run(self, tracer, prompts, expect, tp=0, **kw):
        from veles_tpu.serving import LMEngine, ServingMetrics
        params = tiny_params()
        engine = LMEngine(params, n_heads=2, max_len=64, slots=2,
                          metrics=ServingMetrics("trc_t"),
                          tracer=tracer, tp=tp, **kw).start()
        try:
            futures = [engine.submit(p, self.N_NEW) for p in prompts]
            outs = [f.result(timeout=120) for f in futures]
        finally:
            engine.stop()
        for p, out, exp in zip(prompts, outs, expect):
            numpy.testing.assert_array_equal(
                numpy.concatenate([p, out]), exp)
        return futures

    def test_full_fastpath_traced_chrome_export(self):
        """The acceptance combo minus tp: prefix_cache + prefill_chunk
        + spec_k + paged_kv, traced — parity unchanged, every span
        tree complete, the Chrome export strict-valid with root →
        queue/prefill/decode spans, and the cost ledger populated."""
        from veles_tpu.serving.tracing import (SpanTracer, cost_ledger,
                                               verify_integrity)
        params = tiny_params()
        prompts = [[1, 2, 3, 4, 5, 6, 7, 8, 9, 10], [2, 4, 6, 8],
                   [1, 2, 3, 4, 5, 6, 7, 8, 2, 1]]
        expect = greedy_rows(params, prompts, self.N_NEW)
        tracer = SpanTracer(mode="all", last=16)
        self._run(tracer, prompts, expect, prefill_chunk=8,
                  prefix_cache=32, spec_k=2, paged_kv=True)
        recs = tracer.requests()
        integ = verify_integrity(recs)
        assert integ["requests"] == len(prompts)
        names = {s["name"] for r in recs for s in r["spans"]}
        assert {"engine.request", "queue.wait", "prefill.chunk",
                "decode.verify"} <= names
        chrome = tracer.export_chrome()
        # strict JSON (what Perfetto/chrome://tracing require) with
        # X events carrying rid/sid/parent join keys
        parsed = json.loads(json.dumps(chrome, allow_nan=False))
        xs = [e for e in parsed["traceEvents"] if e["ph"] == "X"]
        assert xs and all("rid" in e["args"] and "ts" in e
                          and "dur" in e for e in xs)
        rows = cost_ledger(recs)
        assert rows and all(r["backend"] == "xla" for r in rows)
        ops = {r["op"] for r in rows}
        assert "decode.verify" in ops and "prefill.chunk" in ops
        # dispatch counts are deduped: total dispatches must not
        # exceed total lanes
        assert all(r["dispatches"] <= r["lanes"] for r in rows)

    def test_tp_traced_acceptance_combo(self, serving_mesh):
        """The FULL acceptance combo: prefix_cache + prefill_chunk +
        spec_k + paged_kv + tp=2 (CPU dryrun mesh), traced end to
        end — greedy parity, complete span trees, and the ledger's
        backend column names the tp path."""
        serving_mesh(2)
        from veles_tpu.serving.tracing import (SpanTracer, cost_ledger,
                                               verify_integrity)
        params = tiny_params()
        prompts = [[1, 2, 3, 4, 5, 6, 7, 8, 9, 10], [2, 4, 6, 8]]
        expect = greedy_rows(params, prompts, self.N_NEW)
        tracer = SpanTracer(mode="all", last=16)
        self._run(tracer, prompts, expect, tp=2, prefill_chunk=8,
                  prefix_cache=32, spec_k=2, paged_kv=True)
        recs = tracer.requests()
        assert verify_integrity(recs)["requests"] == len(prompts)
        rows = cost_ledger(recs)
        assert rows and all(r["backend"] == "xla-tp2" for r in rows)
        json.loads(json.dumps(tracer.export_chrome(), allow_nan=False))

    def test_flight_recorder_reconstructs_faulted_request(self):
        """Inject a chunk fault mid-prefill: the failed request's
        timeline — including the failed dispatch — reconstructs from
        the ring AFTER the fact, and was auto-dumped on failure."""
        from veles_tpu.serving import (FaultPlan, LMEngine,
                                       ServingMetrics)
        from veles_tpu.serving.tracing import (SpanTracer,
                                               format_waterfall,
                                               verify_integrity)
        params = tiny_params()
        prompts = [[1, 2, 3, 4, 5, 6, 7, 8, 9, 10],
                   [2, 4, 6, 8, 1, 3], [5, 5, 5, 5, 5, 5, 5, 5]]
        expect = greedy_rows(params, prompts, self.N_NEW)
        plan = FaultPlan(seed=0).arm("engine.chunk", kind="error",
                                     calls={2})
        tracer = SpanTracer(mode="all", last=16)
        engine = LMEngine(params, n_heads=2, max_len=64, slots=2,
                          prefill_chunk=8, faults=plan, tracer=tracer,
                          metrics=ServingMetrics("rec_t")).start()
        try:
            futures = [engine.submit(p, self.N_NEW) for p in prompts]
            failed, survived = [], 0
            for p, f, exp in zip(prompts, futures, expect):
                try:
                    out = f.result(timeout=120)
                except Exception:   # noqa: BLE001 — the injected fault
                    failed.append(f)
                    continue
                numpy.testing.assert_array_equal(
                    numpy.concatenate([p, out]), exp)
                survived += 1
        finally:
            engine.stop()
        assert len(failed) == 1 and survived == 2
        rid = failed[0].request.trace.rid
        rec = tracer.find(rid)
        assert rec is not None and "InjectedFault" in rec["error"]
        fault_span = [s for s in rec["spans"]
                      if s["name"] == "prefill.chunk"
                      and "error" in s["attrs"]]
        assert fault_span, "failed dispatch missing from the timeline"
        assert "InjectedFault" in format_waterfall(rec)
        assert rid in {d["rid"] for d in tracer.dumps()}
        verify_integrity(tracer.requests())

    def test_untraced_engine_unchanged(self):
        """tracer=None is the default: no trace fields set, no spans
        anywhere, parity as ever — the unarmed contract."""
        params = tiny_params()
        prompts = [[1, 2, 3, 4]]
        expect = greedy_rows(params, prompts, self.N_NEW)
        futures = self._run(None, prompts, expect, prefill_chunk=8)
        assert futures[0].request.trace is None


class TestRouterTracing:
    def test_retry_shows_both_attempts(self):
        """A request whose first attempt dies on a faulted replica
        completes on the second; its ONE trace shows the errored
        attempt, the retry marker, and the winning attempt with the
        engine spans nested under it."""
        from veles_tpu.serving import (FaultPlan, LMEngine, Router,
                                       ServingMetrics)
        from veles_tpu.serving.tracing import (SpanTracer,
                                               verify_integrity)
        params = tiny_params()
        prompts = [[1, 2, 3, 4, 5, 6, 7, 8, 9, 10], [2, 4, 6, 8]]
        expect = greedy_rows(params, prompts, 8)
        plan = FaultPlan(seed=0).arm("engine.chunk", kind="error",
                                     calls={1})
        tracer = SpanTracer(mode="all", last=16)
        replicas = [
            LMEngine(params, n_heads=2, max_len=64, slots=2,
                     prefill_chunk=8, name="rtr_t_r%d" % i,
                     metrics=ServingMetrics(
                         "rtr_t", labels={"replica": str(i)}),
                     faults=plan if i == 0 else None, tracer=tracer)
            for i in range(2)]
        router = Router(replicas, retries=2, tracer=tracer).start()
        try:
            futures = [router.submit(p, 8) for p in prompts]
            for p, f, exp in zip(prompts, futures, expect):
                numpy.testing.assert_array_equal(
                    numpy.concatenate([p, f.result(timeout=120)]), exp)
        finally:
            time.sleep(0.1)      # let hedge-loser/zombie spans settle
            router.stop()
        assert router.metrics.counter("requests_retried") >= 1
        recs = tracer.requests()
        verify_integrity(recs)
        retried = [r for r in recs
                   if sum(1 for s in r["spans"]
                          if s["name"] == "attempt") > 1]
        assert retried, "no trace shows a second attempt"
        rec = retried[0]
        attempts = [s for s in rec["spans"] if s["name"] == "attempt"]
        assert any("error" in s["attrs"] for s in attempts)
        winner = next(s for s in attempts
                      if s["attrs"].get("outcome") == "ok")
        # engine spans of the winning attempt nest under it
        nested = [s for s in rec["spans"]
                  if s["parent"] == winner["sid"]]
        assert any(s["name"] == "queue.wait" for s in nested)
        assert any(s["name"] == "retry.backoff"
                   for s in rec["spans"])


class TestHTTPTracing:
    def _api(self, tracer, params):
        """A serve_lm-shaped API (engine handler + tracer) without the
        char_lm training cost."""
        from veles_tpu.restful_api import RESTfulAPI
        from veles_tpu.serving import LMEngine, ServingMetrics

        engine = LMEngine(params, n_heads=2, max_len=64, slots=2,
                          prefill_chunk=8,
                          metrics=ServingMetrics("http_trc"),
                          tracer=tracer).start()

        def handler(request):
            prompt = numpy.asarray(request["input"], numpy.int32)
            toks = engine.generate(prompt,
                                   int(request.get("n_new", 4)))
            return {"tokens": toks.tolist()}

        api = RESTfulAPI(None, handler=handler, metrics=engine.metrics,
                         tracer=tracer)
        api.lm_engine = engine
        return api.start(port=0)

    def _post(self, port, payload, rid=None, path="/predict"):
        headers = {"Content-Type": "application/json"}
        if rid:
            headers["X-Request-Id"] = rid
        req = urllib.request.Request(
            "http://127.0.0.1:%d%s" % (port, path),
            data=json.dumps(payload).encode(), headers=headers)
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                return resp.status, json.loads(resp.read()), \
                    resp.headers
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read()), e.headers

    def test_request_id_echo_and_trace_json(self):
        """Satellite + tentpole surface: every reply (success AND
        structured error) carries request_id — echoed from
        X-Request-Id or generated — and GET /trace.json exports the
        flight recorder with the client's rid as the join key."""
        from veles_tpu.serving.tracing import SpanTracer
        params = tiny_params()
        tracer = SpanTracer(mode="all", last=32)
        api = self._api(tracer, params)
        try:
            code, out, hdrs = self._post(
                api.port, {"input": [[1, 2, 3]], "n_new": 4},
                rid="client-key-1")
            assert code == 200
            assert out["request_id"] == "client-key-1"
            assert hdrs["X-Request-Id"] == "client-key-1"
            # generated when absent — echoed in header and body alike
            code, out, hdrs = self._post(
                api.port, {"input": [[2, 4, 6]], "n_new": 4})
            assert code == 200
            assert out["request_id"] == hdrs["X-Request-Id"]
            assert len(out["request_id"]) == 16
            # structured errors carry it too
            code, out, _ = self._post(api.port, {"nope": 1},
                                      rid="bad-1")
            assert code == 400 and out["request_id"] == "bad-1"
            code, out, _ = self._post(api.port, {"input": [[1]]},
                                      rid="lost-1", path="/nowhere")
            assert code == 404 and out["request_id"] == "lost-1"
            # the exported trace joins on the same ids
            with urllib.request.urlopen(
                    "http://127.0.0.1:%d/trace.json?last=8" % api.port,
                    timeout=10) as resp:
                trace = json.loads(resp.read())
            xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
            rids = {e["args"].get("rid") for e in xs}
            assert "client-key-1" in rids and "bad-1" in rids
            names = {e["name"] for e in xs}
            assert "http.request" in names and "decode.step" in names
            # root spans carry the reply status
            statuses = {e["args"].get("status") for e in xs
                        if e["name"] == "http.request"}
            assert {200, 400, 404} <= statuses
        finally:
            api.stop()

    def test_request_id_stamped_without_tracer(self):
        """The request_id satellite holds with tracing off."""
        params = tiny_params()
        api = self._api(None, params)
        try:
            code, out, hdrs = self._post(
                api.port, {"input": [[1, 2, 3]], "n_new": 4},
                rid="no-trace-1")
            assert code == 200 and out["request_id"] == "no-trace-1"
            assert hdrs["X-Request-Id"] == "no-trace-1"
            # /trace.json is 404 when no tracer is armed
            try:
                urllib.request.urlopen(
                    "http://127.0.0.1:%d/trace.json" % api.port,
                    timeout=10)
                assert False, "expected 404"
            except urllib.error.HTTPError as e:
                assert e.code == 404
        finally:
            api.stop()


class TestReviewHardening:
    """Pins for the review fixes: sampled-out propagation, hedge-loser
    span closure under an upstream-owned root, last=0 trim."""

    def test_sample_decision_made_once_across_layers(self):
        """sample:P rolls the coin ONCE at the outermost armed layer:
        a sampled-out request must not re-root partial trees at the
        router or engine (the 1-(1-P)^3 inflation bug)."""
        from veles_tpu.serving import (LMEngine, Router,
                                       ServingMetrics)
        from veles_tpu.serving.tracing import SpanTracer
        params = tiny_params()
        tracer = SpanTracer(mode="sample", sample=0.0)
        replicas = [
            LMEngine(params, n_heads=2, max_len=64, slots=2,
                     prefill_chunk=8, name="smp_r%d" % i,
                     metrics=ServingMetrics(
                         "smp", labels={"replica": str(i)}),
                     tracer=tracer)
            for i in range(2)]
        router = Router(replicas, tracer=tracer).start()
        try:
            futs = [router.submit([1, 2, 3, 4], 4) for _ in range(3)]
            for f in futs:
                f.result(timeout=60)
        finally:
            router.stop()
        stats = tracer.stats()
        # one roll per request — the engines never rolled again
        assert stats["started"] == 3
        assert stats["sampled_out"] == 3
        assert stats["retained"] == 0 and stats["live"] == 0

    def test_hedge_loser_spans_closed_under_upstream_root(self):
        """An upstream-owned (HTTP-shaped) root seals the trace the
        moment the handler returns — the hedge loser's attempt span
        must already be closed (outcome hedge-lost), never flagged
        unclosed."""
        from veles_tpu.serving import (FaultPlan, LMEngine, Router,
                                       ServingMetrics)
        from veles_tpu.serving import tracing
        from veles_tpu.serving.tracing import (SpanTracer,
                                               verify_integrity)
        params = tiny_params()
        prompts = [[1, 2, 3, 4, 5, 6], [2, 4, 6, 8]]
        expect = greedy_rows(params, prompts, 8)
        plan = FaultPlan(seed=0).arm("engine.step", kind="latency",
                                     latency_s=0.15)
        tracer = SpanTracer(mode="all", last=16)
        replicas = [
            LMEngine(params, n_heads=2, max_len=64, slots=2,
                     prefill_chunk=8, name="hdg_r%d" % i,
                     metrics=ServingMetrics(
                         "hdg", labels={"replica": str(i)}),
                     faults=plan if i == 0 else None, tracer=tracer)
            for i in range(2)]
        router = Router(replicas, hedge_after_s=0.25,
                        tracer=tracer).start()
        recs = []
        try:
            for p, exp in zip(prompts, expect):
                root = tracer.start_request(rid="up-%d" % len(recs),
                                            name="http.request",
                                            cat="http")
                with tracing.use(root):
                    fut = router.submit(p, 8)
                out = fut.result(timeout=120)
                # seal IMMEDIATELY, exactly like do_POST's finally —
                # the loser may still be decoding on the slow replica
                recs.append(tracer.finish_request(root))
                numpy.testing.assert_array_equal(
                    numpy.concatenate([p, out]), exp)
        finally:
            plan.release()
            router.stop()
        assert router.metrics.counter("requests_hedged") >= 1
        verify_integrity(recs)
        lost = [s for r in recs for s in r["spans"]
                if s["attrs"].get("outcome") == "hedge-lost"]
        assert lost, "no hedge-lost attempt recorded"

    def test_requests_last_zero_is_empty(self):
        from veles_tpu.serving.tracing import SpanTracer
        tr = SpanTracer(mode="all")
        for _ in range(3):
            tr.finish_request(tr.start_request())
        assert tr.requests(last=0) == []
        assert len(tr.requests(last=2)) == 2
        assert len(tr.export_chrome(last=0)["traceEvents"]) == 1  # meta

    def test_injected_503_not_flagged_deadline(self):
        """An injected transient HTTP 503 (the retryable-blip shape)
        is an error dump but NOT a deadline shed — only a real
        DeadlineExceeded sets deadline_blown."""
        from veles_tpu.restful_api import RESTfulAPI
        from veles_tpu.serving import FaultPlan
        from veles_tpu.serving.tracing import SpanTracer
        plan = FaultPlan(seed=0).arm("http.request", kind="error",
                                     exc="http_503", times=1)
        tracer = SpanTracer(mode="all", last=8)
        api = RESTfulAPI(None, handler=lambda req: {"ok": True},
                         faults=plan, tracer=tracer).start(port=0)
        try:
            req = urllib.request.Request(
                "http://127.0.0.1:%d/predict" % api.port,
                data=json.dumps({"input": [[1]]}).encode(),
                headers={"Content-Type": "application/json",
                         "X-Request-Id": "blip-1"})
            try:
                urllib.request.urlopen(req, timeout=30)
                assert False, "expected 503"
            except urllib.error.HTTPError as e:
                assert e.code == 503
                assert json.loads(e.read())["request_id"] == "blip-1"
        finally:
            api.stop()
        rec = tracer.find("blip-1")
        assert rec is not None and rec["error"] == "http 503"
        assert rec["deadline_blown"] is False

    def test_batcher_injected_dispatch_fault_keeps_trees_sound(self):
        """A batcher.dispatch fault fails its clients with their
        queue-wait spans CLOSED — no unclosed spans in the finished
        trees (the fault fires after the spans close)."""
        from veles_tpu.serving import FaultPlan, MicroBatcher
        from veles_tpu.serving.tracing import (SpanTracer,
                                               verify_integrity)
        plan = FaultPlan(seed=0).arm("batcher.dispatch", kind="error",
                                     calls={1})
        tracer = SpanTracer(mode="all", last=8)
        mb = MicroBatcher(lambda x: x * 2, max_batch=4,
                          sample_shape=(2,), faults=plan,
                          tracer=tracer).start()
        try:
            with pytest.raises(Exception, match="injected"):
                mb.submit(numpy.ones((1, 2), numpy.float32))
            out = mb.submit(numpy.ones((1, 2), numpy.float32))
            numpy.testing.assert_array_equal(
                out, 2 * numpy.ones((1, 2), numpy.float32))
        finally:
            mb.stop()
        recs = tracer.requests()
        assert len(recs) == 2
        verify_integrity(recs)
        assert any(r["error"] and "injected" in r["error"]
                   for r in recs)


# ------------------------------------------------- the loop recorder (ISSUE 26)
def _stamps(row):
    from veles_tpu.serving import tracing
    return row[tracing.COL_STAMPS:tracing.COL_END + 1]


class TestLoopRecorder:
    """The always-on engine-loop recorder: what it records and that it
    changes nothing.  No case asserts a duration: only order, counts
    and identities."""

    N_NEW = 8
    PROMPTS = [[1, 2, 3], [2, 4, 6, 8], [5, 1, 5, 1, 5, 1, 5, 1, 5, 1],
               [7, 7], [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5]]
    #: the three decode drivers of LMEngine._serve_loop, and the plain
    #: one over two kinds of cache (``kinds``: lm_cases.kinds_model)
    DRIVERS = {
        "plain": dict(prefill_chunk=8, paged_kv=True),
        "speculative": dict(prefill_chunk=8, paged_kv=True, spec_k=2),
        "megastep": dict(prefill_chunk=8, paged_kv=True, megastep=4),
        "plain_kinds": dict(prefill_chunk=8, paged_kv=True, kinds=True),
    }

    #: and the plain one over what else a lane may hold
    #: (``lm_cases.make_engine``): a pool of latent rows, a slot of
    #: recurrent state, the drafting module's pool and two tokens a step
    KINDS = ["plain_latent", "plain_linear", "plain_mtp"]

    def _engine(self, name="rec_t", kinds=False, kind=None, **kw):
        from veles_tpu.serving import LMEngine, ServingMetrics
        if kind is not None:
            return make_engine(kind, name=name, slots=2, **kw)
        record, params = kinds_model() if kinds else (2, tiny_params())
        return LMEngine(params, record, max_len=64, slots=2,
                        metrics=ServingMetrics(name), name=name, **kw)

    def _serve(self, prompts=None, **kw):
        engine = self._engine(**kw).start()
        try:
            futures = [engine.submit(p, self.N_NEW)
                       for p in prompts or self.PROMPTS]
            outs = [f.result(timeout=120) for f in futures]
        finally:
            engine.stop()
        return engine, outs

    def test_ring_wraps_and_keeps_the_newest(self):
        from veles_tpu.serving import tracing
        rec = tracing.LoopRecorder("wrap", capacity=8)
        for _ in range(21):
            rec.turn()
            rec.mark(tracing.ADMIT)
        rec.close()
        turns = rec.turns()
        assert rec.head == 21
        # the newest, less the one place the writer may be filling now
        assert turns[:, tracing.COL_SEQ].tolist() == list(range(15, 22))
        assert rec.turns(last=3)[:, tracing.COL_SEQ].tolist() \
            == [19, 20, 21]
        with pytest.raises(ValueError, match="power of two"):
            tracing.LoopRecorder("odd", capacity=12)

    def test_dispatch_ring_wraps_and_keeps_the_newest(self):
        """ISSUE 38: the dispatch ring holds twice the turns' capacity (a
        turn makes at most two dispatches), wraps like the turn ring, and
        a handle writes into the row it names."""
        from veles_tpu.serving import tracing
        rec = tracing.LoopRecorder("dwrap", capacity=4)
        handles = []
        for n in range(21):
            rec.turn()
            handles.append(rec.dispatch(
                tracing.STEP_DISPATCH, _program_stub, lanes=n + 1))
            rec.returned(handles[-1])
        rec.fetched(handles[-2])            # the fetch one dispatch late
        rec.close()
        assert handles == list(range(1, 22)) and rec.dispatch_head == 21
        rows = rec.dispatches()
        assert rows.shape[1] == tracing.DISPATCH_WIDTH
        # the newest, less the one place the writer may be filling now
        assert rows[:, tracing.DCOL_SEQ].tolist() == list(range(15, 22))
        assert rows[:, tracing.DCOL_LANES].tolist() == list(range(15, 22))
        assert rows[:, tracing.DCOL_TURN].tolist() == list(range(15, 22))
        assert (rows[:, tracing.DCOL_RETURNED]
                >= rows[:, tracing.DCOL_CALL]).all()
        assert rec.dispatches(last=2)[:, tracing.DCOL_SEQ].tolist() \
            == [20, 21]
        late, newest = rec.dispatches(last=2)
        assert late[tracing.DCOL_FETCHED] >= newest[tracing.DCOL_RETURNED]
        assert late[tracing.DCOL_FETCH_TURN] == 21 \
            and late[tracing.DCOL_TURN] == 20
        assert newest[tracing.DCOL_FETCHED] == 0 \
            == newest[tracing.DCOL_FETCH_TURN]

    @pytest.mark.parametrize("ring", ["turns", "dispatches"])
    @pytest.mark.parametrize("when", ["during", "before"])
    def test_a_copy_taken_under_the_writer_drops_replaced_rows(self, ring,
                                                                when):
        """The reader's rule, for both rings: a row the writer replaced
        while the copy ran (``during``: it wrote on behind the copy;
        ``before``: it had committed rows the first reading of the head
        did not count yet) is dropped, never handed out under the
        sequence number of the row it replaced."""
        from veles_tpu.serving import tracing
        rec = tracing.LoopRecorder("torn", capacity=8)

        def write(n):
            for _ in range(n):
                rec.turn()
                rec.dispatch(tracing.STEP_DISPATCH, _program_stub, lanes=1)
                rec.dispatch(tracing.STEP_DISPATCH, _program_stub, lanes=1)
            rec.close()

        write(10)
        store, head = {"turns": (rec._ring, lambda: rec.head),
                       "dispatches": (rec._dring,
                                      lambda: rec.dispatch_head)}[ring]
        size = len(store)
        h0 = head()
        reads = []

        def moving_head():
            reads.append(1)
            if len(reads) == 1:
                if when == "before":
                    write(3)
                return h0
            if when == "during":
                write(3)
            return head()

        got = tracing._ring_copy(store, moving_head, None)[:, 0].tolist()
        h1 = head()
        assert h1 > h0
        # what the writer replaced, or may be replacing now, is gone; what
        # it had not committed when the copy began is not handed out
        assert got == list(range(h1 + 2 - size, h0 + 1))
        whole = tracing._ring_copy(store, head, None)
        assert whole[:, 0].tolist() == list(range(h1 + 2 - size, h1 + 1))

    def test_skipped_phases_have_no_length(self):
        """A turn that went tick -> admit -> wait: every later phase
        starts and ends at the turn's end; the next turn starts there."""
        from veles_tpu.serving import tracing
        rec = tracing.LoopRecorder("skip", capacity=4)
        rec.turn()
        rec.mark(tracing.ADMIT)
        rec.mark(tracing.WAIT)
        rec.turn()
        rec.mark(tracing.ADMIT)
        rec.dispatch(tracing.STEP_DISPATCH, _program_stub, lanes=2)
        rec.close()
        first, second = rec.turns()
        s = _stamps(first)
        assert (numpy.diff(s) >= 0).all()
        assert len(set(s[tracing.WAIT + 1:].tolist())) == 1
        assert s[-1] == _stamps(second)[0]
        assert first[tracing.COL_STEP_PROGRAM] == 0
        assert rec.programs[second[tracing.COL_STEP_PROGRAM]] \
            == "_program_stub"
        assert second[tracing.COL_ACTIVE] == 2
        # prefill phases were skipped: they collapse onto step.dispatch
        s2 = _stamps(second)
        assert s2[tracing.WAIT] == s2[tracing.STEP_DISPATCH]

    @pytest.mark.parametrize("driver", sorted(DRIVERS))
    def test_turns_and_requests_match_the_engine(self, driver):
        """Per decode driver: tokens are the greedy ones; the phases of
        every turn partition it and turns leave no hole; the turns'
        programs and lane counts match the dispatch counters; request
        stamps are ordered and the token stamps number n_new, summing
        to the tokens_out counter."""
        from veles_tpu.serving import tracing
        engine, outs = self._serve(**self.DRIVERS[driver])
        for p, out in zip(self.PROMPTS, outs):
            assert_greedy(engine, p, out, self.N_NEW)
        rec = engine.recorder
        assert rec is tracing.recorders()[-1]
        turns = rec.turns()
        counters = engine.metrics.snapshot()["counters"]
        # partition: stamps never go back, a turn ends where the next
        # begins, sequence numbers are consecutive from 1
        stamps = turns[:, tracing.COL_STAMPS:tracing.COL_END + 1]
        assert (numpy.diff(stamps, axis=1) >= 0).all()
        assert (stamps[1:, 0] == stamps[:-1, -1]).all()
        assert turns[:, tracing.COL_SEQ].tolist() \
            == list(range(1, len(turns) + 1))
        # programs and counts
        step = turns[:, tracing.COL_STEP_PROGRAM]
        chunk = turns[:, tracing.COL_PREFILL_PROGRAM]
        assert int((step > 0).sum()) == counters["decode_dispatches"]
        assert int((chunk > 0).sum()) == counters["prefill_dispatches"]
        want = {"plain": "step_all", "speculative": "verify_all",
                "megastep": "mega_plain", "plain_kinds": "step_all"}[driver]
        assert {rec.programs[i] for i in set(step[step > 0].tolist())} \
            == {want}
        assert {rec.programs[i] for i in set(chunk[chunk > 0].tolist())} \
            == {"chunk_slot"}
        # a turn that dispatched a decode passed all four step phases in
        # order; one that did not has no step.dispatch/fetch/emit time
        idle = stamps[step == 0]
        assert (idle[:, tracing.STEP_DISPATCH] == idle[:, -1]).all()
        active = turns[:, tracing.COL_ACTIVE]
        busy = turns[:, tracing.COL_BUSY]
        assert ((active >= 1) == (step > 0)).all()
        assert (active <= busy).all() and busy.max() <= engine.slots
        assert int(turns[:, tracing.COL_TOKENS].sum()) \
            == counters["tokens_out"]
        # requests
        reqs = rec.requests()
        assert len(reqs) == len(self.PROMPTS)
        for r, p in zip(sorted(reqs, key=lambda r: r.enqueue),
                        self.PROMPTS):
            assert r.outcome == "ok" and r.prompt_len == len(p)
            assert r.enqueue <= r.admit <= r.first_token <= r.done
            assert r.tokens_out == len(r.token_ns) == r.n_new \
                == self.N_NEW
            assert list(r.token_ns) == sorted(r.token_ns)
            assert r.first_token == r.token_ns[0]
            assert 0 <= r.lane < engine.slots
        assert sum(r.tokens_out for r in reqs) == counters["tokens_out"]

    @pytest.mark.parametrize("driver", sorted(DRIVERS) + KINDS)
    def test_every_dispatch_has_a_record_of_its_own(self, driver):
        """ISSUE 38, per decode driver and per kind of engine: one
        dispatch record per call of a jitted program (they
        number the two dispatch counters),
        numbered in call order; its stamps never go back; a decode
        dispatch and a tail chunk were waited for and fetched in the turn
        that called them, a chunk that is no tail never; and each names
        the turn whose row holds its program and its call's stamp.
        ISSUE 39: the plain driver fetches one dispatch late, so
        its records say the turn after (``DCOL_FETCH_TURN``) wherever the
        next step was called first, and a dispatch is no longer over
        before the next is called; the waits are still made in call
        order, inside the ``step.fetch`` of the turn that made them."""
        from veles_tpu.serving import tracing as t
        kind = driver[len("plain_"):] if driver in self.KINDS else None
        engine, outs = self._serve(**(self.DRIVERS.get(driver)
                                      or dict(kind=kind)))
        for p, out in zip(self.PROMPTS, outs):
            if kind is None:
                assert_greedy(engine, p, out, self.N_NEW)
            else:
                check_tokens(kind, engine, p, out, self.N_NEW)
        rec = engine.recorder
        counters = engine.metrics.snapshot()["counters"]
        rows, turns = rec.dispatches(), rec.turns()
        assert len(rows) == rec.dispatch_head \
            == counters["decode_dispatches"] + counters["prefill_dispatches"]
        assert rows[:, t.DCOL_SEQ].tolist() == list(range(1, len(rows) + 1))
        call, back, wait, got = (rows[:, c] for c in (
            t.DCOL_CALL, t.DCOL_RETURNED, t.DCOL_WAIT, t.DCOL_FETCHED))
        assert (numpy.diff(call) > 0).all()
        assert (call <= back).all()
        waited = wait > 0
        assert ((got > 0) == waited).all()
        assert (back[waited] <= wait[waited]).all() \
            and (wait[waited] <= got[waited]).all()
        late = driver.startswith("plain")
        if late:
            # two dispatches in flight: the jit call is back before the
            # next call, its outputs are fetched behind that one's
            assert (back[:-1] <= call[1:]).all()
            assert (numpy.diff(got[waited]) > 0).all()
            c = counters
            assert c["dispatches_sent_ahead"] + c["pipeline_drains"] \
                == c["decode_dispatches"]
            assert c["dispatches_sent_ahead"] > c["decode_dispatches"] // 2
        else:
            # a dispatch is over before the next is called: the old order
            assert (numpy.maximum(back, got)[:-1] <= call[1:]).all()
            assert "dispatches_sent_ahead" not in counters
        step = rows[:, t.DCOL_PHASE] == t.STEP_DISPATCH
        chunk = rows[:, t.DCOL_PHASE] == t.PREFILL_DISPATCH
        assert (step | chunk).all()
        assert int(step.sum()) == counters["decode_dispatches"]
        assert waited[step].all() and (rows[step, t.DCOL_LANES] >= 1).all()
        assert not rows[chunk, t.DCOL_LANES].any()
        # every request has one tail chunk, whose token is its first: it
        # is stamped behind that chunk's fetch (and, where the chunk
        # itself waits for it, before the next call)
        tails = numpy.flatnonzero(chunk & waited)
        assert len(tails) == len(self.PROMPTS) < int(chunk.sum())
        firsts = sorted(r.first_token for r in rec.requests())
        for i, first in zip(tails, firsts):
            assert got[i] <= first
            assert late or i + 1 == len(rows) or first < call[i + 1]
        behind = rows[:, t.DCOL_FETCH_TURN] - rows[:, t.DCOL_TURN]
        if late:
            assert set(behind[waited].tolist()) == {0, 1}
            assert int((behind[step] == 1).sum()) \
                == counters["dispatches_sent_ahead"]
        else:
            assert not behind[waited].any()
        assert not rows[~waited, t.DCOL_FETCH_TURN].any()
        # the span that caused it: the turn's row
        assert turns[:, t.COL_SEQ].tolist() == list(range(1, len(turns) + 1))
        mine = turns[rows[:, t.DCOL_TURN] - 1]
        for phase, col in ((t.PREFILL_DISPATCH, t.COL_PREFILL_PROGRAM),
                           (t.STEP_DISPATCH, t.COL_STEP_PROGRAM)):
            of = rows[:, t.DCOL_PHASE] == phase
            assert (mine[of, col] == rows[of, t.DCOL_PROGRAM]).all()
            assert (mine[of, t.COL_STAMPS + phase] == call[of]).all()
            # at most one of a kind a turn
            assert len(set(rows[of, t.DCOL_TURN].tolist())) == int(of.sum())
        # the waits lie in the ``step.fetch`` of the turn that made them
        # (the step's own turn in the old order, and then they ARE it)
        theirs = turns[rows[waited & step, t.DCOL_FETCH_TURN] - 1]
        assert (theirs[:, t.COL_STAMPS + t.STEP_FETCH]
                <= wait[waited & step]).all()
        assert (got[waited & step]
                <= theirs[:, t.COL_STAMPS + t.STEP_EMIT]).all()
        if not late:
            assert (mine[step, t.COL_STAMPS + t.STEP_FETCH]
                    == wait[step]).all()
            assert (mine[step, t.COL_STAMPS + t.STEP_EMIT]
                    == got[step]).all()
        assert (mine[step, t.COL_ACTIVE] == rows[step, t.DCOL_LANES]).all()
        assert {rec.programs[i] for i in rows[chunk, t.DCOL_PROGRAM]} \
            == {"chunk_slot"}

    @pytest.mark.parametrize("where", ["engine.step", "engine.chunk",
                                       "call", "fetch"])
    def test_a_dispatch_that_raises_leaves_its_record_as_it_was(
            self, where, monkeypatch):
        """A fault point fires before the jit call, so the dispatch it
        stops has no record and the records still number the counters; a
        program that raises in its call leaves a record with the call's
        stamp alone, one whose fetch raises leaves ``fetched`` 0, and so
        does the younger step that was in flight behind it (ISSUE 39:
        never waited for).  No handle leaks: the next dispatch takes the
        next row, and it is sound."""
        from veles_tpu.serving import FaultPlan, lm_engine, tracing as t
        kw = dict(prefill_chunk=8, paged_kv=True)
        if where.startswith("engine."):
            kw["faults"] = FaultPlan(seed=0).arm(where, kind="error",
                                                 calls={2})
        engine = self._engine(name="rec_raise", **kw).start()
        boom = RuntimeError("raised in the %s" % where)
        if where == "call":
            real, calls = engine._step_jit, []

            def step_all(*args):
                calls.append(1)
                if len(calls) == 2:
                    raise boom
                return real(*args)
            step_all.__name__ = real.__name__
            engine._step_jit = step_all
        elif where == "fetch":
            real_fetch, fetches = lm_engine.xfer.to_host, []

            def to_host(x):
                if isinstance(x, tuple):        # a decode step's outputs
                    fetches.append(1)
                    if len(fetches) == 2:
                        raise boom
                return real_fetch(x)
            monkeypatch.setattr(lm_engine.xfer, "to_host", to_host)
        try:
            # (twelve tokens: two chunks, so engine.chunk's second call
            # is this request's)
            fut = engine.submit(list(range(1, 13)), 6)
            with pytest.raises(Exception, match="raised|injected"):
                fut.result(timeout=60)
            ok = engine.submit([1, 2, 3], 4).result(timeout=60)
        finally:
            engine.stop()
        assert_greedy(engine, [1, 2, 3], ok, 4)
        c = engine.metrics.snapshot()["counters"]
        rows = engine.recorder.dispatches()
        assert rows[:, t.DCOL_SEQ].tolist() == list(range(1, len(rows) + 1))
        done = c["decode_dispatches"] + c["prefill_dispatches"]
        stamps = rows[:, t.DCOL_CALL:t.DCOL_FETCHED + 1]
        open_ = numpy.flatnonzero(
            (rows[:, t.DCOL_PHASE] == t.STEP_DISPATCH) & (stamps[:, 3] == 0))
        if where.startswith("engine."):
            assert len(rows) == done and not len(open_)
        else:
            # (the paged driver counts a step when its call is back,
            # under the step: the one whose fetch raised is counted)
            assert len(rows) == done + (where == "call")
            i, *younger = open_.tolist()
            assert i + 1 + len(younger) < len(rows)
            call, back, wait, got = stamps[i].tolist()
            assert call > 0 and got == 0 == rows[i, t.DCOL_FETCH_TURN]
            assert (back, wait) == (0, 0) if where == "call" \
                else call <= back <= wait
            # the fetch one dispatch late: the step behind the one whose
            # fetch raised was called and never waited for
            assert younger == ([i + 1] if where == "fetch" else [])
            for j in younger:
                call, back, wait, got = stamps[j].tolist()
                assert 0 < call <= back and (wait, got) == (0, 0)
        # every other row is sound, the one behind the failure too
        sound = numpy.ones(len(rows), bool)
        sound[open_] = False
        s = stamps[sound]
        assert (s[:, 0] > 0).all() and (s[:, 0] <= s[:, 1]).all()
        fetched = s[:, 3] > 0
        assert (s[fetched, 1] <= s[fetched, 2]).all() \
            and (s[fetched, 2] <= s[fetched, 3]).all()
        assert (s[~fetched, 2] == 0).all()
        assert (numpy.diff(rows[:, t.DCOL_CALL]) > 0).all()

    def test_the_benchmarks_readers_on_the_fetch_one_dispatch_late(
            self, tmp_path):
        """ISSUE 39, on a CPU run of the real engine under its new order
        (the profiler's host events stand in for the device, as in a
        rehearsal of the benchmark): ``benchmark/lib/dispatch_log.py``'s
        fit pairs every dispatch recorded while the trace was on with its
        execution (``dispatches_matched_share.serve`` 100) and ``parts()``
        splits the idle gaps into its three parts.  ``lib/spans.py``'s
        fit, which rebuilds the calls from "a turn's chunk, then its step,
        fetched in the same row", finds no alignment and gives None, and
        the seven readers on it are left out of the result line: the
        documented state until a ``benchmark`` issue retires them."""
        import jax
        from benchmark.lib import dispatch_log, spans, trace as trace_lib
        from benchmark.lib.files import load_module
        from veles_tpu.serving import tracing as t
        engine = self._engine(name="rec_late", **self.DRIVERS["plain"])
        engine.start()
        t_open = time.monotonic()
        try:
            for f in [engine.submit(p, 4) for p in self.PROMPTS[:2]]:
                f.result(timeout=120)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(str(tmp_path),
                                     profiler_options=options)
            begin = time.monotonic()
            try:
                futures = [engine.submit(p, 24) for p in self.PROMPTS]
                outs = [f.result(timeout=120) for f in futures]
            finally:
                jax.profiler.stop_trace()
            end = time.monotonic()
        finally:
            engine.stop()
        for p, out in zip(self.PROMPTS, outs):
            assert_greedy(engine, p, out, 24)
        c = engine.metrics.snapshot()["counters"]
        assert c["dispatches_sent_ahead"] > c["decode_dispatches"] // 2
        rec = engine.recorder
        art = {"t_open": t_open, "window_s": time.monotonic() - t_open,
               "trace_host_window": (begin, end),
               "trace": trace_lib.read(str(tmp_path)), "counters": {},
               "_spans_recorder": {"recorder": rec, "turns": rec.turns(),
                                   "tracing": t}}
        assert spans.fit(art) is None
        fitted = dispatch_log.fit(art)
        assert fitted is not None and fitted["slack"] > 0
        # every dispatch called since the trace began has its execution
        rows = rec.dispatches()
        traced = rows[rows[:, t.DCOL_CALL] >= int(begin * 1e9)]
        assert len(traced) > 50
        assert fitted["rows"][:, t.DCOL_SEQ].tolist() \
            == traced[:, t.DCOL_SEQ].tolist()
        assert [name for name, _, _ in fitted["execs"]] \
            == [rec.programs[i] for i in traced[:, t.DCOL_PROGRAM]]
        assert dispatch_log.matched_share(art) == 100.0
        parts = dispatch_log.parts(art)
        assert set(parts["ns"]) == set(dispatch_log.PARTS)
        assert min(parts["ns"].values()) >= 0 < sum(parts["ns"].values())
        assert parts["steps"] == int(
            (traced[:, t.DCOL_PHASE] == t.STEP_DISPATCH).sum())

        def read(name):
            return load_module("layer_metrics", name).read(art, None)
        for name in ("dispatches_matched_share.serve", "gap_return_ms.serve",
                     "gap_launch_ms.serve", "gap_host_ms.serve",
                     "token_return_ms.serve", "jit_call_ms.serve"):
            assert read(name) is not None, name
        for name in ("idle_admit_ms.serve", "idle_prefill_ms.serve",
                     "idle_prepare_ms.serve", "idle_dispatch_ms.serve",
                     "idle_fetch_ms.serve", "idle_emit_ms.serve",
                     "idle_attributed_share.serve"):
            assert read(name) is None, name

    @pytest.mark.parametrize("driver", sorted(DRIVERS))
    def test_page_steps_of_every_driver_reach_the_reader(self, driver):
        """ISSUE 29: every decode driver's dispatches through the kernels
        count the page steps they were handed and the live ones (a fused
        program's at the positions it entered with, once per step it
        ran); the recorder's two columns sum to the counters, and the
        benchmark's reader gives the dead share of the window's turns.
        A program whose recorder has no such columns reads None."""
        import types
        from benchmark.lib.files import load_module
        from veles_tpu.serving import tracing
        engine, outs = self._serve(attn_kernel="force",
                                   **self.DRIVERS[driver])
        for p, out in zip(self.PROMPTS, outs):
            assert_greedy(engine, p, out, self.N_NEW)
        c = engine.metrics.snapshot()["counters"]
        given, live = c["attn_page_steps"], c["attn_page_steps_live"]
        assert 0 < live < given
        assert c["attn_kernel_dispatches"] \
            == c["decode_dispatches"] + c["prefill_dispatches"]
        turns = engine.recorder.turns()
        assert int(turns[:, tracing.COL_ATTN_STEPS].sum()) == given
        assert int(turns[:, tracing.COL_ATTN_LIVE].sum()) == live
        # only turns that dispatched a program hold any
        quiet = (turns[:, tracing.COL_STEP_PROGRAM] == 0) \
            & (turns[:, tracing.COL_PREFILL_PROGRAM] == 0)
        assert not turns[quiet, tracing.COL_ATTN_STEPS].any()
        # ISSUE 43: a decode or verify dispatch is handed the live pages
        # alone (what is dead is the chunks'), in blocks that are counted
        steps = (turns[:, tracing.COL_STEP_PROGRAM] != 0) \
            & (turns[:, tracing.COL_PREFILL_PROGRAM] == 0)
        assert steps.any()
        numpy.testing.assert_array_equal(
            turns[steps, tracing.COL_ATTN_STEPS],
            turns[steps, tracing.COL_ATTN_LIVE])
        assert 0 < c["attn_walk_blocks"] <= live
        first, last = turns[0, tracing.COL_STAMPS], turns[-1, tracing.COL_END]
        art = {"t_open": (first - 1) / 1e9, "window_s": (last - first + 2) / 1e9,
               "_spans_recorder": {"recorder": engine.recorder,
                                   "turns": turns, "tracing": tracing}}
        read = load_module(
            "layer_metrics", "attn_dead_page_steps_share.serve").read
        assert read(art, None) == pytest.approx(
            100.0 * (1.0 - live / given))
        older = types.SimpleNamespace(**{
            k: v for k, v in vars(tracing).items()
            if not k.startswith("COL_ATTN")})
        art["_spans_recorder"]["tracing"] = older
        assert read(art, None) is None

    def test_shed_request_leaves_its_outcome(self):
        from veles_tpu.serving import DeadlineExceeded
        engine = self._engine(deadline_s=0.0, prefill_chunk=8).start()
        try:
            fut = engine.submit([1, 2, 3], 4)
            with pytest.raises(DeadlineExceeded):
                fut.result(timeout=60)
        finally:
            engine.stop()
        (r,) = engine.recorder.requests()
        assert r.outcome == "shed"
        assert r.admit == 0 and r.first_token == 0 and r.tokens_out == 0
        assert r.enqueue <= r.done and r.lane == -1

    @pytest.mark.parametrize("layout", [
        {"prefill_chunk": 8, "paged_kv": True}, {"kind": "latent"},
        {"kind": "linear"}, {"kind": "mtp"}],
        ids=["paged", "latent", "linear", "mtp"])
    def test_failed_request_leaves_its_outcome(self, layout):
        from veles_tpu.serving import FaultPlan, InjectedFault
        plan = FaultPlan(seed=0).arm("engine.step", kind="error",
                                     calls={1})
        engine = self._engine(faults=plan, **layout).start()
        try:
            fut = engine.submit([1, 2, 3], 4)
            with pytest.raises(InjectedFault):
                fut.result(timeout=60)
            ok = engine.submit([1, 2, 3], 4).result(timeout=60)
        finally:
            engine.stop()
        first, second = engine.recorder.requests()
        assert first.outcome == "failed" and second.outcome == "ok"
        # the failed one was admitted and had its first token (its
        # prefill's) before the decode step raised
        assert first.enqueue <= first.admit <= first.first_token \
            <= first.done
        assert first.tokens_out == 1 and len(ok) == second.tokens_out

    def test_cancelled_request_leaves_its_outcome(self):
        engine = self._engine(prefill_chunk=8)
        engine.start()
        try:
            # both lanes busy, so the third waits in the queue
            busy = [engine.submit([1, 2, 3, 4], 40) for _ in range(2)]
            queued = engine.submit([4, 3, 2, 1], 4)
            engine._cancel(queued.request)
            for f in busy:
                f.result(timeout=120)
        finally:
            engine.stop()
        by = {r.outcome: r for r in engine.recorder.requests()}
        assert set(by) == {"ok", "cancelled"}
        assert by["cancelled"].tokens_out == 0
        assert queued.cancelled()

    def test_stopped_engines_stay_readable_and_registry_is_bounded(self):
        from veles_tpu.serving import tracing
        engines = []
        for i in range(6):
            engine, _ = self._serve(prompts=[[1, 2, 3]],
                                    name="rec_b%d" % i)
            engines.append(engine)
        kept = tracing.recorders()
        assert len(kept) == 4
        assert [r.name for r in kept] == ["rec_b%d" % i
                                          for i in range(2, 6)]
        # stopped, and still whole
        for engine in engines:
            assert engine._thread is None
            assert len(engine.recorder.turns()) == engine.recorder.head
            assert len(engine.recorder.requests()) == 1

    def test_tokens_identical_with_trace_off_and_all(self):
        """The recorder is on whatever --serve-trace says, an engine
        with it off still makes no SpanTracer, and both serve the same
        tokens with records of the same shape."""
        from veles_tpu.serving import SpanTracer
        off, outs_off = self._serve(prefill_chunk=8, paged_kv=True,
                                    tracer=SpanTracer.from_spec("off"))
        on, outs_on = self._serve(prefill_chunk=8, paged_kv=True,
                                  tracer=SpanTracer.from_spec("all"))
        assert off._tracer is None and on._tracer is not None
        for a, b in zip(outs_off, outs_on):
            numpy.testing.assert_array_equal(a, b)
        for engine in (off, on):
            assert len(engine.recorder.requests()) == len(self.PROMPTS)
            assert engine.recorder.head > 0
        assert off.recorder.programs == on.recorder.programs

    def test_tracer_and_recorder_share_one_clock(self):
        """SpanTracer stamps against the process origin (no per-tracer
        origin): a span lies between two monotonic_offset() readings
        taken around it, on a tracer made at any time, and the recorder's
        stamps convert onto the same axis."""
        from veles_tpu.serving import SpanTracer, tracing
        from veles_tpu.serving.metrics import _ORIGIN, monotonic_offset
        before = monotonic_offset()
        tr = SpanTracer(mode="all")
        ctx = tr.start_request(name="clock")
        rec = tracing.LoopRecorder("clock", capacity=2)
        rec.turn()
        rec.close()
        out = tr.finish_request(ctx)
        after = monotonic_offset()
        root = out["spans"][0]
        assert before <= root["t0"] <= root["t1"] <= after
        (turn,) = rec.turns()
        t0 = turn[tracing.COL_STAMPS] * 1e-9 - _ORIGIN
        assert root["t0"] <= t0 <= root["t1"]
        (ev,) = [e for e in rec.chrome_events(9) if e["ph"] == "X"] or \
            [{"ts": t0 * 1e6}]
        assert abs(ev["ts"] - t0 * 1e6) < 1.0

    def test_http_records_and_trace_json_loop_track(self):
        """do_POST leaves one record per request, ordered, with its
        status; /trace.json carries the engine loop beside the request
        tracks."""
        from veles_tpu.restful_api import RESTfulAPI
        from veles_tpu.serving import SpanTracer, tracing
        tracer = SpanTracer(mode="all", last=8)
        engine = self._engine(prefill_chunk=8, tracer=tracer,
                              name="rec_http").start()

        def handler(request):
            return {"tokens": engine.generate(
                numpy.asarray(request["input"], numpy.int32), 4).tolist()}

        api = RESTfulAPI(None, handler=handler, tracer=tracer)
        api.lm_engine = engine
        api.start(port=0)
        n0 = len(tracing.http_records())
        try:
            req = urllib.request.Request(
                "http://127.0.0.1:%d/predict" % api.port,
                data=json.dumps({"input": [[1, 2, 3]]}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as resp:
                assert len(json.loads(resp.read())["tokens"][0]) == 7
            bad = urllib.request.Request(
                "http://127.0.0.1:%d/predict" % api.port,
                data=b"{not json")
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(bad, timeout=60)
            assert err.value.code == 400
            with urllib.request.urlopen(
                    "http://127.0.0.1:%d/trace.json" % api.port,
                    timeout=60) as resp:
                trace = json.loads(resp.read())
        finally:
            api.stop()
        # the reply is written before the record: wait for both
        deadline = time.monotonic() + 30
        while len(tracing.http_records()) < n0 + 2 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        ok, bad = tracing.http_records()[n0:n0 + 2]
        assert ok.status == 200
        assert ok.recv <= ok.submit <= ok.result <= ok.reply
        assert bad.status == 400 and bad.submit == 0 == bad.result
        assert bad.recv <= bad.reply
        tracks = {e["args"]["name"]: e["tid"]
                  for e in trace["traceEvents"] if e["ph"] == "M"}
        assert "engine loop rec_http" in tracks
        loop = [e for e in trace["traceEvents"] if e["ph"] == "X"
                and e["tid"] == tracks["engine loop rec_http"]]
        assert {e["name"] for e in loop} <= set(tracing.PHASES)
        assert any(e["name"] == "step.dispatch"
                   and e["args"]["program"] == "step_all" for e in loop)
        # ISSUE 38: the dispatches of those turns on a track of their own,
        # a slice each from the call to the fetch, named by program
        sent = [e for e in trace["traceEvents"] if e["ph"] == "X"
                and e["tid"] == tracks["engine loop rec_http dispatches"]]
        assert tracks["engine loop rec_http dispatches"] \
            == tracks["engine loop rec_http"] + 1
        assert {e["name"] for e in sent} <= set(engine.recorder.programs[1:])
        assert any(e["name"] == "step_all" for e in sent)
        shown = {e["args"]["turn"] for e in loop}
        for e in sent:
            assert set(e["args"]) == {"dispatch", "turn", "fetch_turn",
                                      "lanes", "tokens"}
            assert e["args"]["turn"] in shown and e["dur"] > 0
            if e["name"] == "step_all":
                # (fetched in its own turn, a drain, or one dispatch late)
                assert e["args"]["fetch_turn"] - e["args"]["turn"] in (0, 1)
                assert e["args"]["lanes"] == 1
                # over the phases of its turn from step.dispatch on
                inside = [p for p in loop
                          if p["args"]["turn"] == e["args"]["turn"]
                          and p["name"] in ("step.dispatch", "step.fetch")]
                assert inside and all(
                    e["ts"] - 1 <= p["ts"]
                    and p["ts"] + p["dur"] <= e["ts"] + e["dur"] + 1
                    for p in inside)
        # a request's decode.step spans lie inside the loop track's span
        steps = [e for e in trace["traceEvents"] if e["ph"] == "X"
                 and e["name"] == "decode.step"]
        assert steps
        lo = min(e["ts"] for e in loop)
        hi = max(e["ts"] + e["dur"] for e in loop)
        assert all(lo <= e["ts"] <= hi for e in steps)


def _program_stub():
    """Stands in for a jitted program: the recorder reads ``__name__``."""

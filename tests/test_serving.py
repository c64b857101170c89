"""Serving subsystem (ISSUE 1): dynamic micro-batching, continuous LM
decode, admission control, metrics — the traffic layer over the jitted
forward/decode paths."""

import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy
import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

from lm_cases import assert_greedy, kinds_model  # noqa: E402
from load_gen import run_load  # noqa: E402


def _post(port, payload, timeout=30):
    req = urllib.request.Request(
        "http://127.0.0.1:%d/predict" % port,
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


class TestMicroBatcher:
    def test_coalesces_and_preserves_rows(self):
        from veles_tpu.serving import MicroBatcher, ServingMetrics
        dispatched = []

        def forward(x):
            dispatched.append(len(x))
            time.sleep(0.004)      # a realistic dispatch the queue can
            return x * 2.0         # fill behind

        mb = MicroBatcher(forward, max_batch=8, batch_wait_s=0.01,
                          sample_shape=(4,),
                          metrics=ServingMetrics("mb_t1")).start()
        errors = []

        def client(ci):
            try:
                for j in range(5):
                    x = numpy.full((1, 4), ci * 10 + j, numpy.float32)
                    out = mb.submit(x)
                    assert out.shape == (1, 4)
                    numpy.testing.assert_array_equal(out, x * 2)
            except Exception as e:   # noqa: BLE001 — reported below
                errors.append(e)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        mb.stop()
        assert errors == []
        snap = mb.metrics.snapshot()
        assert snap["requests"] == 40
        # coalescing: measurably fewer dispatches than requests, mean
        # dispatch batch size above 1 (the acceptance criterion)
        assert snap["dispatches"] < snap["requests"]
        assert snap["batch_size"]["mean"] > 1
        # every dispatch was a power-of-two bucket (or max_batch)
        assert set(dispatched) <= {1, 2, 4, 8}

    def test_overload_rejects_instead_of_queueing(self):
        from veles_tpu.serving import MicroBatcher, Overloaded

        def slow_forward(x):
            time.sleep(0.05)
            return x

        mb = MicroBatcher(slow_forward, max_batch=2, queue_depth=2,
                          batch_wait_s=0.0, deadline_s=10.0,
                          sample_shape=(3,), name="mb_t2").start()
        outcomes = {"ok": 0, "over": 0}
        lock = threading.Lock()

        def client():
            try:
                mb.submit(numpy.zeros((1, 3), numpy.float32))
                with lock:
                    outcomes["ok"] += 1
            except Overloaded as e:
                assert e.retry_after > 0
                with lock:
                    outcomes["over"] += 1

        threads = [threading.Thread(target=client) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        mb.stop()
        assert outcomes["ok"] + outcomes["over"] == 16
        assert outcomes["over"] > 0                 # bounded, not hung
        assert mb.metrics.snapshot()["rejected"] == outcomes["over"]

    def test_deadline_sheds_stale_requests(self):
        from veles_tpu.serving import DeadlineExceeded, MicroBatcher

        def slow_forward(x):
            time.sleep(0.08)
            return x

        mb = MicroBatcher(slow_forward, max_batch=1, queue_depth=32,
                          batch_wait_s=0.0, deadline_s=0.02,
                          sample_shape=(2,), name="mb_t3").start()
        shed = []

        def client():
            try:
                mb.submit(numpy.zeros((1, 2), numpy.float32))
            except DeadlineExceeded:
                shed.append(1)

        threads = [threading.Thread(target=client) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        mb.stop()
        # the first request(s) dispatch; later ones aged out in queue
        assert shed
        assert mb.metrics.snapshot()["shed"] == len(shed)

    def test_oversized_request_chunks(self):
        from veles_tpu.serving import MicroBatcher
        mb = MicroBatcher(lambda x: x + 1, max_batch=4,
                          sample_shape=(2,), name="mb_t4").start()
        out = mb.submit(numpy.zeros((10, 2), numpy.float32))
        mb.stop()
        assert out.shape == (10, 2)
        assert (out == 1).all()

    def test_bucket_ladder(self):
        from veles_tpu.serving import batch_buckets
        assert batch_buckets(8) == [1, 2, 4, 8]
        assert batch_buckets(6) == [1, 2, 4, 6]
        assert batch_buckets(1) == [1]


class TestBatchedHTTP:
    def _api(self, forward, **knobs):
        from veles_tpu.restful_api import RESTfulAPI
        from veles_tpu.serving import ServingMetrics
        api = RESTfulAPI(None, forward=forward)
        api.enable_batching(metrics=ServingMetrics("http_t"), **knobs)
        return api.start(port=0)

    def test_threaded_load_correct_and_coalesced(self):
        """≥8 concurrent clients: every reply is row-correct, dispatches
        are measurably fewer than requests, mean batch size > 1 (the
        acceptance criterion), /metrics.json reports it all."""
        def forward(x):
            time.sleep(0.004)
            return x * 2.0

        api = self._api(forward, max_batch=8, batch_wait_s=0.01,
                        sample_shape=(4,))
        try:
            summary = run_load(
                "http://127.0.0.1:%d/predict" % api.port,
                payload=None, clients=8, requests_per_client=5,
                payload_fn=lambda ci, n: {
                    "input": [[float(ci * 10 + n)] * 4]})
            assert summary["ok"] == summary["sent"] == 40
            got = set()
            for r in summary["responses"]:
                # each reply is exactly 2× its own request's input row
                assert r["output"][0] == [r["output"][0][0]] * 4
                got.add(r["output"][0][0])
            assert got == {2.0 * (ci * 10 + n)
                           for ci in range(8) for n in range(5)}
            assert summary["latency_s"]["p99"] > 0
            with urllib.request.urlopen(
                    "http://127.0.0.1:%d/metrics.json" % api.port,
                    timeout=10) as resp:
                snap = json.loads(resp.read())
            assert snap["requests"] == 40
            assert snap["dispatches"] < snap["requests"]
            assert snap["batch_size"]["mean"] > 1
            assert snap["responses"] == 40
            assert snap["latency"]["p50"] > 0
        finally:
            api.stop()

    def test_overload_yields_429_with_retry_after(self):
        """A tiny queue under 16 concurrent clients sheds with HTTP 429
        (structured body, Retry-After) instead of hanging."""
        def slow_forward(x):
            time.sleep(0.05)
            return x

        api = self._api(slow_forward, max_batch=2, queue_depth=2,
                        batch_wait_s=0.0, deadline_s=10.0,
                        sample_shape=(3,))
        try:
            summary = run_load(
                "http://127.0.0.1:%d/predict" % api.port,
                payload={"input": [[0.0, 0.0, 0.0]]}, clients=16,
                requests_per_client=1, timeout=30)
            assert summary["sent"] == 16
            assert summary["by_status"].get("429", 0) > 0
            assert summary["ok"] + summary["by_status"]["429"] == 16
            rejected = [r for r in summary["responses"]
                        if r and "retry_after" in r]
            assert rejected and all(r["retry_after"] > 0
                                    for r in rejected)
        finally:
            api.stop()

    def test_malformed_request_fails_alone(self):
        """A wrong-shaped request gets its own 400 — it must never
        poison the coalesced batch it would have joined (other clients'
        replies stay correct)."""
        def forward(x):
            time.sleep(0.005)
            return x * 2.0

        api = self._api(forward, max_batch=8, batch_wait_s=0.02,
                        sample_shape=(4,))
        try:
            results = {"ok": [], "bad": []}
            lock = threading.Lock()

            def good(v):
                out = _post(api.port, {"input": [[v] * 4]})
                with lock:
                    results["ok"].append(out["output"][0][0] == 2 * v)

            def bad():
                try:
                    _post(api.port, {"input": [[1.0] * 5]})  # wrong width
                except urllib.error.HTTPError as e:
                    with lock:
                        results["bad"].append(
                            (e.code, json.loads(e.read())))

            threads = [threading.Thread(target=good, args=(float(i),))
                       for i in range(4)] + \
                      [threading.Thread(target=bad) for _ in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert results["ok"] == [True] * 4
            assert len(results["bad"]) == 2
            for code, body in results["bad"]:
                assert code == 400 and "sample shape" in body["error"]
        finally:
            api.stop()

    def test_retry_after_is_integer_seconds(self):
        """The Retry-After HEADER is RFC 9110 delta-seconds (integer);
        the exact float rides in the JSON body."""
        def slow_forward(x):
            time.sleep(0.05)
            return x

        api = self._api(slow_forward, max_batch=1, queue_depth=1,
                        batch_wait_s=0.0, sample_shape=(2,))
        try:
            headers = []

            def client():
                req = urllib.request.Request(
                    "http://127.0.0.1:%d/predict" % api.port,
                    data=json.dumps({"input": [[0.0, 0.0]]}).encode(),
                    headers={"Content-Type": "application/json"})
                try:
                    urllib.request.urlopen(req, timeout=30).read()
                except urllib.error.HTTPError as e:
                    if e.code == 429:
                        headers.append(e.headers.get("Retry-After"))
                    e.read()

            threads = [threading.Thread(target=client)
                       for _ in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert headers                      # some 429s happened
            for h in headers:
                assert h is not None and h == str(int(h))   # integer
                assert int(h) >= 1
        finally:
            api.stop()

    def test_bad_first_request_does_not_poison_shape(self):
        """No-warmup server: the canonical sample shape is adopted only
        after a SUCCESSFUL dispatch, so a malformed first request fails
        alone (500 from the forward) and later valid traffic serves."""
        def forward(x):
            if x.shape[1] != 4:
                raise RuntimeError("bad width %d" % x.shape[1])
            return x * 2.0

        from veles_tpu.restful_api import RESTfulAPI
        from veles_tpu.serving import MicroBatcher, ServingMetrics
        api = RESTfulAPI(None, forward=forward)
        api.batcher = MicroBatcher(forward, max_batch=4,
                                   metrics=ServingMetrics("poison_t"))
        api.metrics = api.batcher.metrics
        api.start(port=0)
        try:
            with pytest.raises(urllib.error.HTTPError) as err:
                _post(api.port, {"input": [[1.0] * 5]})     # bad FIRST
            assert err.value.code == 500
            out = _post(api.port, {"input": [[3.0] * 4]})   # still fine
            assert out["output"][0] == [6.0] * 4
            # shape adopted from the successful dispatch: mismatches
            # are now client errors, cheap and precise
            with pytest.raises(urllib.error.HTTPError) as err:
                _post(api.port, {"input": [[1.0] * 5]})
            assert err.value.code == 400
            assert "sample shape" in json.loads(err.value.read())["error"]
        finally:
            api.stop()

    def test_malformed_content_length_is_400(self):
        import http.client
        api = self._api(lambda x: x, max_batch=2, sample_shape=(2,))
        try:
            conn = http.client.HTTPConnection("127.0.0.1", api.port,
                                              timeout=10)
            conn.putrequest("POST", "/predict")
            conn.putheader("Content-Length", "abc")
            conn.putheader("Content-Type", "application/json")
            conn.endheaders()
            resp = conn.getresponse()
            body = json.loads(resp.read())
            assert resp.status == 400 and "Content-Length" in body["error"]
            conn.close()
        finally:
            api.stop()

    def test_structured_errors(self):
        api = self._api(lambda x: x, max_batch=2, sample_shape=(2,))
        api.max_body = 200
        try:
            port = api.port

            def post_raw(body, path="/predict"):
                req = urllib.request.Request(
                    "http://127.0.0.1:%d%s" % (port, path), data=body,
                    headers={"Content-Type": "application/json"})
                with pytest.raises(urllib.error.HTTPError) as err:
                    urllib.request.urlopen(req, timeout=10)
                return err.value.code, json.loads(err.value.read())

            code, body = post_raw(b"this is not json")
            assert code == 400 and "error" in body
            code, body = post_raw(b"{}")                # no "input"
            assert code == 400 and "error" in body
            code, body = post_raw(b'{"input": [[0.0, 0.0]]}',
                                  path="/nope")
            assert code == 404 and "error" in body
            huge = json.dumps(
                {"input": [[0.0, 0.0]] * 100}).encode()
            assert len(huge) > api.max_body
            code, body = post_raw(huge)
            assert code == 413 and "error" in body
        finally:
            api.stop()


def _tiny_params(max_len=48, vocab=16, n_heads=2, n_layers=2):
    import jax
    import jax.numpy as jnp
    from veles_tpu import prng
    from veles_tpu.ops.transformer import init_transformer_params
    host = init_transformer_params(prng.get("init"), vocab, d_model=32,
                                   n_heads=n_heads, n_layers=n_layers,
                                   max_len=max_len)
    return jax.tree.map(jnp.asarray, host)


class TestLMEngine:
    def test_greedy_matches_generate(self):
        """Continuous batching is bit-identical to the sequential
        KV-cached ``generate`` for the same prompts (the acceptance
        criterion), including slot reuse when prompts outnumber
        slots."""
        import jax.numpy as jnp
        from veles_tpu.ops.transformer import generate
        from veles_tpu.serving import LMEngine
        params = _tiny_params()
        prompts = [[1, 2, 3], [2, 4, 6, 8, 10],
                   [5, 1, 5, 1, 5, 1, 5, 1, 5], [7, 7], [0, 3, 9, 12]]
        n_new = 6
        expected = [numpy.asarray(generate(
            params, jnp.asarray([p], jnp.int32), n_new, 2,
            temperature=0.0, max_len=48))[0] for p in prompts]
        engine = LMEngine(params, n_heads=2, max_len=48, slots=2,
                          name="lm_t1").start()
        try:
            # submitted together: 5 prompts share 2 slots mid-flight
            futures = [engine.submit(p, n_new) for p in prompts]
            for p, f, exp in zip(prompts, futures, expected):
                got = numpy.concatenate([p, f.result(timeout=60)])
                numpy.testing.assert_array_equal(got, exp)
            snap = engine.metrics.snapshot()
            assert snap["requests"] == 5
            assert snap["gauges"]["slots_total"] == 2
        finally:
            engine.stop()

    def test_batch_generate_and_occupancy(self):
        import jax.numpy as jnp
        from veles_tpu.ops.transformer import generate
        from veles_tpu.serving import LMEngine
        params = _tiny_params()
        prompts = numpy.asarray([[1, 2, 3, 4]] * 4, numpy.int32)
        expected = numpy.asarray(generate(
            params, jnp.asarray(prompts[:1], jnp.int32), 7, 2,
            temperature=0.0, max_len=48))[0]
        engine = LMEngine(params, n_heads=2, max_len=48, slots=4,
                          name="lm_t2").start()
        try:
            out = engine.generate(prompts, 7)
            assert out.shape == (4, 11)
            for row in out:
                numpy.testing.assert_array_equal(row, expected)
            # identical prompts decoding concurrently: the step
            # dispatches ran multiple lanes at once
            assert engine.metrics.snapshot()["batch_size"]["mean"] > 1
        finally:
            engine.stop()

    def test_batch_cancel_on_admission_failure(self):
        """generate() with more rows than the queue admits: rows already
        queued are withdrawn (no zombie decodes holding slots) and the
        caller sees the refusal."""
        from veles_tpu.serving import LMEngine, Overloaded
        params = _tiny_params()
        engine = LMEngine(params, n_heads=2, max_len=48, slots=1,
                          queue_depth=2, name="lm_t4").start()
        try:
            prompts = numpy.asarray([[1, 2, 3]] * 8, numpy.int32)
            with pytest.raises(Overloaded):
                engine.generate(prompts, 40)     # 8 rows >> 1 slot + 2 queue
            # the engine drains quickly: the withdrawn rows must not
            # decode their full 40 tokens each
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                snap = engine.metrics.snapshot()
                if snap["gauges"].get("slots_busy", 1) == 0 \
                        and snap["gauges"].get("queue_depth", 1) == 0:
                    break
                time.sleep(0.05)
            assert snap["gauges"]["queue_depth"] == 0
            # a fresh request still works after the cancelled batch
            out = engine.generate(prompts[:1], 4)
            assert out.shape == (1, 7)
        finally:
            engine.stop()

    def test_rejects_prompt_beyond_cache(self):
        from veles_tpu.serving import LMEngine
        params = _tiny_params(max_len=32)
        engine = LMEngine(params, n_heads=2, max_len=32, slots=1,
                          name="lm_t3").start()
        try:
            with pytest.raises(ValueError, match="exceeds the engine"):
                engine.submit(list(range(30)), 8)
        finally:
            engine.stop()

    def test_worker_survives_step_fault(self):
        """A decode-step fault fails the in-flight lanes to their
        clients and the engine keeps serving — it must never wedge
        with futures nobody will resolve."""
        import jax.numpy as jnp
        from veles_tpu.ops.transformer import generate
        from veles_tpu.serving import LMEngine
        params = _tiny_params()
        engine = LMEngine(params, n_heads=2, max_len=48, slots=2,
                          name="lm_t5").start()
        real_step = engine._step_jit
        calls = {"n": 0}

        def flaky_step(*args):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("injected device fault")
            return real_step(*args)

        engine._step_jit = flaky_step
        try:
            fut = engine.submit([1, 2, 3], 5)
            with pytest.raises(RuntimeError, match="injected"):
                fut.result(timeout=60)
            # the engine recovered: the next request decodes correctly
            out = engine.generate(numpy.asarray([[1, 2, 3]]), 5)
            expected = numpy.asarray(generate(
                params, jnp.asarray([[1, 2, 3]], jnp.int32), 5, 2,
                temperature=0.0, max_len=48))[0]
            numpy.testing.assert_array_equal(out[0], expected)
            assert engine.metrics.snapshot()["errors"] == 1
        finally:
            engine.stop()


class TestServeLMContinuous:
    @pytest.mark.parametrize("deployment", [
        {}, {"paged_kv": 6, "prefill_chunk": 8, "attn_kernel": "force"}],
        ids=["default", "paged_kernels"])
    def test_http_engine_matches_direct(self, deployment):
        """serve_lm(slots=2) over a (briefly) trained char_lm — the
        default engine, and what the benchmark's cells deploy (paged,
        chunked, through the kernels): engine replies are exactly the
        direct greedy continuation, n_new is honored exactly (no tier
        overshoot), and sampling requests still work (direct-path
        fallback)."""
        import jax.numpy as jnp
        from veles_tpu import prng
        from veles_tpu.config import root
        from veles_tpu.ops.transformer import generate
        from veles_tpu.restful_api import serve_lm
        prng.reset()
        prng.seed_all(5)
        root.__dict__.pop("char_lm", None)
        root.char_lm.update({
            "loader": {"minibatch_size": 32, "n_train": 64, "n_valid": 32,
                       "seq_len": 16, "vocab": 16},
            "trainer": {"vocab": 16, "d_model": 32, "n_heads": 2,
                        "n_layers": 1, "max_len": 32,
                        "learning_rate": 3e-3, "n_experts": 0,
                        "pipeline_stages": 0, "remat": False},
            "decision": {"max_epochs": 1, "fail_iterations": 10},
        })
        from veles_tpu.samples import char_lm
        wf = char_lm.train()
        trainer = wf.trainer
        params = trainer._to_portable(trainer.params)
        api = serve_lm(wf, port=0, max_new=8, slots=2, **deployment)
        try:
            for p in ([1, 2, 3], [2, 4, 6, 8, 10]):
                out = _post(api.port, {"input": [p], "n_new": 5})
                row = out["tokens"][0]
                expected = numpy.asarray(generate(
                    params, jnp.asarray([p], jnp.int32), 5,
                    trainer.n_heads, temperature=0.0,
                    max_len=int(trainer.max_len)))[0]
                assert len(row) == len(p) + 5       # exact, no tier
                numpy.testing.assert_array_equal(row, expected)
            # sampling falls back to the direct path and still replies
            out = _post(api.port, {"input": [[1, 2, 3]], "n_new": 4,
                                   "temperature": 0.8, "seed": 3})
            row = out["tokens"][0]
            assert row[:3] == [1, 2, 3] and len(row) == 7
            assert all(0 <= t < 16 for t in row)
            # the engine's counters reached the serving port's metrics
            with urllib.request.urlopen(
                    "http://127.0.0.1:%d/metrics.json" % api.port,
                    timeout=10) as resp:
                snap = json.loads(resp.read())
            assert snap["requests"] >= 2
        finally:
            api.stop()


class TestMetrics:
    def test_snapshot_and_percentiles(self):
        from veles_tpu.serving import ServingMetrics
        m = ServingMetrics("snap_t")
        for i in range(100):
            m.record_enqueue()
            m.record_response(0.001 * (i + 1))
        m.record_dispatch(8, queue_waits=[0.002, 0.004])
        snap = m.snapshot()
        assert snap["requests"] == snap["responses"] == 100
        assert 0.045 < snap["latency"]["p50"] <= 0.06
        assert 0.09 < snap["latency"]["p99"] <= 0.1
        assert snap["batch_size"]["count"] == 1
        assert snap["queue_wait"]["count"] == 2

    def test_prometheus_rendering(self):
        from veles_tpu.serving import ServingMetrics
        m = ServingMetrics("prom_t")
        m.record_enqueue()
        m.record_dispatch(4, queue_waits=[0.003])
        m.set_gauge("slots_busy", 3)
        text = m.render_prometheus()
        assert 'veles_serving_requests_total{engine="prom_t"} 1' in text
        assert '# TYPE veles_serving_batch_size histogram' in text
        # cumulative buckets: a 4-row dispatch counts at le=4 and above
        assert 'veles_serving_batch_size_bucket{engine="prom_t",le="4"}'\
            ' 1' in text
        assert 'veles_serving_batch_size_bucket{engine="prom_t",le="2"}'\
            ' 0' in text
        assert 'veles_serving_batch_size_bucket{engine="prom_t",'\
            'le="+Inf"} 1' in text
        assert 'veles_serving_slots_busy{engine="prom_t"} 3' in text

    def test_multi_engine_render_single_type_line_per_family(self):
        """Two registered engines share ONE `# TYPE` line per family
        (strict Prometheus parsers reject duplicates)."""
        from veles_tpu.serving import metrics as metrics_mod
        a, b = metrics_mod.new("eng_a"), metrics_mod.new("eng_b")
        a.record_enqueue()
        b.record_enqueue()
        text = metrics_mod.render_prometheus()
        assert text.count(
            "# TYPE veles_serving_requests_total counter") == 1
        assert text.count("# TYPE veles_serving_batch_size histogram") \
            == 1
        assert 'veles_serving_requests_total{engine="eng_a"} 1' in text
        assert 'veles_serving_requests_total{engine="eng_b"} 1' in text

    def test_labeled_samples_share_family(self):
        """Satellite (ISSUE 8): the minimal {replica="i"} label path —
        labeled gauges/counters render into the SAME family as their
        unlabeled base name (one # TYPE line, strict-parser rule) and
        surface as name{...} keys in the snapshot."""
        from veles_tpu.serving import ServingMetrics
        m = ServingMetrics("lbl_t")
        m.set_gauge("queue_depth", 7)
        m.set_gauge("queue_depth", 3, labels={"replica": "0"})
        m.set_gauge("queue_depth", 4, labels={"replica": "1"})
        m.inc("routed_requests", 5, labels={"replica": "0"})
        text = m.render_prometheus()
        assert text.count("# TYPE veles_serving_queue_depth gauge") == 1
        assert 'veles_serving_queue_depth{engine="lbl_t"} 7' in text
        assert ('veles_serving_queue_depth{engine="lbl_t",'
                'replica="0"} 3') in text
        assert ('veles_serving_queue_depth{engine="lbl_t",'
                'replica="1"} 4') in text
        assert ('veles_serving_routed_requests_total{engine="lbl_t",'
                'replica="0"} 5') in text
        snap = m.snapshot()
        assert snap["gauges"]["queue_depth"] == 7
        assert snap["gauges"]['queue_depth{replica="0"}'] == 3
        assert snap["counters"]['routed_requests{replica="0"}'] == 5
        assert m.counter("routed_requests", labels={"replica": "0"}) \
            == 5

    def test_replica_instances_coexist_in_registry(self):
        """Replica engines share a family NAME and differ by instance
        labels: the registry keeps one row per (name, labels), and the
        merged render carries one # TYPE with one sample per
        replica."""
        from veles_tpu.serving import metrics as metrics_mod
        r0 = metrics_mod.new("repl_t", labels={"replica": "0"})
        r1 = metrics_mod.new("repl_t", labels={"replica": "1"})
        assert r0 is not r1
        r0.record_enqueue()
        r1.record_enqueue()
        r1.record_enqueue()
        text = metrics_mod.render_prometheus()
        assert text.count(
            "# TYPE veles_serving_requests_total counter") == 1
        assert ('veles_serving_requests_total{engine="repl_t",'
                'replica="0"} 1') in text
        assert ('veles_serving_requests_total{engine="repl_t",'
                'replica="1"} 2') in text
        # restart-with-same-labels still replaces its own row only
        r0b = metrics_mod.new("repl_t", labels={"replica": "0"})
        assert r0b is not r0
        text = metrics_mod.render_prometheus()
        assert ('veles_serving_requests_total{engine="repl_t",'
                'replica="0"} 0') in text
        assert ('veles_serving_requests_total{engine="repl_t",'
                'replica="1"} 2') in text

    def test_ewma_tracks_latency_facts(self):
        """The router's placement signal: TTFT / decode-step EWMAs
        update on record and read back cheaply."""
        from veles_tpu.serving import ServingMetrics
        m = ServingMetrics("ewma_t")
        assert m.ewma("decode_step") == 0.0
        m.record_decode_step(0.1)
        assert m.ewma("decode_step") == pytest.approx(0.1)
        for _ in range(40):
            m.record_decode_step(0.2)
        assert 0.19 < m.ewma("decode_step") <= 0.2
        m.record_ttft(0.05)
        assert m.snapshot()["ewma"]["ttft"] == pytest.approx(0.05)

    def test_new_replaces_registered_row(self):
        """Engine restarts begin at zero — `new` replaces the row."""
        from veles_tpu.serving import metrics as metrics_mod
        m1 = metrics_mod.new("fresh_t")
        m1.record_enqueue()
        m2 = metrics_mod.new("fresh_t")
        assert m2 is not m1
        assert metrics_mod.get("fresh_t") is m2
        assert m2.snapshot()["requests"] == 0

    def test_concurrent_writers_snapshot_and_render(self):
        """ISSUE 12 satellite: threads hammering inc/observe/set_gauge
        (labeled and not) while another thread snapshots and renders —
        no exceptions, counters monotone across successive snapshots,
        histogram _bucket/_sum/_count families intact with ONE # TYPE
        line each, and the final totals exact."""
        from veles_tpu.serving import ServingMetrics
        from veles_tpu.serving.metrics import render_instances
        m = ServingMetrics("conc_t")
        writers, per_writer = 4, 400
        errors = []

        def hammer(wid):
            try:
                for i in range(per_writer):
                    m.record_enqueue()
                    m.record_response(0.001 * (i % 7 + 1))
                    m.record_decode_step(0.002)
                    m.inc("tokens_out", 2)
                    m.inc("routed_requests",
                          labels={"replica": str(wid % 2)})
                    m.set_gauge("queue_depth", i)
                    m.set_gauge("queue_depth", i,
                                labels={"replica": str(wid % 2)})
                    m.set_gauge_max("queue_depth_peak", i)
            except Exception as e:   # noqa: BLE001 — the assertion
                errors.append(e)

        threads = [threading.Thread(target=hammer, args=(w,))
                   for w in range(writers)]
        for t in threads:
            t.start()
        prev_requests = prev_latency = -1
        try:
            while any(t.is_alive() for t in threads):
                snap = m.snapshot()
                text = render_instances([m])
                # counters never go backwards mid-storm
                assert snap["requests"] >= prev_requests
                assert snap["latency"]["count"] >= prev_latency
                prev_requests = snap["requests"]
                prev_latency = snap["latency"]["count"]
                # families are never torn: one # TYPE per family, and
                # the histogram triplet is complete in every render
                assert text.count(
                    "# TYPE veles_serving_latency histogram") == 1
                assert "veles_serving_latency_sum" in text
                assert "veles_serving_latency_count" in text
                assert 'le="+Inf"' in text
        finally:
            for t in threads:
                t.join(timeout=30)
        assert not errors, errors
        snap = m.snapshot()
        total = writers * per_writer
        assert snap["requests"] == total
        assert snap["latency"]["count"] == total
        assert snap["counters"]["tokens_out"] == 2 * total
        assert (snap["counters"]['routed_requests{replica="0"}']
                + snap["counters"]['routed_requests{replica="1"}']
                == total)
        assert snap["gauges"]["queue_depth_peak"] == per_writer - 1
        # the cumulative bucket counts sum to the observation count
        text = m.render_prometheus()
        inf_line = next(
            line for line in text.splitlines()
            if line.startswith("veles_serving_latency_bucket")
            and 'le="+Inf"' in line)
        assert inf_line.endswith(" %d" % total)

    def test_web_status_metrics_endpoint(self):
        """GET /metrics on the dashboard: registered serving engines +
        workflow rows as gauges, one scrape surface."""
        from veles_tpu.serving import metrics as metrics_mod
        from veles_tpu.web_status import WebStatus
        m = metrics_mod.get("ws_t")
        m.record_enqueue()
        m.record_dispatch(2, queue_waits=[0.001])
        status = WebStatus().start(port=0)
        try:
            status.update("wf1", workflow="wf1", process=0, epoch=3,
                          best=0.5, complete=True)
            with urllib.request.urlopen(
                    "http://127.0.0.1:%d/metrics" % status.port,
                    timeout=10) as resp:
                assert resp.headers["Content-Type"].startswith(
                    "text/plain")
                text = resp.read().decode()
            assert 'veles_serving_requests_total{engine="ws_t"} 1' \
                in text
            assert 'veles_serving_queue_wait_bucket{engine="ws_t"' \
                in text
            assert 'veles_workflow_epoch{workflow="wf1",process="0"} 3' \
                in text
            assert 'veles_workflow_best_metric{workflow="wf1"' in text
            assert 'veles_workflow_complete{workflow="wf1"' \
                ',process="0"} 1' in text
        finally:
            status.stop()


class TestTinyModelSmoke:
    def test_two_clients_against_trained_workflow(self):
        """Tier-1 smoke (satellite): a real (tiny) trained workflow
        behind the batched endpoint, 2 concurrent clients, replies
        match the direct path."""
        from veles_tpu import prng
        from veles_tpu.config import root
        from veles_tpu.restful_api import RESTfulAPI
        from veles_tpu.serving import ServingMetrics
        prng.reset()
        prng.seed_all(2)
        root.mnist.update({
            "loader": {"minibatch_size": 50, "n_train": 200,
                       "n_valid": 100},
            "decision": {"max_epochs": 1, "fail_iterations": 5},
            "layers": [
                {"type": "all2all_tanh", "output_sample_shape": 16,
                 "learning_rate": 0.03, "momentum": 0.9},
                {"type": "softmax", "output_sample_shape": 10,
                 "learning_rate": 0.03, "momentum": 0.9},
            ],
        })
        from veles_tpu.samples import mnist
        wf = mnist.train()
        api = RESTfulAPI(wf)
        direct = api.predict(numpy.zeros((1, 784), numpy.float32))
        api.enable_batching(max_batch=4, batch_wait_s=0.005,
                            metrics=ServingMetrics("mnist_t"))
        api.start(port=0)
        try:
            summary = run_load(
                "http://127.0.0.1:%d/predict" % api.port,
                payload={"input": numpy.zeros(
                    (1, 784), numpy.float32).tolist()},
                clients=2, requests_per_client=3)
            assert summary["ok"] == summary["sent"] == 6
            for r in summary["responses"]:
                numpy.testing.assert_allclose(r["output"],
                                              direct["output"],
                                              atol=1e-5)
        finally:
            api.stop()


class TestRouter:
    """ISSUE 8: data-parallel engine replicas behind the metrics-driven
    router — the degenerate single-replica path, balance, sick-replica
    draining, and unchanged admission semantics."""

    def _expected(self, params, prompts, n_new, max_len=48):
        import jax.numpy as jnp
        from veles_tpu.ops.transformer import generate
        return [numpy.asarray(generate(
            params, jnp.asarray([p], jnp.int32), n_new, 2,
            temperature=0.0, max_len=max_len))[0] for p in prompts]

    def _replicas(self, params, n, serving_mesh=None, **kw):
        import jax
        from veles_tpu.serving import LMEngine, ServingMetrics
        devs = jax.devices()
        return [LMEngine(params, n_heads=2, max_len=48,
                         devices=[devs[i % len(devs)]],
                         name="rt_r%d" % i,
                         metrics=ServingMetrics(
                             "rt", labels={"replica": str(i)}), **kw)
                for i in range(n)]

    def test_single_replica_degenerates_bit_identical(self):
        """Router([one engine]) IS today's path: same tokens, same
        Overloaded admission refusal — no behavioral tax for the
        degenerate fleet."""
        from veles_tpu.serving import LMEngine, Overloaded, Router
        params = _tiny_params()
        prompts = [[1, 2, 3], [2, 4, 6, 8, 10], [7, 7]]
        expected = self._expected(params, prompts, 6)
        engine = LMEngine(params, n_heads=2, max_len=48, slots=1,
                          queue_depth=4, name="rt_one")
        router = Router([engine]).start()
        try:
            futures = [router.submit(p, 6) for p in prompts]
            for p, f, exp in zip(prompts, futures, expected):
                got = numpy.concatenate([p, f.result(timeout=60)])
                numpy.testing.assert_array_equal(got, exp)
            # admission refusal surfaces exactly like the bare engine
            real_step = engine._step_jit

            def slow_step(*a):
                time.sleep(0.05)
                return real_step(*a)

            engine._step_jit = slow_step
            try:
                with pytest.raises(Overloaded):
                    for _ in range(12):
                        router.submit([1, 2, 3], 4)
            finally:
                engine._step_jit = real_step
        finally:
            router.stop()

    def test_idle_fleet_spreads_evenly(self, serving_mesh):
        """Cold traffic on an idle 2-replica fleet places by
        fewest-routed tiebreak: the split is even, not replica-0
        pile-up."""
        serving_mesh(2)
        from veles_tpu.serving import Router
        params = _tiny_params()
        replicas = self._replicas(params, 2, slots=2)
        router = Router(replicas).start()
        try:
            prompts = [[1 + i % 5, 2, 3] for i in range(8)]
            expected = self._expected(params, prompts, 4)
            futures = [router.submit(p, 4) for p in prompts]
            for p, f, exp in zip(prompts, futures, expected):
                got = numpy.concatenate([p, f.result(timeout=60)])
                numpy.testing.assert_array_equal(got, exp)
            counts = router.routed_counts()
            assert sum(counts) == 8
            assert max(counts) - min(counts) <= 2, counts
            snap = router.metrics.snapshot()
            assert snap["counters"]['routed_requests{replica="0"}'] \
                + snap["counters"]['routed_requests{replica="1"}'] == 8
        finally:
            router.stop()

    def test_sick_replica_drain_requeues_without_loss(self,
                                                     serving_mesh):
        """Hot-unregister mid-flight: everything pending on the sick
        replica re-places and completes whole and exactly greedy (no
        loss, no duplicate, no partial results), and the drained
        replica receives no new work."""
        serving_mesh(2)
        from veles_tpu.serving import Router
        params = _tiny_params()
        replicas = self._replicas(params, 2, slots=2)
        router = Router(replicas).start()
        real_step = replicas[0]._step_jit

        def slow_step(*a):
            time.sleep(0.05)
            return real_step(*a)

        replicas[0]._step_jit = slow_step
        try:
            prompts = [[1 + i % 7, 3, 5] for i in range(8)]
            expected = self._expected(params, prompts, 6)
            futures = [router.submit(p, 6) for p in prompts]
            time.sleep(0.12)          # replica 0 is mid-decode now
            moved = router.unregister(0, reason="test drain")
            for p, f, exp in zip(prompts, futures, expected):
                out = f.result(timeout=120)
                assert len(out) == 6          # whole, never partial
                numpy.testing.assert_array_equal(
                    numpy.concatenate([p, out]), exp)
            snap = router.metrics.snapshot()
            if moved:
                assert snap["counters"]["requeued_requests"] >= moved
            assert snap["gauges"]["replicas_live"] == 1
            # post-drain placement avoids the sick replica
            f = router.submit(prompts[0], 4)
            assert f.job.replica == 1
            assert len(f.result(timeout=60)) == 4
        finally:
            replicas[0]._step_jit = real_step
            router.stop()

    def test_admission_and_shed_semantics_unchanged(self, serving_mesh):
        """Behind the router, 429 (every live replica's queue full)
        and 503 (deadline shed inside an engine) look exactly like the
        single-engine contract."""
        serving_mesh(2)
        from veles_tpu.serving import (DeadlineExceeded, Overloaded,
                                       Router)
        params = _tiny_params()
        replicas = self._replicas(params, 2, slots=1, queue_depth=2,
                                  deadline_s=0.2)
        router = Router(replicas).start()
        reals = [e._step_jit for e in replicas]

        def make_slow(real):
            def slow_step(*a):
                time.sleep(0.1)
                return real(*a)
            return slow_step

        for e, real in zip(replicas, reals):
            e._step_jit = make_slow(real)
        try:
            futures, rejected = [], 0
            for k in range(12):
                try:
                    futures.append(router.submit([1, 2, 3], 12))
                except Overloaded:
                    rejected += 1
                if k == 3:
                    # let the workers pop the heads into their slots so
                    # the NEXT submits sit queued behind a busy lane
                    # (slots=1, 12 slow steps ≈ 1.2s >> the 0.2s
                    # deadline → those queued requests must shed)
                    time.sleep(0.05)
            assert rejected > 0            # 429 once the fleet is full
            shed = done = 0
            for f in futures:
                try:
                    f.result(timeout=120)
                    done += 1
                except DeadlineExceeded:   # 503 passes through
                    shed += 1
            assert done + shed == len(futures)
            assert shed > 0
        finally:
            for e, real in zip(replicas, reals):
                e._step_jit = real
            router.stop()

    def test_round_robin_policy(self, serving_mesh):
        serving_mesh(2)
        from veles_tpu.serving import Router
        params = _tiny_params()
        replicas = self._replicas(params, 2, slots=2)
        router = Router(replicas, policy="round_robin").start()
        try:
            futures = [router.submit([1, 2, 3], 3) for _ in range(6)]
            for f in futures:
                assert len(f.result(timeout=60)) == 3
            counts = router.routed_counts()
            assert counts == [3, 3], counts
        finally:
            router.stop()

    def test_router_validation(self):
        from veles_tpu.serving import Router
        with pytest.raises(ValueError, match="at least one"):
            Router([])
        from veles_tpu.serving import LMEngine
        params = _tiny_params()
        engine = LMEngine(params, n_heads=2, max_len=48, slots=1,
                          name="rt_v")
        with pytest.raises(ValueError, match="policy"):
            Router([engine], policy="fastest")


class TestFaultPlan:
    """ISSUE 10: the deterministic fault-injection layer — pure host
    logic, no engines."""

    def test_deterministic_call_sites(self):
        from veles_tpu.serving import FaultPlan, InjectedFault
        plan = FaultPlan().arm("engine.step", calls={2, 4})
        fired = []
        for _ in range(5):
            try:
                plan.fire("engine.step")
                fired.append(False)
            except InjectedFault:
                fired.append(True)
        assert fired == [False, True, False, True, False]
        assert plan.calls("engine.step") == 5
        assert plan.fired("engine.step") == 2

    def test_every_after_times_conditions(self):
        from veles_tpu.serving import FaultPlan, InjectedFault
        plan = FaultPlan().arm("s", every=3, after=3, times=2)
        hits = []
        for n in range(1, 13):
            try:
                plan.fire("s")
            except InjectedFault:
                hits.append(n)
        assert hits == [6, 9]          # every 3rd AND after 3, twice

    def test_seeded_prob_is_reproducible(self):
        from veles_tpu.serving import FaultPlan, InjectedFault

        def run(seed):
            plan = FaultPlan(seed=seed).arm("s", prob=0.5)
            out = []
            for _ in range(32):
                try:
                    plan.fire("s")
                    out.append(0)
                except InjectedFault:
                    out.append(1)
            return out

        assert run(7) == run(7)
        assert run(7) != run(8)        # astronomically unlikely equal

    def test_disarm_and_named_exceptions(self):
        from veles_tpu.serving import FaultPlan, Overloaded
        plan = FaultPlan().arm("s", exc="Overloaded")
        with pytest.raises(Overloaded):
            plan.fire("s")
        plan.disarm("s")
        plan.fire("s")                 # no-op again
        with pytest.raises(ValueError, match="unknown fault"):
            FaultPlan().arm("s", exc="NoSuchError")
        with pytest.raises(ValueError, match="kind"):
            FaultPlan().arm("s", kind="explode")

    def test_json_spec(self):
        from veles_tpu.serving import FaultPlan, InjectedHTTPError
        plan = FaultPlan.from_spec({"seed": 3, "sites": [
            {"site": "http.request", "kind": "error", "exc": "http_503",
             "calls": [1]}]})
        with pytest.raises(InjectedHTTPError) as err:
            plan.fire("http.request")
        assert err.value.code == 503
        plan.fire("http.request")      # call 2: unarmed

    def test_freeze_releases(self):
        from veles_tpu.serving import FaultPlan
        plan = FaultPlan().arm("s", kind="freeze", duration_s=60.0)
        t = threading.Thread(target=plan.fire, args=("s",))
        t.start()
        time.sleep(0.05)
        assert t.is_alive()            # frozen
        plan.release()
        t.join(timeout=10)
        assert not t.is_alive()
        plan.fire("s")                 # released plans never freeze

    def test_batcher_dispatch_site_wired_through_enable_batching(self):
        """The batcher.* sites arm through RESTfulAPI(faults=) →
        enable_batching: an injected dispatch fault fails its batch's
        clients (500) through the real fault-isolation path, and the
        worker keeps serving."""
        from veles_tpu.restful_api import RESTfulAPI
        from veles_tpu.serving import FaultPlan, ServingMetrics
        plan = FaultPlan().arm("batcher.dispatch", calls={1})
        api = RESTfulAPI(None, forward=lambda x: x * 2.0, faults=plan)
        api.enable_batching(max_batch=4, sample_shape=(2,),
                            metrics=ServingMetrics("bf_t"))
        api.start(port=0)
        try:
            with pytest.raises(urllib.error.HTTPError) as err:
                _post(api.port, {"input": [[1.0, 2.0]]})
            assert err.value.code == 500
            assert "injected" in json.loads(err.value.read())["error"]
            out = _post(api.port, {"input": [[3.0, 4.0]]})
            assert out["output"][0] == [6.0, 8.0]   # worker survived
            assert plan.fired("batcher.dispatch") == 1
        finally:
            api.stop()


class TestResilience:
    """ISSUE 10: retry/backoff, hedging, health circuit breaker — the
    router-level resilience layer over injected faults."""

    def _expected(self, params, prompts, n_new, max_len=48):
        import jax.numpy as jnp
        from veles_tpu.ops.transformer import generate
        return [numpy.asarray(generate(
            params, jnp.asarray([p], jnp.int32), n_new, 2,
            temperature=0.0, max_len=max_len))[0] for p in prompts]

    def _replicas(self, params, plans, **kw):
        import jax
        from veles_tpu.serving import LMEngine, ServingMetrics
        devs = jax.devices()
        return [LMEngine(params, n_heads=2, max_len=48,
                         devices=[devs[i % len(devs)]],
                         name="rs_r%d" % i, faults=plan,
                         metrics=ServingMetrics(
                             "rs", labels={"replica": str(i)}), **kw)
                for i, plan in enumerate(plans)]

    def test_retry_replaces_faulted_request_on_other_replica(self):
        """An engine FAULT on a live replica re-places the request
        whole on the other replica (requests_retried metered), and
        the delivered tokens are exactly greedy — idempotent because
        replicas are bit-identical."""
        from veles_tpu.serving import FaultPlan, Router
        params = _tiny_params()
        plan = FaultPlan().arm("engine.step", times=20)
        replicas = self._replicas(params, [plan, None], slots=2)
        router = Router(replicas, retries=2,
                        retry_backoff_s=0.01).start()
        try:
            [exp] = self._expected(params, [[1, 2, 3]], 6)
            fut = router.submit([1, 2, 3], 6)
            out = fut.result(timeout=60)
            numpy.testing.assert_array_equal(
                numpy.concatenate([[1, 2, 3], out]), exp)
            assert fut.job.replica == 1          # served by the healthy one
            retried = router.metrics.counter("requests_retried")
            assert retried >= 1
            # budget exhaustion on the SAME fleet: with BOTH replicas
            # now faulting, retries run out and the client sees the
            # injected fault — bounded, never an infinite retry loop
            from veles_tpu.serving import InjectedFault
            replicas[1]._faults = FaultPlan().arm("engine.step",
                                                  times=100)
            fut = router.submit([1, 2, 3], 6)
            with pytest.raises(InjectedFault):
                fut.result(timeout=60)
            assert router.metrics.counter("requests_retried") \
                == retried + 2
        finally:
            router.stop()

    def test_hedge_wins_on_slow_replica(self):
        """A request stuck on the injected-latency replica hedges onto
        the fast one past the threshold; the hedge wins, output stays
        exactly greedy, and the loser is cancelled (not delivered)."""
        from veles_tpu.serving import FaultPlan, Router
        params = _tiny_params()
        plan = FaultPlan().arm("engine.step", kind="latency",
                               latency_s=0.2)
        replicas = self._replicas(params, [plan, None], slots=2)
        router = Router(replicas, hedge_after_s=0.15).start()
        try:
            prompts = [[1, 2, 3], [2, 4, 6]]
            expected = self._expected(params, prompts, 6)
            futures = [router.submit(p, 6) for p in prompts]
            for p, f, exp in zip(prompts, futures, expected):
                out = f.result(timeout=60)
                numpy.testing.assert_array_equal(
                    numpy.concatenate([p, out]), exp)
            m = router.metrics
            assert m.counter("requests_hedged") >= 1
            assert m.counter("hedge_wins") >= 1
        finally:
            router.stop()

    def test_health_checker_quarantines_and_recovers(self):
        """The full circuit-breaker cycle, driven synchronously: a
        frozen replica is quarantined through the drain path (its
        pending work completes on the survivor), and after the
        cooldown the half-open probe re-registers it."""
        from veles_tpu.serving import (FaultPlan, HealthChecker,
                                       Router)
        params = _tiny_params()
        plan = FaultPlan().arm("engine.tick", kind="freeze", after=2,
                               times=1, duration_s=60.0)
        replicas = self._replicas(params, [plan, None], slots=2)
        router = Router(replicas, drain_timeout_s=0.3).start()
        checker = HealthChecker(router, interval_s=0.05,
                                probe_timeout_s=2.0, fail_threshold=2,
                                cooldown_s=0.2, stall_s=0.25)
        try:
            futures = [router.submit([1 + i, 2, 3], 6)
                       for i in range(6)]
            deadline = time.monotonic() + 30
            while router._live[0] and time.monotonic() < deadline:
                checker.step()
                time.sleep(0.05)
            assert not router._live[0]            # quarantined
            assert checker.states()[0] == HealthChecker.OPEN
            assert router.metrics.counter("circuit_open_total") == 1
            for f in futures:                     # no loss, no wedge
                assert len(f.result(timeout=60)) == 6
            # thaw; after the cooldown the half-open probe re-admits
            plan.release()
            time.sleep(0.25)
            deadline = time.monotonic() + 30
            while not router._live[0] \
                    and time.monotonic() < deadline:
                checker.step()
                time.sleep(0.05)
            assert router._live[0]
            assert checker.states()[0] == HealthChecker.HEALTHY
            snap = router.metrics.snapshot()
            assert snap["gauges"][
                'replica_health_state{replica="0"}'] == 0
            # the recovered replica serves again
            out = router.submit([1, 2, 3], 4).result(timeout=60)
            assert len(out) == 4
        finally:
            plan.release()
            checker.stop()
            router.stop()

    def test_probe_warm_absorbs_first_compile(self):
        """Satellite (ISSUE 11): warm_probes() runs each replica's
        first synthetic probe with a generous budget BEFORE monitoring
        starts, so a slow first dispatch of the probe's prompt chunk
        (the foot-gun the HealthChecker docstring warns about) can
        never count as a failed probe and walk an innocent replica
        toward quarantine."""
        from veles_tpu.serving import HealthChecker, LMEngine, Router
        params = _tiny_params()
        engine = LMEngine(params, n_heads=2, max_len=48, slots=1,
                          name="warm_r0").start()
        # emulate a slow first compile: the FIRST prompt-chunk
        # dispatch after start stalls well past the probe timeout
        real = engine._chunk_jit
        state = {"first": True}

        def slow_first(*a):
            if state["first"]:
                state["first"] = False
                time.sleep(0.6)
            return real(*a)

        engine._chunk_jit = slow_first
        router = Router([engine])
        checker = HealthChecker(router, interval_s=0.05,
                                probe_timeout_s=0.25,
                                fail_threshold=1, stall_s=5.0)
        try:
            checker.warm_probes()      # absorbs the 0.6s "compile"
            for _ in range(3):
                checker.step()
            assert checker.states() == [HealthChecker.HEALTHY]
            assert router.metrics.counter("health_probe_failures") == 0
            assert router._live[0]
        finally:
            router.stop()

    def test_429_retry_after_is_minimum_over_replicas(self):
        """Satellite: when every replica refuses, the surfaced
        Retry-After is the MINIMUM over the refusing replicas — the
        client may return as soon as the soonest one frees."""
        from veles_tpu.serving import LMEngine, Overloaded, Router
        params = _tiny_params()
        engines = [LMEngine(params, n_heads=2, max_len=48, slots=1,
                            name="ra_r%d" % i) for i in range(2)]

        def refuse(ra):
            def submit(prompt, n_new):
                raise Overloaded(retry_after=ra)
            return submit

        engines[0].submit = refuse(0.7)
        engines[1].submit = refuse(0.3)
        router = Router(engines)
        with pytest.raises(Overloaded) as err:
            router.submit([1, 2, 3], 4)
        assert err.value.retry_after == pytest.approx(0.3)

    def test_no_live_replicas_is_retryable_429(self):
        """A fully-quarantined fleet is a TRANSIENT condition: submit
        surfaces the Overloaded subclass NoLiveReplicas (429 +
        Retry-After upstream), never a bare 500-class error."""
        from veles_tpu.serving import (LMEngine, NoLiveReplicas,
                                       Overloaded, Router)
        params = _tiny_params()
        engine = LMEngine(params, n_heads=2, max_len=48, slots=1,
                          name="nl_r0")
        router = Router([engine])
        router.unregister(0, reason="test: full-fleet circuit open")
        with pytest.raises(NoLiveReplicas) as err:
            router.submit([1, 2, 3], 4)
        assert isinstance(err.value, Overloaded)
        assert err.value.retry_after > 0

    @pytest.mark.parametrize("model", ["pre_ln", "kinds"])
    def test_checkpoint_restore_after_simulated_crash(self, model):
        """Kill-and-restore: a paged engine freezes mid-traffic, its
        checkpoint re-admits the journaled work on a FRESH engine
        (allocator invariants verified first), resumed outputs are
        bit-identical to greedy generate, the pool ends leak-free,
        and new traffic serves with unchanged parity.  ``kinds``: two
        kinds of cache (no prefix cache), both allocators verified."""
        from veles_tpu.serving import FaultPlan, LMEngine
        if model == "kinds":
            record, params = kinds_model()
            features = {}
        else:
            record, params = 2, _tiny_params(max_len=64)
            features = {"prefix_cache": 8}
        prompts = [[1, 2, 3], [2, 4, 6, 8, 10], [5, 1, 5, 1, 5]]
        plan = FaultPlan().arm("engine.tick", kind="freeze", after=2,
                               duration_s=60.0)
        crashed = LMEngine(params, record, max_len=64, slots=2,
                           paged_kv=8, prefill_chunk=8, name="crash",
                           faults=plan, **features).start()
        try:
            for p in prompts:
                crashed.submit(p, 5)
            time.sleep(0.2)                  # wedged mid-flight
            state = crashed.checkpoint()
            assert len(state["requests"]) == 3
            json.dumps(state)                # JSON-safe by contract
            fresh = LMEngine(params, record, max_len=64, slots=2,
                             paged_kv=8, prefill_chunk=8, name="fresh",
                             **features).start()
            try:
                restored = fresh.restore(state)
                assert len(restored) == 3
                outs = [restored[e["rid"]].result(timeout=60)
                        for e in state["requests"]]
                for p, out in zip(prompts, outs):
                    assert_greedy(fresh, p, out, 5)
                # leak-free: drain the trie, the pool refills whole
                if model == "pre_ln":
                    while fresh._trie.evict_one():
                        pass
                    assert fresh._trie.live_pins() == 0
                inv = fresh.verify_pool_invariants()
                assert inv["free_pages"] == fresh._pool.num_pages
                if fresh._wt is not None:
                    assert fresh._wt.pool.free_pages \
                        == fresh._wt.pool.num_pages
                # new traffic, unchanged parity
                out = fresh.generate(numpy.asarray([prompts[0]]), 5)
                assert_greedy(fresh, prompts[0], out[0][3:], 5)
                assert fresh.metrics.counter("engine_restores") == 1
            finally:
                fresh.stop()
        finally:
            plan.release()
            crashed.stop()

    def test_restore_refuses_garbage_and_oversized(self):
        from veles_tpu.serving import LMEngine
        params = _tiny_params()
        engine = LMEngine(params, n_heads=2, max_len=48, slots=1,
                          name="rg")
        with pytest.raises(ValueError, match="format"):
            engine.restore({"format": 99})
        with pytest.raises(ValueError, match="max_len"):
            engine.restore({"format": 1, "config": {"max_len": 4096},
                            "requests": []})
        # all-or-nothing geometry check: a journaled request the
        # restoring pool can NEVER place refuses up front, before any
        # sibling entry is re-admitted
        paged = LMEngine(params, n_heads=2, max_len=48, slots=1,
                         paged_kv=2, prefill_chunk=8, name="rg_p")
        with pytest.raises(ValueError, match="KV pages"):
            paged.restore({"format": 1, "config": {"max_len": 48},
                           "requests": [
                               {"rid": 1, "prompt": [1, 2], "n_new": 2},
                               {"rid": 2, "prompt": list(range(30)),
                                "n_new": 10}]})


class TestInjectedHTTPFaults:
    """ISSUE 10: the http.request site serves structured transient
    errors, and load_gen's failure classes (satellite) split them from
    real errors."""

    def test_injected_503_is_structured_and_classified(self):
        from veles_tpu.restful_api import RESTfulAPI
        from veles_tpu.serving import FaultPlan, ServingMetrics
        plan = FaultPlan().arm("http.request", exc="http_503",
                               every=2)
        api = RESTfulAPI(None, forward=lambda x: x * 2.0, faults=plan)
        api.metrics = ServingMetrics("httpf_t")
        api.start(port=0)
        try:
            summary = run_load(
                "http://127.0.0.1:%d/predict" % api.port,
                payload={"input": [[1.0, 2.0]]}, clients=1,
                requests_per_client=6)
            assert summary["sent"] == 6
            # every 2nd request got the injected 503 (Retry-After set),
            # the rest served — and the failure CLASSES split them
            assert summary["failures"]["http_503"] == 3
            assert summary["failures"]["timeout"] == 0
            assert summary["failures"]["connection"] == 0
            assert summary["shed_not_errored"] is True
            assert summary["ok"] == 3
        finally:
            api.stop()

    def test_connection_failure_class(self):
        """A dead endpoint lands in the 'connection' class — chaos
        runs can tell a refused socket from a graceful shed."""
        import socket
        sock = socket.socket()
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
        sock.close()                       # nothing listens here now
        summary = run_load("http://127.0.0.1:%d/predict" % port,
                           payload={"input": [[0.0]]}, clients=1,
                           requests_per_client=1, timeout=2)
        assert summary["failures"]["connection"] == 1
        assert summary["shed_not_errored"] is False


class TestWeightSwap:
    """ISSUE 11: zero-downtime weight updates — engine hot-swap (lanes
    finish on the old weights or drain onto the new), tp-mesh swap
    without recompiles, structural-mismatch refusal, canary rollback
    driven by the synchronous HealthChecker, and the publisher loop."""

    def _expected(self, params, prompts, n_new, max_len=48):
        import jax.numpy as jnp
        from veles_tpu.ops.transformer import generate
        return [numpy.asarray(generate(
            params, jnp.asarray([p], jnp.int32), n_new, 2,
            temperature=0.0, max_len=max_len))[0] for p in prompts]

    def _wait_busy(self, engine, n, timeout=10.0):
        deadline = time.monotonic() + timeout
        while engine.metrics.gauge("slots_busy") < n \
                and time.monotonic() < deadline:
            time.sleep(0.002)
        assert engine.metrics.gauge("slots_busy") >= n

    @staticmethod
    def _old_and_new(model):
        """(the engine's model keywords, the tree it starts on, the one
        swapped in: same shapes, other weights) — the ``pre_ln`` model,
        or the two kinds of cache of ``lm_cases.kinds_model``, paged."""
        if model == "kinds":
            (record, pa), (_, pb) = kinds_model(3), kinds_model(4)
            return {"n_heads": record, "paged_kv": True,
                    "prefill_chunk": 8}, pa, pb
        return {"n_heads": 2}, _tiny_params(), _tiny_params()

    @pytest.mark.parametrize("model", ["pre_ln", "kinds"])
    def test_swap_parity_straddling_lanes(self, model):
        """swap_weights mid-traffic: every request completes whole and
        exactly once, each delivered row is bit-identical to the
        weights version its future is stamped with (straddling lanes
        finish on the OLD weights — the default), and post-swap
        traffic serves the new weights."""
        from veles_tpu.serving import LMEngine
        features, pa, pb = self._old_and_new(model)
        prompts = [[1, 2, 3], [2, 4, 6, 8], [5, 1, 5], [7, 7, 1]]
        n_new = 12
        engine = LMEngine(pa, max_len=48, slots=2, name="sw_par",
                          **features).start()
        try:
            futures = [engine.submit(p, n_new) for p in prompts]
            self._wait_busy(engine, 2)
            v = engine.swap_weights(pb, version=7)
            assert v == 7 and engine.weights_version == 7
            seen = set()
            for p, f in zip(prompts, futures):
                out = f.result(timeout=60)
                assert len(out) == n_new      # whole, exactly once
                seen.add(f.version)
                assert_greedy(engine, p, out, n_new,
                              pa if f.version == 0 else pb)
            assert seen <= {0, 7}
            assert 0 in seen        # the confirmed-busy lanes finished
            #                         on the old weights
            fut = engine.submit(prompts[0], n_new)
            out = fut.result(timeout=60)
            assert fut.version == 7
            assert_greedy(engine, prompts[0], out, n_new, pb)
            assert engine.metrics.counter("weight_swaps") == 1
            assert engine.metrics.gauge("weights_version") == 7
        finally:
            engine.stop()

    @pytest.mark.parametrize("model", ["pre_ln", "kinds"])
    def test_swap_drain_requeues_on_new_weights_paged(self, model):
        """drain=True on a paged engine: in-flight lanes are withdrawn
        whole and re-decode from scratch on the NEW weights — futures
        resolve exactly once with the new stamp, and the page pool
        survives the requeue leak-free (allocator invariants; of both
        allocators, for two kinds of cache)."""
        from veles_tpu.serving import FaultPlan, LMEngine
        features, pa, pb = self._old_and_new(model)
        prompts = [[1, 2, 3], [2, 4, 6, 8]]
        n_new = 16
        # slow ticks so the swap provably lands mid-decode
        plan = FaultPlan().arm("engine.step", kind="latency",
                               latency_s=0.02)
        engine = LMEngine(pa, max_len=48, slots=2, name="sw_drain",
                          faults=plan, **dict(
                              features, paged_kv=True,
                              prefill_chunk=8)).start()
        try:
            futures = [engine.submit(p, n_new) for p in prompts]
            self._wait_busy(engine, 2)
            engine.swap_weights(pb, version=3, drain=True)
            for p, f in zip(prompts, futures):
                out = f.result(timeout=60)
                assert len(out) == n_new and f.version == 3
                assert_greedy(engine, p, out, n_new, pb)
            assert engine.metrics.counter(
                "requests_requeued_for_swap") >= 1
            deadline = time.monotonic() + 15
            while engine.metrics.gauge("slots_busy") > 0 \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
            inv = engine.verify_pool_invariants()
            assert inv["free_pages"] == engine._pool.num_pages
            if engine._wt is not None:
                assert engine._wt.pool.free_pages \
                    == engine._wt.pool.num_pages
        finally:
            plan.release()
            engine.stop()

    def test_swap_mismatch_refuses_loudly(self):
        """A shape- or structure-incompatible tree refuses with a loud
        ValueError and the OLD weights keep serving bit-exactly."""
        import jax
        import jax.numpy as jnp
        from veles_tpu import prng
        from veles_tpu.ops.transformer import init_transformer_params
        from veles_tpu.serving import LMEngine
        pa = _tiny_params()
        wrong = jax.tree.map(jnp.asarray, init_transformer_params(
            prng.get("init"), 16, d_model=16, n_heads=2, n_layers=2,
            max_len=48))
        [exp] = self._expected(pa, [[1, 2, 3]], 5)
        engine = LMEngine(pa, n_heads=2, max_len=48, slots=1,
                          name="sw_bad").start()
        try:
            with pytest.raises(ValueError, match="swap refused"):
                engine.swap_weights(wrong)
            broken = dict(pa)
            broken.pop("embed")            # different tree structure
            with pytest.raises(ValueError, match="swap refused"):
                engine.swap_weights(broken)
            assert engine.weights_version == 0
            assert engine.metrics.counter("weight_swaps") == 0
            out = engine.generate(numpy.asarray([[1, 2, 3]]), 5)
            numpy.testing.assert_array_equal(out[0], exp)
        finally:
            engine.stop()

    def test_tp_mesh_swap_no_recompile(self, serving_mesh):
        """A tp=2 engine swaps shard-by-shard under its existing mesh
        (lm_param_specs placement): output flips to the new weights
        bit-exactly, the swapped tree is REALLY sharded, and no
        program compiled a twin (same shapes + pinned shardings → the
        jit-guard bound holds across the swap)."""
        serving_mesh(2)
        from veles_tpu.serving import LMEngine
        pa = _tiny_params()
        pb = _tiny_params()
        prompts = [[1, 2, 3], [2, 4, 6, 8]]
        exp_a = self._expected(pa, prompts, 6)
        exp_b = self._expected(pb, prompts, 6)
        engine = LMEngine(pa, n_heads=2, max_len=48, slots=2, tp=2,
                          prefill_chunk=8, name="sw_tp").start()
        try:
            for p, ea in zip(prompts, exp_a):
                out = engine.submit(p, 6).result(timeout=60)
                numpy.testing.assert_array_equal(
                    numpy.concatenate([p, out]), ea)
            progs = {"step": engine._step_jit,
                     "chunk": engine._chunk_jit}
            sizes = {n: fn._cache_size() for n, fn in progs.items()}
            engine.swap_weights(pb, version=1)
            for p, eb in zip(prompts, exp_b):
                fut = engine.submit(p, 6)
                out = fut.result(timeout=60)
                assert fut.version == 1
                numpy.testing.assert_array_equal(
                    numpy.concatenate([p, out]), eb)
            for name, fn in progs.items():
                assert fn._cache_size() == sizes[name], (
                    "%s compiled a twin program across the swap"
                    % name)
            wq = engine.params["blocks"][0]["attn"]["wq"]
            assert len(wq.addressable_shards) == 2   # really sharded
        finally:
            engine.stop()

    def test_canary_rollback_driven_by_health_checker_step(self):
        """Router.deploy watches the health circuit during the canary
        window: a canary the synchronously-driven HealthChecker.step()
        quarantines mid-watch rolls the deploy back to the previous
        version, and the fleet keeps serving the old weights."""
        import jax
        from veles_tpu.serving import (FaultPlan, HealthChecker,
                                       LMEngine, Router)
        pa = _tiny_params()
        pb = _tiny_params()
        [exp_a] = self._expected(pa, [[1, 2, 3]], 4)
        plan = FaultPlan()
        devs = jax.devices()
        replicas = [LMEngine(pa, n_heads=2, max_len=48, slots=2,
                             devices=[devs[i % len(devs)]],
                             name="cb_r%d" % i,
                             faults=plan if i == 0 else None)
                    for i in range(2)]
        router = Router(replicas, drain_timeout_s=0.3).start()
        checker = HealthChecker(router, interval_s=0.05,
                                probe_timeout_s=2.0, fail_threshold=2,
                                cooldown_s=600.0, stall_s=0.3)
        checker.warm_probes()
        result = {}

        def run_deploy():
            result["rec"] = router.deploy(
                pb, version=1, canary=1, canary_fraction=0.5,
                watch_s=30.0, checker=checker, probe_n_new=1)

        t = threading.Thread(target=run_deploy, daemon=True)
        t.start()
        try:
            # the canary (replica 0) swaps, passes its parity probe and
            # rejoins — the deploy is now in its watch window
            deadline = time.monotonic() + 60
            while (replicas[0].weights_version != 1
                   or not router._live[0]) \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            assert replicas[0].weights_version == 1
            # NOW the canary goes bad: every prompt chunk faults, so the
            # checker's synthetic 1-token probe dies — step()
            # (synchronous) walks it to quarantine, and the deploy's
            # watch sees the circuit
            plan.arm("engine.chunk", kind="error")
            deadline = time.monotonic() + 60
            while router._live[0] and time.monotonic() < deadline:
                checker.step()
                time.sleep(0.03)
            assert not router._live[0]
            t.join(timeout=60)
            assert not t.is_alive()
            rec = result["rec"]
            assert rec["rolled_back"] is True
            assert "canary 0" in rec["reason"]
            assert router.metrics.counter("rollbacks_total") == 1
            plan.disarm()
            # rolled all the way back: both replicas on v0, the
            # survivor serves the OLD weights bit-exactly
            assert replicas[0].weights_version == 0
            assert replicas[1].weights_version == 0
            fut = router.submit([1, 2, 3], 4)
            out = fut.result(timeout=60)
            assert fut.job.version == 0
            numpy.testing.assert_array_equal(
                numpy.concatenate([[1, 2, 3], out]), exp_a)
        finally:
            plan.disarm()
            router.stop()

    def _snapshot_payload(self, params):
        import jax
        host = jax.tree.map(numpy.asarray, params)
        return {"format": 1, "framework_version": "test",
                "workflow_class": "t", "workflow_name": "t",
                "epoch": 1, "best_metric": None, "time": time.time(),
                "state": {"units": {"TransformerTrainer": {
                    "params": host, "opt_state": None, "time": 0}},
                    "prng": {}},
                "config": {}}

    def test_model_manager_publishes_and_rejects(self, tmp_path):
        """The publisher loop end to end: a snapshot landing in the
        watched directory deploys across the fleet exactly once (the
        unchanged directory is a no-op next poll), replies flip to the
        new version, and a numerically-broken checkpoint is rejected
        OFF the hot path with the fleet untouched."""
        import gzip
        import pickle
        from veles_tpu.serving import LMEngine, ModelManager, Router
        pa = _tiny_params()
        pb = _tiny_params()
        [exp_b] = self._expected(pb, [[1, 2, 3]], 5)
        engine = LMEngine(pa, n_heads=2, max_len=48, slots=2,
                          name="mm_r0")
        router = Router([engine]).start()
        manager = ModelManager(router, str(tmp_path), interval_s=3600,
                               probe_n_new=2)

        def write(params, mtime):
            path = tmp_path / "wf_current.pickle.gz"
            with gzip.open(path, "wb") as f:
                pickle.dump(self._snapshot_payload(params), f)
            os.utime(path, (mtime, mtime))
            return path

        try:
            assert manager.poll_once() is None          # empty dir
            write(pb, time.time())
            rec = manager.poll_once()
            assert rec["deployed"] and not rec["rolled_back"]
            assert rec["version"] == 1 and rec["epoch"] == 1
            assert manager.poll_once() is None          # unchanged
            fut = router.submit([1, 2, 3], 5)
            out = fut.result(timeout=60)
            assert fut.job.version == 1
            numpy.testing.assert_array_equal(
                numpy.concatenate([[1, 2, 3], out]), exp_b)
            # a NaN checkpoint is rejected before any engine sees it
            bad_embed = numpy.array(pb["embed"], numpy.float32)
            bad_embed[0, 0] = numpy.nan
            write({**pb, "embed": bad_embed}, time.time() + 60)
            rec = manager.poll_once()
            assert rec["deployed"] is False
            assert "non-finite" in rec["rejected"]
            assert engine.weights_version == 1          # untouched
            assert router.metrics.counter("publish_rejected") == 1
            assert router.metrics.counter("publishes_total") == 1
        finally:
            router.stop()


class TestChaosSmoke:
    def test_chaos_smoke_kill_one_replica(self):
        """Satellite: the <60s chaos-smoke subset runs tier-1 so the
        fault-injection plumbing and the quarantine/drain/exactly-once
        contract cannot rot between TPU sessions."""
        from chaos_smoke import run_smoke
        record = run_smoke()
        assert record["completed_exactly_once"] == record["requests"]
        assert record["parity_vs_generate"] is True
        assert record["replica0_quarantined"] is True
        assert record["smoke_wall_s"] < 60

    def test_chaos_smoke_weight_swap(self):
        """Satellite (ISSUE 11): the <60s weight-swap-under-load
        subset rides tier-1 — requests straddling a canary deploy
        complete exactly once with per-stamped-version parity and
        zero 5xx, and an injected bad canary auto-rolls back with no
        client-visible errors."""
        from chaos_smoke import run_swap_smoke
        record = run_swap_smoke()
        assert record["completed_exactly_once"] == record["requests"]
        assert record["zero_5xx"] is True
        assert record["parity_per_stamped_version"] is True
        assert record["bad_canary_rolled_back"] is True
        assert record["rollbacks_total"] == 1
        assert record["smoke_wall_s"] < 60


@pytest.mark.slow
class TestSustainedLoad:
    def test_sustained_qps_with_histograms(self):
        """Closed-loop sustained load (the slow-marked evidence run):
        paced QPS for a fixed window, zero failures, coalescing and
        full latency histograms on the server side."""
        from veles_tpu.restful_api import RESTfulAPI
        from veles_tpu.serving import ServingMetrics

        def forward(x):
            time.sleep(0.002)
            return x * 3.0

        api = RESTfulAPI(None, forward=forward)
        api.enable_batching(max_batch=16, batch_wait_s=0.005,
                            sample_shape=(8,),
                            metrics=ServingMetrics("sustained_t"))
        api.start(port=0)
        try:
            summary = run_load(
                "http://127.0.0.1:%d/predict" % api.port,
                payload={"input": [[1.0] * 8]}, clients=16,
                qps=200, duration=5.0)
            assert summary["ok"] == summary["sent"] > 100
            assert summary["latency_s"]["p99"] < 5.0
            snap = api.metrics.snapshot()
            assert snap["dispatches"] < snap["requests"]
            assert snap["batch_size"]["mean"] > 1
            assert snap["latency"]["p99"] > 0
        finally:
            api.stop()

"""Continuous telemetry + SLO burn-rate alerting (ISSUE 14): the
time-series store over the serving metrics, runtime/device gauges, the
SLO state machine and its health-checker hook, and the new HTTP
endpoints."""

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
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools"))


def _tiny_params(max_len=48, vocab=16, n_heads=2, n_layers=2):
    import jax
    import jax.numpy as jnp
    from veles_tpu import prng
    from veles_tpu.ops.transformer import init_transformer_params
    host = init_transformer_params(prng.get("init"), vocab, d_model=32,
                                   n_heads=n_heads, n_layers=n_layers,
                                   max_len=max_len)
    return jax.tree.map(jnp.asarray, host)


def _get_json(port, path):
    with urllib.request.urlopen(
            "http://127.0.0.1:%d%s" % (port, path), timeout=10) as r:
        return json.loads(r.read())


class TestTimeSeriesStore:
    def test_counter_windows_and_restart_clamp(self):
        """Counters become restart-tolerant windowed rates: positive
        deltas accumulate, a counter that went BACKWARDS (an engine
        restart replacing its row) contributes zero — the rate is
        never negative."""
        from veles_tpu.serving import ServingMetrics, TimeSeriesStore
        m = ServingMetrics("ts_ctr")
        store = TimeSeriesStore(interval_s=0.05, capacity=64)
        store.add_source(m, key="src")
        for n in (5, 9, 2, 6):       # 9 -> 2 is the restart
            m2 = ServingMetrics("ts_ctr")
            for _ in range(n):
                m2.record_enqueue()
            # swap the sampled instance's counter value directly
            m.requests = m2.requests
            store.sample_once()
        w = store.window("src.counter.requests", 60)
        assert w["kind"] == "counter"
        assert w["last"] == 6
        # deltas: +4 (5->9), clamp(9->2)=0, +4 (2->6)
        assert w["delta"] == 8
        assert w["rate_per_s"] >= 0

    def test_gauge_and_histogram_windows(self):
        from veles_tpu.serving import ServingMetrics, TimeSeriesStore
        m = ServingMetrics("ts_h")
        store = TimeSeriesStore(interval_s=0.05, capacity=64)
        store.add_source(m, key="src")
        store.sample_once()          # baseline point (zero deltas)
        for i, (depth, ttft) in enumerate(
                ((3, 0.004), (7, 0.004), (5, 0.2))):
            m.set_gauge("queue_depth", depth)
            m.record_ttft(ttft)
            store.sample_once()
        g = store.window("src.gauge.queue_depth", 60)
        assert g["last"] == 5 and g["min"] == 3 and g["max"] == 7
        h = store.window("src.hist.ttft", 60)
        assert h["count_delta"] == 3
        # two fast observations, one slow: p50 resolves to the fast
        # bucket bound, p95 to the slow one
        assert h["p50"] <= 0.005
        assert h["p95"] >= 0.2
        assert h["bounds"]          # consumers can interpret buckets
        # the windowed good/total helper the SLO layer uses
        good, total = store.count_in_window("src.hist.ttft", 60, 0.005)
        assert (good, total) == (2, 3)

    def test_capacity_bounds_every_ring(self):
        from veles_tpu.serving import ServingMetrics, TimeSeriesStore
        m = ServingMetrics("ts_cap")
        store = TimeSeriesStore(interval_s=0.01, capacity=8)
        store.add_source(m, key="src")
        for _ in range(40):
            m.record_enqueue()
            store.sample_once()
        assert store.samples == 40
        w = store.window("src.counter.requests", 1e9)
        assert w["points"] == 8          # ring, not unbounded history

    def test_snapshot_strict_json_with_shared_sampled_at(self):
        """/timeseries.json shape: strict JSON (no NaN), the shared
        monotonic sampled_at stamp, per-kind windowed stats plus raw
        points inside the window — and the /metrics.json snapshot
        carries the SAME clock's stamp (the ISSUE 14 small fix), so
        rate math across two scrapes is arithmetic."""
        from veles_tpu.serving import ServingMetrics, TimeSeriesStore
        from veles_tpu.serving.metrics import monotonic_offset
        m = ServingMetrics("ts_snap")
        store = TimeSeriesStore(interval_s=0.05, capacity=16)
        store.add_source(m, key="src")
        for _ in range(3):
            m.record_enqueue()
            m.record_response(0.01)
            m.record_decode_step(float("nan"))   # hostile input
            store.sample_once()
        snap = store.snapshot(window_s=60)
        text = json.dumps(snap, allow_nan=False)   # raises on NaN
        snap2 = json.loads(text)
        assert snap2["samples"] == 3
        assert 0 < snap2["sampled_at"] <= monotonic_offset()
        ctr = snap2["series"]["src.counter.requests"]
        assert ctr["kind"] == "counter" and ctr["last"] == 3
        assert len(ctr["series"]) == 3           # raw ring points
        msnap = m.snapshot()
        assert 0 < msnap["sampled_at"] <= monotonic_offset()
        before = m.snapshot()["sampled_at"]
        time.sleep(0.01)
        assert m.snapshot()["sampled_at"] > before

    def test_concurrent_writers_sampler_and_reads(self):
        """The ISSUE 14 concurrency contract: writer threads hammer
        the metrics, the sampler thread ticks, and concurrent
        window()/snapshot() reads never see a torn window — no
        exceptions, counter 'last' monotone across reads, deltas and
        rates never negative, snapshots strict-JSON throughout."""
        from veles_tpu.serving import ServingMetrics, TimeSeriesStore
        m = ServingMetrics("ts_conc")
        store = TimeSeriesStore(interval_s=0.005, capacity=256)
        store.add_source(m, key="src")
        errors = []
        stop = threading.Event()

        def hammer():
            try:
                i = 0
                while not stop.is_set():
                    m.record_enqueue()
                    m.record_response(0.001 * (i % 5 + 1))
                    m.record_ttft(0.002)
                    m.inc("tokens_out", 3)
                    m.set_gauge("queue_depth", i % 11)
                    i += 1
            except Exception as e:   # noqa: BLE001 — the assertion
                errors.append(e)

        writers = [threading.Thread(target=hammer) for _ in range(3)]
        for t in writers:
            t.start()
        store.start()
        try:
            last_seen = -1
            deadline = time.monotonic() + 0.8
            while time.monotonic() < deadline:
                w = store.window("src.counter.requests", 60)
                if w is not None:
                    assert w["delta"] >= 0
                    assert w["rate_per_s"] >= 0
                    assert w["last"] >= last_seen
                    last_seen = w["last"]
                h = store.window("src.hist.ttft", 60)
                if h is not None:
                    assert h["count_delta"] >= 0
                snap = store.snapshot(window_s=5)
                json.dumps(snap, allow_nan=False)
        finally:
            stop.set()
            for t in writers:
                t.join(timeout=10)
            store.stop()
        assert not errors, errors
        assert store.samples > 10
        # the final ring state agrees with the final counter value
        final = store.window("src.counter.requests", 1e9)
        assert final["last"] <= m.snapshot()["requests"]


class TestRuntimeGauges:
    def test_engine_runtime_probe(self):
        """The ISSUE 14 runtime gauges on a live engine: the jit
        program-cache size as a compile_programs gauge (the invariant
        the jit-guard tests pin, live) with a monotone compiles_total
        counter, process RSS, tokens/s and live MFU from the FLOPs
        model, all written into the engine's own metrics row."""
        from veles_tpu.serving import LMEngine, ServingMetrics
        from veles_tpu.serving.timeseries import (
            engine_flops_per_token, engine_program_cache_size,
            runtime_probe)
        params = _tiny_params()
        engine = LMEngine(params, n_heads=2, max_len=48, slots=2,
                          name="rp_t",
                          metrics=ServingMetrics("rp_t")).start()
        try:
            probe = runtime_probe(engine)
            probe()                      # before any traffic
            snap0 = engine.metrics.snapshot()
            assert snap0["gauges"]["process_rss_bytes"] > 0
            engine.generate(numpy.asarray([[1, 2, 3]] * 2), 6)
            probe()
            time.sleep(0.02)
            probe()
            snap = engine.metrics.snapshot()
            g = snap["gauges"]
            # traffic compiled programs: the gauge reads the live jit
            # caches and the counter accumulated the observed growth
            assert g["compile_programs"] > 0
            assert g["compile_programs"] \
                == engine_program_cache_size(engine)
            assert snap["counters"]["compiles_total"] \
                == g["compile_programs"]
            assert "tokens_per_s" in g
            assert "mfu_live" in g and g["mfu_live"] >= 0
            assert engine_flops_per_token(engine) > 0
        finally:
            engine.stop()

    def test_megastep_waste_gauge(self):
        """The fused-decode early-exit tail as a live gauge: the probe
        derives megastep_waste_frac from the counter deltas between
        its ticks."""
        from veles_tpu.serving import ServingMetrics
        from veles_tpu.serving.timeseries import runtime_probe

        class _Eng:        # metrics-only stand-in; no device needed
            params = None
            n_heads = 2
            max_len = 32
            _mesh = None
            _device = None
            metrics = ServingMetrics("ms_t")

        eng = _Eng()
        probe = runtime_probe(eng, flops_per_token=None)
        probe()
        eng.metrics.record_megastep(k=8, lanes=2, tokens=12,
                                    wasted_iterations=4)
        probe()
        frac = eng.metrics.snapshot()["gauges"]["megastep_waste_frac"]
        assert frac == pytest.approx(4 / 16)


class TestPeaksTable:
    """ONE peaks table, keyed by device kind; a TPU that is not in it
    is an error, never a default (bench.py reads the same table)."""

    @pytest.mark.parametrize("kind,peak", [
        ("TPU v5 lite", 197e12), ("TPU v5e", 197e12), ("TPU v4", 275e12)])
    def test_known_kinds(self, kind, peak):
        from veles_tpu.serving.timeseries import tpu_peak_flops
        assert tpu_peak_flops(kind)[0] == peak

    def test_unknown_tpu_kind_raises(self, monkeypatch):
        import jax
        from veles_tpu.ops import pallas_kernels
        from veles_tpu.serving import timeseries as ts

        class _Device:
            platform, device_kind = "tpu", "TPU v99"
        with pytest.raises(ValueError, match="TPU v99"):
            ts.tpu_peak_flops("TPU v99")
        monkeypatch.delenv("VELES_PEAK_FLOPS", raising=False)
        monkeypatch.setattr(pallas_kernels, "on_tpu", lambda: True)
        monkeypatch.setattr(jax, "devices", lambda *a: [_Device()])
        with pytest.raises(ValueError, match="TPU_PEAK_FLOPS"):
            ts.peak_flops_estimate()
        monkeypatch.setenv("VELES_PEAK_FLOPS", "1e12")
        assert ts.peak_flops_estimate() == (1e12, "env:VELES_PEAK_FLOPS")


class TestSLOMonitor:
    @staticmethod
    def _store(metrics, key="src"):
        from veles_tpu.serving import TimeSeriesStore
        store = TimeSeriesStore(interval_s=0.05, capacity=256)
        store.add_source(metrics, key=key)
        return store

    def test_objective_validation(self):
        from veles_tpu.serving import Objective
        with pytest.raises(ValueError, match="kind"):
            Objective("x", "throughput", 0.9)
        with pytest.raises(ValueError, match="target"):
            Objective("x", "availability", 1.0)
        with pytest.raises(ValueError, match="threshold_s"):
            Objective("x", "latency", 0.9, series="ttft")
        with pytest.raises(ValueError, match="series"):
            Objective("x", "latency", 0.9, series="nope",
                      threshold_s=0.1)

    def test_state_machine_transitions(self):
        """ok → warn → page → ok, driven deterministically by
        synthetic counters and synchronous sample_once(): warn at a
        short-window burn >= 1, page only when EVERY window burns >=
        page_burn, recovery when the short window's burn drops."""
        from veles_tpu.serving import (Objective, ServingMetrics,
                                       SLOMonitor)
        m = ServingMetrics("slo_sm")
        store = self._store(m)
        mon = SLOMonitor(
            store, [Objective("avail", "availability", 0.9)],
            windows_s=(0.4, 300.0), min_events=1)
        store.add_listener(mon.sample_once)
        store.sample_once()                  # baseline
        for _ in range(100):
            m.record_response(0.001)
        store.sample_once()
        assert mon.state("src", "avail") == 0          # OK
        for _ in range(15):                  # ratio 15/115 -> burn 1.3
            m.record_error()
        store.sample_once()
        assert mon.state("src", "avail") == 1          # WARN
        for _ in range(85):                  # ratio 0.5 -> burn 5.0
            m.record_error()
        store.sample_once()
        assert mon.state("src", "avail") == 2          # PAGE
        assert mon.metrics.counter("slo_pages_total") == 1
        # recovery: let the short window age out the bad deltas, then
        # feed clean traffic
        time.sleep(0.5)
        for _ in range(50):
            m.record_response(0.001)
        store.sample_once()
        for _ in range(50):
            m.record_response(0.001)
        store.sample_once()
        assert mon.state("src", "avail") == 0          # recovered
        assert mon.metrics.counter("slo_recoveries_total") == 1
        snap = mon.snapshot()
        json.dumps(snap, allow_nan=False)
        assert snap["pages_total"] == 1

    def test_latency_objective_bucket_resolution(self):
        from veles_tpu.serving import (Objective, ServingMetrics,
                                       SLOMonitor)
        m = ServingMetrics("slo_lat")
        store = self._store(m)
        mon = SLOMonitor(
            store,
            [Objective("ttft", "latency", 0.9, series="ttft",
                       threshold_s=0.05)],
            windows_s=(60.0, 300.0), min_events=1, page_burn=2.0)
        store.sample_once()
        for _ in range(20):
            m.record_ttft(0.004)             # good
        store.sample_once()
        mon.sample_once()
        assert mon.state("src", "ttft") == 0
        for _ in range(20):
            m.record_ttft(0.4)               # bad: ratio 0.5, burn 5
        store.sample_once()
        mon.sample_once()
        assert mon.state("src", "ttft") == 2

    def test_min_events_holds_state(self):
        """One failed request on an idle fleet is not a page."""
        from veles_tpu.serving import (Objective, ServingMetrics,
                                       SLOMonitor)
        m = ServingMetrics("slo_idle")
        store = self._store(m)
        mon = SLOMonitor(
            store, [Objective("avail", "availability", 0.999)],
            windows_s=(60.0, 300.0), min_events=5)
        store.sample_once()
        m.record_error()                     # ratio 1.0 but 1 event
        store.sample_once()
        rows = mon.sample_once()
        assert mon.state("src", "avail") == 0
        assert rows[0]["held"] is True       # gate, not a verdict

    def test_latency_threshold_between_bounds_rounds_down(self):
        """The conservative cut (review-hardened): a threshold
        BETWEEN bucket bounds rounds DOWN, so traffic violating the
        threshold but under the next bound up still burns — bucket
        resolution can over-alert, never hide a violation."""
        from veles_tpu.serving import (Objective, ServingMetrics,
                                       SLOMonitor)
        m = ServingMetrics("slo_cut")
        store = self._store(m)
        # threshold 0.3 sits between the 0.25 and 0.5 bounds
        mon = SLOMonitor(
            store,
            [Objective("ttft", "latency", 0.9, series="ttft",
                       threshold_s=0.3)],
            windows_s=(60.0, 300.0), min_events=1)
        store.sample_once()
        for _ in range(20):
            m.record_ttft(0.45)          # violates 0.3, under 0.5
        store.sample_once()
        mon.sample_once()
        assert mon.state("src", "ttft") == 2       # PAGE, not OK
        good, total = store.count_in_window("src.hist.ttft", 60, 0.3)
        assert (good, total) == (0, 20)

    def test_held_page_never_refeeds_checker(self):
        """Review-hardened: a PAGE carried by the min_events gate
        (a quarantined replica serves no traffic, so its window never
        refills) must not keep signaling the checker — otherwise a
        recovered replica is re-quarantined forever on one stale
        burst."""
        from veles_tpu.serving import (Objective, ServingMetrics,
                                       SLOMonitor, TimeSeriesStore)

        class StubChecker:
            def __init__(self):
                self.pages, self.oks = [], []

            def note_slo_page(self, i, reason=""):
                self.pages.append(i)

            def note_slo_ok(self, i):
                self.oks.append(i)

        m0 = ServingMetrics("slo_held0")
        m1 = ServingMetrics("slo_held1")
        store = TimeSeriesStore(interval_s=0.02, capacity=64)
        store.add_source(m0, key="r0")
        store.add_source(m1, key="r1")
        checker = StubChecker()
        mon = SLOMonitor(
            store, [Objective("avail", "availability", 0.9)],
            windows_s=(0.4, 300.0), min_events=5, checker=checker,
            source_replicas={"r0": 0, "r1": 1})
        store.add_listener(mon.sample_once)
        store.sample_once()
        for _ in range(20):                  # fresh burn on r0 only
            m0.record_error()
            m1.record_response(0.001)
        store.sample_once()
        assert mon.state("r0", "avail") == 2
        assert checker.pages == [0]
        # traffic stops; the short window drains below min_events —
        # the held PAGE must signal nothing (neither page nor ok)
        time.sleep(0.5)
        pages_before = list(checker.pages)
        store.sample_once()
        store.sample_once()
        rows = {(r["source"], r["objective"]): r
                for r in mon.sample_once()}
        assert rows[("r0", "avail")]["state"] == 2
        assert rows[("r0", "avail")]["held"] is True
        assert checker.pages == pages_before

    def test_from_spec_file_and_shed_objective(self, tmp_path):
        from veles_tpu.serving import ServingMetrics, SLOMonitor
        spec = {"windows_s": [0.5, 120], "warn_burn": 1.0,
                "page_burn": 3.0, "min_events": 2,
                "objectives": [
                    {"name": "shed", "kind": "shed_rate",
                     "target": 0.9}]}
        path = tmp_path / "slo.json"
        path.write_text(json.dumps(spec))
        m = ServingMetrics("slo_file")
        store = self._store(m)
        mon = SLOMonitor.from_spec(str(path), store)
        assert mon.windows_s == (0.5, 120.0)
        assert mon.page_burn == 3.0
        store.sample_once()
        for _ in range(10):
            m.record_response(0.001)
        for _ in range(10):                  # 10 shed / 20 -> burn 5
            m.record_shed()
        store.sample_once()
        mon.sample_once()
        assert mon.state("src", "shed") == 2
        assert SLOMonitor.from_spec(None, store) is None
        with pytest.raises(ValueError, match="objectives"):
            SLOMonitor.from_spec({"nope": 1}, store)

    def test_page_feeds_health_checker_not_fleet_wide(self):
        """The router hook: a paging REPLICA source counts as health
        failures toward quarantine; a fleet-wide burn (every source
        paging) is never fed — and a solo engine is never quarantined
        by its own burn."""
        from veles_tpu.serving import (HealthChecker, LMEngine,
                                       Objective, Router,
                                       ServingMetrics, SLOMonitor,
                                       TimeSeriesStore)
        params = _tiny_params()
        replicas = [LMEngine(params, n_heads=2, max_len=48, slots=1,
                             name="slo_hc%d" % i,
                             metrics=ServingMetrics(
                                 "slo_hc",
                                 labels={"replica": str(i)}))
                    for i in range(2)]
        router = Router(replicas).start()
        checker = HealthChecker(router, fail_threshold=2,
                                cooldown_s=600.0)
        try:
            store = TimeSeriesStore(interval_s=0.05, capacity=64)
            keys = []
            for i, e in enumerate(replicas):
                store.add_source(e.metrics, key="r%d" % i)
                keys.append("r%d" % i)
            mon = SLOMonitor(
                store, [Objective("avail", "availability", 0.9)],
                windows_s=(60.0, 300.0), min_events=1,
                checker=checker,
                source_replicas={k: i for i, k in enumerate(keys)})
            store.add_listener(mon.sample_once)
            store.sample_once()
            # fleet-wide burn: BOTH replicas error — no quarantine
            for e in replicas:
                for _ in range(10):
                    e.metrics.record_error()
            store.sample_once()
            assert mon.state("r0", "avail") == 2
            assert mon.state("r1", "avail") == 2
            assert router._live == [True, True]
            # replica-scoped burn: only r0 keeps erroring while r1
            # recovers; two paging scans quarantine r0
            time.sleep(0.05)
            for _ in range(200):
                replicas[1].metrics.record_response(0.001)
            for _ in range(20):
                replicas[0].metrics.record_error()
            store.sample_once()
            assert mon.state("r1", "avail") in (0, 1)
            store.sample_once()
            assert router._live[0] is False
            assert checker.states()[0] == checker.OPEN
            assert router._live[1] is True
        finally:
            checker.stop()
            router.stop()

    def test_page_streak_survives_successful_probes(self):
        """A slow-but-RESPONSIVE replica keeps answering the health
        checker's synthetic probes; those successes reset the probe
        fail count but must NOT clear the SLO page streak — and
        note_slo_ok (the burn actually stopping) must."""
        from veles_tpu.serving import (HealthChecker, LMEngine,
                                       Router, ServingMetrics)
        params = _tiny_params()
        replicas = [LMEngine(params, n_heads=2, max_len=48, slots=1,
                             name="slo_pr%d" % i,
                             metrics=ServingMetrics("slo_pr%d" % i))
                    for i in range(2)]
        router = Router(replicas).start()
        checker = HealthChecker(router, fail_threshold=2,
                                cooldown_s=600.0)
        try:
            checker.warm_probes()
            checker.note_slo_page(0, reason="burning")
            # a full probe scan succeeds in between (the production
            # cadence): the page streak must survive it
            checker.step()
            assert checker.states()[0] == checker.HEALTHY
            checker.note_slo_page(0, reason="still burning")
            assert checker.states()[0] == checker.OPEN
            assert router._live[0] is False
            # ...and a cleared burn resets the streak: one page, then
            # ok, then one page again never sums to a quarantine
            checker.note_slo_page(1, reason="blip")
            checker.note_slo_ok(1)
            checker.note_slo_page(1, reason="later blip")
            assert checker.states()[1] == checker.HEALTHY
            # an OPERATOR drain is not the checker's to manage: page
            # signals against replica 1 after a manual unregister are
            # ignored (same fixture — replica 0 is already quarantined
            # by the checker above, which is the other no-op branch)
            router.unregister(1, reason="operator")
            checker.note_slo_page(1, reason="test")
            assert checker.states()[1] == checker.HEALTHY
            checker.note_slo_page(0, reason="already open")  # no-op
            assert checker.states()[0] == checker.OPEN
            with pytest.raises(ValueError):
                checker.note_slo_page(7)
        finally:
            checker.stop()
            router.stop()


class TestTelemetryEndpoints:
    def _serve(self):
        """A tiny server with every ISSUE 14 surface armed: metrics,
        a sampled store, an SLO monitor, and a tracer — no engine
        needed (the endpoints read components)."""
        from veles_tpu.restful_api import RESTfulAPI
        from veles_tpu.serving import (Objective, ServingMetrics,
                                       SLOMonitor, SpanTracer,
                                       TimeSeriesStore)
        m = ServingMetrics("ep_t")
        store = TimeSeriesStore(interval_s=0.05, capacity=32)
        store.add_source(m, key="ep")
        mon = SLOMonitor(
            store, [Objective("avail", "availability", 0.99)],
            windows_s=(60.0, 300.0), min_events=1)
        tracer = SpanTracer(mode="all", last=8)
        ctx = tracer.start_request(name="seed")
        tracer.add(ctx, "decode.step", "decode", 0.0, 0.002,
                   attrs={"bucket": 2, "backend": "xla"})
        tracer.finish_request(ctx)
        for i in range(3):
            m.record_enqueue()
            m.record_response(0.01)
            m.record_ttft(0.01)
            store.sample_once()
        mon.sample_once()
        api = RESTfulAPI(None, handler=lambda p: {"ok": True},
                         metrics=m, tracer=tracer, telemetry=store,
                         slo=mon)
        return api.start(port=0)

    def test_endpoints_strict_json_and_status_panel(self):
        api = self._serve()
        try:
            ts = _get_json(api.port, "/timeseries.json?window=30")
            assert ts["window_s"] == 30.0
            assert ts["samples"] == 3
            assert "ep.counter.responses" in ts["series"]
            assert ts["sampled_at"] > 0
            slo = _get_json(api.port, "/slo.json")
            assert slo["worst_state_name"] == "ok"
            assert slo["objectives"][0]["objective"] == "avail"
            assert slo["sampled_at"] > 0
            ms = _get_json(api.port, "/metrics.json")
            assert ms["sampled_at"] > 0       # the small fix
            with urllib.request.urlopen(
                    "http://127.0.0.1:%d/status" % api.port,
                    timeout=10) as r:
                assert r.headers["Content-Type"].startswith(
                    "text/plain")
                text = r.read().decode()
            assert "veles_tpu serving status" in text
            assert "[slo" in text and "[telemetry" in text
            # schema guard: the live payloads conform to the shapes
            # tools/check_stream_records.py enforces tier-1
            import check_stream_records as csr
            assert csr.check_timeseries_payload(ts) == []
            assert csr.check_slo_payload(slo) == []
        finally:
            api.stop()

    def test_bad_window_param_is_400(self):
        api = self._serve()
        try:
            with pytest.raises(urllib.error.HTTPError) as err:
                _get_json(api.port, "/timeseries.json?window=banana")
            assert err.value.code == 400
            with pytest.raises(urllib.error.HTTPError) as err:
                _get_json(api.port, "/timeseries.json?window=-5")
            assert err.value.code == 400
        finally:
            api.stop()

    def test_endpoints_absent_without_components(self):
        """A server without telemetry/slo keeps 404 semantics for the
        new paths (but /status always answers)."""
        from veles_tpu.restful_api import RESTfulAPI
        api = RESTfulAPI(None, handler=lambda p: {"ok": True})
        api.start(port=0)
        try:
            for path in ("/timeseries.json", "/slo.json"):
                with pytest.raises(urllib.error.HTTPError) as err:
                    _get_json(api.port, path)
                assert err.value.code == 404
            with urllib.request.urlopen(
                    "http://127.0.0.1:%d/status" % api.port,
                    timeout=10) as r:
                assert b"serving status" in r.read()
        finally:
            api.stop()


class TestWebStatusTimeseries:
    def test_dashboard_serves_default_store(self):
        """web_status.py exposes the process's default telemetry
        store at /timeseries.json — dashboard and serving port share
        one set of rings; 404 when none is published."""
        from veles_tpu.serving import ServingMetrics, TimeSeriesStore
        from veles_tpu.serving import timeseries as ts_mod
        from veles_tpu.web_status import WebStatus
        old = ts_mod.get_default()
        status = WebStatus().start(port=0)
        try:
            ts_mod.set_default(None)
            with pytest.raises(urllib.error.HTTPError) as err:
                _get_json(status.port, "/timeseries.json")
            assert err.value.code == 404
            m = ServingMetrics("ws_ts")
            store = TimeSeriesStore(interval_s=0.05, capacity=16)
            store.add_source(m, key="ws")
            m.record_enqueue()
            store.sample_once()
            ts_mod.set_default(store)
            snap = _get_json(status.port, "/timeseries.json")
            assert "ws.counter.requests" in snap["series"]
        finally:
            ts_mod.set_default(old)
            status.stop()


class TestServeLMTelemetry:
    def test_serve_lm_wires_store_slo_and_endpoints(self):
        """End to end through serve_lm(telemetry=, slo=True): the
        store samples the engine on its cadence, the SLO monitor
        rides the tick, every new endpoint answers on the serving
        port, and stop() tears the sampler down before the engine."""
        from veles_tpu import prng
        from veles_tpu.config import root
        from veles_tpu.restful_api import serve_lm
        from veles_tpu.serving import timeseries as ts_mod
        prng.reset()
        prng.seed_all(5)
        root.__dict__.pop("char_lm", None)
        root.char_lm.update({
            "loader": {"minibatch_size": 32, "n_train": 64,
                       "n_valid": 32, "seq_len": 16, "vocab": 16},
            "trainer": {"vocab": 16, "d_model": 32, "n_heads": 2,
                        "n_layers": 1, "max_len": 32,
                        "learning_rate": 3e-3, "n_experts": 0,
                        "pipeline_stages": 0, "remat": False},
            "decision": {"max_epochs": 1, "fail_iterations": 10},
        })
        from veles_tpu.samples import char_lm
        wf = char_lm.train()
        api = serve_lm(wf, port=0, max_new=8, slots=2,
                       telemetry=0.05, slo=True)
        try:
            assert api.telemetry is not None
            assert api.slo is not None
            assert ts_mod.get_default() is api.telemetry
            payload = {"input": [[3, 4, 5]], "n_new": 4}
            req = urllib.request.Request(
                "http://127.0.0.1:%d/predict" % api.port,
                data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as resp:
                out = json.loads(resp.read())
            assert len(out["tokens"][0]) == 7
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline \
                    and api.telemetry.samples < 3:
                time.sleep(0.05)
            assert api.telemetry.samples >= 3
            ts = _get_json(api.port, "/timeseries.json")
            resp_series = [n for n in ts["series"]
                           if n.endswith("counter.responses")]
            assert resp_series
            slo = _get_json(api.port, "/slo.json")
            assert slo["objectives"]       # evaluated on the cadence
            assert slo["worst_state_name"] in ("ok", "warn", "page")
            # the runtime probe ran: compile_programs is live
            ms = _get_json(api.port, "/metrics.json")
            assert ms["gauges"]["compile_programs"] > 0
        finally:
            api.stop()
        assert api.telemetry._thread is None      # sampler stopped


class TestChaosSLOSmoke:
    @pytest.mark.slow
    def test_slo_burn_alert_scenario_smoke(self):
        """The full chaos scenario at smoke size (slow suite — the
        tier-1 representative of the burn→page→quarantine path is
        TestSLOMonitor::test_page_feeds_health_checker_not_fleet_wide,
        and the scenario itself is asserted by every
        tools/chaos_bench.py run; the PR 3/8 watchdog-headroom
        discipline)."""
        from chaos_bench import (build_params, expected_rows,
                                 mixed_length_prompts,
                                 scenario_slo_burn_alert)
        vocab, max_len, n_heads, n_new = 16, 48, 2, 6
        params = build_params(vocab=vocab, d_model=32, n_heads=2,
                              n_layers=2, max_len=max_len, seed=7)
        prompts = mixed_length_prompts(4, vocab, 3,
                                       max_len - n_new - 4, seed=5)
        expect = expected_rows(params, prompts, n_new, n_heads,
                               max_len)
        record = scenario_slo_burn_alert(
            params, n_heads, max_len, prompts, n_new, expect,
            spike_s=0.05)
        assert record["replica0_quarantined"] is True
        assert record["sampling_windows_to_quarantine"] <= 2
        assert record["completed_exactly_once"] == 8
